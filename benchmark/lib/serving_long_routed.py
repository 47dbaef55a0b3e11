"""Kind ``serve-long-routed``: what ``lib/serving_long.py`` does, with one
more of ``lib/serving_large.py``'s pieces exchanged, forced by a
configuration whose router is a softmax over hundreds of outputs.

Nothing is copied: ``run`` below calls ``serving_large.run`` under
``serving_long``'s exchange (the bucketed reference calls, the decay leaves,
the two-prompt warm-up) with ``seeded_weights`` bound once more, for the
call's length:

- **The router's selection bias** (``seeded_weights``).  Every leaf as
  ``serving_long`` draws it, but for the leaves whose path ends in
  ``router/e_bias``, which ``lib/weights_routed.py`` draws ``c n / width``
  from the same key (its text says why: under 0.02 n one fixed dozen of
  outputs takes nearly every token's choices).  ``c`` is the
  configuration's ``router_bias_c``, chosen once (its ``assumed.weights``).
"""

from __future__ import annotations

from lib import serving_large, serving_long, weights_routed


def seeded_weights(c: float):
    """``serving_long.seeded_weights`` with the selection biases drawn by
    ``weights_routed.bias_leaf`` at scale ``c``."""
    def draw(key, shapes: dict, dtypes: dict) -> dict:
        flat = serving_long.seeded_weights(key, shapes, dtypes)
        for path, shape in shapes.items():
            special = weights_routed.bias_leaf(key, path, tuple(shape), c)
            if special is not None:
                flat[path] = special.astype(dtypes[path])
        return flat

    return draw


def run(ctx, *, control: bool = False):
    c = float(ctx["cell"].config["router_bias_c"])
    with serving_long.exchanged():  # restores serving_large's own at exit
        serving_large.seeded_weights = seeded_weights(c)
        return serving_large.run(ctx, control=control)
