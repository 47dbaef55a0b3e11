"""Kind ``serve-long``: what ``lib/serving_large.py`` does, with three of its
pieces exchanged, each forced by a configuration that serves a whole
vocabulary at a long ``max_len`` through ``linear_attention`` layers.

Nothing of ``lib/serving_large.py`` is copied: ``run`` below calls
``serving_large.run`` with three of that module's names bound to the
functions here for the call's length (``build``, ``_drive``, ``_sample``, the
verdict, the record and every printed line are its own).  The next
``benchmark`` PR folds this by giving ``serving_large.run`` the three as
parameters (PERF.md section 7.3(d)).

- **The reference's logits** (``regrets``).  ``serving_large.regrets`` pads
  every checked request to ``max_len`` and takes the reference's logits at
  every position: ``[max_len, vocab]`` float32 is 13.6 GB at 33,792 x
  100,352.  Here a request is padded to a BUCKET of lengths (a ladder of
  2^k and 1.5 x 2^k up to ``max_len``: at most a dozen compiled shapes) and
  the reference is asked for the logits at the served positions only
  (``forward_logits_at(params, keys, tokens, lo, n, prec)``, ``n`` the
  mix's longest output: 411 MB).
- **The decay leaves** (``seeded_weights``).  Every leaf by
  ``weights.leaf`` as before, but for ``A_log`` and ``dt_bias`` of the
  linear layers, which ``lib/weights_gdn.py`` draws from the same key by
  the family's initialisation (its text says why: under 0.02 n the state
  forgets in a few tokens and a broken carry would pass ``correct``).
- **The warm-up** (``warm``).  ONE short and ONE longest prompt in place of
  one a stratum: no prompt length compiles anything any more (PERF.md
  section 7.3(b)), the chunk program is one shape and the decode step
  another, and 32 prompts of this mix are 200k tokens of prefill, some 13 s
  of ``setup_s``.  The two still run every program the window runs (the
  first token's, too) before the compile counter starts.
"""

from __future__ import annotations

import contextlib
import time

import numpy as np

from lib import arrivals, program, serving_large, weights_gdn


def seeded_weights(key, shapes: dict, dtypes: dict) -> dict:
    flat = _theirs["seeded_weights"](key, shapes, dtypes)
    for path, shape in shapes.items():
        special = weights_gdn.decay_leaf(key, path, tuple(shape))
        if special is not None:
            flat[path] = special.astype(dtypes[path])
    return flat


def warm(eng, mix: dict, vocab: int, seed: int) -> int:
    rs = np.random.RandomState((int(seed) + 1) % 2**32)
    lengths = arrivals.distinct_prompt_lengths(mix["lengths"])
    ends = sorted({lengths[0], lengths[-1]})
    for n in ends:
        eng.submit([int(t) for t in rs.randint(1, vocab, size=n)],
                   max_new_tokens=2)
    eng.run()
    eng.finished.clear()
    return len(ends)


def bucket(need: int, max_len: int) -> int:
    """The smallest length of the ladder 64, 96, 128, 192, .. (2^k and 1.5 x
    2^k) that holds ``need`` positions; ``max_len`` tops it."""
    ladder = sorted({int(m * 2**k) for k in range(6, 24) for m in (1, 1.5)})
    return min([n for n in ladder if need <= n < max_len] + [max_len])


def regrets(ctx, ref, params: dict, sample: list,
            precs: tuple[str, ...]) -> dict:
    """For each precision: at every served position, how far the token that
    precision would serve lies below the float32 reference's best logit.
    ``"served"`` stands for the engine's own tokens."""
    import jax.numpy as jnp

    mix = ctx["cell"].mix
    keys = program.model_keys(ctx["cell"].config)
    max_len, n_max = mix["engine"]["max_len"], mix["lengths"]["output"]["hi"]
    out = {p: [] for p in ("served",) + precs}
    t0 = time.perf_counter()
    widths = []
    for r in sample:
        seq = list(r["prompt"]) + list(r["out"])
        lo, n = len(r["prompt"]) - 1, len(r["out"])
        width = bucket(lo + n_max, max_len)
        widths.append(width)
        toks = np.zeros((width,), np.int32)
        toks[:len(seq)] = seq
        served = np.zeros((n_max,), np.int32)
        served[:n] = r["out"]
        l32 = ref.forward_logits_at(params, keys, toks, lo, n_max, "f32")
        best = jnp.max(l32, -1)

        def gap_of(tok):  # fixed shapes on the device, the slice on the host
            g = best - jnp.take_along_axis(l32, tok[:, None], -1)[:, 0]
            return np.asarray(g)[:n]

        out["served"].append(gap_of(jnp.asarray(served)))
        for p in precs:
            low = jnp.argmax(ref.forward_logits_at(
                params, keys, toks, lo, n_max, p), -1)
            out[p].append(gap_of(low.astype(jnp.int32)))
    res = {}
    for p, parts in out.items():
        g = np.concatenate(parts) if parts else np.zeros((0,))
        res[p] = {"max": float(g.max()) if g.size else float("nan"),
                  "mean": float(g.mean()) if g.size else float("nan"),
                  "positions": int(g.size),
                  "share_positive": float((g > 0).mean()) if g.size else 0.0}
    res["seconds"] = time.perf_counter() - t0
    res["padded_to"] = widths
    return res


EXCHANGED = {"seeded_weights": seeded_weights, "_warm": warm,
             "regrets": regrets}
_theirs = {name: getattr(serving_large, name) for name in EXCHANGED}


@contextlib.contextmanager
def exchanged():
    """``serving_large``'s three names bound to the functions above."""
    for name, fn in EXCHANGED.items():
        setattr(serving_large, name, fn)
    try:
        yield
    finally:
        for name, fn in _theirs.items():
            setattr(serving_large, name, fn)


def run(ctx, *, control: bool = False):
    with exchanged():
        return serving_large.run(ctx, control=control)
