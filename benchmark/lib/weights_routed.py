"""The one leaf of a softmax-routed expert layer that ``weights.leaf`` must
not draw: the router's selection bias ``e_bias``, which takes part in the
choice of a token's experts and never in their weights.

``weights.leaf`` draws 0.02 n for every leaf.  Added to SIGMOID scores
(0.2-0.8) that is a nudge.  Under a SOFTMAX over hundreds of outputs the
scores are small: at 768 outputs and logits of deviation 1.57 a token's
twelve best are 0.011-0.052 and the twelfth lies 0.0004 over the thirteenth,
so the dozen outputs whose bias is largest (0.04 and more) would take 85% of
EVERY token's choices: one fixed set of experts, and a cell that measures
either no expert or one expert under every token.  So the bias is drawn
``c n / width``, a deviation that follows the scores' own size (their mean
is ``1 / width``): at ``c = 1`` and width 768 9% of a token's twelve choices
differ from the unbiased twelve (my CPU run, PR 39; the configuration's
``assumed.weights`` has the sweep).  From the same key and the leaf's path,
and rounded through bfloat16, like every other leaf, so program and
reference hold the same numbers.
"""

from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp


def bias_leaf(key, path: str, shape: tuple[int, ...], c: float):
    """The leaf at ``path`` if it is a router's selection bias, else None."""
    if not path.endswith("router/e_bias"):
        return None
    k = jax.random.fold_in(key, zlib.crc32(path.encode()) & 0x7FFFFFFF)
    x = (c / shape[-1]) * jax.random.normal(k, shape, jnp.float32)
    return x.astype(jnp.bfloat16).astype(jnp.float32)
