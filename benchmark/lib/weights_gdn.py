"""The two leaves of a ``linear_attention`` layer that ``weights.leaf`` must
not draw: the decay's rate ``A_log`` and the step's bias ``dt_bias``.

``weights.leaf`` draws 0.02 n for every leaf.  Under that, ``A = exp(A_log)``
is 1 and ``dt = softplus(dt_bias)`` is 0.69, so every head keeps half its
state a token: the state forgets in a few tokens, and a state that was lost
between two prefill chunks, or carried in the wrong slot, would serve nearly
the same tokens and pass ``correct``.  So these two are drawn by the
family's own initialisation (Mamba2's and Gated DeltaNet's): ``A`` uniform
in (0, 16) and ``dt`` log-uniform in (0.001, 0.1), ``dt_bias`` its inverse
softplus.  Half the heads then keep more than 0.9 of their state a token and
a tenth keep more than 0.99.  From the same key and the leaf's path, like
every other leaf, so program and reference hold the same numbers.
"""

from __future__ import annotations

import math
import zlib

import jax
import jax.numpy as jnp


def decay_leaf(key, path: str, shape: tuple[int, ...]):
    """The leaf at ``path`` if it is one of the two, else None."""
    k = jax.random.fold_in(key, zlib.crc32(path.encode()) & 0x7FFFFFFF)
    if path.endswith("/A_log"):
        return jnp.log(jax.random.uniform(k, shape, jnp.float32, 1e-3, 16.0))
    if path.endswith("/dt_bias"):
        dt = jnp.exp(jax.random.uniform(
            k, shape, jnp.float32, math.log(1e-3), math.log(0.1)))
        return dt + jnp.log(-jnp.expm1(-dt))
    return None
