"""Weights from ``--seed``, made by the benchmark and not by the program.

One generator hands the same numbers to the system under test and to the
plain reference: every leaf is drawn on the device from a key folded from
the seed and the leaf's path, scaled (0.02, and 1 + 0.02 n for a norm's
``scale``), and rounded through bfloat16, so that the bf16 train state, the
float32 served parameters and the float32 reference all hold exactly the
same values.  The paths and shapes are the reference's ``param_shapes``.
"""

from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp


def seed_key(seed: int):
    """A key from any non-negative seed (the driver's pass 2**31)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed % 2**32), seed >> 32)


def leaf(key, path: str, shape: tuple[int, ...]):
    k = jax.random.fold_in(key, zlib.crc32(path.encode()) & 0x7FFFFFFF)
    x = 0.02 * jax.random.normal(k, shape, jnp.float32)
    if path.endswith("scale"):
        x = 1.0 + x
    return x.astype(jnp.bfloat16).astype(jnp.float32)


def flat(key, shapes: dict[str, tuple[int, ...]]) -> dict:
    """Every leaf by path; call it under ``jax.jit`` for one program."""
    return {p: leaf(key, p, s) for p, s in shapes.items()}


def nest(flat_params: dict) -> dict:
    """``{"a/b/c": x}`` to the nested dict a flax module takes."""
    out: dict = {}
    for path, x in flat_params.items():
        node = out
        *parents, last = path.split("/")
        for name in parents:
            node = node.setdefault(name, {})
        node[last] = x
    return out


def unnest(tree: dict, prefix: str = "") -> dict:
    out = {}
    for k, v in tree.items():
        path = f"{prefix}{k}"
        if isinstance(v, dict):
            out.update(unnest(v, path + "/"))
        else:
            out[path] = v
    return out
