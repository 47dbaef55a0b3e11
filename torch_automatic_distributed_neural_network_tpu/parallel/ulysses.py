"""Ulysses-style sequence parallelism (SURVEY.md §2.2 'Ulysses').

DeepSpeed-Ulysses pattern: activations arrive sharded on the *sequence*
dim; two ``all_to_all``s re-shard them on the *head* dim so every device
runs dense attention over the full sequence for its subset of heads, then
the output is scattered back to sequence shards.

Chosen by the planner when head count is divisible by the ``seq`` degree
and the sequence is short enough that full-sequence attention fits —
otherwise ring attention (ring.py) takes over.  Must run inside shard_map
with inputs sharded [B, S/cp, H, D] on ``axis_name``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map
from jax.lax import axis_size

from ..ops.attention import xla_attention


def _a2a(x, axis_name, *, split_dim, concat_dim):
    return jax.lax.all_to_all(
        x, axis_name, split_axis=split_dim, concat_axis=concat_dim, tiled=True
    )


def ulysses_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    axis_name: str = "seq",
) -> jax.Array:
    """All-to-all sequence parallelism.  Local shapes [B, S/cp, H, D]
    in, [B, S/cp, H, D] out; inside, attention runs on [B, S, H/cp, D].

    GQA note: k/v heads must also divide the cp degree; callers with
    fewer kv heads broadcast them first (ops.attention does this).
    """
    cp = axis_size(axis_name)
    hq = q.shape[2]
    if hq % cp:
        raise ValueError(f"Ulysses needs heads ({hq}) divisible by cp ({cp})")
    if k.shape[2] != hq:
        rep = hq // k.shape[2]
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    # seq-sharded -> head-sharded: split heads, gather sequence
    q, k, v = (
        _a2a(t, axis_name, split_dim=2, concat_dim=1) for t in (q, k, v)
    )
    out = xla_attention(q, k, v, causal=causal)
    # head-sharded -> seq-sharded
    return _a2a(out, axis_name, split_dim=1, concat_dim=2)


def ulysses_attention_sharded(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mesh: Mesh,
    *,
    causal: bool = True,
    axis_name: str = "seq",
    batch_spec=P(("data", "fsdp")),
    head_axis: str | None = "tensor",
) -> jax.Array:
    spec = P(batch_spec[0] if len(batch_spec) else None, axis_name,
             head_axis, None)
    fn = shard_map(
        functools.partial(ulysses_attention, causal=causal,
                          axis_name=axis_name),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )
    return fn(q, k, v)
