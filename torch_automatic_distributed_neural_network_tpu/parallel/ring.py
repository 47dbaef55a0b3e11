"""Ring attention — context parallelism over the ``seq`` mesh axis
(SURVEY.md §3.4, §2.2 'Ring attention').

Each device holds one sequence block of Q and one of K/V.  K/V blocks
rotate around the ICI ring via ``ppermute`` while every device folds each
visiting block into its local accumulator.  The block-local attention is
the first-party Pallas flash kernel (ops/flash_attention.py) — the two
fast paths compose: the kernel returns ``(o, lse)`` per block and blocks
merge by logsumexp weights, so attention over a sequence of length S
costs O(S/cp) memory per chip and the score matrix is never materialized
in either direction (the kernel's custom VJP handles the block backward).

Causal block dispatch (lax.switch per ring step):

- block from an earlier ring position -> full (unmasked) kernel;
- the device's own block          -> causal kernel (triangular);
- block from a later position     -> skipped entirely (zero weight) —
  no FLOPs spent on fully-masked blocks, unlike a masked einsum.

Scheduling note: the fori_loop body computes on the resident block and
then rotates; whether the ppermute hop actually overlaps the next block's
compute is the compiler's latency-hiding decision, NOT a property this
code enforces, and it is not measured on the current code.

This module is the *explicit-collective* tier: it must be called inside a
``shard_map`` region where q/k/v are sharded along ``axis_name``.  The
model-facing dispatch (ops.attention with impl='ring') applies the
shard_map using the ambient ParallelContext.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map
from jax.lax import axis_size

from ..ops.flash_attention import flash_attention_with_lse

_NEG_BIG = -0.7 * float(jnp.finfo(jnp.float32).max)


def _merge_norm(o, lse, o2, lse2):
    """Merge two *normalized* partial attentions by logsumexp weight.

    o, o2: [B, S, H, D] fp32; lse, lse2: [B, H, S] fp32.
    """
    lse_new = jnp.logaddexp(lse, lse2)
    w = jnp.exp(lse - lse_new).transpose(0, 2, 1)[..., None]
    w2 = jnp.exp(lse2 - lse_new).transpose(0, 2, 1)[..., None]
    return o * w + o2 * w2, lse_new


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    axis_name: str = "seq",
    block_q: int = 1024,
    block_k: int = 1024,
    interpret: bool | None = None,
) -> jax.Array:
    """Block-ring attention; call inside shard_map with q/k/v sharded on
    the sequence dim over ``axis_name``.  Shapes [B, S_local, H|Hkv, D].

    GQA: K/V rotate around the ring at their *small* head count (ICI
    traffic scales with Hkv, not H); the flash kernel broadcasts heads
    per block.
    """
    cp = axis_size(axis_name)
    my = jax.lax.axis_index(axis_name)
    b, sl, hq, dh = q.shape

    flash = functools.partial(
        flash_attention_with_lse,
        block_q=block_q, block_k=block_k, interpret=interpret,
    )

    def full_block(q, kb, vb):
        return flash(q, kb, vb, causal=False)

    def diag_block(q, kb, vb):
        return flash(q, kb, vb, causal=True)

    def skip_block(q, kb, vb):
        return (
            jnp.zeros((b, sl, hq, dh), q.dtype),
            jnp.full((b, hq, sl), _NEG_BIG, jnp.float32),
        )

    def body(step, carry):
        o, lse, kb, vb = carry
        # block kb originated on device (my - step) % cp
        origin = (my - step) % cp
        if causal:
            # earlier block -> full; own block -> triangular; later ->
            # skip (whole-block causal skipping across the ring)
            case = jnp.where(origin == my, 0, jnp.where(origin < my, 1, 2))
            o2, lse2 = jax.lax.switch(
                case, (diag_block, full_block, skip_block), q, kb, vb
            )
        else:
            o2, lse2 = full_block(q, kb, vb)
        o, lse = _merge_norm(o, lse, o2.astype(jnp.float32), lse2)
        # rotate kv to the next device (uniform across the ring every step;
        # the final hop restores the original placement)
        kb, vb = _rotate((kb, vb), axis_name)
        return o, lse, kb, vb

    o0 = jnp.zeros((b, sl, hq, dh), jnp.float32)
    lse0 = jnp.full((b, hq, sl), _NEG_BIG, jnp.float32)
    o, _, _, _ = jax.lax.fori_loop(0, cp, body, (o0, lse0, k, v))
    return o.astype(q.dtype)


def _rotate(kv, axis_name):
    n = axis_size(axis_name)
    perm = [(i, (i + 1) % n) for i in range(n)]
    return jax.tree.map(lambda x: jax.lax.ppermute(x, axis_name, perm), kv)


def ring_attention_sharded(
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
    """Apply ring attention to *unsharded-view* arrays under ``mesh`` by
    wrapping it in shard_map (the model-facing adapter)."""
    spec = P(batch_spec[0] if len(batch_spec) else None, axis_name,
             head_axis, None)

    fn = shard_map(
        functools.partial(ring_attention, causal=causal, axis_name=axis_name),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )
    return fn(q, k, v)
