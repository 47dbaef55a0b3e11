"""Attention kernels with a single dispatch surface.

Implementations:

- ``xla``   — plain jnp einsum attention; XLA fuses it well for moderate
              sequence lengths and it runs everywhere (CPU sim included).
- ``chunked`` — query-block scan over the same einsum math with fp32
              online numerics and per-block rematerialization: peak
              score memory O(block_q * S) instead of O(S^2), pure XLA,
              runs everywhere and takes explicit masks.  The auto path
              uses it for long sequences whenever the Pallas kernel
              can't run (non-TPU backends, explicit masks) — it is what
              keeps long-seq memfit numbers honest off-TPU.
- ``flash`` — Pallas block-streaming attention (ops/flash_attention.py),
              O(seq) memory, MXU-tiled; TPU only.
- ``ring``  — context-parallel ring attention (parallel/ring.py): KV blocks
              rotate around the ``seq`` mesh axis via ppermute with
              online-softmax accumulation (SURVEY.md §3.4).

Models call :func:`attention` and the parallel plan decides the impl; the
CPU-sim tests exercise every impl against the ``xla`` oracle.

Shapes follow the TPU-friendly convention [batch, seq, heads, head_dim]
(BSHD) — keeps the trailing two dims MXU-tileable after the head fold.
"""

from __future__ import annotations

import functools
from typing import Literal

import jax
import jax.numpy as jnp
import numpy as np

Impl = Literal["xla", "chunked", "flash", "ring", "auto"]

# auto-dispatch floor for the chunked path off-TPU: below this the full
# S^2 score tensor is small enough that the plain einsum fuses better
CHUNKED_MIN_SEQ = 1024


def diff_heads(n_heads: int, kv_heads: int) -> tuple[np.ndarray, np.ndarray]:
    """Differential attention's wiring: ``(key_of [H], values_of [H, 2])``.
    Query heads ``2j, 2j + 1`` are pair ``j``; KV heads ``2g, 2g + 1`` are
    group ``g``, which serves the ``H / kvH`` pairs ``j // (H / kvH) == g``.
    Query head ``2j + i`` scores key head ``2g + i`` and reads BOTH value
    heads of the group, side by side (values twice as wide as keys)."""
    h = np.arange(n_heads)
    g = h // (2 * (n_heads // kv_heads))
    return 2 * g + h % 2, np.stack([2 * g, 2 * g + 1], axis=-1)


def diff_plain_heads(q, k, v):
    """Differential attention's first half as 2 H PLAIN heads of hd, for an
    entry that takes keys and values of one width: ``q`` [B, S, H, hd],
    ``k``, ``v`` [B, T, kvH, hd] -> every query head twice, against its key
    head, once with each of its pair's two value heads.  What attention
    returns for them, [B, S, 2 H, hd], reshaped to [B, S, H, 2 hd] is a
    head's softmax over its values of 2 hd."""
    key_of, values_of = diff_heads(q.shape[2], k.shape[2])
    return (jnp.repeat(q, 2, axis=2), k[:, :, np.repeat(key_of, 2)],
            v[:, :, values_of.reshape(-1)])


def _check_window(window, causal):
    """Shared by every attention entry point: a window only makes sense
    as a causal band, and window < 1 would mask EVERY key — with the
    finite mask bias that yields a UNIFORM softmax over all positions
    (an acausality leak), not an error, so reject it up front."""
    if window is None:
        return
    if not causal:
        raise ValueError("window= requires causal=True (the sliding "
                         "window is a causal band)")
    if window < 1:
        raise ValueError(f"window must be >= 1, got {window}")


def _mask_bias(scores_dtype, mask):
    big_neg = jnp.finfo(scores_dtype).min * 0.5
    return jnp.where(mask, 0.0, big_neg).astype(scores_dtype)


def xla_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = False,
    window: int | None = None,
    mask: jax.Array | None = None,
    softmax_dtype=jnp.float32,
) -> jax.Array:
    """Reference einsum attention.  q,k,v: [B, S, H, D] (k,v may have fewer
    heads for GQA — broadcast over query groups)."""
    _check_window(window, causal)
    b, sq, hq, d = q.shape
    _, sk, hk, _ = k.shape
    if hk != hq:
        assert hq % hk == 0, (hq, hk)
        k = jnp.repeat(k, hq // hk, axis=2)
        v = jnp.repeat(v, hq // hk, axis=2)
    scale = 1.0 / np.sqrt(d)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(softmax_dtype) * scale
    if causal:
        causal_mask = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
        if window is not None:
            # sliding band: q attends keys in (q - window, q]
            causal_mask &= jnp.triu(
                jnp.ones((sq, sk), bool), k=sk - sq - window + 1)
        scores = scores + _mask_bias(scores.dtype, causal_mask[None, None])
    if mask is not None:
        # mask: [B, 1|H, Q|1, K] boolean, True = attend
        scores = scores + _mask_bias(scores.dtype, mask)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs.astype(v.dtype), v)


def chunked_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = False,
    window: int | None = None,
    mask: jax.Array | None = None,
    block_q: int = 256,
    softmax_dtype=jnp.float32,
) -> jax.Array:
    """Memory-efficient einsum attention: lax.scan over query blocks.

    Numerically identical to :func:`xla_attention` (same fp32 softmax,
    same GQA broadcast, same mask conventions) but the [B,H,S,S] score
    tensor never materializes — each scan step holds [B,H,block_q,S],
    and ``jax.checkpoint`` on the block recomputes scores in the
    backward instead of stashing them per block.  This is the flash
    algorithm's memory shape in pure XLA, so it runs on any backend and
    supports explicit masks (which the Pallas kernel does not).
    """
    _check_window(window, causal)
    b, sq, hq, d = q.shape
    _, sk, hk, _ = k.shape
    if hk != hq:
        assert hq % hk == 0, (hq, hk)
        k = jnp.repeat(k, hq // hk, axis=2)
        v = jnp.repeat(v, hq // hk, axis=2)
    block_q = min(block_q, sq)
    n_blocks = -(-sq // block_q)
    pad = n_blocks * block_q - sq
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
        if mask is not None and mask.shape[2] > 1:
            # keep mask rows aligned with padded q rows (a fully-False
            # row yields a uniform softmax via the finite mask bias; the
            # row's output is sliced off below)
            mask = jnp.pad(mask, ((0, 0), (0, 0), (0, pad), (0, 0)))
    q_blocks = q.reshape(b, n_blocks, block_q, hq, d).swapaxes(0, 1)
    scale = 1.0 / np.sqrt(d)
    k_pos = jnp.arange(sk)

    @jax.checkpoint
    def block(q_i, start):
        scores = jnp.einsum("bqhd,bkhd->bhqk", q_i, k).astype(
            softmax_dtype) * scale
        if causal:
            # global q position p attends key positions <= p + (sk - sq)
            q_pos = start + jnp.arange(block_q)
            allow = k_pos[None, :] <= q_pos[:, None] + (sk - sq)
            if window is not None:
                allow &= (k_pos[None, :]
                          > q_pos[:, None] + (sk - sq) - window)
            scores = scores + _mask_bias(scores.dtype, allow[None, None])
        if mask is not None:
            m = mask
            if m.shape[2] > 1:  # [B, 1|H, Q, K]: slice this block's rows
                m = jax.lax.dynamic_slice_in_dim(m, start, block_q, axis=2)
            scores = scores + _mask_bias(scores.dtype, m)
        probs = jax.nn.softmax(scores, axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", probs.astype(v.dtype), v)

    def body(_, inp):
        q_i, start = inp
        return None, block(q_i, start)

    _, out = jax.lax.scan(
        body, None, (q_blocks, jnp.arange(n_blocks) * block_q))
    out = out.swapaxes(0, 1).reshape(b, n_blocks * block_q, hq, d)
    return out[:, :sq]


def _flash_ok(q: jax.Array, k: jax.Array, mask) -> bool:
    """Auto-dispatch gate for the Pallas flash kernel: TPU backend, no
    explicit mask, a sequence long enough that block streaming wins.
    The flash-vs-einsum speedup is not measured on the current code;
    512 is a conservative floor set by the kernel's block size, not the
    perf crossover."""
    if mask is not None:
        return False
    if q.shape[1] < 512 or q.shape[1] != k.shape[1]:
        return False
    return jax.default_backend() == "tpu"


def attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = False,
    window: int | None = None,
    mask: jax.Array | None = None,
    impl: Impl = "auto",
) -> jax.Array:
    """Dispatching attention entry point used by all models.

    With impl='auto': if the ambient ParallelContext has a nontrivial
    ``seq`` axis, context parallelism kicks in — Ulysses when the local
    head count divides the cp degree (cheapest: two all_to_alls), ring
    attention otherwise (SURVEY.md §5 long-context tiers).  Without a
    context (or cp=1): plain XLA attention.

    ``window`` (requires ``causal=True``) is Mistral-style sliding-window
    attention, supported natively by the xla/chunked/flash paths (the
    flash kernel skips out-of-band blocks at the grid level).
    """
    from ..parallel import context as pctx

    _check_window(window, causal)

    ctx = pctx.current()
    cp = ctx.seq_degree if ctx is not None else 1

    if impl == "auto" and ctx is not None and ctx.attn_impl:
        impl = ctx.attn_impl
    if impl == "auto":
        if cp > 1:
            if ctx.seq_impl in ("ring", "ulysses"):
                impl = ctx.seq_impl  # user override via AutoDistribute
            else:
                tp = ctx.degrees.get(ctx.head_axis, 1)
                local_heads = q.shape[2] // max(tp, 1)
                seq = q.shape[1]
                if local_heads % cp == 0 and seq <= 8192:
                    impl = "ulysses"
                else:
                    impl = "ring"
        elif _flash_ok(q, k, mask):
            impl = "flash"
        elif q.shape[1] >= CHUNKED_MIN_SEQ and q.shape[1] == k.shape[1]:
            # long sequence but the Pallas kernel can't run (non-TPU
            # backend or explicit mask): O(block*S) memory via the
            # query-block scan instead of the S^2 einsum
            impl = "chunked"
        else:
            impl = "xla"

    if impl == "xla":
        return xla_attention(q, k, v, causal=causal, window=window,
                             mask=mask)
    if impl == "chunked":
        return chunked_attention(q, k, v, causal=causal, window=window,
                                 mask=mask)
    if impl == "flash":
        from .flash_attention import flash_attention

        if mask is not None:
            raise NotImplementedError(
                "flash attention does not take explicit masks (causal only)"
            )
        if ctx is not None and cp > 1:
            raise NotImplementedError(
                "flash attention cannot span a sharded sequence axis — "
                "use impl='ring' or 'ulysses' (or 'auto') under context "
                "parallelism"
            )
        if ctx is not None and (ctx.present_batch_axes
                                or ctx.degrees.get(ctx.head_axis, 1) > 1):
            # Inside a GSPMD-jitted step on a nontrivial mesh the Mosaic
            # custom call is not partitionable — run it under shard_map
            # over the batch (and head, under TP) axes, which is exact:
            # attention is independent per batch element and per head.
            from jax import shard_map
            from jax.sharding import PartitionSpec as P

            tp = ctx.degrees.get(ctx.head_axis, 1)
            head_axis = ctx.head_axis if tp > 1 else None
            if tp > 1 and q.shape[2] % tp:
                # head count indivisible by the tensor degree — the
                # einsum path under GSPMD is the safe fallback
                return xla_attention(q, k, v, causal=causal, window=window)
            if k.shape[2] != q.shape[2]:
                # GQA: broadcast K/V heads first so all three operands
                # shard evenly on the head axis (n_kv_heads may not
                # divide the tensor degree)
                rep = q.shape[2] // k.shape[2]
                k = jnp.repeat(k, rep, axis=2)
                v = jnp.repeat(v, rep, axis=2)
            spec = P(ctx.batch_spec_entry(), None, head_axis, None)
            fn = shard_map(
                functools.partial(flash_attention, causal=causal,
                                  window=window),
                mesh=ctx.mesh,
                in_specs=(spec, spec, spec),
                out_specs=spec,
                check_vma=False,
            )
            return fn(q, k, v)
        return flash_attention(q, k, v, causal=causal, window=window)
    if impl in ("ring", "ulysses"):
        if ctx is None or cp <= 1:
            # degenerate: no seq axis -> plain attention is identical,
            # and the xla path handles window/mask natively — so a
            # single-chip run of a windowed model must not hit the
            # cp-only NotImplementedErrors below
            return xla_attention(q, k, v, causal=causal, window=window,
                                 mask=mask)
        if mask is not None:
            raise NotImplementedError(
                f"{impl} attention does not take explicit masks (causal only)"
            )
        if window is not None:
            raise NotImplementedError(
                "sliding-window attention is not yet supported under "
                "context parallelism (ring/ulysses) — train windowed "
                "models with dp/fsdp/tp, or drop seq_parallel"
            )
        head_axis = (
            ctx.head_axis if ctx.degrees.get(ctx.head_axis, 1) > 1 else None
        )
        from jax.sharding import PartitionSpec as P

        batch_spec = P(ctx.batch_spec_entry())
        if impl == "ring":
            from ..parallel.ring import ring_attention_sharded

            return ring_attention_sharded(
                q, k, v, ctx.mesh, causal=causal, axis_name=ctx.seq_axis,
                batch_spec=batch_spec, head_axis=head_axis,
            )
        from ..parallel.ulysses import ulysses_attention_sharded

        return ulysses_attention_sharded(
            q, k, v, ctx.mesh, causal=causal, axis_name=ctx.seq_axis,
            batch_spec=batch_spec, head_axis=head_axis,
        )
    raise ValueError(f"Unknown attention impl {impl!r}")
