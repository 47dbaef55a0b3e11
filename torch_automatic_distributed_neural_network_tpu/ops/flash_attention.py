"""Pallas TPU flash attention (forward + backward kernels).

First-party block-streaming attention for the MXU (SURVEY.md §2.3: the
"native" tier on TPU is Pallas/Mosaic, not C++ we link ourselves).  The
reference's analog is torch.nn.functional.scaled_dot_product_attention
riding on cuDNN/flash CUDA kernels; here the kernel is implemented from
scratch:

- online-softmax streaming over K/V blocks -> O(seq) memory,
- fp32 accumulation, bf16-friendly inputs,
- causal masking with whole-block skipping (upper-triangle blocks are
  never computed),
- GQA (fewer K/V heads) by broadcast,
- arbitrary sequence lengths via padding + key masking,
- custom VJP with flash backward kernels (dq and dk/dv passes), so the
  attention matrix is never materialized in either direction.

Layout convention is BSHD [batch, seq, heads, head_dim]; internally the
kernels run on [batch*heads, seq, head_dim] with grid
(batch*heads, q_blocks, k_blocks) and VMEM scratch accumulators carried
across the innermost (arbitrary) grid dimension.

CPU fallback: ``interpret=True`` runs the same kernels in the Pallas
interpreter so every test exercises the real kernel logic on the 8-device
CPU sim (SURVEY.md §4).
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .attention import _check_window

# jax 0.5 renamed pltpu.TPUCompilerParams -> CompilerParams; accept both
_CompilerParams = getattr(pltpu, "CompilerParams", None) or getattr(
    pltpu, "TPUCompilerParams"
)

_NEG_BIG = -0.7 * float(np.finfo(np.float32).max)
_LANES = 128  # TPU lane width: scratch row-stats are stored broadcast


@dataclasses.dataclass(frozen=True)
class _Cfg:
    causal: bool
    seq_q: int  # true (unpadded) lengths
    seq_k: int
    block_q: int
    block_k: int
    interpret: bool
    # sliding window (Mistral-style): attend iff q_pos - window < k_pos
    # <= q_pos.  None = full causal.  Requires causal=True.
    window: int | None = None


def _block_relevant(qi, ki, cfg: _Cfg):
    """Grid-level whole-block skip: True iff ANY (q, k) pair in the
    (qi, ki) tile can attend.  Causal skips above the diagonal; a
    sliding window additionally skips blocks entirely OLDER than
    q_block_start - window (window implies causal, enforced at entry)."""
    if not cfg.causal:
        return True
    ok = ki * cfg.block_k <= qi * cfg.block_q + cfg.block_q - 1
    if cfg.window is not None:
        ok = jnp.logical_and(
            ok,
            ki * cfg.block_k + cfg.block_k - 1 > qi * cfg.block_q - cfg.window,
        )
    return ok


def _pair_mask(q_pos, k_pos, cfg: _Cfg):
    """Element mask shared by forward and recompute: key padding,
    causality, sliding window."""
    mask = k_pos < cfg.seq_k
    if cfg.causal:
        mask = jnp.logical_and(mask, q_pos >= k_pos)
    if cfg.window is not None:
        mask = jnp.logical_and(mask, q_pos - k_pos < cfg.window)
    return mask


def _default_interpret() -> bool:
    return jax.default_backend() == "cpu"


# ---------------------------------------------------------------------------
# Forward kernel
# ---------------------------------------------------------------------------


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref,
                *, cfg: _Cfg, scale: float):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, _NEG_BIG)
        l_ref[:] = jnp.zeros_like(l_ref)

    # skip blocks with no attendable pair (causal diagonal / window band)
    @pl.when(_block_relevant(qi, ki, cfg))
    def _block():
        q = q_ref[0]  # [bq, d]
        k = k_ref[0]  # [bk, d]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # [bq, bk]

        q_pos = qi * cfg.block_q + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 0)
        k_pos = ki * cfg.block_k + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        s = jnp.where(_pair_mask(q_pos, k_pos, cfg), s, _NEG_BIG)

        m_prev = m_ref[:, :1]  # [bq, 1] (stored broadcast over lanes)
        l_prev = l_ref[:, :1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)  # [bq, 1]
        m_new = jnp.maximum(m_prev, m_cur)
        # clamp so exp(_NEG_BIG - m) underflows to 0 for masked entries
        m_new = jnp.maximum(m_new, _NEG_BIG / 2)
        p = jnp.exp(s - m_new)  # [bq, bk] fp32
        alpha = jnp.exp(m_prev - m_new)  # [bq, 1]
        l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        pv = jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0],
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [bq, d]
        acc_ref[:] = acc_ref[:] * alpha + pv
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(ki == nk - 1)
    def _finish():
        l_safe = jnp.maximum(l_ref[:, :1], 1e-30)
        o_ref[0] = (acc_ref[:] / l_safe).astype(o_ref.dtype)
        # row stats are stored broadcast over the 128-lane dim (TPU tiling
        # forbids (1, block_q) blocks of a 2-D [bh, seq] array)
        lse_ref[0] = m_ref[:] + jnp.log(jnp.maximum(l_ref[:], 1e-30))


def _fwd(q, k, v, cfg: _Cfg):
    """q,k,v: [bh, S_pad, d] (padded).  Returns (o, lse) with lse fp32."""
    bh, sq, d = q.shape
    sk = k.shape[1]
    nq, nk = sq // cfg.block_q, sk // cfg.block_k
    scale = 1.0 / float(np.sqrt(d))
    kernel = functools.partial(_fwd_kernel, cfg=cfg, scale=scale)
    grid = (bh, nq, nk)
    o, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, cfg.block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, cfg.block_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, cfg.block_k, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, cfg.block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, cfg.block_q, _LANES), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
            jax.ShapeDtypeStruct((bh, sq, _LANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((cfg.block_q, d), jnp.float32),
            pltpu.VMEM((cfg.block_q, _LANES), jnp.float32),
            pltpu.VMEM((cfg.block_q, _LANES), jnp.float32),
        ],
        compiler_params=_CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=cfg.interpret,
        name="tadnn_flash_fwd",
    )(q, k, v)
    return o, lse


# ---------------------------------------------------------------------------
# Backward kernels
# ---------------------------------------------------------------------------
#
# Standard flash backward split into two accumulation passes:
#   dkv pass: grid (bh, k_blocks, q_blocks) — fixed K/V block accumulates
#             dk, dv over visiting Q blocks.
#   dq  pass: grid (bh, q_blocks, k_blocks) — fixed Q block accumulates dq.
# Both recompute p = exp(s - lse) from the saved logsumexp; delta =
# rowsum(do * o) is precomputed outside the kernel.


def _recompute_p(q, k, qi, ki, lse, cfg: _Cfg, scale):
    """lse: [bq, 1] (sliced from the lane-broadcast stats)."""
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale  # [bq, bk]
    q_pos = qi * cfg.block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    k_pos = ki * cfg.block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(_pair_mask(q_pos, k_pos, cfg), s, _NEG_BIG)
    return jnp.exp(s - lse)  # [bq, bk]


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_acc, dv_acc, *, cfg: _Cfg, scale: float):
    ki = pl.program_id(1)
    qi = pl.program_id(2)
    nq = pl.num_programs(2)

    @pl.when(qi == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    @pl.when(_block_relevant(qi, ki, cfg))
    def _block():
        q = q_ref[0]
        k = k_ref[0]
        do = do_ref[0].astype(jnp.float32)
        p = _recompute_p(q, k, qi, ki, lse_ref[0][:, :1], cfg, scale)
        # dv += p^T do
        dv_acc[:] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        # dp = do v^T ; ds = p * (dp - delta) * scale
        dp = jax.lax.dot_general(
            do, v_ref[0].astype(jnp.float32), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [bq, bk]
        ds = p * (dp - delta_ref[0][:, :1]) * scale
        # dk += ds^T q
        dk_acc[:] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(qi == nq - 1)
    def _finish():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
               dq_ref, dq_acc, *, cfg: _Cfg, scale: float):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    @pl.when(_block_relevant(qi, ki, cfg))
    def _block():
        q = q_ref[0]
        k = k_ref[0]
        do = do_ref[0].astype(jnp.float32)
        p = _recompute_p(q, k, qi, ki, lse_ref[0][:, :1], cfg, scale)
        dp = jax.lax.dot_general(
            do, v_ref[0].astype(jnp.float32), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - delta_ref[0][:, :1]) * scale
        dq_acc[:] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(ki == nk - 1)
    def _finish():
        dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)


def _bwd(cfg: _Cfg, res, do):
    return _bwd_impl(cfg, res, do, None)


def _bwd_stats(cfg: _Cfg, res, cot):
    """VJP for the (o, lse)-returning forward.  The lse cotangent folds
    into the delta term: dL/ds = p*(dp - delta) + p*dlse = p*(dp -
    (delta - dlse)), so the kernels run unchanged with an adjusted delta.
    """
    do, dlse_full = cot
    # dlse arrives in the lane-broadcast layout; callers slice one lane,
    # so summing over lanes recovers the row cotangent.
    dlse = jnp.sum(dlse_full.astype(jnp.float32), axis=-1)
    return _bwd_impl(cfg, res, do, dlse)


def _bwd_impl(cfg: _Cfg, res, do, dlse):
    q, k, v, o, lse = res
    bh, sq, d = q.shape
    sk = k.shape[1]
    nq, nk = sq // cfg.block_q, sk // cfg.block_k
    scale = 1.0 / float(np.sqrt(d))
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    if dlse is not None:
        delta = delta - dlse
    delta = jnp.broadcast_to(delta[..., None], (*delta.shape, _LANES))

    q_spec = pl.BlockSpec((1, cfg.block_q, d), lambda b, i, j: (b, i, 0))
    k_spec_kv = pl.BlockSpec((1, cfg.block_k, d), lambda b, i, j: (b, i, 0))
    q_spec_kv = pl.BlockSpec((1, cfg.block_q, d), lambda b, i, j: (b, j, 0))
    row_kv = pl.BlockSpec((1, cfg.block_q, _LANES), lambda b, i, j: (b, j, 0))
    k_spec_q = pl.BlockSpec((1, cfg.block_k, d), lambda b, i, j: (b, j, 0))
    row_q = pl.BlockSpec((1, cfg.block_q, _LANES), lambda b, i, j: (b, i, 0))

    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, cfg=cfg, scale=scale),
        grid=(bh, nk, nq),
        in_specs=[q_spec_kv, k_spec_kv, k_spec_kv, q_spec_kv, row_kv, row_kv],
        out_specs=[k_spec_kv, k_spec_kv],
        out_shape=[
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((cfg.block_k, d), jnp.float32),
            pltpu.VMEM((cfg.block_k, d), jnp.float32),
        ],
        compiler_params=_CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=cfg.interpret,
        name="tadnn_flash_bwd_dkv",
    )(q, k, v, do, lse, delta)

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, cfg=cfg, scale=scale),
        grid=(bh, nq, nk),
        in_specs=[q_spec, k_spec_q, k_spec_q, q_spec, row_q, row_q],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((cfg.block_q, d), jnp.float32)],
        compiler_params=_CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=cfg.interpret,
        name="tadnn_flash_bwd_dq",
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# custom-vjp core on folded [bh, S, d] arrays
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _flash_core(q, k, v, cfg: _Cfg):
    o, _ = _fwd(q, k, v, cfg)
    return o


def _flash_core_fwd(q, k, v, cfg: _Cfg):
    o, lse = _fwd(q, k, v, cfg)
    return o, (q, k, v, o, lse)


_flash_core.defvjp(_flash_core_fwd, _bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _flash_core_stats(q, k, v, cfg: _Cfg):
    """Like _flash_core but also returns the lane-broadcast logsumexp —
    the merge statistic ring attention needs (parallel/ring.py)."""
    return _fwd(q, k, v, cfg)


def _flash_core_stats_fwd(q, k, v, cfg: _Cfg):
    o, lse = _fwd(q, k, v, cfg)
    return (o, lse), (q, k, v, o, lse)


_flash_core_stats.defvjp(_flash_core_stats_fwd, _bwd_stats)


# ---------------------------------------------------------------------------
# Public BSHD entry point
# ---------------------------------------------------------------------------


def _pad_to(x, target, dim):
    pad = target - x.shape[dim]
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[dim] = (0, pad)
    return jnp.pad(x, widths)


# Per-seq (block_q, block_k) fwd+bwd winners of an earlier v5e sweep;
# their utilization is not measured on the current code.  2048-wide q
# blocks, and bq>=1024 x bk>=1024 combinations beyond these, exceed the
# compile helper's VMEM budget and fail to compile.
_MEASURED_BLOCKS = {
    2048: (512, 2048),
    8192: (512, 2048),
    16384: (1024, 1024),
}


def default_blocks(seq_k: int) -> tuple[int, int]:
    """Measured per-seq block defaults (nearest swept seq_k wins)."""
    key = min(_MEASURED_BLOCKS, key=lambda sw: abs(sw - seq_k))
    return _MEASURED_BLOCKS[key]


def _prep_bshd(q, k, v, causal, block_q, block_k, interpret,
               window=None):
    """Shared BSHD preprocessing: GQA broadcast, fold to [B*H, S, D], pad
    to block multiples.  Returns (qf, kf, vf, cfg, (b, hq, sq, d))."""
    _check_window(window, causal)
    if interpret is None:
        interpret = _default_interpret()
    b, sq, hq, d = q.shape
    sk, hk = k.shape[1], k.shape[2]
    if block_q is None or block_k is None:
        dq, dk = default_blocks(sk)
        block_q = dq if block_q is None else block_q
        block_k = dk if block_k is None else block_k
    if hk != hq:
        assert hq % hk == 0, (hq, hk)
        k = jnp.repeat(k, hq // hk, axis=2)
        v = jnp.repeat(v, hq // hk, axis=2)
    if causal and sq != sk:
        raise NotImplementedError(
            "causal flash attention requires seq_q == seq_k"
        )

    block_q = min(block_q, max(sq, 1))
    block_k = min(block_k, max(sk, 1))
    sq_pad = -(-sq // block_q) * block_q
    sk_pad = -(-sk // block_k) * block_k
    cfg = _Cfg(causal=causal, seq_q=sq, seq_k=sk, block_q=block_q,
               block_k=block_k, interpret=interpret, window=window)

    def fold(x):  # BSHD -> [B*H, S, D]
        x = jnp.swapaxes(x, 1, 2)
        return x.reshape(b * hq, x.shape[2], d)

    qf = _pad_to(fold(q), sq_pad, 1)
    kf = _pad_to(fold(k), sk_pad, 1)
    vf = _pad_to(fold(v), sk_pad, 1)
    return qf, kf, vf, cfg, (b, hq, sq, d)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = False,
    window: int | None = None,
    block_q: int | None = None,
    block_k: int | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """Flash attention over BSHD tensors [batch, seq, heads, head_dim].

    Numerically matches :func:`..attention.xla_attention` (the oracle the
    tests compare against) while never materializing the [S, S] score
    matrix.  K/V may have fewer heads (GQA) — broadcast to Q's head count.

    ``window`` (requires ``causal=True``) is Mistral-style sliding-window
    attention: position q attends keys in ``(q - window, q]``.  Blocks
    entirely outside the band are skipped at the grid level (fwd AND both
    bwd passes), so compute scales O(S * window) instead of O(S^2 / 2).

    Block defaults resolve per-sequence (:func:`default_blocks`):
    512x2048 up to seq 8k, 1024x1024 at 16k+ (not measured on the
    current code).  2048-wide q blocks exceed the VMEM budget and fail
    to compile.
    """
    qf, kf, vf, cfg, (b, hq, sq, d) = _prep_bshd(
        q, k, v, causal, block_q, block_k, interpret, window
    )
    of = _flash_core(qf, kf, vf, cfg)
    of = of[:, :sq]
    o = of.reshape(b, hq, sq, d)
    return jnp.swapaxes(o, 1, 2)


def flash_attention_with_lse(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = False,
    block_q: int | None = None,
    block_k: int | None = None,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Flash attention returning ``(o, lse)`` — ``o`` as BSHD, ``lse``
    [batch, heads, seq] fp32 logsumexp of each row's scores.

    The lse output is what makes per-block results mergeable: ring
    attention (parallel/ring.py) combines normalized block outputs as
    ``sum_i o_i * exp(lse_i - logaddexp_i(lse_i))``.  Gradients flow
    through both outputs (the lse cotangent folds into the kernels'
    delta term).
    """
    qf, kf, vf, cfg, (b, hq, sq, d) = _prep_bshd(
        q, k, v, causal, block_q, block_k, interpret
    )
    of, lse_f = _flash_core_stats(qf, kf, vf, cfg)
    o = jnp.swapaxes(of[:, :sq].reshape(b, hq, sq, d), 1, 2)
    lse = lse_f[:, :sq, 0].reshape(b, hq, sq)
    return o, lse
