"""Pallas TPU flash attention (forward + backward kernels).

First-party block-streaming attention for the MXU (SURVEY.md §2.3: the
"native" tier on TPU is Pallas/Mosaic, not C++ we link ourselves).  The
reference's analog is torch.nn.functional.scaled_dot_product_attention
riding on cuDNN/flash CUDA kernels; here the kernel is implemented from
scratch:

- online-softmax streaming over K/V tiles -> O(seq) memory,
- fp32 accumulation; every matmul takes its operands in the inputs' dtype
  (``p`` and ``ds`` are rounded to it before their products, in the
  backward as in the forward: what the MXU computes either way), and the
  softmax scale rides on q, applied once a query tile, not on the scores,
- the kernels do the causal work and no more.  A grid step holds a
  *stripe* of query tiles and a stripe of key tiles in VMEM (a whole head
  each where it fits: ``flash_plan``) and loops over the tile pairs INSIDE
  the kernel, for each query tile from the first key tile the band reaches
  to the last one at the diagonal (``_key_tile_span``; the dk/dv kernel
  walks the same pairs from the key tile's side, ``_query_tile_span``).  A
  (query tile, key tile) pair above the diagonal, or older than the window,
  is never visited: no matmul, no vector pass, no grid step; where a whole
  pair of stripes is dead its index map is clamped to the last live one, so
  nothing is fetched for it.  A pair wholly inside the band runs WITHOUT a
  mask; only a pair that the diagonal, the window's edge or (non-causal)
  the key padding's edge crosses builds one (``_over_tiles``: three loops,
  masked, plain, masked),
- GQA (fewer K/V heads) by broadcast,
- arbitrary sequence lengths via padding + key masking,
- custom VJP with flash backward kernels (dq and dk/dv passes), so the
  attention matrix is never materialized in either direction.

Layout convention is BSHD [batch, seq, heads, head_dim]; internally the
kernels run on [batch*heads, seq, head_dim] with grid
(batch*heads, stripes of the side that holds the outputs, stripes of the
other side) and VMEM scratch accumulators carried across the innermost
(arbitrary) grid dimension.  ``flash_plan`` is the one rule that picks
tiles and stripes from what a call can see (lengths, head size, dtype,
window, causal or not); the entry records it as a ``flash.plan`` journal
event and ``tadnn report`` prints it.

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

from ..obs import journal as _journal
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
    # tiles of each side a grid step keeps resident and loops over; each
    # divides its side's tile count (1 and 1: one tile pair a grid step)
    stripe_q: int = 1
    stripe_k: int = 1

    @property
    def n_q(self) -> int:
        return -(-self.seq_q // self.block_q)

    @property
    def n_k(self) -> int:
        return -(-self.seq_k // self.block_k)


def _min(a, b):
    """min of Python ints (the plan) or of traced scalars (a kernel)."""
    if isinstance(a, int) and isinstance(b, int):
        return min(a, b)
    return jnp.minimum(a, b)


def _max(a, b):
    """max, likewise."""
    if isinstance(a, int) and isinstance(b, int):
        return max(a, b)
    return jnp.maximum(a, b)


def _clip(x, lo, hi):
    return _min(_max(x, lo), hi)


def _key_tile_span(qi, cfg: _Cfg):
    """The key tiles that query tile ``qi`` visits: ``(lo, m0, m1, hi)``.
    Tiles ``[lo, hi)`` hold a pair that attends; of them ``[lo, m0)`` (the
    window's edge) and ``[m1, hi)`` (the diagonal, or the padded last tile
    of a non-causal call) build a mask and ``[m0, m1)`` need none.  ``qi``
    is a Python int (the plan) or a traced scalar (a kernel)."""
    bq, bk, nk = cfg.block_q, cfg.block_k, cfg.n_k
    if not cfg.causal:
        return 0, 0, cfg.seq_k // bk, nk
    q_lo = qi * bq
    q_hi = q_lo + bq - 1
    hi = _min(q_hi // bk + 1, nk)
    m1 = _min((q_lo + 1) // bk, hi)  # tiles whose every key <= every query
    if cfg.window is None:
        return 0, 0, m1, hi
    lo = _max(q_lo - cfg.window + 1, 0) // bk
    # plain from the first tile whose oldest key the LAST query still sees
    m0 = _clip(_max(q_hi - cfg.window + bk, 0) // bk, lo, hi)
    return lo, m0, _max(m1, m0), hi


def _query_tile_span(ki, cfg: _Cfg):
    """The same pairs from key tile ``ki``'s side, for the dk/dv kernel:
    query tiles ``[lo, hi)``, masked on ``[lo, m0)`` (the diagonal; every
    tile where ``ki`` is the padded last tile of a non-causal call) and on
    ``[m1, hi)`` (the window's edge)."""
    bq, bk, nq = cfg.block_q, cfg.block_k, cfg.n_q
    k_lo = ki * bk
    k_hi = k_lo + bk - 1
    if not cfg.causal:
        if cfg.seq_k % bk == 0:
            return 0, 0, nq, nq
        # nq on the tile that holds padded keys, 0 on the others
        return 0, _clip((k_hi - cfg.seq_k + 1) * nq, 0, nq), nq, nq
    lo = _min(k_lo // bq, nq)
    if cfg.window is None:
        hi = m1 = nq
    else:
        hi = _min((k_hi + cfg.window - 1) // bq + 1, nq)
        m1 = (k_lo + cfg.window) // bq
    m0 = _clip((k_hi + bq - 1) // bq, lo, hi)  # every key <= every query
    return lo, m0, _clip(m1, m0, hi), hi


def _masked(s, qi, ki, cfg: _Cfg):
    """Scores [bq, bk] of a tile that needs a mask.  Under causality a mask
    is a compare of ``row - column`` with the tile's offset from the
    diagonal (and with that plus the window), and the key padding needs
    none of its own: a padded key is at or under the diagonal only of
    padded queries, whose rows are cut off and whose ``do`` is 0."""
    col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    if not cfg.causal:
        keep = col < cfg.seq_k - ki * cfg.block_k
    else:
        rel = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) - col
        off = ki * cfg.block_k - qi * cfg.block_q
        keep = rel >= off
        if cfg.window is not None:
            keep = jnp.logical_and(keep, rel < off + cfg.window)
    return jnp.where(keep, s, _NEG_BIG)


def _over_tiles(span, first, count, tile):
    """Run ``tile(t, masked)`` over the tiles of ``span`` (a ``*_tile_span``)
    that lie in the resident stripe ``[first, first + count)``: three loops,
    masked, plain, masked, each of a traced length; one that is empty for
    every grid step is not emitted."""
    lo, m0, m1, hi = span
    for a, b, masked in ((lo, m0, True), (m0, m1, False), (m1, hi, True)):
        if isinstance(a, int) and isinstance(b, int) and a >= b:
            continue
        jax.lax.fori_loop(
            _clip(a, first, first + count), _clip(b, first, first + count),
            lambda t, _, masked=masked: tile(t, masked), None)


def _stripe_index(span_of, held: int, stripe: int, n_stripes: int,
                  cfg: _Cfg):
    """Index map of the operands a kernel walks along its LAST grid axis,
    a stripe at a time: the grid's stripe, clamped to those that the spans
    of the step's ``held`` tiles (the other side's stripe, grid axis 1)
    reach, so that a dead grid step names the block of the step before and
    nothing is copied for it."""
    if n_stripes == 1:
        return lambda b, i, s: (b, 0, 0)

    def index(b, i, s):
        lo = span_of(i * held, cfg)[0]
        hi = span_of(i * held + held - 1, cfg)[3]
        return b, _clip(s, lo // stripe, (hi - 1) // stripe), 0

    return index


def _rows(t, block: int):
    """Rows of tile ``t`` of a resident stripe."""
    return pl.ds(pl.multiple_of(t * block, block), block)


def _lanes(x, n: int):
    """``x`` [rows, 128], each row one value in every lane, as [rows, n]:
    whole vregs again where ``n`` is a multiple of the lane width (no
    cross-lane move), a broadcast of lane 0 elsewhere (interpreted sizes)."""
    if n == _LANES:
        return x
    if n % _LANES == 0:
        return pltpu.repeat(x, n // _LANES, axis=1)
    return jnp.broadcast_to(x[:, :1], (x.shape[0], n))


def _lane_sums(p):
    """[rows, n] -> [rows, 128] whose lanes SUM to the row's sum, by adding
    whole vregs (the cross-lane reduction is left to the caller, once a
    query tile); off the lane width the row's sum sits in lane 0."""
    rows, n = p.shape
    if n % _LANES == 0:
        return functools.reduce(
            jnp.add, (p[:, t:t + _LANES] for t in range(0, n, _LANES)))
    lane = jax.lax.broadcasted_iota(jnp.int32, (rows, _LANES), 1)
    return jnp.where(lane == 0, jnp.sum(p, axis=-1, keepdims=True), 0.0)


def _dot(a, b, contract):
    return jax.lax.dot_general(a, b, (contract, ((), ())),
                               preferred_element_type=jnp.float32)


_NT = ((1,), (1,))  # a b^T
_NN = ((1,), (0,))  # a b
_TN = ((0,), (0,))  # a^T b


def _default_interpret() -> bool:
    return jax.default_backend() == "cpu"


# ---------------------------------------------------------------------------
# Forward kernel
# ---------------------------------------------------------------------------


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref,
                *, cfg: _Cfg, scale: float):
    qs, ks = pl.program_id(1), pl.program_id(2)
    bq, bk, d = cfg.block_q, cfg.block_k, q_ref.shape[-1]
    first_q, first_k = qs * cfg.stripe_q, ks * cfg.stripe_k

    @pl.when(ks == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, _NEG_BIG)
        l_ref[:] = jnp.zeros_like(l_ref)

    def query_tile(t, _):
        qi, qr = first_q + t, _rows(t, bq)
        q = q_ref[0, qr, :] * scale  # [bq, d], scaled once: the scores are not

        def tile(ki, masked):
            kr = _rows(ki - first_k, bk)
            s = _dot(q, k_ref[0, kr, :], _NT)  # [bq, bk] fp32
            if masked:
                s = _masked(s, qi, ki, cfg)
            # row statistics stay a whole vreg wide (a value in every lane)
            m_prev = m_ref[qr, :]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            if masked:
                # a row with no key in this tile: exp(_NEG_BIG - m) stays 0
                m_new = jnp.maximum(m_new, _NEG_BIG / 2)
            p = jnp.exp(s - _lanes(m_new, bk))
            alpha = jnp.exp(m_prev - m_new)
            l_ref[qr, :] = l_ref[qr, :] * alpha + _lane_sums(p)
            acc_ref[qr, :] = acc_ref[qr, :] * _lanes(alpha, d) + _dot(
                p.astype(v_ref.dtype), v_ref[0, kr, :], _NN)
            m_ref[qr, :] = m_new

        _over_tiles(_key_tile_span(qi, cfg), first_k, cfg.stripe_k, tile)

    jax.lax.fori_loop(0, cfg.stripe_q, query_tile, None)

    @pl.when(ks == pl.num_programs(2) - 1)
    def _finish():
        l = jnp.maximum(jnp.sum(l_ref[:], axis=-1, keepdims=True), 1e-30)
        o_ref[0] = (acc_ref[:] / l).astype(o_ref.dtype)
        # row stats are stored broadcast over the 128-lane dim (TPU tiling
        # forbids (1, block_q) blocks of a 2-D [bh, seq] array)
        lse_ref[0] = m_ref[:] + jnp.log(l)


def _params(cfg: _Cfg):
    return dict(
        compiler_params=_CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=cfg.interpret)


def _specs(cfg: _Cfg, q_leads: bool):
    """(grid without the heads' axis, BlockSpec maker of the query side,
    of the key side), each maker taking the operand's width.  The side that
    leads holds the kernel's outputs and walks grid axis 1; the other is
    walked, clamped, along the last axis, over which the outputs
    accumulate."""
    rows_q, rows_k = cfg.stripe_q * cfg.block_q, cfg.stripe_k * cfg.block_k
    stripes_q, stripes_k = cfg.n_q // cfg.stripe_q, cfg.n_k // cfg.stripe_k

    def lead(b, i, s):
        return b, i, 0

    if q_leads:
        grid, q_index = (stripes_q, stripes_k), lead
        k_index = _stripe_index(_key_tile_span, cfg.stripe_q, cfg.stripe_k,
                                stripes_k, cfg)
    else:
        grid, k_index = (stripes_k, stripes_q), lead
        q_index = _stripe_index(_query_tile_span, cfg.stripe_k, cfg.stripe_q,
                                stripes_q, cfg)
    return (grid,
            lambda width: pl.BlockSpec((1, rows_q, width), q_index),
            lambda width: pl.BlockSpec((1, rows_k, width), k_index))


def _fwd(q, k, v, cfg: _Cfg):
    """q,k,v: [bh, S_pad, d] (padded).  Returns (o, lse) with lse fp32."""
    bh, sq, d = q.shape
    rows_q = cfg.stripe_q * cfg.block_q
    scale = 1.0 / float(np.sqrt(d))
    grid, q_side, k_side = _specs(cfg, q_leads=True)
    o, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, cfg=cfg, scale=scale),
        grid=(bh, *grid),
        in_specs=[q_side(d), k_side(d), k_side(d)],
        out_specs=[q_side(d), q_side(_LANES)],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
            jax.ShapeDtypeStruct((bh, sq, _LANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((rows_q, d), jnp.float32),
            pltpu.VMEM((rows_q, _LANES), jnp.float32),
            pltpu.VMEM((rows_q, _LANES), jnp.float32),
        ],
        name="tadnn_flash_fwd",
        **_params(cfg),
    )(q, k, v)
    return o, lse


# ---------------------------------------------------------------------------
# Backward kernels
# ---------------------------------------------------------------------------
#
# Standard flash backward split into two accumulation passes:
#   dkv pass: grid (bh, k_stripes, q_stripes) — a K/V tile accumulates
#             dk, dv over the Q tiles from its diagonal on.
#   dq  pass: grid (bh, q_stripes, k_stripes) — a Q tile accumulates dq
#             over the K tiles up to its diagonal.
# Both recompute p = exp(s - lse) from the saved logsumexp; delta =
# rowsum(do * o) is precomputed outside the kernel.  The scale rides on q
# (as in the forward, so s is the forward's to the bit): dk takes it from
# the scaled q, dq is scaled once at the end.


def _recompute(q, k, v, do, lse, delta, qi, ki, masked, cfg: _Cfg):
    """(p, ds) of one tile, fp32 [bq, bk], ds without the scale; lse and
    delta [bq, 128] as stored."""
    bk = k.shape[0]
    s = _dot(q, k, _NT)
    if masked:
        s = _masked(s, qi, ki, cfg)
    p = jnp.exp(s - _lanes(lse, bk))
    dp = _dot(do, v, _NT)
    return p, p * (dp - _lanes(delta, bk))


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_acc, dv_acc, *, cfg: _Cfg, scale: float):
    ks, qs = pl.program_id(1), pl.program_id(2)
    bq, bk = cfg.block_q, cfg.block_k
    first_q, first_k = qs * cfg.stripe_q, ks * cfg.stripe_k

    @pl.when(qs == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    def key_tile(t, _):
        ki, kr = first_k + t, _rows(t, bk)
        k, v = k_ref[0, kr, :], v_ref[0, kr, :]

        def tile(qi, masked):
            qr = _rows(qi - first_q, bq)
            q = q_ref[0, qr, :] * scale
            do = do_ref[0, qr, :]
            p, ds = _recompute(q, k, v, do, lse_ref[0, qr, :],
                               delta_ref[0, qr, :], qi, ki, masked, cfg)
            dv_acc[kr, :] += _dot(p.astype(do.dtype), do, _TN)
            dk_acc[kr, :] += _dot(ds.astype(q.dtype), q, _TN)

        _over_tiles(_query_tile_span(ki, cfg), first_q, cfg.stripe_q, tile)

    jax.lax.fori_loop(0, cfg.stripe_k, key_tile, None)

    @pl.when(qs == pl.num_programs(2) - 1)
    def _finish():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
               dq_ref, dq_acc, *, cfg: _Cfg, scale: float):
    qs, ks = pl.program_id(1), pl.program_id(2)
    bq, bk = cfg.block_q, cfg.block_k
    first_q, first_k = qs * cfg.stripe_q, ks * cfg.stripe_k

    @pl.when(ks == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    def query_tile(t, _):
        qi, qr = first_q + t, _rows(t, bq)
        q = q_ref[0, qr, :] * scale
        do, lse, delta = do_ref[0, qr, :], lse_ref[0, qr, :], delta_ref[0, qr, :]

        def tile(ki, masked):
            kr = _rows(ki - first_k, bk)
            k = k_ref[0, kr, :]
            _, ds = _recompute(q, k, v_ref[0, kr, :], do, lse, delta,
                               qi, ki, masked, cfg)
            dq_acc[qr, :] += _dot(ds.astype(k.dtype), k, _NN)

        _over_tiles(_key_tile_span(qi, cfg), first_k, cfg.stripe_k, tile)

    jax.lax.fori_loop(0, cfg.stripe_q, query_tile, None)

    @pl.when(ks == pl.num_programs(2) - 1)
    def _finish():
        dq_ref[0] = (dq_acc[:] * scale).astype(dq_ref.dtype)


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
    bh, _, d = q.shape
    rows_q = cfg.stripe_q * cfg.block_q
    rows_k = cfg.stripe_k * cfg.block_k
    scale = 1.0 / float(np.sqrt(d))
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    if dlse is not None:
        delta = delta - dlse
    delta = jnp.broadcast_to(delta[..., None], (*delta.shape, _LANES))

    grid, q_side, k_side = _specs(cfg, q_leads=False)
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, cfg=cfg, scale=scale),
        grid=(bh, *grid),
        in_specs=[q_side(d), k_side(d), k_side(d), q_side(d),
                  q_side(_LANES), q_side(_LANES)],
        out_specs=[k_side(d), k_side(d)],
        out_shape=[
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((rows_k, d), jnp.float32),
            pltpu.VMEM((rows_k, d), jnp.float32),
        ],
        name="tadnn_flash_bwd_dkv",
        **_params(cfg),
    )(q, k, v, do, lse, delta)

    grid, q_side, k_side = _specs(cfg, q_leads=True)
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, cfg=cfg, scale=scale),
        grid=(bh, *grid),
        in_specs=[q_side(d), k_side(d), k_side(d), q_side(d),
                  q_side(_LANES), q_side(_LANES)],
        out_specs=q_side(d),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((rows_q, d), jnp.float32)],
        name="tadnn_flash_bwd_dq",
        **_params(cfg),
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


# What the tile rule reckons with.  Measured on one v5e chip on PR 40's tree
# (parent 1065010), one layer's call in bf16 with head_dim 128, ms a call of
# forward / dk,dv / dq (PERF.md §6, PR 40):
#   [16, 1024, 16, 128]: 512 x 512 tiles 0.864 / 1.326 / 1.052 (the old
#     512 x 1024, which computed the whole square, 1.344 / 1.625 / 1.246);
#     256 x 512 0.999 / 1.617 / 1.151, 512 x 256 1.232 / 1.628 / 1.298,
#     256 x 256 1.465 / 1.712 / 1.393, 1024 x 1024 1.097 / 1.630 / 1.224:
#     a tile pair costs a fixed latency beside its matmuls, so 512 beats the
#     smaller tiles though they visit 10 of 16 where it visits 3 of 4.
#   [4, S, 16, 128] at 512 x 512, stripes of 2,048 rows: S 2,048 0.642 /
#     1.048 / 0.824 (old 512 x 2048: 1.089 / 1.526 / 1.161), 8,192 8.37 /
#     14.50 / 12.24 (13.36 / 17.10 / 15.02), 16,384 31.65 / 56.18 / 46.10
#     (old 1024 x 1024: 40.69 / 72.26 / 47.93).
# A v5e core has 128 MiB of VMEM; a kernel may use ``_VMEM_LIMIT`` of it
# (Mosaic's default of 16 MiB holds no long stripe), and the rule keeps what
# it can count under ``_VMEM_BUDGET``, half of that: what the compiler adds
# is not counted.
_VMEM_LIMIT = 64 * 2**20
_VMEM_BUDGET = 32 * 2**20
_STRIPE_ROWS = 2048  # rows of a side a grid step holds: a head at <= 2,048
_TILE = 512


@dataclasses.dataclass(frozen=True)
class FlashPlan:
    """What one call of the entry does, from its shapes alone."""
    block_q: int
    block_k: int
    stripe_q: int  # query tiles a grid step holds and loops over
    stripe_k: int  # key tiles a grid step holds and loops over
    tiles_visited: int  # (query tile, key tile) pairs a kernel computes
    tiles_square: int   # pairs of the whole [seq_q, seq_k] square
    tiles_masked: int   # visited pairs that build a mask
    vmem_bytes: int     # the largest reckoned use of the three kernels


def _vmem_bytes(bq, bk, stripe_q, stripe_k, d, itemsize):
    """Reckoned VMEM of the largest of the three kernels: both sides'
    stripes double-buffered, the accumulators, and six fp32 temporaries the
    size of a tile's scores."""
    q_rows, k_rows = stripe_q * bq, stripe_k * bk
    x, stat = d * itemsize, _LANES * 4  # a row of q/k/v/do; of lse/delta
    fwd = 2 * (q_rows * (2 * x + stat) + k_rows * 2 * x) \
        + q_rows * (d * 4 + 2 * stat)
    dkv = 2 * (q_rows * (2 * x + 2 * stat) + k_rows * 4 * x) \
        + k_rows * 2 * d * 4
    dq = 2 * (q_rows * (3 * x + 2 * stat) + k_rows * 2 * x) + q_rows * d * 4
    return max(fwd, dkv, dq) + 6 * bq * bk * 4


def _stripe(n_tiles: int, block: int, rows: int) -> int:
    """The most tiles that divide ``n_tiles`` and stay within ``rows``."""
    return max(t for t in range(1, n_tiles + 1)
               if n_tiles % t == 0 and (t == 1 or t * block <= rows))


def flash_plan(seq_q: int, seq_k: int, head_dim: int, itemsize: int, *,
               causal: bool, window: int | None = None,
               block_q: int | None = None,
               block_k: int | None = None) -> FlashPlan:
    """The one rule that picks a call's tiles and stripes, and the count of
    what the kernels then visit.

    Tiles: ``_TILE`` x ``_TILE`` clipped to the sequence (measured on a v5e
    at [16, 1024, 16, 128] and [4, 2048 / 8192 / 16384, 16, 128] bf16,
    PERF.md §6 PR 40).  Stripes: on each side as many tiles as divide the
    sequence and stay within ``_STRIPE_ROWS`` rows, halved until the
    reckoned VMEM is under ``_VMEM_BUDGET``: a whole head where it fits, so
    that a grid step is a head, its operands are fetched once and the next
    head's arrive behind its work.  A caller's ``block_q`` / ``block_k``
    override the tiles; the stripes follow.
    """
    bq = min(block_q or _TILE, max(seq_q, 1))
    bk = min(block_k or _TILE, max(seq_k, 1))
    nq, nk = -(-seq_q // bq), -(-seq_k // bk)
    rows = _STRIPE_ROWS
    while True:
        stripe_q, stripe_k = _stripe(nq, bq, rows), _stripe(nk, bk, rows)
        vmem = _vmem_bytes(bq, bk, stripe_q, stripe_k, head_dim, itemsize)
        if vmem <= _VMEM_BUDGET or stripe_q == stripe_k == 1:
            break
        rows //= 2
    cfg = _Cfg(causal=causal, seq_q=seq_q, seq_k=seq_k, block_q=bq,
               block_k=bk, interpret=False, window=window)
    spans = [_key_tile_span(qi, cfg) for qi in range(nq)]
    return FlashPlan(
        block_q=bq, block_k=bk, stripe_q=stripe_q, stripe_k=stripe_k,
        tiles_visited=sum(hi - lo for lo, _, _, hi in spans),
        tiles_square=nq * nk,
        tiles_masked=sum(m0 - lo + hi - m1 for lo, m0, m1, hi in spans),
        vmem_bytes=vmem)


def _prep_bshd(q, k, v, causal, block_q, block_k, interpret,
               window=None):
    """Shared BSHD preprocessing: the call's plan (recorded as a
    ``flash.plan`` journal event, once a trace), GQA broadcast, fold to
    [B*H, S, D], pad to tile multiples.  Returns (qf, kf, vf, cfg,
    (b, hq, sq, d))."""
    _check_window(window, causal)
    if interpret is None:
        interpret = _default_interpret()
    b, sq, hq, d = q.shape
    sk, hk = k.shape[1], k.shape[2]
    if hk != hq:
        assert hq % hk == 0, (hq, hk)
        k = jnp.repeat(k, hq // hk, axis=2)
        v = jnp.repeat(v, hq // hk, axis=2)
    if causal and sq != sk:
        raise NotImplementedError(
            "causal flash attention requires seq_q == seq_k"
        )

    plan = flash_plan(sq, sk, d, q.dtype.itemsize, causal=causal,
                      window=window, block_q=block_q, block_k=block_k)
    _journal.event("flash.plan", seq=sk, head_dim=d, block_q=plan.block_q,
                   block_k=plan.block_k, tiles_visited=plan.tiles_visited,
                   tiles_square=plan.tiles_square,
                   tiles_masked=plan.tiles_masked)
    cfg = _Cfg(causal=causal, seq_q=sq, seq_k=sk, block_q=plan.block_q,
               block_k=plan.block_k, interpret=interpret, window=window,
               stripe_q=plan.stripe_q, stripe_k=plan.stripe_k)

    def fold(x):  # BSHD -> [B*H, S, D]
        x = jnp.swapaxes(x, 1, 2)
        return x.reshape(b * hq, x.shape[2], d)

    qf = _pad_to(fold(q), cfg.n_q * cfg.block_q, 1)
    kf = _pad_to(fold(k), cfg.n_k * cfg.block_k, 1)
    vf = _pad_to(fold(v), cfg.n_k * cfg.block_k, 1)
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
    attention: position q attends keys in ``(q - window, q]``.  Tiles
    entirely outside the band are never visited (fwd AND both bwd
    passes), so compute scales O(S * window) instead of O(S^2 / 2).

    Tiles and stripes come from :func:`flash_plan` (``block_q`` /
    ``block_k`` override its tiles).
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
