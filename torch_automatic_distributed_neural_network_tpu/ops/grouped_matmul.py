"""Pallas grouped matmul for the expert FFN: rows sorted by expert, each
row tile multiplied by its own expert's matrix, and only the experts that
own a tile are ever read.

    rows:       [n_tiles * tm, K]   tile t holds rows of ONE expert
    w:          [G, K, N]           the experts held here
    tile_group: [n_tiles] int32     expert of tile t (scalar prefetch)
    n_active:   [] int32            tiles 0 .. n_active hold rows

Grid ``(n_active, K / tk)``, K innermost: a grid step DMAs one ``[tk, N]``
slab of the tile's expert (whole rows of the matrix, so the copy is
contiguous) and accumulates ``[tm, N]`` in float32 in VMEM.  The number of
tiles LAID is a static bound (``parallel/expert.held_expert_ffn`` lays the
rows out for the case of every pair on this chip); the grid is the tiles
that HOLD rows, a traced number (as the paged kernels' work lists are), so
a tile past ``n_active`` costs nothing at all: it is not read, not
multiplied and NOT WRITTEN.  Those tiles of the output hold whatever the
buffer held (anything, NaN included; the interpreter fills them with NaN):
a caller may read rows of tiles ``0 .. n_active`` only, as
``held_expert_ffn``'s combine does.  HBM traffic is one read of each expert
that owns a tile (twice for an expert with two tiles), which is what a
decode step is bound by.

Two programs: ``tadnn_moe_grouped_mm_gate_up`` computes
``silu(rows Wg) * (rows Wu)`` in one pass over the rows, and
``tadnn_moe_grouped_mm_down`` the plain product.  The first can take the
TOKENS in place of the rows and the token of each row (``src``): it then
holds the tokens whole in VMEM and picks a tile's rows itself, by a product
with a matrix of one 1 a row, and the padded copy of the tokens is never
made.  Off the TPU the same kernels run in the Pallas interpreter, as
``ops/paged_attention.py``'s do.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _default_interpret() -> bool:
    return jax.default_backend() == "cpu"


def _k_tile(K: int) -> int:
    """Rows of the expert's matrix a grid step takes: the largest of these
    that divides K (two matrices of [256, 3072] bf16, double-buffered, are
    6 MB of the 16 MB a kernel may use)."""
    for tk in (256, 128):
        if K % tk == 0:
            return tk
    return K


def _kernel(tg_ref, x_ref, *refs, fused: bool, gather: bool):
    del tg_ref
    if gather:
        src_ref, *refs = refs
    if fused:
        wg_ref, wu_ref, o_ref, acc_g, acc_u = refs
    else:
        wu_ref, o_ref, acc_u = refs
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        acc_u[...] = jnp.zeros_like(acc_u)
        if fused:
            acc_g[...] = jnp.zeros_like(acc_g)

    if gather:
        # the tile's rows out of the tokens, by a product with one 1 a row
        # (exact; no 1 where ``src`` names no token: a row of zeros)
        tm, n_tok = src_ref.shape[0], x_ref.shape[1]
        pick = src_ref[...] == jax.lax.broadcasted_iota(
            jnp.int32, (tm, n_tok), 1)
        x = jnp.dot(
            pick.astype(x_ref.dtype), x_ref[k],
            preferred_element_type=jnp.float32,
            precision=(jax.lax.Precision.HIGHEST
                       if x_ref.dtype == jnp.float32 else None),
        ).astype(x_ref.dtype)
    else:
        x = x_ref[...]
    acc_u[...] += jnp.dot(x, wu_ref[0], preferred_element_type=jnp.float32)
    if fused:
        acc_g[...] += jnp.dot(x, wg_ref[0],
                              preferred_element_type=jnp.float32)

    @pl.when(k == pl.num_programs(1) - 1)
    def _finish():
        y = acc_u[...]
        if fused:
            y = jax.nn.silu(acc_g[...]) * y
        o_ref[...] = y.astype(o_ref.dtype)


def grouped_matmul(rows: jax.Array, w_up: jax.Array, tile_group: jax.Array,
                   n_active: jax.Array, *, tm: int,
                   w_gate: jax.Array | None = None,
                   src: jax.Array | None = None,
                   interpret: bool | None = None) -> jax.Array:
    """``rows`` [n_tiles * tm, K] times each tile's expert of ``w_up``
    [G, K, N]; with ``w_gate``, ``silu(rows Wg) * (rows Wu)``.  Returns
    [n_tiles * tm, N] in ``rows.dtype``; the tiles past ``n_active`` are
    not visited: not written (they may hold anything) and not to be read.
    With no tile active, tile 0 is run all the same (a grid of no steps is
    not asked of the chip): its rows are nobody's.

    With ``src`` [n_tiles * tm] int32, ``rows`` is the TOKENS [T, K] and
    padded row ``r`` is ``rows[src[r]]`` (zeros where ``src[r]`` is no
    token, say -1): the kernel holds the tokens in VMEM (one buffer: they
    are copied once) and picks a tile's rows itself, so no
    [n_tiles * tm, K] array is ever written."""
    if interpret is None:
        interpret = _default_interpret()
    gather = src is not None
    K = rows.shape[1]
    G, _, N = w_up.shape
    M = src.shape[0] if gather else rows.shape[0]
    n_tiles, tk = M // tm, _k_tile(K)
    nk = K // tk
    fused = w_gate is not None

    if gather:
        # the tokens whole, a slab of columns a leading index, copied once
        n_tok = rows.shape[0]
        x_args = (rows.reshape(n_tok, nk, tk).swapaxes(0, 1),
                  src.astype(jnp.int32).reshape(M, 1))
        x_specs = [pl.BlockSpec((nk, n_tok, tk), lambda t, k, tg: (0, 0, 0),
                                pipeline_mode=pl.Buffered(1)),
                   pl.BlockSpec((tm, 1), lambda t, k, tg: (t, 0))]
    else:
        x_args = (rows,)
        x_specs = [pl.BlockSpec((tm, tk), lambda t, k, tg: (t, k))]
    w_spec = pl.BlockSpec((1, tk, N), lambda t, k, tg: (tg[t], k, 0))
    acc = pltpu.VMEM((tm, N), jnp.float32)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        # the live tiles alone, a traced number (one where there is none:
        # its rows are nobody's); the pipeline reads the indices of the step
        # after the last, so the list of experts is one longer than the grid
        grid=(jnp.clip(n_active.astype(jnp.int32).reshape(()), 1, n_tiles),
              nk),
        in_specs=x_specs + [w_spec] * (2 if fused else 1),
        out_specs=pl.BlockSpec((tm, N), lambda t, k, tg: (t, 0)),
        scratch_shapes=[acc] * (2 if fused else 1),
    )
    return pl.pallas_call(
        functools.partial(_kernel, fused=fused, gather=gather),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((M, N), rows.dtype),
        interpret=interpret,
        name=("tadnn_moe_grouped_mm_gate_up" if fused
              else "tadnn_moe_grouped_mm_down"),
    )(jnp.pad(tile_group.astype(jnp.int32), (0, 1), mode="edge"), *x_args,
      *((w_gate, w_up) if fused else (w_up,)))
