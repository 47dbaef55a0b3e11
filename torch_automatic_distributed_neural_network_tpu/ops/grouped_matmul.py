"""Pallas grouped matmul for the expert FFN: rows sorted by expert, each
row tile multiplied by its own expert's matrix, and only the experts that
own a tile are ever read.

    rows:       [n_tiles * tm, K]   tile t holds rows of ONE expert
    w:          [G, K, N]           the experts held here
    tile_group: [n_tiles] int32     expert of tile t (scalar prefetch)
    n_active:   [1] int32           tiles 0 .. n_active hold rows

Grid ``(n_tiles, K / tk)``, K innermost: a grid step DMAs one ``[tk, N]``
slab of the tile's expert (whole rows of the matrix, so the copy is
contiguous) and accumulates ``[tm, N]`` in float32 in VMEM.  The number of
tiles is a static bound (``parallel/expert.held_expert_ffn`` lays the rows
out); past ``n_active`` a step's block indices stay where the last active
step left them, so it copies nothing, multiplies nothing and writes zeros.
HBM traffic is one read of each expert that owns a tile (twice for an
expert with two tiles), which is what a decode step is bound by.

Two programs: ``tadnn_moe_grouped_mm_gate_up`` computes
``silu(rows Wg) * (rows Wu)`` in one pass over the rows, and
``tadnn_moe_grouped_mm_down`` the plain product.  Off the TPU the same
kernels run in the Pallas interpreter, as ``ops/paged_attention.py``'s do.
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


def _kernel(tg_ref, na_ref, x_ref, *refs, fused: bool):
    del tg_ref
    if fused:
        wg_ref, wu_ref, o_ref, acc_g, acc_u = refs
    else:
        wu_ref, o_ref, acc_u = refs
    t, k = pl.program_id(0), pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        acc_u[...] = jnp.zeros_like(acc_u)
        if fused:
            acc_g[...] = jnp.zeros_like(acc_g)

    @pl.when(t < na_ref[0])
    def _tile():
        x = x_ref[...]
        acc_u[...] += jnp.dot(x, wu_ref[0],
                              preferred_element_type=jnp.float32)
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
                   interpret: bool | None = None) -> jax.Array:
    """``rows`` [n_tiles * tm, K] times each tile's expert of ``w_up``
    [G, K, N]; with ``w_gate``, ``silu(rows Wg) * (rows Wu)``.  Returns
    [n_tiles * tm, N] in ``rows.dtype``, zeros in the tiles past
    ``n_active``."""
    if interpret is None:
        interpret = _default_interpret()
    M, K = rows.shape
    G, _, N = w_up.shape
    n_tiles, tk = M // tm, _k_tile(K)
    nk = K // tk
    fused = w_gate is not None

    def live(t, k, na):
        # past the last active tile every index stays at that tile's last
        # step: an unchanged block index is not copied again
        on = t < na[0]
        return jnp.where(on, t, jnp.maximum(na[0] - 1, 0)), \
            jnp.where(on, k, nk - 1)

    def x_map(t, k, tg, na):
        tt, kk = live(t, k, na)
        return tt, kk

    def w_map(t, k, tg, na):
        tt, kk = live(t, k, na)
        return tg[tt], kk, 0

    w_spec = pl.BlockSpec((1, tk, N), w_map)
    acc = pltpu.VMEM((tm, N), jnp.float32)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n_tiles, nk),
        in_specs=[pl.BlockSpec((tm, tk), x_map)]
        + [w_spec] * (2 if fused else 1),
        out_specs=pl.BlockSpec((tm, N), lambda t, k, tg, na: (t, 0)),
        scratch_shapes=[acc] * (2 if fused else 1),
    )
    return pl.pallas_call(
        functools.partial(_kernel, fused=fused),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((M, N), rows.dtype),
        interpret=interpret,
        name=("tadnn_moe_grouped_mm_gate_up" if fused
              else "tadnn_moe_grouped_mm_down"),
    )(tile_group.astype(jnp.int32), n_active.astype(jnp.int32).reshape(1),
      rows, *((w_gate, w_up) if fused else (w_up,)))
