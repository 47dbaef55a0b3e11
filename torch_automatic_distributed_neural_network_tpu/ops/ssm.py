"""The selective scan of a ``state_space`` layer (Mamba-1): a diagonal
state-space recurrence whose step size, input map and output map all depend
on the token.

A channel ``c`` of the ``d_in`` inner channels keeps ``N`` numbers, the state
``h`` [N, d_in] in float32 (channels in the lanes: the published module's
``[d_in, N]`` transposed, so that a row of 16 numbers is not padded to a
tile of 128).  A token with input ``c_t`` [d_in] (the convolved, activated
projection), step ``Delta_t`` [d_in] (> 0, after its softplus), input map
``B_t`` [N] and output map ``C_t`` [N] does

    h_t = exp(Delta_t A) * h_{t-1} + (Delta_t * c_t) B_t^T ,   A = -exp(A_log) [N, d_in]
    y_t = C_t . h_t + D * c_t                                   (the sum over N)

Nothing of ``ops/gated_delta.py`` computes this: its rules write a rank-one
CORRECTION of what the state predicts, with a decay a head or a key channel
that the token picks; here the state is a vector a channel, the decay ``exp(
Delta_t A)`` is a different number for each of its ``N x d_in`` entries a
token, and there is no matmul form (the decay between two tokens does not
factor out of a product).  So the arithmetic is elementwise, and what a
kernel buys is the ORDER: the state stays in vector registers across the
tokens, where ``lax.scan`` pays a program step and a trip of the state
through memory a token (512 tokens x 9 layers a prefill chunk).

Three forms of the same arithmetic:

- :func:`ssm_recurrent`, the equations token by token under ``lax.scan``:
  the oracle, and the CPU path of the two below;
- the CHUNK form for a prompt, :func:`ssm_chunk`: on a TPU the kernel
  ``tadnn_ssm_chunk``, grid over blocks of ``SSM_LANES`` channels, the
  chunk's tokens in a loop inside with the block's state [N, lanes] carried
  in registers, eight tokens' outputs stored as one tile; state in, state
  out;
- the STEP form for decode, :func:`ssm_step`: one token a slot against a
  pool of states ``[rows, N, d_in]`` read and written in place through a
  vector of row ids (row 0 the null row of the slots that do not decode): on
  a TPU the kernel ``tadnn_ssm_step``, grid over the slots, a slot's whole
  row a step.

A row with ``Delta == 0`` leaves the state as it was (a padded chunk's tail,
an inactive slot).  ``Delta``, the exponential and the state are float32 in
every form.  The platform picks between a kernel and the plain form, as for
the other kernels here; there is no switch.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32 = jnp.float32
SSM_LANES = 512  # channels a grid step of the chunk kernel takes, at most
SSM_ROWS = 8  # tokens whose outputs the chunk kernel stores as one tile
_CHUNK_VMEM = 48 * 2**20  # of a v5e's 128 MiB: a chunk's [T, N, 2] maps in tiles


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def chunk_lanes(d_in: int) -> int | None:
    """Channels a grid step of ``tadnn_ssm_chunk`` takes (whole tiles of 128
    lanes that divide ``d_in``), or None where the kernel does not tile."""
    for lanes in (SSM_LANES, 256, 128):
        if d_in % lanes == 0:
            return lanes
    return None


def _f32(*xs):
    return tuple(x.astype(F32) for x in xs)


# -- token by token: the oracle ------------------------------------------------


def ssm_recurrent(c, delta, A, B, C, D, h0):
    """The equations as written, a token a scan step: ``c``, ``delta``
    [T, d_in], ``A`` [N, d_in] (negative), ``B``, ``C`` [T, N], ``D``
    [d_in], ``h0`` [N, d_in].  Returns ``(y [T, d_in] float32, h)``."""
    c, delta, A, B, C, D, h0 = _f32(c, delta, A, B, C, D, h0)

    def step(h, x):
        c, dt, b, cc = x
        h = jnp.exp(dt[None, :] * A) * h + (dt * c)[None, :] * b[:, None]
        return h, jnp.sum(h * cc[:, None], axis=0) + D * c

    h, y = jax.lax.scan(step, h0, (c, delta, B, C))
    return y, h


# -- the chunk form ---------------------------------------------------------------


def _chunk_kernel(c_ref, dt_ref, bc_ref, a_ref, d_ref, h0_ref, y_ref, h_ref,
                  *, groups: int):
    """A block of channels over all of a chunk's tokens.  ``bc_ref`` [T, N,
    2] holds a token's ``B`` and ``C`` as COLUMNS (``N`` on the sublanes),
    which broadcast along the lanes beside a state whose channels lie
    there; ``c`` and ``Delta`` are rows and broadcast along the sublanes."""
    A, D = a_ref[:], d_ref[:]

    def group(g, h):
        t0 = pl.multiple_of(g * SSM_ROWS, SSM_ROWS)
        c8, d8 = c_ref[pl.ds(t0, SSM_ROWS), :], dt_ref[pl.ds(t0, SSM_ROWS), :]
        bc8 = bc_ref[pl.ds(t0, SSM_ROWS)]
        ys = []
        for i in range(SSM_ROWS):  # static: eight outputs, one store
            c, dt = c8[i:i + 1], d8[i:i + 1]
            h = jnp.exp(dt * A) * h + (dt * c) * bc8[i][:, 0:1]
            ys.append(jnp.sum(h * bc8[i][:, 1:2], axis=0, keepdims=True)
                      + D * c)
        y_ref[pl.ds(t0, SSM_ROWS), :] = jnp.concatenate(ys, axis=0)
        return h

    h_ref[:] = jax.lax.fori_loop(0, groups, group, h0_ref[:])


def ssm_chunk_pallas(c, delta, A, B, C, D, h0, *, interpret: bool = False):
    """The chunk form as the kernel ``tadnn_ssm_chunk`` (shapes as
    :func:`ssm_recurrent`; ``d_in`` in whole tiles, ``chunk_lanes``)."""
    T, d_in = c.shape
    N = A.shape[0]
    lanes = chunk_lanes(d_in)
    c, delta, A, B, C, D, h0 = _f32(c, delta, A, B, C, D, h0)
    pad = -T % SSM_ROWS  # rows with Delta = 0 leave the state as it was
    rows = lambda x: jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1))
    Tp = T + pad
    wide = pl.BlockSpec((Tp, lanes), lambda j: (0, j))
    state = pl.BlockSpec((N, lanes), lambda j: (0, j))
    y, h = pl.pallas_call(
        functools.partial(_chunk_kernel, groups=Tp // SSM_ROWS),
        grid=(d_in // lanes,),
        in_specs=[wide, wide,
                  pl.BlockSpec((Tp, N, 2), lambda j: (0, 0, 0)),
                  state, pl.BlockSpec((1, lanes), lambda j: (0, j)), state],
        out_specs=[wide, state],
        out_shape=[jax.ShapeDtypeStruct((Tp, d_in), F32),
                   jax.ShapeDtypeStruct((N, d_in), F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=_CHUNK_VMEM),
        interpret=interpret,
        name="tadnn_ssm_chunk",
    )(rows(c), rows(delta), rows(jnp.stack([B, C], axis=-1)), A, D[None],
      h0)
    return y[:T], h


def ssm_chunk(c, delta, A, B, C, D, h0):
    """A chunk of one sequence from the carried state ``h0``: ``(y [T, d_in]
    float32, h)``.  The kernel on a TPU where ``d_in`` tiles, else the
    recurrence."""
    if _on_tpu() and chunk_lanes(c.shape[-1]):
        return ssm_chunk_pallas(c, delta, A, B, C, D, h0)
    return ssm_recurrent(c, delta, A, B, C, D, h0)


# -- the step form ---------------------------------------------------------------


def ssm_step_xla(c, delta, A, B, C, D, pool, rows):
    """One token a slot in plain ``jax.numpy``: ``c``, ``delta`` [S, d_in],
    ``B``, ``C`` [S, N]; slot ``s`` reads and writes row ``rows[s]`` of
    ``pool`` [R, N, d_in].  Returns ``(y [S, d_in] float32, pool)``."""
    c, delta, A, B, C, D = _f32(c, delta, A, B, C, D)
    h = (jnp.exp(delta[:, None, :] * A) * pool[rows]
         + (delta * c)[:, None, :] * B[:, :, None])
    return jnp.sum(h * C[:, :, None], axis=1) + D * c, pool.at[rows].set(h)


def _step_kernel(rows_ref, c_ref, dt_ref, bc_ref, a_ref, d_ref, s_ref, y_ref,
                 out_ref):
    """One slot: its whole row of the pool in and out, once."""
    del rows_ref
    c, dt, bc = c_ref[0], dt_ref[0], bc_ref[0]
    h = jnp.exp(dt * a_ref[:]) * s_ref[0] + (dt * c) * bc[:, 0:1]
    out_ref[0] = h
    y_ref[0] = jnp.sum(h * bc[:, 1:2], axis=0, keepdims=True) + d_ref[:] * c


def ssm_step_pallas(c, delta, A, B, C, D, pool, rows, *,
                    interpret: bool = False):
    """The step form as the kernel ``tadnn_ssm_step``: grid (slots,); a
    slot's row of ``pool`` is read and written where it lies (the pool is
    aliased to the output, the row ids are a scalar prefetch)."""
    S, d_in = c.shape
    N = A.shape[0]
    c, delta, A, B, C, D = _f32(c, delta, A, B, C, D)
    row = pl.BlockSpec((1, 1, d_in), lambda s, r: (s, 0, 0))
    st = pl.BlockSpec((1, N, d_in), lambda s, r: (r[s], 0, 0))
    y, pool = pl.pallas_call(
        _step_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(S,),
            in_specs=[row, row,
                      pl.BlockSpec((1, N, 2), lambda s, r: (s, 0, 0)),
                      pl.BlockSpec((N, d_in), lambda s, r: (0, 0)),
                      pl.BlockSpec((1, d_in), lambda s, r: (0, 0)), st],
            out_specs=[row, st]),
        out_shape=[jax.ShapeDtypeStruct((S, 1, d_in), F32),
                   jax.ShapeDtypeStruct(pool.shape, F32)],
        input_output_aliases={6: 1},  # the pool, after the row ids
        interpret=interpret,
        name="tadnn_ssm_step",
    )(rows.astype(jnp.int32), c[:, None], delta[:, None],
      jnp.stack([B, C], axis=-1), A, D[None], pool)
    return y[:, 0], pool


def ssm_step(c, delta, A, B, C, D, pool, rows):
    """One decode token a slot against the pool of states, in place: ``(y
    [S, d_in] float32, pool)``, slot ``s`` on row ``rows[s]``.  Row 0 is the
    null row of the slots that do not decode: with ``delta = 0`` they leave
    it as it was.  (A form that walks the pool's rows in blocks of eight,
    nine grid steps where this has 64, was tried in the cell and bought
    nothing that could be told from the cell's own two levels: ``PERF.md``
    section 6, PR 46.)"""
    form = ssm_step_pallas if _on_tpu() else ssm_step_xla
    return form(c, delta, A, B, C, D, pool, rows)
