"""The gated delta rule of a ``linear_attention`` layer: a recurrent state
in place of keys and values.

A head keeps a matrix ``S`` [d_k, d_v] in float32.  A token with query
``q``, key ``k`` (both L2-normalised, d_k), value ``v`` (d_v), log-decay
``g <= 0`` and write strength ``beta`` (in (0, 1), or (0, 2) where the
model allows negative eigenvalues) does

    S_t = a_t S_{t-1} + beta_t k_t (v_t - a_t S_{t-1}^T k_t)^T ,  a_t = exp(g_t)
    o_t = S_t^T q_t

(Gated DeltaNet: Yang, Kautz, Hatamizadeh 2024).  Three forms of the same
arithmetic live here:

- :func:`gated_delta_recurrent`, the equations token by token under
  ``lax.scan``: what every other form is tested against;
- the CHUNK form for a prompt (training's forward pass, the engine's
  prefill chunk): sub-chunks of ``SUB_CHUNK`` tokens, inside one the
  tokens' writes are solved together (``(I + A) U = beta (V - G K S_0)``,
  ``A`` strictly lower triangular: the WY form of a product of
  Householder-like factors) and the state is carried from one sub-chunk to
  the next.  The solve is ``_unit_lower_inverse``, by halves, float32
  products at highest precision in every form; the kernels solve two
  sub-chunks' systems as one block-diagonal ``[128, 128]`` one, whose
  products fill the MXU's array and add only exact zeros (the same bits,
  5 products a sub-chunk for 12).  :func:`gated_delta_chunk` runs the
  Pallas kernel ``tadnn_gdn_chunk`` (grid: heads x groups of sub-chunks)
  on a TPU and the plain ``jax.numpy`` form
  (:func:`gated_delta_chunk_xla`) elsewhere; state in, state out;
- the STEP form for decode, one token a slot against a pool of states
  ``[rows, H, d_k, d_v]`` read and written in place through a vector of
  row ids (row 0 the null row of the slots that do not decode):
  :func:`gated_delta_step`, the kernel ``tadnn_gdn_step`` on a TPU and
  :func:`gated_delta_step_xla` elsewhere.

A decay may also be a VECTOR a head: ``g`` [T, H, d_k], one log-decay a key
channel, ``a_t = exp(g_t)`` in R^{d_k} (Kimi Delta Attention):

    S_t = (I - beta_t k_t k_t^T) Diag(a_t) S_{t-1} + beta_t k_t v_t^T

which with all of a head's channels equal is the rule above to the letter.
Every entry point takes either (by ``g``'s rank); the recurrence is the same
lines.  The chunk form is not: between two tokens of a sub-chunk the decay
no longer factors out of ``k_t . k_j``, so ``A[t, j] = sum_c kb_t[c] k_j[c]
exp(Gamma_t[c] - Gamma_j[c])`` (and ``P`` with ``q``) has to be formed with
no exponential of a positive number, and the chip gets kernels of its own,
``tadnn_kda_chunk`` and ``tadnn_kda_step`` (:func:`kda_chunk_pallas`,
:func:`kda_step_pallas`); the scalar rule's kernels stay as they are.
The step kernel of a decay a channel walks the slots that decode and no
other (a work list, :func:`live_slots`, is its grid) and takes its
operands as the projections leave them.

- On the chip ONE kernel a layer a chunk does all of it
  (``_kda_chunk_kernel``): it reads ``q, k, v, g, beta`` as the mixer's
  projections leave them (``[T, H d]``, a head a block of 128 lanes) and
  writes ``o`` so; nothing is prepared or relaid in XLA.  Inside, a
  sub-chunk's exponents are sums of its log-decays taken on the MXU (a 0/1
  matrix times ``g``, exact), and ``A`` and ``P`` are built BY HALVES, with
  the masks of ``_unit_lower_inverse``: at level ``s`` = 1, 2, .. 32 the
  rows of a block's upper half against the rows of its lower half THROUGH A
  REFERENCE ROW, the upper half's first, ``exp(Gamma_t - Gamma_r) .
  exp(Gamma_r - Gamma_j)``, both exponents <= 0: one exponential of a
  [64, d_k] tile and one product a level, no pairwise tensor.  Every
  level's product is float32 at highest precision, whatever dtype ``q``
  came in: no coarser than ``kda_products`` anywhere (its pairs inside a
  block are float32 sums; its blocks against earlier ones take bfloat16
  operands when serving), because the served tokens' regret, whose widest
  position has little room under its limit, is what settles the levels'
  precision (``PERF.md`` section 6, PR 42).  The solve is float32 at highest
  precision, as before.
- ``kda_products`` (the same sums in blocks of ``KDA_BLOCK``, pair by pair
  inside a block) with ``kda_chunk_xla`` is the CPU path and the oracle of
  the kernel's parity tests.

The platform picks between a kernel and its plain form, as for the paged
attention kernel; there is no switch.  State, decay, beta and every
accumulator are float32; the matmuls' operands are in the dtype ``q`` comes
in (bfloat16 when serving; float32 operands ask for float32 products too).

Shapes: one sequence, ``q, k`` [T, H, d_k], ``v`` [T, H, d_v], ``g, beta``
[T, H] float32 (``g`` [T, H, d_k] where the decay is a channel's),
``state`` [H, d_k, d_v] float32.  A batch folds into H.
A row with ``beta == 0`` and ``g == 0`` leaves the state as it was (a
padded chunk's tail, an inactive slot).
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

SUB_CHUNK = 64  # tokens solved together inside a chunk
CHUNK_GROUP = 8  # at most this many sub-chunks of a head a grid step
STEP_HEADS = 10  # at most this many heads of a slot's state a grid step
KDA_BLOCK = 16  # tokens whose channel-wise decays are formed pair by pair
F32 = jnp.float32
HI = jax.lax.Precision.HIGHEST


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _exact(dtype):
    """float32 operands ask for float32 products (one bfloat16 pass is the
    default on a TPU)."""
    return HI if dtype == F32 else None


def l2norm(x: jax.Array, eps: float = 1e-6) -> jax.Array:
    """``x / ||x||_2`` over the last axis, in float32."""
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + eps)


def causal_conv(full: jax.Array, w: jax.Array, n: int,
                bias: jax.Array | None = None) -> jax.Array:
    """A depthwise causal convolution of ``K`` taps (and a ``bias`` [D]
    where the mixer has one), then SiLU: ``full``
    [..., K - 1 + n, D] is the ``K - 1`` rows before the sequence (zeros at
    its start, else the tail the cache kept) and its ``n`` rows; ``w``
    [K, D], tap ``K - 1`` on the row itself.  Float32 sums; [..., n, D]."""
    K = w.shape[0]
    y = sum(w[i].astype(F32) * full[..., i:i + n, :].astype(F32)
            for i in range(K))
    return jax.nn.silu(y if bias is None else y + bias.astype(F32))


# -- token by token: the oracle ------------------------------------------------


def gated_delta_recurrent(q, k, v, g, beta, state):
    """The equations as written, a token a scan step, in float32 at highest
    precision; ``g`` [T, H], or [T, H, d_k] for a decay a channel.  Returns
    ``(o [T, H, d_v] float32, state)``."""
    def step(S, x):
        q, k, v, g, beta = x
        # the state decayed: by a number a head, or a row (key channel) each
        S = jnp.exp(g if g.ndim == 2 else g[:, None])[..., None] * S
        u = beta[:, None] * (v - jnp.einsum("hkv,hk->hv", S, k, precision=HI))
        S = S + k[:, :, None] * u[:, None, :]
        return S, jnp.einsum("hkv,hk->hv", S, q, precision=HI)

    state, o = jax.lax.scan(step, state.astype(F32), tuple(
        x.astype(F32) for x in (q, k, v, g, beta)))
    return o, state


# -- the chunk form ---------------------------------------------------------------


def _sub_chunk(T: int) -> int:
    return SUB_CHUNK if T >= SUB_CHUNK else -(-T // 8) * 8


def _split(x, sub: int):
    """``x`` [T, H, ...] padded to whole sub-chunks of ``sub`` tokens with
    rows of zeros (which leave a state alone): [H, NS, sub, ...]."""
    x = jnp.pad(x, ((0, -x.shape[0] % sub),) + ((0, 0),) * (x.ndim - 1))
    x = x.reshape(-1, sub, *x.shape[1:])
    return jnp.moveaxis(x, 2, 0)


def _pass_state(state, Wv, Wk, P, qg, kdT, gc, op, T: int):
    """The state through the sub-chunks, one after the other, every head at
    once: ``Wv``, ``Wk`` the solved factors, ``P`` the decayed q.k products,
    ``gc`` a sub-chunk's whole decay (anything that broadcasts against a
    head's [d_k, d_v] state).  ``(o [T, H, d_v] float32, state)``."""
    mm = functools.partial(jnp.matmul, precision=_exact(op),
                           preferred_element_type=F32)

    def body(S, x):  # one sub-chunk of every head
        Wv, Wk, P, qg, kdT, gc = x
        U = Wv - mm(Wk, S.astype(op))
        o = mm(qg, S.astype(op)) + mm(P, U.astype(op))
        return gc * S + mm(kdT, U.astype(op)), o

    per = lambda x: jnp.moveaxis(x, 1, 0)  # sub-chunks lead
    state, o = jax.lax.scan(body, state.astype(F32), tuple(
        per(x) for x in (Wv, Wk, P, qg, kdT, gc)))
    # [NS, H, sub, d_v] -> [T, H, d_v]
    o = jnp.moveaxis(o, 1, 2).reshape(-1, *o.shape[1:2], o.shape[-1])
    return o[:T], state


def _chunk_operands(q, k, v, g, beta):
    """What a sub-chunk's solve multiplies, every decay folded in here (no
    exponential of a positive number anywhere: each is of a difference
    ``gamma_t - gamma_j`` with ``j <= t``).  ``[H, NS, sub, .]`` each, the
    transposed keys ``[H, NS, d_k, sub]``; the sequence padded to whole
    sub-chunks with rows that leave the state alone."""
    T = q.shape[0]
    sub = _sub_chunk(T)
    op = q.dtype
    q, k, v, g, beta = (_split(x, sub).astype(F32)
                        for x in (q, k, v, g, beta))  # g, beta [H, NS, sub]
    gam = jnp.cumsum(g, -1)
    diff = gam[..., :, None] - gam[..., None, :]
    lower = jnp.tril(jnp.ones((sub, sub), bool))
    decay = jnp.exp(jnp.where(lower, diff, -jnp.inf))  # [H, NS, sub, sub]
    G = jnp.exp(gam)[..., None]
    to_end = jnp.exp(gam[..., -1:] - gam)[..., None]
    kb = beta[..., None] * k
    return dict(
        q=q.astype(op), qg=(G * q).astype(op), kb=kb.astype(op),
        kbg=(G * kb).astype(op), kT=jnp.swapaxes(k, -1, -2).astype(op),
        kdT=jnp.swapaxes(to_end * k, -1, -2).astype(op),
        bv=(beta[..., None] * v).astype(op), decay=decay,
        gc=jnp.exp(gam[..., -1])), T


def _unit_lower_inverse(A, dot, block=None):
    """``(I + A)^-1`` for strictly lower triangular ``A`` [.., n, n], by
    halves: with ``X`` the inverse of the diagonal blocks of size ``s``
    (zero elsewhere; the identity at ``s = 1``) and ``R`` the part of ``A``
    in the lower-left quarter of each diagonal block of size ``2 s``, ``X -
    X R X`` is the inverse of the diagonal blocks of size ``2 s``.  Every
    factor is the inverse of a piece of the true system, whose entries the
    recurrence bounds: no power of ``A`` is ever formed (``A^32`` cancels
    catastrophically in float32 once neighbouring keys are alike).  The
    first round is no product: at ``s = 1`` ``X`` is the identity, so ``X -
    X R X`` is ``I - R`` (to the bit: a product with the identity returns
    its other factor).  Then ``ceil(log2 n) - 1`` rounds of two products;
    shifts and masks only, so that the kernel runs the same lines.  Where
    ``A`` is zero outside diagonal blocks of ``block`` rows (a power of two:
    several systems side by side, ``_solve``), the rounds stop at
    ``block``: what would join two blocks is zero, and every product adds
    exact zeros to the terms a block alone would sum."""
    n = A.shape[-1]
    r = jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
    c = jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)

    def quarter(bit):
        s = 1 << bit
        return jnp.where(((r >> (bit + 1)) == (c >> (bit + 1))) & (
            (r & s) != 0) & ((c & s) == 0), A, 0.0)

    X = (r == c).astype(F32) - quarter(0)
    for bit in range(1, math.ceil(math.log2(block or n))):
        X = X - dot(dot(X, quarter(bit)), X)
    return X


def _solve(systems):
    """The chunk kernels' solve: ``(I + A)^-1`` of one sub-chunk's ``A``
    [sub, sub], or of two as ONE system, on the diagonal of a ``[2 sub, 2
    sub]`` matrix of zeros: its inverse by halves holds each one's (the
    same bits), from products that fill the MXU's array where a ``[64, 64]``
    one uses a quarter of it, and its rows times two sub-chunks' stacked
    rows are the two products behind the solve.  Float32 products at
    highest precision."""
    A = systems[0]
    if len(systems) == 2:
        zero = jnp.zeros_like(A)
        A = jnp.concatenate([jnp.concatenate([A, zero], 1),
                             jnp.concatenate([zero, systems[1]], 1)], 0)
    return _unit_lower_inverse(A, functools.partial(
        jnp.dot, precision=HI, preferred_element_type=F32),
        block=systems[0].shape[-1])


def gated_delta_chunk_xla(q, k, v, g, beta, state):
    """The chunk form in plain ``jax.numpy``: the CPU path, and the oracle
    of the kernel's parity tests.  Returns ``(o [T, H, d_v] float32,
    state)``."""
    ops, T = _chunk_operands(q, k, v, g, beta)
    op, exact = q.dtype, _exact(q.dtype)
    mm = functools.partial(jnp.matmul, precision=exact,
                           preferred_element_type=F32)
    sub = ops["decay"].shape[-1]
    lower = jnp.tril(jnp.ones((sub, sub), bool))
    strict = jnp.tril(lower, -1)
    A = jnp.where(strict, ops["decay"] * mm(ops["kb"], ops["kT"]), 0.0)
    Tm = _unit_lower_inverse(
        A, functools.partial(jnp.matmul, precision=HI)).astype(op)
    Wv, Wk = mm(Tm, ops["bv"]), mm(Tm, ops["kbg"]).astype(op)
    P = jnp.where(lower, ops["decay"] * mm(ops["q"], ops["kT"]),
                  0.0).astype(op)
    return _pass_state(state, Wv, Wk, P, ops["qg"], ops["kdT"],
                       ops["gc"][..., None, None], op, T)


def _chunk_kernel(q_ref, qg_ref, kb_ref, kbg_ref, kT_ref, kdT_ref, bv_ref,
                  d_ref, gc_ref, s0_ref, o_ref, s_ref, s_scr, *, exact):
    """One (head, group of sub-chunks) grid step; the head's state rides in
    VMEM scratch from its first group to its last.  What a sub-chunk does
    without the state (its key-key matrix, its solve, the products with the
    solved factor) is written out for every sub-chunk of the group before
    the state passes through them, so that those chains, which do not
    depend on each other, can be scheduled side by side; the solves two
    sub-chunks at a time (``_solve``), a group's odd last one alone."""
    n = pl.program_id(1)

    @pl.when(n == 0)
    def _load():
        s_scr[:] = s0_ref[0]

    op = q_ref.dtype
    dot = functools.partial(jnp.dot, precision=exact,
                            preferred_element_type=F32)
    sub = d_ref.shape[-1]
    r = jax.lax.broadcasted_iota(jnp.int32, (sub, sub), 0)
    c = jax.lax.broadcasted_iota(jnp.int32, (sub, sub), 1)
    systems = []
    for i in range(d_ref.shape[1]):  # static: the group's sub-chunks
        kT, decay = kT_ref[0, i], d_ref[0, i]
        systems.append((
            jnp.where(r > c, decay * dot(kb_ref[0, i], kT), 0.0),
            jnp.where(r >= c, decay * dot(q_ref[0, i], kT), 0.0).astype(op)))
    solved = []
    for i in range(0, len(systems), 2):  # two at a time; an odd last alone
        As, Pqk = zip(*systems[i:i + 2])
        Tm = _solve(As).astype(op)  # block-diagonal against stacked rows
        Wv, Wk = (dot(Tm, jnp.concatenate(
            [ref[0, i + j] for j in range(len(As))], 0))
            for ref in (bv_ref, kbg_ref))
        Wk = Wk.astype(op)
        solved += [(Wv[j * sub:(j + 1) * sub], Wk[j * sub:(j + 1) * sub], P)
                   for j, P in enumerate(Pqk)]
    S = s_scr[:]
    for i, (Wv, Wk, Pqk) in enumerate(solved):
        Sop = S.astype(op)
        U = (Wv - dot(Wk, Sop)).astype(op)
        o_ref[0, i] = dot(qg_ref[0, i], Sop) + dot(Pqk, U)
        S = gc_ref[0, i] * S + dot(kdT_ref[0, i], U)
    s_scr[:] = S

    @pl.when(n == pl.num_programs(1) - 1)
    def _store():
        s_ref[0] = S


def gated_delta_chunk_pallas(q, k, v, g, beta, state, *,
                             interpret: bool = False):
    """The chunk form as the kernel ``tadnn_gdn_chunk``: grid (heads,
    groups of up to ``CHUNK_GROUP`` sub-chunks), the groups of a head in
    order."""
    ops, T = _chunk_operands(q, k, v, g, beta)
    H, NS, sub, dk = ops["q"].shape
    dv = ops["bv"].shape[-1]
    gc = jnp.broadcast_to(ops["gc"][..., None, None], (H, NS, 1, dv))
    grp = max(n for n in range(1, CHUNK_GROUP + 1) if NS % n == 0)

    def blk(*tail):
        return pl.BlockSpec((1, grp, *tail), lambda h, n: (h, n, 0, 0))

    whole = pl.BlockSpec((1, dk, dv), lambda h, n: (h, 0, 0))
    o, state = pl.pallas_call(
        functools.partial(_chunk_kernel, exact=_exact(q.dtype)),
        grid=(H, NS // grp),
        in_specs=[blk(sub, dk)] * 4 + [blk(dk, sub)] * 2 + [
            blk(sub, dv), blk(sub, sub), blk(1, dv), whole],
        out_specs=[blk(sub, dv), whole],
        out_shape=[jax.ShapeDtypeStruct((H, NS, sub, dv), F32),
                   jax.ShapeDtypeStruct((H, dk, dv), F32)],
        scratch_shapes=[pltpu.VMEM((dk, dv), F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="tadnn_gdn_chunk",
    )(ops["q"], ops["qg"], ops["kb"], ops["kbg"], ops["kT"], ops["kdT"],
      ops["bv"], ops["decay"], gc, state.astype(F32))
    return jnp.moveaxis(o, 0, 2).reshape(NS * sub, H, dv)[:T], state


def gated_delta_chunk(q, k, v, g, beta, state):
    """A sequence (or a prefill chunk of one) through the gated delta rule
    from ``state``: ``(o [T, H, d_v] float32, the state after it)``; the
    decay a head's (``g`` [T, H]) or a channel's (``g`` [T, H, d_k])."""
    if g.ndim == 3:
        form = kda_chunk_pallas if _on_tpu() else kda_chunk_xla
    else:
        form = gated_delta_chunk_pallas if _on_tpu() else gated_delta_chunk_xla
    return form(q, k, v, g, beta, state)


# -- the chunk form, a decay a channel -------------------------------------------


def kda_products(q, kb, k, gam, op):
    """What a vector decay puts in the place of ``decay * (kb kT)`` and
    ``decay * (q kT)``: for a sub-chunk's rows ``q, kb, k`` [.., n, d_k] and
    their cumulative log-decays ``gam`` [.., n, d_k], all float32,

        A[t, j] = sum_c kb_t[c] k_j[c] exp(gam_t[c] - gam_j[c])   (j < t)
        P[t, j] = sum_c q_t[c]  k_j[c] exp(gam_t[c] - gam_j[c])   (j <= t)

    and zero above, [.., n, n] float32, with no exponential of a positive
    number.  The rows go in blocks of ``KDA_BLOCK``.  A block's rows against
    every EARLIER row pass through the block's first row ``r``: ``(x_t
    exp(gam_t - gam_r)) . (k_j exp(gam_r - gam_j))``, both exponents <= 0,
    one matmul with operands in ``op`` (the dtype q came in).  Inside a
    block ``exp(gam_t - gam_j)`` is formed pair by pair ([blk, blk, d_k]:
    elementwise and a sum, float32)."""
    n = k.shape[-2]
    blk = KDA_BLOCK if n % KDA_BLOCK == 0 else 8
    mm = functools.partial(jnp.matmul, precision=_exact(op),
                           preferred_element_type=F32)
    lower = jnp.tril(jnp.ones((blk, blk), bool))[..., None]
    rows_a, rows_p = [], []
    for lo in range(0, n, blk):
        sl = slice(lo, lo + blk)
        g_blk = gam[..., sl, :]
        pair = jnp.exp(jnp.where(
            lower, g_blk[..., :, None, :] - g_blk[..., None, :, :], -jnp.inf))
        kp = k[..., None, sl, :] * pair  # [.., t, j, c]
        parts = [jnp.sum(x[..., sl, None, :] * kp, -1) for x in (kb, q)]
        if lo:  # the earlier rows, through the block's first
            ref = gam[..., lo:lo + 1, :]
            back = jnp.swapaxes(k[..., :lo, :] * jnp.exp(
                ref - gam[..., :lo, :]), -1, -2).astype(op)
            into = jnp.exp(g_blk - ref)
            parts = [jnp.concatenate(
                [mm((x[..., sl, :] * into).astype(op), back), inner], -1)
                for x, inner in zip((kb, q), parts)]
        wide = [(0, 0)] * (k.ndim - 1) + [(0, n - lo - blk)]
        rows_a.append(jnp.pad(parts[0], wide))
        rows_p.append(jnp.pad(parts[1], wide))
    r = jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
    c = jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
    return (jnp.where(r > c, jnp.concatenate(rows_a, -2), 0.0),
            jnp.concatenate(rows_p, -2))


def _kda_operands(q, k, v, g, beta):
    """``_chunk_operands`` for ``g`` [T, H, d_k]: ``[H, NS, sub, .]`` each,
    the keys that write the state transposed ``[H, NS, d_k, sub]``, the
    sub-chunk's decay a column ``[H, NS, d_k, 1]``; ``A`` and ``P`` are
    ``kda_products``'s."""
    T = q.shape[0]
    op = q.dtype
    q, k, v, g, beta = (_split(x, _sub_chunk(T)).astype(F32)
                        for x in (q, k, v, g, beta))
    gam = jnp.cumsum(g, -2)
    G = jnp.exp(gam)
    kb = beta[..., None] * k
    A, P = kda_products(q, kb, k, gam, op)
    to_end = jnp.exp(gam[..., -1:, :] - gam)
    return dict(
        A=A, P=P.astype(op), bv=(beta[..., None] * v).astype(op),
        kbg=(G * kb).astype(op), qg=(G * q).astype(op),
        kdT=jnp.swapaxes(to_end * k, -1, -2).astype(op),
        gc=G[..., -1, :, None]), T


def kda_chunk_xla(q, k, v, g, beta, state):
    """The chunk form for a decay a channel in plain ``jax.numpy``: the CPU
    path, and the oracle of ``tadnn_kda_chunk``'s parity tests.  The solve
    and the state's passage through the sub-chunks are the scalar rule's.
    Returns ``(o [T, H, d_v] float32, state)``."""
    ops, T = _kda_operands(q, k, v, g, beta)
    op = q.dtype
    mm = functools.partial(jnp.matmul, precision=_exact(op),
                           preferred_element_type=F32)
    Tm = _unit_lower_inverse(
        ops["A"], functools.partial(jnp.matmul, precision=HI)).astype(op)
    Wv, Wk = mm(Tm, ops["bv"]), mm(Tm, ops["kbg"]).astype(op)
    return _pass_state(state, Wv, Wk, ops["P"], ops["qg"], ops["kdT"],
                       ops["gc"], op, T)


def _pieces(x):
    """Float32 ``x`` as three bfloat16 arrays, each the rounding of what the
    ones before left: their sum is ``x`` to the last bit."""
    out = []
    for _ in range(3):
        out.append(x.astype(jnp.bfloat16))
        x = x - out[-1].astype(F32)
    return out


def _kda_chunk_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, s0_ref, o_ref, s_ref,
                      s_scr, *, sub: int, exact):
    """One (head, group of sub-chunks) grid step, as ``_chunk_kernel``, from
    the rows as the mixer made them: ``q, k, g`` [rows, d_k] and ``v``
    [rows, d_v] of this head, ``beta`` [rows, H] of every head.  Every decay
    is formed here.  A sub-chunk's exponents are SUMS of its log-decays, all
    of them one product of a 0/1 matrix with ``g`` (exact: ``g`` goes in as
    its three bfloat16 pieces, the sums are float32): the running sum
    ``gam``, and a block a level of the halving (below), so no exponent of
    ``A`` or ``P`` is a difference of two large numbers and none is
    positive.  (``last - gam``, the decay from a row to the sub-chunk's
    end, IS such a difference, as ``_kda_operands`` has it: at most a
    rounding above 0.)  Then the sub-chunks' solves, side by side and two
    systems as one (``_solve``), then the state through them, each of its
    rows decayed by its own factor.

    ``A[t, j] = sum_c kb_t[c] k_j[c] exp(gam_t[c] - gam_j[c])`` (and ``P``
    with ``q``) BY HALVES, the masks of ``_unit_lower_inverse``: at level
    ``s`` (1, 2, .. ``sub / 2``) the rows ``t`` of the upper half of a block
    of ``2 s`` (``t & s``) against the rows ``j`` of its lower half, through
    the upper half's first row ``r``: ``exp(gam_t - gam_r) . exp(gam_r -
    gam_j)``, both exponents sums of log-decays.  A row is a reader or a key
    at a level, never both, so a level is ONE exponential of a [sub, d_k]
    tile, three scalings and one product ``[kb e; q e] (k e)^T`` masked to
    the level's quarters, which are disjoint and cover every ``j < t`` once;
    ``P``'s diagonal is a row sum.  The levels' products and the solve's
    are float32 at highest precision whatever ``q``'s dtype (with bfloat16
    operands at the levels one served position's regret passed its limit);
    the products with the state take operands in the dtype ``q`` came in."""
    h, n = pl.program_id(0), pl.program_id(1)

    @pl.when(n == 0)
    def _load():
        s_scr[:] = s0_ref[0]

    op = q_ref.dtype
    dot = functools.partial(jnp.dot, precision=exact,
                            preferred_element_type=F32)

    def over(x, y, axis: int, precision=exact):  # the same axis of both
        return jax.lax.dot_general(x, y, (((axis,), (axis,)), ((), ())),
                                   precision=precision,
                                   preferred_element_type=F32)

    r = jax.lax.broadcasted_iota(jnp.int32, (sub, sub), 0)
    c = jax.lax.broadcasted_iota(jnp.int32, (sub, sub), 1)
    bits = max(0, math.ceil(math.log2(sub)))
    sums, quarters = [c <= r], []  # which log-decays an exponent sums
    for bit in range(bits):
        s = 1 << bit
        ref = ((r >> (bit + 1)) << (bit + 1)) + s
        upper = (r & s) != 0
        sums.append((c > jnp.where(upper, ref, r))
                    & (c <= jnp.where(upper, r, ref)))
        quarters.append(upper & ((c & s) == 0)
                        & ((r >> (bit + 1)) == (c >> (bit + 1))))
    # [(1 + bits) sub, sub] of 0 and 1
    sums = jnp.concatenate(sums, 0).astype(jnp.bfloat16)
    dk = k_ref.shape[-1]
    eye = (jax.lax.broadcasted_iota(jnp.int32, (dk, dk), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (dk, dk), 1))
    heads = jax.lax.broadcasted_iota(jnp.int32, b_ref.shape, 1)
    beta_h = jnp.sum(jnp.where(heads == h, b_ref[:], 0.0), 1, keepdims=True)
    groups = [slice(i * sub, (i + 1) * sub)
              for i in range(q_ref.shape[0] // sub)]
    systems = []
    for rows in groups:  # static: the group's sub-chunks
        q, k = q_ref[rows].astype(F32), k_ref[rows].astype(F32)
        beta = beta_h[rows]
        kb = beta * k
        E = sum(jnp.dot(sums, piece, preferred_element_type=F32)
                for piece in _pieces(g_ref[rows]))  # every entry <= 0
        A = jnp.zeros((sub, sub), F32)
        P = jnp.where(r == c, jnp.sum(q * k, 1, keepdims=True), 0.0)
        for bit in range(bits):
            e = jnp.exp(E[(1 + bit) * sub:(2 + bit) * sub])
            X = over(jnp.concatenate([kb * e, q * e], 0), k * e, 1, HI)
            A = A + jnp.where(quarters[bit], X[:sub], 0.0)
            P = P + jnp.where(quarters[bit], X[sub:], 0.0)
        gam = E[:sub]
        G, last = jnp.exp(gam), gam[sub - 1:]
        gc = jnp.sum(jnp.where(eye, jnp.exp(last), 0.0), 1, keepdims=True)
        systems.append((
            A, (beta * v_ref[rows].astype(F32)).astype(op),
            (G * kb).astype(op),
            (P.astype(op), (G * q).astype(op),
             (jnp.exp(last - gam) * k).astype(op), gc)))
    solved = []
    for i in range(0, len(systems), 2):  # two at a time; an odd last alone
        As, bv, kbg, rest = zip(*systems[i:i + 2])
        Tm = _solve(As).astype(op)  # block-diagonal against stacked rows
        Wv = dot(Tm, jnp.concatenate(bv, 0))
        Wk = dot(Tm, jnp.concatenate(kbg, 0)).astype(op)
        solved += [(Wv[j * sub:(j + 1) * sub], Wk[j * sub:(j + 1) * sub], *x)
                   for j, x in enumerate(rest)]
    S = s_scr[:]
    for rows, (Wv, Wk, Pqk, qg, kd, gc) in zip(groups, solved):
        Sop = S.astype(op)
        U = (Wv - dot(Wk, Sop)).astype(op)
        o_ref[rows] = dot(qg, Sop) + dot(Pqk, U)
        S = gc * S + over(kd, U, 0)
    s_scr[:] = S

    @pl.when(n == pl.num_programs(1) - 1)
    def _store():
        s_ref[0] = S


def kda_chunk_pallas(q, k, v, g, beta, state, *, interpret: bool = False):
    """The chunk form for a decay a channel as the kernel
    ``tadnn_kda_chunk``: grid (heads, groups of up to ``CHUNK_GROUP``
    sub-chunks), the groups of a head in order, its state in VMEM between
    them.  The kernel reads ``q, k, v, g`` as they come, ``[T, H d]`` with a
    head's ``d`` channels a block of lanes, and writes ``o`` so; nothing is
    prepared before it but the padding of ``T`` to whole sub-chunks with
    rows that leave the state alone.  Widths that are no whole tile of 128
    lanes are padded with channels of zeros, which change nothing: a path
    for the CPU tests' small widths only, every served model's are whole
    tiles."""
    T, H, dk = k.shape
    dv = v.shape[-1]
    if dk % 128 or dv % 128:
        wide = lambda x, *dims: jnp.pad(x, [(0, 0)] * (x.ndim - len(dims)) + [
            (0, -d % 128) for d in dims])
        o, new = kda_chunk_pallas(
            wide(q, dk), wide(k, dk), wide(v, dv), wide(g, dk), beta,
            wide(state, dk, dv), interpret=interpret)
        return o[..., :dv], new[:, :dk, :dv]
    sub = _sub_chunk(T)
    rows = lambda x: jnp.pad(x.reshape(T, -1), ((0, -T % sub), (0, 0)))
    NS = -(-T // sub)
    grp = max(n for n in range(1, CHUNK_GROUP + 1) if NS % n == 0)

    def tile(d):  # a group's rows of head h
        return pl.BlockSpec((grp * sub, d), lambda h, n: (n, h))

    whole = pl.BlockSpec((1, dk, dv), lambda h, n: (h, 0, 0))
    o, state = pl.pallas_call(
        functools.partial(_kda_chunk_kernel, sub=sub, exact=_exact(q.dtype)),
        grid=(H, NS // grp),
        in_specs=[tile(dk), tile(dk), tile(dv), tile(dk),
                  pl.BlockSpec((grp * sub, H), lambda h, n: (n, 0)), whole],
        out_specs=[tile(dv), whole],
        out_shape=[jax.ShapeDtypeStruct((NS * sub, H * dv), F32),
                   jax.ShapeDtypeStruct((H, dk, dv), F32)],
        scratch_shapes=[pltpu.VMEM((dk, dv), F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="tadnn_kda_chunk",
    )(rows(q), rows(k), rows(v), rows(g.astype(F32)), rows(beta.astype(F32)),
      state.astype(F32))
    return o[:T].reshape(T, H, dv), state


# -- the step form ---------------------------------------------------------------


class LiveSlots(NamedTuple):
    """A decode step's work list for a step kernel that walks the slots
    that decode and no other: ``order`` [S] the live slots first, in slot
    order, then the rest; ``n_live`` how many; ``live`` [S] which."""

    order: jax.Array
    n_live: jax.Array
    live: jax.Array


def live_slots(live: jax.Array) -> LiveSlots:
    """The work list of a step in which the slots ``live`` [S] decode."""
    live = live.astype(bool)
    return LiveSlots(jnp.argsort(~live, stable=True).astype(jnp.int32),
                     jnp.sum(live, dtype=jnp.int32), live)


def gated_delta_step_xla(q, k, v, g, beta, pool, rows):
    """One token a slot in plain ``jax.numpy``: ``q, k`` [S, H, d_k], ``v``
    [S, H, d_v], ``g, beta`` [S, H]; slot ``s`` reads and writes row
    ``rows[s]`` of ``pool`` [R, H, d_k, d_v].  Returns ``(o [S, H, d_v]
    float32, pool)``."""
    q, k, v, g, beta = (x.astype(F32) for x in (q, k, v, g, beta))
    S = pool[rows]
    a = jnp.exp(g)[..., None]
    Sk = jnp.einsum("shkv,shk->shv", S, k, precision=HI)
    u = beta[..., None] * (v - a * Sk)
    S = a[..., None] * S + k[..., None] * u[..., None, :]
    o = jnp.einsum("shkv,shk->shv", S, q, precision=HI)
    return o, pool.at[rows].set(S)


def _step_kernel(rows_ref, kT_ref, qT_ref, row_ref, s_ref, o_ref, out_ref,
                 *, heads: int):
    """A group of ``heads`` heads of one slot, on the VPU: a head's key and
    query as columns [d_k, 1], its value, decay, beta and k.q as rows
    [1, d_v] (rows ``c * heads + i`` of the packed operand)."""
    del rows_ref
    for i in range(heads):  # static
        S = s_ref[0, i]
        kc, qc = kT_ref[0, 0][:, i:i + 1], qT_ref[0, 0][:, i:i + 1]
        v, a, b, kq = (row_ref[0, 0, c * heads + i:c * heads + i + 1]
                       for c in range(4))
        Sk = jnp.sum(S * kc, axis=0, keepdims=True)
        Sq = jnp.sum(S * qc, axis=0, keepdims=True)
        u = b * (v - a * Sk)
        out_ref[0, i] = a * S + kc * u
        o_ref[0, 0, i:i + 1] = a * Sq + kq * u


def _head_group(H: int) -> int:
    return max(n for n in range(1, STEP_HEADS + 1) if H % n == 0)


def gated_delta_step_pallas(q, k, v, g, beta, pool, rows, *,
                            interpret: bool = False):
    """The step form as the kernel ``tadnn_gdn_step``: grid (slots, groups
    of heads); a slot's rows of ``pool`` are read and written where they
    lie (the pool is aliased to the output, the row ids are a scalar
    prefetch)."""
    S, H, dk = k.shape
    dv = v.shape[-1]
    hb = _head_group(H)
    G = H // hb
    q, k, v, g, beta = (x.astype(F32) for x in (q, k, v, g, beta))

    def cols(x):  # [S, H, dk] -> [S, G, dk, hb]
        return jnp.swapaxes(x.reshape(S, G, hb, dk), -1, -2)

    wide = lambda x: jnp.broadcast_to(x[..., None], (S, H, dv))
    packed = jnp.stack([v, wide(jnp.exp(g)), wide(beta),
                        wide(jnp.sum(k * q, -1))], axis=2)  # [S, H, 4, dv]
    packed = jnp.swapaxes(packed.reshape(S, G, hb, 4, dv), 2, 3).reshape(
        S, G, 4 * hb, dv)
    col = pl.BlockSpec((1, 1, dk, hb), lambda s, j, r: (s, j, 0, 0))
    row4 = pl.BlockSpec((1, 1, 4 * hb, dv), lambda s, j, r: (s, j, 0, 0))
    st = pl.BlockSpec((1, hb, dk, dv), lambda s, j, r: (r[s], j, 0, 0))
    o, pool = pl.pallas_call(
        functools.partial(_step_kernel, heads=hb),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(S, G),
            in_specs=[col, col, row4, st],
            out_specs=[pl.BlockSpec((1, 1, hb, dv),
                                    lambda s, j, r: (s, j, 0, 0)), st]),
        out_shape=[jax.ShapeDtypeStruct((S, G, hb, dv), F32),
                   jax.ShapeDtypeStruct(pool.shape, F32)],
        input_output_aliases={4: 1},  # the pool, after the row ids
        interpret=interpret,
        name="tadnn_gdn_step",
    )(rows.astype(jnp.int32), cols(k), cols(q), packed, pool)
    return o.reshape(S, H, dv), pool


def gated_delta_step(q, k, v, g, beta, pool, rows, *,
                     work: LiveSlots | None = None):
    """One decode token a slot against the pool of states, in place:
    ``(o [S, H, d_v] float32, pool)``; the decay a head's (``g`` [S, H]) or
    a channel's (``g`` [S, H, d_k]).  Row 0 is the null row of the
    slots that do not decode: with ``beta = 0`` and ``g = 0`` they leave it
    as it was.  ``work`` is the step's list of the slots that decode
    (``live_slots``, built once a step): the kernel of a decay a channel
    walks those alone, the other forms every slot and take no list.  (On a
    v5e the compiler stages a pool of the scalar rule's size through on-chip
    memory round the call, in copies of its own: the kernel's time in a
    trace does not hold its HBM traffic.)"""
    if g.ndim == 3 and _on_tpu():
        return kda_step_pallas(q, k, v, g, beta, pool, rows, work=work)
    if g.ndim == 3:
        form = kda_step_xla
    else:
        form = gated_delta_step_pallas if _on_tpu() else gated_delta_step_xla
    return form(q, k, v, g, beta, pool, rows)


def step_rows_walked(live: int, slots: int) -> int:
    """The rows of its pool a call of :func:`gated_delta_step` with a decay
    a channel walks when ``live`` of ``slots`` slots decode: the kernel the
    live ones (one item where there is none), the plain form every slot's
    (its gather and scatter).  For the engine's ``state_rows_walked``."""
    return max(live, 1) if _on_tpu() else slots


# -- the step form, a decay a channel ---------------------------------------------


def kda_step_xla(q, k, v, g, beta, pool, rows):
    """``gated_delta_step_xla`` for ``g`` [S, H, d_k]: a slot's state rows
    decayed each by its own factor, then the same write and read."""
    q, k, v, g, beta = (x.astype(F32) for x in (q, k, v, g, beta))
    S = jnp.exp(g)[..., None] * pool[rows]
    u = beta[..., None] * (v - jnp.einsum("shkv,shk->shv", S, k,
                                          precision=HI))
    S = S + k[..., None] * u[..., None, :]
    o = jnp.einsum("shkv,shk->shv", S, q, precision=HI)
    return o, pool.at[rows].set(S)


def _kda_step_kernel(order_ref, rows_ref, beta_ref, kq_ref, k_ref, q_ref, a_ref,
                     v_ref, s_ref, o_ref, out_ref, *, heads: int, H: int):
    """A group of ``heads`` heads of one live slot, on the VPU, as
    ``_step_kernel``: the group's keys, queries and decays arrive as they
    lie, a tile [heads, d_k] each, and are turned here, the three stacked
    and transposed as one, so that a head's is a column [d_k, 1]; its value
    a row [1, d_v]; its beta and k.q two numbers of scalar memory."""
    del rows_ref
    at = order_ref[pl.program_id(0)] * H + pl.program_id(1) * heads
    cols = jnp.concatenate([k_ref[0], q_ref[0], a_ref[0]]).T  # [d_k, 3 heads]
    for i in range(heads):  # static
        kc, qc, ac = (cols[:, c * heads + i:c * heads + i + 1]
                      for c in range(3))
        S = ac * s_ref[0, i]  # every row by its channel's decay
        u = beta_ref[at + i] * (
            v_ref[0, i:i + 1] - jnp.sum(S * kc, axis=0, keepdims=True))
        out_ref[0, i] = S + kc * u
        o_ref[0, i:i + 1] = (jnp.sum(S * qc, axis=0, keepdims=True)
                             + kq_ref[at + i] * u)


def kda_step_pallas(q, k, v, g, beta, pool, rows, *,
                    work: LiveSlots | None = None, interpret: bool = False):
    """The step form for a decay a channel as the kernel ``tadnn_kda_step``:
    grid (LIVE slots, groups of heads), item ``t`` the slot ``work.order[t]``
    (``work`` None: the slots off the null row).  The grid's first axis is
    the traced ``n_live``: a slot that does not decode costs no grid step
    and its row of ``pool``, the null row, is neither read nor written; a
    call with no live slot runs item 0 all the same (a grid of no steps is
    not asked of the chip), a dead slot whose ``beta = 0`` and ``g = 0``
    leave the null row as it was.  A live slot's rows of ``pool`` are read
    and written where they lie (the pool is aliased to the output, the
    order and the row ids are scalar prefetches).  ``q``, ``k``, ``exp(g)``
    and ``v`` go in as the projections leave them, blocks [1, heads, d] of
    [S, H, d] (8 heads at the served widths, one float32 tile; a group
    that is neither whole tiles nor all of H is a block for the interpreter
    alone), ``beta`` and ``k.q`` as [S H] numbers of scalar memory, and
    ``o`` comes out [S, H, d_v]: nothing is relaid or broadcast before the
    call.  The
    kernel leaves ``o`` of a slot that does not decode unwritten; the
    select behind it makes those rows zero."""
    S, H, dk = k.shape
    dv = v.shape[-1]
    hb = _head_group(H)
    q, k, v, g, beta = (x.astype(F32) for x in (q, k, v, g, beta))
    if work is None:
        work = live_slots(rows > 0)
    item = lambda t, j, order, *_: (order[t], j, 0)  # noqa: E731
    st = pl.BlockSpec((1, hb, dk, dv),
                      lambda t, j, order, rows, *_: (rows[order[t]], j, 0, 0))
    key, val = pl.BlockSpec((1, hb, dk), item), pl.BlockSpec((1, hb, dv), item)
    o, pool = pl.pallas_call(
        functools.partial(_kda_step_kernel, heads=hb, H=H),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            # the live slots alone, a traced number (one where there is
            # none); the pipeline reads the indices of the step after the
            # last, so the order is one longer than the grid
            grid=(jnp.clip(work.n_live, 1, S), H // hb),
            in_specs=[key, key, key, val, st], out_specs=[val, st]),
        out_shape=[jax.ShapeDtypeStruct((S, H, dv), F32),
                   jax.ShapeDtypeStruct(pool.shape, F32)],
        input_output_aliases={8: 1},  # the pool, after the prefetches
        interpret=interpret,
        name="tadnn_kda_step",
    )(jnp.pad(work.order, (0, 1), mode="edge"), rows.astype(jnp.int32),
      beta.reshape(-1), jnp.sum(k * q, -1).reshape(-1), k, q, jnp.exp(g), v,
      pool)
    return jnp.where(work.live[:, None, None], o, 0.0), pool
