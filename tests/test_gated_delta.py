"""The gated delta rule (``ops/gated_delta.py``): the chunk form and the step
form, each as plain ``jax.numpy`` and as its Pallas kernel in the
interpreter, against the token-by-token recurrence; with a decay a head
(``decay="head"``: ``tadnn_gdn_chunk``, ``tadnn_gdn_step``) and a decay a key
channel (``"channel"``: ``tadnn_kda_chunk``, ``tadnn_kda_step``).  Float32
throughout, so the tolerance is rounding alone: 1e-5 of values of order
one."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

import chip_smoke
from torch_automatic_distributed_neural_network_tpu.ops import gated_delta as gd

H, DK, DV = 3, 16, 24
TOL = dict(rtol=1e-5, atol=1e-5)


DECAYS = ["head", "channel"]


def inputs(T: int, seed: int = 0, *, neg_eigval: bool = True,
           heads: int = H, dk: int = DK, dv: int = DV, decay: str = "head"):
    """Queries, keys and values as a layer makes them, and decays from the
    family's initialisation (A uniform in (0, 16), dt log-uniform in
    (0.001, 0.1)): most heads forget slowly, so a lost carry shows.  With
    ``decay="channel"`` a step ``dt`` a key channel: ``g`` [T, heads, dk]."""
    ks = jax.random.split(jax.random.key(seed), 7)
    q = gd.l2norm(jax.random.normal(ks[0], (T, heads, dk))) * dk ** -0.5
    k = gd.l2norm(jax.random.normal(ks[1], (T, heads, dk)))
    v = jax.random.normal(ks[2], (T, heads, dv))
    beta = jax.nn.sigmoid(jax.random.normal(ks[3], (T, heads)))
    if neg_eigval:
        beta = 2.0 * beta
    wide = (dk,) if decay == "channel" else ()
    A = jax.random.uniform(ks[4], (heads,) + (1,) * len(wide), minval=1e-3,
                           maxval=16.0)
    dt = jnp.exp(jax.random.uniform(ks[5], (T, heads) + wide,
                                    minval=np.log(1e-3), maxval=np.log(0.1)))
    state = jax.random.normal(ks[6], (heads, dk, dv))
    return q, k, v, -A * dt, beta, state


def strong(args):
    """``inputs``' operands with every write strength in (1, 2): the half of
    ``beta = 2 sigmoid(.)`` where ``I - beta k k^T`` turns a key's direction
    round, which a model with ``linear_neg_eigval`` serves and a coarser
    solve would meet first."""
    q, k, v, g, beta, state = args
    return q, k, v, g, 1.0 + beta / 2.0, state


def _by_decay(head, channel):
    """The form of the rule that ``g``'s rank asks for."""
    return lambda *a, **kw: (channel if a[3].ndim == 3 else head)(*a, **kw)


CHUNK_FORMS = {
    "xla": _by_decay(gd.gated_delta_chunk_xla, gd.kda_chunk_xla),
    "pallas": lambda *a: _by_decay(
        gd.gated_delta_chunk_pallas, gd.kda_chunk_pallas)(*a, interpret=True),
}


def rows_of(keep, x):
    """``x`` [T, ...] with zeros where ``keep`` [T] is false."""
    return jnp.where(keep.reshape(-1, *(1,) * (x.ndim - 1)), x, 0.0)


@pytest.mark.parametrize("decay", DECAYS)
@pytest.mark.parametrize("form", sorted(CHUNK_FORMS))
@pytest.mark.parametrize("neg_eigval", [True, False])
@pytest.mark.parametrize("T", [1, 5, 64, 100, 130, 192, 512])
def test_chunk_form_is_the_recurrence(form, neg_eigval, T, decay):
    """Lengths that are no whole sub-chunk, one shorter than a sub-chunk,
    and several sub-chunks (two: a pair of systems solved as one; three: a
    pair and a single; eight: a serving cell's chunk, four pairs); beta up
    to 2 and up to 1."""
    args = inputs(T, seed=T, neg_eigval=neg_eigval, decay=decay)
    o_ref, s_ref = gd.gated_delta_recurrent(*args)
    o, s = CHUNK_FORMS[form](*args)
    np.testing.assert_allclose(o, o_ref, **TOL)
    np.testing.assert_allclose(s, s_ref, **TOL)


@pytest.mark.parametrize("decay", DECAYS)
@pytest.mark.parametrize("form", sorted(CHUNK_FORMS))
def test_neighbouring_keys_that_are_alike(form, decay):
    """Keys that all point nearly the same way with beta near 2, as a slowly
    varying residual stream makes them: the solve inside a sub-chunk must
    not form powers of the key-key matrix (they reach 1e9 and cancel; a
    first form of this file was out by 1e-2 at the end of a sub-chunk)."""
    q, k, v, g, beta, state = inputs(128, seed=4, decay=decay)
    base = jax.random.normal(jax.random.key(1), (1, H, DK))
    k = gd.l2norm(base + 0.05 * k)
    beta = 1.9 + 0.1 * beta / 2.0
    g = g / 100.0
    o_ref, s_ref = gd.gated_delta_recurrent(q, k, v, g, beta, state)
    o, s = CHUNK_FORMS[form](q, k, v, g, beta, state)
    scale = float(jnp.abs(o_ref).max())
    np.testing.assert_allclose(o, o_ref, rtol=1e-4, atol=1e-4 * scale)
    np.testing.assert_allclose(s, s_ref, rtol=1e-4,
                               atol=1e-4 * float(jnp.abs(s_ref).max()))


def _systems(case: str, n: int):
    """Two strictly lower triangular ``[n, n]`` systems as a sub-chunk makes
    them, ``A[t, j] = beta_t k_t . k_j`` times a decay."""
    q, k, v, g, beta, _ = inputs(2 * n, seed=31, heads=1)
    if case == "alike_keys":  # test_neighbouring_keys_that_are_alike's
        base = jax.random.normal(jax.random.key(1), (1, 1, DK))
        k = gd.l2norm(base + 0.05 * k)
        beta, g = 1.9 + 0.1 * beta / 2.0, g / 100.0
    elif case == "random":  # no keys behind it: entries of order one
        A = jax.random.normal(jax.random.key(2), (2, n, n))
        return tuple(jnp.tril(A, -1))
    k, beta, gam = (x[:, 0].reshape(2, n, -1) for x in (k, beta, g))
    gam = jnp.cumsum(gam[..., 0], -1)
    decay = jnp.exp(jnp.tril(gam[..., :, None] - gam[..., None, :]))
    return tuple(jnp.tril(decay * beta * jnp.einsum("btc,bjc->btj", k, k), -1))


@pytest.mark.parametrize("n", [1, 8, 40, 64])
@pytest.mark.parametrize("case", ["keys", "alike_keys", "random"])
def test_solve_of_two_systems_as_one_is_each_alone(case, n):
    """What the chunk kernels' solve does since PR 48, against what it did:
    ``_unit_lower_inverse`` without its first round's two products is the
    parent's lines (kept in ``chip_smoke.py``, which holds the kernels on
    the chip against them), and two systems on the diagonal of one ``[2 n, 2 n]``
    matrix (``n`` a power of two), solved in ``log2 n`` rounds, are each
    alone: exactly on the CPU, where a product that adds exact zeros
    changes no bit, and to 1e-6 of the largest entry anywhere; with keys
    that all point nearly the same way and beta near 2, where the inverse's
    entries grow."""
    dot = functools.partial(jnp.matmul, precision=gd.HI)
    systems = _systems(case, n)
    alone = [gd._unit_lower_inverse(A, dot) for A in systems]
    exact = jax.default_backend() == "cpu"

    def same(got, want):
        np.testing.assert_allclose(
            got, want, rtol=0, atol=1e-6 * float(jnp.abs(want).max()))
        if exact:
            np.testing.assert_array_equal(got, want)

    for A, X in zip(systems, alone):
        same(X, chip_smoke.parents_unit_lower_inverse(A, dot))
        np.testing.assert_allclose(  # and it IS the inverse
            (jnp.eye(n) + A) @ X, jnp.eye(n),
            atol=2e-5 * float(jnp.abs(X).max()))
    if n & (n - 1) or n == 1:
        return  # a sub-chunk shorter than 64 rows is its chunk's only one
    both = gd._solve(systems)  # the kernels' own lines
    same(both[:n, :n], alone[0])
    same(both[n:, n:], alone[1])
    np.testing.assert_array_equal(both[:n, n:], 0.0)
    np.testing.assert_array_equal(both[n:, :n], 0.0)


def test_decay_is_near_one_in_these_tests():
    """What makes the carry matter: half the (token, head) pairs keep more
    than 0.9 of the state."""
    g = inputs(200)[3]
    assert float(jnp.median(jnp.exp(g))) > 0.9


@pytest.mark.parametrize("decay", DECAYS)
@pytest.mark.parametrize("form", sorted(CHUNK_FORMS))
def test_state_carries_over_chunk_calls(form, decay):
    """Three calls of 70, 64 and 23 tokens, each from the state the one
    before left, are one call of 157."""
    q, k, v, g, beta, state = inputs(157, seed=3, decay=decay)
    o_ref, s_ref = gd.gated_delta_recurrent(q, k, v, g, beta, state)
    outs, at = [], 0
    for n in (70, 64, 23):
        sl = slice(at, at + n)
        o, state = CHUNK_FORMS[form](q[sl], k[sl], v[sl], g[sl], beta[sl],
                                     state)
        outs.append(o)
        at += n
    np.testing.assert_allclose(jnp.concatenate(outs), o_ref, **TOL)
    np.testing.assert_allclose(state, s_ref, **TOL)


@pytest.mark.parametrize("decay", DECAYS)
@pytest.mark.parametrize("form", sorted(CHUNK_FORMS))
def test_rows_without_beta_and_decay_leave_the_state_alone(form, decay):
    """A padded chunk's tail: beta 0 and g 0."""
    q, k, v, g, beta, state = inputs(40, seed=5, decay=decay)
    n = 27
    keep = jnp.arange(40) < n
    o, s = CHUNK_FORMS[form](q, k, v, rows_of(keep, g), rows_of(keep, beta),
                             state)
    o_ref, s_ref = gd.gated_delta_recurrent(
        q[:n], k[:n], v[:n], g[:n], beta[:n], state)
    np.testing.assert_allclose(o[:n], o_ref, **TOL)
    np.testing.assert_allclose(s, s_ref, **TOL)


STEP_FORMS = {
    "xla": _by_decay(gd.gated_delta_step_xla, gd.kda_step_xla),
    "pallas": lambda *a: _by_decay(
        gd.gated_delta_step_pallas, gd.kda_step_pallas)(*a, interpret=True),
}


@pytest.mark.parametrize("decay", DECAYS)
@pytest.mark.parametrize("form", sorted(STEP_FORMS))
@pytest.mark.parametrize("heads", [3, 22, "64_strong", "64_strong_bf16"])
def test_step_form_is_the_recurrence(form, heads, decay):
    """Four slots over rows 3, 1, 0, 4 of a pool of six, six tokens each:
    every slot's row follows its own recurrence; the slot on the null row
    (beta 0 and g 0, as the decode program gives a slot that does not
    decode) leaves it as it was, and so do the rows no slot has (the kernel
    takes a slot's heads in groups, a divisor of their number at most
    ``STEP_HEADS``: 3 at once, 22 as 11 pairs, 64 as 8 groups of 8: the
    second served shape, with every beta in (1, 2) and, ``_bf16``, serving's
    bfloat16 q, k and v within their rounding of the float32 answer)."""
    S, T = 4, 6
    rows = jnp.asarray([3, 1, 0, 4], jnp.int32)
    live = rows > 0
    heads, *flags = str(heads).split("_")
    heads = int(heads)
    assert gd._head_group(heads) == {3: 3, 22: 2, 64: 8}[heads]
    harder = strong if "strong" in flags else (lambda args: args)
    per = [harder(inputs(T, seed=10 + s, heads=heads, decay=decay))
           for s in range(S)]
    tol, given = TOL, jnp.float32
    if "bf16" in flags:
        tol, given = dict(rtol=0.05, atol=0.05), jnp.bfloat16
    pool0 = jax.random.normal(jax.random.key(9), (6, heads, DK, DV))
    pool = pool0
    outs = []
    for t in range(T):
        q, k, v, g, beta = (jnp.stack([p[i][t] for p in per])
                            for i in range(5))
        o, pool = STEP_FORMS[form](
            q.astype(given), k.astype(given), v.astype(given),
            rows_of(live, g), rows_of(live, beta), pool, rows)
        assert o.dtype == pool.dtype == jnp.float32
        outs.append(o)
    for s in (0, 1, 3):
        q, k, v, g, beta, _ = per[s]
        o_ref, s_ref = gd.gated_delta_recurrent(q, k, v, g, beta,
                                                pool0[rows[s]])
        np.testing.assert_allclose(jnp.stack([o[s] for o in outs]), o_ref,
                                   **tol)
        np.testing.assert_allclose(pool[rows[s]], s_ref, **tol)
    for r in (0, 2, 5):
        np.testing.assert_array_equal(pool[r], pool0[r])


def _parent_kda_step_kernel(rows_ref, kT_ref, qT_ref, aT_ref, row_ref, s_ref,
                            o_ref, out_ref, *, heads: int):
    del rows_ref
    for i in range(heads):
        kc, qc, ac = (ref[0, 0][:, i:i + 1]
                      for ref in (kT_ref, qT_ref, aT_ref))
        v, b, kq = (row_ref[0, 0, c * heads + i:c * heads + i + 1]
                    for c in range(3))
        S = ac * s_ref[0, i]
        u = b * (v - jnp.sum(S * kc, axis=0, keepdims=True))
        out_ref[0, i] = S + kc * u
        o_ref[0, 0, i:i + 1] = jnp.sum(S * qc, axis=0, keepdims=True) + kq * u


def parent_kda_step(q, k, v, g, beta, pool, rows):
    """``tadnn_kda_step`` as it was before it took a work list (PR 49's
    tree, to the letter, in the interpreter): grid (ALL slots, groups of
    heads), a dead slot on the null row; keys, queries and decays relaid as
    columns [S, G, d_k, hb], value, beta and k.q broadcast and stacked
    [S, G, 3 hb, d_v].  The oracle of the kernel's bits."""
    S, H, dk = k.shape
    dv = v.shape[-1]
    hb = gd._head_group(H)
    G = H // hb
    q, k, v, g, beta = (x.astype(jnp.float32) for x in (q, k, v, g, beta))
    cols = lambda x: jnp.swapaxes(x.reshape(S, G, hb, dk), -1, -2)
    wide = lambda x: jnp.broadcast_to(x[..., None], (S, H, dv))
    packed = jnp.stack([v, wide(beta), wide(jnp.sum(k * q, -1))], axis=2)
    packed = jnp.swapaxes(packed.reshape(S, G, hb, 3, dv), 2, 3).reshape(
        S, G, 3 * hb, dv)
    col = pl.BlockSpec((1, 1, dk, hb), lambda s, j, r: (s, j, 0, 0))
    row3 = pl.BlockSpec((1, 1, 3 * hb, dv), lambda s, j, r: (s, j, 0, 0))
    st = pl.BlockSpec((1, hb, dk, dv), lambda s, j, r: (r[s], j, 0, 0))
    o, pool = pl.pallas_call(
        functools.partial(_parent_kda_step_kernel, heads=hb),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(S, G),
            in_specs=[col, col, col, row3, st],
            out_specs=[pl.BlockSpec((1, 1, hb, dv),
                                    lambda s, j, r: (s, j, 0, 0)), st]),
        out_shape=[jax.ShapeDtypeStruct((S, G, hb, dv), jnp.float32),
                   jax.ShapeDtypeStruct(pool.shape, jnp.float32)],
        input_output_aliases={5: 1},
        interpret=True,
    )(rows.astype(jnp.int32), cols(k), cols(q), cols(jnp.exp(g)), packed,
      pool)
    return o.reshape(S, H, dv), pool


LIVE = {  # which of 12 slots decode
    "all": range(12),
    "five_scattered": (1, 4, 5, 8, 11),
    "one": (7,),
    "none": (),
    "behind_dead": (9, 10),  # the list's first item is slot 9
    "strong": (0, 3, 6),  # every beta in (1, 2)
}


@pytest.mark.parametrize("case", sorted(LIVE))
@pytest.mark.parametrize("heads", [32, 64])
def test_kda_step_walks_the_live_slots_alone(heads, case):
    """``tadnn_kda_step`` over 12 slots of which ``LIVE[case]`` decode, at
    the two served head counts (groups of 8: 4 and 8 a slot): the live
    slots' rows and outputs are the plain form's within rounding AND the
    parent's kernel's bit for bit (the same float32 operations in the same
    order: only where the operands lie changed), every other row of the pool
    is untouched bit for bit, the null row among them, whether one slot
    decodes, none or all, and ``o`` of a slot that does not decode is zero.
    The call is jitted: the grid's first axis is a traced number."""
    S = 12
    live = jnp.zeros((S,), bool).at[jnp.asarray(LIVE[case], jnp.int32)].set(
        True)
    rows = jnp.where(live, 1 + jnp.arange(S), 0).astype(jnp.int32)
    harder = strong if case == "strong" else (lambda args: args)
    q, k, v, g, beta, _ = harder(inputs(S, seed=31, heads=heads,
                                        decay="channel"))
    g, beta = rows_of(live, g), rows_of(live, beta)
    if case == "strong":
        assert bool(jnp.all(beta[live] > 1.0))
    pool0 = jax.random.normal(jax.random.key(8), (S + 1, heads, DK, DV))
    step = jax.jit(functools.partial(gd.kda_step_pallas, interpret=True))
    o, pool = step(q, k, v, g, beta, pool0, rows)
    o_par, pool_par = jax.jit(parent_kda_step)(q, k, v, g, beta, pool0, rows)
    o_ref, pool_ref = gd.kda_step_xla(q, k, v, g, beta, pool0, rows)
    on = np.asarray(live)
    np.testing.assert_array_equal(np.asarray(o)[on], np.asarray(o_par)[on])
    np.testing.assert_array_equal(pool, pool_par)
    np.testing.assert_allclose(np.asarray(o)[on], np.asarray(o_ref)[on],
                               **TOL)
    np.testing.assert_allclose(pool, pool_ref, **TOL)
    assert not np.asarray(o)[~on].any()
    dead_rows = np.setdiff1d(np.arange(S + 1), np.asarray(rows)[on])
    np.testing.assert_array_equal(np.asarray(pool)[dead_rows],
                                  np.asarray(pool0)[dead_rows])
    # the list a decode step hands the kernel is the one it derives
    o_w, pool_w = step(q, k, v, g, beta, pool0, rows,
                       work=gd.live_slots(live))
    np.testing.assert_array_equal(o_w, o)
    np.testing.assert_array_equal(pool_w, pool)


def test_live_slots_lists_the_live_slots_first_in_slot_order():
    work = gd.live_slots(jnp.asarray([0, 1, 0, 0, 1, 1, 0], bool))
    assert work.order.tolist() == [1, 4, 5, 0, 2, 3, 6]
    assert int(work.n_live) == 3 and work.order.dtype == jnp.int32
    none = gd.live_slots(jnp.zeros((4,), bool))
    assert none.order.tolist() == [0, 1, 2, 3] and int(none.n_live) == 0


@pytest.mark.parametrize("decay", DECAYS)
def test_chunk_then_steps_is_one_sequence(decay):
    """Prefill in a chunk, then decode a token at a time from the state it
    left: the recurrence over the whole sequence."""
    q, k, v, g, beta, state = inputs(90, seed=21, decay=decay)
    o_ref, s_ref = gd.gated_delta_recurrent(q, k, v, g, beta, state)
    n = 83
    o, s = gd.gated_delta_chunk(q[:n], k[:n], v[:n], g[:n], beta[:n], state)
    pool = jnp.zeros((2, H, DK, DV)).at[1].set(s)
    outs = [o]
    for t in range(n, 90):
        o, pool = gd.gated_delta_step(
            q[t][None], k[t][None], v[t][None], g[t][None], beta[t][None],
            pool, jnp.asarray([1], jnp.int32))
        outs.append(o)
    np.testing.assert_allclose(jnp.concatenate(outs), o_ref, **TOL)
    np.testing.assert_allclose(pool[1], s_ref, **TOL)


@pytest.mark.parametrize("decay", DECAYS)
def test_bf16_operands_keep_a_float32_state(decay):
    """Serving's dtypes: bfloat16 q, k, v in, float32 state and output out,
    within bfloat16's rounding of the float32 answer."""
    q, k, v, g, beta, state = inputs(100, seed=2, decay=decay)
    lo = lambda x: x.astype(jnp.bfloat16)
    o, s = CHUNK_FORMS["xla"](lo(q), lo(k), lo(v), g, beta, state)
    o_ref, s_ref = gd.gated_delta_recurrent(q, k, v, g, beta, state)
    assert o.dtype == s.dtype == jnp.float32
    np.testing.assert_allclose(o, o_ref, rtol=0.05, atol=0.05)
    np.testing.assert_allclose(s, s_ref, rtol=0.05, atol=0.08)


def test_causal_conv_is_a_convolution_with_its_tail():
    """Two halves, the second fed the first's last K - 1 rows, are the
    whole; a sequence's start is a tail of zeros."""
    K, D, T = 4, 10, 12
    x = jax.random.normal(jax.random.key(0), (T, D))
    w = jax.random.normal(jax.random.key(1), (K, D))
    pad = jnp.concatenate([jnp.zeros((K - 1, D)), x])
    want = jax.nn.silu(sum(w[i] * pad[i:i + T] for i in range(K)))
    np.testing.assert_allclose(gd.causal_conv(pad, w, T), want, rtol=1e-6)
    first = gd.causal_conv(pad[:K - 1 + 7], w, 7)
    second = gd.causal_conv(pad[7:], w, T - 7)
    np.testing.assert_allclose(jnp.concatenate([first, second]), want,
                               rtol=1e-6)


def test_a_vector_decay_with_equal_channels_is_the_scalar_rule():
    """``g`` [T, H, d_k] with every channel of a head the head's number is
    the rule of ``g`` [T, H] to the letter: in the oracle, and in each form
    against it."""
    q, k, v, g, beta, state = inputs(100, seed=6)
    wide = jnp.broadcast_to(g[..., None], (*g.shape, DK))
    o_ref, s_ref = gd.gated_delta_recurrent(q, k, v, g, beta, state)
    o, s = gd.gated_delta_recurrent(q, k, v, wide, beta, state)
    np.testing.assert_array_equal(o, o_ref)
    np.testing.assert_array_equal(s, s_ref)
    for form in CHUNK_FORMS.values():
        o, s = form(q, k, v, wide, beta, state)
        np.testing.assert_allclose(o, o_ref, **TOL)
        np.testing.assert_allclose(s, s_ref, **TOL)


@pytest.mark.parametrize("form", sorted(CHUNK_FORMS))
@pytest.mark.parametrize("T", [23, 64, 130])
def test_a_channel_that_forgets_beside_one_that_never_does(form, T):
    """Adversarial decays a channel: channel 0 forgets all it held in one
    token (g = -60: ``exp(-g)`` is 1e26 and its 64th power past float32),
    channel 1 never forgets (g = 0), channel 2 hardly (-1e-6), the others
    as the family draws them.  No exponential of a positive number is
    formed anywhere, so every number is finite and the recurrence's."""
    q, k, v, g, beta, state = inputs(T, seed=8, decay="channel")
    g = g.at[:, :, 0].set(-60.0).at[:, :, 1].set(0.0).at[:, :, 2].set(-1e-6)
    o_ref, s_ref = gd.gated_delta_recurrent(q, k, v, g, beta, state)
    o, s = CHUNK_FORMS[form](q, k, v, g, beta, state)
    assert np.isfinite(o).all() and np.isfinite(s).all()
    np.testing.assert_allclose(o, o_ref, **TOL)
    np.testing.assert_allclose(s, s_ref, **TOL)
    assert float(jnp.abs(s[:, 0]).max()) < 2.1  # one token's write, alone


# -- the chunk kernel of a decay a channel: everything formed on the chip ------


def _kda_case(case: str):
    """``(args, oracle, rows)`` of a case of the test below: the kernel's
    operands, what it must return (``(o, state)`` from an oracle that is not
    the kernel) and how many of ``o``'s rows count."""
    if case.startswith("T"):  # a padded tail, several groups of sub-chunks
        T = int(case[1:])
        args = inputs(T, seed=T, heads=2, decay="channel")
        return args, gd.gated_delta_recurrent(*args), T
    if case.startswith("wide"):  # Kimi-Linear's widths: nothing is padded
        T = int(case[4:])
        args = inputs(T, seed=T, heads=2, dk=128, dv=128, decay="channel")
        return args, gd.gated_delta_recurrent(*args), T
    if case.startswith("heads64_"):  # the second served shape's heads, at
        T = int(case[9:])            # a small d, every beta in (1, 2)
        args = strong(inputs(T, seed=T, heads=64, dk=16, dv=16,
                             decay="channel"))
        assert float(args[4].min()) > 1.0 and float(args[4].max()) < 2.0
        return args, gd.gated_delta_recurrent(*args), T
    if case == "steep":  # log-decays down to -30 a token a channel
        q, k, v, g, beta, state = inputs(150, seed=12, decay="channel")
        u = jax.random.uniform(jax.random.key(13), g.shape)
        args = (q, k, v, -30.0 * u * u, beta, state)
        return args, gd.gated_delta_recurrent(*args), 150
    if case == "idle_rows":  # a padded chunk's tail: beta 0 and g 0
        q, k, v, g, beta, state = inputs(100, seed=14, decay="channel")
        keep = jnp.arange(100) < 71
        args = (q, k, v, rows_of(keep, g), rows_of(keep, beta), state)
        return args, gd.gated_delta_recurrent(
            q[:71], k[:71], v[:71], g[:71], beta[:71], state), 71
    assert case == "equal_channels"  # the scalar rule, to the letter
    q, k, v, g, beta, state = inputs(200, seed=15)
    wide = jnp.broadcast_to(g[..., None], (*g.shape, DK))
    return (q, k, v, wide, beta, state), gd.gated_delta_chunk_xla(
        q, k, v, g, beta, state), 200


@pytest.mark.parametrize("case", [
    "T8", "T70", "T454", "T512", "T1024", "wide70", "wide512", "carried",
    "steep", "idle_rows", "equal_channels", "heads64_T70", "heads64_T200",
    "bf16_T454", "bf16_wide70", "bf16_steep", "bf16_heads64_T200"])
def test_kda_chunk_kernel_forms_its_decays_itself(case):
    """``tadnn_kda_chunk`` in the interpreter, from q, k, v, g, beta as the
    mixer hands them over (the halving through reference rows, the running
    sums and every scaling inside it), against the recurrence token by token
    and against ``kda_chunk_xla``, whose ``kda_products`` forms the decays
    pair by pair: lengths with a padded tail and with several groups of
    sub-chunks; a state carried from a chunk before; decays steep enough
    that any positive exponent would overflow; rows that leave the state
    alone; all of a head's channels equal, which is the scalar rule's
    ``gated_delta_chunk_xla``; and 64 heads (the second served shape's: the
    kernel's ``beta`` operand a ``[rows, 64]`` block) with every beta in
    (1, 2), where a coarser solve would show first.  The ``bf16_`` cases
    are serving's dtypes (bfloat16 q, k, v; the levels' products stay
    float32): within
    bfloat16's rounding of the recurrence on the float32 inputs, closer to
    ``kda_chunk_xla`` on the same bfloat16 inputs, and no further from the
    recurrence than that form is."""
    kernel = lambda *a: gd.kda_chunk_pallas(*a, interpret=True)  # noqa: E731
    if case.startswith("bf16_"):
        (q, k, v, *rest), (o_ref, s_ref), n = _kda_case(case[5:])
        args = (*(x.astype(jnp.bfloat16) for x in (q, k, v)), *rest)
        o, s = kernel(*args)
        assert o.dtype == s.dtype == jnp.float32
        assert np.isfinite(o).all() and np.isfinite(s).all()
        np.testing.assert_allclose(o, o_ref, rtol=0.05, atol=0.05)
        np.testing.assert_allclose(s, s_ref, rtol=0.05, atol=0.08)
        o_xla, s_xla = gd.kda_chunk_xla(*args)
        # (64 heads of keys of 16 with every beta in (1, 2): the widest of
        # 64 x 16 x 16 state numbers lies 0.071 apart where the two forms
        # round, and 0.060 from the recurrence; the root mean squares below
        # are what a coarser solve would move)
        close = 0.1 if "heads64" in case else 0.01
        np.testing.assert_allclose(o, o_xla, rtol=close, atol=close)
        np.testing.assert_allclose(s, s_xla, rtol=close, atol=close)
        rms = lambda x, ref: float(jnp.sqrt(jnp.mean((x - ref) ** 2)))  # noqa: E731
        # no further from the recurrence than the pairwise float32 blocks
        # (bfloat16 operands at the levels read 1.10 times in the output)
        assert rms(o, o_ref) <= 1.05 * rms(o_xla, o_ref)
        assert rms(s, s_ref) <= 1.05 * rms(s_xla, s_ref)
        return
    if case == "carried":  # 454 + 70 tokens are one sequence of 524
        q, k, v, g, beta, state = inputs(524, seed=11, heads=2,
                                         decay="channel")
        o_ref, s_ref = gd.gated_delta_recurrent(q, k, v, g, beta, state)
        first = slice(0, 454)
        o1, mid = kernel(q[first], k[first], v[first], g[first], beta[first],
                         state)
        args = (q[454:], k[454:], v[454:], g[454:], beta[454:], mid)
        o, s = kernel(*args)
        np.testing.assert_allclose(jnp.concatenate([o1, o]), o_ref, **TOL)
        np.testing.assert_allclose(s, s_ref, **TOL)
        want, n = (o_ref[454:], s_ref), 70
    else:
        args, want, n = _kda_case(case)
        o, s = kernel(*args)
    assert np.isfinite(o).all() and np.isfinite(s).all()
    for o_ref, s_ref in (want, gd.kda_chunk_xla(*args)):
        np.testing.assert_allclose(o[:n], o_ref[:n], **TOL)
        np.testing.assert_allclose(s, s_ref, **TOL)
