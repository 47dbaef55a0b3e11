"""The gated delta rule (``ops/gated_delta.py``): the chunk form and the step
form, each as plain ``jax.numpy`` and as its Pallas kernel in the
interpreter, against the token-by-token recurrence.  Float32 throughout, so
the tolerance is rounding alone: 1e-5 of values of order one."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torch_automatic_distributed_neural_network_tpu.ops import gated_delta as gd

H, DK, DV = 3, 16, 24
TOL = dict(rtol=1e-5, atol=1e-5)


def inputs(T: int, seed: int = 0, *, neg_eigval: bool = True,
           heads: int = H, dk: int = DK, dv: int = DV):
    """Queries, keys and values as a layer makes them, and decays from the
    family's initialisation (A uniform in (0, 16), dt log-uniform in
    (0.001, 0.1)): most heads forget slowly, so a lost carry shows."""
    ks = jax.random.split(jax.random.key(seed), 7)
    q = gd.l2norm(jax.random.normal(ks[0], (T, heads, dk))) * dk ** -0.5
    k = gd.l2norm(jax.random.normal(ks[1], (T, heads, dk)))
    v = jax.random.normal(ks[2], (T, heads, dv))
    beta = jax.nn.sigmoid(jax.random.normal(ks[3], (T, heads)))
    if neg_eigval:
        beta = 2.0 * beta
    A = jax.random.uniform(ks[4], (heads,), minval=1e-3, maxval=16.0)
    dt = jnp.exp(jax.random.uniform(ks[5], (T, heads), minval=np.log(1e-3),
                                    maxval=np.log(0.1)))
    state = jax.random.normal(ks[6], (heads, dk, dv))
    return q, k, v, -A * dt, beta, state


CHUNK_FORMS = {
    "xla": gd.gated_delta_chunk_xla,
    "pallas": lambda *a: gd.gated_delta_chunk_pallas(*a, interpret=True),
}


@pytest.mark.parametrize("form", sorted(CHUNK_FORMS))
@pytest.mark.parametrize("neg_eigval", [True, False])
@pytest.mark.parametrize("T", [1, 5, 64, 100, 130])
def test_chunk_form_is_the_recurrence(form, neg_eigval, T):
    """Lengths that are no whole sub-chunk, one shorter than a sub-chunk,
    and several sub-chunks; beta up to 2 and up to 1."""
    args = inputs(T, seed=T, neg_eigval=neg_eigval)
    o_ref, s_ref = gd.gated_delta_recurrent(*args)
    o, s = CHUNK_FORMS[form](*args)
    np.testing.assert_allclose(o, o_ref, **TOL)
    np.testing.assert_allclose(s, s_ref, **TOL)


@pytest.mark.parametrize("form", sorted(CHUNK_FORMS))
def test_neighbouring_keys_that_are_alike(form):
    """Keys that all point nearly the same way with beta near 2, as a slowly
    varying residual stream makes them: the solve inside a sub-chunk must
    not form powers of the key-key matrix (they reach 1e9 and cancel; a
    first form of this file was out by 1e-2 at the end of a sub-chunk)."""
    q, k, v, g, beta, state = inputs(128, seed=4)
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


def test_decay_is_near_one_in_these_tests():
    """What makes the carry matter: half the (token, head) pairs keep more
    than 0.9 of the state."""
    g = inputs(200)[3]
    assert float(jnp.median(jnp.exp(g))) > 0.9


@pytest.mark.parametrize("form", sorted(CHUNK_FORMS))
def test_state_carries_over_chunk_calls(form):
    """Three calls of 70, 64 and 23 tokens, each from the state the one
    before left, are one call of 157."""
    q, k, v, g, beta, state = inputs(157, seed=3)
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


@pytest.mark.parametrize("form", sorted(CHUNK_FORMS))
def test_rows_without_beta_and_decay_leave_the_state_alone(form):
    """A padded chunk's tail: beta 0 and g 0."""
    q, k, v, g, beta, state = inputs(40, seed=5)
    n = 27
    keep = (jnp.arange(40) < n)[:, None]
    o, s = CHUNK_FORMS[form](q, k, v, jnp.where(keep, g, 0.0),
                             jnp.where(keep, beta, 0.0), state)
    o_ref, s_ref = gd.gated_delta_recurrent(
        q[:n], k[:n], v[:n], g[:n], beta[:n], state)
    np.testing.assert_allclose(o[:n], o_ref, **TOL)
    np.testing.assert_allclose(s, s_ref, **TOL)


STEP_FORMS = {
    "xla": gd.gated_delta_step_xla,
    "pallas": lambda *a: gd.gated_delta_step_pallas(*a, interpret=True),
}


@pytest.mark.parametrize("form", sorted(STEP_FORMS))
@pytest.mark.parametrize("heads", [3, 22])
def test_step_form_is_the_recurrence(form, heads):
    """Four slots over rows 3, 1, 0, 4 of a pool of six, six tokens each:
    every slot's row follows its own recurrence; the slot on the null row
    (beta 0 and g 0, as the decode program gives a slot that does not
    decode) leaves it as it was, and so do the rows no slot has (the kernel
    takes a slot's heads in groups, a divisor of their number at most
    ``STEP_HEADS``: 3 at once, 22 as 11 pairs)."""
    S, T = 4, 6
    rows = jnp.asarray([3, 1, 0, 4], jnp.int32)
    live = (rows > 0)[:, None]
    per = [inputs(T, seed=10 + s, heads=heads) for s in range(S)]
    pool0 = jax.random.normal(jax.random.key(9), (6, heads, DK, DV))
    pool = pool0
    outs = []
    for t in range(T):
        q, k, v, g, beta = (jnp.stack([p[i][t] for p in per])
                            for i in range(5))
        o, pool = STEP_FORMS[form](q, k, v, jnp.where(live, g, 0.0),
                                   jnp.where(live, beta, 0.0), pool, rows)
        outs.append(o)
    for s in (0, 1, 3):
        q, k, v, g, beta, _ = per[s]
        o_ref, s_ref = gd.gated_delta_recurrent(q, k, v, g, beta,
                                                pool0[rows[s]])
        np.testing.assert_allclose(jnp.stack([o[s] for o in outs]), o_ref,
                                   **TOL)
        np.testing.assert_allclose(pool[rows[s]], s_ref, **TOL)
    for r in (0, 2, 5):
        np.testing.assert_array_equal(pool[r], pool0[r])


def test_chunk_then_steps_is_one_sequence():
    """Prefill in a chunk, then decode a token at a time from the state it
    left: the recurrence over the whole sequence."""
    q, k, v, g, beta, state = inputs(90, seed=21)
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


def test_bf16_operands_keep_a_float32_state():
    """Serving's dtypes: bfloat16 q, k, v in, float32 state and output out,
    within bfloat16's rounding of the float32 answer."""
    q, k, v, g, beta, state = inputs(100, seed=2)
    lo = lambda x: x.astype(jnp.bfloat16)
    o, s = gd.gated_delta_chunk_xla(lo(q), lo(k), lo(v), g, beta, state)
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
