"""The selective scan's three forms (``ops/ssm.py``) against the
recurrence, token by token, in float32: the chunk kernel and the step kernel
run in the Pallas interpreter here (``tests/test_chip_compile_kernels.py``
compiles them for a described v5e; ``chip_smoke.py`` runs them on the chip).
Tolerance 1e-5: the forms do the same float32 arithmetic in the same order
but for the sum over the state's ``N`` numbers."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torch_automatic_distributed_neural_network_tpu.ops import ssm

TOL = 1e-5


def _inputs(T: int, d_in: int, N: int, seed: int = 0):
    ks = jax.random.split(jax.random.key(seed), 7)
    c = jax.nn.silu(jax.random.normal(ks[0], (T, d_in)))
    delta = jnp.exp(jax.random.uniform(ks[1], (T, d_in), minval=np.log(1e-3),
                                       maxval=np.log(0.3)))
    A = -jax.random.uniform(ks[2], (N, d_in), minval=1e-3, maxval=16.0)
    B, C = (jax.random.normal(k, (T, N)) for k in ks[3:5])
    D = jax.random.normal(ks[5], (d_in,))
    h0 = jax.random.normal(ks[6], (N, d_in))
    return c, delta, A, B, C, D, h0


def _by_hand(c, delta, A, B, C, D, h):
    """The equations in numpy, a Python loop."""
    c, delta, A, B, C, D, h = (np.asarray(x, np.float64)
                               for x in (c, delta, A, B, C, D, h))
    ys = []
    for t in range(c.shape[0]):
        h = np.exp(delta[t][None] * A) * h + (delta[t] * c[t])[None] \
            * B[t][:, None]
        ys.append((h * C[t][:, None]).sum(0) + D * c[t])
    return np.stack(ys), h


def test_recurrence_is_the_equations():
    args = _inputs(9, 12, 4)
    y, h = ssm.ssm_recurrent(*args)
    want_y, want_h = _by_hand(*args)
    np.testing.assert_allclose(y, want_y, atol=TOL, rtol=0)
    np.testing.assert_allclose(h, want_h, atol=TOL, rtol=0)


@pytest.mark.parametrize("T,d_in,N", [(64, 256, 16), (19, 128, 8),
                                      (8, 1024, 16)])
def test_chunk_kernel_matches_recurrence(T, d_in, N):
    """Whole and part groups of eight tokens, one and several blocks of
    channels, a carried state."""
    args = _inputs(T, d_in, N, seed=T)
    y, h = ssm.ssm_chunk_pallas(*args, interpret=True)
    want_y, want_h = ssm.ssm_recurrent(*args)
    np.testing.assert_allclose(y, want_y, atol=TOL, rtol=0)
    np.testing.assert_allclose(h, want_h, atol=TOL, rtol=0)


def test_chunk_in_two_calls_is_one():
    """The state carried from a chunk to the next: two calls are one."""
    c, delta, A, B, C, D, h0 = _inputs(48, 128, 16, seed=5)
    y1, h1 = ssm.ssm_chunk_pallas(c[:24], delta[:24], A, B[:24], C[:24], D,
                                  h0, interpret=True)
    y2, h2 = ssm.ssm_chunk_pallas(c[24:], delta[24:], A, B[24:], C[24:], D,
                                  h1, interpret=True)
    want_y, want_h = ssm.ssm_recurrent(c, delta, A, B, C, D, h0)
    np.testing.assert_allclose(jnp.concatenate([y1, y2]), want_y, atol=TOL,
                               rtol=0)
    np.testing.assert_allclose(h2, want_h, atol=TOL, rtol=0)


def test_a_row_with_no_step_leaves_the_state():
    """``Delta = 0``: a padded chunk's tail, an inactive slot."""
    c, delta, A, B, C, D, h0 = _inputs(16, 128, 16, seed=2)
    delta = delta.at[8:].set(0.0)
    _, h = ssm.ssm_chunk_pallas(c, delta, A, B, C, D, h0, interpret=True)
    _, want = ssm.ssm_recurrent(c[:8], delta[:8], A, B[:8], C[:8], D, h0)
    np.testing.assert_allclose(h, want, atol=TOL, rtol=0)


@pytest.mark.parametrize("form", ["xla", "pallas"])
def test_step_forms_match_recurrence(form):
    """Five slots on rows of a pool of nine, one of them the null row with
    ``Delta = 0``; the rows nobody names stay as they were."""
    S, d_in, N, R = 5, 256, 16, 9
    c, delta, A, B, C, D, _ = _inputs(S, d_in, N, seed=3)
    pool = jax.random.normal(jax.random.key(9), (R, N, d_in))
    rows = jnp.asarray([3, 0, 7, 1, 8], jnp.int32)
    delta = delta.at[1].set(0.0)
    if form == "xla":
        y, new = ssm.ssm_step_xla(c, delta, A, B, C, D, pool, rows)
    else:
        y, new = ssm.ssm_step_pallas(c, delta, A, B, C, D, pool, rows,
                                     interpret=True)
    for s, r in enumerate(np.asarray(rows)):
        want_y, want_h = ssm.ssm_recurrent(
            c[s:s + 1], delta[s:s + 1], A, B[s:s + 1], C[s:s + 1], D, pool[r])
        np.testing.assert_allclose(y[s], want_y[0], atol=TOL, rtol=0)
        np.testing.assert_allclose(new[r], want_h, atol=TOL, rtol=0)
    untouched = [r for r in range(R) if r not in set(np.asarray(rows)) | {0}]
    np.testing.assert_array_equal(new[jnp.asarray(untouched)],
                                  pool[jnp.asarray(untouched)])
    np.testing.assert_array_equal(new[0], pool[0])  # the null row


def test_lanes_a_step_takes():
    assert ssm.chunk_lanes(5120) == 512 and ssm.chunk_lanes(128) == 128
    assert ssm.chunk_lanes(384) == 128 and ssm.chunk_lanes(96) is None
