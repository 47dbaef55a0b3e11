"""The no-drop expert layer (``parallel/expert.held_expert_ffn``) against a
plain loop over the (token, expert) pairs, at the corners of its layout:
the pairs that land here fill the first ``n_active`` row tiles and the
kernels write those alone, so whatever else a padded array holds may not
reach the sum; and ``route_top_k``'s passes of max-and-mask against
``jax.lax.top_k``.  Float32 on the CPU, the kernels in the Pallas
interpreter (which fills what a kernel leaves unwritten with NaN).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torch_automatic_distributed_neural_network_tpu.ops import (
    grouped_matmul as gmm,
)
from torch_automatic_distributed_neural_network_tpu.parallel.expert import (
    expert_tiles,
    held_expert_ffn,
    route_top_k,
    top_k_by_passes,
)

D, F, HELD, PUBLISHED, FIRST = 32, 16, 8, 32, 8


def _silu(v):
    return v / (1.0 + np.exp(-v))


def _loop(x, chosen, weights, wg, wu, wd, valid):
    """Every pair on an expert held here, one at a time, in float64."""
    out = np.zeros(x.shape, np.float64)
    sizes = np.zeros((HELD,), int)
    for t, j in np.ndindex(*chosen.shape):
        e = chosen[t, j] - FIRST
        if 0 <= e < HELD and (valid is None or valid[t]):
            sizes[e] += 1
            h = _silu(x[t] @ wg[e]) * (x[t] @ wu[e])
            out[t] += weights[t, j] * (h @ wd[e])
    return out, sizes


def _distinct(rs, T, k, lo, hi):
    """``k`` different experts of ``lo .. hi`` a token."""
    return np.stack([rs.choice(np.arange(lo, hi), size=k, replace=False)
                     for _ in range(T)]).astype(np.int32)


def _layer_case(T, k, choose, *, valid=None, poison=False):
    def run(monkeypatch):
        rs = np.random.RandomState(T * 31 + k)
        x = rs.randn(T, D).astype(np.float32)
        wg, wu = (rs.randn(HELD, D, F).astype(np.float32) * 0.3
                  for _ in range(2))
        wd = rs.randn(HELD, F, D).astype(np.float32) * 0.3
        chosen = choose(rs, T, k)
        weights = rs.rand(T, k).astype(np.float32)
        mask = None if valid is None else np.arange(T) < valid
        want, sizes = _loop(x, chosen, weights, wg, wu, wd, mask)
        tm, n_tiles = expert_tiles(T, k, HELD)
        assert tm == (16 if T * k <= 256 else 128)
        if poison:
            # what the chip leaves in the tiles no step wrote: anything
            kernel = gmm.grouped_matmul

            def poisoned(rows, w, tile_group, n_active, **kw):
                y = kernel(rows, w, tile_group, n_active, **kw)
                dead = jnp.arange(y.shape[0]) // kw["tm"] >= n_active
                return jnp.where(dead[:, None], jnp.nan, y)

            monkeypatch.setattr(gmm, "grouped_matmul", poisoned)
        got, stats = jax.jit(
            lambda *a: held_expert_ffn(*a, first_expert=FIRST, valid=(
                None if mask is None else jnp.asarray(mask))))(
            *map(jnp.asarray, (x, chosen, weights, wg, wu, wd)))
        got = np.asarray(got)
        assert np.isfinite(got).all()
        np.testing.assert_allclose(got, want, atol=2e-5, rtol=0)
        if not sizes.any():
            assert not got.any()  # exact zeros, not small numbers
        assert {n: int(v) for n, v in stats.items()} == {
            "pairs": sizes.sum(), "experts_touched": (sizes > 0).sum(),
            "max_expert_tokens": sizes.max(),
            "tiles_active": (-(-sizes // tm)).sum(),
            # no zero-compute expert in this layer; the rows that are real
            "zero_pairs": 0, "rows": T if mask is None else mask.sum()}
        assert int(stats["tiles_active"]) <= n_tiles
        return stats

    return run


def _all_here(run, T, k):
    def check(monkeypatch):
        stats = run(monkeypatch)
        assert int(stats["pairs"]) == T * k  # the no-drop worst case

    return check


def _ties(monkeypatch):
    """Rows with equal scores: the passes pick what ``top_k`` picks, in its
    order (ties to the lower index), and the routed weights follow."""
    del monkeypatch
    rs = np.random.RandomState(3)
    scores = rs.randint(0, 6, size=(64, PUBLISHED)).astype(np.float32)
    scores[0] = 1.0  # one value a whole row
    scores[1, 5:] = -np.inf  # fewer finite scores than choices
    scores[2] = -np.inf
    want = np.asarray(jax.lax.top_k(jnp.asarray(scores), 8)[1])
    assert (want[0] == np.arange(8)).all()
    got, there = top_k_by_passes(jnp.asarray(scores), jnp.asarray(scores), 8)
    np.testing.assert_array_equal(np.asarray(got), want)
    np.testing.assert_array_equal(
        np.asarray(there), np.take_along_axis(scores, want, -1))
    # through the router: a bias that makes ties of distinct scores
    logits = rs.randn(48, PUBLISHED).astype(np.float32)
    bias = np.where(np.arange(PUBLISHED) % 2, 4.0, 0.0).astype(np.float32)
    chosen, weights = route_top_k(jnp.asarray(logits), jnp.asarray(bias), 4)
    s = 1 / (1 + np.exp(-logits.astype(np.float64)))
    pick = np.asarray(jax.lax.top_k(jax.nn.sigmoid(jnp.asarray(logits))
                                    + jnp.asarray(bias), 4)[1])
    np.testing.assert_array_equal(np.asarray(chosen), pick)
    w = np.take_along_axis(s, pick, -1)
    np.testing.assert_allclose(np.asarray(weights),
                               w / w.sum(-1, keepdims=True), rtol=1e-5)


def _one_expert(rs, T, k):
    # the held expert in the first choice, the others on another chip
    return np.concatenate([np.full((T, 1), FIRST + 3, np.int32),
                           _distinct(rs, T, k - 1, 0, FIRST)], 1)


CASES = {
    "even_choice": _layer_case(
        40, 4, lambda rs, T, k: _distinct(rs, T, k, 0, PUBLISHED)),
    "every_pair_here": _all_here(_layer_case(
        40, 8, lambda rs, T, k: _distinct(rs, T, k, FIRST, FIRST + HELD)),
        40, 8),
    "every_pair_here_small_tiles": _all_here(_layer_case(
        6, 8, lambda rs, T, k: _distinct(rs, T, k, FIRST, FIRST + HELD)),
        6, 8),
    "every_pair_on_one_expert": _layer_case(48, 3, _one_expert),
    "no_pair_here": _layer_case(
        40, 4, lambda rs, T, k: _distinct(rs, T, k, FIRST + HELD, PUBLISHED)),
    "no_pair_here_poisoned": _layer_case(
        40, 4, lambda rs, T, k: _distinct(rs, T, k, 0, FIRST), poison=True),
    "padded_chunk_tail": _layer_case(
        40, 4, lambda rs, T, k: _distinct(rs, T, k, 0, PUBLISHED), valid=29),
    "nothing_valid": _layer_case(
        12, 4, lambda rs, T, k: _distinct(rs, T, k, 0, PUBLISHED), valid=0),
    "pairs_256_small_tiles": _layer_case(
        32, 8, lambda rs, T, k: _distinct(rs, T, k, 0, PUBLISHED)),
    "pairs_257_large_tiles": _layer_case(
        257, 1, lambda rs, T, k: _distinct(rs, T, k, 4, 20)),
    "dead_tiles_poisoned": _layer_case(
        40, 4, lambda rs, T, k: _distinct(rs, T, k, 0, PUBLISHED),
        poison=True),
    "dead_tiles_poisoned_large_tiles": _layer_case(
        80, 4, lambda rs, T, k: _distinct(rs, T, k, 0, PUBLISHED),
        poison=True),
    "top_k_by_passes_on_ties": _ties,
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_expert_layer(case, monkeypatch):
    CASES[case](monkeypatch)
