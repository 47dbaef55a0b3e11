"""Pallas flash-attention kernel vs the XLA einsum oracle (SURVEY.md §4:
every impl is exercised on the CPU sim via the Pallas interpreter)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torch_automatic_distributed_neural_network_tpu.ops.attention import (
    attention,
    xla_attention,
)
from torch_automatic_distributed_neural_network_tpu.ops import (
    flash_attention as fa,
)
from torch_automatic_distributed_neural_network_tpu.ops.flash_attention import (
    flash_attention,
    flash_attention_with_lse,
    flash_plan,
)


def _qkv(b, s, h, d, hk=None, dtype=jnp.float32, seed=0):
    ks = jax.random.split(jax.random.key(seed), 3)
    q = jax.random.normal(ks[0], (b, s, h, d), dtype)
    k = jax.random.normal(ks[1], (b, s, hk or h, d), dtype)
    v = jax.random.normal(ks[2], (b, s, hk or h, d), dtype)
    return q, k, v


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("s", [128, 200])
def test_forward_matches_oracle(causal, s):
    q, k, v = _qkv(2, s, 4, 64)
    ref = xla_attention(q, k, v, causal=causal)
    out = flash_attention(q, k, v, causal=causal, block_q=128, block_k=128)
    assert jnp.max(jnp.abs(ref - out)) < 2e-5


def test_gqa_broadcast():
    q, k, v = _qkv(2, 128, 8, 64, hk=2)
    ref = xla_attention(q, k, v, causal=True)
    out = flash_attention(q, k, v, causal=True, block_q=128, block_k=128)
    assert jnp.max(jnp.abs(ref - out)) < 2e-5


@pytest.mark.parametrize("causal", [False, True])
def test_grads_match_oracle(causal):
    q, k, v = _qkv(1, 192, 4, 64, seed=3)

    def loss(fn):
        return lambda q, k, v: (fn(q, k, v, causal=causal) ** 2).sum()

    g_ref = jax.grad(loss(xla_attention), argnums=(0, 1, 2))(q, k, v)
    g_fl = jax.grad(
        loss(lambda q, k, v, causal: flash_attention(
            q, k, v, causal=causal, block_q=128, block_k=128)),
        argnums=(0, 1, 2),
    )(q, k, v)
    for a, b in zip(g_ref, g_fl):
        assert jnp.max(jnp.abs(a - b)) < 5e-5


def test_multiblock_streaming():
    # several k blocks per q block exercises the online-softmax merge
    q, k, v = _qkv(1, 256, 2, 32, seed=7)
    ref = xla_attention(q, k, v, causal=True)
    out = flash_attention(q, k, v, causal=True, block_q=64, block_k=64)
    assert jnp.max(jnp.abs(ref - out)) < 2e-5


def test_dispatch_defaults_to_xla_on_cpu():
    # auto impl on CPU (no seq axis) must stay on the einsum path
    q, k, v = _qkv(1, 128, 2, 32)
    out = attention(q, k, v, causal=True)
    ref = xla_attention(q, k, v, causal=True)
    assert jnp.max(jnp.abs(ref - out)) < 1e-6


def test_flash_under_sharded_mesh():
    # the GSPMD train step can't partition a bare Mosaic call — attention()
    # must wrap flash in shard_map over batch (+ head under TP) axes
    import torch_automatic_distributed_neural_network_tpu as tad
    from torch_automatic_distributed_neural_network_tpu.parallel import (
        context as pctx,
    )

    mesh = tad.build_mesh(data=2, tensor=4)
    q, k, v = _qkv(4, 128, 8, 32, seed=11)
    ctx = pctx.ParallelContext(mesh=mesh)
    ref = xla_attention(q, k, v, causal=True)
    with pctx.use(ctx):
        out = jax.jit(
            lambda q, k, v: attention(q, k, v, causal=True, impl="flash")
        )(q, k, v)
    assert jnp.max(jnp.abs(ref - out)) < 2e-5


@pytest.mark.parametrize("s,w,bq,bk", [
    (96, 17, 32, 32),     # window not aligned to blocks
    (128, 64, 32, 64),    # block-aligned window
    (64, 1, 16, 16),      # degenerate: attend self only
    (80, 200, 32, 32),    # window > seq == full causal
])
def test_sliding_window_matches_oracle(s, w, bq, bk):
    q, k, v = _qkv(2, s, 4, 32, seed=s + w)

    def loss_ref(q_, k_, v_):
        return jnp.sum(
            xla_attention(q_, k_, v_, causal=True, window=w) ** 2)

    def loss_fl(q_, k_, v_):
        return jnp.sum(flash_attention(
            q_, k_, v_, causal=True, window=w, block_q=bq, block_k=bk,
        ) ** 2)

    ref = xla_attention(q, k, v, causal=True, window=w)
    out = flash_attention(q, k, v, causal=True, window=w,
                          block_q=bq, block_k=bk)
    assert jnp.max(jnp.abs(ref - out)) < 2e-5
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    g_fl = jax.grad(loss_fl, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ref, g_fl):
        assert jnp.max(jnp.abs(a - b)) < 2e-4


def test_sliding_window_gqa_and_chunked():
    from torch_automatic_distributed_neural_network_tpu.ops.attention import (
        chunked_attention,
    )

    q, k, v = _qkv(2, 128, 8, 32, hk=2, seed=7)
    ref = xla_attention(q, k, v, causal=True, window=21)
    out = flash_attention(q, k, v, causal=True, window=21,
                          block_q=32, block_k=32)
    assert jnp.max(jnp.abs(ref - out)) < 2e-5
    chk = chunked_attention(q, k, v, causal=True, window=21, block_q=32)
    assert jnp.max(jnp.abs(ref - chk)) < 2e-5


def test_sliding_window_validation(devices8):
    q, k, v = _qkv(1, 32, 2, 16)
    with pytest.raises(ValueError, match="causal"):
        flash_attention(q, k, v, causal=False, window=8)
    with pytest.raises(ValueError, match="causal"):
        xla_attention(q, k, v, causal=False, window=8)
    with pytest.raises(ValueError, match="window"):
        flash_attention(q, k, v, causal=True, window=0)
    # without a sharded seq axis the ring/ulysses impls are degenerate —
    # a windowed model on a single chip must fall back to xla attention,
    # not trip the cp-only NotImplementedError
    out = attention(q, k, v, causal=True, window=8, impl="ring")
    ref = xla_attention(q, k, v, causal=True, window=8)
    assert jnp.max(jnp.abs(ref - out)) == 0
    # with a REAL seq axis the unsupported combination still errors loudly
    import torch_automatic_distributed_neural_network_tpu as tad
    from torch_automatic_distributed_neural_network_tpu.parallel import (
        context as pctx,
    )

    mesh = tad.build_mesh(data=4, seq=2)
    with pctx.use(pctx.ParallelContext(mesh=mesh)):
        with pytest.raises(NotImplementedError, match="context parallelism"):
            attention(q, k, v, causal=True, window=8, impl="ring")


def test_window_validation_shared_across_paths():
    # round-5 review: window<1 must be rejected by EVERY path — with the
    # finite mask bias an all-masked row softmaxes UNIFORMLY over all
    # keys (acausal leak), so xla/chunked must error like flash does
    from torch_automatic_distributed_neural_network_tpu.ops.attention import (
        attention as attn_dispatch,
        chunked_attention,
    )

    q, k, v = _qkv(1, 32, 2, 16)
    for fn in (xla_attention, chunked_attention):
        with pytest.raises(ValueError, match=">= 1"):
            fn(q, k, v, causal=True, window=0)
    with pytest.raises(ValueError, match=">= 1"):
        attn_dispatch(q, k, v, causal=True, window=-3)
    # and a contradictory MODEL config is rejected at construction
    from torch_automatic_distributed_neural_network_tpu.models.transformer_core import (  # noqa: E501
        TransformerConfig,
    )

    with pytest.raises(ValueError, match="causal"):
        TransformerConfig(causal=False, sliding_window=64)
    with pytest.raises(ValueError, match=">= 1"):
        TransformerConfig(sliding_window=0)


# -- the tile plans the rule can give, at small sizes -------------------------


def _oracle_with_lse(q, k, v, causal, window=None):
    """Plain attention that also returns each row's logsumexp [b, h, s]."""
    b, sq, hq, d = q.shape
    sk, hk = k.shape[1], k.shape[2]
    k, v = (jnp.repeat(x, hq // hk, axis=2) for x in (k, v))
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(d)
    if causal:
        qp, kp = jnp.arange(sq)[:, None], jnp.arange(sk)[None, :]
        ok = qp >= kp
        if window is not None:
            ok &= qp - kp < window
        s = jnp.where(ok, s, -jnp.inf)
    lse = jax.nn.logsumexp(s, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", jnp.exp(s - lse[..., None]), v)
    return o, lse


# id: (seq, q heads, kv heads, causal, window, block_q, block_k,
#      stripe rows or None for the rule's own, lse cotangent)
_PLANS = {
    "diagonal-crosses-a-tile": (128, 2, 2, True, None, 64, 64, None, False),
    "diagonal-on-edges-bq<bk": (128, 2, 2, True, None, 32, 64, None, False),
    "diagonal-on-edges-bq>bk": (128, 2, 2, True, None, 64, 32, None, False),
    "tiles-no-multiple": (96, 2, 2, True, None, 48, 32, None, False),
    "padded-200": (200, 2, 2, True, None, 64, 64, None, False),
    "padded-200-tiles-differ": (200, 2, 2, True, None, 128, 64, None, False),
    "padded-200-non-causal": (200, 2, 2, False, None, 64, 64, None, False),
    "gqa": (128, 4, 1, True, None, 32, 64, None, False),
    "window-narrower-than-a-tile": (128, 2, 2, True, 17, 32, 32, None, False),
    "window-wider-than-a-tile": (160, 2, 2, True, 80, 32, 32, None, False),
    "window-bq>bk": (160, 2, 2, True, 50, 64, 32, None, False),
    "non-causal": (128, 2, 2, False, None, 64, 32, None, False),
    "the-rule's-own-tiles": (320, 1, 1, True, None, None, None, None, False),
    "stripes-of-two-tiles": (256, 2, 2, True, None, 32, 32, 64, False),
    "stripes-tiles-differ": (256, 1, 1, True, None, 64, 32, 128, False),
    "stripes-window": (256, 1, 1, True, 70, 32, 32, 64, False),
    "stripes-non-causal-padded": (250, 1, 1, False, None, 32, 32, 64, False),
    "lse-cotangent-causal": (128, 2, 2, True, None, 32, 64, None, True),
    "lse-cotangent-non-causal": (200, 2, 1, False, None, 64, 64, None, True),
    "lse-cotangent-stripes": (256, 1, 1, True, None, 32, 32, 64, True),
}


@pytest.mark.parametrize("case", list(_PLANS))
def test_tile_plans_forward_and_gradients(case, monkeypatch):
    """Forward AND gradients against the oracle, in interpret mode, over
    the tile plans the rule can give: a diagonal that crosses a tile or
    runs along tile edges, padding, GQA, a window, stripes shorter than
    the sequence (the rule's ``_STRIPE_ROWS`` cut to the test's size), and
    the ``lse`` cotangent of ``flash_attention_with_lse``."""
    s, hq, hk, causal, window, bq, bk, stripe_rows, with_lse = _PLANS[case]
    if stripe_rows:
        monkeypatch.setattr(fa, "_STRIPE_ROWS", stripe_rows)
        plan = flash_plan(s, s, 32, 4, causal=causal, window=window,
                          block_q=bq, block_k=bk)
        assert 1 < plan.stripe_k < -(-s // bk)
        assert 1 < plan.stripe_q < -(-s // bq)
    q, k, v = _qkv(2, s, hq, 32, hk=hk, seed=len(case))
    w = jax.random.normal(jax.random.key(99), q.shape)
    u = jax.random.normal(jax.random.key(98), (2, hq, s))

    def loss(fn):
        def f(q, k, v):
            o, lse = fn(q, k, v)
            return jnp.sum(o * w) + (jnp.sum(lse * u) if with_lse else 0.0)
        return f

    def flash(q, k, v):
        kw = dict(causal=causal, block_q=bq, block_k=bk)
        if with_lse:
            return flash_attention_with_lse(q, k, v, **kw)
        return flash_attention(q, k, v, window=window, **kw), 0.0

    def oracle(q, k, v):
        return _oracle_with_lse(q, k, v, causal, window)

    (o, lse), (o_ref, lse_ref) = flash(q, k, v), oracle(q, k, v)
    assert jnp.max(jnp.abs(o - o_ref)) < 2e-5
    if with_lse:
        assert jnp.max(jnp.abs(lse - lse_ref)) < 2e-5
    g = jax.grad(loss(flash), argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss(oracle), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g, g_ref):
        assert jnp.max(jnp.abs(a - b)) < 1e-4


def _pairs(cfg, span_of, n, flip):
    """{(query tile, key tile): masked} from one side's spans."""
    out = {}
    for t in range(n):
        lo, m0, m1, hi = span_of(t, cfg)
        assert 0 <= lo <= m0 <= m1 <= hi, (t, lo, m0, m1, hi)
        for o in range(lo, hi):
            out[(o, t) if flip else (t, o)] = not m0 <= o < m1
    return out


@pytest.mark.parametrize("seq,bq,bk,causal,window", [
    (1024, 256, 256, True, None), (1024, 512, 256, True, None),
    (1024, 128, 512, True, None), (200, 64, 64, True, None),
    (200, 64, 64, False, None), (256, 64, 64, False, None),
    (96, 48, 32, True, None), (96, 32, 32, True, 17),
    (4096, 256, 128, True, 1000), (160, 64, 32, True, 50),
    (80, 32, 32, True, 200),
])
def test_both_sides_visit_the_same_pairs(seq, bq, bk, causal, window):
    """The dk/dv kernel walks query tiles of a key tile, the other two key
    tiles of a query tile: the same pairs, masked on the same ones, every
    pair that holds an attending element visited and every pair that holds
    a non-attending one masked."""
    cfg = fa._Cfg(causal=causal, seq_q=seq, seq_k=seq, block_q=bq,
                  block_k=bk, interpret=True, window=window)
    by_q = _pairs(cfg, fa._key_tile_span, cfg.n_q, False)
    assert by_q == _pairs(cfg, fa._query_tile_span, cfg.n_k, True)
    qp, kp = np.arange(cfg.n_q * bq)[:, None], np.arange(cfg.n_k * bk)[None]
    ok = np.broadcast_to(kp < seq, (qp.size, kp.size))
    if causal:
        ok = ok & (qp >= kp)
        if window is not None:
            ok = ok & (qp - kp < window)
    for qi in range(cfg.n_q):
        for ki in range(cfg.n_k):
            tile = ok[qi * bq:(qi + 1) * bq, ki * bk:(ki + 1) * bk]
            # padded QUERY rows are computed like any other and sliced off
            assert ((qi, ki) in by_q) == bool(tile.any()), (qi, ki)
            if not tile.all():
                assert by_q.get((qi, ki), True), (qi, ki)


def test_plan_skips_and_fits_at_the_benchmark_shapes():
    """The regression test for "the skip is alive at the cell's shape":
    at (1024, 1024, 128, bf16, causal) fewer tiles are visited than the
    square holds and fewer masked than visited; at the three lengths the
    old table held, the plan's stripes divide the tiles and its reckoned
    VMEM fits the budget it is held to."""
    cell = flash_plan(1024, 1024, 128, 2, causal=True)
    assert cell.tiles_visited < cell.tiles_square
    assert cell.tiles_masked < cell.tiles_visited
    assert cell.stripe_k * cell.block_k == 1024  # a head's K and V resident
    for seq in (2048, 8192, 16384):
        plan = flash_plan(seq, seq, 128, 2, causal=True)
        assert plan.vmem_bytes <= fa._VMEM_BUDGET < fa._VMEM_LIMIT
        assert (seq // plan.block_q) % plan.stripe_q == 0
        assert (seq // plan.block_k) % plan.stripe_k == 0
        assert plan.tiles_visited < 0.65 * plan.tiles_square
    full = flash_plan(1024, 1024, 128, 2, causal=False)
    assert full.tiles_visited == full.tiles_square and not full.tiles_masked
    band = flash_plan(4096, 4096, 128, 2, causal=True, window=1500)
    assert band.tiles_visited < band.tiles_square // 2
    assert band.tiles_masked < band.tiles_visited


def test_entry_records_its_plan_and_report_prints_it(tmp_path):
    from torch_automatic_distributed_neural_network_tpu.obs import (
        journal as jr,
        report,
    )

    q, k, v = _qkv(1, 128, 2, 32)
    path = str(tmp_path / "journal.jsonl")
    with jr.as_default(jr.Journal(path, validate=True)) as journal:
        flash_attention(q, k, v, causal=True, block_q=32, block_k=32)
        journal.close()
    (ev,) = [e for e in jr.Journal.read(path) if e["name"] == "flash.plan"]
    assert (ev["seq"], ev["head_dim"]) == (128, 32)
    assert (ev["tiles_visited"], ev["tiles_square"], ev["tiles_masked"]) == (
        10, 16, 4)
    text = report.format_report(report.generate(path))
    assert "flash tiles visited / square, masked: 10 / 16, 4" in text
