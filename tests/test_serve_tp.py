"""TP-sharded serving parity pins (ISSUE 13) on the 8-device CPU sim.

**TP == unsharded**: shard_map-ing the paged kernel, KV pool and adapter
pool over a 2-device tensor axis is a pure re-layout of the same
arithmetic (attention is kv-head-parallel, adapter b factors split the
channels the projection already splits), so kernel outputs and engine
tokens must match the single-device run — fp and int8 KV, GQA, adapters
included.

Plus the capacity-lint fix (serve_estimate charges adapter + KV pool
per TP shard) and the discrete-event replay's DCN tax on a decode step.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from torch_automatic_distributed_neural_network_tpu.analysis.serve_lint import (
    serve_estimate,
)
from torch_automatic_distributed_neural_network_tpu.inference.serve import (
    ServeEngine,
)
from torch_automatic_distributed_neural_network_tpu.inference.serve.adapters import (
    pool_adapter_bytes,
    random_adapter,
)
from torch_automatic_distributed_neural_network_tpu.models import GPT2
from torch_automatic_distributed_neural_network_tpu.ops.paged_attention import (
    paged_attention,
    tensor_degree,
)
from torch_automatic_distributed_neural_network_tpu.training.lora import (
    LoraSpec,
)
from torch_automatic_distributed_neural_network_tpu.tune.simulate import (
    replay_serve,
)

VOCAB = 128


def _model_and_vars(seed=1, p=12):
    model = GPT2("test", vocab_size=VOCAB, max_seq_len=64,
                 dtype=jnp.float32, remat=False)
    tokens = jnp.asarray(
        np.random.RandomState(0).randint(1, VOCAB, size=(1, p)), jnp.int32)
    return model, model.init(jax.random.key(seed), tokens)


def _prompts(n=6, seed=3, lo=4, hi=20):
    rs = np.random.RandomState(seed)
    return [[int(t) for t in rs.randint(1, VOCAB, size=rs.randint(lo, hi))]
            for _ in range(n)]


def _tokens_of(done):
    return {tuple(r.prompt): list(r.out_tokens) for r in done}


def _serve(model, variables, prompts, *, adapters=(), spec=None, **kw):
    eng = ServeEngine(model, variables, n_slots=4, max_len=64,
                      block_size=8, prefill_chunk=8, lora_spec=spec,
                      **kw)
    for name, lora in adapters:
        eng.register_adapter(name, lora)
    names = [a[0] for a in adapters]
    for i, p in enumerate(prompts):
        eng.submit(p, max_new_tokens=8, eos_id=None,
                   adapter=(names[i % (len(names) + 1) - 1]
                            if names and i % (len(names) + 1) else None))
    done = eng.run()
    eng.scheduler.check_invariants()
    return _tokens_of(done), eng


# -- tensor_degree helper -----------------------------------------------------


def test_tensor_degree(devices8):
    assert tensor_degree(None) == 1
    mesh = Mesh(np.array(jax.devices()[:2]), ("tensor",))
    assert tensor_degree(mesh) == 2
    assert tensor_degree(mesh, axis="data") == 1


# -- kernel: TP=2 shard_map vs unsharded --------------------------------------


@pytest.mark.parametrize("quantized", [False, True])
def test_kernel_tp2_matches_unsharded(devices8, quantized):
    """GQA (8q/4kv) paged kernel under a 2-way tensor mesh must equal
    the single-device kernel bitwise: attention is head-parallel and
    each GQA group lives wholly on one shard, so no combine exists to
    introduce drift."""
    from torch_automatic_distributed_neural_network_tpu.inference.quant \
        import quantize_kv

    rs = np.random.RandomState(0)
    S, Hq, kvH, hd, bs, MB, NB = 4, 8, 4, 32, 8, 4, 24
    k = jnp.asarray(rs.randn(NB, bs, kvH, hd), jnp.float32)
    v = jnp.asarray(rs.randn(NB, bs, kvH, hd), jnp.float32)
    if quantized:
        k, v = quantize_kv(k), quantize_kv(v)
    tables = np.zeros((S, MB), np.int32)
    perm = rs.permutation(np.arange(1, NB))[:S * MB].reshape(S, MB)
    tables[:] = perm
    tables = jnp.asarray(tables)
    ctx = jnp.asarray([0, 5, 17, 31], jnp.int32)
    q = jnp.asarray(rs.randn(S, Hq, hd), jnp.float32)

    mesh = Mesh(np.array(jax.devices()[:2]), ("tensor",))
    want = paged_attention(q, k, v, tables, ctx)
    got = paged_attention(q, k, v, tables, ctx, mesh=mesh)
    assert float(jnp.max(jnp.abs(got - want))) == 0.0


def test_kernel_indivisible_heads_falls_back(devices8):
    """kvH=3 does not divide tp=2: the dispatch must fall back to the
    unsharded kernel rather than mis-shard a GQA group."""
    rs = np.random.RandomState(2)
    S, Hq, kvH, hd, bs = 2, 6, 3, 16, 8
    k = jnp.asarray(rs.randn(8, bs, kvH, hd), jnp.float32)
    v = jnp.asarray(rs.randn(8, bs, kvH, hd), jnp.float32)
    tables = jnp.asarray([[1, 2], [3, 4]], jnp.int32)
    ctx = jnp.asarray([7, 12], jnp.int32)
    q = jnp.asarray(rs.randn(S, Hq, hd), jnp.float32)
    mesh = Mesh(np.array(jax.devices()[:2]), ("tensor",))
    want = paged_attention(q, k, v, tables, ctx)
    got = paged_attention(q, k, v, tables, ctx, mesh=mesh)
    assert float(jnp.max(jnp.abs(got - want))) == 0.0


# -- engine: TP=2 == unsharded ------------------------------------------------


@pytest.mark.slow
@pytest.mark.parametrize("quant_kv", [False, True])
def test_engine_tp2_matches_unsharded(devices8, quant_kv):
    model, variables = _model_and_vars()
    prompts = _prompts(seed=7)
    mesh = Mesh(np.array(jax.devices()[:2]), ("tensor",))
    base, _ = _serve(model, variables, prompts, quant_kv=quant_kv)
    tp, eng = _serve(model, variables, prompts, quant_kv=quant_kv,
                     mesh=mesh)
    assert tp == base
    assert eng.pool.spec is not None  # pool actually sharded


@pytest.mark.slow
def test_engine_tp2_with_adapters_matches_unsharded(devices8):
    """TP=2 with a sharded adapter pool (b factors split over the
    tensor axis), fp32 and int8 factors — all token-identical to the plain single-device engine."""
    model, variables = _model_and_vars()
    spec = LoraSpec(rank=4)
    adapters = [(f"t{i}", random_adapter(variables["params"], spec,
                                         seed=10 + i)) for i in range(2)]
    prompts = _prompts(seed=9, n=6)
    mesh = Mesh(np.array(jax.devices()[:2]), ("tensor",))
    for quant_adapters in (False, True):
        base, _ = _serve(model, variables, prompts, adapters=adapters,
                         spec=spec, n_adapters=4,
                         quant_adapters=quant_adapters)
        tp, eng = _serve(model, variables, prompts, adapters=adapters,
                         spec=spec, n_adapters=4,
                         quant_adapters=quant_adapters, mesh=mesh)
        assert tp == base, f"quant_adapters={quant_adapters}"
        # the wide factor really landed sharded
        b = eng.adapter_pool.factors["q"]["b"]
        leaf = b["q"] if isinstance(b, dict) else b
        assert "tensor" in str(leaf.sharding.spec)


# -- serve_estimate: per-shard charging ---------------------------------------


def test_pool_adapter_bytes_shards_b_factors():
    from types import SimpleNamespace

    cfg = SimpleNamespace(n_layers=2, n_heads=16, kv_heads=4, head_dim=64,
                          d_model=64)
    full = pool_adapter_bytes(cfg, rank=8, n_adapters=4)
    tp2 = pool_adapter_bytes(cfg, rank=8, n_adapters=4,
                             degrees={"tensor": 2})
    assert pool_adapter_bytes(cfg, rank=8, n_adapters=4,
                              degrees={"tensor": 1}) == full
    # a replicated, b split: the drop is exactly the b shards' savings
    q_out, v_out = 16 * 64, 4 * 64
    saved = 2 * 4 * 4 * 8 * (q_out // 2 + v_out // 2)  # L*A*4B*rank*o/2
    assert full - tp2 == saved
    # indivisible channels stay replicated
    cfg3 = SimpleNamespace(n_layers=2, n_heads=3, kv_heads=3, head_dim=5,
                           d_model=15)
    assert pool_adapter_bytes(cfg3, rank=8, n_adapters=4,
                              degrees={"tensor": 2}) == \
        pool_adapter_bytes(cfg3, rank=8, n_adapters=4)


def test_serve_estimate_tp_shard_clears_ml006():
    """A deployment the replicated arithmetic rejects (ML006: adapter
    pool ate the KV budget) must pass once charged per TP shard — the
    satellite fix this issue ships."""
    from types import SimpleNamespace

    # b-heavy geometry: q_out = 16*64 = 1024 >> d_model = 64, so the
    # sharded b factors dominate the pool
    cfg = SimpleNamespace(n_layers=4, n_heads=16, kv_heads=4, head_dim=64,
                          d_model=64)
    kw = dict(budget="4MiB", headroom=0.0, block_size=16, max_len=256,
              streams=1, adapters=32, adapter_rank=16)
    f1, est1 = serve_estimate(cfg, **kw)
    assert est1["max_streams"] == 0
    assert [f.code for f in f1] == ["ML006"]
    f4, est4 = serve_estimate(cfg, degrees={"tensor": 4}, **kw)
    assert est4["adapter_pool_bytes"] < est1["adapter_pool_bytes"]
    assert est4["block_bytes_per_device"] < est1["block_bytes_per_device"]
    assert est4["max_streams"] >= 1
    assert f4 == []


# -- replay: the DCN tax -----------------------------------------------------


def test_replay_prices_dcn():
    """A tp group that spans slices pays ``dcn_step_s`` on every decode
    step: the same tokens, a longer wall."""
    reqs = [(0.0, 32, 16, 16) for _ in range(4)]
    kw = dict(n_slots=4, block_size=8, max_len=64, prefill_chunk=8,
              decode_step_s=1e-3, prefill_chunk_s=1e-3)
    untaxed = replay_serve(reqs, **kw)
    taxed = replay_serve(reqs, dcn_step_s=5e-4, **kw)
    assert taxed["new_tokens"] == untaxed["new_tokens"]
    assert taxed["steps"] == untaxed["steps"]
    assert taxed["wall_s"] > untaxed["wall_s"]
