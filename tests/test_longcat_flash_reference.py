"""A model of shortcut-connected double layers (two ``latent_attention``
mixers and two dense FFNs a published layer, ONE expert branch that reads
the first FFN's normed input and is added after the second FFN; a softmax
router whose last outputs are zero-compute experts; a constant behind each
latent's norm) against the plain reference
``benchmark/reference/longcat_flash.py``, on seeded weights at tiny sizes:
``model.apply``, the three serving programs through the pool's latent pages,
and ``ServeEngine`` itself.

Tolerance: everything here is float32 at ``highest`` matmul precision.  The
program expands a chunk's keys a block at a time under an online softmax,
decodes in the absorbed form and sums a token's pairs in another order than
the reference's scan over the held experts: measured 5e-7 on logits of
magnitude 0.5.  ``ATOL`` is 2e-5; bfloat16 compute is out by more than
twenty times that and a bfloat16 cache by ten
(``test_bf16_compute_is_outside_the_tolerance``,
``test_a_bfloat16_cache_is_outside_the_tolerance``), so a run in the next
precision down fails every case here.
"""

from __future__ import annotations

import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import serve_by_hand

from torch_automatic_distributed_neural_network_tpu.inference import decode
from torch_automatic_distributed_neural_network_tpu.inference.serve import (
    ServeEngine,
    programs,
)
from torch_automatic_distributed_neural_network_tpu.inference.serve.kv_pool import (
    PagedKVPool,
)
from torch_automatic_distributed_neural_network_tpu.models.transformer_core import (
    DecoderLM,
    Router,
    SparseMLP,
    TransformerConfig,
    layer_plan,
)
from torch_automatic_distributed_neural_network_tpu.obs import schema
from torch_automatic_distributed_neural_network_tpu.obs.journal import Journal
from torch_automatic_distributed_neural_network_tpu.parallel.expert import (
    expert_tiles,
    route_top_k,
    top_k_by_passes,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmark")
ATOL = 2e-5


def _load(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load(os.path.join(BENCH, "reference", "longcat_flash.py"),
            "longcat_flash_reference")
weights = _load(os.path.join(BENCH, "lib", "weights.py"), "bench_weights")

CHUNK, BS = 8, 4
# two published layers = four sublayers; 16 experts of which 4 are held
# (4..7), 8 zero-compute ones behind them, top 3
KEYS = dict(
    vocab_size=96, d_model=48, n_layers=4, n_heads=4, d_ff=80,
    max_seq_len=128, norm="rmsnorm", norm_eps=1e-5, act="swiglu", pos="rope",
    rope_theta=1e7, tie_embeddings=False,
    layer_types=["latent_attention"] * 4, latent_q_rank=24, latent_kv_rank=16,
    latent_nope_head_dim=8, latent_rope_head_dim=4, latent_value_head_dim=8,
    latent_q_scale=2.0 ** 0.5, latent_kv_scale=3.0 ** 0.5,
    n_dense_layers=2, experts_published=16, experts_held=4, first_expert=4,
    experts_per_token=3, zero_experts=8, shortcut_experts=True,
    expert_d_ff=24, score_func="softmax", route_norm=False, route_scale=6.0)
RANK, ROT = KEYS["latent_kv_rank"], KEYS["latent_rope_head_dim"]
WIDTH = KEYS["experts_published"] + KEYS["zero_experts"]


def _params(keys: dict = KEYS, seed: int = 3, *, rope_scale: float = 8.0,
            router_scale: float = 12.0) -> dict:
    """Seeded leaves; the columns that give the rotated parts are made
    ``rope_scale`` times larger, so that position carries a share of a score
    that a test can see, and the router ``router_scale`` times larger, so
    that a token's softmax scores differ (at 0.02 n all 24 are a 24th and
    the expert branch is a constant times the identity)."""
    flat = weights.flat(weights.seed_key(seed), ref.param_shapes(keys))
    r, n = keys["latent_kv_rank"], keys["latent_nope_head_dim"]
    for path in flat:
        if path.endswith("attn/kv_a_proj/kernel"):
            flat[path] = flat[path].at[:, r:].multiply(rope_scale)
        if path.endswith("attn/q_b_proj/kernel"):
            flat[path] = flat[path].at[:, :, n:].multiply(rope_scale)
        if path.endswith("moe/router/kernel"):
            flat[path] = flat[path] * router_scale
    return flat


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


def _tokens(n: int, seed: int = 0):
    return np.random.RandomState(seed).randint(1, KEYS["vocab_size"], size=n)


def _model(keys: dict = KEYS, dtype=jnp.float32):
    return DecoderLM(TransformerConfig(**keys, remat=False, dtype=dtype))


def _want(flat: dict, seq, keys: dict = KEYS) -> np.ndarray:
    return np.asarray(ref.forward_logits(flat, keys, np.asarray(seq)[None]))[0]


# -- the model -----------------------------------------------------------------


def test_the_programs_parameters_are_the_references():
    abstract = jax.eval_shape(_model().init, jax.random.key(0),
                              np.zeros((1, 8), np.int32))["params"]
    got = {k: tuple(v.shape) for k, v in weights.unnest(abstract).items()}
    assert got == ref.param_shapes(KEYS)
    # the branch's experts lie beside the opening sublayer's dense FFN
    assert got["layers_0/moe/router/kernel"] == (48, WIDTH)
    assert "layers_1/moe/router/kernel" not in got
    assert got["layers_2/moe/experts_gate"] == (4, 48, 24)
    assert _model().cfg.num_params() == sum(
        int(np.prod(s)) for s in got.values())


def test_the_plan_is_one_entry_a_sublayer():
    assert layer_plan(_model().cfg) == [
        ("layers_0", "latent_attention", False, "open"),
        ("layers_1", "latent_attention", False, "close"),
        ("layers_2", "latent_attention", False, "open"),
        ("layers_3", "latent_attention", False, "close")]


@pytest.mark.parametrize("control", [
    "none", "no_zero_compute_term", "branch_reads_the_second_sublayer",
    "latent_scales", "top_k", "route_scale", "normalised_weights"])
def test_model_apply_matches_reference(control, monkeypatch):
    """Two sequences of 70 positions in one batch; and for each control the
    reference with that one thing changed lies far outside the tolerance, so
    the comparison does test what the name says."""
    flat = _params()
    toks = np.stack([_tokens(70, 1), _tokens(70, 2)])
    got = np.asarray(_model().apply({"params": weights.nest(flat)}, toks))
    want = np.asarray(ref.forward_logits(flat, KEYS, toks))
    np.testing.assert_allclose(got, want, atol=ATOL, rtol=0)
    if control == "none":
        return
    keys = KEYS
    if control == "no_zero_compute_term":
        monkeypatch.setattr(ref, "zero_compute",
                            lambda p, u, cfg, prec: jnp.zeros_like(u))
    elif control == "branch_reads_the_second_sublayer":
        # an expert FFN in the ordinary place: it reads the input of the
        # FFN it is added behind
        def layer(p0, p1, x, cfg, prec):
            eps = cfg["norm_eps"]
            x = ref.attention(p0, x, cfg, prec)
            x = x + ref.dense(p0, ref.rms(x, p0["mlp_norm/scale"], eps), prec)
            x = ref.attention(p1, x, cfg, prec)
            u = ref.rms(x, p1["mlp_norm/scale"], eps)
            return x + ref.dense(p1, u, prec) + ref.moe(p0, u, cfg, prec)

        monkeypatch.setattr(ref, "layer", layer)
    elif control == "latent_scales":
        keys = {**KEYS, "latent_q_scale": 1.0, "latent_kv_scale": 1.0}
    elif control == "top_k":
        keys = {**KEYS, "experts_per_token": 2}
    elif control == "route_scale":
        keys = {**KEYS, "route_scale": 1.0}
    elif control == "normalised_weights":
        keys = {**KEYS, "route_norm": True}
    ref._layer.clear_cache()  # (the jitted layer closes over the module)
    off = np.asarray(ref.forward_logits(flat, keys, toks))
    ref._layer.clear_cache()
    assert np.abs(off - got).max() > 100 * ATOL, control


def test_bf16_compute_is_outside_the_tolerance():
    flat = _params()
    toks = _tokens(40, 1)[None]
    got = np.asarray(_model(dtype=jnp.bfloat16).apply(
        {"params": weights.nest(flat)}, toks))
    assert np.abs(got - _want(flat, toks[0])).max() > 20 * ATOL


def test_what_a_page_stores_is_the_scaled_latent():
    """``project``'s cache row: ``[kv_scale * RMSNorm(c), rot(k_r)]``, and
    the query's latent scaled before ``q_b_proj`` (the reference's two
    constants, after the norms)."""
    from torch_automatic_distributed_neural_network_tpu.models.transformer_core import (
        LatentAttention,
    )

    cfg = _model().cfg
    flat = _params()
    own = {"params": weights.nest(flat)["layers_1"]["attn"]}
    x = jnp.asarray(np.random.RandomState(0).randn(1, 12, 48), jnp.float32)
    pos = jnp.arange(12)[None]
    _, _, latent = LatentAttention(cfg).apply(own, x, pos, method="project")
    p = ref.sub(flat, "layers_1")
    c = x[0] @ p["attn/kv_a_proj/kernel"][:, :RANK]
    want = KEYS["latent_kv_scale"] * ref.rms(c, p["attn/kv_a_norm/scale"],
                                             1e-5)
    np.testing.assert_allclose(np.asarray(latent[0, :, :RANK]),
                               np.asarray(want), atol=1e-5, rtol=0)
    plain = TransformerConfig(**{**KEYS, "latent_kv_scale": 1.0},
                              dtype=jnp.float32)
    _, _, unscaled = LatentAttention(plain).apply(own, x, pos,
                                                  method="project")
    np.testing.assert_allclose(
        np.asarray(latent[..., :RANK]),
        KEYS["latent_kv_scale"] * np.asarray(unscaled[..., :RANK]), atol=1e-5)
    np.testing.assert_array_equal(np.asarray(latent[..., RANK:]),
                                  np.asarray(unscaled[..., RANK:]))


# -- the router and the expert branch -------------------------------------------


def test_the_router_scores_by_softmax_over_its_whole_width():
    """Softmax over ``experts_published + zero_experts``; the bias takes
    part in the choice and never in the weight; the chosen weights are not
    normalised; times ``route_scale``."""
    cfg = _model().cfg
    assert cfg.router_width == WIDTH == 24
    rs = np.random.RandomState(0)
    x = jnp.asarray(rs.randn(50, 48), jnp.float32)
    kernel = jnp.asarray(rs.randn(48, WIDTH) * 0.3, jnp.float32)
    bias = jnp.asarray(rs.randn(WIDTH) * 0.05, jnp.float32)
    chosen, w = Router(cfg).apply(
        {"params": {"kernel": kernel, "e_bias": bias}}, x)
    p = np.asarray(jax.nn.softmax(x @ kernel, -1))
    np.testing.assert_allclose(p.sum(-1), 1.0, atol=1e-6)
    want = np.argsort(-(p + np.asarray(bias)), -1, kind="stable")[:, :3]
    np.testing.assert_array_equal(np.asarray(chosen), want)
    np.testing.assert_allclose(
        np.asarray(w), 6.0 * np.take_along_axis(p, want, -1), atol=1e-6)
    assert (np.asarray(chosen) >= 16).any()  # zero-compute ids are chosen
    # the bias moved some choices, and no weight
    plain, _ = Router(cfg).apply(
        {"params": {"kernel": kernel, "e_bias": jnp.zeros_like(bias)}}, x)
    assert (np.asarray(plain) != np.asarray(chosen)).any()
    assert not np.allclose(np.asarray(w).sum(-1), 6.0)  # not normalised


def test_twelve_passes_over_768_are_top_k():
    """``top_k_by_passes`` at the published shape: the 12 largest of 768,
    in order, ties to the lower index, as ``jax.lax.top_k`` picks."""
    rs = np.random.RandomState(1)
    scores = jax.nn.softmax(jnp.asarray(rs.randn(300, 768) * 1.57,
                                        jnp.float32), -1)
    select = scores + jnp.asarray(rs.randn(768) * 1e-3, jnp.float32)
    select = select.at[:7, 100].set(select[:7, 5])  # ties
    chosen, picked = jax.jit(lambda a, b: top_k_by_passes(a, b, 12))(
        select, scores)
    _, want = jax.lax.top_k(select, 12)
    np.testing.assert_array_equal(np.asarray(chosen), np.asarray(want))
    np.testing.assert_array_equal(
        np.asarray(picked),
        np.asarray(jnp.take_along_axis(scores, want, axis=-1)))
    both, w = route_top_k(jnp.log(scores), select - scores, 12,
                          score_func="softmax", route_norm=False,
                          route_scale=6.0)
    np.testing.assert_array_equal(np.asarray(both), np.asarray(want))
    np.testing.assert_allclose(np.asarray(w), 6.0 * np.asarray(picked),
                               rtol=1e-5)


def _branch(flat, keys, name="layers_2"):
    """The branch's module and its parameters of one published layer."""
    return (SparseMLP(TransformerConfig(**keys, dtype=jnp.float32)),
            dict(weights.nest(flat)[name]["moe"]))


def test_all_shares_and_the_zero_part_once_add_up_to_the_uncut_branch():
    """The chip's share of an expert-parallel deployment: 16 experts over
    four chips, four each, beside 8 zero-compute experts that EVERY chip
    computes for its own tokens.  The held experts' parts of the four
    shares, plus the zero-compute part counted once, are the uncut
    reference's ``MoE(u)``; every real pair lands on exactly one chip."""
    whole = {**KEYS, "experts_held": 16, "first_expert": 0}
    flat = _params(whole)
    layer = ref.sub(flat, "layers_2")
    u = jnp.asarray(np.random.RandomState(1).randn(40, 48), jnp.float32)
    want = np.asarray(ref.moe(layer, u, whole, "f32"))
    common = np.asarray(ref.zero_compute(layer, u, whole, "f32"))
    assert np.abs(common).max() > 0.1  # the zero-compute part is there
    total, pairs, zero = common.copy(), 0, set()
    for chip in range(4):
        keys = {**KEYS, "experts_held": 4, "first_expert": 4 * chip}
        module, mine = _branch(flat, keys)
        for name in ("experts_gate", "experts_up", "experts_down"):
            mine[name] = mine[name][4 * chip:4 * chip + 4]
        y, stats = module.apply({"params": mine}, u)
        total += np.asarray(y) - common
        pairs += int(stats["pairs"])
        zero.add(int(stats["zero_pairs"]))
        assert int(stats["rows"]) == 40
        part = np.asarray(ref.moe(
            {**layer, **{"moe/" + n: mine[n] for n in
                         ("experts_gate", "experts_up", "experts_down")}},
            u, keys, "f32"))
        np.testing.assert_allclose(np.asarray(y), part, atol=ATOL, rtol=0)
    assert len(zero) == 1  # every chip counts the same zero-compute pairs
    assert pairs + zero.pop() == 40 * 3
    np.testing.assert_allclose(total, want, atol=4 * ATOL, rtol=0)


def test_a_row_of_zero_compute_choices_reads_no_expert():
    """A bias that lifts the zero-compute outputs over every expert: all
    three choices of every row are zero-compute, the result is exactly
    ``(sum of the weights) * u`` in float32, no tile is live and no pair
    lands here."""
    flat = _params()
    module, mine = _branch(flat, KEYS)
    mine["router"] = {**mine["router"], "e_bias": jnp.where(
        jnp.arange(WIDTH) >= 16, 10.0, 0.0)}
    u = jnp.asarray(np.random.RandomState(2).randn(24, 48), jnp.float32)
    y, stats = module.apply({"params": mine}, u)
    chosen, w = Router(module.cfg).apply({"params": mine["router"]}, u)
    assert (np.asarray(chosen) >= 16).all()
    np.testing.assert_array_equal(
        np.asarray(y), np.asarray(w.sum(-1, keepdims=True) * u))
    assert (int(stats["tiles_active"]), int(stats["pairs"]),
            int(stats["experts_touched"])) == (0, 0, 0)
    assert (int(stats["zero_pairs"]), int(stats["rows"])) == (72, 24)


def test_rows_that_are_no_token_route_nowhere():
    """A padded chunk's tail and an empty slot: no expert, no zero-compute
    term (their rows of the result are exact zeros) and no count."""
    flat = _params()
    module, mine = _branch(flat, KEYS)
    u = jnp.asarray(np.random.RandomState(3).randn(2, 10, 48), jnp.float32)
    valid = jnp.asarray(np.arange(20).reshape(2, 10) % 3 != 0)
    y, stats = module.apply({"params": mine}, u, valid)
    full, every = module.apply({"params": mine}, u)
    y, full = np.asarray(y), np.asarray(full)
    np.testing.assert_array_equal(y[~np.asarray(valid)], 0.0)
    np.testing.assert_allclose(y[np.asarray(valid)], full[np.asarray(valid)],
                               atol=1e-6, rtol=0)
    assert int(stats["rows"]) == int(valid.sum()) == 13
    assert int(every["rows"]) == 20
    assert 0 < int(stats["zero_pairs"]) < int(every["zero_pairs"])
    assert int(stats["pairs"]) + int(stats["zero_pairs"]) <= 13 * 3


def test_tiles_are_laid_for_the_pairs_that_can_land_here():
    """A token's choices are different experts: at most ``held`` of its
    ``top_k`` land here.  The benchmark's shapes (top 4 and 8 of 32 held,
    top 12 of 16) lay what they laid."""
    assert expert_tiles(536, 12, 16) == (128, -(-536 * 12 // 128) + 16)
    assert expert_tiles(528, 4, 32) == (128, -(-528 * 4 // 128) + 32)
    assert expert_tiles(536, 8, 32) == (128, -(-536 * 8 // 128) + 32)
    assert expert_tiles(24, 12, 16) == (128, 3 + 16)
    assert expert_tiles(100, 12, 4) == (128, 4 + 4)  # 400 pairs, not 1,200
    assert expert_tiles(4, 3, 2) == (16, 1 + 2)


def test_the_latents_norms_stay_float32_and_the_branch_is_rounded():
    cfg = _model(dtype=jnp.bfloat16).cfg
    held = decode.compute_dtype_params(weights.nest(_params()), cfg)
    attn, moe = held["layers_0"]["attn"], held["layers_0"]["moe"]
    assert attn["q_a_norm"]["scale"].dtype == jnp.float32
    assert attn["kv_a_norm"]["scale"].dtype == jnp.float32
    assert attn["kv_b_proj"]["kernel"].dtype == jnp.bfloat16
    assert moe["router"]["kernel"].dtype == jnp.float32
    assert moe["router"]["e_bias"].dtype == jnp.float32
    assert moe["experts_gate"].dtype == jnp.bfloat16
    assert held["layers_0"]["mlp"]["up_proj"]["kernel"].dtype == jnp.bfloat16


def test_the_configurations_in_the_benchmark_keep_their_trees_and_plans():
    """Every configuration of ``BENCHMARK.json`` with ``layer_types``: the
    plan's entries, and the parameter paths ``model.init`` builds under one
    layer of each kind of entry.  The four that were there open and close
    nothing and have no ``moe`` subtree."""
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    seen = {}
    for entry in bench["configs"]:
        with open(os.path.join(REPO, entry["file"])) as f:
            keys = json.load(f)["model"]
        if not keys.get("layer_types"):
            continue
        cfg = TransformerConfig(**keys)
        plan = layer_plan(cfg)
        tree = jax.eval_shape(DecoderLM(cfg).init, jax.random.key(0),
                              np.zeros((1, 8), np.int32))["params"]
        seen[entry["name"]] = (
            [(kind, sparse, branch) for _, kind, sparse, branch in plan],
            {name: sorted(tree[name]) for name, *_ in plan})
    old = {"trinity-large-ep8": 5, "olmo-hybrid-7b-pp2": 16,
           "joyai-llm-flash-ep8": 20, "kimi-linear-48b-ep8": 16,
           "phi4-mini-flash-3p8b": 32,  # PR 46: no branch either
           "solar-open2-250b-ep8": 4}  # PR 49: nor here
    assert set(seen) == set(old) | {"longcat-flash-omni-ep32"}
    plan, trees = seen["kimi-linear-48b-ep8"]  # PR 41: no branch either
    assert plan == [("latent_attention" if i % 4 == 3 else "linear_attention",
                     i >= 1, None) for i in range(16)]
    assert trees["layers_1"] == trees["layers_3"] == [
        "attn", "attn_norm", "mlp", "mlp_norm"]
    for name, n in old.items():
        plan, trees = seen[name]
        assert len(plan) == n and all(b is None for _, _, b in plan)
        assert all("moe" not in t for t in trees.values())
    plan, _ = seen["solar-open2-250b-ep8"]  # EVERY entry an expert FFN
    assert plan == [("linear_attention" if i else "full_attention", True,
                     None) for i in range(4)]
    plan, trees = seen["joyai-llm-flash-ep8"]
    assert plan == [("latent_attention", i >= 1, None) for i in range(20)]
    assert trees["layers_1"] == ["attn", "attn_norm", "mlp", "mlp_norm"]
    plan, _ = seen["trinity-large-ep8"]
    assert plan == [("sliding_attention", False, None),
                    ("sliding_attention", True, None),
                    ("sliding_attention", True, None),
                    ("sliding_attention", True, None),
                    ("full_attention", True, None)]
    plan, _ = seen["olmo-hybrid-7b-pp2"]
    assert plan == [("full_attention" if i % 4 == 3 else "linear_attention",
                     False, None) for i in range(16)]
    plan, trees = seen["longcat-flash-omni-ep32"]
    assert plan == [("latent_attention", False, ("open", "close")[i % 2])
                    for i in range(8)]
    assert trees["layers_0"] == ["attn", "attn_norm", "mlp", "mlp_norm",
                                 "moe"]
    assert trees["layers_1"] == ["attn", "attn_norm", "mlp", "mlp_norm"]


def test_parameter_counts_are_the_published_layers():
    """At the cell's cut: 181.1M of two mixers, 453.0M of two dense FFNs,
    4.7M of router and 16 experts of 37.75M a published layer; and the
    widths as the catalog row has them."""
    with open(os.path.join(BENCH, "configs",
                           "longcat-flash-omni-ep32.json")) as f:
        doc = json.load(f)
    cfg = TransformerConfig(**doc["model"])
    d = 6144
    mixer = (d * 1536 + 1536 * 64 * 192 + d * 576 + 512 * 64 * 256
             + 64 * 128 * d)
    assert 2 * mixer == 181_141_504
    assert cfg.mixer_params("latent_attention") == mixer + 1536 + 512
    assert 2 * 3 * d * 12288 == 452_984_832
    branch = d * 768 + 768 + 16 * 3 * d * 2048
    layer = 2 * (cfg.mixer_params("latent_attention") + 2 * d) \
        + 2 * 3 * d * 12288 + branch
    assert cfg.num_params() == 4 * layer + 2 * 16384 * d + d \
        == doc["parameters"]
    built = jax.eval_shape(DecoderLM(cfg).init, jax.random.key(0),
                           np.zeros((1, 8), np.int32))["params"]
    assert cfg.num_params() == sum(
        int(np.prod(x.shape)) for x in jax.tree.leaves(built))
    published = {
        "attention_bias": False, "vocab_size": 131072, "hidden_size": 6144,
        "ffn_hidden_size": 12288, "expert_ffn_hidden_size": 2048,
        "num_layers": 28, "num_attention_heads": 64, "kv_lora_rank": 512,
        "q_lora_rank": 1536, "qk_rope_head_dim": 64, "v_head_dim": 128,
        "qk_nope_head_dim": 128, "mla_scale_q_lora": True,
        "mla_scale_kv_lora": True, "routed_scaling_factor": 6,
        "n_routed_experts": 512, "max_position_embeddings": 131072,
        "rms_norm_eps": 1e-05, "rope_theta": 10000000,
        "attention_method": "MLA", "zero_expert_num": 256,
        "zero_expert_type": "identity", "moe_topk": 12}
    assert doc["source_keys"] == published
    assert sorted(doc["reduced"]) == sorted(
        k for k, v in published.items() if doc[k] != v) == [
        "n_routed_experts", "num_layers", "vocab_size"]
    assert (doc["num_layers"], doc["n_routed_experts"], doc["vocab_size"]) \
        == (4, 16, 16384)
    m = doc["model"]
    assert (m["d_model"], m["n_heads"], m["d_ff"], m["expert_d_ff"],
            m["experts_published"], m["zero_experts"],
            m["experts_per_token"], m["route_scale"]) == (
        6144, 64, 12288, 2048, 512, 256, 12, 6.0)
    assert m["latent_q_scale"] == 2.0
    assert m["latent_kv_scale"] == pytest.approx(12 ** 0.5, abs=1e-12)


# -- the three serving programs, driven by hand --------------------------------


def Served(flat: dict, **kw):
    """``serve_by_hand.Served`` over this file's model and page sizes."""
    return serve_by_hand.Served(KEYS, weights.nest(flat), chunk=CHUNK,
                                block=BS, **kw)


def _close(got: dict, want: np.ndarray, what: str = ""):
    for pos, row in got.items():
        np.testing.assert_allclose(row, want[pos], atol=ATOL, rtol=0,
                                   err_msg=f"{what} position {pos}")


@pytest.mark.parametrize("impl", ["paged", "dense"])
def test_serving_programs_match_reference(impl, monkeypatch):
    """A prompt of 21 tokens (three chunks of 8, the last PADDED: 5 real
    rows) and 30 decode steps through the latent pages, in slot 1 of 3: the
    logits of each chunk's last row and of every decode step are the
    reference's full forward pass's, through the latent kernel and through
    the dense gather alike; and the reference without the zero-compute term
    is far from both."""
    flat = _params()
    seq = _tokens(51, 5)
    got = Served(flat, impl=impl).sequence(1, seq, 21)
    assert sorted(got) == [7, 15] + list(range(20, 51))
    _close(got, _want(flat, seq))
    monkeypatch.setattr(ref, "zero_compute",
                        lambda p, u, cfg, prec: jnp.zeros_like(u))
    ref._layer.clear_cache()
    off = _want(flat, seq)
    ref._layer.clear_cache()
    assert max(np.abs(r - off[p]).max() for p, r in got.items()) > 100 * ATOL


def test_a_bfloat16_cache_is_outside_the_tolerance():
    flat = _params()
    seq = _tokens(51, 5)
    got = Served(flat, cache=jnp.bfloat16).sequence(1, seq, 21)
    want = _want(flat, seq)
    assert max(np.abs(r - want[p]).max() for p, r in got.items()) > 10 * ATOL


def test_a_chunk_that_carries_decode_rows_matches_reference():
    """``chunk_and_step``: slot 0 prefills 19 tokens in three chunks while
    slots 1 and 2 decode IN those chunks' calls: the branch's result crosses
    a plan entry for the chunk's rows and the decode rows together.  The
    chunks' logits are the reference's; the decode rows are served the
    reference's first choice; the rows they wrote are read by plain decode
    steps afterwards, whose logits are the reference's too.  The call's
    counters: the rows routed are the chunk's real rows and the decoding
    slots (the padded tail and no empty slot), the same in both branches."""
    flat = _params()
    a, b, c = _tokens(40, 7), _tokens(45, 8), _tokens(30, 9)
    sv = Served(flat)
    got_b, got_c = sv.prefill(1, b[:10]), sv.prefill(2, c[:6])
    want_a, want_b, want_c = (_want(flat, s) for s in (a, b, c))
    got_a = {}
    for i, pos in enumerate(range(0, 19, CHUNK)):
        part = list(a[pos:pos + CHUNK][:19 - pos])
        lg, served = sv.fused(0, part, pos, {1: b[10 + i], 2: c[6 + i]})
        got_a[pos + len(part) - 1] = lg
        assert served[1] == int(np.argmax(want_b[10 + i]))
        assert served[2] == int(np.argmax(want_c[6 + i]))
        pairs, _, _, _, zero, rows = sv.counters[:6]
        assert rows == len(part) + 2
        assert 0 < zero and pairs + zero <= 2 * 3 * rows
    assert sorted(got_a) == [7, 15, 18]
    for i in range(12):  # all three decode, a step each
        lg = sv.decode({0: a[19 + i], 1: b[13 + i], 2: c[9 + i]})
        got_a[19 + i], got_b[13 + i], got_c[9 + i] = lg[0], lg[1], lg[2]
        assert sv.counters[5] == 3
    sv.decode({1: b[25]})  # two empty slots route nowhere
    assert sv.counters[5] == 1 and sv.counters[4] <= 2 * 3
    _close(got_a, want_a, "the chunk's slot")
    _close(got_b, want_b, "slot 1")
    _close(got_c, want_c, "slot 2")


def test_neighbouring_slots_do_not_touch_each_others_pages():
    flat = _params()
    a, b = _tokens(40, 7), _tokens(45, 8)
    sv = Served(flat)
    got_a, got_b = sv.prefill(0, a[:10]), {}
    chunks = sv.chunks(1, b[:19])  # three chunks, between slot 0's steps
    for pos in range(10, 20):
        got_a[pos] = sv.decode({0: a[pos]})[0]
        if pos % 3 == 0:
            got_b.update(next(chunks))
    assert next(chunks, None) is None and sorted(got_b) == [7, 15, 18]
    for i in range(20):  # both decode, a step each
        lg = sv.decode({0: a[20 + i], 1: b[19 + i]})
        got_a[20 + i], got_b[19 + i] = lg[0], lg[1]
    _close(got_a, _want(flat, a), "slot 0")
    _close(got_b, _want(flat, b[:39]), "slot 1")


def test_pool_bytes_are_the_arithmetic():
    """At the cell's shape: 8 latent layers (two a published layer) of
    4,097 pages of 64 tokens of ONE row of 512 + 64 numbers, stored in 640
    lanes: 10,240 B a token, 2.5 GiB."""
    with open(os.path.join(BENCH, "configs",
                           "longcat-flash-omni-ep32.json")) as f:
        cfg = TransformerConfig(**json.load(f)["model"])
    assert cfg.page_row("latent_attention") == (576,)
    made = {}

    def arrays():
        made["pool"] = PagedKVPool(cfg, num_blocks=4097, block_size=64,
                                   n_slots=24, max_blocks=544,
                                   prefill_chunk=512)
        return made["pool"].kv

    kv = jax.eval_shape(arrays)
    pool = made["pool"]
    assert [x.shape for x in kv["k"]] == [(4097, 64, 640)] * 8
    assert {x.shape for x in kv["v"]} == {(0,)}
    assert pool.bytes_full == pool.bytes_latent == 8 * 4097 * 64 * 640 * 2
    assert pool.bytes_per_block == 8 * 64 * 640 * 2 == 64 * 10240
    assert round(pool.bytes_full / 2**30, 2) == 2.50


# -- the engine -------------------------------------------------------------------


def _engine(flat, journal=None, **kw):
    return ServeEngine(_model(), {"params": weights.nest(flat)}, **{
        "n_slots": 3, "max_len": 96, "block_size": BS, "prefill_chunk": CHUNK,
        "cache_dtype": jnp.float32, "export_cache": False,
        "journal": journal, **kw})


def _regret(flat, req) -> float:
    lg = _want(flat, req.prompt + req.out_tokens)
    n, m = len(req.prompt), len(req.out_tokens)
    rows = lg[n - 1:n - 1 + m]
    return float((rows.max(-1) - rows[np.arange(m), req.out_tokens]).max())


SHAPES = [(5, 20), (23, 30), (14, 17), (30, 8), (3, 3), (41, 12)]
SERVED = {"chunked": {},
          "optimistic": {"admission": "optimistic"},
          "dense": {"attention_impl": "dense"},
          "speculative": {"speculative": 2},
          "prefix_cache": {"prefix_cache": True}}


@pytest.mark.parametrize("option", sorted(SERVED))
def test_engine_serves_the_references_first_choice(option, tmp_path):
    """The engine itself, scheduler and all: six requests over three slots
    (slots are reused, chunks and decode steps interleave and ride in one
    call, the last chunks are padded), each served token the reference's
    first choice at its position, under every engine option this block is
    served with: the branch breaks neither speculation (a verify step's
    rows go through the same entries) nor the prefix cache (a page holds
    latent rows; nothing of the branch is cached)."""
    flat = _params()
    journal = Journal(None, host0_only=False, validate=True)
    eng = _engine(flat, journal, **SERVED[option])
    reqs = [eng.submit([int(t) for t in _tokens(n, 10 + i)], max_new_tokens=m)
            for i, (n, m) in enumerate(SHAPES)]
    eng.run()
    eng.scheduler.check_invariants()
    for r, (n, m) in zip(reqs, SHAPES):
        assert len(r.out_tokens) == m
        assert _regret(flat, r) <= ATOL, (option, n, m)
    steps = journal.named("serve.step")
    fuses = option != "speculative"
    assert (sum(s.get("fused", 0) for s in steps) > 3) == fuses
    # the two new counters on every call that read a step, whether its
    # rows rode in a chunk (the call laid the chunk's tiles) or not; the
    # three older pair counters where they decoded alone
    ev = journal.named("serve.engine")[-1]
    read = [s for s in steps if "moe_rows" in s]
    assert read and all("moe_zero_pairs" in s for s in read)
    with_chunk, alone = ev["moe_tiles_laid"]
    rode = [with_chunk != alone and s["moe_tiles_laid"] == with_chunk
            for s in read]
    assert any(rode) == fuses and not all(rode)
    for s, in_chunk in zip(read, rode):
        assert ("moe_pairs" in s) == (not in_chunk)
        assert 0 <= s["moe_zero_pairs"] <= 2 * 3 * s["moe_rows"]
        assert s["moe_rows"] <= (CHUNK + 3 if in_chunk else 3 * 3)
    assert sum(s["moe_zero_pairs"] for s in read) > 0
    assert (ev["zero_experts"], ev["shortcut_experts"]) == (8, True)
    assert (ev["experts_held"], ev["experts_published"]) == (4, 16)
    assert ev["layer_kinds"] == KEYS["layer_types"]
    if option != "chunked":
        return
    # tiles: two branches' worth a call
    assert ev["moe_tiles_laid"] == [
        2 * expert_tiles(CHUNK + 3, 3, 4)[1], 2 * expert_tiles(3, 3, 4)[1]]
    assert ev["kv_bytes_full"] == ev["kv_bytes_latent"] \
        == eng.pool.bytes_latent == 4 * 73 * BS * 128 * 4
    from torch_automatic_distributed_neural_network_tpu.obs import (
        report as obs_report,
    )

    path = tmp_path / "journal.jsonl"
    path.write_text("".join(json.dumps(r) + "\n" for r in journal.records))
    text = obs_report.format_report(obs_report.generate(str(path)))
    assert "experts 4 held of 16 + 8 zero-compute (a shortcut branch" in text
    assert "zero-compute experts: " in text and " a row, all expert" in text
    assert "(4 latent layers)" in text


def test_the_counters_are_in_the_schema():
    step = schema.REGISTRY["serve.step"]
    engine = schema.REGISTRY["serve.engine"]
    assert step.optional["moe_zero_pairs"] == step.optional["moe_rows"] \
        == "int"
    assert engine.optional["zero_experts"] == "int"
    assert engine.optional["shortcut_experts"] == "bool"
    assert step.optional["attn_pages_copied"] \
        == step.optional["attn_pages_live"] == "int"
    assert programs.N_COUNTERS == 10
    assert programs.step_output(5).shape == (5 + 5 + 10,)


def test_the_branch_keeps_the_experts_scope():
    """The shortcut branch's ops stand under ``tadnn.ffn_expert`` (inside the
    opening sublayer's ``tadnn.ffn``), the dense FFNs under ``tadnn.ffn``."""
    sv = Served(_params())
    packed = programs.pack_chunk_and_step(
        sv._packed_chunk(0, [1, 2, 3], 0), programs.pack_step(
            np.zeros((3, sv.MB), np.int32), np.zeros((3,), np.int32),
            np.zeros((3, 1), np.int32), np.zeros((3,), np.int32),
            np.zeros((3,), np.int32)))
    text = sv._fused.lower(
        sv.params, sv.kv, packed, programs.step_output(3),
        sv.pool.win_tables[0], sv.pool.win_tables,
        jax.random.key(0)).as_text(debug_info=True)
    assert "tadnn.ffn/tadnn.ffn_expert" in text
    scoped = {part for part in text.replace('"', "/").split("/")
              if part.startswith("tadnn.")}
    assert {"tadnn.ffn", "tadnn.ffn_expert", "tadnn.attend_chunk",
            "tadnn.attend_step"} <= scoped


REFUSED = {
    "mesh": ({"mesh": "a mesh"}, "expert layers"),
    "quant_kv": ({"quant_kv": True}, "no int8 form"),
    "lora_spec": ({"lora_spec": "a spec"}, "layer_types"),
}


@pytest.mark.parametrize("option", sorted(REFUSED))
def test_unsupported_options_are_refused_at_construction(option):
    """What this block is not served with, each refusal with its reason:
    a mesh (the expert layer has no exchange, a latent row no head axis),
    int8 pages (a latent row has no int8 form), tenants (the adapter pool
    factorizes a scanned stack)."""
    kw, reason = REFUSED[option]
    with pytest.raises(ValueError, match=f"{option}.*{reason}"):
        _engine(_params(), **kw)


@pytest.mark.parametrize("bad,reason", [
    ({"n_layers": 3, "layer_types": ["latent_attention"] * 3},
     "shortcut_experts pairs the sublayers"),
    ({"n_dense_layers": 0}, "n_dense_layers == n_layers // 2"),
    ({"experts_per_token": 25}, "25 a token"),
    ({"n_dense_layers": None, "shortcut_experts": False},
     "zero_experts widen a router"),
    ({"layer_types": None, "latent_q_rank": None, "latent_kv_rank": None,
      "latent_nope_head_dim": None, "latent_rope_head_dim": None,
      "latent_value_head_dim": None}, "give layer_types too"),
])
def test_config_refuses_what_it_cannot_build(bad, reason):
    with pytest.raises(ValueError, match=reason):
        TransformerConfig(**{**KEYS, **bad})
