"""A hybrid of gated grouped-query attention and 64-head-style Kimi Delta
Attention (``layer_types`` with a ``full_attention`` layer FIRST, over the
bare embeddings, ``attn_gate`` and no rotation, ``pos="none"``; three
``linear_attention`` layers behind it whose decay is a vector a head and
whose ``beta`` reaches 2, ``linear_neg_eigval``; a sigmoid-routed expert FFN
beside a shared expert in EVERY layer, ``n_dense_layers=0``) against the
plain reference ``benchmark/reference/solar_open2.py``, on seeded weights at
tiny sizes: ``model.apply``, the share of an expert-parallel layer, and what
the configuration builds at the published widths.  ``ServeEngine`` itself is
in ``test_solar_open2_engine.py`` (shared: ``solar_open2_tiny.py``).

Tolerance: everything here is float32 at ``highest`` matmul precision; the
program runs the chunk form of the recurrence (a sub-chunk's channel-wise
decays folded into its operands, a triangular solve) and one attention call
over all heads, the reference scans the tokens and attends a KV head at a
time, so they differ by the order of float32 sums and by the chunk form's
solve: measured 1.5e-6 on logits of magnitude 0.57.  ``ATOL`` is 2e-5;
bfloat16 compute is out by 2e-2, a thousand times the tolerance, so a run in
the next precision down fails every case here.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torch_automatic_distributed_neural_network_tpu.models.transformer_core import (
    DecoderLM,
    SparseMLP,
    TransformerConfig,
)

from solar_open2_tiny import (
    ATOL,
    FULL,
    _highest,
    KEYS,
    LINEAR,
    _model,
    _params,
    _published,
    ref,
    _tokens,
    _want,
    weights,
    weights_gdn,
)

pytestmark = pytest.mark.usefixtures("_highest")


def test_the_programs_parameters_are_the_references():
    abstract = jax.eval_shape(_model().init, jax.random.key(0),
                              np.zeros((1, 8), np.int32))["params"]
    assert ({k: tuple(v.shape) for k, v in weights.unnest(abstract).items()}
            == ref.param_shapes(KEYS))


def test_the_reference_is_its_own_file():
    """Written from the equations: it imports nothing of the program, of the
    benchmark's library or of another reference."""
    with open(ref.__file__) as f:
        imports = [l.split()[1] for l in f if l.startswith(("import ",
                                                            "from "))]
    assert sorted(imports) == ["__future__", "functools", "jax", "jax.numpy",
                               "math"]


def test_model_apply_matches_reference():
    """Two sequences of 70 positions (the chunk form's sub-chunk is 64: one
    whole and a part) in one batch.  The comparison does test what tells
    this model apart: the reference with ``beta`` in (0, 1), with a rotation
    it does not have (the program's, ``pos="rope"``), or with the attention
    layer's gate left open is far from both."""
    flat = _params()
    toks = np.stack([_tokens(70, 1), _tokens(70, 2)])
    nested = {"params": weights.nest(flat)}
    got = np.asarray(jax.jit(_model().apply)(nested, toks))
    want = np.asarray(ref.forward_logits(flat, KEYS, toks))
    np.testing.assert_allclose(got, want, atol=ATOL, rtol=0)
    weak = np.asarray(ref.forward_logits(
        flat, {**KEYS, "linear_neg_eigval": False}, toks))
    assert np.abs(weak - got).max() > 100 * ATOL
    rotated = np.asarray(jax.jit(_model({**KEYS, "pos": "rope"}).apply)(
        nested, toks))
    assert np.abs(rotated - got).max() > 100 * ATOL
    # a gate of sigmoid(0) = 1/2 on every channel is another model
    ungated = {k: jnp.zeros_like(v) if k.endswith("attn/gate_proj/kernel")
               else v for k, v in flat.items()}
    assert np.abs(np.asarray(ref.forward_logits(ungated, KEYS, toks))
                  - got).max() > 100 * ATOL


def test_bf16_compute_is_outside_the_tolerance():
    flat = _params()
    toks = _tokens(40, 1)[None]
    got = np.asarray(jax.jit(_model(dtype=jnp.bfloat16).apply)(
        {"params": weights.nest(flat)}, toks))
    assert np.abs(got - _want(flat, toks[0])).max() > 20 * ATOL


def test_the_first_layer_sees_no_position():
    """Layer 0 is attention over the bare embeddings and nothing is rotated
    (``use_rope: false``): its output at the LAST position does not care in
    which order the tokens before it came; the whole model does, through the
    KDA layers' convolutions and decays."""
    cfg = _model().cfg
    assert cfg.layer_types[0] == "full_attention"
    assert not cfg.layer_rotates("full_attention")
    flat = _params()
    toks = _tokens(24, 5)
    swapped = np.concatenate([toks[:-1][::-1], toks[-1:]])
    first = {**KEYS, "n_layers": 1, "layer_types": ["full_attention"]}
    one = {k: v for k, v in flat.items()
           if not k.startswith("layers_") or k.startswith("layers_0/")}
    a, b = (np.asarray(ref.forward_logits(one, first, t[None]))[0, -1]
            for t in (toks, swapped))
    np.testing.assert_allclose(a, b, atol=1e-6)
    a, b = (_want(flat, t)[-1] for t in (toks, swapped))
    assert np.abs(a - b).max() > 100 * ATOL


def test_decays_are_the_familys_at_both_shapes():
    """``weights_gdn.decay_leaf`` finds ``A_log`` and ``dt_bias`` by their
    path whatever their shape: here [4] and [32], at the published widths
    [64] and [8192].  Half the (head, channel) pairs then keep more than 0.9
    of their state a token (under 0.02 n each would keep half: a lost carry
    would pass)."""
    flat = _params()
    kept = []
    for i in LINEAR:
        A = flat[f"layers_{i}/attn/A_log"]
        dt = flat[f"layers_{i}/attn/dt_bias"]
        assert A.shape == (4,) and dt.shape == (4 * 8,)
        kept.append(np.exp(-np.exp(A)[:, None] * np.log1p(np.exp(
            dt.reshape(4, 8)))))
    kept = np.concatenate(kept).ravel()
    assert np.median(kept) > 0.9 and kept.min() < 0.9
    shapes = ref.param_shapes(_published()["model"])
    found = {p: s for p, s in shapes.items() if weights_gdn.decay_leaf(
        jax.random.key(0), p, (2,)) is not None}
    assert found == {f"layers_{i}/attn/{leaf}": shape for i in (1, 2, 3)
                     for leaf, shape in (("A_log", (64,)),
                                         ("dt_bias", (8192,)))}


def test_eight_shares_add_up_to_the_uncut_layer():
    """The chip's share of an expert-parallel deployment at top 8: 32
    experts over eight chips, four each.  The routed parts of the eight
    shares, with the shared expert (which every chip computes alike) counted
    once, are the uncut reference layer; and every chip's mixer, which it
    computes for its own tokens whole, is the uncut layer's as it stands."""
    base = {**KEYS, "experts_published": 32, "experts_per_token": 8}
    whole = {**base, "experts_held": 32, "first_expert": 0}
    flat = _params(whole)
    layer = ref.leaves_of(flat, "layers_2")
    x = jnp.asarray(np.random.RandomState(1).randn(40, 48), jnp.float32)
    want = np.asarray(ref.expert_ffn(layer, x, whole, "f32"))
    common = np.asarray(ref.swiglu(
        x, layer["mlp/shared/gate_proj/kernel"],
        layer["mlp/shared/up_proj/kernel"],
        layer["mlp/shared/down_proj/kernel"], "f32"))
    total, pairs = common.copy(), 0
    for chip in range(8):
        keys = {**base, "experts_held": 4, "first_expert": 4 * chip}
        mine = dict(weights.nest(flat)["layers_2"]["mlp"])
        for name in ("experts_gate", "experts_up", "experts_down"):
            mine[name] = mine[name][4 * chip:4 * chip + 4]
        y, stats = SparseMLP(TransformerConfig(**keys, dtype=jnp.float32)
                             ).apply({"params": mine}, x)
        total += np.asarray(y) - common
        pairs += int(stats["pairs"])
        part = np.asarray(ref.expert_ffn(
            {**layer, **{"mlp/" + n: mine[n] for n in
                         ("experts_gate", "experts_up", "experts_down")}},
            x, keys, "f32"))
        np.testing.assert_allclose(np.asarray(y), part, atol=ATOL, rtol=0)
    assert pairs == 40 * 8  # every pair lands on exactly one chip
    np.testing.assert_allclose(total, want, atol=8 * ATOL, rtol=0)
    # what no chip's share changes: the mixers read no expert key
    for kind, mixer in (("full_attention", ref.gated_attention),
                        ("linear_attention", ref.kimi_delta_attention)):
        i = KEYS["layer_types"].index(kind)
        p = ref.leaves_of(flat, f"layers_{i}")
        np.testing.assert_array_equal(
            np.asarray(mixer(p, x, whole, "f32")),
            np.asarray(mixer(p, x, {**base, "experts_held": 4,
                                    "first_expert": 12}, "f32")))


def test_parameter_counts_are_the_issues_arithmetic():
    """The configuration file's ``parameters`` is ISSUE 49's sum, term by
    term, and what ``model.init`` builds at the published widths; whole, the
    same shapes are the published 250B-A15B."""
    config = _published()
    cfg = TransformerConfig(**config["model"])
    d, H, KV, c = 4096, 64, 8, 128
    kda = (3 * d * H * c + H * c * d + 2 * (d * 128 + 128 * H * c)
           + d * H + 4 * 3 * H * c + H + H * c + c)
    gqa = 3 * d * H * c + 2 * d * KV * c  # q, gate and o; k and v
    assert (kda, gqa) == (137_732_288, 109_051_904)
    assert cfg.mixer_params("linear_attention") == kda
    assert cfg.mixer_params("full_attention") == gqa
    expert = 3 * d * 1280
    router = d * 320 + 320
    assert (router, expert) == (1_311_040, 15_728_640)
    layer = router + expert + 40 * expert
    assert layer == 646_185_280
    total = (3 * kda + gqa + 4 * 2 * d + 4 * layer + 2 * 24576 * d + d)
    assert total == cfg.num_params() == config["parameters"] == 3_308_353_344
    built = jax.eval_shape(DecoderLM(cfg).init, jax.random.key(0),
                           np.zeros((1, 8), np.int32))["params"]
    assert total == sum(int(np.prod(x.shape))
                        for x in jax.tree.leaves(built))
    assert ({k: tuple(v.shape) for k, v in weights.unnest(built).items()}
            == ref.param_shapes(config["model"]))
    # held: 6.21 GB of bf16 layers + 0.81 GB of float32 embedding and head
    tables = 2 * 24576 * d
    assert round(2 * (total - tables) / 1e9, 2) == 6.21
    assert round(4 * tables / 1e9, 2) == 0.81
    # whole: 12 attention and 36 KDA layers, 320 experts, 196,608 rows
    whole = (36 * kda + 12 * gqa + 48 * 2 * d
             + 48 * (router + expert + 320 * expert) + 2 * 196608 * d + d)
    active = whole - 48 * (320 - 8) * expert
    assert (round(whole / 1e9, 2), round(active / 1e9, 2)) == (250.29, 14.74)
    # every published width, as published
    src = config["source_keys"]
    assert (cfg.d_model, cfg.n_heads, cfg.kv_heads, cfg.head_dim, cfg.d_ff,
            cfg.expert_d_ff, cfg.experts_per_token, cfg.experts_published,
            cfg.shared_experts, cfg.route_scale, cfg.norm_eps,
            cfg.n_dense_layers, cfg.tie_embeddings) == (
        src["hidden_size"], src["num_attention_heads"],
        src["num_key_value_heads"], src["head_dim"],
        src["intermediate_size"], src["moe_intermediate_size"],
        src["num_experts_per_tok"], src["n_routed_experts"],
        src["n_shared_experts"], src["routed_scaling_factor"],
        src["rms_norm_eps"], src["first_k_dense_replace"],
        src["tie_word_embeddings"])
    lin = src["linear_attn_config"]
    assert (cfg.linear_value_heads, cfg.linear_key_heads,
            cfg.linear_key_head_dim, cfg.linear_value_head_dim,
            cfg.linear_conv_kernel) == (
        lin["num_heads"], lin["num_heads"], lin["head_dim"], lin["head_dim"],
        lin["short_conv_kernel_size"])
    assert (cfg.linear_neg_eigval, cfg.attn_gate, cfg.pos != "rope",
            cfg.linear_decay_rank is None) == (
        src["kda_allow_neg_eigval"], src["use_gqa_gate"],
        not src["use_rope"], src["kda_use_full_proj"])
    # the layers held: published layers 0-3, numbered from 0
    kinds = list(cfg.layer_types)
    assert [i for i, k in enumerate(kinds) if k == "full_attention"] \
        == config["gqa_layers"] == src["gqa_layers"][:1]
    assert kinds.count("linear_attention") == src["gqa_interval"]
    assert src["gqa_layers"] == list(range(0, src["num_hidden_layers"],
                                           src["gqa_interval"] + 1))


def test_held_leaves_keep_the_dtypes_the_reference_is_given():
    """``compute_dtype_params`` rounds the projections (the attention gate's
    and the low-rank pairs too) and the expert stacks, and keeps the decay's
    leaves, the filters, the norms, the router and both tables."""
    from torch_automatic_distributed_neural_network_tpu.inference import decode

    cfg = _model(dtype=jnp.bfloat16).cfg
    held = weights.unnest(jax.eval_shape(
        lambda p: decode.compute_dtype_params(p, cfg),
        weights.nest(_params())))
    i, j = LINEAR[0], FULL[0]
    for name in ("A_log", "dt_bias", "conv", "o_norm/scale"):
        assert held[f"layers_{i}/attn/{name}"].dtype == jnp.float32, name
    for name in ("f_a_proj", "f_b_proj", "g_a_proj", "g_b_proj", "b_proj"):
        assert held[f"layers_{i}/attn/{name}/kernel"].dtype == jnp.bfloat16
    for name in ("q_proj", "k_proj", "gate_proj", "o_proj"):
        assert held[f"layers_{j}/attn/{name}/kernel"].dtype == jnp.bfloat16
    assert held[f"layers_{j}/mlp/experts_down"].dtype == jnp.bfloat16
    assert held[f"layers_{j}/mlp/router/kernel"].dtype == jnp.float32
    assert held["embed/embedding"].dtype == jnp.float32
    assert held["lm_head/kernel"].dtype == jnp.float32


@pytest.mark.parametrize("bad,reason", [
    ({"linear_key_heads": 3}, "linear_key_heads == linear_value_heads"),
    ({"layer_types": ["full_attention"] * 8},
     "linear_neg_eigval.*layer_types has none"),
    ({"experts_held": 14}, "experts 4..18 held"),
    ({"layer_types": None}, "describe linear_attention layers"),
    ({"experts_per_token": 17}, "17 a token, of 16 published"),
])
def test_config_refuses_what_it_cannot_build(bad, reason):
    with pytest.raises(ValueError, match=reason):
        TransformerConfig(**{**KEYS, **bad})


@pytest.mark.parametrize("keys", [
    {"linear_neg_eigval": False},  # beta in (0, 1)
    {"attn_gate": False},
    {"n_dense_layers": 1},  # a dense layer after all
    {"n_kv_heads": 8},  # no grouping
    {"layer_types": ["linear_attention"] * 3 + ["full_attention"],
     "n_layers": 4},  # the attention layer LAST in the period
])
def test_config_builds_what_its_data_describes(keys):
    """The composition's data are free of each other; ``num_params`` counts
    each."""
    cfg = TransformerConfig(**{**KEYS, **keys})
    built = jax.eval_shape(DecoderLM(cfg).init, jax.random.key(0),
                           np.zeros((1, 8), np.int32))["params"]
    assert cfg.num_params() == sum(int(np.prod(x.shape))
                                   for x in jax.tree.leaves(built))
