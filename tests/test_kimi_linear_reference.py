"""A hybrid of Kimi Delta Attention and latent attention (``layer_types``
with ``linear_attention`` layers whose decay is a vector a head,
``linear_decay="channel"``, a ``latent_attention`` layer WITHOUT a query
bottleneck and without rotation among every four, sigmoid-routed experts
beside a shared one) against the plain reference
``benchmark/reference/kimi_linear.py``, on seeded weights at tiny sizes:
``model.apply`` and what a configuration builds here; the three serving
programs through a pool that holds state rows AND latent pages are in
``test_kimi_linear_programs.py`` and ``ServeEngine`` itself in
``test_kimi_linear_engine.py`` (shared: ``kimi_linear_tiny.py``).

Tolerance: everything here is float32 at ``highest`` matmul precision; the
program runs the chunk form of the recurrence (a sub-chunk's channel-wise
decays folded into its operands block by block) and attends a chunk's
latents a key block at a time, the reference scans the tokens and expands
every head over the whole sequence, so they differ by the order of float32
sums and by the chunk form's solve: measured 5e-7 on logits of magnitude
0.6.  ``ATOL`` is 2e-5; bfloat16 compute is out by 1.7e-2 and bfloat16
latent pages alone (two of the eight layers) by 1.2e-4, six times the
tolerance, so a run in the next precision down fails every case here.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torch_automatic_distributed_neural_network_tpu.models.transformer_core import (
    DecoderLM,
    SparseMLP,
    TransformerConfig,
)
from torch_automatic_distributed_neural_network_tpu.ops import gated_delta as gd

from kimi_linear_tiny import (
    ATOL,
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
)

pytestmark = pytest.mark.usefixtures("_highest")


# -- the model -----------------------------------------------------------------


def test_the_programs_parameters_are_the_references():
    abstract = jax.eval_shape(_model().init, jax.random.key(0),
                              np.zeros((1, 8), np.int32))["params"]
    assert ({k: tuple(v.shape) for k, v in weights.unnest(abstract).items()}
            == ref.param_shapes(KEYS))


def test_decays_are_the_familys_a_channel():
    """``weights_gdn.decay_leaf`` finds ``dt_bias`` by its path whatever its
    shape: the [H d_k] one is drawn by the family's initialisation too, so
    half the (head, channel) pairs keep more than 0.9 of their state a
    token (under 0.02 n each would keep half: a lost carry would pass)."""
    flat = _params()
    kept = []
    for i in LINEAR:
        A, dt = flat[f"layers_{i}/attn/A_log"], flat[f"layers_{i}/attn/dt_bias"]
        assert A.shape == (4,) and dt.shape == (4 * 8,)
        kept.append(np.exp(-np.exp(A)[:, None] * np.log1p(np.exp(
            dt.reshape(4, 8)))))
    kept = np.concatenate(kept).ravel()
    assert np.median(kept) > 0.9 and kept.min() < 0.9


def test_model_apply_matches_reference():
    """Two sequences of 70 positions (the chunk form's sub-chunk is 64: one
    whole and a part) in one batch; and the reference whose decay is ONE
    number a head (a head's channels all given their mean bias) is far from
    both, so the comparison does test the decay a channel."""
    flat = _params()
    toks = np.stack([_tokens(70, 1), _tokens(70, 2)])
    got = np.asarray(jax.jit(_model().apply)(
        {"params": weights.nest(flat)}, toks))
    want = np.asarray(ref.forward_logits(flat, KEYS, toks))
    np.testing.assert_allclose(got, want, atol=ATOL, rtol=0)
    a_head = {k: (jnp.repeat(v.reshape(4, 8).mean(-1), 8)
                  if k.endswith("attn/dt_bias") else
                  jnp.zeros_like(v) if k.endswith("attn/f_b_proj/kernel")
                  else v) for k, v in flat.items()}
    off = np.asarray(ref.forward_logits(a_head, KEYS, toks))
    assert np.abs(off - got).max() > 100 * ATOL


def test_bf16_compute_is_outside_the_tolerance():
    flat = _params()
    toks = _tokens(40, 1)[None]
    got = np.asarray(jax.jit(_model(dtype=jnp.bfloat16).apply)(
        {"params": weights.nest(flat)}, toks))
    assert np.abs(got - _want(flat, toks[0])).max() > 20 * ATOL


def test_the_latent_layer_sees_no_position():
    """``mla_use_nope``: the configuration rotates nothing
    (``layer_rotates`` false on a latent layer), so a latent layer's output
    at a position depends on WHICH tokens came before and not on where: the
    same model with ``rope_layers="all"`` (which rotates the 4 shared
    numbers) is another model on the same weights."""
    cfg = _model().cfg
    assert not cfg.layer_rotates("latent_attention")
    flat = _params()
    toks = _tokens(30, 4)[None]
    got = np.asarray(jax.jit(_model().apply)(
        {"params": weights.nest(flat)}, toks))
    rotating = _model({**KEYS, "rope_layers": "all"})
    rotated = np.asarray(jax.jit(rotating.apply)(
        {"params": weights.nest(flat)}, toks))
    assert np.abs(got - rotated).max() > 10 * ATOL


def test_the_mixer_with_equal_channels_is_the_scalar_rules():
    """The recurrence the KDA layers run, given one decay for all of a
    head's channels, is ``gated_delta_recurrent``'s scalar rule (the two
    mixers are one rule; ``tests/test_gated_delta.py`` holds every form to
    it)."""
    ks = jax.random.split(jax.random.key(0), 6)
    T, H, dk, dv = 50, 4, 8, 16
    q = gd.l2norm(jax.random.normal(ks[0], (T, H, dk))) * dk ** -0.5
    k = gd.l2norm(jax.random.normal(ks[1], (T, H, dk)))
    v = jax.random.normal(ks[2], (T, H, dv))
    beta = jax.nn.sigmoid(jax.random.normal(ks[3], (T, H)))
    g = -jax.random.uniform(ks[4], (T, H), minval=1e-3, maxval=0.3)
    o_ref = gd.gated_delta_recurrent(q, k, v, g, beta,
                                     jnp.zeros((H, dk, dv)))[0]
    wide = jnp.broadcast_to(g[..., None], (T, H, dk))
    np.testing.assert_allclose(ref.delta_rule(q, k, v, wide, beta), o_ref,
                               atol=1e-6)
    np.testing.assert_allclose(
        gd.kda_chunk_xla(q, k, v, wide, beta, jnp.zeros((H, dk, dv)))[0],
        o_ref, atol=1e-5)


def test_eight_shares_add_up_to_the_uncut_layer():
    """The chip's share of an expert-parallel deployment at top 8: 32
    experts over eight chips, four each.  The routed parts of the eight
    shares, with the shared expert (which every chip computes alike) counted
    once, are the uncut reference layer."""
    base = {**KEYS, "experts_published": 32, "experts_per_token": 8}
    whole = {**base, "experts_held": 32, "first_expert": 0}
    flat = _params(whole)
    layer = ref.sub(flat, "layers_2")
    x = jnp.asarray(np.random.RandomState(1).randn(40, 48), jnp.float32)
    want = np.asarray(ref.ffn(layer, x, whole, True, "f32"))
    common = np.asarray(ref.shared(layer, x, "f32"))
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
        part = np.asarray(ref.ffn(
            {**layer, **{"mlp/" + n: mine[n] for n in
                         ("experts_gate", "experts_up", "experts_down")}},
            x, keys, True, "f32"))
        np.testing.assert_allclose(np.asarray(y), part, atol=ATOL, rtol=0)
    assert pairs == 40 * 8  # every pair lands on exactly one chip
    np.testing.assert_allclose(total, want, atol=8 * ATOL, rtol=0)


def test_parameter_counts_are_the_issues_arithmetic():
    """The configuration file's ``parameters`` is ISSUE 41's sum, term by
    term, and what ``model.init`` builds at the published widths."""
    config = _published()
    cfg = TransformerConfig(**config["model"])
    d, H = 2304, 32
    kda = (3 * d * H * 128 + H * 128 * d + 2 * (d * 128 + 128 * H * 128)
           + d * H + 4 * 3 * H * 128 + H + H * 128 + 128)
    latent = (d * H * 192 + d * 576 + 512 + 512 * H * 256 + H * 128 * d)
    assert (kda, latent) == (39_514_272, 29_114_880)
    assert cfg.mixer_params("linear_attention") == kda
    assert cfg.mixer_params("latent_attention") == latent
    experts = d * 256 + 256 + 33 * 3 * d * 1024
    assert experts == 234_160_384
    total = (12 * kda + 4 * latent + 16 * 2 * d + 3 * d * 9216 + 15 * experts
             + 2 * 20480 * d + d)
    assert total == cfg.num_params() == config["parameters"] == 4_261_185_408
    built = jax.eval_shape(DecoderLM(cfg).init, jax.random.key(0),
                           np.zeros((1, 8), np.int32))["params"]
    assert total == sum(int(np.prod(x.shape))
                        for x in jax.tree.leaves(built))
    # every published width, as published
    src = config["source_keys"]
    assert (cfg.d_model, cfg.d_ff, cfg.expert_d_ff, cfg.experts_per_token,
            cfg.experts_published, cfg.route_scale, cfg.norm_eps) == (
        src["hidden_size"], src["intermediate_size"],
        src["moe_intermediate_size"], src["num_experts_per_token"],
        src["num_experts"], src["routed_scaling_factor"],
        src["rms_norm_eps"])
    lin = src["linear_attn_config"]
    assert (cfg.linear_value_heads, cfg.linear_key_head_dim,
            cfg.linear_value_head_dim, cfg.linear_conv_kernel) == (
        lin["num_heads"], lin["head_dim"], lin["head_dim"],
        lin["short_conv_kernel_size"])
    assert (cfg.latent_q_rank, cfg.latent_kv_rank, cfg.latent_nope_head_dim,
            cfg.latent_rope_head_dim, cfg.latent_value_head_dim) == (
        src["q_lora_rank"], src["kv_lora_rank"], src["qk_nope_head_dim"],
        src["qk_rope_head_dim"], src["v_head_dim"])
    # the layers held: published layers 1-16, numbered from 1
    held = config["linear_attn_config"]
    kinds = list(cfg.layer_types)
    assert [i + 1 for i, k in enumerate(kinds) if k == "linear_attention"] \
        == held["kda_layers"]
    assert [i + 1 for i, k in enumerate(kinds) if k == "latent_attention"] \
        == held["full_attn_layers"]
    assert set(held["kda_layers"]) < set(lin["kda_layers"])


def test_decay_leaves_stay_float32_when_the_rest_is_rounded():
    """``compute_dtype_params`` rounds the projections (the low-rank pairs
    too) and keeps the decay's leaves, the filters and the norms."""
    from torch_automatic_distributed_neural_network_tpu.inference import decode

    cfg = _model(dtype=jnp.bfloat16).cfg
    held = weights.unnest(jax.eval_shape(
        lambda p: decode.compute_dtype_params(p, cfg),
        weights.nest(_params())))
    for name in ("A_log", "dt_bias", "conv", "o_norm/scale"):
        assert held[f"layers_0/attn/{name}"].dtype == jnp.float32, name
    for name in ("f_a_proj", "f_b_proj", "g_a_proj", "g_b_proj", "q_proj"):
        assert held[f"layers_0/attn/{name}/kernel"].dtype == jnp.bfloat16
    assert held["layers_3/attn/kv_a_norm/scale"].dtype == jnp.float32
    assert held["layers_3/attn/q_proj/kernel"].dtype == jnp.bfloat16


# -- the configuration ---------------------------------------------------------


@pytest.mark.parametrize("bad,reason", [
    ({"latent_kv_rank": None}, "a latent_attention layer needs"),
    ({"rope_layers": "all", "latent_rope_head_dim": 3},
     "that rotates.*even latent_rope_head_dim"),
    ({"linear_key_heads": 3}, "linear_key_heads == linear_value_heads"),
    ({"layer_types": ["latent_attention"] * 8},
     "linear_decay.*layer_types has none"),
    ({"layer_types": ["linear_attention"] * 8},
     "latent_kv_rank.*layer_types has none"),
])
def test_config_refuses_what_it_cannot_build(bad, reason):
    with pytest.raises(ValueError, match=reason):
        TransformerConfig(**{**KEYS, **bad})


@pytest.mark.parametrize("keys", [
    {"latent_rope_head_dim": 3},  # odd, and never rotated
    {"latent_q_rank": 12},  # a bottleneck after all
    {"linear_decay": "head"},  # the low-rank maps with a decay a head
    {"linear_decay_rank": None, "linear_gate_rank": None,
     "linear_gate_act": "silu"},
])
def test_config_builds_what_its_data_describes(keys):
    """The mixers' data are free of each other: an unrotated latent layer of
    any width, queries with or without a bottleneck, a decay a head or a
    channel through one matrix or two; ``num_params`` counts each."""
    cfg = TransformerConfig(**{**KEYS, **keys})
    built = jax.eval_shape(DecoderLM(cfg).init, jax.random.key(0),
                           np.zeros((1, 8), np.int32))["params"]
    assert cfg.num_params() == sum(int(np.prod(x.shape))
                                   for x in jax.tree.leaves(built))
    assert np.isfinite(np.asarray(jax.jit(DecoderLM(dataclasses.replace(
        cfg, dtype=jnp.float32, remat=False)).apply)(
            {"params": jax.jit(DecoderLM(cfg).init)(
                jax.random.key(1), np.zeros((1, 8), np.int32))["params"]},
            _tokens(12, 1)[None]))).all()
