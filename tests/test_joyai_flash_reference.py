"""A model of ``latent_attention`` layers (multi-head latent attention: one
low-rank latent a token in the place of per-head keys and values, a rotated
key part all heads share, sigmoid-routed experts beside a shared one)
against the plain reference ``benchmark/reference/joyai_flash.py``, on
seeded weights at tiny sizes: ``model.apply`` and what a configuration
builds here; the three serving programs through the pool's latent pages are
in ``test_joyai_flash_programs.py`` and ``ServeEngine`` itself in
``test_joyai_flash_engine.py`` (shared: ``joyai_flash_tiny.py``).

Tolerance: everything here is float32 at ``highest`` matmul precision.  The
program expands a chunk's keys a block at a time under an online softmax and
decodes in the ABSORBED form (the query taken into the latent space, every
head over the one cached row), the reference expands every head over the
whole sequence, so they differ by the order of float32 sums: measured 4e-7
on logits of magnitude 0.5.  ``ATOL`` is 2e-5; a cache kept in bfloat16 is
out by 2.5e-4 (twelve times the tolerance) and bfloat16 compute by more
(``test_a_bfloat16_cache_is_outside_the_tolerance`` there,
``test_bf16_compute_is_outside_the_tolerance`` here), so a run in the next
precision down fails every case here.
"""

from __future__ import annotations

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torch_automatic_distributed_neural_network_tpu.inference import decode
from torch_automatic_distributed_neural_network_tpu.models.transformer_core import (
    DecoderLM,
    LatentAttention,
    SparseMLP,
    TransformerConfig,
    deinterleave,
)

from joyai_flash_tiny import (
    ATOL,
    BENCH,
    _highest,
    KEYS,
    _model,
    _params,
    RANK,
    ref,
    ROT,
    _tokens,
    _want,
    weights,
    _without_rotated_key,
)

pytestmark = pytest.mark.usefixtures("_highest")


# -- the model -----------------------------------------------------------------


def test_the_programs_parameters_are_the_references():
    abstract = jax.eval_shape(_model().init, jax.random.key(0),
                              np.zeros((1, 8), np.int32))["params"]
    assert ({k: tuple(v.shape) for k, v in weights.unnest(abstract).items()}
            == ref.param_shapes(KEYS))


@pytest.mark.parametrize("control", [
    "none", "a_key_without_rotation", "pairs_side_by_side", "top_k",
    "route_scale"])
def test_model_apply_matches_reference(control):
    """Two sequences of 70 positions in one batch; and for each control the
    reference with that one thing changed lies far outside the tolerance, so
    the comparison does test what the name says (on random weights the test
    has to see position: the rotated key part zeroed must fail)."""
    flat = _params()
    toks = np.stack([_tokens(70, 1), _tokens(70, 2)])
    got = np.asarray(_model().apply({"params": weights.nest(flat)}, toks))
    want = np.asarray(ref.forward_logits(flat, KEYS, toks))
    np.testing.assert_allclose(got, want, atol=ATOL, rtol=0)
    if control == "none":
        return
    keys, leaves = KEYS, flat
    if control == "a_key_without_rotation":
        leaves = _without_rotated_key(flat)
    elif control == "pairs_side_by_side":
        # rotate-half pairing on the weights as they are: another model
        half = np.concatenate([np.arange(0, ROT, 2), np.arange(1, ROT, 2)])
        inv = np.argsort(half)
        n = KEYS["latent_nope_head_dim"]
        leaves = {k: (v.at[:, RANK:].set(v[:, RANK:][:, inv])
                      if k.endswith("attn/kv_a_proj/kernel") else v)
                  for k, v in flat.items()}
        assert n  # (q_rope's columns left alone: q and k now pair apart)
    elif control == "top_k":
        keys = {**KEYS, "experts_per_token": 3}
    elif control == "route_scale":
        keys = {**KEYS, "route_scale": 1.0}
    off = np.asarray(ref.forward_logits(leaves, keys, toks))
    assert np.abs(off - got).max() > 100 * ATOL, control


def test_bf16_compute_is_outside_the_tolerance():
    flat = _params()
    toks = _tokens(40, 1)[None]
    got = np.asarray(_model(dtype=jnp.bfloat16).apply(
        {"params": weights.nest(flat)}, toks))
    assert np.abs(got - _want(flat, toks[0])).max() > 20 * ATOL


def test_deinterleave_brings_pairs_to_the_halves():
    x = jnp.arange(8.0)[None]
    np.testing.assert_array_equal(
        np.asarray(deinterleave(x))[0], [0, 2, 4, 6, 1, 3, 5, 7])


def test_the_absorbed_form_is_the_expanded_one():
    """One layer's mixer on 24 positions: every head's scores over expanded
    keys and its sum over expanded values (``expand``), against the query
    taken into the latent space, all heads over the one row a key, and the
    result taken out again (``absorb``, ``lift``)."""
    cfg = _model().cfg
    own = {"params": weights.nest(_params())["layers_1"]["attn"]}
    mixer = LatentAttention(cfg)
    x = jnp.asarray(np.random.RandomState(0).randn(1, 24, 48), jnp.float32)
    pos = jnp.arange(24)[None]
    q_nope, q_rope, latent = mixer.apply(own, x, pos, method="project")
    assert latent.shape == (1, 24, RANK + ROT) == (1, 24, *cfg.page_row(
        "latent_attention"))
    c, k_r = latent[..., :RANK], latent[..., RANK:]
    mask = jnp.tril(jnp.ones((24, 24), bool))
    scale = (KEYS["latent_nope_head_dim"] + ROT) ** -0.5

    k_nope, v = mixer.apply(own, c, method="expand")
    s = (jnp.einsum("bqhd,bkhd->bhqk", q_nope, k_nope)
         + jnp.einsum("bqhd,bkd->bhqk", q_rope, k_r)) * scale
    p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), -1)
    expanded = jnp.einsum("bhqk,bkhd->bqhd", p, v)

    q_lat = mixer.apply(own, q_nope, q_rope, method="absorb")
    s2 = jnp.einsum("bqhf,bkf->bhqk", q_lat, latent) * scale
    np.testing.assert_allclose(np.asarray(s2), np.asarray(s), atol=1e-6)
    p2 = jax.nn.softmax(jnp.where(mask, s2, -jnp.inf), -1)
    absorbed = mixer.apply(own, jnp.einsum("bhqk,bkc->bqhc", p2, c),
                           method="lift")
    np.testing.assert_allclose(np.asarray(absorbed), np.asarray(expanded),
                               atol=1e-6, rtol=0)
    # and the whole mixer is the expanded form
    whole = mixer.apply(own, x, pos)
    np.testing.assert_allclose(
        np.asarray(whole),
        np.asarray(mixer.apply(own, expanded, method="out_proj")), atol=1e-6)


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


def test_parameter_counts_are_the_published_layers():
    """26,347,520 of attention a layer, 182,589,696 an expert layer held
    here (32 of 256 experts), 3,605,789,440 in all at the cell's cut, and
    ``num_params`` is what ``model.init`` builds."""
    with open(os.path.join(BENCH, "configs", "joyai-llm-flash-ep8.json")) as f:
        doc = json.load(f)
    cfg = TransformerConfig(**doc["model"])
    assert cfg.mixer_params("latent_attention") == 26_347_520
    router, expert = 2048 * 256 + 256, 3 * 2048 * 768
    assert (cfg.mixer_params("latent_attention") + router + 33 * expert
            + 2 * 2048) == 182_589_696
    assert cfg.num_params() == 3_605_789_440 == doc["parameters"]
    built = jax.eval_shape(DecoderLM(cfg).init, jax.random.key(0),
                           np.zeros((1, 8), np.int32))["params"]
    assert cfg.num_params() == sum(
        int(np.prod(x.shape)) for x in jax.tree.leaves(built))
    # the widths as published, and what is cut
    for key, want in (("hidden_size", 2048), ("q_lora_rank", 1536),
                      ("kv_lora_rank", 512), ("qk_nope_head_dim", 128),
                      ("qk_rope_head_dim", 64), ("v_head_dim", 128),
                      ("num_attention_heads", 32), ("intermediate_size", 7168),
                      ("moe_intermediate_size", 768),
                      ("num_experts_per_tok", 8),
                      ("routed_scaling_factor", 2.5)):
        assert doc[key] == doc["source_keys"][key] == want, key
    assert sorted(doc["reduced"]) == sorted(
        k for k in doc["source_keys"] if doc[k] != doc["source_keys"][k])
    assert (doc["num_hidden_layers"], doc["n_routed_experts"],
            doc["vocab_size"], doc["num_nextn_predict_layers"]) \
        == (20, 32, 16160, 0)
    assert doc["model"]["experts_published"] == 256


def test_the_latents_norms_stay_float32_when_the_rest_is_rounded():
    cfg = _model(dtype=jnp.bfloat16).cfg
    held = decode.compute_dtype_params(weights.nest(_params()), cfg)
    attn = held["layers_1"]["attn"]
    assert attn["q_a_norm"]["scale"].dtype == jnp.float32
    assert attn["kv_a_norm"]["scale"].dtype == jnp.float32
    assert attn["kv_b_proj"]["kernel"].dtype == jnp.bfloat16
    assert held["layers_1"]["mlp"]["router"]["kernel"].dtype == jnp.float32


# -- the configuration ---------------------------------------------------------


@pytest.mark.parametrize("bad,reason", [
    # (no query rank is a model now: queries without a bottleneck; and so
    # is a latent layer that does not rotate: test_kimi_linear_reference.py)
    ({"latent_kv_rank": None}, "a latent_attention layer needs"),
    ({"latent_rope_head_dim": 3}, "even latent_rope_head_dim"),
    ({"latent_nope_head_dim": None}, "queries without a bottleneck"),
    ({"layer_types": ["full_attention"] * 4}, "layer_types has none"),
])
def test_config_refuses_what_it_cannot_build(bad, reason):
    with pytest.raises(ValueError, match=reason):
        TransformerConfig(**{**KEYS, **bad})
