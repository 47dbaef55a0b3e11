"""A hybrid of Kimi Delta Attention and latent attention (``layer_types``
with ``linear_attention`` layers whose decay is a vector a head,
``linear_decay="channel"``, a ``latent_attention`` layer WITHOUT a query
bottleneck and without rotation among every four, sigmoid-routed experts
beside a shared one) against the plain reference
``benchmark/reference/kimi_linear.py``, on seeded weights at tiny sizes:
``model.apply``, the three serving programs through a pool that holds state
rows AND latent pages, and ``ServeEngine`` itself.

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
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import serve_by_hand

from torch_automatic_distributed_neural_network_tpu.inference.serve import (
    ServeEngine,
)
from torch_automatic_distributed_neural_network_tpu.inference.serve.kv_pool import (
    PagedKVPool,
    state_row_bytes,
)
from torch_automatic_distributed_neural_network_tpu.models.transformer_core import (
    DecoderLM,
    SparseMLP,
    TransformerConfig,
)
from torch_automatic_distributed_neural_network_tpu.obs.journal import Journal
from torch_automatic_distributed_neural_network_tpu.ops import gated_delta as gd

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
ATOL = 2e-5


def _load(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load(os.path.join(BENCH, "reference", "kimi_linear.py"),
            "kimi_linear_reference")
weights = _load(os.path.join(BENCH, "lib", "weights.py"), "bench_weights")
weights_gdn = _load(os.path.join(BENCH, "lib", "weights_gdn.py"),
                    "bench_weights_gdn")

CHUNK, BS = 8, 4
KEYS = dict(
    vocab_size=96, d_model=48, n_layers=8, n_heads=4, d_ff=80,
    max_seq_len=128, norm="rmsnorm", norm_eps=1e-5, act="swiglu", pos="rope",
    rope_layers="sliding", tie_embeddings=False,
    layer_types=(["linear_attention"] * 3 + ["latent_attention"]) * 2,
    linear_key_heads=4, linear_value_heads=4, linear_key_head_dim=8,
    linear_value_head_dim=16, linear_conv_kernel=4, linear_decay="channel",
    linear_decay_rank=6, linear_gate_rank=6, linear_gate_act="sigmoid",
    latent_kv_rank=16, latent_nope_head_dim=8, latent_rope_head_dim=4,
    latent_value_head_dim=8, n_dense_layers=1, experts_published=16,
    experts_held=4, first_expert=4, experts_per_token=4, shared_experts=1,
    expert_d_ff=24, score_func="sigmoid", route_norm=True, route_scale=2.446)
LINEAR = [i for i, k in enumerate(KEYS["layer_types"])
          if k == "linear_attention"]
LATENT = [i for i, k in enumerate(KEYS["layer_types"])
          if k == "latent_attention"]
RANK, ROT = KEYS["latent_kv_rank"], KEYS["latent_rope_head_dim"]


def _params(keys: dict = KEYS, seed: int = 3) -> dict:
    key = weights.seed_key(seed)
    shapes = ref.param_shapes(keys)
    flat = weights.flat(key, shapes)
    for path, shape in shapes.items():
        special = weights_gdn.decay_leaf(key, path, shape)
        if special is not None:
            flat[path] = special
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


def _published() -> dict:
    with open(os.path.join(BENCH, "configs", "kimi-linear-48b-ep8.json")) as f:
        return json.load(f)


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
    got = np.asarray(_model().apply({"params": weights.nest(flat)}, toks))
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
    got = np.asarray(_model(dtype=jnp.bfloat16).apply(
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
    got = np.asarray(_model().apply({"params": weights.nest(flat)}, toks))
    rotated = np.asarray(_model({**KEYS, "rope_layers": "all"}).apply(
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
def test_serving_programs_match_reference(impl):
    """A prompt of 21 tokens (three chunks of 8, the last PADDED: 5 real
    rows) and 30 decode steps through state rows and latent pages, in slot
    1 of 3: the logits of each chunk's last row and of every decode step are
    the reference's full forward pass's."""
    flat = _params()
    seq = _tokens(51, 5)
    got = Served(flat, impl=impl).sequence(1, seq, 21)
    assert sorted(got) == [7, 15] + list(range(20, 51))
    _close(got, _want(flat, seq))


def test_a_bfloat16_cache_is_outside_the_tolerance():
    """The same run with the latent pages in bfloat16."""
    flat = _params()
    seq = _tokens(51, 5)
    got = Served(flat, cache=jnp.bfloat16).sequence(1, seq, 21)
    want = _want(flat, seq)
    assert max(np.abs(r - want[p]).max() for p, r in got.items()) > 5 * ATOL


def test_a_chunk_that_carries_decode_rows_matches_reference():
    """``chunk_and_step``: slot 0 prefills 19 tokens in three chunks while
    slots 1 and 2 decode IN those chunks' calls (the chunk form on one row
    of a layer's state pool and the step form on two others, one call).
    The chunks' logits are the reference's; the decode rows are served the
    reference's first choice; and what they wrote is read by plain decode
    steps afterwards, whose logits are the reference's too."""
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
    assert sorted(got_a) == [7, 15, 18]
    for i in range(12):  # all three decode, a step each
        lg = sv.decode({0: a[19 + i], 1: b[13 + i], 2: c[9 + i]})
        got_a[19 + i], got_b[13 + i], got_c[9 + i] = lg[0], lg[1], lg[2]
    _close(got_a, want_a, "the chunk's slot")
    _close(got_b, want_b, "slot 1")
    _close(got_c, want_c, "slot 2")


def test_neighbouring_slots_do_not_touch_each_others_rows_or_pages():
    """Two requests in slots 0 and 1, their chunks and decode steps
    interleaved (one prefills while the other decodes, then both decode in
    one step): each follows its own reference."""
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


def test_a_reused_slot_reads_nothing_of_the_request_before():
    """A second request in a slot whose state rows and pages the first left
    behind: its first chunk starts its states from zeros (``pos0 == 0``),
    and its latent rows are read up to its own length alone."""
    flat = _params()
    sv = Served(flat)
    sv.sequence(2, _tokens(30, 9), 12)
    assert float(jnp.abs(sv.kv["k"][0][3]).max()) > 0  # slot 2 is row 3
    seq = _tokens(26, 10)
    _close(sv.sequence(2, seq, 9), _want(flat, seq))


def test_inactive_slots_write_the_null_row_and_the_null_block():
    """A decode step with slots 0 and 2 inactive: their rows (1 and 3) of
    every linear layer's state and tail are what they were, bit for bit;
    of a latent layer's pages only the null block and the active slot's
    own page changed."""
    flat = _params()
    sv = Served(flat)
    sv.prefill(0, _tokens(9, 1))
    sv.prefill(2, _tokens(11, 2))
    sv.prefill(1, _tokens(5, 3))
    before = jax.tree.map(np.asarray, sv.kv)
    sv.decode({1: 17})
    after = jax.tree.map(np.asarray, sv.kv)
    for i in LINEAR:
        for side in ("k", "v"):
            np.testing.assert_array_equal(after[side][i][[1, 3]],
                                          before[side][i][[1, 3]])
            assert (after[side][i][2] != before[side][i][2]).any()
        assert (after["v"][i][0] != before["v"][i][0]).any()
        assert np.isfinite(after["k"][i][0]).all()
    mine = sv.rows[1][5 // BS]  # position 5 of slot 1
    for i in LATENT:
        changed = np.unique(np.nonzero(after["k"][i] != before["k"][i])[0])
        assert set(changed) <= {0, mine} and mine in changed
        assert not after["k"][i][..., RANK + ROT:].any()
        assert after["v"][i].size == 0


def test_pool_bytes_are_the_arithmetic():
    """At the cell's shape: 12 linear layers of 97 rows of (32 x 128 x 128
    float32 + 3 x 12,288 bfloat16) and 4 latent layers of 6,145 pages of 64
    tokens of ONE row of 512 + 64 numbers stored in 640 lanes; the
    allocator counts the latent layers' pages alone."""
    cfg = TransformerConfig(**_published()["model"])
    assert state_row_bytes(cfg) == (32 * 128 * 128 * 4, 3 * 12288 * 2)
    made = {}

    def arrays():
        made["pool"] = PagedKVPool(cfg, num_blocks=6145, block_size=64,
                                   n_slots=96, max_blocks=576,
                                   prefill_chunk=512)
        return made["pool"].kv

    kv = jax.eval_shape(arrays)
    pool = made["pool"]
    assert {x.shape for i, x in enumerate(kv["k"]) if i % 4 == 3} \
        == {(6145, 64, 640)}
    assert {x.shape for i, x in enumerate(kv["k"]) if i % 4 != 3} \
        == {(97, 32, 128, 128)}
    assert {x.shape for i, x in enumerate(kv["v"]) if i % 4 != 3} \
        == {(97, 3, 12288)}
    assert pool.n_full == 4
    assert pool.bytes_full == pool.bytes_latent == 4 * 6145 * 64 * 640 * 2
    assert pool.bytes_per_block == 4 * 64 * 640 * 2
    assert pool.bytes_state == (12 * 97 * 2097152, 12 * 97 * 73728)
    assert pool.bytes_window == 0
    assert pool.total_bytes == sum(
        int(np.prod(x.shape)) * x.dtype.itemsize for x in jax.tree.leaves(kv))
    assert pool.allocator.num_blocks == 6145
    assert round(pool.bytes_state[0] / 96 / 1e6, 1) == 25.4  # a slot's rows
    assert round(pool.bytes_full / 1e9, 2) == 2.01
    assert round(sum(pool.bytes_state) / 1e9, 2) == 2.53


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
SERVED = {"reserve": {}, "single_shot": {"prefill_chunk": None},
          "optimistic": {"admission": "optimistic"},
          "dense": {"attention_impl": "dense"},
          "disaggregate": {"disaggregate": True},
          "two_chunks_a_step": {"prefill_chunks_per_step": 2}}


@pytest.mark.parametrize("option", sorted(SERVED))
def test_engine_serves_the_references_first_choice(option, tmp_path):
    """The engine itself, scheduler and all: six requests over three slots
    (slots are reused, chunks and decode steps interleave and ride in one
    call, the last chunks are padded), each served token the reference's
    first choice at its position, under every engine option this model is
    served with."""
    flat = _params()
    journal = Journal(None, host0_only=False)
    eng = _engine(flat, journal, **SERVED[option])
    reqs = [eng.submit([int(t) for t in _tokens(n, 10 + i)], max_new_tokens=m)
            for i, (n, m) in enumerate(SHAPES)]
    eng.run()
    eng.scheduler.check_invariants()
    for r, (n, m) in zip(reqs, SHAPES):
        assert len(r.out_tokens) == m
        assert _regret(flat, r) <= ATOL, (option, n, m)
    steps = journal.named("serve.step")
    fuses = option not in ("single_shot", "disaggregate")
    assert (sum(s.get("fused", 0) for s in steps) > 3) == fuses
    # the state rows a call's step kernels read and wrote: its decode rows
    # over the six linear layers
    counted = [s["state_rows"] for s in steps if "state_rows" in s]
    assert counted and all(n % 6 == 0 and 0 < n <= 18 for n in counted)
    if option != "reserve":
        return
    ev = journal.named("serve.engine")[-1]
    assert ev["layer_kinds"] == KEYS["layer_types"]
    assert ev["linear_mixer"] == ["gated_delta", "channel"]
    assert (ev["state_bytes_linear"], ev["conv_bytes_linear"]) \
        == eng.pool.bytes_state == (6 * 4 * 4 * 8 * 16 * 4,
                                    6 * 4 * 3 * 4 * 32 * 4)
    # pages for max_len are the two latent layers' alone
    assert ev["kv_bytes_full"] == ev["kv_bytes_latent"] \
        == eng.pool.bytes_latent == 2 * 73 * BS * 128 * 4
    assert ev["latent_row"] == [RANK, ROT, 128]
    assert ev["kv_bytes_window"] == 0
    assert (ev["experts_held"], ev["experts_published"]) == (4, 16)
    from torch_automatic_distributed_neural_network_tpu.obs import (
        report as obs_report,
    )

    path = tmp_path / "journal.jsonl"
    path.write_text("".join(json.dumps(r) + "\n" for r in journal.records))
    text = obs_report.format_report(obs_report.generate(str(path)))
    assert "latent: one row a token of 16 + 4 numbers, stored in 128" in text
    assert "(2 latent layers)" in text
    assert "of recurrent state" in text and "(6 linear layers)" in text
    assert "gated_delta: a decay a channel" in text
    assert "state rows a call" in text


def test_a_preempted_request_restarts_and_serves_the_same_tokens():
    """A pool too small for three growing requests under optimistic
    admission (the pages are the latent layers'): one is preempted, queued
    again and prefilled again from position 0, where its slot's states
    start from zeros; every request serves what it serves alone."""
    flat = _params()
    shapes = [(20, 30), (22, 28), (18, 30)]
    alone = []
    for i, (n, m) in enumerate(shapes):
        eng = _engine(flat)
        r = eng.submit([int(t) for t in _tokens(n, 40 + i)], max_new_tokens=m)
        eng.run()
        alone.append(r.out_tokens)
    eng = _engine(flat, admission="optimistic", num_blocks=28)
    reqs = [eng.submit([int(t) for t in _tokens(n, 40 + i)], max_new_tokens=m)
            for i, (n, m) in enumerate(shapes)]
    eng.run()
    eng.scheduler.check_invariants()
    assert sum(r.preempted for r in reqs) >= 1
    assert [r.out_tokens for r in reqs] == alone
    assert max(_regret(flat, r) for r in reqs) <= ATOL


REFUSED = {
    "prefix_cache": ({"prefix_cache": True},
                     "state at the matched boundary"),
    "speculative": ({"speculative": 2}, "cannot be taken out"),
    "mesh": ({"mesh": "a mesh"},
             "expert layers.*no sharded form.*no head axis to shard"),
    "quant_kv": ({"quant_kv": True}, "no int8 form"),
    "lora_spec": ({"lora_spec": "a spec"}, "layer_types"),
}


@pytest.mark.parametrize("option", sorted(REFUSED))
def test_unsupported_options_are_refused_at_construction(option):
    """What a recurrent state and a latent page refuse, each with its
    reason, stays refused for a model that has both: none is loosened."""
    kw, reason = REFUSED[option]
    with pytest.raises(ValueError, match=f"{option}.*{reason}"):
        _engine(_params(), **kw)


def test_a_pool_of_rows_and_latent_pages_refuses_what_it_has_no_form_for():
    cfg = _model().cfg
    with pytest.raises(ValueError, match="no sharded form"):
        PagedKVPool(cfg, num_blocks=9, block_size=4, mesh="a mesh")
    with pytest.raises(ValueError, match="no sharded and no int8 form"):
        PagedKVPool(cfg, num_blocks=9, block_size=4, quantize=True)


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
    assert np.isfinite(np.asarray(DecoderLM(dataclasses.replace(
        cfg, dtype=jnp.float32, remat=False)).apply(
            {"params": DecoderLM(cfg).init(
                jax.random.key(1), np.zeros((1, 8), np.int32))["params"]},
            _tokens(12, 1)[None]))).all()
