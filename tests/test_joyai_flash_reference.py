"""A model of ``latent_attention`` layers (multi-head latent attention: one
low-rank latent a token in the place of per-head keys and values, a rotated
key part all heads share, sigmoid-routed experts beside a shared one)
against the plain reference ``benchmark/reference/joyai_flash.py``, on
seeded weights at tiny sizes: ``model.apply``, the three serving programs
through the pool's latent pages, and ``ServeEngine`` itself.

Tolerance: everything here is float32 at ``highest`` matmul precision.  The
program expands a chunk's keys a block at a time under an online softmax and
decodes in the ABSORBED form (the query taken into the latent space, every
head over the one cached row), the reference expands every head over the
whole sequence, so they differ by the order of float32 sums: measured 4e-7
on logits of magnitude 0.5.  ``ATOL`` is 2e-5; a cache kept in bfloat16 is
out by 2.5e-4 (twelve times the tolerance) and bfloat16 compute by more
(``test_a_bfloat16_cache_is_outside_the_tolerance``,
``test_bf16_compute_is_outside_the_tolerance``), so a run in the next
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
)
from torch_automatic_distributed_neural_network_tpu.inference.serve.kv_pool import (
    PagedKVPool,
)
from torch_automatic_distributed_neural_network_tpu.models.transformer_core import (
    DecoderLM,
    LatentAttention,
    SparseMLP,
    TransformerConfig,
    deinterleave,
)
from torch_automatic_distributed_neural_network_tpu.obs.journal import Journal

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
ATOL = 2e-5


def _load(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load(os.path.join(BENCH, "reference", "joyai_flash.py"),
            "joyai_flash_reference")
weights = _load(os.path.join(BENCH, "lib", "weights.py"), "bench_weights")

CHUNK, BS = 8, 4
KEYS = dict(
    vocab_size=96, d_model=48, n_layers=4, n_heads=4, d_ff=80,
    max_seq_len=128, norm="rmsnorm", norm_eps=1e-6, act="swiglu", pos="rope",
    rope_theta=32e6, tie_embeddings=False,
    layer_types=["latent_attention"] * 4, latent_q_rank=24, latent_kv_rank=16,
    latent_nope_head_dim=8, latent_rope_head_dim=4, latent_value_head_dim=8,
    n_dense_layers=1, experts_published=16, experts_held=4, first_expert=4,
    experts_per_token=4, shared_experts=1, expert_d_ff=24,
    score_func="sigmoid", route_norm=True, route_scale=2.5)
RANK, ROT = KEYS["latent_kv_rank"], KEYS["latent_rope_head_dim"]


def _params(keys: dict = KEYS, seed: int = 3, *, rope_scale: float = 8.0
            ) -> dict:
    """Seeded leaves; the columns that give the rotated parts are made
    ``rope_scale`` times larger, so that position carries a share of a score
    that a test can see (at 0.02 n the rotated part is a 64th of it)."""
    flat = weights.flat(weights.seed_key(seed), ref.param_shapes(keys))
    r, n = keys["latent_kv_rank"], keys["latent_nope_head_dim"]
    for path in flat:
        if path.endswith("attn/kv_a_proj/kernel"):
            flat[path] = flat[path].at[:, r:].multiply(rope_scale)
        if path.endswith("attn/q_b_proj/kernel"):
            flat[path] = flat[path].at[:, :, n:].multiply(rope_scale)
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


def _without_rotated_key(flat: dict) -> dict:
    """The same leaves with the rotated key part zeroed: a model that sees a
    position only through the causal mask."""
    return {k: v.at[:, RANK:].set(0.0) if k.endswith("attn/kv_a_proj/kernel")
            else v for k, v in flat.items()}


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
    rows) and 30 decode steps through the latent pages, in slot 1 of 3: the
    logits of each chunk's last row and of every decode step are the
    reference's full forward pass's, through the latent kernel and through
    the dense gather alike; and the reference without the rotated key part
    is far from both."""
    flat = _params()
    seq = _tokens(51, 5)
    got = Served(flat, impl=impl).sequence(1, seq, 21)
    assert sorted(got) == [7, 15] + list(range(20, 51))
    _close(got, _want(flat, seq))
    off = _want(_without_rotated_key(flat), seq)
    assert max(np.abs(r - off[p]).max() for p, r in got.items()) > 100 * ATOL


def test_a_bfloat16_cache_is_outside_the_tolerance():
    """The same run with the latent pages in bfloat16: out by far more than
    the tolerance."""
    flat = _params()
    seq = _tokens(51, 5)
    got = Served(flat, cache=jnp.bfloat16).sequence(1, seq, 21)
    want = _want(flat, seq)
    assert max(np.abs(r - want[p]).max() for p, r in got.items()) > 10 * ATOL


def test_a_chunk_that_carries_decode_rows_matches_reference():
    """``chunk_and_step``: slot 0 prefills 19 tokens in three chunks while
    slots 1 and 2 decode IN those chunks' calls.  The chunks' logits are the
    reference's; the decode rows are served the reference's first choice;
    and the rows they wrote into their pages are read by plain decode steps
    afterwards, whose logits are the reference's too."""
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
        # a decode row's token is the first choice at ITS position
        assert served[1] == int(np.argmax(want_b[10 + i]))
        assert served[2] == int(np.argmax(want_c[6 + i]))
    assert sorted(got_a) == [7, 15, 18]
    for i in range(12):  # all three decode, a step each
        lg = sv.decode({0: a[19 + i], 1: b[13 + i], 2: c[9 + i]})
        got_a[19 + i], got_b[13 + i], got_c[9 + i] = lg[0], lg[1], lg[2]
    _close(got_a, want_a, "the chunk's slot")
    _close(got_b, want_b, "slot 1")
    _close(got_c, want_c, "slot 2")


def test_neighbouring_slots_do_not_touch_each_others_pages():
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
    """A second, shorter request in pages the first left full."""
    flat = _params()
    sv = Served(flat)
    sv.sequence(2, _tokens(60, 9), 31)
    seq = _tokens(26, 10)
    _close(sv.sequence(2, seq, 9), _want(flat, seq))


def test_inactive_slots_write_to_the_null_block():
    """A decode step with slots 0 and 2 inactive: their pages are what they
    were, bit for bit; a stored row ends in zeros."""
    flat = _params()
    sv = Served(flat)
    sv.prefill(0, _tokens(9, 1))
    sv.prefill(2, _tokens(11, 2))
    sv.prefill(1, _tokens(5, 3))
    before = jax.tree.map(np.asarray, sv.kv)
    sv.decode({1: 17})
    after = jax.tree.map(np.asarray, sv.kv)
    mine = sv.rows[1][5 // BS]
    for i in range(KEYS["n_layers"]):
        changed = np.unique(np.nonzero(after["k"][i] != before["k"][i])[0])
        assert set(changed) <= {0, mine} and mine in changed
        assert not after["k"][i][..., RANK + ROT:].any()
        assert after["v"][i].size == 0


def test_pool_bytes_are_the_arithmetic():
    """At the cell's shape: 20 latent layers of 4,097 pages of 64 tokens of
    ONE row of 512 + 64 numbers, stored in 640 lanes; no second array; the
    allocator counts the pages."""
    with open(os.path.join(BENCH, "configs", "joyai-llm-flash-ep8.json")) as f:
        cfg = TransformerConfig(**json.load(f)["model"])
    assert cfg.page_row("latent_attention") == (576,)
    assert cfg.page_row(None) == (32 * 64,) * 2
    made = {}

    def arrays():
        made["pool"] = PagedKVPool(cfg, num_blocks=4097, block_size=64,
                                   n_slots=24, max_blocks=544,
                                   prefill_chunk=512)
        return made["pool"].kv

    kv = jax.eval_shape(arrays)
    pool = made["pool"]
    assert {x.shape for x in kv["k"]} == {(4097, 64, 640)}
    assert {x.shape for x in kv["v"]} == {(0,)}
    assert pool.bytes_full == pool.bytes_latent == 20 * 4097 * 64 * 640 * 2
    assert pool.bytes_per_block == 20 * 64 * 640 * 2
    assert pool.bytes_window == 0 and pool.bytes_state == (0, 0)
    assert pool.total_bytes == sum(
        int(np.prod(x.shape)) * x.dtype.itemsize for x in jax.tree.leaves(kv))
    assert pool.allocator.num_blocks == 4097
    assert round(pool.bytes_full / 2**30, 2) == 6.25
    # a model of keys and values is counted as it was
    plain = PagedKVPool(TransformerConfig(
        vocab_size=64, d_model=32, n_layers=2, n_heads=4, n_kv_heads=2),
        num_blocks=9, block_size=4)
    assert plain.bytes_full == 2 * 9 * 4 * 2 * 2 * 8 * 2
    assert plain.bytes_latent == 0


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
SERVED = {"chunked": {}, "single_shot": {"prefill_chunk": None},
          "optimistic": {"admission": "optimistic"},
          "dense": {"attention_impl": "dense"},
          "disaggregate": {"disaggregate": True},
          "speculative": {"speculative": 2},
          "prefix_cache": {"prefix_cache": True},
          "two_chunks_a_step": {"prefill_chunks_per_step": 2},
          # the chunk's attention as ONE kernel a layer, as on the chip (the
          # interpreter here; key blocks of 8 so that chunks cross them)
          "chunk_kernel": {}}


@pytest.mark.parametrize("option", sorted(SERVED))
def test_engine_serves_the_references_first_choice(option, tmp_path,
                                                   monkeypatch):
    """The engine itself, scheduler and all: six requests over three slots
    (slots are reused, chunks and decode steps interleave and ride in one
    call, the last chunks are padded), each served token the reference's
    first choice at its position, under every engine option a latent model
    is served with."""
    flat = _params()
    journal = Journal(None, host0_only=False)
    if option == "chunk_kernel":
        from torch_automatic_distributed_neural_network_tpu.ops import (
            paged_attention as paged,
        )

        monkeypatch.setattr(paged, "LATENT_KEYS", 8)
        monkeypatch.setattr(paged, "latent_chunk_tiles", lambda *a: True)
    eng = _engine(flat, journal, **SERVED[option])
    reqs = [eng.submit([int(t) for t in _tokens(n, 10 + i)], max_new_tokens=m)
            for i, (n, m) in enumerate(SHAPES)]
    eng.run()
    eng.scheduler.check_invariants()
    for r, (n, m) in zip(reqs, SHAPES):
        assert len(r.out_tokens) == m
        assert _regret(flat, r) <= ATOL, (option, n, m)
    steps = journal.named("serve.step")
    fuses = option not in ("single_shot", "disaggregate", "speculative")
    assert (sum(s.get("fused", 0) for s in steps) > 3) == fuses
    if option == "disaggregate":
        assert eng.pool.transferred_bytes \
            == eng.pool.transferred_blocks * eng.pool.bytes_per_block > 0
    if option == "speculative":
        assert eng.spec_accepted > 0
    # the counters the kernel brought: which form a chunk attends in, and
    # on every call that dispatched a chunk the key blocks its four layers'
    # kernel calls ran (blocks of 8 keys: the chunk's last position's)
    ev = journal.named("serve.engine")[-1]
    form = {"chunk_kernel": "kernel", "single_shot": None}.get(
        option, "blocks")
    assert ev["chunk_attention"] == (form and {"latent_attention": form})
    counted = [s for s in steps if "chunk_key_blocks" in s]
    if option == "chunk_kernel":
        chunks = sum(-(-n // CHUNK) for n, _ in SHAPES)
        assert sum(s["n_prefill_chunks"] for s in counted) == chunks \
            == sum(s.get("n_prefill_chunks", 0) for s in steps)
        assert sum(s["chunk_key_blocks"] for s in counted) == 4 * sum(
            (pos + CHUNK - 1) // 8 + 1
            for n, _ in SHAPES for pos in range(0, n, CHUNK))
    else:
        assert not counted
    if option != "chunked":
        return
    # the kernel's grid: work lists of the live (slot, 512-key group) items
    assert sum(s.get("attn_grid_items", 0) for s in steps) > 0
    assert all(s["attn_grid_items"] <= s["attn_grid_dense"]
               for s in steps if s.get("attn_grid_dense"))
    assert ev["layer_kinds"] == KEYS["layer_types"]
    assert ev["kv_bytes_full"] == ev["kv_bytes_latent"] \
        == eng.pool.bytes_latent == 4 * 73 * BS * 128 * 4
    assert ev["latent_row"] == [RANK, ROT, 128]
    assert (ev["kv_bytes_window"], ev["state_bytes_linear"]) == (0, 0)
    assert (ev["experts_held"], ev["experts_published"]) == (4, 16)
    from torch_automatic_distributed_neural_network_tpu.obs import (
        report as obs_report,
    )

    path = tmp_path / "journal.jsonl"
    path.write_text("".join(json.dumps(r) + "\n" for r in journal.records))
    text = obs_report.format_report(obs_report.generate(str(path)))
    assert "latent: one row a token of 16 + 4 numbers, stored in 128" in text
    assert "(4 latent layers)" in text
    assert "paged attention grid:" in text


def test_a_shared_prefix_is_read_where_it_lies():
    """Prefix reuse over latent pages: requests that share a prompt's first
    24 tokens match its pages in the radix index, skip those chunks and
    attend the shared rows through their own tables (a rotated key part
    holds its ABSOLUTE position, which a shared prefix shares).  Every token
    is the reference's first choice, and the tokens are those of an engine
    without the cache."""
    flat = _params()
    head = [int(t) for t in _tokens(24, 70)]
    prompts = [head + [int(t) for t in _tokens(n, 71 + n)]
               for n in (9, 5, 14)] + [head[:22], head]

    def serve(**kw):
        eng = _engine(flat, n_slots=2, **kw)
        reqs = []
        for p in prompts:  # one after the other: the index fills first
            reqs.append(eng.submit(list(p), max_new_tokens=7))
            eng.run()
        return eng, reqs

    eng, reqs = serve(prefix_cache=True)
    _, plain = serve()
    assert [r.out_tokens for r in reqs] == [r.out_tokens for r in plain]
    assert max(_regret(flat, r) for r in reqs) <= ATOL
    assert eng.prefix_hits >= 3 and eng.prefix_saved_chunks >= 6
    eng.scheduler.check_invariants()


def test_a_shared_page_is_forked_before_it_is_written():
    """Copy-on-write over latent pages: the page a running request's next
    row lands in gets a second owner (``allocator.ref``); the engine copies
    the page (the one array; the array of no elements beside it is left
    alone) and writes the copy.  What is served is the reference's first
    choice, and the first owner's page keeps its rows."""
    flat = _params()
    eng = _engine(flat, n_slots=1, prefix_cache=True)
    req = eng.submit([int(t) for t in _tokens(10, 80)], max_new_tokens=9)
    while req.state != "running":
        eng.step()
    bi = (req.n_prompt + req.n_generated - 1) // BS
    shared = req.blocks[bi]
    eng.pool.allocator.ref(shared)  # a second owner
    kept = np.asarray(eng.pool.kv["k"][1][shared])
    eng.step()
    assert eng.cow_forks == 1 and req.blocks[bi] != shared
    eng.pool.allocator.release([shared])
    eng.run()
    np.testing.assert_array_equal(np.asarray(eng.pool.kv["k"][1][shared]),
                                  kept)
    assert len(req.out_tokens) == 9 and _regret(flat, req) <= ATOL
    eng.scheduler.check_invariants()


def test_a_preempted_request_restarts_and_serves_the_same_tokens():
    """A pool too small for three growing requests under optimistic
    admission: one is preempted, queued again and prefilled again from
    position 0; every request serves what it serves alone."""
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


def test_a_slot_decoded_one_step_too_far_spoils_nothing():
    """The dispatch-ahead: a request that ends at an EOS is decoded once
    more before the host reads the EOS, which writes one row too many into
    a page it owned.  The requests that take the slot afterwards serve the
    reference's first choice."""
    flat = _params()
    eng = _engine(flat, n_slots=1)
    probe = eng.submit([int(t) for t in _tokens(12, 50)], max_new_tokens=8)
    eng.run()
    eos = probe.out_tokens[3]
    eng = _engine(flat, n_slots=1)
    first = eng.submit(list(probe.prompt), max_new_tokens=8, eos_id=eos)
    later = [eng.submit([int(t) for t in _tokens(n, 60 + n)],
                        max_new_tokens=6) for n in (9, 17)]
    eng.run()
    assert first.out_tokens == probe.out_tokens[:probe.out_tokens.index(eos) + 1]
    assert eng.discarded_tokens >= 1
    assert max(_regret(flat, r) for r in later) <= ATOL


REFUSED = {
    "mesh": ({"mesh": "a mesh"}, "no head axis to shard"),
    "quant_kv": ({"quant_kv": True}, "no int8 form"),
    "lora_spec": ({"lora_spec": "a spec"}, "layer_types"),
}


@pytest.mark.parametrize("option", sorted(REFUSED))
def test_unsupported_options_are_refused_at_construction(option):
    """What a model with latent layers is not served with, each refusal
    with its reason."""
    kw, reason = REFUSED[option]
    with pytest.raises(ValueError, match=f"{option}.*{reason}"):
        _engine(_params(), **kw)


def test_a_latent_pool_refuses_what_it_has_no_form_for():
    cfg = _model().cfg
    for kw in ({"quantize": True}, {"mesh": "a mesh"}):
        with pytest.raises(ValueError, match="no sharded and no int8 form"):
            PagedKVPool(cfg, num_blocks=9, block_size=4, **kw)


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
