"""A model whose layers differ (``TransformerConfig.layer_types``: window
and full attention mixed, rotary on the window layers only, QK-norm, output
gate, sandwich norms, sigmoid-routed experts beside a shared one) against
the plain reference ``benchmark/reference/afmoe.py``, on seeded weights at
tiny sizes: ``model.apply``, and the serving programs themselves
(``inference/serve/programs.py``) run through the two-kind pool over several
prefill chunks and two windows of decode steps.

Tolerance: everything here is float32 at ``highest`` matmul precision, and
program and reference differ only in the order of float32 sums (the online
softmax over key blocks, the grouped matmul's K tiles, XLA's fusions):
measured 4e-7 on logits of magnitude 0.6.  ``ATOL`` is 2e-5, fifty times
that; bfloat16 compute is out by 1e-3 or more
(``test_bf16_compute_is_outside_the_tolerance``), so a run in the next
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

from torch_automatic_distributed_neural_network_tpu.inference.serve import (
    ServeEngine,
    programs,
)
from torch_automatic_distributed_neural_network_tpu.inference.serve.kv_pool import (
    PagedKVPool,
    blocks_for_tokens,
    window_pages,
    write_chunk,
)
from torch_automatic_distributed_neural_network_tpu.models.transformer_core import (
    DecoderLM,
    SparseMLP,
    TransformerConfig,
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


ref = _load(os.path.join(BENCH, "reference", "afmoe.py"), "afmoe_reference")
weights = _load(os.path.join(BENCH, "lib", "weights.py"), "bench_weights")

WINDOW, CHUNK, BS = 8, 4, 2  # a ring of 7 pages: 14 positions
KEYS = dict(
    vocab_size=96, d_model=64, n_layers=5, n_heads=8, n_kv_heads=2,
    head_size=16, d_ff=96, max_seq_len=64, norm="rmsnorm", norm_eps=1e-5,
    act="swiglu", pos="rope", sliding_window=WINDOW, tie_embeddings=False,
    rope_theta=10000.0,
    layer_types=["sliding_attention"] * 4 + ["full_attention"],
    rope_layers="sliding", qk_norm=True, attn_gate=True, sandwich_norm=True,
    embed_scale=True, n_dense_layers=1, experts_published=16, experts_held=4,
    first_expert=4, experts_per_token=2, shared_experts=1, expert_d_ff=32,
    score_func="sigmoid", route_norm=True, route_scale=2.448)


def _params(keys: dict, seed: int = 3, edit=None) -> dict:
    flat = weights.flat(weights.seed_key(seed), ref.param_shapes(keys))
    return edit(flat) if edit else flat


def _skewed(flat: dict) -> dict:
    """Every token picks held expert 5 first: a zero router and a bias
    that towers over the sigmoid's range."""
    out = dict(flat)
    for k in flat:
        if k.endswith("router/kernel"):
            out[k] = jnp.zeros_like(flat[k])
        if k.endswith("router/e_bias"):
            out[k] = flat[k].at[5].set(10.0)
    return out


def _biased(flat: dict) -> dict:
    """A bias as large as the scores: the choice follows ``s + b`` and no
    longer ``s``, the weights still come from ``s``."""
    return {k: v * 40.0 if k.endswith("router/e_bias") else v
            for k, v in flat.items()}


# name -> (edit of the seeded weights, key whose change must show: the
# reference with that key switched differs from the program by far more than
# the tolerance, so the case does test what its name says)
CASES = {
    "rotary_on_window_layers_only": (None, {"rope_layers": "all"}),
    "output_gate": (None, {"attn_gate": False}),
    "qk_norm": (None, {"qk_norm": False}),
    "choice_by_biased_score_weight_by_score": (_biased, {"route_norm": False}),
    "skewed_router_drops_nothing": (_skewed, {"experts_per_token": 1}),
    "three_windows_recycled_pages": (None, {"sliding_window": 2 * WINDOW}),
}


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


def _tokens(n: int, seed: int = 0):
    return np.random.RandomState(seed).randint(1, KEYS["vocab_size"], size=n)


def _model(keys: dict, dtype=jnp.float32):
    return DecoderLM(TransformerConfig(**keys, dtype=dtype))


def _switched(keys: dict, switch: dict, flat: dict) -> tuple[dict, dict]:
    """The reference's keys with one switched, and the leaves it then has."""
    other = {**keys, **switch}
    return other, {k: flat[k] for k in ref.param_shapes(other)}


@pytest.mark.parametrize("case", sorted(CASES))
def test_model_apply_matches_reference(case):
    edit, switch = CASES[case]
    flat = _params(KEYS, edit=edit)
    toks = np.stack([_tokens(3 * WINDOW, 1), _tokens(3 * WINDOW, 2)])
    got = np.asarray(_model(KEYS).apply({"params": weights.nest(flat)}, toks))
    want = np.asarray(ref.forward_logits(flat, KEYS, toks))
    np.testing.assert_allclose(got, want, atol=ATOL, rtol=0)
    other, leaves = _switched(KEYS, switch, flat)
    off = np.asarray(ref.forward_logits(leaves, other, toks))
    assert np.abs(off - got).max() > 100 * ATOL, case
    if edit is _biased:  # the bias does change the choice, never the weight
        layer = ref.sub(flat, "layers_1")
        x = jnp.asarray(np.random.RandomState(2).randn(64, 64), jnp.float32)
        chosen, w = ref.route(layer, x, KEYS, "f32")
        s = jax.nn.sigmoid(x @ layer["mlp/router/kernel"])
        assert (np.sort(chosen, -1) != np.sort(
            jax.lax.top_k(s, 2)[1], -1)).any()
        np.testing.assert_allclose(w.sum(-1), KEYS["route_scale"], rtol=1e-5)


def _serve_logits(keys: dict, flat: dict, seq: np.ndarray, n_prompt: int,
                  impl: str):
    """Logits the serving programs give for ``seq`` in slot 1 of 3: the
    prompt in chunks (one row a chunk: its last), then one decode step a
    token with the next token of ``seq`` forced.  Returns
    ``{position: logits}`` and the decode steps' expert counters."""
    cfg = TransformerConfig(**keys, dtype=jnp.float32)
    params = weights.nest(flat)
    n_slots, max_len, slot = 3, 64, 1
    MB = blocks_for_tokens(max_len, BS)
    pool = PagedKVPool(cfg, num_blocks=n_slots * MB + 1, block_size=BS,
                       dtype=jnp.float32, n_slots=n_slots, max_blocks=MB,
                       prefill_chunk=CHUNK)
    blocks = pool.alloc(blocks_for_tokens(len(seq), BS))
    row = jnp.asarray(pool.table_row(blocks, MB), jnp.int32)
    chunk = jax.jit(lambda *a: programs.prefill_chunk(
        *a, cfg=cfg, max_blocks=MB))
    step = jax.jit(lambda *a: programs.decode_logits(
        *a, cfg=cfg, attention_impl=impl))
    kv, out = pool.kv, {}
    for pos in range(0, n_prompt, CHUNK):
        part = list(seq[pos:pos + CHUNK])
        part = part[:n_prompt - pos]
        kv, lg = chunk(params, kv, programs.pack_chunk(
            np.asarray(row), part + [0] * (CHUNK - len(part)), pos,
            len(part) - 1), pool.win_tables[slot])
        out[pos + len(part) - 1] = np.asarray(lg[0])
    tables = np.zeros((n_slots, MB), np.int32)
    tables[slot] = np.asarray(row)
    active = np.zeros((n_slots,), bool)
    active[slot] = True
    counters = []
    for pos in range(n_prompt, len(seq)):
        ctx = np.zeros((n_slots,), np.int32)
        tok = np.zeros((n_slots, 1), np.int32)
        ctx[slot], tok[slot, 0] = pos, seq[pos]
        kv, lg, moe = step(params, kv, jnp.asarray(tables), pool.win_tables,
                           jnp.asarray(ctx), jnp.asarray(tok),
                           jnp.asarray(active))
        out[pos] = np.asarray(lg[slot, 0])
        counters.append(np.asarray(moe))
    return out, np.stack(counters)


# the dense gather is checked once: it shares all but the attention call
# with the paged step
@pytest.mark.parametrize("case,impl", [(c, "paged") for c in sorted(CASES)]
                         + [("three_windows_recycled_pages", "dense")])
def test_serving_programs_match_reference(case, impl):
    """Prefill chunks and decode steps through the pool: 11 prompt tokens
    (three chunks, the last padded) and 2 windows of decode steps, 27
    positions in all, which is more than window + chunk: the ring of 7
    pages has gone round."""
    edit, switch = CASES[case]
    flat = _params(KEYS, edit=edit)
    n_prompt, seq = 11, _tokens(11 + 2 * WINDOW, 5)
    assert len(seq) > WINDOW + CHUNK
    assert window_pages(WINDOW, CHUNK, BS) * BS < len(seq)
    got, counters = _serve_logits(KEYS, flat, seq, n_prompt, impl)
    want = np.asarray(ref.forward_logits(flat, KEYS, seq[None]))[0]
    other, leaves = _switched(KEYS, switch, flat)
    off = np.asarray(ref.forward_logits(leaves, other, seq[None]))[0]
    assert sorted(got) == [3, 7] + list(range(10, len(seq)))
    for pos, row in got.items():
        np.testing.assert_allclose(row, want[pos], atol=ATOL, rtol=0,
                                   err_msg=f"{case} position {pos}")
    assert max(np.abs(off[p] - r).max() for p, r in got.items()) > 100 * ATOL
    if case == "skewed_router_drops_nothing":
        # one token a step, four expert layers: its pair on expert 5 is
        # computed in every one of them (and the second choice where held)
        assert (counters[:, 0] >= 4).all() and (counters[:, 2] == 1).all()


def test_skewed_chunk_drops_nothing():
    """All 24 rows of one chunk on ONE expert (a capacity router at factor
    1.25 would keep 8 of them): every pair is computed."""
    flat = _skewed(_params(KEYS))
    cfg = TransformerConfig(**KEYS, dtype=jnp.float32)
    p = weights.nest(flat)["layers_2"]["mlp"]
    x = jnp.asarray(np.random.RandomState(0).randn(24, 64), jnp.float32)
    y, stats = SparseMLP(cfg).apply({"params": p}, x)
    assert int(stats["max_expert_tokens"]) == 24
    assert int(stats["pairs"]) >= 24
    want = ref.ffn(ref.sub(flat, "layers_2"), x, KEYS, True, "f32")
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), atol=ATOL,
                               rtol=0)


def test_bf16_compute_is_outside_the_tolerance():
    flat = _params(KEYS)
    toks = _tokens(3 * WINDOW, 1)[None]
    got = np.asarray(_model(KEYS, jnp.bfloat16).apply(
        {"params": weights.nest(flat)}, toks))
    want = np.asarray(ref.forward_logits(flat, KEYS, toks))
    assert np.abs(got - want).max() > 20 * ATOL


def test_eight_shares_add_up_to_the_uncut_layer():
    """The chip's share of an expert-parallel deployment: 16 experts over
    eight chips, two each.  The routed parts of the eight shares, with the
    shared expert (which every chip computes alike) counted once, are the
    uncut reference layer."""
    whole = {**KEYS, "experts_held": 16, "first_expert": 0}
    flat = _params(whole)
    layer = ref.sub(flat, "layers_3")
    x = jnp.asarray(np.random.RandomState(1).randn(40, 64), jnp.float32)
    want = np.asarray(ref.ffn(layer, x, whole, True, "f32"))
    common = np.asarray(ref.shared(layer, x, "f32"))
    total = common.copy()
    for chip in range(8):
        keys = {**KEYS, "experts_held": 2, "first_expert": 2 * chip}
        mine = dict(weights.nest(flat)["layers_3"]["mlp"])
        for name in ("experts_gate", "experts_up", "experts_down"):
            mine[name] = mine[name][2 * chip:2 * chip + 2]
        y, stats = SparseMLP(TransformerConfig(**keys, dtype=jnp.float32)
                             ).apply({"params": mine}, x)
        total += np.asarray(y) - common
        part = np.asarray(ref.ffn(
            {**layer, **{"mlp/" + n: mine[n] for n in
                         ("experts_gate", "experts_up", "experts_down")}},
            x, keys, True, "f32"))
        np.testing.assert_allclose(np.asarray(y), part, atol=ATOL, rtol=0)
    np.testing.assert_allclose(total, want, atol=8 * ATOL, rtol=0)


@pytest.mark.parametrize("n_slots,max_len,bs,chunk,window,heads", [
    (3, 64, 2, 4, 8, {}),
    (16, 13312, 16, 512, 4096,
     {"n_heads": 48, "n_kv_heads": 8, "head_size": 128})])
def test_pool_bytes_are_the_arithmetic(n_slots, max_len, bs, chunk, window,
                                       heads):
    """Full layers keep pages for ``max_len`` a slot, sliding layers a ring
    of ``ceil((window + chunk) / bs) + 1`` pages a slot; each pool has one
    null block."""
    keys = {**KEYS, "sliding_window": window, "max_seq_len": max_len,
            **heads}
    cfg = TransformerConfig(**keys)
    MB = blocks_for_tokens(max_len, bs)
    made = {}

    def arrays():  # shapes alone: the larger pool is 2 GB
        made["pool"] = PagedKVPool(
            cfg, num_blocks=n_slots * MB + 1, block_size=bs, n_slots=n_slots,
            max_blocks=MB, prefill_chunk=chunk)
        return made["pool"].kv

    kv = jax.eval_shape(arrays)
    cell = 2 * bs * cfg.kv_heads * cfg.head_dim * 2  # k and v, bfloat16
    W = -(-(window + chunk) // bs) + 1
    full, ring = 1 * (n_slots * MB + 1) * cell, 4 * (n_slots * W + 1) * cell
    assert (made["pool"].bytes_full, made["pool"].bytes_window) == (full, ring)
    held = sum(int(np.prod(x.shape)) * x.dtype.itemsize
               for x in jax.tree.leaves(kv))
    assert held == full + ring
    if max_len == 13312:  # the cell's shape: 1.94 GiB, not 4.06
        assert round((full + ring) / 2**30, 2) == 1.94
        assert round(5 * full / 2**30, 2) == 4.06


def test_engine_serves_recycled_pages_and_counts_experts(tmp_path):
    """The engine itself, scheduler and all: five requests over three
    slots, the longest 53 positions (six windows; the ring holds 14), each
    served token the reference's first choice, and the expert counters on
    the ``serve.step`` events."""
    flat = _params(KEYS)
    journal = Journal(None, host0_only=False)
    eng = ServeEngine(_model(KEYS), {"params": weights.nest(flat)}, n_slots=3,
                      max_len=64, block_size=BS, prefill_chunk=CHUNK,
                      cache_dtype=jnp.float32, journal=journal,
                      export_cache=False)
    shapes = [(5, 20), (23, 30), (14, 17), (30, 8), (3, 3)]
    reqs = [eng.submit([int(t) for t in _tokens(n, 10 + i)], max_new_tokens=m)
            for i, (n, m) in enumerate(shapes)]
    eng.run()
    eng.scheduler.check_invariants()
    for r, (n, m) in zip(reqs, shapes):
        assert len(r.out_tokens) == m
        seq = np.asarray(r.prompt + r.out_tokens)
        lg = np.asarray(ref.forward_logits(flat, KEYS, seq[None]))[0]
        rows = lg[n - 1:n - 1 + m]
        regret = rows.max(-1) - rows[np.arange(m), r.out_tokens]
        assert regret.max() <= ATOL, (n, m, regret.max())
    ev = journal.named("serve.engine")[-1]
    assert ev["layer_kinds"] == KEYS["layer_types"]
    assert (ev["experts_held"], ev["experts_published"]) == (4, 16)
    assert ev["kv_bytes_full"] == eng.pool.bytes_full > 0
    assert ev["kv_bytes_window"] == eng.pool.bytes_window > 0
    # ``tadnn report`` prints them on its engine and serving lines
    from torch_automatic_distributed_neural_network_tpu.obs import (
        report as obs_report,
    )

    path = tmp_path / "journal.jsonl"
    path.write_text("".join(
        json.dumps(r) + "\n" for r in journal.records))
    text = obs_report.format_report(obs_report.generate(str(path)))
    assert "in window rings (1 full, 4 sliding layers)" in text
    assert "experts 4 held of 16" in text
    assert "pairs here on" in text and "tokens on one" in text
    # the counters come back with a step's tokens, one call after its
    # dispatch: every step that decoded ALONE is read, by a call that
    # decoded (a step whose rows rode in a chunk routed the chunk's rows
    # with them: its counters are not a decode step's, and are left out)
    steps = [s for s in journal.named("serve.step") if "moe_pairs" in s]
    alone = [s for s in journal.named("serve.step")
             if "decode_dispatch" in s["phases"] and not s["fused"]]
    assert len(steps) == len(alone) > 0
    assert 0 < sum(s["fused"] for s in journal.named("serve.step"))
    assert all(s["decode_s"] for s in steps)
    assert all(0 <= s["moe_experts_touched"] <= s["moe_pairs"]
               <= 4 * 2 * eng.n_slots for s in steps)
    assert any(s["moe_pairs"] for s in steps)
    assert all(s["moe_max_expert_tokens"] <= eng.n_slots for s in steps)


def test_unsupported_options_are_refused_at_construction():
    """What a model with sliding layers or expert layers is not served
    with, each for its own reason (the message gives it): prefix reuse
    needs a finished prompt's keys to stay, and a ring has written over
    them; the adapter pool factorizes a scanned stack."""
    from torch_automatic_distributed_neural_network_tpu.training.lora import (
        LoraSpec,
    )

    model = _model(KEYS)
    variables = {"params": weights.nest(_params(KEYS))}
    for bad in ({"prefix_cache": True},
                {"lora_spec": LoraSpec(rank=2, alpha=4.0)}):
        with pytest.raises(ValueError, match=next(iter(bad))):
            ServeEngine(model, variables, n_slots=2, max_len=64,
                        block_size=BS, export_cache=False,
                        **{"prefill_chunk": CHUNK, **bad})
    with pytest.raises(ValueError, match="layer_types"):
        TransformerConfig(attn_gate=True)


def _engine_tokens(flat, **kw):
    eng = ServeEngine(_model(KEYS), {"params": weights.nest(flat)},
                      **{"n_slots": 3, "max_len": 64, "block_size": BS,
                         "prefill_chunk": CHUNK, "export_cache": False,
                         "cache_dtype": jnp.float32, **kw})
    shapes = [(5, 20), (23, 30), (14, 17)]
    reqs = [eng.submit([int(t) for t in _tokens(n, 10 + i)], max_new_tokens=m)
            for i, (n, m) in enumerate(shapes)]
    eng.run()
    eng.scheduler.check_invariants()
    return eng, [(r.prompt, r.out_tokens) for r in reqs]


@pytest.mark.parametrize("option", [
    "speculative", "dense", "chunk_of_no_whole_pages",
    "quant_kv", "export_cache"])
def test_engine_options_serve_a_model_with_layer_kinds(option, tmp_path):
    """The options of the shared programs on a model whose layers differ:
    each serves the reference's first choice at every position (int8 KV:
    a choice within its quantization error of the first)."""
    kw = {"speculative": {"speculative": 2},
          "dense": {"attention_impl": "dense"},
          # a page of 8 and a chunk of 4: written a token at a time
          "chunk_of_no_whole_pages": {"block_size": 8},
          "quant_kv": {"quant_kv": True},
          # both programs compiled ahead of time and stored
          "export_cache": {"export_cache": str(tmp_path)}}[option]
    flat = _params(KEYS)
    eng, served = _engine_tokens(flat, **kw)
    # int8 keys and values are off by up to 1/254 of a head's range a token:
    # measured 0.041 on logits of magnitude 0.6
    limit = 0.1 if option == "quant_kv" else ATOL
    for prompt, out in served:
        seq = np.asarray(prompt + out)
        lg = np.asarray(ref.forward_logits(flat, KEYS, seq[None]))[0]
        rows = lg[len(prompt) - 1:len(prompt) - 1 + len(out)]
        regret = rows.max(-1) - rows[np.arange(len(out)), out]
        assert regret.max() <= limit, (option, regret.max())
    if option == "speculative":
        assert eng.spec_drafted > 0
    if option == "quant_kv":
        assert all(set(leaf) == {"q", "scale"} for leaf in eng.pool.kv["k"])
    if option == "export_cache":
        assert sorted(i["kind"] for i in eng.export_info) == [
            "serve_decode", "serve_fused"]


@pytest.mark.parametrize("quantize", [False, True])
def test_chunk_past_the_tables_end_lands_in_the_null_block(quantize):
    """A padded last chunk that reaches past the table row (``max_len`` no
    multiple of the chunk: 5 pages of 4, a chunk of 8 at position 16)
    writes its real rows where they belong and the rest into the null
    block: the slice of the row is not clamped back over live pages."""
    cfg = TransformerConfig(vocab_size=96, d_model=64, n_layers=1, n_heads=8,
                            n_kv_heads=2, d_ff=96, max_seq_len=20)
    pool = PagedKVPool(cfg, num_blocks=7, block_size=4, dtype=jnp.float32,
                       quantize=quantize)
    leaf = jax.tree.map(lambda x: x + 7, pool.kv["k"][0])
    row = jnp.asarray([3, 1, 4, 6, 2], jnp.int32)
    rows = jnp.asarray(np.random.RandomState(0).randn(
        8, cfg.kv_heads, cfg.head_dim), jnp.float32)
    out = jax.jit(write_chunk)(leaf, row, jnp.int32(16), rows)
    before, after = jax.tree.leaves(leaf), jax.tree.leaves(out)
    for b, a in zip(before, after):
        np.testing.assert_array_equal(  # pages 3, 1, 4, 6 and 5 untouched
            np.asarray(a)[[1, 3, 4, 5, 6]], np.asarray(b)[[1, 3, 4, 5, 6]])
    if not quantize:  # the chunk's first page is the row's last block
        np.testing.assert_array_equal(
            np.asarray(out)[2], np.asarray(rows[:4]).reshape(4, -1))


@pytest.mark.parametrize("window", [None, 100])
def test_folded_kernel_matches_plain_attention(window):
    """The MXU form of the paged decode kernel at a page of 16 and several
    groups of 8 pages: contexts inside the first group, across groups and
    at the table's end; with a window the grid covers the band alone (2
    groups of 5) and starts at each slot's own first group."""
    from torch_automatic_distributed_neural_network_tpu.ops.paged_attention import (
        paged_attention_folded,
    )

    S, Hq, kvH, hd, bs, MB = 3, 12, 4, 32, 16, 40
    rs = np.random.RandomState(0)
    k = jnp.asarray(rs.randn(S * MB + 1, bs, kvH * hd), jnp.float32)
    v = jnp.asarray(rs.randn(S * MB + 1, bs, kvH * hd), jnp.float32)
    q = jnp.asarray(rs.randn(S, Hq, hd), jnp.float32)
    tables = jnp.asarray(1 + rs.permutation(S * MB).reshape(S, MB), jnp.int32)
    ctx = jnp.asarray([5, 300, MB * bs - 1], jnp.int32)
    got = paged_attention_folded(q, k, v, tables, ctx, window=window)
    for s in range(S):
        n = int(ctx[s]) + 1
        lo = 0 if window is None else max(0, n - window)
        keys = k[tables[s]].reshape(MB * bs, kvH, hd)[lo:n]
        vals = v[tables[s]].reshape(MB * bs, kvH, hd)[lo:n]
        for h in range(Hq):
            w = jax.nn.softmax(keys[:, h // (Hq // kvH)] @ q[s, h]
                               / np.sqrt(hd))
            np.testing.assert_allclose(
                got[s, h], w @ vals[:, h // (Hq // kvH)], atol=ATOL, rtol=0)
