"""A hybrid model (``layer_types`` with ``linear_attention`` layers: a gated
delta rule over a recurrent state, a full-attention layer among every few,
norms on the sublayers' outputs) against the plain reference
``benchmark/reference/olmo_hybrid.py``, on seeded weights at tiny sizes:
``model.apply``, the two serving programs through the pool's third kind of
cache, and ``ServeEngine`` itself.

Tolerance: everything here is float32 at ``highest`` matmul precision; the
program runs the chunk form of the recurrence and the reference a scan over
the tokens, so they differ by the order of float32 sums and by the chunk
form's solve: measured 1.5e-6 on logits of magnitude 0.5.  ``ATOL`` is
2e-5; a state kept in bfloat16 between calls is out by 1e-3
(``test_a_bfloat16_state_is_outside_the_tolerance``) and so is bfloat16
compute, so a run in the next precision down fails every case here.
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
)
from torch_automatic_distributed_neural_network_tpu.models.transformer_core import (
    DecoderLM,
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


ref = _load(os.path.join(BENCH, "reference", "olmo_hybrid.py"),
            "olmo_hybrid_reference")
weights = _load(os.path.join(BENCH, "lib", "weights.py"), "bench_weights")
weights_gdn = _load(os.path.join(BENCH, "lib", "weights_gdn.py"),
                    "bench_weights_gdn")

CHUNK, BS = 8, 4
KEYS = dict(
    vocab_size=96, d_model=48, n_layers=8, n_heads=6, n_kv_heads=6,
    head_size=8, d_ff=80, max_seq_len=128, norm="rmsnorm", norm_eps=1e-6,
    act="swiglu", pos="rope", rope_layers="sliding", tie_embeddings=False,
    layer_types=(["linear_attention"] * 3 + ["full_attention"]) * 2,
    qk_norm=True, qk_norm_over="projection", sandwich_norm=True,
    pre_norm=False, linear_key_heads=6, linear_value_heads=6,
    linear_key_head_dim=8, linear_value_head_dim=16, linear_conv_kernel=4,
    linear_neg_eigval=True)
LINEAR = [i for i, k in enumerate(KEYS["layer_types"])
          if k == "linear_attention"]

# key whose change must show: the reference with that key switched differs
# from the program by far more than the tolerance, so the comparison does
# test what the name says
SWITCHES = {
    "beta_reaches_two": {"linear_neg_eigval": False},
    "norm_over_the_whole_projection": None,  # switched in the program
    "four_taps": {"linear_conv_kernel": 3},
}


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


def test_decays_are_the_familys():
    """Half the heads keep more than 0.9 of their state a token."""
    flat = _params()
    a = np.concatenate([
        np.exp(-np.exp(flat[f"layers_{i}/attn/A_log"]) * np.log1p(np.exp(
            flat[f"layers_{i}/attn/dt_bias"]))) for i in LINEAR])
    assert np.median(a) > 0.9 and a.min() < 0.9


@pytest.mark.parametrize("switch", sorted(SWITCHES))
def test_model_apply_matches_reference(switch):
    """Two sequences of 70 positions (the chunk form's sub-chunk is 64: one
    whole and a part) in one batch."""
    flat = _params()
    toks = np.stack([_tokens(70, 1), _tokens(70, 2)])
    got = np.asarray(_model().apply({"params": weights.nest(flat)}, toks))
    want = np.asarray(ref.forward_logits(flat, KEYS, toks))
    np.testing.assert_allclose(got, want, atol=ATOL, rtol=0)
    if switch == "norm_over_the_whole_projection":
        # a gain a head channel in place of one a projection channel
        other = TransformerConfig(**{**KEYS, "qk_norm_over": "head"},
                                  dtype=jnp.float32, remat=False)
        p = weights.nest(flat)
        for i, kind in enumerate(KEYS["layer_types"]):
            if kind == "full_attention":
                for n in ("q_norm", "k_norm"):
                    gain = p[f"layers_{i}"]["attn"][n]["scale"]
                    p[f"layers_{i}"]["attn"][n]["scale"] = gain[:8]
        off = np.asarray(DecoderLM(other).apply({"params": p}, toks))
    else:
        keys = {**KEYS, **SWITCHES[switch]}
        leaves = {k: v[:s[0]] if k.endswith("attn/conv") else v
                  for k, v in flat.items()
                  for s in [ref.param_shapes(keys)[k]]}
        off = np.asarray(ref.forward_logits(leaves, keys, toks))
    assert np.abs(off - got).max() > 100 * ATOL, switch


def test_bf16_compute_is_outside_the_tolerance():
    flat = _params()
    toks = _tokens(40, 1)[None]
    got = np.asarray(_model(dtype=jnp.bfloat16).apply(
        {"params": weights.nest(flat)}, toks))
    assert np.abs(got - _want(flat, toks[0])).max() > 20 * ATOL


def test_parameter_counts_are_the_published_layers():
    """215.5M a linear layer and 185.8M a full one at the published widths
    (88.7M and 59.0M of mixer beside 126.8M of SwiGLU), and ``num_params``
    is what ``model.init`` builds."""
    cfg = TransformerConfig(
        vocab_size=100352, d_model=3840, n_layers=4, n_heads=30,
        head_size=128, d_ff=11008, norm="rmsnorm", act="swiglu", pos="rope",
        rope_layers="sliding", tie_embeddings=False,
        layer_types=["linear_attention"] * 3 + ["full_attention"],
        qk_norm=True, qk_norm_over="projection", sandwich_norm=True,
        pre_norm=False, linear_key_heads=30, linear_value_heads=30,
        linear_key_head_dim=96, linear_value_head_dim=192,
        linear_neg_eigval=True)
    ffn = 3 * 3840 * 11008 + 2 * 3840
    assert round((cfg.mixer_params("linear_attention") + ffn) / 1e6, 1) == 215.6
    assert round(cfg.mixer_params("linear_attention") / 1e6, 1) == 88.8
    assert round((cfg.mixer_params("full_attention") + ffn) / 1e6, 1) == 185.8
    built = jax.eval_shape(DecoderLM(cfg).init, jax.random.key(0),
                           np.zeros((1, 8), np.int32))["params"]
    assert cfg.num_params() == sum(
        int(np.prod(x.shape)) for x in jax.tree.leaves(built))
    assert round((cfg.num_params() - 2 * 100352 * 3840 - 3840) / 1e6, 1) \
        == 832.5  # one period


# -- the two serving programs, driven by hand ----------------------------------


class Served:
    """The programs over a pool of ``n_slots`` slots, as the engine drives
    them: ``prefill(slot, tokens)`` in chunks, ``decode({slot: token})`` one
    step.  Both return logits."""

    def __init__(self, flat: dict, n_slots: int = 3, max_len: int = 96,
                 round_state=None):
        self.cfg = TransformerConfig(**KEYS, dtype=jnp.float32, remat=False)
        self.params = weights.nest(flat)
        self.n_slots, self.MB = n_slots, blocks_for_tokens(max_len, BS)
        self.pool = PagedKVPool(
            self.cfg, num_blocks=n_slots * self.MB + 1, block_size=BS,
            dtype=jnp.float32, n_slots=n_slots, max_blocks=self.MB,
            prefill_chunk=CHUNK)
        self.kv = self.pool.kv
        self.rows = {s: self.pool.table_row(self.pool.alloc(self.MB), self.MB)
                     for s in range(n_slots)}
        self.ctx = {}
        self.round_state = round_state
        self._chunk = jax.jit(lambda *a: programs.prefill_chunk(
            *a, cfg=self.cfg, max_blocks=self.MB))
        self._step = jax.jit(lambda *a: programs.decode_logits(
            *a, cfg=self.cfg))

    def _rounded(self):
        if self.round_state is not None:
            self.kv = {**self.kv, "k": [
                x.astype(self.round_state).astype(x.dtype) if i in LINEAR
                else x for i, x in enumerate(self.kv["k"])]}

    def chunks(self, slot: int, tokens):
        """A chunk a ``next``: ``{its last real position: logits}``."""
        tokens = list(tokens)
        self.ctx[slot] = len(tokens)
        for pos in range(0, len(tokens), CHUNK):
            part = tokens[pos:pos + CHUNK]
            self.kv, lg = self._chunk(self.params, self.kv, programs.pack_chunk(
                self.rows[slot], part + [0] * (CHUNK - len(part)), pos,
                len(part) - 1, slot), self.pool.win_tables[slot])
            self._rounded()
            yield {pos + len(part) - 1: np.asarray(lg[0])}

    def prefill(self, slot: int, tokens) -> dict:
        return {p: r for c in self.chunks(slot, tokens) for p, r in c.items()}

    def decode(self, toks: dict) -> dict:
        S = self.n_slots
        tables = np.zeros((S, self.MB), np.int32)
        ctx, tok = np.zeros((S,), np.int32), np.zeros((S, 1), np.int32)
        active = np.zeros((S,), bool)
        for s, t in toks.items():
            tables[s], ctx[s], tok[s, 0], active[s] = (
                self.rows[s], self.ctx[s], t, True)
            self.ctx[s] += 1
        self.kv, lg, _ = self._step(
            self.params, self.kv, jnp.asarray(tables), self.pool.win_tables,
            jnp.asarray(ctx), jnp.asarray(tok), jnp.asarray(active))
        self._rounded()
        return {s: np.asarray(lg[s, 0]) for s in toks}

    def sequence(self, slot: int, seq, n_prompt: int) -> dict:
        out = self.prefill(slot, seq[:n_prompt])
        for pos in range(n_prompt, len(seq)):
            out[pos] = self.decode({slot: seq[pos]})[slot]
        return out


def _close(got: dict, want: np.ndarray, what: str = ""):
    for pos, row in got.items():
        np.testing.assert_allclose(row, want[pos], atol=ATOL, rtol=0,
                                   err_msg=f"{what} position {pos}")


def test_serving_programs_match_reference():
    """A prompt of 21 tokens (three chunks of 8, the last with 5 real rows:
    no whole chunk and fewer than a sub-chunk) and 30 decode steps through
    the state pool, in slot 1 of 3: the logits of each chunk's last row and
    of every decode step are the reference's full forward pass's."""
    flat = _params()
    seq = _tokens(51, 5)
    got = Served(flat).sequence(1, seq, 21)
    assert sorted(got) == [7, 15] + list(range(20, 51))
    _close(got, _want(flat, seq))


def test_a_bfloat16_state_is_outside_the_tolerance():
    """The same run with every linear layer's state rounded through
    bfloat16 after each call: out by far more than the tolerance."""
    flat = _params()
    seq = _tokens(51, 5)
    got = Served(flat, round_state=jnp.bfloat16).sequence(1, seq, 21)
    want = _want(flat, seq)
    assert max(np.abs(r - want[p]).max() for p, r in got.items()) > 20 * ATOL


def test_neighbouring_slots_do_not_touch_each_others_state():
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


def test_a_reused_slot_starts_from_zeros():
    """A second request in a slot whose state the first left behind: its
    first chunk starts from zeros (``pos0 == 0``), no host write between."""
    flat = _params()
    sv = Served(flat)
    sv.sequence(2, _tokens(30, 9), 12)
    assert float(jnp.abs(sv.kv["k"][0][3]).max()) > 0  # slot 2 is row 3
    seq = _tokens(26, 10)
    _close(sv.sequence(2, seq, 9), _want(flat, seq))


def test_the_null_row_takes_inactive_slots_writes():
    """A decode step with slots 0 and 2 inactive: their rows (1 and 3) of
    every linear layer's state and tail are what they were, bit for bit,
    and the tail's null row took the write."""
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


def test_pool_bytes_are_the_arithmetic():
    """At the cell's shape: 12 linear layers of 9 rows of (30 x 96 x 192
    float32 + 3 x 11,520 bfloat16), 4 full layers of 4,609 pages of 16
    tokens of 30 x 128 keys and values; the allocator counts the pages."""
    cfg = TransformerConfig(**{
        **KEYS, "d_model": 3840, "n_layers": 16, "n_heads": 30,
        "n_kv_heads": 30, "head_size": 128,
        "layer_types": (["linear_attention"] * 3 + ["full_attention"]) * 4,
        "linear_key_heads": 30, "linear_value_heads": 30,
        "linear_key_head_dim": 96, "linear_value_head_dim": 192})
    made = {}

    def arrays():
        made["pool"] = PagedKVPool(cfg, num_blocks=4609, block_size=16,
                                   n_slots=8, max_blocks=2112,
                                   prefill_chunk=512)
        return made["pool"].kv

    kv = jax.eval_shape(arrays)
    pool = made["pool"]
    assert pool.bytes_full == 4 * 4609 * 16 * 30 * 128 * 2 * 2
    assert pool.bytes_state == (12 * 9 * 2211840, 12 * 9 * 69120)
    assert pool.bytes_window == 0
    assert pool.total_bytes == sum(
        int(np.prod(x.shape)) * x.dtype.itemsize for x in jax.tree.leaves(kv))
    assert pool.allocator.num_blocks == 4609
    assert round(pool.bytes_full / 1e9, 2) == 4.53
    assert round(sum(pool.bytes_state) / 1e9, 2) == 0.25


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


@pytest.mark.parametrize("option", [
    "chunked", "quant_kv", "optimistic", "dense"])
def test_engine_serves_the_references_first_choice(option, tmp_path):
    """The engine itself, scheduler and all: six requests over three slots
    (slots are reused, chunks and decode steps interleave, the last chunks
    are padded), each served token the reference's first choice at its
    position (int8 KV on the full layers: within its quantization error of
    the first)."""
    kw = {"chunked": {},
          "quant_kv": {"quant_kv": True},
          "optimistic": {"admission": "optimistic"},
          "dense": {"attention_impl": "dense"}}[option]
    flat = _params()
    journal = Journal(None, host0_only=False)
    eng = _engine(flat, journal, **kw)
    reqs = [eng.submit([int(t) for t in _tokens(n, 10 + i)], max_new_tokens=m)
            for i, (n, m) in enumerate(SHAPES)]
    eng.run()
    eng.scheduler.check_invariants()
    # int8 keys and values are off by up to 1/254 of a head's range a token
    limit = 0.1 if option == "quant_kv" else ATOL
    for r, (n, m) in zip(reqs, SHAPES):
        assert len(r.out_tokens) == m
        assert _regret(flat, r) <= limit, (option, n, m)
    if option != "chunked":
        return
    ev = journal.named("serve.engine")[-1]
    assert ev["layer_kinds"] == KEYS["layer_types"]
    assert (ev["state_bytes_linear"], ev["conv_bytes_linear"]) \
        == eng.pool.bytes_state
    assert ev["state_bytes_linear"] == 6 * 4 * 6 * 8 * 16 * 4
    assert ev["conv_bytes_linear"] == 6 * 4 * 3 * 6 * 32 * 4
    assert ev["kv_bytes_full"] == eng.pool.bytes_full > 0
    assert ev["kv_bytes_window"] == 0
    from torch_automatic_distributed_neural_network_tpu.obs import (
        report as obs_report,
    )

    path = tmp_path / "journal.jsonl"
    path.write_text("".join(json.dumps(r) + "\n" for r in journal.records))
    text = obs_report.format_report(obs_report.generate(str(path)))
    assert "of recurrent state" in text and "(6 linear layers)" in text


def test_a_preempted_request_restarts_and_serves_the_same_tokens():
    """A pool too small for three growing requests under optimistic
    admission: one is preempted, queued again and prefilled again from
    position 0, where its slot's state starts from zeros; every request
    serves what it serves alone."""
    flat = _params()
    shapes = [(20, 30), (22, 28), (18, 30)]
    alone = []
    eng = _engine(flat)  # one engine, a request at a time: each alone in it
    for i, (n, m) in enumerate(shapes):
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
    more before the host reads the EOS, which writes one token too many
    into its slot's state.  Nobody reads that state again: the requests
    that take the slot afterwards serve the reference's first choice."""
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
    "prefix_cache": ({"prefix_cache": True},
                     "state at the matched boundary"),
    "speculative": ({"speculative": 2}, "cannot be taken out"),
    "mesh": ({"mesh": "a mesh"}, "no sharded form"),
}


@pytest.mark.parametrize("option", sorted(REFUSED))
def test_unsupported_options_are_refused_at_construction(option):
    """What a model with linear layers is not served with, each refusal
    with its reason."""
    kw, reason = REFUSED[option]
    with pytest.raises(ValueError, match=f"{option}.*{reason}"):
        _engine(_params(), **kw)


@pytest.mark.parametrize("bad,reason", [
    ({"linear_key_heads": 3}, "linear_key_heads == linear_value_heads"),
    ({"layer_types": ["full_attention"] * 8}, "layer_types has none"),
    ({"sandwich_norm": False}, "without norms"),
    ({"layer_types": ["conv"] * 8}, "entries of"),
])
def test_config_refuses_what_it_cannot_build(bad, reason):
    with pytest.raises(ValueError, match=reason):
        TransformerConfig(**{**KEYS, **bad})
