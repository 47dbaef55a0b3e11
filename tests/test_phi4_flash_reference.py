"""A decoder-hybrid-decoder (``layer_types`` with ``state_space`` layers
beside windowed differential attention, one ``full_attention`` layer whose
pages the ``shared_attention`` layers read again, ``gated_memory`` layers
that read the scan's output of the same token, no positional signal) against
the plain reference ``benchmark/reference/phi4_flash.py``, on seeded weights
at tiny sizes: ``model.apply``, what a configuration builds here, and the
prefill program by hand; ``ServeEngine`` itself is in
``test_phi4_flash_engine.py`` (shared: ``phi4_flash_tiny.py``).

Tolerance: everything here is float32 at ``highest`` matmul precision; the
program attends a chunk's keys a block at a time through the pages and the
reference over the whole sequence, and both scan the tokens one by one
(``ops/ssm.py``'s CPU path is the recurrence itself), so they differ by the
order of float32 sums: measured 1.9e-7 on logits of magnitude 0.5.  ``ATOL``
is 5e-6, twenty-five times that; bfloat16 compute is out by 6e-3, a dropped
second softmax by 9e-3, a state or a tail lost at a chunk boundary by 1e-4
and more, so a run in the next precision down, or one that leaves out part
of the mathematics, fails every case here.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torch_automatic_distributed_neural_network_tpu.inference import decode
from torch_automatic_distributed_neural_network_tpu.inference.serve import (
    programs,
)
from torch_automatic_distributed_neural_network_tpu.inference.serve.kv_pool import (
    PagedKVPool,
    state_row_bytes,
    window_pages,
)
from torch_automatic_distributed_neural_network_tpu.models.transformer_core import (
    DecoderLM,
    TransformerConfig,
)
from torch_automatic_distributed_neural_network_tpu.ops.attention import (
    diff_heads,
)

from phi4_flash_tiny import (
    ATOL,
    BS,
    CHUNK,
    _highest,  # noqa: F401
    KEYS,
    _model,
    _params,
    _published,
    ref,
    SCANS,
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


def test_the_published_sizes_build_the_published_count():
    """3,852,562,944 parameters, the published 3.8B, by the analytic count,
    by the program's parameter tree and by the reference's shapes; nothing
    is cut: depth 32, vocabulary 200,064, every head; every number of the
    source under its own key."""
    c = _published()
    keys = c["model"]
    cfg = TransformerConfig(**keys)
    assert cfg.num_params() == c["parameters"] == 3852562944
    abstract = jax.eval_shape(DecoderLM(cfg).init, jax.random.key(0),
                              np.zeros((1, 8), np.int32))["params"]
    prog = {k: tuple(v.shape) for k, v in weights.unnest(abstract).items()}
    assert sum(int(np.prod(s)) for s in prog.values()) == 3852562944
    assert prog == ref.param_shapes(keys)
    assert prog["embed/embedding"] == (200064, 2560)
    kinds = keys["layer_types"]
    assert [kinds.count(k) for k in (
        "state_space", "sliding_attention", "full_attention", "gated_memory",
        "shared_attention")] == [9, 8, 1, 7, 7]
    assert kinds[17] == "full_attention" and cfg.cross_start == 18
    assert {cfg.source_layer(i) for i in range(19, 32, 2)} == {17}
    assert {cfg.source_layer(i) for i in range(18, 32, 2)} == {16}
    assert (cfg.mixer_params("state_space"), cfg.mixer_params(
        "sliding_attention"), cfg.mixer_params("shared_attention"),
        cfg.mixer_params("gated_memory")) == (41241600, 19668864, 13112704,
                                              26214400)
    assert c["reduced"] == {} and c["compute_dtype"] == "bfloat16"
    assert (c["hidden_size"], c["num_hidden_layers"], c["vocab_size"],
            c["num_attention_heads"], c["num_key_value_heads"],
            c["intermediate_size"], c["sliding_window"],
            c["mb_per_layer"]) == (2560, 32, 200064, 40, 20, 10240, 512, 2)
    assert (keys["d_model"], keys["n_layers"], keys["ssm_inner"],
            keys["ssm_state"], keys["ssm_dt_rank"]) == (2560, 32, 5120, 16,
                                                        160)
    # a slot's row in a state-space layer: [16, 5120] float32 and a tail of
    # three rows of 5,120 channels
    assert state_row_bytes(cfg, jnp.bfloat16, "state_space") == (
        16 * 5120 * 4, 3 * 5120 * 2)
    assert window_pages(512, 512, 64) == 17


def test_decays_are_the_familys():
    """``weights_gdn.decay_leaf`` finds ``A_log`` [N, d_in] and ``dt_bias``
    by the end of their path: steps of 0.001-0.1 against rates up to 16, so
    most of the state's entries outlive a chunk (under 0.02 n every entry
    would keep exp(-0.69) of itself a token and a lost carry would pass)."""
    flat = _params()
    kept = []
    for i in SCANS:
        A, dt = flat[f"layers_{i}/attn/A_log"], flat[f"layers_{i}/attn/dt_bias"]
        assert A.shape == (8, 96) and dt.shape == (96,)
        kept.append(np.exp(-np.exp(A) * np.log1p(np.exp(dt))[None, :]))
    kept = np.concatenate(kept).ravel()
    assert np.median(kept) > 0.8 and kept.min() < 0.5 and kept.max() > 0.999


def test_model_apply_matches_reference():
    """Two sequences of 70 positions (the window is 12) in one batch."""
    flat = _params()
    toks = np.stack([_tokens(70, 1), _tokens(70, 2)])
    got = np.asarray(jax.jit(_model().apply)(
        {"params": weights.nest(flat)}, toks))
    want = np.asarray(ref.forward_logits(flat, KEYS, toks))
    np.testing.assert_allclose(got, want, atol=ATOL, rtol=0)


def test_bf16_compute_is_outside_the_tolerance():
    flat = _params()
    toks = _tokens(40, 1)[None]
    got = np.asarray(jax.jit(_model(dtype=jnp.bfloat16).apply)(
        {"params": weights.nest(flat)}, toks))
    assert np.abs(got - _want(flat, toks[0])).max() > 20 * ATOL


def _without_second_softmax(flat: dict) -> dict:
    """The same weights with lambda brought to 0 in every attention layer:
    ``exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init(l) = 1 - (1 +
    lambda_init(l)) + lambda_init(l)``.  What is left is ONE softmax a head
    pair (the pair's norm and constant as they were)."""
    out = dict(flat)
    hd = KEYS["d_model"] // KEYS["n_heads"]
    for i, kind in enumerate(KEYS["layer_types"]):
        if "attention" not in kind:
            continue
        init = float(ref.lambda_init(i))
        root = np.sqrt(np.log1p(init) / hd)
        for name, v in (("q1", 0.0), ("k1", 0.0), ("q2", root), ("k2", root)):
            out[f"layers_{i}/attn/lambda_{name}"] = jnp.full((hd,), v)
    return out


def test_a_dropped_second_softmax_is_outside_the_tolerance():
    """Differential attention with its second softmax left out is another
    model on the same weights, five hundred tolerances away; and the pairs
    are the ADJACENT heads on the two key heads of a group."""
    flat = _params()
    toks = _tokens(40, 4)
    got = np.asarray(jax.jit(_model().apply)(
        {"params": weights.nest(flat)}, toks[None]))[0]
    np.testing.assert_allclose(got, _want(flat, toks), atol=ATOL, rtol=0)
    off = _want(_without_second_softmax(flat), toks)
    assert np.abs(off - got).max() > 100 * ATOL
    key_of, values_of = diff_heads(40, 20)
    assert list(key_of[:8]) == [0, 1, 0, 1, 2, 3, 2, 3]
    assert values_of[:8].tolist() == [[0, 1]] * 4 + [[2, 3]] * 4


def test_no_position_reaches_the_attention_layers():
    """``pos: none``: nothing is added to the embedding and nothing rotated
    (rotating the window layers, which ``pos: rope`` with ``rope_layers:
    sliding`` would, is another model)."""
    cfg = _model().cfg
    assert not any(cfg.layer_rotates(k) for k in KEYS["layer_types"])
    abstract = jax.eval_shape(_model().init, jax.random.key(0),
                              np.zeros((1, 8), np.int32))["params"]
    assert "pos_embed" not in abstract
    flat = _params()
    toks = _tokens(30, 4)[None]
    got = np.asarray(jax.jit(_model().apply)(
        {"params": weights.nest(flat)}, toks))
    rotated = np.asarray(jax.jit(_model(
        {**KEYS, "pos": "rope", "rope_layers": "sliding"}).apply)(
            {"params": weights.nest(flat)}, toks))
    assert np.abs(got - rotated).max() > 10 * ATOL


def test_what_the_configuration_may_not_say():
    with pytest.raises(ValueError, match="needs a state_space layer before"):
        TransformerConfig(**{**KEYS, "layer_types": ["gated_memory"] + KEYS[
            "layer_types"][1:]})
    with pytest.raises(ValueError, match="needs a full_attention layer"):
        TransformerConfig(**{**KEYS, "layer_types": KEYS["layer_types"][:3]
                             + ["sliding_attention"]
                             + KEYS["layer_types"][4:]})
    with pytest.raises(ValueError, match="describe state_space layers"):
        TransformerConfig(vocab_size=8, d_model=8, n_layers=1, n_heads=2,
                          layer_types=["full_attention"], ssm_inner=16)
    with pytest.raises(ValueError, match="diff_attention pairs"):
        TransformerConfig(**{**KEYS, "n_kv_heads": 1})


# -- the prefill program by hand -------------------------------------------------


def _pool(cfg, n_slots=2, max_blocks=24):
    return PagedKVPool(cfg, num_blocks=n_slots * max_blocks + 1, block_size=BS,
                       dtype=jnp.float32, n_slots=n_slots,
                       max_blocks=max_blocks, prefill_chunk=CHUNK)


def test_the_pool_holds_two_paged_sets_and_two_rows_for_eight_layers():
    """One full layer's pages, one ring, two state rows; the four
    cross-decoder layers keep arrays of no elements, and every byte count
    says so."""
    cfg = _model().cfg
    pool = _pool(cfg)
    assert (pool.n_full, pool.ring.count(True), pool.state.count(True),
            pool.none.count(True)) == (1, 1, 2, 4)
    assert [x.size for x, none in zip(pool.kv["k"], pool.none) if none] \
        == [0] * 4
    row = 2 * KEYS["n_kv_heads"] * 6 * 4  # keys and values, float32
    assert pool.bytes_per_block == BS * row
    assert pool.bytes_full == 49 * BS * row
    W = window_pages(12, CHUNK, BS)
    assert pool.bytes_window == (2 * W + 1) * BS * row
    assert pool.bytes_state == (2 * 3 * 8 * 96 * 4, 2 * 3 * 3 * 96 * 4)
    assert pool.total_bytes == sum(
        x.nbytes for side in pool.kv.values() for x in side)


def _prefill_logits(flat, toks):
    """``prefill_chunk`` by hand over ``toks``, a chunk at a time: ``(the
    last chunk's logits [V], the pool's arrays)``."""
    cfg = _model().cfg
    params = decode.per_layer_params(weights.nest(flat), cfg)
    pool, MB, n = _pool(cfg), 24, len(toks)
    row = pool.table_row(pool.alloc(-(-n // BS)), MB)
    fn = jax.jit(lambda kv, packed, win: programs.prefill_chunk(
        params, kv, packed, win, cfg=cfg, max_blocks=MB))
    kv = pool.kv
    for pos in range(0, n, CHUNK):
        part = list(toks[pos:pos + CHUNK])
        kv, logits = fn(kv, programs.pack_chunk(
            row, part + [0] * (CHUNK - len(part)), pos, len(part) - 1,
            slot=1), pool.win_tables[1])
    return np.asarray(logits)[0], kv


@pytest.mark.parametrize("n", [5, 8, 21])
def test_prefill_stops_at_the_self_decoder_but_for_the_last_row(n):
    """``prefill_chunk`` over a prompt of one, exactly one and three chunks
    (the last padded): the returned logits are the reference's at the
    prompt's last position, though only that ONE row ran layers 4..7 (its
    cross layers attend layer 3's pages, which the earlier chunks wrote;
    its memory units read the memory of the same row); the state and the
    tail crossed two chunk boundaries."""
    flat = _params()
    assert _model().cfg.cross_start == 4
    toks = _tokens(n, 7)
    logits, kv = _prefill_logits(flat, toks)
    np.testing.assert_allclose(logits, _want(flat, toks)[-1], atol=ATOL,
                               rtol=0)
    # the cross-decoder's entries stay arrays of no elements
    assert [kv["k"][i].size for i in (4, 5, 6, 7)] == [0] * 4


@pytest.mark.parametrize("lost", ["state", "tail"])
def test_a_lost_carry_is_outside_the_tolerance(monkeypatch, lost):
    """A chunk that starts its scan from zeros, or its convolution from a
    tail of zeros, where the chunk before left a state and a tail, is
    twenty tolerances and more from the reference at the last of 21 positions: the
    draw of ``A_log`` and ``dt_bias`` keeps a state alive over a chunk (and
    ``phi4_flash_tiny._params`` says why the scan's inputs are scaled)."""
    flat = _params()
    toks = _tokens(21, 7)
    if lost == "state":
        real = programs.ssm_chunk
        monkeypatch.setattr(
            programs, "ssm_chunk", lambda c, dt, A, B, C, D, h0: real(
                c, dt, A, B, C, D, jnp.zeros_like(h0)))
    else:
        real = programs._chunk_scan
        monkeypatch.setattr(
            programs, "_chunk_scan", lambda shared, state, tails, *a: real(
                shared, state, jnp.zeros_like(tails), *a))
    logits, _ = _prefill_logits(flat, toks)
    assert np.abs(logits - _want(flat, toks)[-1]).max() > 10 * ATOL
