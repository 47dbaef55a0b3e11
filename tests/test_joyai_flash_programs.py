"""The three serving programs over the tiny model of latent layers
(``joyai_flash_tiny.py``), driven by hand through the pool's latent pages,
against ``benchmark/reference/joyai_flash.py`` (tolerance:
``test_joyai_flash_reference.py``)."""

from __future__ import annotations

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import serve_by_hand

from torch_automatic_distributed_neural_network_tpu.inference.serve.kv_pool import (
    PagedKVPool,
)
from torch_automatic_distributed_neural_network_tpu.models.transformer_core import (
    TransformerConfig,
)

from joyai_flash_tiny import (
    ATOL,
    BENCH,
    BS,
    CHUNK,
    _highest,
    KEYS,
    _model,
    _params,
    RANK,
    ROT,
    _tokens,
    _want,
    weights,
    _without_rotated_key,
)

pytestmark = pytest.mark.usefixtures("_highest")


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


def test_a_latent_pool_refuses_what_it_has_no_form_for():
    cfg = _model().cfg
    for kw in ({"quantize": True}, {"mesh": "a mesh"}):
        with pytest.raises(ValueError, match="no sharded and no int8 form"):
            PagedKVPool(cfg, num_blocks=9, block_size=4, **kw)
