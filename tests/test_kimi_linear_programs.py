"""The three serving programs over the tiny Kimi-Linear hybrid
(``kimi_linear_tiny.py``), driven by hand through a pool that holds state
rows AND latent pages, against ``benchmark/reference/kimi_linear.py``
(tolerance: ``test_kimi_linear_reference.py``)."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import serve_by_hand

from torch_automatic_distributed_neural_network_tpu.inference.serve.kv_pool import (
    PagedKVPool,
    state_row_bytes,
)
from torch_automatic_distributed_neural_network_tpu.models.transformer_core import (
    TransformerConfig,
)

from kimi_linear_tiny import (
    ATOL,
    BS,
    CHUNK,
    _highest,
    KEYS,
    LATENT,
    LINEAR,
    _model,
    _params,
    _published,
    RANK,
    ROT,
    _tokens,
    _want,
    weights,
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


def test_a_pool_of_rows_and_latent_pages_refuses_what_it_has_no_form_for():
    cfg = _model().cfg
    with pytest.raises(ValueError, match="no sharded form"):
        PagedKVPool(cfg, num_blocks=9, block_size=4, mesh="a mesh")
    with pytest.raises(ValueError, match="no sharded and no int8 form"):
        PagedKVPool(cfg, num_blocks=9, block_size=4, quantize=True)
