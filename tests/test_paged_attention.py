"""Paged-attention kernel + chunked-prefill parity (tier-1, fast).

The fused Pallas decode kernel (ops/paged_attention.py) runs here in
interpret mode (CPU) against :func:`paged_attention_reference`, which
IS the engine's dense ``gather_blocks`` + ``xla_attention`` decode path
— so kernel-vs-reference parity below is paged-vs-dense parity.  The
sweep covers block sizes {8, 16}, fp and int8 KV pools, ragged slot
lengths, sliding windows, GQA, and inactive (null-table) slots.  The
engine-level tests pin token parity between ``attention_impl="paged"``
and ``"dense"`` through real serving traffic — including a
preempted-then-recomputed request — and chunked prefill against
``generate()``'s one forward over the prompt, through the same slots.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torch_automatic_distributed_neural_network_tpu.inference import generate
from torch_automatic_distributed_neural_network_tpu.inference.quant import (
    quantize_kv,
)
from torch_automatic_distributed_neural_network_tpu.inference.serve import (
    ServeEngine,
)
from torch_automatic_distributed_neural_network_tpu.models import GPT2
from torch_automatic_distributed_neural_network_tpu.ops.paged_attention import (
    paged_attention,
    paged_attention_reference,
)

VOCAB = 128


def _pool_state(rs, *, n_slots, max_blocks, block_size, kv_heads,
                head_dim, num_blocks, ctx_lens, quantized):
    """Random pool + per-slot block tables with the engine's layout:
    block 0 reserved (null), slot s owns ``blocks_for(ctx)`` blocks,
    table rows null-padded."""
    k = rs.randn(num_blocks, block_size, kv_heads, head_dim)
    v = rs.randn(num_blocks, block_size, kv_heads, head_dim)
    k = jnp.asarray(k, jnp.float32)
    v = jnp.asarray(v, jnp.float32)
    if quantized:
        k, v = quantize_kv(k), quantize_kv(v)
    tables = np.zeros((n_slots, max_blocks), np.int32)
    nxt = 1
    for s, ctx in enumerate(ctx_lens):
        n = ctx // block_size + 1  # blocks holding keys 0..ctx
        assert n <= max_blocks
        for j in range(n):
            tables[s, j] = nxt
            nxt += 1
    assert nxt <= num_blocks
    return k, v, jnp.asarray(tables), jnp.asarray(ctx_lens, jnp.int32)


@pytest.mark.parametrize("block_size", [8, 16])
@pytest.mark.parametrize("quantized", [False, True])
@pytest.mark.parametrize("window", [None, 5])
def test_kernel_matches_dense_reference(block_size, quantized, window):
    """Ragged contexts, GQA (8q/4kv), both pools, windowed and not."""
    rs = np.random.RandomState(0)
    S, Hq, kvH, hd = 4, 8, 4, 32
    max_blocks = 48 // block_size  # up to 48 keys per slot
    ctx_lens = [0, 5, 17, 41]  # ragged: empty-ish through multi-block
    k, v, tables, ctx = _pool_state(
        rs, n_slots=S, max_blocks=max_blocks, block_size=block_size,
        kv_heads=kvH, head_dim=hd, num_blocks=32, ctx_lens=ctx_lens,
        quantized=quantized)
    q = jnp.asarray(rs.randn(S, Hq, hd), jnp.float32)

    got = paged_attention(q, k, v, tables, ctx, window=window)
    want = paged_attention_reference(q, k, v, tables, ctx, window=window)
    err = float(jnp.max(jnp.abs(got - want[:, : Hq])))
    assert err < 1e-5, f"bs={block_size} quant={quantized} w={window}: {err}"


def test_kernel_null_table_slot_is_finite():
    """An all-null table (inactive slot) must produce finite output —
    the engine relies on masked-sampling, not on this value, but NaNs
    here would poison the scan's carried activations."""
    rs = np.random.RandomState(1)
    S, Hq, kvH, hd, bs = 2, 4, 4, 32, 8
    k, v, tables, ctx = _pool_state(
        rs, n_slots=S, max_blocks=4, block_size=bs, kv_heads=kvH,
        head_dim=hd, num_blocks=16, ctx_lens=[9, 0], quantized=False)
    tables = tables.at[1].set(0)  # slot 1: fully null table
    out = paged_attention(
        jnp.asarray(rs.randn(S, Hq, hd), jnp.float32), k, v, tables, ctx)
    assert bool(jnp.all(jnp.isfinite(out)))


# -- the folded (MXU) kernel: a work list of (slot, first page) items ---------

_BS, _MB = 16, 24  # 3 items of 8 pages on a full layer: 128 keys, max_len 384
_MAX = _BS * _MB


def _first_pages(ctx, running, window, pages, bs, mb):
    """The host's own reckoning of a slot's items, as their first pages: the
    entries ``lo .. ctx // bs`` in steps of ``pages`` from ``lo``, the
    band's first entry under a window; one item at 0 for an idle slot."""
    if not running:
        return [0]
    lo = 0 if window is None else max(ctx - window + 1, 0) // bs
    return list(range(lo, min(ctx // bs, mb - 1) + 1, pages))


def _poison_unlisted(pools, tables, s, firsts, pages):
    """NaN in every page of slot ``s`` that no listed item reads."""
    read = {j for p0 in firsts for j in range(p0, p0 + pages)}
    dead = [j for j in range(tables.shape[1]) if j not in read]
    for pool in pools:
        pool[tables[s, dead]] = np.nan

# name: (query heads, kv heads, contexts, the running slots or None for
# all, window)
_WORK_CASES = {
    "every_slot_inactive": (4, 2, [7, 200, 0, 383], [], None),
    "one_of_16_running": (4, 2, [0] * 11 + [300] + [0] * 4, [11], None),
    "ctx_0": (4, 2, [0, 0], None, None),
    "group_edges": (4, 2, [127, 128, 255, 256], None, None),
    "every_slot_full": (4, 2, [_MAX - 1] * 3, None, None),
    "window_band_inside_a_group": (4, 2, [300, 50, 200, 383], None, 100),
    "window_shorter_context": (4, 2, [5, 99, 100], None, 100),
    "window_with_idle_slots": (4, 2, [300, 250, 0, 383], [0, 3], 100),
    "gqa_48_on_8": (48, 8, [3, 130, 383], None, None),
    "mha_16_on_16": (16, 16, [3, 130, 383], None, 200),
}


@pytest.mark.parametrize("case", sorted(_WORK_CASES))
def test_folded_kernel_visits_only_live_groups(case, small_items):
    """``paged_attention`` over folded pages against the dense reference on
    ragged contexts: the kernel's grid is ``folded_work_list``, whose items
    are the host's own reckoning (8 pages from entry 0 on a full layer; the
    band cut evenly from its first entry under a window: 100 keys are one
    item of 8, 200 two of 7; one item for a slot that does not run), and
    every page outside a listed item is poisoned, so a visit to one would
    show."""
    from torch_automatic_distributed_neural_network_tpu.ops.paged_attention \
        import folded_work_list, item_pages

    Hq, kvH, ctxs, running, window = _WORK_CASES[case]
    S, hd = len(ctxs), 32
    running = list(range(S)) if running is None else running
    rs = np.random.RandomState(len(case))
    k = rs.randn(S * _MB + 1, _BS, kvH * hd).astype(np.float32)
    v = rs.randn(S * _MB + 1, _BS, kvH * hd).astype(np.float32)
    tables = 1 + rs.permutation(S * _MB).reshape(S, _MB).astype(np.int32)
    pages, steps = item_pages((k, v), _MB, window)
    assert (pages, steps) == {None: (8, 3), 100: (8, 1), 200: (7, 2)}[window]
    want = []
    for s, ctx in enumerate(ctxs):
        firsts = _first_pages(ctx, s in running, window, pages, _BS, _MB)
        want += [(s, p0) for p0 in firsts]
        _poison_unlisted((k, v), tables, s, firsts, pages)
        # the engine's table holds the null block past the newest key
        tables[s, ctx // _BS + 1:] = 0
    q = jnp.asarray(rs.randn(S, Hq, hd), jnp.float32)
    ctx = jnp.asarray(ctxs, jnp.int32)
    active = jnp.asarray([s in running for s in range(S)])
    work = folded_work_list(ctx, active, pools=(k, v), max_blocks=_MB,
                            window=window)
    n = int(work.n_items)
    assert n == len(want) <= work.dense == work.slot_of.shape[0] - 1
    assert work.dense == S * steps
    # slot order, ascending inside a slot, from the band's first entry
    assert list(zip(np.asarray(work.slot_of)[:n].tolist(),
                    np.asarray(work.page0_of)[:n].tolist())) == want
    got = paged_attention(q, jnp.asarray(k), jnp.asarray(v),
                          jnp.asarray(tables), ctx, window=window, work=work)
    assert bool(jnp.all(jnp.isfinite(got)))
    if running:
        want = paged_attention_reference(
            q, jnp.nan_to_num(jnp.asarray(k)), jnp.nan_to_num(jnp.asarray(v)),
            jnp.asarray(tables), ctx, window=window)
        np.testing.assert_allclose(np.asarray(got)[running],
                                   np.asarray(want)[running], atol=1e-5)
    if case == "every_slot_full":  # the list is the whole dense grid
        assert n == work.dense == S * 3
    # built where the caller gives none, every slot taken as running
    if len(running) == S:
        np.testing.assert_array_equal(
            np.asarray(got), np.asarray(paged_attention(
                q, jnp.asarray(k), jnp.asarray(v), jnp.asarray(tables), ctx,
                window=window)))


def test_folded_kernel_runs_as_many_grid_steps_as_the_list_has_items(
        small_items):
    """The grid's one dimension is the traced ``n_items``, no static bound:
    what ``attn_grid_items`` counts is what the kernel runs."""
    from torch_automatic_distributed_neural_network_tpu.ops.paged_attention \
        import folded_work_list

    S, Hq, kvH, hd = 4, 4, 2, 32
    k = jnp.zeros((S * _MB + 1, _BS, kvH * hd), jnp.float32)
    tables = jnp.zeros((S, _MB), jnp.int32)

    def run(q, ctx, active):
        work = folded_work_list(ctx, active, pools=(k, k), max_blocks=_MB)
        return paged_attention(q, k, k, tables, ctx, work=work), work.n_items

    args = (jnp.zeros((S, Hq, hd), jnp.float32),
            jnp.asarray([0, 127, 128, 383], jnp.int32),
            jnp.asarray([True, True, True, False]))
    jaxpr = jax.make_jaxpr(run)(*args).jaxpr
    (call,) = [e for e in jaxpr.eqns if e.primitive.name == "pallas_call"]
    mapping = call.params["grid_mapping"]
    assert len(mapping.grid) == 1 and mapping.num_dynamic_grid_bounds == 1
    assert call.invars[0] is jaxpr.outvars[1]  # the bound IS n_items
    assert int(run(*args)[1]) == 1 + 1 + 2 + 1


# -- the geometry of an item: the pages its bytes ask for, from a band's first --

# name: (pools a kernel reads, block size, lanes of a page's row, table
# entries, window, (pages an item takes, the most items a slot has)): the
# cells' shapes, bfloat16
_SHAPES = {
    "trinity-large-ep8.full": (2, 16, 8 * 128, 832, None, (16, 52)),
    "trinity-large-ep8.window4096": (2, 16, 8 * 128, 832, 4096, (16, 17)),
    "latent-640-lanes": (1, 64, 640, 544, None, (16, 34)),
    "gpt2-1p3b": (2, 16, 16 * 128, 64, None, (8, 8)),
    "olmo-hybrid-7b-pp2": (2, 16, 30 * 128, 2112, None, (8, 264)),
    "phi4-mini-flash-3p8b.full": (2, 64, 20 * 64, 544, None, (8, 68)),
    "phi4-mini-flash-3p8b.window512": (2, 64, 20 * 64, 544, 512, (5, 2)),
    # a page pair of 262 KB: the least item, 72 of them under 576 entries
    "solar-open2-250b-ep8": (2, 64, 8 * 128, 576, None, (8, 72)),
}


def _contexts(case, edge, bs, max_len):
    """(contexts, the running slots or None for all) round ``edge``: the
    window, or an item's keys."""
    ctxs, running = {
        "ragged": ([0, 3, bs - 1, bs, edge // 2, edge + 7,
                    3 * edge + bs // 2, max_len - 1], None),
        "edges": ([edge - 2, edge - 1, edge, edge + bs - 2, edge + bs - 1,
                   edge + bs, 2 * edge - 1, 2 * edge], None),
        "every_slot_full": ([max_len - 1] * 3, None),
        "idle_slots": ([5 * edge // 2, 0, max_len - 1, edge, 17], [0, 3]),
    }[case]
    return [min(c, max_len - 1) for c in ctxs], running


@pytest.mark.parametrize("case", ["ragged", "edges", "every_slot_full",
                                  "idle_slots"])
@pytest.mark.parametrize("shape", sorted(_SHAPES))
def test_work_list_items_take_their_bytes_and_tile_the_band(shape, case):
    """``item_pages`` at the cells' page shapes (16 pages a step where a
    page weighs 64 or 82 KB, 8 from 131 KB on, a window's band cut evenly:
    9 pages of 328 KB in 2 items of 5) and ``folded_work_list`` of them: a
    slot's items are disjoint runs of ``pages`` entries that cover every
    entry with an attendable key, the first at entry 0 or at the band's
    first entry, so that a window copies at most ``band + pages - 1``
    entries; a slot that does not decode keeps ONE item; no item reads past
    the padded table; the two counters are the copies and the live
    entries."""
    from torch_automatic_distributed_neural_network_tpu.ops.paged_attention \
        import folded_work_list, item_pages

    n_pools, bs, lanes, mb, window, want = _SHAPES[shape]
    pools = (jax.ShapeDtypeStruct((4097, bs, lanes), jnp.bfloat16),) * n_pools
    pages, steps = item_pages(pools, mb, window)
    assert (pages, steps) == want
    ctxs, running = _contexts(case, window or pages * bs, bs, mb * bs)
    S = len(ctxs)
    running = list(range(S)) if running is None else running
    active = jnp.asarray([s in running for s in range(S)])
    work = folded_work_list(jnp.asarray(ctxs, jnp.int32), active,
                            pools=pools, max_blocks=mb, window=window)
    n = int(work.n_items)
    assert n <= work.dense == S * steps == work.slot_of.shape[0] - 1
    slot_of = np.asarray(work.slot_of)[:n].tolist()
    page0_of = np.asarray(work.page0_of)[:n].tolist()
    assert slot_of == sorted(slot_of) and set(slot_of) == set(range(S))
    padded = mb + (pages if window else -mb % pages)
    copied = live = 0
    for s, ctx in enumerate(ctxs):
        mine = [p for t, p in zip(slot_of, page0_of) if t == s]
        assert (int(work.first[s]), int(work.last[s])) == (
            slot_of.index(s), slot_of.index(s) + len(mine) - 1)
        if s not in running:
            assert mine == [0]
            continue
        lo = 0 if window is None else max(ctx - window + 1, 0) // bs
        hi = ctx // bs
        # runs of ``pages`` from ``lo``: disjoint, and every entry that
        # holds an attendable key in exactly one
        assert mine == list(range(lo, hi + 1, pages))
        assert mine[-1] + pages <= padded
        assert len(mine) <= steps
        assert len(mine) * pages <= hi - lo + 1 + pages - 1
        copied += len(mine) * pages
        live += hi - lo + 1
    assert (int(work.pages_copied), int(work.pages_live)) == (copied, live)
    if "phi4" in shape and window and case == "edges":
        # a band of 8 or 9 pages: 10 copies where groups of 8 made 16
        assert copied == 10 * S and live in range(8 * S, 9 * S + 1)


# name: (latent, differential wiring, block size, table entries, window,
# ITEM_BYTES in pages, (pages an item takes, items a slot), contexts)
_ITEM_CASES = {
    "window_items_of_5": (False, False, 8, 24, 64, 8, (5, 2),
                          [3, 62, 63, 64, 100, 191]),
    "window_items_of_8": (False, False, 8, 24, 113, 8, (8, 2),
                          [0, 111, 112, 113, 150, 191]),
    "window_items_of_16": (False, False, 4, 48, 121, 16, (16, 2),
                           [2, 119, 120, 121, 160, 191]),
    "full_items_of_16": (False, False, 4, 48, None, 16, (16, 3),
                         [0, 63, 64, 127, 128, 191]),
    "differential_window_items_of_5": (False, True, 8, 24, 64, 8, (5, 2),
                                       [3, 63, 64, 100, 191]),
    "differential_full_items_of_8": (False, True, 8, 24, None, 8, (8, 3),
                                     [3, 63, 64, 100, 191]),
    "latent_items_of_16": (True, False, 64, 40, None, 16, (16, 3),
                           [5, 1023, 1024, 2047, 2559]),
}


@pytest.mark.parametrize("case", sorted(_ITEM_CASES))
def test_mxu_kernels_match_the_plain_form_at_every_item_size(case,
                                                             monkeypatch):
    """Both MXU kernels in the interpreter against plain ``jax.numpy`` at
    items of 5, 8 and 16 pages: a window whose band is cut into items that
    start at its first entry (inside, at and past the window's edge), the
    same at differential attention's wiring, a full layer, and latent pages
    at 1,024 keys a step.  ``work`` built by the caller and by the entry
    give the same rows, and every page no item reads is poisoned."""
    from torch_automatic_distributed_neural_network_tpu.ops import (
        paged_attention as paged,
    )
    from torch_automatic_distributed_neural_network_tpu.ops.attention import (
        diff_plain_heads,
        xla_attention,
    )

    latent, diff, bs, mb, window, by_bytes, want, ctxs = _ITEM_CASES[case]
    S, Hq, kvH, hd = len(ctxs), 4, 2, 32
    rs = np.random.RandomState(len(case))
    lanes = 128 if latent else kvH * hd
    pools = [np.zeros((S * mb + 1, bs, lanes), np.float32)
             for _ in range(1 if latent else 2)]
    for pool in pools:
        pool[..., :24 if latent else lanes] = rs.randn(
            S * mb + 1, bs, 24 if latent else lanes)
    monkeypatch.setattr(paged, "ITEM_BYTES",
                        by_bytes * len(pools) * bs * lanes * 4)
    pages, steps = paged.item_pages(pools, mb, window)
    assert (pages, steps) == want
    tables = 1 + rs.permutation(S * mb).reshape(S, mb).astype(np.int32)
    for s, c in enumerate(ctxs):
        _poison_unlisted(pools, tables, s, _first_pages(
            c, True, window, pages, bs, mb), pages)
        tables[s, c // bs + 1:] = 0
    for pool in pools:
        pool[0] = 0.0
    ctx = jnp.asarray(ctxs, jnp.int32)
    work = paged.folded_work_list(ctx, pools=pools, max_blocks=mb,
                                  window=window)
    pools, tables = [jnp.asarray(pool) for pool in pools], jnp.asarray(tables)
    clean = [jnp.nan_to_num(pool) for pool in pools]
    if latent:
        q = jnp.asarray(rs.randn(S, Hq, 24), jnp.float32)
        run = lambda work: paged_attention(  # noqa: E731
            q, pools[0], jnp.zeros((0,), jnp.float32), tables, ctx,
            scale=0.3, value_dim=20, work=work)
        want_rows = paged.latent_attention_reference(
            q[:, None], clean[0][tables].reshape(S, mb * bs, lanes), ctx,
            scale=0.3, value_dim=20)[:, 0]
    else:
        q = jnp.asarray(rs.randn(S, Hq, hd), jnp.float32)
        run = lambda work: paged_attention(  # noqa: E731
            q, *pools, tables, ctx, window=window, work=work, diff=diff)
        if diff:  # 2 H plain heads: a query head, a value head
            kd, vd = (pool[tables].reshape(S, mb * bs, kvH, hd)
                      for pool in clean)
            key = jnp.arange(mb * bs)[None, :]
            mask = key <= ctx[:, None]
            if window is not None:
                mask &= key > ctx[:, None] - window
            want_rows = xla_attention(
                *diff_plain_heads(q[:, None], kd, vd), causal=False,
                mask=mask[:, None, None, :]).reshape(S, Hq, 2 * hd)
        else:
            want_rows = paged_attention_reference(q, *clean, tables, ctx,
                                                  window=window)
    got = run(work)
    assert got.shape == want_rows.shape
    np.testing.assert_allclose(np.asarray(got), np.asarray(want_rows),
                               atol=1e-5)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(run(None)))


def test_reference_fp_pool_skips_dequantize_and_matches_int8():
    """gather_blocks (the reference path): fp pool returns the stored
    values untouched; int8 pool dequantizes to within the pinned
    quantization bound."""
    from torch_automatic_distributed_neural_network_tpu.inference.serve \
        .kv_pool import gather_blocks

    rs = np.random.RandomState(2)
    dense = jnp.asarray(rs.randn(8, 8, 2, 16), jnp.float32)
    table = jnp.asarray([[1, 3], [2, 0]], jnp.int32)
    g_fp = gather_blocks(dense, table, jnp.float32)
    np.testing.assert_array_equal(
        np.asarray(g_fp.reshape(2, 2, 8, 2, 16)),
        np.asarray(dense[table]))
    q = quantize_kv(dense)
    g_q = gather_blocks(q, table, jnp.float32)
    scale = np.asarray(q["scale"])[np.asarray(table)].reshape(2, 16, 2, 1)
    assert float(jnp.max(jnp.abs(g_q - g_fp))) <= float(scale.max()) / 2


# -- engine-level parity (fast: tiny model, few tokens) -----------------------


def _model_and_vars(seed=1):
    model = GPT2("test", vocab_size=VOCAB, max_seq_len=64,
                 dtype=jnp.float32, remat=False)
    tokens = jnp.asarray(
        np.random.RandomState(0).randint(1, VOCAB, size=(1, 12)),
        jnp.int32)
    return model, model.init(jax.random.key(seed), tokens)


def _serve(model, variables, prompts, *, max_new=6, **kw):
    eng = ServeEngine(model, variables, n_slots=2, max_len=64,
                      block_size=8, **kw)
    reqs = [eng.submit(p, max_new_tokens=max_new, eos_id=0)
            for p in prompts]
    eng.run()
    eng.scheduler.check_invariants()
    assert eng.pool.allocator.n_live == 0
    return [r.out_tokens for r in reqs], eng


@pytest.mark.parametrize("quant_kv", [False, True])
def test_engine_paged_matches_dense_tokens(quant_kv):
    """Token parity through real serving traffic: same requests, same
    rng, the only difference is the decode attention impl."""
    model, variables = _model_and_vars()
    rs = np.random.RandomState(3)
    prompts = [[int(t) for t in rs.randint(1, VOCAB, size=(p,))]
               for p in (5, 11, 9)]
    got_p, _ = _serve(model, variables, prompts,
                      attention_impl="paged", quant_kv=quant_kv)
    got_d, _ = _serve(model, variables, prompts,
                      attention_impl="dense", quant_kv=quant_kv)
    assert got_p == got_d


def test_engine_chunked_prefill_matches_generate():
    """A prompt streamed in [1, C] chunks must emit the same tokens as
    ``generate()``, whose prefill is one forward over the whole prompt —
    and a chunk that doesn't divide the prompt exercises the padded final
    chunk.  (Float32 caches on both sides: greedy tokens to the last.)"""
    model, variables = _model_and_vars()
    rs = np.random.RandomState(4)
    prompts = [[int(t) for t in rs.randint(1, VOCAB, size=(p,))]
               for p in (5, 13, 16)]
    whole = []
    for p in prompts:
        seq, lengths = generate(
            model, variables, jnp.asarray(p, jnp.int32)[None, :],
            max_new_tokens=6, eos_id=0, cache_dtype=jnp.float32,
            early_stop=True, return_lengths=True)
        whole.append([int(t) for t in np.asarray(
            seq[0, len(p):int(lengths[0])])])
    for chunk in (8, 32):
        chunked, eng = _serve(model, variables, prompts,
                              prefill_chunk=chunk, cache_dtype=jnp.float32)
        assert chunked == whole, (chunk, chunked, whole)
        assert eng.prefill_chunk == chunk  # divides max_len: no snap


def test_engine_prefill_chunk_snaps_to_max_len_divisor():
    model, variables = _model_and_vars()
    eng = ServeEngine(model, variables, n_slots=2, max_len=64,
                      block_size=8, prefill_chunk=48)
    assert eng.prefill_chunk == 16  # gcd(48, 64)
    with pytest.raises(ValueError, match="attention_impl"):
        ServeEngine(model, variables, attention_impl="fused?")


def test_engine_paged_preempted_request_recomputes_correctly():
    """Optimistic admission over an undersized pool: a preempted slot
    is recomputed from scratch into FRESH blocks — under the paged
    kernel its tokens must still match an uncontended dense run."""
    model, variables = _model_and_vars()
    rs = np.random.RandomState(5)
    prompts = [[int(t) for t in rs.randint(1, VOCAB, size=(12,))]
               for _ in range(4)]
    max_new = 12

    eng = ServeEngine(model, variables, n_slots=4, max_len=32,
                      block_size=8, num_blocks=10,
                      admission="optimistic", attention_impl="paged")
    reqs = [eng.submit(p, max_new_tokens=max_new, eos_id=None)
            for p in prompts]
    eng.run()
    assert eng.scheduler.n_preemptions > 0, "pool never contended"
    eng.scheduler.check_invariants()

    for req, p in zip(reqs, prompts):
        ref, _ = _serve(model, variables, [p], max_new=max_new,
                        attention_impl="dense")
        assert req.out_tokens == ref[0], req.rid


# -- the latent kernel: every head over ONE row a key, a page read once ---------

_LBS, _LMB = 16, 96  # 512 keys a grid step: 32 pages; 3 groups, max_len 1536
_LMAX = _LBS * _LMB

# name: (contexts, the running slots or None for all)
_LATENT_CASES = {
    "empty": ([0, 0, 0], None),
    "one_key_beside_idle_slots": ([0, 700, 1535], [0]),
    "page_boundaries": ([15, 16, 17, 31], None),
    "group_boundaries": ([511, 512, 1023, 1024], None),
    "every_slot_full": ([_LMAX - 1] * 3, None),
    "ragged": ([3, 130, 1400, 77, 600], None),
}


@pytest.mark.parametrize("case", sorted(_LATENT_CASES))
def test_latent_kernel_matches_the_dense_form(case, small_items):
    """``paged_attention`` over latent pages (one row a token, 20 + 4
    numbers stored in 128 lanes, values its first 20; no second array)
    against plain ``jax.numpy`` on ragged contexts, 6 heads: the grid is
    ``folded_work_list`` at 32 pages an item, every page outside a listed
    group is poisoned, and with every slot at ``max_len`` the list is as
    long as its arrays (the case that halted the chip's core in PR 30: the
    arrays hold one entry more than the list)."""
    from torch_automatic_distributed_neural_network_tpu.ops.paged_attention \
        import (
            folded_work_list,
            is_latent,
            item_pages,
            latent_attention_reference,
        )

    ctxs, running = _LATENT_CASES[case]
    S, Hq, row, value, lanes = len(ctxs), 6, 24, 20, 128
    running = list(range(S)) if running is None else running
    rs = np.random.RandomState(len(case))
    pool = np.zeros((S * _LMB + 1, _LBS, lanes), np.float32)
    pool[..., :row] = rs.randn(S * _LMB + 1, _LBS, row)
    tables = 1 + rs.permutation(S * _LMB).reshape(S, _LMB).astype(np.int32)
    pages = item_pages((pool,), _LMB)[0]
    assert pages == 32
    want_items = 0
    for s, ctx in enumerate(ctxs):
        firsts = _first_pages(ctx, s in running, None, pages, _LBS, _LMB)
        want_items += len(firsts)
        _poison_unlisted((pool,), tables, s, firsts, pages)
        tables[s, ctx // _LBS + 1:] = 0  # the null block past the newest key
    pool[0] = 0.0
    none = jnp.zeros((0,), jnp.float32)
    assert is_latent(jnp.asarray(pool), none)
    q = jnp.asarray(rs.randn(S, Hq, row), jnp.float32)
    ctx = jnp.asarray(ctxs, jnp.int32)
    active = jnp.asarray([s in running for s in range(S)])
    work = folded_work_list(ctx, active, pools=(pool,), max_blocks=_LMB)
    n = int(work.n_items)
    assert n == want_items <= work.dense == S * 3
    assert work.slot_of.shape[0] == work.dense + 1
    got = paged_attention(q, jnp.asarray(pool), none, jnp.asarray(tables),
                          ctx, scale=0.3, value_dim=value, work=work)
    assert got.shape == (S, Hq, value)
    assert bool(jnp.all(jnp.isfinite(got)))
    dense = jnp.nan_to_num(jnp.asarray(pool))[jnp.asarray(tables)].reshape(
        S, _LMAX, lanes)
    want = latent_attention_reference(q[:, None], dense, ctx, scale=0.3,
                                      value_dim=value)[:, 0]
    np.testing.assert_allclose(np.asarray(got)[running],
                               np.asarray(want)[running], atol=1e-5)
    if case == "every_slot_full":
        assert n == work.dense
    if len(running) == S:  # built where the caller gives none
        np.testing.assert_array_equal(
            np.asarray(got), np.asarray(paged_attention(
                q, jnp.asarray(pool), none, jnp.asarray(tables), ctx,
                scale=0.3, value_dim=value)))


def test_latent_kernel_is_named_and_reads_a_page_once(small_items):
    """One ``pallas_call`` named ``tadnn_paged_decode_latent`` that takes the
    ONE pool array ONCE (the kernel copies an item's 8 pages of 64 tokens
    itself into a ``[2, 8, 64, F]`` buffer; no value pages beside them), its
    grid the traced ``n_items``."""
    S, Hq, row = 3, 4, 24
    pool = jnp.zeros((S * 24 + 1, 64, 128), jnp.float32)
    tables = jnp.zeros((S, 24), jnp.int32)

    def run(q, ctx):
        return paged_attention(q, pool, jnp.zeros((0,), jnp.float32), tables,
                               ctx, scale=1.0, value_dim=20)

    jaxpr = jax.make_jaxpr(run)(jnp.zeros((S, Hq, row), jnp.float32),
                                jnp.asarray([0, 511, 1535], jnp.int32)).jaxpr
    (call,) = [e for e in jaxpr.eqns if e.primitive.name == "pallas_call"]
    assert "tadnn_paged_decode_latent" in str(call.params)
    pools = [v for v in call.invars if getattr(v.aval, "shape", None)
             == pool.shape]
    assert len(pools) == 1
    assert "f32[2,8,64,128]" in str(call.params["jaxpr"])
    assert call.params["grid_mapping"].num_dynamic_grid_bounds == 1


# -- both MXU kernels fetch an item's pages themselves: the copies' order ------

# name: (latent, query heads, kv heads, contexts, running slots or None for
# all, window).  Folded: 128 keys an item, max_len 384; latent: 512, 1,536.
_FETCH_CASES = {
    "one_item": (False, 4, 2, [100], None, None),
    "one_item_latent": (True, 6, None, [300], None, None),
    "list_fills_its_arrays": (False, 4, 2, [_MAX - 1] * 3, None, None),
    "list_fills_its_arrays_latent": (True, 6, None, [_LMAX - 1] * 3, None,
                                     None),
    "idle_slots_between_running": (False, 4, 2, [383, 0, 200, 0, 0, 300],
                                   [0, 2, 5], None),
    "idle_slots_between_running_latent": (True, 6, None,
                                          [1535, 0, 600, 0, 0, 1100],
                                          [0, 2, 5], None),
    "window": (False, 4, 2, [300, 50, 383, 129], None, 100),
    "group_edges": (False, 4, 2, [127, 128, 255, 256, 0], None, None),
    "group_edges_latent": (True, 6, None, [511, 512, 1023, 1024, 0], None,
                           None),
    "gqa_48_on_8": (False, 48, 8, [3, 130, 383], None, None),
    "mha_16_on_16_window": (False, 16, 16, [3, 130, 383], None, 200),
    "latent_row_of_640_lanes": (True, 8, None, [700, 15], None, None),
}


@pytest.mark.parametrize("case", sorted(_FETCH_CASES))
def test_mxu_kernels_fetch_their_pages_without_a_race(case, capfd,
                                                      small_items):
    """Both entries under the TPU interpreter that runs a copy only when it
    is waited for, fills unwritten memory with NaN and follows every read and
    write with a vector clock: item w + 1's copies are started before item
    w's are waited for, the first item's in a prologue, none past the list.
    A copy that is not waited for before its buffer is read leaves NaN, one
    that lands in a buffer still read is a race; pages outside the listed
    groups are NaN too.  The one item of a slot that does not run holds the
    null block alone and is skipped, fetch and arithmetic.  Then with every
    copy run as it is started: a copy nobody waits for (one started past the
    list's end) leaves its semaphore counted up when the kernel ends, which
    the interpreter prints."""
    from jax._src.pallas.mosaic.interpret import interpret_pallas_call as tpu
    from jax.experimental.pallas import tpu as pltpu

    from torch_automatic_distributed_neural_network_tpu.ops.paged_attention \
        import (
            folded_work_list,
            item_pages,
            latent_attention_reference,
        )

    latent, Hq, kvH, ctxs, running, window = _FETCH_CASES[case]
    S = len(ctxs)
    running = list(range(S)) if running is None else running
    rs = np.random.RandomState(len(case))
    if latent:
        bs, mb = _LBS, _LMB
        lanes, row, value = (640, 576, 512) if "640" in case else (128, 24, 20)
        pools = [np.zeros((S * mb + 1, bs, lanes), np.float32)]
        pools[0][..., :row] = rs.randn(S * mb + 1, bs, row)
        q = jnp.asarray(rs.randn(S, Hq, row), jnp.float32)
    else:
        bs, mb, hd = _BS, _MB, 32
        pools = [rs.randn(S * mb + 1, bs, kvH * hd).astype(np.float32)
                 for _ in range(2)]
        q = jnp.asarray(rs.randn(S, Hq, hd), jnp.float32)
    pages = item_pages(pools, mb, window)[0]
    assert pages == (32 if latent else {None: 8, 100: 8, 200: 7}[window])
    tables = 1 + rs.permutation(S * mb).reshape(S, mb).astype(np.int32)
    want_items = 0
    for s, c in enumerate(ctxs):
        firsts = _first_pages(c, s in running, window, pages, bs, mb)
        want_items += len(firsts)
        _poison_unlisted(pools, tables, s, firsts, pages)
        # the engine's table: the null block past the newest key, and in
        # every entry of a slot that does not run (``programs._step_shared``)
        tables[s, (c // bs + 1) * (s in running):] = 0
    for pool in pools:
        pool[0] = 0.0
    ctx = jnp.asarray(ctxs, jnp.int32)
    active = jnp.asarray([s in running for s in range(S)])
    work = folded_work_list(ctx, active, pools=pools, max_blocks=mb,
                            window=window)
    assert int(work.n_items) == want_items
    if "fills" in case:
        assert want_items == work.dense == work.slot_of.shape[0] - 1
    pools, tables = [jnp.asarray(pool) for pool in pools], jnp.asarray(tables)

    def run(interpret):
        if latent:
            return paged_attention(
                q, pools[0], jnp.zeros((0,), jnp.float32), tables, ctx,
                scale=0.3, value_dim=value, work=work, interpret=interpret)
        return paged_attention(q, *pools, tables, ctx, window=window,
                               work=work, interpret=interpret)

    got = run(pltpu.InterpretParams(detect_races=True))
    assert not tpu.races.races_found
    if latent:
        dense = jnp.nan_to_num(pools[0])[tables].reshape(S, mb * bs, lanes)
        want = latent_attention_reference(q[:, None], dense, ctx, scale=0.3,
                                          value_dim=value)[:, 0]
    else:
        want = paged_attention_reference(
            q, *[jnp.nan_to_num(pool) for pool in pools], tables, ctx,
            window=window)
    np.testing.assert_allclose(np.asarray(got)[running],
                               np.asarray(want)[running], atol=1e-5)
    # an item of null pages alone is neither fetched nor multiplied (its
    # buffer would be read unwritten, NaN here): its row is zeros
    idle = [s for s in range(S) if s not in running]
    assert not np.asarray(got)[idle].any()
    capfd.readouterr()
    np.testing.assert_array_equal(np.asarray(got), np.asarray(
        run(pltpu.InterpretParams(dma_execution_mode="eager"))))
    assert "non-zero count" not in capfd.readouterr().out


# -- the chunk kernel: a prompt chunk's queries over the slot's latent pages ----

_CC, _CBS, _CMB, _CKEYS = 16, 4, 16, 8  # a chunk of 16, 2 pages a key block
# name: (the chunk's first position, its real rows, shuffled pages)
_CHUNK_CASES = {
    "prompt_start": (0, _CC, True),
    "inside_a_key_block": (4, _CC, True),
    "blocks_deep": (32, _CC, True),
    "ragged_last_chunk": (16, 5, True),
    "pages_in_order": (16, _CC, False),
}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("case", sorted(_CHUNK_CASES))
def test_latent_chunk_kernel_matches_the_plain_form_and_the_module(
        monkeypatch, case, dtype):
    """``programs._chunk_latent`` through ``tadnn_latent_chunk`` (the
    interpreter; key blocks of 8, so a chunk of 16 crosses several) against
    the same call through ``_over_key_blocks``, and both against
    ``LatentAttention.__call__`` over the whole sequence: a chunk that
    starts a prompt, one that starts inside a key block, one several blocks
    deep, a padded last chunk (its real rows are compared; the table holds
    the null block past the prompt's pages), the slot's pages out of order
    in the pool and in order.  Every page past the chunk's last key block
    is poisoned: the grid follows the context."""
    from torch_automatic_distributed_neural_network_tpu.inference.serve import (
        programs,
    )
    from torch_automatic_distributed_neural_network_tpu.inference.serve.kv_pool import (
        write_chunk,
    )
    from torch_automatic_distributed_neural_network_tpu.models.transformer_core import (
        LatentAttention,
        TransformerConfig,
    )
    from torch_automatic_distributed_neural_network_tpu.ops import (
        paged_attention as paged,
    )

    pos0, n_real, shuffled = _CHUNK_CASES[case]
    cfg = TransformerConfig(
        vocab_size=64, d_model=48, n_layers=1, n_heads=4, d_ff=64,
        max_seq_len=128, pos="rope", norm="rmsnorm",
        layer_types=("latent_attention",), latent_q_rank=24,
        latent_kv_rank=16, latent_nope_head_dim=8, latent_rope_head_dim=4,
        latent_value_head_dim=8, dtype=dtype, remat=False)
    monkeypatch.setattr(paged, "LATENT_CHUNK_KEYS", _CKEYS)
    monkeypatch.setattr(programs, "KEY_BLOCK", _CKEYS)
    T, lanes = pos0 + n_real, 128
    rs = np.random.RandomState(len(case))
    x = jnp.asarray(rs.randn(1, pos0 + _CC, cfg.d_model), dtype)
    positions = jnp.arange(pos0 + _CC)[None]
    mixer = LatentAttention(cfg)
    own = mixer.init(jax.random.key(2), x, positions)
    own = jax.tree.map(lambda a: a * 2.0, own)  # scores that tell keys apart
    piece = lambda name, *a: mixer.apply(own, *a, method=name)  # noqa: E731
    q_nope, q_rope, latent = piece("project", x, positions)
    # the slot's pages: those of the prompt's T tokens, the null block after
    n_pages = -(-T // _CBS)
    ids = 1 + (rs.permutation(_CMB) if shuffled else np.arange(_CMB))
    row = jnp.asarray(np.where(np.arange(_CMB) < n_pages, ids, 0), jnp.int32)
    pool = np.zeros((_CMB + 1, _CBS, lanes), np.float32)
    hi = (pos0 + _CC - 1) // _CKEYS  # the chunk's last key block
    pool[ids[(hi + 1) * (_CKEYS // _CBS):]] = np.nan
    pool = jnp.asarray(pool, dtype)
    if pos0:  # what the chunks before this one wrote
        pool = write_chunk(pool, row, 0, latent[0, :pos0])
    shared = {"rows": {"pages": row}, "pos0": jnp.int32(pos0)}
    chunk = (q_nope[0, pos0:], q_rope[0, pos0:], latent[0, pos0:])

    plain, pages = programs._chunk_latent(cfg, shared, pool, piece, *chunk)
    monkeypatch.setattr(paged, "latent_chunk_tiles", lambda *a: True)
    assert programs.chunk_attention_form(
        cfg, "latent_attention", _CC, _CBS) == "kernel"
    jaxpr = jax.make_jaxpr(lambda *a: programs._chunk_latent(
        cfg, shared, pool, piece, *a))(*chunk).jaxpr
    assert [e.primitive.name for e in jaxpr.eqns].count("pallas_call") == 1
    got, pages_k = programs._chunk_latent(cfg, shared, pool, piece, *chunk)
    np.testing.assert_array_equal(
        np.nan_to_num(np.asarray(pages, np.float32)),
        np.nan_to_num(np.asarray(pages_k, np.float32)))
    assert got.shape == plain.shape == (_CC, 4, 8) and got.dtype == dtype
    f32 = lambda a: np.asarray(a, np.float32)[:n_real]  # noqa: E731
    assert np.all(np.isfinite(f32(got)))
    # (as shares of the largest number compared: bfloat16 rounds a head's
    # output of 20 to 0.06, and the module's softmax is another program's)
    tight, loose = (2e-5, 1e-4) if dtype == jnp.float32 else (1e-2, 3e-2)
    assert np.abs(f32(got) - f32(plain)).max() \
        <= tight * np.abs(f32(plain)).max()
    with jax.default_matmul_precision("highest"):
        whole = mixer.apply(own, x[:, :T], positions[:, :T])[0, pos0:]
        out = piece("out_proj", got[None].astype(dtype))[0]
    assert np.abs(f32(out) - f32(whole)).max() \
        <= loose * np.abs(f32(whole)).max()


def test_latent_chunk_kernel_is_named_and_its_grid_is_traced():
    """One ``pallas_call`` named ``tadnn_latent_chunk`` (a name no reader of
    the decode kernels matches) whose page operands are the ONE pool array
    (8 pages of 64 tokens a key block at the cell's block size) and whose
    grid is (groups of heads, the key blocks the chunk reaches): a traced
    number, which ``latent_chunk_key_blocks`` gives the engine's counter."""
    from torch_automatic_distributed_neural_network_tpu.ops import (
        paged_attention as paged,
    )

    C, H, bs, MB = 128, 8, 64, 24
    pool = jnp.zeros((MB + 1, bs, 128), jnp.float32)

    def run(q_nope, q_rope, pos0):
        return paged.latent_chunk_attention(
            q_nope, q_rope, pool, jnp.zeros((MB,), jnp.int32), pos0,
            jnp.zeros((20, H, 16)), jnp.zeros((20, H, 16)), scale=1.0)

    jaxpr = jax.make_jaxpr(run)(jnp.zeros((C, H, 16)), jnp.zeros((C, H, 4)),
                                jnp.int32(0)).jaxpr
    (call,) = [e for e in jaxpr.eqns if e.primitive.name == "pallas_call"]
    assert "tadnn_latent_chunk" in str(call.params["name"])
    assert "tadnn_paged_decode" not in str(call.params["name"])
    pools = [v for v in call.invars if getattr(v.aval, "shape", None)
             == pool.shape]
    assert len(pools) == 8
    grid = call.params["grid_mapping"].grid
    assert grid[0] == H // paged.LATENT_CHUNK_HEADS
    assert not isinstance(grid[1], int)  # follows pos0, not max_len
    assert [paged.latent_chunk_key_blocks(p, 512, 544, 64)
            for p in (0, 512, 8192, 32256)] == [1, 2, 17, 64]
    # on the CPU the plain form is what runs, whatever the shapes
    assert not paged.latent_chunk_tiles(512, 64, 32, 512, 128, jnp.bfloat16)
