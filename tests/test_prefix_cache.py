"""Cross-request prefix caching tests: the radix reuse index and
chained content hashes (host-only — tier-1), the ref-counted
copy-on-write allocator contract (double-release stays loud through
sharing; randomized churn leaks nothing), scheduler admission charging
only uncached blocks and evicting cold index leaves before preempting,
bitwise token parity cache-on vs cache-off across every serving mode
(slow), and the report/serve_lint prefix surfaces."""

import json

import jax.numpy as jnp
import numpy as np
import pytest

from torch_automatic_distributed_neural_network_tpu.analysis.serve_lint import (
    serve_estimate,
)
from torch_automatic_distributed_neural_network_tpu.inference.serve import (
    BlockAllocator,
    PrefixCache,
    Request,
    Scheduler,
    ServeEngine,
    block_hashes,
)
from torch_automatic_distributed_neural_network_tpu.obs import (
    report as obs_report,
)

from test_serve import VOCAB, _model_and_vars

# -- chained content hashes ---------------------------------------------------


def test_block_hashes_full_blocks_only():
    assert block_hashes([], 8) == []
    assert block_hashes([1] * 7, 8) == []  # trailing partial: no key
    assert len(block_hashes([1] * 8, 8)) == 1
    assert len(block_hashes([1] * 17, 8)) == 2


def test_block_hashes_chain_commits_to_whole_prefix():
    # same block-1 tokens, different block 0: keys must diverge at
    # EVERY position from the first difference on — a key names the
    # full prefix, never just its local tokens
    a = block_hashes([1] * 8 + [9] * 8, 8)
    b = block_hashes([2] * 8 + [9] * 8, 8)
    assert a[0] != b[0]
    assert a[1] != b[1]
    # identical prompts agree (deterministic keys)
    assert a == block_hashes([1] * 8 + [9] * 8, 8)


# -- radix index --------------------------------------------------------------


def _mk_index(num_blocks=16, block_size=8):
    alloc = BlockAllocator(num_blocks)
    clock = [0.0]
    pc = PrefixCache(block_size=block_size, allocator=alloc,
                     clock=lambda: clock[0])
    return pc, alloc, clock


def test_insert_then_match_and_chain_break():
    pc, alloc, _ = _mk_index()
    owner = alloc.acquire(2)
    pc.insert([1] * 8 + [9] * 8, owner)
    assert pc.n_blocks == 2
    # full match, prefix match, and the chained-key break: sharing
    # block 1's tokens without block 0's prefix must match NOTHING
    assert pc.match([1] * 8 + [9] * 8) == (owner, 16)
    assert pc.match([1] * 8 + [7] * 8) == (owner[:1], 8)
    assert pc.match([2] * 8 + [9] * 8) == ([], 0)
    # max_tokens caps at block granularity
    assert pc.match([1] * 8 + [9] * 8, max_tokens=15) == (owner[:1], 8)
    # the index holds one ref per node on top of the owner's
    assert all(alloc.refcount(b) == 2 for b in owner)


def test_insert_first_publisher_wins():
    pc, alloc, _ = _mk_index()
    first = alloc.acquire(1)
    dup = alloc.acquire(1)
    assert pc.insert([5] * 8, first) == 1
    assert pc.insert([5] * 8, dup) == 0  # recomputed content: no-op
    assert pc.match([5] * 8)[0] == first
    assert alloc.refcount(first[0]) == 2
    assert alloc.refcount(dup[0]) == 1  # untouched by the losing insert


def test_evict_lru_leaves_only_and_exposes_parents():
    pc, alloc, clock = _mk_index()
    owner = alloc.acquire(3)
    pc.insert([1] * 24, owner)  # one 3-deep chain
    alloc.release(owner)  # index holds the only refs now
    assert pc.n_evictable() == 3
    # interior nodes are never dropped directly: evict(1) takes the
    # deepest leaf, exposing its parent for the next call
    assert pc.evict(1) == 1
    assert pc.n_blocks == 2
    assert pc.match([1] * 24) == (owner[:2], 16)
    assert pc.evict(5) == 2  # drains the rest, chain-outward
    assert pc.n_blocks == 0 and alloc.n_live == 0


def test_evict_skips_referenced_blocks_and_orders_by_last_hit():
    pc, alloc, clock = _mk_index()
    cold = alloc.acquire(1)
    hot = alloc.acquire(1)
    pinned = alloc.acquire(1)
    pc.insert([1] * 8, cold)
    clock[0] = 1.0
    pc.insert([2] * 8, hot)
    pc.insert([3] * 8, pinned)
    alloc.release(cold)
    alloc.release(hot)
    clock[0] = 2.0
    pc.match([2] * 8)  # bump hot's last_hit
    # pinned still carries its owner's ref: not evictable at all
    assert pc.n_evictable() == 2
    assert pc.evict(1) == 1  # coldest (never re-hit) goes first
    assert pc.match([1] * 8) == ([], 0)
    assert pc.match([2] * 8)[1] == 8
    assert pc.evict(5) == 1  # hot goes, pinned survives
    assert pc.match([3] * 8)[1] == 8
    alloc.release(pinned)
    assert pc.clear() == 1 and alloc.n_live == 0


# -- ref-counted allocator: the loud double-free contract ---------------------


def test_release_stays_loud_through_sharing():
    a = BlockAllocator(8)
    got = a.acquire(2)
    for b in got:
        a.ref(b)  # second owner
    a.release(got)  # first owner out: blocks stay live
    assert all(a.refcount(b) == 1 for b in got)
    a.release(got)  # second owner's release is legal
    assert a.n_live == 0
    with pytest.raises(ValueError, match="double-free|not currently"):
        a.release(got)  # no outstanding reference: loud again
    # a failed release took nothing with it
    assert a.n_free == 7


def test_acquire_fork_release_churn_no_leaks():
    """Randomized acquire/ref/release churn over a shared pool: the
    model's per-owner refcounts must equal the allocator's at every
    step, and draining every owner returns the pool to empty."""
    rs = np.random.RandomState(11)
    a = BlockAllocator(24)
    held: list[int] = []  # one entry per outstanding reference
    for _ in range(2000):
        r = rs.rand()
        if held and r < 0.45:
            a.release([held.pop(rs.randint(len(held)))])
        elif held and r < 0.65:
            b = held[rs.randint(len(held))]  # share: CoW-style ref
            a.ref(b)
            held.append(b)
        else:
            got = a.acquire(int(rs.randint(1, 4)))
            if got is not None:
                held.extend(got)
        counts: dict[int, int] = {}
        for b in held:
            counts[b] = counts.get(b, 0) + 1
        assert counts == {b: a.refcount(b) for b in set(held)}
        assert a.n_free + len(set(held)) == 23
    for b in held:
        a.release([b])
    assert a.n_free == 23 and a.n_live == 0


# -- scheduler: admission charges only the uncached suffix --------------------


def _sched_with_cache(num_blocks, n_slots=2, block_size=8):
    alloc = BlockAllocator(num_blocks)
    pc = PrefixCache(block_size=block_size, allocator=alloc)
    s = Scheduler(n_slots=n_slots, allocator=alloc, block_size=block_size,
                  prefix_cache=pc)
    return s, pc, alloc


def test_admit_refs_matched_blocks_and_charges_suffix_only():
    s, pc, alloc = _sched_with_cache(num_blocks=8)
    seed = alloc.acquire(2)
    pc.insert([1] * 16, seed)
    alloc.release(seed)  # index-only now
    # 20 prompt + 4 new = 24 tokens = 3 blocks; 2 come from the index
    s.submit(Request(prompt=[1] * 16 + [2] * 4, max_new_tokens=4))
    (slot, req), = s.admit()
    assert req.cached_tokens == 16 and req.cached_blocks == 2
    assert req.blocks[:2] == seed  # shared, not copied
    assert all(alloc.refcount(b) == 2 for b in seed)  # index + request
    s.check_invariants()
    free_before = alloc.n_free
    req.out_tokens = [5] * 4
    s.evict(slot)
    s.check_invariants()
    # the request's refs went back but the index still holds the chain
    assert alloc.n_free == free_before + 1
    assert pc.n_blocks == 2


def test_admission_evicts_cold_index_leaves_before_refusing():
    # 5 allocatable blocks, 4 held by a cold indexed chain: a 2-block
    # request with no matching prefix must reclaim from the index
    # rather than queue-stall
    s, pc, alloc = _sched_with_cache(num_blocks=6)
    seed = alloc.acquire(4)
    pc.insert([9] * 32, seed)
    alloc.release(seed)
    s.submit(Request(prompt=[1] * 10, max_new_tokens=4))
    admitted = s.admit()
    assert len(admitted) == 1
    assert pc.evicted_blocks > 0
    s.check_invariants()


def test_check_invariants_catches_index_refcount_drift():
    s, pc, alloc = _sched_with_cache(num_blocks=8)
    seed = alloc.acquire(1)
    pc.insert([4] * 8, seed)
    alloc.release(seed)
    s.check_invariants()
    # manufacture drift: drop the index's ref behind its back
    alloc.release([seed[0]])
    with pytest.raises(AssertionError):
        s.check_invariants()


# -- engine parity: cache-on output must be bitwise cache-off's ---------------


def _run_engine(shared, uniques, *, prefix_cache, max_new=6, **kw):
    model, variables = _model_and_vars()
    eng = ServeEngine(model, variables, n_slots=3, max_len=64,
                      block_size=8, prefill_chunk=8,
                      prefix_cache=prefix_cache, **kw)
    prompts = [shared + u for u in uniques]
    for p in prompts:
        eng.submit(p, max_new_tokens=max_new, eos_id=0)
    done = eng.run()
    assert len(done) == len(prompts)
    eng.scheduler.check_invariants()
    if prefix_cache:
        assert eng.prefix_hits > 0  # reuse actually happened
        n_index = eng.prefix_cache.n_blocks
        assert eng.pool.allocator.n_live == n_index  # only index refs
        assert eng.prefix_cache.clear() == n_index
    assert eng.pool.allocator.n_live == 0
    return sorted((tuple(r.prompt), tuple(r.out_tokens)) for r in done)


def _mix(seed=3, n=6, shared_len=24, unique_len=9):
    rs = np.random.RandomState(seed)
    shared = [int(t) for t in rs.randint(1, VOCAB, size=(shared_len,))]
    uniques = [[int(t) for t in rs.randint(1, VOCAB, size=(unique_len,))]
               for _ in range(n)]
    return shared, uniques


@pytest.mark.slow
@pytest.mark.parametrize("attention_impl", ["paged", "dense"])
def test_prefix_cache_bitwise_parity(devices8, attention_impl):
    shared, uniques = _mix()
    kw = dict(attention_impl=attention_impl)
    on = _run_engine(shared, uniques, prefix_cache=True, **kw)
    off = _run_engine(shared, uniques, prefix_cache=False, **kw)
    assert on == off


@pytest.mark.slow
def test_prefix_cache_bitwise_parity_int8_kv(devices8):
    # int8 KV: reuse is aligned to lcm(block, chunk) so the quantized
    # chunk partition — and with it every (q, scale) pair — is
    # identical to the uncached run's
    shared, uniques = _mix(seed=4)
    on = _run_engine(shared, uniques, prefix_cache=True, quant_kv=True)
    off = _run_engine(shared, uniques, prefix_cache=False, quant_kv=True)
    assert on == off


@pytest.mark.slow
def test_prefix_cache_parity_under_preemption(devices8):
    # optimistic admission over a tight pool: preempted requests
    # recompute through the cache (their republished blocks may even
    # hit) and still land bitwise on the cache-off tokens
    shared, uniques = _mix(seed=6, n=5, shared_len=16, unique_len=5)
    kw = dict(num_blocks=14, admission="optimistic", max_new=8)
    on = _run_engine(shared, uniques, prefix_cache=True, **kw)
    off = _run_engine(shared, uniques, prefix_cache=False, **kw)
    assert on == off


@pytest.mark.slow
def test_cow_fork_protects_shared_decode_block(devices8):
    """A decode write landing in a block another table shares must fork
    it first: seed the index so a hit's LAST matched block is partially
    filled, then decode writes into that block position."""
    model, variables = _model_and_vars()
    eng = ServeEngine(model, variables, n_slots=2, max_len=64,
                      block_size=8, prefill_chunk=8, prefix_cache=True)
    rs = np.random.RandomState(9)
    shared = [int(t) for t in rs.randint(1, VOCAB, size=(16,))]
    # 24-token prompts share blocks 0-1 through the index; the second
    # request's suffix and decode writes stay in its private blocks,
    # with the CoW guard covering any boundary write
    first = eng.submit(shared + [3] * 8, max_new_tokens=6, eos_id=0)
    eng.run()
    second = eng.submit(shared + [4] * 8, max_new_tokens=6, eos_id=0)
    done = eng.run()
    assert any(r.rid == second.rid for r in done)
    assert eng.prefix_hits >= 1
    # whether or not a fork fired on this geometry, the shared prefix
    # must be re-servable: a third identical-prefix request still hits
    # and the first request's tokens were not perturbed
    third = eng.submit(shared + [3] * 8, max_new_tokens=6, eos_id=0)
    eng.run()
    assert third.out_tokens == first.out_tokens
    eng.scheduler.check_invariants()


@pytest.mark.slow
def test_cow_fork_fires_on_manufactured_block_sharing(devices8):
    """Force the guard itself: alias a running request's write block
    into a second table via allocator.ref, then step — the engine must
    fork rather than write the shared copy."""
    model, variables = _model_and_vars()
    eng = ServeEngine(model, variables, n_slots=1, max_len=64,
                      block_size=8, prefill_chunk=8, prefix_cache=True)
    req = eng.submit([2] * 12, max_new_tokens=8, eos_id=None)
    while req.state != "running":
        eng.step()
    # the block the next decode write lands in (engine's ctx math)
    bi = (req.n_prompt + req.n_generated - 1) // 8
    b = req.blocks[bi]
    eng.pool.allocator.ref(b)  # manufactured second owner
    before = eng.cow_forks
    eng.step()
    assert eng.cow_forks == before + 1
    assert req.blocks[bi] != b  # table now points at the fork
    eng.pool.allocator.release([b])
    eng.run()
    assert req.n_generated == 8
    eng.scheduler.check_invariants()


# -- report + capacity-lint surfaces ------------------------------------------


def test_report_renders_prefix_section(tmp_path):
    jp = tmp_path / "journal.jsonl"
    recs = [{"kind": "event", "name": "serve.engine", "t": 0.0,
             "attention_impl": "paged", "prefill_chunk": 8}]
    recs += [{"kind": "event", "name": "serve.step", "t": 0.1 * i,
              "step": i, "occupancy": 0.5, "prefix_blocks": 4 + i,
              "prefix_hit_tokens": 16 * i} for i in (1, 2)]
    # journal.event(..., kind=...) lets the kwarg win over the record's
    # own "kind" field (the serve.adapter idiom) — mirror that here
    recs += [
        {"name": "serve.prefix", "t": 0.05, "rid": 0, "kind": "match",
         "hit": False, "cached_tokens": 0, "cached_blocks": 0},
        {"name": "serve.prefix", "t": 0.15, "rid": 1, "kind": "match",
         "hit": True, "cached_tokens": 16, "cached_blocks": 2},
        {"name": "serve.prefix", "t": 0.12, "rid": 0,
         "kind": "publish", "n_blocks": 3},
        {"name": "serve.prefix", "t": 0.18, "rid": 1, "kind": "cow",
         "block": 5, "fork": 9},
    ]
    recs += [{"kind": "event", "name": "serve.request", "t": 0.2 + i,
              "rid": i, "n_prompt": 20, "n_new": 4, "total_s": 0.2}
             for i in (0, 1)]
    with open(jp, "w") as f:
        for r in recs:
            f.write(json.dumps(r) + "\n")
    report = obs_report.generate(str(jp))
    srv = report["serving"]
    assert srv["prefix_queries"] == 2
    assert srv["prefix_hit_requests"] == 1
    assert srv["prefix_cached_tokens"] == 16
    assert srv["prefix_hit_rate"] == pytest.approx(16 / 40)
    assert srv["prefix_saved_chunks"] == 2  # 16 cached / chunk 8
    assert srv["prefix_published_blocks"] == 3
    assert srv["cow_forks"] == 1
    assert srv["prefix_blocks"] == 6  # last step's resident count
    text = obs_report.format_report(report)
    assert "prefix cache: 1/2 request(s) hit" in text
    assert "hit rate 40.0%" in text and "1 CoW fork(s)" in text


def test_serve_estimate_charges_prefix_index_and_dedupes_streams():
    from test_serve import _cfg

    base = serve_estimate(_cfg(), budget=1 << 22, block_size=8,
                          max_len=64)[1]
    est = serve_estimate(_cfg(), budget=1 << 22, block_size=8,
                         max_len=64, prefix_cache=True,
                         expected_hit_rate=0.75)[1]
    # metadata is charged (never free) yet small next to KV blocks
    assert est["prefix_index_bytes"] > 0
    lost = base["num_blocks"] - est["num_blocks"]
    assert 0 < lost <= base["num_blocks"] * 0.05
    # shared blocks counted once: effective concurrency beats physical
    assert est["effective_max_streams"] > est["max_streams"]
    assert est["expected_hit_rate"] == 0.75
    with pytest.raises(ValueError, match="expected_hit_rate"):
        serve_estimate(_cfg(), budget=1 << 22, block_size=8, max_len=64,
                       prefix_cache=True, expected_hit_rate=1.0)


# -- TTL leases (gateway r17) --------------------------------------------------


def test_ttl_expiry_is_lazy_and_journaled():
    from torch_automatic_distributed_neural_network_tpu.obs.journal import (
        Journal,
    )

    alloc = BlockAllocator(16)
    clock = [0.0]
    jnl = Journal(None, host0_only=False)
    pc = PrefixCache(block_size=8, allocator=alloc,
                     clock=lambda: clock[0], journal=jnl)
    leased = alloc.acquire(2)
    forever = alloc.acquire(1)
    pc.insert([1] * 16, leased, ttl_s=5.0)
    pc.insert([2] * 8, forever)  # no lease: lives until LRU eviction
    alloc.release(leased)
    alloc.release(forever)
    clock[0] = 4.9
    assert pc.match([1] * 16)[1] == 16  # still live
    assert pc.expired_blocks == 0
    clock[0] = 5.1
    # expiry is lazy: the next match sweeps the lease before walking
    assert pc.match([1] * 16) == ([], 0)
    assert pc.expired_blocks == 2
    assert pc.match([2] * 8)[1] == 8  # the unleased entry survives
    expire_events = [r for r in jnl.records
                     if r.get("name") == "serve.prefix"
                     and r.get("kind") == "expire"]
    assert len(expire_events) == 1
    assert expire_events[0]["n_blocks"] == 2


def test_ttl_republish_refreshes_lease():
    pc, alloc, clock = _mk_index()
    owner = alloc.acquire(1)
    pc.insert([1] * 8, owner, ttl_s=5.0)
    clock[0] = 4.0
    dup = alloc.acquire(1)
    pc.insert([1] * 8, dup, ttl_s=5.0)  # re-publish extends to t=9
    alloc.release(owner)
    alloc.release(dup)
    clock[0] = 6.0
    assert pc.match([1] * 8)[1] == 8  # old deadline passed, lease held
    clock[0] = 9.5
    assert pc.match([1] * 8) == ([], 0)
    assert pc.expired_blocks == 1


def test_ttl_evict_counts_expired_toward_shortfall():
    pc, alloc, clock = _mk_index()
    leased = alloc.acquire(2)
    pc.insert([1] * 16, leased, ttl_s=1.0)
    alloc.release(leased)
    clock[0] = 2.0
    # evict() sweeps leases first; the shortfall is already covered so
    # no LRU eviction happens on top
    assert pc.evict(1) == 2
    assert pc.n_blocks == 0 and alloc.n_live == 0


def test_ttl_referenced_blocks_stop_serving_but_free_lazily():
    pc, alloc, clock = _mk_index()
    owner = alloc.acquire(1)
    pc.insert([1] * 8, owner, ttl_s=1.0)
    clock[0] = 5.0
    # the lease is past due: the content must no longer be SERVED even
    # though the publisher's live ref pins the block — staleness and
    # memory reclaim are separate deadlines
    assert pc.match([1] * 8) == ([], 0)
    assert pc.expire() == 0  # referenced: not freeable yet
    assert pc.n_blocks == 1
    alloc.release(owner)
    assert pc.expire() == 1  # ref gone: the sweep reclaims it
    assert pc.n_blocks == 0 and alloc.n_live == 0
