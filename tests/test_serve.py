"""tadnn serve tests: paged-KV allocator and scheduler invariants
(cheap, host-only — tier-1), continuous-batching token parity with
sequential generate() on the CPU sim mesh (slow), serving telemetry
rendering through tadnn report, and the serve_estimate capacity lint."""

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torch_automatic_distributed_neural_network_tpu.analysis.serve_lint import (
    serve_estimate,
)
from torch_automatic_distributed_neural_network_tpu.inference import generate
from torch_automatic_distributed_neural_network_tpu.inference.decode import (
    compute_dtype_params,
    per_layer_params,
)
from torch_automatic_distributed_neural_network_tpu.inference.quant import (
    quantize_for_decode,
)
from torch_automatic_distributed_neural_network_tpu.inference.serve import (
    programs,
)
from torch_automatic_distributed_neural_network_tpu.inference.serve import (
    BlockAllocator,
    Request,
    Scheduler,
    ServeEngine,
    blocks_for_tokens,
    random_adapter,
)
from torch_automatic_distributed_neural_network_tpu.models import GPT2, MoE
from torch_automatic_distributed_neural_network_tpu.obs import (
    report as obs_report,
)
from torch_automatic_distributed_neural_network_tpu.planner import path_str
from torch_automatic_distributed_neural_network_tpu.training.lora import (
    LoraSpec,
)

from serve_by_hand import chunk_alone

VOCAB = 128


def _model_and_vars(seed=1, p=12):
    model = GPT2("test", vocab_size=VOCAB, max_seq_len=64,
                 dtype=jnp.float32, remat=False)
    tokens = jnp.asarray(
        np.random.RandomState(0).randint(1, VOCAB, size=(1, p)), jnp.int32)
    return model, model.init(jax.random.key(seed), tokens)


# -- block allocator ----------------------------------------------------------


def test_blocks_for_tokens():
    assert blocks_for_tokens(0, 8) == 1  # even empty holds one block
    assert blocks_for_tokens(1, 8) == 1
    assert blocks_for_tokens(8, 8) == 1
    assert blocks_for_tokens(9, 8) == 2
    assert blocks_for_tokens(64, 16) == 4


def test_allocator_all_or_nothing_and_null_block():
    a = BlockAllocator(5)  # ids 1..4 allocatable, 0 reserved
    assert a.n_free == 4
    got = a.alloc(3)
    assert got is not None and len(got) == 3 and 0 not in got
    assert a.alloc(2) is None  # only 1 left: no partial grant
    assert a.n_free == 1  # the failed alloc took nothing
    a.free(got)
    assert a.n_free == 4 and a.n_live == 0


def test_allocator_rejects_double_free_and_foreign_ids():
    a = BlockAllocator(4)
    got = a.alloc(2)
    a.free(got)
    with pytest.raises(ValueError, match="double-free|not currently"):
        a.free(got)
    with pytest.raises(ValueError):
        a.free([0])  # the null block is never live


def test_allocator_churn_no_leak():
    rs = np.random.RandomState(7)
    a = BlockAllocator(33)
    held = []
    for _ in range(500):
        if held and rs.rand() < 0.5:
            a.free(held.pop(rs.randint(len(held))))
        else:
            got = a.alloc(int(rs.randint(1, 5)))
            if got is not None:
                held.append(got)
        live = {b for blocks in held for b in blocks}
        assert live == a._live
        assert a.n_free + len(live) == 32
    for blocks in held:
        a.free(blocks)
    assert a.n_free == 32 and a.n_live == 0


# -- scheduler ----------------------------------------------------------------


def _mk_sched(num_blocks, n_slots=2, block_size=8, admission="reserve"):
    alloc = BlockAllocator(num_blocks)
    return Scheduler(n_slots=n_slots, allocator=alloc,
                     block_size=block_size, admission=admission)


def test_reserve_admission_and_eviction():
    # each request: 10 prompt + 6 new = 16 tokens = 2 blocks reserved
    s = _mk_sched(num_blocks=6)  # 5 allocatable -> 2 requests fit
    reqs = [Request(prompt=[1] * 10, max_new_tokens=6) for _ in range(3)]
    for r in reqs:
        s.submit(r)
    admitted = s.admit()
    assert [slot for slot, _ in admitted] == [0, 1]
    assert all(len(r.blocks) == 2 for _, r in admitted)
    assert s.n_queued == 1 and s.n_active == 2
    s.check_invariants()
    # FIFO blocks admission until a slot AND its blocks free up
    assert s.admit() == []
    reqs[0].out_tokens = [5] * 6  # finished
    done = s.evict(0)
    assert done.state == "done" and not done.blocks
    admitted = s.admit()
    assert [r.rid for _, r in admitted] == [reqs[2].rid]
    s.check_invariants()


def test_reserve_admission_gated_by_blocks_not_slots():
    # 3 allocatable blocks, 2-block reservations: one request at a time
    # even with both slots empty
    s = _mk_sched(num_blocks=4)
    for _ in range(2):
        s.submit(Request(prompt=[1] * 10, max_new_tokens=6))
    assert len(s.admit()) == 1
    assert s.n_queued == 1
    s.check_invariants()


def test_optimistic_grow_and_preemption():
    # pool of 4 blocks; two 8-token prompts admit at 1 block each, then
    # growth past the block boundary forces a preemption of the youngest
    s = _mk_sched(num_blocks=5, block_size=8, admission="optimistic")
    a, b = (Request(prompt=[1] * 8, max_new_tokens=16, eos_id=None)
            for _ in range(2))
    s.submit(a)
    s.submit(b)
    admitted = s.admit()
    assert len(admitted) == 2
    assert all(len(r.blocks) == 1 for _, r in admitted)
    # simulate decode until growth needs more than the pool holds:
    # each grows at 9, 17, 25 tokens -> 2nd and 3rd growth of one of
    # them must preempt the other (4 allocatable, 3+2 needed)
    preempted = []
    for _ in range(20):
        for r in s.slots:
            if r is not None:
                r.out_tokens.append(2)
        preempted += s.grow_for_step()
        s.check_invariants()
        if preempted:
            break
    assert preempted, "pool exhaustion never triggered preemption"
    victim = preempted[0]
    assert victim.preempted == 1
    assert victim.state == "queued" and not victim.blocks
    # requeued in FIFO (t_submit) order — here the queue is otherwise
    # empty, so the victim is simply next
    assert s.queue[0] is victim
    assert s.n_preemptions == 1
    s.check_invariants()


def test_finished_on_eos_and_budget():
    r = Request(prompt=[1, 2], max_new_tokens=4, eos_id=0)
    assert not r.finished()
    r.out_tokens = [5, 0]
    assert r.finished()  # EOS before budget
    r2 = Request(prompt=[1, 2], max_new_tokens=2, eos_id=None)
    r2.out_tokens = [9, 9]
    assert r2.finished()  # budget exhausted


# -- engine: continuous batching vs sequential generate() ---------------------


@pytest.mark.slow
@pytest.mark.parametrize("attention_impl", ["paged", "dense"])
def test_continuous_batching_matches_sequential_generate(
        devices8, attention_impl):
    """Token parity: mixed-length requests through 3 slots must emit
    exactly the tokens greedy generate() emits one request at a time —
    under BOTH decode paths (the fused paged kernel and the dense
    gather_blocks reference)."""
    model, variables = _model_and_vars()
    rs = np.random.RandomState(42)
    prompts = [[int(t) for t in rs.randint(1, VOCAB, size=(p,))]
               for p in (5, 9, 12, 7, 16)]
    max_new = 12

    # float32 caches on both sides: a bf16 cache moves a logit by 1e-3,
    # and this toy model has ties that near
    eng = ServeEngine(model, variables, n_slots=3, max_len=64,
                      block_size=8, attention_impl=attention_impl,
                      cache_dtype=jnp.float32)
    reqs = [eng.submit(p, max_new_tokens=max_new, eos_id=0)
            for p in prompts]
    done = eng.run()
    assert len(done) == len(prompts)
    eng.scheduler.check_invariants()
    assert eng.pool.allocator.n_live == 0  # every block returned

    for req in reqs:
        prompt = jnp.asarray(req.prompt, jnp.int32)[None, :]
        seq, lengths = generate(
            model, variables, prompt, max_new_tokens=max_new,
            eos_id=0, early_stop=True, return_lengths=True,
            cache_dtype=jnp.float32)
        n = int(lengths[0]) - len(req.prompt)
        expect = [int(t) for t in np.asarray(seq[0, len(req.prompt):
                                                 len(req.prompt) + n])]
        assert req.out_tokens == expect, (req.rid, req.out_tokens, expect)


@pytest.mark.slow
def test_engine_int8_kv_serves(devices8):
    model, variables = _model_and_vars()
    eng = ServeEngine(model, variables, n_slots=2, max_len=64,
                      block_size=8, quant_kv=True)
    for p in (6, 11, 9):
        eng.submit([1] * p, max_new_tokens=6, eos_id=0)
    done = eng.run()
    assert len(done) == 3
    assert all(0 < r.n_generated <= 6 for r in done)
    assert all(0 <= t < VOCAB for r in done for t in r.out_tokens)
    eng.scheduler.check_invariants()


@pytest.mark.slow
def test_engine_optimistic_preempts_and_finishes(devices8):
    # 9 allocatable blocks cannot reserve 4 requests of 24 tokens
    # (3 blocks each): optimistic admission oversubscribes and preempts
    model, variables = _model_and_vars()
    eng = ServeEngine(model, variables, n_slots=4, max_len=32,
                      block_size=8, num_blocks=10, admission="optimistic")
    for _ in range(4):
        eng.submit([3] * 12, max_new_tokens=12, eos_id=None)
    done = eng.run()
    assert len(done) == 4
    assert all(r.n_generated == 12 for r in done)
    assert eng.scheduler.n_preemptions > 0
    assert eng.pool.allocator.n_free == 9  # zero leaked blocks
    eng.scheduler.check_invariants()


def test_submit_rejects_impossible_requests():
    model, variables = _model_and_vars()
    eng = ServeEngine(model, variables, n_slots=2, max_len=64,
                      block_size=8, num_blocks=3)
    with pytest.raises(ValueError, match="exceeds engine max_len"):
        eng.submit([1] * 60, max_new_tokens=10)
    with pytest.raises(ValueError, match="empty prompt"):
        eng.submit([], max_new_tokens=4)
    with pytest.raises(ValueError, match="pool has"):
        eng.submit([1] * 30, max_new_tokens=10)  # 5 blocks > 2 usable


# -- serving telemetry -> tadnn report ----------------------------------------


def test_report_renders_serving_section(tmp_path):
    jp = tmp_path / "journal.jsonl"
    recs = [{"kind": "event", "name": "serve.step", "t": 0.1 * i,
             "step": i, "n_active": 2, "n_queued": 0,
             "occupancy": 0.5, "free_blocks": 3} for i in range(1, 5)]
    recs += [
        {"kind": "event", "name": "serve.request", "t": 0.3, "rid": 0,
         "n_prompt": 4, "n_new": 6, "queue_s": 0.01, "prefill_s": 0.05,
         "decode_s": 0.2, "total_s": 0.26, "tokens_per_s": 30.0,
         "preempted": 0},
        {"kind": "event", "name": "serve.request", "t": 0.4, "rid": 1,
         "n_prompt": 2, "n_new": 4, "queue_s": 0.02, "prefill_s": 0.04,
         "decode_s": 0.3, "total_s": 0.36, "tokens_per_s": 13.3,
         "preempted": 1},
    ]
    with open(jp, "w") as f:
        for r in recs:
            f.write(json.dumps(r) + "\n")
    report = obs_report.generate(str(jp))
    srv = report["serving"]
    assert srv["n_requests"] == 2 and srv["n_steps"] == 4
    assert srv["p50_latency_s"] == 0.26
    assert srv["p99_latency_s"] == 0.36
    assert srv["total_new_tokens"] == 10
    assert srv["mean_occupancy"] == pytest.approx(0.5)
    assert srv["preemptions"] == 1
    # goodput over the journal window: 10 tokens / (0.4 - 0.1) s
    assert srv["goodput_tokens_per_s"] == pytest.approx(10 / 0.3)
    text = obs_report.format_report(report)
    assert "serving: 2 request(s)" in text
    assert "p50" in text and "p99" in text and "goodput" in text


def test_report_renders_serving_breakdown(tmp_path):
    """r02 fields: the engine-config event, per-step phase timings and
    the count of prefill chunks (the steps' ``n_prefill_chunks``: a chunk
    has no record of its own) land in the serving section."""
    jp = tmp_path / "journal.jsonl"
    recs = [{"kind": "event", "name": "serve.engine", "t": 0.0,
             "attention_impl": "paged", "prefill_chunk": 32,
             "n_slots": 4, "max_len": 64, "block_size": 8,
             "quant_kv": False, "weights_cast": 12,
             "weight_bytes_compute": 3 * 2**29,
             "weight_bytes_fp32": 2**28}]
    recs += [{"kind": "event", "name": "serve.step", "t": 0.1 * i,
              "step": i, "n_active": 2, "n_queued": 0,
              "n_prefilling": 1, "occupancy": 0.5, "free_blocks": 3,
              "prefill_s": 0.02, "decode_s": 0.01,
              "n_prefill_chunks": int(i < 3)} for i in range(1, 4)]
    recs += [{"kind": "event", "name": "serve.request", "t": 0.4,
              "rid": 0, "n_prompt": 40, "n_new": 6, "queue_s": 0.01,
              "prefill_s": 0.05, "decode_s": 0.2, "total_s": 0.26,
              "tokens_per_s": 30.0, "preempted": 0}]
    with open(jp, "w") as f:
        for r in recs:
            f.write(json.dumps(r) + "\n")
    srv = obs_report.generate(str(jp))["serving"]
    assert srv["attention_impl"] == "paged"
    assert srv["prefill_chunk"] == 32
    assert srv["mean_decode_step_s"] == pytest.approx(0.01)
    assert srv["mean_prefill_step_s"] == pytest.approx(0.02)
    assert srv["n_prefill_chunks"] == 2
    text = obs_report.format_report(obs_report.generate(str(jp)))
    assert "decode impl paged" in text
    assert "prefill chunks x2 (C=32)" in text
    assert ("weights 1.50 GiB in the compute dtype + 0.25 GiB float32 "
            "(12 leaves rounded once)") in text


@pytest.mark.slow
def test_engine_journals_render_end_to_end(tmp_path, devices8):
    from torch_automatic_distributed_neural_network_tpu.obs.journal import (
        Journal,
    )

    model, variables = _model_and_vars()
    jp = tmp_path / "journal.jsonl"
    with Journal(str(jp), host0_only=False) as jnl:
        eng = ServeEngine(model, variables, n_slots=2, max_len=64,
                          block_size=8, journal=jnl)
        for p in (4, 7):
            eng.submit([2] * p, max_new_tokens=5, eos_id=0)
        eng.run()
    report = obs_report.generate(str(jp))
    srv = report["serving"]
    assert srv["n_requests"] == 2
    assert srv["n_steps"] >= 1
    assert "p50_latency_s" in srv and "mean_occupancy" in srv
    assert "serving:" in obs_report.format_report(report)


# -- serve_estimate capacity lint ---------------------------------------------


def _cfg():
    return GPT2("test", vocab_size=VOCAB, max_seq_len=64,
                dtype=jnp.float32, remat=False).cfg


def test_serve_estimate_fit_no_findings():
    findings, est = serve_estimate(_cfg(), budget="64MiB", headroom=0.0,
                                   block_size=16, max_len=256, streams=8)
    assert findings == []
    assert est["max_streams"] >= 8
    assert est["blocks_per_stream"] == 16


def test_serve_estimate_ml005_warns_on_partial_fit():
    # test cfg: one bf16 block of 16 tokens is 2L*16*4kvH*32hd*2B*2(kv)
    # = 16 KiB -> 1 MiB holds 64 blocks, 63 usable, 3 full streams
    findings, est = serve_estimate(_cfg(), budget="1MiB", headroom=0.0,
                                   block_size=16, max_len=256, streams=8)
    assert est["block_bytes_per_device"] == 16 * 1024
    assert est["max_streams"] == 3
    assert [f.code for f in findings] == ["ML005"]
    assert findings[0].severity == "warn"
    assert "--quant-kv" in findings[0].msg


def test_serve_estimate_ml004_errors_when_nothing_fits():
    findings, est = serve_estimate(_cfg(), budget="8KiB", headroom=0.0,
                                   block_size=16, max_len=256)
    assert est["max_streams"] == 0
    assert [f.code for f in findings] == ["ML004"]
    assert findings[0].severity == "error"


def test_serve_estimate_int8_kv_shrinks_blocks():
    _, dense = serve_estimate(_cfg(), budget="1MiB", headroom=0.0)
    _, int8 = serve_estimate(_cfg(), budget="1MiB", headroom=0.0,
                             quant_kv=True)
    assert int8["block_bytes_per_device"] < dense["block_bytes_per_device"]
    assert int8["max_streams"] > dense["max_streams"]


def test_serve_estimate_dense_charges_gather_workspace():
    """attention_impl='dense' budgets the per-step gathered k+v views
    (and can only lose streams for it); paged charges exactly 0."""
    _, paged = serve_estimate(_cfg(), budget="1MiB", headroom=0.0,
                              block_size=16, max_len=256, streams=3)
    _, dense = serve_estimate(_cfg(), budget="1MiB", headroom=0.0,
                              block_size=16, max_len=256, streams=3,
                              attention_impl="dense")
    assert paged["attention_impl"] == "paged"
    assert paged["decode_workspace_bytes"] == 0
    # 3 streams x 2 sides x 256 tokens x 4 kvH x 32 hd x 2 B = 384 KiB
    assert dense["decode_workspace_bytes"] == 3 * 2 * 256 * 4 * 32 * 2
    assert dense["max_streams"] <= paged["max_streams"]
    with pytest.raises(ValueError, match="attention_impl"):
        serve_estimate(_cfg(), budget="1MiB", attention_impl="fused")


# -- pure decision functions (scheduler refactor) -----------------------------


def test_pure_admission_plan_matches_scheduler():
    from torch_automatic_distributed_neural_network_tpu.inference.serve import (
        admission_plan,
    )

    for admission in ("reserve", "optimistic"):
        alloc = BlockAllocator(num_blocks=9)
        sched = Scheduler(n_slots=4, allocator=alloc, block_size=4,
                          admission=admission)
        reqs = [Request(prompt=[1] * 6, max_new_tokens=10)
                for _ in range(6)]
        for r in reqs:
            sched.submit(r)
        planned = admission_plan(
            [(r.n_prompt, r.max_new_tokens) for r in sched.queue],
            n_free_slots=4, n_free_blocks=alloc.n_free,
            block_size=4, admission=admission)
        admitted = sched.admit()
        assert len(admitted) == planned
        sched.check_invariants()


def test_pure_admission_plan_fifo_stops_at_first_nonfit():
    from torch_automatic_distributed_neural_network_tpu.inference.serve import (
        admission_plan,
    )

    # head needs 4 blocks, only 3 free: nothing admits even though the
    # smaller request behind it would fit (FIFO, no reordering)
    n = admission_plan([(13, 3), (1, 1)], n_free_slots=2,
                       n_free_blocks=3, block_size=4,
                       admission="reserve")
    assert n == 0
    # slots bound it too
    n = admission_plan([(1, 1), (1, 1), (1, 1)], n_free_slots=1,
                       n_free_blocks=100, block_size=4,
                       admission="reserve")
    assert n == 1


def test_pure_preemption_victim_matches_scheduler():
    from torch_automatic_distributed_neural_network_tpu.inference.serve import (
        preemption_victim,
    )

    alloc = BlockAllocator(num_blocks=32)
    sched = Scheduler(n_slots=3, allocator=alloc, block_size=4,
                      admission="optimistic")
    for _ in range(3):
        sched.submit(Request(prompt=[1] * 4, max_new_tokens=4))
    sched.admit()
    occupied = [(r.t_admit, r.slot) for r in sched.slots if r is not None]
    want = preemption_victim(occupied)
    victim = sched.preempt_youngest()
    assert victim is not None and victim.slot is None
    assert want == occupied[-1][1]  # youngest admit = last admitted
    sched.check_invariants()
    assert preemption_victim([]) is None
    # strict > keeps the FIRST max on ties, like max() over slot order
    assert preemption_victim([(1.0, 0), (1.0, 2)]) == 0


def test_pure_decode_needs_block_boundary():
    from torch_automatic_distributed_neural_network_tpu.inference.serve import (
        decode_needs_block,
    )

    # 8 tokens in 2 blocks of 4: next write (pos 8) needs block 3
    assert not decode_needs_block(6, 2, 2, block_size=4)
    assert decode_needs_block(6, 3, 2, block_size=4)
    # speculative lookahead pulls the boundary forward
    assert decode_needs_block(6, 2, 2, block_size=4, spec_lookahead=1)


def test_pure_prefill_schedule_oldest_first():
    from torch_automatic_distributed_neural_network_tpu.inference.serve import (
        prefill_schedule,
    )

    order = prefill_schedule([(3.0, 0), (1.0, 2), (2.0, 1)], 2)
    assert order == [2, 1]
    # None admit times sort as 0.0 (first)
    assert prefill_schedule([(3.0, 0), (None, 2)], 4) == [2, 0]


def test_scheduler_injected_clock_drives_timestamps():
    clock = [100.0]
    alloc = BlockAllocator(num_blocks=16)
    sched = Scheduler(n_slots=2, allocator=alloc, block_size=4,
                      admission="reserve", clock=lambda: clock[0])
    req = Request(prompt=[1, 2], max_new_tokens=2)
    sched.submit(req)
    sched.admit()
    assert req.t_admit == 100.0
    clock[0] = 107.5
    sched.evict(req.slot)
    assert req.t_done == 107.5


# -- priority classes (gateway r17) --------------------------------------------


def test_priority_orders_admission_under_reserve():
    # 5 allocatable blocks, 2-block reservations: two admits per round.
    # A batch-class request (priority 1) submitted FIRST must yield to
    # interactive (priority 0) requests submitted after it.
    s = _mk_sched(num_blocks=6)
    batch = Request(prompt=[1] * 10, max_new_tokens=6, priority=1)
    int_a = Request(prompt=[2] * 10, max_new_tokens=6, priority=0)
    int_b = Request(prompt=[3] * 10, max_new_tokens=6, priority=0)
    for r in (batch, int_a, int_b):
        s.submit(r)
    admitted = s.admit()
    assert [r.rid for _, r in admitted] == [int_a.rid, int_b.rid]
    assert s.n_queued == 1  # batch waits
    s.check_invariants()
    int_a.out_tokens = [5] * 6
    s.evict(0)
    assert [r.rid for _, r in s.admit()] == [batch.rid]
    s.check_invariants()


def test_priority_fifo_within_class_and_default_is_legacy_order():
    s = _mk_sched(num_blocks=20, n_slots=6)
    # same class: strict submission order (t_submit then rid)
    reqs = [Request(prompt=[i + 1] * 10, max_new_tokens=6, priority=1)
            for i in range(3)]
    for r in reqs:
        s.submit(r)
    assert [q.rid for q in s.queue] == [r.rid for r in reqs]
    # default priority 0 degenerates to pure FIFO with earlier zeros
    plain = Request(prompt=[9] * 10, max_new_tokens=6)
    assert plain.priority == 0
    s.submit(plain)
    assert [q.rid for q in s.queue][0] == plain.rid
    admitted = s.admit()
    assert [r.rid for _, r in admitted] == (
        [plain.rid] + [r.rid for r in reqs])


def test_priority_requeue_keeps_class_position():
    # a preempted interactive request goes back AHEAD of queued batch
    # work, behind nothing of its own class that submitted earlier
    # (3 allocatable blocks: only ONE 2-block reservation fits, so the
    # batch request is still queued when the interactive one bounces)
    s = _mk_sched(num_blocks=4)
    inter = Request(prompt=[1] * 10, max_new_tokens=6, priority=0)
    batch = Request(prompt=[2] * 10, max_new_tokens=6, priority=1)
    s.submit(inter)
    s.submit(batch)
    admitted = s.admit()
    assert [r.rid for _, r in admitted] == [inter.rid]
    s.requeue(admitted[0][0])
    assert [q.rid for q in s.queue] == [inter.rid, batch.rid]
    s.check_invariants()


# -- differential fuzz: Scheduler class vs pure decision functions ------------


def test_fuzz_scheduler_matches_pure_functions():
    """~1k fuzzed request streams: every admission round, prefill plan
    and preemption the Scheduler class takes must match what the pure
    module-level functions (admission_plan / prefill_schedule /
    preemption_victim) decide from the same observable state — the
    PR-12 equivalence pins, but over randomized schedules instead of
    four hand-picked ones.  Host-only and fast: no engine, no jax."""
    from torch_automatic_distributed_neural_network_tpu.inference.serve import (
        admission_plan,
        preemption_victim,
        prefill_schedule,
    )

    rs = np.random.RandomState(1234)
    for trial in range(1000):
        n_slots = int(rs.randint(1, 5))
        block_size = int(rs.choice([2, 4]))
        num_blocks = int(rs.randint(4, 17))
        admission = "optimistic" if rs.randint(2) else "reserve"
        t = [0.0]
        alloc = BlockAllocator(num_blocks=num_blocks)
        sched = Scheduler(n_slots=n_slots, allocator=alloc,
                          block_size=block_size, admission=admission,
                          clock=lambda: t[0])
        pending = [
            Request(prompt=[1] * int(rs.randint(1, 10)),
                    max_new_tokens=int(rs.randint(1, 5)),
                    priority=int(rs.choice([0, 0, 1])))
            for _ in range(int(rs.randint(1, 6)))
        ]
        ctx = f"trial {trial} ({admission}, slots={n_slots}, " \
              f"blocks={num_blocks}x{block_size})"
        for _ in range(12):
            t[0] += 1.0
            if pending and rs.rand() < 0.6:
                sched.submit(pending.pop())
            keys = [Scheduler._queue_key(r) for r in sched.queue]
            assert keys == sorted(keys), ctx
            planned = admission_plan(
                [(r.n_prompt, r.max_new_tokens) for r in sched.queue],
                sum(s is None for s in sched.slots), alloc.n_free,
                block_size=block_size, admission=admission)
            admitted = sched.admit()
            assert len(admitted) == planned, ctx
            for _slot, req in admitted:
                req.state = "prefilling"  # chunked-prefill mode
            budget = [1, 2, 3][int(rs.randint(3))]
            prefilling = [(r.t_admit, s)
                          for s, r in enumerate(sched.slots)
                          if r is not None and r.state == "prefilling"]
            plan = sched.prefill_plan(budget)
            assert [s for s, _ in plan] == \
                prefill_schedule(prefilling, budget), ctx
            for _slot, req in plan:
                if rs.rand() < 0.5:  # this chunk completed the prompt
                    req.state = "running"
                    req.out_tokens.append(1)
            for r in sched.slots:
                if (r is not None and r.state == "running"
                        and not r.finished()):
                    r.out_tokens.append(1)
            if admission == "optimistic" and rs.rand() < 0.3:
                want = preemption_victim(
                    [(r.t_admit, r.slot) for r in sched.slots
                     if r is not None])
                victim = sched.preempt_youngest()
                assert (victim is None) == (want is None), ctx
                if want is not None:
                    assert victim is not None and victim.slot is None
                    assert sched.slots[want] is None, ctx
            for s, r in enumerate(list(sched.slots)):
                if (r is not None and r.state == "running"
                        and r.finished()):
                    sched.evict(s)
            sched.check_invariants()


def test_debug_invariants_env_gate(monkeypatch):
    """TADNN_DEBUG_INVARIANTS=1 arms the per-step invariant audit; ""
    and "0" leave it off.  Run one short request through an armed engine
    so the audit actually executes on every step."""
    model, variables = _model_and_vars()
    for value, armed in (("", False), ("0", False), ("1", True)):
        if value:
            monkeypatch.setenv("TADNN_DEBUG_INVARIANTS", value)
        else:
            monkeypatch.delenv("TADNN_DEBUG_INVARIANTS", raising=False)
        eng = ServeEngine(model, variables, n_slots=2, max_len=32,
                          block_size=8)
        assert eng._debug_invariants is armed, value
        if armed:
            eng.submit([1, 2, 3], max_new_tokens=4, eos_id=0)
            done = eng.run()
            assert len(done) == 1


# -- the tree the base programs take: weights rounded once --------------------
#
# Every weight below is float32 and NOT representable in bfloat16, so a
# rounding made twice, not at all, or after a float32 sum would show.  The
# comparisons are bit for bit on the CPU backend as it is: it was checked
# (PR 26) that this backend keeps the float32 -> bf16 -> float32 pair around
# a weight, so nothing is compiled with xla_allow_excess_precision=false.


def _bf16_model_and_vars(moe=False):
    kw = dict(vocab_size=VOCAB, max_seq_len=64, dtype=jnp.bfloat16,
              remat=False)
    model = (MoE if moe else GPT2)("test", **kw)
    variables = model.init(jax.random.key(1), jnp.ones((1, 12), jnp.int32))
    leaves, treedef = jax.tree.flatten(variables["params"])
    keys = jax.random.split(jax.random.key(7), len(leaves))
    rough = [x + 0.013 * jax.random.normal(k, x.shape, x.dtype)
             for x, k in zip(leaves, keys)]
    assert all(x.dtype == jnp.float32 for x in rough)
    assert all(bool((x.astype(jnp.bfloat16).astype(jnp.float32) != x).any())
               for x in rough)
    return model, {"params": jax.tree.unflatten(treedef, rough)}


def _paths(tree):
    return {path_str(p): x
            for p, x in jax.tree_util.tree_flatten_with_path(tree)[0]}


def _is_layer_weight(path: str) -> bool:
    """What the layer code rounds to ``cfg.dtype`` at use and the TPU's
    compiler rounds too (``pos_embed`` it adds in float32: it stays)."""
    return (path.startswith(("layers/attn/", "layers/mlp/"))
            and "/router/" not in path
            and not path.endswith(("/kernel/q", "/kernel/scale")))  # int8


@pytest.mark.parametrize("kind", ["plain", "moe", "int8", "rounded_already",
                                  "float32_compute"])
def test_compute_dtype_params_rounds_the_layer_weights_and_nothing_else(kind):
    model, variables = _bf16_model_and_vars(moe=kind == "moe")
    cfg, given = model.cfg, variables["params"]
    if kind == "int8":
        given = quantize_for_decode(given)
    elif kind == "rounded_already":
        given = compute_dtype_params(given, cfg)
    elif kind == "float32_compute":
        cfg = dataclasses.replace(cfg, dtype=jnp.float32)
    before, after = _paths(given), _paths(compute_dtype_params(given, cfg))
    assert list(before) == list(after)
    cast = [p for p in before if after[p] is not before[p]]
    want = [p for p in before
            if _is_layer_weight(p) and before[p].dtype == jnp.float32
            and cfg.dtype != jnp.float32]
    assert cast == want
    for p in cast:
        assert after[p].dtype == cfg.dtype
        assert bool((after[p] == before[p].astype(cfg.dtype)).all()), p
    if kind == "plain":
        assert {"layers/attn/q_proj/bias", "layers/attn/o_proj/kernel",
                "layers/mlp/down_proj/kernel"} <= set(cast)
        assert {"embed/embedding", "pos_embed", "final_norm/scale",
                "layers/attn_norm/bias", "layers/mlp_norm/scale"
                } <= set(before) - set(cast)
    if kind == "moe":  # rope, rmsnorm, no biases, untied head
        assert {"layers/attn/q_proj/kernel", "layers/mlp/experts_up",
                "layers/mlp/experts_down"} <= set(cast)
        assert {"layers/mlp/router/kernel", "embed/embedding",
                "layers/attn_norm/scale"} <= set(before) - set(cast)
    if kind == "int8":  # only the biases are left to round
        assert cast and all(p.endswith("/bias") for p in cast)
    if kind in ("rounded_already", "float32_compute"):
        assert not cast


def test_compute_dtype_params_keeps_a_leafs_sharding(devices8):
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    model, variables = _bf16_model_and_vars()
    mesh = Mesh(np.asarray(devices8[:2]), ("tensor",))
    params = variables["params"]
    sh = NamedSharding(mesh, P(None, None, "tensor"))
    up = jax.device_put(params["layers"]["mlp"]["up_proj"]["kernel"], sh)
    params["layers"]["mlp"]["up_proj"]["kernel"] = up
    out = compute_dtype_params(params, model.cfg)
    got = out["layers"]["mlp"]["up_proj"]["kernel"]
    assert got.dtype == jnp.bfloat16
    assert got.sharding.is_equivalent_to(sh, got.ndim)


def _base_program_operands(model, variables, program):
    """(function, operands after the params) of one base program as the
    engine builds it, on a pool that holds a few written tokens."""
    # 8 pages of 8 keys a slot: the paged kernel's [heads, keys] tiles are
    # then shaped like no weight of the model
    eng = ServeEngine(model, variables, n_slots=2, max_len=64, block_size=8,
                      prefill_chunk=8, export_cache=False)
    rs = np.random.RandomState(3)
    kv = jax.tree.map(
        lambda x: jnp.asarray(rs.normal(size=x.shape), x.dtype), eng.pool.kv)
    if program == "prefill_chunk":  # the second chunk of a prompt, 6 real
        return chunk_alone(eng).__wrapped__, (
            kv, programs.pack_chunk(
                [1, 2] + [0] * 6, rs.randint(1, VOCAB, size=(8,)), 8, 5),
            eng.pool.win_tables[0])
    tables = np.asarray([[1, 2] + [0] * 6, [3] + [0] * 7], np.int32)
    return eng._step_fn.__wrapped__, (
        kv, programs.pack_step(
            tables, np.asarray([9, 3]), rs.randint(1, VOCAB, size=(2, 1)),
            np.asarray([True, True]), np.zeros((2,), np.int32)),
        programs.step_output(2), eng.pool.win_tables, {}, jax.random.key(0))


@pytest.mark.parametrize("program", ["decode_step", "prefill_chunk"])
def test_base_program_returns_the_same_for_the_rounded_tree(program):
    """Tokens and KV of ``programs.decode_step``, last-position logits and
    KV of ``programs.prefill_chunk``: bit for bit what the float32 tree
    (the scanned stack, sliced and rounded inside the call) gives."""
    model, variables = _bf16_model_and_vars()
    fn, operands = _base_program_operands(model, variables, program)
    given = variables["params"]
    rounded = compute_dtype_params(given, model.cfg)
    a = jax.tree.leaves(jax.jit(fn)(given, *operands))
    for tree in (rounded, per_layer_params(rounded, model.cfg)):
        b = jax.tree.leaves(jax.jit(fn)(tree, *operands))
        assert len(a) == len(b) >= 2
        for x, y in zip(a, b):
            assert x.dtype == y.dtype
            np.testing.assert_array_equal(np.asarray(x.astype(jnp.float32)),
                                          np.asarray(y.astype(jnp.float32)))
    if program == "prefill_chunk":
        assert a[-1].shape == (1, VOCAB) and bool(jnp.any(a[-1] != 0))


@pytest.mark.parametrize("program", ["decode_step", "prefill_chunk"])
def test_base_program_lowers_without_a_convert_of_a_weight(program):
    """What keeps the loop-invariant converts from coming back: lowered
    for the tree the engine holds, neither base program has a float32 ->
    bf16 ``convert`` of a tensor shaped like a layer weight, stacked
    ``[n_layers, ...]`` or one layer's slice of it (where the layer code
    writes it; XLA moves it out of the scan, over the whole stack).  The
    same search finds them in the program lowered for the float32 tree."""
    import re

    model, variables = _bf16_model_and_vars()
    fn, operands = _base_program_operands(model, variables, program)
    given = variables["params"]
    shapes = set()
    for p, x in _paths(given).items():
        if _is_layer_weight(p):
            shapes |= {x.shape, x.shape[1:]}

    def weight_converts(params):
        text = jax.jit(fn).lower(params, *operands).as_text()
        found = re.findall(
            r"stablehlo\.convert [^\n]*\(tensor<([0-9x]+)xf32>\) -> "
            r"tensor<[0-9x]+xbf16>", text)
        return [s for s in found
                if tuple(int(d) for d in s.split("x")) in shapes]

    rounded = compute_dtype_params(given, model.cfg)
    assert weight_converts(rounded) == []
    assert weight_converts(per_layer_params(rounded, model.cfg)) == []
    assert len(weight_converts(given)) >= 6


def _serve_tokens(eng, tenants=()):
    rs = np.random.RandomState(5)
    prompts = [[int(t) for t in rs.randint(1, VOCAB, size=(n,))]
               for n in (5, 19, 11)]
    names = [None, None, None]
    for name, lora in tenants:
        eng.register_adapter(name, lora)
        names = [None, name, name]
    reqs = [eng.submit(p, max_new_tokens=6, adapter=a)
            for p, a in zip(prompts, names)]
    eng.run()
    return [list(r.out_tokens) for r in reqs]


@pytest.mark.parametrize("kind", ["plain", "int8_weights", "speculative",
                                  "lora"])
def test_engine_serves_the_tokens_of_the_float32_tree(kind):
    """End to end, greedy: an engine emits what the same engine emits when
    its base programs are handed the tree it was given (which is what
    they were handed before the rounding moved to construction)."""
    model, variables = _bf16_model_and_vars()
    if kind == "int8_weights":
        variables = quantize_for_decode(variables)
    kw = dict(n_slots=2, max_len=32, block_size=8, prefill_chunk=8,
              export_cache=False)
    tenants = ()
    if kind == "speculative":
        kw["speculative"] = 2
    if kind == "lora":
        kw.update(lora_spec=LoraSpec(rank=4, alpha=8.0), n_adapters=2)
        tenants = (("t0", random_adapter(
            variables["params"], kw["lora_spec"], seed=11)),)
    eng = ServeEngine(model, variables, **kw)
    assert eng.params is not variables["params"]
    before = ServeEngine(model, variables, **kw)
    before.params = variables["params"]
    got, want = _serve_tokens(eng, tenants), _serve_tokens(before, tenants)
    assert got == want
    assert all(len(t) == 6 for t in got) and len({tuple(t) for t in got}) > 1


def test_tenant_prefill_merges_into_the_float32_weights():
    """A ``lora_spec`` engine hands its tenant prefill the tree it was
    given: ``merge_lora`` adds the delta to the float32 weight and the
    program rounds the sum.  An engine without tenants keeps no float32
    layer weight alive."""
    import gc
    import weakref

    model, variables = _bf16_model_and_vars()
    spec = LoraSpec(rank=4, alpha=8.0)
    eng = ServeEngine(model, variables, n_slots=2, max_len=32, block_size=8,
                      prefill_chunk=8, lora_spec=spec, n_adapters=2,
                      export_cache=False)
    seen = []
    lora_fn = eng._prefill_lora_fn
    eng._prefill_lora_fn = lambda params, *rest: (
        seen.append(params) or lora_fn(params, *rest))
    eng.register_adapter(
        "t0", random_adapter(variables["params"], spec, seed=11))
    eng.submit([3, 1, 4, 1, 5, 9, 2, 6, 5, 3], max_new_tokens=2,
               adapter="t0")
    eng.run()
    assert len(seen) == 2  # ten tokens in chunks of eight
    given = _paths(variables["params"])
    for params in seen:
        assert all(x is given[p] for p, x in _paths(params).items())
    assert given["layers/attn/q_proj/kernel"].dtype == jnp.float32
    assert "layers" not in eng.params  # taken apart: layers_0 ..
    assert (eng.params["layers_0"]["attn"]["q_proj"]["kernel"].dtype
            == jnp.bfloat16)

    plain = ServeEngine(model, variables, n_slots=2, max_len=32,
                        block_size=8, prefill_chunk=8, export_cache=False)
    assert plain._merge_base is None
    kernel = weakref.ref(variables["params"]["layers"]["mlp"]["up_proj"]
                         ["kernel"])
    embedding = variables["params"]["embed"]["embedding"]
    del variables, given, seen, eng, lora_fn, params
    gc.collect()
    assert kernel() is None
    assert plain.params["embed"]["embedding"] is embedding


def test_engine_event_counts_the_rounded_weights():
    from torch_automatic_distributed_neural_network_tpu.obs.journal import (
        Journal,
    )

    model, variables = _bf16_model_and_vars()
    j = Journal(None, validate=True, host0_only=False)
    eng = ServeEngine(model, variables, n_slots=2, max_len=32, block_size=8,
                      journal=j, export_cache=False)
    (ev,) = j.named("serve.engine")
    held = jax.tree.leaves(eng.params)
    rounded = [x for x in held if x.dtype == jnp.bfloat16]
    # 6 kernels and 6 biases of the scanned stack, a layer each here
    assert ev["weights_cast"] == 12
    assert len(rounded) == 12 * model.cfg.n_layers
    assert ev["weight_bytes_compute"] == sum(x.nbytes for x in rounded)
    assert ev["weight_bytes_fp32"] == sum(
        x.nbytes for x in held if x.dtype == jnp.float32)
    assert ev["weight_bytes_compute"] + ev["weight_bytes_fp32"] == sum(
        x.nbytes for x in held)


# -- a step's tokens stay on the device; the host reads them one call late ----
#
# ``step()`` dispatches decode n + 1 and only then fetches decode n.  What may
# not change: the tokens (greedy: those of ``generate``; sampled: those of the
# same engine made to read before it dispatches), the count of tokens, and the
# pages.  What lags: an EOS is seen one step late, and a token's wall time is
# the moment of the read.


def _f32_engine(journal=None, **kw):
    """An engine whose cache is float32 like its weights: greedy tokens
    are then those of ``generate`` over a float32 cache to the last one (a
    bf16 cache moves a logit by 1e-3, enough to flip a near tie)."""
    model, variables = _model_and_vars()
    kw = {**dict(n_slots=3, max_len=64, block_size=8, prefill_chunk=8,
                 cache_dtype=jnp.float32, export_cache=False), **kw}
    return ServeEngine(model, variables, journal=journal, **kw)


def _greedy(prompt, max_new, eos_id=None):
    model, variables = _model_and_vars()
    seq, lengths = generate(
        model, variables, jnp.asarray(prompt, jnp.int32)[None, :],
        max_new_tokens=max_new, eos_id=eos_id, cache_dtype=jnp.float32,
        early_stop=eos_id is not None, return_lengths=True)
    return [int(t) for t in np.asarray(seq[0, len(prompt):int(lengths[0])])]


def _prompts(lengths, seed=42):
    rs = np.random.RandomState(seed)
    return [[int(t) for t in rs.randint(1, VOCAB, size=(n,))]
            for n in lengths]


def _same_rids(monkeypatch):
    """Requests of the next engine count from 1000 again: a prompt's first
    token is sampled under a key folded with its request id."""
    import itertools

    from torch_automatic_distributed_neural_network_tpu.inference.serve import (
        scheduler as sched_mod,
    )

    monkeypatch.setattr(sched_mod, "_rid_counter", itertools.count(1000))


@pytest.mark.parametrize("case", ["eos", "optimistic_preemption",
                                  "prefix_hit", "sampled"])
def test_engine_reading_one_step_late_serves_the_same_tokens(
        case, monkeypatch):
    monkeypatch.setenv("TADNN_DEBUG_INVARIANTS", "1")
    prompts, max_new, eos_id, kw = _prompts((5, 9, 12, 7, 16)), 10, None, {}
    if case == "eos":
        # a token that greedy decoding reaches in mid-request
        eos_id = _greedy(prompts[2], max_new)[4]
    if case == "optimistic_preemption":
        # 9 allocatable blocks cannot hold 3 requests of 28 tokens
        prompts = _prompts((12, 12, 12, 12))
        max_new = 16
        kw = dict(admission="optimistic", num_blocks=10, max_len=32)
    if case == "prefix_hit":
        # the last request shares the 16 tokens of the one before it
        prompts[3], prompts[4] = prompts[4], prompts[4] + prompts[3][:2]
        kw = dict(prefix_cache=True, n_slots=2)
    if case == "sampled":
        from torch_automatic_distributed_neural_network_tpu.inference.decode import (  # noqa: E501
            SampleConfig,
        )

        kw = dict(sample=SampleConfig(temperature=0.9))

    def serve(these, ahead=None):
        _same_rids(monkeypatch)
        eng = _f32_engine(**kw)
        if ahead is not None:
            eng._ahead = ahead
        reqs = [eng.submit(p, max_new_tokens=max_new, eos_id=eos_id)
                for p in these]
        done = eng.run()
        assert len(done) == len(these) and eng.scheduler.idle()
        assert all(r.n_inflight == 0 and r.state == "done" for r in reqs)
        return eng, [list(r.out_tokens) for r in reqs]

    eng, tokens = serve(prompts)
    assert eng.steps_ahead > 0.8 * eng._step_count
    if case == "sampled":
        # alone in the engine, the first request draws what it drew in a
        # full batch: its slot, its steps and its keys are the same
        assert serve(prompts[:1])[1][0] == tokens[0]
        assert len({tuple(t) for t in tokens}) == len(tokens)
    else:
        assert tokens == [_greedy(p, max_new, eos_id) for p in prompts]
    # and read before dispatch, as a speculative engine does, it serves the
    # same in the same number of steps (an EOS costs the late reader one
    # more step: the one it dispatched before it saw the EOS)
    twin, want = serve(prompts, ahead=0)
    assert twin.steps_ahead == 0 and tokens == want
    if case == "eos":
        assert any(len(t) < max_new and t[-1] == eos_id for t in tokens)
        assert eng.discarded_tokens > 0 == twin.discarded_tokens
    else:
        assert eng._step_count == twin._step_count
    if case == "optimistic_preemption":
        assert eng.scheduler.n_preemptions > 0
        assert eng.pool.allocator.n_free == 9  # zero leaked blocks
        # a victim's step in flight is thrown away with it
        assert eng.discarded_tokens > 0
    if case == "prefix_hit":
        assert eng.prefix_hits == 1 and eng.prefix_cached_tokens == 16


class _Trace:
    """The engine's dispatches and reads in order: its step function and the
    program that leaves a first token on the device wrapped, and
    ``jax.device_get`` as the engine module sees it.  ``step_of`` says which
    dispatch produced an output array (0: none yet)."""

    def __init__(self, eng, monkeypatch):
        from torch_automatic_distributed_neural_network_tpu.inference.serve import (
            engine as engine_mod,
        )

        self.log, self.keep, self.step_of = [], [], {}
        self.n_dispatched = 0
        step_fn, first_fn, fused_fn = (eng._step_fn, eng._first_fn,
                                       eng._fused_fn)
        self.step_of[id(eng._out)] = 0
        self.keep.append(eng._out)

        def step(*a):
            kv, out = step_fn(*a)
            self.n_dispatched += 1
            self._name(out, self.n_dispatched)
            self.log.append(("dispatch", self.n_dispatched))
            return kv, out

        def fused(*a):  # a step whose rows ride in a chunk is a step
            kv, out, logits = fused_fn(*a)
            rows = a[2][-eng.n_slots * (eng.max_blocks + 4):].reshape(
                eng.n_slots, -1)
            if rows[:, -2].any():  # not the engine's first chunk, alone
                self.n_dispatched += 1
                self._name(out, self.n_dispatched)
                self.log.append(("dispatch", self.n_dispatched))
            return kv, out, logits

        def first(prev, *a):
            out = first_fn(prev, *a)
            self._name(out, self.step_of[id(prev)])
            return out

        def device_get(x):
            self.log.append(("read", self.step_of[id(x)], self.n_dispatched))
            return jax.device_get(x)

        eng._step_fn, eng._first_fn = step, first
        if fused_fn is not None:
            eng._fused_fn = fused
        fake = type("jax", (), {"device_get": staticmethod(device_get)})
        for name in ("random", "jit", "tree", "eval_shape", "Array"):
            setattr(fake, name, getattr(jax, name))
        monkeypatch.setattr(engine_mod, "jax", fake)

    def _name(self, out, step):
        self.keep.append(out)  # an id is only unique while its array lives
        self.step_of[id(out)] = step


@pytest.mark.parametrize("speculative", [0, 2])
def test_decode_is_dispatched_before_the_step_before_is_read(
        speculative, monkeypatch):
    """Whenever a slot continues, decode n + 1 goes out before n is read;
    never with drafts to make from the tokens just produced."""
    from torch_automatic_distributed_neural_network_tpu.obs.journal import (
        Journal,
    )

    j = Journal(None, validate=True, host0_only=False)
    eng = _f32_engine(journal=j, speculative=speculative, n_slots=2)
    trace = _Trace(eng, monkeypatch)
    for p in _prompts((5, 12, 9)):
        eng.submit(p, max_new_tokens=6)
    eng.run()
    reads = [e for e in trace.log if e[0] == "read"]
    assert trace.n_dispatched >= 5 and len(reads) >= trace.n_dispatched
    steps = [s for s in j.named("serve.step") if s["decode_s"]]
    ev = j.named("serve.engine")[-1]
    if speculative:
        # every read is of the newest output: nothing is in flight behind it
        assert all(step == newest for _, step, newest in reads)
        assert ev["dispatch_ahead"] == 0 == eng.steps_ahead
        assert not any(s["ahead"] for s in steps)
        return
    assert ev["dispatch_ahead"] == 1
    # a read is of the step before the newest, but for the one that drains
    # the last step (no slot continued) and the first (no step before it)
    assert [step for _, step, _ in reads] == list(range(len(reads)))
    assert [newest - step for _, step, newest in reads] == (
        [1] * (len(reads) - 1) + [0])
    assert eng.steps_ahead == trace.n_dispatched - 1
    assert [s["ahead"] for s in steps] == [0] + [1] * (len(steps) - 2) + [0]
    assert sum(s["ahead"] for s in steps) / len(steps) > 0.8


def test_eos_is_seen_one_step_late_and_costs_one_slot_step(monkeypatch):
    monkeypatch.setenv("TADNN_DEBUG_INVARIANTS", "1")
    from torch_automatic_distributed_neural_network_tpu.obs.journal import (
        Journal,
    )

    prompt, other = _prompts((9, 14))
    free_run = _greedy(prompt, 12)
    eos_id = next(t for i, t in enumerate(free_run)
                  if 2 <= i < 10 and t not in free_run[:i])
    want = free_run[:free_run.index(eos_id) + 1]
    j = Journal(None, validate=True, host0_only=False)
    eng = _f32_engine(journal=j, n_slots=2)
    req = eng.submit(prompt, max_new_tokens=12, eos_id=eos_id)
    bystander = eng.submit(other, max_new_tokens=12)
    while req.state != "done":
        eng.step()
    # the tokens end at the EOS; the step that was in flight when the host
    # read it is counted and thrown away, and the slot and its pages are free
    assert req.out_tokens == want and len(req.token_walls) == len(want)
    assert eng.discarded_tokens == 1 == sum(
        s["discarded_tokens"] for s in j.named("serve.step"))
    assert req.blocks == [] and req.slot is None and req.n_inflight == 0
    assert eng.scheduler.slots.count(None) == 1
    n_live = eng.pool.allocator.n_live
    assert n_live == len(bystander.blocks)
    eng.run()
    # the write of the thrown-away step fell into the request's own page:
    # the request beside it decoded what it decodes alone
    assert bystander.out_tokens == _greedy(other, 12)
    assert eng.pool.allocator.n_live == 0
    eng.scheduler.check_invariants()


def test_run_and_idle_drain_the_unread_step():
    """``idle()`` is false until the host holds the last token of every
    request, and a slot whose request ends by length goes to the next one
    with no empty step between."""
    eng = _f32_engine(n_slots=1)
    m = 5
    reqs = [eng.submit(p, max_new_tokens=m) for p in _prompts((6, 7))]
    calls = 0
    while not eng.scheduler.idle():
        eng.step()
        calls += 1
        unread = any(r.n_inflight for r in reqs)
        assert unread == bool(eng._rows)
        if unread:
            assert not eng.scheduler.idle()
    assert all(len(r.out_tokens) == m == len(r.token_walls)
               and r.state == "done" and r.t_done is not None for r in reqs)
    # one chunk, m - 1 steps and the call that reads the last, per request;
    # the second request's chunk shares the call that drains the first
    assert calls == 2 * m - 1
    assert [r.out_tokens for r in reqs] == [
        _greedy(r.prompt, m) for r in reqs]
    assert eng.discarded_tokens == 0 and eng.scheduler.draining == []
    assert eng.run() == eng.finished and len(eng.finished) == 2


def test_a_token_is_stamped_when_the_host_reads_it(monkeypatch):
    """Under an injected clock: every stamp is taken after the read that
    brought the token and before the next dispatch, a request's stamps rise
    strictly, and its first token and the next never share one."""
    eng = _f32_engine(n_slots=2)
    trace = _Trace(eng, monkeypatch)
    ticks = [0]

    def clock():
        ticks[0] += 1
        trace.log.append(("clock", ticks[0]))
        return float(ticks[0])

    eng.scheduler.clock = clock
    reqs = [eng.submit(p, max_new_tokens=5) for p in _prompts((5, 11, 20))]
    for r in reqs:
        r.t_submit = 0.0
    emitted_before = eng.tokens_emitted
    eng.run()
    assert eng.tokens_emitted - emitted_before == 15
    last = {}  # clock tick -> the kind of the engine event before it
    for i, e in enumerate(trace.log):
        if e[0] == "clock":
            last[e[1]] = next((p[0] for p in reversed(trace.log[:i])
                               if p[0] != "clock"), None)
    for r in reqs:
        assert len(r.token_walls) == 5 and r.t_first_token == r.token_walls[0]
        assert all(b > a for a, b in zip(r.token_walls, r.token_walls[1:]))
        assert r.token_walls[1] - r.token_walls[0] > 0
        assert all(last[int(w)] == "read" for w in r.token_walls)
        assert r.t_admit < r.t_first_token <= r.t_done


def test_nothing_compiles_after_the_warm_up_across_admissions():
    from torch_automatic_distributed_neural_network_tpu.obs.journal import (
        Journal,
    )

    j = Journal(None, validate=True, host0_only=False)
    eng = _f32_engine(journal=j, n_slots=2, admission="optimistic",
                      num_blocks=7, max_len=32)
    eng.submit(_prompts((9,))[0], max_new_tokens=3)
    eng.run()
    warm = len(j.named("serve.step"))
    assert sum(s["compiles"] for s in j.named("serve.step")) > 0
    for p, m in zip(_prompts((3, 12, 17, 6, 12), seed=7), (1, 14, 12, 2, 14)):
        eng.submit(p, max_new_tokens=m)
    eng.run()
    later = j.named("serve.step")[warm:]
    assert len(later) > 20 and eng.scheduler.n_preemptions > 0
    assert sum(s["compiles"] for s in later) == 0
    assert eng._step_fn._cache_size() == 1 == eng._first_fn._cache_size()
    # every chunk, alone or with the step's decode rows, is one program,
    # and the engine holds no chunk alone beside it
    assert eng._fused_fn._cache_size() == 1
    assert eng._prefill_fn is None and eng.fused_steps > 0


def test_work_list_kernel_serves_the_dense_paths_tokens_without_a_compile(
        small_items):
    """A mixed-length batch whose contexts grow across items (8 pages
    of 2 keys: 4 items in ``max_len`` 64): the paged engine's greedy
    tokens are the dense path's, the kernel's grid follows the contexts
    (``attn_grid_items`` on the step events) and no step after the warm-up
    compiles: the list's length is a traced value, not a shape."""
    from torch_automatic_distributed_neural_network_tpu.obs.journal import (
        Journal,
    )

    prompts, news = _prompts((3, 17, 30, 9), seed=5), (44, 30, 20, 12)
    tokens = {}
    for impl in ("paged", "dense"):
        j = Journal(None, validate=True, host0_only=False)
        eng = _f32_engine(journal=j, attention_impl=impl, block_size=2)
        eng.submit(_prompts((9,))[0], max_new_tokens=3)
        eng.run()
        warm = len(j.named("serve.step"))
        reqs = [eng.submit(p, max_new_tokens=m)
                for p, m in zip(prompts, news)]
        eng.run()
        tokens[impl] = [r.out_tokens for r in reqs]
        later = j.named("serve.step")[warm:]
        assert sum(s["compiles"] for s in later) == 0
        assert eng._step_fn._cache_size() == 1
        grid = [(s["attn_grid_items"], s["attn_grid_dense"]) for s in later
                if "attn_grid_items" in s]
        assert len(grid) > 40
        if impl == "dense":
            assert set(grid) == {(0, 0)}
            continue
        # 2 layers x 3 slots x 4 groups; a slot has 1 to 3 groups live
        assert {d for _, d in grid} == {24}
        assert min(n for n, _ in grid) == 6 < max(n for n, _ in grid) <= 18
    assert tokens["paged"] == tokens["dense"]
    assert [len(t) for t in tokens["paged"]] == list(news)


def test_report_prints_the_share_of_steps_dispatched_ahead(tmp_path):
    """``tadnn report`` on a journal fixture: the share of decoding steps
    dispatched ahead and the slot-steps thrown away, beside the phases."""
    jp = tmp_path / "journal.jsonl"
    recs = [{"kind": "event", "name": "serve.engine", "t": 0.0,
             "attention_impl": "paged", "prefill_chunk": 32, "n_slots": 4,
             "max_len": 64, "block_size": 8, "quant_kv": False,
             "dispatch_ahead": 1}]
    for i in range(1, 11):
        decoding = i > 1  # the first call only admits and runs a chunk
        recs.append({
            "kind": "event", "name": "serve.step", "t": 0.01 * i, "step": i,
            "n_active": 4, "n_queued": 0, "occupancy": 1.0,
            "free_blocks": 3, "new_tokens": 4 if decoding else 0,
            "prefill_s": 0.0, "decode_s": 0.006 if decoding else 0.0,
            "phases": {"decode_dispatch": 0.001,
                       "decode_wait": 0.004} if decoding else {"admit": 1e-4},
            "step_s": 0.007, "t_end": 0.01 * i, "n_prefill_chunks": 0,
            "compiles": 0, "ahead": int(i > 2),
            "discarded_tokens": 2 if i == 7 else 0,
            # a step's counters come with its tokens, a call late
            **({"attn_grid_items": 10 + i, "attn_grid_dense": 64,
                "attn_pages_copied": 8 * (10 + i), "attn_pages_live": 50 + i}
               if i > 2 else {})})
    jp.write_text("".join(json.dumps(r) + "\n" for r in recs))
    report = obs_report.generate(str(jp))
    srv = report["serving"]
    assert (srv["attn_grid_items"], srv["attn_grid_dense"]) == (132, 512)
    assert ("paged attention grid: 132 live (slot, key group) items of 512 "
            "in a dense grid over the decode steps (0.258)"
            ) in obs_report.format_report(report)
    assert (srv["attn_pages_copied"], srv["attn_pages_live"]) == (1056, 452)
    assert ("paged attention pages: 452 hold a key a slot attends of 1056 "
            "its items copied (0.428)") in obs_report.format_report(report)
    assert srv["decode_calls"] == 9
    assert srv["steps_ahead_share"] == pytest.approx(8 / 9)
    assert srv["discarded_tokens"] == 2 and srv["step_new_tokens"] == 36
    text = obs_report.format_report(report)
    assert "step phases (mean ms, host):" in text and "decode_wait" in text
    assert ("decode dispatched ahead of the read in 88.9% of 9 decoding "
            "step(s); 2 slot-step(s) decoded and thrown away "
            "(5.26% of 38)") in text
    # a journal from before the counters prints no such line
    for r in recs:
        r.pop("ahead", None)
        r.pop("attn_grid_items", None)
        r.pop("attn_grid_dense", None)
        r.pop("attn_pages_copied", None)
        r.pop("attn_pages_live", None)
    jp.write_text("".join(json.dumps(r) + "\n" for r in recs))
    text = obs_report.format_report(obs_report.generate(str(jp)))
    assert "dispatched ahead" not in text
    assert "paged attention grid" not in text
    assert "paged attention pages" not in text
