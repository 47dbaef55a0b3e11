"""The one phase primitive (obs.journal.phase) and what rides on it: the
phases of ``ServeEngine.step`` on the ``serve.step`` event and on the
profiler's timeline, the Trainer's goodput buckets on the same timeline,
the process's compile counter, and the names of the serving programs.
CPU only: what a chip's trace shows of these names is a chip run's to say.
"""

import gc
import glob
import json
import os
import re
import time

import jax
import jax.numpy as jnp
import optax
import pytest

import torch_automatic_distributed_neural_network_tpu as tad
from torch_automatic_distributed_neural_network_tpu.inference.serve import (
    ServeEngine,
)
from torch_automatic_distributed_neural_network_tpu.inference.serve.engine import (
    PHASES,
)
from torch_automatic_distributed_neural_network_tpu.models import GPT2, MLP
from torch_automatic_distributed_neural_network_tpu.obs import (
    GoodputMeter,
    report as obs_report,
)
from torch_automatic_distributed_neural_network_tpu.obs import (
    journal as obs_journal,
)
from torch_automatic_distributed_neural_network_tpu.obs.journal import (
    Journal,
    compile_counter,
    phase,
)
from torch_automatic_distributed_neural_network_tpu.training import (
    softmax_xent_loss,
)

from serve_by_hand import as_two_calls, chunk_alone

VOCAB = 128


# -- the primitive -----------------------------------------------------------


def test_phase_adds_its_seconds_under_the_key_and_accumulates():
    acc = {}
    for _ in range(3):
        with phase(acc, "upload", "serve.decode_upload", step=7):
            pass
    with phase(acc, "wait", "serve.decode_wait"):
        pass
    assert set(acc) == {"upload", "wait"}
    assert acc["upload"] >= 0.0 and acc["wait"] >= 0.0


def test_phase_records_the_time_of_a_block_that_raises():
    acc = {}
    with pytest.raises(KeyError):
        with phase(acc, "commit", "serve.prefill_commit"):
            raise KeyError("x")
    assert acc["commit"] >= 0.0


def test_phase_writes_no_journal_record():
    j = Journal(None, host0_only=False)
    n = len(j.records)
    with obs_journal.as_default(j):
        with phase({}, "evict", "serve.evict"):
            pass
    assert len(j.records) == n


def test_goodput_measure_is_a_phase_and_knows_its_buckets():
    m = GoodputMeter()
    with m.measure("input_stall"):
        pass
    assert isinstance(m.measure("step"), phase)
    assert m.seconds["input_stall"] >= 0.0
    with pytest.raises(ValueError, match="unknown goodput bucket"):
        m.measure("lunch")
    s = m.summary(total_wall_s=1.0)
    assert sum(s["fractions"].values()) == pytest.approx(1.0)


def test_compile_counter_is_one_per_process_and_counts_new_programs():
    c = compile_counter()
    assert compile_counter() is c
    f = jax.jit(lambda x: x * 3 + 1)
    n, secs = c.n, c.backend_s
    f(jnp.ones((5,)))
    assert c.n > n and c.backend_s > secs
    n = c.n
    f(jnp.ones((5,)))  # the same shape again: nothing to build
    assert c.n == n


def test_compile_counter_hears_a_load_by_part_and_nothing_on_a_second_call(
        tmp_path):
    """A fresh ``jax.jit`` call moves the trace, the lowering and the
    backend (a persistent-cache miss reads nothing); the same call again
    moves none of the four; the same program from another function object
    is traced and lowered anew and then READ from the persistent cache, a
    read that lies inside the backend's time.  ``load_s`` is the three
    without the read, and no more than the call took."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    c = compile_counter()
    prev = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir", "jax_enable_compilation_cache",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes")}
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    cc.reset_cache()

    x = jnp.ones((7, 7))  # (made here: an eager op is a program too)

    def timed(fn):
        before, t0 = c.loads(), time.monotonic()
        fn(x)
        return c.loads(before), time.monotonic() - t0

    try:
        f = jax.jit(lambda x: jnp.tanh(x @ x) * 52.0)
        miss, miss_s = timed(f)
        again, _ = timed(f)
        hit, hit_s = timed(jax.jit(lambda x: jnp.tanh(x @ x) * 52.0))
    finally:
        for k, v in prev.items():
            jax.config.update(k, v)
        cc.reset_cache()
    assert miss["n"] == 1 == hit["n"] and again["n"] == 0
    assert all(miss[k] > 0 for k in ("trace_s", "lower_s", "backend_s"))
    assert miss["cache_read_s"] == 0
    assert not any(again.values())
    assert all(hit[k] > 0 for k in ("trace_s", "lower_s", "cache_read_s"))
    assert hit["cache_read_s"] <= hit["backend_s"]
    for load, call_s in ((miss, miss_s), (hit, hit_s)):
        assert load["load_s"] == pytest.approx(
            load["trace_s"] + load["lower_s"] + load["backend_s"])
        assert load["load_s"] <= call_s


def test_a_nested_jit_loads_in_no_more_than_its_call():
    """A jitted function that calls jitted functions is traced in traces
    nested in its own, and JAX announces each as it begins (the counter's
    depth rests on that): the parts count the outermost alone, so their sum
    is no more than the call took.  A JAX that announces nothing would sum
    every nested trace beside the one that holds it and fail here."""
    c = compile_counter()
    heard = []

    def on(event, dur_s, **_kw):
        if event.endswith("jaxpr_trace_duration"):
            heard.append(dur_s)

    @jax.jit
    def inner(x):
        for _ in range(40):
            x = jnp.tanh(x @ x) + jnp.linalg.norm(x)
        return x

    @jax.jit
    def outer(x):
        for _ in range(3):
            x = inner(x) * 0.5 + jax.nn.softmax(x)
        return x

    x = jnp.ones((9, 9))
    jax.monitoring.register_event_duration_secs_listener(on)
    try:
        before, t0 = c.loads(), time.monotonic()
        outer(x)
        load, call_s = c.loads(before), time.monotonic() - t0
    finally:
        jax.monitoring.unregister_event_duration_listener(on)
    assert load["n"] == 1 and 0 < load["load_s"] <= call_s
    # the traces nested: the outermost holds the others' seconds, and the
    # counter took it alone
    assert len(heard) > 3 and load["trace_s"] == pytest.approx(max(heard))
    assert sum(heard) > load["trace_s"]


def test_two_threads_loads_are_both_counted():
    """The depth is a thread's own: an interval of one thread open round
    another thread's does not make that one an inner interval."""
    from concurrent.futures import ThreadPoolExecutor

    from torch_automatic_distributed_neural_network_tpu.obs.journal import (
        CompileCounter,
    )

    c = CompileCounter()
    trace, backend = (k for k, v in c.PARTS.items()
                      if v in ("trace_s", "backend_s"))
    with ThreadPoolExecutor(1) as a, ThreadPoolExecutor(1) as b:
        a.submit(c._on_scalar, trace, 0.0).result()
        b.submit(c._on_scalar, trace, 0.0).result()
        b.submit(c._on_scalar, trace, 0.0).result()  # nested, in b
        b.submit(c._on, trace, 0.25).result()
        b.submit(c._on, trace, 1.0).result()
        a.submit(c._on, trace, 2.0).result()
        a.submit(c._on_scalar, backend, 0.0).result()
        a.submit(c._on, backend, 4.0).result()
    assert c.loads() == {"n": 1, "trace_s": 3.0, "lower_s": 0.0,
                         "backend_s": 4.0, "cache_read_s": 0.0,
                         "load_s": 7.0}


def test_the_second_annotation_api_is_gone():
    from torch_automatic_distributed_neural_network_tpu.utils import (
        profiling,
    )

    assert not hasattr(profiling, "trace")
    assert not hasattr(profiling, "annotate")
    assert callable(profiling.compiled_cost)


# -- a tiny engine: events, names, compiles ----------------------------------


# a prompt's first token stays on the device and comes with the next read:
# only an engine that reads before it dispatches (speculative) waits for it
READER_FIRST = {"prefill_first_token"}


def _engine(journal, block_size=8):
    model = GPT2("test", vocab_size=VOCAB, max_seq_len=64,
                 dtype=jnp.float32, remat=False)
    variables = model.init(jax.random.key(1), jnp.ones((1, 12), jnp.int32))
    return ServeEngine(model, variables, n_slots=2, max_len=64,
                       block_size=block_size, prefill_chunk=8,
                       journal=journal, export_cache=False)


def _prompt(n):
    return [1 + (3 * i) % (VOCAB - 1) for i in range(n)]


@pytest.fixture(scope="module")
def served():
    """One engine under a validating journal: prompts of 12 and 5 tokens
    (the first's two chunks run alone, the second's with the first's decode
    rows: one program either way), then (alone, so that each has steps of
    its own) 12 again and a first 20.  Returns (engine, journal, records
    by step)."""
    j = Journal(None, validate=True, host0_only=False)
    eng = _engine(j)
    for n in (12, 5):
        eng.submit(_prompt(n), max_new_tokens=4)
    eng.run()
    marks = {}
    for tag, n in (("repeat", 12), ("new", 20)):
        start = len(j.named("serve.step"))
        eng.submit(_prompt(n), max_new_tokens=3)
        eng.run()
        marks[tag] = j.named("serve.step")[start:]
    return eng, j, marks


def test_serve_step_events_carry_phases_under_the_schema(served):
    _eng, j, _ = served
    steps = j.named("serve.step")  # each validated when it was written
    assert len(steps) >= 8
    for s in steps:
        assert set(s["phases"]) <= set(PHASES)
        assert all(v >= 0.0 for v in s["phases"].values())
        assert sum(s["phases"].values()) <= s["step_s"]
        assert s["compiles"] >= 0 and s["n_prefill_chunks"] >= 0
        # fields that were there keep their meaning
        assert s["decode_s"] <= s["step_s"] and s["prefill_s"] <= s["step_s"]
    seen = set().union(*(s["phases"] for s in steps))
    assert seen == set(PHASES) - READER_FIRST
    assert [s["t_end"] for s in steps] == sorted(s["t_end"] for s in steps)


def test_n_prefill_chunks_counts_the_chunks_the_reads_account_for(served):
    """A chunk has no record of its own: a call's ``n_prefill_chunks`` is
    what it dispatched, and the chunk's rows and position are on the event
    of the call that waits for it (``read``)."""
    eng, j, _ = served
    steps = j.named("serve.step")
    assert not [r for r in j.records if r["name"].endswith("prefill_chunk")]
    prompts = [r.n_prompt for r in eng.finished]
    assert prompts == [12, 5, 12, 20]
    chunks = sum(-(-n // 8) for n in prompts)
    assert sum(s["n_prefill_chunks"] for s in steps) == chunks
    assert sum(r.prefill_chunks for r in eng.finished) == chunks
    reads = [s["read"] for s in steps if "read" in s]
    assert sum(r["chunk_rows"] for r in reads) == sum(prompts)
    assert all(r.prefill_compute_s > 0 for r in eng.finished)


READ_KEYS = {"programs", "rows", "ctx_keys", "chunk_rows", "chunk_pos"}
WAITS = {"decode_wait", "prefill_first_token"}


def test_a_call_that_waited_says_what_for_and_no_other_does(served):
    _eng, j, _ = served
    steps = j.named("serve.step")
    for s in steps:
        assert ("read" in s) == bool(WAITS & set(s["phases"])), s["step"]
        if "read" in s:
            r = s["read"]
            assert set(r) == READ_KEYS
            assert all(type(v) is int and v >= 0 for v in r.values())
            assert r["programs"] >= 1
    # the first prompt's two chunks went out with nothing to read; the
    # first read covers both
    first = next(s["read"] for s in steps if "read" in s)
    assert first == {"programs": 2, "rows": 0, "ctx_keys": 0,
                     "chunk_rows": 12, "chunk_pos": 8}


def _chunky_run(traffic=((21, 9), (12, 6), (27, 4)), fused=True,
                **engine_kw):
    """Prompts (length, new tokens) over two slots, so that chunks ride with
    decode rows (not ``fused``: each goes out in its own place, as where an
    engine has no program for both): (engine, the ``serve.step`` events,
    the calls the three programs were dispatched in: a list a program)."""
    j = Journal(None, validate=True, host0_only=False)
    model = GPT2("test", vocab_size=VOCAB, max_seq_len=64,
                 dtype=jnp.float32, remat=False)
    variables = model.init(jax.random.key(1), jnp.ones((1, 12), jnp.int32))
    eng = ServeEngine(model, variables, n_slots=2, max_len=64, block_size=8,
                      prefill_chunk=8, journal=j, export_cache=False,
                      **engine_kw)
    if not fused:
        as_two_calls(eng)
    sent = {"_step_fn": [], "_prefill_fn": [], "_fused_fn": []}
    for name, calls in sent.items():
        fn = getattr(eng, name)
        if fn is None:
            continue

        def counting(*a, _fn=fn, _calls=calls):
            _calls.append(eng._step_count + 1)
            return _fn(*a)

        setattr(eng, name, counting)
    for n, new in traffic:
        eng.submit(_prompt(n), max_new_tokens=new)
    eng.run()
    return eng, j.named("serve.step"), sent


def test_reads_add_up_to_what_was_dispatched():
    eng, steps, sent = _chunky_run()
    reads = [s["read"] for s in steps if "read" in s]
    n_programs = sum(len(v) for v in sent.values())
    assert sent["_fused_fn"] and sent["_step_fn"] and not sent["_prefill_fn"]
    assert sum(r["programs"] for r in reads) == n_programs
    assert sum(r["chunk_rows"] for r in reads) == 21 + 12 + 27
    assert sum(s["n_prefill_chunks"] for s in steps) == 3 + 2 + 4
    # the rows a read covers are the tokens and thrown-away slot-steps it
    # brought, less the first tokens of prompts that ended
    assert sum(r["rows"] for r in reads) == sum(
        s["new_tokens"] + s["discarded_tokens"] for s in steps) - 3
    # steady state: one program a read, and a decode row has a context
    steady = [r for r in reads if r["programs"] == 1]
    assert len(steady) > len(reads) // 2
    assert all(r["ctx_keys"] >= r["rows"] * 5 for r in steady if r["rows"])
    assert any(r["chunk_rows"] and r["rows"] for r in steady)  # a fused call
    assert any(not r["chunk_rows"] for r in steady)  # a decode-only one


def test_a_requests_chunk_positions_rise_by_the_chunk():
    """``chunk_pos`` is the chunk's first position: of a prompt of 27 that
    is prefilled while the other slot decodes (one program a read, each
    with the decode row beside the chunk) 0, 8, 16 and 24, the last with
    the 3 rows that are left."""
    _eng, steps, _ = _chunky_run(traffic=((12, 30), (27, 4)))
    chunks = [(s["read"]["chunk_pos"], s["read"]["chunk_rows"],
               s["read"]["rows"]) for s in steps
              if s.get("read", {}).get("programs") == 1
              and s["read"]["chunk_rows"]]
    assert chunks == [(0, 8, 1), (8, 8, 1), (16, 8, 1), (24, 3, 1)]


def test_a_chunk_in_its_own_place_is_on_the_read_that_waits_for_it():
    """A chunk that goes out behind an unread step is not what that step's
    read waits for: it is on the NEXT call's read, with that call's step
    (two programs: no steady-state call).  A prompt's last chunk samples
    its first token into the unread output, so the same call's read waits
    for it, and for the chunk before it."""
    eng, steps, sent = _chunky_run(traffic=((12, 30), (27, 4)), fused=False)
    assert eng._ahead == 1 and len(sent["_prefill_fn"]) == 2 + 4
    reads = {s["step"]: s["read"] for s in steps if "read" in s}
    assert sum(r["programs"] for r in reads.values()) == sum(
        len(v) for v in sent.values())
    assert sum(r["chunk_rows"] for r in reads.values()) == 12 + 27
    at = sent["_prefill_fn"][2:]  # the calls the second prompt's went out in
    assert at == list(range(at[0], at[0] + 4))
    got = {k: (r["programs"], r["chunk_pos"], r["chunk_rows"], r["rows"])
           for k, r in reads.items() if at[0] <= k <= at[3] + 1}
    assert got == {at[0]: (1, 0, 0, 1),        # the step before, alone
                   at[1]: (2, 0, 8, 1), at[2]: (2, 8, 8, 1),
                   at[3]: (3, 24, 8 + 3, 1),   # chunk, step, last chunk
                   at[3] + 1: (1, 0, 0, 2)}    # both slots decode


def test_an_engine_that_reads_first_reads_its_own_call():
    """At dispatch depth 0 (speculative) a call waits for the program it
    dispatched itself: ``read`` and ``n_prefill_chunks`` then describe the
    same call, and the last chunk's first token is a read of its own that
    the call's one ``read`` takes in."""
    eng, steps, sent = _chunky_run(speculative=2)
    assert eng._ahead == 0 and not sent["_fused_fn"]
    reads = [s["read"] for s in steps if "read" in s]
    assert sum(r["programs"] for r in reads) == sum(
        len(v) for v in sent.values())
    assert sum(r["chunk_rows"] for r in reads) == 21 + 12 + 27
    for s in steps:
        if "read" not in s:
            assert not WAITS & set(s["phases"])
            continue
        decoded = s["step"] in sent["_step_fn"]
        assert (s["read"]["rows"] > 0) == decoded, s["step"]
    both = [s for s in steps if {"decode_wait", "prefill_first_token"}
            <= set(s["phases"])]
    assert both and all(s["read"]["chunk_rows"] and s["read"]["rows"]
                        for s in both)


def test_gc_counter_is_one_per_process_and_counts_by_generation():
    from torch_automatic_distributed_neural_network_tpu.obs.journal import (
        gc_counter,
    )

    c = gc_counter()
    assert gc_counter() is c and gc.callbacks.count(c._on) == 1
    passes, secs = list(c.passes), c.total_s
    gc.collect()
    assert c.passes[2] == passes[2] + 1 and c.passes[:2] == passes[:2]
    assert c.total_s > secs
    gc.collect(0)
    assert c.passes[0] == passes[0] + 1 and c.passes[2] == passes[2] + 1


def test_a_collection_inside_a_step_is_on_that_steps_event():
    """``gc_s`` / ``gc_full`` are the collector's seconds and full passes
    INSIDE the call, whichever phase they fell in: a pass forced in one
    call's admission is on that event and on neither neighbour's (with the
    collector's own schedule switched off, nothing else runs)."""
    j = Journal(None, validate=True, host0_only=False)
    eng = _engine(j)
    eng.submit(_prompt(12), max_new_tokens=8)
    for _ in range(3):
        eng.step()  # past the steps that compile (they collect and freeze)
    admit, calls = eng.scheduler.admit, []

    def collecting():
        calls.append(eng._step_count + 1)
        if len(calls) == 2:
            gc.collect()
        return admit()

    eng.scheduler.admit = collecting
    was = gc.isenabled()
    gc.disable()
    try:
        for _ in range(3):
            eng.step()
    finally:
        if was:
            gc.enable()
        gc.unfreeze()
    before, at, after = j.named("serve.step")[3:6]
    assert at["step"] == calls[1]
    assert at["gc_full"] == 1 and at["gc_s"] > 0
    assert at["gc_s"] <= at["phases"]["admit"] <= at["step_s"]
    for s in (before, after):
        assert s["gc_full"] == 0 and s["gc_s"] == 0.0


def test_token_stamps_and_t_end_share_the_schedulers_clock(served):
    eng, j, _ = served
    last = j.named("serve.step")[-1]["t_end"]
    walls = [w for r in eng.finished for w in r.token_walls]
    assert walls and max(walls) <= last


def test_first_steps_report_compiles_and_no_prompt_length_after(served):
    """The chunk and the step have one shape each and a prefill lands in
    the pages it is read from, so neither a repeated prompt length nor one
    never seen compiles (a commit a distinct length once did)."""
    _eng, j, marks = served
    assert sum(s["compiles"] for s in marks["repeat"]) == 0
    assert sum(s["compiles"] for s in marks["new"]) == 0
    assert j.named("serve.step")[0]["compiles"] > 0
    events = [r for r in j.named("compile") if r.get("fn") == "serve"]
    assert events and all(r["dur_s"] > 0 for r in events)
    # one compile event for each program a step loaded, none for the step
    assert [r["program"] for r in events] == list(_eng.programs)
    assert all(s["compiles"] for s in j.named("serve.step")
               if s["step"] in {p["at_step"]
                                for p in _eng.programs.values()})


def test_decode_steps_carry_the_attention_grid_they_ran(monkeypatch,
                                                        small_items):
    """``attn_grid_items`` / ``attn_grid_dense`` come back with a step's
    tokens (one call after its dispatch): the (slot, first page) items the
    step's paged calls ran, which the host can reckon from the contexts
    and flags it packed, and the ``slots x items`` of a dense grid, over
    the model's two layers; beside them ``attn_pages_copied`` (items of
    running slots x the 8 pages each copies) and ``attn_pages_live`` (the
    table entries among them that hold a key).  Pages of 2 keys: 4 items
    of 16 in 64."""
    from torch_automatic_distributed_neural_network_tpu.inference.serve import (
        programs,
    )

    packed, pack_step = [], programs.pack_step

    def recording(tables, ctx_lens, tok, source, adapter_ids):
        packed.append((ctx_lens.copy(), source.copy()))
        return pack_step(tables, ctx_lens, tok, source, adapter_ids)

    monkeypatch.setattr(programs, "pack_step", recording)
    j = Journal(None, validate=True, host0_only=False)
    eng = _engine(j, block_size=2)
    for n, new in ((5, 30), (21, 12), (12, 3)):
        eng.submit(_prompt(n), max_new_tokens=new)
    eng.run()
    steps = j.named("serve.step")
    counted = [s for s in steps if "attn_grid_items" in s]
    assert len(counted) == len(packed) > 20
    # a call that read a step's tokens has that step's counters
    assert all("attn_grid_items" in s for s in steps if s["new_tokens"]
               and not s["n_prefill_chunks"])
    reckoned = [2 * sum(ctx // 16 + 1 if flag else 1
                        for ctx, flag in zip(ctx_lens, source))
                for ctx_lens, source in packed]
    assert [s["attn_grid_items"] for s in counted] == reckoned
    assert {s["attn_grid_dense"] for s in counted} == {2 * 2 * 4}
    assert all(s["attn_grid_items"] <= s["attn_grid_dense"] for s in counted)
    assert min(reckoned) == 4 < max(reckoned)
    assert [(s["attn_pages_copied"], s["attn_pages_live"])
            for s in counted] == [
        (2 * 8 * sum(ctx // 16 + 1 for ctx, flag in zip(ctx_lens, source)
                     if flag),
         2 * sum(ctx // 2 + 1 for ctx, flag in zip(ctx_lens, source) if flag))
        for ctx_lens, source in packed]
    assert all(s["attn_pages_live"] <= s["attn_pages_copied"]
               for s in counted)
    assert not any(k.startswith("moe_") for s in steps for k in s)


def test_a_step_that_compiled_freezes_the_heap():
    """What building a program leaves on the heap lives as long as the
    engine; a full pass of the collector over it is a 100 ms stall inside
    some later step.  A step that compiled collects and freezes, and only
    such a step: the passes after it walk the young objects alone."""
    gc.unfreeze()
    j = Journal(None, validate=True, host0_only=False)
    eng = _engine(j)
    eng.submit(_prompt(12), max_new_tokens=6)
    eng.step()
    assert j.named("serve.step")[0]["compiles"] > 0
    frozen = gc.get_freeze_count()
    assert frozen > 10_000 and len(gc.get_objects()) < frozen // 10
    eng.run()
    quiet = [s for s in j.named("serve.step")[-3:]]
    assert quiet and not any(s["compiles"] for s in quiet)
    gc.unfreeze()  # the count at the last compiling step is what matters
    assert gc.get_freeze_count() == 0


def test_serving_programs_are_named(served):
    eng, _, _ = served
    assert eng.compiled_decode_text().startswith(
        "HloModule jit_serve_decode_step")
    # the chunk that carries a step's decode rows is read under the chunk's
    # name: it is a chunk with more rows
    text = eng._fused_fn.lower(*eng._abstract_fused_args()).as_text()
    assert "module @jit_serve_prefill_chunk" in text.splitlines()[0]


# -- the parts of a call, as named scopes in the three programs --------------


def _kind_models() -> dict:
    from test_joyai_flash_reference import KEYS as LATENT
    from test_serve_fused import LINEAR, MIXED

    from torch_automatic_distributed_neural_network_tpu.models.transformer_core import (
        DecoderLM,
        TransformerConfig,
    )

    made = {"full_attention": (GPT2("test", vocab_size=VOCAB, max_seq_len=64,
                                    dtype=jnp.float32, remat=False),
                               dict(block_size=8, prefill_chunk=8))}
    for kind, keys, kw in (
            ("sliding_attention", MIXED, dict(block_size=2, prefill_chunk=4)),
            ("linear_attention", LINEAR, dict(block_size=4, prefill_chunk=8)),
            ("latent_attention", LATENT, dict(block_size=4, prefill_chunk=8))):
        made[kind] = DecoderLM(TransformerConfig(
            **keys, remat=False, dtype=jnp.float32)), kw
    return made


@pytest.fixture(scope="module")
def kind_engines():
    """A tiny engine a kind of layer (a GPT-2 block; sliding and full layers
    with held experts; linear and full layers; latent layers with held
    experts), built and never stepped: nothing compiles."""
    out = {}
    for kind, (model, kw) in _kind_models().items():
        variables = jax.eval_shape(model.init, jax.random.key(1),
                                   jnp.ones((1, 8), jnp.int32))
        variables = jax.tree.map(
            lambda a: jnp.zeros(a.shape, a.dtype), variables)
        out[kind] = ServeEngine(
            model, variables, n_slots=2, max_len=64, journal=Journal(
                None, host0_only=False), cache_dtype=jnp.float32,
            export_cache=False, **kw)
    return out


# (the chunk alone is no program of these engines: as a speculative or a
# tenant engine holds it)
PROGRAM_ARGS = {
    "decode_step": (lambda e: e._step_fn, "_abstract_decode_args"),
    "prefill_chunk": (chunk_alone, "_abstract_prefill_args"),
    "chunk_and_step": (lambda e: e._fused_fn, "_abstract_fused_args")}


@pytest.mark.parametrize("program", sorted(PROGRAM_ARGS))
@pytest.mark.parametrize("kind", ["full_attention", "sliding_attention",
                                  "linear_attention", "latent_attention"])
def test_a_programs_parts_are_scoped_by_name(kind_engines, kind, program):
    """``programs.SCOPES`` are a contract like the kernels' names: each part
    of a call is a component of its ops' ``op_name``.  Lowered only (the
    names are in the locations), at tiny sizes."""
    from torch_automatic_distributed_neural_network_tpu.inference.serve import (
        programs,
    )

    eng = kind_engines[kind]
    fn, args = PROGRAM_ARGS[program]
    text = fn(eng).lower(*getattr(eng, args)()).as_text(
        debug_info=True)
    # a component of an op's name: ``"jit(..)/tadnn.head/dot_general"``, and
    # inside a layer's own function ``"tadnn.mix_in/LayerNorm/sub"``
    found = set(re.findall(r"(?<=[/\"])tadnn\.[a-z_]+(?=[/\"])", text))
    want = set(programs.SCOPES)
    if not eng.cfg.n_expert_layers:
        want.discard("tadnn.ffn_expert")
    if program == "decode_step":
        want.discard("tadnn.attend_chunk")
    if program == "prefill_chunk":
        want.discard("tadnn.attend_step")
    assert found == want, (kind, program)


def test_report_renders_the_step_phases(served, tmp_path):
    eng, j, _ = served
    path = tmp_path / "journal.jsonl"
    with Journal(str(path), host0_only=False) as out:
        for r in j.records[1:]:
            out._write(r)
    rep = obs_report.generate(str(path))
    srv = rep["serving"]
    assert set(srv["step_phase_mean_s"]) == set(PHASES) - READER_FIRST
    assert srv["mean_step_self_s"] >= 0.0
    assert srv["steps_that_compiled"] >= 1
    text = obs_report.format_report(rep)
    assert "step phases (mean ms, host):" in text
    assert "decode_wait" in text
    assert "XLA built programs in this process during" in text
    # start-up, from the newest serve.engine: the build and a line a program
    assert "start-up: engine built in" in text and "(weights " in text
    for name, p in eng.programs.items():
        assert re.search(rf"{name} (COMPILED|loaded) at step {p['at_step']} "
                         rf"in \d+\.\d\d s \(trace ", text)
    assert rep["compile"]["count"] >= srv["steps_that_compiled"]


def test_report_tells_steps_with_a_prefill_chunk_from_decode_only(
        served, tmp_path):
    """``n_prefill_chunks`` is what splits the decoding steps into the
    ITL's two kinds; the report reads it and nothing else for that."""
    _eng, j, _ = served
    steps = [s for s in j.named("serve.step") if s["decode_s"]]
    chunked = [s for s in steps if s["n_prefill_chunks"]]
    assert chunked and len(chunked) < len(steps)
    path = tmp_path / "journal.jsonl"
    with Journal(str(path), host0_only=False) as out:
        for r in j.records[1:]:
            out._write(r)
    rep = obs_report.generate(str(path))
    srv = rep["serving"]
    assert srv["decode_steps_with_chunk"] == len(chunked)
    assert srv["decode_steps"] == len(steps)
    assert srv["mean_step_with_chunk_s"] == pytest.approx(
        sum(s["step_s"] for s in chunked) / len(chunked))
    assert (f"{len(chunked)} of {len(steps)} decoding step(s) also ran a "
            "prefill chunk") in obs_report.format_report(rep)


def test_report_tells_calls_by_what_they_waited_for(served, tmp_path):
    """A step is read one call late, so a call's time is that of the
    program it WAITED for: ``read`` splits the calls, not what the call
    dispatched."""
    _eng, j, _ = served
    path = tmp_path / "journal.jsonl"
    with Journal(str(path), host0_only=False) as out:
        for r in j.records[1:]:
            out._write(r)
    rep = obs_report.generate(str(path))
    srv = rep["serving"]
    steady = [s for s in j.named("serve.step")
              if s.get("read", {}).get("programs") == 1]
    chunk = [s for s in steady if s["read"]["chunk_rows"]]
    assert chunk and len(chunk) < len(steady)
    assert srv["calls_by_read"]["chunk"][1] == len(chunk)
    assert srv["calls_by_read"]["decode"][1] == len(steady) - len(chunk)
    assert "chunk_deep" not in srv["calls_by_read"]  # 64 positions in all
    assert srv["n_prefill_chunks"] == sum(
        s["n_prefill_chunks"] for s in j.named("serve.step"))
    text = obs_report.format_report(rep)
    assert "a call by the one program it waited for (median ms): a chunk" \
        in text
    assert f"prefill chunks x{srv['n_prefill_chunks']} (C=8)" in text


def _journal_of(tmp_path, steps) -> str:
    path = tmp_path / "journal.jsonl"
    with open(path, "w") as f:
        for r in steps:
            f.write(json.dumps(r) + "\n")
    return str(path)


def _decode_call(i, step_s, rows=2, keys_a_row=45, **more):
    return {"kind": "event", "name": "serve.step", "t": 0.02 * i,
            "step": i, "n_active": 2, "n_queued": 0, "new_tokens": rows,
            "occupancy": 1.0, "free_blocks": 3, "decode_s": 0.9 * step_s,
            "step_s": step_s, "phases": {"decode_wait": 0.8 * step_s},
            "gc_s": 0.0, "gc_full": 0, "n_prefill_chunks": 0,
            "read": {"programs": 1, "rows": rows,
                     "ctx_keys": rows * keys_a_row, "chunk_rows": 0,
                     "chunk_pos": 0}, **more}


def test_report_reads_a_decode_call_by_the_keys_a_row_read(tmp_path):
    """``read.ctx_keys`` over ``read.rows``: a decode step's attention grows
    with the keys a row reads, so the report gives the decode-only calls'
    median in doublings of them (one group under 1,024)."""
    steps = ([_decode_call(i, 0.010 + 1e-4 * i, keys_a_row=300 + 100 * i)
              for i in range(1, 6)]
             + [_decode_call(i, 0.014 + 1e-4 * i, rows=4, keys_a_row=2100 + i)
                for i in range(6, 9)]
             + [_decode_call(9, 0.0175, rows=3, keys_a_row=8192),
                _decode_call(10, 0.0176, rows=3, keys_a_row=16383)])
    rep = obs_report.generate(_journal_of(tmp_path, steps))
    by = rep["serving"]["decode_calls_by_keys"]
    assert list(by) == ["0-1023", "2048-4095", "8192-16383"]
    assert [n for _, n in by.values()] == [5, 3, 2]
    assert by["0-1023"][0] == pytest.approx(0.0103)
    assert by["2048-4095"][0] == pytest.approx(0.0147)
    assert rep["serving"]["calls_by_read"]["decode"][1] == 10
    assert ("a decode step alone by the keys a row read (median ms): "
            "0-1023 10.30 (5 calls), 2048-4095 14.70 (3 calls), "
            "8192-16383 17.55 (2 calls)") in obs_report.format_report(rep)
    # one group says nothing that the median by kind does not
    rep = obs_report.generate(_journal_of(tmp_path, steps[:5]))
    assert rep["serving"].get("decode_calls_by_keys") is None
    assert "by the keys a row read" not in obs_report.format_report(rep)


def test_report_on_a_journal_from_before_read_gives_no_share_of_rows(
        tmp_path):
    """An engine of PR 33-35 journals ``fused`` and no ``read``: the rows
    read are not known, so the share of them that rode is left out (and is
    not a count over nothing)."""
    steps = [_decode_call(i, 0.012, fused=1, fused_decode_rows=2,
                          n_prefill_chunks=1) for i in range(1, 6)]
    new = obs_report.generate(_journal_of(tmp_path, steps))["serving"]
    assert new["fused_share_of_decode_rows"] == pytest.approx(1.0)
    for s in steps:
        del s["read"], s["gc_s"], s["gc_full"]
    rep = obs_report.generate(_journal_of(tmp_path, steps))
    old = rep["serving"]
    assert old["fused_steps"] == 5 and old["fused_share_of_chunk_steps"] == 1
    assert old.get("fused_share_of_decode_rows") is None
    assert old.get("calls_by_read") is None and old.get("stalls") is None
    text = obs_report.format_report(rep)
    assert "100.0% of the steps with a chunk" in text
    assert "of the decode rows" not in text


def test_report_lists_a_stall_with_the_collector_beside_it(tmp_path):
    steps = [{"kind": "event", "name": "serve.step", "t": 0.02 * i,
              "step": i, "n_active": 2, "n_queued": 0, "new_tokens": 2,
              "occupancy": 1.0, "free_blocks": 3, "decode_s": 0.011,
              "step_s": 0.012, "phases": {"decode_wait": 0.01},
              "gc_s": 0.0, "gc_full": 0,
              "read": {"programs": 1, "rows": 2, "ctx_keys": 90,
                       "chunk_rows": 0, "chunk_pos": 0}}
             for i in range(1, 12)]
    steps[6].update(step_s=1.512, gc_s=1.4, gc_full=1,
                    phases={"decode_wait": 0.01, "emit": 1.45})
    # a read of many programs is long, and no stall
    steps[8].update(step_s=0.4)
    steps[8]["read"] = {**steps[8]["read"], "programs": 9}
    rep = obs_report.generate(_journal_of(tmp_path, steps))
    st = rep["serving"]["stalls"]
    assert st["n"] == 1 and st["lost_s"] == pytest.approx(1.5)
    assert st["gc_s"] == 1.4 and st["gc_full"] == 1
    assert st["worst"]["step"] == 7 and st["worst"]["phase"] == "emit"
    text = obs_report.format_report(rep)
    assert ("1 stalled call(s) lost 1500.0 ms; Python's collector ran "
            "1400.0 ms inside them (1 full pass(es)); the longest, step 7, "
            "took 1512.0 ms where its kind (decode) takes 12.00, most of "
            "it in emit") in text


# -- the timeline: a profiler capture on the CPU -----------------------------


def _annotations(logdir):
    """Every ``serve.*`` / ``train.*`` / ``gc.*`` annotation of a capture:
    (name, start_ns, end_ns, stats)."""
    from jax.profiler import ProfileData

    path = glob.glob(os.path.join(
        logdir, "plugins", "profile", "*", "*.xplane.pb"))[0]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(("serve.", "train.", "gc.")):
                    out.append((ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns,
                                dict(ev.stats)))
    return out


@pytest.fixture(scope="module")
def captured(served, tmp_path_factory):
    """A capture of a few engine steps and of a three-step Trainer.fit."""
    from torch_automatic_distributed_neural_network_tpu.data.synthetic import (
        SyntheticClassification,
    )
    from torch_automatic_distributed_neural_network_tpu.training import (
        Trainer,
        TrainerConfig,
    )

    eng = served[0]
    ad = tad.AutoDistribute(MLP(features=(32, 16, 10)),
                            optimizer=optax.sgd(0.1),
                            loss_fn=softmax_xent_loss, strategy="dp")
    trainer = Trainer(ad, TrainerConfig(steps=3, log_every=0,
                                        preflight=False))
    logdir = str(tmp_path_factory.mktemp("capture"))
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(logdir, profiler_options=options)
    try:
        eng.submit(_prompt(12), max_new_tokens=3)
        eng.run()
        gc.collect()  # a full pass: ``gc.full`` (the engine's counter)
        trainer.fit(SyntheticClassification(batch_size=16))
    finally:
        jax.profiler.stop_trace()
    return _annotations(logdir)


def test_phase_annotations_nest_inside_their_steps_annotation(captured):
    steps = {st["step"]: (s, e) for n, s, e, st in captured
             if n == "serve.step"}
    assert len(steps) >= 3
    inner = [(n, s, e, st) for n, s, e, st in captured
             if n.startswith("serve.") and n != "serve.step"]
    assert {n for n, *_ in inner} == {
        "serve." + p for p in set(PHASES) - READER_FIRST}
    for n, s, e, st in inner:
        lo, hi = steps[st["step"]]  # the same step number
        assert lo <= s <= e <= hi, (n, st)
    chunk = next(st for n, _, _, st in inner
                 if n == "serve.prefill_dispatch")
    assert {"rid", "pos", "step"} <= set(chunk)


def test_a_full_pass_of_the_collector_is_on_the_timeline(captured):
    """``gc_counter`` opens ``gc.full`` over a generation-2 pass, so that
    under a capture a stall of the collector's lies on the device trace's
    clock with the phases; it is no phase, carries no step, and is named
    for the process and not for who serves in it."""
    passes = [(s, e, st) for n, s, e, st in captured if n == "gc.full"]
    assert passes and all(e > s and "step" not in st for s, e, st in passes)


def test_trainer_buckets_and_dispatch_are_on_the_timeline(captured):
    names = [n for n, *_ in captured]
    assert names.count("train.step_dispatch") == 3
    assert sorted(st["step"] for n, _, _, st in captured
                  if n == "train.step_dispatch") == [0, 1, 2]
    # a bucket is on the timeline under its own name (no map of renames):
    # the first batch and two more
    assert names.count("train.input_stall") >= 3
    assert "train.input" not in names and "train.fence" not in names
    assert "train.compile" in names  # init before step 0
