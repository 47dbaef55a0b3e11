"""The one phase primitive (obs.journal.phase) and what rides on it: the
phases of ``ServeEngine.step`` on the ``serve.step`` event and on the
profiler's timeline, the Trainer's goodput buckets on the same timeline,
the process's compile counter, and the names of the serving programs.
CPU only: what a chip's trace shows of these names is a chip run's to say.
"""

import glob
import os

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
    n, secs = c.n, c.seconds
    f(jnp.ones((5,)))
    assert c.n > n and c.seconds > secs
    n = c.n
    f(jnp.ones((5,)))  # the same shape again: nothing to build
    assert c.n == n


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


def test_n_prefill_chunks_counts_the_steps_chunk_events(served):
    _eng, j, _ = served
    chunks = 0
    for r in j.records:
        if r["name"] == "serve.prefill_chunk":
            chunks += 1
        elif r["name"] == "serve.step":
            assert r["n_prefill_chunks"] == chunks, r["step"]
            chunks = 0


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
    # one compile event for each step that compiled, no more
    assert len(events) == sum(
        1 for s in j.named("serve.step") if s["compiles"])


def test_decode_steps_carry_the_attention_grid_they_ran(monkeypatch):
    """``attn_grid_items`` / ``attn_grid_dense`` come back with a step's
    tokens (one call after its dispatch): the live (slot, key group) items
    the step's paged calls ran, which the host can reckon from the contexts
    and flags it packed, and the ``slots x groups`` of a dense grid, over
    the model's two layers.  Pages of 2 keys: 4 groups of 16 in 64."""
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
    assert not any(k.startswith("moe_") for s in steps for k in s)


def test_a_step_that_compiled_freezes_the_heap():
    """What building a program leaves on the heap lives as long as the
    engine; a full pass of the collector over it is a 100 ms stall inside
    some later step.  A step that compiled collects and freezes, and only
    such a step: the passes after it walk the young objects alone."""
    import gc

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
    text = eng._prefill_fn.lower(*eng._abstract_prefill_args()).as_text()
    assert "module @jit_serve_prefill_chunk" in text.splitlines()[0]
    # the chunk that carries a step's decode rows is read under the chunk's
    # name: it is a chunk with more rows
    text = eng._fused_fn.lower(*eng._abstract_fused_args()).as_text()
    assert "module @jit_serve_prefill_chunk" in text.splitlines()[0]


def test_report_renders_the_step_phases(served, tmp_path):
    _eng, j, _ = served
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


@pytest.mark.parametrize("speculative", [0, 2])
def test_single_shot_prefill_is_not_timed_as_admit(speculative):
    """With ``prefill_chunk=None`` the forward is the ``prefill_dispatch``
    phase of the admitting step (the prompt lands in the request's pages as
    it runs: no commit).  Its first token is waited for apart only where
    the engine reads before it dispatches; otherwise it comes with the
    step's one read."""
    j = Journal(None, validate=True, host0_only=False)
    model = GPT2("test", vocab_size=VOCAB, max_seq_len=64,
                 dtype=jnp.float32, remat=False)
    variables = model.init(jax.random.key(1), jnp.ones((1, 12), jnp.int32))
    eng = ServeEngine(model, variables, n_slots=2, max_len=64,
                      block_size=8, prefill_chunk=None, journal=j,
                      speculative=speculative, export_cache=False)
    eng.submit(_prompt(9), max_new_tokens=3)
    eng.run()
    first = j.named("serve.step")[0]
    assert {"admit", "prefill_dispatch", "decode_wait"} <= set(
        first["phases"])
    assert ("prefill_first_token" in first["phases"]) == bool(speculative)
    # the forward compiles inside prefill_dispatch: admit is the
    # scheduler's bookkeeping and stays far below it
    assert first["compiles"] > 0
    assert first["phases"]["admit"] < first["phases"]["prefill_dispatch"]
    assert sum(first["phases"].values()) <= first["step_s"]
    assert first["n_prefill_chunks"] == 0 and first["prefill_s"] == 0.0


# -- the timeline: a profiler capture on the CPU -----------------------------


def _annotations(logdir):
    """Every ``serve.*`` / ``train.*`` annotation of a capture:
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
                if ev.name.startswith(("serve.", "train.")):
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


def test_trainer_buckets_and_dispatch_are_on_the_timeline(captured):
    names = [n for n, *_ in captured]
    assert names.count("train.step_dispatch") == 3
    assert sorted(st["step"] for n, _, _, st in captured
                  if n == "train.step_dispatch") == [0, 1, 2]
    assert names.count("train.input") >= 3  # the first batch and two more
    assert "train.compile" in names  # init before step 0
