"""A step's decode rows ride in its prefill chunk: ONE program for both
(``programs.chunk_and_step``) wherever a non-speculative engine without
tenants has a chunk to run and a slot decoding.

What may not change is what is served: the tokens of the chunk and the step
as two calls (here the same engine with the fused program taken away and
the chunk alone in its place, the path a speculative or a tenant engine
runs), for a GPT-2-shaped model, a model of sliding
and full layers with held experts, and one with ``linear_attention``
layers.  What moves: a prompt whose last chunk carried decode rows starts
decoding in the next call, with the first token that call's logits gave.
Float32 models and caches on the CPU, so that greedy tokens are equal to
the last one.
"""

from __future__ import annotations

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torch_automatic_distributed_neural_network_tpu.inference.serve import (
    ServeEngine,
)
from torch_automatic_distributed_neural_network_tpu.models.transformer_core import (
    DecoderLM,
    TransformerConfig,
)
from torch_automatic_distributed_neural_network_tpu.obs import schema
from torch_automatic_distributed_neural_network_tpu.obs.journal import Journal
from torch_automatic_distributed_neural_network_tpu.training.lora import (
    LoraSpec,
)

from serve_by_hand import as_two_calls
from test_serve import VOCAB, _f32_engine, _greedy, _model_and_vars, _prompts

# a model of sliding and full layers, rotary on the sliding ones, a dense
# FFN then held experts beside a shared one; and one of linear and full
# layers with the norms on the sublayers' outputs
MIXED = dict(
    vocab_size=96, d_model=64, n_layers=4, n_heads=8, n_kv_heads=2,
    head_size=16, d_ff=96, max_seq_len=64, norm="rmsnorm", norm_eps=1e-5,
    act="swiglu", pos="rope", sliding_window=8, tie_embeddings=False,
    layer_types=["sliding_attention"] * 3 + ["full_attention"],
    rope_layers="sliding", qk_norm=True, attn_gate=True, sandwich_norm=True,
    embed_scale=True, n_dense_layers=1, experts_published=16, experts_held=4,
    first_expert=4, experts_per_token=2, shared_experts=1, expert_d_ff=32,
    score_func="sigmoid", route_norm=True, route_scale=2.448)
LINEAR = dict(
    vocab_size=96, d_model=48, n_layers=4, n_heads=6, n_kv_heads=6,
    head_size=8, d_ff=80, max_seq_len=128, norm="rmsnorm", norm_eps=1e-6,
    act="swiglu", pos="rope", rope_layers="sliding", tie_embeddings=False,
    layer_types=["linear_attention"] * 3 + ["full_attention"],
    qk_norm=True, qk_norm_over="projection", sandwich_norm=True,
    pre_norm=False, linear_key_heads=6, linear_value_heads=6,
    linear_key_head_dim=8, linear_value_head_dim=16, linear_conv_kernel=4,
    linear_neg_eigval=True)
# family -> (engine keywords, prompt lengths): chunks of 8 (4 on the ring),
# prompts that end inside a chunk and one that fills its last, more
# requests than slots so that slots are reused at different depths
FAMILIES = {
    "gpt2": (dict(block_size=8, prefill_chunk=8), (5, 21, 12, 16, 30, 9)),
    "sliding_full_experts": (dict(block_size=2, prefill_chunk=4),
                             (5, 23, 14, 30, 3, 8)),
    "linear_attention": (dict(block_size=4, prefill_chunk=8),
                         (5, 23, 14, 30, 3, 16)),
}
MAX_NEW = (9, 14, 6, 11, 3, 8)


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


@pytest.fixture(scope="module")
def models():
    made = {"gpt2": _model_and_vars()}
    for name, keys in (("sliding_full_experts", MIXED),
                       ("linear_attention", LINEAR)):
        model = DecoderLM(TransformerConfig(**keys, remat=False,
                                            dtype=jnp.float32))
        made[name] = model, model.init(jax.random.key(5),
                                       jnp.ones((1, 8), jnp.int32))
    return made


def _serve(models, family, *, fused=True, journal=None, **kw):
    """The family's requests through its engine, step by step: (engine,
    requests, for each call whether it ended with a prompt just finished
    and not yet decoding)."""
    model, variables = models[family]
    own, lengths = FAMILIES[family]
    vocab = model.cfg.vocab_size
    eng = ServeEngine(model, variables, journal=journal, **{
        "n_slots": 3, "max_len": 64, "cache_dtype": jnp.float32,
        "export_cache": False, **own, **kw})
    if not fused:
        as_two_calls(eng)
    rs = np.random.RandomState(11)
    reqs = [eng.submit([int(t) for t in rs.randint(1, vocab, size=n)],
                       max_new_tokens=m)
            for n, m in zip(lengths, MAX_NEW)]
    waiting = []
    while not eng.scheduler.idle():
        eng.step()
        waiting.append([r.rid for r in reqs if r.state == "running"
                        and r.n_dispatched == 1])
    return eng, reqs, waiting


@pytest.fixture(scope="module")
def served(models):
    """``served(family, fused)``: a family's requests through its engine (or
    its two-call twin), served ONCE for the cases that only read the run:
    ``_serve``'s three and the journal, the invariants audited every step."""
    made = {}

    def get(family, fused=True):
        if (family, fused) not in made:
            journal = Journal(None, validate=True, host0_only=False)
            with pytest.MonkeyPatch.context() as env:
                env.setenv("TADNN_DEBUG_INVARIANTS", "1")
                made[family, fused] = (*_serve(
                    models, family, fused=fused, journal=journal), journal)
        return made[family, fused]

    return get


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_a_fused_step_serves_the_two_calls_tokens(served, family):
    eng, reqs, waiting, journal = served(family)
    twin, want, twin_waiting, _ = served(family, fused=False)
    assert [r.out_tokens for r in reqs] == [r.out_tokens for r in want]
    assert all(len(r.out_tokens) == m for r, m in zip(reqs, MAX_NEW))
    assert twin.fused_steps == 0 == twin.fused_decode_rows
    assert eng.steps_ahead > 0.8 * eng._step_count  # dispatch-ahead is on
    # the counters say what ran: a fused call is a call with a chunk and
    # decode rows, as many as slots were decoding when it went out
    steps = journal.named("serve.step")
    fused = [s for s in steps if s["fused"]]
    assert len(fused) == eng.fused_steps > 5
    assert sum(s["fused_decode_rows"] for s in steps) \
        == eng.fused_decode_rows >= len(fused)
    assert all(s["n_prefill_chunks"] == 1 and s["decode_s"]
               and 1 <= s["fused_decode_rows"] <= eng.n_slots - 1
               for s in fused)
    assert all(not s["fused_decode_rows"] for s in steps if not s["fused"])
    # once a slot decodes, every chunk carries the decode rows
    chunked = [s for s in steps if s["n_prefill_chunks"]]
    assert len(fused) >= len(chunked) - 3
    # a prompt that ended in a fused call waits one call for its first
    # decode step (and is then served its own first token: the tokens
    # above); as two calls it joins the decode step of the same call
    ended_in_fused = [rid for s, rids in zip(steps, waiting)
                      if s["fused"] for rid in rids]
    assert ended_in_fused and not any(twin_waiting)
    assert all(not rids for s, rids in zip(steps, waiting) if not s["fused"])
    if family == "gpt2":
        assert [r.out_tokens for r in reqs] == [
            _greedy(r.prompt, m) for r, m in zip(reqs, MAX_NEW)]
    if family == "sliding_full_experts":
        # a fused call's expert layers routed the chunk's rows too: their
        # counters are not a decode step's and are left out of the read
        alone = [s for s in steps
                 if "decode_dispatch" in s["phases"] and not s["fused"]]
        assert len([s for s in steps if "moe_pairs" in s]) == len(alone)


@pytest.mark.parametrize("family", ["gpt2", "linear_attention"])
def test_each_programs_first_call_is_on_the_engines_description(models,
                                                                family):
    """Start-up accounts for itself.  The engine's description says what
    building it cost; after a step in which a program had its first call
    it is said AGAIN with the table of programs so far, each by the name a
    trace shows: the counter's parts over that one call, their sum no more
    than the call took.  A ``compile`` event a program, on the step it
    loaded in; a second run loads nothing and says nothing again."""
    journal = Journal(None, validate=True, host0_only=False)
    eng, _, _ = _serve(models, family, journal=journal)
    first, *again = journal.named("serve.engine")
    last = again[-1]
    assert first["build_s"] >= sum(first["build_phases"].values()) > 0
    assert {"weights", "pool", "describe"} <= set(first["build_phases"])
    assert first["build_loads"]["load_s"] <= first["build_s"]
    # the same description, the table beside it
    assert "programs" not in first
    assert set(last) == set(first) | {"programs"}
    assert all(last[k] == v for k, v in first.items()
               if k not in ("t", "wall"))
    # the chunk that carries a step's rows, the sampler, the step: what
    # this engine dispatched, and no other
    assert list(last["programs"]) == list(eng.programs)
    assert set(eng.programs) == {"serve_prefill_chunk", "serve_first_token",
                                 "serve_decode_step"}
    for p in last["programs"].values():
        assert p["n"] >= 1 and 0 < p["load_s"] <= p["call_s"]
        assert p["load_s"] == pytest.approx(
            p["trace_s"] + p["lower_s"] + p["backend_s"])
        assert 0 <= p["cache_read_s"] <= p["backend_s"]
        assert "reloaded_at" not in p
    steps = journal.named("serve.step")
    # the description again after each step that loaded, and after no other
    at = sorted({p["at_step"] for p in eng.programs.values()})
    assert [len(e["programs"]) for e in again] == [
        sum(p["at_step"] <= step for p in eng.programs.values())
        for step in at]
    # ``compiles`` is still what the PROCESS built or loaded in the step
    assert all(s["compiles"] >= sum(p["at_step"] == s["step"]
                                    for p in eng.programs.values())
               for s in steps)
    events = [e for e in journal.named("compile") if e["fn"] == "serve"]
    assert [(e["program"], e["dur_s"]) for e in events] == [
        (name, p["load_s"]) for name, p in eng.programs.items()]
    # (the parts are in ``programs``, once)
    assert all(set(e) - set(schema.BASE_FIELDS) == {"fn", "program"}
               for e in events)
    n_records = len(journal.records)
    eng.submit([3, 1, 4, 1, 5, 9, 2, 6, 5, 3], max_new_tokens=5)
    eng.run()
    assert len(eng.programs) == 3
    assert {r["name"] for r in journal.records[n_records:]} == {
        "serve.step", "serve.request_done"}
    assert not any(s["compiles"]
                   for s in journal.named("serve.step")[len(steps):])


def test_a_program_loaded_again_is_named_where_it_happens(models):
    """Once every program has had its first call, a window can compile only
    by loading one of them AGAIN (here: JAX's caches dropped): the call in
    which the counter moves is a ``compile`` event under the program's name
    with that load's seconds, the program's row takes the seconds in and
    dates the load, and the description is said again."""
    journal = Journal(None, validate=True, host0_only=False)
    eng, _, _ = _serve(models, "gpt2", journal=journal)
    before = {k: dict(v) for k, v in eng.programs.items()}
    n_steps = len(journal.named("serve.step"))
    n_events = len(journal.named("compile"))
    n_said = len(journal.named("serve.engine"))
    jax.clear_caches()
    eng.submit([3, 1, 4, 1, 5, 9, 2, 6, 5, 3], max_new_tokens=5)
    eng.run()
    assert list(eng.programs) == list(before)
    events = journal.named("compile")[n_events:]
    assert {e["program"] for e in events} == set(before)
    steps = {s["step"]: s for s in journal.named("serve.step")[n_steps:]}
    for name, p in eng.programs.items():
        was = before[name]
        mine = [e for e in events if e["program"] == name]
        assert p["n"] == was["n"] + len(mine) > was["n"]
        assert p["load_s"] == pytest.approx(
            was["load_s"] + sum(e["dur_s"] for e in mine))
        assert p["load_s"] <= p["call_s"] and p["at_step"] == was["at_step"]
        assert steps[p["reloaded_at"]]["compiles"] >= 1
    said = journal.named("serve.engine")[n_said:]
    assert said and said[-1]["programs"] == eng.programs
    # an event keeps the row as it stood, not the dict the engine adds to
    assert journal.named("serve.engine")[n_said - 1]["programs"] == before


@pytest.mark.parametrize("fused", [True, False], ids=["fused", "two_calls"])
def test_live_expert_tiles_are_counted_on_every_call(served, fused, tmp_path):
    """``moe_tiles_active`` rides with every step's tokens, a fused step's
    too, beside the tiles that step laid out (``serve.engine`` states both
    kinds of call's); the schema names them and ``tadnn report`` prints
    live of laid."""
    from torch_automatic_distributed_neural_network_tpu.obs import (
        report as obs_report,
        schema,
    )
    from torch_automatic_distributed_neural_network_tpu.parallel.expert import (
        expert_tiles,
    )

    eng, _, _, journal = served("sliding_full_experts", fused)
    steps = journal.named("serve.step")
    engine = journal.named("serve.engine")[-1]
    path = tmp_path / "journal.jsonl"
    path.write_text("".join(json.dumps(r) + "\n" for r in journal.records))
    cfg = eng.cfg
    chunk, step = (cfg.n_expert_layers * expert_tiles(
        rows, cfg.experts_per_token, cfg.n_experts_held)[1]
        for rows in (4 + 3, 3))  # chunk + slots, slots
    assert engine["moe_tiles_laid"] == [chunk, step]
    read = [s for s in steps if "moe_tiles_active" in s]
    assert len(read) >= len(steps) - 6  # all but the calls before a read
    assert all(0 < s["moe_tiles_active"] <= s["moe_tiles_laid"]
               for s in read)
    assert {s["moe_tiles_laid"] for s in read} == (
        {chunk, step} if fused else {step})
    # a step that decoded alone has the other three too; a fused one not
    assert all(("moe_pairs" in s) <= ("moe_tiles_active" in s) for s in steps)
    for event in ("serve.step", "serve.engine"):
        assert "moe_tiles_laid" in schema.REGISTRY[event].optional
    assert schema.REGISTRY["serve.step"].optional["moe_tiles_active"] == "int"
    rep = obs_report.generate(str(path))
    text = obs_report.format_report(rep)
    by_laid = rep["serving"]["moe_tiles"]
    assert [laid for laid, _, _ in by_laid] == sorted(
        {chunk, step} if fused else {step}, reverse=True)
    assert sum(n for _, n, _ in by_laid) == len(read)
    assert all(0 < live <= laid * n for laid, n, live in by_laid)
    assert (f"expert row tiles (laid: {chunk} a call with a chunk, {step} a "
            f"decode-only call), live of laid a call: ") in text
    laid, n, live = by_laid[-1]
    assert f"{live / n:.1f} of {laid} (" in text and f"over {n} calls" in text


@pytest.mark.parametrize("case", ["int8_kv", "sampled", "dense_attention"])
def test_fused_steps_under_the_engines_other_options(
        models, case, monkeypatch):
    """Options that change what a row reads: the fused call follows them
    through the same entries (``paged_attention`` for an int8 pool's VPU
    kernel, the dense gather, ``_sample`` under the step's key)."""
    import itertools

    from torch_automatic_distributed_neural_network_tpu.inference.decode import (
        SampleConfig,
    )
    from torch_automatic_distributed_neural_network_tpu.inference.serve import (
        scheduler as sched_mod,
    )

    kw = {"int8_kv": dict(quant_kv=True),
          "sampled": dict(sample=SampleConfig(temperature=0.8)),
          "dense_attention": dict(attention_impl="dense")}[case]
    got = []
    for fused in (True, False):
        # a prompt's first token is sampled under its request's id
        monkeypatch.setattr(sched_mod, "_rid_counter", itertools.count(1000))
        journal = Journal(None, validate=True, host0_only=False)
        eng, reqs, _ = _serve(models, "gpt2", fused=fused, journal=journal,
                              **kw)
        got.append([r.out_tokens for r in reqs])
        assert bool(eng.fused_steps) == fused
    if case == "sampled":
        # the same keys: a step's, whichever program the rows are in, and
        # a request's own for its first token.  A prompt that ends in a
        # fused call decodes one call later, under a later step's key, so
        # only the requests that never did keep every token
        assert [len(t) for t in got[0]] == list(MAX_NEW)
        assert [t[0] for t in got[0]] == [t[0] for t in got[1]]
    else:
        assert got[0] == got[1]


def _tenant_engine(**kw):
    model, variables = _model_and_vars()
    return ServeEngine(model, variables, lora_spec=LoraSpec(rank=4), **{
        "n_slots": 3, "max_len": 64, "block_size": 8, "prefill_chunk": 8,
        "cache_dtype": jnp.float32, "export_cache": False, **kw})


@pytest.mark.parametrize("engine", ["speculative", "tenant"])
def test_engines_that_cannot_fuse_run_the_two_calls(engine):
    """Read off the engine's own state: a verify step has 1 + k rows a
    slot, a tenant's rows add deltas the chunk's must not see.  Neither
    builds the fused program, neither runs a fused step, and each serves
    what it served before: the greedy tokens."""
    journal = Journal(None, validate=True, host0_only=False)
    make = {"speculative": lambda: _f32_engine(journal, speculative=2),
            "tenant": lambda: _tenant_engine(journal=journal)}[engine]
    eng = make()
    assert eng._fused_fn is None and eng._prefill_fn is not None
    prompts = _prompts((5, 21, 12, 16, 9))
    reqs = [eng.submit(p, max_new_tokens=7) for p in prompts]
    eng.run()
    steps = journal.named("serve.step")
    assert eng.fused_steps == 0 == sum(s["fused"] for s in steps)
    assert not any(s["fused_decode_rows"] for s in steps)
    assert [r.out_tokens for r in reqs] == [_greedy(p, 7) for p in prompts]
    ev = journal.named("serve.engine")[-1]
    assert ev["speculative"] == (2 if engine == "speculative" else 0)


def test_a_rider_preempted_to_grow_another_runs_no_chunk(monkeypatch):
    """Optimistic admission over a pool too small: ``grow`` runs before
    the fused dispatch, so a victim is not among its decode rows, and when
    the victim is the prompt whose chunk waited for the rows (the youngest
    slot is the one prefilling), the chunk is dropped with it.  The
    scheduler's invariants hold after every call and every request serves
    what it serves alone."""
    monkeypatch.setenv("TADNN_DEBUG_INVARIANTS", "1")
    journal = Journal(None, validate=True, host0_only=False)
    short, long_ = _prompts((6, 6)), _prompts((30,), seed=7)[0]
    # 13 pages of 4: two prompts of 2 pages, one of 8, and ONE to grow into
    eng = _f32_engine(journal, admission="optimistic", num_blocks=14,
                      block_size=4)
    reqs = [eng.submit(p, max_new_tokens=24) for p in short]
    eng.step()
    late = eng.submit(long_, max_new_tokens=6)  # the youngest, 4 chunks
    victims = []
    preempt = eng.scheduler.preempt_youngest

    def watched():
        victims.append((eng._step_count + 1, eng.scheduler.slots.index(late)
                        if late in eng.scheduler.slots else None,
                        late.rid in eng._prefill))
        return preempt()

    monkeypatch.setattr(eng.scheduler, "preempt_youngest", watched)
    eng.run()
    eng.scheduler.check_invariants()
    # the long prompt was the victim while it was prefilling
    assert victims and victims[0][1] is not None and victims[0][2]
    assert late.preempted >= 1 and eng.fused_steps > 0
    assert [r.out_tokens for r in reqs] == [_greedy(p, 24) for p in short]
    assert late.out_tokens == _greedy(long_, 6)
    assert eng.pool.allocator.n_free == 13  # zero leaked blocks
    # the call that dropped the rider ran no chunk and no fused step, and
    # decoded the others
    (step,) = [s for s in journal.named("serve.step")
               if s["step"] == victims[0][0]]
    assert step["n_prefill_chunks"] == 0 == step["fused"]
    assert step["decode_s"] and "decode_dispatch" in step["phases"]


def test_a_copy_on_write_fork_in_a_fused_step():
    """The guard runs before the decode rows write, in a call whose rows
    ride in a chunk as in any other: alias a running request's write block
    into a second owner while another slot is prefilling, then step (the
    invariants are checked once the manufactured owner has let go)."""
    journal = Journal(None, validate=True, host0_only=False)
    eng = _f32_engine(journal, n_slots=2, prefix_cache=True)
    first, second = _prompts((12, 30))
    req = eng.submit(first, max_new_tokens=10)
    while req.state != "running":
        eng.step()
    other = eng.submit(second, max_new_tokens=4)
    eng.step()  # admits the second prompt: its chunks carry req's rows
    assert other.state == "prefilling" and eng.fused_steps >= 1
    bi = (req.n_prompt + req.n_dispatched - 1) // 8
    b = req.blocks[bi]
    eng.pool.allocator.ref(b)  # manufactured second owner
    forks, fused = eng.cow_forks, eng.fused_steps
    eng.step()
    assert eng.cow_forks == forks + 1 and eng.fused_steps == fused + 1
    assert req.blocks[bi] != b  # the rows wrote the fork
    eng.pool.allocator.release([b])
    eng.run()
    eng.scheduler.check_invariants()
    assert req.out_tokens == _greedy(first, 10)
    assert other.out_tokens == _greedy(second, 4)


def test_report_prints_the_fused_shares(tmp_path, served):
    import json

    from torch_automatic_distributed_neural_network_tpu.obs import (
        report as obs_report,
    )

    eng, _reqs, _, journal = served("gpt2")
    path = tmp_path / "journal.jsonl"
    path.write_text("".join(json.dumps(r) + "\n" for r in journal.records))
    rep = obs_report.generate(str(path))
    srv = rep["serving"]
    steps = journal.named("serve.step")
    assert srv["fused_steps"] == eng.fused_steps
    assert srv["fused_share_of_chunk_steps"] == pytest.approx(
        eng.fused_steps / sum(1 for s in steps if s["n_prefill_chunks"]))
    assert 0 < srv["fused_share_of_decode_rows"] < 1
    text = obs_report.format_report(rep)
    assert "carried the decode rows" in text
    # an engine that ran none prints no such line
    journal = served("gpt2", fused=False)[3]
    path.write_text("".join(json.dumps(r) + "\n" for r in journal.records))
    rep = obs_report.generate(str(path))
    assert "fused_steps" not in rep["serving"]
    assert "carried the decode rows" not in obs_report.format_report(rep)
