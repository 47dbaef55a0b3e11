"""Live serving telemetry tests: per-request span timelines from the
engine (serve.request_done), the streaming window aggregator and its
mergeable latency sketch (obs/live), the hysteresis SLO monitor
(obs/slo_monitor + tadnn monitor CLI), Journal.follow tail iteration,
serve-journal merging, and report rendering of the timeline and
incident sections."""

import json
import random

import pytest

from torch_automatic_distributed_neural_network_tpu import cli
from torch_automatic_distributed_neural_network_tpu.obs import (
    report as obs_report,
)
from torch_automatic_distributed_neural_network_tpu.obs.journal import (
    Journal,
)
from torch_automatic_distributed_neural_network_tpu.obs.live import (
    LatencySketch,
    LiveAggregator,
    aggregate_stream,
)
from torch_automatic_distributed_neural_network_tpu.obs.slo_monitor import (
    MonitorPolicy,
    SLOMonitor,
    format_summary,
    monitor_records,
    window_prediction,
)
from torch_automatic_distributed_neural_network_tpu.tune.slo import SLOSpec


# -- helpers ------------------------------------------------------------------


def _step(t, *, occupancy=0.75, new_tokens=4, n_queued=0):
    return {"kind": "event", "name": "serve.step", "t": t,
            "occupancy": occupancy, "new_tokens": new_tokens,
            "n_queued": n_queued}


def _done(t, rid, *, total_s=0.2, ttft_s=0.05, itl=(0.01, 0.01, 0.01),
          n_new=4, n_prompt=10, cached_tokens=0):
    return {"kind": "event", "name": "serve.request_done", "t": t,
            "rid": rid, "n_prompt": n_prompt, "n_new": n_new,
            "total_s": total_s, "ttft_s": ttft_s, "itl_s": list(itl),
            "queue_s": 0.01, "prefill_s": ttft_s, "decode_s": 0.1,
            "cached_tokens": cached_tokens or None, "preempted": 0}


def _degraded_journal():
    """8 windows of 5s; windows 2-4 serve pathological latencies —
    enough consecutive bad windows to breach (after hysteresis) and
    enough clean ones after to recover.  Pure dicts: deterministic."""
    recs = []
    for w in range(8):
        slow = w in (2, 3, 4)
        for i in range(5):
            t = w * 5.0 + i
            recs.append(_step(t))
            recs.append(_done(t, rid=w * 10 + i,
                              total_s=(5.0 if slow else 0.2)))
    return recs


# -- latency sketch -----------------------------------------------------------


def test_sketch_percentile_accuracy_bound():
    rng = random.Random(0)
    vals = [rng.lognormvariate(-3, 1) for _ in range(5000)]
    s = LatencySketch()
    for v in vals:
        s.add(v)
    exact = sorted(vals)
    for q in (0.5, 0.9, 0.99):
        true = exact[max(0, -(-int(q * len(exact)) // 1) - 1)]
        est = s.percentile(q)
        # bucket midpoints sit within sqrt(growth) of the true value;
        # 5% leaves margin over the ~4% design bound
        assert abs(est - true) / true < 0.05, (q, est, true)
    assert s.n == len(vals)
    assert s.percentile(0.0) == pytest.approx(min(vals))
    assert s.percentile(1.0) == pytest.approx(max(vals))


def test_sketch_merge_equals_union():
    rng = random.Random(1)
    vals = [rng.uniform(1e-4, 2.0) for _ in range(2000)]
    whole = LatencySketch()
    a, b = LatencySketch(), LatencySketch()
    for i, v in enumerate(vals):
        whole.add(v)
        (a if i % 2 else b).add(v)
    a.merge(b)
    for q in (0.01, 0.5, 0.99):
        assert a.percentile(q) == whole.percentile(q)
    assert a.n == whole.n and a.total == pytest.approx(whole.total)


def test_sketch_merge_rejects_different_shape():
    with pytest.raises(ValueError, match="shape"):
        LatencySketch(growth=1.08).merge(LatencySketch(growth=1.5))


def test_sketch_json_roundtrip():
    s = LatencySketch()
    for v in (0.001, 0.01, 0.1, 1.0):
        s.add(v)
    r = LatencySketch.from_json(
        json.loads(json.dumps(s.to_json())))
    assert r.percentile(0.5) == s.percentile(0.5)
    assert r.n == s.n


# -- window aggregation -------------------------------------------------------


def test_window_aggregates_known_answers():
    agg = LiveAggregator(window_s=5.0, clock=None)
    closed = []
    for rec in _degraded_journal():
        closed += agg.add(rec)
    last = agg.flush()
    assert last is not None
    windows = closed + [last]
    assert len(windows) == 8
    w0 = windows[0]
    # 5 steps x 4 tokens over a 5s window
    assert w0["new_tokens"] == 20
    assert w0["tok_s"] == pytest.approx(4.0)
    assert w0["n_done"] == 5 and w0["n_steps"] == 5
    assert w0["occupancy"] == pytest.approx(0.75)
    assert w0["preemptions"] == 0
    # sketch percentiles stay within the design bound of the exact
    # single-valued distributions fed in
    assert w0["ttft_p50_s"] == pytest.approx(0.05, rel=0.05)
    assert w0["itl_p99_s"] == pytest.approx(0.01, rel=0.05)
    assert w0["p99_s"] == pytest.approx(0.2, rel=0.05)
    assert windows[2]["p99_s"] == pytest.approx(5.0, rel=0.05)
    # run-wide roll-up merges every window
    summ = agg.summary()
    assert summ["n_windows"] == 8
    assert summ["n_done"] == 40
    assert summ["new_tokens"] == 160
    assert summ["tok_s"] == pytest.approx(4.0)


def test_window_event_time_is_replayable():
    """Same records -> same windows, independent of arrival pacing:
    the aggregator keys on the records' own t stamps."""
    recs = _degraded_journal()
    a = list(aggregate_stream(recs, window_s=5.0))
    b = list(aggregate_stream(iter(recs), window_s=5.0))
    assert a == b


def test_empty_windows_not_emitted():
    agg = LiveAggregator(window_s=1.0, clock=None)
    closed = agg.add(_step(0.5))
    closed += agg.add(_step(10.5))  # jumps 9 idle windows
    closed += [w for w in [agg.flush()] if w]
    assert [w["window"] for w in closed] == [0, 10]


def test_preemption_and_prefix_counters():
    agg = LiveAggregator(window_s=5.0, clock=None)
    agg.add(_step(0.0))
    agg.add({"kind": "event", "name": "serve.preempt", "t": 1.0,
             "rid": 7})
    agg.add(_done(2.0, rid=1, cached_tokens=8, n_prompt=10))
    agg.add({"kind": "event", "name": "serve.speculate", "t": 3.0,
             "drafted": 10, "accepted": 6})
    w = agg.flush()
    assert w["preemptions"] == 1
    assert w["prefix_hit_rate"] == pytest.approx(0.8)
    assert w["accept_rate"] == pytest.approx(0.6)


# -- SLO monitor hysteresis ---------------------------------------------------


def test_breach_then_recover_deterministic():
    pol = MonitorPolicy(slo=SLOSpec.parse("p99_ms<=2500"),
                        window_s=5.0, breach_after=2, recover_after=2,
                        warmup_windows=0)
    sink = Journal(None, host0_only=False)
    summary = monitor_records(_degraded_journal(), pol, journal=sink)
    kinds = [i["kind"] for i in summary["incidents"]]
    assert kinds == ["breach", "recover"]
    # breach on the SECOND consecutive bad window (windows 2,3), not
    # the first; recovery on the second clean window after (5,6)
    assert summary["incidents"][0]["window_start_s"] == 15.0
    assert summary["incidents"][1]["window_start_s"] == 30.0
    assert summary["breaches"] == 1 and summary["recoveries"] == 1
    assert summary["n_violating"] == 3
    assert summary["state"] == "ok"
    names = [r["name"] for r in sink.records
             if r["name"].startswith("slo.")]
    assert names == ["slo.breach", "slo.recover"]
    # deterministic: a second replay produces the identical summary
    again = monitor_records(_degraded_journal(), pol,
                            journal=Journal(None, host0_only=False))
    assert again == summary


def test_single_bad_window_does_not_flap():
    recs = []
    for w in range(4):
        recs.append(_step(w * 5.0))
        recs.append(_done(w * 5.0 + 1, rid=w,
                          total_s=(9.0 if w == 1 else 0.1)))
    pol = MonitorPolicy(slo=SLOSpec.parse("p99_ms<=2500"),
                        window_s=5.0, breach_after=2, recover_after=2,
                        warmup_windows=0)
    summary = monitor_records(recs, pol,
                              journal=Journal(None, host0_only=False))
    assert summary["incidents"] == []
    assert summary["n_violating"] == 1


def test_warmup_windows_skip_compile_era():
    """The first traffic window carries the jit compiles; with the
    default warmup skip the degraded-from-the-start journal still
    reports, but only post-warmup windows are judged."""
    recs = [_step(1.0), _done(2.0, rid=0, total_s=30.0)]
    pol = MonitorPolicy(slo=SLOSpec.parse("p99_ms<=2500"),
                        window_s=5.0, breach_after=1, recover_after=1,
                        warmup_windows=1)
    summary = monitor_records(recs, pol,
                              journal=Journal(None, host0_only=False))
    assert summary["n_windows"] == 1
    assert summary["n_evaluated"] == 0
    assert summary["breaches"] == 0


def test_window_prediction_maps_slo_fields():
    pred = window_prediction({"tok_s": 80.0, "p99_s": 1.0,
                              "ttft_p99_s": 0.5, "itl_p99_s": 0.02},
                             n_chips=4)
    assert pred["tok_s_per_chip"] == pytest.approx(20.0)
    ok, _ = SLOSpec.parse(
        "tok_s_chip>=10,p99_ms<=2500,ttft_ms<=600,itl_ms<=50"
    ).evaluate(pred)
    assert ok
    ok, violations = SLOSpec.parse("itl_ms<=10").evaluate(pred)
    assert not ok and "itl_p99_s" in violations[0]


def test_slo_absence_is_violation_live():
    # a window with no finished requests has no p99 — a latency SLO
    # must treat that as non-compliance, not a free pass
    ok, violations = SLOSpec.parse("p99_ms<=2500").evaluate(
        window_prediction({"tok_s": 5.0, "p99_s": None}))
    assert not ok and "no prediction" in violations[0]


# -- tadnn monitor CLI --------------------------------------------------------


def _write_journal(path, recs):
    with open(path, "w") as f:
        for r in recs:
            f.write(json.dumps(r) + "\n")


def test_monitor_cli_replay_check_exit_codes(tmp_path, capsys):
    jpath = tmp_path / "serve.journal.jsonl"
    _write_journal(jpath, _degraded_journal())
    out = tmp_path / "summary.json"
    # degraded journal breaches -> nonzero under --check
    assert cli.main([
        "monitor", str(jpath), "--replay", "--slo", "p99_ms<=2500",
        "--warmup-windows", "0", "--check", "--out", str(out)]) == 1
    summary = json.loads(out.read_text())
    assert summary["breaches"] == 1
    assert [i["kind"] for i in summary["incidents"]] == [
        "breach", "recover"]
    text = capsys.readouterr().out
    assert "BREACH" in text and "ttft" in text
    # a healthy journal (same traffic, fast everywhere) passes the gate
    good = [dict(r, total_s=0.2)
            if r["name"] == "serve.request_done" else r
            for r in _degraded_journal()]
    jok = tmp_path / "ok.journal.jsonl"
    _write_journal(jok, good)
    assert cli.main([
        "monitor", str(jok), "--replay", "--slo", "p99_ms<=2500",
        "--warmup-windows", "0", "--check"]) == 0
    # an unparseable SLO is a loud usage error, not a silent pass
    assert cli.main([
        "monitor", str(jok), "--slo", "p99_parsecs<=1"]) == 2
    assert cli.main([
        "monitor", str(tmp_path / "missing.jsonl")]) == 2


def test_monitor_cli_incident_journal_renders_in_report(tmp_path):
    jpath = tmp_path / "serve.journal.jsonl"
    _write_journal(jpath, _degraded_journal())
    inc = tmp_path / "incidents.jsonl"
    assert cli.main([
        "monitor", str(jpath), "--slo", "p99_ms<=2500",
        "--warmup-windows", "0",
        "--incident-journal", str(inc)]) == 0  # no --check: exit 0
    merged = tmp_path / "journal.jsonl"
    merged.write_text(jpath.read_text() + inc.read_text())
    rep = obs_report.generate(str(merged), None)
    assert rep["slo_incidents"]["breaches"] == 1
    assert rep["slo_incidents"]["recoveries"] == 1
    text = obs_report.format_report(rep)
    assert "slo incidents" in text and "BREACH" in text


# -- report rendering ---------------------------------------------------------


def test_report_renders_timeline(tmp_path):
    recs = _degraded_journal()
    jpath = tmp_path / "journal.jsonl"
    _write_journal(jpath, recs)
    rep = obs_report.generate(str(jpath), None)
    sv = rep["serving"]
    assert sv["ttft_p50_s"] == pytest.approx(0.05)
    assert sv["itl_p99_s"] == pytest.approx(0.01)
    assert sv["phase_mean_s"]["queue"] == pytest.approx(0.01)
    text = obs_report.format_report(rep)
    assert "timeline: ttft p50" in text


def test_report_accepts_legacy_serve_request_name(tmp_path):
    legacy = [{"kind": "event", "name": "serve.request", "t": 0.5,
               "rid": 0, "n_prompt": 10, "n_new": 4, "total_s": 0.2,
               "queue_s": 0.0, "preempted": 0}]
    jpath = tmp_path / "journal.jsonl"
    _write_journal(jpath, legacy)
    rep = obs_report.generate(str(jpath), None)
    assert rep["serving"]["n_requests"] == 1


def test_format_summary_smoke():
    pol = MonitorPolicy(slo=SLOSpec.parse("p99_ms<=2500"),
                        warmup_windows=0)
    summary = monitor_records(_degraded_journal(), pol,
                              journal=Journal(None, host0_only=False))
    text = format_summary(summary)
    assert "BREACH" in text and "recovered" in text
    assert "ttft p50" in text


# -- Journal.follow -----------------------------------------------------------


def test_follow_tolerates_concurrent_appender(tmp_path):
    path = str(tmp_path / "live.jsonl")
    writes = [
        '{"kind": "event", "name": "a", "t": 0.1}\n',
        '{"kind": "event", "name": "b", "t"',    # torn mid-record...
        ': 0.2}\n{"kind": "event", "name": "c", "t": 0.3}\n',
    ]
    f = open(path, "w")
    f.write(writes[0])
    f.flush()
    state = {"i": 1}

    def feed(_):
        # the injected sleep plays the concurrent writer: each idle
        # poll appends the next chunk (including the torn-line split)
        if state["i"] < len(writes):
            f.write(writes[state["i"]])
            f.flush()
            state["i"] += 1

    got = list(Journal.follow(path, poll_s=1.0, idle_timeout=2.0,
                              sleep=feed))
    f.close()
    assert [r["name"] for r in got] == ["a", "b", "c"]
    assert got[1]["t"] == 0.2  # the torn record arrived whole


def test_follow_survives_rotation_mid_follow(tmp_path):
    import os
    import warnings

    path = str(tmp_path / "live.jsonl")
    f = open(path, "w")
    f.write('{"kind": "event", "name": "a", "t": 0.1}\n')
    f.flush()
    state = {"i": 0, "f": f}

    def feed(_):
        state["i"] += 1
        if state["i"] == 1:
            # append a record plus a TORN tail, then rotate out from
            # under the tail (exactly what Journal._rotate does): the
            # torn fragment's completion lands in <path>.1, never in
            # the live file — the follower must drop it, not glue it
            # to the new generation's first line
            state["f"].write(
                '{"kind": "event", "name": "b", "t": 0.2}\n'
                '{"kind": "event", "na')
            state["f"].flush()
        elif state["i"] == 2:
            state["f"].close()
            os.replace(path, path + ".1")
            state["f"] = open(path, "w")
            state["f"].write(
                '{"kind": "event", "name": "c", "t": 0.3}\n')
            state["f"].flush()

    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        got = list(Journal.follow(path, poll_s=1.0, idle_timeout=3.0,
                                  sleep=feed))
    state["f"].close()
    # records from BOTH generations, in order, the torn line dropped
    assert [r["name"] for r in got] == ["a", "b", "c"]
    rot = [w for w in caught if "rotated mid-follow" in str(w.message)]
    assert len(rot) == 1  # once per rotation, not once per poll
    assert "torn" in str(rot[0].message)


def test_follow_survives_truncation(tmp_path):
    path = str(tmp_path / "live.jsonl")
    f = open(path, "w")
    f.write('{"kind": "event", "name": "a", "t": 0.1}\n')
    f.flush()
    state = {"i": 0}

    def feed(_):
        state["i"] += 1
        if state["i"] == 1:
            # same-inode truncate-and-rewrite (copytruncate-style
            # rotation): size shrinks below the read position.  (An
            # equal-or-larger rewrite is indistinguishable from an
            # append by stat alone; the shrink is the detectable — and
            # the usual — case.)
            f.seek(0)
            f.truncate()
            f.write('{"name": "z", "t": 0.2}\n')
            f.flush()

    import warnings

    with warnings.catch_warnings(record=True):
        warnings.simplefilter("always")
        got = list(Journal.follow(path, poll_s=1.0, idle_timeout=3.0,
                                  sleep=feed))
    f.close()
    assert [r["name"] for r in got] == ["a", "z"]


def test_follow_stop_callback(tmp_path):
    path = str(tmp_path / "live.jsonl")
    _write_journal(path, [{"kind": "event", "name": "x", "t": 0.0}])
    got = list(Journal.follow(path, stop=lambda: True,
                              sleep=lambda s: None))
    assert [r["name"] for r in got] == ["x"]


def test_journal_flushes_every_append(tmp_path):
    path = str(tmp_path / "j.jsonl")
    with Journal(path, host0_only=False) as j:
        j.event("serve.step", step=1)
        # visible to a reader BEFORE close: the live-tail contract
        assert any(r["name"] == "serve.step" for r in Journal.read(path))


# -- multihost serve journal merge -------------------------------------------


def test_merge_run_carries_serve_and_slo_events(tmp_path):
    from torch_automatic_distributed_neural_network_tpu.obs import (
        aggregate,
    )

    base = 1700000000.0
    for host in range(2):
        recs = [
            {"kind": "event", "name": "journal.start", "t": 0.0,
             "wall": base + host, "host": host},
            dict(_done(1.0, rid=host), wall=base + 10 + host),
            {"kind": "event", "name": "slo.breach", "t": 2.0,
             "wall": base + 20 + host, "window_start_s": 0.0,
             "window_end_s": 5.0, "violations": ["p99_s: too slow"]},
        ]
        _write_journal(tmp_path / f"serve.host{host}.jsonl", recs)
    merged = aggregate.merge_run(str(tmp_path))
    records = Journal.read(merged)
    dones = [r for r in records if r["name"] == "serve.request_done"]
    breaches = [r for r in records if r["name"] == "slo.breach"]
    assert len(dones) == 2 and len(breaches) == 2
    # host-tagged, fields untouched, wall-interleaved
    assert sorted(r["host"] for r in dones) == [0, 1]
    assert all(r["itl_s"] == [0.01, 0.01, 0.01] for r in dones)
    assert all(r["violations"] == ["p99_s: too slow"]
               for r in breaches)
    walls = [r["wall"] for r in records]
    assert walls == sorted(walls)
    rep = obs_report.generate(merged, None)
    assert rep["serving"]["n_requests"] == 2
    assert rep["slo_incidents"]["breaches"] == 2


# -- engine emits the timeline (integration, tiny model) ----------------------


@pytest.mark.slow
def test_engine_request_done_timeline():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from torch_automatic_distributed_neural_network_tpu.inference.serve import (
        ServeEngine,
    )
    from torch_automatic_distributed_neural_network_tpu.models import GPT2

    model = GPT2("test", vocab_size=128, max_seq_len=64,
                 dtype=jnp.float32, remat=False)
    tokens = jnp.asarray(
        np.random.RandomState(0).randint(1, 128, size=(1, 10)),
        jnp.int32)
    variables = model.init(jax.random.key(1), tokens)
    jnl = Journal(None, host0_only=False)
    eng = ServeEngine(model, variables, n_slots=2, max_len=64,
                      block_size=8, prefill_chunk=8, journal=jnl)
    rs = np.random.RandomState(3)
    for _ in range(3):
        eng.submit([int(t) for t in rs.randint(1, 128, size=10)],
                   max_new_tokens=4, eos_id=None)
    done = eng.run()
    assert len(done) == 3
    events = jnl.named("serve.request_done")
    assert len(events) == 3
    for e in events:
        assert e["n_new"] == 4
        # one TTFT stamp + 3 decode steps -> 3 inter-token latencies
        assert len(e["itl_s"]) == e["n_new"] - 1
        assert e["ttft_s"] > 0 and e["ttft_s"] <= e["total_s"]
        # phase attribution covers the request's wall time
        assert (e["queue_s"] + e["prefill_s"] + e["decode_s"]
                == pytest.approx(e["total_s"], rel=1e-6))
        assert e["prefill_chunks"] >= 2  # 10 tokens / C=8 -> 2 chunks
    # serve.step carries the per-step token count the live monitor
    # sums for its tok/s windows
    steps = jnl.named("serve.step")
    assert sum(s["new_tokens"] for s in steps) == 12
    # the whole stream folds into windows end to end
    windows = list(aggregate_stream(jnl.records, window_s=60.0))
    assert windows and windows[0]["n_done"] == 3
    assert windows[0]["new_tokens"] == 12


def test_follow_waits_for_missing_file(tmp_path):
    # the path does not exist yet (monitor started before the engine's
    # first event): follow polls for creation, then tails normally
    path = str(tmp_path / "notyet.jsonl")
    state = {"polls": 0}

    def feed(_):
        state["polls"] += 1
        if state["polls"] == 2:  # created on the second idle poll
            _write_journal(path, [
                {"kind": "event", "name": "a", "t": 0.1}])

    got = list(Journal.follow(path, poll_s=1.0, idle_timeout=5.0,
                              sleep=feed))
    assert [r["name"] for r in got] == ["a"]
    assert state["polls"] >= 2


def test_follow_missing_file_times_out_quietly(tmp_path):
    path = str(tmp_path / "never.jsonl")
    got = list(Journal.follow(path, poll_s=1.0, idle_timeout=2.0,
                              sleep=lambda s: None))
    assert got == []


def test_follow_missing_file_honors_stop(tmp_path):
    path = str(tmp_path / "never.jsonl")
    got = list(Journal.follow(path, stop=lambda: True,
                              sleep=lambda s: None))
    assert got == []


def test_monitor_cli_follow_accepts_missing_journal(tmp_path, capsys):
    # without --follow a missing journal is a usage error (exit 2, see
    # test_monitor_cli_replay_check_exit_codes); WITH --follow it waits
    # under --idle-timeout and exits 0 on a quiet timeout
    missing = str(tmp_path / "notyet.jsonl")
    assert cli.main([
        "monitor", missing, "--follow", "--idle-timeout", "0.5",
        "--slo", "p99_ms<=2500"]) == 0
    capsys.readouterr()
