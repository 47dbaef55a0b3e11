"""Runtime tracing tests (obs/trace, obs/aggregate + journal hardening):
profiler capture + attribution on a real CPU-sim step, measured-vs-modeled
collective bytes, multihost journal merge with seeded skew, report
rendering, journal rotation and the torn-line reader."""

import json
import os
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import torch_automatic_distributed_neural_network_tpu as tad
from torch_automatic_distributed_neural_network_tpu import (
    cli,
    topology,
    tune,
)
from torch_automatic_distributed_neural_network_tpu.models import MLP
from torch_automatic_distributed_neural_network_tpu.obs import (
    Journal,
    aggregate,
)
from torch_automatic_distributed_neural_network_tpu.obs import (
    comms as obs_comms,
)
from torch_automatic_distributed_neural_network_tpu.obs import (
    journal as obs_journal,
)
from torch_automatic_distributed_neural_network_tpu.obs import (
    report as obs_report,
)
from torch_automatic_distributed_neural_network_tpu.obs import (
    trace as obs_trace,
)
from torch_automatic_distributed_neural_network_tpu.training import (
    softmax_xent_loss,
)


def toy_batch(seed=0, batch=16, dim=8, classes=10):
    rng = np.random.RandomState(seed)
    return {
        "x": jnp.asarray(rng.randn(batch, dim), jnp.float32),
        "label": jnp.asarray(rng.randint(0, classes, size=(batch,))),
    }


def make_ad(strategy="dp", **kw):
    return tad.AutoDistribute(
        MLP(features=(32, 16, 10)),
        optimizer=optax.sgd(0.1),
        loss_fn=softmax_xent_loss,
        strategy=strategy,
        **kw,
    )


# ------------------------------------------------- pure interval math


def test_union_merges_overlaps():
    u = obs_trace._union([(0, 10), (5, 15), (20, 30), (30, 31)])
    assert u == [(0, 15), (20, 31)]
    assert obs_trace._total(u) == 26


def test_overlap_of_unions():
    a = obs_trace._union([(0, 10), (20, 30)])
    b = obs_trace._union([(5, 25)])
    assert obs_trace._overlap(a, b) == 5 + 5


def test_attribute_synthetic_exposed_math():
    # window [0, 100)us; compute [0, 60); collective [40, 80):
    # collective 40us, 20 hidden behind compute, 20 exposed
    parsed = {
        "steps": [{"step": 7, "ts": 0, "dur": 100}],
        "ops": [
            {"name": "fusion.1", "ts": 0, "dur": 60, "tid": 1},
            {"name": "all-reduce-start.2", "ts": 40, "dur": 40, "tid": 2},
        ],
    }
    (rec,) = obs_trace.attribute(parsed)
    assert rec["step"] == 7
    assert rec["wall_s"] == pytest.approx(100e-6)
    assert rec["compute_s"] == pytest.approx(60e-6)
    assert rec["collective_s"] == pytest.approx(40e-6)
    assert rec["exposed_collective_s"] == pytest.approx(20e-6)
    assert rec["collectives"] == {"all-reduce": pytest.approx(40e-6)}


def test_attribute_clips_ops_to_window():
    parsed = {
        "steps": [{"step": 0, "ts": 50, "dur": 50}],
        "ops": [{"name": "all-gather.9", "ts": 0, "dur": 80, "tid": 1}],
    }
    (rec,) = obs_trace.attribute(parsed)
    # only the [50, 80) slice of the op lands inside the step
    assert rec["collective_s"] == pytest.approx(30e-6)
    assert rec["collective_s"] <= rec["wall_s"]


def test_exposed_fraction_bounds_and_none():
    assert obs_trace.exposed_fraction([]) is None
    assert obs_trace.exposed_fraction(
        [{"collective_s": 0.0, "exposed_collective_s": 0.0}]) is None
    f = obs_trace.exposed_fraction(
        [{"collective_s": 1.0, "exposed_collective_s": 0.25}])
    assert f == pytest.approx(0.25)


# ------------------------------------------- HLO collective byte parse


def test_hlo_collective_bytes_parses_definitions():
    text = """
  %all-reduce.3 = f32[1024,256]{1,0} all-reduce(f32[1024,256]{1,0} %add.5), replica_groups={}
  %ag.1 = bf16[8,4]{1,0} all-gather-start(bf16[1,4]{1,0} %p), dimensions={0}
  %done.2 = f32[1024,256]{1,0} all-reduce-done(f32[1024,256]{1,0} %all-reduce.3)
  %fusion.7 = f32[512]{0} fusion(f32[512]{0} %x), kind=kLoop
"""
    out = obs_trace.hlo_collective_bytes(text)
    assert out["all-reduce"]["count"] == 1  # -done must NOT double-count
    assert out["all-reduce"]["payload_bytes"] == 1024 * 256 * 4
    assert out["all-gather"]["count"] == 1
    assert out["all-gather"]["payload_bytes"] == 8 * 4 * 2
    assert "fusion" not in out


def test_hlo_collective_bytes_tuple_shape():
    text = "%rs = (f32[64]{0}, u32[]) reduce-scatter(f32[512]{0} %g)"
    out = obs_trace.hlo_collective_bytes(text)
    assert out["reduce-scatter"]["payload_bytes"] == 64 * 4 + 4


# ------------------------------------- real capture on the 8-device sim


@pytest.fixture(scope="module")
def traced_run(tmp_path_factory, devices8):
    """One profiler capture of 3 real dp steps, plus the HLO/planner
    collective-bytes crosscheck, journaled to a run directory."""
    out = tmp_path_factory.mktemp("tracerun")
    ad = make_ad("dp")
    batch = toy_batch()
    rng = jax.random.key(0)
    state = ad.init(rng, batch)
    state, m = ad.step(state, batch)  # warm the compile outside capture
    jax.block_until_ready(m)
    jnl = Journal(str(out / "journal.jsonl"))
    state, recs = obs_trace.trace_steps(
        ad.step, state, batch, steps=3, first_step=1,
        logdir=str(out / "profile"), flops_per_step=1e6, journal=jnl,
    )
    measured = obs_trace.measured_collective_bytes(ad, rng, batch)
    with obs_journal.as_default(jnl):
        est = obs_comms.comm_profile(ad, rng, batch)
    xc = obs_trace.crosscheck_collectives(
        measured, est["per_device"], journal=jnl)
    jnl.close()
    return {"dir": str(out), "recs": recs, "measured": measured,
            "est": est, "xc": xc}


def test_capture_produces_per_step_attribution(traced_run):
    recs = traced_run["recs"]
    assert [r["step"] for r in recs] == [1, 2, 3]
    for r in recs:
        assert r["wall_s"] > 0
        assert r["n_ops"] > 0  # the window contains device work (fenced)
        assert 0 <= r["compute_s"] <= r["wall_s"] + 1e-9
        assert 0 <= r["collective_s"] <= r["wall_s"] + 1e-9
        assert r["exposed_collective_s"] <= r["collective_s"] + 1e-9
        assert r["measured_mfu"] > 0


def test_capture_sees_dp_collectives(traced_run):
    # dp on 8 devices all-reduces grads: the timeline must show it
    assert any(r["collective_s"] > 0 for r in traced_run["recs"])
    assert any("all-reduce" in (r.get("collectives") or {})
               for r in traced_run["recs"])


def test_trace_journal_events(traced_run):
    events = Journal.read(os.path.join(traced_run["dir"], "journal.jsonl"))
    steps = [e for e in events if e.get("name") == "trace.step"]
    assert len(steps) == 3
    assert all(e.get("trace", "").endswith(".json.gz") for e in steps)
    colls = [e for e in events if e.get("name") == "trace.collective"]
    assert colls


def test_measured_vs_modeled_within_2x(traced_run):
    xc = {c["category"]: c for c in traced_run["xc"]}
    ar = xc["grad_allreduce"]
    assert ar["measured_bytes"] > 0 and ar["modeled_bytes"] > 0
    assert ar["within_2x"]
    # on the bench config the planner's ring math matches the
    # executable payload exactly
    assert ar["ratio"] == pytest.approx(1.0, rel=0.05)


def test_exposed_fraction_from_real_trace(traced_run):
    f = obs_trace.exposed_fraction(traced_run["recs"])
    assert f is None or 0.0 <= f <= 1.0


def test_report_renders_trace_sections(traced_run):
    rep = obs_report.generate(traced_run["dir"])
    assert rep["trace"]["n_steps"] == 3
    assert rep["trace"]["mean_wall_s"] > 0
    tc = {e["category"]: e for e in rep["trace_collectives"]}
    assert tc["grad_allreduce"]["within_2x"]
    text = obs_report.format_report(rep)
    assert "trace:" in text
    assert "exposed-comm crosscheck" in text


# --------------------------------------------- trainer instrumentation


def test_trainer_trace_every_n(tmp_path, devices8):
    from torch_automatic_distributed_neural_network_tpu.data.synthetic import (
        SyntheticClassification,
    )
    from torch_automatic_distributed_neural_network_tpu.training import (
        Trainer,
        TrainerConfig,
    )

    jnl = Journal(str(tmp_path / "journal.jsonl"))
    trainer = Trainer(
        make_ad("dp"),
        TrainerConfig(steps=5, log_every=0, trace_every_n=3,
                      trace_dir=str(tmp_path / "profile"),
                      preflight=False),
        journal=jnl,
    )
    trainer.fit(SyntheticClassification(batch_size=16))
    jnl.close()
    events = Journal.read(str(tmp_path / "journal.jsonl"))
    steps = [e for e in events if e.get("name") == "trace.step"]
    # steps=5 from start=0: only i=3 matches (i != start, (i-start)%3==0)
    assert [e["step"] for e in steps] == [3]
    # the traced step's wall time lands in the trace bucket, not goodput
    assert trainer.goodput["seconds"]["trace"] > 0
    assert trainer.goodput["seconds"]["step"] > 0


def test_trainer_trace_failure_falls_back(tmp_path, devices8, monkeypatch):
    from torch_automatic_distributed_neural_network_tpu.data.synthetic import (
        SyntheticClassification,
    )
    from torch_automatic_distributed_neural_network_tpu.training import (
        Trainer,
        TrainerConfig,
    )

    def boom(*a, **k):
        raise RuntimeError("no profiler here")

    monkeypatch.setattr(obs_trace, "trace_steps", boom)
    jnl = Journal(None)
    trainer = Trainer(
        make_ad("dp"),
        TrainerConfig(steps=4, log_every=0, trace_every_n=2,
                      preflight=False),
        journal=jnl,
    )
    trainer.fit(SyntheticClassification(batch_size=16))  # must not raise
    errs = [e for e in jnl.records if e.get("name") == "trace.error"]
    assert errs and "no profiler here" in errs[0]["error"]


# --------------------------------------------------- multihost merging


def _write_host_journal(path, host, wall_s, n=4):
    j = Journal(str(path), host0_only=False, meta={"host": host})
    for k in range(n):
        j.event("trace.step", step=k, wall_s=wall_s)
    j.close()


def test_multihost_merge_and_skew(tmp_path):
    # seeded skew: host 1 is 30% slower than host 0
    _write_host_journal(tmp_path / "journal.host0.jsonl", 0, 0.010)
    _write_host_journal(tmp_path / "journal.host1.jsonl", 1, 0.013)
    merged_path = aggregate.merge_run(str(tmp_path))
    assert merged_path.endswith("journal.merged.jsonl")
    records = Journal.read(merged_path)
    assert {r["host"] for r in records} == {0, 1}
    walls = [r.get("wall") or 0.0 for r in records]
    assert walls == sorted(walls)  # interleaved on the shared clock
    skew = aggregate.host_skew(records)
    assert skew["n_hosts"] == 2
    assert skew["per_host"][0]["mean"] == pytest.approx(0.010)
    assert skew["per_host"][1]["mean"] == pytest.approx(0.013)
    assert skew["skew_fraction"] == pytest.approx(0.3, rel=1e-6)
    # a re-merge must not ingest the merged file itself
    assert len(Journal.read(aggregate.merge_run(str(tmp_path)))) == \
        len(records)


def test_report_prefers_merged_journal_and_shows_hosts(tmp_path, capsys):
    _write_host_journal(tmp_path / "journal.host0.jsonl", 0, 0.010)
    _write_host_journal(tmp_path / "journal.host1.jsonl", 1, 0.015)
    rc = cli.main(["report", str(tmp_path), "--merge"])
    assert rc == 0
    out = capsys.readouterr().out
    assert "journal.merged.jsonl" in out
    assert "hosts: 2" in out
    assert "straggler" in out  # 50% skew > the 10% callout threshold


def test_host_skew_needs_two_hosts():
    assert aggregate.host_skew(
        [{"name": "trace.step", "host": 0, "wall_s": 0.01}]) is None


# ------------------------------------------------- journal hardening


def test_journal_rotation_caps_file(tmp_path):
    path = str(tmp_path / "journal.jsonl")
    j = Journal(path, max_bytes=600)
    for k in range(40):
        j.event("tick", k=k, pad="x" * 40)
    j.close()
    assert j.rotations >= 1
    assert os.path.exists(path + ".1")
    assert os.path.getsize(path + ".1") < 1200  # capped, not unbounded
    records = Journal.read(path)
    assert any(r.get("name") == "journal.rotated" for r in records)


def test_journal_rotation_env(tmp_path, monkeypatch):
    monkeypatch.setenv("TADNN_JOURNAL_MAX_BYTES", "500")
    j = Journal(str(tmp_path / "j.jsonl"))
    assert j._max_bytes == 500
    j.close()


def test_reader_skips_torn_lines_with_one_warning(tmp_path):
    path = str(tmp_path / "torn.jsonl")
    with open(path, "w") as f:
        f.write(json.dumps({"kind": "event", "name": "a"}) + "\n")
        f.write('{"kind": "event", "name": "b", "tr\n')  # torn mid-write
        f.write("42\n")  # non-dict JSON is torn too
        f.write(json.dumps({"kind": "event", "name": "c"}) + "\n")
    with pytest.warns(UserWarning, match="2 torn/corrupt"):
        records = Journal.read(path)
    assert [r["name"] for r in records] == ["a", "c"]
    with warnings.catch_warnings():
        warnings.simplefilter("error")  # second read: silent
        assert len(Journal.read(path)) == 2


# ----------------------------------------------- cost-model feedback


def test_cost_measured_overlap_shrinks_comm():
    params = {"big": {"kernel": np.zeros((512, 512), np.float32)}}
    topo = topology.Topology(num_devices=8, num_hosts=1,
                             platform="tpu", device_kind="v5p")
    cand = tune.Candidate("dp", (("data", 8),))
    base = tune.cost.score(params, topo, cand)
    fed = tune.cost.score(params, topo, cand, measured_overlap=0.25)
    assert fed.step_time_s < base.step_time_s
    assert fed.breakdown["measured_overlap"] == 0.25
    # fully-hidden comms: only latency remains of the comm terms
    hidden = tune.cost.score(params, topo, cand, measured_overlap=0.0)
    assert hidden.step_time_s <= fed.step_time_s


def test_overlap_from_trace_roundtrip():
    f = tune.cost.overlap_from_trace(
        [{"collective_s": 2.0, "exposed_collective_s": 1.0}])
    assert f == pytest.approx(0.5)
