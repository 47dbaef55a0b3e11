"""Whole runs at rehearsal size (CPU, tiny model): the result line, the
refusal without a TPU, the control that must come out not correct, and the
timed path broken underneath, which must flip ``correct`` to false."""
import importlib.util
import json
import os
import subprocess
import sys

import pytest

from lib import harness

BENCH = harness.load_json(os.path.join(harness.REPO, "BENCHMARK.json"))
CELLS = [w["name"] for w in BENCH["workloads"]]
RUN = os.path.join(harness.BENCH_DIR, "run.py")
ENV = {**os.environ, "JAX_PLATFORMS": "cpu"}


def cell_of_kind(kind: str) -> str:
    for name in CELLS:
        if harness.Cell(name, BENCH).mix["kind"] == kind:
            return name
    pytest.skip(f"no cell of kind {kind}")


def run_cli(*args, timeout=600):
    return subprocess.run([sys.executable, RUN, *args], env=ENV,
                          cwd=harness.REPO, capture_output=True, text=True,
                          timeout=timeout)


def last_json(stdout: str) -> dict:
    return json.loads(stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("trace", ["0", "1"])
def test_rehearsal_ends_in_the_contracts_line(cell, trace):
    p = run_cli("--workload", cell, "--seed", str(2**31 + 17), "--seconds",
                "2", "--trace", trace, "--rehearsal")
    assert p.returncode == 0, p.stderr[-2000:]
    lines = p.stdout.strip().splitlines()
    assert json.loads(lines[0])["rehearsal"] is True
    res = last_json(p.stdout)
    assert set(res) == {"correct", "attempted", "failed", "metrics", "device"}
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 0
    assert res["metrics"] == {}  # a rehearsal prints no device metric
    assert set(res["device"]) == {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    assert res["device"]["platform"] == "cpu"
    checks = [json.loads(l) for l in lines if l.startswith('{"check"')]
    assert checks and all({"value", "limit", "ok"} <= set(c) for c in checks)
    assert any(c["check"] == "compiles_in_window" and c["value"] == 0
               for c in checks)


def test_without_a_tpu_it_refuses_and_prints_no_result():
    p = run_cli("--workload", CELLS[0], "--seed", "1", "--seconds", "1",
                "--trace", "0")
    assert p.returncode == 2
    assert '"correct"' not in p.stdout


@pytest.mark.parametrize("kind", ["train", "serve"])
@pytest.mark.parametrize("seed", ["11", "12", "14"])
def test_the_lower_precision_control_is_not_correct(kind, seed):
    """The reference in fp8, in the program's place, against the limits of
    the rehearsal size: exit 0 means the verdict was NOT correct."""
    p = run_cli("--workload", cell_of_kind(kind), "--seed", seed,
                "--seconds", "3", "--rehearsal", "--control")
    assert p.returncode == 0, p.stdout[-1500:] + p.stderr[-1500:]
    res = last_json(p.stdout)
    assert res["control"] is True and res["correct"] is False
    if kind == "serve":  # the same run's own tokens passed
        assert res["program_correct"] is True


def load_run():
    spec = importlib.util.spec_from_file_location("bench_run_under_test", RUN)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_a_step_that_returns_its_state_unchanged_is_not_correct(
        monkeypatch, capsys):
    from torch_automatic_distributed_neural_network_tpu.core import (
        AutoDistribute,
    )

    def stuck(self, state, batch):  # reports a loss, trains nothing
        return state, self.eval_step(state, batch)

    monkeypatch.setattr(AutoDistribute, "step", stuck)
    rc = load_run().main(["--workload", cell_of_kind("train"), "--seed", "21",
                          "--seconds", "1", "--trace", "0", "--rehearsal"])
    res = last_json(capsys.readouterr().out)
    assert rc == 0 and res["correct"] is False


def test_a_served_token_altered_where_it_is_made_is_not_correct(
        monkeypatch, capsys):
    from torch_automatic_distributed_neural_network_tpu.inference.serve import (
        engine,
    )

    real = engine._sample

    def off_by_one(logits, rng, cfg):
        return (real(logits, rng, cfg) + 1) % logits.shape[-1]

    monkeypatch.setattr(engine, "_sample", off_by_one)
    rc = load_run().main(["--workload", cell_of_kind("serve"), "--seed", "22",
                          "--seconds", "2", "--trace", "0", "--rehearsal"])
    res = last_json(capsys.readouterr().out)
    assert rc == 0 and res["correct"] is False
