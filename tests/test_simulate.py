"""Fleet-scale what-if planner tests (tune/simulate + tune/slo +
restart-survival math): SLO parsing/ranking known answers, analytic
survival pins, deterministic traffic sampling, the discrete-event serve
replay, degenerate 1-chip sweeps, and the `tadnn simulate` CLI — all
device-free."""

import json
import math
import types

import numpy as np
import pytest

from torch_automatic_distributed_neural_network_tpu import cli, topology
from torch_automatic_distributed_neural_network_tpu.obs import (
    report as obs_report,
)
from torch_automatic_distributed_neural_network_tpu.training.resilience import (
    survival_probability,
    window_budget_exhausted,
)
from torch_automatic_distributed_neural_network_tpu.tune import (
    simulate as sim_mod,
)
from torch_automatic_distributed_neural_network_tpu.tune.simulate import (
    SimulatePolicy,
    TrafficMix,
    replay_serve,
)
from torch_automatic_distributed_neural_network_tpu.tune.slo import (
    SLOSpec,
    rank,
)


# ---------------------------------------------------------------- slo


def test_slo_parse_known_answer():
    spec = SLOSpec.parse(
        "tok_s_chip>=40, p99_ms<=2500, headroom>=0.1, survival>=0.9")
    assert spec.min_tok_s_per_chip == 40.0
    assert spec.max_p99_s == pytest.approx(2.5)  # ms -> s
    assert spec.min_hbm_headroom_frac == pytest.approx(0.1)
    assert spec.min_survival == pytest.approx(0.9)


def test_slo_parse_empty_means_dont_care():
    spec = SLOSpec.parse("")
    assert spec == SLOSpec()
    ok, violations = spec.evaluate({})
    assert ok and violations == []


def test_slo_parse_rejects_unknown_field_and_wrong_comparator():
    with pytest.raises(ValueError, match="unknown SLO field"):
        SLOSpec.parse("tokens>=40")
    with pytest.raises(ValueError, match="takes >="):
        SLOSpec.parse("tok_s_chip<=40")
    with pytest.raises(ValueError, match="no >= or <="):
        SLOSpec.parse("tok_s_chip=40")


def test_slo_evaluate_missing_metric_is_a_violation():
    spec = SLOSpec.parse("tok_s_chip>=40")
    ok, violations = spec.evaluate({"tok_s_per_chip": None})
    assert not ok and "no prediction" in violations[0]


def test_slo_evaluate_memory_fit_is_always_checked():
    ok, violations = SLOSpec().evaluate({"fits": False})
    assert not ok and "memory" in violations[0]


def test_slo_ranking_known_answer():
    # pass beats fail regardless of throughput; among passes higher
    # tok/s wins; among fails fewer violations win.
    preds = [
        {"name": "fast_but_fails", "tok_s_per_chip": 900.0,
         "p99_s": 10.0, "hbm_headroom_frac": 0.0, "step_time_s": 0.1},
        {"name": "slow_pass", "tok_s_per_chip": 50.0, "p99_s": 1.0,
         "hbm_headroom_frac": 0.5, "step_time_s": 0.3},
        {"name": "fast_pass", "tok_s_per_chip": 80.0, "p99_s": 1.0,
         "hbm_headroom_frac": 0.5, "step_time_s": 0.2},
        {"name": "fails_less", "tok_s_per_chip": 900.0, "p99_s": 10.0,
         "hbm_headroom_frac": 0.5, "step_time_s": 0.1},
    ]
    spec = SLOSpec.parse("tok_s_chip>=40,p99_ms<=2000,headroom>=0.1")
    ranked = rank(preds, spec)
    assert [p["name"] for p in ranked] == [
        "fast_pass", "slow_pass", "fails_less", "fast_but_fails"]
    assert ranked[0]["slo_ok"] and not ranked[2]["slo_ok"]
    assert len(ranked[2]["slo_violations"]) < len(
        ranked[3]["slo_violations"])


# ---------------------------------------------------- restart survival


def test_window_budget_exhausted():
    # 2 restarts per rolling hour: the third failure inside one window
    # exhausts the budget, spread-out failures never do.
    assert not window_budget_exhausted([0.0, 1800.0],
                                       max_restarts=2, window_s=3600.0)
    assert window_budget_exhausted([0.0, 1800.0, 3599.0],
                                   max_restarts=2, window_s=3600.0)
    assert not window_budget_exhausted([0.0, 3601.0, 7202.0],
                                       max_restarts=2, window_s=3600.0)
    assert not window_budget_exhausted([], max_restarts=0,
                                       window_s=3600.0)
    assert window_budget_exhausted([5.0], max_restarts=0,
                                   window_s=3600.0)


def test_survival_zero_rate_is_certain():
    assert survival_probability(rate_per_hour=0.0,
                                mission_hours=24.0) == 1.0
    assert survival_probability(rate_per_hour=5.0,
                                mission_hours=0.0) == 1.0


def test_survival_analytic_poisson_pins():
    # window >= mission makes the rolling window global, so survival is
    # the exact Poisson CDF P(N <= max_restarts).
    # max_restarts=0: P(no failure) = e^-lambda.
    lam = 1.5
    got = survival_probability(rate_per_hour=lam, mission_hours=1.0,
                               max_restarts=0, window_s=3600.0)
    assert got == pytest.approx(math.exp(-lam), rel=1e-9)
    # rate 2/h over 1h with budget 2: (1 + 2 + 2) e^-2 = 5 e^-2.
    got = survival_probability(rate_per_hour=2.0, mission_hours=1.0,
                               max_restarts=2, window_s=3600.0)
    assert got == pytest.approx(5.0 * math.exp(-2.0), rel=1e-9)


def test_survival_monte_carlo_brackets_analytic():
    # Rolling window shorter than the mission -> MC path.  Survival
    # must be deterministic per seed and bounded by the analytic
    # global-window answer (global window can only be stricter).
    kw = dict(rate_per_hour=2.0, mission_hours=4.0, max_restarts=2)
    a = survival_probability(window_s=3600.0, **kw)
    b = survival_probability(window_s=3600.0, **kw)
    assert a == b
    global_window = survival_probability(window_s=4 * 3600.0, **kw)
    assert global_window <= a <= 1.0


# ------------------------------------------------------------- traffic


def test_traffic_parse_aliases_and_errors():
    mix = TrafficMix.parse("rate=8,n=16,prompt=64,max_new=32,decode=24")
    assert mix.rate_per_s == 8.0 and mix.n_requests == 16
    assert mix.prompt_mean == 64 and mix.max_new == 32
    assert mix.decode_mean == 24
    with pytest.raises(ValueError, match="unknown traffic field"):
        TrafficMix.parse("qps=8")
    with pytest.raises(ValueError, match="not name=value"):
        TrafficMix.parse("rate:8")


def test_traffic_sample_deterministic_and_clamped():
    mix = TrafficMix(rate_per_s=100.0, n_requests=32, prompt_mean=300,
                     max_new=128, jitter=0.5, seed=3)
    a = mix.sample(max_len=64)
    assert a == mix.sample(max_len=64)
    arrivals = [r[0] for r in a]
    assert arrivals == sorted(arrivals) and len(a) == 32
    for _, n_prompt, max_new, n_decode in a:
        assert 1 <= n_prompt <= 63
        assert 1 <= max_new <= 64 - n_prompt
        assert 1 <= n_decode <= max_new


def test_traffic_zero_jitter_is_exact():
    mix = TrafficMix(rate_per_s=0.0, n_requests=4, prompt_mean=10,
                     max_new=6, jitter=0.0)
    assert mix.sample(max_len=64) == [(0.0, 10, 6, 6)] * 4


# -------------------------------------------------------- serve replay


def test_replay_serve_finishes_simple_batch():
    reqs = [(0.0, 8, 8, 8) for _ in range(6)]
    out = replay_serve(reqs, n_slots=4, block_size=8, max_len=32,
                       decode_step_s=1e-3, prefill_chunk_s=1e-3)
    assert out["n_finished"] == 6 and not out["stalled"]
    # every request decodes exactly n_decode tokens
    assert out["new_tokens"] == 6 * 8
    assert out["tokens_per_s"] > 0 and out["wall_s"] > 0
    assert 0.0 < out["mean_occupancy"] <= 1.0
    assert out["p99_s"] >= out["p50_s"] > 0


def test_replay_serve_optimistic_preempts_under_pressure():
    # a pool sized for far fewer tokens than optimistic admission lets
    # in forces decode-time preemption; reserve admission never does.
    reqs = [(0.0, 4, 24, 24) for _ in range(4)]
    kw = dict(n_slots=4, block_size=4, max_len=32, num_blocks=13,
              prefill_chunk=None)
    opt = replay_serve(reqs, admission="optimistic", **kw)
    res = replay_serve(reqs, admission="reserve", **kw)
    assert opt["preemptions"] > 0
    assert res["preemptions"] == 0
    assert opt["n_finished"] == res["n_finished"] == 4


# ------------------------------------------------------------ simulate


def _tiny_cfg():
    return types.SimpleNamespace(n_layers=2, kv_heads=4, head_dim=32)


def _tiny_params(d=64, vocab=256):
    class Shape:
        def __init__(self, *shape):
            self.shape = shape
            self.dtype = np.float32
    return {
        "embed": {"embedding": Shape(vocab, d)},
        "h0": {"attn": {"kernel": Shape(d, d)},
               "mlp": {"kernel": Shape(d, 4 * d)}},
        "head": {"kernel": Shape(d, vocab)},
    }


def test_simulate_sweep_end_to_end():
    traffic = TrafficMix(rate_per_s=64.0, n_requests=24, prompt_mean=16,
                         max_new=16)
    report = sim_mod.simulate(
        _tiny_params(), ["v5p-16"], model_cfg=_tiny_cfg(),
        policy=SimulatePolicy(use_cache=False, preemption_rate_per_h=0.05),
        traffic=traffic,
        slo=SLOSpec.parse("tok_s_chip>=1,headroom>=0.05,survival>=0.2"))
    assert report["n_candidates"] >= 200  # acceptance floor
    assert report["cache"] == "off"
    assert set(report["topologies"]) >= {"v5p-16", "v5p-8x2", "v5p-4x4"}
    top = report["predictions"][0]
    for field in ("topology", "plan", "admission", "mfu", "step_time_s",
                  "hbm_headroom_frac", "survival", "tok_s_per_chip",
                  "p99_s", "mean_occupancy", "slo_ok"):
        assert field in top, field
    assert top["slo_ok"] and top["fits"]
    assert 0.0 < top["survival"] < 1.0  # preemption rate bites
    ranked = report["predictions"]
    assert all(ranked[i]["slo_ok"] >= ranked[i + 1]["slo_ok"]
               for i in range(len(ranked) - 1))


def test_simulate_degenerate_single_chip():
    report = sim_mod.simulate(
        _tiny_params(), ["v5p-1"], model_cfg=_tiny_cfg(),
        policy=SimulatePolicy(use_cache=False),
        traffic=TrafficMix(n_requests=8, prompt_mean=8, max_new=8),
        slo=SLOSpec())
    assert report["n_candidates"] >= 1
    top = report["predictions"][0]
    assert top["num_devices"] == 1 and top["topology"] == "v5p-1"
    assert top["tok_s_per_chip"] is not None


def test_simulate_cache_roundtrip(tmp_path):
    kw = dict(model_cfg=_tiny_cfg(),
              policy=SimulatePolicy(),
              traffic=TrafficMix(n_requests=8, prompt_mean=8, max_new=8),
              slo=SLOSpec(), cache_path=str(tmp_path / "sim.jsonl"))
    first = sim_mod.simulate(_tiny_params(), ["v5p-8"], **kw)
    second = sim_mod.simulate(_tiny_params(), ["v5p-8"], **kw)
    assert first["cache"] == "miss" and second["cache"] == "hit"
    assert second["predictions"][0]["plan"] == \
        first["predictions"][0]["plan"]
    # different SLO -> different key -> miss
    third = sim_mod.simulate(
        _tiny_params(), ["v5p-8"],
        **{**kw, "slo": SLOSpec.parse("tok_s_chip>=1")})
    assert third["cache"] == "miss"


def test_simulate_rejects_unknown_sku():
    with pytest.raises(ValueError, match="unknown"):
        sim_mod.simulate(
            _tiny_params(), ["v9z-16"], model_cfg=_tiny_cfg(),
            policy=SimulatePolicy(use_cache=False),
            traffic=TrafficMix(), slo=SLOSpec())


# ----------------------------------------------------------------- cli


def test_cli_simulate_smoke(tmp_path, capsys):
    out_path = tmp_path / "sim.json"
    rc = cli.main([
        "simulate", "--topology", "v5p-16", "--family", "gpt2",
        "--size", "test", "--seq", "64", "--batch", "1",
        "--traffic", "rate=32,n=16,prompt=16,max_new=16",
        "--slo", "tok_s_chip>=1", "--no-cache",
        "--journal", str(tmp_path / "journal.jsonl"),
        "--out", str(out_path)])
    assert rc == 0
    report = json.loads(out_path.read_text())
    assert report["n_candidates"] >= 200
    assert report["predictions"][0]["slo_ok"]
    # the journal carries the decision for `tadnn report`
    events = [json.loads(ln) for ln in
              (tmp_path / "journal.jsonl").read_text().splitlines()]
    names = {e.get("name") for e in events}
    assert {"simulate.sweep", "simulate.candidate",
            "simulate.decision"} <= names
    rendered = obs_report.format_report(
        obs_report.generate(str(tmp_path)))
    assert "simulate:" in rendered and "meet the SLO" in rendered


def test_cli_simulate_bad_slo_exits_2(capsys):
    rc = cli.main([
        "simulate", "--topology", "v5p-8", "--family", "gpt2",
        "--size", "test", "--seq", "64", "--batch", "1",
        "--slo", "bogus>=1", "--no-cache"])
    assert rc == 2
    assert "unknown SLO field" in capsys.readouterr().err


def test_cli_tune_simulate_delegates(capsys):
    rc = cli.main([
        "tune", "--family", "gpt2", "--size", "test", "--seq", "64",
        "--batch", "1", "--simulate", "v5p-8",
        "--traffic", "rate=32,n=8,prompt=8,max_new=8",
        "--slo", "tok_s_chip>=1", "--no-cache", "--json"])
    assert rc == 0
    report = json.loads(capsys.readouterr().out)
    assert report["topologies"][0] == "v5p-8"
