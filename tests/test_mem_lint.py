"""Memory (ML00x) & dtype (DT00x) lint tests: the liveness estimator,
budget findings, dtype-flow rules, tuner profile pruning, suppression,
the `tadnn check --memory` CLI, trainer preflight budgets, and the
committed bench-model snapshot (tests/data/mem_estimate_reference.json).

Everything runs on the 8 simulated CPU devices from conftest.py.
"""

import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax import shard_map
from jax.sharding import PartitionSpec as P

import torch_automatic_distributed_neural_network_tpu as tad
from torch_automatic_distributed_neural_network_tpu import (
    analysis,
    cli,
    topology,
)
from torch_automatic_distributed_neural_network_tpu.analysis import (
    dtype_lint,
    mem_lint,
    plan_lint,
)
from torch_automatic_distributed_neural_network_tpu.models import MLP
from torch_automatic_distributed_neural_network_tpu.obs import Journal
from torch_automatic_distributed_neural_network_tpu.obs import (
    journal as obs_journal,
)
from torch_automatic_distributed_neural_network_tpu.training import (
    Trainer,
    TrainerConfig,
    softmax_xent_loss,
)
from torch_automatic_distributed_neural_network_tpu.tune import (
    space as tune_space,
)

REF_PATH = pathlib.Path(__file__).parent / "data" / "mem_estimate_reference.json"
REF = json.loads(REF_PATH.read_text())


def codes(findings):
    return [f.code for f in findings]


def sds(*shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype)


def _batch(n=64, d=8, seed=0):
    rng = np.random.RandomState(seed)
    return {
        "x": jnp.asarray(rng.randn(n, d), jnp.float32),
        "label": jnp.asarray(rng.randint(0, 10, size=(n,))),
    }


def _small_ad(strategy="fsdp", grad_accum=1):
    return tad.AutoDistribute(
        MLP(features=(32, 10)), optimizer=optax.adamw(1e-3),
        loss_fn=softmax_xent_loss, strategy=strategy, grad_accum=grad_accum)


def _synthetic_est(peak, act, *, remat=True):
    rest = peak - act
    return mem_lint.MemEstimate(
        params_bytes=rest, optimizer_bytes=0, model_state_bytes=0,
        batch_bytes=0, activation_bytes=act, peak_bytes=peak,
        strategy="fsdp", degrees={"fsdp": 8}, grad_accum=1, remat=remat,
        transient_by_class={})


# ---------------------------------------------------------------------------
# size parsing / budget resolution
# ---------------------------------------------------------------------------


class TestParseSize:
    @pytest.mark.parametrize("text,expect", [
        ("16GiB", 16 * 2**30),
        ("2MiB", 2 * 2**20),
        ("1KiB", 1024),
        ("32GB", 32 * 10**9),
        ("1500MB", 1500 * 10**6),
        ("4K", 4096),
        ("95 GiB", 95 * 2**30),
        ("512", 512),
        ("1.5GiB", int(1.5 * 2**30)),
    ])
    def test_units(self, text, expect):
        assert topology.parse_size(text) == expect

    def test_numeric_passthrough(self):
        assert topology.parse_size(8589934592) == 8589934592
        assert topology.parse_size(1.5e9) == 1500000000

    @pytest.mark.parametrize("bad", ["banana", "GiB", "", "12XB"])
    def test_unparseable_raises(self, bad):
        with pytest.raises(ValueError):
            topology.parse_size(bad)

    def test_resolve_budget(self):
        assert mem_lint.resolve_budget(1024) == 1024
        assert mem_lint.resolve_budget("2MiB") == 2 * 2**20


# ---------------------------------------------------------------------------
# the liveness estimator
# ---------------------------------------------------------------------------


class TestEstimator:
    def test_sharded_tree_bytes(self):
        tree = {"w": sds(8, 4), "b": sds(8, 4)}
        specs = {"w": P("fsdp", None), "b": P(None, None)}
        per_dev, total = mem_lint.sharded_tree_bytes(
            tree, specs, {"fsdp": 8})
        assert total == 2 * 8 * 4 * 4
        # 'w' sharded 8-way, 'b' replicated in full
        assert per_dev == 8 * 4 * 4 // 8 + 8 * 4 * 4

    def test_estimate_has_consistent_breakdown(self, devices8):
        ad = _small_ad()
        findings, rep = analysis.memory_check(
            ad, _batch(), rng=jax.random.key(0), budget="16GiB",
            compiled=False)
        assert rep["peak_bytes"] == (
            rep["params_bytes"] + rep["optimizer_bytes"]
            + rep["model_state_bytes"] + rep["batch_bytes"]
            + rep["activation_bytes"])
        assert rep["params_bytes"] > 0 and rep["activation_bytes"] > 0
        # adamw: two f32 moments mirroring the sharded param tree
        assert rep["optimizer_bytes"] == pytest.approx(
            2 * rep["params_bytes"], rel=0.05)
        assert rep["strategy"] == "fsdp" and rep["degrees"] == {"fsdp": 8}
        assert not [f for f in findings if f.layer == "mem"]

    def test_grad_accum_shrinks_transient(self, devices8):
        reps = {}
        for ga in (1, 4):
            _, reps[ga] = analysis.memory_check(
                _small_ad(grad_accum=ga), _batch(),
                rng=jax.random.key(0), budget="16GiB", compiled=False)
        assert reps[4]["activation_bytes"] < reps[1]["activation_bytes"]
        assert reps[4]["grad_accum"] == 4

    def test_literal_outputs_are_tolerated(self):
        # a jaxpr whose outvars include a (unhashable) Literal constant
        # — the gpt2 train step does this via a constant metric
        closed = jax.make_jaxpr(
            lambda x: ((x * 2).sum(), 1.0))(jnp.ones((4,)))
        prof = mem_lint.activation_profile_from_trace(closed, {}, None)
        assert prof["peak_bytes"] == 4 * 4  # the x*2 intermediate

    def test_persistent_only_without_trace(self, devices8):
        ad = _small_ad()
        ad.build_plan(jax.random.key(0), _batch())
        state_abs = jax.eval_shape(ad._make_state_fn(_batch()),
                                   jax.random.key(0))
        est = mem_lint.estimate_step_memory(
            None, ad.plan, state_abs.params,
            opt_state=state_abs.opt_state)
        assert est.activation_bytes == 0
        assert est.peak_bytes == est.params_bytes + est.optimizer_bytes


# ---------------------------------------------------------------------------
# ML00x findings
# ---------------------------------------------------------------------------


class TestMemFindings:
    def test_over_budget_is_ml001_error(self):
        fs = mem_lint.lint_memory(
            _synthetic_est(1000, 200), budget_bytes=500)
        assert codes(fs) == ["ML001"]
        assert fs[0].severity == analysis.ERROR
        assert "OOM" in fs[0].msg and analysis.exit_code(fs) == 1

    def test_headroom_margin_is_ml002_warn(self):
        fs = mem_lint.lint_memory(
            _synthetic_est(950, 200), budget_bytes=1000, headroom=0.1)
        assert codes(fs) == ["ML002"]
        assert fs[0].severity == analysis.WARN

    def test_headroom_is_configurable(self):
        est = _synthetic_est(950, 200)
        assert codes(mem_lint.lint_memory(
            est, budget_bytes=1000, headroom=0.0)) == []
        assert codes(mem_lint.lint_memory(
            est, budget_bytes=1000, headroom=0.3)) == ["ML002"]

    def test_activation_dominated_no_remat_adds_ml003(self):
        fs = mem_lint.lint_memory(
            _synthetic_est(1000, 800, remat=False), budget_bytes=500)
        assert codes(fs) == ["ML001", "ML003"]
        # with remat already on there is nothing to suggest
        fs = mem_lint.lint_memory(
            _synthetic_est(1000, 800, remat=True), budget_bytes=500)
        assert codes(fs) == ["ML001"]

    def test_real_model_oom_end_to_end(self, devices8):
        findings, rep = analysis.memory_check(
            _small_ad(), _batch(), rng=jax.random.key(0),
            budget=1024, compiled=False)
        assert "ML001" in codes(findings)
        assert rep["budget_bytes"] == 1024
        assert analysis.exit_code(findings) == 1


# ---------------------------------------------------------------------------
# DT00x dtype-flow lint
# ---------------------------------------------------------------------------


class TestDtypeLint:
    def test_scalar_downcast_is_dt001(self):
        closed = jax.make_jaxpr(
            lambda x: jnp.sum(x * x).astype(jnp.bfloat16))(jnp.ones((8, 4)))
        fs = dtype_lint.lint_dtypes(closed)
        assert "DT001" in codes(fs)

    def test_reduction_downcast_is_dt001_unless_compute_dtype(self):
        closed = jax.make_jaxpr(
            lambda a, b: (a @ b).astype(jnp.bfloat16))(
                jnp.ones((8, 4)), jnp.ones((4, 8)))
        assert "DT001" in codes(dtype_lint.lint_dtypes(closed))
        # casting to the configured mixed-precision compute dtype is
        # the policy, not a finding
        assert codes(dtype_lint.lint_dtypes(
            closed, compute_dtype=jnp.bfloat16)) == []

    def test_f16_matmul_is_dt002_bf16_exempt(self):
        h = jnp.ones((8, 8), jnp.float16)
        fs = dtype_lint.lint_dtypes(jax.make_jaxpr(lambda a, b: a @ b)(h, h))
        assert codes(fs) == ["DT002"]
        bf = jnp.ones((8, 8), jnp.bfloat16)
        fs = dtype_lint.lint_dtypes(
            jax.make_jaxpr(lambda a, b: a @ b)(bf, bf))
        assert codes(fs) == []

    def test_weak_type_into_collective_is_dt003(self, devices8):
        mesh = jax.make_mesh((8,), ("d",))
        f = shard_map(lambda x: jax.lax.psum(x, "d"), mesh=mesh,
                      in_specs=P(), out_specs=P(), check_vma=False)
        # tracing with a Python float keeps the operand weak-typed
        fs = dtype_lint.lint_dtypes(jax.make_jaxpr(f)(2.0))
        assert "DT003" in codes(fs)

    def test_mixed_param_dtypes_is_dt004(self):
        fs = dtype_lint.lint_param_dtypes({
            "a": sds(4, 4), "b": sds(4, 4),
            "head": sds(4, 2, dtype=jnp.bfloat16),
        })
        assert codes(fs) == ["DT004"]
        assert "head" in fs[0].where and "bfloat16" in fs[0].msg
        assert dtype_lint.lint_param_dtypes(
            {"a": sds(4, 4), "b": sds(4, 4)}) == []

    def test_clean_train_step_has_no_dtype_findings(self, devices8):
        findings, _ = analysis.memory_check(
            _small_ad(), _batch(), rng=jax.random.key(0),
            budget="16GiB", compiled=False)
        assert not [f for f in findings if f.layer == "dtype"]


# ---------------------------------------------------------------------------
# tuner: liveness profile replaces the coarse heuristic
# ---------------------------------------------------------------------------


class TestTunerProfile:
    def _profile_and_params(self):
        ad = _small_ad()
        prof = ad.activation_profile(jax.random.key(0), _batch())
        abstract = jax.eval_shape(
            lambda r: ad._split_variables(ad._init_variables(r, _batch()))[0],
            jax.random.key(0))
        return prof, abstract

    def test_activation_profile_shape(self, devices8):
        prof, _ = self._profile_and_params()
        assert prof["batch_items"] == 64
        for variant in ("noremat", "remat"):
            assert prof[variant]["peak_bytes"] > 0
        assert prof["noremat"]["batch_bytes"] > 0

    def test_profiled_activation_bytes_rescales(self):
        prof = {"batch_items": 100,
                "noremat": {"batch_bytes": 1000, "param_like_bytes": 400,
                            "other_bytes": 10}}
        got = tune_space._profiled_activation_bytes(
            prof, 50, remat=False, param_frac=0.25)
        assert got == 1000 * 50 // 100 + 400 // 4 + 10

    def test_oom_candidate_pruned_fitting_one_survives(self, devices8):
        prof, abstract = self._profile_and_params()
        topo = topology.Topology(num_devices=8, num_hosts=1,
                                 platform="tpu", device_kind="v5p")
        kept, pruned = tune_space.enumerate_candidates(
            abstract, topo, act_profile=prof, batch_items=64)
        assert {c.strategy for c in kept} >= {"dp", "fsdp"} and not pruned
        # a budget between dp's and fsdp's footprint: the replicated dp
        # candidate is pruned via measured liveness, sharded fsdp survives
        kept, pruned = tune_space.enumerate_candidates(
            abstract, topo, act_profile=prof, batch_items=64, safety=1e-7)
        assert "fsdp" in {c.strategy for c in kept}
        assert "dp" in {c.strategy for c, _ in pruned}
        why = dict((c.strategy, w) for c, w in pruned)["dp"]
        assert "memory:" in why and "liveness" in why

    def test_candidate_memory_marks_profiled(self, devices8):
        prof, abstract = self._profile_and_params()
        cand = tune_space.Candidate("fsdp", (("fsdp", 8),))
        with_prof = tune_space.candidate_memory(
            abstract, cand, batch_items=64, act_profile=prof)
        without = tune_space.candidate_memory(abstract, cand, batch_items=64)
        assert with_prof["profiled"] and not without["profiled"]
        assert with_prof["activation_bytes"] != without["activation_bytes"]


# ---------------------------------------------------------------------------
# suppression + PL005 threshold
# ---------------------------------------------------------------------------


class TestSuppression:
    def test_filter_ignored_drops_codes_case_insensitive(self):
        fs = [analysis.Finding("ML001", analysis.ERROR, "mem", "x", "m"),
              analysis.Finding("DT001", analysis.WARN, "dtype", "x", "m")]
        assert codes(analysis.filter_ignored(fs, ["ml001"])) == ["DT001"]
        assert codes(analysis.filter_ignored(fs, [])) == ["ML001", "DT001"]

    def test_unknown_ignore_code_raises(self):
        with pytest.raises(ValueError, match="ZZ999"):
            analysis.filter_ignored([], ["ZZ999"])

    def test_analyze_applies_ignore(self):
        spec = {"param_specs": {"w": P(None)}, "batch_spec": P("data"),
                "degrees": {"data": 4, "tensor": 2}, "strategy": "dp"}
        assert "PL004" in codes(analysis.analyze(spec))
        assert codes(analysis.analyze(spec, ignore=("PL004",))) == []

    def test_pl005_threshold_defaults_from_rule_table(self):
        assert analysis.RULES["PL005"].threshold == 64 * 2**20
        big = {"emb": sds(512, 128), "w": sds(16, 4)}
        specs = {"emb": P(None, None), "w": P("fsdp", None)}
        degrees = {"data": 1, "fsdp": 8, "tensor": 1}
        # 256 KiB leaf: under the 64 MiB table default, over 1 KiB
        assert "PL005" not in codes(plan_lint.lint_specs(
            specs, P("fsdp"), degrees, "fsdp", big))
        fs = plan_lint.lint_specs(
            specs, P("fsdp"), degrees, "fsdp", big, big_leaf_bytes=1024)
        (f,) = [f for f in fs if f.code == "PL005"]
        assert "MiB leaf" in f.msg and "threshold" in f.msg


# ---------------------------------------------------------------------------
# CLI: tadnn check --memory
# ---------------------------------------------------------------------------


SMALL_CLI = ["check", "--memory", "--no-source", "--no-compiled",
             "--size", "32,10", "--batch", "64"]


class TestCheckMemoryCLI:
    def test_undersized_budget_exits_1_with_ml001(self, devices8, capsys):
        assert cli.main(SMALL_CLI + ["--budget", "64KiB"]) == 1
        out = capsys.readouterr().out
        assert "ML001" in out and "OOM" in out

    def test_real_budget_exits_0_with_breakdown(self, devices8, capsys):
        assert cli.main(SMALL_CLI + ["--budget", "16GiB"]) == 0
        out = capsys.readouterr().out
        assert "memory estimate" in out and "peak" in out

    def test_json_includes_memory_report(self, devices8, capsys):
        assert cli.main(SMALL_CLI + ["--budget", "16GiB", "--json"]) == 0
        out = json.loads(capsys.readouterr().out)
        assert out["memory"]["peak_bytes"] > 0
        assert out["memory"]["budget_bytes"] == 16 * 2**30

    def test_ignore_suppresses_ml001(self, devices8, capsys):
        argv = SMALL_CLI + ["--budget", "64KiB", "--ignore", "ML001",
                            "--ignore", "ML002", "--ignore", "ML003"]
        assert cli.main(argv) == 0
        assert "ML001" not in capsys.readouterr().out

    def test_unknown_ignore_code_exits_2(self, devices8, capsys):
        assert cli.main(SMALL_CLI + ["--budget", "16GiB",
                                     "--ignore", "NOPE1"]) == 2


# ---------------------------------------------------------------------------
# trainer preflight budget
# ---------------------------------------------------------------------------


class TestPreflightBudget:
    def _fit(self, cfg, journal):
        ad = _small_ad()
        data = (_batch(seed=i) for i in range(cfg.steps))
        Trainer(ad, cfg, journal=journal).fit(data)
        return journal

    def test_predicted_oom_raises_under_raise_action(self, devices8):
        cfg = TrainerConfig(steps=1, preflight=True,
                            preflight_action="raise",
                            preflight_budget=1024)
        with pytest.raises(analysis.PreflightError) as ei:
            self._fit(cfg, Journal())
        assert "ML001" in str(ei.value)

    def test_preflight_ignore_unblocks(self, devices8):
        cfg = TrainerConfig(
            steps=1, preflight=True, preflight_action="raise",
            preflight_budget=1024,
            preflight_ignore=("ML001", "ML002", "ML003"))
        j = self._fit(cfg, Journal())
        assert j.named("lint.summary")[0]["errors"] == 0

    def test_preflight_journals_mem_estimate(self, devices8):
        cfg = TrainerConfig(steps=1, preflight=True,
                            preflight_budget="16GiB")
        j = self._fit(cfg, Journal())
        (est,) = j.named("lint.mem_estimate")
        assert est["phase"] == "preflight" and est["peak_bytes"] > 0
        assert est["budget_bytes"] == 16 * 2**30


# ---------------------------------------------------------------------------
# report rendering
# ---------------------------------------------------------------------------


class TestReportRendering:
    def test_memory_estimate_section(self, tmp_path, devices8):
        from torch_automatic_distributed_neural_network_tpu.obs import (
            report as obs_report,
        )

        jpath = tmp_path / "journal.jsonl"
        with Journal(str(jpath)) as j:
            with obs_journal.as_default(j):
                _, rep = analysis.memory_check(
                    _small_ad(), _batch(), rng=jax.random.key(0),
                    budget="16GiB", compiled=False)
        out = obs_report.generate(str(jpath))
        me = out["memory_estimate"]
        assert me["peak_bytes"] == rep["peak_bytes"]
        assert me["budget_bytes"] == 16 * 2**30
        text = obs_report.format_report(out)
        assert "memory estimate (static, per device)" in text
        assert "budget" in text


# ---------------------------------------------------------------------------
# bench snapshot: the committed reference + the compiled cross-check
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def bench_report(devices8):
    cfg = REF["config"]
    rng = np.random.RandomState(0)
    sample = {
        "x": jnp.asarray(rng.randn(cfg["batch"], cfg["input_dim"]),
                         jnp.float32),
        "label": jnp.asarray(rng.randint(0, 10, size=(cfg["batch"],))),
    }
    ad = tad.AutoDistribute(
        MLP(features=tuple(cfg["features"])), optimizer=optax.adamw(1e-4),
        loss_fn=softmax_xent_loss, strategy=cfg["strategy"])
    _, rep = analysis.memory_check(
        ad, sample, rng=jax.random.key(0), budget="16GiB", compiled=True)
    return rep


class TestBenchSnapshot:
    def test_static_estimate_matches_reference(self, bench_report):
        tol = REF["tolerance"]
        for key, want in REF["static"].items():
            got = bench_report[key]
            if want == 0:
                assert got == 0, key
            else:
                assert abs(got - want) <= tol * want, (
                    f"{key}: {got} drifted > {tol:.0%} from the committed "
                    f"reference {want} — if the estimator changed on "
                    f"purpose, regenerate {REF_PATH.name}")

    def test_static_within_2x_of_compiled(self, bench_report):
        ratio = bench_report.get("static_over_compiled")
        assert ratio is not None, bench_report.get("compiled")
        assert 0.5 <= ratio <= 2.0, (
            f"static/compiled ratio {ratio} outside the 2x acceptance "
            "band")
