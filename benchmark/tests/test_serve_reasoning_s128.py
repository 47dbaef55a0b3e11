"""The cell ``solar-open2-250b-ep8.serve-backlog-reasoning-s128`` and what
came with it: the rehearsal is correct and its fp8 control is not, a timed
path whose write strength is clipped at 1 is not correct, the traced run ends
in the contract's line, the mix and the configuration are ISSUE 49's
parameter for parameter, and the count functions give this shape's
operations and bytes from the configuration.  The cell adds no reader:
``tests/test_benchmark_contract.py`` holds the KDA readers to a hand-made
trace on this model too."""
import importlib.util
import json
import os
import subprocess
import sys

import pytest

from lib import counts_gdn, counts_kda, counts_moe, harness

BENCH = harness.load_json(os.path.join(harness.REPO, "BENCHMARK.json"))
CELL = "solar-open2-250b-ep8.serve-backlog-reasoning-s128"
RUN = os.path.join(harness.BENCH_DIR, "run.py")
PEAKS = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def run_cli(*args, timeout=900):
    return subprocess.run(
        [sys.executable, RUN, *args],
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, cwd=harness.REPO,
        capture_output=True, text=True, timeout=timeout)


@pytest.mark.parametrize("seed", ["11", "4123456789"])
def test_the_rehearsal_is_correct(seed):
    p = run_cli("--workload", CELL, "--seed", seed, "--seconds", "3",
                "--rehearsal")
    assert p.returncode == 0, p.stdout[-1500:] + p.stderr[-1500:]
    res = json.loads(p.stdout.strip().splitlines()[-1])
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] >= 16


@pytest.mark.parametrize("seed", ["11", "12", "4123456789"])
def test_the_fp8_control_is_not_correct(seed):
    p = run_cli("--workload", CELL, "--seed", seed, "--seconds", "3",
                "--rehearsal", "--control")
    assert p.returncode == 0, p.stdout[-1500:] + p.stderr[-1500:]
    res = json.loads(p.stdout.strip().splitlines()[-1])
    assert res["control"] is True and res["correct"] is False
    assert res["program_correct"] is True


def test_the_traced_rehearsal_ends_in_the_contracts_line():
    """``--trace 1`` off the chip: no device trace, so the device readers
    give nothing and the line holds no device metric; the counters' readers
    (the pools' bytes, the slots' occupancy) are printed as rehearsal
    values."""
    p = run_cli("--workload", CELL, "--seed", "7", "--seconds", "3",
                "--trace", "1", "--rehearsal")
    assert p.returncode == 0, p.stdout[-1500:] + p.stderr[-1500:]
    lines = [json.loads(l) for l in p.stdout.strip().splitlines()
             if l.startswith("{")]
    res = lines[-1]
    assert set(res) == {"correct", "attempted", "failed", "metrics", "device"}
    assert res["correct"] is True and res["metrics"] == {}
    shown = next(l["rehearsal_values_not_device_metrics"] for l in lines
                 if "rehearsal_values_not_device_metrics" in l)
    assert shown["kv_pool_gib"]["value"] > 0
    assert shown["state_pool_gib"]["value"] > 0
    assert 0 < shown["slot_occupancy"]["value"] <= 100
    assert not {"kda_step_ms", "kda_chunk_roofline",
                "paged_attn_roofline.by_kind",
                "moe_grouped_mm_chunk_ms"} & set(shown)


def test_a_write_strength_clipped_at_one_is_not_correct(monkeypatch, capsys):
    """The timed path broken underneath where this model differs from the
    other KDA model: ``beta`` held to (0, 1] in the program's mixer (the
    reference writes with up to 2) must flip ``correct``."""
    import jax.numpy as jnp

    from torch_automatic_distributed_neural_network_tpu.models import (
        transformer_core,
    )

    real = transformer_core.GatedDeltaMixer.project

    def clipped(self, x):
        pre, g, beta = real(self, x)
        return pre, g, jnp.minimum(beta, 1.0)

    monkeypatch.setattr(transformer_core.GatedDeltaMixer, "project", clipped)
    spec = importlib.util.spec_from_file_location("bench_run_under_test", RUN)
    run = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(run)
    rc = run.main(["--workload", CELL, "--seed", "23", "--seconds", "2",
                   "--trace", "0", "--rehearsal"])
    res = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and res["correct"] is False


def test_the_cell_is_the_issues_parameter_for_parameter():
    cell = harness.Cell(CELL, BENCH)
    mix = cell.mix
    assert cell.chips == 1 and mix["kind"] == "serve-long"
    assert mix["arrivals"] == {"kind": "backlog", "requests_per_second": 16}
    assert mix["engine"] == {
        "n_slots": 128, "max_len": 36864, "block_size": 64,
        "num_blocks": 8601, "prefill_chunk": 512, "attention_impl": "paged",
        "quant_kv": False, "admission": "reserve", "prefix_cache": False}
    assert (mix["drain_seconds"], mix["trace_seconds"]) == (0, 2.0)
    assert mix["check_requests"] >= 8
    assert all(mix.get(k) for k in ("why_block_size", "why_num_blocks",
                                    "why_trace_seconds", "rehearsal"))
    assert (mix["engine"]["num_blocks"] - 1) * 64 == 550400
    assert mix["lengths"]["prompt"]["hi"] + mix["lengths"]["output"]["hi"] \
        == mix["engine"]["max_len"]
    # the other reasoning mix's lengths and seed, letter for letter
    other = harness.load_json(os.path.join(
        harness.BENCH_DIR, "traffic", "serve-backlog-reasoning.json"))
    assert other["lengths"] == mix["lengths"] == {
        "strata": 32,
        "prompt": {"median": 1024, "sigma": 1.2, "lo": 128, "hi": 32768},
        "output": {"median": 1024, "sigma": 0.6, "lo": 128, "hi": 4096}}
    assert other["traffic_seed"] == mix["traffic_seed"] == 1442695040
    m, c = cell.config["model"], cell.config
    assert (m["d_model"], m["n_heads"], m["n_kv_heads"], m["head_size"],
            m["expert_d_ff"], m["vocab_size"], m["n_layers"]) == (
        4096, 64, 8, 128, 1280, 24576, 4)
    assert m["layer_types"] == ["full_attention"] + ["linear_attention"] * 3
    assert (m["linear_value_heads"], m["linear_key_heads"],
            m["linear_key_head_dim"], m["linear_value_head_dim"],
            m["linear_conv_kernel"], m["linear_decay"],
            m["linear_decay_rank"], m["linear_gate_rank"],
            m["linear_gate_act"], m["linear_neg_eigval"]) == (
        64, 64, 128, 128, 4, "channel", 128, 128, "sigmoid", True)
    assert (m["pos"], m["attn_gate"], m["tie_embeddings"]) == (
        "none", True, False)  # nothing rotated, a gated output, untied
    assert (m["experts_published"], m["experts_held"], m["first_expert"],
            m["experts_per_token"], m["shared_experts"], m["n_dense_layers"],
            m["score_func"], m["route_norm"], m["route_scale"]) == (
        320, 40, 0, 8, 1, 0, "sigmoid", True, 1.0)
    assert m["max_seq_len"] == mix["engine"]["max_len"]
    assert sorted(c["reduced"]) == ["gqa_layers", "n_routed_experts",
                                    "num_hidden_layers", "vocab_size"]
    entry = next(x for x in BENCH["configs"] if x["name"] == c["name"])
    assert sorted(entry["reduced"]) == sorted(c["reduced"])
    assert entry["source"] == c["source"] and len(c["source"]) <= 200
    assert c["parameters"] == 3308353344 and c["weights_seed"]
    assert "96" in c["deployment"] and "twelve" in c["deployment"]
    # every published number under its own name, but for the four cuts
    assert {k for k, v in c["source_keys"].items() if c[k] != v} == set(
        c["reduced"])
    assert (c["num_hidden_layers"], c["gqa_layers"], c["n_routed_experts"],
            c["vocab_size"]) == (4, [0], 40, 24576)
    assert set(c["assumed"]) >= {
        "attention_gate", "no_rotation", "qk_norm", "low_rank_width",
        "decay", "write_strength", "router", "hidden_act",
        "intermediate_size", "max_seq_len", "modelling_code"}
    reh = c["rehearsal"]["model"]
    assert (reh["d_model"], reh["experts_held"], reh["experts_published"],
            reh["experts_per_token"]) == (64, 4, 16, 2)


def test_the_limits_lie_between_their_readings():
    limits = harness.Cell(CELL, BENCH).limits
    assert limits["control_precision"] == "fp8"
    for group in (limits, limits["rehearsal"]):
        mean = group["served_token_regret.mean"]
        assert mean["sound_max"] < mean["limit"] < mean["control_min"]
        assert group["served_token_regret.max"]["sound_max"] \
            < group["served_token_regret.max"]["limit"]
    assert limits["token_count_mismatches"]["limit"] == 0
    assert limits["compiles_in_window"]["limit"] == 0


def test_counts_are_the_arithmetic_of_this_shape():
    """Nothing in the count functions holds the other KDA model's 32 heads
    or a dense first layer: they take the shape from the configuration."""
    keys = harness.Cell(CELL, BENCH).config["model"]
    assert counts_gdn.linear_layers(keys) == (3, 64, 128, 128)
    assert counts_kda.recurrence_flops(2, 64, 128, 128) == 2 * 7 * 64 * 16384
    assert counts_kda.recurrence_bytes(1, 1, 64, 128, 128, itemsize=2) \
        == 64 * (1024 + 512 + 4) + 2 * 64 * 16384 * 4
    # 128 decoding slots' three layers: 3.26 GB in and out, 4.0 ms at the
    # chip's rate
    a_step = 3 * counts_kda.recurrence_bytes(128, 128, 64, 128, 128,
                                             itemsize=2)
    assert round(a_step / 1e9, 2) == 3.26
    assert round(1e3 * a_step / PEAKS["hbm_bytes_per_s"], 1) == 4.0
    # a decode token at context 2,700 reads 2,700 keys and values of 4,096 B
    # in the ONE attention layer
    flops, moved = counts_moe.paged_attention_by_kind(
        [2700], n_full=1, n_window=0, window=None, heads=keys["n_heads"],
        kv_heads=keys["n_kv_heads"], head_dim=keys["head_size"], itemsize=2)
    assert moved == 2700 * 4096 and flops == 4 * 2700 * 64 * 128
    # every held expert of the four layers, read once: 5.03 GB
    assert round(4 * counts_moe.grouped_mm_bytes(
        keys["experts_held"], keys["d_model"], keys["expert_d_ff"],
        itemsize=2) / 1e9, 2) == 5.03
