"""The cell ``phi4-mini-flash-3p8b.serve-backlog-reasoning-s64`` and what
came with it: the rehearsal is correct and its fp8 control is not, its traced
run ends in the contract's line, the mix and the configuration are ISSUE
46's parameter for parameter (nothing is cut), and the counts of the scan
and of differential attention over one shared cache are the arithmetic.
(``tests/test_benchmark_contract.py`` holds the seven new readers to a
hand-made trace and to the records of programs without the kernels.)"""
import json
import os
import subprocess
import sys

import pytest

from lib import counts_diff_attn, counts_ssm, harness

BENCH = harness.load_json(os.path.join(harness.REPO, "BENCHMARK.json"))
CELL = "phi4-mini-flash-3p8b.serve-backlog-reasoning-s64"
NEW = ("ssm_chunk_ms", "ssm_chunk_roofline", "ssm_step_ms",
       "ssm_step_roofline", "diff_attn_decode_ms", "diff_attn_roofline",
       "cross_rows_share")
PEAKS = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def run_cli(*args, timeout=900):
    return subprocess.run(
        [sys.executable, os.path.join(harness.BENCH_DIR, "run.py"), *args],
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
    give nothing and the line holds no device metric; the counters'
    readers (``cross_rows_share``, the pools' bytes) are printed as
    rehearsal values."""
    p = run_cli("--workload", CELL, "--seed", "7", "--seconds", "3",
                "--trace", "1", "--rehearsal")
    assert p.returncode == 0, p.stdout[-1500:] + p.stderr[-1500:]
    lines = [json.loads(l) for l in p.stdout.strip().splitlines()
             if l.startswith("{")]
    res = lines[-1]
    assert res["correct"] is True and res["metrics"] == {}
    shown = next(l["rehearsal_values_not_device_metrics"] for l in lines
                 if "rehearsal_values_not_device_metrics" in l)
    assert 0 < shown["cross_rows_share"]["value"] < 100
    assert shown["kv_pool_gib"]["value"] > 0
    assert shown["state_pool_gib"]["value"] > 0
    assert not {"ssm_chunk_ms", "ssm_step_roofline",
                "diff_attn_roofline"} & set(shown)


def test_the_cell_is_the_issues_parameter_for_parameter():
    cell = harness.Cell(CELL, BENCH)
    mix = cell.mix
    assert cell.chips == 1 and mix["kind"] == "serve-long"
    assert mix["arrivals"] == {"kind": "backlog", "requests_per_second": 8}
    assert mix["lengths"] == {
        "strata": 32,
        "prompt": {"median": 1024, "sigma": 1.2, "lo": 128, "hi": 32768},
        "output": {"median": 1024, "sigma": 0.6, "lo": 128, "hi": 2048}}
    assert mix["engine"] == {
        "n_slots": 64, "max_len": 34816, "block_size": 64, "num_blocks": 6145,
        "prefill_chunk": 512, "attention_impl": "paged", "quant_kv": False,
        "admission": "reserve", "prefix_cache": False}
    assert (mix["drain_seconds"], mix["check_requests"],
            mix["trace_seconds"]) == (0, 8, 2.0)
    assert all(mix.get(k) for k in ("why_block_size", "why_num_blocks",
                                    "why_trace_seconds", "traffic_seed"))
    assert (mix["engine"]["num_blocks"] - 1) * 64 == 393216
    assert mix["lengths"]["prompt"]["hi"] + mix["lengths"]["output"]["hi"] \
        == mix["engine"]["max_len"]
    # the other reasoning mix's prompts: two hybrids answer one shape of list
    other = harness.load_json(os.path.join(
        harness.BENCH_DIR, "traffic", "serve-backlog-reasoning.json"))
    assert other["lengths"]["prompt"] == mix["lengths"]["prompt"]
    assert other["traffic_seed"] == mix["traffic_seed"]
    m, c = cell.config["model"], cell.config
    assert (m["d_model"], m["n_heads"], m["n_kv_heads"], m["d_ff"],
            m["vocab_size"], m["n_layers"], m["sliding_window"]) == (
        2560, 40, 20, 10240, 200064, 32, 512)
    assert m["layer_types"] == (
        ["state_space", "sliding_attention"] * 8
        + ["state_space", "full_attention"]
        + ["gated_memory", "shared_attention"] * 7)
    assert (m["ssm_inner"], m["ssm_state"], m["ssm_dt_rank"]) == (
        5120, 16, 160)
    assert (m["pos"], m["norm"], m["act"], m["tie_embeddings"],
            m["diff_attention"], m["mlp_bias"]) == (
        "none", "layernorm", "swiglu", True, True, False)
    assert m["max_seq_len"] == mix["engine"]["max_len"]
    # nothing is cut: every number of the source under its own key
    assert c["reduced"] == {}
    entry = next(x for x in BENCH["configs"] if x["name"] == c["name"])
    assert entry["reduced"] == [] and entry["file"].endswith(
        "configs/phi4-mini-flash-3p8b.json")
    assert entry["source"] == c["source"] and len(c["source"]) <= 200
    assert {k: c[k] for k in (
        "embd_pdrop", "hidden_act", "hidden_size", "intermediate_size",
        "layer_norm_eps", "max_position_embeddings", "mb_per_layer",
        "model_type", "num_attention_heads", "num_hidden_layers",
        "num_key_value_heads", "resid_pdrop", "sliding_window",
        "tie_word_embeddings", "mlp_bias", "lm_head_bias", "vocab_size")} == {
        "embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 2560,
        "intermediate_size": 10240, "layer_norm_eps": 1e-05,
        "max_position_embeddings": 262144, "mb_per_layer": 2,
        "model_type": "phi4flash", "num_attention_heads": 40,
        "num_hidden_layers": 32, "num_key_value_heads": 20, "resid_pdrop": 0,
        "sliding_window": 512, "tie_word_embeddings": True,
        "mlp_bias": False, "lm_head_bias": False, "vocab_size": 200064}
    assert c["parameters"] == 3852562944 and c["weights_seed"]
    assert c["compute_dtype"] == "bfloat16"
    assert set(c["assumed"]) >= {
        "ssm_sizes", "ssm_biases", "ssm_draw", "attention_biases",
        "differential_attention", "positions", "memory", "split",
        "max_seq_len", "modelling_code"}
    reh = c["rehearsal"]["model"]
    assert (reh["d_model"], reh["n_layers"]) == (64, 8)
    assert reh["layer_types"] == [
        "state_space", "sliding_attention", "state_space", "full_attention",
        "gated_memory", "shared_attention", "gated_memory",
        "shared_attention"]


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


def test_counts_are_the_arithmetic():
    keys = harness.Cell(CELL, BENCH).config["model"]
    assert counts_ssm.scan_layers(keys) == (9, 5120, 16)
    assert counts_ssm.scan_flops(2, 5120, 16) == 2 * 5120 * (7 * 16 + 3)
    assert counts_ssm.scan_bytes(1, 1, 5120, 16, itemsize=2) \
        == 5120 * 10 + 2 * 16 * 4 + 2 * 16 * 5120 * 4
    # 64 decoding slots' nine scans: 407 MB in and out, 0.5 ms at the chip's
    # rate
    a_step = 9 * counts_ssm.scan_bytes(64, 64, 5120, 16, itemsize=2)
    assert round(a_step / 1e6) == 407
    assert round(1e3 * a_step / PEAKS["hbm_bytes_per_s"], 2) == 0.5
    # a decode row at context 2,700: 8 reads of all of it and 8 windows
    assert counts_diff_attn.keys_read([2700], keys) == 8 * 2700 + 8 * 512
    assert counts_diff_attn.decode_bytes(
        counts_diff_attn.keys_read([2700], keys), 20, 64, itemsize=2) \
        == 25696 * 5120


@pytest.mark.parametrize("metric", NEW)
def test_a_record_without_the_kernels_reads_none(metric):
    read = harness.load_module(os.path.join(
        harness.BENCH_DIR, "metrics", metric + ".py"), "bench_metric").read
    cell = harness.Cell(CELL, BENCH)
    keys, engine = cell.config["model"], cell.mix["engine"]
    req = {"prompt": [1] * 20, "walls": [0.5, 1.0], "t_admit": 0.1}
    base = {"model_keys": keys, "engine": engine, "requests": [req],
            "serve_steps": [{"state_rows": 18, "t_end": 0.5}]}
    # an untraced run; a trace without the kernels; a model of another kind
    assert read(base) is None
    other = ("%tadnn_gdn_step.1 = f32[8,3,10,192] custom-call()", 0, 1000)
    traced = {"peaks": PEAKS, "trace_mono": (0.0, 2.0),
              "trace": {"n_devices": 1, "ops": {"d": [other]},
                        "modules": {"d": [("jit_serve_prefill_chunk(1)", 0,
                                           2000)]},
                        "module_seconds": {"jit_serve_prefill_chunk": [2e-6]}}}
    assert read({**base, **traced}) is None
    assert read({**base, **traced, "model_keys": {"d_model": 64}}) is None
    assert read({**base, **traced, "serve_steps": []}) is None
