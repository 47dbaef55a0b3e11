"""The cell ``kimi-linear-48b-ep8.serve-backlog-reasoning`` and what came
with it: the rehearsal is correct and its fp8 control is not, the mix and the
configuration are ISSUE 41's parameter for parameter, the KDA kernels' counts
are the arithmetic, and the four new readers give ``None`` (and do not raise)
on the record of a program or a model without the kernels, as the parent
commit's is.  (``tests/test_benchmark_contract.py`` holds the readers to a
hand-made trace.)"""
import json
import os
import subprocess
import sys

import pytest

from lib import counts_gdn, counts_kda, harness

BENCH = harness.load_json(os.path.join(harness.REPO, "BENCHMARK.json"))
CELL = "kimi-linear-48b-ep8.serve-backlog-reasoning"
NEW = ("kda_chunk_ms", "kda_step_ms", "kda_chunk_roofline",
       "kda_step_roofline")
PEAKS = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def run_cli(*args, timeout=600):
    return subprocess.run(
        [sys.executable, os.path.join(harness.BENCH_DIR, "run.py"), *args],
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, cwd=harness.REPO,
        capture_output=True, text=True, timeout=timeout)


def reader(metric: str):
    return harness.load_module(os.path.join(
        harness.BENCH_DIR, "metrics", metric + ".py"), "bench_metric")


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


def test_the_cell_is_the_issues_parameter_for_parameter():
    cell = harness.Cell(CELL, BENCH)
    mix = cell.mix
    assert cell.chips == 1 and mix["kind"] == "serve-long"
    assert mix["arrivals"]["kind"] == "backlog"
    assert mix["lengths"] == {
        "strata": 32,
        "prompt": {"median": 1024, "sigma": 1.2, "lo": 128, "hi": 32768},
        "output": {"median": 1024, "sigma": 0.6, "lo": 128, "hi": 4096}}
    assert mix["engine"] == {
        "n_slots": 96, "max_len": 36864, "block_size": 64, "num_blocks": 6145,
        "prefill_chunk": 512, "attention_impl": "paged", "quant_kv": False,
        "admission": "reserve", "prefix_cache": False}
    assert (mix["drain_seconds"], mix["check_requests"],
            mix["trace_seconds"]) == (0, 8, 2.0)
    assert all(mix.get(k) for k in ("why_block_size", "why_num_blocks",
                                    "why_trace_seconds", "traffic_seed"))
    assert (mix["engine"]["num_blocks"] - 1) * 64 == 393216
    assert mix["lengths"]["prompt"]["hi"] + mix["lengths"]["output"]["hi"] \
        == mix["engine"]["max_len"]
    m, c = cell.config["model"], cell.config
    assert (m["d_model"], m["n_heads"], m["d_ff"], m["expert_d_ff"],
            m["vocab_size"], m["n_layers"]) == (2304, 32, 9216, 1024, 20480,
                                                16)
    assert m["layer_types"] == (["linear_attention"] * 3
                                + ["latent_attention"]) * 4
    assert (m["linear_value_heads"], m["linear_key_head_dim"],
            m["linear_value_head_dim"], m["linear_conv_kernel"],
            m["linear_decay"], m["linear_decay_rank"], m["linear_gate_rank"],
            m["linear_gate_act"]) == (32, 128, 128, 4, "channel", 128, 128,
                                      "sigmoid")
    assert "latent_q_rank" not in m and (
        m["latent_kv_rank"], m["latent_nope_head_dim"],
        m["latent_rope_head_dim"], m["latent_value_head_dim"]) == (
        512, 128, 64, 128)
    assert (m["pos"], m["rope_layers"]) == ("rope", "sliding")  # no rotation
    assert (m["experts_published"], m["experts_held"],
            m["experts_per_token"], m["n_dense_layers"],
            m["route_scale"]) == (256, 32, 8, 1, 2.446)
    assert sorted(c["reduced"]) == ["linear_attn_config", "num_experts",
                                    "num_hidden_layers", "vocab_size"]
    entry = next(x for x in BENCH["configs"] if x["name"] == c["name"])
    assert sorted(entry["reduced"]) == sorted(c["reduced"])
    assert entry["source"] == c["source"] and len(c["source"]) <= 200
    assert c["parameters"] == 4261185408 and c["weights_seed"]
    # every published number under its own name, but for the four cuts
    assert {k for k, v in c["source_keys"].items() if c[k] != v} == set(
        c["reduced"])
    lin, src = c["linear_attn_config"], c["source_keys"]["linear_attn_config"]
    assert {k for k in src if lin[k] != src[k]} == {"kda_layers",
                                                    "full_attn_layers"}


def test_counts_are_the_arithmetic():
    keys = harness.Cell(CELL, BENCH).config["model"]
    assert counts_gdn.linear_layers(keys) == (12, 32, 128, 128)
    assert counts_kda.recurrence_flops(2, 32, 128, 128) == 2 * 7 * 32 * 16384
    assert counts_kda.recurrence_bytes(1, 1, 32, 128, 128, itemsize=2) \
        == 32 * (1024 + 512 + 4) + 2 * 32 * 16384 * 4
    # a decoding slot's 12 layers: 50 MB in and out, 61 us at the chip's rate
    a_slot = 12 * counts_kda.recurrence_bytes(1, 1, 32, 128, 128, itemsize=2)
    assert round(a_slot / 1e6, 1) == 50.9
    assert round(1e6 * a_slot / PEAKS["hbm_bytes_per_s"]) == 62


@pytest.mark.parametrize("metric", NEW)
def test_a_record_without_the_kernels_reads_none(metric):
    read = reader(metric).read
    cell = harness.Cell(CELL, BENCH)
    keys, engine = cell.config["model"], cell.mix["engine"]
    req = {"prompt": [1] * 20, "walls": [0.5, 1.0], "t_admit": 0.1}
    base = {"model_keys": keys, "engine": engine, "requests": [req],
            "serve_steps": [{"state_rows": 24, "t_end": 0.5}]}
    # an untraced run; a trace without the kernels (the parent's program); a
    # model without linear layers
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
