"""The cell ``joyai-llm-flash-ep8.serve-backlog-deep`` and what came with
it: the control at rehearsal size is not correct, the mix and the
configuration are the issue's parameter for parameter (with the three
departures the mix's own ``why_*`` keys give), the latent kernel's counts are
the arithmetic, its share is its least time over its time, and both new
readers give ``None`` (and do not raise) on the record of a program or a
model without the kernel, as the parent commit's is."""
import json
import os
import subprocess
import sys

import pytest

from lib import counts_mla, harness

BENCH = harness.load_json(os.path.join(harness.REPO, "BENCHMARK.json"))
CELL = "joyai-llm-flash-ep8.serve-backlog-deep"
NEW = ("latent_attn_roofline", "latent_attn_decode_ms")
PEAKS = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def run_cli(*args, timeout=600):
    return subprocess.run(
        [sys.executable, os.path.join(harness.BENCH_DIR, "run.py"), *args],
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, cwd=harness.REPO,
        capture_output=True, text=True, timeout=timeout)


def reader(metric: str):
    return harness.load_module(os.path.join(
        harness.BENCH_DIR, "metrics", metric + ".py"), "bench_metric")


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
    assert mix["arrivals"] == {"kind": "backlog", "requests_per_second": 6}
    assert mix["lengths"] == {
        "strata": 32,
        "prompt": {"median": 4096, "sigma": 1.0, "lo": 256, "hi": 32768},
        "output": {"median": 512, "sigma": 0.6, "lo": 64, "hi": 2048}}
    # pages of 64 and not 16, 4,097 of them (262,144 tokens, over the
    # 196,608 asked for), and 2 s of trace and not 5: each with its reason
    assert mix["engine"] == {
        "n_slots": 24, "max_len": 34816, "block_size": 64, "num_blocks": 4097,
        "prefill_chunk": 512, "attention_impl": "paged", "quant_kv": False,
        "admission": "reserve", "prefix_cache": False}
    assert (mix["drain_seconds"], mix["check_requests"],
            mix["trace_seconds"]) == (0, 8, 2.0)
    assert all(mix.get(k) for k in ("why_block_size", "why_num_blocks",
                                    "why_trace_seconds", "traffic_seed"))
    assert (mix["engine"]["num_blocks"] - 1) * 64 >= 196608
    assert mix["lengths"]["prompt"]["hi"] + mix["lengths"]["output"]["hi"] \
        <= mix["engine"]["max_len"]
    m, c = cell.config["model"], cell.config
    assert (m["d_model"], m["n_heads"], m["d_ff"], m["expert_d_ff"],
            m["vocab_size"], m["n_layers"]) == (2048, 32, 7168, 768, 16160, 20)
    assert (m["latent_q_rank"], m["latent_kv_rank"], m["latent_nope_head_dim"],
            m["latent_rope_head_dim"], m["latent_value_head_dim"]) == (
        1536, 512, 128, 64, 128)
    assert m["layer_types"] == ["latent_attention"] * 20
    assert (m["experts_published"], m["experts_held"],
            m["experts_per_token"], m["n_dense_layers"]) == (256, 32, 8, 1)
    assert sorted(c["reduced"]) == [
        "n_routed_experts", "num_hidden_layers", "num_nextn_predict_layers",
        "vocab_size"]
    assert c["parameters"] == 3605789440 and c["weights_seed"]
    # every published number under its own name, but for the four cuts
    assert {k for k, v in c["source_keys"].items() if c[k] != v} == set(
        c["reduced"])


def test_counts_are_the_arithmetic():
    keys = harness.Cell(CELL, BENCH).config["model"]
    assert counts_mla.latent_layers(keys) == (20, 32, 576, 512)
    assert counts_mla.latent_layers({"d_model": 64}) == (0, 0, 0, 0)
    assert counts_mla.latent_layers(
        {"layer_types": ["full_attention"] * 2}) == (0, 0, 0, 0)
    # a key: 32 heads' scores over 576 numbers and values over 512
    assert counts_mla.latent_attention_flops(1, 32, 576, 512) == 69632
    assert counts_mla.latent_attention_bytes(1, 576, itemsize=2) == 1152
    # 60 operations a byte: under the chip's ridge, so bytes bind it
    assert 69632 / 1152 < PEAKS["flops_per_s"] / PEAKS["hbm_bytes_per_s"]


def _traced(keys, ops, requests, span=(0.0, 2.0)) -> dict:
    return {"model_keys": keys, "peaks": PEAKS, "trace_mono": span,
            "requests": requests,
            "trace": {"n_devices": 1, "ops": {"d": ops}, "modules": {"d": []},
                      "module_seconds": {}}}


def test_the_kernels_share_is_its_least_time_over_its_time(capsys):
    """Two calls of 20 layers, the kernel 50 us a layer; one request of a
    4,000-token prompt that decodes its second and third token inside the
    traced span (its first comes from the chunk, its fourth after the span):
    4,001 + 4,002 keys a layer."""
    keys = harness.Cell(CELL, BENCH).config["model"]
    ops = [(f"%tadnn_paged_decode_latent.{i} = bf16[24,32,512] custom-call()",
            1000 * i, 1000 * i + 50_000) for i in range(40)]
    ops.append(("%fusion.9 = bf16[24,32,576] fusion(%tadnn_paged_decode_"
                "latent.1)", 0, 10**9))  # a consumer is not the kernel
    req = {"prompt": [1] * 4000, "walls": [0.5, 1.0, 1.5, 2.5]}
    rec = _traced(keys, ops, [req])
    share = reader("latent_attn_roofline").read(rec)
    line = json.loads(capsys.readouterr().out.strip())["latent_attn"]
    least = 20 * (4001 + 4002) * 1152 / PEAKS["hbm_bytes_per_s"]
    assert line["bound"] == "memory" and line["calls"] == 40
    assert line["decode_tokens"] == 2 and line["latent_layers"] == 20
    assert share == pytest.approx(100 * least / (40 * 50e-6))
    assert 0 < share < 100
    # the kernel in one call: 20 layers of 50 us
    assert reader("latent_attn_decode_ms").read(rec) == pytest.approx(1.0)


@pytest.mark.parametrize("metric", NEW)
def test_a_record_without_the_kernel_reads_none(metric):
    read = reader(metric).read
    keys = harness.Cell(CELL, BENCH).config["model"]
    req = {"prompt": [1] * 20, "walls": [0.5, 1.0]}
    # an untraced run; a model without latent layers; a program without the
    # kernel (the parent's); a traced span in which no token was decoded
    assert read({"model_keys": keys, "requests": [req]}) is None
    other = ("%tadnn_paged_decode_folded.1 = bf16[8,16,128] custom-call()",
             0, 1000)
    assert read(_traced({"d_model": 64, "n_heads": 4}, [other], [req])) is None
    assert read(_traced(keys, [other], [req])) is None
    assert read(_traced(keys, [], [])) is None
