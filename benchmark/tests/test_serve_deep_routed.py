"""The cell ``longcat-flash-omni-ep32.serve-backlog-deep-routed`` and what
came with it: it resolves to its files, its rehearsal ends in the contract's
line, the control at rehearsal size is not correct, the mix is
``serve-backlog-deep``'s requests and engine letter for letter, the
configuration is the issue's parameter for parameter, the router's selection
bias is drawn ``c n / width`` and nothing else is drawn otherwise, the
latent counts read 8 layers and 64 heads, and both new readers give
``None`` (and do not raise) on the record of a program without the
counters, as the parent commit's is."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from lib import counts_mla, harness, serving_long, serving_long_routed, weights

BENCH = harness.load_json(os.path.join(harness.REPO, "BENCHMARK.json"))
CELL = "longcat-flash-omni-ep32.serve-backlog-deep-routed"
TWIN = "joyai-llm-flash-ep8.serve-backlog-deep"
NEW = ("zero_expert_share", "moe_live_pairs_per_row")


def run_cli(*args, timeout=600):
    return subprocess.run(
        [sys.executable, os.path.join(harness.BENCH_DIR, "run.py"), *args],
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, cwd=harness.REPO,
        capture_output=True, text=True, timeout=timeout)


def reader(metric: str):
    return harness.load_module(os.path.join(
        harness.BENCH_DIR, "metrics", metric + ".py"), "bench_metric")


@pytest.mark.parametrize("trace", ["0", "1"])
def test_the_rehearsal_ends_in_the_contracts_line(trace):
    p = run_cli("--workload", CELL, "--seed", "2147483693", "--seconds", "3",
                "--trace", trace, "--rehearsal")
    assert p.returncode == 0, p.stdout[-1500:] + p.stderr[-1500:]
    lines = p.stdout.strip().splitlines()
    assert json.loads(lines[0])["rehearsal"] is True
    res = json.loads(lines[-1])
    assert set(res) >= {"correct", "attempted", "failed", "metrics", "device"}
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 0 and res["metrics"] == {}
    shown = json.loads(lines[-2])["rehearsal_values_not_device_metrics"]
    if trace == "1":  # the two new metrics print on a traced run
        assert 0 < shown["zero_expert_share"]["value"] < 100
        assert 0 <= shown["moe_live_pairs_per_row"]["value"] <= 3
        assert shown["kv_pool_gib"]["value"] > 0
    else:
        assert set(shown) == {"serve_tokens_per_s", "setup_s"}


@pytest.mark.parametrize("seed", ["11", "12", "4123456789"])
def test_the_fp8_control_is_not_correct(seed):
    p = run_cli("--workload", CELL, "--seed", seed, "--seconds", "3",
                "--rehearsal", "--control")
    assert p.returncode == 0, p.stdout[-1500:] + p.stderr[-1500:]
    res = json.loads(p.stdout.strip().splitlines()[-1])
    assert res["control"] is True and res["correct"] is False
    assert res["program_correct"] is True


def test_the_cell_is_the_issues_parameter_for_parameter():
    cell, twin = harness.Cell(CELL, BENCH), harness.Cell(TWIN, BENCH)
    mix = cell.mix
    assert cell.chips == 1 and mix["kind"] == "serve-long-routed"
    # the requests and the engine of the other latent cell, letter for letter
    for key in ("arrivals", "lengths", "engine", "model_options",
                "traffic_seed", "drain_seconds", "check_requests",
                "trace_seconds"):
        assert mix[key] == twin.mix[key], key
    assert mix["engine"] == {
        "n_slots": 24, "max_len": 34816, "block_size": 64, "num_blocks": 4097,
        "prefill_chunk": 512, "attention_impl": "paged", "quant_kv": False,
        "admission": "reserve", "prefix_cache": False}
    assert mix["traffic_seed"] == 1618033988
    assert all(mix.get(k) for k in ("why_block_size", "why_num_blocks",
                                    "why_trace_seconds", "why_traffic_seed"))
    m, c = cell.config["model"], cell.config
    assert (m["d_model"], m["n_heads"], m["d_ff"], m["expert_d_ff"],
            m["vocab_size"], m["n_layers"]) == (6144, 64, 12288, 2048, 16384,
                                                8)
    assert (m["latent_q_rank"], m["latent_kv_rank"], m["latent_nope_head_dim"],
            m["latent_rope_head_dim"], m["latent_value_head_dim"]) == (
        1536, 512, 128, 64, 128)
    assert m["layer_types"] == ["latent_attention"] * 8
    assert (m["experts_published"], m["zero_experts"], m["experts_held"],
            m["experts_per_token"], m["n_dense_layers"],
            m["shortcut_experts"]) == (512, 256, 16, 12, 4, True)
    assert (m["score_func"], m["route_norm"], m["route_scale"]) == (
        "softmax", False, 6.0)
    assert sorted(c["reduced"]) == ["n_routed_experts", "num_layers",
                                    "vocab_size"]
    assert c["parameters"] == 5172749312 and c["weights_seed"]
    # every published number under its own name, but for the three cuts
    assert {k for k, v in c["source_keys"].items() if c[k] != v} == set(
        c["reduced"])
    entry = next(e for e in BENCH["configs"] if e["name"] == c["name"])
    assert entry["reduced"] == list(c["reduced"])
    assert entry["source"] == c["source"]
    # the bias's scale, with its two measured shares in words
    assert c["router_bias_c"] == 1.0
    assert "9.1%" in c["assumed"]["weights"]
    assert "33.6%" in c["assumed"]["weights"]
    assert "32 chips" in c["deployment"] and "seven pipeline stages" in \
        c["deployment"]


def test_the_cell_joins_the_lists_the_issue_names():
    on = {m["name"] for m in BENCH["per_layer"] if CELL in m["workloads"]}
    assert on == {
        "slot_occupancy", "decode_step_ms.backlog", "device_idle_share.serve",
        "prefill_chunk_device_ms.backlog", "serve_host_ms.backlog",
        "chunk_call_ms.backlog", "decode_call_ms.backlog",
        "chunk_call_deep_ms.backlog", "serve_stall_ms.backlog", "kv_pool_gib",
        "moe_grouped_mm_chunk_ms", "latent_attn_roofline",
        "latent_attn_decode_ms", "latent_chunk_attn_ms", *NEW}
    e2e = {m["name"] for m in BENCH["end_to_end"]
           if CELL in m.get("workloads", [CELL])}
    assert e2e == {"serve_tokens_per_s", "setup_s"}
    assert len(BENCH["workloads"]) == 7 and len(BENCH["configs"]) == 5
    assert BENCH["workloads"][-1]["name"] == CELL
    # appended at the end of every list it joined
    for m in BENCH["per_layer"] + BENCH["end_to_end"]:
        if CELL in m.get("workloads", ()):
            assert m["workloads"][-1] == CELL, m["name"]


def test_the_latent_counts_read_eight_layers_of_64_heads():
    keys = harness.Cell(CELL, BENCH).config["model"]
    assert counts_mla.latent_layers(keys) == (8, 64, 576, 512)
    # a key: 64 heads' scores over 576 numbers and values over 512; 121
    # operations a byte, under the chip's ridge (240): bytes bind it
    assert counts_mla.latent_attention_flops(1, 64, 576, 512) == 139264
    assert 139264 / counts_mla.latent_attention_bytes(1, 576, itemsize=2) \
        < 197e12 / 819e9


def test_the_selection_bias_alone_is_drawn_otherwise():
    """``serving_long_routed.seeded_weights``: the leaves ``serving_long``
    draws, but for ``router/e_bias``: ``c n / width`` from the same key
    (deviation ``c / width``, not 0.02), rounded through bfloat16; the
    exchange is undone after the call."""
    import jax.numpy as jnp

    from lib import serving_large

    shapes = {"layers_0/moe/router/e_bias": (768,),
              "layers_0/moe/router/kernel": (64, 768),
              "layers_0/mlp_norm/scale": (64,),
              "layers_1/mlp/router/e_bias": (256,)}
    dtypes = {k: jnp.float32 for k in shapes}
    key = weights.seed_key(1414213562)
    plain = serving_long.seeded_weights(key, shapes, dtypes)
    mine = serving_long_routed.seeded_weights(1.0)(key, shapes, dtypes)
    for path in shapes:
        same = np.array_equal(np.asarray(plain[path]), np.asarray(mine[path]))
        assert same == (not path.endswith("router/e_bias")), path
    for path, width in (("layers_0/moe/router/e_bias", 768),
                        ("layers_1/mlp/router/e_bias", 256)):
        b = np.asarray(mine[path])
        assert abs(b.std() * width - 1.0) < 0.15 and abs(b.mean()) < 3 / width
        assert np.array_equal(
            b, np.asarray(jnp.asarray(b).astype(jnp.bfloat16)
                          .astype(jnp.float32)))
        assert abs(np.asarray(plain[path]).std() - 0.02) < 0.003
    half = serving_long_routed.seeded_weights(0.5)(key, shapes, dtypes)
    np.testing.assert_allclose(
        np.asarray(half["layers_0/moe/router/e_bias"]),
        0.5 * np.asarray(mine["layers_0/moe/router/e_bias"]), rtol=1e-2)
    theirs = serving_large.seeded_weights
    assert theirs is not serving_long.seeded_weights  # nothing left bound


@pytest.mark.parametrize("metric", NEW)
def test_a_record_without_the_counters_reads_none(metric):
    read = reader(metric).read
    keys = harness.Cell(CELL, BENCH).config["model"]
    step = {"moe_pairs": 3, "moe_experts_touched": 2, "moe_tiles_active": 2}
    # the parent's events (no moe_rows), no event at all, and for the share
    # a model whose router has no zero-compute output
    assert read({"model_keys": keys, "serve_steps": [step] * 3}) is None
    assert read({"model_keys": keys, "serve_steps": []}) is None
    assert read({"model_keys": keys}) is None
    twin = harness.Cell(TWIN, BENCH).config["model"]
    full = {**step, "moe_rows": 24, "moe_zero_pairs": 0}
    if metric == "zero_expert_share":
        assert read({"model_keys": twin, "serve_steps": [full]}) is None


def test_the_readers_are_the_issues_arithmetic(capsys):
    keys = harness.Cell(CELL, BENCH).config["model"]
    steps = [{"moe_rows": 536, "moe_zero_pairs": 8000},  # a fused call
             {"moe_rows": 24, "moe_zero_pairs": 400, "moe_pairs": 30},
             {"moe_rows": 24, "moe_zero_pairs": 392, "moe_pairs": 18},
             {"decode_s": 0.01}]  # a call that read nothing
    rec = {"model_keys": keys, "serve_steps": steps}
    assert reader("zero_expert_share").read(rec) == pytest.approx(
        100 * 8792 / (12 * 4 * 584))
    assert reader("moe_live_pairs_per_row").read(rec) == pytest.approx(
        48 / (4 * 48))
    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines()]
    assert lines[0]["zero_experts"]["share_by_width"] == pytest.approx(1 / 3)
    assert lines[1]["moe_live_pairs"]["due_a_row"] == 0.25
