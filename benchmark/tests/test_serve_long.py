"""Kind ``serve-long`` and what came with it: the control at rehearsal size
is not correct, the counts are the arithmetic, the ladder of padded lengths
holds every request, the decay leaves are the family's, and every new reader
gives ``None`` (and does not raise) on the record of a program that lacks
the counters, as an older commit does."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from lib import counts_gdn, harness, serving_large, serving_long, weights, \
    weights_gdn

BENCH = harness.load_json(os.path.join(harness.REPO, "BENCHMARK.json"))
CELLS = [w["name"] for w in BENCH["workloads"]]
NEW = ("gdn_chunk_roofline", "gdn_step_roofline", "gdn_chunk_ms",
       "gdn_decode_ms", "state_pool_gib")


def run_cli(*args, timeout=600):
    return subprocess.run(
        [sys.executable, os.path.join(harness.BENCH_DIR, "run.py"), *args],
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, cwd=harness.REPO,
        capture_output=True, text=True, timeout=timeout)


def long_cell() -> str:
    for name in CELLS:
        if harness.Cell(name, BENCH).mix["kind"] == "serve-long":
            return name
    pytest.skip("no cell of kind serve-long")


@pytest.mark.parametrize("seed", ["11", "12", "4123456789"])
def test_the_fp8_control_is_not_correct(seed):
    p = run_cli("--workload", long_cell(), "--seed", seed, "--seconds", "3",
                "--rehearsal", "--control")
    assert p.returncode == 0, p.stdout[-1500:] + p.stderr[-1500:]
    res = json.loads(p.stdout.strip().splitlines()[-1])
    assert res["control"] is True and res["correct"] is False
    assert res["program_correct"] is True


def test_the_cell_is_the_issues_parameter_for_parameter():
    cell = harness.Cell(long_cell(), BENCH)
    mix = cell.mix
    assert mix["arrivals"] == {"kind": "backlog", "requests_per_second": 3}
    assert mix["lengths"] == {
        "strata": 32,
        "prompt": {"median": 4096, "sigma": 1.0, "lo": 256, "hi": 32768},
        "output": {"median": 256, "sigma": 0.6, "lo": 32, "hi": 1024}}
    assert mix["engine"] == {
        "n_slots": 8, "max_len": 33792, "block_size": 16, "num_blocks": 4609,
        "prefill_chunk": 512, "attention_impl": "paged", "quant_kv": False,
        "admission": "reserve", "prefix_cache": False}
    assert (mix["drain_seconds"], mix["check_requests"],
            mix["trace_seconds"]) == (0, 8, 5.0)
    assert mix.get("traffic_seed") and "weights_seed" not in cell.config
    m = cell.config["model"]
    assert (m["d_model"], m["n_heads"], m["n_kv_heads"], m["head_size"],
            m["d_ff"], m["vocab_size"]) == (3840, 30, 30, 128, 11008, 100352)
    assert (m["linear_key_heads"], m["linear_value_heads"],
            m["linear_key_head_dim"], m["linear_value_head_dim"],
            m["linear_conv_kernel"]) == (30, 30, 96, 192, 4)
    assert m["layer_types"] == (["linear_attention"] * 3
                                + ["full_attention"]) * 4
    assert sorted(cell.config["reduced"]) == ["layer_types",
                                              "num_hidden_layers"]
    # the longest prompt and the longest answer fit a slot
    assert mix["lengths"]["prompt"]["hi"] + mix["lengths"]["output"]["hi"] \
        <= mix["engine"]["max_len"]


def test_the_ladder_holds_every_request():
    assert serving_long.bucket(1, 33792) == 64
    assert serving_long.bucket(4096, 33792) == 4096
    assert serving_long.bucket(4097, 33792) == 6144
    assert serving_long.bucket(24577, 33792) == 32768
    assert serving_long.bucket(32769, 33792) == 33792
    assert serving_long.bucket(33792, 33792) == 33792
    assert serving_long.bucket(100, 128) == 128
    seen = {serving_long.bucket(n, 33792) for n in range(1, 33793, 7)}
    assert len(seen) <= 20 and all(n % 32 == 0 for n in seen)


def test_the_decay_leaves_are_the_familys_and_the_others_are_not_touched():
    import jax.numpy as jnp

    key = weights.seed_key(4123456789)
    shapes = {"layers_0/attn/A_log": (30,), "layers_0/attn/dt_bias": (30,),
              "layers_0/attn/conv": (4, 64), "layers_3/attn/q_norm/scale": (8,)}
    dtypes = {p: np.dtype("float32") for p in shapes}
    flat = serving_long.seeded_weights(key, shapes, dtypes)
    A = np.exp(np.asarray(flat["layers_0/attn/A_log"]))
    dt = np.log1p(np.exp(np.asarray(flat["layers_0/attn/dt_bias"])))
    assert 0 < A.min() and A.max() <= 16 and A.max() > 4
    assert 1e-3 * 0.999 <= dt.min() and dt.max() <= 0.1 * 1.001
    for p in ("layers_0/attn/conv", "layers_3/attn/q_norm/scale"):
        np.testing.assert_array_equal(
            np.asarray(flat[p]), np.asarray(weights.leaf(key, p, shapes[p])))
        assert weights_gdn.decay_leaf(key, p, shapes[p]) is None
    # the same key and path give the same leaf: program and reference agree
    again = weights_gdn.decay_leaf(key, "layers_0/attn/A_log", (30,))
    np.testing.assert_array_equal(np.asarray(again),
                                  np.asarray(flat["layers_0/attn/A_log"]))
    assert flat["layers_0/attn/A_log"].dtype == jnp.float32


def test_the_exchange_is_undone():
    before = {n: getattr(serving_large, n) for n in serving_long.EXCHANGED}
    with serving_long.exchanged():
        assert serving_large.regrets is serving_long.regrets
        assert serving_large._warm is serving_long.warm
    assert {n: getattr(serving_large, n)
            for n in serving_long.EXCHANGED} == before


def test_counts_are_the_arithmetic():
    # a chunk of 512 tokens of 30 heads, keys of 96 and values of 192
    assert counts_gdn.recurrence_flops(512, 30, 96, 192) == \
        7 * 512 * 30 * 96 * 192
    per_token = 30 * ((2 * 96 + 2 * 192) * 2 + 8)
    state = 2 * 30 * 96 * 192 * 4
    assert counts_gdn.recurrence_bytes(512, 1, 30, 96, 192, itemsize=2) == \
        512 * per_token + state
    # a decode step of 7 slots: the state binds it
    assert counts_gdn.recurrence_bytes(7, 7, 30, 96, 192, itemsize=2) == \
        7 * (per_token + state)
    keys = harness.Cell(long_cell(), BENCH).config["model"]
    assert counts_gdn.linear_layers(keys) == (12, 30, 96, 192)
    assert counts_gdn.linear_layers({"d_model": 64}) == (0, 0, 0, 0)


@pytest.mark.parametrize("metric", NEW)
def test_a_record_without_the_counters_reads_none(metric):
    reader = harness.load_module(os.path.join(
        harness.BENCH_DIR, "metrics", metric + ".py"), "bench_metric")
    keys = {"d_model": 64, "n_heads": 4}
    step = {"decode_s": 0.01, "t_end": 1.0, "occupancy": 1.0,
            "new_tokens": 2}
    bare = {"model_keys": keys, "serve_steps": [step], "requests": [],
            "engine": {"n_slots": 2, "max_len": 32, "block_size": 16,
                       "prefill_chunk": 16}}
    assert reader.read(bare) is None
    traced = {**bare, "trace_mono": (0.0, 2.0), "peaks": {
        "flops_per_s": 1.0, "hbm_bytes_per_s": 1.0}, "trace": {
        "n_devices": 1, "ops": {"d": []}, "modules": {"d": []},
        "module_seconds": {}}, "serve_engine": {"kv_bytes_full": 1},
        "requests": [{"prompt": [1] * 20, "t_admit": 0.5}]}
    assert reader.read(traced) is None


def test_a_kernels_share_is_its_least_time_over_its_time():
    """One run of the chunk program, its kernel taking 1 ms in 12 layers of
    30 heads: the share is the recurrence's own bytes over that."""
    keys = harness.Cell(long_cell(), BENCH).config["model"]
    op = ("%tadnn_gdn_chunk.3 = f32[1] custom-call()", 1_000_000, 2_000_000)
    mod = ("jit_serve_prefill_chunk(1)", 0, 5_000_000)
    rec = {"model_keys": keys, "peaks": {"flops_per_s": 197e12,
                                         "hbm_bytes_per_s": 819e9},
           "trace": {"n_devices": 1, "ops": {"d": [op]},
                     "modules": {"d": [mod]},
                     "module_seconds": {"jit_serve_prefill_chunk": [5e-3]}}}
    share, work = counts_gdn.kernel_share(
        rec, "jit_serve_prefill_chunk", "tadnn_gdn_chunk", 512, 1)
    least = 12 * counts_gdn.recurrence_bytes(512, 1, 30, 96, 192,
                                             itemsize=2) / 819e9
    assert work["bound"] == "memory" and work["runs"] == 1
    assert share == pytest.approx(100 * least / 1e-3)
    assert 0 < share < 100


def test_a_staged_kernels_time_holds_the_compilers_copies():
    """A run of the decode program: the state pool is sliced into on-chip
    memory (two windows, one of them overlapping the kernel), the kernel
    runs, the pool is copied back; a copy of another array is not counted.
    The time is the union of the kernel and the pool's windows."""
    pool = "f32[9,30,96,192]"
    ev = lambda name, text, s, e: (f"%{name} = {text}", s, e)  # noqa: E731
    ops = [
        ev("slice-start.7", f"(({pool}), f32[3,30,96,192]) slice-start(%p)",
           100, 110),
        ev("slice-start.8", f"(({pool}), f32[6,30,96,192]) slice-start(%p)",
           110, 120),
        ev("fusion.1", "bf16[8,3840] fusion(%x)", 120, 400),
        ev("slice-done.7", "f32[3,30,96,192] slice-done(%slice-start.7)",
           400, 405),
        ev("slice-done.8", "f32[6,30,96,192] slice-done(%slice-start.8)",
           600, 610),
        ev("tadnn_gdn_step.3", f"(f32[8,3,10,192], {pool}) custom-call(%q)",
           500, 700),
        ev("copy-start.2", f"({pool}, {pool}, u32[]) copy-start(%t)",
           700, 705),
        ev("copy-start.9", "(bf16[8,3840], bf16[8,3840], u32[]) "
           "copy-start(%y)", 705, 710),
        ev("copy-done.9", "bf16[8,3840] copy-done(%copy-start.9)", 2000, 2500),
        ev("copy-done.2", f"{pool} copy-done(%copy-start.2)", 900, 1000),
    ]
    rec = {"trace": {"n_devices": 1, "ops": {"d": ops},
                     "modules": {"d": [("jit_serve_decode_step(5)", 0, 3000),
                                       ("jit_other(1)", 3000, 4000)]}}}
    took, runs = counts_gdn.staged_seconds(
        rec, "jit_serve_decode_step", "tadnn_gdn_step", pool)
    # [100, 405] u [110, 610] u [500, 700] u [700, 1000] = [100, 1000]
    assert runs == 1 and took == pytest.approx(900e-9)
    alone, _ = counts_gdn.staged_seconds(
        rec, "jit_serve_decode_step", "tadnn_gdn_step", "f32[1,1]")
    assert alone == pytest.approx(200e-9)
