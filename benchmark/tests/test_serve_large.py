"""Kind ``serve-large`` and the per-layer readers that came with it: the
control at rehearsal size is not correct, the counts are the arithmetic,
and every new reader gives ``None`` (and does not raise) on the record of a
program that lacks the counters, as an older commit does."""
import json
import os
import subprocess
import sys

import pytest

from lib import counts, counts_moe, harness

BENCH = harness.load_json(os.path.join(harness.REPO, "BENCHMARK.json"))
CELLS = [w["name"] for w in BENCH["workloads"]]


def run_cli(*args, timeout=600):
    return subprocess.run(
        [sys.executable, os.path.join(harness.BENCH_DIR, "run.py"), *args],
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, cwd=harness.REPO,
        capture_output=True, text=True, timeout=timeout)


def last_json(stdout: str) -> dict:
    return json.loads(stdout.strip().splitlines()[-1])


NEW = ("kv_pool_gib", "moe_grouped_mm_decode_ms", "moe_grouped_mm_chunk_ms",
       "moe_grouped_mm_roofline", "paged_attn_roofline.by_kind")


def large_cell() -> str:
    for name in CELLS:
        if harness.Cell(name, BENCH).mix["kind"] == "serve-large":
            return name
    pytest.skip("no cell of kind serve-large")


@pytest.mark.parametrize("seed", ["11", "12", "14"])
def test_the_fp8_control_is_not_correct(seed):
    p = run_cli("--workload", large_cell(), "--seed", seed, "--seconds", "3",
                "--rehearsal", "--control")
    assert p.returncode == 0, p.stdout[-1500:] + p.stderr[-1500:]
    res = last_json(p.stdout)
    assert res["control"] is True and res["correct"] is False
    assert res["program_correct"] is True


def test_a_big_leaf_is_what_weights_leaf_draws():
    """``serving_large.big_leaf`` takes the path's share of a leaf as
    operands (so that like shapes share one program) and draws the values
    ``weights.leaf`` draws for that path."""
    import zlib

    import jax
    import jax.numpy as jnp
    import numpy as np

    from lib import serving_large, weights

    key = weights.seed_key(4123456789)
    for path in ("layers_1/mlp/experts_up", "layers_1/attn_norm/scale"):
        fold = np.uint32(zlib.crc32(path.encode()) & 0x7FFFFFFF)
        for dtype in (jnp.bfloat16, jnp.float32):
            got = jax.jit(serving_large.big_leaf, static_argnums=(2, 3))(
                key, fold, (3, 40, 24), np.dtype(dtype),
                path.endswith("scale"))
            want = weights.leaf(key, path, (3, 40, 24)).astype(dtype)
            assert got.dtype == want.dtype
            np.testing.assert_array_equal(np.asarray(got, np.float32),
                                          np.asarray(want, np.float32))


def test_every_seed_of_the_large_cell_does_the_same_work():
    """The cell names ``weights_seed`` and ``traffic_seed`` (see
    ``lib/serving_large.py``): two seeds of a rehearsal serve the same
    requests on the same weights, so they emit the same tokens in the same
    number of finished requests' worth of work; the mix is still ISSUE
    27's, block by block."""
    from lib import arrivals

    cell = harness.Cell(large_cell(), BENCH)
    assert cell.config.get("weights_seed") is not None
    mix = cell.mix
    plan = arrivals.plan(mix, mix["traffic_seed"], 51.0, 1000)
    assert len(plan) == round(mix["arrivals"]["requests_per_second"] * 51.0)
    k = mix["lengths"]["strata"]
    want_p = sorted(arrivals.lognormal_strata(**mix["lengths"]["prompt"],
                                              strata=k))
    want_o = sorted(arrivals.lognormal_strata(**mix["lengths"]["output"],
                                              strata=k))
    for i in range(0, len(plan) - k + 1, k):
        assert sorted(len(p.prompt) for p in plan[i:i + k]) == want_p
        assert sorted(p.max_new for p in plan[i:i + k]) == want_o
    routing = []
    for seed in ("11", "4123456789"):
        p = run_cli("--workload", large_cell(), "--seed", seed, "--seconds",
                    "2", "--rehearsal")
        assert p.returncode == 0, p.stdout[-1500:] + p.stderr[-1500:]
        lines = [json.loads(x) for x in p.stdout.splitlines()
                 if x.startswith("{")]
        assert lines[-1]["correct"] is True
        routing.append([x["routing"]["held_share_of_pairs"] for x in lines
                        if "routing" in x][0])
    # the same pairs on the same experts, however many steps the 2 s held
    assert abs(routing[0] - routing[1]) < 0.02, routing


def test_counts_are_the_arithmetic():
    # three matrices of 3072 x 3072 in bfloat16 an expert
    assert counts_moe.grouped_mm_bytes(7, 3072, 3072, itemsize=2) == \
        7 * 3 * 3072 * 3072 * 2
    assert counts_moe.grouped_mm_flops(8, 3072, 3072) == 8 * 6 * 3072 * 3072
    flops, bytes_ = counts_moe.paged_attention_by_kind(
        [100, 5000], n_full=1, n_window=4, window=4096, heads=48, kv_heads=8,
        head_dim=128, itemsize=2)
    keys = (100 + 5000) + 4 * (100 + 4096)
    assert bytes_ == counts.paged_attention_bytes(keys, 8, 128, itemsize=2)
    assert flops == counts.paged_attention_flops(keys, 48, 128)


@pytest.mark.parametrize("metric", NEW)
def test_a_record_without_the_counters_reads_none(metric):
    reader = harness.load_module(os.path.join(
        harness.BENCH_DIR, "metrics", metric + ".py"), "bench_metric")
    keys = {"d_model": 64, "n_heads": 4}
    step = {"decode_s": 0.01, "t_end": 1.0, "occupancy": 1.0}
    bare = {"model_keys": keys, "serve_steps": [step], "requests": [],
            "engine": {"n_slots": 2, "max_len": 32, "block_size": 16}}
    assert reader.read(bare) is None
    traced = {**bare, "trace_mono": (0.0, 2.0), "peaks": {
        "flops_per_s": 1.0, "hbm_bytes_per_s": 1.0}, "trace": {
        "n_devices": 1, "ops": {"d": []}, "modules": {"d": []},
        "module_seconds": {}}}
    assert reader.read(traced) is None
