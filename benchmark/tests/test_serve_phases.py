"""The readers of the serving program's own names and phases, on a
hand-made record: module events, ``serve_steps`` with phases, ``trace_mono``;
and ``None`` wherever an older program leaves nothing to read."""
import json
import os

import pytest

from lib import harness, serve_phases

BENCH = harness.load_json(os.path.join(harness.REPO, "BENCHMARK.json"))
NEW = ("decode_device_ms", "prefill_chunk_device_ms", "serve_host_ms")


def step(t_end, step_s, decode_s=0.030, **phases):
    return {"decode_s": decode_s, "occupancy": 1.0, "step_s": step_s,
            "t_end": t_end, "phases": phases}


def record():
    steps = [
        # untraced: 30 ms a step, 26 of them the wait
        step(10.0, 0.030, decode_prepare=0.001, decode_upload=0.001,
             decode_dispatch=0.001, decode_wait=0.026, emit=0.0005),
        step(10.1, 0.032, decode_prepare=0.001, decode_upload=0.001,
             decode_dispatch=0.001, decode_wait=0.026, emit=0.0005,
             prefill_dispatch=0.002),
        # a commit step: the first token's wait is not the host's time
        step(10.2, 0.060, decode_prepare=0.001, decode_upload=0.001,
             decode_dispatch=0.001, decode_wait=0.026, emit=0.0005,
             prefill_dispatch=0.002, prefill_first_token=0.018,
             prefill_commit=0.008),
        # traced: the profiler slows the upload
        step(20.0, 0.050, decode_prepare=0.001, decode_upload=0.020,
             decode_dispatch=0.002, decode_wait=0.026, emit=0.0005),
        step(20.1, 0.050, decode_prepare=0.001, decode_upload=0.020,
             decode_dispatch=0.002, decode_wait=0.026, emit=0.0005),
        # a step that decoded nothing is not a decoding step
        step(20.2, 0.002, decode_s=0.0, prefill_dispatch=0.0015),
    ]
    return {
        "serve_steps": steps, "trace_mono": (19.5, 20.5),
        "trace": {"n_devices": 1, "module_seconds": {
            "jit_serve_decode_step": [0.0262, 0.0261, 0.0263],
            "jit_serve_prefill_chunk": [0.0175] * 4 + [0.0180, 0.0170],
            "jit_convert_element_type": [1e-5]}}}


def test_device_time_of_the_two_named_programs():
    rec = record()
    assert serve_phases.decode_device_ms(rec) == pytest.approx(26.2)
    assert serve_phases.prefill_chunk_device_ms(rec) == pytest.approx(17.5)
    rec["trace"]["module_seconds"]["jit_serve_prefill_chunk"] = [0.0175] * 4
    assert serve_phases.prefill_chunk_device_ms(rec) is None  # under five


def test_host_ms_is_the_step_less_its_waits_over_decoding_steps(capsys):
    v = serve_phases.serve_host_ms(record())
    # 4, 6, 16, 24, 24 ms over the five decoding steps
    assert v == pytest.approx(16.0)
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    ph = line["serve_phases_ms"]
    assert ph["steps"] == 6 and ph["traced_steps"] == 3
    assert ph["traced"]["decode_upload"] == pytest.approx(20.0)
    assert ph["untraced"]["decode_upload"] == pytest.approx(1.0)
    assert ph["untraced"]["prefill_first_token"] == pytest.approx(18.0)
    assert ph["all"]["step"] == pytest.approx(41.0)
    assert ph["untraced"]["self"] == pytest.approx(0.5)


@pytest.mark.parametrize("rec", [
    {},
    {"serve_steps": [], "trace": None},
    # the program before it had names or phases
    {"serve_steps": [{"decode_s": 0.03, "occupancy": 1.0}],
     "trace_mono": (0.0, 5.0),
     "trace": {"n_devices": 1,
               "module_seconds": {"jit__unknown": [0.0262, 0.0175]}}},
    {"serve_steps": [step(1.0, 0.03, decode_wait=0.02)],
     "trace": {"n_devices": 0}},
], ids=["empty", "no-trace", "older-program", "no-device-in-trace"])
def test_nothing_to_read_is_none_and_never_raises(rec):
    assert serve_phases.decode_device_ms(rec) is None
    assert serve_phases.prefill_chunk_device_ms(rec) is None
    if not rec.get("serve_steps") or "phases" not in rec["serve_steps"][0]:
        assert serve_phases.serve_host_ms(rec) is None


def test_untraced_record_still_gives_the_host_time():
    rec = record()
    del rec["trace"], rec["trace_mono"]
    assert serve_phases.serve_host_ms(rec) == pytest.approx(16.0)


@pytest.mark.parametrize("kind", NEW)
def test_each_new_metric_is_declared_for_both_serving_cells(kind):
    mine = [m for m in BENCH["per_layer"] if m["name"].startswith(kind + ".")]
    assert len(mine) == 2 and all(m["layer"] == "serve step" for m in mine)
    moved = {m["moves"] for m in mine}
    assert len(moved) == 2  # one reader a cell kind, split by what it moves
    for m in mine:
        reader = harness.load_module(os.path.join(
            harness.BENCH_DIR, "metrics", m["name"] + ".py"), "bench_metric")
        assert reader.read is getattr(serve_phases, kind)
        assert BENCH["per_layer"].index(m) >= 10  # appended, none moved
