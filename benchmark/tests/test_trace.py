"""The trace reduction, on interval arithmetic and on a small recorded
trace: one step of the 1.3B train cell on a TPU v5 lite (my chip run,
PR 24), cut down as its ``note`` says."""
import gzip
import json
import os

import pytest

from lib import trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "train_step_trace.json.gz")
SPANS = ("fence_on_loss", "train_step_dispatch", "input_batch")


@pytest.fixture(scope="module")
def parsed():
    fx = json.load(gzip.open(DATA, "rt"))
    return {"devices": {k: {kk: [tuple(x) for x in vv]
                            for kk, vv in v.items()}
                        for k, v in fx["devices"].items()},
            "host": [tuple(h) for h in fx["host"]]}


def test_union_overlap_gaps_clip():
    u = trace.union([(0, 2), (1, 3), (5, 6), (6, 6)])
    assert u == [(0, 3), (5, 6)] and trace.total(u) == 4
    assert trace.overlap(u, [(2, 5.5)]) == 1.5
    assert trace.gaps(u, -1, 8) == [(-1, 0), (3, 5), (6, 8)]
    assert trace.clip([(0, 10)], 2, 4) == [(2, 4)]
    assert trace.clip([(0, 1)], 2, 4) == []


def test_instruction_text_is_parsed():
    op = ('%attn.33 = (bf16[256,1024,128]{2,1,0:T(8,128)(2,1)}, f32[256,1024,'
          '128]{2,1,0:T(8,128)}) custom-call(bf16[256,1024,128]{2,1,0} %a, '
          'bf16[256,1024,128]{2,1,0} %b, bf16[256,1024,128]{2,1,0} %c), '
          'custom_call_target="tpu_custom_call", operand_layout_constraints={}')
    assert trace.opcode(op) == "custom-call"
    assert trace.is_pallas(op) and trace.n_operands(op) == 3
    assert trace.short(op) == ("%attn.33 = (bf16[256,1024,128], "
                               "f32[256,1024,128]) custom-call")
    loop = "%while.8 = (s32[]{:T(128)}, bf16[16,1024]{1,0}) while(%tuple.1)"
    assert trace.opcode(loop) == "while"
    fus = "%fusion.5 = bf16[24,8192]{1,0:T(8,128)(2,1)} fusion(bf16[2] %x), kind=kLoop"
    assert trace.opcode(fus) == "fusion"
    assert trace.short(fus) == "%fusion.5 = bf16[24,8192] fusion"
    assert trace.module_base("jit_train_step(123)") == "jit_train_step"


def test_recorded_step_reduces(parsed):
    r = trace.reduce(parsed, SPANS)
    assert r["n_devices"] == 1
    assert 1.09 < r["window_s"] < 1.11  # one 1.096 s step and its fence
    assert 0 < r["busy_s"] <= r["window_s"]
    assert r["collective_s"] == 0.0 and r["collective_exposed_s"] == 0.0
    # loops are containers: their bodies' ops are counted, they are not
    assert not any(trace.opcode(n) in trace.CONTAINERS
                   for n in r["op_seconds"])
    assert any(trace.opcode(n[0]) == "while"
               for n in parsed["devices"]["/device:TPU:0"]["ops"])
    assert set(r["gap_seconds"]) <= set(SPANS) | {"outside_spans"}
    assert list(r["module_seconds"]) == ["jit_train_step"]
    top = trace.top(r["op_seconds"], 5)
    assert len(top) == 5 and all(len(n) <= 120 for n, _ in top)
    assert top == sorted(top, key=lambda x: -x[1])


def test_recorded_step_holds_the_flash_kernels(parsed):
    r = trace.reduce(parsed, SPANS)
    ops = trace.ops_in_modules(r, "jit_train_step")
    pallas = [(n, s) for n, s in ops if trace.is_pallas(n)]
    # 24 layers x (forward, recomputed forward, dq, dk/dv)
    assert len(pallas) == 96
    assert sorted({trace.n_operands(n) for n, _ in pallas}) == [3, 6]
    assert sum(trace.n_operands(n) == 3 for n, _ in pallas) == 48
    assert 0.12 < sum(s for _, s in pallas) < 0.15
    assert trace.ops_in_modules(r, "jit_other") == []


def test_no_device_plane_reduces_to_nothing():
    assert trace.reduce({"devices": {}, "host": []}) == {"n_devices": 0}


def test_read_xplane_of_a_cpu_capture(tmp_path):
    """The real file format, on the only device a test has: a CPU capture
    holds host lines and no TPU plane."""
    import jax
    import jax.numpy as jnp

    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("input_batch"):
        jnp.ones((8, 8)).sum().block_until_ready()
    jax.profiler.stop_trace()
    parsed = trace.read_xplane(trace.find_xplane(str(tmp_path)))
    assert parsed["devices"] == {}
    assert any(n == "input_batch" for n, _, _ in parsed["host"])
    assert trace.reduce(parsed, ("input_batch",)) == {"n_devices": 0}
