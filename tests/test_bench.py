"""bench.py / bench_serve.py measure on the device JAX gives them or fail.

No probe child, no re-exec onto another backend, no stored "last good"
number: a mode that cannot measure is a non-zero exit with a message,
every record names its device, and step timing is fenced with
``jax.block_until_ready``.
"""

import json
import pathlib

import jax
import jax.numpy as jnp
import pytest

import bench
import bench_serve

_REPO = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture(autouse=True)
def _no_compile_cache(monkeypatch):
    # main() turns the persistent compile cache on; keep the test
    # process's jax config as it was
    monkeypatch.setenv("TADNN_NO_COMPILE_CACHE", "1")


def test_bad_sweep_seqs_is_loud():
    with pytest.raises(SystemExit) as e:
        bench._attention_block_sweep(
            {"sweep": 1, "seqs": "4096"}, heads=16, hd=128, on_tpu=True)
    assert "4096" in str(e.value) and "sweep table" in str(e.value)


def test_block_sweep_needs_the_tpu():
    with pytest.raises(SystemExit) as e:
        bench._attention_block_sweep(
            {"sweep": 1}, heads=16, hd=128, on_tpu=False)
    assert "TPU" in str(e.value)


def test_too_few_devices_is_a_nonzero_exit(monkeypatch, capsys):
    # conftest gives 8 devices; memfit's default world is 64
    monkeypatch.setattr(bench.sys, "argv", ["bench.py", "mode=memfit"])
    with pytest.raises(SystemExit) as e:
        bench.main()
    # a string code: python prints it to stderr and exits 1
    assert isinstance(e.value.code, str)
    assert "needs >= 64 devices and 8" in e.value.code
    assert "xla_force_host_platform_device_count=64" in e.value.code
    assert capsys.readouterr().out == ""  # no record for a run that measured nothing


@pytest.mark.parametrize("mode,n", [("pipeline", 4), ("overlap", 2),
                                    ("collectives", 2)])
def test_multi_device_modes_state_their_need(monkeypatch, mode, n):
    monkeypatch.setattr(jax, "device_count", lambda: 1)
    fn = {"pipeline": bench.bench_pipeline, "overlap": bench.bench_overlap,
          "collectives": bench.bench_collectives}[mode]
    with pytest.raises(SystemExit) as e:
        fn({"steps": 1})
    assert f"mode={mode} needs >= {n} devices and 1" in str(e.value)


def test_no_record_without_a_device(monkeypatch, capsys):
    monkeypatch.setattr(bench.sys, "argv", ["bench.py", "mode=gpt2"])
    monkeypatch.setattr(bench, "bench_gpt2", lambda args: {
        "metric": "m", "value": 1.0, "unit": "u", "vs_baseline": 0.0})
    bench.main()
    rec = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    d = jax.devices()[0]
    assert rec["device"] == {"platform": d.platform, "kind": d.device_kind,
                             "count": jax.device_count()}
    assert rec["value"] == 1.0


@pytest.fixture
def fenced(monkeypatch):
    """What jax.block_until_ready was called on, in order."""
    seen = []
    real = jax.block_until_ready
    monkeypatch.setattr(jax, "block_until_ready",
                        lambda x: seen.append(x) or real(x))
    return seen


def test_timed_chain_fences_with_block_until_ready(fenced):
    def step(state, batch):
        return state + batch, {"loss": state * 2.0}

    state, dt = bench.timed_chain(step, jnp.zeros(()), [1.0, 2.0, 3.0])
    assert float(state) == 6.0 and dt > 0
    # ONE fence, over the final state and metrics
    assert len(fenced) == 1
    assert float(fenced[0][0]) == 6.0 and float(fenced[0][1]["loss"]) == 6.0
    with pytest.raises(ValueError, match="at least one batch"):
        bench.timed_chain(step, jnp.zeros(()), [])


def test_timed_grad_fences_with_block_until_ready(fenced):
    calls = []

    def grad(q, k, v):
        calls.append(q)
        return (q * 2.0, k, v)

    x = jnp.ones((2,))
    dt = bench.timed_grad(grad, x, x, x, iters=3)
    assert dt > 0 and len(calls) == 3 and len(fenced) == 1


@pytest.mark.parametrize("script", ["bench.py", "bench_serve.py"])
def test_bench_scripts_hide_no_device(script):
    """Static guard on what was taken out: no child process, no forcing
    of a platform, no last-good file written."""
    src = (_REPO / script).read_text()
    for gone in ("subprocess", "probe_backend", "cpu_sim_env",
                 "_save_last_good", "backend_unreachable",
                 'environ["JAX_PLATFORMS"]', 'setdefault("JAX_PLATFORMS"',
                 'environ["XLA_FLAGS"]'):
        assert gone not in src, f"{script} still has {gone!r}"
    assert "enable_compilation_cache()" in src


def test_bench_serve_failure_is_an_error_not_a_record(monkeypatch, capsys):
    def boom(args, journal):
        raise RuntimeError("engine fell over")

    monkeypatch.setattr(bench_serve.sys, "argv", ["bench_serve.py"])
    monkeypatch.setattr(bench_serve, "run_load", boom)
    with pytest.raises(RuntimeError, match="engine fell over"):
        bench_serve.main()
    assert capsys.readouterr().out == ""
