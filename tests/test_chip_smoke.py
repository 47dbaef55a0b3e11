"""chip_smoke.py off the chip: its rehearsal path, and its refusals.

The rehearsal runs the smoke's own phases — Trainer steps, four serve
engines, the kernel-vs-reference check, the cache line — at the ``test``
model size on the CPU backend.  It proves paths and arguments; what the
chip run proves (the compiled kernels, the timings) it cannot.
"""

import json

import jax
import pytest

import chip_smoke


@pytest.fixture(autouse=True)
def _no_compile_cache(monkeypatch):
    # the smoke turns the persistent compile cache on for its process;
    # under pytest that process is the whole test session
    monkeypatch.setenv("TADNN_NO_COMPILE_CACHE", "1")


@pytest.fixture(scope="module")
def rehearsal():
    """One rehearsal run, shared: (exit code, stdout lines)."""
    import contextlib
    import io

    buf = io.StringIO()
    with pytest.MonkeyPatch.context() as mp, contextlib.redirect_stdout(buf):
        mp.setenv("TADNN_NO_COMPILE_CACHE", "1")  # as _no_compile_cache
        rc = chip_smoke.main(["--rehearsal"])
    return rc, [json.loads(ln) for ln in buf.getvalue().splitlines()]


def test_rehearsal_says_so_on_its_first_line(rehearsal):
    rc, lines = rehearsal
    assert rc == 0
    assert lines[0]["phase"] == "rehearsal"
    assert "not a chip run" in lines[0]["note"]


def test_rehearsal_last_line_is_exactly_the_contract(rehearsal):
    _, lines = rehearsal
    d = jax.devices()[0]
    assert lines[-1] == {"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(jax.devices())}}
    assert list(lines[-1]) == ["ok", "device"]
    assert list(lines[-1]["device"]) == ["platform", "kind", "count"]


def test_rehearsal_train_phase(rehearsal):
    _, lines = rehearsal
    (train,) = [r for r in lines if r.get("phase") == "train"]
    sz = chip_smoke.REHEARSAL
    assert train["n_devices"] == 1 and train["model"] == "gpt2-test"
    assert train["precision"] == "bf16" and train["steps"] == sz.steps
    assert len(train["losses"]) == sz.steps >= 4
    assert train["losses"][-1] < train["losses"][0]
    assert train["preflight_skipped"] is False
    assert train["compile_s"] > 0 and train["step_s_median"] > 0


def test_rehearsal_serve_phases(rehearsal):
    _, lines = rehearsal
    sz = chip_smoke.REHEARSAL
    serve = {r["phase"]: r for r in lines
             if str(r.get("phase", "")).startswith("serve.")}
    assert sorted(serve) == ["serve.dense.bf16", "serve.dense.int8",
                             "serve.paged.bf16", "serve.paged.int8"]
    for rec in serve.values():
        assert rec["n_finished"] == sz.streams
        assert rec["tokens_generated"] == sz.streams * sz.max_new
    for kv in ("bf16", "int8"):
        paged = serve[f"serve.paged.{kv}"]
        assert paged["attention_impl"] == "paged"
        # a numeric comparison on the pool the engine served from
        assert paged["reference_max_abs"] > 0
        assert (paged["kernel_vs_reference_max_abs"]
                <= paged["kernel_rtol"] * paged["reference_max_abs"])


@pytest.mark.parametrize("name", sorted(chip_smoke.FUSED_CUTS))
def test_rehearsal_fused_phases(rehearsal, name):
    """A chunk that carries a step's decode rows against the two calls, a
    few layers of each serving configuration: the chunk's logits, every
    pool array and the step's tokens."""
    _, lines = rehearsal
    (rec,) = [r for r in lines if r.get("phase") == "fused." + name]
    assert rec["rows"] == 16 + 4 and rec["tokens_equal"] == 3
    assert rec["token_regret_max"] == 0.0
    assert max(rec["chunk_logits_max_abs_err"],
               rec["pool_max_abs_err"]) <= 1e-5


def test_rehearsal_cache_line(rehearsal):
    _, lines = rehearsal
    (cache,) = [r for r in lines if r.get("phase") == "cache"]
    # the fixture opted out (TADNN_NO_COMPILE_CACHE): nothing was placed
    assert cache["dir"] is None and cache["placed_by"] is None


def test_refuses_without_a_chip(capsys):
    assert jax.devices()[0].platform != "tpu"
    assert chip_smoke.main([]) != 0
    out, err = capsys.readouterr()
    assert out == ""  # no result, not even a config line
    assert "needs a TPU" in err and "--rehearsal" in err


def test_a_phase_that_raises_fails_the_run(monkeypatch, capsys):
    def boom(*a, **k):
        raise RuntimeError("serve fell over")

    monkeypatch.setattr(chip_smoke, "train_phase",
                        lambda *a, **k: {"phase": "train"})
    monkeypatch.setattr(chip_smoke, "serve_phase", boom)
    assert chip_smoke.main(["--rehearsal"]) != 0
    out, err = capsys.readouterr()
    lines = [json.loads(ln) for ln in out.splitlines()]
    assert lines[-1] == {"ok": False, "error": "RuntimeError: serve fell over"}
    assert not any(r.get("ok") is True for r in lines)
    assert "serve fell over" in err  # the traceback says what failed


def test_four_chip_option_needs_four_devices(monkeypatch, capsys):
    monkeypatch.setattr(jax, "devices", lambda *a: jax.local_devices()[:2])
    assert chip_smoke.main(["--rehearsal", "--chips", "4"]) != 0
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()]
    assert lines[-1]["ok"] is False
    assert "needs four devices" in lines[-1]["error"]


@pytest.mark.slow
def test_rehearsal_four_chips(capsys):
    """The --chips 4 path on four of the eight virtual devices: the
    sharded train phase against one device, and no other phase."""
    assert chip_smoke.main(["--rehearsal", "--chips", "4"]) == 0
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()]
    phases = [r["phase"] for r in lines if "phase" in r]
    assert phases == ["rehearsal", "config", "train.one_device",
                      "train.4chips.fsdp", "train.4chips.auto", "cache"]
    fsdp = lines[3]
    assert fsdp["mesh"]["degrees"] == {"fsdp": 4}
    assert max(fsdp["loss_rel_diff_vs_one_device"]) <= fsdp["parity_rtol"]
    assert len({s["device"] for s in fsdp["largest_param"]["shards"]}) == 4
    assert lines[-1]["ok"] is True
