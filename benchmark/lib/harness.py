"""What every kind of cell shares: finding a cell's files, the device, the
compile counter, the profiler window, the verdict, and the result line."""

from __future__ import annotations

import contextlib
import importlib.util
import json
import math
import os
import shutil
import sys
import time

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH_DIR)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """One entry of ``workloads`` with the three files its names lead to."""

    def __init__(self, name: str, bench: dict | None = None):
        self.bench = bench or load_json(os.path.join(REPO, "BENCHMARK.json"))
        hits = [w for w in self.bench["workloads"] if w["name"] == name]
        if not hits:
            raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
        self.entry = hits[0]
        self.name = name
        self.chips = int(self.entry["chips"])
        cfg_entry = next(c for c in self.bench["configs"]
                         if c["name"] == self.entry["config"])
        self.config = load_json(os.path.join(REPO, cfg_entry["file"]))
        self.mix = load_json(os.path.join(
            BENCH_DIR, "traffic", self.entry["traffic"] + ".json"))
        self.limits = load_json(os.path.join(
            BENCH_DIR, "limits", name + ".json"))

    def metrics(self, group: str) -> list[dict]:
        """The metrics of ``end_to_end`` or ``per_layer`` this cell reports:
        those without a ``workloads`` key and those that list the cell."""
        return [m for m in self.bench[group]
                if "workloads" not in m or self.name in m["workloads"]]

    def generator(self):
        kind = self.mix["kind"]
        return load_module(os.path.join(BENCH_DIR, "generators", kind + ".py"),
                           "bench_generator_" + kind.replace("-", "_"))


def apply_rehearsal(cell: Cell) -> None:
    """Tiny sizes for a run off the chip: each file's ``rehearsal`` group
    overrides its own keys, group by group."""
    for doc in (cell.config, cell.mix, cell.limits):
        for key, val in (doc.get("rehearsal") or {}).items():
            if isinstance(val, dict) and isinstance(doc.get(key), dict):
                doc[key] = {**doc[key], **val}
            else:
                doc[key] = val


PROGRAM = "torch_automatic_distributed_neural_network_tpu"


def program_or_exit() -> None:
    """The system under test is the one in THIS checkout.  Where the
    checkout holds none (or another copy would be imported, say one
    installed in the environment), exit 2 without printing a result."""
    import importlib.util

    spec = importlib.util.find_spec(PROGRAM)
    origin = os.path.abspath(spec.origin) if spec and spec.origin else ""
    if not origin.startswith(REPO + os.sep):
        print(f"benchmark: no {PROGRAM} in this checkout ({REPO}); found "
              f"{origin or 'none'}", file=sys.stderr)
        raise SystemExit(2)


def device_or_exit(chips: int, rehearsal: bool):
    """The devices the cell runs on; exit 2 without printing a result when
    JAX has no TPU or fewer chips than the cell asks for."""
    import jax

    devs = jax.devices()
    on_chip = devs[0].platform == "tpu"
    if not on_chip and not rehearsal:
        print(f"benchmark: needs a TPU, JAX found {devs[0].platform!r} "
              f"(--rehearsal runs tiny sizes off the chip)", file=sys.stderr)
        raise SystemExit(2)
    if len(devs) < chips:
        print(f"benchmark: the cell asks for {chips} chips, JAX found "
              f"{len(devs)}", file=sys.stderr)
        raise SystemExit(2)
    return devs[:chips], on_chip


def enable_cache() -> str | None:
    """The program's own switch (``JAX_COMPILATION_CACHE_DIR`` if set, else
    ``<checkout>/.jax_cache``), and every program kept, however quick its
    compile: the server runs many small per-shape programs."""
    import jax

    from torch_automatic_distributed_neural_network_tpu.topology import (
        enable_compilation_cache,
    )

    path = enable_compilation_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def mark(ctx: dict, phase: str) -> None:
    """One line saying how long after process start a set-up phase ended."""
    print(json.dumps({"setup_phase": phase,
                      "t_s": round(time.perf_counter() - ctx["t0"], 3)}),
          flush=True)


class CompileCounter:
    """Counts backend compiles (and persistent-cache reads, which also mean
    a program was not yet in this process) through ``jax.monitoring``."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_retrieval_time_sec")

    def __init__(self):
        import jax

        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, _dur, **_kw):
        if name in self.EVENTS:
            self.n += 1


class Profiler:
    """A profiler capture of part of the window, in a run of its own
    (``--trace 1``).  Spans are ``jax.profiler.TraceAnnotation``s the
    benchmark puts around its own calls into the program."""

    def __init__(self, on: bool, logdir: str):
        self.on, self.logdir, self.active = on, logdir, False
        self.t_start = self.t_stop = None

    def start(self):
        if not self.on or self.t_start is not None:
            return
        import jax

        shutil.rmtree(self.logdir, ignore_errors=True)
        # no Python call tracing: it slows the host loop it is measuring;
        # TraceAnnotation spans are host-tracer events and stay
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(self.logdir, profiler_options=options)
        self.active, self.t_start = True, time.perf_counter()
        self.mono_start = time.monotonic()

    def stop(self):
        if not self.active:
            return
        import jax

        self.mono_stop = time.monotonic()
        jax.profiler.stop_trace()
        self.active, self.t_stop = False, time.perf_counter()

    def span(self, name: str):
        if not self.active:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(name)

    def reduced(self, spans: tuple[str, ...]) -> dict | None:
        if not self.on or self.t_stop is None:
            return None
        from lib import trace

        out = trace.reduce(trace.read_xplane(trace.find_xplane(self.logdir)),
                           spans)
        shutil.rmtree(self.logdir, ignore_errors=True)
        return out


class Verdict:
    """Every number compared, printed beside its limit; ``correct`` is that
    each is finite and within it."""

    def __init__(self, limits: dict):
        self.limits = limits
        self.rows: list[dict] = []

    def check(self, name: str, value: float) -> None:
        limit = self.limits[name]["limit"]
        ok = math.isfinite(value) and value <= limit
        self.rows.append({"check": name, "value": value, "limit": limit,
                          "ok": ok})
        print(json.dumps(self.rows[-1]), flush=True)

    @property
    def correct(self) -> bool:
        return bool(self.rows) and all(r["ok"] for r in self.rows)


def memory_peak_bytes(devices) -> int | None:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def device_record(devices, *, reduced: dict | None = None) -> dict:
    import jax

    d = devices[0]
    rec = {"platform": d.platform, "kind": d.device_kind,
           "count": jax.device_count(),
           "memory_peak_bytes": memory_peak_bytes(devices)}
    if reduced and reduced.get("n_devices"):
        rec["busy_s"] = reduced["busy_s"]
        rec["window_s"] = reduced["window_s"]
    return rec
