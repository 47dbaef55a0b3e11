"""Runtime device-timeline tracing: measured per-step attribution.

The analytic side of the comms story (planner.expected_collective_bytes,
tune/cost.py's roofline) predicts traffic but cannot see *exposed*
collective time — communication XLA failed to hide behind compute, the
term the ROADMAP's 61.4% -> 70% MFU push needs measured, not modeled.
This module closes that loop:

- :class:`StepTracer` captures a ``jax.profiler`` trace around
  instrumented steps (perfetto/Chrome-trace JSON — stdlib-parseable,
  unlike the xplane protobuf) with a ``tadnn_step`` TraceAnnotation
  marking each step's window;
- :func:`attribute` parses the timeline into per-step compute time,
  collective time, exposed collective time (interval arithmetic over
  the device-op lanes) and measured MFU, journaled as ``trace.step``;
- :func:`hlo_collective_bytes` reads collective payload bytes out of
  the compiled HLO text (the profiler events carry durations, not
  bytes), and :func:`crosscheck_collectives` journals the measured vs
  modeled ratio per collective category as ``trace.collective``.

Everything below the capture layer is pure stdlib (gzip/json/re), so
``tadnn report`` can re-attribute a saved trace on a machine with no
accelerator runtime.
"""

from __future__ import annotations

import glob
import gzip
import json
import math
import os
import re
import tempfile
from typing import Any, Callable, Iterable, Sequence

from . import journal as _journal

# The TraceAnnotation name marking one instrumented step's window on the
# python thread of the profile (args carry the step number).
STEP_ANNOTATION = "tadnn_step"

# HLO op-name prefixes that are collectives (async forms are emitted as
# <op>-start / <op>-done; matching on the prefix catches both).
COLLECTIVE_OPS = (
    "all-reduce",
    "all-gather",
    "reduce-scatter",
    "all-to-all",
    "collective-permute",
)

# Which planner.expected_collective_bytes per-device category each HLO
# collective family lands in (tune/cost.py._CATEGORY_AXES is the same
# classification from the modeled side).
CATEGORY_BY_OP = {
    "all-reduce": "grad_allreduce",
    "all-gather": "param_allgather",
    "reduce-scatter": "grad_reduce_scatter",
}

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}


def is_collective(op_name: str) -> bool:
    """True for HLO ops that move data between devices (either the sync
    form or the async ``-start``/``-done`` halves)."""
    return op_name.startswith(COLLECTIVE_OPS)


# -- capture ----------------------------------------------------------------


class StepTracer:
    """Profiler capture with per-step window annotations.

    Usage::

        with StepTracer() as tr:
            for i in range(5):
                with tr.step(i):
                    state, m = ad.step(state, batch)
                    jax.block_until_ready(m)   # fence: the window must
                                               # contain the device work
        recs = attribute(parse_perfetto(tr.trace_path))

    The fence matters: dispatch is async, so an unfenced window measures
    host-side enqueue, not the device timeline.  ``trace_path`` is the
    perfetto_trace.json.gz the capture produced (set on exit).
    """

    def __init__(self, logdir: str | None = None):
        self.logdir = logdir or tempfile.mkdtemp(prefix="tadnn_trace_")
        self.trace_path: str | None = None

    def __enter__(self) -> "StepTracer":
        import jax

        jax.profiler.start_trace(
            self.logdir,
            create_perfetto_link=False,
            create_perfetto_trace=True,
        )
        return self

    def step(self, i: int):
        """Annotation context marking step ``i``'s window on the trace."""
        import jax

        return jax.profiler.TraceAnnotation(STEP_ANNOTATION, step=i)

    def __exit__(self, *exc: Any) -> None:
        import jax

        jax.profiler.stop_trace()
        self.trace_path = find_perfetto_trace(self.logdir)


def find_perfetto_trace(logdir: str) -> str | None:
    """Newest perfetto_trace.json.gz under a profiler logdir (each
    capture writes ``plugins/profile/<timestamp>/``)."""
    hits = glob.glob(os.path.join(
        logdir, "plugins", "profile", "*", "perfetto_trace.json.gz"
    ))
    return max(hits, key=os.path.getmtime) if hits else None


# -- parsing ----------------------------------------------------------------


def parse_perfetto(path: str) -> dict:
    """Parse a perfetto/Chrome-trace JSON(.gz) into the two lanes the
    attribution needs: step windows (``tadnn_step`` annotations) and
    device op events (anything carrying an ``hlo_op`` arg).  Timestamps
    and durations are microseconds on one shared clock."""
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rt") as f:
        data = json.load(f)
    steps: list[dict] = []
    ops: list[dict] = []
    for e in data.get("traceEvents", ()):
        if e.get("ph") != "X":
            continue
        args = e.get("args") or {}
        name = e.get("name", "")
        if name == STEP_ANNOTATION:
            try:
                step = int(args.get("step", len(steps)))
            except (TypeError, ValueError):
                step = len(steps)
            steps.append({"step": step, "ts": e["ts"],
                          "dur": e.get("dur", 0.0)})
        elif "hlo_op" in args:
            ops.append({"name": args["hlo_op"], "ts": e["ts"],
                        "dur": e.get("dur", 0.0), "tid": e.get("tid")})
    steps.sort(key=lambda s: s["ts"])
    ops.sort(key=lambda o: o["ts"])
    return {"steps": steps, "ops": ops, "path": path}


def _union(intervals: Iterable[tuple[float, float]]) -> list[tuple[float, float]]:
    """Merge possibly-overlapping [start, end) intervals."""
    ivs = sorted((s, e) for s, e in intervals if e > s)
    out: list[tuple[float, float]] = []
    for s, e in ivs:
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def _total(union: Sequence[tuple[float, float]]) -> float:
    return sum(e - s for s, e in union)


def _overlap(a: Sequence[tuple[float, float]],
             b: Sequence[tuple[float, float]]) -> float:
    """Total length of the intersection of two interval unions."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        s = max(a[i][0], b[j][0])
        e = min(a[i][1], b[j][1])
        if e > s:
            total += e - s
        if a[i][1] <= b[j][1]:
            i += 1
        else:
            j += 1
    return total


def attribute(parsed: dict, *, flops_per_step: float | None = None,
              peak_flops_per_chip: float | None = None,
              n_chips: int | None = None) -> list[dict]:
    """Per-step attribution from a parsed timeline.

    For each ``tadnn_step`` window: clip the device-op events to it,
    classify collective vs compute by HLO op name, and compute

    - ``compute_s`` / ``collective_s``: union lengths of each class
      (union, not sum — parallel op lanes must not double-count);
    - ``exposed_collective_s``: collective union MINUS its overlap with
      the compute union — communication the schedule failed to hide,
      the measured analog of tune/cost.py's worst-case comm term;
    - ``measured_mfu`` when the caller supplies ``flops_per_step``
      (peak/chip-count default to the live backend's).

    All durations in seconds.  Invariants (tested):
    ``exposed <= collective`` and ``compute, collective <= wall``.
    """
    recs = []
    for win in parsed["steps"]:
        t0, t1 = win["ts"], win["ts"] + win["dur"]
        comp, coll = [], []
        coll_by_family: dict[str, float] = {}
        n_ops = 0
        for op in parsed["ops"]:
            s = max(op["ts"], t0)
            e = min(op["ts"] + op["dur"], t1)
            if e <= s:
                continue
            n_ops += 1
            if is_collective(op["name"]):
                coll.append((s, e))
                fam = next(f for f in COLLECTIVE_OPS
                           if op["name"].startswith(f))
                coll_by_family[fam] = coll_by_family.get(fam, 0.0) + (
                    (e - s) / 1e6
                )
            else:
                comp.append((s, e))
        comp_u, coll_u = _union(comp), _union(coll)
        collective_s = _total(coll_u) / 1e6
        exposed_s = collective_s - _overlap(comp_u, coll_u) / 1e6
        wall_s = win["dur"] / 1e6
        rec = {
            "step": win["step"],
            "wall_s": wall_s,
            "compute_s": _total(comp_u) / 1e6,
            "collective_s": collective_s,
            "exposed_collective_s": max(0.0, exposed_s),
            "n_ops": n_ops,
        }
        if coll_by_family:
            rec["collectives"] = {
                k: round(v, 9) for k, v in sorted(coll_by_family.items())
            }
        mfu = _measured_mfu(flops_per_step, wall_s,
                            peak_flops_per_chip, n_chips)
        if mfu is not None:
            rec["measured_mfu"] = mfu
        recs.append(rec)
    return recs


def _measured_mfu(flops_per_step: float | None, wall_s: float,
                  peak: float | None, n_chips: int | None) -> float | None:
    if not flops_per_step or wall_s <= 0:
        return None
    if peak is None or n_chips is None:
        try:
            import jax

            from ..training.metrics import peak_flops_per_chip

            peak = peak if peak is not None else peak_flops_per_chip()
            n_chips = n_chips if n_chips is not None else jax.device_count()
        except Exception:
            return None
    if not peak or not n_chips:
        return None
    return flops_per_step / wall_s / (peak * n_chips)


# -- capture + attribute in one call ----------------------------------------


def trace_steps(
    step_fn: Callable[[Any, Any], tuple[Any, Any]],
    state: Any,
    batch: Any,
    *,
    steps: int = 3,
    first_step: int = 0,
    logdir: str | None = None,
    flops_per_step: float | None = None,
    journal: "Any | None" = None,
) -> tuple[Any, list[dict]]:
    """Run ``steps`` instrumented calls of ``step_fn(state, batch) ->
    (state, metrics)`` under one profiler capture, attribute the
    timeline, and journal one ``trace.step`` event per step.  Returns
    ``(final_state, attribution_records)``.

    Each step is fenced (``block_until_ready`` on its metrics) so the
    annotation window contains the device work — the capture is NOT
    steady-state throughput and its wall time lands in the trainer's
    ``trace`` goodput bucket, never ``step``.
    """
    import jax

    tracer = StepTracer(logdir)
    with tracer:
        for k in range(steps):
            with tracer.step(first_step + k):
                state, metrics = step_fn(state, batch)
                jax.block_until_ready(metrics)
    if tracer.trace_path is None:
        raise FileNotFoundError(
            f"profiler produced no perfetto_trace.json.gz under "
            f"{tracer.logdir} (jax {jax.__version__} without perfetto "
            "trace support?)"
        )
    recs = attribute(parse_perfetto(tracer.trace_path),
                     flops_per_step=flops_per_step)
    jnl = journal if journal is not None else _journal.get_default()
    for r in recs:
        jnl.event("trace.step", trace=tracer.trace_path, **r)
    return state, recs


# -- measured collective bytes (compiled HLO text) --------------------------

# `%name = <shape> all-reduce(...)` — the definition line of a collective
# instruction.  `-start` covers async forms; `-done` deliberately does
# NOT match (its result repeats the -start shape and would double-count).
_COLL_DEF_RE = re.compile(
    r"=\s*(?P<shape>\([^)]*\)|\S+)\s+"
    r"(?P<op>" + "|".join(COLLECTIVE_OPS) + r")(?:-start)?\("
)
_SHAPE_RE = re.compile(r"([a-z][a-z0-9]*)\[([0-9,]*)\]")


def _shape_bytes(shape_text: str) -> int:
    """Total bytes of every ``dtype[dims]`` in an HLO shape string
    (handles tuple shapes; unknown dtypes counted at 4 bytes)."""
    total = 0
    for dtype, dims in _SHAPE_RE.findall(shape_text):
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES.get(dtype, 4)
    return total


def hlo_collective_bytes(compiled_text: str) -> dict[str, dict]:
    """Per-family collective payload bytes parsed from compiled HLO text.

    The profiler timeline has durations but no byte counts, so the
    measured-bytes side of the crosscheck comes from the executable
    itself: every collective instruction's result shape, summed per op
    family.  Per-device numbers (HLO text is the per-device SPMD
    program), directly comparable to
    ``expected_collective_bytes()['per_device'][cat]['payload_bytes']``.
    """
    out: dict[str, dict] = {}
    for m in _COLL_DEF_RE.finditer(compiled_text):
        fam = m.group("op")
        b = _shape_bytes(m.group("shape"))
        rec = out.setdefault(fam, {"count": 0, "payload_bytes": 0})
        rec["count"] += 1
        rec["payload_bytes"] += b
    return out


def measured_collective_bytes(ad: Any, rng: Any, sample_batch: Any) -> dict:
    """Measured per-device collective bytes for an AutoDistribute's
    compiled step (AOT text lowering — nothing executed)."""
    text = ad.compiled_step_text(rng, sample_batch)
    return hlo_collective_bytes(text) if text else {}


def crosscheck_collectives(
    measured: dict, modeled_per_device: dict, *,
    grad_accum: int = 1, journal: "Any | None" = None,
) -> list[dict]:
    """Join measured (HLO) and modeled (planner) collective bytes and
    journal one ``trace.collective`` event per category.

    ``ratio`` is measured/modeled payload bytes; ``within_2x`` is the
    acceptance band (the modeled side is exact ring-payload math, so on
    the bench configs the ratio lands at ~1.0 — drift beyond 2x means
    the plan model and the executable disagree about what moves).  The
    HLO text is one microbatch; ``grad_accum`` scales it to the modeled
    per-step convention.
    """
    cats = {CATEGORY_BY_OP.get(f, f): v for f, v in measured.items()}
    out = []
    for fam, cat in CATEGORY_BY_OP.items():
        meas = cats.get(cat, {}).get("payload_bytes", 0) * max(1, grad_accum)
        model = (modeled_per_device.get(cat) or {}).get("payload_bytes", 0)
        if not meas and not model:
            continue
        ratio = (meas / model) if (meas and model) else None
        rec = {
            "category": cat,
            "hlo_op": fam,
            "measured_bytes": int(meas),
            "modeled_bytes": int(model),
            "count": cats.get(cat, {}).get("count", 0),
            "ratio": round(ratio, 4) if ratio is not None else None,
            "within_2x": (ratio is not None and 0.5 <= ratio <= 2.0),
        }
        out.append(rec)
        jnl = journal if journal is not None else _journal.get_default()
        jnl.event("trace.collective", **rec)
    return out


def exposed_fraction(steps: Sequence[dict]) -> float | None:
    """Fraction of total collective time that is exposed across a set of
    ``trace.step`` records — the measured-overlap feed for
    ``tune.cost.score(measured_overlap=...)``.  None when the steps saw
    no collectives (single device)."""
    coll = sum(s.get("collective_s") or 0.0 for s in steps)
    exp = sum(s.get("exposed_collective_s") or 0.0 for s in steps)
    if coll <= 0 or not math.isfinite(coll):
        return None
    return min(1.0, max(0.0, exp / coll))
