"""What the serving program says of itself, read for the per-layer metrics
of the layer ``serve step``: the device time of its two named programs from
the trace's ``module_seconds``, and the host's share of an engine step from
the ``phases`` the engine puts on its ``serve.step`` events (host seconds by
phase, ``step_s`` for the whole iteration, ``t_end`` on the clock of
``trace_mono``).  A program without the names or the phases (an older
commit) gives ``None`` everywhere, and nothing here raises for it.
"""

from __future__ import annotations

import json
import statistics

DECODE_MODULE = "jit_serve_decode_step"
PREFILL_MODULE = "jit_serve_prefill_chunk"
# phases in which the host waits for the device, so not the host's own time
WAITS = ("decode_wait", "prefill_first_token")


def module_ms(rec, module: str, at_least: int = 1):
    """Median device duration of one named program over the traced part, in
    ms; ``None`` where the trace holds fewer than ``at_least`` runs of it."""
    t = rec.get("trace")
    if not t or not t.get("n_devices"):
        return None
    runs = (t.get("module_seconds") or {}).get(module) or ()
    if len(runs) < at_least:
        return None
    return 1e3 * statistics.median(runs)


def decode_device_ms(rec):
    return module_ms(rec, DECODE_MODULE)


def prefill_chunk_device_ms(rec):
    return module_ms(rec, PREFILL_MODULE, at_least=5)


def phase_medians_ms(steps: list[dict]) -> dict:
    """Median of every phase over the steps in which it ran, of the whole
    step and of the step's self time (``step_s`` less its phases), in ms."""
    by: dict[str, list[float]] = {}
    for s in steps:
        for k, v in s["phases"].items():
            by.setdefault(k, []).append(v)
        by.setdefault("step", []).append(s["step_s"])
        by.setdefault("self", []).append(
            s["step_s"] - sum(s["phases"].values()))
    return {k: 1e3 * statistics.median(v) for k, v in by.items()}


def serve_host_ms(rec):
    """Median over the decoding steps of the step less its waits for the
    device, in ms.  Prints the phase medians first: over all the steps, and
    apart over those that ended inside and outside the traced part, which
    says what the profiler slows."""
    steps = [s for s in rec.get("serve_steps") or ()
             if isinstance(s.get("phases"), dict) and s.get("step_s")]
    if not steps:
        return None
    span = rec.get("trace_mono")

    def traced(s) -> bool:
        return bool(span) and s.get("t_end") is not None \
            and span[0] <= s["t_end"] <= span[1]

    inside = [s for s in steps if traced(s)]
    outside = [s for s in steps if not traced(s)]
    print(json.dumps({"serve_phases_ms": {
        "steps": len(steps), "traced_steps": len(inside),
        "all": phase_medians_ms(steps),
        "traced": phase_medians_ms(inside),
        "untraced": phase_medians_ms(outside)}}), flush=True)
    host = [s["step_s"] - sum(s["phases"].get(w, 0.0) for w in WAITS)
            for s in steps if s.get("decode_s")]
    return 1e3 * statistics.median(host) if host else None
