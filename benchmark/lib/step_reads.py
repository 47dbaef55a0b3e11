"""A serving call by what it WAITED for, over the whole window: the ``read``
field the engine puts on the ``serve.step`` event of the call that reads
(``programs`` that went out since the read before, the decode ``rows`` and
their ``ctx_keys``, the ``chunk_rows`` and ``chunk_pos`` of the chunk they
carried), and the collector's ``gc_s`` / ``gc_full`` beside it.  The engine
reads a program one call after it dispatched it, so ``read`` and not
``n_prefill_chunks`` says whose time a call's ``step_s`` is.

A read that covers more than one program (a prompt's chunks go out unread
while nobody decodes: 17 at a backlog's 17th call) is no steady-state call:
it is left out of every median and is no stall.  A program without the field
(an older commit) gives ``None`` everywhere, and nothing here raises for it.
"""

from __future__ import annotations

import json
import statistics

DEEP_POS = 8192       # a chunk from this position on is a deep one
DEPTH_BUCKET = 4096   # chunk calls are held against others of their depth
STALL_TIMES, STALL_OVER_S = 3.0, 0.050
FEWEST_DEEP = 5


def calls(rec) -> list[dict] | None:
    """The window's calls that waited for exactly one program; ``None``
    where no event says what it read."""
    steps = [s for s in rec.get("serve_steps") or ()
             if isinstance(s.get("read"), dict) and s.get("step_s")]
    if not steps:
        return None
    return [s for s in steps if s["read"].get("programs") == 1]


def _median_ms(group) -> float | None:
    return 1e3 * statistics.median(s["step_s"] for s in group) if group else None


def chunk_call_ms(rec, from_pos: int = 0, at_least: int = 1):
    """Median ``step_s`` of the calls whose program carried a prefill chunk
    that began at ``from_pos`` or beyond, in ms."""
    got = calls(rec)
    if got is None:
        return None
    group = [s for s in got if s["read"]["chunk_rows"] > 0
             and s["read"]["chunk_pos"] >= from_pos]
    return _median_ms(group) if len(group) >= at_least else None


def chunk_call_deep_ms(rec):
    return chunk_call_ms(rec, DEEP_POS, FEWEST_DEEP)


def decode_call_ms(rec):
    """The same for the calls that waited for a decode step alone."""
    got = calls(rec)
    if got is None:
        return None
    return _median_ms([s for s in got if not s["read"]["chunk_rows"]])


def kind(step: dict) -> tuple:
    """What a call is held against: a decode-only call against the others,
    a chunk call against those of its depth (its attention grows with it)."""
    r = step["read"]
    if not r["chunk_rows"]:
        return ("decode",)
    return ("chunk", r["chunk_pos"] // DEPTH_BUCKET)


def stalls(rec) -> list[tuple[dict, float]] | None:
    """(call, its kind's median ``step_s``) of every call that took more
    than ``STALL_TIMES`` the median of its kind and ``STALL_OVER_S`` over
    it; ``None`` without ``read``."""
    got = calls(rec)
    if got is None:
        return None
    by: dict[tuple, list] = {}
    for s in got:
        by.setdefault(kind(s), []).append(s)
    out = []
    for group in by.values():
        m = statistics.median(s["step_s"] for s in group)
        out += [(s, m) for s in group
                if s["step_s"] > max(STALL_TIMES * m, m + STALL_OVER_S)]
    return sorted(out, key=lambda sm: sm[0].get("step") or 0)


def serve_stall_ms(rec):
    """What the window's stalled calls took beyond their kind's median, in
    ms (0.0 for a window without one).  Prints ``serve_stalls`` first: up to
    20 of them, each with its largest phase and what the collector and the
    compiler did inside it, and the window's collector totals."""
    found = stalls(rec)
    if found is None:
        return None
    rows = []
    for s, m in found[:20]:
        phases = s.get("phases") or {}
        top = max(phases, key=phases.get, default=None)
        rows.append({
            "step": s.get("step"), "step_s": s["step_s"], "median_s": m,
            "kind": kind(s)[0], "chunk_pos": s["read"]["chunk_pos"],
            "phase": top, "phase_s": phases.get(top),
            "gc_s": s.get("gc_s"), "gc_full": s.get("gc_full"),
            "compiles": s.get("compiles")})
    steps = rec["serve_steps"]
    print(json.dumps({"serve_stalls": {
        "n": len(found), "calls": rows,
        "window_gc_s": sum(s.get("gc_s") or 0.0 for s in steps),
        "window_gc_full": sum(s.get("gc_full") or 0 for s in steps),
        "window_calls": len(steps)}}), flush=True)
    return 1e3 * sum(s["step_s"] - m for s, m in found)
