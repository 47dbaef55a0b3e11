"""From a profiler trace (``.xplane.pb``) to busy/idle, per-operation time
and gap attribution.  The interval arithmetic is ``obs/trace.py``'s
(``_union``, ``_overlap``), copied here so that no PR that claims a gain
can change it.

What a v5e trace holds (looked at by hand, PR 24): one plane per chip named
``/device:TPU:<n>``, with a line ``XLA Modules`` (one event per executed
program, named ``jit_<function>(<fingerprint>)``) and a line ``XLA Ops``
(one event per HLO operation inside a module); host threads sit in the
plane ``/host:CPU``, where ``jax.profiler.TraceAnnotation`` spans appear by
name.  All times are nanoseconds on one clock.
"""

from __future__ import annotations

import glob
import os
import re

COLLECTIVE = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
              "collective-permute", "collective-broadcast")


def union(intervals):
    """Merge possibly-overlapping [start, end) intervals."""
    ivs = sorted((s, e) for s, e in intervals if e > s)
    out: list[tuple[float, float]] = []
    for s, e in ivs:
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def total(u) -> float:
    return sum(e - s for s, e in u)


def overlap(a, b) -> float:
    """Total length of the intersection of two interval unions."""
    tot, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            tot += e - s
        if a[i][1] <= b[j][1]:
            i += 1
        else:
            j += 1
    return tot


def clip(intervals, lo: float, hi: float):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def gaps(u, lo: float, hi: float):
    """The complement of a union inside [lo, hi]."""
    out, at = [], lo
    for s, e in u:
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if hi > at:
        out.append((at, hi))
    return out


def find_xplane(logdir: str) -> str:
    hits = glob.glob(os.path.join(logdir, "plugins", "profile", "*",
                                  "*.xplane.pb"))
    if not hits:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return max(hits, key=os.path.getmtime)


def read_xplane(path: str) -> dict:
    """``{"devices": {plane: {"modules": [...], "ops": [...]}}, "host":
    [...]}``, every event ``(name, start_ns, end_ns)``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    out = {"devices": {}, "host": []}
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            dev = {"modules": [], "ops": []}
            for line in plane.lines:
                if line.name == "XLA Modules":
                    key = "modules"
                elif line.name == "XLA Ops":
                    key = "ops"
                else:
                    continue
                for ev in line.events:
                    dev[key].append((ev.name, ev.start_ns,
                                     ev.start_ns + ev.duration_ns))
            out["devices"][plane.name] = dev
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for ev in line.events:
                    out["host"].append((ev.name, ev.start_ns,
                                        ev.start_ns + ev.duration_ns))
    return out


CONTAINERS = ("while", "conditional", "call")


def _body(name: str) -> str:
    return name.split(" = ", 1)[-1]


def _opcode_at(body: str) -> tuple[int, int]:
    """(start, end) of the opcode in an instruction's text after ``=``:
    the word before the first ``(`` that is not part of the result shape
    (a tuple shape opens with ``(`` at once; layouts sit in braces)."""
    depth = brace = 0
    for i, ch in enumerate(body):
        if ch == "{":
            brace += 1
        elif ch == "}":
            brace -= 1
        elif brace:
            continue
        elif ch == "(":
            if depth or i == 0 or body[i - 1] in " (":
                depth += 1
            else:
                start = body.rfind(" ", 0, i) + 1
                return start, i
        elif ch == ")" and depth:
            depth -= 1
    return 0, len(body)


def opcode(name: str) -> str:
    """The HLO opcode of an ``XLA Ops`` event, whose name is the whole
    instruction: ``%x = shape opcode(operands), attributes``."""
    body = _body(name)
    a, b = _opcode_at(body)
    return body[a:b]


def short(name: str) -> str:
    """``%fusion.5 = bf16[24,8192,2048] fusion``: the instruction's name,
    its shape without layouts, and its opcode."""
    body = _body(name)
    a, b = _opcode_at(body)
    shape = re.sub(r"\{[^{}]*\}", "", body[:a]).strip()
    return f"{name.split(' = ', 1)[0]} = {shape} {body[a:b]}"[:120]


def is_pallas(name: str) -> bool:
    return 'custom_call_target="tpu_custom_call"' in name


def n_operands(name: str) -> int:
    """Operands of an instruction, counted by their ``%`` references."""
    body = _body(name)
    _, b = _opcode_at(body)
    depth = 0
    for i in range(b, len(body)):
        if body[i] == "(":
            depth += 1
        elif body[i] == ")":
            depth -= 1
            if not depth:
                return body[b:i].count("%")
    return 0


def module_base(name: str) -> str:
    """``jit_train_step(123456)`` -> ``jit_train_step``."""
    return re.sub(r"\(\d+\)$", "", name)


def reduce(parsed: dict, spans: tuple[str, ...] = ()) -> dict:
    """Busy and idle seconds, per-op and per-module seconds, the longest
    idle gaps by the host span they fall in, collectives exposed.

    The window is the hull of the host spans named in ``spans`` when any
    exist (the benchmark's own annotations around its calls into the
    program), else the hull of the device events.  Per-device numbers are
    averaged over the devices that ran anything.
    """
    host = [(n, s, e) for n, s, e in parsed["host"] if n in spans]
    devs = {k: v for k, v in parsed["devices"].items()
            if v["ops"] or v["modules"]}
    if not devs:
        return {"n_devices": 0}
    if host:
        lo, hi = min(s for _, s, _ in host), max(e for _, _, e in host)
    else:
        evs = [x for d in devs.values() for x in d["ops"] + d["modules"]]
        lo, hi = min(s for _, s, _ in evs), max(e for _, _, e in evs)
    busy_s, exposed_s, coll_s = [], [], []
    op_s: dict[str, float] = {}
    mod_ev: dict[str, list[float]] = {}
    gap_by: dict[str, float] = {}
    for d in devs.values():
        ops = d["ops"] or d["modules"]
        busy = union(clip([(s, e) for _, s, e in ops], lo, hi))
        busy_s.append(total(busy) / 1e9)
        coll = union(clip([(s, e) for n, s, e in ops
                           if n.startswith(COLLECTIVE)], lo, hi))
        comp = union(clip([(s, e) for n, s, e in ops
                           if not n.startswith(COLLECTIVE)], lo, hi))
        coll_s.append(total(coll) / 1e9)
        exposed_s.append((total(coll) - overlap(coll, comp)) / 1e9)
        for n, s, e in ops:
            # a while loop's event spans its body's own events
            if e > lo and s < hi and opcode(n) not in CONTAINERS:
                op_s[n] = op_s.get(n, 0.0) + (min(e, hi) - max(s, lo)) / 1e9
        for n, s, e in d["modules"]:
            if lo <= (s + e) / 2 <= hi:
                mod_ev.setdefault(module_base(n), []).append((e - s) / 1e9)
        for gs, ge in gaps(busy, lo, hi):
            # the innermost (shortest) host span that covers the gap's middle
            mid = (gs + ge) / 2
            cover = [(e - s, n) for n, s, e in host if s <= mid <= e]
            label = min(cover)[1] if cover else "outside_spans"
            gap_by[label] = gap_by.get(label, 0.0) + (ge - gs) / 1e9
    n = len(devs)
    return {
        "n_devices": n,
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(busy_s) / n,
        "collective_s": sum(coll_s) / n,
        "collective_exposed_s": sum(exposed_s) / n,
        "op_seconds": {k: v / n for k, v in op_s.items()},
        # by the midpoint: the device's clock and the host's differ by
        # about half a millisecond, so an edge event may poke out
        "modules": {k: [(nm, s, e) for nm, s, e in d["modules"]
                        if lo <= (s + e) / 2 <= hi] for k, d in devs.items()},
        "ops": {k: [(nm, s, e) for nm, s, e in d["ops"]
                    if lo <= (s + e) / 2 <= hi
                    and opcode(nm) not in CONTAINERS]
                for k, d in devs.items()},
        "window_ns": (lo, hi),
        "module_seconds": mod_ev,
        "gap_seconds": {k: v / n for k, v in gap_by.items()},
    }


def top(d: dict, k: int = 10) -> list:
    return [[short(n) if " = " in n else n, v]
            for n, v in sorted(d.items(), key=lambda kv: -kv[1])[:k]]


def ops_in_modules(reduced: dict, base: str) -> list:
    """The (name, seconds) of every device op that ran inside a module
    whose name without its fingerprint is ``base``, over all devices."""
    out = []
    for dev, mods in reduced["modules"].items():
        spans = union((s, e) for nm, s, e in mods if module_base(nm) == base)
        i = 0
        for nm, s, e in sorted(reduced["ops"][dev], key=lambda x: x[1]):
            while i < len(spans) and spans[i][1] < s:
                i += 1
            if i < len(spans) and spans[i][0] <= s and e <= spans[i][1]:
                out.append((nm, (e - s) / 1e9))
    return out
