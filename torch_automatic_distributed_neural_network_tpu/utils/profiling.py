"""Compiled-program cost analysis (SURVEY.md §5): FLOPs, memory and
bytes accessed of a jitted step from XLA, for MFU accounting and the
planner's cross-checks.  Timeline annotations live in ``obs.journal``
(``phase``); captures in ``obs.trace`` and ``tadnn profile``.
"""

from __future__ import annotations

from typing import Any

import jax


def _flops_of(compiled) -> float | None:
    try:
        cost = compiled.cost_analysis()
        if isinstance(cost, list):  # some backends return one dict per device
            cost = cost[0]
        return float(cost.get("flops", 0.0)) or None
    except Exception:
        return None


def _memory_of(compiled) -> dict | None:
    try:
        ma = compiled.memory_analysis()
        if ma is None:
            return None
        out = {}
        for k in ("argument_size_in_bytes", "output_size_in_bytes",
                  "temp_size_in_bytes", "alias_size_in_bytes",
                  "generated_code_size_in_bytes"):
            v = getattr(ma, k, None)
            if v is not None:
                out[k.replace("_in_bytes", "")] = int(v)
        return out or None
    except Exception:
        return None


def _bytes_accessed_of(compiled) -> float | None:
    """Total HBM bytes the executable touches per invocation (XLA cost
    analysis) — the measured upper bound for the planner's analytic
    comm-bytes estimate (obs.comms.crosscheck)."""
    try:
        cost = compiled.cost_analysis()
        if isinstance(cost, list):
            cost = cost[0]
        return float(cost.get("bytes accessed", 0.0)) or None
    except Exception:
        return None


# content-addressed memo for cost analyses: the analysis of an HLO
# module is a pure function of its text, so the digest of the lowered
# program is the whole key.  In-process hits skip the XLA compile;
# cross-process hits ride in the export cache's index as JSON-only
# records (no payload file), validated against the same env
# fingerprint as executables.
_cost_memo: dict[str, dict] = {}


def _cost_cache_key(lowered) -> str | None:
    import hashlib

    try:
        text = lowered.as_text()
    except Exception:
        return None  # backend can't render — compile uncached
    return "cost-" + hashlib.sha256(text.encode()).hexdigest()[:32]


def compiled_cost(fn, *args, **kwargs) -> dict | None:
    """ONE AOT compile, all analyses: ``{'flops': ..., 'memory': ...,
    'bytes_accessed': ...}``.

    Prefer this over calling :func:`compiled_flops` and
    :func:`compiled_memory` separately — each does its own
    lower().compile(), minutes of redundant XLA work on big sharded
    steps.  Results are memoized on the digest of the lowered HLO (and,
    when the export cache is enabled via ``TADNN_EXPORT_CACHE``,
    persisted in its index), so repeated what-if sweeps over the same
    program skip the compile entirely — a ``cost_analysis.cached``
    event marks each skip.

    Lower/compile failures return ``{'flops': None, 'memory': None,
    'error': '<reason>'}`` (and emit a ``cost_analysis.error`` journal
    event), so "compile failed: <why>" is distinguishable from "compiled
    fine but the backend exposes no analysis" (which returns analysis
    fields of None with NO 'error' key).  Failures are never cached.
    """
    from ..obs import journal as _journal

    try:
        lowered = fn.lower(*args, **kwargs)
    except Exception as e:
        reason = f"{type(e).__name__}: {e}"
        _journal.event("cost_analysis.error", error=reason)
        return {"flops": None, "memory": None, "error": reason}
    key = _cost_cache_key(lowered)
    if key is not None and key in _cost_memo:
        _journal.event("cost_analysis.cached", key=key, tier="memory")
        return dict(_cost_memo[key])
    cache = None
    if key is not None:
        from ..export import cache as _export_cache

        cache = _export_cache.resolve(None)  # env-gated, off by default
        if cache is not None:
            rec = cache.lookup(key)
            if rec is not None and cache.check_live(rec) is None:
                analysis = rec.get("analysis") or {}
                _cost_memo[key] = dict(analysis)
                _journal.event("cost_analysis.cached", key=key,
                               tier="disk")
                return dict(analysis)
    try:
        with _journal.span("compile", fn="aot_cost_analysis"):
            compiled = lowered.compile()
    except Exception as e:
        reason = f"{type(e).__name__}: {e}"
        _journal.event("cost_analysis.error", error=reason)
        return {"flops": None, "memory": None, "error": reason}
    out = {"flops": _flops_of(compiled), "memory": _memory_of(compiled)}
    ba = _bytes_accessed_of(compiled)
    if ba is not None:
        out["bytes_accessed"] = ba
    if key is not None:
        _cost_memo[key] = dict(out)
        if cache is not None:
            try:
                cache.put_record(key, {
                    "kind": "cost_analysis",
                    "env": _export_cache.env_fingerprint(),
                    "analysis": dict(out),
                })
            except OSError:
                pass  # read-only cache dir — the analysis still returns
    return out


def compiled_flops(fn, *args, **kwargs) -> float | None:
    """FLOP estimate for a jitted callable from XLA's cost analysis.

    Returns None when the backend doesn't expose cost analysis (e.g. some
    experimental platforms); callers fall back to analytic 6ND estimates.
    """
    cost = compiled_cost(fn, *args, **kwargs)
    return cost["flops"] if cost and not cost.get("error") else None


def compiled_memory(fn, *args, **kwargs) -> dict | None:
    """Per-executable memory breakdown from XLA's memory analysis:
    argument/output/temp/alias sizes in bytes.  The ground truth to check
    the planner's analytic HBM model against on real hardware.  None when
    the backend doesn't expose it."""
    cost = compiled_cost(fn, *args, **kwargs)
    return cost["memory"] if cost and not cost.get("error") else None


def memory_stats(device: Any | None = None) -> dict | None:
    dev = device or jax.devices()[0]
    try:
        return dev.memory_stats()
    except Exception:
        return None
