"""Operations and bytes, as functions of shapes and of the program's own
step counters, for what a model of mixed layer kinds with an expert FFN
adds: the grouped matmuls over the experts a step touches, and the paged
decode kernel with a window on some layers.  The numerators of
``moe_grouped_mm_roofline`` and ``paged_attn_roofline.by_kind``."""

from __future__ import annotations

from lib import counts


def grouped_mm_bytes(experts_touched: float, d_model: int, d_ff: int, *,
                     itemsize: int) -> float:
    """Least HBM traffic of the expert FFN's grouped matmuls: the gate, up
    and down matrices of every expert that got a token, read once (the rows
    and results are a few kilobytes a pair and are not counted)."""
    return float(3 * experts_touched * d_model * d_ff * itemsize)


def grouped_mm_flops(pairs: float, d_model: int, d_ff: int) -> float:
    """Three products of 2 d f a (token, expert) pair."""
    return float(3 * 2 * pairs * d_model * d_ff)


def paged_attention_by_kind(contexts: list[int], *, n_full: int,
                            n_window: int, window: int | None, heads: int,
                            kv_heads: int, head_dim: int,
                            itemsize: int) -> tuple[float, float]:
    """(flops, bytes) of one decode token's attention at each of
    ``contexts`` (keys visible on a full layer): a full layer reads every
    key, a window layer the last ``min(context, window)``."""
    full = sum(contexts)
    win = sum(min(c, window) for c in contexts) if window else full
    keys = n_full * full + n_window * win
    return (counts.paged_attention_flops(keys, heads, head_dim),
            counts.paged_attention_bytes(keys, kv_heads, head_dim,
                                         itemsize=itemsize))


def traced_decode_steps(rec) -> list[dict]:
    """The window's decoding ``serve.step`` events that ended inside the
    traced part."""
    span = rec.get("trace_mono")
    if not span:
        return []
    return [s for s in rec.get("serve_steps") or ()
            if s.get("decode_s") and s.get("t_end") is not None
            and span[0] <= s["t_end"] <= span[1]]


def module_ops(rec, module: str, wanted) -> tuple[float, int]:
    """(device seconds of the ops ``wanted(name)`` picks inside the program
    ``module``, runs of that program) over the traced part."""
    from lib import trace

    t = rec.get("trace")
    if not t or not t.get("n_devices"):
        return 0.0, 0
    runs = len((t.get("module_seconds") or {}).get(module) or ())
    took = sum(s for n, s in trace.ops_in_modules(t, module) if wanted(n))
    return took, runs
