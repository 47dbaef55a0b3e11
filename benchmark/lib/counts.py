"""Operations and bytes as functions of shapes.  No PR that claims a gain
may change these: they are the numerators of every utilisation here."""

from __future__ import annotations


def train_step_model_flops(n_params: int, tokens: int) -> float:
    """6 N T: forward 2 N T, backward 4 N T.  Recomputation is not counted
    and neither is attention's own s^2 term (6 L s d a token, 4% of 6 N at
    1.3B and s = 1024), so this is model FLOPs, a lower bound on the work."""
    return 6.0 * n_params * tokens


def flash_attention_flops(batch: int, heads: int, seq: int, head_dim: int,
                          *, causal: bool, window: int | None,
                          backward: bool) -> float:
    """Matmul FLOPs of attention over the positions the mask admits:
    forward QK^T and PV (4 hd a pair); backward recomputes QK^T and forms
    dP, dQ, dK, dV (10 hd a pair)."""
    if causal:
        w = min(window or seq, seq)
        pairs = w * (w + 1) / 2 + (seq - w) * w
    else:
        pairs = seq * seq
    per_pair = (10 if backward else 4) * head_dim
    return float(batch * heads * pairs * per_pair)


def flash_attention_bytes(batch: int, heads: int, kv_heads: int, seq: int,
                          head_dim: int, *, itemsize: int,
                          backward: bool) -> float:
    """Least HBM traffic: forward reads q, k, v and writes o; backward reads
    q, k, v, o, do and writes dq, dk, dv."""
    q = batch * heads * seq * head_dim * itemsize
    kv = batch * kv_heads * seq * head_dim * itemsize
    return float(4 * q + 4 * kv) if backward else float(2 * q + 2 * kv)


def paged_attention_bytes(context_tokens: int, kv_heads: int, head_dim: int,
                          *, itemsize: int) -> float:
    """Least HBM traffic of one decode step's attention in one layer: every
    cached key and value of every running slot read once."""
    return float(2 * context_tokens * kv_heads * head_dim * itemsize)


def paged_attention_flops(context_tokens: int, heads: int,
                          head_dim: int) -> float:
    """q.k and p.v over the context: 4 hd a (head, key) pair."""
    return float(4 * context_tokens * heads * head_dim)


def roofline_seconds(flops: float, bytes_: float, peaks: dict) -> tuple[float, str]:
    """The least time the chip could take, and which bound sets it."""
    tf = flops / peaks["flops_per_s"]
    tb = bytes_ / peaks["hbm_bytes_per_s"]
    return (tf, "compute") if tf >= tb else (tb, "memory")
