"""Operations and bytes of a gated delta rule whose decay is a VECTOR a head
(Kimi Delta Attention: ``linear_decay == "channel"``), as functions of
shapes: the numerators of ``kda_chunk_roofline`` and ``kda_step_roofline``,
and the two kernels' device time as both of those and ``kda_chunk_ms`` /
``kda_step_ms`` read it.

As ``lib/counts_gdn.py`` counts the scalar rule: the RECURRENCE's own work,
token by token, whatever form a kernel computes it in (a chunked kernel's
key-key products block by block, its exponentials pair by pair and its
triangular solve are not operations the arithmetic needs, so a kernel that
spends them reads a lower share).

- Operations: a token and a head decay the state's rows (d_k d_v), do
  ``S^T k`` (2 d_k d_v), the rank-one write (2 d_k d_v) and ``S^T q``
  (2 d_k d_v): 7 d_k d_v, the scalar rule's count (its decay is the same
  d_k d_v multiplications by one number).
- Bytes: q and k (d_k each), v (d_v) and the output (d_v) of every token
  and head once, in the compute dtype; beta, 4 bytes, and THE d_k FLOAT32
  DECAYS a token a head (where the scalar rule reads one); and the state
  [heads, d_k, d_v] in float32 in and out once a sequence a call (a prefill
  chunk is one sequence; a decode step is one a decoding slot).

The step kernel runs in BOTH serving programs: since the chunk carries a
step's decode rows (``jit_serve_prefill_chunk`` is ``chunk_and_step``) a
backlog's traced seconds, which start at t = 0 where every call has a chunk,
may hold no ``jit_serve_decode_step`` at all.  So its time is taken over the
runs of either program, and its decoding rows from the engine's own
``state_rows`` (the slots whose state a call's step kernel read and wrote,
over the linear layers) on the ``serve.step`` events that ended inside the
traced part.  A program without the kernels or the counter gives ``None``.
"""

from __future__ import annotations

from lib import counts, counts_gdn, counts_moe, serve_phases

CHUNK_KERNEL, STEP_KERNEL = "tadnn_kda_chunk", "tadnn_kda_step"


recurrence_flops = counts_gdn.recurrence_flops  # 7 d_k d_v: the same rule


def recurrence_bytes(tokens: float, sequences: float, heads: int, d_k: int,
                     d_v: int, *, itemsize: int) -> float:
    per_token = heads * ((2 * d_k + 2 * d_v) * itemsize + 4 * d_k + 4)
    state = 2 * heads * d_k * d_v * 4
    return float(tokens * per_token + sequences * state)


def chunk_seconds(rec) -> tuple[float, int]:
    """(device seconds of the ops named ``tadnn_kda_chunk`` inside
    ``jit_serve_prefill_chunk``, runs of that program) over the traced
    part."""
    return counts_moe.module_ops(rec, serve_phases.PREFILL_MODULE,
                                 lambda name: CHUNK_KERNEL in name)


def step_seconds(rec) -> tuple[float, int]:
    """(device seconds of the ops named ``tadnn_kda_step`` WITH the
    compiler's own copies of the state pools round them,
    ``counts_gdn.staged_seconds``; runs) over the runs of both serving
    programs in the traced part."""
    _, heads, d_k, d_v = counts_gdn.linear_layers(rec["model_keys"])
    pool = f"f32[{rec['engine']['n_slots'] + 1},{heads},{d_k},{d_v}]"
    total, runs = 0.0, 0
    for module in (serve_phases.PREFILL_MODULE, serve_phases.DECODE_MODULE):
        took, n = counts_gdn.staged_seconds(rec, module, STEP_KERNEL, pool)
        if took:
            total, runs = total + took, runs + n
    return total, runs


def traced_state_rows(rec) -> tuple[float, int]:
    """(decoding slots a call: ``state_rows`` over the linear layers, their
    mean; the ``serve.step`` events that carry the counter and ended inside
    the traced part)."""
    span = rec.get("trace_mono")
    n = counts_gdn.linear_layers(rec["model_keys"])[0]
    if not span or not n:
        return 0.0, 0
    rows = [s["state_rows"] / n for s in rec.get("serve_steps") or ()
            if s.get("state_rows") is not None and s.get("t_end") is not None
            and span[0] <= s["t_end"] <= span[1]]
    return (sum(rows) / len(rows), len(rows)) if rows else (0.0, 0)


def kernel_share(rec, took: float, runs: int, tokens: float,
                 sequences: float):
    """(share of the roofline in %, working to print) of a kernel that took
    ``took`` device seconds over ``runs`` runs of its program, a run doing
    ``tokens`` tokens of ``sequences`` sequences in every linear layer.
    None where the trace or the model has nothing to read."""
    peaks = rec.get("peaks")
    n, heads, d_k, d_v = counts_gdn.linear_layers(rec["model_keys"])
    if not peaks or not n or not runs or not took or not tokens:
        return None
    least, bound = counts.roofline_seconds(
        n * recurrence_flops(tokens, heads, d_k, d_v),
        n * recurrence_bytes(tokens, sequences, heads, d_k, d_v, itemsize=2),
        peaks)
    return 100.0 * least * runs / took, {
        "runs": runs, "bound": bound, "tokens_a_run": tokens,
        "sequences_a_run": sequences, "linear_layers": n,
        "least_s_a_run": least, "took_s_a_run": took / runs}
