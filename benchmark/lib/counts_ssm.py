"""Operations and bytes of the selective scan of a ``state_space`` layer
(Mamba-1), as functions of shapes: the numerators of ``ssm_chunk_roofline``
and ``ssm_step_roofline``, and the two kernels' device time as both of those
and ``ssm_chunk_ms`` / ``ssm_step_ms`` read it.

What is counted is the RECURRENCE's own work, token by token, as
``lib/counts_gdn.py`` counts the delta rule's:

- Operations: a token and a channel do, for each of the state's N numbers,
  the decay's exponent and its exponential (2), the update (decay times
  state, input times ``B``, their sum: 3) and the output's product and sum
  (2): 7 N; and once a channel ``Delta c``, ``D c`` and the output's last
  add: 7 N + 3.  None of it is a matmul: the chip's peak, which is its
  matmul units', is far above what elementwise arithmetic reaches, so the
  bound that binds here is memory.
- Bytes: of every token ``c`` in the compute dtype, ``Delta`` in float32
  (the configuration states it) and the output in float32 (it is the
  memory units' operand), d_in numbers each, and ``B`` and ``C``, N float32
  each; the state [N, d_in] float32 in and out once a sequence a call (a
  prefill chunk is one sequence; a decode step is one a decoding slot).

The step kernel is read in the runs of EITHER serving program: the chunk
carries a step's decode rows (``jit_serve_prefill_chunk`` is
``chunk_and_step``), so a backlog's traced seconds may hold no
``jit_serve_decode_step`` at all.  The compiler's own copies of a state pool
round the step kernel belong to its time (``counts_gdn.staged_seconds``).  A
program without the kernels or the counter gives ``None``.
"""

from __future__ import annotations

from lib import counts, counts_gdn, counts_moe, serve_phases

CHUNK_KERNEL, STEP_KERNEL = "tadnn_ssm_chunk", "tadnn_ssm_step"
MODULES = (serve_phases.PREFILL_MODULE, serve_phases.DECODE_MODULE)


def scan_layers(model_keys: dict) -> tuple[int, int, int]:
    """(state-space layers, inner channels, state size) of a
    configuration's ``model`` keys; (0, 0, 0) without such layers."""
    n = list(model_keys.get("layer_types") or ()).count("state_space")
    if not n:
        return 0, 0, 0
    return n, model_keys["ssm_inner"], model_keys["ssm_state"]


def scan_flops(tokens: float, d_in: int, n_state: int) -> float:
    return float(tokens * d_in * (7 * n_state + 3))


def scan_bytes(tokens: float, sequences: float, d_in: int, n_state: int, *,
               itemsize: int) -> float:
    per_token = d_in * (itemsize + 4 + 4) + 2 * n_state * 4
    return float(tokens * per_token + sequences * 2 * n_state * d_in * 4)


def step_seconds(rec) -> tuple[float, int]:
    """(device seconds of the ops named ``tadnn_ssm_step`` WITH the
    compiler's own copies of the state pools round them,
    ``counts_gdn.staged_seconds``; runs) over the runs of both serving
    programs in the traced part."""
    _, d_in, n_state = scan_layers(rec["model_keys"])
    pool = f"f32[{rec['engine']['n_slots'] + 1},{n_state},{d_in}]"
    total, runs = 0.0, 0
    for module in MODULES:
        took, n = counts_gdn.staged_seconds(rec, module, STEP_KERNEL, pool)
        if took:
            total, runs = total + took, runs + n
    return total, runs


def chunk_seconds(rec) -> tuple[float, int]:
    """(device seconds of the ops named ``tadnn_ssm_chunk`` inside
    ``jit_serve_prefill_chunk``, runs of that program) over the traced part
    (a chunk reads and writes ONE row of a pool, beside the kernel: the
    compiler's whole-pool copies are the step kernel's)."""
    return counts_moe.module_ops(rec, serve_phases.PREFILL_MODULE,
                                 lambda name: CHUNK_KERNEL in name)


def traced_state_rows(rec) -> tuple[float, int]:
    """(decoding slots a call: the engine's ``state_rows`` over the
    state-space layers, their mean; the ``serve.step`` events that carry the
    counter and ended inside the traced part)."""
    span = rec.get("trace_mono")
    n = scan_layers(rec["model_keys"])[0]
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
    ``tokens`` tokens of ``sequences`` sequences in every state-space layer.
    None where the trace or the model has nothing to read."""
    peaks = rec.get("peaks")
    n, d_in, n_state = scan_layers(rec["model_keys"])
    if not peaks or not n or not runs or not took or not tokens:
        return None
    least, bound = counts.roofline_seconds(
        n * scan_flops(tokens, d_in, n_state),
        n * scan_bytes(tokens, sequences, d_in, n_state, itemsize=2), peaks)
    return 100.0 * least * runs / took, {
        "runs": runs, "bound": bound, "tokens_a_run": tokens,
        "sequences_a_run": sequences, "scan_layers": n,
        "least_s_a_run": least, "took_s_a_run": took / runs}
