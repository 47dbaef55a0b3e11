"""Operations and bytes of the gated delta rule of a ``linear_attention``
layer, as functions of shapes: the numerators of ``gdn_chunk_roofline`` and
``gdn_step_roofline``.

What is counted is the RECURRENCE's own work, token by token, whatever
form a kernel computes it in: a chunked kernel's extra products (the
key-key matrix of a sub-chunk, its triangular solve) are not operations the
arithmetic needs, so a kernel that spends them reads a lower share.

- Operations: a token and a head do ``S^T k`` (2 d_k d_v), the decay of
  ``S`` (d_k d_v), the rank-one write (2 d_k d_v) and ``S^T q`` (2 d_k d_v):
  7 d_k d_v.
- Bytes: q and k (d_k each), v (d_v) and the output (d_v) of every token
  and head once, in the compute dtype; g and beta, 4 bytes each; and the
  state [heads, d_k, d_v] in float32 in and out once a sequence a call (a
  prefill chunk is one sequence; a decode step is one a decoding slot).
"""

from __future__ import annotations


def recurrence_flops(tokens: float, heads: int, d_k: int, d_v: int) -> float:
    return float(7 * tokens * heads * d_k * d_v)


def recurrence_bytes(tokens: float, sequences: float, heads: int, d_k: int,
                     d_v: int, *, itemsize: int) -> float:
    per_token = heads * ((2 * d_k + 2 * d_v) * itemsize + 2 * 4)
    state = 2 * heads * d_k * d_v * 4
    return float(tokens * per_token + sequences * state)


def linear_layers(model_keys: dict) -> tuple[int, int, int, int]:
    """(linear layers, heads, d_k, d_v) of a configuration's ``model``
    keys; (0, 0, 0, 0) for a model without such layers."""
    n = list(model_keys.get("layer_types") or ()).count("linear_attention")
    if not n:
        return 0, 0, 0, 0
    return (n, model_keys["linear_value_heads"],
            model_keys["linear_key_head_dim"],
            model_keys["linear_value_head_dim"])


def staged_seconds(rec, module: str, kernel: str, shape: str):
    """(device seconds, runs of the program ``module``) of the kernel named
    ``kernel`` TOGETHER WITH the compiler's own copies of the arrays it
    works on: on a v5e XLA stages an array of a few tens of megabytes
    through on-chip memory round a Pallas call (``slice-start`` /
    ``slice-done`` in, ``copy-start`` / ``copy-done`` out, asynchronous, of
    the WHOLE array), so the kernel's own events hold none of its HBM
    traffic and a share read off them alone passes 100%.  Counted here, a
    run of the program at a time: the union of the kernel's events and of
    every window from a ``*-start`` whose text names an array of ``shape``
    (as the trace spells it, ``f32[9,30,96,192]``) to its ``*-done``.  The
    windows bracket the transfers, so this is an upper bound of the time the
    bytes took and the share stays under 100%; what else ran inside a window
    is in it too."""
    import bisect
    import re

    from lib import trace

    t = rec.get("trace")
    if not t or not t.get("n_devices"):
        return 0.0, 0
    asyncs = re.compile(r"%?((?:slice|copy)-(start|done)(?:\.\d+)?)$")
    total, runs = 0.0, 0
    for dev, mods in t["modules"].items():
        ops = sorted(t["ops"][dev], key=lambda x: x[1])
        at = [s for _, s, _ in ops]
        for nm, ms, me in mods:
            if trace.module_base(nm) != module:
                continue
            runs += 1
            ivs, open_ = [], {}
            for n, s, e in ops[bisect.bisect_left(at, ms):
                               bisect.bisect_right(at, me)]:
                head = n.split(" = ", 1)[0]
                if kernel in head:
                    ivs.append((s, e))
                    continue
                m = asyncs.match(head)
                if not m:
                    continue
                pair = m.group(1).replace("-done", "-start")
                if m.group(2) == "start":
                    if shape in n:
                        open_[pair] = s
                elif pair in open_:
                    ivs.append((open_.pop(pair), e))
            total += trace.total(trace.union(ivs)) / 1e9
    return total, runs


def kernel_share(rec, module: str, kernel: str, tokens: float,
                 sequences: float, staged: str | None = None):
    """(share of the roofline in %, working to print) of the kernel named
    ``kernel`` inside the program ``module`` over the traced part, a run of
    the program doing ``tokens`` tokens of ``sequences`` sequences in every
    linear layer; ``staged`` names the arrays whose copies by the compiler
    belong to the kernel's time (``staged_seconds``).  None where the trace
    or the model has nothing to read."""
    from lib import counts, counts_moe

    peaks = rec.get("peaks")
    n, heads, d_k, d_v = linear_layers(rec["model_keys"])
    took, runs = (staged_seconds(rec, module, kernel, staged) if staged
                  else counts_moe.module_ops(rec, module,
                                             lambda nm: kernel in nm))
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
