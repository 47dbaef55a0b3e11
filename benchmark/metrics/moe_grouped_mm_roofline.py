"""The grouped-matmul kernels' share of their roofline over the decode
steps of the traced part, in %: the least time to read the experts the
steps touched (gate, up and down of each, once) and to do the pairs'
products, from the ``moe_experts_touched`` and ``moe_pairs`` counters of
the ``serve.step`` events, over the device time of the kernels
``tadnn_moe_grouped_mm*`` inside ``jit_serve_decode_step``.  Per step: the
counters' mean over the traced steps against the kernels' time a run of
the program.  Prefill chunks are left out on both sides (they are not
fenced, so they have no counters)."""
import json

from lib import counts, counts_moe, serve_phases


def read(rec):
    peaks, m = rec.get("peaks"), rec["model_keys"]
    steps = [s for s in counts_moe.traced_decode_steps(rec)
             if s.get("moe_experts_touched") is not None]
    took, runs = counts_moe.module_ops(
        rec, serve_phases.DECODE_MODULE,
        lambda n: "tadnn_moe_grouped_mm" in n)
    if not peaks or not steps or not runs or not took:
        return None
    d, f = m["d_model"], m["expert_d_ff"]
    touched = sum(s["moe_experts_touched"] for s in steps) / len(steps)
    pairs = sum(s["moe_pairs"] for s in steps) / len(steps)
    least, bound = counts.roofline_seconds(
        counts_moe.grouped_mm_flops(pairs, d, f),
        counts_moe.grouped_mm_bytes(touched, d, f, itemsize=2), peaks)
    print(json.dumps({"moe_grouped_mm": {
        "steps": len(steps), "decode_runs": runs, "bound": bound,
        "experts_touched_a_step": touched, "pairs_a_step": pairs,
        "max_expert_tokens": max(s["moe_max_expert_tokens"] for s in steps),
        "least_s_a_step": least, "took_s_a_step": took / runs}}), flush=True)
    return 100.0 * least * runs / took
