"""The paged decode kernel's share of its roofline over the traced part, in
%, for a model whose layers differ: a full-attention layer must read every
cached key and value of every running slot, a sliding layer only the last
``min(context, window)``; head size and layer kinds come from the
configuration (``head_size``, ``layer_types``).  The kernel is told as
``paged_attn_roofline`` tells it (a ``tpu_custom_call`` whose operands
include the block tables) or by its name, ``tadnn_paged_decode``; the
contexts are the benchmark's own record of the requests."""
import json

from lib import counts, counts_moe, trace


def read(rec):
    t, peaks, span = rec.get("trace"), rec.get("peaks"), rec.get("trace_mono")
    m = rec["model_keys"]
    if (not t or not t.get("n_devices") or not peaks or not span
            or not m.get("layer_types")):
        return None
    eng = rec["engine"]
    tables = f"s32[{eng['n_slots']},{eng['max_len'] // eng['block_size']}]"
    calls = [e - s for ops in t["ops"].values() for n, s, e in ops
             if "tadnn_paged_decode" in n
             or (trace.is_pallas(n) and tables in n)]
    if not calls:
        return None
    ctx = [len(q["prompt"]) + j for q in rec["requests"]
           for j, w in enumerate(q["walls"])
           if j >= 1 and span[0] <= w <= span[1]]
    kinds = list(m["layer_types"])
    heads = m["n_heads"]
    flops, bytes_ = counts_moe.paged_attention_by_kind(
        ctx, n_full=kinds.count("full_attention"),
        n_window=kinds.count("sliding_attention"),
        window=m.get("sliding_window"), heads=heads,
        kv_heads=m.get("n_kv_heads") or heads,
        head_dim=m.get("head_size") or m["d_model"] // heads,
        itemsize=1 if eng.get("quant_kv") else 2)
    least, bound = counts.roofline_seconds(flops, bytes_, peaks)
    took_s = sum(calls) / 1e9
    print(json.dumps({"paged_attn_by_kind": {
        "calls": len(calls), "bound": bound, "decode_tokens": len(ctx),
        "mean_context": sum(ctx) / max(1, len(ctx)), "least_s": least,
        "took_s": took_s}}), flush=True)
    return 100.0 * least / took_s
