"""The paged decode kernel's share of its roofline over the traced window,
in %: the least time to read every cached key and value of every running
slot once a layer a step (it is bound by memory: one query row a slot),
over the time the kernel took on the device.

The kernel is told by its signature: a ``tpu_custom_call`` whose operands
include the block tables, ``s32[slots, max_len / block_size]``.  The context
lengths are the benchmark's own record of the requests: output token j
(j >= 1; token 0 comes from prefill) of a request attends prompt + j keys.
"""
import json

from lib import counts, trace


def read(rec):
    t, peaks, span = rec.get("trace"), rec.get("peaks"), rec.get("trace_mono")
    if not t or not t.get("n_devices") or not peaks or not span:
        return None
    eng, m = rec["engine"], rec["model_keys"]
    tables = f"s32[{eng['n_slots']},{eng['max_len'] // eng['block_size']}]"
    calls = [e - s for ops in t["ops"].values() for n, s, e in ops
             if trace.is_pallas(n) and tables in n]
    if not calls:
        return None
    ctx = sum(len(q["prompt"]) + j for q in rec["requests"]
              for j, w in enumerate(q["walls"])
              if j >= 1 and span[0] <= w <= span[1])
    heads, kv = m["n_heads"], m.get("n_kv_heads") or m["n_heads"]
    hd = m["d_model"] // heads
    item = 1 if eng.get("quant_kv") else 2
    least, bound = counts.roofline_seconds(
        m["n_layers"] * counts.paged_attention_flops(ctx, heads, hd),
        m["n_layers"] * counts.paged_attention_bytes(ctx, kv, hd,
                                                     itemsize=item), peaks)
    took_s = sum(calls) / 1e9
    print(json.dumps({"paged_attn": {"calls": len(calls), "bound": bound,
                                     "context_tokens": ctx, "least_s": least,
                                     "took_s": took_s}}), flush=True)
    return 100.0 * least / took_s
