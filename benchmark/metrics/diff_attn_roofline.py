"""The decode attention's share of its roofline over the traced part, in %,
for differential attention over ONE shared full cache: the least time to
read, for every decode token, all of its context once for the full layer and
once more for every layer that shares its pages, and the last ``window``
keys on every window layer (``lib/counts_diff_attn.py``: 8 full reads and 8
windows a row here; scores over hd against values of 2 hd: 6 hd operations a
head a key), over the device time of the ops named ``tadnn_paged_decode*``.
The contexts are the benchmark's own record of the requests."""
import json

from lib import counts, counts_diff_attn


def read(rec):
    peaks, m = rec.get("peaks"), rec["model_keys"]
    if not peaks or not counts_diff_attn.layers(m)[0]:
        return None
    took, calls = counts_diff_attn.kernel_seconds(rec)
    ctx = counts_diff_attn.traced_contexts(rec)
    if not calls or not ctx:
        return None
    heads = m["n_heads"]
    hd = m.get("head_size") or m["d_model"] // heads
    keys = counts_diff_attn.keys_read(ctx, m)
    least, bound = counts.roofline_seconds(
        counts_diff_attn.decode_flops(keys, heads, hd),
        counts_diff_attn.decode_bytes(
            keys, m.get("n_kv_heads") or heads, hd,
            itemsize=1 if rec["engine"].get("quant_kv") else 2), peaks)
    print(json.dumps({"diff_attn": {
        "calls": calls, "bound": bound, "decode_tokens": len(ctx),
        "mean_context": sum(ctx) / len(ctx), "keys_read": keys,
        "least_s": least, "took_s": took}}), flush=True)
    return 100.0 * least / took
