"""The latent decode kernel's share of its roofline over the traced part, in
%: the least time of absorbed latent attention's own operations and bytes
(``lib/counts_mla.py``: every live key's 576 numbers read once in every
latent layer, 32 heads x (576 + 512) x 2 operations a key) over the device
time of the ops named ``tadnn_paged_decode_latent``, in the decode step and
in the chunks that carry a step's decode rows alike.  The contexts are the
benchmark's own record of the requests: every token after a request's first
is one decode row, at the context it had then.  A program or a model
without the kernel gives ``None``."""
import json

from lib import counts, counts_mla


def read(rec):
    peaks, span = rec.get("peaks"), rec.get("trace_mono")
    n, heads, row, value = counts_mla.latent_layers(rec["model_keys"])
    took_s, calls = counts_mla.kernel_seconds(rec)
    if not peaks or not span or not n:
        return None
    ctx = [len(q["prompt"]) + j for q in rec["requests"]
           for j, w in enumerate(q["walls"])
           if j >= 1 and span[0] <= w <= span[1]]
    if not calls or not ctx:
        return None
    keys = n * sum(ctx)
    least, bound = counts.roofline_seconds(
        counts_mla.latent_attention_flops(keys, heads, row, value),
        counts_mla.latent_attention_bytes(keys, row, itemsize=2), peaks)
    print(json.dumps({"latent_attn": {
        "calls": calls, "bound": bound, "decode_tokens": len(ctx),
        "mean_context": sum(ctx) / len(ctx), "latent_layers": n,
        "least_s": least, "took_s": took_s}}), flush=True)
    return 100.0 * least / took_s
