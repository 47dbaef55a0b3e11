"""The flash attention kernels' share of their roofline in the traced train
steps, in %: the least time the chip could take for the calls made (the
larger of FLOPs over peak FLOP/s and bytes over peak bytes/s, counts from
``lib/counts.py``) over the time the kernels took on the device.

The kernels are the ``tpu_custom_call`` instructions of the trace (the train
step holds no other Pallas kernel).  They carry no stable name, so a call
is told by its signature alone: three operands (q, k, v) is a forward call;
the others are the two halves (dq; dk and dv) of a backward pass, each given
half of the backward pass's least time.  The bound is printed.
"""
import json

from lib import counts, trace


def read(rec):
    t, peaks = rec.get("trace"), rec.get("peaks")
    if not t or not t.get("n_devices") or not peaks:
        return None
    calls = [(n, e - s) for ops in t["ops"].values() for n, s, e in ops
             if trace.is_pallas(n)]
    if not calls:
        return None
    mix, m = rec["cell"].mix, rec["model_keys"]
    heads, kv = m["n_heads"], m.get("n_kv_heads") or m["n_heads"]
    shape = dict(batch=mix["batch_size"], heads=heads, seq=mix["seq_len"],
                 head_dim=m["d_model"] // heads)
    least = {}
    for backward in (False, True):
        least[backward] = counts.roofline_seconds(
            counts.flash_attention_flops(**shape, causal=True,
                                         window=m.get("sliding_window"),
                                         backward=backward),
            counts.flash_attention_bytes(
                mix["batch_size"], heads, kv, mix["seq_len"],
                shape["head_dim"], itemsize=2, backward=backward), peaks)
    n_fwd = sum(trace.n_operands(n) == 3 for n, _ in calls)
    n_bwd = len(calls) - n_fwd
    floor_s = n_fwd * least[False][0] + n_bwd * least[True][0] / 2
    took_s = sum(d for _, d in calls) / 1e9
    print(json.dumps({"flash_attn": {
        "forward_calls": n_fwd, "backward_halves": n_bwd,
        "bound_forward": least[False][1], "bound_backward": least[True][1],
        "least_s": floor_s, "took_s": took_s}}), flush=True)
    return 100.0 * floor_s / took_s
