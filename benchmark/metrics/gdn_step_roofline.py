"""The step kernel's share of its roofline over the traced part, in %: the
least time of the gated delta rule's OWN operations and bytes for one token
of every decoding slot (``lib/counts_gdn.py``; the state [heads, d_k, d_v]
float32 in and out once a slot: what binds it) over the device time of the
ops named ``tadnn_gdn_step`` inside ``jit_serve_decode_step`` AND of the
compiler's own copies of the state pools round them
(``counts_gdn.staged_seconds``: on a v5e the pool reaches the kernel
through on-chip memory, so the kernel's events alone hold none of its HBM
traffic), every linear layer of a run.  The decoding slots of a step are
the ``new_tokens`` of the ``serve.step`` events that ended inside the traced
part, their mean."""
import json

from lib import counts_gdn, counts_moe, serve_phases


def read(rec):
    steps = counts_moe.traced_decode_steps(rec)
    if not steps:
        return None
    slots = sum(s["new_tokens"] for s in steps) / len(steps)
    n, heads, d_k, d_v = counts_gdn.linear_layers(rec["model_keys"])
    pool = f"f32[{rec['engine']['n_slots'] + 1},{heads},{d_k},{d_v}]"
    got = counts_gdn.kernel_share(rec, serve_phases.DECODE_MODULE,
                                  "tadnn_gdn_step", slots, slots, pool)
    if got is None:
        return None
    print(json.dumps({"gdn_step": {**got[1], "steps": len(steps)}}),
          flush=True)
    return got[0]
