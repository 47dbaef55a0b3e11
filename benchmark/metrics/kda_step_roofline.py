"""The KDA step kernel's share of its roofline over the traced part, in %:
the least time of the vector-decay delta rule's OWN operations and bytes for
one token of every decoding slot (``lib/counts_kda.py``; the state [heads,
d_k, d_v] float32 in and out once a slot: what binds it) over the device
time of the ops named ``tadnn_kda_step`` AND of the compiler's own copies of
the state pools round them, every linear layer of a run of either serving
program (``counts_kda.step_seconds``).  The decoding slots of a call are the
engine's ``state_rows`` over the linear layers, the mean of the
``serve.step`` events that ended inside the traced part."""
import json

from lib import counts_kda


def read(rec):
    slots, steps = counts_kda.traced_state_rows(rec)
    if not steps:
        return None
    got = counts_kda.kernel_share(rec, *counts_kda.step_seconds(rec), slots,
                                  slots)
    if got is None:
        return None
    print(json.dumps({"kda_step": {**got[1], "steps": steps}}), flush=True)
    return got[0]
