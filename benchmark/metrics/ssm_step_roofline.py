"""The selective scan's step kernel's share of its roofline over the traced
part, in %: the least time of the recurrence's OWN operations and bytes for
one token of every decoding slot (``lib/counts_ssm.py``; the state [N, d_in]
float32 in and out once a slot: what binds it) over the device time of the
ops named ``tadnn_ssm_step`` AND of the compiler's own copies of the state
pools round them, every state-space layer of a run of either serving program
(``counts_ssm.step_seconds``).  The decoding slots of a call are the
engine's ``state_rows`` over the state-space layers, the mean of the
``serve.step`` events that ended inside the traced part."""
import json

from lib import counts_ssm


def read(rec):
    slots, steps = counts_ssm.traced_state_rows(rec)
    if not steps:
        return None
    got = counts_ssm.kernel_share(rec, *counts_ssm.step_seconds(rec), slots,
                                  slots)
    if got is None:
        return None
    print(json.dumps({"ssm_step": {**got[1], "steps": steps}}), flush=True)
    return got[0]
