"""The selective scan's chunk kernel's share of its roofline over the
traced part, in %: the least time of the recurrence's OWN operations and
bytes (``lib/counts_ssm.py``: 7 N + 3 operations a token a channel; c, Delta
and the output once a token, the state in and out once a chunk) over the
device time of the ops named ``tadnn_ssm_chunk`` inside
``jit_serve_prefill_chunk``, every state-space layer of a run.  A chunk's tokens are the engine's
``prefill_chunk`` times the share of it that the window's prompts fill
(their last chunks are padded)."""
import json
import math

from lib import counts_ssm


def read(rec):
    C = rec["engine"].get("prefill_chunk")
    prompts = [len(q["prompt"]) for q in rec.get("requests") or ()
               if q.get("t_admit") is not None]
    if not C or not prompts:
        return None
    fill = sum(prompts) / (C * sum(math.ceil(n / C) for n in prompts))
    got = counts_ssm.kernel_share(rec, *counts_ssm.chunk_seconds(rec),
                                  C * fill, 1.0)
    if got is None:
        return None
    print(json.dumps({"ssm_chunk": {**got[1], "chunk_fill": fill}}),
          flush=True)
    return got[0]
