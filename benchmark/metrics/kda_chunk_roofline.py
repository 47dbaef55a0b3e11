"""The KDA chunk kernel's share of its roofline over the traced part, in %:
the least time of the vector-decay delta rule's OWN operations and bytes
(``lib/counts_kda.py``: 7 d_k d_v operations a token a head; q, k, v, beta
and the d_k float32 decays in and the output out once, the state in and out
once a chunk) over the device time of the ops named ``tadnn_kda_chunk``
inside ``jit_serve_prefill_chunk``, every linear layer of a run.  A chunk's
tokens are the engine's ``prefill_chunk`` times the share of it that the
window's prompts fill (their last chunks are padded)."""
import json
import math

from lib import counts_kda


def read(rec):
    C = rec["engine"].get("prefill_chunk")
    prompts = [len(q["prompt"]) for q in rec.get("requests") or ()
               if q.get("t_admit") is not None]
    if not C or not prompts:
        return None
    fill = sum(prompts) / (C * sum(math.ceil(n / C) for n in prompts))
    got = counts_kda.kernel_share(rec, *counts_kda.chunk_seconds(rec),
                                  C * fill, 1.0)
    if got is None:
        return None
    print(json.dumps({"kda_chunk": {**got[1], "chunk_fill": fill}}),
          flush=True)
    return got[0]
