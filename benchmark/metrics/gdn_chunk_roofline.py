"""The chunk kernel's share of its roofline over the traced part, in %: the
least time of the gated delta rule's OWN operations and bytes
(``lib/counts_gdn.py``: 7 d_k d_v operations a token a head; q, k, v, g,
beta in and the output out once, the state in and out once a chunk) over the
device time of the ops named ``tadnn_gdn_chunk`` inside
``jit_serve_prefill_chunk``, every linear layer of a run.  A chunk's tokens
are the engine's ``prefill_chunk`` times the share of it that the window's
prompts fill (their last chunks are padded)."""
import json
import math

from lib import counts_gdn, serve_phases


def read(rec):
    C = rec["engine"].get("prefill_chunk")
    prompts = [len(q["prompt"]) for q in rec.get("requests") or ()
               if q.get("t_admit") is not None]
    if not C or not prompts:
        return None
    fill = sum(prompts) / (C * sum(math.ceil(n / C) for n in prompts))
    got = counts_gdn.kernel_share(rec, serve_phases.PREFILL_MODULE,
                                  "tadnn_gdn_chunk", C * fill, 1.0)
    if got is None:
        return None
    print(json.dumps({"gdn_chunk": {**got[1], "chunk_fill": fill}}),
          flush=True)
    return got[0]
