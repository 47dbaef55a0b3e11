"""The (token, expert) pairs that landed on the experts HELD here, a routed
row an expert layer: what the grouped matmuls multiply, which their time is
held against (the share due is ``experts_per_token`` x the share of the
router's outputs that are held).  From the ``moe_pairs`` and ``moe_rows``
counters of the window's ``serve.step`` events that carry both: the calls
whose decode rows went through the layers alone (the program leaves
``moe_pairs`` off a call whose rows rode in a chunk), so the rows counted are
decode rows.  A program without ``moe_rows`` gives ``None``."""
import json


def read(rec):
    m = rec["model_keys"]
    steps = [s for s in rec.get("serve_steps") or ()
             if s.get("moe_rows") and s.get("moe_pairs") is not None]
    if not steps:
        return None
    layers = m["n_layers"] - (m.get("n_dense_layers") or 0)
    rows = sum(s["moe_rows"] for s in steps)
    pairs = sum(s["moe_pairs"] for s in steps)
    width = m["experts_published"] + (m.get("zero_experts") or 0)
    print(json.dumps({"moe_live_pairs": {
        "calls": len(steps), "rows": rows, "pairs": pairs,
        "expert_layers": layers,
        "due_a_row": m["experts_per_token"] * m["experts_held"] / width}}),
          flush=True)
    return pairs / (layers * rows)
