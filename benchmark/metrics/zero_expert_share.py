"""The share of a token's expert choices that are ZERO-COMPUTE experts (the
router's outputs past the published experts: a pair on one adds ``w * u``
and reads no weight), in %: the ``moe_zero_pairs`` counter of the window's
``serve.step`` events over ``experts_per_token`` x the expert layers x their
``moe_rows`` (the valid rows a call routed, chunk rows and decode rows
together).  With one draw of weights and requests it is a constant of the
cell: a change that moves it has changed the routing, not the speed.  A
program without the counters, or a model without such experts, gives
``None``."""
import json


def read(rec):
    m = rec["model_keys"]
    steps = [s for s in rec.get("serve_steps") or ()
             if s.get("moe_rows") and s.get("moe_zero_pairs") is not None]
    if not steps or not m.get("zero_experts"):
        return None
    layers = m["n_layers"] - (m.get("n_dense_layers") or 0)
    rows = sum(s["moe_rows"] for s in steps)
    zero = sum(s["moe_zero_pairs"] for s in steps)
    print(json.dumps({"zero_experts": {
        "calls": len(steps), "rows": rows, "zero_pairs": zero,
        "expert_layers": layers, "pairs_a_row": zero / rows,
        "share_by_width": m["zero_experts"]
        / (m["experts_published"] + m["zero_experts"])}}), flush=True)
    return 100.0 * zero / (m["experts_per_token"] * layers * rows)
