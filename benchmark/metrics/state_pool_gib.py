"""Bytes the linear layers' caches hold, in GiB, from the engine's
``serve.engine`` event: the recurrent states (float32, a row a slot and the
null row, every linear layer) plus the convolution tails
(``state_bytes_linear`` + ``conv_bytes_linear``).  It does not grow with
``max_len``: that is what a hybrid model buys.  A program without those
counters gives ``None``."""
import json


def read(rec):
    ev = rec.get("serve_engine") or {}
    if not ev.get("state_bytes_linear"):
        return None
    print(json.dumps({"state_pool": {
        "state_gib": ev["state_bytes_linear"] / 2**30,
        "conv_gib": ev["conv_bytes_linear"] / 2**30,
        "kv_full_gib": (ev.get("kv_bytes_full") or 0) / 2**30}}), flush=True)
    return (ev["state_bytes_linear"] + ev["conv_bytes_linear"]) / 2**30
