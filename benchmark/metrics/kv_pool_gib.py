"""Bytes the KV pool holds, in GiB, from the engine's ``serve.engine``
event: pages for ``max_len`` a slot on the full-attention layers plus the
sliding layers' rings (``kv_bytes_full`` + ``kv_bytes_window``).  A program
without those counters gives ``None``."""
import json


def read(rec):
    ev = rec.get("serve_engine") or {}
    if ev.get("kv_bytes_full") is None or ev.get("kv_bytes_window") is None:
        return None
    print(json.dumps({"kv_pool": {
        "full_gib": ev["kv_bytes_full"] / 2**30,
        "window_gib": ev["kv_bytes_window"] / 2**30,
        "layer_kinds": ev.get("layer_kinds")}}), flush=True)
    return (ev["kv_bytes_full"] + ev["kv_bytes_window"]) / 2**30
