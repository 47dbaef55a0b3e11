"""Device time of the decode attention of all the layers that attend pages
in one serving call, in ms: the ops named ``tadnn_paged_decode*`` (the
folded MXU kernel at differential attention's wiring: the window layers, the
full layer and the layers that read its pages again) over the traced part,
divided by the runs of the two serving programs that hold them.  A model
without differential attention or a program without the kernel gives
``None``."""
from lib import counts_diff_attn, serve_phases


def read(rec):
    if not counts_diff_attn.layers(rec["model_keys"])[0]:
        return None
    took, calls = counts_diff_attn.kernel_seconds(rec)
    mods = (rec.get("trace") or {}).get("module_seconds") or {}
    runs = sum(len(mods.get(m) or ()) for m in (
        serve_phases.PREFILL_MODULE, serve_phases.DECODE_MODULE))
    return 1e3 * took / runs if calls and runs else None
