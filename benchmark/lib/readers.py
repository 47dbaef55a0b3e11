"""What several per-layer readers share: one quantity read in cells that
report different end-to-end metrics is split into one reader a cell kind,
each a line that calls here."""

from __future__ import annotations

import statistics


def decode_step_ms(rec):
    """Median ``decode_s`` of the window's ``serve.step`` events, in ms: host
    time round one decode step, which ends in a ``device_get`` of its tokens."""
    d = [s["decode_s"] for s in rec.get("serve_steps") or () if s["decode_s"]]
    return 1e3 * statistics.median(d) if d else None


def device_idle_share(rec):
    """1 - (union of device-op intervals / traced window), in %."""
    t = rec.get("trace")
    if not t or not t.get("n_devices"):
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
