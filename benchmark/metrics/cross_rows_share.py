"""Rows that ran the cross-decoder (the layers from the engine's
``cross_start`` on, which keep no cache) over rows that ran the layers
before it, in %, over the window's calls: ``read.cross_rows`` over
``read.self_rows`` of the ``serve.step`` events.  The engine reads both off
its programs' own walks of the layers as they are traced (the rows that
entered layer 0 and the rows that entered layer ``cross_start``), so a walk
that stopped narrowing would read 100 on every call: as built, 100 on a
decode-only call and (slots + 1) / (slots + chunk) on one that carries a
chunk, whose rows but the one whose logits are wanted stop at the
self-decoder's last layer.  Over a window the number also moves with the
share of calls that carry a chunk.  A program without the counters gives
``None``."""


def read(rec):
    reads = [s["read"] for s in rec.get("serve_steps") or ()
             if isinstance(s.get("read"), dict)
             and s["read"].get("self_rows")]
    if not reads:
        return None
    return 100.0 * sum(r["cross_rows"] for r in reads) / sum(
        r["self_rows"] for r in reads)
