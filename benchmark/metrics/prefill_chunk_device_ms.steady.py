"""``prefill_chunk_device_ms`` (device time of one prefill chunk, the
program ``jit_serve_prefill_chunk``) where it moves ``itl_p95_ms``."""
from lib.serve_phases import prefill_chunk_device_ms as read  # noqa: F401
