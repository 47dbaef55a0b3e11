"""``prefill_chunk_device_ms`` (device time of one prefill chunk, the
program ``jit_serve_prefill_chunk``) where it moves ``serve_tokens_per_s``."""
from lib.serve_phases import prefill_chunk_device_ms as read  # noqa: F401
