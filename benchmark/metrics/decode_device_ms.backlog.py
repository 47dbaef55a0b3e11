"""``decode_device_ms`` (device time of one decode step, the program
``jit_serve_decode_step``) where it moves ``serve_tokens_per_s``."""
from lib.serve_phases import decode_device_ms as read  # noqa: F401
