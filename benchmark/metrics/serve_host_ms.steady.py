"""``serve_host_ms`` (the host's own time in a decoding engine step:
``step_s`` less its waits for the device) where it moves ``itl_p95_ms``."""
from lib.serve_phases import serve_host_ms as read  # noqa: F401
