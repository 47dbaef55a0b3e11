"""``decode_step_ms`` where it moves ``itl_p95_ms``."""
from lib.readers import decode_step_ms as read  # noqa: F401
