"""``decode_step_ms`` where it moves ``serve_tokens_per_s``."""
from lib.readers import decode_step_ms as read  # noqa: F401
