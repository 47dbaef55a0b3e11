"""``device_idle_share`` of the serve cells."""
from lib.readers import device_idle_share as read  # noqa: F401
