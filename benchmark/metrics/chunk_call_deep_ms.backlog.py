"""``chunk_call_ms.backlog`` over the chunks that began at position 8,192 or
beyond (``read.chunk_pos``: the keys the chunk's attention reads beyond its
own), in ms; nothing under five such calls."""
from lib.step_reads import chunk_call_deep_ms as read  # noqa: F401
