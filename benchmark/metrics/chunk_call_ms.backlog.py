"""Median ``step_s`` of the calls that waited for a program with a prefill
chunk in it (``read.chunk_rows > 0``), over the WHOLE window, in ms: the
call that does most of a long backlog's work, at the depth the window has."""
from lib.step_reads import chunk_call_ms as read  # noqa: F401
