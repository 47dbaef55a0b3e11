"""Median ``step_s`` of the calls that waited for a decode step alone
(``read.chunk_rows == 0``), over the WHOLE window, in ms: what a trace that
starts at t = 0 of a backlog, where every call has a chunk, never holds."""
from lib.step_reads import decode_call_ms as read  # noqa: F401
