"""What the window lost to stalled calls, in ms: the sum of ``step_s`` less
the median of the call's kind over the calls that took more than three times
that median and 50 ms over it (a decode-only call against the others, a
chunk call against those within 4,096 positions of its depth).  The line
``serve_stalls`` before it says where each stood: its largest phase, the
collector's seconds and full passes inside it, compiles."""
from lib.step_reads import serve_stall_ms as read  # noqa: F401
