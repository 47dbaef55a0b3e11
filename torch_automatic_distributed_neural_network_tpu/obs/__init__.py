"""Run-wide observability layer (SURVEY.md §5; TorchTitan-style, see
PAPERS.md): span/event journal, recompile + comms accounting, goodput
breakdown, and the ``tadnn report`` backend.

The layer is pull-free and zero-dep: library code emits spans/events to
a process-global journal (``set_default`` / ``TADNN_JOURNAL`` env); when
none is installed every call is a cheap no-op.
"""

from . import aggregate, live, schema, slo_monitor, trace
from .goodput import BUCKETS, GoodputMeter
from .journal import (
    Journal,
    as_default,
    compile_counter,
    event,
    get_default,
    phase,
    set_default,
    span,
)
from .live import LatencySketch, LiveAggregator
from .slo_monitor import MonitorPolicy, SLOMonitor

__all__ = [
    "BUCKETS",
    "GoodputMeter",
    "Journal",
    "LatencySketch",
    "LiveAggregator",
    "MonitorPolicy",
    "SLOMonitor",
    "aggregate",
    "as_default",
    "compile_counter",
    "schema",
    "event",
    "get_default",
    "phase",
    "set_default",
    "span",
    "live",
    "slo_monitor",
    "trace",
]
