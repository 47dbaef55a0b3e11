"""Goodput accounting: bucket run wall-clock into where it actually went.

Buckets (the TorchTitan-style breakdown, PAPERS.md):

- ``compile``      jit trace + XLA compile (first step, shape-churn
                   recompiles, AOT compile_report calls)
- ``step``         steady-state training-step host time (the goodput)
- ``checkpoint``   save/restore + async-commit waits
- ``eval``         periodic evaluation passes
- ``trace``        profiler-instrumented steps (TrainerConfig.
                   trace_every_n, obs/trace.py) — fenced and captured,
                   so their wall time is overhead, not goodput
- ``input_stall``  waiting on the data source for the next batch
- ``idle``         everything unaccounted (guards, logging, callbacks,
                   host-side bookkeeping) — computed as the remainder

``summary()`` fractions are of total wall-clock and sum to ~1.0 by
construction; ``goodput`` is step / total.

A measured bucket is a ``journal.phase``: under a profiler capture it
shows on the timeline as ``train.<bucket>`` (``train.step`` is the fence on
a dispatched step; the dispatch itself is ``train.step_dispatch``).
"""

from __future__ import annotations

import time

from .journal import phase

BUCKETS = ("compile", "step", "checkpoint", "eval", "trace",
           "input_stall", "idle")


class GoodputMeter:
    """Accumulates seconds per bucket against a run-start reference."""

    def __init__(self):
        self._t_start = time.monotonic()
        self.seconds: dict[str, float] = {b: 0.0 for b in BUCKETS}

    def _known(self, bucket: str) -> str:
        if bucket not in self.seconds:
            raise ValueError(
                f"unknown goodput bucket {bucket!r}; expected one of {BUCKETS}"
            )
        return bucket

    def add(self, bucket: str, seconds: float) -> None:
        self.seconds[self._known(bucket)] += max(0.0, seconds)

    def measure(self, bucket: str) -> phase:
        return phase(self.seconds, self._known(bucket), "train." + bucket)

    def total_wall_s(self) -> float:
        return time.monotonic() - self._t_start

    def summary(self, total_wall_s: float | None = None) -> dict:
        """Bucket seconds + fractions-of-wall-clock summing to ~1.0.

        ``idle`` is the remainder of the wall clock not claimed by any
        measured bucket, clamped at 0 (measured buckets can slightly
        overlap the total on coarse clocks).
        """
        total = total_wall_s if total_wall_s is not None else self.total_wall_s()
        secs = dict(self.seconds)
        measured = sum(v for b, v in secs.items() if b != "idle")
        secs["idle"] = max(0.0, total - measured)
        total = max(total, 1e-9)
        return {
            "total_wall_s": total,
            "seconds": {b: secs[b] for b in BUCKETS},
            "fractions": {b: secs[b] / total for b in BUCKETS},
            "goodput": secs["step"] / total,
        }
