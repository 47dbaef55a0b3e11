"""Median host time per fenced train step inside the window, in ms."""
import statistics


def read(rec):
    steps = rec.get("step_seconds")
    return 1e3 * statistics.median(steps) if steps else None
