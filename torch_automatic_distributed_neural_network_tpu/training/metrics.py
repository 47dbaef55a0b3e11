"""Metrics / observability (SURVEY.md §5): structured JSONL metrics with
throughput and MFU accounting — the BASELINE.json:2 headline numbers
(images/sec/chip, tokens/sec/chip) made measurable.

MFU honesty rule (SURVEY.md §7 hard part #4): record both the raw
throughput and the model-flops assumptions used for the MFU conversion.
"""

from __future__ import annotations

import dataclasses
import json
import sys
import time
import warnings
from typing import Any, IO

import jax

def peak_flops_per_chip(device_kind: str | None = None) -> float:
    """Peak dense bf16 FLOP/s of one chip, from the one table
    (``topology.chip_spec``); raises for a kind the table does not hold."""
    from ..topology import chip_spec

    return chip_spec(device_kind or jax.devices()[0].device_kind).flops_per_s


def transformer_step_flops(n_params: int, tokens_per_batch: int) -> float:
    """Standard 6ND approximation: fwd+bwd FLOPs per step for a dense
    decoder with N params on D tokens.  With remat add ~1 extra forward
    (8ND) — callers pass the multiplier they actually run with."""
    return 6.0 * n_params * tokens_per_batch


@dataclasses.dataclass
class Throughput:
    items_per_sec: float
    items_per_sec_per_chip: float
    step_time_s: float
    mfu: float | None = None


class MetricsLogger:
    """JSONL metrics sink + rolling throughput meter.

    Writes one JSON object per log call: step, loss/aux, step_time,
    items/sec/chip, MFU when flops-per-step is known.  Host-0 only under
    multi-host.
    """

    def __init__(
        self,
        path: str | None = None,
        *,
        items_name: str = "items",
        flops_per_step: float | None = None,
        console: bool = True,
        console_every: int = 10,
    ):
        self.path = path
        self._file: IO | None = open(path, "a") if path else None
        self.items_name = items_name
        self.flops_per_step = flops_per_step
        self.console = console and jax.process_index() == 0
        self.console_every = console_every
        self._t_last: float | None = None
        # looked up only where MFU is reported: an unknown chip is an
        # error there, and no business of a logger that reports none
        self._peak = peak_flops_per_chip() if flops_per_step else None
        self._n_chips = jax.device_count()
        self._dropped_warned: set[str] = set()

    def start_step(self) -> None:
        self._t_last = time.perf_counter()

    def log_step(self, step: int, metrics: dict, items_per_step: int) -> dict:
        now = time.perf_counter()
        dt = (now - self._t_last) if self._t_last is not None else float("nan")
        self._t_last = now
        record: dict[str, Any] = {
            "step": step,
            "time": time.time(),
            "step_time_s": dt,
            f"{self.items_name}_per_sec": items_per_step / dt if dt else None,
            f"{self.items_name}_per_sec_per_chip": (
                items_per_step / dt / self._n_chips if dt else None
            ),
        }
        if self.flops_per_step and dt and dt == dt:
            record["mfu"] = self.flops_per_step / dt / (
                self._peak * self._n_chips
            )
            record["flops_per_step"] = self.flops_per_step
        for k, v in metrics.items():
            if k == "model_state":
                continue
            try:
                record[k] = float(v)
            except (TypeError, ValueError):
                self._warn_dropped(k, v)
        parts = [f"step {step:5d}"]
        if "loss" in record:
            parts.append(f"loss {record['loss']:.4f}")
        ips = record.get(f"{self.items_name}_per_sec_per_chip")
        if ips:
            parts.append(f"{ips:,.0f} {self.items_name}/s/chip")
        if "mfu" in record:
            parts.append(f"MFU {record['mfu']:.1%}")
        self._emit(record, parts,
                   console=self.console and step % self.console_every == 0)
        return record

    def _emit(self, record: dict, console_parts: list[str],
              *, console: bool) -> None:
        """Shared sink: JSONL write + optional host-0 console line."""
        if self._file:
            self._file.write(json.dumps(record) + "\n")
            self._file.flush()
        if console:
            print("  ".join(console_parts), file=sys.stderr)

    def log_eval(self, step: int, metrics: dict) -> dict:
        """Write an evaluation record: plain fields only — no step-time /
        throughput / MFU math (those are meaningless for an eval pass and
        would corrupt consumers averaging the training records)."""
        record: dict[str, Any] = {"step": step, "time": time.time()}
        for k, v in metrics.items():
            try:
                record[k] = float(v)
            except (TypeError, ValueError):
                self._warn_dropped(k, v)
        parts = [f"step {step:5d}"] + [
            f"{k} {v:.4f}" for k, v in record.items()
            if k not in ("step", "time")
        ]
        self._emit(record, parts, console=self.console)
        return record

    def _warn_dropped(self, key: str, value: Any) -> None:
        """Warn ONCE per metric key that is silently unloggable — a step
        fn returning arrays/strings otherwise loses those series with no
        trace, and the gap is only noticed at analysis time."""
        if key in self._dropped_warned:
            return
        self._dropped_warned.add(key)
        warnings.warn(
            f"MetricsLogger: dropping non-scalar metric {key!r} "
            f"(type {type(value).__name__}) — log_step/log_eval record "
            "only float()-able scalars; reduce it in the step fn "
            "(warned once per key)",
            stacklevel=3,
        )

    def close(self) -> None:
        """Close the JSONL file (idempotent; later log calls fall back to
        console-only instead of crashing on a closed handle)."""
        if self._file:
            self._file.close()
            self._file = None

    def __enter__(self) -> "MetricsLogger":
        return self

    def __exit__(self, *exc: Any) -> None:
        self.close()
