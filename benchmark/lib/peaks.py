"""The table of peaks, keyed by ``device_kind``.  A kind that is not here is
an error, never a default.

Source: Google Cloud documentation, "TPU v5e" system architecture page:
197 TFLOP/s bf16, 16 GB HBM2e at 819 GB/s per chip.  (The program keeps
its own copy in ``topology._CHIP_SPECS``; the benchmark does not read it.)
"""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16 * 2**30},
    "TPU v5e": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
                "hbm_bytes": 16 * 2**30},
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no peaks for device kind {device_kind!r}; known: "
            f"{sorted(PEAKS)}. Add it to benchmark/lib/peaks.py with its "
            f"source.") from None
