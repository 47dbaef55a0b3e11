"""benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process per run; it alone imports JAX and holds the chip(s).  The cell
is an entry of ``workloads`` in BENCHMARK.json; its configuration, traffic
mix and limits are data files found by the names in that entry, the mix's
``kind`` names the generator under ``generators/``, and each per-layer
metric is a reader under ``metrics/`` found by the metric's name.  No cell,
configuration or mix is named in any ``.py`` file here.

Prints what it likes on earlier lines and ONE last line: the result object
(``correct``, ``attempted``, ``failed``, ``metrics``, ``device`` and, with
``--trace 1``, ``breakdown``).  ``--trace 0`` reports the cell's end-to-end
metrics, ``--trace 1`` its per-layer metrics.  Without a TPU it exits 2 and
prints no result; ``--rehearsal`` is the only way off the chip (tiny sizes,
says so on its first line, prints no device metric).  ``--control`` computes
the lower-precision control instead of a result (see README.md).
"""

from __future__ import annotations

import time

T0 = time.perf_counter()  # set-up counts from here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.dirname(HERE))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearsal", action="store_true")
    ap.add_argument("--control", action="store_true")
    args = ap.parse_args(argv)

    from lib import harness

    harness.program_or_exit()
    cell = harness.Cell(args.workload)
    if args.rehearsal:
        print(json.dumps({"rehearsal": True, "note": "REHEARSAL at tiny sizes "
                          "off the chip: no number below is a device metric"}),
              flush=True)
        harness.apply_rehearsal(cell)
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        if cell.chips > 1:
            os.environ.setdefault(
                "XLA_FLAGS",
                f"--xla_force_host_platform_device_count={cell.chips}")
    devices, on_chip = harness.device_or_exit(cell.chips, args.rehearsal)
    cache_dir = harness.enable_cache()
    compiles = harness.CompileCounter()
    profiler = harness.Profiler(
        bool(args.trace), os.path.join(harness.BENCH_DIR, ".trace"))
    print(json.dumps({"cell": cell.name, "seed": args.seed,
                      "seconds": args.seconds, "trace": args.trace,
                      "cache_dir": cache_dir,
                      "device": harness.device_record(devices)}), flush=True)

    ctx = {"cell": cell, "args": args, "devices": devices, "on_chip": on_chip,
           "t0": T0, "compiles": compiles, "profiler": profiler}
    gen = cell.generator()
    if args.control:
        return gen.control(ctx)
    rec = gen.run(ctx)

    values = rec["end_to_end"] if not args.trace else {}
    if args.trace:
        for m in cell.metrics("per_layer"):
            reader = harness.load_module(
                os.path.join(HERE, "metrics", m["name"] + ".py"),
                "bench_metric")
            v = reader.read(rec)
            if v is not None:
                values[m["name"]] = v
    wanted = cell.metrics("per_layer" if args.trace else "end_to_end")
    metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
               for m in wanted if m["name"] in values}
    if args.rehearsal:
        print(json.dumps({"rehearsal_values_not_device_metrics": metrics}))
        metrics = {}
    result = {"correct": rec["correct"], "attempted": rec["attempted"],
              "failed": rec["failed"], "metrics": metrics,
              "device": harness.device_record(devices,
                                              reduced=rec.get("trace"))}
    if rec.get("memory_peak_bytes") is not None:
        # read before the reference ran, so that it stays the program's
        result["device"]["memory_peak_bytes"] = rec["memory_peak_bytes"]
    if args.trace and rec.get("trace") and rec["trace"].get("n_devices"):
        from lib import trace

        result["breakdown"] = {
            "device_ops": trace.top(rec["trace"]["op_seconds"]),
            "idle_gaps": trace.top(rec["trace"]["gap_seconds"])}
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
