"""benchmark/sweep.py --workload <open-loop cell> --seed <n> --seconds <s> --rates a,b,c

Finds the knee of a serving cell ONCE, when the cell is defined: the mix's
engine is built and warmed as in a run, then the open loop is driven at each
fixed rate in turn (drained in between), and one line a rate says whether
the queue grew over the window.  The knee is the highest rate at which it
did not; the cell's own rate is then written into its traffic file as a
number (about four fifths of the knee).  The driver never runs this.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import copy  # noqa: E402
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
    ap.add_argument("--rates", required=True)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--rehearsal", action="store_true")
    args = ap.parse_args(argv)

    from lib import arrivals, harness, program, serving, stats

    harness.program_or_exit()
    cell = harness.Cell(args.workload)
    if args.rehearsal:
        harness.apply_rehearsal(cell)
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
    devices, on_chip = harness.device_or_exit(cell.chips, args.rehearsal)
    harness.enable_cache()
    ctx = {"cell": cell, "args": args, "devices": devices, "on_chip": on_chip,
           "t0": T0, "compiles": harness.CompileCounter(),
           "profiler": harness.Profiler(False, "")}
    vocab = program.model_keys(cell.config)["vocab_size"]
    eng, journal, _shapes, _key = serving._build(ctx)
    serving._warm(eng, cell.mix, vocab, args.seed)
    for k, rate in enumerate(float(r) for r in args.rates.split(",")):
        mix = copy.deepcopy(cell.mix)
        mix["arrivals"]["rate_per_s"] = rate
        plan = arrivals.plan(mix, args.seed + k, args.seconds, vocab)
        n0 = len(journal.named("serve.step"))
        t_open, sub = serving._drive(ctx, eng, plan, args.seconds, 120.0)
        t_close = t_open + args.seconds
        eng.finished.clear()
        steps = journal.named("serve.step")[n0:]
        # queue length at the step nearest each quarter of the window
        t_rel = [s["t"] - steps[0]["t"] for s in steps]
        quarters = []
        for q in (0.25, 0.5, 0.75, 1.0):
            j = max((i for i, t in enumerate(t_rel) if t <= q * args.seconds),
                    default=0)
            quarters.append(steps[j]["n_queued"] + steps[j]["n_active"])
        reqs = [s["req"] for s in sub]
        ttft = [1e3 * (s["req"].t_first_token - s["due"]) for s in sub
                if s["req"].t_first_token is not None]
        itl = [1e3 * (b - a) for r in reqs
               for a, b in zip(r.token_walls, r.token_walls[1:])]
        tokens = sum(sum(1 for w in r.token_walls if w <= t_close) for r in reqs)
        print(json.dumps({
            "rate_per_s": rate, "requests": len(reqs),
            "in_system_at_quarters": quarters,
            "queued_max": max(s["n_queued"] for s in steps),
            "ttft_p50_ms": stats.percentile(ttft, 0.5),
            "ttft_p95_ms": stats.percentile(ttft, 0.95),
            "ttft_max_ms": max(ttft),
            "itl_p50_ms": stats.percentile(itl, 0.5),
            "itl_p95_ms": stats.percentile(itl, 0.95),
            "tokens_per_s_in_window": tokens / args.seconds,
            "drain_s": time.monotonic() - t_close}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
