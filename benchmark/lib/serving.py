"""Kind ``serve`` (open loop or standing backlog): one ``ServeEngine`` driven by
one thread.  Requests come from ``lib/arrivals.py``; each is submitted when
it is due (the loop submits between engine steps, so a request can be late
by up to one step: the lateness is printed) and timed from its DUE time.

Set-up sends one short request of every distinct prompt length of the mix
through the engine, because the engine's prefill commit runs small
per-length programs; after that nothing compiles in the window (counted).

``correct`` comes after the window, once the engine is freed: for a seeded
sample of finished requests with the longest in it, the reference's full
forward pass over prompt + served tokens gives the logits at every served
position, and a served token may lie below the reference's best logit there
by at most the limit (its regret).  Greedy traffic only.
"""

from __future__ import annotations

import gc
import json
import time

import numpy as np

SPANS = ("engine_step", "submit_due", "idle_wait")


def _build(ctx):
    import jax

    from lib import harness

    from lib import program, weights
    from torch_automatic_distributed_neural_network_tpu.inference.serve import (
        ServeEngine,
    )
    from torch_automatic_distributed_neural_network_tpu.obs.journal import (
        Journal,
    )

    cell, args = ctx["cell"], ctx["args"]
    mix, config = cell.mix, cell.config
    harness.mark(ctx, "imports")
    model = program.build_model(config, mix.get("model_options"))
    shapes = program.check_shapes(model, config, np.zeros((1, 8), np.int32))
    key = weights.seed_key(args.seed)
    harness.mark(ctx, "shapes_checked")
    params = jax.jit(lambda k: weights.nest(weights.flat(k, shapes)))(key)
    jax.block_until_ready(params)
    harness.mark(ctx, "weights_from_seed")
    journal = Journal(None, host0_only=False)
    eng = ServeEngine(model, {"params": params}, journal=journal,
                      export_cache=False, **mix["engine"])
    return eng, journal, shapes, key


def _warm(eng, mix: dict, vocab: int, seed: int) -> int:
    from lib import arrivals

    rs = np.random.RandomState((int(seed) + 1) % 2**32)
    lengths = arrivals.distinct_prompt_lengths(mix["lengths"])
    for n in lengths:
        eng.submit([int(t) for t in rs.randint(1, vocab, size=n)],
                   max_new_tokens=2)
    eng.run()
    eng.finished.clear()
    return len(lengths)


def _drive(ctx, eng, plan, seconds: float, drain_s: float):
    """The window, then (``drain_s`` > 0) stepping on until every request
    that fell due has finished.  Returns (t_open, submitted records)."""
    profiler = ctx["profiler"]
    trace_s = ctx["cell"].mix["trace_seconds"]
    sched = eng.scheduler
    sub: list[dict] = []
    i = 0
    t_open = time.monotonic()
    profiler.start()
    while True:
        now = time.monotonic() - t_open
        if profiler.active and now >= trace_s:
            profiler.stop()
        if now >= seconds:
            if i < len(plan) or sched.idle() or now >= seconds + drain_s:
                break
        if i < len(plan) and plan[i].due_s <= now:
            with profiler.span("submit_due"):
                while i < len(plan) and plan[i].due_s <= now:
                    p = plan[i]
                    req = eng.submit(list(p.prompt), max_new_tokens=p.max_new)
                    sub.append({"req": req, "due": t_open + p.due_s,
                                "submitted": time.monotonic()})
                    i += 1
        if sched.idle():
            nxt = plan[i].due_s if i < len(plan) else seconds
            with profiler.span("idle_wait"):
                time.sleep(max(0.0, min(nxt, seconds) - now))
            continue
        with profiler.span("engine_step"):
            eng.step()
    profiler.stop()
    return t_open, sub


def _sample(finished: list, k: int, seed: int) -> list:
    """``k`` finished requests drawn from the seed, the longest among them."""
    if not finished:
        return []
    rs = np.random.RandomState((int(seed) + 2) % 2**32)
    longest = max(range(len(finished)),
                  key=lambda j: len(finished[j]["prompt"]) + len(finished[j]["out"]))
    others = [j for j in range(len(finished)) if j != longest]
    pick = [longest] + [others[j] for j in rs.permutation(len(others))[:k - 1]]
    return [finished[j] for j in pick]


def regrets(ctx, shapes, key, sample: list, precs: tuple[str, ...]) -> dict:
    """For each precision: at every served position, how far the token that
    precision would serve lies below the float32 reference's best logit.
    ``"served"`` stands for the engine's own tokens."""
    import jax
    import jax.numpy as jnp

    from lib import program, weights
    from reference import decoder

    keys = program.model_keys(ctx["cell"].config)
    width = ctx["cell"].mix["engine"]["max_len"]
    params = jax.jit(lambda k: weights.flat(k, shapes))(key)

    out = {p: [] for p in ("served",) + precs}
    t0 = time.perf_counter()
    for r in sample:
        toks = np.zeros((1, width), np.int32)
        seq = list(r["prompt"]) + list(r["out"])
        toks[0, :len(seq)] = seq
        lo, n = len(r["prompt"]) - 1, len(r["out"])
        served = np.zeros((width,), np.int32)
        served[lo:lo + n] = r["out"]
        l32 = decoder.forward_logits(params, keys, toks, "f32")[0]
        best = jnp.max(l32, -1)

        def gap_of(tok):  # fixed shapes on the device, the slice on the host
            g = best - jnp.take_along_axis(l32, tok[:, None], -1)[:, 0]
            return np.asarray(g)[lo:lo + n]

        out["served"].append(gap_of(jnp.asarray(served)))
        for p in precs:
            low = decoder.forward_logits(params, keys, toks, p)[0]
            out[p].append(gap_of(jnp.argmax(low, -1).astype(jnp.int32)))
    res = {}
    for p, parts in out.items():
        g = np.concatenate(parts) if parts else np.zeros((0,))
        res[p] = {"max": float(g.max()) if g.size else float("nan"),
                  "mean": float(g.mean()) if g.size else float("nan"),
                  "positions": int(g.size),
                  "share_positive": float((g > 0).mean()) if g.size else 0.0}
    res["seconds"] = time.perf_counter() - t0
    return res


def run(ctx, *, control: bool = False):
    import jax

    from lib import arrivals, harness, peaks, program, stats

    cell, args, profiler = ctx["cell"], ctx["args"], ctx["profiler"]
    mix, devices = cell.mix, ctx["devices"]
    seconds = float(args.seconds)
    vocab = program.model_keys(cell.config)["vocab_size"]
    eng, journal, shapes, key = _build(ctx)
    harness.mark(ctx, "engine_built")
    n_warm = _warm(eng, mix, vocab, args.seed)
    harness.mark(ctx, "prompt_lengths_warmed")
    plan = arrivals.plan(mix, args.seed, seconds, vocab)
    steps_before = len(journal.named("serve.step"))
    compiles_before = ctx["compiles"].n
    setup_s = time.perf_counter() - ctx["t0"]
    t_open, sub = _drive(ctx, eng, plan, seconds, float(mix.get("drain_seconds", 0)))
    t_close = t_open + seconds
    compiles_in_window = ctx["compiles"].n - compiles_before
    steps = journal.named("serve.step")[steps_before:]
    memory_peak = harness.memory_peak_bytes(devices)

    reqs = []
    for s in sub:
        r = s["req"]
        reqs.append({
            "due": s["due"], "late_s": s["submitted"] - s["due"],
            "t_admit": r.t_admit, "t_first": r.t_first_token,
            "walls": list(r.token_walls), "prompt": list(r.prompt),
            "out": list(r.out_tokens), "max_new": r.max_new_tokens,
            "done": r.t_done is not None})
    t_end = time.monotonic()
    tokens_in_window = sum(sum(1 for w in q["walls"] if w <= t_close)
                           for q in reqs)
    finished = [q for q in reqs if q["done"]]
    wrong_count = sum(len(q["out"]) != q["max_new"] for q in finished)
    late = [q["late_s"] for q in reqs] or [0.0]
    print(json.dumps({"window": {
        "planned": len(plan), "submitted": len(reqs),
        "finished": len(finished), "tokens_in_window": tokens_in_window,
        "serve_tokens_per_s": tokens_in_window / seconds,
        "engine_steps": len(steps), "warmed_prompt_lengths": n_warm,
        "generator_late_s_max": max(late),
        "generator_late_s_p95": stats.percentile(late, 0.95),
        "compiles_in_window": compiles_in_window,
        "drained_s": t_end - t_close}}), flush=True)

    e2e = {"setup_s": setup_s,
           "serve_tokens_per_s": tokens_in_window / seconds}
    unserved = sum(q["t_first"] is None for q in reqs)
    itl = [1e3 * (b - a) for q in reqs
           for a, b in zip(q["walls"], q["walls"][1:])]
    if itl:
        e2e["itl_p95_ms"] = stats.percentile(itl, 0.95)
    # a request never answered waited at least until now: it counts as the
    # worst, not as missing
    ttft = [1e3 * ((q["t_first"] if q["t_first"] is not None else t_end)
                   - q["due"]) for q in reqs]
    waits = [1e3 * (q["t_admit"] - q["due"]) for q in reqs
             if q["t_admit"] is not None]
    print(json.dumps({"callers": {
        "requests": len(reqs), "unserved": unserved,
        "ttft_p50_ms": stats.percentile(ttft, 0.5),
        "ttft_p95_ms": stats.percentile(ttft, 0.95),
        "ttft_max_ms": max(ttft),
        "queue_wait_p95_ms": stats.percentile(waits, 0.95) if waits else None,
        "itl_p50_ms": stats.percentile(itl, 0.5) if itl else None,
        "itl_gaps": len(itl)}}), flush=True)
    record = {
        "cell": cell, "chips": len(devices),
        "serve_steps": steps, "requests": reqs,
        "model_keys": program.model_keys(cell.config),
        "engine": mix["engine"], "end_to_end": e2e,
        "memory_peak_bytes": memory_peak,
    }
    if args.trace:
        record["trace"] = profiler.reduced(SPANS)
        record["trace_mono"] = (profiler.mono_start, profiler.mono_stop)
    if ctx["on_chip"]:
        record["peaks"] = peaks.peaks(devices[0].device_kind)

    # the engine is freed before the reference takes the chip
    del eng, sub
    gc.collect()
    verdict = harness.Verdict(cell.limits)
    sample = _sample(finished, int(mix["check_requests"]), args.seed)
    precs = (cell.limits["control_precision"],) if control else ()
    res = regrets(ctx, shapes, key, sample, precs)
    print(json.dumps({"reference": res, "checked_requests": len(sample)}),
          flush=True)
    verdict.check("served_token_regret.max", res["served"]["max"])
    verdict.check("served_token_regret.mean", res["served"]["mean"])
    verdict.check("token_count_mismatches", float(wrong_count))
    verdict.check("compiles_in_window", float(compiles_in_window))
    record["correct"] = verdict.correct
    record["attempted"] = len(reqs) if mix.get("drain_seconds") else len(finished)
    record["failed"] = int(wrong_count + (unserved if mix.get("drain_seconds") else 0))
    if control:
        low = harness.Verdict(cell.limits)
        p = precs[0]
        low.check("served_token_regret.max", res[p]["max"])
        low.check("served_token_regret.mean", res[p]["mean"])
        print(json.dumps({"control": True, "precision": p,
                          "correct": low.correct,
                          "program_correct": verdict.correct}), flush=True)
        return 0 if not low.correct else 1
    return record
