"""Kind ``serve-large``: what ``lib/serving.py`` does, for a configuration
whose weights do not fit in float32 and whose architecture
``reference/decoder.py`` does not cover.

The same pieces as kind ``serve``, imported and not copied: the request
generator, the warm-up of every prompt length, the window's driver, the
seeded sample, the verdict, the spans.  Four things differ.

- **Weights.**  Every leaf is still drawn by ``weights.leaf`` from the seed
  and the leaf's path (so one generator feeds the system and the reference),
  a large leaf a program, and is then held in the dtype in which the ENGINE holds
  it: the program's own ``compute_dtype_params`` is asked (on shapes alone)
  which leaves it rounds to the compute dtype at construction.  The values
  are the same (``weights.leaf`` rounds through bfloat16), but no float32
  copy of an expert stack is ever kept: 17 GB would not fit.  This and
  ``lib/program.py`` are the files that know how the program spells things.
- **Reference.**  The file the configuration names under ``reference``,
  loaded by ``harness.load_module``; it takes the same leaves (widened where
  used) and must offer ``param_shapes(keys)`` and ``forward_logits(params,
  keys, tokens, prec)``.
- **What the seed draws.**  As in kind ``serve``, where the configuration and
  the mix say nothing.  But on seeded random weights this architecture
  routes a whole SEQUENCE to the same few experts (after the sandwich norm
  the attention branch is the context's mean value vector at full size, so
  the rows of a sequence share a direction: one expert takes 20-60% of a
  sequence's tokens in a layer, and the share of a sequence's pairs that
  lands on the 32 experts held here is 0.05-0.23 where 1/8 is due), and a
  window's work is dominated by some forty long prompts.  So how many
  pairs land here, and with them ``serve_tokens_per_s`` (by 2%, where
  repeats of one seed agree within 0.6%), was a draw of the seed's weights
  and token values: the driver's check refused the cell for that spread.
  A configuration may therefore name ``weights_seed`` and a mix
  ``traffic_seed``: weights, and lengths' order, pairing and token values
  (``arrivals.plan``), are then drawn from THOSE numbers, the same for
  every run, and ``--seed`` draws the warm-up's tokens and the sample of
  finished requests that the reference checks.  Every run then does the
  same work; what is lost is the check's coverage of other weights.
- **Record.**  The same keys as kind ``serve`` writes, so that the readers
  under ``metrics/`` read it, and ``serve_engine``: the engine's own
  ``serve.engine`` event (layer kinds, experts held, the pool's bytes).

``correct`` is decided as in kind ``serve``: served-token regret (widest
and mean) against the float32 reference's logits, every finished request's
token count, no compile in the window.  ``--control`` scores the fp8
reference's own first choices the same way and must come out not correct.
"""

from __future__ import annotations

import gc
import json
import os
import time

import numpy as np

from lib import arrivals, harness, peaks, program, stats, weights
from lib.serving import SPANS, _drive, _sample, _warm


BIG_LEAF = 2**26  # elements: 256 MiB in float32


def load_reference(config: dict):
    return harness.load_module(os.path.join(harness.REPO, config["reference"]),
                               "bench_reference")


def held_dtypes(model, abstract_params) -> dict:
    """Path -> the dtype in which the engine holds that leaf."""
    import jax

    from torch_automatic_distributed_neural_network_tpu.inference.decode import (
        compute_dtype_params,
    )

    held = jax.eval_shape(lambda p: compute_dtype_params(p, model.cfg),
                          abstract_params)
    return {k: v.dtype for k, v in weights.unnest(held).items()}


def big_leaf(key, fold, shape: tuple[int, ...], dtype, scale: bool):
    """``weights.leaf(key, path, shape)`` in ``dtype``, with the path's
    share of it (``fold``: the crc32 it folds into the key; ``scale``:
    whether the path ends in "scale") as OPERANDS, so that leaves of one
    shape share one compiled program: 12 expert stacks of [32, 3072, 3072]
    were 12 programs of 9 s each on an empty compile cache.  The same
    values (``tests/test_serve_large.py`` compares them)."""
    import jax
    import jax.numpy as jnp

    x = 0.02 * jax.random.normal(jax.random.fold_in(key, fold), shape,
                                 jnp.float32)
    x = jnp.where(scale, 1.0 + x, x)
    return x.astype(jnp.bfloat16).astype(jnp.float32).astype(dtype)


def seeded_weights(key, shapes: dict, dtypes: dict) -> dict:
    """Every leaf by path, each in the dtype the engine holds it in.  A
    large leaf is drawn alone (its float32 draw is then the only one
    alive) by ``big_leaf``; the small leaves of each top-level group by
    ``weights.leaf`` in one program a group."""
    import zlib

    import jax

    draw = jax.jit(big_leaf, static_argnums=(2, 3))
    flat, groups = {}, {}
    for path, shape in shapes.items():
        if int(np.prod(shape)) >= BIG_LEAF:
            flat[path] = draw(
                key, np.uint32(zlib.crc32(path.encode()) & 0x7FFFFFFF),
                tuple(shape), np.dtype(dtypes[path]), path.endswith("scale"))
        else:
            groups.setdefault(path.split("/")[0], []).append(path)
    for paths in groups.values():
        flat.update(jax.jit(lambda k, paths=paths: {
            p: weights.leaf(k, p, shapes[p]).astype(dtypes[p])
            for p in paths})(key))
    return flat


def build(ctx):
    """The engine on seeded weights; returns it with its journal, the flat
    weights (the reference takes the same arrays) and the reference."""
    import jax

    from torch_automatic_distributed_neural_network_tpu.inference.serve import (
        ServeEngine,
    )
    from torch_automatic_distributed_neural_network_tpu.obs.journal import (
        Journal,
    )

    cell, args = ctx["cell"], ctx["args"]
    mix, config = cell.mix, cell.config
    harness.mark(ctx, "imports")
    ref = load_reference(config)
    model = program.build_model(config, mix.get("model_options"))
    shapes = ref.param_shapes(program.model_keys(config))
    abstract = jax.eval_shape(model.init, jax.random.key(0),
                              np.zeros((1, 8), np.int32))["params"]
    prog = {k: tuple(v.shape) for k, v in weights.unnest(abstract).items()}
    if prog != shapes:
        diff = sorted(set(prog.items()) ^ set(shapes.items()))
        raise RuntimeError(
            f"the program's parameters differ from the reference's: {diff}")
    dtypes = held_dtypes(model, abstract)
    harness.mark(ctx, "shapes_checked")
    flat = seeded_weights(
        weights.seed_key(config.get("weights_seed", args.seed)), shapes, dtypes)
    jax.block_until_ready(flat)
    harness.mark(ctx, "weights_from_seed")
    journal = Journal(None, host0_only=False)
    eng = ServeEngine(model, {"params": weights.nest(flat)}, journal=journal,
                      export_cache=False, **mix["engine"])
    return eng, journal, flat, ref


def regrets(ctx, ref, params: dict, sample: list,
            precs: tuple[str, ...]) -> dict:
    """For each precision: at every served position, how far the token that
    precision would serve lies below the float32 reference's best logit.
    ``"served"`` stands for the engine's own tokens."""
    import jax.numpy as jnp

    keys = program.model_keys(ctx["cell"].config)
    width = ctx["cell"].mix["engine"]["max_len"]
    out = {p: [] for p in ("served",) + precs}
    t0 = time.perf_counter()
    for r in sample:
        toks = np.zeros((1, width), np.int32)
        seq = list(r["prompt"]) + list(r["out"])
        toks[0, :len(seq)] = seq
        lo, n = len(r["prompt"]) - 1, len(r["out"])
        served = np.zeros((width,), np.int32)
        served[lo:lo + n] = r["out"]
        l32 = ref.forward_logits(params, keys, toks, "f32").reshape(
            width, -1)
        best = jnp.max(l32, -1)

        def gap_of(tok):  # fixed shapes on the device, the slice on the host
            g = best - jnp.take_along_axis(l32, tok[:, None], -1)[:, 0]
            return np.asarray(g)[lo:lo + n]

        out["served"].append(gap_of(jnp.asarray(served)))
        for p in precs:
            low = jnp.argmax(ref.forward_logits(params, keys, toks, p), -1)
            out[p].append(gap_of(low.reshape(width).astype(jnp.int32)))
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
    cell, args, profiler = ctx["cell"], ctx["args"], ctx["profiler"]
    mix, devices = cell.mix, ctx["devices"]
    seconds = float(args.seconds)
    vocab = program.model_keys(cell.config)["vocab_size"]
    eng, journal, params, ref = build(ctx)
    harness.mark(ctx, "engine_built")
    n_warm = _warm(eng, mix, vocab, args.seed)
    harness.mark(ctx, "prompt_lengths_warmed")
    plan = arrivals.plan(mix, mix.get("traffic_seed", args.seed), seconds,
                         vocab)
    steps_before = len(journal.named("serve.step"))
    compiles_before = ctx["compiles"].n
    setup_s = time.perf_counter() - ctx["t0"]
    t_open, sub = _drive(ctx, eng, plan, seconds,
                         float(mix.get("drain_seconds", 0)))
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

    # a run that lost seconds to a pause of the runtime or the host shows
    # it here: the longest engine steps beside the median one
    step_s = sorted(s["step_s"] for s in steps if s.get("step_s"))
    if step_s:
        worst = max(range(len(steps)),
                    key=lambda j: steps[j].get("step_s") or 0.0)
        print(json.dumps({"steps": {
            "longest_at": worst,
            "longest_phases_ms": {k: round(1e3 * v, 1) for k, v in
                                  (steps[worst].get("phases") or {}).items()
                                  if v > 1e-3},
            "median_ms": 1e3 * stats.percentile(step_s, 0.5),
            "longest_ms": [round(1e3 * x, 1) for x in step_s[-5:]],
            "over_100ms": sum(x > 0.1 for x in step_s),
            "over_100ms_total_s": sum(x for x in step_s if x > 0.1)}}),
              flush=True)
    moe = [s for s in steps if s.get("moe_pairs") is not None]
    rows = sum(s["new_tokens"] for s in moe)
    if rows:
        m = program.model_keys(cell.config)
        due = (m["experts_per_token"]
               * (m["n_layers"] - m["n_dense_layers"]))
        print(json.dumps({"routing": {
            "decode_steps": len(moe),
            "held_share_of_pairs": sum(s["moe_pairs"] for s in moe)
            / (rows * due),
            "held_share_due": m["experts_held"] / m["experts_published"],
            "pairs_a_step": sum(s["moe_pairs"] for s in moe) / len(moe),
            "experts_touched_a_step":
                sum(s["moe_experts_touched"] for s in moe) / len(moe),
            "decode_wait_median_ms": 1e3 * stats.percentile(
                [s["phases"].get("decode_wait", 0.0) for s in moe], 0.5),
        }}), flush=True)
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
    print(json.dumps({"callers": {
        "requests": len(reqs), "unserved": unserved,
        "ttft_p50_ms": stats.percentile(ttft, 0.5),
        "ttft_p95_ms": stats.percentile(ttft, 0.95),
        "itl_p50_ms": stats.percentile(itl, 0.5) if itl else None,
        "itl_gaps": len(itl)}}), flush=True)
    record = {
        "cell": cell, "chips": len(devices),
        "serve_steps": steps, "requests": reqs,
        "serve_engine": (journal.named("serve.engine") or [None])[-1],
        "model_keys": program.model_keys(cell.config),
        "engine": mix["engine"], "end_to_end": e2e,
        "memory_peak_bytes": memory_peak,
    }
    if args.trace:
        record["trace"] = profiler.reduced(SPANS)
        record["trace_mono"] = (profiler.mono_start, profiler.mono_stop)
    if ctx["on_chip"]:
        record["peaks"] = peaks.peaks(devices[0].device_kind)

    # the engine is freed before the reference takes the chip; the weights
    # stay, the reference reads the same arrays
    del eng, sub
    gc.collect()
    print(json.dumps({"engine_freed": {"bytes_in_use": [
        (d.memory_stats() or {}).get("bytes_in_use") for d in devices]}}),
          flush=True)
    verdict = harness.Verdict(cell.limits)
    sample = _sample(finished, int(mix["check_requests"]), args.seed)
    precs = (cell.limits["control_precision"],) if control else ()
    res = regrets(ctx, ref, params, sample, precs)
    print(json.dumps({"reference": res, "checked_requests": len(sample)}),
          flush=True)
    verdict.check("served_token_regret.max", res["served"]["max"])
    verdict.check("served_token_regret.mean", res["served"]["mean"])
    verdict.check("token_count_mismatches", float(wrong_count))
    verdict.check("compiles_in_window", float(compiles_in_window))
    record["correct"] = verdict.correct
    record["attempted"] = len(reqs) if mix.get("drain_seconds") else len(finished)
    record["failed"] = int(wrong_count
                           + (unserved if mix.get("drain_seconds") else 0))
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
