"""Kind ``train``: ``AutoDistribute`` + ``Trainer`` on fresh seeded batches.

Set-up builds ONE object (the compiled step with its state) and drives it
through its first steps, whose losses, first gradient and parameter change
the plain reference follows afterwards; the same object then runs the
warm-up steps and the timed window, all inside one ``Trainer.fit`` through
the Trainer's own input path (handed the state, as a resumed run is).
The feed closes the window: it raises ``StopIteration`` once ``--seconds``
have passed, which the Trainer takes as a clean end of data.
"""

from __future__ import annotations

import dataclasses
import gc
import json
import math
import statistics
import time

SPANS = ("train_step_dispatch", "input_batch", "fence_on_loss")


class Feed:
    """Step-indexed source: ``SyntheticLM(seed).batch(i)`` until the window
    that ``open`` started has lasted ``seconds``."""

    step_indexed = True

    def __init__(self, source, seconds: float, profiler):
        self.source, self.seconds, self.profiler = source, seconds, profiler
        self.t_open = None

    def open(self, t: float):
        self.t_open = t

    def batch(self, i: int):
        if (self.t_open is not None
                and time.perf_counter() - self.t_open >= self.seconds):
            raise StopIteration
        with self.profiler.span("input_batch"):
            return self.source.batch(i)


def _leaf_norms(tree_flat: dict) -> dict:
    import jax
    import jax.numpy as jnp

    norms = jax.jit(lambda t: {k: jnp.sqrt(jnp.sum(jnp.square(
        v.astype(jnp.float32)))) for k, v in t.items()})(tree_flat)
    return {k: float(v) for k, v in norms.items()}


def _delta_norms(params_flat: dict, key, shapes: dict) -> dict:
    """Per-leaf norm of (parameters - the seed's initial parameters), the
    latter made again leaf by leaf and not kept."""
    import jax
    import jax.numpy as jnp

    from lib import weights

    norms = jax.jit(lambda t, k: {p: jnp.sqrt(jnp.sum(jnp.square(
        v.astype(jnp.float32) - weights.leaf(k, p, shapes[p]))))
        for p, v in t.items()})(params_flat, key)
    return {k: float(v) for k, v in norms.items()}


def _find_mu(opt_state):
    """Adam's first moment, wherever optax's chain keeps it."""
    import jax

    hits = [s for s in jax.tree.leaves(
        opt_state, is_leaf=lambda x: hasattr(x, "mu")) if hasattr(s, "mu")]
    if len(hits) != 1:
        raise RuntimeError(f"expected one Adam state, found {len(hits)}")
    return hits[0].mu


def gap_worst_leaf(prog: dict, ref: dict) -> tuple[float, str]:
    """The gap between the program's norm and the reference's, by the worst
    leaf, against the reference's norm of that leaf or of the median leaf,
    whichever is larger (some gradients are all but zero)."""
    med = statistics.median(ref.values())
    gaps = {k: abs(prog[k] - ref[k]) / max(ref[k], med) for k in ref}
    worst = max(gaps, key=gaps.get)
    return gaps[worst], worst


def gap_total(prog: dict, ref: dict) -> float:
    p = math.sqrt(sum(v * v for v in prog.values()))
    r = math.sqrt(sum(v * v for v in ref.values()))
    return abs(p - r) / r


def compare(verdict, prog: dict, ref: dict) -> None:
    for i, (lp, lr) in enumerate(zip(prog["losses"], ref["losses"])):
        verdict.check(f"loss_rel_gap.step{i + 1}", abs(lp - lr) / abs(lr))
    g, leaf = gap_worst_leaf(prog["grad_norms"], ref["grad_norms"])
    print(json.dumps({"grad_norm_worst_leaf": leaf}), flush=True)
    verdict.check("first_grad_norm_gap.worst_leaf", g)
    verdict.check("param_change_norm_gap.total",
                  gap_total(prog["delta_norms"], ref["delta_norms"]))
    d, leaf = gap_worst_leaf(prog["delta_norms"], ref["delta_norms"])
    print(json.dumps({"info": "param_change_norm_gap.worst_leaf", "value": d,
                      "leaf": leaf}), flush=True)


def _setup(ctx):
    import jax
    import numpy as np

    from lib import program, weights
    from torch_automatic_distributed_neural_network_tpu.data.synthetic import (
        SyntheticLM,
    )

    cell, args = ctx["cell"], ctx["args"]
    mix, config = cell.mix, cell.config
    keys = program.model_keys(config)
    data = SyntheticLM(vocab_size=keys["vocab_size"], seq_len=mix["seq_len"] + 1,
                       batch_size=mix["batch_size"],
                       seed=int(args.seed) % (2**32 - 2**20))
    model = program.build_model(config, mix.get("model_options"))
    sample = np.zeros((1, mix["seq_len"]), np.int32)
    shapes = program.check_shapes(model, config, sample)
    key = weights.seed_key(args.seed)
    return keys, data, model, shapes, key, jax


def reference_numbers(ctx, keys, data, shapes, key, prec: str) -> dict:
    import jax

    from lib import weights
    from reference import decoder

    mix = ctx["cell"].mix
    params = jax.jit(lambda k: weights.flat(k, shapes))(key)
    batches = [data.batch(i) for i in range(mix["reference_steps"])]
    t0 = time.perf_counter()
    out = decoder.train_steps(
        params, keys, batches, mix["optimizer"], prec=prec,
        rows=mix["reference_rows"],
        make_leaf=lambda k: weights.leaf(key, k, shapes[k]))
    out["seconds"] = time.perf_counter() - t0
    print(json.dumps({"reference": prec, **out}), flush=True)
    return out


def run(ctx) -> dict:
    import optax

    import torch_automatic_distributed_neural_network_tpu as tad
    from lib import counts, harness, peaks, weights
    from torch_automatic_distributed_neural_network_tpu.obs.journal import (
        Journal,
    )
    from torch_automatic_distributed_neural_network_tpu.training import (
        Trainer,
        TrainerConfig,
        next_token_loss,
    )

    keys, data, model, shapes, key, jax = _setup(ctx)
    jnp = jax.numpy
    harness.mark(ctx, "imports_and_shapes")
    cell, args, profiler = ctx["cell"], ctx["args"], ctx["profiler"]
    mix, devices = cell.mix, ctx["devices"]
    opt = mix["optimizer"]
    n_follow, n_warm = mix["reference_steps"], mix["warmup_steps"]
    tokens_per_step = mix["batch_size"] * mix["seq_len"]

    ad = tad.AutoDistribute(
        model,
        optimizer=optax.adamw(opt["lr"], b1=opt["b1"], b2=opt["b2"],
                              eps=opt["eps"],
                              weight_decay=opt["weight_decay"]),
        loss_fn=next_token_loss,
        # the state is built on zeros (no program in set-up depends on the
        # seed, so every seed finds it in the compile cache) and is given
        # the benchmark's own weights below
        init_fn=lambda _rng, _batch: {"params": weights.nest(
            {p: jnp.zeros(s, jnp.float32) for p, s in shapes.items()})},
        devices=devices, export_cache=False, **mix["autodistribute"])
    rng = jax.random.fold_in(key, 1)
    state = ad.init(rng, data.batch(0))
    harness.mark(ctx, "state_and_step_built")
    dtype = ad.precision.param_dtype
    shardings = jax.tree.map(lambda x: x.sharding, state.params)
    state = dataclasses.replace(state, params=None)  # free the zeros first
    state = dataclasses.replace(state, params=jax.jit(
        lambda k: jax.tree.map(lambda x: x.astype(dtype),
                               weights.nest(weights.flat(k, shapes))),
        out_shardings=shardings)(key))
    jax.block_until_ready(state.params)
    harness.mark(ctx, "weights_from_seed")
    raw_step = ad.step

    dispatch_s: dict[int, float] = {}

    def spanned_step(state, batch):
        t = time.perf_counter()
        with profiler.span("train_step_dispatch"):
            out = raw_step(state, batch)
        dispatch_s[len(dispatch_s) + 1] = time.perf_counter() - t
        return out

    ad.step = spanned_step  # a span round the benchmark's call, no more

    feed = Feed(data, float(args.seconds), profiler)
    prog: dict = {"losses": []}
    done: dict[int, float] = {}   # step -> when its loss was seen ready
    fence_s: dict[int, float] = {}
    all_losses: list[float] = []
    win: dict = {}
    k_open = n_follow + n_warm
    waiting: dict = {}            # the one step dispatched and not yet seen

    def see(i, metrics):
        """Wait for step i's loss; note when it came."""
        t = time.perf_counter()
        with profiler.span("fence_on_loss"):
            loss = float(jax.block_until_ready(metrics["loss"]))
        done[i] = time.perf_counter()
        fence_s[i] = done[i] - t
        all_losses.append(loss)
        if i <= k_open:
            harness.mark(ctx, f"step_{i}")
        if i <= n_follow:
            prog["losses"].append(loss)
        if i == k_open:
            win["compiles_at_open"] = ctx["compiles"].n
            win["setup_s"] = done[i] - ctx["t0"]
            feed.open(done[i])
            profiler.start()

    def on_step(i, state, metrics):
        # The first steps are read one by one.  From then on the fence lags
        # by one step, as a training loop's does: step i is already queued
        # when step i - 1 is waited for, so a pause of the host (the one-chip
        # machine shares its host's cores) under one step long idles nothing.
        for j, m in list(waiting.items()):
            see(j, m)
            del waiting[j]
        if i <= n_follow:
            see(i, metrics)
        else:
            waiting[i] = metrics
        if i == 1:
            mu = weights.unnest(_find_mu(state.opt_state))
            prog["grad_norms"] = {k: v / (1.0 - opt["b1"])
                                  for k, v in _leaf_norms(mu).items()}
            harness.mark(ctx, "first_grad_norms")
        if i == n_follow:
            prog["delta_norms"] = _delta_norms(
                weights.unnest(state.params), key, shapes)
            harness.mark(ctx, "param_change_norms")
        if (profiler.active and time.perf_counter() - profiler.t_start
                >= mix["trace_seconds"]):
            profiler.stop()

    trainer = Trainer(
        ad, TrainerConfig(steps=10**9, log_every=0, **mix["trainer"]),
        callbacks=[on_step], items_per_step=tokens_per_step,
        journal=Journal(None, host0_only=False))
    state = trainer.fit(feed, state=state)
    for j, m in list(waiting.items()):
        see(j, m)
    profiler.stop()
    compiles_in_window = ctx["compiles"].n - win["compiles_at_open"]
    print(json.dumps({"plan": {"strategy": ad.plan.strategy,
                               "remat": bool(ad.plan.remat)},
                      "compiles_in_window": compiles_in_window}), flush=True)

    t_open = done[k_open]
    inside = [j for j in sorted(done) if j > k_open
              and done[j] - t_open <= float(args.seconds)]
    if not inside:
        raise RuntimeError("no whole step completed inside the window")
    step_s = [done[j] - done[j - 1] for j in inside]
    tps_chip = (tokens_per_step * len(inside)
                / (done[inside[-1]] - t_open) / len(devices))
    record = {
        "cell": cell, "chips": len(devices),
        "step_seconds": step_s, "tokens_per_step": tokens_per_step,
        "n_params": sum(math.prod(s) for s in shapes.values()),
        "model_keys": keys,
        "end_to_end": {"train_tokens_per_s_chip": tps_chip,
                       "setup_s": win["setup_s"]},
        "attempted": len(inside),
    }
    if args.trace:
        report = ad.compile_report(rng, data.batch(0))
        record["step_hbm_bytes"] = (report or {}).get("per_device_peak_bytes")
        record["trace"] = profiler.reduced(SPANS)
    if ctx["on_chip"]:
        record["peaks"] = peaks.peaks(devices[0].device_kind)
    record["memory_peak_bytes"] = harness.memory_peak_bytes(devices)
    slow = sorted(inside, key=lambda j: -(done[j] - done[j - 1]))[:3]
    print(json.dumps({"window": {"steps": len(inside),
                                 "step_s_median": statistics.median(step_s),
                                 # [step, seconds, of which dispatch, fence]
                                 "slowest_steps": [
                                     [j, done[j] - done[j - 1],
                                      dispatch_s[j], fence_s[j]]
                                     for j in slow],
                                 "tokens_per_s_chip": tps_chip,
                                 "model_flops_per_step":
                                 counts.train_step_model_flops(
                                     record["n_params"], tokens_per_step)}}),
          flush=True)

    # the program's state is freed before the reference takes the chip
    del state, trainer, ad, raw_step
    gc.collect()
    verdict = harness.Verdict(cell.limits)
    ref = reference_numbers(ctx, keys, data, shapes, key, "f32")
    compare(verdict, prog, ref)
    verdict.check("nonfinite_losses",
                  float(sum(not math.isfinite(x) for x in all_losses)))
    verdict.check("compiles_in_window", float(compiles_in_window))
    record["correct"] = verdict.correct
    record["failed"] = int(sum(not math.isfinite(x) for x in all_losses))
    return record


def control(ctx) -> int:
    """The reference in the precision below the stated one (fp8 operands)
    in the program's place, against the float32 reference.  Needs no
    measured window.  Exit 0 when the verdict is NOT correct, as it must
    be."""
    from lib import harness

    keys, data, _model, shapes, key, _jax = _setup(ctx)
    low = reference_numbers(ctx, keys, data, shapes, key,
                            ctx["cell"].limits["control_precision"])
    ref = reference_numbers(ctx, keys, data, shapes, key, "f32")
    verdict = harness.Verdict(ctx["cell"].limits)
    compare(verdict, low, ref)
    print(json.dumps({"control": True, "correct": verdict.correct}), flush=True)
    return 0 if not verdict.correct else 1
