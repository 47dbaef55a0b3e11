"""Headline benchmark: GPT-2 1.3B tokens/sec/chip (the BASELINE.json:10
named config), on the device JAX gives this process.

Prints ONE JSON line to stdout:
    {"metric": "...", "value": N, "unit": "...", "vs_baseline": N,
     "device": {"platform": "...", "kind": "...", "count": N}}

A mode that cannot measure — too few devices visible, a sweep that
needs the TPU — exits non-zero with a message; there is no fallback to
another backend and no replay of an earlier number.

The reference publishes no numbers (BASELINE.md): ``vs_baseline`` is
measured MFU / the 40%-MFU north-star target (BASELINE.json:5), so 1.0
means "hit the target".  MFU here is strict model-MFU — 6NT useful FLOPs
only; activation recompute (remat) is credited only via the 8/6 multiplier
when the *outer* loss-level checkpoint is on.  Everything else -> stderr.

Flags (key=value):
    model=1p3b|medium|small|large (gpt2) / test|nano|small|mixtral_tiny (moe)
    seq=1024  batch=16  steps=30  strategy=auto
    precision=bf16|mixed|fp32 (1p3b needs mixed or bf16 to fit 16 GB)
    remat_policy=nothing|dots  remat=auto|on|off
    mode=gpt2|resnet|moe|collectives|overlap
"""

import json
import sys
import time


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def timed_chain(step, state, batches):
    """Run the step over every batch (async dispatch chains on state) and
    fence once at the end; returns (state, seconds per step)."""
    import jax

    if not batches:
        raise ValueError("timed_chain needs at least one batch (steps >= 1)")
    t0 = time.perf_counter()
    metrics = None
    for b in batches:
        state, metrics = step(state, b)
    jax.block_until_ready((state, metrics))
    return state, (time.perf_counter() - t0) / len(batches)


def timed_grad(grad, q, k, v, iters):
    """Seconds per call of an already-compiled attention ``grad`` over
    ``iters`` chained calls (q feeds q, so dispatch stays async), fenced
    once at the end."""
    import jax

    t0 = time.perf_counter()
    g = None
    for _ in range(iters):
        g = grad(q, k, v)
        q = q + 0.0 * g[0]
    jax.block_until_ready(g)
    return (time.perf_counter() - t0) / iters


def require_devices(n, mode):
    """A mode that needs ``n`` devices fails where fewer are visible."""
    import jax

    have = jax.device_count()
    if have < n:
        raise SystemExit(
            f"mode={mode} needs >= {n} devices and {have} "
            f"({jax.devices()[0].device_kind}) are visible; for the CPU "
            f"sim run it under JAX_PLATFORMS=cpu "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={n}")


def parse_args():
    args = {
        "model": "1p3b", "seq": 1024, "batch": 16, "steps": 30,
        "strategy": "auto", "mode": "gpt2", "precision": "bf16",
        # remat_policy steers the model's per-layer checkpointing; remat
        # auto|on|off steers the planner's outer loss-level checkpoint
        # (off for 1p3b: the per-layer 'nothing' policy already bounds
        # activations, and an outer dots-policy checkpoint would re-save
        # every MLP hidden across the scan — 3 GB on 1.3B).
        "remat_policy": "nothing", "remat": "off",
    }
    for item in sys.argv[1:]:
        k, _, v = item.partition("=")
        args[k] = int(v) if v.isdigit() else v
    return args


def timed_lm_bench(ad, data, *, flop_params, seq, batch, steps):
    """Shared LM benchmark core: init+compile, warm, timed chain, MFU.

    ``flop_params`` is the parameter count the 6NT FLOP model uses —
    total params for dense LMs, *active* params for MoE.  Returns
    (tokens/s/chip, mfu, step_seconds, n_chips).
    """
    import jax

    import torch_automatic_distributed_neural_network_tpu as tad
    from torch_automatic_distributed_neural_network_tpu.training import (
        peak_flops_per_chip,
        transformer_step_flops,
    )

    t0 = time.perf_counter()
    state = ad.init(jax.random.key(0), data.batch(0))
    state, m = ad.step(state, data.batch(0))  # compile
    jax.block_until_ready(m)
    log(f"compile+init: {time.perf_counter()-t0:.1f}s "
        f"plan={ad.plan.strategy} mesh={tad.mesh_degrees(ad.plan.mesh)}")
    for i in range(2):  # warmup
        state, m = ad.step(state, data.batch(i))
    jax.block_until_ready(m)

    batches = [data.batch(i) for i in range(steps)]
    state, dt = timed_chain(ad.step, state, batches)
    n_chips = jax.device_count()
    tokens_per_step = batch * seq
    tps_chip = tokens_per_step / dt / n_chips
    # 6NT fwd+bwd; remat recomputes the forward -> 8NT of hardware FLOPs
    flops_mult = 8.0 / 6.0 if ad.plan.remat else 1.0
    flops = transformer_step_flops(flop_params, tokens_per_step) * flops_mult
    mfu = flops / dt / (peak_flops_per_chip() * n_chips)
    # Two distinct remat knobs (advisor round-2): the planner's OUTER
    # loss-level jax.checkpoint (ad.plan.remat) and the model's PER-LAYER
    # nn.remat policy (e.g. 'nothing' = full per-layer recompute).  Print
    # both so the artifact alone is unambiguous.
    model_cfg = getattr(getattr(ad, "model", None), "cfg", None)
    layer_policy = getattr(model_cfg, "remat_policy", None) if getattr(
        model_cfg, "remat", False) else "off"
    log(f"mean step {dt*1e3:.1f}ms  {tps_chip:,.0f} tokens/s/chip  "
        f"MFU {mfu:.1%} (remat: outer={'on' if ad.plan.remat else 'off'}, "
        f"per-layer={layer_policy or 'n/a'}; strategy={ad.plan.strategy})")
    return tps_chip, mfu, dt, n_chips


def _parse_remat(args):
    """Tri-state outer-checkpoint knob shared by every LM mode: auto
    (planner decides) | on | off."""
    return {"auto": None, "on": True, "off": False}[args["remat"]]


def bench_gpt2(args):
    import jax
    import optax

    import torch_automatic_distributed_neural_network_tpu as tad
    from torch_automatic_distributed_neural_network_tpu.data.synthetic import (
        SyntheticLM,
    )
    from torch_automatic_distributed_neural_network_tpu.models import (
        GPT2,
        gpt2_config,
    )
    from torch_automatic_distributed_neural_network_tpu.training import (
        next_token_loss,
    )

    seq, batch, steps = args["seq"], args["batch"], args["steps"]
    mcfg = gpt2_config(args["model"], max_seq_len=seq)
    log(f"bench: GPT-2 {args['model']} ({mcfg.num_params()/1e6:.0f}M params) "
        f"seq={seq} batch={batch} on {jax.device_count()} x "
        f"{jax.devices()[0].device_kind}")

    data = SyntheticLM(vocab_size=mcfg.vocab_size, seq_len=seq + 1,
                       batch_size=batch)
    ad = tad.AutoDistribute(
        GPT2(args["model"], max_seq_len=seq,
             remat_policy=args["remat_policy"]),
        optimizer=optax.adamw(1e-4),
        loss_fn=next_token_loss,
        strategy=args["strategy"],
        precision=args["precision"],
        remat=_parse_remat(args),
    )
    tps_chip, mfu, dt, n_chips = timed_lm_bench(
        ad, data, flop_params=mcfg.num_params(), seq=seq, batch=batch,
        steps=steps,
    )
    return {
        "metric": f"gpt2_{args['model']}_tokens_per_sec_per_chip",
        "value": round(tps_chip, 1),
        "unit": "tokens/s/chip",
        "vs_baseline": round(mfu / 0.40, 4),
        "extra": {
            "mfu": round(mfu, 4),
            "step_time_ms": round(dt * 1e3, 2),
            "seq": seq,
            "batch": batch,
            "params_m": round(mcfg.num_params() / 1e6),
            "n_chips": n_chips,
            "strategy": ad.plan.strategy,
            "precision": ad.precision.name,
            "remat_policy": args["remat_policy"],
        },
    }


def bench_moe(args):
    import optax

    import torch_automatic_distributed_neural_network_tpu as tad
    from torch_automatic_distributed_neural_network_tpu.data.synthetic import (
        SyntheticLM,
    )
    from torch_automatic_distributed_neural_network_tpu.models import (
        MoE,
        moe_config,
    )
    from torch_automatic_distributed_neural_network_tpu.training import (
        moe_next_token_loss,
    )

    moe_sizes = ("test", "nano", "small", "mixtral_tiny")
    size = args["model"]
    if size not in moe_sizes:
        size = "nano"
        log(f"mode=moe: model={args['model']!r} is not a MoE preset "
            f"{moe_sizes}; using {size!r}")
    seq, batch, steps = args["seq"], args["batch"], args["steps"]
    mcfg = moe_config(size, max_seq_len=seq)
    log(f"bench: MoE {size} ({mcfg.num_params()/1e6:.0f}M total / "
        f"{mcfg.active_params()/1e6:.0f}M active) seq={seq} batch={batch}")
    data = SyntheticLM(vocab_size=mcfg.vocab_size, seq_len=seq + 1,
                       batch_size=batch)
    ad = tad.AutoDistribute(
        MoE(size, max_seq_len=seq),
        optimizer=optax.adamw(1e-4),
        loss_fn=moe_next_token_loss,
        strategy=args["strategy"],
    )
    # MFU on *active* params (top-k of E experts touched per token)
    tps_chip, mfu, dt, _ = timed_lm_bench(
        ad, data, flop_params=mcfg.active_params(), seq=seq, batch=batch,
        steps=steps,
    )
    return {
        "metric": f"moe_{size}_tokens_per_sec_per_chip",
        "value": round(tps_chip, 1),
        "unit": "tokens/s/chip",
        "vs_baseline": round(mfu / 0.40, 4),
        "extra": {"mfu_active": round(mfu, 4), "strategy": ad.plan.strategy,
                  "n_experts": mcfg.n_experts, "top_k": mcfg.top_k,
                  "step_time_ms": round(dt * 1e3, 2)},
    }


def bench_resnet(args):
    import jax
    import optax

    import torch_automatic_distributed_neural_network_tpu as tad
    from torch_automatic_distributed_neural_network_tpu.data.synthetic import (
        SyntheticClassification,
    )
    from torch_automatic_distributed_neural_network_tpu.models import ResNet50
    from torch_automatic_distributed_neural_network_tpu.training import (
        softmax_xent_loss_mutable,
    )

    batch, steps = args["batch"] * 16, args["steps"]
    data = SyntheticClassification(image_shape=(224, 224, 3), num_classes=1000,
                                   batch_size=batch)
    ad = tad.AutoDistribute(
        ResNet50(num_classes=1000),
        optimizer=optax.sgd(0.1, momentum=0.9),
        loss_fn=softmax_xent_loss_mutable,
        strategy="dp",
    )
    t0 = time.perf_counter()
    state = ad.init(jax.random.key(0), data.batch(0))
    state, m = ad.step(state, data.batch(0))
    jax.block_until_ready(m)
    log(f"compile+init: {time.perf_counter()-t0:.1f}s batch={batch}")
    # Pre-stage a few distinct batches on device: this benchmark measures
    # step throughput; input-pipeline cost (host RNG + the host-to-device
    # copy of 77 MB image batches) is reported separately by the loader
    # microbenches, and real runs overlap transfers with dispatch.
    # Images stage as bf16 (the model's first op casts to bf16 anyway),
    # which halves both HBM residency and transfer time.
    import jax.numpy as jnp
    import numpy as np

    def to_bf16(b):
        return {k: v.astype(jnp.bfloat16) if v.dtype == np.float32 else v
                for k, v in b.items()}

    n_staged = 8 if batch <= 256 else 4
    t0 = time.perf_counter()
    staged = [ad.shard_batch(to_bf16(data.batch(i))) for i in range(n_staged)]
    jax.block_until_ready(staged)  # finish transfers before the timed loop
    log(f"staged {n_staged} batches: {time.perf_counter()-t0:.1f}s")
    # warm with a *staged* batch: committed device arrays compile a
    # separate executable from host-numpy args
    state, m = ad.step(state, staged[0])
    jax.block_until_ready(m)
    batches = [staged[i % len(staged)] for i in range(steps)]
    state, dt = timed_chain(ad.step, state, batches)
    n_chips = jax.device_count()
    ips_chip = batch / dt / n_chips
    # Analytic conv FLOP model (2/MAC, bwd=2x fwd) -> MFU against the same
    # 40%-MFU north star the GPT-2 metric uses (BASELINE.json:5).  Cross-
    # checked against XLA cost_analysis when the backend exposes it.
    from torch_automatic_distributed_neural_network_tpu.training import (
        peak_flops_per_chip,
    )
    cfg = ad.model.cfg
    flops = cfg.train_step_flops((224, 224), batch)
    mfu = flops / dt / (peak_flops_per_chip() * n_chips)
    # Cross-check against XLA cost_analysis only on request: the AOT
    # lower().compile() does not reuse the jit cache, so it recompiles
    # the whole ResNet step.
    xla_flops = None
    if args.get("xla_flops"):
        from torch_automatic_distributed_neural_network_tpu.utils.profiling import (
            compiled_flops,
        )
        xla_flops = (compiled_flops(ad._step_fn, state, staged[0])
                     if ad._step_fn is not None else None)
    log(f"mean step {dt*1e3:.1f}ms  {ips_chip:,.0f} images/s/chip  "
        f"MFU {mfu:.1%} (analytic {flops/1e12:.2f} TFLOP/step"
        + (f", xla cost_analysis {xla_flops/1e12:.2f}" if xla_flops else "")
        + ")")
    return {
        "metric": "resnet50_images_per_sec_per_chip",
        "value": round(ips_chip, 1),
        "unit": "images/s/chip",
        "vs_baseline": round(mfu / 0.40, 4),
        "extra": {
            "batch": batch,
            "step_time_ms": round(dt * 1e3, 2),
            "mfu": round(mfu, 4),
            "flops_per_step_analytic": flops,
            "flops_per_step_xla": xla_flops,
            "n_chips": n_chips,
        },
    }


def bench_attention(args):
    """Isolate the Pallas flash kernel's win vs plain XLA einsum attention
    (fwd+bwd) at seq 512 / 2k / 8k — the native-tier justification
    (SURVEY.md §2.3; VERDICT round-2 weak #7).

    FLOP accounting: causal attention does 0.5 * 12 * B*H*S^2*D model
    FLOPs fwd+bwd (4 S^2-matmuls fwd, 2x that bwd, half masked).  Both
    impls are credited the same useful FLOPs, so TFLOP/s compare directly
    even though the einsum path really computes the masked half too.
    """
    import jax
    import jax.numpy as jnp

    from torch_automatic_distributed_neural_network_tpu.ops.attention import (
        xla_attention,
    )
    from torch_automatic_distributed_neural_network_tpu.training import (
        peak_flops_per_chip,
    )

    on_tpu = jax.default_backend() == "tpu"
    heads, hd = 16, 128
    if args.get("sweep"):
        return _attention_block_sweep(args, heads, hd, on_tpu)
    # window=N benches the sliding-window band (seqs > N show the
    # O(S*window) grid-skip win; the xla rows band their mask too)
    window = (int(args["window"]) or None) if "window" in args else None
    rows = []
    seq_rows = ((512, 16), (2048, 4), (8192, 1))
    if window:
        seq_rows = ((2048, 4), (8192, 1), (16384, 1))
    for seq, batch in seq_rows:
        key = jax.random.key(seq)
        kq, kk, kv = jax.random.split(key, 3)
        shape = (batch, seq, heads, hd)
        q = jax.random.normal(kq, shape, jnp.bfloat16)
        k = jax.random.normal(kk, shape, jnp.bfloat16)
        v = jax.random.normal(kv, shape, jnp.bfloat16)
        # useful FLOPs: the causal half; with a window, only the band's
        # (q, k) pairs count (both impls credited identically).  The
        # no-window formula stays the historical 0.5*S^2 so canonical
        # rows remain comparable with committed captures.
        if window and window < seq:
            pairs = window * seq - window * (window - 1) // 2
            flops = 12 * batch * heads * pairs * hd
        else:
            flops = 0.5 * 12 * batch * heads * seq * seq * hd

        if window:
            # the banded reference rides chunked_attention (identical
            # numerics to xla_attention, O(block*S) memory): the plain
            # einsum's [H, S, S] fp32 scores at the 16k row would be
            # 17 GB — past a 16 GB v5e (round-5 review)
            from torch_automatic_distributed_neural_network_tpu.ops.attention import (
                chunked_attention,
            )
            impls = {"xla": lambda q_, k_, v_: chunked_attention(
                q_, k_, v_, causal=True, window=window)}
        else:
            impls = {"xla": lambda q_, k_, v_: xla_attention(
                q_, k_, v_, causal=True)}
        if on_tpu:
            from torch_automatic_distributed_neural_network_tpu.ops.flash_attention import (
                flash_attention,
            )
            impls["flash"] = lambda q_, k_, v_: flash_attention(
                q_, k_, v_, causal=True, window=window)

        row = {"seq": seq, "batch": batch,
               **({"window": window} if window else {})}
        for name, fn in impls.items():
            def loss(q_, k_, v_):
                return jnp.sum(fn(q_, k_, v_).astype(jnp.float32))

            grad = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
            jax.block_until_ready(grad(q, k, v))  # compile
            dt = timed_grad(grad, q, k, v, 20 if seq <= 2048 else 10)
            row[name + "_ms"] = round(dt * 1e3, 3)
            row[name + "_tflops"] = round(flops / dt / 1e12, 1)
            row[name + "_hw_util"] = round(flops / dt / peak_flops_per_chip(), 4)
        if "flash_ms" in row and "xla_ms" in row:
            row["speedup"] = round(row["xla_ms"] / row["flash_ms"], 2)
        rows.append(row)
        log(f"attention seq={seq}: " + "  ".join(
            f"{k}={v}" for k, v in row.items() if k not in ("seq", "batch")))

    mid = next(r for r in rows if r["seq"] == 2048)
    value = mid.get("speedup", 0.0)
    return {
        "metric": "flash_attention_speedup_vs_xla_seq2048",
        "value": value,
        "unit": "x",
        # vs_baseline: flash hardware utilization at 8k against the 40%
        # north star (long-seq is where the kernel is load-bearing)
        "vs_baseline": round(
            rows[-1].get("flash_hw_util", 0.0) / 0.40, 4),
        "extra": {"rows": rows, "heads": heads, "head_dim": hd,
                  "backend": jax.default_backend()},
    }


def _attention_block_sweep(args, heads, hd, on_tpu):
    """block_q x block_k sweep for the flash kernel on the chip across
    seq {2k, 8k, 16k}; reports per-seq winners and the hw-util ceiling
    found.  Run: ``python bench.py mode=attention sweep=1`` (TPU only —
    interpreter-mode timings are meaningless)."""
    import jax
    import jax.numpy as jnp

    from torch_automatic_distributed_neural_network_tpu.ops.flash_attention import (
        flash_attention,
    )
    from torch_automatic_distributed_neural_network_tpu.training import (
        peak_flops_per_chip,
    )

    if not on_tpu:
        raise SystemExit("mode=attention sweep=1 needs the TPU backend, "
                         f"and this process runs on {jax.default_backend()}")
    blocks = (256, 512, 1024, 2048)
    if "blocks" in args:  # e.g. blocks=384,512,640,768 — finer grids
        blocks = tuple(int(x) for x in str(args["blocks"]).split(","))
    # 1024 is the GPT-2 headline seq (off by default: the r4 sweep only
    # covered 2k+); 32768 is the single-chip long-context datapoint
    all_rows = ((1024, 8), (2048, 4), (8192, 1), (16384, 1), (32768, 1))
    want = {2048, 8192, 16384}
    if "seqs" in args:  # e.g. seqs=8192 — focus the grid on one length
        want = {int(x) for x in str(args["seqs"]).split(",")}
    unknown = want - {r[0] for r in all_rows}
    if unknown:  # a typo'd seq must not silently yield a 0.0 record
        raise SystemExit(f"seqs= not in the sweep table: {sorted(unknown)}; "
                         f"known: {sorted(r[0] for r in all_rows)}")
    seq_rows = tuple(r for r in all_rows if r[0] in want)
    rows = []
    best = {}
    for seq, batch in seq_rows:
        key = jax.random.key(seq)
        kq, kk, kv = jax.random.split(key, 3)
        shape = (batch, seq, heads, hd)
        q = jax.random.normal(kq, shape, jnp.bfloat16)
        k = jax.random.normal(kk, shape, jnp.bfloat16)
        v = jax.random.normal(kv, shape, jnp.bfloat16)
        flops = 0.5 * 12 * batch * heads * seq * seq * hd
        for bq in blocks:
            for bk in blocks:
                if bq > seq or bk > seq:
                    continue

                def loss(q_, k_, v_):
                    return jnp.sum(flash_attention(
                        q_, k_, v_, causal=True, block_q=bq, block_k=bk,
                    ).astype(jnp.float32))

                try:
                    grad = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
                    # compile (VMEM overflows raise)
                    jax.block_until_ready(grad(q, k, v))
                except Exception as e:  # noqa: BLE001 — the sweep records it
                    log(f"sweep seq={seq} bq={bq} bk={bk}: FAIL "
                        f"{str(e)[:120]}")
                    rows.append({"seq": seq, "block_q": bq, "block_k": bk,
                                 "error": str(e)[:200]})
                    continue
                dt = timed_grad(grad, q, k, v, 10 if seq <= 8192 else 5)
                util = flops / dt / peak_flops_per_chip()
                row = {"seq": seq, "block_q": bq, "block_k": bk,
                       "ms": round(dt * 1e3, 3),
                       "tflops": round(flops / dt / 1e12, 1),
                       "hw_util": round(util, 4)}
                rows.append(row)
                log(f"sweep seq={seq} bq={bq} bk={bk}: {row['ms']}ms "
                    f"{row['tflops']} TF/s util {util:.1%}")
                cur = best.get(seq)
                if cur is None or util > cur["hw_util"]:
                    best[seq] = row
    for seq, row in sorted(best.items()):
        log(f"BEST seq={seq}: block_q={row['block_q']} "
            f"block_k={row['block_k']} util {row['hw_util']:.1%}")
    top8k = best.get(8192, {})
    return {
        "metric": "flash_block_sweep_best_util_seq8192",
        "value": top8k.get("hw_util", 0.0),
        "unit": "fraction_of_peak",
        "vs_baseline": round(top8k.get("hw_util", 0.0) / 0.40, 4),
        "extra": {"best": {str(k): v for k, v in best.items()},
                  "rows": rows, "heads": heads, "head_dim": hd},
    }


def bench_decode(args):
    """Decode throughput (inference/decode.py): prefill tokens/s and
    per-token decode tokens/s at batch 1 and 8 (VERDICT r2 missing #5).

    Method: ``generate(max_new_tokens=1)`` times prefill (+1 step);
    ``generate(max_new_tokens=1+N)`` minus that isolates N cached decode
    steps.  Both executables are warmed before timing.
    """
    import jax
    import numpy as np
    import optax

    import torch_automatic_distributed_neural_network_tpu as tad
    from torch_automatic_distributed_neural_network_tpu.data.synthetic import (
        SyntheticLM,
    )
    from torch_automatic_distributed_neural_network_tpu.models import (
        GPT2,
        gpt2_config,
    )
    from torch_automatic_distributed_neural_network_tpu.training import (
        next_token_loss,
    )

    on_tpu = jax.default_backend() == "tpu"
    moe = args["model"] == "moe"
    gen_kwargs = {}
    if moe:
        # E=8 experts, expert-sharded (strategy='ep'), capacity-routed
        # decode (moe_decode='routed', inference/decode.py r4) — the
        # sharded-serving datapoint for VERDICT r3 weak #5
        from torch_automatic_distributed_neural_network_tpu.models import (
            MoE,
            moe_config,
        )
        from torch_automatic_distributed_neural_network_tpu.training import (
            moe_next_token_loss,
        )

        size = "nano" if not on_tpu else "small"
        prompt_len, new_tokens = (128, 32) if not on_tpu else (512, 256)
        mcfg = moe_config(size, max_seq_len=prompt_len + new_tokens + 1)
        strategy = "ep" if jax.device_count() >= 8 else "dp"
        log(f"bench: decode MoE {size} E={mcfg.n_experts} "
            f"({mcfg.num_params()/1e6:.0f}M total) routed strategy="
            f"{strategy} prefill={prompt_len} decode={new_tokens}")
        data = SyntheticLM(vocab_size=mcfg.vocab_size,
                           seq_len=prompt_len + 1, batch_size=8)
        ad = tad.AutoDistribute(
            MoE(size, max_seq_len=prompt_len + new_tokens + 1),
            # decode-only bench: sgd keeps init from materializing adamw
            # moments generate() never reads (2x params fp32 on the 16
            # GiB chip for the ~0.9B 'small' MoE)
            optimizer=optax.sgd(1e-4),
            loss_fn=moe_next_token_loss,
            strategy=strategy,
        )
        gen_kwargs = {"moe_decode": "routed"}
        size = f"moe_{size}"
    else:
        if on_tpu:
            size = args["model"] if args["model"] in (
                "small", "medium") else "small"
            prompt_len, new_tokens = 512, 256
        else:
            # CPU sim: the 124M model's 256-step decode scan grinds for
            # tens of minutes — smoke-test at test scale instead.
            size, prompt_len, new_tokens = "test", 128, 64
            log("mode=decode: CPU sim -> model=test prefill=128 decode=64")
        mcfg = gpt2_config(size, max_seq_len=prompt_len + new_tokens + 1)
        log(f"bench: decode GPT-2 {size} ({mcfg.num_params()/1e6:.0f}M) "
            f"prefill={prompt_len} decode={new_tokens}")
        data = SyntheticLM(vocab_size=mcfg.vocab_size,
                           seq_len=prompt_len + 1, batch_size=8)
        ad = tad.AutoDistribute(
            GPT2(size, max_seq_len=prompt_len + new_tokens + 1),
            optimizer=optax.adamw(1e-4),
            loss_fn=next_token_loss,
            strategy="dp",
        )
    state = ad.init(jax.random.key(0), data.batch(0))

    quant_arg = str(args.get("quant", ""))
    if quant_arg not in ("", "int8"):
        # an unknown spelling must not silently benchmark the fp path
        raise SystemExit(f"unknown quant={quant_arg!r}; supported: int8")
    quant = quant_arg == "int8"
    if quant:
        # weight-only int8 serving (inference/quant.py): weights stream
        # int8 through the bandwidth-bound decode steps (~4x fewer
        # bytes than the fp32 state here; ~2x vs bf16 serving weights).
        # Pre-quantize ONCE (the long-lived-serving regime this bench
        # models) and jit generate whole-program with the int8 params as
        # ARGUMENTS — timing ad.generate(quant=) instead would re-read
        # the full fp32 set for in-program quantization every call and
        # understate the decode win (round-5 review, second pass).
        import functools

        from torch_automatic_distributed_neural_network_tpu.inference import (
            generate as generate_fn,
        )
        from torch_automatic_distributed_neural_network_tpu.inference.quant import (
            quantize_for_decode,
        )

        qparams = quantize_for_decode(state.params)
        nb = sum(x.nbytes for x in jax.tree.leaves(state.params))
        nq = sum(x.nbytes for x in jax.tree.leaves(qparams))
        log(f"quant=int8: weights {nb/2**20:.0f} -> {nq/2**20:.0f} MiB "
            f"({nb/nq:.1f}x smaller)")
        size = f"{size}_int8"

        @functools.lru_cache(maxsize=4)
        def _jitted(n_new):
            return jax.jit(lambda qp, pr: generate_fn(
                ad.model, {"params": qp}, pr, max_new_tokens=n_new,
                mesh=ad.plan.mesh if jax.device_count() > 1 else None,
                **gen_kwargs))

        def run_generate(prompt, n_new):
            return _jitted(n_new)(qparams, prompt)
    else:
        def run_generate(prompt, n_new):
            return ad.generate(state, prompt, max_new_tokens=n_new,
                               **gen_kwargs)

    rows = []
    for batch in (1, 8):
        prompt = np.asarray(data.batch(0)["input_ids"])[:batch, :prompt_len]
        prompt = jax.numpy.asarray(prompt, dtype=jax.numpy.int32)

        def timed_generate(n_new, iters=3):
            # warm: trace + compile + run
            jax.block_until_ready(run_generate(prompt, n_new))
            t0 = time.perf_counter()
            for _ in range(iters):
                out = run_generate(prompt, n_new)
            jax.block_until_ready(out)
            return (time.perf_counter() - t0) / iters

        t_prefill = timed_generate(1)
        t_full = timed_generate(1 + new_tokens)
        t_decode = max(t_full - t_prefill, 1e-9)
        prefill_tps = batch * prompt_len / t_prefill
        decode_tps = batch * new_tokens / t_decode
        rows.append({
            "batch": batch,
            "prefill_ms": round(t_prefill * 1e3, 1),
            "prefill_tokens_per_s": round(prefill_tps, 1),
            "decode_tokens_per_s": round(decode_tps, 1),
            "decode_ms_per_token": round(t_decode * 1e3 / new_tokens, 3),
        })
        log(f"decode batch={batch}: prefill {prefill_tps:,.0f} tok/s "
            f"({t_prefill*1e3:.0f}ms), decode {decode_tps:,.0f} tok/s "
            f"({t_decode*1e3/new_tokens:.1f}ms/tok)")

    return {
        "metric": (f"{size}_decode_tokens_per_sec_batch8" if moe
                   else f"gpt2_{size}_decode_tokens_per_sec_batch8"),
        "value": rows[-1]["decode_tokens_per_s"],
        "unit": "tokens/s",
        "vs_baseline": 0.0,
        "extra": {"rows": rows, "prompt_len": prompt_len,
                  "new_tokens": new_tokens, "params_m":
                  round(mcfg.num_params() / 1e6),
                  "strategy": ad.plan.strategy if ad.plan else None,
                  **({"moe_decode": "routed"} if moe else {}),
                  "backend": jax.default_backend()},
    }


def bench_checkpoint(args):
    """Checkpoint save/restore wall time + step-time impact (VERDICT r2
    next #10).  The Orbax wrapper saves async (CheckpointManager enables
    it); measured here: (a) save() call latency — the device->host copy
    the train loop actually blocks on, (b) full drain (wait()), (c)
    restore, (d) step time in the shadow of an in-flight save vs
    baseline — the number that proves async saving doesn't stall steps.
    """
    import os
    import shutil
    import tempfile

    import jax
    import numpy as np
    import optax

    import torch_automatic_distributed_neural_network_tpu as tad
    from torch_automatic_distributed_neural_network_tpu.data.synthetic import (
        SyntheticLM,
    )
    from torch_automatic_distributed_neural_network_tpu.models import (
        GPT2,
        gpt2_config,
    )
    from torch_automatic_distributed_neural_network_tpu.training import (
        CheckpointManager,
        next_token_loss,
    )
    from torch_automatic_distributed_neural_network_tpu.training.checkpoint import (
        abstract_state_for,
    )

    on_tpu = jax.default_backend() == "tpu"
    if on_tpu:
        size = args["model"] if args["model"] in (
            "test", "small", "medium", "large", "1p3b") else "1p3b"
        seq, batch = args["seq"], args["batch"]
    else:
        # CPU sim: a 14.7 GiB 1.3B state would grind for hours — always
        # use the test model; the TPU run records the real 1.3B numbers.
        size, seq, batch = "test", 64, 8
        log("mode=checkpoint: CPU sim -> forcing model=test")
    mcfg = gpt2_config(size, max_seq_len=seq)
    data = SyntheticLM(vocab_size=mcfg.vocab_size, seq_len=seq + 1,
                       batch_size=batch)
    ad = tad.AutoDistribute(
        GPT2(size, max_seq_len=seq,
             remat_policy=args["remat_policy"]),
        # same remat recipe as the headline gpt2 mode: for 1p3b the
        # per-layer 'nothing' policy bounds activations; letting the
        # planner auto-add the outer dots-policy checkpoint re-saves
        # every MLP hidden across the scan and OOMs the 16G chip
        remat=_parse_remat(args),
        optimizer=optax.adamw(1e-4),
        loss_fn=next_token_loss,
        strategy=args["strategy"],
        precision=args["precision"] if on_tpu else "fp32",
    )
    state = ad.init(jax.random.key(0), data.batch(0))
    state, m = ad.step(state, data.batch(0))
    jax.block_until_ready(m)
    state_bytes = sum(
        leaf.size * leaf.dtype.itemsize
        for leaf in jax.tree.leaves(state)
        if hasattr(leaf, "size")
    )
    log(f"checkpoint bench: GPT-2 {size} state {state_bytes/2**30:.2f} GiB")

    # baseline step time (no checkpoint in flight)
    batches = [data.batch(i) for i in range(10)]
    state, dt_base = timed_chain(ad.step, state, batches)

    ckpt_dir = tempfile.mkdtemp(prefix="tadnn_ckpt_bench_")
    try:
        mngr = CheckpointManager(ckpt_dir)
        t0 = time.perf_counter()
        mngr.save(int(state.step), state)
        t_save_call = time.perf_counter() - t0
        # steps in the shadow of the in-flight async save
        state, dt_shadow = timed_chain(ad.step, state, batches)
        t0 = time.perf_counter()
        mngr.wait()
        t_drain = time.perf_counter() - t0
        # free the live training state before restoring: holding both
        # copies of a 7.3 GiB state OOMs the 16 GiB chip at restore
        state = None
        batches = None
        t0 = time.perf_counter()
        abstract = abstract_state_for(ad, jax.random.key(0), data.batch(0))
        restored = mngr.restore(abstract)
        jax.block_until_ready(restored.params)
        t_restore = time.perf_counter() - t0
        mngr.close()
    finally:
        shutil.rmtree(ckpt_dir, ignore_errors=True)

    spike = dt_shadow / dt_base if dt_base > 0 else float("inf")
    log(f"save() call {t_save_call*1e3:.0f}ms, drain {t_drain*1e3:.0f}ms, "
        f"restore {t_restore*1e3:.0f}ms; step {dt_base*1e3:.1f}ms -> "
        f"{dt_shadow*1e3:.1f}ms during save ({spike:.2f}x)")
    return {
        "metric": "checkpoint_step_time_spike_during_save",
        "value": round(spike, 3),
        "unit": "x",
        "vs_baseline": 0.0,
        "extra": {
            "model": size,
            "state_gib": round(state_bytes / 2**30, 3),
            "save_call_ms": round(t_save_call * 1e3, 1),
            "drain_ms": round(t_drain * 1e3, 1),
            "restore_ms": round(t_restore * 1e3, 1),
            "step_ms_baseline": round(dt_base * 1e3, 2),
            "step_ms_during_save": round(dt_shadow * 1e3, 2),
            "backend": jax.default_backend(),
        },
    }


def bench_memfit(args):
    """BASELINE.md row 4 — "Llama-3-8B FSDP-style shard + grad checkpoint
    trains end-to-end on v5p-64" — proved without the slice.

    AOT-compiles the REAL sharded train step from abstract shapes only
    (``AutoDistribute.compile_report``: no params, opt state, or
    activations are ever materialized) on a simulated 64-device mesh, and
    reads XLA's per-device memory analysis.  ``scan_layers`` keeps the
    HLO layer-count-independent, so compiling the 8B graph costs about
    the same as a 1-layer model.  value = per-device peak GiB;
    vs_baseline = v5p HBM budget / peak (>1 = fits).
    """
    import jax

    n = int(args.get("devices", 64))
    require_devices(n, "memfit")

    import numpy as np
    import optax

    import torch_automatic_distributed_neural_network_tpu as tad
    from torch_automatic_distributed_neural_network_tpu.models import (
        Llama,
        llama_config,
    )
    from torch_automatic_distributed_neural_network_tpu.training import (
        next_token_loss,
    )

    size = str(args.get("memfit_model", "8b"))
    seq = int(args.get("memfit_seq", 4096))
    batch = int(args.get("memfit_batch", n))
    cp = int(args.get("memfit_cp", 1))  # context-parallel degree
    hbm_gib = float(args.get("hbm_gib", 88.5))  # v5p: 95 GB = ~88.5 GiB
    # loss=blockwise folds the LM head into a seq-blockwise CE so the
    # fp32 [B,S,128k] logits pair (16.3 of r3's 17.2 GiB peak) never
    # materializes; loss=full is the plain next_token_loss baseline
    loss_kind = str(args.get("memfit_loss", "blockwise"))
    ce_block = int(args.get("memfit_ce_block", 512))
    mcfg = llama_config(size, max_seq_len=seq)
    log(f"memfit: Llama {size} ({mcfg.num_params()/1e9:.2f}B params) "
        f"seq={seq} batch={batch} fsdp={n // cp}"
        + (f" x cp={cp}" if cp > 1 else "")
        + f" loss={loss_kind} (abstract AOT compile)")
    if loss_kind == "blockwise":
        from torch_automatic_distributed_neural_network_tpu.training import (
            blockwise_next_token_loss,
        )

        loss_fn = blockwise_next_token_loss(ce_block)
    else:
        loss_fn = next_token_loss
    ad = tad.AutoDistribute(
        # per-layer full recompute (the 1.3B bench recipe) + mixed
        # precision: bf16 compute/grads/moments, fp32 master params
        Llama(size, max_seq_len=seq, remat_policy="nothing"),
        optimizer=optax.adamw(3e-4),
        loss_fn=loss_fn,
        strategy="fsdp",
        precision="mixed",
        remat=False,
        seq_parallel=cp,
    )
    sample = {"tokens": np.zeros((batch, seq + 1), np.int32)}
    t0 = time.perf_counter()
    report = ad.compile_report(jax.random.key(0), sample)
    dt = time.perf_counter() - t0
    if report is None or not report.get("per_device_peak_bytes"):
        raise SystemExit("mode=memfit: this backend exposes no memory "
                         "analysis for the compiled step")
    peak_gib = report["per_device_peak_bytes"] / 2**30
    mem = report["memory"]
    log(f"compiled in {dt:.0f}s: per-device peak {peak_gib:.2f} GiB "
        f"(state {mem.get('argument_size', 0)/2**30:.2f} GiB + temps "
        f"{mem.get('temp_size', 0)/2**30:.2f} GiB) vs {hbm_gib} GiB HBM")
    label = f"fsdp{n // cp}" + (f"_cp{cp}" if cp > 1 else "") + (
        "_blockwise_ce" if loss_kind == "blockwise" else "")
    return {
        "metric": f"llama{size}_{label}_per_device_peak",
        "value": round(peak_gib, 3),
        "unit": "GiB",
        "vs_baseline": round(hbm_gib / peak_gib, 3),
        "extra": {
            "memory": mem,
            "flops_per_step_xla": report.get("flops"),
            "params_b": round(mcfg.num_params() / 1e9, 3),
            "seq": seq, "batch": batch, "n_devices": n,
            "precision": "mixed", "remat_policy": "nothing",
            "loss": loss_kind,
            **({"ce_block": ce_block} if loss_kind == "blockwise" else {}),
            "compile_s": round(dt, 1),
            "hbm_budget_gib": hbm_gib,
            "note": ("abstract-shapes AOT compile on a CPU-sim mesh; "
                     "sizes are per-device from XLA memory_analysis of "
                     "the SPMD executable — fits iff vs_baseline > 1"),
        },
    }


def bench_pipeline(args):
    """Microbatch sweep comparing all three schedules at M=2/4/8 on
    pipe=2 and pipe=4: 'dense' (round-2 GPipe, bubble iterations compute
    on garbage), 'cond' (bubbles skip compute via per-device lax.cond),
    and '1f1b' (hand-scheduled backward, 2S-1 stash ring — pays one
    extra forward wavefront but ALSO skips backward-tick bubbles, which
    AD-GPipe cannot).

    On the CPU sim the devices share host cores, so skipped bubble FLOPs
    translate directly into wall-clock — an upper bound on the real-chip
    win, where bubbles are idle-time and 'cond' mainly saves energy/HBM
    traffic.  The bubble-iteration fraction (S-1)/(M+S-1) is the model.
    """
    import jax
    import optax

    require_devices(4, "pipeline")

    import torch_automatic_distributed_neural_network_tpu as tad
    from torch_automatic_distributed_neural_network_tpu.data.synthetic import (
        SyntheticLM,
    )
    from torch_automatic_distributed_neural_network_tpu.models import GPT2
    from torch_automatic_distributed_neural_network_tpu.parallel.pipeline import (
        bubble_fraction,
    )
    from torch_automatic_distributed_neural_network_tpu.training import (
        next_token_loss,
    )

    seq, vocab = 128, 512
    steps = min(int(args["steps"]), 10)  # 18 compiled configs dominate
    rows = []
    for stages in (2, 4):
        for M in (2, 4, 8):
            # per-device batch (batch / data_degree) must divide every M:
            # 32 covers data=4 x M=8 at stages=2
            batch = 32
            data = SyntheticLM(vocab_size=vocab, seq_len=seq + 1,
                               batch_size=batch)
            times = {}
            # interleaved needs M % S == 0 and benefits exactly when the
            # bubble matters (small M); V=2 over the 8-layer stack
            scheds = ["dense", "cond", "1f1b"]
            if M % stages == 0:
                scheds += ["interleaved", "interleaved_1f1b"]
            for sched in scheds:
                ad = tad.AutoDistribute(
                    GPT2("test", vocab_size=vocab, max_seq_len=seq,
                         n_layers=8),
                    optimizer=optax.adamw(1e-4),
                    loss_fn=next_token_loss,
                    strategy="dp",
                    pipeline_stages=stages,
                    microbatches=M,
                    pipeline_schedule=sched,
                    pipeline_virtual=2 if sched.startswith("interleaved")
                    else 1,
                )
                state = ad.step(ad.init(jax.random.key(0), data.batch(0)),
                                data.batch(0))[0]  # compile+warm
                batches = [data.batch(i) for i in range(steps)]
                state, dt = timed_chain(ad.step, state, batches)
                times[sched] = dt
            row = {
                "stages": stages, "microbatches": M,
                "dense_ms": round(times["dense"] * 1e3, 1),
                "cond_ms": round(times["cond"] * 1e3, 1),
                # 1f1b trades one extra forward wavefront for the
                # M-independent memory bound; this column records the
                # cost side of that trade honestly
                "onef_oneb_ms": round(times["1f1b"] * 1e3, 1),
                "speedup": round(times["dense"] / times["cond"], 3),
                "onef_vs_cond": round(times["1f1b"] / times["cond"], 3),
                "bubble_frac": round(bubble_fraction(stages, M), 3),
                **({
                    "interleaved_ms": round(times["interleaved"] * 1e3, 1),
                    "interleaved_vs_cond": round(
                        times["interleaved"] / times["cond"], 3),
                    "interleaved_1f1b_ms": round(
                        times["interleaved_1f1b"] * 1e3, 1),
                    "interleaved_1f1b_vs_cond": round(
                        times["interleaved_1f1b"] / times["cond"], 3),
                    "bubble_frac_v2": round(
                        (stages - 1) / (M * 2 + stages - 1), 3),
                } if "interleaved" in times else {}),
            }
            rows.append(row)
            log(f"pipe={stages} M={M}: dense {row['dense_ms']}ms "
                f"cond {row['cond_ms']}ms 1f1b {row['onef_oneb_ms']}ms"
                + (f" interleavedV2 {row['interleaved_ms']}ms"
                   f" inter1f1b {row['interleaved_1f1b_ms']}ms"
                   if "interleaved_ms" in row else "")
                + f" -> cond {row['speedup']}x, 1f1b/cond "
                f"{row['onef_vs_cond']}x (bubble {row['bubble_frac']:.0%})")

    worst = max(rows, key=lambda r: r["speedup"])
    return {
        "metric": "pipeline_cond_schedule_speedup_max",
        "value": worst["speedup"],
        "unit": "x",
        "vs_baseline": 0.0,
        "extra": {
            "rows": rows,
            "backend": jax.default_backend(),
            "note": (
                "CPU-sim: shared host cores make skipped bubble compute "
                "show up as wall-clock; on a real slice 'cond' saves "
                "energy/HBM traffic during warmup/drain instead"
            ),
        },
    }


def bench_overlap(args):
    """C4: comm/compute overlap measurement (collectives.bench_overlap).

    Needs >= 2 devices (on TPU slices set
    ``collectives.LATENCY_HIDING_XLA_FLAGS``; on the CPU sim the number
    is a methodology demo only).
    """
    import jax

    require_devices(2, "overlap")

    from torch_automatic_distributed_neural_network_tpu.parallel.collectives import (
        bench_overlap as run_overlap,
    )

    r = run_overlap()
    log(f"overlap on {r.n_devices} devices: compute {r.t_compute_s*1e3:.1f}ms "
        f"comm {r.t_comm_s*1e3:.1f}ms both {r.t_both_s*1e3:.1f}ms "
        f"-> {r.overlap_frac:.0%} of the cheaper phase hidden")
    extra = r.to_json()
    if jax.default_backend() == "cpu":
        extra["note"] = (
            "CPU-sim devices share host cores: t_both inflates from "
            "oversubscription, so the fraction is a lower bound / "
            "methodology demo; the real signal needs a multi-chip slice"
        )
    return {
        "metric": "comm_compute_overlap_frac",
        "value": round(r.overlap_frac, 4),
        "unit": "fraction",
        "vs_baseline": 0.0,
        "extra": extra,
    }


def bench_collectives(args):
    import jax

    require_devices(2, "collectives")

    from torch_automatic_distributed_neural_network_tpu.parallel.collectives import (
        bench_collective,
    )

    r = bench_collective("allreduce", size_bytes=64 * 2**20, axis="data")
    backend = jax.default_backend()
    log(f"allreduce 64MiB/rank on {r.n_devices} devices ({backend}): "
        f"bus {r.bus_bw_gbps:.1f} GB/s")
    extra = {**r.to_json(), "backend": backend}
    metric = "allreduce_bus_bandwidth"
    if backend == "cpu":
        # never let a host-shared-memory number masquerade as ICI
        metric = "allreduce_bus_bandwidth_cpu_sim"
        extra["note"] = (
            "CPU-sim: bytes move through host RAM; methodology check "
            "only — the ICI number needs a multi-chip TPU slice"
        )
    return {
        "metric": metric,
        "value": round(r.bus_bw_gbps, 2),
        "unit": "GB/s",
        "vs_baseline": 0.0,
        "extra": extra,
    }


def main():
    args = parse_args()
    from torch_automatic_distributed_neural_network_tpu.topology import (
        device_record,
        enable_compilation_cache,
    )

    enable_compilation_cache()
    fn = {"gpt2": bench_gpt2, "resnet": bench_resnet, "moe": bench_moe,
          "collectives": bench_collectives, "overlap": bench_overlap,
          "attention": bench_attention, "pipeline": bench_pipeline,
          "decode": bench_decode, "checkpoint": bench_checkpoint,
          "memfit": bench_memfit}[args["mode"]]
    result = fn(args)
    result["device"] = device_record()
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
