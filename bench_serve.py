#!/usr/bin/env python
"""Serving load-generator: the SERVE_BENCH_r*.json trajectory.

Drives `inference/serve.ServeEngine` with N concurrent seeded streams
against a tiny decoder and reports aggregate decode throughput plus the
latency distribution — the serving analog of bench.py:

- exactly ONE JSON line on stdout
  (``{"metric", "value", "unit", "vs_baseline", "extra", "device"}``);
  everything else goes to stderr;
- it runs on the device JAX gives it and says which (``device``,
  ``extra.backend``); a run that fails exits non-zero and prints no
  record;
- ``vs_baseline`` is the ratio to the committed ``SERVE_LAST_GOOD.json``
  record when that holds the same metric (the file is read, never
  written), and ``tadnn report --check`` covers the committed
  ``SERVE_BENCH_r*.json`` rounds (obs/report.check_bench).

The committed rounds were captured on the 8-device CPU sim
(``JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8``,
metric suffix ``_cpu_sim``): they are SCHEDULING numbers — occupancy,
queue time, iteration-level batching wins — not device speeds.  A run
on the chip drops the suffix.

Usage (all key=value, bench.py-style):

    python bench_serve.py [streams=24] [slots=4] [prompt_len=120]
        [max_new=4] [block_size=8] [quant_kv=0] [seed=0]
        [attention_impl=paged|dense] [prefill_chunk=8]
        [adapters=0] [adapter_rank=8] [quant_adapters=0] [speculative=0]
        [disaggregate=1] [tp=1] [prefix_cache=1] [shared_prefix=112]
        [gateway=0] [replicas=1]

``gateway=1`` drives the SAME mix through the real HTTP/SSE ingress
(inference/gateway): ``replicas=N`` engines behind the prefix-affinity
router, one blocking SSE client per stream.  The number it reports is
the HTTP/ingress overhead vs the direct-engine run on the same knobs.

r05 makes the canonical run a SHARED-PREFIX mix: every stream's prompt
opens with the same ``shared_prefix`` seeded tokens (a common system
preamble) followed by a unique per-stream suffix, and the engine runs
with the cross-request prefix cache on (``prefix_cache=1``) — later
streams match the resident preamble blocks in the radix index and
prefill only their suffix.  ``extra`` records the mix
(``shared_prefix``), the measured ``prefix`` stats (hit rate, cached
tokens, saved prefill chunks, CoW forks) and the geometry
(``prompt_len=120, shared_prefix=112, max_new=4, prefill_chunk=8``,
chosen so redundant prefill is the dominant cache-off cost).  The r05
acceptance comparison is the same argv with ``prefix_cache=0``.

r03 adds the multi-tenant knobs: ``adapters=N`` registers N random
rank-``adapter_rank`` LoRA tenants in the engine's paged adapter pool
(one jitted trace for all of them) and round-robins streams over them;
``speculative=K`` turns on K-token n-gram draft-and-verify decode.
``extra`` then records the adapter mix and the measured accept rate.

r04 makes the canonical run DISAGGREGATED (``disaggregate=1``): the
prefill worker loop runs uncapped on its own (virtual) slice, finished
KV blocks ship into decode slots through the pool, and
``extra["breakdown"]["phase"]`` records the per-slice busy seconds
(prefill-slice vs decode-slice, first step dropped as compile) plus the
serialized and overlapped wall models.  ``tp=N`` shards the KV pool,
adapter pool and paged kernel over N CPU-sim devices (non-canonical —
the sim measures scheduling, not sharded-kernel speed).

r02 adds a per-step component breakdown (``extra["breakdown"]``):
gather / attention / scatter milliseconds per decode step measured by
micro-benching the step's per-layer pieces on the engine's own pool
arrays, plus mean decode-step and prefill-chunk latency from the run's
journal.  On the paged path ``gather_ms_per_step`` is 0.0 by
construction — the fused kernel (ops/paged_attention.py) reads the
block table in-kernel and the dense view is never materialized.
"""

from __future__ import annotations

import json
import os
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
LAST_GOOD_PATH = os.path.join(REPO, "SERVE_LAST_GOOD.json")


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def parse_args():
    args = {
        "streams": 24, "slots": 4, "prompt_len": 120, "max_new": 4,
        "block_size": 8, "max_len": 128, "quant_kv": 0, "seed": 0,
        "vocab": 128, "attention_impl": "paged", "prefill_chunk": 8,
        "adapters": 0, "adapter_rank": 8, "quant_adapters": 0,
        "speculative": 0, "disaggregate": 1, "tp": 1,
        "prefix_cache": 1, "shared_prefix": 112,
        "gateway": 0, "replicas": 1,
    }
    for item in sys.argv[1:]:
        k, _, v = item.partition("=")
        args[k] = int(v) if v.lstrip("-").isdigit() else v
    return args


def _load_last_good() -> dict:
    try:
        with open(LAST_GOOD_PATH) as f:
            return json.load(f)
    except (OSError, ValueError):
        return {}


def _pct(sorted_vals, q):
    import math

    if not sorted_vals:
        return 0.0
    return sorted_vals[min(len(sorted_vals) - 1,
                           max(0, math.ceil(q * len(sorted_vals)) - 1))]


def _time_ms(fn, *xs, reps: int = 20) -> float:
    """Mean wall ms per call of an already-jitted ``fn`` (one warmup
    call pays compile outside the timed window)."""
    import jax

    jax.block_until_ready(fn(*xs))
    t0 = time.perf_counter()
    out = None
    for _ in range(reps):
        out = fn(*xs)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps * 1e3


def _component_breakdown(eng, impl: str) -> dict:
    """Micro-bench the decode step's per-layer pieces on the engine's
    own pool arrays: gather (dense view materialization), attention
    (the chosen impl's kernel), scatter (the token write).  Numbers are
    ms per WHOLE decode step (x n_layers, x2 sides where both k and v
    pay), a synthetic full-occupancy state (every slot at max context)
    so the gather cost is the worst case the paged kernel deletes."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from torch_automatic_distributed_neural_network_tpu.inference.serve \
        .kv_pool import gather_blocks, write_token
    from torch_automatic_distributed_neural_network_tpu.ops.attention \
        import xla_attention
    from torch_automatic_distributed_neural_network_tpu.ops \
        .paged_attention import paged_attention

    cfg = eng.cfg
    S, MB, bs = eng.n_slots, eng.max_blocks, eng.pool.block_size
    L = cfg.n_layers
    nb = eng.pool.num_blocks
    tables = np.zeros((S, MB), np.int32)
    for s in range(S):
        for j in range(MB):
            tables[s, j] = 1 + (s * MB + j) % (nb - 1)
    tables = jnp.asarray(tables)
    ctx = jnp.full((S,), eng.max_len - 1, jnp.int32)
    rs = np.random.RandomState(0)
    q = jnp.asarray(rs.randn(S, cfg.n_heads, cfg.head_dim), jnp.float32)
    new = jnp.asarray(rs.randn(S, cfg.kv_heads, cfg.head_dim),
                      jnp.float32)
    k0, v0 = eng.pool.kv["k"][0], eng.pool.kv["v"][0]  # layer 0's pages

    gather = jax.jit(lambda kl, t: gather_blocks(
        kl, t, cfg.dtype, cfg.kv_heads))
    t_gather = _time_ms(gather, k0, tables)
    scatter = jax.jit(lambda kl, t, p, x: write_token(kl, t, p, x))
    t_scatter = _time_ms(scatter, k0, tables, ctx, new)
    if impl == "paged":
        attn = jax.jit(lambda qq, kl, vl, t, c: paged_attention(
            qq, kl, vl, t, c, window=cfg.sliding_window))
        t_attn = _time_ms(attn, q, k0, v0, tables, ctx)
        t_gather_step = 0.0  # eliminated: the kernel reads the table
    else:
        kd, vd = gather(k0, tables), gather(v0, tables)
        key_idx = jnp.arange(kd.shape[1])[None, :]
        mask = (key_idx <= ctx[:, None])[:, None, None, :]
        attn = jax.jit(lambda qq, k_, v_: xla_attention(
            qq[:, None], k_, v_, causal=False, mask=mask))
        t_attn = _time_ms(attn, q, kd, vd)
        t_gather_step = 2 * L * t_gather
    return {
        "gather_ms_per_step": round(t_gather_step, 3),
        "gather_ms_per_call": round(t_gather, 3),  # what dense would pay
        "attention_ms_per_step": round(L * t_attn, 3),
        "scatter_ms_per_step": round(2 * L * t_scatter, 3),
    }


def run_load(args, journal) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from torch_automatic_distributed_neural_network_tpu.inference.serve \
        import ServeEngine
    from torch_automatic_distributed_neural_network_tpu.models import GPT2

    model = GPT2("test", vocab_size=int(args["vocab"]),
                 max_seq_len=int(args["max_len"]), dtype=jnp.float32,
                 remat=False)
    rs = np.random.RandomState(int(args["seed"]))
    prompt0 = rs.randint(1, int(args["vocab"]),
                         size=(1, int(args["prompt_len"])))
    variables = model.init(jax.random.key(1),
                           jnp.asarray(prompt0, jnp.int32))

    impl = str(args["attention_impl"])
    chunk = int(args["prefill_chunk"]) or None  # 0 -> single-shot
    n_adapters = int(args["adapters"])
    lora_spec = None
    if n_adapters:
        from torch_automatic_distributed_neural_network_tpu.training \
            .lora import LoraSpec

        lora_spec = LoraSpec(rank=int(args["adapter_rank"]))
    tp = int(args["tp"])
    mesh = None
    if tp > 1:
        from jax.sharding import Mesh

        devs = jax.devices()
        if len(devs) < tp:
            raise RuntimeError(
                f"tp={tp} needs {tp} devices, have {len(devs)}")
        mesh = Mesh(np.array(devs[:tp]), ("tensor",))
    eng = ServeEngine(
        model, variables,
        n_slots=int(args["slots"]),
        max_len=int(args["max_len"]),
        block_size=int(args["block_size"]),
        quant_kv=bool(int(args["quant_kv"])),
        attention_impl=impl,
        prefill_chunk=chunk,
        lora_spec=lora_spec,
        n_adapters=n_adapters + 1 if n_adapters else 8,
        quant_adapters=bool(int(args["quant_adapters"])),
        speculative=int(args["speculative"]),
        prefix_cache=bool(int(args["prefix_cache"])),
        mesh=mesh,
        disaggregate=bool(int(args["disaggregate"])),
        journal=journal,
    )
    if n_adapters:
        from torch_automatic_distributed_neural_network_tpu.inference \
            .serve import random_adapter

        for i in range(n_adapters):
            eng.register_adapter(
                f"tenant{i}",
                random_adapter(variables["params"], lora_spec,
                               seed=int(args["seed"]) + 100 + i))
    # warm every serving executable outside the timed window: two
    # throwaway requests (distinct content, so no cross-talk with the
    # load's prefix matches) run to completion, compiling the chunked
    # prefill, BOTH commit shapes (full-miss and — with the cache on,
    # where the second warm request hits the first's published blocks —
    # the hit-suffix), and the decode step.  Compile time is not a
    # serving number; the timed window below measures steady-state
    # scheduling only.
    warm_prompt = [int(t) for t in
                   rs.randint(1, int(args["vocab"]),
                              size=(int(args["prompt_len"]),))]
    for _ in range(2):
        eng.submit(warm_prompt, max_new_tokens=2, eos_id=0,
                   adapter="tenant0" if n_adapters else None)
        eng.run()
    if eng.prefix_cache is not None:
        eng.prefix_cache.clear()  # warm blocks must not crowd the pool
        eng.prefix_queries = eng.prefix_hits = 0
        eng.prefix_cached_tokens = eng.prefix_saved_chunks = 0
        eng.cow_forks = 0
    eng.finished.clear()
    warm_steps = len(journal.named("serve.step"))
    warm_chunks = len(journal.named("serve.prefill_chunk"))
    # shared-prefix mix (r05): one seeded preamble opens every prompt,
    # the tail is unique per stream — exactly the traffic shape the
    # radix index exists for.  shared_prefix=0 restores fully random
    # prompts; the knob shapes CONTENT only, so a prefix_cache=0 run
    # over the same mix is the honest baseline.
    n_shared = max(0, min(int(args["shared_prefix"]),
                          int(args["prompt_len"]) - 1))
    shared = [int(t) for t in rs.randint(1, int(args["vocab"]),
                                         size=(n_shared,))]
    for j in range(int(args["streams"])):
        suffix = rs.randint(1, int(args["vocab"]),
                            size=(int(args["prompt_len"]) - n_shared,))
        eng.submit(shared + [int(t) for t in suffix],
                   max_new_tokens=int(args["max_new"]), eos_id=0,
                   adapter=(f"tenant{j % n_adapters}"
                            if n_adapters else None))
    t0 = time.perf_counter()
    done = eng.run()
    wall = time.perf_counter() - t0

    totals = sorted((r.t_done or 0.0) - r.t_submit for r in done)
    new_tokens = sum(r.n_generated for r in done)
    # latency percentiles from the r06 request timelines: TTFT =
    # submit -> first sampled token, ITL = consecutive token-stamp
    # diffs (a speculative burst contributes zeros — tokens that
    # arrived together)
    ttfts = sorted(r.t_first_token - r.t_submit for r in done
                   if r.t_first_token is not None)
    itls = sorted(b - a for r in done
                  for a, b in zip(r.token_walls, r.token_walls[1:]))

    # per-step breakdown: journal means for the TIMED window's steps
    # (warm-phase records sliced off — they carry the compiles) plus a
    # component micro-bench on the engine's own pool arrays
    decode_ts = [r["decode_s"]
                 for r in journal.named("serve.step")[warm_steps:]
                 if r.get("decode_s")]
    chunk_ts = [r["seconds"] for r in
                journal.named("serve.prefill_chunk")[warm_chunks:]
                if r.get("seconds") is not None]
    breakdown = _component_breakdown(eng, impl)
    breakdown["decode_step_ms"] = (
        round(1e3 * sum(decode_ts) / len(decode_ts), 3)
        if decode_ts else None)
    breakdown["prefill_chunk_ms"] = (
        round(1e3 * sum(chunk_ts) / len(chunk_ts), 3)
        if chunk_ts else None)
    # per-slice phase breakdown from the timed window's serve.step
    # records: what each slice spent busy, and the wall the steps would
    # cost serialized (one chip) vs overlapped (disaggregated slices)
    step_recs = journal.named("serve.step")[warm_steps:]
    pf_busy = sum(r.get("prefill_s") or 0.0 for r in step_recs)
    dec_busy = sum(r.get("decode_s") or 0.0 for r in step_recs)
    breakdown["phase"] = {
        "prefill_slice_busy_s": round(pf_busy, 4),
        "decode_slice_busy_s": round(dec_busy, 4),
        "serialized_wall_s": round(pf_busy + dec_busy, 4),
        "overlapped_wall_model_s": round(sum(
            max(r.get("prefill_s") or 0.0, r.get("decode_s") or 0.0)
            for r in step_recs), 4),
    }
    device_kind = jax.devices()[0].device_kind
    on_cpu = jax.default_backend() == "cpu"
    metric = "serve_tokens_per_sec" + ("_cpu_sim" if on_cpu else "")
    value = new_tokens / max(wall, 1e-9)

    last = (_load_last_good().get("serve") or {}).get("result") or {}
    vs = (value / last["value"]
          if last.get("metric") == metric and last.get("value") else 1.0)
    return {
        "metric": metric,
        "value": round(value, 2),
        "unit": "tokens/s",
        "vs_baseline": round(vs, 4),
        "extra": {
            "streams": int(args["streams"]),
            "slots": int(args["slots"]),
            "prompt_len": int(args["prompt_len"]),
            "max_new": int(args["max_new"]),
            "block_size": int(args["block_size"]),
            "max_len": int(args["max_len"]),
            "quant_kv": bool(int(args["quant_kv"])),
            "attention_impl": impl,
            "prefill_chunk": chunk,
            "disaggregate": eng.disaggregate,
            "tp": tp,
            "kv_ships": eng.pool.n_transfers,
            "shipped_blocks": eng.pool.transferred_blocks,
            "shipped_bytes": eng.pool.transferred_bytes,
            "breakdown": breakdown,
            "n_requests": len(done),
            "new_tokens": new_tokens,
            "wall_s": round(wall, 4),
            "p50_ms": round(_pct(totals, 0.50) * 1e3, 2),
            "p99_ms": round(_pct(totals, 0.99) * 1e3, 2),
            "ttft_ms": ({"p50": round(_pct(ttfts, 0.50) * 1e3, 2),
                         "p99": round(_pct(ttfts, 0.99) * 1e3, 2)}
                        if ttfts else None),
            "itl_ms": ({"p50": round(_pct(itls, 0.50) * 1e3, 3),
                        "p99": round(_pct(itls, 0.99) * 1e3, 3)}
                       if itls else None),
            "mean_occupancy": (round(eng.mean_occupancy, 4)
                               if eng.mean_occupancy is not None
                               else None),
            "preemptions": eng.scheduler.n_preemptions,
            "n_adapters": n_adapters,
            "adapter_rank": (int(args["adapter_rank"])
                             if n_adapters else None),
            "quant_adapters": bool(int(args["quant_adapters"])
                                   and n_adapters),
            "adapter_hit_rate": (
                round(eng.adapter_pool.allocator.hit_rate, 4)
                if eng.adapter_pool is not None else None),
            "speculative": int(args["speculative"]),
            "spec_accept_rate": (
                round(eng.spec_accepted / eng.spec_drafted, 4)
                if eng.spec_drafted else None),
            "prefix_cache": bool(int(args["prefix_cache"])),
            "shared_prefix": n_shared,
            "prefix": ({
                "queries": eng.prefix_queries,
                "hit_requests": eng.prefix_hits,
                "cached_tokens": eng.prefix_cached_tokens,
                "hit_rate": round(
                    eng.prefix_cached_tokens
                    / max(1, len(done) * int(args["prompt_len"])), 4),
                "saved_prefill_chunks": eng.prefix_saved_chunks,
                "cow_forks": eng.cow_forks,
            } if int(args["prefix_cache"]) else None),
            "device_kind": device_kind,
            "backend": jax.default_backend(),
        },
    }


def run_gateway_load(args, journal) -> dict:
    """gateway=1: the same shared-prefix mix, but through the REAL
    HTTP/SSE path — ``replicas=N`` engines behind the prefix-affinity
    router, an asyncio ingress in a background thread, and one
    blocking SSE client per stream.  The number this mode exists for
    is the GATEWAY OVERHEAD — tokens/s and latency through HTTP vs the
    direct-engine r05 run on the same argv minus ``gateway=1`` — not a
    new headline.
    """
    import asyncio
    import threading
    from concurrent.futures import ThreadPoolExecutor

    import jax
    import jax.numpy as jnp
    import numpy as np

    from torch_automatic_distributed_neural_network_tpu.inference \
        .gateway import EngineReplica, Gateway, HttpIngress, sse_generate
    from torch_automatic_distributed_neural_network_tpu.inference.serve \
        import ServeEngine
    from torch_automatic_distributed_neural_network_tpu.models import GPT2

    model = GPT2("test", vocab_size=int(args["vocab"]),
                 max_seq_len=int(args["max_len"]), dtype=jnp.float32,
                 remat=False)
    rs = np.random.RandomState(int(args["seed"]))
    prompt0 = rs.randint(1, int(args["vocab"]),
                         size=(1, int(args["prompt_len"])))
    variables = model.init(jax.random.key(1),
                           jnp.asarray(prompt0, jnp.int32))

    def make(name: str) -> EngineReplica:
        eng = ServeEngine(
            model, variables, n_slots=int(args["slots"]),
            max_len=int(args["max_len"]),
            block_size=int(args["block_size"]),
            attention_impl=str(args["attention_impl"]),
            prefill_chunk=int(args["prefill_chunk"]) or None,
            prefix_cache=bool(int(args["prefix_cache"])),
            journal=journal)
        return EngineReplica(name, eng)

    replicas = [make(f"replica{i}")
                for i in range(int(args["replicas"]))]
    gw = Gateway(replicas, journal=journal)
    loop = asyncio.new_event_loop()
    ingress = HttpIngress(gw, port=0)

    def _serve():
        asyncio.set_event_loop(loop)
        loop.run_until_complete(ingress.start())
        loop.run_forever()

    thread = threading.Thread(target=_serve, daemon=True)
    thread.start()
    deadline = time.perf_counter() + 30
    while not ingress.port and time.perf_counter() < deadline:
        time.sleep(0.02)
    if not ingress.port:
        raise RuntimeError("ingress failed to bind")

    def call(prompt):
        return sse_generate("127.0.0.1", ingress.port, {
            "prompt": prompt, "max_new_tokens": int(args["max_new"]),
            "eos_id": 0}, timeout=300.0)

    # warm the serving executables through the full HTTP path (compile
    # time is not a gateway number)
    warm = [int(t) for t in rs.randint(1, int(args["vocab"]),
                                       size=(int(args["prompt_len"]),))]
    for _ in range(2):
        call(warm)
    for r in replicas:
        pc = r.engine.prefix_cache
        if pc is not None:
            pc.clear()

    n_shared = max(0, min(int(args["shared_prefix"]),
                          int(args["prompt_len"]) - 1))
    shared = [int(t) for t in rs.randint(1, int(args["vocab"]),
                                         size=(n_shared,))]
    prompts = []
    for _ in range(int(args["streams"])):
        suffix = rs.randint(1, int(args["vocab"]),
                            size=(int(args["prompt_len"]) - n_shared,))
        prompts.append(shared + [int(t) for t in suffix])

    t0 = time.perf_counter()
    with ThreadPoolExecutor(max_workers=len(prompts)) as pool:
        results = list(pool.map(call, prompts))
    wall = time.perf_counter() - t0

    asyncio.run_coroutine_threadsafe(ingress.stop(), loop).result(30)
    loop.call_soon_threadsafe(loop.stop)
    thread.join(timeout=30)

    new_tokens = sum(
        sum(1 for e in ev if "token" in e) for ev in results)
    totals = sorted(ev[-1]["usage"].get("total_s") or 0.0
                    for ev in results if ev and ev[-1].get("done"))
    prefix = gw.summary()
    device_kind = jax.devices()[0].device_kind
    on_cpu = jax.default_backend() == "cpu"
    metric = ("serve_gateway_tokens_per_sec"
              + ("_cpu_sim" if on_cpu else ""))
    value = new_tokens / max(wall, 1e-9)
    return {
        "metric": metric,
        "value": round(value, 2),
        "unit": "tokens/s",
        "vs_baseline": 1.0,
        "extra": {
            "gateway": {
                "http": True,
                "replicas": int(args["replicas"]),
                "router": prefix["router"],
                "prefix_hit_tokens": prefix["prefix_hit_tokens"],
                "accepted": prefix["accepted"],
                "done": prefix["done"],
            },
            "streams": int(args["streams"]),
            "slots": int(args["slots"]),
            "prompt_len": int(args["prompt_len"]),
            "max_new": int(args["max_new"]),
            "shared_prefix": n_shared,
            "prefix_cache": bool(int(args["prefix_cache"])),
            "n_requests": len(results),
            "new_tokens": new_tokens,
            "wall_s": round(wall, 4),
            "p50_ms": round(_pct(totals, 0.50) * 1e3, 2),
            "p99_ms": round(_pct(totals, 0.99) * 1e3, 2),
            "device_kind": device_kind,
            "backend": jax.default_backend(),
        },
    }


def main():
    args = parse_args()
    from torch_automatic_distributed_neural_network_tpu.obs.journal import (
        Journal,
    )
    from torch_automatic_distributed_neural_network_tpu.topology import (
        device_record,
        enable_compilation_cache,
    )

    enable_compilation_cache()
    jpath = os.environ.get("TADNN_SERVE_JOURNAL")  # None -> in-memory
    with Journal(jpath, host0_only=False,
                 meta={"tool": "bench_serve"}) as jnl:
        result = (run_gateway_load(args, jnl)
                  if int(args.get("gateway", 0))
                  else run_load(args, jnl))
    result["device"] = device_record()
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
