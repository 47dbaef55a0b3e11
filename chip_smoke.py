"""chip_smoke.py — the quickest proof that tadnn still starts on the chip.

One process, one import of JAX, which holds the chip itself.  With no
arguments it needs ONE TPU chip and drives the main path once through
the entry points a user calls, at the full published width of GPT-2
1.3B (24 layers, d_model 2048, 16 heads, vocab 50257; random weights
from ``--seed``):

1. **train** — ``AutoDistribute`` + ``Trainer`` as ``examples/train_gpt2.py``
   builds them (``SyntheticLM`` data, bf16 train state, batch 16 x seq
   1024).  Passes when every loss is finite, the last is below the first,
   and the compiled step holds the Pallas flash kernel.
2. **serve** — the same model in ``ServeEngine``: 8 streams (prompt 128,
   32 new tokens) to completion with bf16 and with int8 KV, on the paged
   kernel and next to the dense path.  Passes when every request
   finishes with its token count, the paged kernel is in the decode
   executable compiled (not interpreted), and its output is within a
   stated tolerance of ``paged_attention_reference`` on the real pool.
3. **kernels** — the kernels of a hybrid model at ITS published widths,
   which the 1.3B model does not reach: the folded paged kernel at 30 query
   heads on 30 KV heads of 128 with every slot full, and the gated delta
   rule's chunk and step kernels (30 heads, keys of 96, values of 192)
   against the token-by-token recurrence, compiled and not interpreted,
   and the chunk kernels against themselves as they were before two
   sub-chunks' systems were solved as one (the same bits: 0.0 expected);
   and the latent decode kernel at JoyAI-LLM-Flash's widths (32 heads over
   rows of 512 + 64 numbers, 24 slots of 34,816: empty, partly filled,
   every slot full) against plain ``jax.numpy``, with a call's time at each,
   and a chunk's attention over 16k keys as the one kernel against the
   plain form, with the time of both.
4. **fused** — the serving program in which a step's decode rows ride in
   a prefill chunk (``programs.chunk_and_step``), at the widths of the four
   serving configurations and a few layers of each (16 heads of 128; 48
   query heads on 8 KV heads with a ring and experts; 30 heads of 128 with
   state rows; 32 heads over latent pages with experts), against the chunk and the step as two calls on the same
   pool: the chunk's logits, the step's tokens and every pool array.
5. **cache** — where the persistent compile cache is, who placed it, and
   how many entries it held before and after.

``--chips 4`` runs, and runs only, what exists only across chips: the
same train phase over four devices under ``strategy="fsdp"`` and under
the planner's own ``auto`` choice, against the same seed and batch on
one device (the repo's parity oracle).

Every phase prints one JSON line; a phase that raises prints what failed
and the exit code is non-zero.  The LAST line is exactly
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``
with the values JAX reports.  Without a TPU the script refuses to run;
``--rehearsal`` is the one way to run it off the chip — the same phases
at the ``test`` model size, for finding wrong paths and arguments on the
CPU — and it says so on its first line.  A rehearsal is not a chip run.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import math
import os
import statistics
import sys
import time
import traceback


@dataclasses.dataclass(frozen=True)
class Sizes:
    model: str
    vocab: int
    seq: int
    batch: int
    steps: int  # Trainer steps; the first one compiles
    lr: float
    streams: int
    prompt: int
    max_new: int
    slots: int
    max_len: int
    block_size: int = 16


CHIP = Sizes(model="1p3b", vocab=50257, seq=1024, batch=16, steps=8, lr=1e-4,
             streams=8, prompt=128, max_new=32, slots=4, max_len=256)
# (a model this small needs a larger step to move in a few updates)
REHEARSAL = Sizes(model="test", vocab=512, seq=64, batch=8, steps=6, lr=1e-2,
                  streams=4, prompt=24, max_new=6, slots=2, max_len=64)

# bf16 train state, different reduction orders: per-step loss of a
# sharded run against the one-device run of the same seed and batch
# (5e-4 was measured on a v5e 2x2 for fsdp and tp_fsdp)
PARITY_RTOL = 0.01
# fp32 query, fp32 kernel arithmetic, against the fp32 reference at the
# highest matmul precision: max |kernel - ref| over max |ref|
# (2e-6 was measured on a v5e, bf16 and int8 pools)
KERNEL_RTOL = 1e-4


def emit(record: dict) -> None:
    print(json.dumps(record), flush=True)


def memory_record(devices) -> dict:
    """Per-device allocator stats (the CPU backend reports none)."""
    stats = [d.memory_stats() or {} for d in devices]
    return {"peak_bytes_in_use": [s.get("peak_bytes_in_use") for s in stats],
            "bytes_in_use": [s.get("bytes_in_use") for s in stats]}


def mesh_record(mesh) -> dict:
    """The mesh AutoDistribute built and which branch of
    ``topology.build_mesh`` gives that device order: the ICI-aware
    ``create_device_mesh`` or its row-major reshape fallback."""
    from jax.experimental import mesh_utils

    from torch_automatic_distributed_neural_network_tpu import mesh_degrees

    devices = list(mesh.devices.flat)
    try:
        mesh_utils.create_device_mesh(mesh.devices.shape, devices=devices)
        branch = "create_device_mesh"
    except (ValueError, NotImplementedError, AssertionError) as e:
        branch = f"row-major reshape ({type(e).__name__}: {e})"
    return {"degrees": {a: n for a, n in mesh_degrees(mesh).items() if n > 1},
            "device_ids": [d.id for d in devices], "build_mesh_branch": branch}


class OneBatch:
    """Step-indexed source (the Trainer's protocol) that serves one batch
    at every step.  A few steps on fresh batches move the loss by less
    than the batches differ; on one batch every update has to lower it,
    which is the sharper check that the optimizer path works."""

    step_indexed = True

    def __init__(self, batch):
        self._batch = batch

    def batch(self, step: int):
        return self._batch


def train_phase(sz: Sizes, *, label: str, devices, strategy: str, seed: int,
                on_chip: bool) -> dict:
    """GPT-2 through AutoDistribute + Trainer; returns the phase record."""
    import jax
    import optax

    import torch_automatic_distributed_neural_network_tpu as tad
    from torch_automatic_distributed_neural_network_tpu.data.synthetic import (
        SyntheticLM,
    )
    from torch_automatic_distributed_neural_network_tpu.models import GPT2
    from torch_automatic_distributed_neural_network_tpu.obs.journal import (
        Journal,
    )
    from torch_automatic_distributed_neural_network_tpu.topology import (
        device_record,
    )
    from torch_automatic_distributed_neural_network_tpu.training import (
        Trainer,
        TrainerConfig,
        next_token_loss,
    )

    data = OneBatch(SyntheticLM(vocab_size=sz.vocab, seq_len=sz.seq + 1,
                                batch_size=sz.batch, seed=seed).batch(0))
    ad = tad.AutoDistribute(
        # the 1.3B recipe: per-layer full recompute bounds the
        # activations, so the planner's outer checkpoint stays off
        GPT2(sz.model, vocab_size=sz.vocab, max_seq_len=sz.seq,
             remat_policy="nothing"),
        optimizer=optax.adamw(sz.lr),
        loss_fn=next_token_loss,
        strategy=strategy,
        precision="bf16",
        remat=False,
        devices=devices,
        export_cache=False,  # the AOT export cache stays out of the smoke
    )
    rng = jax.random.key(seed)
    t0 = time.perf_counter()
    text = ad.compiled_step_text(rng, data.batch(0))
    compile_s = time.perf_counter() - t0
    if text is None:
        raise RuntimeError("the train step did not lower and compile")
    kernels = text.count("tpu_custom_call")
    if on_chip and not kernels:
        raise RuntimeError(
            "no tpu_custom_call in the compiled train step: attention "
            "dispatched to the einsum path, not the Pallas flash kernel")

    losses: list[float] = []
    stamps: list[float] = []

    def on_step(step, state, metrics):
        losses.append(float(jax.block_until_ready(metrics["loss"])))
        stamps.append(time.perf_counter())

    journal = Journal(None, host0_only=False)
    trainer = Trainer(
        ad, TrainerConfig(steps=sz.steps, log_every=1), callbacks=[on_step],
        items_per_step=sz.batch * sz.seq, journal=journal)
    t0 = time.perf_counter()
    state = trainer.fit(data, rng=rng)
    fit_s = time.perf_counter() - t0
    step_s = [b - a for a, b in zip(stamps, stamps[1:])]

    if len(losses) != sz.steps or not all(math.isfinite(x) for x in losses):
        raise RuntimeError(f"losses not finite or missing: {losses}")
    if not losses[-1] < losses[0]:
        raise RuntimeError(f"loss did not fall: {losses}")

    # where one large parameter lives: code that has only ever seen one
    # real chip may put everything on the first
    big = max(jax.tree.leaves(state.params), key=lambda x: x.size)
    shards = [{"device": s.device.id, "shape": list(s.data.shape)}
              for s in big.addressable_shards]
    skipped = journal.named("lint.skipped")
    record = {
        "phase": label, "device": device_record(),
        "n_devices": len(devices), "strategy": ad.plan.strategy,
        "remat": bool(ad.plan.remat), "mesh": mesh_record(ad.plan.mesh),
        "model": f"gpt2-{sz.model}", "vocab": sz.vocab, "seq": sz.seq,
        "batch": sz.batch, "precision": ad.precision.name,
        "data": "SyntheticLM(seed) batch 0, at every step",
        "steps": sz.steps, "losses": [round(x, 4) for x in losses],
        "compile_s": round(compile_s, 2),
        "first_step_s": round(stamps[0] - t0, 2), "fit_s": round(fit_s, 2),
        "step_s_median": round(statistics.median(step_s), 4),
        "custom_calls_in_step": kernels,
        "preflight_skipped": ([e.get("error") for e in skipped] or False),
        "largest_param": {"shape": list(big.shape), "shards": shards},
        "memory": memory_record(devices),
    }
    del state, trainer, ad, big
    gc.collect()
    return record


def kernel_vs_reference(eng, sz: Sizes, seed: int, on_chip: bool) -> dict:
    """The paged kernel against ``paged_attention_reference`` on layer 0
    of the pool the engine just served from: a numeric comparison (token
    equality is the wrong test for a bf16 path)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from torch_automatic_distributed_neural_network_tpu.ops import (
        paged_attention as pa,
    )

    if on_chip and pa._default_interpret():
        raise RuntimeError("the paged kernel would run interpreted on a TPU")
    cfg = eng.cfg
    S, MB, nb = eng.n_slots, eng.max_blocks, eng.pool.num_blocks
    k0, v0 = eng.pool.kv["k"][0], eng.pool.kv["v"][0]  # layer 0's pages
    tables = jnp.asarray(
        1 + np.arange(S * MB).reshape(S, MB) % (nb - 1), jnp.int32)
    ctx = jnp.full((S,), sz.prompt + sz.max_new - 1, jnp.int32)
    q = jax.random.normal(jax.random.key(seed + 1),
                          (S, cfg.n_heads, cfg.head_dim), jnp.float32)
    # on the chip the kernel is asked for compiled, not left to the default
    out = jax.jit(lambda *a: pa.paged_attention(
        *a, window=cfg.sliding_window,
        interpret=False if on_chip else None))(q, k0, v0, tables, ctx)
    with jax.default_matmul_precision("highest"):
        ref = jax.jit(lambda *a: pa.paged_attention_reference(
            *a, window=cfg.sliding_window))(q, k0, v0, tables, ctx)
    err = float(jnp.max(jnp.abs(out - ref)))
    scale = float(jnp.max(jnp.abs(ref)))
    if not (scale > 0 and math.isfinite(err) and err <= KERNEL_RTOL * scale):
        raise RuntimeError(
            f"paged kernel vs reference: max abs err {err:.3e} against "
            f"max |ref| {scale:.3e} exceeds rtol {KERNEL_RTOL}")
    return {"kernel_vs_reference_max_abs": err, "reference_max_abs": scale,
            "kernel_rtol": KERNEL_RTOL}


def serve_phase(sz: Sizes, *, seed: int, on_chip: bool) -> None:
    """The model in ServeEngine: paged (the default) next to dense, with
    bf16 and int8 KV.  Emits one record per engine."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from torch_automatic_distributed_neural_network_tpu.inference.serve import (
        ServeEngine,
    )
    from torch_automatic_distributed_neural_network_tpu.models import GPT2
    from torch_automatic_distributed_neural_network_tpu.obs.journal import (
        Journal,
    )
    from torch_automatic_distributed_neural_network_tpu.topology import (
        device_record,
    )

    device = jax.devices()[0]
    model = GPT2(sz.model, vocab_size=sz.vocab, max_seq_len=sz.max_len,
                 remat=False)
    rs = np.random.RandomState(seed)
    prompts = rs.randint(1, sz.vocab, size=(sz.streams + 1, sz.prompt))
    variables = jax.jit(model.init)(
        jax.random.key(seed), jnp.asarray(prompts[:1], jnp.int32))
    params_dtype = str(jax.tree.leaves(variables)[0].dtype)

    dense_tokens: dict[str, list] = {}
    for kv in ("bf16", "int8"):
        for impl in ("dense", "paged"):
            journal = Journal(None, host0_only=False)
            eng = ServeEngine(
                model, variables, n_slots=sz.slots, max_len=sz.max_len,
                block_size=sz.block_size, quant_kv=(kv == "int8"),
                attention_impl=impl, journal=journal, export_cache=False)
            # a throwaway request compiles the prefill and decode traces
            t0 = time.perf_counter()
            eng.submit([int(t) for t in prompts[-1]], max_new_tokens=2)
            eng.run()
            warm_s = time.perf_counter() - t0
            eng.finished.clear()
            warm_steps = len(journal.named("serve.step"))
            for p in prompts[:sz.streams]:
                eng.submit([int(t) for t in p], max_new_tokens=sz.max_new)
            t0 = time.perf_counter()
            done = eng.run()
            run_s = time.perf_counter() - t0

            counts = sorted(r.n_generated for r in done)
            if len(done) != sz.streams or counts != [sz.max_new] * sz.streams:
                raise RuntimeError(
                    f"serve {impl}/{kv}: {len(done)} of {sz.streams} "
                    f"requests finished, token counts {counts}")
            tokens = [r.out_tokens for r in sorted(done, key=lambda r: r.rid)]
            decode_s = [e["decode_s"]
                        for e in journal.named("serve.step")[warm_steps:]
                        if e.get("decode_s")]
            record = {
                "phase": f"serve.{impl}.{kv}", "device": device_record(),
                "model": f"gpt2-{sz.model}", "params_dtype": params_dtype,
                "attention_impl": eng.attention_impl, "kv": kv,
                "streams": sz.streams, "slots": sz.slots,
                "prompt_len": sz.prompt, "max_new": sz.max_new,
                "n_finished": len(done),
                "tokens_generated": sum(counts),
                "warm_s": round(warm_s, 2), "run_s": round(run_s, 2),
                "decode_step_s_median": round(
                    statistics.median(decode_s), 5),
                "memory": memory_record([device]),
            }
            if impl == "dense":
                dense_tokens[kv] = tokens
            else:
                kernels = eng.compiled_decode_text().count("tpu_custom_call")
                if on_chip and not kernels:
                    raise RuntimeError(
                        "no tpu_custom_call in the compiled decode step: "
                        "the paged kernel did not run compiled")
                record["custom_calls_in_decode_step"] = kernels
                record.update(kernel_vs_reference(eng, sz, seed, on_chip))
                # informational: bf16 paths may part ways token by token
                record["streams_agreeing_with_dense"] = sum(
                    a == b for a, b in zip(tokens, dense_tokens[kv]))
            emit(record)
            del eng
            gc.collect()


def delta_rule_float64(q, k, v, g, beta, state):
    """``ops.gated_delta.gated_delta_recurrent`` on the host in float64: the
    oracle of the chunk kernels' checks.  The same lines in float32 ON THE
    CHIP are not one: a token's state is the last one's times ``exp(g)``,
    512 roundings of the chip's exponential compounded, 1.1e-4 to 2.5e-4 of
    the largest entry from this, where the kernels (ONE exponential of
    summed log-decays) are 3e-6 to 4e-6 (``PERF.md`` section 6, PR 48)."""
    import numpy as np

    q, k, v, g, beta, S = (np.asarray(x, np.float64)
                           for x in (q, k, v, g, beta, state))
    o = np.empty(v.shape)
    for t in range(q.shape[0]):
        a = np.exp(g[t])  # a head's [H], or a key channel's [H, d_k]
        S = a.reshape(*a.shape, *(1,) * (3 - a.ndim)) * S
        u = beta[t][:, None] * (v[t] - np.einsum("hkv,hk->hv", S, k[t]))
        S = S + k[t][:, :, None] * u[:, None, :]
        o[t] = np.einsum("hkv,hk->hv", S, q[t])
    return o, S


def parents_unit_lower_inverse(A, dot, block=None):
    """``ops.gated_delta._unit_lower_inverse`` as it was before PR 48: ``X``
    starts as the identity and EVERY round is two products."""
    import jax
    import jax.numpy as jnp

    n = A.shape[-1]
    r = jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
    c = jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
    X = (r == c).astype(jnp.float32)
    for bit in range(math.ceil(math.log2(block or n))):
        quarter = ((r >> (bit + 1)) == (c >> (bit + 1))) & (
            (r & (1 << bit)) != 0) & ((c & (1 << bit)) == 0)
        X = X - dot(dot(X, jnp.where(quarter, A, 0.0)), X)
    return X


def same_as_parent(rec: dict, name: str, entry, args, interpret: bool) -> None:
    """A chunk kernel's output and state against the kernel as it was before
    PR 48 solved two sub-chunks' systems as one: a group of ONE sub-chunk
    (every solve alone, every product behind it 64 rows deep; the group
    only ever decided what is scheduled side by side) and the parent's lines
    for the solve.  The change rests on the two being the same bits: the
    differences are recorded, 0.0 expected, and past 1e-6 of the largest
    entry the phase fails."""
    import jax
    import jax.numpy as jnp

    from torch_automatic_distributed_neural_network_tpu.ops import (
        gated_delta as gd,
    )

    run = lambda: jax.jit(lambda *a: entry(  # noqa: E731 — traced anew
        *a, interpret=interpret))(*args)
    new = run()
    keep = gd.CHUNK_GROUP, gd._unit_lower_inverse
    gd.CHUNK_GROUP, gd._unit_lower_inverse = 1, parents_unit_lower_inverse
    try:
        old = run()
    finally:
        gd.CHUNK_GROUP, gd._unit_lower_inverse = keep
    for what, a, b in zip(("out", "state"), new, old):
        diff = float(jnp.max(jnp.abs(a - b)))
        rec[f"{name}_{what}_vs_parent_max_abs_diff"] = diff
        if not diff <= 1e-6 * float(jnp.max(jnp.abs(b))):
            raise RuntimeError(f"{name} {what}: {diff:.3e} from the kernel "
                               "before PR 48, which it should equal")


def hybrid_kernels_phase(*, seed: int, on_chip: bool) -> dict:
    """The kernels a hybrid model adds, at Olmo-Hybrid-7B's widths on the
    chip (a tenth of them in a rehearsal), float32 in and against float32
    oracles at the highest matmul precision."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from torch_automatic_distributed_neural_network_tpu.ops import (
        gated_delta as gd,
        paged_attention as pa,
    )

    H, dk, dv, hd, C, S = (30, 96, 192, 128, 512, 8) if on_chip else (
        3, 16, 24, 16, 40, 3)
    interpret = not on_chip
    keys = iter(jax.random.split(jax.random.key(seed + 7), 16))
    rec = {"phase": "kernels", "heads": H}

    def close(name, got, want, rtol=KERNEL_RTOL):
        err = float(jnp.max(jnp.abs(got - want)))
        scale = float(jnp.max(jnp.abs(want)))
        rec[name + "_max_abs_err"], rec[name + "_max_abs"] = err, scale
        if not (scale > 0 and math.isfinite(err) and err <= rtol * scale):
            raise RuntimeError(f"{name}: max abs err {err:.3e} against max "
                               f"|ref| {scale:.3e} exceeds rtol {rtol}")

    # 1. the folded paged kernel, 30 on 30 heads (a folded row of 3,840),
    # every slot full: the work list is as long as its arrays
    MB, bs = 64, 16
    pool = lambda: (0.5 * jax.random.normal(  # noqa: E731
        next(keys), (S * MB + 1, bs, H * hd), jnp.float32)).astype(
            jnp.bfloat16)
    k0, v0 = pool(), pool()
    tables = jnp.asarray(1 + np.arange(S * MB).reshape(S, MB), jnp.int32)
    q = jax.random.normal(next(keys), (S, H, hd), jnp.float32)
    for name, ctx in (("paged_30x128_full", jnp.full((S,), MB * bs - 1)),
                      ("paged_30x128_mixed", jnp.arange(S) * 131 % (MB * bs))):
        ctx = ctx.astype(jnp.int32)
        out = jax.jit(lambda *a: pa.paged_attention(
            *a, interpret=interpret))(q, k0, v0, tables, ctx)
        with jax.default_matmul_precision("highest"):
            ref = jax.jit(pa.paged_attention_reference)(q, k0, v0, tables, ctx)
        close(name, out, ref)
    # 2. the chunk kernel: a chunk of 512 from a state, decays as the
    # family initialises them, beta up to 2
    qk = lambda: gd.l2norm(jax.random.normal(  # noqa: E731
        next(keys), (C, H, dk), jnp.float32))
    q, k = qk() * dk ** -0.5, qk()
    v = jax.random.normal(next(keys), (C, H, dv), jnp.float32)
    beta = 2.0 * jax.nn.sigmoid(jax.random.normal(next(keys), (C, H)))
    A = jax.random.uniform(next(keys), (H,), minval=1e-3, maxval=16.0)
    g = -A * jnp.exp(jax.random.uniform(
        next(keys), (C, H), minval=math.log(1e-3), maxval=math.log(0.1)))
    state = jax.random.normal(next(keys), (H, dk, dv), jnp.float32)
    o_ref, s_ref = delta_rule_float64(q, k, v, g, beta, state)
    o, s1 = jax.jit(lambda *a: gd.gated_delta_chunk_pallas(
        *a, interpret=interpret))(q, k, v, g, beta, state)
    close("gdn_chunk_out", o, o_ref)
    close("gdn_chunk_state", s1, s_ref)
    lo = lambda x: x.astype(jnp.bfloat16)  # noqa: E731 — serving's operands
    o, s1 = jax.jit(lambda *a: gd.gated_delta_chunk_pallas(
        *a, interpret=interpret))(lo(q), lo(k), lo(v), g, beta, state)
    close("gdn_chunk_bf16_out", o, o_ref, rtol=0.05)
    same_as_parent(rec, "gdn_chunk", gd.gated_delta_chunk_pallas,
                   (q, k, v, g, beta, state), interpret)
    same_as_parent(rec, "gdn_chunk_bf16", gd.gated_delta_chunk_pallas,
                   (lo(q), lo(k), lo(v), g, beta, state), interpret)
    # 3. the step kernel: S slots over rows of a pool, in place; two slots
    # share the null row, one row is nobody's
    rows = jnp.asarray([(r + 2) % (S + 1) if r < S - 2 else 0
                        for r in range(S)], jnp.int32)
    pool0 = jax.random.normal(next(keys), (S + 2, H, dk, dv), jnp.float32)
    idle = (rows == 0)[:, None]  # as the decode program masks them
    args = (q[:S], k[:S], v[:S], jnp.where(idle, 0.0, g[:S]),
            jnp.where(idle, 0.0, beta[:S]))
    o_ref, p_ref = jax.jit(gd.gated_delta_step_xla)(*args, pool0, rows)
    o, p1 = jax.jit(lambda *a: gd.gated_delta_step_pallas(
        *a, interpret=interpret), donate_argnums=(5,))(
            *args, pool0 + 0.0, rows)
    live = np.asarray(rows) > 0
    close("gdn_step_out", o[live], o_ref[live])
    close("gdn_step_state", p1[rows[live]], p_ref[rows[live]])
    for r in (0, S + 1):  # the null row and a row no slot has
        if not bool(jnp.array_equal(p1[r], pool0[r])):
            raise RuntimeError(f"gdn step: row {r} was written")
    kda_kernel_checks(rec, close, next(keys), on_chip)
    scan_kernel_checks(rec, close, next(keys), on_chip)
    latent_kernel_checks(rec, close, next(keys), on_chip)
    return rec


def kda_kernel_checks(rec: dict, close, key, on_chip: bool) -> None:
    """The kernels of a delta rule whose decay is a vector a head, at
    Kimi-Linear's widths on the chip (32 heads of 128 and 128; a tenth of
    them in a rehearsal): ``tadnn_kda_chunk`` over a chunk of 512 from a
    state, float32 and serving's bfloat16 operands, and ``tadnn_kda_step``
    over the slots' rows of a pool in place, against the token-by-token
    recurrence; decays a channel as the family initialises them, with one
    channel that forgets in a token beside one that never does."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from torch_automatic_distributed_neural_network_tpu.ops import (
        gated_delta as gd,
    )

    H, dk, dv, C, S = (32, 128, 128, 512, 8) if on_chip else (3, 16, 24, 40, 3)
    interpret = not on_chip
    keys = iter(jax.random.split(key, 8))
    qk = lambda: gd.l2norm(jax.random.normal(  # noqa: E731
        next(keys), (C, H, dk), jnp.float32))
    q, k = qk() * dk ** -0.5, qk()
    v = jax.random.normal(next(keys), (C, H, dv), jnp.float32)
    beta = jax.nn.sigmoid(jax.random.normal(next(keys), (C, H)))
    A = jax.random.uniform(next(keys), (H, 1), minval=1e-3, maxval=16.0)
    g = -A * jnp.exp(jax.random.uniform(
        next(keys), (C, H, dk), minval=math.log(1e-3), maxval=math.log(0.1)))
    g = g.at[:, :, 0].set(-60.0).at[:, :, 1].set(0.0)
    state = jax.random.normal(next(keys), (H, dk, dv), jnp.float32)
    o_ref, s_ref = delta_rule_float64(q, k, v, g, beta, state)
    chunk = jax.jit(lambda *a: gd.kda_chunk_pallas(*a, interpret=interpret))
    o, s1 = chunk(q, k, v, g, beta, state)
    close("kda_chunk_out", o, o_ref)
    close("kda_chunk_state", s1, s_ref)
    lo = lambda x: x.astype(jnp.bfloat16)  # noqa: E731 — serving's operands
    o, s1 = chunk(lo(q), lo(k), lo(v), g, beta, state)
    close("kda_chunk_bf16_out", o, o_ref, rtol=0.05)
    same_as_parent(rec, "kda_chunk", gd.kda_chunk_pallas,
                   (q, k, v, g, beta, state), interpret)
    same_as_parent(rec, "kda_chunk_bf16", gd.kda_chunk_pallas,
                   (lo(q), lo(k), lo(v), g, beta, state), interpret)
    rows = jnp.asarray([(r + 2) % (S + 1) if r < S - 2 else 0
                        for r in range(S)], jnp.int32)
    pool0 = jax.random.normal(next(keys), (S + 2, H, dk, dv), jnp.float32)
    idle = rows == 0  # as the decode program masks them
    args = (q[:S], k[:S], v[:S], jnp.where(idle[:, None, None], 0.0, g[:S]),
            jnp.where(idle[:, None], 0.0, beta[:S]))
    o_ref, p_ref = jax.jit(gd.kda_step_xla)(*args, pool0, rows)
    o, p1 = jax.jit(lambda *a: gd.kda_step_pallas(
        *a, interpret=interpret), donate_argnums=(5,))(
            *args, pool0 + 0.0, rows)
    live = np.asarray(rows) > 0
    close("kda_step_out", o[live], o_ref[live])
    close("kda_step_state", p1[rows[live]], p_ref[rows[live]])
    for r in (0, S + 1):  # the null row and a row no slot has
        if not bool(jnp.array_equal(p1[r], pool0[r])):
            raise RuntimeError(f"kda step: row {r} was written")
    if bool(jnp.any(o[~live])):  # the kernel walks the live slots alone
        raise RuntimeError("kda step: an idle slot's output is not zero")


def scan_kernel_checks(rec: dict, close, key, on_chip: bool) -> None:
    """What a decoder-hybrid-decoder adds, at Phi-4-mini-flash's widths on
    the chip (5,120 channels of 16 state numbers, 64 slots; 40 query heads
    on 20 KV heads of 64 in pages of 64 tokens; a fraction of them in a
    rehearsal): ``tadnn_ssm_chunk`` over a chunk of 512 from a state and
    ``tadnn_ssm_step`` over the slots' rows of a pool in place, against the
    token-by-token recurrence, steps and rates as the family initialises
    them; and differential attention's decode (the folded kernel at another
    wiring: a query head on ITS key head, over the pair's two value heads)
    against plain ``jax.numpy``, full and over a window of 512.  On the chip
    also a call's time of each."""
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from torch_automatic_distributed_neural_network_tpu.ops import (
        paged_attention as pa,
        ssm,
    )
    from torch_automatic_distributed_neural_network_tpu.ops.attention import (
        diff_heads,
    )

    n, N, C, S = (5120, 16, 512, 64) if on_chip else (256, 8, 40, 3)
    interpret = not on_chip
    keys = iter(jax.random.split(key, 16))

    def timed(name, fn, *args):
        if not on_chip:
            return
        jax.block_until_ready(fn(*args))
        t0 = time.perf_counter()
        for _ in range(20):
            out = fn(*args)
        jax.block_until_ready(out)
        rec[name + "_call_ms"] = 1e3 * (time.perf_counter() - t0) / 20

    c = jax.nn.silu(jax.random.normal(next(keys), (C, n), jnp.float32))
    delta = jnp.exp(jax.random.uniform(
        next(keys), (C, n), minval=math.log(1e-3), maxval=math.log(0.1)))
    A = -jax.random.uniform(next(keys), (N, n), minval=1e-3, maxval=16.0)
    B, Cm = (jax.random.normal(next(keys), (C, N), jnp.float32)
             for _ in range(2))
    D = jax.random.normal(next(keys), (n,), jnp.float32)
    h0 = jax.random.normal(next(keys), (N, n), jnp.float32)
    y_ref, h_ref = jax.jit(ssm.ssm_recurrent)(c, delta, A, B, Cm, D, h0)
    chunk = jax.jit(lambda *a: ssm.ssm_chunk_pallas(*a, interpret=interpret))
    y, h1 = chunk(c, delta, A, B, Cm, D, h0)
    close("ssm_chunk_out", y, y_ref)
    close("ssm_chunk_state", h1, h_ref)
    timed("ssm_chunk", chunk, c, delta, A, B, Cm, D, h0)
    rows = jnp.asarray([(r + 2) % (S + 1) if r < S - 2 else 0
                        for r in range(S)], jnp.int32)
    pool0 = jax.random.normal(next(keys), (S + 2, N, n), jnp.float32)
    idle = (rows == 0)[:, None]  # as the decode program masks them
    args = (c[:S], jnp.where(idle, 0.0, delta[:S]), A, B[:S], Cm[:S], D)
    y_ref, p_ref = jax.jit(ssm.ssm_step_xla)(*args, pool0, rows)
    step = jax.jit(lambda *a: ssm.ssm_step_pallas(*a, interpret=interpret),
                   donate_argnums=(6,))
    y, p1 = step(*args, pool0 + 0.0, rows)
    live = np.asarray(rows) > 0
    close("ssm_step_out", y[live], y_ref[live])
    close("ssm_step_state", p1[rows[live]], p_ref[rows[live]])
    for r in (0, S + 1):  # the null row and a row no slot has
        if not bool(jnp.array_equal(p1[r], pool0[r])):
            raise RuntimeError(f"ssm step: row {r} was written")
    if on_chip:  # (not donated: a call's time with the pool where it lies)
        timed("ssm_step", jax.jit(lambda *a: ssm.ssm_step_pallas(*a)[0]),
              *args, pool0, rows)

    H, KV, hd, bs, MB, Sd = (40, 20, 64, 64, 48, 8) if on_chip else (
        8, 4, 16, 4, 10, 3)
    pages = lambda: (0.5 * jax.random.normal(  # noqa: E731
        next(keys), (Sd * MB + 1, bs, KV * hd), jnp.float32)).astype(
            jnp.bfloat16)
    k0, v0 = pages(), pages()
    tables = jnp.asarray(1 + np.arange(Sd * MB).reshape(Sd, MB), jnp.int32)
    q = jax.random.normal(next(keys), (Sd, H, hd), jnp.float32)
    ctx = (jnp.arange(Sd) * 977 % (MB * bs)).astype(jnp.int32).at[0].set(
        MB * bs - 1)
    key_of, values_of = diff_heads(H, KV)

    def plain(q, k0, v0, tables, ctx, window):
        heads = lambda p: p[tables].astype(jnp.float32).reshape(  # noqa: E731
            Sd, MB * bs, KV, hd)
        kd, vd = heads(k0), heads(v0)
        s = jnp.einsum("shd,sthd->sht", q, kd[:, :, key_of]) / math.sqrt(hd)
        pos = jnp.arange(MB * bs)[None, None, :]
        ok = pos <= ctx[:, None, None]
        if window is not None:
            ok &= pos > ctx[:, None, None] - window
        w = jax.nn.softmax(jnp.where(ok, s, -jnp.inf), axis=-1)
        return jnp.einsum(
            "sht,sthiv->shiv", w, vd[:, :, values_of]).reshape(Sd, H, 2 * hd)

    for name, window in (("diff_decode_full", None),
                         ("diff_decode_window", 8 * bs)):
        run = jax.jit(lambda *a, w=window: pa.paged_attention(
            *a, window=w, diff=True, interpret=interpret))
        out = run(q, k0, v0, tables, ctx)
        with jax.default_matmul_precision("highest"):
            ref = jax.jit(lambda *a, w=window: plain(*a, w))(
                q, k0, v0, tables, ctx)
        close(name, out, ref)
        timed(name, run, q.astype(jnp.bfloat16), k0, v0, tables, ctx)


def latent_kernel_checks(rec: dict, close, key, on_chip: bool) -> None:
    """The latent decode kernel at JoyAI-LLM-Flash's widths on the chip (32
    heads over rows of 512 + 64 numbers stored in 640 lanes, 24 slots of
    34,816 in pages of 64): every slot empty, partly filled, and every slot
    at ``max_len`` (the work list as long as its arrays), float32 queries
    against plain ``jax.numpy``; then, on the chip, a call's time at each
    (serving's bfloat16 queries, 20 calls), and a chunk's attention over a
    16k context in the expanded form the program runs (block by block in
    ``jax.numpy``, then as the one kernel a chip runs, one against the
    other) and in the absorbed form it does not (PERF.md section 6 says
    which and why)."""
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from torch_automatic_distributed_neural_network_tpu.inference.serve import (
        programs,
    )
    from torch_automatic_distributed_neural_network_tpu.ops import (
        paged_attention as pa,
    )

    H, r, rot, n, S, MB, bs = (32, 512, 64, 128, 24, 544, 64) if on_chip \
        else (4, 16, 4, 8, 3, 24, 4)
    lanes, scale = -(-(r + rot) // 128) * 128, (n + rot) ** -0.5
    keys = iter(jax.random.split(key, 8))
    pool = jnp.pad(
        (0.5 * jax.random.normal(next(keys), (S * MB + 1, bs, r + rot),
                                 jnp.float32)).astype(jnp.bfloat16),
        ((0, 0), (0, 0), (0, lanes - r - rot)))
    none = jnp.zeros((0,), pool.dtype)
    tables = jnp.asarray(1 + np.arange(S * MB).reshape(S, MB), jnp.int32)
    q = jax.random.normal(next(keys), (S, H, r + rot), jnp.float32)
    kernel = jax.jit(lambda q, pool, t, c: pa.paged_attention(
        q, pool, none, t, c, scale=scale, value_dim=r,
        interpret=not on_chip))
    dense = jax.jit(lambda q, pool, t, c: pa.latent_attention_reference(
        q[:, None], pool[t].reshape(S, MB * bs, lanes), c, scale=scale,
        value_dim=r)[:, 0])
    cases = (("latent_empty", jnp.zeros((S,), jnp.int32)),
             ("latent_mixed", (jnp.arange(S) * 1531 % (MB * bs)).astype(
                 jnp.int32)),
             ("latent_full", jnp.full((S,), MB * bs - 1, jnp.int32)))
    for name, ctx in cases:
        with jax.default_matmul_precision("highest"):
            want = dense(q, pool, tables, ctx)
        close(name, kernel(q, pool, tables, ctx), want)
    if not on_chip:
        return
    lo = q.astype(jnp.bfloat16)
    for name, ctx in cases:
        jax.block_until_ready(kernel(lo, pool, tables, ctx))
        t0 = time.perf_counter()
        for _ in range(20):
            out = kernel(lo, pool, tables, ctx)
        jax.block_until_ready(out)
        rec[name + "_us_a_call"] = 1e6 * (time.perf_counter() - t0) / 20
        rec[name + "_keys"] = int(jnp.sum(ctx + 1))
    # a chunk of 512 queries at positions 15,872..16,383 of slot 0
    C, pos0 = 512, 16384 - 512
    w_uk, w_uv = (0.02 * jax.random.normal(next(keys), (r, H, n))).astype(
        jnp.bfloat16), (0.02 * jax.random.normal(
            next(keys), (r, H, n))).astype(jnp.bfloat16)
    q_nope = jax.random.normal(next(keys), (C, H, n)).astype(jnp.bfloat16)
    q_rope = jax.random.normal(next(keys), (C, H, rot)).astype(jnp.bfloat16)
    f32 = dict(preferred_element_type=jnp.float32)

    def rows_of(ids):
        return pool[ids].reshape(-1, lanes)

    def expanded(ids):
        rows = rows_of(ids)
        k_nope = jnp.einsum("tc,chn->thn", rows[:, :r], w_uk)
        v = jnp.einsum("tc,chn->thn", rows[:, :r], w_uv)
        s = (jnp.einsum("chd,thd->hct", q_nope, k_nope, **f32) + jnp.einsum(
            "chd,td->hct", q_rope, rows[:, r:r + rot], **f32)) * scale
        return s, lambda p: jnp.einsum("hct,thd->hcd", p.astype(v.dtype), v,
                                       **f32)

    q_lat = jnp.concatenate(
        [jnp.einsum("chn,rhn->chr", q_nope, w_uk), q_rope], -1)

    def absorbed(ids):
        rows = rows_of(ids)
        s = jnp.einsum("chf,tf->hct", q_lat, rows[:, :r + rot], **f32) * scale
        return s, lambda p: jnp.einsum(
            "hct,tr->hcr", p.astype(rows.dtype), rows[:, :r], **f32)

    for name, block, dv in (("chunk_expanded", expanded, n),
                            ("chunk_absorbed", absorbed, r)):
        fn = jax.jit(lambda row, block=block, dv=dv: programs._over_key_blocks(
            row, bs, C, pos0, None, (H,), dv, block))
        jax.block_until_ready(fn(tables[0]))
        t0 = time.perf_counter()
        for _ in range(5):
            out = fn(tables[0])
        jax.block_until_ready(out)
        rec[f"latent_{name}_16k_ms"] = 1e3 * (time.perf_counter() - t0) / 5
    # the expanded form again as the ONE kernel the program runs on the chip
    # (``tadnn_latent_chunk``): against the blocks above, and its time
    kern = jax.jit(lambda row: pa.latent_chunk_attention(
        q_nope, q_rope, pool, row, pos0, w_uk, w_uv, scale=scale))
    plain = jax.jit(lambda row: programs._over_key_blocks(
        row, bs, C, pos0, None, (H,), n, expanded))(tables[0])
    close("latent_chunk_kernel", kern(tables[0]).astype(jnp.float32),
          plain.transpose(1, 0, 2), rtol=1e-2)  # (a bfloat16 output's ulp)
    t0 = time.perf_counter()
    for _ in range(20):
        out = kern(tables[0])
    jax.block_until_ready(out)
    rec["latent_chunk_kernel_16k_ms"] = 1e3 * (time.perf_counter() - t0) / 20


# the five serving configurations at their published widths and a few of
# their layers (what is cut is depth, vocabulary, the number of experts and
# the window: no width), for ``fused_phase``
FUSED_CUTS = {
    "gpt2-1p3b": dict(n_layers=2, vocab_size=8192),
    "trinity-large-ep8": dict(
        n_layers=3, n_dense_layers=1, sliding_window=256, experts_held=8,
        experts_published=64, layer_types=[
            "sliding_attention", "sliding_attention", "full_attention"]),
    "olmo-hybrid-7b-pp2": dict(
        n_layers=3, vocab_size=8192, layer_types=[
            "linear_attention", "linear_attention", "full_attention"]),
    "joyai-llm-flash-ep8": dict(
        n_layers=3, n_dense_layers=1, vocab_size=8192, experts_held=8,
        experts_published=64, layer_types=["latent_attention"] * 3),
    "kimi-linear-48b-ep8": dict(
        n_layers=3, n_dense_layers=1, vocab_size=8192, experts_held=8,
        experts_published=64, layer_types=[
            "linear_attention", "linear_attention", "latent_attention"]),
}
# bf16 layers: rows of one product taken C + S at a time against C and S
FUSED_RTOL = 0.03


def fused_phase(name: str, *, seed: int, on_chip: bool) -> dict:
    """One chunk of one slot's prompt and one token of three other slots at
    different depths: as ``prefill_chunk`` then ``decode_logits``, and as
    ONE ``chunk_and_step``, from the same pool.  Passes when the chunk's
    logits and every pool array agree within ``FUSED_RTOL`` of their
    largest value and each token the fused call serves lies within it of
    the step's best logit."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from torch_automatic_distributed_neural_network_tpu.inference import decode
    from torch_automatic_distributed_neural_network_tpu.inference.serve import (
        programs,
    )
    from torch_automatic_distributed_neural_network_tpu.inference.serve.kv_pool import (
        PagedKVPool,
    )
    from torch_automatic_distributed_neural_network_tpu.models.transformer_core import (
        DecoderLM,
        TransformerConfig,
    )

    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "benchmark", "configs", name + ".json")) as f:
        config = json.load(f)
    cuts = FUSED_CUTS[name]
    keys = {**config["model"], **cuts}
    latent = "latent_attention" in (cuts.get("layer_types") or ())
    # (latent pages are 64 tokens at the cell: 8 page copies a kernel step)
    S, C, bs, max_len = 4, 128, 64 if latent else 16, 1024
    if not on_chip:  # the configuration's rehearsal sizes at the cut's depth
        keys = {**keys, **config["rehearsal"]["model"],
                "n_layers": cuts["n_layers"],
                "layer_types": cuts.get("layer_types")}
        C, bs, max_len = 16, 16, 128
    keys["max_seq_len"] = max(max_len, keys["max_seq_len"])
    cfg = TransformerConfig(
        **keys, dtype=jnp.bfloat16 if on_chip else jnp.float32, remat=False)
    rtol = FUSED_RTOL if on_chip else KERNEL_RTOL
    MB = max_len // bs
    model = DecoderLM(cfg)
    params = jax.jit(lambda k: decode.per_layer_params(
        decode.compute_dtype_params(
            model.init(k, jnp.ones((1, 8), jnp.int32))["params"], cfg),
        cfg))(jax.random.key(seed))
    pool = PagedKVPool(cfg, num_blocks=S * MB + 1, block_size=bs,
                       dtype=cfg.dtype, n_slots=S, max_blocks=MB,
                       prefill_chunk=C)
    win, kv = pool.win_tables, pool.kv
    rows = 1 + np.arange(S * MB).reshape(S, MB)  # a slot's pages
    sample = decode.SampleConfig(temperature=0.0)
    chunk = jax.jit(lambda *a: programs.prefill_chunk(
        *a, cfg=cfg, max_blocks=MB))
    step = jax.jit(lambda *a: programs.decode_logits(*a, cfg=cfg))
    fused = jax.jit(lambda *a: programs.chunk_and_step(
        *a, cfg=cfg, sample=sample, max_blocks=MB, chunk=C))
    rs = np.random.RandomState(seed)
    # three slots hold prompts of 1, 2 and 3 chunks (the last padded); the
    # fourth is one chunk into a prompt that ends inside its second
    lens = [C - 5, 2 * C - 9, 3 * C - 1, 2 * C - 3]
    prompts = [rs.randint(1, cfg.vocab_size, size=n) for n in lens]
    first = np.zeros((S,), np.int32)

    def operands(slot, pos):
        part = prompts[slot][pos:pos + C]
        return programs.pack_chunk(
            rows[slot], [*part, *[0] * (C - len(part))], pos, len(part) - 1,
            slot)

    for slot in range(S):
        for pos in range(0, lens[slot] - (C if slot == S - 1 else 0), C):
            kv, lg = chunk(params, kv, operands(slot, pos), win[slot])
        first[slot] = int(jnp.argmax(lg[0]))
    of_chunk = operands(S - 1, C)
    tables = rows * (np.arange(S) < S - 1)[:, None]  # the chunk's slot: null
    ctx = np.asarray(lens[:-1] + [0], np.int32)
    src = np.asarray([programs.TOKEN_HOST, programs.TOKEN_FIRST,
                      programs.TOKEN_PREV, 0], np.int32)
    prev = np.zeros((2 * S + programs.N_COUNTERS,), np.int32)
    prev[1], prev[S + 2] = first[1], first[2]
    tok = np.where(src == programs.TOKEN_HOST, first, 0).astype(np.int32)
    of_step = programs.pack_step(tables, ctx, tok[:, None], src,
                                 np.zeros((S,), np.int32))
    # two calls
    kv_a, lg_chunk_a = chunk(params, kv, of_chunk, win[S - 1])
    kv_a, lg_step, _ = step(params, kv_a, jnp.asarray(tables, jnp.int32),
                            win, jnp.asarray(ctx), jnp.asarray(
                                first * (src > 0))[:, None], jnp.asarray(
                                    src > 0))
    # one
    kv_b, out, lg_chunk_b = fused(
        params, kv, programs.pack_chunk_and_step(of_chunk, of_step),
        jnp.asarray(prev), win[S - 1], win, jax.random.key(0))
    rec = {"phase": "fused." + name, "rows": C + S, "layers": cfg.n_layers,
           "rtol": rtol}

    def close(what, got, want, flips=False):
        """``flips``: a layer past the first expert FFN, where bf16 rows
        taken C + S at a time now and then round to another 4th expert
        than the same rows taken alone (3-4% of positions against float32,
        PERF.md): those positions' keys and values differ, the others
        must not."""
        diff = jnp.abs(got.astype(jnp.float32) - want.astype(jnp.float32))
        err = float(jnp.max(diff))
        scale = float(jnp.max(jnp.abs(want.astype(jnp.float32))))
        off = float(jnp.mean(diff > rtol * scale))
        rec.setdefault(what + "_max_abs_err", 0.0)
        rec[what + "_max_abs_err"] = max(rec[what + "_max_abs_err"], err)
        if flips:
            rec[what + "_share_off"] = max(rec.get(what + "_share_off", 0.0),
                                          off)
        if not (scale > 0 and math.isfinite(err)
                and (off <= 0.02 if flips else err <= rtol * scale)):
            raise RuntimeError(f"fused {name}: {what}: max abs err {err:.3e} "
                               f"against max |ref| {scale:.3e} exceeds rtol "
                               f"{rtol} ({off:.2%} of the elements)")

    close("chunk_logits", lg_chunk_b, lg_chunk_a)
    plan = programs.layer_plan(cfg)
    first_sparse = next((i for i, (_, _, sparse, _) in enumerate(plan)
                         if sparse), len(plan))
    for side in ("k", "v"):
        for i, (a, b) in enumerate(zip(kv_a[side], kv_b[side])):
            for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
                if not x.size:  # nothing lies beside a layer's latent pages
                    continue
                close("pool" if i <= first_sparse else "pool_past_experts",
                      y, x, flips=on_chip and i > first_sparse)
    lg = np.asarray(lg_step[:, 0], np.float32)
    tokens = np.asarray(out[S:2 * S])
    regret = lg.max(-1) - lg[np.arange(S), tokens]
    rec["token_regret_max"] = float(regret[:S - 1].max())
    rec["tokens_equal"] = int((tokens == lg.argmax(-1))[:S - 1].sum())
    if not (regret[:S - 1] <= (0.2 if on_chip and cfg.n_expert_layers
                               else rtol) * np.abs(lg).max()).all() \
            or tokens[-1]:
        raise RuntimeError(f"fused {name}: tokens {tokens.tolist()} lie "
                           f"{regret.tolist()} under the step's best logits")
    if on_chip:
        text = fused.lower(
            params, kv, programs.pack_chunk_and_step(of_chunk, of_step),
            jnp.asarray(prev), win[S - 1], win,
            jax.random.key(0)).compile().as_text()
        rec["custom_calls"] = text.count("tpu_custom_call")
        kernels = ["tadnn_paged_decode_latent" if latent
                   else "tadnn_paged_decode_folded"]
        if "linear_attention" in (cfg.layer_types or ()):
            rule = "kda" if cfg.linear_decay == "channel" else "gdn"
            kernels += [f"tadnn_{rule}_chunk", f"tadnn_{rule}_step"]
        for kernel in kernels:
            if kernel not in text:
                raise RuntimeError(f"fused {name}: {kernel} is not in the "
                                   "compiled program")
    return rec


def count_entries(path: str | None) -> int | None:
    if path is None:
        return None
    return len(os.listdir(path)) if os.path.isdir(path) else 0


def run_one_chip(sz: Sizes, seed: int, on_chip: bool) -> None:
    import jax

    emit(train_phase(sz, label="train", devices=jax.devices()[:1],
                     strategy="auto", seed=seed, on_chip=on_chip))
    serve_phase(sz, seed=seed, on_chip=on_chip)
    emit(hybrid_kernels_phase(seed=seed, on_chip=on_chip))
    for name in FUSED_CUTS:
        emit(fused_phase(name, seed=seed, on_chip=on_chip))
        gc.collect()


def run_four_chips(sz: Sizes, seed: int, on_chip: bool) -> None:
    """The sharded train phase and what it is compared with, no other."""
    import jax

    devices = jax.devices()[:4]
    if len(devices) < 4:
        raise RuntimeError(f"--chips 4 needs four devices, JAX found "
                           f"{len(jax.devices())}")
    runs = {
        "train.one_device": train_phase(
            sz, label="train.one_device", devices=devices[:1],
            strategy="auto", seed=seed, on_chip=on_chip),
    }
    emit(runs["train.one_device"])
    for strategy in ("fsdp", "auto"):
        label = f"train.4chips.{strategy}"
        rec = runs[label] = train_phase(
            sz, label=label, devices=devices, strategy=strategy, seed=seed,
            on_chip=on_chip)
        ref = runs["train.one_device"]["losses"]
        rec["loss_rel_diff_vs_one_device"] = [
            round(abs(a - b) / abs(b), 5) for a, b in zip(rec["losses"], ref)]
        rec["parity_rtol"] = PARITY_RTOL
        emit(rec)
        if max(rec["loss_rel_diff_vs_one_device"]) > PARITY_RTOL:
            raise RuntimeError(
                f"{label}: losses {rec['losses']} differ from one device "
                f"{ref} by more than rtol {PARITY_RTOL}")
        in_use = rec["memory"]["bytes_in_use"]
        if on_chip and not all(in_use):
            raise RuntimeError(f"{label}: a chip holds no memory: {in_use}")
    # fsdp is the one whose layout is known beforehand: the large
    # parameter is cut four ways, one piece on each chip
    big = runs["train.4chips.fsdp"]["largest_param"]
    holders = {s["device"] for s in big["shards"]}
    if len(holders) != 4 or any(s["shape"] == big["shape"]
                                for s in big["shards"]):
        raise RuntimeError(f"fsdp did not shard the largest parameter "
                           f"over four devices: {big}")


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the sharded train phase over four chips "
                         "and its one-device comparison")
    ap.add_argument("--seed", type=int, default=0,
                    help="weights, data and prompts are made from it")
    ap.add_argument("--rehearsal", action="store_true",
                    help="run off the chip at the test model size")
    args = ap.parse_args(argv)

    import jax

    from torch_automatic_distributed_neural_network_tpu.topology import (
        compilation_cache_dir,
        device_record,
        enable_compilation_cache,
    )

    on_chip = jax.devices()[0].platform == "tpu"
    if not on_chip and not args.rehearsal:
        print(f"chip_smoke: needs a TPU and JAX found "
              f"{jax.devices()[0].platform!r}; --rehearsal runs the phases "
              f"at the test size off the chip", file=sys.stderr)
        return 2
    sz = REHEARSAL if args.rehearsal else CHIP
    if args.rehearsal:
        emit({"phase": "rehearsal",
              "note": "REHEARSAL at the test model size: not a chip run, "
                      "no number below is a device metric"})
    cache_dir = enable_compilation_cache()  # None: opted out
    placed_by = cache_dir and (
        "JAX_COMPILATION_CACHE_DIR" if compilation_cache_dir()[1] == "env"
        else "fixed in-checkout default")
    entries_before = count_entries(cache_dir)
    emit({"phase": "config", "device": device_record(), "chips": args.chips,
          "rehearsal": args.rehearsal, "seed": args.seed,
          "export_cache": "off", **dataclasses.asdict(sz)})
    try:
        if args.chips == 4:
            run_four_chips(sz, args.seed, on_chip)
        else:
            run_one_chip(sz, args.seed, on_chip)
        emit({"phase": "cache", "dir": cache_dir, "placed_by": placed_by,
              "entries_before": entries_before,
              "entries_after": count_entries(cache_dir)})
    except Exception as e:  # noqa: BLE001 — report the phase, then fail
        traceback.print_exc()
        emit({"ok": False, "error": f"{type(e).__name__}: {e}"})
        return 1
    emit({"ok": True, "device": device_record()})
    return 0


if __name__ == "__main__":
    sys.exit(main())
