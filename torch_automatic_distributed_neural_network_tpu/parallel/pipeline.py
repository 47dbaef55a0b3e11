"""Pipeline parallelism (SURVEY.md §2.2 'PP'; §7 phase 9).

GPipe-style schedule under the single-controller GSPMD model (SURVEY.md §7
hard part #5): the whole pipeline is ONE compiled program — a `lax.scan`
microbatch loop inside a `shard_map` region, with activations hopping to
the next stage over the ICI ring via `ppermute`.

Layout: the decoder's scanned layer stack gives parameters a leading
``[n_layers, ...]`` dim (models/transformer_core.py).  Sharding that dim
over the ``pipe`` mesh axis hands each pipe rank a contiguous block of
``n_layers / n_stages`` layers — its stage.  Inside the stage, layers run
under a local `lax.scan`; between stages, the activation is `ppermute`d
one hop.  Reverse-mode AD through the scan+ppermute yields the GPipe
backward schedule automatically (full forward, then full backward, per
microbatch) — no hand-written backward pass.

v2 — partial-manual shard_map: the region is manual over the ``pipe``
axis ONLY (``axis_names={'pipe'}``); every other mesh axis stays under
GSPMD's automatic partitioning *inside* the region.  That is what makes
the compositions work with zero extra collective code:

- pipe x tensor: the planner leaves the Megatron col/row specs on the
  stacked layer weights' trailing dims (planner.param_spec_tree), and
  GSPMD partitions each stage's matmuls over ``tensor`` as usual;
- pipe x data/fsdp: the microbatch tensors stay batch-sharded over the
  data axes inside the region.

Attention inside stages runs as einsum (``attn_impl='xla'`` via the
ParallelContext): a Mosaic/Pallas custom call cannot be GSPMD-partitioned
over the auto axes of a partial-manual region.

Dropout rngs thread through stages: each (microbatch, layer) folds its
own key from the step rng, so the pattern is schedule-independent and
deterministic under resume.

Schedule cost: ``M + S - 1`` iterations for M microbatches on S stages;
bubble fraction ``(S-1)/(M+S-1)`` of the *iterations*.  With the default
``schedule='cond'`` a per-device ``lax.cond`` skips the stage computation
on bubble iterations (HLO conditionals are runtime control flow even in
SPMD programs — each pipe rank takes its own branch, and the tensor/data
auto-axis peers of a rank agree on the predicate, so collectives inside
the taken branch stay consistent).  ``schedule='dense'`` keeps the
round-2 compute-everything-and-mask behavior for A/B measurement.
``schedule='1f1b'`` replaces
AD-through-the-scan with a hand-scheduled backward (onef_oneb_grads):
M-independent live-activation memory.

``schedule='interleaved'`` (round 4) implements the Megatron
interleaved schedule with ``V`` virtual stages per device
(:func:`spmd_pipeline_interleaved`): the stacked ``[L, ...]`` layer dim
is VIEWED as ``[V, S, C]`` (pure reshape — natural layer (vS+s)C+j
lands at index (v, s, j)) and dim 1 is sharded on ``pipe``, so each
device holds exactly its V round-robin chunks with NO gather or
all-to-all, and the existing ring permutation (i -> i+1) already
delivers the right activation every tick.  Each tick runs ONE chunk of
``C = L/(SV)`` layers (capacity-1, the real Megatron discipline — not
the V-chunks-per-tick layout sketch), so the forward takes
``MV + S - 1`` ticks and the bubble fraction shrinks V-fold to
``(S-1)/(MV + S - 1)``.  Constraint: ``M % S == 0`` (Megatron's
microbatch grouping).  Backward is reverse-mode AD through the scan
(GPipe-style), so live stash grows to MV chunk inputs.

``schedule='interleaved_1f1b'`` combines both: the interleaved forward
under custom_vjp plus a hand-scheduled backward over the REVERSED chunk
chain (:func:`onef_oneb_grads_interleaved`) — live stash bounded by the
2VS-1 ring (M-independent) AND the V-fold bubble shrink.
"""

from __future__ import annotations

import functools
from typing import Any, Callable

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from .. import topology as topo_mod


def _to_varying(x, axis_name: str):
    """Cast to device-varying along ``axis_name`` (no-op data movement)."""
    if hasattr(jax.lax, "pcast"):
        return jax.lax.pcast(x, (axis_name,), to="varying")
    return jax.lax.pvary(x, (axis_name,))


def spmd_pipeline(
    stage_fn: Callable[[Any, jax.Array, jax.Array], jax.Array],
    stage_params: Any,
    microbatches: jax.Array,
    *,
    n_stages: int,
    axis_name: str = "pipe",
    schedule: str = "cond",
) -> jax.Array:
    """GPipe microbatch loop.  MUST run inside `shard_map` manual over
    ``axis_name`` with ``stage_params`` sharded on it (leading dim) and
    ``microbatches`` of shape ``[M, mb, ...]`` replicated along it.

    ``stage_fn(local_stage_params, x, mb_idx) -> y`` applies one stage's
    layers to microbatch ``mb_idx`` (the schedule-independent microbatch
    id, for rng folding); activation shape/dtype must be preserved
    (transformer blocks are).  Returns ``[M, mb, ...]`` outputs,
    replicated along ``axis_name``.

    ``schedule`` picks how bubble iterations are handled:

    - ``'cond'`` (default) — a per-device ``lax.cond`` skips the stage
      computation entirely when the iteration is a bubble for this rank
      (stage s works on microbatch t-s; warmup/drain iterations outside
      [0, M) pass the activation through untouched).  The HLO conditional
      is real runtime control flow, so bubble FLOPs (and their backward)
      are never executed — the (S-1)/(M+S-1) fraction of compute the
      dense schedule burned on garbage.
    - ``'dense'`` — the round-2 behavior: every rank computes every
      iteration and bubble results are masked out.  Kept for A/B
      measurement and as a fallback.

    Both schedules run the same ``M + S - 1`` iterations and are
    trajectory-identical (the parity test pins them); 'cond' only removes
    work whose results were already discarded.
    """
    if schedule not in ("cond", "dense"):
        raise ValueError(f"unknown pipeline schedule {schedule!r}")
    S = n_stages
    M = microbatches.shape[0]
    stage = jax.lax.axis_index(axis_name)

    # mark loop state as device-varying along the pipe axis so the scan
    # carry type is stable (jax vma tracking inside shard_map)
    microbatches = _to_varying(microbatches, axis_name)

    def checked_stage(params, x, mb_idx):
        # trace-time shape check (stage_fn may use axis_index, which
        # eval_shape outside the region cannot trace)
        y = stage_fn(params, x, mb_idx)
        if y.shape != x.shape or y.dtype != x.dtype:
            raise ValueError(
                f"pipeline stage_fn must preserve activation shape/dtype; "
                f"got {x.shape}/{x.dtype} -> {y.shape}/{y.dtype}"
            )
        return y

    # zeros_like inherits every varying axis of the (cast) microbatches —
    # e.g. 'data' when the batch is also sharded — keeping scan carry types
    # stable no matter which other mesh axes are in play
    act0 = jnp.zeros_like(microbatches[0])
    outputs0 = jnp.zeros_like(microbatches)
    perm = [(i, (i + 1) % S) for i in range(S)]

    def body(carry, t):
        act, outputs = carry
        # the microbatch this stage works on at iteration t (bubble
        # iterations clamp and redo a boundary microbatch; their results
        # are never stored)
        mb_idx = jnp.clip(t - stage, 0, M - 1)
        # stage 0 ingests microbatch t
        inp = jnp.where(
            stage == 0,
            jax.lax.dynamic_index_in_dim(
                microbatches, jnp.clip(t, 0, M - 1), 0, keepdims=False
            ),
            act,
        )
        if schedule == "cond":
            # real work iff 0 <= t - stage < M; bubbles pass through
            work = jnp.logical_and(t - stage >= 0, t - stage < M)
            out = jax.lax.cond(
                work,
                lambda a: checked_stage(stage_params, a, mb_idx),
                lambda a: a,
                inp,
            )
        else:
            out = checked_stage(stage_params, inp, mb_idx)
        # the last stage finishes microbatch t-(S-1) at iteration t
        out_idx = jnp.clip(t - (S - 1), 0, M - 1)
        is_done = jnp.logical_and(stage == S - 1, t >= S - 1)
        cur = jax.lax.dynamic_index_in_dim(outputs, out_idx, 0, keepdims=False)
        outputs = jax.lax.dynamic_update_index_in_dim(
            outputs, jnp.where(is_done, out, cur), out_idx, 0
        )
        # one ICI hop to the next stage (ring; last->first carries garbage)
        nxt = jax.lax.ppermute(out, axis_name, perm)
        return (nxt, outputs), None

    (_, outputs), _ = jax.lax.scan(
        body, (act0, outputs0), jnp.arange(M + S - 1)
    )
    # Only the last stage holds real outputs — masked psum broadcasts them
    # so the shard_map out_spec is replicated along the pipe axis.  The
    # result stays fp32 THROUGH the region boundary: the replication-
    # materializing all-reduce(copy) the partial-manual boundary emits
    # trips a CHECK in XLA:CPU's AllReducePromotion pass when it is bf16
    # (callers cast back outside the region).
    masked = jnp.where(stage == S - 1, outputs, jnp.zeros_like(outputs))
    return jax.lax.psum(masked.astype(jnp.float32), axis_name)


def spmd_pipeline_interleaved(
    stage_fn: Callable[[Any, jax.Array, jax.Array, jax.Array], jax.Array],
    stage_params: Any,
    microbatches: jax.Array,
    *,
    n_stages: int,
    virtual: int,
    axis_name: str = "pipe",
    schedule: str = "cond",
) -> jax.Array:
    """Megatron interleaved forward: V virtual stages per device.

    Must run inside `shard_map` manual over ``axis_name``.
    ``stage_params`` leaves are ``[V, C, ...]`` per device (the global
    ``[V, S, C]`` view sharded on dim 1); ``stage_fn(chunk_params, x,
    mb_idx, v_idx)`` applies one C-layer chunk.

    Chunk q = v*S + s lives on device s = q % S — so the chain q -> q+1
    is exactly the ring hop i -> i+1, except the wrap S-1 -> 0 advances
    the virtual index, and v=0 on device 0 ingests fresh microbatches.
    Device s's k-th chunk execution (at tick t = s + k) handles::

        v = (k // S) % V
        m = (k // (S*V)) * S + k % S        (requires M % S == 0)

    This order satisfies both dependencies tick-tight: the same-(v,m)
    producer on device s-1 finished at t-1, and device 0's (v,m) needs
    (v-1,m) from device S-1, which finished at t-1 as well (k differs by
    exactly S).  ``M*V + S - 1`` ticks of one C-layer chunk each.
    """
    if schedule not in ("cond", "dense"):
        raise ValueError(f"unknown pipeline schedule {schedule!r}")
    S, V = n_stages, virtual
    M = microbatches.shape[0]
    if M % S:
        raise ValueError(
            f"interleaved schedule needs microbatches % stages == 0 "
            f"(Megatron grouping); got M={M}, S={S}"
        )
    stage = jax.lax.axis_index(axis_name)
    microbatches = _to_varying(microbatches, axis_name)

    act0 = jnp.zeros_like(microbatches[0])
    outputs0 = jnp.zeros_like(microbatches)
    perm = [(i, (i + 1) % S) for i in range(S)]
    T = M * V + S - 1

    def body(carry, t):
        act, outputs = carry
        k = t - stage  # this device's chunk-execution index
        work = jnp.logical_and(k >= 0, k < M * V)
        kc = jnp.clip(k, 0, M * V - 1)
        v = (kc // S) % V
        m = (kc // (S * V)) * S + kc % S
        # v=0 on device 0 ingests microbatch m; everything else takes
        # the ring activation (see the tick-tightness argument above)
        inp = jnp.where(
            jnp.logical_and(stage == 0, v == 0),
            jax.lax.dynamic_index_in_dim(microbatches, m, 0, keepdims=False),
            act,
        )
        chunk_params = jax.tree.map(
            lambda p: jax.lax.dynamic_index_in_dim(p, v, 0, keepdims=False),
            stage_params,
        )
        if schedule == "cond":
            out = jax.lax.cond(
                work,
                lambda a: stage_fn(chunk_params, a, m, v),
                lambda a: a,
                inp,
            )
        else:
            out = stage_fn(chunk_params, inp, m, v)
        # the chain's last chunk (v = V-1 on device S-1) completes m
        is_done = jnp.logical_and(
            jnp.logical_and(stage == S - 1, v == V - 1), work
        )
        cur = jax.lax.dynamic_index_in_dim(outputs, m, 0, keepdims=False)
        outputs = jax.lax.dynamic_update_index_in_dim(
            outputs, jnp.where(is_done, out, cur), m, 0
        )
        nxt = jax.lax.ppermute(out, axis_name, perm)
        return (nxt, outputs), None

    (_, outputs), _ = jax.lax.scan(body, (act0, outputs0), jnp.arange(T))
    masked = jnp.where(stage == S - 1, outputs, jnp.zeros_like(outputs))
    return jax.lax.psum(masked.astype(jnp.float32), axis_name)


# ---------------------------------------------------------------------------
# 1F1B: memory-bounded backward schedule
# ---------------------------------------------------------------------------


def onef_oneb_grads(
    stage_fn: Callable[[Any, jax.Array, jax.Array], jax.Array],
    stage_params: Any,
    microbatches: jax.Array,
    cotangents: jax.Array,
    *,
    n_stages: int,
    axis_name: str = "pipe",
) -> tuple[Any, jax.Array]:
    """Hand-scheduled 1F1B combined forward+backward pass.

    Runs inside the same partial-manual ``shard_map`` region as
    :func:`spmd_pipeline`; returns ``(param_grads, input_cotangents)``
    for the whole trunk given output ``cotangents`` of shape
    ``[M, mb, ...]``.

    Why a hand-written backward at all: reverse-mode AD through the GPipe
    scan stashes one stage-input per iteration — ``M + S - 1`` live
    activations — and (jax 0.9) refuses `lax.cond` in the differentiated
    path when branches carry different residuals (dropout).  This
    schedule is not differentiated — each backward tick recomputes its
    stage forward from a stashed input and applies the cotangent with an
    explicit ``jax.vjp`` — so both limits disappear: live stage inputs
    are a ``2S - 1`` ring independent of M, and bubbles skip compute via
    ``lax.cond`` even with dropout on.

    FLOP accounting, in forward-units (bwd ~= 2 fwd): this pass runs the
    forward wavefront (to regenerate inter-stage activations and
    stashes) + per-tick vjp recompute + backward = 4 units, on top of
    the primal forward the custom_vjp wrapper already ran = **5 units
    total, vs 4 for AD-GPipe with the remat-everything policy** — one
    extra forward (~25% more step FLOPs) is the price of the
    M-independent memory bound.  Worth it exactly when M must be large
    (deep pipelines want M >> S to kill the bubble fraction) and
    activations, not FLOPs, are the binding constraint.

    Implementation: the exact ``V=1`` case of
    :func:`onef_oneb_grads_interleaved` — with one chunk per device the
    interleaved tick/ring algebra reduces line-for-line to the classic
    1F1B lockstep (fwd(m) at tick ``m + s``, bwd(m) at
    ``m + 2S - 1 - s``, stash ring ``2S - 1``), so ONE scheduler carries
    both proofs.  Trajectory parity with the AD-GPipe path is pinned in
    tests/test_pipeline.py.
    """
    wrapped = jax.tree.map(lambda p: p[None], stage_params)
    dparams, dmbs = onef_oneb_grads_interleaved(
        lambda params, x, m, v: stage_fn(params, x, m),
        wrapped, microbatches, cotangents,
        n_stages=n_stages, virtual=1, axis_name=axis_name,
    )
    return jax.tree.map(lambda p: p.squeeze(0), dparams), dmbs


def onef_oneb_grads_interleaved(
    stage_fn: Callable[[Any, jax.Array, jax.Array, jax.Array], jax.Array],
    stage_params: Any,          # leaves [V, C, ...] per device
    microbatches: jax.Array,
    cotangents: jax.Array,
    *,
    n_stages: int,
    virtual: int,
    axis_name: str = "pipe",
) -> tuple[Any, jax.Array]:
    """Interleaved 1F1B: the hand-scheduled backward over the V*S virtual
    chunk chain.

    Schedule (Q = V*S; forward exactly :func:`spmd_pipeline_interleaved`'s
    k-ordering): device s's j-th BACKWARD execution handles::

        v = V-1 - (j // S) % V          (the forward's v, reversed)
        m = (j // (S*V)) * S + j % S
        at tick t = Q + (S-1-s) + j

    Tick-tightness mirrors the forward proofs: bwd(q) needs bwd(q+1)
    from device s+1 one tick earlier (same j, one smaller device skew),
    and the S-1 -> 0 chain wrap advances v with j differing by exactly S.
    The first backward (chunk Q-1, m=0, device S-1, j=0, t=Q) fires one
    tick after its forward (t=Q-1) — the delay D=Q is minimal.

    Memory: the stash ring holds ``2Q - 1`` chunk inputs (a chunk input
    is written at fwd index k and read at bwd index j with
    k - j <= 2Q - 1 - ...; the bound is the V=1 ring's 2S-1 scaled by
    V), still INDEPENDENT of M — unlike AD through the interleaved
    forward, whose stash grows as M*V.  Wall-clock: T = MV + Q + S - 1
    ticks of 1/V-stage compute ~= (M + S + (S-1)/V) stage-units vs 1F1B's
    (M + 2S - 1): strictly fewer for V > 1.
    """
    S, V = n_stages, virtual
    Q = V * S
    M = microbatches.shape[0]
    if V > 1 and M % S:
        # the grouped (v, m) ordering needs whole groups of S; with one
        # chunk per device (V=1, classic 1F1B) m(k) = k and any M works
        raise ValueError(
            f"interleaved schedule needs microbatches % stages == 0; "
            f"got M={M}, S={S}"
        )
    B = 2 * Q - 1
    stage = jax.lax.axis_index(axis_name)

    microbatches = _to_varying(microbatches, axis_name)
    cotangents = _to_varying(cotangents, axis_name)

    act0 = jnp.zeros_like(microbatches[0])
    cot0 = jnp.zeros_like(cotangents[0])
    stash0 = _to_varying(
        jnp.zeros((B,) + act0.shape, act0.dtype), axis_name
    )
    dparams0 = jax.tree.map(
        lambda p: _to_varying(jnp.zeros(p.shape, jnp.float32), axis_name),
        stage_params,
    )
    dmbs0 = jnp.zeros_like(microbatches)
    fwd_perm = [(i, (i + 1) % S) for i in range(S)]
    bwd_perm = [(i, (i - 1) % S) for i in range(S)]

    def chunk_of(idx, v):
        """(group, residue) of execution index ``idx`` recombined with
        virtual stage ``v`` -> the forward execution index k."""
        return (idx // (S * V)) * (S * V) + v * S + idx % S

    def tick(carry, t):
        act, cot, stash, dparams, dmbs = carry

        # ---- backward indices; stash read FIRST (ring aliasing: the
        # forward may write this very slot later in the same tick) ----
        j = t - Q - (S - 1 - stage)
        work_b = jnp.logical_and(j >= 0, j < M * V)
        jc = jnp.clip(j, 0, M * V - 1)
        v_b = V - 1 - (jc // S) % V
        m_b = (jc // (S * V)) * S + jc % S
        k_read = chunk_of(jc, v_b)  # where this chunk's fwd stashed
        x0 = jax.lax.dynamic_index_in_dim(
            stash, k_read % B, 0, keepdims=False)

        # ---- forward slot (spmd_pipeline_interleaved's schedule) ----
        k = t - stage
        work_f = jnp.logical_and(k >= 0, k < M * V)
        kc = jnp.clip(k, 0, M * V - 1)
        v_f = (kc // S) % V
        m_f = (kc // (S * V)) * S + kc % S
        inp = jnp.where(
            jnp.logical_and(stage == 0, v_f == 0),
            jax.lax.dynamic_index_in_dim(
                microbatches, m_f, 0, keepdims=False),
            act,
        )
        fwd_params = jax.tree.map(
            lambda p: jax.lax.dynamic_index_in_dim(
                p, v_f, 0, keepdims=False),
            stage_params,
        )
        y = jax.lax.cond(
            work_f,
            lambda a: stage_fn(fwd_params, a, m_f, v_f),
            lambda a: a,
            inp,
        )
        slot_f = kc % B
        old = jax.lax.dynamic_index_in_dim(stash, slot_f, 0,
                                           keepdims=False)
        stash = jax.lax.dynamic_update_index_in_dim(
            stash, jnp.where(work_f, inp, old), slot_f, 0
        )

        # ---- backward compute ----
        g_in = jnp.where(
            jnp.logical_and(stage == S - 1, v_b == V - 1),
            jax.lax.dynamic_index_in_dim(cotangents, m_b, 0,
                                         keepdims=False),
            cot,
        )
        bwd_params = jax.tree.map(
            lambda p: jax.lax.dynamic_index_in_dim(
                p, v_b, 0, keepdims=False),
            stage_params,
        )

        def do_bwd(operand):
            x0, g = operand
            _, vjp_fn = jax.vjp(
                lambda p, xx: stage_fn(p, xx, m_b, v_b), bwd_params, x0
            )
            dp, dx = vjp_fn(g)
            return jax.tree.map(
                lambda a: a.astype(jnp.float32), dp
            ), dx.astype(jnp.float32)

        def no_bwd(operand):
            _, g = operand
            return jax.tree.map(
                lambda p: _to_varying(
                    jnp.zeros(p.shape, jnp.float32), axis_name
                ),
                bwd_params,
            ), g.astype(jnp.float32)

        dp, dx = jax.lax.cond(work_b, do_bwd, no_bwd, (x0, g_in))
        # scatter-add this chunk's param grads into virtual slot v_b
        dparams = jax.tree.map(
            lambda acc, d: acc.at[v_b].add(d), dparams, dp
        )
        # chunk 0 (v=0, device 0) emits the trunk-input cotangent
        store = jnp.logical_and(
            jnp.logical_and(stage == 0, v_b == 0), work_b)
        cur = jax.lax.dynamic_index_in_dim(dmbs, m_b, 0, keepdims=False)
        dmbs = jax.lax.dynamic_update_index_in_dim(
            dmbs, jnp.where(store, dx.astype(dmbs.dtype), cur), m_b, 0
        )

        act = jax.lax.ppermute(y, axis_name, fwd_perm)
        cot = jax.lax.ppermute(dx, axis_name, bwd_perm)
        return (act, cot, stash, dparams, dmbs), None

    T = M * V + Q + S - 1
    (_, _, _, dparams, dmbs), _ = jax.lax.scan(
        tick, (act0, cot0, stash0, dparams0, dmbs0), jnp.arange(T)
    )
    dparams = jax.tree.map(
        lambda g, p: g.astype(p.dtype), dparams, stage_params
    )
    masked = jnp.where(stage == 0, dmbs, jnp.zeros_like(dmbs))
    return dparams, jax.lax.psum(masked, axis_name)


# ---------------------------------------------------------------------------
# DecoderLM integration
# ---------------------------------------------------------------------------


def make_pipelined_apply(
    model: nn.Module,
    mesh: Mesh,
    *,
    n_microbatches: int = 8,
    axis_name: str = "pipe",
    remat: bool | None = None,
    schedule: str = "cond",
    virtual: int = 1,
) -> Callable:
    """Build ``apply(variables, tokens, rngs=...) -> logits`` running
    ``model``'s layer stack as a GPipe pipeline over ``mesh``'s ``pipe``
    axis.

    ``model`` must be a ``DecoderLM`` (models/transformer_core.py) with
    ``scan_layers=True`` — the scanned stack's leading dim is what the
    pipeline shards into stages.  Embedding and LM head run outside the
    shard_map region (GSPMD shards them over data/tensor axes as usual);
    only the O(n_layers) trunk — where the parameters live — is
    pipelined.  Tensor-parallel stages need no special handling: the
    region is manual over ``pipe`` only, so the stacked weights'
    col/row specs partition each stage's matmuls automatically.

    Mirrors DecoderLM.__call__; the parity test (tests/test_pipeline.py)
    pins the two together.
    """
    from ..models.transformer_core import DecoderLayer, DecoderLM, make_norm

    if schedule not in ("cond", "dense", "1f1b", "interleaved",
                        "interleaved_1f1b"):
        raise ValueError(f"unknown pipeline schedule {schedule!r}")
    if not isinstance(model, DecoderLM):
        raise TypeError(
            f"pipeline parallelism needs a DecoderLM-family model "
            f"(GPT2/Llama); got {type(model).__name__}"
        )
    cfg = model.cfg
    if not cfg.scan_layers:
        raise ValueError("pipeline parallelism requires cfg.scan_layers=True")
    S = topo_mod.mesh_degrees(mesh).get(axis_name, 1)
    if S <= 1:
        raise ValueError(f"mesh has no {axis_name!r} axis > 1")
    interleaved = schedule in ("interleaved", "interleaved_1f1b")
    V = virtual if interleaved else 1
    if interleaved and V < 2:
        raise ValueError(
            "schedule='interleaved' needs virtual >= 2 (V=1 is plain "
            "GPipe — use schedule='cond')"
        )
    if not interleaved and virtual > 1:
        raise ValueError(
            f"virtual={virtual} only applies to schedule='interleaved'"
        )
    if cfg.n_layers % (S * V):
        raise ValueError(
            f"n_layers={cfg.n_layers} not divisible by "
            f"{S} stages x {V} virtual"
        )
    M = n_microbatches
    if interleaved and M % S:
        raise ValueError(
            f"interleaved schedule needs microbatches % stages == 0; "
            f"got M={M}, S={S}"
        )
    L_local = cfg.n_layers // S
    C_chunk = cfg.n_layers // (S * V)

    layer = DecoderLayer(cfg)

    def one_layer(p, x, positions, mask, rngs):
        return layer.apply({"params": p}, x, positions, mask, rngs=rngs)

    if cfg.remat if remat is None else remat:
        one_layer = jax.checkpoint(
            one_layer,
            policy=(
                jax.checkpoint_policies.nothing_saveable
                if cfg.remat_policy == "nothing"
                else jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims
            ),
        )

    def make_stage_fn(key_data, positions_mbs=None, mask_mbs=None,
                      use_dropout=True):
        """``positions_mbs``/``mask_mbs`` are the custom per-token
        positions / attention mask pre-split to ``[M, mb, ...]`` and
        replicated into the region; each stage indexes its current
        microbatch's slice by ``mb_idx`` (they never hop with the
        activation — every stage holds the full copy).  ``use_dropout``
        False = deterministic pass (eval): no dropout rngs are threaded,
        matching the flax missing-rng convention."""

        def stage_fn(stage_params, x, mb_idx, v_idx=None):
            # fp32 in/out: activations and their cotangents cross every
            # stage hop and the region boundary in fp32 (see pipe_region);
            # compute inside the stage stays in the model dtype
            x = x.astype(cfg.dtype)
            if positions_mbs is None:
                positions = jnp.arange(x.shape[1])[None, :]
            else:
                positions = jax.lax.dynamic_index_in_dim(
                    positions_mbs, mb_idx, 0, keepdims=False
                )
            mask = (
                None if mask_mbs is None
                else jax.lax.dynamic_index_in_dim(
                    mask_mbs, mb_idx, 0, keepdims=False
                )
            )
            stage = jax.lax.axis_index(axis_name)
            # global index of this block's first layer: contiguous
            # L_local-sized stages, or the (v*S + s)-th C-sized chunk of
            # the interleaved [V, S, C] view
            layer_base = (
                stage * L_local if v_idx is None
                else (v_idx * S + stage) * C_chunk
            )

            def body(carry, xs):
                p, li = xs
                if cfg.dropout_rate and use_dropout:
                    # schedule-independent key: one stream per
                    # (microbatch, global layer) pair
                    base = jax.random.wrap_key_data(key_data)
                    key = jax.random.fold_in(
                        base, mb_idx * cfg.n_layers + layer_base + li
                    )
                    rngs = {"dropout": key}
                else:
                    rngs = None
                return one_layer(p, carry, positions, mask, rngs), None

            n_block = jax.tree.leaves(stage_params)[0].shape[0]
            y, _ = jax.lax.scan(
                body, x, (stage_params, jnp.arange(n_block))
            )
            return y.astype(jnp.float32)

        return stage_fn

    from . import context as pctx

    def _split_mb(t, b):
        return t.reshape((M, b // M) + t.shape[1:])

    def _unpack_extras(extras, b, has_pos, has_mask):
        """Shared by the forward and 1F1B-backward regions: split the
        replicated custom positions / attention mask to [M, mb, ...]."""
        it = iter(extras)
        positions_mbs = _split_mb(next(it), b) if has_pos else None
        mask_mbs = _split_mb(next(it), b) if has_mask else None
        return positions_mbs, mask_mbs

    def _region_ctx():
        """Inside a pipeline region: manual over pipe, auto over
        everything else.  Mesh-axis sharding constraints are disabled
        (they would name auto axes from inside a manual region) and
        attention is forced to the einsum path, which GSPMD partitions
        over the auto axes."""
        return pctx.use(pctx.ParallelContext(
            mesh=mesh, enable_constraints=False, attn_impl="xla",
        ))

    @functools.lru_cache(maxsize=None)
    def make_pipe(has_pos: bool, has_mask: bool, use_dropout: bool = True,
                  schedule_override: str | None = None):
        """shard_map'd pipeline region for the given extra-input shape
        (custom positions and/or attention mask: replicated [B, ...]
        arrays split to [M, mb, ...] and indexed per microbatch)."""

        def pipe_region(layer_params, x, key_data, *extras):
            b = x.shape[0]
            if b % M:
                raise ValueError(
                    f"batch {b} not divisible by {M} microbatches"
                )
            positions_mbs, mask_mbs = _unpack_extras(
                extras, b, has_pos, has_mask
            )
            mbs = _split_mb(x, b)
            with _region_ctx():
                # Dropout forces the dense schedule UNDER AD: the cond
                # branches then differ in AD residuals (the work branch
                # carries PRNG-key/dropout-mask residuals the passthrough
                # branch lacks), which trips an internal assertion in
                # JAX's cond partial-eval (jax 0.9 conditionals.py:619).
                # Dense is trajectory-identical, just without the bubble
                # skip.  The 1F1B path passes schedule_override='cond':
                # its forward is inside custom_vjp and never
                # differentiated, so cond is safe even with dropout.
                if schedule_override is not None:
                    eff_schedule = schedule_override
                else:
                    eff_schedule = "dense" if use_dropout else "cond"
                    if schedule in ("dense",):
                        eff_schedule = "dense"
                stage_fn = make_stage_fn(key_data, positions_mbs,
                                         mask_mbs, use_dropout)
                if interleaved:
                    # leaves arrive [V, 1, C, ...] (the [V, S, C] view
                    # sharded on dim 1) — drop the unit stage dim
                    local = jax.tree.map(
                        lambda p: p.squeeze(1), layer_params
                    )
                    out = spmd_pipeline_interleaved(
                        stage_fn, local, mbs,
                        n_stages=S, virtual=V, axis_name=axis_name,
                        schedule=eff_schedule,
                    )
                else:
                    out = spmd_pipeline(
                        stage_fn, layer_params, mbs,
                        n_stages=S, axis_name=axis_name,
                        schedule=eff_schedule,
                    )
            return out.reshape(x.shape)  # fp32 across the region boundary

        n_extras = int(has_pos) + int(has_mask)
        layer_spec = P(None, axis_name) if interleaved else P(axis_name)
        return shard_map(
            pipe_region,
            mesh=mesh,
            in_specs=(layer_spec, P(), P()) + (P(),) * n_extras,
            out_specs=P(),
            axis_names={axis_name},
        )

    def _float0_zeros(x):
        import numpy as _np

        return _np.zeros(_np.shape(x), dtype=jax.dtypes.float0)

    @functools.lru_cache(maxsize=None)
    def make_trunk_1f1b(has_pos: bool, has_mask: bool,
                        use_dropout: bool = True):
        """The 1F1B trunk: forward = the cond-schedule pipeline (safe even
        with dropout — custom_vjp means it is never differentiated),
        backward = :func:`onef_oneb_grads`' hand-scheduled lockstep pass.
        Memory: AD never stashes per-tick residuals here; the backward's
        live set is the 2S-1 stash ring + the (params, x) custom_vjp
        residual."""
        fwd_pipe = make_pipe(has_pos, has_mask, use_dropout,
                             schedule_override="cond")
        n_extras = int(has_pos) + int(has_mask)

        def bwd_region(layer_params, x, key_data, *extras_g):
            *extras, g = extras_g
            b = x.shape[0]
            positions_mbs, mask_mbs = _unpack_extras(
                extras, b, has_pos, has_mask
            )
            stage_fn = make_stage_fn(key_data, positions_mbs, mask_mbs,
                                     use_dropout)
            with _region_ctx():
                if interleaved:
                    local = jax.tree.map(
                        lambda p: p.squeeze(1), layer_params
                    )
                    dparams, dmbs = onef_oneb_grads_interleaved(
                        stage_fn, local, _split_mb(x, b), _split_mb(g, b),
                        n_stages=S, virtual=V, axis_name=axis_name,
                    )
                    # restore the sharded [V, 1, C, ...] layout
                    dparams = jax.tree.map(
                        lambda p: p[:, None], dparams
                    )
                else:
                    dparams, dmbs = onef_oneb_grads(
                        stage_fn, layer_params, _split_mb(x, b),
                        _split_mb(g, b),
                        n_stages=S, axis_name=axis_name,
                    )
            return dparams, dmbs.reshape(x.shape)

        layer_spec = P(None, axis_name) if interleaved else P(axis_name)
        bwd_pipe = shard_map(
            bwd_region,
            mesh=mesh,
            in_specs=(layer_spec, P(), P()) + (P(),) * (n_extras + 1),
            out_specs=(layer_spec, P()),
            axis_names={axis_name},
        )

        @jax.custom_vjp
        def trunk(layer_params, x, key_data, *extras):
            return fwd_pipe(layer_params, x, key_data, *extras)

        def trunk_fwd(layer_params, x, key_data, *extras):
            out = fwd_pipe(layer_params, x, key_data, *extras)
            return out, (layer_params, x, key_data, extras)

        def trunk_bwd(res, g):
            layer_params, x, key_data, extras = res
            dparams, dx = bwd_pipe(layer_params, x, key_data, *extras, g)
            # integer-dtype primals (rng key data, positions, mask) take
            # float0 cotangents
            return (dparams, dx, _float0_zeros(key_data),
                    *(map(_float0_zeros, extras)))

        trunk.defvjp(trunk_fwd, trunk_bwd)
        return trunk

    embed = nn.Embed(
        cfg.vocab_size, cfg.d_model, dtype=cfg.dtype,
        embedding_init=nn.initializers.normal(0.02),
    )

    def apply(variables, tokens, positions=None, mask=None, rngs=None):
        # Custom positions/mask thread through stages: replicated into the
        # region, split to [M, mb, ...], indexed by microbatch id (they
        # never ride the ppermute ring).  mask must be per-batch-row
        # boolean [B, 1|H, Q, K] (ops/attention convention); the causal
        # mask itself stays implicit in the attention op.
        dropout_key = (rngs or {}).get("dropout")
        # flax missing-rng convention: no dropout key -> deterministic
        # pass (dropout off) — the eval path relies on this; training
        # through AutoDistribute.step always passes the step rng.
        use_dropout = cfg.dropout_rate > 0 and dropout_key is not None
        key_data = jax.random.key_data(
            dropout_key if dropout_key is not None else jax.random.key(0)
        )
        params = variables["params"] if "params" in variables else variables
        x = embed.apply({"params": params["embed"]}, tokens)
        if cfg.pos == "learned":
            x = x + params["pos_embed"][None, : tokens.shape[1]].astype(
                cfg.dtype
            )
        # The pipelined trunk transports activations (and their backward
        # cotangents — the transpose of the region's pcast is a psum) in
        # fp32: bf16 vma-inserted all-reduces trip a CHECK in XLA:CPU's
        # AllReducePromotion pass (reducer contains a Sharding custom-call
        # it cannot clone), and fp32 residual transport across stage hops
        # is numerically conservative anyway.  Stage compute stays bf16.
        if schedule in ("1f1b", "interleaved_1f1b"):
            pipe = make_trunk_1f1b(positions is not None, mask is not None,
                                   use_dropout)
        else:
            pipe = make_pipe(positions is not None, mask is not None,
                             use_dropout)
        # plain model.apply accepts broadcastable extras (leading dim 1);
        # the microbatch split needs the full batch dim — broadcast first
        B = tokens.shape[0]
        extras = tuple(
            jnp.broadcast_to(e, (B,) + e.shape[1:])
            for e in (positions, mask) if e is not None
        )
        layer_params = params["layers"]
        if interleaved:
            # the [V, S, C] interleaved view of the layer dim (a pure
            # reshape: natural layer (vS+s)C+j -> index (v, s, j));
            # sharding dim 1 on pipe hands each device its V round-robin
            # chunks with zero weight movement
            layer_params = jax.tree.map(
                lambda p: p.reshape((V, S, C_chunk) + p.shape[1:]),
                layer_params,
            )
        x = pipe(layer_params, x.astype(jnp.float32), key_data, *extras)
        x = x.astype(cfg.dtype)
        x = make_norm(cfg, "final_norm").apply(
            {"params": params["final_norm"]}, x
        )
        if cfg.tie_embeddings:
            logits = embed.apply(
                {"params": params["embed"]},
                x.astype(jnp.float32),
                method="attend",
            )
        else:
            logits = nn.Dense(
                cfg.vocab_size, dtype=jnp.float32, use_bias=False,
            ).apply({"params": params["lm_head"]}, x)
        return logits.astype(jnp.float32)

    return apply


def bubble_fraction(n_stages: int, n_microbatches: int) -> float:
    """GPipe bubble overhead: idle fraction of the schedule."""
    return (n_stages - 1) / (n_microbatches + n_stages - 1)
