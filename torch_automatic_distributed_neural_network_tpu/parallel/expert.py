"""Expert parallelism (EP) for Mixture-of-Experts layers (SURVEY.md §2.2).

The reference's exercised configs are dense (BASELINE.json:7-11); EP is
brief-mandated.  TPU-native design, GShard-style (static shapes only):

- **Routing** is capacity-based top-k: every (batch-row) group dispatches
  at most ``capacity`` tokens to each expert, overflow tokens are dropped
  (their residual path carries them).  All shapes are static — no sort /
  no ragged gather, so the whole layer stays jit/scan/MXU friendly.
- **Dispatch/combine are einsums** against one-hot masks.  Under GSPMD the
  planner shards the expert dim of the expert weights and the dispatched
  activations on the ``expert`` mesh axis; XLA then inserts the
  all_to_all pair automatically (the NCCL-alltoall analog rides ICI).
- ``moe_ffn_sharded`` is the explicit-collective twin (shard_map +
  ``lax.all_to_all``) used to validate the GSPMD path and for meshes where
  manual placement wins; it matches ``moe_ffn`` bit-for-bit on CPU sim.

Terminology: E experts, C capacity slots per group, B groups (batch
rows), S tokens per group, d model width, f expert hidden width.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P


def expert_capacity(
    tokens_per_group: int, n_experts: int, top_k: int,
    capacity_factor: float,
) -> int:
    """Slots each expert reserves per group; multiple of 8 for TPU lanes."""
    c = int(tokens_per_group * top_k * capacity_factor / n_experts)
    return max(8, -(-c // 8) * 8)


def top_k_routing(
    router_logits: jax.Array,  # [B, S, E] (any float dtype; softmax in fp32)
    top_k: int,
    capacity: int,
    *,
    renormalize: bool = True,
) -> tuple[jax.Array, jax.Array, dict]:
    """Capacity-based top-k token->expert assignment.

    Returns ``(combine, dispatch, metrics)`` with
    ``combine: [B, S, E, C]`` float gate weights (0 where dropped),
    ``dispatch: [B, S, E, C]`` the 0/1 routing mask, and metrics holding
    the Switch/GShard load-balance ``aux_loss``, router ``z_loss`` and the
    dropped-token fraction.  The k choices claim capacity in choice-major
    order (all 1st choices first), matching the reference MoE stacks.
    """
    if router_logits.ndim != 3:
        raise ValueError(f"router_logits must be [B,S,E], got {router_logits.shape}")
    B, S, E = router_logits.shape
    logits = router_logits.astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)

    remaining = probs
    counts = jnp.zeros((B, 1, E), jnp.float32)  # claimed slots per expert
    gates, masks, first_choice = [], [], None
    for _ in range(top_k):
        onehot = jax.nn.one_hot(jnp.argmax(remaining, -1), E,
                                dtype=jnp.float32)  # [B,S,E]
        if first_choice is None:
            first_choice = onehot
        gate = (remaining * onehot).sum(-1)  # [B,S]
        remaining = remaining * (1.0 - onehot)
        # position of each token inside its expert's capacity buffer
        pos = jnp.cumsum(onehot, axis=1) - onehot + counts  # [B,S,E]
        counts = counts + onehot.sum(axis=1, keepdims=True)
        kept = ((pos < capacity) * onehot).sum(-1)  # [B,S] 1 if within capacity
        slot = (pos * onehot).sum(-1).astype(jnp.int32)  # [B,S]
        disp = (onehot[..., None]
                * jax.nn.one_hot(slot, capacity, dtype=jnp.float32)[:, :, None]
                * kept[..., None, None])  # [B,S,E,C]
        masks.append(disp)
        gates.append(gate * kept)

    dispatch = sum(masks)
    gate_stack = jnp.stack(gates, -1)  # [B,S,k]
    if renormalize:
        gate_stack = gate_stack / jnp.maximum(
            gate_stack.sum(-1, keepdims=True), 1e-9
        )
    combine = sum(
        g[..., None, None] * m for g, m in zip(
            jnp.moveaxis(gate_stack, -1, 0), masks
        )
    )

    # Switch-style load-balance loss on the first choice: E * sum_e f_e p_e
    frac_dispatched = first_choice.mean(axis=1)  # [B,E]
    mean_prob = probs.mean(axis=1)  # [B,E]
    aux_loss = E * (frac_dispatched * mean_prob).sum(-1).mean()
    z_loss = jnp.mean(jax.scipy.special.logsumexp(logits, axis=-1) ** 2)
    dropped = 1.0 - dispatch.sum((-2, -1)).mean() / top_k
    metrics = {"aux_loss": aux_loss, "z_loss": z_loss,
               "dropped_fraction": dropped}
    return combine, dispatch, metrics


def expert_mlp(h_in: jax.Array, w_up, w_gate, w_down,
               act: Callable[[jax.Array], jax.Array],
               constrain_hidden: Callable[[jax.Array], jax.Array] = lambda t: t,
               constrain_out: Callable[[jax.Array], jax.Array] = lambda t: t,
               ) -> jax.Array:
    """Per-expert FFN on dispatched tokens: [..., E, C, d] -> [..., E, C, d].

    Einsum keeps the E dim explicit so the planner can shard it; the
    contraction dims land on the MXU as one batched matmul per expert.
    The constraints pin every einsum output to the dispatched layout —
    without them GSPMD's sharding propagation invents transient layouts on
    the backward transposes and logs "Involuntary full rematerialization"
    (observed on the 8-device moe/ep compile, VERDICT round 2 weak #2).
    They differ under ep_tp: the hidden [..., E, C, f] carries the f dim
    on ``tensor`` (Megatron column split inside each expert), while the
    output [..., E, C, d] is tensor-replicated (the down contraction
    psums over tensor).
    """
    h = constrain_hidden(jnp.einsum("...ecd,edf->...ecf", h_in, w_up))
    if w_gate is not None:
        h = act(constrain_hidden(
            jnp.einsum("...ecd,edf->...ecf", h_in, w_gate))) * h
    else:
        h = act(h)
    return constrain_out(jnp.einsum("...ecf,efd->...ecd", h, w_down))


def moe_ffn(
    x: jax.Array,  # [B, S, d]
    router_logits: jax.Array,  # [B, S, E]
    w_up: jax.Array,  # [E, d, f]
    w_down: jax.Array,  # [E, f, d]
    *,
    w_gate: jax.Array | None = None,  # [E, d, f] -> SwiGLU experts
    top_k: int = 2,
    capacity_factor: float = 1.25,
    act: Callable[[jax.Array], jax.Array] = jax.nn.gelu,
    mesh: Mesh | None = None,
    expert_axis: str = "expert",
    batch_axes: tuple[str, ...] = ("data", "fsdp"),
    capacity: int | None = None,
) -> tuple[jax.Array, dict]:
    """MoE feed-forward, GSPMD formulation.

    Dense einsum dispatch/combine; if ``mesh`` has a nontrivial
    ``expert_axis`` the dispatched tensor is constrained to it so XLA
    emits the dispatch/return all_to_all pair over ICI.

    ``capacity`` overrides the per-group slot count derived from this
    call's token count — decode chunks pass the TRAINING group's value
    to pin training-identical drop decisions (inference/decode.py).
    """
    B, S, d = x.shape
    E = w_up.shape[0]
    if capacity is None:
        capacity = expert_capacity(S, E, top_k, capacity_factor)
    combine, dispatch, metrics = top_k_routing(router_logits, top_k, capacity)

    compute_dtype = x.dtype
    h = jnp.einsum("bsec,bsd->becd", dispatch.astype(compute_dtype), x)
    constrain_hidden = constrain_out = lambda t: t
    if mesh is not None:
        degrees = dict(zip(mesh.axis_names, mesh.devices.shape))
        if degrees.get(expert_axis, 1) > 1:
            # [B, E, C, *]: batch stays on the data axes, experts move to
            # the expert axis -> GSPMD inserts the all_to_all pair here
            # and at the combine einsum below.  Constraints on every
            # expert-MLP intermediate (see expert_mlp) keep the 8-device
            # layout consistent through fwd AND the backward weight-grad
            # transposes.  Under ep_tp (MOE_TP_RULES) the hidden f dim
            # additionally rides the tensor axis.
            present = tuple(
                a for a in batch_axes
                if a != expert_axis and degrees.get(a, 1) > 1
            )
            out_sharding = jax.sharding.NamedSharding(
                mesh, P(present or None, expert_axis)
            )
            tensor_split = (
                degrees.get("tensor", 1) > 1
                and w_up.shape[-1] % degrees["tensor"] == 0
            )
            hidden_sharding = jax.sharding.NamedSharding(
                mesh, P(present or None, expert_axis, None, "tensor")
            ) if tensor_split else out_sharding
            constrain_out = lambda t: jax.lax.with_sharding_constraint(
                t, out_sharding)
            constrain_hidden = lambda t: jax.lax.with_sharding_constraint(
                t, hidden_sharding)
            h = constrain_out(h)
    h = expert_mlp(h, w_up, w_gate, w_down, act,
                   constrain_hidden, constrain_out)
    y = jnp.einsum("bsec,becd->bsd", combine.astype(compute_dtype), h)
    return y.astype(x.dtype), metrics


def moe_ffn_sharded(
    x: jax.Array,
    router_logits: jax.Array,
    w_up: jax.Array,
    w_down: jax.Array,
    *,
    mesh: Mesh,
    w_gate: jax.Array | None = None,
    top_k: int = 2,
    capacity_factor: float = 1.25,
    act: Callable[[jax.Array], jax.Array] = jax.nn.gelu,
    expert_axis: str = "expert",
    batch_axes: tuple[str, ...] = ("data",),
) -> tuple[jax.Array, dict]:
    """Explicit-collective EP twin of :func:`moe_ffn`.

    shard_map over (batch_axes..., expert_axis): tokens live on the
    batch x expert grid, expert weights are sharded over ``expert_axis``.
    Each shard routes its local tokens, then one ``lax.all_to_all``
    regroups dispatched slots by owning expert, local experts run their
    FFN, and the inverse all_to_all returns results for the combine —
    the manual analog of what GSPMD emits for :func:`moe_ffn`.
    """
    degrees = dict(zip(mesh.axis_names, mesh.devices.shape))
    ep = degrees.get(expert_axis, 1)
    E = w_up.shape[0]
    if E % ep:
        raise ValueError(f"{E} experts not divisible by ep={ep}")
    _, S, _ = x.shape
    capacity = expert_capacity(S, E, top_k, capacity_factor)

    present_batch = tuple(a for a in batch_axes if degrees.get(a, 1) > 1)
    tok_spec = P((*present_batch, expert_axis) if ep > 1 else present_batch or None)
    w_spec = P(expert_axis if ep > 1 else None)

    def local_fn(x_l, logits_l, w_up_l, w_gate_l, w_down_l):
        combine, dispatch, metrics = top_k_routing(logits_l, top_k, capacity)
        h = jnp.einsum("bsec,bsd->becd", dispatch.astype(x_l.dtype), x_l)
        if ep > 1:
            # [B_l, E, C, d] -> regroup by expert owner: split the E dim
            # across the ring, concat received blocks on the group dim.
            h = jax.lax.all_to_all(
                h, expert_axis, split_axis=1, concat_axis=0, tiled=True
            )  # [B_l*ep, E/ep, C, d]
        h = expert_mlp(h, w_up_l, w_gate_l, w_down_l, act)
        if ep > 1:
            h = jax.lax.all_to_all(
                h, expert_axis, split_axis=0, concat_axis=1, tiled=True
            )  # [B_l, E, C, d]
        y = jnp.einsum("bsec,becd->bsd", combine.astype(x_l.dtype), h)
        # metrics are per-shard means over identical group sizes
        metrics = jax.tree.map(
            lambda m: jax.lax.pmean(
                m, (*present_batch, expert_axis) if ep > 1 else present_batch
            ) if present_batch or ep > 1 else m,
            metrics,
        )
        return y.astype(x_l.dtype), metrics

    gate_args = (w_gate,) if w_gate is not None else ()
    gate_specs = (w_spec,) if w_gate is not None else ()

    def fn(x_, logits_, up_, down_, *gate_):
        return local_fn(x_, logits_, up_, gate_[0] if gate_ else None, down_)

    y, metrics = shard_map(
        fn, mesh=mesh,
        in_specs=(tok_spec, tok_spec, w_spec, w_spec, *gate_specs),
        out_specs=(tok_spec, P()),
        check_vma=False,
    )(x, router_logits, w_up, w_down, *gate_args)
    return y, metrics


# -- no-drop routing over the experts held here --------------------------------
#
# The capacity router above drops what overflows a slot buffer and needs
# every expert on the mesh.  The layer below is the other kind: many small
# experts, a choice over ALL the published ones, no capacity, no dropped
# token, and a chip that is told which experts it holds and adds only what
# those give (the share of an expert-parallel deployment; on one chip it
# runs without its exchange).


def top_k_by_passes(select: jax.Array, scores: jax.Array,
                    top_k: int) -> tuple[jax.Array, jax.Array]:
    """``(chosen [T, k] int32, scores there [T, k])``: the ``top_k`` largest
    of each row of ``select`` [T, E] in descending order, ties to the lower
    index, which is what ``jax.lax.top_k`` picks, and ``scores`` [T, E] at
    each choice.  By ``top_k`` passes of max-and-mask over the row: on the
    chip ``top_k`` sorts all ``E`` scores of a row, which costs more than 8
    passes over 256, and a gather of ``T * top_k`` single numbers is as
    many steps, so a pass picks its score too."""
    ids = jnp.arange(select.shape[-1], dtype=jnp.int32)
    # a taken expert reads -inf and nothing else does, so that no pass takes
    # one again (a score of -inf ranks with the lowest finite number)
    select = jnp.maximum(select, jnp.finfo(select.dtype).min)
    chosen, picked = [], []
    for _ in range(top_k):
        best = jnp.argmax(select, axis=-1).astype(jnp.int32)  # first of equals
        it = ids == best[..., None]
        chosen.append(best)
        picked.append(jnp.where(it, scores, 0).sum(-1))
        select = jnp.where(it, -jnp.inf, select)
    return jnp.stack(chosen, axis=-1), jnp.stack(picked, axis=-1)


def route_top_k(
    logits: jax.Array,  # [T, E] float32, over all the published experts
    e_bias: jax.Array | None,  # [E]: takes part in the choice, never in the weight
    top_k: int,
    *,
    score_func: str = "sigmoid",
    route_norm: bool = True,
    route_scale: float = 1.0,
) -> tuple[jax.Array, jax.Array]:
    """``(chosen [T, k] int32, weights [T, k] float32)``: the top ``k`` of
    ``score + e_bias`` a token, weighted by the score alone, normalised to
    sum to 1 (``route_norm``) and scaled."""
    logits = logits.astype(jnp.float32)
    if score_func == "sigmoid":
        scores = jax.nn.sigmoid(logits)
    elif score_func == "softmax":
        scores = jax.nn.softmax(logits, axis=-1)
    else:
        raise ValueError(f"unknown score_func {score_func!r}")
    select = scores if e_bias is None else scores + e_bias
    chosen, weights = top_k_by_passes(select, scores, top_k)
    if route_norm:
        weights = weights / (weights.sum(-1, keepdims=True) + 1e-20)
    return chosen, weights * route_scale


def expert_tiles(n_rows: int, top_k: int, held: int) -> tuple[int, int]:
    """``(tm, n_tiles)``: the rows of a tile and the tiles
    ``held_expert_ffn`` lays for ``n_rows`` tokens: room for every pair that
    CAN land on this chip (a token's choices are ``top_k`` different
    experts, so at most ``held`` of them are held here), each held expert's
    last tile part empty."""
    pairs = n_rows * min(top_k, held)
    tm = 16 if pairs <= 256 else 128
    return tm, -(-pairs // tm) + min(held, pairs)


def held_expert_ffn(
    x: jax.Array,  # [T, d]
    chosen: jax.Array,  # [T, k] expert ids among the published ones
    weights: jax.Array,  # [T, k]
    w_gate: jax.Array,  # [held, d, f]
    w_up: jax.Array,  # [held, d, f]
    w_down: jax.Array,  # [held, f, d]
    *,
    first_expert: int = 0,
    valid: jax.Array | None = None,  # [T] bool: rows that are real
    n_experts: int | None = None,  # ids from here on are zero-compute experts
) -> tuple[jax.Array, dict]:
    """``sum_e w_e * SwiGLU_e(x)`` over the chosen experts that are held
    here (``first_expert .. first_expert + held``); what the others would
    add is left out.  No capacity: every (token, expert) pair that lands
    here is computed, however uneven the choice.

    The pairs are sorted by expert and laid out in row tiles of one expert
    each (an expert's rows padded up to a whole tile), so that the grouped
    matmuls (``ops/grouped_matmul.py``) read exactly the experts that own a
    tile; the results are gathered back a pair at a time and summed with
    the routing weights in float32.  Shapes are static (``expert_tiles``:
    ``T * k`` pairs in at most ``T * k / tm + min(held, T * k)`` tiles, the
    case of every pair on this chip), the work is not: the pairs that
    landed here fill the first ``n_active`` tiles, which are the kernels'
    grid, and the first kernel picks a tile's rows out of ``x`` itself, so
    no padded copy of the tokens is written.  A tile past ``n_active`` is
    never written and holds anything (NaN too), as does a tile's padding:
    a pair that is not here reads row 0 and is masked AFTER the gather (row
    0 is nobody's when no pair is here: the sum is then exact zeros).

    The index arrays are built from the sort's ``order`` by compares, sums
    and gathers of whole rows, never by a scatter or a lookup an element:
    the chip runs either an element at a time (4,288 steps for the pairs of
    a chunk), and a sum over a [held, pairs] mask is a few vector adds.

    A pair on an id at or past ``n_experts`` chose a ZERO-COMPUTE expert,
    which gives back its input: a row gets ``(the sum of its weights on such
    ids) * x``, in float32, beside the matmuls' result and outside them; such
    a pair is never sorted into a tile (its id lies past every held expert).
    Every chip adds this term for its own tokens.

    Returns ``(y [T, d], stats)`` with the step's counters: ``pairs`` that
    landed here, ``experts_touched``, ``max_expert_tokens``,
    ``tiles_active`` (``n_active``, of the ``expert_tiles`` laid),
    ``zero_pairs`` (the valid rows' pairs on zero-compute experts) and
    ``rows`` (the valid rows)."""
    from ..ops.grouped_matmul import grouped_matmul

    T, d = x.shape
    k, held = chosen.shape[1], w_up.shape[0]
    P = T * k
    tm, n_tiles = expert_tiles(T, k, held)

    # pair p is choice p // T of token p % T: a token's pairs lie T apart,
    # so that the combine adds k whole [T, d] slabs
    local = chosen.T.reshape(P) - first_expert
    here = (local >= 0) & (local < held)
    if valid is not None:
        here &= jnp.tile(valid, k)
    key = jnp.where(here, local, held)  # the pairs of absent experts sort last
    order = jnp.argsort(key, stable=True)  # sorted position -> pair
    place = jnp.argsort(order)  # pair -> sorted position
    # [held, P], the experts leading: a sum over them is whole-vector adds
    mine = key == jnp.arange(held, dtype=key.dtype)[:, None]
    sizes = mine.sum(1, dtype=jnp.int32)
    starts = jnp.cumsum(sizes) - sizes
    tiles = (sizes + tm - 1) // tm
    tile_ends = jnp.cumsum(tiles)
    n_active = tile_ends[-1]
    first_row = (tile_ends - tiles) * tm  # an expert's first padded row
    tile_group = jnp.minimum(
        jnp.searchsorted(tile_ends, jnp.arange(n_tiles), side="right",
                         method="compare_all"),
        held - 1).astype(jnp.int32)
    # padded row -> the token it copies (-1: none).  A tile's rows are ONE
    # run of sorted positions, from ``base`` on while the expert has rows
    # ``left``: a slice a tile, not a lookup a row ...
    before = jnp.arange(n_tiles) * tm - first_row[tile_group]
    base = jnp.minimum(starts[tile_group] + before, P)
    left = sizes[tile_group] - before  # <= 0 past the last live tile
    # ... read as the two whole blocks of tm positions it lies in (a gather
    # of rows) and moved left by what is over, a binary digit at a time
    # (tm is a power of two): every step a select over the whole array
    n_blocks = -(-P // tm) + 2
    blocks = jnp.pad(order % T, (0, n_blocks * tm - P)).reshape(n_blocks, tm)
    block, over = base // tm, base % tm
    run = jnp.concatenate([blocks[block], blocks[block + 1]], axis=1)
    for bit in range(tm.bit_length() - 1):
        run = jnp.where((over >> bit & 1)[:, None] == 1,
                        jnp.roll(run, -(1 << bit), axis=1), run)
    src = jnp.where(jnp.arange(tm) < left[:, None], run[:, :tm], -1)
    src = src.reshape(-1)

    h = grouped_matmul(x, w_up, tile_group, n_active, tm=tm, w_gate=w_gate,
                       src=src)
    y = grouped_matmul(h, w_down, tile_group, n_active, tm=tm)

    # pair -> its padded row: its sorted position, moved by its expert's
    # padding (row 0 where the pair is not here)
    shift = jnp.where(mine, (first_row - starts)[:, None], 0).sum(0)
    pair_row = jnp.where(here, place + shift, 0)
    per_pair = jnp.where(here[:, None], y[pair_row], 0).reshape(k, T, d)
    w = jnp.where(here.reshape(k, T), weights.T.astype(jnp.float32), 0.0)
    out = jnp.einsum("kt,ktd->td", w, per_pair.astype(jnp.float32))
    real = jnp.ones((T,), bool) if valid is None else valid
    zero_pairs = jnp.int32(0)
    if n_experts is not None:
        zero = (chosen >= n_experts) & real[:, None]
        w_zero = jnp.where(zero, weights.astype(jnp.float32), 0.0).sum(-1)
        out = out + w_zero[:, None] * x.astype(jnp.float32)
        zero_pairs = zero.sum().astype(jnp.int32)
    stats = {"pairs": here.sum().astype(jnp.int32),
             "experts_touched": (sizes > 0).sum().astype(jnp.int32),
             "max_expert_tokens": sizes.max().astype(jnp.int32),
             "tiles_active": n_active.astype(jnp.int32),
             "zero_pairs": zero_pairs,
             "rows": real.sum().astype(jnp.int32)}
    return out.astype(x.dtype), stats
