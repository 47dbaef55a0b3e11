"""Partition planner (component C2).

Reference capability (SURVEY.md C2; BASELINE.json north star): inspect the
model structure and device topology and emit a shard plan, automatically
choosing between data-parallel, tensor-parallel and FSDP-style execution
(BASELINE.json:8-11) so that a one-line ``AutoDistribute(model)`` runs
unmodified.

TPU-native realization: the plan is a ``jax.sharding.Mesh`` plus a pytree of
``PartitionSpec`` — GSPMD then inserts all collectives.  The planner is a
pure function ``(abstract params, mesh, policy) -> ShardPlan`` and is fully
unit-testable without devices.

Strategy catalogue (mirrors the reference's exercised configs):

- ``dp``        replicate params, shard batch on ``data``  (DDP analog)
- ``fsdp``      ZeRO-3: shard every param's largest divisible axis on the
                ``fsdp`` mesh axis; optimizer state inherits the same specs
- ``tp``        Megatron column/row splits on attention/MLP weights over the
                ``tensor`` axis, chosen by name-pattern rules
- ``tp_fsdp``   TP rules first, FSDP on what remains
- ``auto``      pick one of the above from model size vs per-chip HBM and
                mesh shape
- ``tuned``     cost-model-driven search over candidate factorizations
                (tune/ subsystem: enumerate -> score -> cache); falls
                back to the ``auto`` heuristic when the space is
                degenerate
"""

from __future__ import annotations

import dataclasses
import math
import re
from typing import Any, Callable, Mapping, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from . import topology as topo_mod

# ---------------------------------------------------------------------------
# Sharding rules
# ---------------------------------------------------------------------------

Axis = str | tuple[str, ...] | None


@dataclasses.dataclass(frozen=True)
class Rule:
    """Name-pattern sharding rule.

    ``pattern`` is a regex searched against the '/'-joined parameter path
    (e.g. ``"layers_3/attn/q_proj/kernel"``).  ``dim_axes`` assigns mesh
    axes to the *trailing* dimensions of the parameter: the last
    ``len(dim_axes)`` dims get the listed axes; leading dims are
    unsharded.  First matching rule wins.
    """

    pattern: str
    dim_axes: tuple[Axis, ...]

    def matches(self, path: str) -> bool:
        return re.search(self.pattern, path) is not None


# Megatron-style transformer rules (SURVEY.md C5): column-split the
# fan-out projections (QKV, MLP up/gate), row-split the fan-in
# projections (attention out, MLP down).  Embeddings vocab-split.
TRANSFORMER_RULES: tuple[Rule, ...] = (
    Rule(r"(q_proj|k_proj|v_proj|qkv|query|key|value|wq|wk|wv)/kernel", (None, "tensor")),
    Rule(r"(o_proj|out_proj|attn_out|wo|proj_out)/kernel", ("tensor", None)),
    Rule(r"(up_proj|gate_proj|fc1|wi|w1|w3|mlp_in)/kernel", (None, "tensor")),
    Rule(r"(down_proj|fc2|wo_mlp|w2|mlp_out)/kernel", ("tensor", None)),
    Rule(r"(embed|embedding|wte|tok_embed)[^/]*/(embedding|kernel)", ("tensor", None)),
    Rule(r"(lm_head|output_proj|unembed)/kernel", (None, "tensor")),
    # biases of column-split layers follow the split output dim
    Rule(r"(q_proj|k_proj|v_proj|qkv|up_proj|gate_proj|fc1|wi|w1|w3)/bias", ("tensor",)),
    # torch-bridge naming (models/torch_bridge.py): MHA weights keep the
    # TORCH [out, in] layout — packed qkv `in_w` [3d, d] column-splits
    # dim 0, `out_w` [d, d] row-splits its contraction (input) dim 1 —
    # while Linear kernels are transposed to flax [in, out] layout
    # (lin1 fan-out -> column, lin2 fan-in -> row).
    Rule(r"(sa|ca)\.in_w$", ("tensor", None)),
    Rule(r"(sa|ca)\.in_b$", ("tensor",)),
    Rule(r"(sa|ca)\.out_w$", (None, "tensor")),
    Rule(r"lin1\.kernel$", (None, "tensor")),
    Rule(r"lin1\.bias$", ("tensor",)),
    Rule(r"lin2\.kernel$", ("tensor", None)),
    # norms / scalars replicated
    Rule(r"(norm|ln|layernorm|rmsnorm|scale)", ()),
)

# MoE expert banks (models/moe.py): [.., E, d, f] einsum weights — the E
# dim (third-from-last, stable under nn.scan layer stacking) shards over
# the ``expert`` mesh axis (SURVEY.md §2.2 EP row); routers replicate.
MOE_RULES: tuple[Rule, ...] = (
    Rule(r"(experts?_(up|gate|down)|expert_bank|moe_w\d)[^/]*$", ("expert", None, None)),
    Rule(r"router/", ()),
)

# ep_tp (Mixtral-style EP x TP): experts on the ``expert`` axis AND each
# expert Megatron-split on ``tensor`` — fan-out banks [E, d, f] column-
# split the f dim, the fan-in bank [E, f, d] row-splits it; the down
# contraction then reduces over tensor (GSPMD psum), exactly the dense
# Megatron pattern per expert.
MOE_TP_RULES: tuple[Rule, ...] = (
    # fan-in first: 'down' banks and the w2 of the w1/w2/w3 convention
    # ([E, f, d]) row-split — contraction dim f on tensor
    Rule(r"(experts?_down|moe_w2)[^/]*$", ("expert", "tensor", None)),
    # fan-out ([E, d, f]) column-split — output dim f on tensor
    Rule(r"(experts?_(up|gate)|expert_bank|moe_w[13])[^/]*$",
         ("expert", None, "tensor")),
    # unknown-orientation banks: expert axis only (the MOE_RULES layout)
    Rule(r"moe_w\d[^/]*$", ("expert", None, None)),
    Rule(r"router/", ()),
)


# ---------------------------------------------------------------------------
# Plan
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class ShardPlan:
    """The planner's output: everything needed to jit a sharded step.

    ``opt_spec_tree`` (set when ``zero1=True``) is a params-structured
    PartitionSpec tree for the OPTIMIZER state only: each param's
    largest still-unsharded divisible dim additionally shards over the
    ``data`` axis (ZeRO-1 cross-replica weight-update sharding, arxiv
    2004.13336) while the params themselves keep ``param_specs``.
    """

    mesh: Mesh
    strategy: str
    param_specs: Any  # pytree of PartitionSpec, same structure as params
    batch_spec: P  # spec for the leading (batch) dim of inputs
    remat: bool = False
    zero1: bool = False
    opt_spec_tree: Any = None  # params-structured specs for opt state

    def param_shardings(self) -> Any:
        return jax.tree.map(
            lambda s: NamedSharding(self.mesh, s),
            self.param_specs,
            is_leaf=lambda x: isinstance(x, P),
        )

    def opt_shardings(self) -> Any:
        """NamedShardings for the optimizer-state specs (param specs
        when no distinct zero1 tree exists)."""
        specs = (self.opt_spec_tree if self.opt_spec_tree is not None
                 else self.param_specs)
        return jax.tree.map(
            lambda s: NamedSharding(self.mesh, s),
            specs,
            is_leaf=lambda x: isinstance(x, P),
        )

    def batch_sharding(self) -> NamedSharding:
        return NamedSharding(self.mesh, self.batch_spec)

    def describe(self) -> str:
        strat = self.strategy + ("+zero1" if self.zero1 else "")
        lines = [f"ShardPlan(strategy={strat}, mesh={dict(zip(self.mesh.axis_names, self.mesh.devices.shape))})"]
        flat = _flatten_with_paths(self.param_specs)
        opt_flat = (_flatten_with_paths(self.opt_spec_tree)
                    if self.opt_spec_tree is not None else None)
        for i, (path, spec) in enumerate(flat):
            line = f"  {path}: {spec}"
            if opt_flat is not None and opt_flat[i][1] != spec:
                line += f"  [opt: {opt_flat[i][1]}]"
            lines.append(line)
        return "\n".join(lines)


# ---------------------------------------------------------------------------
# Helpers
# ---------------------------------------------------------------------------


def _flatten_with_paths(tree: Any) -> list[tuple[str, Any]]:
    flat, _ = jax.tree_util.tree_flatten_with_path(
        tree, is_leaf=lambda x: isinstance(x, P)
    )
    out = []
    for keypath, leaf in flat:
        out.append((path_str(keypath), leaf))
    return out


def path_str(keypath: Sequence[Any]) -> str:
    parts = []
    for k in keypath:
        if hasattr(k, "key"):
            parts.append(str(k.key))
        elif hasattr(k, "idx"):
            parts.append(str(k.idx))
        elif hasattr(k, "name"):
            parts.append(str(k.name))
        else:
            parts.append(str(k))
    return "/".join(parts)


def _axis_size(axis: Axis, degrees: Mapping[str, int]) -> int:
    if axis is None:
        return 1
    if isinstance(axis, tuple):
        return math.prod(degrees.get(a, 1) for a in axis)
    return degrees.get(axis, 1)


def _norm_spec(dims: Sequence[Axis]) -> P:
    """Drop trailing unsharded dims so P(None) == P() comparisons hold."""
    dims = list(dims)
    while dims and dims[-1] is None:
        dims.pop()
    return P(*dims)


def _spec_from_rule(
    rule: Rule, shape: tuple[int, ...], degrees: Mapping[str, int]
) -> P | None:
    """Build a PartitionSpec from a rule, or None if shapes don't divide."""
    n = len(rule.dim_axes)
    if n > len(shape):
        return None
    dims: list[Axis] = [None] * (len(shape) - n) + list(rule.dim_axes)
    for d, ax in enumerate(dims):
        size = _axis_size(ax, degrees)
        if size > 1 and shape[d] % size != 0:
            return None  # indivisible — caller falls back
    return _norm_spec(dims)


def _fsdp_spec(
    shape: tuple[int, ...],
    degrees: Mapping[str, int],
    existing: P | None = None,
    fsdp_axes: tuple[str, ...] = ("fsdp",),
) -> P:
    """Shard the largest still-unsharded, divisible dim over the fsdp axes.

    ZeRO-3 pattern (SURVEY.md C6, PAPERS.md:5,7): parameters are stored
    sharded and all-gathered on use by GSPMD; optimizer state inherits the
    spec, giving ZeRO-1/2 for free.
    """
    size = math.prod(_axis_size(a, degrees) for a in fsdp_axes)
    if size <= 1:
        return existing or P()
    used: list[Axis] = list(existing) if existing is not None else [None] * len(shape)
    while len(used) < len(shape):
        used.append(None)
    # prefer the largest dim; tie-break on the first
    order = sorted(range(len(shape)), key=lambda d: -shape[d])
    for d in order:
        if used[d] is None and shape[d] % size == 0:
            used[d] = fsdp_axes if len(fsdp_axes) > 1 else fsdp_axes[0]
            return _norm_spec(used)
    return _norm_spec(used)  # nothing divisible — stays as-is


# ---------------------------------------------------------------------------
# Planner entry points
# ---------------------------------------------------------------------------

def _hbm_bytes(device_kind: str) -> int:
    return topo_mod.chip_spec(device_kind).hbm_bytes


# Parameter paths holding a scanned layer stack (leading [n_layers, ...]
# dim, models/transformer_core.py nn.scan) — the dim pipeline parallelism
# shards into stages.
PIPE_STACK_PATTERN = r"(^|/)layers/"


def param_spec_tree(
    abstract_params: Any,
    mesh: Mesh,
    strategy: str,
    rules: Sequence[Rule] = TRANSFORMER_RULES,
    fsdp_axes: tuple[str, ...] = ("fsdp",),
    pipe_stack_pattern: str = PIPE_STACK_PATTERN,
) -> Any:
    """Assign a PartitionSpec to every parameter by path+shape.

    Pure function over abstract shapes — the unit-testable core (SURVEY.md
    §7 phase 3).
    """
    degrees = topo_mod.mesh_degrees(mesh)
    use_tp = (strategy in ("tp", "tp_fsdp", "ep_tp")
              and degrees.get("tensor", 1) > 1)
    use_fsdp = (
        strategy in ("fsdp", "tp_fsdp", "ep_fsdp")
        and _axis_size(fsdp_axes, degrees) > 1
    )
    use_ep = degrees.get("expert", 1) > 1
    pipe = degrees.get("pipe", 1)

    def assign(keypath, leaf):
        shape = tuple(getattr(leaf, "shape", ()))
        path = path_str(keypath)
        spec: P | None = None
        if (
            pipe > 1
            and re.search(pipe_stack_pattern, path)
            and shape
            and shape[0] % pipe == 0
        ):
            # leading layer-stack dim -> pipeline stages (parallel/
            # pipeline.py); under pipe x tp the trailing dims keep their
            # Megatron col/row split (the stage-local TP composition)
            entries: list[Axis] = [None] * len(shape)
            if use_tp:
                for rule in rules:
                    if rule.matches(path):
                        tp = _spec_from_rule(rule, shape, degrees)
                        if tp is not None:
                            entries = list(tp)
                            entries += [None] * (len(shape) - len(entries))
                        break
            if entries[0] is None:
                entries[0] = "pipe"
            spec = _norm_spec(entries)
        if spec is None and use_ep:
            for rule in (MOE_TP_RULES if use_tp else MOE_RULES):
                if rule.matches(path):
                    spec = _spec_from_rule(rule, shape, degrees)
                    break
        if spec is None and use_tp:
            for rule in rules:
                if rule.matches(path):
                    spec = _spec_from_rule(rule, shape, degrees)
                    break
        if use_fsdp and len(shape) >= 1:
            spec = _fsdp_spec(shape, degrees, existing=spec, fsdp_axes=fsdp_axes)
        return spec if spec is not None else P()

    tree = jax.tree_util.tree_map_with_path(assign, abstract_params)
    if use_tp and not any(
        _spec_uses_axis(s, "tensor")
        for s in jax.tree.leaves(tree, is_leaf=lambda x: isinstance(x, P))
    ):
        import warnings

        warnings.warn(
            f"strategy {strategy!r} requests tensor parallelism but ZERO "
            "parameters matched a tensor rule: the 'tensor' mesh axis "
            "will sit unused and every parameter is replicated across "
            "it (silent tp degradation).  Models with nonstandard param "
            "names — e.g. hand-written modules or from_torch bridges of "
            "custom architectures — need custom rules: pass "
            "AutoDistribute(..., rules=(planner.Rule(r'my_proj/kernel', "
            "(None, 'tensor')), ...)) mapping your param paths to "
            "column/row splits (see planner.TRANSFORMER_RULES).",
            stacklevel=2,
        )
    return tree


def _spec_uses_axis(spec: P, axis: str) -> bool:
    for entry in spec:
        if entry == axis:
            return True
        if isinstance(entry, (tuple, list)) and axis in entry:
            return True
    return False


def zero1_spec_tree(
    abstract_params: Any,
    mesh: Mesh,
    param_specs: Any,
) -> Any:
    """ZeRO-1 optimizer-state spec tree (arxiv 2004.13336).

    Per param: the largest still-unsharded divisible dim additionally
    shards over the ``data`` axis, so the optimizer moments (and the
    weight update itself) live 1/dp-th per replica while the params keep
    their own specs.  Indivisible leaves keep the param spec — their
    moments stay replicated and are charged honestly by the memory
    model.  Pure shape math; ``mesh`` may be a degrees mapping.
    """
    degrees = topo_mod.mesh_degrees(mesh)
    if degrees.get("data", 1) <= 1:
        return param_specs  # no data replicas — nothing to shard over
    spec_flat, treedef = jax.tree_util.tree_flatten(
        param_specs, is_leaf=lambda x: isinstance(x, P)
    )
    leaves = jax.tree.leaves(abstract_params)
    if len(spec_flat) != len(leaves):
        raise ValueError(
            f"param_specs ({len(spec_flat)} leaves) does not match "
            f"abstract_params ({len(leaves)} leaves)"
        )
    out = []
    for spec, leaf in zip(spec_flat, leaves):
        shape = tuple(getattr(leaf, "shape", ()))
        if not shape:
            out.append(spec)
            continue
        out.append(_fsdp_spec(shape, degrees, existing=spec,
                              fsdp_axes=("data",)))
    return jax.tree_util.tree_unflatten(treedef, out)


def batch_partition_spec(mesh: Mesh) -> P:
    """Batch dim sharded over every data-carrying axis present in the mesh.

    The ``expert`` axis carries batch too (EP groups double as DP ranks,
    DeepSpeed-MoE style): tokens ride the expert axis until the MoE
    dispatch all_to_all regroups them by expert.
    """
    degrees = topo_mod.mesh_degrees(mesh)
    axes = tuple(a for a in ("data", "fsdp", "expert") if degrees.get(a, 1) > 1)
    return P(axes) if axes else P(None)


def tree_bytes(abstract_params: Any) -> int:
    leaves = jax.tree.leaves(abstract_params)
    total = 0
    for leaf in leaves:
        shape = getattr(leaf, "shape", ())
        dtype = np.dtype(getattr(leaf, "dtype", np.float32))
        total += math.prod(shape) * dtype.itemsize if shape else dtype.itemsize
    return total


def _expert_banks(abstract_params: Any) -> list[tuple[str, Any]]:
    """(path, leaf) of every MoE expert bank ([..., E, d, f], models/moe.py)."""
    flat, _ = jax.tree_util.tree_flatten_with_path(abstract_params)
    return [
        (path_str(keypath), leaf)
        for keypath, leaf in flat
        if len(tuple(getattr(leaf, "shape", ()))) >= 3
        and re.search(MOE_RULES[0].pattern, path_str(keypath))
    ]


def detect_expert_count(abstract_params: Any) -> int | None:
    """Number of experts E if the model has MoE expert banks, else None.

    E is third-from-last in the bank shape, stable under the scanned
    [n_layers, ...] stacking.
    """
    banks = _expert_banks(abstract_params)
    return int(banks[0][1].shape[-3]) if banks else None


def tp_applicable(abstract_params: Any, rules: Sequence[Rule]) -> bool:
    """True if any rule would actually shard a dim of this model's params
    on the 'tensor' axis (replication/bias rules don't count)."""
    paths = [p for p, _ in _flatten_with_paths(
        jax.tree.map(lambda x: P(), abstract_params))]
    tp_rules = [
        r for r in rules
        if any(
            ax == "tensor" or (isinstance(ax, tuple) and "tensor" in ax)
            for ax in r.dim_axes
        )
    ]
    return any(r.matches(p) for p in paths for r in tp_rules)


def choose_strategy(
    abstract_params: Any,
    topo: topo_mod.Topology,
    rules: Sequence[Rule] = TRANSFORMER_RULES,
    state_factor: float = 4.0,
) -> tuple[str, dict[str, int]]:
    """Auto policy: pick (strategy, mesh axis degrees) from model size vs
    HBM and whether TP rules apply to this model's parameter names.

    Heuristics (documented, deliberately simple — SURVEY.md §7 'hard parts'
    #1 says start rule-based and fail loudly):

    - 1 device -> no-op DP (identity path, BASELINE.json:7)
    - params + grads + adam state (~4x param bytes in fp32 master) fit in
      60% of one chip's HBM -> plain DP (cheapest collectives)
    - else if any TP rule matches and a tensor degree <= 8 divides the
      device count -> tp_fsdp (TP inside, FSDP across)
    - else -> FSDP over all devices
    """
    n = topo.num_devices
    if n == 1:
        return "dp", {"data": 1}
    pbytes = tree_bytes(abstract_params)
    # params + grads + 2 adam moments; state_factor scales param bytes to
    # full train-state bytes (4.0 for uniform fp32; training/precision.py
    # supplies the mixed-precision value, e.g. 2.5 for fp32 master + bf16
    # grads/moments)
    train_state_bytes = state_factor * pbytes
    e_count = detect_expert_count(abstract_params)
    if e_count:
        # MoE model: put the expert dim on its own axis so dispatch rides
        # one all_to_all instead of replicating every expert everywhere.
        e = math.gcd(n, e_count)
        if e > 1:
            rest = n // e
            # per-device bytes: only the expert banks shard under 'ep';
            # dense params stay replicated unless fsdp joins in.
            expert_b = sum(
                math.prod(leaf.shape)
                * np.dtype(getattr(leaf, "dtype", np.float32)).itemsize
                for _, leaf in _expert_banks(abstract_params)
            )
            dense_b = pbytes - expert_b
            per_device = state_factor * (dense_b + expert_b / e)
            if per_device < 0.6 * _hbm_bytes(topo.device_kind):
                return "ep", {"expert": e, "data": rest}
            # Memory-tight: the fsdp axis must be real (>=2) or dense
            # params stay replicated — shrink the expert degree once to
            # free devices for it (e divides n, so one shrink to a proper
            # divisor always leaves n // e >= 2).
            if n // e < 2:
                e = max(d for d in range(1, e) if e % d == 0)
            if e > 1:
                return "ep_fsdp", {"expert": e, "fsdp": n // e}
            # can't keep both axes nontrivial -> fall through to fsdp/dp
    if train_state_bytes < 0.6 * _hbm_bytes(topo.device_kind):
        return "dp", {"data": n}
    if tp_applicable(abstract_params, rules):
        for t in (8, 4, 2):
            # both axes must stay nontrivial: n == t would leave a dead
            # degree-1 fsdp axis (spurious PL004 downstream)
            if n % t == 0 and n // t >= 2:
                return "tp_fsdp", {"fsdp": n // t, "tensor": t}
    # defensive: a degenerate topology must never reach the fsdp
    # catch-all — a {"fsdp": 1} mesh is a dead axis, not a strategy
    if n == 1:
        return "dp", {"data": 1}
    return "fsdp", {"fsdp": n}


def spec_axes(spec: P) -> set[str]:
    """Mesh axis names a PartitionSpec actually uses."""
    out: set[str] = set()
    for entry in spec:
        if entry is None:
            continue
        for ax in (entry if isinstance(entry, (tuple, list)) else (entry,)):
            if ax:
                out.add(ax)
    return out


# pre-analysis/ name; tune/ and external callers may still use it
_spec_axes = spec_axes


# ---------------------------------------------------------------------------
# Reshard slicing (sharded-checkpoint support, training/shards.py)
# ---------------------------------------------------------------------------


def spec_to_json(spec: P) -> list:
    """A PartitionSpec as a JSON-serializable list (axis name, list of
    axis names, or None per dim) — the on-disk form a sharded-checkpoint
    manifest records so a restore under a different mesh can re-derive
    the writer's slicing."""
    out: list = []
    for entry in spec:
        if isinstance(entry, (tuple, list)):
            out.append(list(entry))
        else:
            out.append(entry)
    return out


def spec_from_json(dims: Sequence[Any]) -> P:
    """Inverse of :func:`spec_to_json`."""
    return P(*[tuple(d) if isinstance(d, list) else d for d in dims])


def leaf_shard_slices(
    shape: Sequence[int],
    spec: P,
    degrees: Mapping[str, int],
) -> list[tuple[tuple[int, int], ...]]:
    """The unique shard slices of one leaf under ``spec`` on a mesh with
    the given axis ``degrees`` — pure index math, no devices.

    Each element is a per-dim ``(start, stop)`` tuple; together they tile
    the global shape exactly (replicas collapsed — this is the replica-0
    set a sharded checkpoint writes and the coverage a restore verifies
    against).  A dim whose sharding degree does not divide it is treated
    as unsharded, matching the planner's divisibility rules.
    """
    per_dim: list[list[tuple[int, int]]] = []
    for d, size in enumerate(shape):
        axes = spec[d] if d < len(spec) else None
        deg = _axis_size(axes, degrees) if axes else 1
        if deg <= 1 or size % deg != 0:
            per_dim.append([(0, int(size))])
            continue
        chunk = size // deg
        per_dim.append([(i * chunk, (i + 1) * chunk) for i in range(deg)])
    out: list[tuple[tuple[int, int], ...]] = [()]
    for choices in per_dim:
        out = [prefix + (c,) for prefix in out for c in choices]
    return sorted(out)


def expected_collective_bytes(
    plan: ShardPlan,
    abstract_params: Any,
    *,
    grad_dtype: Any = np.float32,
    grad_accum: int = 1,
) -> dict:
    """Analytic per-step collective traffic implied by a ShardPlan.

    Derived purely from the plan + abstract param shapes — the expected
    cost of the collectives GSPMD inserts for the *parameter/gradient*
    path, per device per optimizer step:

    - ``grad_allreduce``: gradients of params replicated across a
      batch-carrying axis (dp; dense params under ep) are all-reduced
      over it.  Payload = the param's (possibly tp-sharded) grad bytes.
    - ``param_allgather``: ZeRO-3 params sharded on a batch-carrying
      axis (fsdp) are all-gathered on use — counted twice (forward +
      backward re-gather, the remat-compatible schedule).
    - ``grad_reduce_scatter``: the matching gradient shard reduction.

    With ``plan.zero1`` (cross-replica weight-update sharding, arxiv
    2004.13336) two more categories appear for the leaves whose
    ``opt_spec_tree`` spec shards over axes the param spec does not:

    - ``zero1_grad_reduce_scatter``: the grad all-reduce over those
      axes is REPLACED by a reduce-scatter onto the optimizer shard
      (wire ``(n-1)/n`` instead of ``2(n-1)/n`` of payload);
    - ``zero1_param_allgather``: the freshly updated params are
      all-gathered once per optimizer step (NOT per accumulation
      slice — the update runs once, after accumulation).

    Wire bytes use the ring formulas (allreduce ``2(n-1)/n``, gather/
    scatter ``(n-1)/n`` of payload).  Gradient-path collectives run once
    per accumulation slice, so everything except the zero1 param
    all-gather scales by ``grad_accum``.

    Activation-shaped traffic (tp activation all-reduces, MoE dispatch
    all_to_all, pipeline stage p2p) depends on model internals invisible
    to abstract param shapes; it is reported under ``model_dependent``
    as explicit unknowns rather than silently omitted.  Cross-check the
    whole estimate against XLA's measured ``bytes_accessed``
    (utils.profiling.compiled_cost / obs.comms.crosscheck).
    """
    degrees = topo_mod.mesh_degrees(plan.mesh)
    batch_axes = [
        a for a in _spec_axes(plan.batch_spec) if degrees.get(a, 1) > 1
    ]
    grad_itemsize = np.dtype(grad_dtype).itemsize

    specs = jax.tree.leaves(plan.param_specs,
                            is_leaf=lambda x: isinstance(x, P))
    leaves = jax.tree.leaves(abstract_params)
    if len(specs) != len(leaves):
        raise ValueError(
            f"param_specs ({len(specs)} leaves) does not match "
            f"abstract_params ({len(leaves)} leaves)"
        )

    zero1_active = bool(getattr(plan, "zero1", False))
    opt_specs = None
    if zero1_active and getattr(plan, "opt_spec_tree", None) is not None:
        opt_specs = jax.tree.leaves(plan.opt_spec_tree,
                                    is_leaf=lambda x: isinstance(x, P))
        if len(opt_specs) != len(specs):
            raise ValueError(
                f"opt_spec_tree ({len(opt_specs)} leaves) does not match "
                f"param_specs ({len(specs)} leaves)"
            )

    cats = {
        "grad_allreduce": {"payload_bytes": 0.0, "wire_bytes": 0.0},
        "param_allgather": {"payload_bytes": 0.0, "wire_bytes": 0.0},
        "grad_reduce_scatter": {"payload_bytes": 0.0, "wire_bytes": 0.0},
    }
    if opt_specs is not None:
        cats["zero1_grad_reduce_scatter"] = {
            "payload_bytes": 0.0, "wire_bytes": 0.0}
        cats["zero1_param_allgather"] = {
            "payload_bytes": 0.0, "wire_bytes": 0.0}
    for i, (spec, leaf) in enumerate(zip(specs, leaves)):
        shape = tuple(getattr(leaf, "shape", ()))
        count = math.prod(shape) if shape else 1
        p_itemsize = np.dtype(getattr(leaf, "dtype", np.float32)).itemsize
        axes_used = spec_axes(spec)
        # axes the zero1 opt spec adds beyond the param spec: the grad
        # all-reduce over them becomes RS + (post-update) param AG
        z1_deg = 1
        if opt_specs is not None:
            for a in spec_axes(opt_specs[i]) - axes_used:
                z1_deg *= degrees.get(a, 1)
        # fraction of the param each device holds after non-batch-axis
        # sharding (tensor / pipe / expert)
        f_other = 1.0
        for a in axes_used:
            if a not in batch_axes:
                f_other /= degrees.get(a, 1)
        # 'expert'-sharded banks communicate via the token all_to_all
        # (model_dependent below), not via param gather/grad reduce —
        # exclude the expert axis from both paths for those leaves.
        reduce_deg = 1
        zero3_deg = 1
        z1_axes = (spec_axes(opt_specs[i]) - axes_used
                   if opt_specs is not None else set())
        for a in batch_axes:
            if a == "expert" and a in axes_used:
                continue
            if a in axes_used:
                zero3_deg *= degrees[a]
            elif a in z1_axes:
                pass  # replaced by the zero1 RS/AG below
            else:
                reduce_deg *= degrees[a]
        grad_payload = count * f_other / max(1, zero3_deg) * grad_itemsize
        if z1_deg > 1:
            cats["zero1_grad_reduce_scatter"]["payload_bytes"] += (
                grad_payload)
            cats["zero1_grad_reduce_scatter"]["wire_bytes"] += (
                (z1_deg - 1) / z1_deg * grad_payload
            )
            ag = count * f_other / max(1, zero3_deg) * p_itemsize
            cats["zero1_param_allgather"]["payload_bytes"] += ag
            cats["zero1_param_allgather"]["wire_bytes"] += (
                (z1_deg - 1) / z1_deg * ag
            )
        if reduce_deg > 1:
            # any residual reduction (e.g. expert for dense params under
            # ep) operates on the zero1 shard when one exists
            payload = grad_payload / z1_deg
            cats["grad_allreduce"]["payload_bytes"] += payload
            cats["grad_allreduce"]["wire_bytes"] += (
                2 * (reduce_deg - 1) / reduce_deg * payload
            )
        if zero3_deg > 1:
            ag = count * f_other * p_itemsize * 2  # fwd + bwd re-gather
            rs = count * f_other * grad_itemsize
            cats["param_allgather"]["payload_bytes"] += ag
            cats["param_allgather"]["wire_bytes"] += (
                (zero3_deg - 1) / zero3_deg * ag
            )
            cats["grad_reduce_scatter"]["payload_bytes"] += rs
            cats["grad_reduce_scatter"]["wire_bytes"] += (
                (zero3_deg - 1) / zero3_deg * rs
            )
    for name, c in cats.items():
        # the zero1 param all-gather happens once per optimizer step,
        # after accumulation — it does not repeat per slice
        k = 1 if name == "zero1_param_allgather" else grad_accum
        c["payload_bytes"] = int(c["payload_bytes"] * k)
        c["wire_bytes"] = int(c["wire_bytes"] * k)
    model_dependent = {}
    if degrees.get("tensor", 1) > 1:
        model_dependent["tp_activation_allreduce"] = None
    if degrees.get("expert", 1) > 1:
        model_dependent["ep_dispatch_all_to_all"] = None
    if degrees.get("pipe", 1) > 1:
        model_dependent["pipe_stage_p2p"] = None
    if degrees.get("seq", 1) > 1:
        model_dependent["cp_kv_exchange"] = None
    return {
        "strategy": plan.strategy,
        "mesh": dict(degrees),
        "grad_accum": grad_accum,
        "grad_dtype": str(np.dtype(grad_dtype)),
        "per_device": cats,
        "total_wire_bytes": int(sum(c["wire_bytes"] for c in cats.values())),
        "model_dependent": model_dependent,
        "assumptions": [
            "ring collectives: allreduce 2(n-1)/n, gather/scatter (n-1)/n",
            "ZeRO-3 params all-gathered twice per step (fwd + bwd)",
            "gradient-path collectives repeat per grad_accum slice",
            "activation-shaped traffic (tp/ep/pipe/cp) is model-dependent"
            " and reported as unknown, not zero",
        ],
    }


def make_plan(
    abstract_params: Any,
    *,
    mesh: Mesh | None = None,
    strategy: str = "auto",
    rules: Sequence[Rule] = TRANSFORMER_RULES,
    devices: Sequence[jax.Device] | None = None,
    remat: bool | None = None,
    seq: int = 1,
    pipe: int = 1,
    state_factor: float = 4.0,
    tune_policy: Any = None,
    zero1: bool = False,
) -> ShardPlan:
    """The planner: abstract params + topology -> ShardPlan.

    ``abstract_params`` is any pytree of objects with ``.shape``/``.dtype``
    (e.g. the output of ``jax.eval_shape``).  If ``mesh`` is given the
    strategy is applied on it as-is; otherwise the mesh is built from the
    chosen/requested strategy.  ``pipe`` > 1 adds a pipeline axis; layer
    stacks shard their leading dim onto it (parallel/pipeline.py).

    ``strategy='tuned'`` hands the choice to the tune/ subsystem
    (enumerate candidate factorizations, rank by the analytic cost
    model, cache the decision); ``tune_policy`` is an optional
    ``tune.TunePolicy`` refining the search (batch size, grad-accum
    choices, cache on/off).  Falls back to the ``auto`` heuristic when
    the candidate space is degenerate (e.g. 1 device).

    ``zero1=True`` reshards the optimizer state over the ``data`` axis
    (ZeRO-1 / cross-replica weight-update sharding, arxiv 2004.13336):
    the plan gains an ``opt_spec_tree`` distinct from ``param_specs``,
    and the trainer's update path reduce-scatters grads onto the
    optimizer shard and all-gathers fresh params.  A no-op when the
    mesh has no nontrivial ``data`` axis.  Under ``strategy='tuned'``
    the tuner may also pick a zero1 variant itself.
    """
    known = ("auto", "tuned", "dp", "fsdp", "tp", "tp_fsdp", "ep",
             "ep_fsdp", "ep_tp")
    if strategy not in known:
        raise ValueError(f"Unknown strategy {strategy!r}; expected one of {known}")
    if pipe > 1 and strategy in ("ep", "ep_fsdp", "ep_tp"):
        raise ValueError(
            "pipeline parallelism composes with dp/fsdp/tp (v2); "
            f"strategy {strategy!r} + pipe={pipe} is not supported"
        )
    topo = topo_mod.detect(devices)
    resolved = strategy
    if mesh is None:
        n = topo.num_devices
        if seq > 1 and pipe > 1:
            raise ValueError(
                "seq-parallel + pipeline in one plan is a design "
                "constraint (both are manual-collective regions); raise "
                "microbatches for per-stage memory, or use seq without "
                "pipe — README strategy-composition matrix"
            )
        if pipe > 1:
            if n % pipe:
                raise ValueError(
                    f"pipeline degree {pipe} does not divide {n} devices"
                )
            n //= pipe
        if seq > 1:
            if n % seq:
                raise ValueError(
                    f"seq-parallel degree {seq} does not divide "
                    f"{n} devices"
                )
            n //= seq
        if strategy in ("auto", "tuned"):
            sub_topo = dataclasses.replace(topo, num_devices=n)
            if strategy == "tuned":
                from . import tune as tune_mod

                result = tune_mod.tune(
                    abstract_params, sub_topo, rules=rules,
                    policy=tune_policy
                    or tune_mod.TunePolicy(state_factor=state_factor),
                )
                resolved, degrees = result.strategy, dict(result.degrees)
                zero1 = zero1 or bool(getattr(result, "zero1", False))
            else:
                resolved, degrees = choose_strategy(
                    abstract_params, sub_topo, rules,
                    state_factor=state_factor,
                )
            if pipe > 1 and resolved in ("ep", "ep_fsdp"):
                import warnings

                warnings.warn(
                    f"{strategy} strategy chose {resolved!r} but pipeline "
                    f"parallelism does not compose with expert parallelism "
                    f"(README strategy-composition matrix); falling back "
                    f"to 'fsdp' — the expert banks shard on the fsdp axis "
                    f"instead of having their own all_to_all dispatch",
                    stacklevel=2,
                )
                resolved, degrees = "fsdp", {"fsdp": n}
        elif strategy == "dp":
            degrees = {"data": n}
        elif strategy == "fsdp":
            degrees = {"fsdp": n}
        elif strategy == "tp":
            degrees = {"tensor": n}
        elif strategy == "tp_fsdp":
            t = min(8, n)
            while n % t:
                t //= 2
            # keep both axes nontrivial when possible (8 devs -> 4x2 not 8x1)
            while t > 2 and n // t < 2:
                t //= 2
            degrees = {"fsdp": n // t, "tensor": t}
        elif strategy in ("ep", "ep_fsdp", "ep_tp"):
            e_count = detect_expert_count(abstract_params)
            if not e_count:
                raise ValueError(
                    "strategy 'ep' needs MoE expert banks "
                    "(parameters matching MOE_RULES, e.g. experts_up); "
                    "none found in this model"
                )
            e = math.gcd(n, e_count)
            if e == 1 and n > 1:
                raise ValueError(
                    f"strategy {strategy!r}: gcd(n_devices={n}, "
                    f"n_experts={e_count}) == 1 — no expert axis is "
                    "possible on this device count; use fsdp/dp or change "
                    "the device count / expert count"
                )
            if strategy == "ep_tp":
                # keep room for a nontrivial tensor axis: halve the expert
                # degree (still divides n and e_count) until >=2 devices
                # remain for tensor
                rem = n // e
                while rem < 2 and e > 1 and e % 2 == 0:
                    e //= 2
                    rem = n // e
                if rem < 2 and n > 1:
                    import warnings

                    warnings.warn(
                        f"strategy 'ep_tp': {n} devices leave no room for "
                        f"a tensor axis next to expert={e} — degenerating "
                        f"to pure 'ep' (no per-expert Megatron split)",
                        stacklevel=2,
                    )
                t = min(8, rem)
                while rem % t:
                    t //= 2
                degrees = {"expert": e, "tensor": t, "data": rem // t}
            else:
                degrees = {"expert": e,
                           ("data" if strategy == "ep" else "fsdp"): n // e}
        else:
            raise ValueError(f"Unknown strategy {strategy!r}")
        if seq > 1:
            degrees["seq"] = seq
        if pipe > 1:
            degrees["pipe"] = pipe
        mesh = topo_mod.build_mesh(devices=devices, **degrees)
    else:
        if pipe > 1 and topo_mod.mesh_degrees(mesh).get("pipe", 1) != pipe:
            raise ValueError(
                f"pipe={pipe} conflicts with the explicit mesh "
                f"(its 'pipe' axis is "
                f"{topo_mod.mesh_degrees(mesh).get('pipe', 1)})"
            )
        if seq > 1 and topo_mod.mesh_degrees(mesh).get("seq", 1) != seq:
            raise ValueError(
                f"seq_parallel={seq} conflicts with the explicit mesh "
                f"(its 'seq' axis is {topo_mod.mesh_degrees(mesh).get('seq', 1)}); "
                "build the mesh with seq=<degree> or drop seq_parallel"
            )
        if strategy in ("auto", "tuned"):
            # an explicit mesh fixes every degree — nothing to tune
            d = topo_mod.mesh_degrees(mesh)
            if d.get("expert", 1) > 1:
                if d.get("tensor", 1) > 1:
                    resolved = "ep_tp"
                else:
                    resolved = "ep_fsdp" if d.get("fsdp", 1) > 1 else "ep"
            elif d.get("tensor", 1) > 1 and d.get("fsdp", 1) > 1:
                resolved = "tp_fsdp"
            elif d.get("tensor", 1) > 1:
                resolved = "tp"
            elif d.get("fsdp", 1) > 1:
                resolved = "fsdp"
            else:
                resolved = "dp"

    param_specs = param_spec_tree(abstract_params, mesh, resolved, rules)
    degrees_final = topo_mod.mesh_degrees(mesh)
    if resolved in ("tp", "tp_fsdp", "ep_tp") and degrees_final.get(
            "tensor", 1) > 1:
        sharded = any(
            "tensor" in (ax for dim in spec for ax in
                         (dim if isinstance(dim, tuple) else (dim,)) if ax)
            for _, spec in _flatten_with_paths(param_specs)
        )
        if not sharded:
            import warnings

            warnings.warn(
                f"Strategy {resolved!r} requested a tensor axis of "
                f"{degrees_final['tensor']} but no parameter matched any TP "
                "rule — the model will run unsharded on that axis. Pass "
                "custom rules= matching your parameter names.",
                stacklevel=2,
            )
    if remat is None:
        remat = resolved in ("fsdp", "tp_fsdp", "ep_fsdp")
        if not remat:
            # Replicated params (dp/tp/ep): turn checkpointing on when
            # the per-device train state (params+grads+2 adam moments,
            # fp32, after tensor/expert/pipe sharding) eats half a chip's
            # HBM — activations would not fit otherwise.
            pb = tree_bytes(abstract_params)
            e_deg = degrees_final.get("expert", 1)
            if e_deg > 1:
                eb = sum(
                    math.prod(leaf.shape)
                    * np.dtype(getattr(leaf, "dtype", np.float32)).itemsize
                    for _, leaf in _expert_banks(abstract_params)
                )
                pb = (pb - eb) + eb // e_deg
            pb //= max(1, degrees_final.get("tensor", 1))
            pb //= max(1, degrees_final.get("pipe", 1))
            remat = state_factor * pb > 0.5 * _hbm_bytes(topo.device_kind)
    opt_spec_tree = None
    if zero1:
        opt_spec_tree = zero1_spec_tree(abstract_params, mesh, param_specs)
        if degrees_final.get("data", 1) <= 1:
            # no data axis to shard over: the plan is honest about being
            # a no-op (opt state follows params) but keeps the flag off
            # so downstream paths don't pay the branch
            zero1 = False
            opt_spec_tree = None
    return ShardPlan(
        mesh=mesh,
        strategy=resolved,
        param_specs=param_specs,
        batch_spec=batch_partition_spec(mesh),
        remat=remat,
        zero1=zero1,
        opt_spec_tree=opt_spec_tree,
    )
