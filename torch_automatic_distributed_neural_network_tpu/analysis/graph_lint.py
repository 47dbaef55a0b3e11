"""Graph lint: trace the jitted step to a closed jaxpr and walk it (GL00x).

Trace only — ``jax.make_jaxpr`` runs the Python of the step function
under abstract values and never invokes XLA, so this layer is cheap
enough to run as a preflight on every Trainer start.

The collective inventory covers the *explicit* collectives visible in
the jaxpr — the manual ``shard_map``/``pmap`` regions (ring attention,
pipeline p2p, MoE dispatch, megatron-sp gathers).  GSPMD-inserted
collectives live below the jaxpr (XLA's SPMD partitioner runs at
compile time), so the cross-check direction is: any explicit collective
over a mesh axis where the plan's analytic model
(``planner.expected_collective_bytes``) predicts no traffic of that
shape is an implicit reshard the planner did not ask for → GL002.
"""

from __future__ import annotations

import math
from typing import Any, Iterator, Mapping

from .. import planner as planner_mod
from .. import topology as topo_mod
from . import ERROR, WARN, Finding

# jaxpr primitive name -> collective kind the allowance table keys on.
COLLECTIVE_KINDS: dict[str, str] = {
    "all_gather": "gather",
    "all_gather_invariant": "gather",
    "psum": "reduce",
    "psum2": "reduce",
    # what a psum traces to under shard_map's default check_vma=True
    "psum_invariant": "reduce",
    "pmax": "reduce",
    "pmin": "reduce",
    "reduce_scatter": "scatter",
    "psum_scatter": "scatter",
    "all_to_all": "a2a",
    "ppermute": "permute",
    "pshuffle": "permute",
}

# Host-side-effect primitives: each one is a device->host sync in the
# middle of the step (and keeps XLA from fusing across it).
HOST_EFFECT_PRIMS = frozenset({
    "debug_callback", "debug_print", "pure_callback", "io_callback",
    "callback", "outside_call", "host_callback",
})


def trace_step(fn: Any, *args: Any, **kwargs: Any):
    """Trace ``fn`` to a ClosedJaxpr from abstract (or concrete) args —
    the no-compile entry the preflight uses."""
    import jax

    return jax.make_jaxpr(fn)(*args, **kwargs)


def _jaxpr_of(obj: Any):
    """Unwrap ClosedJaxpr -> Jaxpr; pass Jaxpr through; else None."""
    if hasattr(obj, "jaxpr") and hasattr(obj, "consts"):
        return obj.jaxpr
    if hasattr(obj, "eqns") and hasattr(obj, "invars"):
        return obj
    return None


def iter_eqns(closed: Any) -> Iterator[Any]:
    """Every equation in a (closed) jaxpr, recursing into sub-jaxprs
    carried in eqn params (pjit/scan/cond/while/remat/shard_map/...)."""
    jaxpr = _jaxpr_of(closed)
    if jaxpr is None:
        return
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            stack = [v]
            while stack:
                item = stack.pop()
                sub = _jaxpr_of(item)
                if sub is not None:
                    yield from iter_eqns(sub)
                elif isinstance(item, (list, tuple)):
                    stack.extend(item)


def _axis_names(eqn: Any) -> tuple[str, ...]:
    """Mesh axis names a collective eqn operates over."""
    for key in ("axis_name", "axes", "axis_names"):
        if key in eqn.params:
            v = eqn.params[key]
            if isinstance(v, (tuple, list, frozenset, set)):
                return tuple(str(a) for a in v)
            return (str(v),)
    return ()


def _out_bytes(eqn: Any) -> int:
    import numpy as np

    total = 0
    for v in eqn.outvars:
        aval = getattr(v, "aval", None)
        shape = tuple(getattr(aval, "shape", ()))
        try:
            itemsize = np.dtype(getattr(aval, "dtype", np.float32)).itemsize
        except TypeError:
            itemsize = 4
        total += (math.prod(shape) if shape else 1) * itemsize
    return total


def collective_inventory(closed: Any) -> list[dict]:
    """Aggregate the explicit collectives in a traced step.

    Returns one record per (primitive, axes) pair:
    ``{"prim", "kind", "axes", "count", "bytes"}`` — ``bytes`` is the
    summed output-buffer size (per trace; a collective inside ``scan``
    counts once, its per-step cost is count × loop length, which the
    jaxpr does not expose — treat bytes as a lower bound).
    """
    agg: dict[tuple[str, tuple[str, ...]], dict] = {}
    for eqn in iter_eqns(closed):
        name = eqn.primitive.name
        kind = COLLECTIVE_KINDS.get(name)
        if kind is None:
            continue
        axes = _axis_names(eqn)
        key = (name, axes)
        rec = agg.setdefault(
            key, {"prim": name, "kind": kind, "axes": axes,
                  "count": 0, "bytes": 0})
        rec["count"] += 1
        rec["bytes"] += _out_bytes(eqn)
    return list(agg.values())


def _allowed_axes(plan: planner_mod.ShardPlan,
                  abstract_params: Any | None) -> dict[str, set[str]]:
    """Per-collective-kind mesh axes the plan's analytic comms model
    accounts for (either as param/grad traffic or as a declared
    ``model_dependent`` unknown in ``expected_collective_bytes``)."""
    import jax
    from jax.sharding import PartitionSpec as P

    degrees = topo_mod.mesh_degrees(plan.mesh)

    def live(*axes: str) -> set[str]:
        return {a for a in axes if degrees.get(a, 1) > 1}

    batch_axes = {
        a for a in planner_mod.spec_axes(plan.batch_spec)
        if degrees.get(a, 1) > 1
    }
    param_axes: set[str] = set()
    for spec in jax.tree.leaves(plan.param_specs,
                                is_leaf=lambda x: isinstance(x, P)):
        param_axes |= planner_mod.spec_axes(spec)
    # ZeRO-3 axes: batch-carrying axes that also shard params — the ones
    # the model predicts param all-gather / grad reduce-scatter over.
    zero3 = {a for a in batch_axes & param_axes if a != "expert"}
    # ZeRO-1 axes: axes the opt_spec_tree shards beyond the param specs —
    # the plan's zero1 RS (grads onto the opt shard) and AG (fresh
    # params) ride these, so they're accounted traffic, not reshards.
    zero1: set[str] = set()
    if getattr(plan, "zero1", False) and getattr(
            plan, "opt_spec_tree", None) is not None:
        for spec in jax.tree.leaves(plan.opt_spec_tree,
                                    is_leaf=lambda x: isinstance(x, P)):
            zero1 |= planner_mod.spec_axes(spec)
        zero1 = {a for a in zero1 - param_axes if degrees.get(a, 1) > 1}
    tensor = live("tensor")
    seq = live("seq")
    pipe = live("pipe")
    expert = live("expert")
    return {
        "gather": zero3 | zero1 | tensor | seq | pipe,
        "reduce": batch_axes | zero3 | tensor | seq | pipe,
        "scatter": zero3 | zero1 | tensor | seq,
        "a2a": expert | seq,
        "permute": seq | pipe,
    }


def lint_collectives(
    closed: Any,
    plan: planner_mod.ShardPlan,
    abstract_params: Any | None = None,
    *,
    grad_accum: int = 1,
) -> tuple[list[Finding], dict]:
    """GL002 + the crosscheck record joining inventory and estimate."""
    inventory = collective_inventory(closed)
    estimate = None
    if abstract_params is not None:
        try:
            estimate = planner_mod.expected_collective_bytes(
                plan, abstract_params, grad_accum=grad_accum)
        except Exception as e:  # estimate is advisory, never fatal
            estimate = {"error": f"{type(e).__name__}: {e}"}
    allowed = _allowed_axes(plan, abstract_params)
    findings: list[Finding] = []
    unpredicted: list[dict] = []
    for rec in inventory:
        ok = allowed.get(rec["kind"], set())
        bad = [a for a in rec["axes"] if a not in ok]
        if not bad:
            continue
        unpredicted.append(rec)
        findings.append(Finding(
            "GL002", WARN, "graph",
            f"<{rec['prim']} over {'/'.join(bad)}>",
            f"{rec['count']}× {rec['prim']} over mesh axis "
            f"{'/'.join(repr(a) for a in bad)} "
            f"(~{rec['bytes']} B buffers) is not predicted by the "
            f"plan's analytic comms model (strategy "
            f"{plan.strategy!r}) — an implicit reshard the planner "
            "did not ask for; check the sharding constraints feeding "
            "this op",
        ))
    crosscheck = {
        "inventory": inventory,
        "unpredicted": unpredicted,
        "estimate_total_wire_bytes": (
            estimate.get("total_wire_bytes") if estimate else None),
        "model_dependent": (
            sorted(estimate.get("model_dependent", {}))
            if estimate and "model_dependent" in estimate else []),
    }
    return findings, crosscheck


def lint_hazards(closed: Any) -> list[Finding]:
    """GL001 host side-effects + GL003 weak-typed captured scalars."""
    findings: list[Finding] = []
    for eqn in iter_eqns(closed):
        name = eqn.primitive.name
        if name in HOST_EFFECT_PRIMS:
            detail = eqn.params.get("fmt")
            findings.append(Finding(
                "GL001", WARN, "graph", f"<{name}>",
                "host side-effect inside the jitted step"
                + (f" ({detail!r})" if isinstance(detail, str) else "")
                + " — each call is a device→host sync and an XLA "
                "fusion barrier; gate it out of production steps",
            ))
    jaxpr = _jaxpr_of(closed)
    consts = getattr(closed, "consts", [])
    for var, val in zip(getattr(jaxpr, "constvars", []), consts):
        aval = getattr(var, "aval", None)
        if aval is None:
            continue
        if tuple(getattr(aval, "shape", (1,))) == () and getattr(
                aval, "weak_type", False):
            findings.append(Finding(
                "GL003", WARN, "graph", f"<const {val!r}>",
                "weak-typed Python scalar captured at trace time — its "
                "value is baked into the compiled step (silently stale "
                "if the Python variable changes; a recompile per value "
                "if hoisted to a static arg); pass it as a traced "
                "argument or wrap in a typed array",
            ))
    return findings


def lint_static_args(static_args: Mapping[str, Any]) -> list[Finding]:
    """GL004: static jit arguments must be hashable — jit raises a
    ``TypeError`` deep inside the dispatch path otherwise; this names
    the argument up front."""
    findings: list[Finding] = []
    for name, val in static_args.items():
        try:
            hash(val)
        except TypeError:
            findings.append(Finding(
                "GL004", ERROR, "graph", f"<static arg {name!r}>",
                f"{type(val).__name__} value is unhashable — jit "
                "cannot cache on it; use a hashable config "
                "(frozen dataclass / tuple) or make it a traced arg",
            ))
    return findings


def lint_graph(
    closed: Any,
    *,
    plan: planner_mod.ShardPlan | None = None,
    abstract_params: Any | None = None,
    grad_accum: int = 1,
    static_args: Mapping[str, Any] | None = None,
) -> list[Finding]:
    """All graph-layer rules over one traced step."""
    findings = lint_hazards(closed)
    if plan is not None:
        coll, _ = lint_collectives(
            closed, plan, abstract_params, grad_accum=grad_accum)
        findings += coll
    if static_args:
        findings += lint_static_args(static_args)
    return findings
