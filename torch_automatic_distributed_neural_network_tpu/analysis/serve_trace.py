"""Serving-trace lint: GL/DT rules over the ServeEngine's jaxprs.

Training steps have had graph + dtype preflight since ISSUE 4; the
serving decode/prefill traces (the programs a replica actually runs
per token) had none.  This module closes that gap for ``tadnn check
--serving --trace-serve``: build a ServeEngine on the requested config,
reproduce the exact abstract operands the AOT export path feeds
``jax.eval_shape`` (engine ``_export_compiled``), trace the *unjitted*
step functions with ``jax.make_jaxpr`` — trace-only, nothing compiles —
and run :mod:`.graph_lint` + :mod:`.dtype_lint` over both traces.

Host side-effects inside the decode step (GL001) are the marquee catch:
one stray ``debug_print`` in the sampled-token path syncs every decode
step of every stream.
"""

from __future__ import annotations

from typing import Any

from . import Finding


def serve_trace_check(
    model: Any,
    variables: Any,
    *,
    n_slots: int = 4,
    max_len: int = 64,
    block_size: int = 8,
    quant_kv: bool = False,
    attention_impl: str = "paged",
    prefill_chunk: int = 32,
    compute_dtype: Any = None,
) -> tuple[list[Finding], dict]:
    """Build a ServeEngine and lint the two traces it holds: the decode
    step and the chunk that carries a step's decode rows.

    Returns ``(findings, stats)`` where ``stats`` carries per-trace
    equation/collective counts for the JSON output.  The engine is
    real (the traces must match dispatch bit-for-bit) but small —
    callers pass test-size models; no request is ever submitted and
    no XLA compile runs.
    """
    import jax

    from ..inference.serve import ServeEngine
    from . import dtype_lint, graph_lint

    eng = ServeEngine(
        model, variables,
        n_slots=n_slots, max_len=max_len, block_size=block_size,
        quant_kv=quant_kv, attention_impl=attention_impl,
        prefill_chunk=prefill_chunk, journal=None,
    )
    params_abs = jax.eval_shape(lambda: eng.params)
    findings: list[Finding] = []
    stats: dict[str, dict] = {}

    def lint_one(tag: str, jit_fn: Any, abstract_args: tuple) -> None:
        # jax.jit wraps with functools.wraps: __wrapped__ is the plain
        # partial the engine built; tracing it (rather than through
        # pjit) keeps the jaxpr flat, though iter_eqns would recurse
        # either way.
        fn = getattr(jit_fn, "__wrapped__", jit_fn)
        closed = graph_lint.trace_step(fn, *abstract_args)
        fs = graph_lint.lint_graph(closed, abstract_params=params_abs)
        fs += dtype_lint.lint_dtypes(
            closed, abstract_params=params_abs,
            compute_dtype=compute_dtype)
        # re-anchor the layer-level `where` so decode/prefill findings
        # are tellable apart in one report
        findings.extend(
            Finding(f.code, f.severity, f.layer,
                    f"serve:{tag}:{f.where}", f.msg)
            for f in fs)
        eqns = list(graph_lint.iter_eqns(closed))
        stats[tag] = {
            "eqns": len(eqns),
            "collectives": len(graph_lint.collective_inventory(closed)),
        }

    # the exact operand tuples _export_compiled feeds eval_shape
    lint_one("decode", eng._step_fn, eng._abstract_decode_args())
    lint_one("prefill", eng._fused_fn, eng._abstract_fused_args())
    return findings, stats
