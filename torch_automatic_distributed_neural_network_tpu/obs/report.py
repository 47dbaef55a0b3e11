"""`tadnn report`: join the event journal with MetricsLogger JSONL and
answer "where did the wall-clock go?" from artifacts the run produced.

Inputs: a run directory (containing ``journal.jsonl`` and optionally
``metrics.jsonl``) or explicit file paths.  Output: one dict (``--json``)
or a human summary — throughput, MFU, compile/recompile accounting,
expected comm bytes vs. XLA bytes-accessed, and the goodput breakdown.
"""

from __future__ import annotations

import json
import math
import os
import statistics
from typing import Any

from .journal import Journal
from .schema import names_for

# merged-first: a multihost run's aggregate view (obs/aggregate.py)
# carries host tags the per-host files lack
JOURNAL_NAMES = ("journal.merged.jsonl", "journal.jsonl", "events.jsonl")
METRICS_NAMES = ("metrics.jsonl",)


def _find(directory: str, names: tuple[str, ...], suffix: str) -> str | None:
    for n in names:
        p = os.path.join(directory, n)
        if os.path.isfile(p):
            return p
    hits = sorted(
        f for f in os.listdir(directory) if f.endswith(suffix)
    )
    return os.path.join(directory, hits[0]) if hits else None


def resolve_paths(target: str,
                  metrics: str | None = None) -> tuple[str, str | None]:
    """(journal_path, metrics_path) from a dir / journal file + override."""
    if os.path.isdir(target):
        jp = _find(target, JOURNAL_NAMES, ".journal.jsonl")
        if jp is None:
            raise FileNotFoundError(
                f"no journal (journal.jsonl / *.journal.jsonl) in {target}"
            )
        mp = metrics or _find(target, METRICS_NAMES, ".metrics.jsonl")
        return jp, mp
    return target, metrics


def _read_metrics(path: str) -> list[dict]:
    out = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if line:
                try:
                    out.append(json.loads(line))
                except ValueError:
                    continue
    return out


def _finite(vals) -> list[float]:
    return [v for v in vals
            if isinstance(v, (int, float)) and math.isfinite(v)]


def _ratio(num, den) -> float | None:
    return num / den if den else None


def _mean(vals) -> float | None:
    vals = _finite(vals)
    return sum(vals) / len(vals) if vals else None


DEEP_POS = 8192      # a chunk from this position on is a deep one
STALL_FLOOR_S = 0.05  # a stall is this much over its kind's median, and 3x
KEYS_FLOOR = 1024    # decode rows that read fewer keys than this are one group


def _calls_by_read(ssteps: list[dict]) -> dict:
    """The serving calls by what they WAITED for (``serve.step``'s ``read``:
    the one program that went out a call before; a read that covers several
    is no steady-state call and is left out): ``{kind: [median step_s,
    calls]}`` for a call whose program carried a prefill chunk, the deep
    ones of those apart, and a decode-only one; and the stalls, a call more
    than three times and 50 ms over the median of its kind (chunk calls by
    4,096 positions of depth, since a chunk's attention grows with it), with
    what the collector took inside them.  The decode-only calls also by
    the keys a row read (``read.ctx_keys`` over ``read.rows``, in doublings
    from ``KEYS_FLOOR``): a decode step's attention grows with them as a
    chunk's does with its position.  ``{}`` for a journal without
    ``read``."""
    calls = [e for e in ssteps if (e.get("read") or {}).get("programs") == 1
             and e.get("step_s") is not None]
    if not calls:
        return {}

    def bucket(e) -> tuple:
        r = e["read"]
        return (("chunk", r["chunk_pos"] // 4096) if r["chunk_rows"]
                else ("decode",))

    def median_ms(group) -> list | None:
        return ([statistics.median(e["step_s"] for e in group), len(group)]
                if group else None)

    chunk = [e for e in calls if e["read"]["chunk_rows"]]
    deep = [e for e in chunk if e["read"]["chunk_pos"] >= DEEP_POS]
    decode = [e for e in calls if not e["read"]["chunk_rows"]]
    out: dict[str, Any] = {"calls": {k: v for k, v in (
        ("chunk", median_ms(chunk)), ("chunk_deep", median_ms(deep)),
        ("decode", median_ms(decode))) if v}}
    by_keys: dict[int, list] = {}
    for e in decode:
        keys = e["read"]["ctx_keys"] // max(1, e["read"]["rows"])
        lo = 0 if keys < KEYS_FLOOR else 1 << keys.bit_length() - 1
        by_keys.setdefault(lo, []).append(e)
    if len(by_keys) > 1:
        out["decode_by_keys"] = {
            f"{lo}-{max(KEYS_FLOOR, 2 * lo) - 1}": median_ms(by_keys[lo])
            for lo in sorted(by_keys)}
    by: dict[tuple, list] = {}
    for e in calls:
        by.setdefault(bucket(e), []).append(e)
    stalled = []
    for group in by.values():
        m = statistics.median(e["step_s"] for e in group)
        stalled += [(e, m) for e in group
                    if e["step_s"] > max(3 * m, m + STALL_FLOOR_S)]
    if stalled:
        worst, m = max(stalled, key=lambda em: em[0]["step_s"] - em[1])
        phases = worst.get("phases") or {}
        out["stalls"] = {
            "n": len(stalled),
            "lost_s": sum(e["step_s"] - m for e, m in stalled),
            "gc_s": sum(e.get("gc_s") or 0.0 for e, _ in stalled),
            "gc_full": sum(e.get("gc_full") or 0 for e, _ in stalled),
            "worst": {"step": worst.get("step"), "step_s": worst["step_s"],
                      "median_s": m, "kind": bucket(worst)[0],
                      "phase": max(phases, key=phases.get, default=None)}}
    return out


def generate(target: str, metrics_path: str | None = None) -> dict:
    """Build the run-summary dict from on-disk artifacts."""
    journal_path, metrics_path = resolve_paths(target, metrics_path)
    events = Journal.read(journal_path)
    report: dict[str, Any] = {
        "journal": journal_path,
        "metrics": metrics_path,
        "n_journal_records": len(events),
    }
    if events:
        ts = _finite([e.get("t") for e in events])
        report["journal_wall_s"] = (max(ts) - min(ts)) if ts else 0.0

    def last(name):
        for e in reversed(events):
            if e.get("name") == name:
                return e
        return None

    plan = last("plan")
    if plan:
        report["plan"] = {k: plan.get(k)
                          for k in ("strategy", "mesh", "remat", "precision",
                                    "zero1")
                          if plan.get(k) is not None}
    flash = last("flash.plan")
    if flash:
        report["flash_plan"] = {
            k: flash.get(k)
            for k in ("seq", "head_dim", "block_q", "block_k",
                      "tiles_visited", "tiles_square", "tiles_masked")}
    decision = last("tune.decision")
    hit = last("tune.cache_hit")
    fallback = last("tune.fallback")
    chosen = decision or hit or fallback
    if chosen:
        tuning: dict[str, Any] = {
            "source": ("cache" if chosen is hit else
                       "fallback" if chosen is fallback else
                       chosen.get("source", "cost_model")),
            "strategy": chosen.get("strategy"),
            "mesh": chosen.get("degrees") or chosen.get("mesh"),
            "grad_accum": chosen.get("grad_accum"),
            "step_time_ms": chosen.get("step_time_ms"),
            "reason": chosen.get("reason"),
            "n_candidates": chosen.get("n_candidates"),
            "breakdown": chosen.get("breakdown"),
        }
        cands = [e for e in events if e.get("name") == "tune.candidate"]
        if cands:
            tuning["candidates"] = [
                {k: e.get(k) for k in
                 ("rank", "strategy", "mesh", "grad_accum",
                  "step_time_ms", "fits")}
                for e in cands
            ]
        trials = [e for e in events
                  if e.get("name") == "tune.trial.result"]
        if trials:
            tuning["trials"] = [
                {k: e.get(k) for k in
                 ("candidate", "step_time_ms", "error") if e.get(k)}
                for e in trials
            ]
        report["tuning"] = {k: v for k, v in tuning.items()
                            if v is not None}
    compiles = [e for e in events if e.get("name") == "compile"]
    recompiles = [e for e in events if e.get("name") == "recompile"]
    report["compile"] = {
        "count": len(compiles),
        "total_s": sum(_finite(e.get("dur_s") for e in compiles)),
        "recompile_count": len(recompiles),
        "recompile_total_s": sum(_finite(e.get("dur_s") for e in recompiles)),
        "recompile_reasons": [
            {k: e.get(k) for k in ("fn", "signature", "dur_s")}
            for e in recompiles
        ],
    }
    exports = [e for e in events
               if (e.get("name") or "").startswith("export.")]
    if exports:
        hits = [e for e in exports if e["name"] == "export.hit"]
        stores = [e for e in exports if e["name"] == "export.store"]
        stales = [e for e in exports if e["name"] == "export.stale"]
        deser = sum(_finite(e.get("deserialize_s") for e in hits))
        compw = sum(_finite(e.get("compile_s") for e in stores))
        exp: dict[str, Any] = {
            "hits": len(hits),
            "misses": len([e for e in exports
                           if e["name"] == "export.miss"]),
            "stores": len(stores),
            "stale": len(stales),
            "fallbacks": len([e for e in exports
                              if e["name"] == "export.fallback"]),
            "errors": len([e for e in exports
                           if e["name"] == "export.error"]),
            "prewarms": len([e for e in exports
                             if e["name"] == "export.prewarm"]),
            "gc_dropped": sum(
                int(e.get("dropped") or 0) for e in exports
                if e["name"] == "export.gc") or None,
            "gc_payload_bytes_freed": sum(
                int(e.get("payload_bytes_freed") or 0) for e in exports
                if e["name"] == "export.gc") or None,
            "deserialize_total_s": deser or None,
            "mean_deserialize_s": _mean(e.get("deserialize_s")
                                        for e in hits),
            "compile_total_s": compw or None,
            "mean_compile_s": _mean(e.get("compile_s") for e in stores),
            # the cold-start win this run actually realized: compile
            # wall of the entries it wrote vs deserialize wall of the
            # entries it read (same-config runs make this the speedup)
            "compile_over_deserialize": (
                round(compw / deser, 1) if compw and deser else None),
            "stale_reasons": [
                {k: e.get(k) for k in ("kind", "reason")}
                for e in stales
            ] or None,
        }
        report["export"] = {k: v for k, v in exp.items()
                            if v is not None}
    good = last("goodput")
    if good:
        report["goodput"] = {k: good.get(k)
                             for k in ("total_wall_s", "seconds",
                                       "fractions", "goodput")}
    comms = last("comms.estimate")
    if comms:
        report["comms"] = {k: comms.get(k)
                           for k in ("strategy", "total_wire_bytes",
                                     "per_device", "model_dependent")}
    cross = last("comms.crosscheck")
    if cross:
        report["comms_crosscheck"] = {
            k: cross.get(k)
            for k in ("expected_wire_bytes", "xla_bytes_accessed",
                      "comm_fraction_of_bytes_accessed", "consistent")}
    tsteps = [e for e in events if e.get("name") == "trace.step"]
    if tsteps:
        coll = sum(_finite(e.get("collective_s") for e in tsteps))
        exp = sum(_finite(e.get("exposed_collective_s") for e in tsteps))
        wall = sum(_finite(e.get("wall_s") for e in tsteps))
        trace: dict[str, Any] = {
            "n_steps": len(tsteps),
            "mean_wall_s": _mean(e.get("wall_s") for e in tsteps),
            "mean_compute_s": _mean(e.get("compute_s") for e in tsteps),
            "mean_collective_s": _mean(e.get("collective_s")
                                       for e in tsteps),
            "mean_exposed_s": _mean(e.get("exposed_collective_s")
                                    for e in tsteps),
            "collective_fraction": (coll / wall) if wall else None,
            # of the collective time, how much the schedule failed to
            # hide — the ROADMAP's overlap-push observable
            "exposed_fraction": (exp / coll) if coll else None,
            "mean_measured_mfu": _mean(e.get("measured_mfu")
                                       for e in tsteps
                                       if e.get("measured_mfu")
                                       is not None),
            "mfu_series": [
                {"step": e.get("step"), "mfu": e["measured_mfu"]}
                for e in tsteps if e.get("measured_mfu") is not None
            ][-24:],
        }
        report["trace"] = {k: v for k, v in trace.items()
                          if v not in (None, [])}
    tcoll = [e for e in events if e.get("name") == "trace.collective"]
    if tcoll:
        latest: dict[str, dict] = {}
        for e in tcoll:  # keep the newest record per category
            latest[e.get("category", "?")] = e
        report["trace_collectives"] = [
            {k: e.get(k) for k in
             ("category", "hlo_op", "count", "measured_bytes",
              "modeled_bytes", "ratio", "within_2x")}
            for e in latest.values()
        ]
    from .aggregate import host_skew

    skew = host_skew(events)
    if skew:
        report["hosts"] = skew
    stalls = [e for e in events if e.get("name") == "watchdog.stall"]
    restarts = [e for e in events if e.get("name") == "elastic.restart"]
    corrupt = [e for e in events if e.get("name") == "ckpt.corrupt"]
    rollbacks = [e for e in events
                 if e.get("name") == "resilience.rollback"]
    chaos = [e for e in events if e.get("name") == "resilience.chaos"]
    escalations = [e for e in events
                   if e.get("name") == "resilience.stall_escalation"]
    exhausted = [e for e in events if e.get("name") == "data_exhausted"]
    if (stalls or restarts or corrupt or rollbacks or chaos
            or escalations or exhausted):
        report["incidents"] = {
            "watchdog_stalls": len(stalls),
            "elastic_restarts": len(restarts),
            "corrupt_checkpoints": len(corrupt),
            "anomaly_rollbacks": len(rollbacks),
            "chaos_faults": len(chaos),
            "stall_escalations": len(escalations),
            "data_exhausted": len(exhausted),
        }
        detail = []
        for e in corrupt:
            detail.append({"what": "ckpt.corrupt", "step": e.get("step"),
                           "reason": e.get("reason")})
        for e in rollbacks:
            detail.append({"what": "rollback", "reason": e.get("reason"),
                           "at_step": e.get("at_step"),
                           "to_step": e.get("to_step"),
                           "skipped_batches": e.get("skipped_batches")})
        if detail:
            report["incident_detail"] = detail
        gave_up = [e for e in restarts if e.get("gave_up")]
        if restarts:
            report["incidents"]["restarts_gave_up"] = len(gave_up)
    rounds = [e for e in events if e.get("name") == "launch.round"]
    lrestarts = [e for e in events if e.get("name") == "launch.restart"]
    lchaos = [e for e in events if e.get("name") == "launch.chaos"]
    replans = [e for e in events if e.get("name") == "launch.replan"]
    async_saves = [e for e in events if e.get("name") == "ckpt.async_save"]
    done = [e for e in events if e.get("name") == "launch.done"]
    if rounds or lrestarts or done:
        launch: dict = {
            "rounds": len(rounds),
            "restarts": len(lrestarts),
            "chaos_faults": [{"kind": e.get("kind"), "step": e.get("step"),
                              "host": e.get("host")} for e in lchaos],
            "replans": [{"from": e.get("world_from"),
                         "to": e.get("world_to")} for e in replans],
            "worlds": [e.get("world") for e in rounds],
            "completed": bool(done),
        }
        if lrestarts:
            launch["broken_by"] = [
                {"host": e.get("host"), "step": e.get("step"),
                 "reason": e.get("reason")} for e in lrestarts]
            launch["gave_up"] = any(e.get("gave_up") for e in lrestarts)
        if done:
            launch["final_step"] = done[-1].get("final_step")
            launch["final_loss"] = done[-1].get("final_loss")
        if async_saves:
            durs = _finite(e.get("off_thread_s") for e in async_saves)
            launch["async_saves"] = {
                "n": len(async_saves),
                "max_queue_depth": max((e.get("queue_depth") or 0)
                                       for e in async_saves),
                "mean_off_thread_s": (sum(durs) / len(durs)
                                      if durs else None),
            }
        report["launch"] = launch
    # the registry's deprecation table supplies every name this event
    # was ever emitted under (the r06 rename); older journals render
    sreqs = [e for e in events
             if e.get("name") in names_for("serve.request_done")]
    ssteps = [e for e in events if e.get("name") == "serve.step"]
    spreempt = [e for e in events if e.get("name") == "serve.preempt"]
    sengine = last("serve.engine")
    if sreqs or ssteps:
        totals = sorted(_finite(e.get("total_s") for e in sreqs))

        def pct(vals, q):
            if not vals:
                return None
            return vals[min(len(vals) - 1,
                            max(0, math.ceil(q * len(vals)) - 1))]

        new_tokens = sum(_finite(e.get("n_new") for e in sreqs))
        ts = _finite([e.get("t") for e in sreqs + ssteps])
        wall = (max(ts) - min(ts)) if len(ts) > 1 else None
        serving: dict[str, Any] = {
            "n_requests": len(sreqs),
            "n_steps": len(ssteps),
            "p50_latency_s": pct(totals, 0.50),
            "p99_latency_s": pct(totals, 0.99),
            "mean_queue_s": _mean(e.get("queue_s") for e in sreqs),
            "mean_tokens_per_s": _mean(e.get("tokens_per_s")
                                       for e in sreqs),
            "total_new_tokens": new_tokens,
            # aggregate goodput: generated tokens over the serving
            # window — the number batching discipline moves
            "goodput_tokens_per_s": (new_tokens / wall
                                     if wall else None),
            "mean_occupancy": _mean(e.get("occupancy") for e in ssteps),
            "preemptions": (len(spreempt)
                            or sum(int(e.get("preempted") or 0)
                                   for e in sreqs)),
            # per-step phase breakdown (engines that journal the r02
            # fields; absent keys drop out below)
            "mean_decode_step_s": _mean(e.get("decode_s")
                                        for e in ssteps),
            "mean_prefill_step_s": _mean(e.get("prefill_s")
                                         for e in ssteps),
            "n_prefill_chunks": sum(
                e.get("n_prefill_chunks") or 0 for e in ssteps) or None,
            "attention_impl": (sengine or {}).get("attention_impl"),
            "prefill_chunk": (sengine or {}).get("prefill_chunk"),
            # the tree the base programs take (engine construction)
            "weights_cast": (sengine or {}).get("weights_cast"),
            "weight_bytes_compute": (sengine or {}).get(
                "weight_bytes_compute"),
            "weight_bytes_fp32": (sengine or {}).get("weight_bytes_fp32"),
            # layer kinds, the experts held and the pool by kind of page;
            # start-up: what the constructor cost and each program's first
            # call (the newest ``serve.engine`` holds the table)
            **{k: (sengine or {}).get(k) for k in (
                "build_s", "build_phases", "build_loads", "programs",
                "layer_kinds", "experts_held", "experts_published",
                "zero_experts", "shortcut_experts", "kv_bytes_full",
                "kv_bytes_window", "state_bytes_linear", "conv_bytes_linear",
                "linear_mixer", "linear_write_max", "kv_bytes_latent",
                "latent_row", "attention_form", "attn_gate", "dense_layers",
                "cross_start", "paged_sets", "shared_readers")},
            # rows that ran the cross-decoder over rows that ran the layers
            # before it (``read.cross_rows`` / ``read.self_rows``)
            "cross_rows_share": _ratio(
                sum((e.get("read") or {}).get("cross_rows", 0)
                    for e in ssteps),
                sum((e.get("read") or {}).get("self_rows", 0)
                    for e in ssteps)),
            # the state rows a call's step kernels read and wrote
            "mean_state_rows": _mean(e.get("state_rows") for e in ssteps),
            # of the rows those kernels walked, the decoding slots' share
            "state_rows_live_share": _ratio(
                sum(e.get("state_rows", 0) for e in ssteps
                    if "state_rows_walked" in e),
                sum(e.get("state_rows_walked", 0) for e in ssteps)),
            # the decode steps' expert counters (engines with experts)
            "mean_moe_pairs": _mean(e.get("moe_pairs") for e in ssteps),
            "mean_moe_experts_touched": _mean(
                e.get("moe_experts_touched") for e in ssteps),
            "max_moe_expert_tokens": max(
                _finite(e.get("moe_max_expert_tokens") for e in ssteps),
                default=None),
            # the calls' pairs on zero-compute experts and the rows routed
            "moe_zero_pairs": sum(_finite(
                e.get("moe_zero_pairs") for e in ssteps)) or None,
            "moe_rows": sum(_finite(
                e.get("moe_rows") for e in ssteps)) or None,
            # the expert layers' row tiles that held a pair, by the tiles
            # the call laid out (the engine says what each kind of call
            # lays): [laid a call, calls, live tiles over them]
            "moe_tiles_laid": (sengine or {}).get("moe_tiles_laid"),
            "moe_tiles": [
                [laid, len(live), sum(live)]
                for laid in sorted({e.get("moe_tiles_laid") or 0
                                    for e in ssteps} - {0}, reverse=True)
                for live in [[e["moe_tiles_active"] for e in ssteps
                              if e.get("moe_tiles_laid") == laid]]],
            # the grid steps the decode steps' paged attention calls ran
            # (their work lists' items), and a dense slots x groups grid's
            "attn_grid_items": sum(_finite(
                e.get("attn_grid_items") for e in ssteps)) or None,
            "attn_grid_dense": sum(_finite(
                e.get("attn_grid_dense") for e in ssteps)) or None,
            # the page copies those items started, and the entries among
            # them that held an attendable key
            "attn_pages_copied": sum(_finite(
                e.get("attn_pages_copied") for e in ssteps)) or None,
            "attn_pages_live": sum(_finite(
                e.get("attn_pages_live") for e in ssteps)) or None,
            # sharded serving
            "tp": (sengine or {}).get("tp"),
        }
        # where the host's time in a step goes (engine.PHASES): mean
        # seconds of each phase over the steps in which it ran, and the
        # step's self time (step_s less its phases)
        phased = [e for e in ssteps if e.get("phases")]
        if phased:
            keys = list(dict.fromkeys(
                k for e in phased for k in e.get("phases")))
            serving["step_phase_mean_s"] = {
                k: _mean(e.get("phases").get(k) for e in phased)
                for k in keys}
            serving["mean_step_s"] = _mean(e.get("step_s") for e in phased)
            serving["mean_step_self_s"] = _mean(
                e.get("step_s") - sum(e.get("phases").values())
                for e in phased if e.get("step_s") is not None)
            serving["steps_that_compiled"] = sum(
                1 for e in ssteps if e.get("compiles"))
            # the engine reads a decode step one call after dispatching
            # it: the share of decoding calls whose step went out with
            # the one before unread, and the slot-steps decoded and
            # thrown away (EOS seen a step late, preemption) beside the
            # tokens kept (engines that journal the two counters)
            counted = [e for e in ssteps if e.get("ahead") is not None
                       and e.get("decode_s")]
            if counted:
                serving["decode_calls"] = len(counted)
                serving["steps_ahead_share"] = (
                    sum(e["ahead"] for e in counted) / len(counted))
                serving["discarded_tokens"] = sum(
                    e.get("discarded_tokens") or 0 for e in counted)
                serving["step_new_tokens"] = sum(
                    e.get("new_tokens") or 0 for e in counted)
            # a step's decode rows may ride in its prefill chunk, one
            # program for both (engines that journal the two counters):
            # the share of the steps with a chunk that carried them, and
            # of the decode rows read that rode in one (``read.rows``: left
            # out for an engine from before ``read``)
            fused = [e for e in ssteps if e.get("fused")]
            if fused:
                reads = [e["read"] for e in ssteps if e.get("read")]
                serving["fused_steps"] = len(fused)
                serving["fused_share_of_chunk_steps"] = len(fused) / sum(
                    1 for e in ssteps if e.get("n_prefill_chunks"))
                if reads:
                    serving["fused_share_of_decode_rows"] = sum(
                        e.get("fused_decode_rows") or 0
                        for e in fused) / max(1, sum(r["rows"] for r in reads))
            # the two kinds of decoding step behind the ITL's two
            # modes: a step that also ran prefill chunks, and one that
            # only decoded
            decoding = [e for e in phased if e.get("decode_s")
                        and e.get("step_s") is not None]
            chunked = [e for e in decoding if e.get("n_prefill_chunks")]
            if chunked:
                serving["decode_steps_with_chunk"] = len(chunked)
                serving["decode_steps"] = len(decoding)
                serving["mean_step_with_chunk_s"] = _mean(
                    e.get("step_s") for e in chunked)
                serving["mean_step_decode_only_s"] = _mean(
                    e.get("step_s") for e in decoding
                    if not e.get("n_prefill_chunks"))
            # the key blocks a chunk call's attention kernels ran (engines
            # whose chunk attends in a kernel): [calls, blocks over them]
            blocks = _finite(e.get("chunk_key_blocks") for e in ssteps)
            serving["chunk_attention"] = (sengine or {}).get(
                "chunk_attention")
            serving["chunk_key_blocks"] = ([len(blocks), sum(blocks)]
                                           if blocks else None)
            # the same by what a call WAITED for, which is what its time
            # is (a step is read one call late), and the stalls
            by_read = _calls_by_read(ssteps)
            serving["calls_by_read"] = by_read.get("calls")
            serving["decode_calls_by_keys"] = by_read.get("decode_by_keys")
            serving["stalls"] = by_read.get("stalls")
        # request span timelines (r06 serve.request_done fields): TTFT
        # and inter-token latency percentiles plus the mean phase mix —
        # where a request's wall time went, attributed per phase
        ttfts = sorted(_finite(e.get("ttft_s") for e in sreqs))
        itls = sorted(_finite(
            v for e in sreqs for v in (e.get("itl_s") or ())))
        if ttfts:
            serving["ttft_p50_s"] = pct(ttfts, 0.50)
            serving["ttft_p99_s"] = pct(ttfts, 0.99)
        if itls:
            serving["itl_p50_s"] = pct(itls, 0.50)
            serving["itl_p99_s"] = pct(itls, 0.99)
        phase_means = {
            label: _mean(e.get(key) for e in sreqs)
            for label, key in (("queue", "queue_s"),
                               ("prefill", "prefill_s"),
                               ("decode", "decode_s"),
                               ("lost", "lost_s"))}
        if any(v is not None for v in phase_means.values()):
            serving["phase_mean_s"] = {
                k: v for k, v in phase_means.items() if v is not None}
        spec = [e for e in events if e.get("name") == "serve.speculate"]
        if spec:
            drafted = sum(_finite(e.get("drafted") for e in spec))
            accepted = sum(_finite(e.get("accepted") for e in spec))
            serving["spec_rounds"] = len(spec)
            serving["spec_k"] = spec[-1].get("k")
            serving["spec_drafted"] = int(drafted)
            serving["spec_accepted"] = int(accepted)
            serving["spec_accept_rate"] = (accepted / drafted
                                           if drafted else None)
        sadapt = [e for e in events if e.get("name") == "serve.adapter"]
        occ_res = _mean(e.get("adapters_resident") for e in ssteps
                        if e.get("adapters_resident") is not None)
        if sadapt or occ_res is not None:
            hits = [e for e in sadapt if e.get("kind") == "hit"]
            faults = [e for e in sadapt if e.get("kind") == "fault"]
            serving["adapter_hits"] = len(hits)
            serving["adapter_faults"] = len(faults)
            serving["adapter_evictions"] = sum(
                1 for e in faults if e.get("evicted"))
            serving["adapter_stalls"] = sum(
                1 for e in sadapt if e.get("kind") == "stall")
            binds = len(hits) + len(faults)
            serving["adapter_hit_rate"] = (len(hits) / binds
                                           if binds else None)
            serving["mean_adapters_resident"] = occ_res
            serving["mean_adapters_pinned"] = _mean(
                e.get("adapters_pinned") for e in ssteps
                if e.get("adapters_pinned") is not None)
        sprefix = [e for e in events if e.get("name") == "serve.prefix"]
        if sprefix or any(e.get("prefix_blocks") is not None
                          for e in ssteps):
            matches = [e for e in sprefix if e.get("kind") == "match"]
            cached = int(sum(_finite(
                e.get("cached_tokens") for e in matches)))
            prompt_tokens = sum(_finite(
                e.get("n_prompt") for e in sreqs))
            serving["prefix_queries"] = len(matches)
            serving["prefix_hit_requests"] = sum(
                1 for e in matches if e.get("hit"))
            serving["prefix_cached_tokens"] = cached
            serving["prefix_hit_rate"] = (
                cached / prompt_tokens if prompt_tokens else None)
            chunk = serving.get("prefill_chunk")
            # per-request floor, matching the engine: a cached span
            # shorter than one chunk skips nothing
            serving["prefix_saved_chunks"] = (
                int(sum(int(t) // chunk for t in _finite(
                    e.get("cached_tokens") for e in matches)))
                if chunk else None)
            serving["prefix_published_blocks"] = int(sum(_finite(
                e.get("n_blocks") for e in sprefix
                if e.get("kind") == "publish")))
            serving["cow_forks"] = sum(
                1 for e in sprefix if e.get("kind") == "cow")
            resident = [e.get("prefix_blocks") for e in ssteps
                        if e.get("prefix_blocks") is not None]
            serving["prefix_blocks"] = (resident[-1] if resident
                                        else None)
        report["serving"] = {k: v for k, v in serving.items()
                             if v is not None}
    # SLO incidents (obs/slo_monitor): breach/recover transitions the
    # monitor journaled while watching (or replaying) this run
    breaches = [e for e in events if e.get("name") == "slo.breach"]
    recovers = [e for e in events if e.get("name") == "slo.recover"]
    if breaches or recovers:
        report["slo_incidents"] = {
            "breaches": len(breaches),
            "recoveries": len(recovers),
            "incidents": sorted(
                ([{"kind": "breach",
                   "window_start_s": e.get("window_start_s"),
                   "window_end_s": e.get("window_end_s"),
                   "violations": e.get("violations") or []}
                  for e in breaches]
                 + [{"kind": "recover",
                     "window_start_s": e.get("window_start_s"),
                     "window_end_s": e.get("window_end_s"),
                     "ok_windows": e.get("ok_windows")}
                    for e in recovers]),
                key=lambda i: (i.get("window_start_s") or 0.0)),
        }
    # gateway fleet events (inference/gateway): ingress admission,
    # replan decisions, and elastic resizes from the closed-loop
    # autoscaler
    greqs = [e for e in events if e.get("name") == "gateway.request"]
    grejects = [e for e in events if e.get("name") == "gateway.reject"]
    greplans = [e for e in events if e.get("name") == "gateway.replan"]
    gscales = [e for e in events if e.get("name") == "gateway.scale"]
    gfails = [e for e in events
              if e.get("name") == "gateway.failover"
              and e.get("kind") != "parked"]
    ghedges = [e for e in events if e.get("name") == "gateway.hedge"]
    gbreaker = [e for e in events if e.get("name") == "gateway.breaker"]
    gdegrade = [e for e in events
                if e.get("name") in ("gateway.degrade",
                                     "gateway.restore")]
    if (greqs or grejects or greplans or gscales or gfails
            or ghedges or gbreaker or gdegrade):
        gw: dict[str, Any] = {
            "requests": len(greqs),
            "rejected": len(grejects),
            "rejected_rate_limit": sum(
                1 for e in grejects if e.get("kind") == "rate_limit"),
            "rejected_backpressure": sum(
                1 for e in grejects if e.get("kind") == "backpressure"),
            "rejected_degraded": sum(
                1 for e in grejects if e.get("kind") == "degraded"),
            "failovers": [
                {"t": e.get("t"), "replica": e.get("replica"),
                 "reason": e.get("reason"),
                 "n_requeued": e.get("n_requeued")}
                for e in gfails],
            "hedges_dispatched": sum(
                1 for e in ghedges if e.get("kind") == "dispatch"),
            "hedges_won": sum(
                1 for e in ghedges if e.get("kind") == "win"
                and e.get("winner") == "hedge"),
            "breaker_opens": sum(
                1 for e in gbreaker if e.get("to") == "open"),
            "degrade_history": [
                {"t": e.get("t"),
                 "kind": e.get("name", ".").split(".", 1)[1],
                 "level": e.get("level"), "reason": e.get("reason")}
                for e in gdegrade],
            "replans": [
                {"t": e.get("t"), "reason": e.get("reason"),
                 "current": e.get("current"), "chosen": e.get("chosen"),
                 "rate_per_s": e.get("rate_per_s")}
                for e in greplans],
            "scales": [
                {"t": e.get("t"), "kind": e.get("kind"),
                 "replica": e.get("replica"),
                 "reason": e.get("reason"),
                 "n_replicas": e.get("n_replicas"),
                 "requeued": e.get("requeued")}
                for e in gscales],
        }
        if gscales:
            final = [e.get("n_replicas") for e in gscales
                     if e.get("n_replicas") is not None]
            gw["final_replicas"] = final[-1] if final else None
        report["gateway"] = gw
    lint_findings = [e for e in events if e.get("name") == "lint.finding"]
    lint_summary = last("lint.summary")
    lint_skipped = last("lint.skipped")
    if lint_findings or lint_summary or lint_skipped:
        lint: dict[str, Any] = {
            "errors": (lint_summary or {}).get("errors",
                                               len([f for f in lint_findings
                                                    if f.get("severity")
                                                    == "error"])),
            "warnings": (lint_summary or {}).get("warnings",
                                                 len([f for f in lint_findings
                                                      if f.get("severity")
                                                      == "warn"])),
            "by_code": (lint_summary or {}).get("by_code"),
            "phase": (lint_summary or lint_skipped or {}).get("phase"),
            "findings": [
                {k: e.get(k) for k in ("code", "severity", "where", "msg")}
                for e in lint_findings
            ],
        }
        if lint_skipped:
            lint["skipped"] = lint_skipped.get("error")
        report["lint"] = {k: v for k, v in lint.items() if v is not None}
    protocol = [e for e in events if e.get("name") == "lint.protocol"]
    if protocol:
        report["protocol"] = [
            {k: e.get(k) for k in ("model", "scope", "states",
                                   "transitions", "depth", "frontier_peak",
                                   "wall_s", "complete", "violations")}
            for e in protocol]
    mem_est = last("lint.mem_estimate")
    if mem_est:
        keys = ("params_bytes", "optimizer_bytes", "model_state_bytes",
                "batch_bytes", "activation_bytes", "peak_bytes",
                "budget_bytes", "strategy", "degrees", "grad_accum",
                "remat", "phase", "static_over_compiled")
        me = {k: mem_est.get(k) for k in keys if mem_est.get(k) is not None}
        compiled = mem_est.get("compiled") or {}
        if compiled.get("per_device_peak_bytes"):
            me["compiled_peak_bytes"] = compiled["per_device_peak_bytes"]
        report["memory_estimate"] = me
    sest = last("lint.serve_estimate")
    if sest:
        report["serve_estimate"] = {
            k: sest.get(k)
            for k in ("max_streams", "requested_streams", "num_blocks",
                      "blocks_per_stream", "block_size", "max_len",
                      "quant_kv", "budget_bytes",
                      "block_bytes_per_device", "attention_impl",
                      "decode_workspace_bytes", "adapter_pool_bytes",
                      "n_adapters", "adapter_rank", "quant_adapters",
                      "prefix_cache", "prefix_index_bytes",
                      "expected_hit_rate", "effective_max_streams")
            if sest.get(k) is not None}
    ssweep = last("simulate.sweep")
    scands = [e for e in events if e.get("name") == "simulate.candidate"]
    sdec = last("simulate.decision")
    if ssweep or scands or sdec:
        sim: dict[str, Any] = {}
        if ssweep:
            sim.update({k: ssweep.get(k)
                        for k in ("n_topologies", "n_candidates",
                                  "n_replays", "n_slo_ok")
                        if ssweep.get(k) is not None})
        if scands:
            sim["ranked"] = [
                {k: e.get(k) for k in
                 ("rank", "topology", "plan", "admission", "mfu",
                  "step_time_s", "hbm_headroom_frac", "tok_s_per_chip",
                  "p99_s", "survival", "slo_ok", "slo_violations")}
                for e in scands]
        if sdec:
            sim["decision"] = {
                k: sdec.get(k) for k in
                ("topology", "plan", "admission", "slo_ok",
                 "slo_violations", "mfu", "tok_s_per_chip", "p99_s",
                 "hbm_headroom_frac", "survival")}
        report["simulate"] = sim
    if metrics_path and os.path.isfile(metrics_path):
        recs = _read_metrics(metrics_path)
        steps = [r for r in recs if "step_time_s" in r]
        per_chip = [v for r in steps for k, v in r.items()
                    if k.endswith("_per_sec_per_chip") and v]
        report["training"] = {
            "n_step_records": len(steps),
            "last_step": max((r.get("step", 0) for r in steps), default=None),
            "mean_step_time_s": _mean(r.get("step_time_s") for r in steps),
            "items_per_sec_per_chip": _mean(per_chip),
            "mean_mfu": _mean(r.get("mfu") for r in steps
                              if "mfu" in r),
            "final_loss": next(
                (r["loss"] for r in reversed(steps) if "loss" in r), None),
        }
    return report


def _fmt_bytes(n) -> str:
    if n is None:
        return "n/a"
    for unit in ("B", "KiB", "MiB", "GiB", "TiB"):
        if abs(n) < 1024 or unit == "TiB":
            return f"{n:.1f} {unit}" if unit != "B" else f"{int(n)} B"
        n /= 1024
    return f"{n:.1f} TiB"


def format_report(report: dict) -> str:
    """Human-readable rendering of :func:`generate`'s dict."""
    lines = [f"run journal: {report['journal']} "
             f"({report['n_journal_records']} records, "
             f"{report.get('journal_wall_s', 0.0):.1f}s span)"]
    plan = report.get("plan")
    if plan:
        strat = str(plan.get("strategy"))
        if plan.get("zero1"):
            strat += "+zero1"
        lines.append(f"plan: strategy={strat} "
                     f"mesh={plan.get('mesh')}")
    tun = report.get("tuning")
    if tun:
        head = (f"tuner: strategy={tun.get('strategy')} "
                f"mesh={tun.get('mesh')} ({tun.get('source')}")
        if tun.get("n_candidates"):
            head += f", {tun['n_candidates']} candidates"
        if tun.get("step_time_ms") is not None:
            head += f", modeled {tun['step_time_ms']:.3f}ms/step"
        lines.append(head + ")")
        if tun.get("reason"):
            lines.append(f"  {tun['reason']}")
        b = tun.get("breakdown")
        if b:
            lines.append(
                "  breakdown: " + "  ".join(
                    f"{k.removesuffix('_ms')} {b[k]:.3f}ms"
                    for k in ("compute_ms", "comm_ms", "hbm_ms",
                              "latency_ms") if b.get(k) is not None))
        trials = tun.get("trials")
        if trials:
            ok = [t for t in trials if t.get("step_time_ms") is not None]
            msg = f"  measured trials: {len(trials)}"
            if ok:
                best = min(ok, key=lambda t: t["step_time_ms"])
                msg += (f", best {best.get('candidate')} "
                        f"{best['step_time_ms']:.3f}ms")
            lines.append(msg)
    c = report["compile"]
    lines.append(
        f"compiles: {c['count']} ({c['total_s']:.2f}s)   "
        f"recompiles: {c['recompile_count']} "
        f"({c['recompile_total_s']:.2f}s)"
        + ("  <- shape churn, check input pipeline"
           if c["recompile_count"] else "")
    )
    ex = report.get("export")
    if ex:
        parts = [f"export cache: {ex.get('hits', 0)} hit(s)"]
        if ex.get("mean_deserialize_s") is not None:
            parts.append(
                f"deserialize {ex['mean_deserialize_s'] * 1e3:.1f}ms mean")
        if ex.get("stores"):
            parts.append(f"{ex['stores']} store(s)")
        if ex.get("mean_compile_s") is not None:
            parts.append(f"compile {ex['mean_compile_s']:.2f}s mean")
        if ex.get("compile_over_deserialize"):
            parts.append(
                f"{ex['compile_over_deserialize']}x compile/deserialize")
        if ex.get("prewarms"):
            parts.append(f"{ex['prewarms']} prewarm(s)")
        if ex.get("gc_dropped"):
            parts.append(
                f"gc dropped {ex['gc_dropped']} "
                f"({_fmt_bytes(ex.get('gc_payload_bytes_freed') or 0)} "
                f"freed)")
        lines.append("  ".join(parts))
        if ex.get("stale"):
            reasons = ex.get("stale_reasons") or []
            first = reasons[0].get("reason") if reasons else None
            lines.append(
                f"  STALE entries skipped: {ex['stale']} (recompiled)"
                + (f" — {first}" if first else ""))
        if ex.get("fallbacks"):
            lines.append(
                f"  !! {ex['fallbacks']} exported executable(s) "
                f"rejected runtime args — fell back to jit")
        if ex.get("errors"):
            lines.append(f"  !! {ex['errors']} export error(s) "
                         f"(see export.error events)")
    tr = report.get("training")
    if tr:
        parts = [f"steps logged: {tr['n_step_records']}"]
        if tr.get("mean_step_time_s") is not None:
            parts.append(f"mean step {tr['mean_step_time_s'] * 1e3:.1f}ms")
        if tr.get("items_per_sec_per_chip"):
            parts.append(f"{tr['items_per_sec_per_chip']:,.0f} items/s/chip")
        if tr.get("mean_mfu") is not None:
            parts.append(f"MFU {tr['mean_mfu']:.1%}")
        if tr.get("final_loss") is not None:
            parts.append(f"final loss {tr['final_loss']:.4f}")
        lines.append("training: " + "  ".join(parts))
    fl = report.get("flash_plan")
    if fl:
        lines.append(
            "  flash tiles visited / square, masked: "
            f"{fl['tiles_visited']} / {fl['tiles_square']}, "
            f"{fl['tiles_masked']}  (seq {fl['seq']}, head_dim "
            f"{fl['head_dim']}, tiles {fl['block_q']} x {fl['block_k']})")
    good = report.get("goodput")
    if good and good.get("fractions"):
        fr = good["fractions"]
        lines.append(
            "goodput: {:.1%} of {:.1f}s wall".format(
                good.get("goodput", 0.0), good.get("total_wall_s", 0.0))
        )
        lines.append("  " + "  ".join(
            f"{b} {fr[b]:.1%}" for b in
            ("compile", "step", "checkpoint", "eval", "trace",
             "input_stall", "idle")
            if b in fr))
    comms = report.get("comms")
    if comms:
        per = comms.get("per_device") or {}
        lines.append(
            f"comms (per device/step, {comms.get('strategy')}): "
            f"wire { _fmt_bytes(comms.get('total_wire_bytes')) }   "
            + "  ".join(f"{k} {_fmt_bytes(v)}" for k, v in per.items() if v)
        )
        md = comms.get("model_dependent")
        if md:
            lines.append(f"  model-dependent (unquantified): {', '.join(md)}")
    cross = report.get("comms_crosscheck")
    if cross and cross.get("xla_bytes_accessed"):
        lines.append(
            f"  XLA bytes-accessed {_fmt_bytes(cross['xla_bytes_accessed'])}"
            f" -> comm fraction "
            f"{cross.get('comm_fraction_of_bytes_accessed') or 0:.1%}"
            + ("" if cross.get("consistent") else
               "  !! estimate exceeds measurement")
        )
    trc = report.get("trace")
    if trc:
        head = f"trace: {trc['n_steps']} instrumented step(s)"
        if trc.get("mean_wall_s") is not None:
            head += f", mean wall {trc['mean_wall_s'] * 1e3:.1f}ms"
        if trc.get("mean_measured_mfu") is not None:
            head += f", measured MFU {trc['mean_measured_mfu']:.1%}"
        lines.append(head)
        if trc.get("collective_fraction") is not None:
            exp = trc.get("exposed_fraction")
            lines.append(
                f"  collective {trc['collective_fraction']:.1%} of step "
                f"wall"
                + (f", exposed {exp:.1%} of collective time"
                   if exp is not None else "")
            )
        series = trc.get("mfu_series")
        if series and len(series) > 1:
            lines.append("  mfu over time: " + "  ".join(
                f"s{p['step']} {p['mfu']:.1%}" for p in series[-8:]))
    tc = report.get("trace_collectives")
    if tc:
        lines.append("exposed-comm crosscheck (measured HLO vs modeled "
                     "planner bytes, per device/step):")
        for e in tc:
            lines.append(
                f"  {e.get('category'):<20} x{e.get('count', 0)}  "
                f"measured {_fmt_bytes(e.get('measured_bytes'))}  "
                f"modeled {_fmt_bytes(e.get('modeled_bytes'))}  "
                f"ratio {e.get('ratio')}"
                + ("" if e.get("within_2x") else "  !! outside 2x band")
            )
    hosts = report.get("hosts")
    if hosts:
        sf = hosts.get("skew_fraction")
        lines.append(
            f"hosts: {hosts['n_hosts']}  {hosts.get('event')} "
            f"{hosts.get('field')} "
            f"{hosts['fastest'] * 1e3:.1f}..{hosts['slowest'] * 1e3:.1f}ms"
            + (f"  skew {sf:.1%}" if sf is not None else "")
            + ("  <- straggler gates every collective"
               if sf is not None and sf > 0.1 else "")
        )
    inc = report.get("incidents")
    if inc:
        parts = [f"{inc['watchdog_stalls']} watchdog stalls",
                 f"{inc['elastic_restarts']} elastic restarts"]
        for key, label in (("corrupt_checkpoints", "corrupt checkpoints"),
                           ("anomaly_rollbacks", "anomaly rollbacks"),
                           ("chaos_faults", "chaos faults"),
                           ("stall_escalations", "stall escalations"),
                           ("data_exhausted", "data exhaustions")):
            if inc.get(key):
                parts.append(f"{inc[key]} {label}")
        if inc.get("restarts_gave_up"):
            parts.append(f"{inc['restarts_gave_up']} gave up (budget)")
        lines.append("incidents: " + ", ".join(parts))
        for d in report.get("incident_detail", [])[-4:]:
            if d["what"] == "ckpt.corrupt":
                lines.append(f"  ckpt.corrupt step {d.get('step')}: "
                             f"{d.get('reason')}")
            else:
                lines.append(
                    f"  rollback ({d.get('reason')}): step "
                    f"{d.get('at_step')} -> {d.get('to_step')}, skipped "
                    f"{d.get('skipped_batches')} batch(es)")
    la = report.get("launch")
    if la:
        worlds = la.get("worlds") or []
        head = (f"launch: {la.get('rounds', 0)} round(s), "
                f"{la.get('restarts', 0)} cohort restart(s), worlds "
                + (" -> ".join(str(w) for w in worlds) if worlds else "?"))
        if la.get("completed"):
            head += (f"; completed at step {la.get('final_step')}"
                     + (f" loss {la['final_loss']:.6g}"
                        if la.get("final_loss") is not None else ""))
        elif la.get("gave_up"):
            head += "; GAVE UP (restart budget)"
        lines.append(head)
        for f in la.get("chaos_faults", [])[-4:]:
            lines.append(f"  chaos {f.get('kind')} -> host "
                         f"{f.get('host')} at step {f.get('step')}")
        for b in la.get("broken_by", [])[-3:]:
            lines.append(f"  cohort broken by host {b.get('host')} at "
                         f"step {b.get('step')}: {b.get('reason')}")
        for r in la.get("replans", []):
            lines.append(f"  replanned world {r.get('from')} -> "
                         f"{r.get('to')} (choose_strategy at new size)")
        asv = la.get("async_saves")
        if asv:
            mean = asv.get("mean_off_thread_s")
            lines.append(
                f"  async saves: {asv['n']}, max queue depth "
                f"{asv['max_queue_depth']}"
                + (f", mean off-thread {mean * 1e3:.1f}ms"
                   if mean is not None else ""))
    sv = report.get("serving")
    if sv:
        head = f"serving: {sv.get('n_requests', 0)} request(s)"
        if sv.get("p50_latency_s") is not None:
            head += (f", latency p50 {sv['p50_latency_s'] * 1e3:.0f}ms"
                     f" p99 {sv.get('p99_latency_s', 0) * 1e3:.0f}ms")
        if sv.get("goodput_tokens_per_s") is not None:
            head += f", goodput {sv['goodput_tokens_per_s']:.1f} tok/s"
        lines.append(head)
        parts = []
        if sv.get("ttft_p50_s") is not None:
            tl = (f"  timeline: ttft p50 {sv['ttft_p50_s'] * 1e3:.1f}ms"
                  f" p99 {sv.get('ttft_p99_s', 0) * 1e3:.1f}ms")
            if sv.get("itl_p50_s") is not None:
                tl += (f"  itl p50 {sv['itl_p50_s'] * 1e3:.2f}ms"
                       f" p99 {sv.get('itl_p99_s', 0) * 1e3:.2f}ms")
            pm = sv.get("phase_mean_s") or {}
            if pm:
                tl += ("  phase mix " + " ".join(
                    f"{k} {v * 1e3:.0f}ms" for k, v in pm.items()))
            lines.append(tl)
        if sv.get("mean_occupancy") is not None:
            parts.append(f"slot occupancy {sv['mean_occupancy']:.1%} "
                         f"over {sv.get('n_steps', 0)} step(s)")
        if sv.get("mean_queue_s") is not None:
            parts.append(f"mean queue {sv['mean_queue_s'] * 1e3:.0f}ms")
        if sv.get("mean_tokens_per_s") is not None:
            parts.append(
                f"per-request {sv['mean_tokens_per_s']:.1f} tok/s")
        parts.append(f"{sv.get('preemptions', 0)} preemption(s)")
        lines.append("  " + "  ".join(parts))
        bparts = []
        if sv.get("attention_impl"):
            bparts.append(f"decode impl {sv['attention_impl']}")
        if sv.get("mean_decode_step_s") is not None:
            bparts.append(
                f"decode step {sv['mean_decode_step_s'] * 1e3:.1f}ms")
        if sv.get("n_prefill_chunks"):
            bparts.append(
                f"prefill chunks x{sv['n_prefill_chunks']}"
                + (f" (C={sv['prefill_chunk']})"
                   if sv.get("prefill_chunk") else ""))
        if sv.get("weights_cast") is not None:
            bparts.append(
                f"weights {sv['weight_bytes_compute'] / 2**30:.2f} GiB in "
                f"the compute dtype + {sv['weight_bytes_fp32'] / 2**30:.2f}"
                f" GiB float32 ({sv['weights_cast']} leaves rounded once)")
        if bparts:
            lines.append("  " + "  ".join(bparts))
        if sv.get("build_s") is not None:
            lines.append(
                f"  start-up: engine built in {sv['build_s']:.2f} s ("
                + ", ".join(f"{k.replace('_', ' ')} {v:.2f}" for k, v in
                            (sv.get("build_phases") or {}).items())
                + f"; {(sv.get('build_loads') or {}).get('load_s', 0.0):.2f}"
                " s of it loading its small programs)")
            for name, p in (sv.get("programs") or {}).items():
                # no read of the compile cache: XLA built it
                built = p["backend_s"] > 0 and not p["cache_read_s"]
                lines.append(
                    f"    {name} {'COMPILED' if built else 'loaded'} at step "
                    f"{p['at_step']} in {p['load_s']:.2f} s (trace "
                    f"{p['trace_s']:.2f}, lowering {p['lower_s']:.2f}, "
                    + (f"compile {p['backend_s']:.2f}" if built else
                       f"cache read {p['cache_read_s']:.2f}")
                    + f"; first call {p['call_s']:.2f} s)"
                    + (f"; LOADED AGAIN since, last at step "
                       f"{p['reloaded_at']}: the seconds are those of all "
                       f"{p['n']} loads" if "reloaded_at" in p else ""))
        if sv.get("kv_bytes_full") is not None:
            kinds = sv.get("layer_kinds") or ()
            eparts = [
                f"KV pool {sv['kv_bytes_full'] / 2**30:.2f} GiB in pages "
                f"for max_len + {sv['kv_bytes_window'] / 2**30:.2f} GiB in "
                f"window rings"
                + (f" ({kinds.count('full_attention')} full, "
                   f"{kinds.count('sliding_attention')} sliding layers)"
                   if kinds else "")]
            if sv.get("kv_bytes_latent"):
                rank, rot, stored = sv["latent_row"]
                eparts.append(
                    f"of the pages {sv['kv_bytes_latent'] / 2**30:.2f} GiB "
                    f"latent: one row a token of {rank} + {rot} numbers, "
                    f"stored in {stored} "
                    f"({kinds.count('latent_attention')} latent layers)")
            if sv.get("state_bytes_linear"):
                eparts.append(
                    f"state pool {sv['state_bytes_linear'] / 2**30:.3f} GiB "
                    f"of recurrent state + "
                    f"{sv['conv_bytes_linear'] / 2**30:.3f} GiB of "
                    f"convolution tails "
                    + " and ".join(
                        f"({kinds.count(kind)} {name} layers)"
                        for kind, name in (("linear_attention", "linear"),
                                           ("state_space", "state-space"))
                        if kind in kinds)
                    + (", {0}: a decay a {1}".format(*sv["linear_mixer"])
                       if sv.get("linear_mixer") else "")
                    + (f", beta up to {sv['linear_write_max']}"
                       if sv.get("linear_write_max") else "")
                    + (f", {sv['mean_state_rows']:.1f} state rows a call"
                       if sv.get("mean_state_rows") is not None else "")
                    + (f" ({sv['state_rows_live_share']:.0%} of the rows "
                       f"the step kernels walked)"
                       if sv.get("state_rows_live_share") is not None
                       else ""))
            if sv.get("cross_start") is not None:
                eparts.append(
                    f"cross-decoder from layer {sv['cross_start']}: "
                    f"{sv['paged_sets']} paged sets for "
                    f"{sv['paged_sets'] + sv['shared_readers']} attention "
                    f"layers ({sv['shared_readers']} read another layer's "
                    f"pages)"
                    + (f", its layers ran {sv['cross_rows_share']:.1%} of "
                       f"the rows the layers before it ran (read.cross_rows "
                       f"/ read.self_rows)"
                       if sv.get("cross_rows_share") is not None else ""))
            if sv.get("attention_form") == "differential":
                eparts.append("attention: differential (pairs of heads, two "
                              "softmaxes subtracted, values twice as wide)")
            if sv.get("attn_gate"):
                eparts.append("attention: a sigmoid gate on its output")
            if sv.get("experts_published"):
                eparts.append(
                    f"experts {sv['experts_held']} held of "
                    f"{sv['experts_published']}"
                    + (" in EVERY layer (no dense FFN)"
                       if sv.get("dense_layers") == 0 else "")
                    + (f" + {sv['zero_experts']} zero-compute"
                       if sv.get("zero_experts") else "")
                    + (" (a shortcut branch over two sublayers)"
                       if sv.get("shortcut_experts") else ""))
            if sv.get("mean_moe_pairs") is not None:
                eparts.append(
                    f"a decode step: {sv['mean_moe_pairs']:.1f} pairs here "
                    f"on {sv['mean_moe_experts_touched']:.1f} experts, at "
                    f"most {sv['max_moe_expert_tokens']} tokens on one")
            lines.append("  " + "  ".join(eparts))
        if sv.get("moe_zero_pairs"):
            lines.append(
                f"  zero-compute experts: {sv['moe_zero_pairs']} pairs over "
                f"{sv['moe_rows']} routed rows "
                f"({sv['moe_zero_pairs'] / sv['moe_rows']:.2f} a row, all "
                f"expert layers)")
        if sv.get("moe_tiles"):
            chunk, alone = sv["moe_tiles_laid"] or (0, 0)
            lines.append(
                f"  expert row tiles (laid: {chunk} a call with a chunk, "
                f"{alone} a decode-only call), live of laid a call: "
                + ", ".join(
                    f"{live / n:.1f} of {laid} ({live / n / laid:.3f}) "
                    f"over {n} calls" for laid, n, live in sv["moe_tiles"]))
        if sv.get("attn_grid_dense"):
            lines.append(
                f"  paged attention grid: {sv['attn_grid_items']} live "
                f"(slot, key group) items of {sv['attn_grid_dense']} in a "
                f"dense grid over the decode steps "
                f"({sv['attn_grid_items'] / sv['attn_grid_dense']:.3f})")
        if sv.get("attn_pages_copied"):
            lines.append(
                f"  paged attention pages: {sv['attn_pages_live']} hold a "
                f"key a slot attends of {sv['attn_pages_copied']} its items "
                f"copied "
                f"({sv['attn_pages_live'] / sv['attn_pages_copied']:.3f})")
        if sv.get("step_phase_mean_s"):
            lines.append(
                "  step phases (mean ms, host): " + " ".join(
                    f"{k} {v * 1e3:.2f}"
                    for k, v in sv["step_phase_mean_s"].items()
                    if v is not None)
                + (f" self {sv['mean_step_self_s'] * 1e3:.2f}"
                   if sv.get("mean_step_self_s") is not None else "")
                + (f" of step {sv['mean_step_s'] * 1e3:.2f}"
                   if sv.get("mean_step_s") is not None else "")
                + (f"; XLA built programs in this process during "
                   f"{sv['steps_that_compiled']} step(s)"
                   if sv.get("steps_that_compiled") else ""))
        if sv.get("steps_ahead_share") is not None:
            kept = sv.get("step_new_tokens") or 0
            thrown = sv.get("discarded_tokens") or 0
            lines.append(
                f"  decode dispatched ahead of the read in "
                f"{sv['steps_ahead_share']:.1%} of "
                f"{sv['decode_calls']} decoding step(s); {thrown} "
                f"slot-step(s) decoded and thrown away"
                + (f" ({thrown / (kept + thrown):.2%} of {kept + thrown})"
                   if kept + thrown else ""))
        if sv.get("fused_steps"):
            lines.append(
                f"  {sv['fused_steps']} prefill chunk(s) carried the decode "
                f"rows of their step, one program for both: "
                f"{sv['fused_share_of_chunk_steps']:.1%} of the steps with "
                f"a chunk"
                + (f", {sv['fused_share_of_decode_rows']:.1%} of the decode "
                   f"rows" if sv.get("fused_share_of_decode_rows") is not None
                   else ""))
        if sv.get("decode_steps_with_chunk"):
            only = sv.get("mean_step_decode_only_s")
            lines.append(
                f"  {sv['decode_steps_with_chunk']} of "
                f"{sv['decode_steps']} decoding step(s) also ran a "
                f"prefill chunk: {sv['mean_step_with_chunk_s'] * 1e3:.2f}"
                " ms a step"
                + (f" against {only * 1e3:.2f} ms decode-only"
                   if only is not None else ""))
        if sv.get("calls_by_read"):
            names = {"chunk": "a chunk", "decode": "a decode step alone",
                     "chunk_deep": f"a chunk from position {DEEP_POS} on"}
            lines.append(
                "  a call by the one program it waited for (median ms): "
                + ", ".join(f"{names[k]} {m * 1e3:.2f} ({n} calls)"
                            for k, (m, n) in sv["calls_by_read"].items()))
        if sv.get("chunk_key_blocks"):
            n, blocks = sv["chunk_key_blocks"]
            kernel = [k for k, v in (sv.get("chunk_attention") or {}).items()
                      if v == "kernel"]
            lines.append(
                f"  a chunk's attention in a kernel ({', '.join(kernel)} "
                f"layers): {blocks / n:.1f} key blocks a chunk call over "
                f"the layers ({n} calls)")
        if sv.get("decode_calls_by_keys"):
            lines.append(
                "  a decode step alone by the keys a row read (median ms): "
                + ", ".join(f"{k} {m * 1e3:.2f} ({n} calls)" for k, (m, n)
                            in sv["decode_calls_by_keys"].items()))
        if sv.get("stalls"):
            st, w = sv["stalls"], sv["stalls"]["worst"]
            lines.append(
                f"  {st['n']} stalled call(s) lost {st['lost_s'] * 1e3:.1f} "
                f"ms; Python's collector ran {st['gc_s'] * 1e3:.1f} ms "
                f"inside them ({st['gc_full']} full pass(es)); the longest, "
                f"step {w['step']}, took {w['step_s'] * 1e3:.1f} ms where "
                f"its kind ({w['kind']}) takes {w['median_s'] * 1e3:.2f}"
                + (f", most of it in {w['phase']}" if w["phase"] else ""))
        if (sv.get("tp") or 1) > 1:
            lines.append(f"  tp {sv['tp']}")
        if sv.get("spec_rounds"):
            rate = sv.get("spec_accept_rate")
            lines.append(
                f"  speculative: k={sv.get('spec_k')}, "
                f"{sv.get('spec_accepted', 0)}/{sv.get('spec_drafted', 0)} "
                "drafts accepted"
                + (f" ({rate:.1%})" if rate is not None else "")
                + f" over {sv['spec_rounds']} round(s)")
        if ("adapter_hits" in sv or "adapter_faults" in sv
                or sv.get("mean_adapters_resident") is not None):
            aparts = [
                f"{sv.get('adapter_hits', 0)} hit(s) / "
                f"{sv.get('adapter_faults', 0)} fault(s)"]
            if sv.get("adapter_hit_rate") is not None:
                aparts.append(f"hit rate {sv['adapter_hit_rate']:.1%}")
            if sv.get("adapter_evictions"):
                aparts.append(f"{sv['adapter_evictions']} eviction(s)")
            if sv.get("adapter_stalls"):
                aparts.append(f"{sv['adapter_stalls']} pool stall(s)")
            if sv.get("mean_adapters_resident") is not None:
                aparts.append(
                    f"mean resident {sv['mean_adapters_resident']:.1f}"
                    + (f" (pinned {sv['mean_adapters_pinned']:.1f})"
                       if sv.get("mean_adapters_pinned") is not None
                       else ""))
            lines.append("  adapters: " + "  ".join(aparts))
        if "prefix_queries" in sv or sv.get("prefix_blocks") is not None:
            pparts = [
                f"{sv.get('prefix_hit_requests', 0)}/"
                f"{sv.get('prefix_queries', 0)} request(s) hit"]
            if sv.get("prefix_hit_rate") is not None:
                pparts.append(
                    f"hit rate {sv['prefix_hit_rate']:.1%} "
                    f"({sv.get('prefix_cached_tokens', 0)} cached "
                    f"token(s))")
            if sv.get("prefix_saved_chunks") is not None:
                pparts.append(
                    f"{sv['prefix_saved_chunks']} prefill chunk(s) "
                    f"saved")
            if sv.get("cow_forks"):
                pparts.append(f"{sv['cow_forks']} CoW fork(s)")
            if sv.get("prefix_blocks") is not None:
                pparts.append(f"{sv['prefix_blocks']} block(s) indexed")
            lines.append("  prefix cache: " + "  ".join(pparts))
    slo = report.get("slo_incidents")
    if slo:
        lines.append(f"slo incidents: {slo.get('breaches', 0)} "
                     f"breach(es), {slo.get('recoveries', 0)} "
                     f"recovery(ies)")
        for inc in slo.get("incidents", ()):
            where = (f"window [{inc.get('window_start_s')}s, "
                     f"{inc.get('window_end_s')}s)")
            if inc.get("kind") == "breach":
                lines.append("  BREACH " + where + ": "
                             + "; ".join(inc.get("violations") or ()))
            else:
                lines.append(
                    "  recovered " + where
                    + (f" after {inc['ok_windows']} clean window(s)"
                       if inc.get("ok_windows") is not None else ""))
    gw = report.get("gateway")
    if gw:
        rej = gw.get("rejected", 0)
        lines.append(
            f"gateway: {gw.get('requests', 0)} request(s) accepted, "
            f"{rej} rejected"
            + (f" ({gw.get('rejected_rate_limit', 0)} rate-limit, "
               f"{gw.get('rejected_backpressure', 0)} backpressure)"
               if rej else ""))
        for rp in gw.get("replans", ()):
            lines.append(
                f"  replan t={(rp.get('t') or 0.0):7.2f}s "
                f"[{rp.get('reason')}]: {rp.get('current')} -> "
                f"{rp.get('chosen')} replica(s) at "
                f"{(rp.get('rate_per_s') or 0):.0f} req/s")
        for sc in gw.get("scales", ()):
            what = (f"scale-{sc.get('kind')}" if sc.get("kind")
                    else "scale")
            extra = (f", {sc['requeued']} request(s) requeued"
                     if sc.get("requeued") is not None else "")
            lines.append(
                f"  {what} t={(sc.get('t') or 0.0):7.2f}s: "
                f"{sc.get('replica')} -> fleet of "
                f"{sc.get('n_replicas')}{extra}")
        for fo in gw.get("failovers", ()):
            lines.append(
                f"  failover t={(fo.get('t') or 0.0):7.2f}s: "
                f"{fo.get('replica')} ({fo.get('reason')}), "
                f"{fo.get('n_requeued')} request(s) salvaged")
        if gw.get("hedges_dispatched"):
            lines.append(
                f"  hedges: {gw['hedges_dispatched']} dispatched, "
                f"{gw.get('hedges_won', 0)} won")
        if gw.get("breaker_opens"):
            lines.append(
                f"  circuit breaker: opened "
                f"{gw['breaker_opens']} time(s)")
        for dg in gw.get("degrade_history", ()):
            lines.append(
                f"  {dg.get('kind')} t={(dg.get('t') or 0.0):7.2f}s: "
                f"level {dg.get('level')} ({dg.get('reason') or '?'})")
        if gw.get("final_replicas") is not None:
            lines.append(
                f"  final fleet: {gw['final_replicas']} replica(s)")
    sest = report.get("serve_estimate")
    if sest:
        head = (f"serve estimate: {sest.get('max_streams')} stream(s) "
                f"of {sest.get('max_len')} tokens "
                f"({sest.get('num_blocks')} blocks x "
                f"bs {sest.get('block_size')}"
                f"{', int8 KV' if sest.get('quant_kv') else ''})")
        if sest.get("requested_streams") is not None:
            head += f", requested {sest['requested_streams']}"
        if sest.get("attention_impl"):
            head += f", {sest['attention_impl']} decode"
        if sest.get("decode_workspace_bytes"):
            head += (f" (+{sest['decode_workspace_bytes'] // 1024} KiB "
                     f"gather workspace)")
        if sest.get("n_adapters"):
            head += (f", adapter pool {sest['n_adapters']}x "
                     f"r{sest.get('adapter_rank')} "
                     f"{'int8' if sest.get('quant_adapters') else 'f32'} "
                     f"({_fmt_bytes(sest.get('adapter_pool_bytes'))})")
        if sest.get("prefix_cache"):
            head += (f", prefix index "
                     f"{_fmt_bytes(sest.get('prefix_index_bytes'))}")
            if sest.get("effective_max_streams") is not None:
                head += (f" (~{sest['effective_max_streams']} effective "
                         f"stream(s) at "
                         f"{sest.get('expected_hit_rate') or 0:.0%} hit "
                         f"rate)")
        lines.append(head)
    sim = report.get("simulate")
    if sim:
        head = "simulate:"
        if sim.get("n_candidates") is not None:
            head += (f" {sim['n_candidates']} candidate(s) over "
                     f"{sim.get('n_topologies', '?')} topology(ies)")
            if sim.get("n_replays") is not None:
                head += f", {sim['n_replays']} serve replay(s)"
            if sim.get("n_slo_ok") is not None:
                head += f", {sim['n_slo_ok']} meet the SLO"
        lines.append(head)
        for e in (sim.get("ranked") or [])[:8]:
            mfu = (f"mfu {e['mfu']:.1%}"
                   if e.get("mfu") is not None else "mfu -")
            step = (f"step {e['step_time_s'] * 1e3:.1f}ms"
                    if e.get("step_time_s") is not None else "step -")
            hd = (f"headroom {e['hbm_headroom_frac']:.0%}"
                  if e.get("hbm_headroom_frac") is not None
                  else "headroom -")
            tok = (f"{e['tok_s_per_chip']:.1f} tok/s/chip"
                   if e.get("tok_s_per_chip") is not None else "- tok/s")
            p99 = (f"p99 {e['p99_s'] * 1e3:.0f}ms"
                   if e.get("p99_s") is not None else "p99 -")
            surv = (f"surv {e['survival']:.3f}"
                    if e.get("survival") is not None else "surv -")
            tail = (" ok" if e.get("slo_ok")
                    else "  !! " + "; ".join(e.get("slo_violations")
                                             or ("no SLO result",)))
            lines.append(
                f"  #{e.get('rank')} {e.get('topology')} "
                f"{e.get('plan')} [{e.get('admission')}]  "
                f"{mfu}  {step}  {hd}  {tok}  {p99}  {surv} " + tail)
    lint = report.get("lint")
    if lint:
        head = (f"lint ({lint.get('phase', 'check')}): "
                f"{lint.get('errors', 0)} error(s), "
                f"{lint.get('warnings', 0)} warning(s)")
        by_code = lint.get("by_code")
        if by_code:
            head += "  [" + "  ".join(
                f"{c}×{n}" for c, n in sorted(by_code.items())) + "]"
        lines.append(head)
        for f in lint.get("findings", [])[-6:]:
            lines.append(f"  {f.get('code')} {f.get('severity')} "
                         f"{f.get('where')}: {f.get('msg')}")
        if lint.get("skipped"):
            lines.append(f"  preflight skipped: {lint['skipped']}")
    protocol = report.get("protocol")
    if protocol:
        lines.append("protocol model check:")
        for p in protocol:
            status = ("ok" if p.get("complete") and not p.get("violations")
                      else "TRUNCATED" if not p.get("complete")
                      else "VIOLATED")
            lines.append(
                f"  {p.get('model')}: {p.get('states')} states / "
                f"{p.get('transitions')} transitions, depth "
                f"{p.get('depth')}, frontier peak "
                f"{p.get('frontier_peak')}, {p.get('wall_s')}s — "
                f"{status}"
                + (f" ({p.get('violations')} counterexample(s))"
                   if p.get("violations") else ""))
    me = report.get("memory_estimate")
    if me:
        mesh = "x".join(f"{a}{n}" for a, n in
                        sorted((me.get("degrees") or {}).items()))
        head = (f"memory estimate (static, per device): peak "
                f"{_fmt_bytes(me.get('peak_bytes'))}")
        if me.get("budget_bytes"):
            head += f" / budget {_fmt_bytes(me['budget_bytes'])}"
        head += (f"  [{me.get('strategy')} mesh {mesh or '1'}"
                 f"{', remat' if me.get('remat') else ''}]")
        lines.append(head)
        lines.append(
            f"  params {_fmt_bytes(me.get('params_bytes'))}"
            f"  optimizer {_fmt_bytes(me.get('optimizer_bytes'))}"
            f"  activations {_fmt_bytes(me.get('activation_bytes'))}"
            f"  batch {_fmt_bytes(me.get('batch_bytes'))}")
        if me.get("compiled_peak_bytes"):
            lines.append(
                f"  xla compiled peak "
                f"{_fmt_bytes(me['compiled_peak_bytes'])} "
                f"(static/compiled {me.get('static_over_compiled')}x)")
    return "\n".join(lines)

