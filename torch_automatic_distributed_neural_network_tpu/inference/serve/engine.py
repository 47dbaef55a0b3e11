"""Serving engine: persistent jitted decode over a slot-padded batch.

One fixed-shape decode step serves every live request at once.  The
batch axis is ``n_slots`` *slots*, not requests: a slot is either bound
to a running request or inactive (null block table, masked sampling).
Each call advances EVERY active request by one token; between calls the
scheduler evicts finished requests and admits queued ones, so the step
executable compiles once and runs for the life of the server — no
recompiles as the request mix churns (a prompt streams through one
fixed-shape chunk program, whatever its length).

The programs are ``programs.decode_step``, ``programs.prefill_chunk``
and ``programs.chunk_and_step`` (``serve/programs.py``): the same three for
every model.  They walk the layers one by one, a model with one kind of
layer and a model whose layers differ (``cfg.layer_types``: window, full,
linear and latent attention, expert FFNs) alike, the per-layer math the TRAINING
modules applied piecewise, and every layer's pages, or the recurrent state
of a ``linear_attention`` layer, updated in place.

Which of them a call runs is read off the engine's own state.  A call with
a chunk to run AND a slot decoding dispatches ONE program,
``chunk_and_step``: the chunk's C rows and the S decode rows go through the
layers together, so the weights, an expert layer's touched experts and the
head are read once and not twice (a trace shows it under the chunk's name,
``jit_serve_prefill_chunk``: it is a chunk with more rows).  It is
dispatched where the decode step is, after ``grow`` and the copy-on-write
guard and before the step before is read; a prompt whose last chunk carried
decode rows starts decoding in the NEXT call, with the first token this
call's logits gave (as two calls it joins the same call's step: its second
token moves by one call, no token changes).  While nobody decodes, a chunk
of such an engine runs in its own place as the SAME program with every
decode row idle (null tables, the null row: a few rows more in each
product), so the engine builds two programs and not three: a third cost
2 s of every start-up, an idle row costs microseconds of a prompt's first
chunks.  An engine fuses unless a step's rows are not one token a slot
off the base weights: a speculative engine (a verify step has 1 + k rows a
slot) and one built with ``lora_spec`` (a tenant's rows add its delta) hold
``prefill_chunk`` (or its tenant form) beside the decode step instead and
run the two as two calls.  ``serve.step`` carries ``fused`` and
``fused_decode_rows``.

Prefill writes a prompt's keys and values straight into the request's
blocks and attends through its table: the same math as ``generate()``'s
prefill over the same stored values, which is what makes token-parity
with sequential generation testable (greedy decoding is deterministic;
for stochastic sampling the engine is reproducible under its own rng but
not per-request-identical to ``generate()``, since one categorical call
samples all slots).  Prefill is CHUNKED: the prompt streams through one
jitted [1, C]-chunk trace (C = ``prefill_chunk`` snapped to a divisor of
max_len), one chunk per engine step, INTERLEAVED with decode — a long
prompt does not stall every running request for its whole prefill, and no
per-prompt-length retrace exists.

The decode-step attention is config-gated (``attention_impl``):
``"paged"`` (default) runs the fused Pallas kernel that reads the
block table in-kernel (ops/paged_attention.py — no dense gather);
``"dense"`` keeps the reference ``gather_blocks`` + ``xla_attention``
path the kernel is parity-pinned against.

Multi-tenant LoRA (``lora_spec=...``): each request may name a
registered adapter; the decode step gathers its (A, B) factors from the
fixed-shape adapter pool by per-slot id and applies the segmented
low-rank delta inside the layer body, so heterogeneous tenants
(and the base model, via identity adapter 0) share the ONE decode
trace.  Prefill merges the tenant's factors into the weights INSIDE a
jitted chunk step (rank-r matmul fused into the weight load, factors
are traced operands — still one chunk trace for every tenant).
Adapters are pinned in the pool only while their request is RUNNING;
if every pool slot is pinned when a prefill completes, the request is
bounced back to the queue recompute-style (see scheduler.requeue).

Speculative decoding (``speculative=k``, greedy only): each step drafts
k tokens per slot host-side (prompt-lookup n-grams — no draft model),
verifies ``[last, d_1..d_k]`` in the same batched step (the chunk axis
T = 1+k is baked into the trace), and accepts the longest agreeing
prefix plus the target's bonus token — between 1 and k+1 tokens per
slot per step, token-identical to plain greedy.  Rolled-back draft KV
needs no cleanup: positions past a slot's context are masked out of
attention and overwritten by the next step's writes.  Accept rates
journal as ``serve.speculate`` events.

A step's tokens stay on the device, and the host reads them ONE CALL
LATE: ``step()`` dispatches decode n + 1 (each continuing slot takes its
token from step n's output array, where it lies) and only then fetches
step n, so eviction, admission, chunk dispatch and the next step's
operands are the host's work while the device decodes.  Where a write
lands, and whether a request has used up ``max_new_tokens``, is known from
the count of tokens DISPATCHED (``Request.n_dispatched``), never from a
token's value.  What lags is the value alone: an ``eos_id`` is seen one
step late (that step's token for the slot is thrown away,
``discarded_tokens``; its KV write fell into a page the request owned, and
on a ``linear_attention`` layer it spoiled the state of a slot whose next
prompt starts from zeros anyway), and a token's wall time is the moment the
host holds it.  The depth is 1 or 0
by what the engine can see: speculative drafts are a lookup over the
tokens just produced, so ``speculative > 0`` reads before it dispatches.

Telemetry: every finished request journals a ``serve.request_done``
event carrying its full span timeline — submit -> admit (queue wait)
-> prefill chunks (prefix-cache skip included) -> first token (TTFT)
-> per-token inter-token latencies -> preempt/recompute tax -> finish — and every step a
``serve.step`` event (slot occupancy, free blocks, tokens emitted,
adapter residency, and ``phases``: the host seconds of each phase of
the iteration, see ``PHASES``) through ``obs.journal``.  Each phase is
also a ``serve.<phase>`` profiler annotation inside one ``serve.step``
annotation, so a profiler capture shows the host loop on the device
trace's clock; nothing is fenced for it.  ``tadnn report`` renders
p50/p99 latency, TTFT/ITL percentiles, goodput, occupancy, and
speculative accept rates from exactly these records, and ``tadnn
monitor`` (obs/slo_monitor) folds the same stream into rolling SLO
windows while the engine is still running.  Timeline stamps route
through the scheduler's injectable clock so a discrete-event replay
produces the same fields on virtual time.

Start-up accounts for itself: ``serve.engine``, emitted at the end of
construction, says what building the engine cost (``build_s``, the parts
in ``build_phases``, what XLA loaded meanwhile in ``build_loads``), and a
program's FIRST call is recorded where it happens (``_dispatched``): the
process's compile counter's parts over that one call (trace, lowering,
a read of the compile cache or a build), and so is a later call in which
it was loaded AGAIN.  After a step in which a program loaded the event is
emitted again with the table so far (``programs``), so the newest
``serve.engine`` is the engine's description; each load is one ``compile``
event that names the program.
"""

from __future__ import annotations

import dataclasses
import gc
import math
import os
import time
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from ...models.transformer_core import TransformerConfig, layer_plan
from ...obs import journal as _journal
from ...obs.schema import BASE_FIELDS
from ...ops.gated_delta import step_rows_walked
from ...ops.paged_attention import latent_chunk_key_blocks, tensor_degree
from ...parallel.expert import expert_tiles
from ...training.lora import LoraSpec
from ..decode import (
    SampleConfig,
    _sample,
    compute_dtype_params,
    per_layer_params,
)
from ..speculative import accept_length, ngram_propose
from . import programs
from .adapters import AdapterPool
from .kv_pool import STATE_KINDS, PagedKVPool, blocks_for_tokens, \
    stored_row
from .prefix_cache import PrefixCache
from .scheduler import Request, Scheduler

# the phases of one ``ServeEngine.step`` in program order: keys of the
# ``serve.step`` event's ``phases``, annotations ``serve.<phase>``.  Only
# ``prefill_first_token`` and ``decode_wait`` wait for the device; the
# others are host work and dispatches.  ``decode_wait`` is the wait for the
# step dispatched ONE CALL AGO (this call's is already queued behind it),
# and for the first tokens of prompts that ended since.  Only an engine
# that reads before it dispatches (``speculative > 0``) has a
# ``prefill_first_token``, and its ``decode_wait`` is for this call's step.
# A prefill lands in the request's pages as it runs: there is no commit
# to time
PHASES = ("evict", "admit", "prefill_dispatch", "prefill_first_token",
          "grow", "decode_prepare", "decode_upload",
          "decode_dispatch", "decode_wait", "emit")


def first_token(prev, logits, where, rng, sample: SampleConfig):
    """A prompt's first token, sampled from its last chunk's ``logits``
    [1, V] and left on the device: ``prev`` (a decode step's output,
    ``programs.decode_step``) with the token at its slot among the first
    tokens.  ``where`` is [slot, request id]; the key is the request's own,
    whatever else is running."""
    slot, rid = where[0], where[1]
    if sample.temperature != 0.0:  # greedy sampling reads no key
        rng = jax.random.split(jax.random.fold_in(rng, rid))[1]
    return prev.at[slot].set(_sample(logits, rng, sample)[0])


@dataclasses.dataclass
class _PrefillState:
    """Host-side cursor of one in-flight prefill: how many prompt
    tokens have streamed into the request's pages so far (a prefix-cache
    hit starts the cursor past the reused blocks), and the tenant's
    factor tree (None for base-model requests)."""

    pos: int = 0
    lora: Any = None


class ServeEngine:
    """Continuous-batching server over a model + paged KV pool.

        eng = ServeEngine(model, variables, n_slots=8, max_len=256)
        eng.submit([1, 2, 3], max_new_tokens=32, eos_id=0)
        done = eng.run()          # [Request] with .prompt + .out_tokens

    ``submit`` is non-blocking (requests queue); ``step()`` advances the
    world by one decode iteration (evict / admit+prefill / grow /
    decode); ``run()`` steps until idle.  A long-lived server calls
    ``submit`` from its frontend and ``step`` in a loop — nothing here
    blocks on a full batch.

    ``eng.params`` is the tree the base programs take, not the one the
    engine was given: ``decode.per_layer_params`` has rounded the layers'
    weights to ``cfg.dtype`` once (``compute_dtype_params``), so no call
    rounds them again, and taken a scanned ``layers`` stack apart into
    ``layers_0 ..``, so no call slices it.
    Only an engine with ``lora_spec`` also keeps the tree it was given,
    for the tenant prefill's float32 merge.
    """

    def __init__(self, model, variables: Any, *,
                 n_slots: int = 8,
                 max_len: int = 256,
                 block_size: int = 16,
                 num_blocks: int | None = None,
                 quant_kv: bool = False,
                 cache_dtype=jnp.bfloat16,
                 sample: SampleConfig | None = None,
                 admission: str = "reserve",
                 attention_impl: str = "paged",
                 prefill_chunk: int = 32,
                 lora_spec: LoraSpec | None = None,
                 n_adapters: int = 8,
                 quant_adapters: bool = False,
                 speculative: int = 0,
                 prefix_cache: bool = False,
                 mesh=None,
                 rng: jax.Array | None = None,
                 journal: Any = None,
                 export_cache: Any = None):
        if attention_impl not in ("paged", "dense"):
            raise ValueError(
                f"unknown attention_impl {attention_impl!r} "
                f"(expected 'paged' or 'dense')")
        # what this construction costs a restart: host seconds of all of it
        # and of its parts (``serve.engine``'s ``build_s``, ``build_phases``)
        # and what XLA loaded meanwhile (``build_loads``: the casts' and the
        # pool's small programs, an abstract trace of each serving program)
        t_build = time.monotonic()
        self._compiles = _journal.compile_counter()
        loads_before = self._compiles.loads()
        build: dict[str, float] = {}
        self.cfg: TransformerConfig = model.cfg
        # the tree the base programs take (see the class docstring)
        with _journal.phase(build, "weights", "serve.build.weights"):
            self.params = per_layer_params(variables["params"], self.cfg)
        # the tenant prefill adds a low-rank delta to the weight as it
        # was given and rounds the SUM: it keeps that tree, and an
        # engine without tenants keeps no reference to it
        self._merge_base = (variables["params"] if lora_spec is not None
                            else None)
        self.sample = sample or SampleConfig(temperature=0.0)
        self.n_slots = n_slots
        self.max_len = max_len
        self.attention_impl = attention_impl
        self.speculative = int(speculative)
        if self.speculative < 0:
            raise ValueError(f"speculative={speculative} must be >= 0")
        if self.speculative and self.sample.temperature != 0.0:
            raise ValueError(
                "speculative decoding is greedy-only (the accept rule "
                "compares against the target's argmax; sampled variants "
                "need rejection resampling) — use temperature=0.0")
        if prefill_chunk < 1:
            raise ValueError(f"prefill_chunk={prefill_chunk} must be >= 1")
        # snap the chunk to a divisor of max_len, so the cursor can never
        # run past it (learned positions past the table's end would clamp
        # and silently corrupt a chunk's embeddings)
        self.prefill_chunk = math.gcd(
            min(int(prefill_chunk), max_len), max_len)
        self.mesh = mesh
        kinds = self.cfg.layer_types or ()
        # the layers that keep a recurrent state, a row a slot
        self._n_linear = sum(kind in STATE_KINDS for kind in kinds)
        # those of them whose decay is a channel's (Kimi Delta Attention)
        self._n_kda = (kinds.count("linear_attention")
                       if self.cfg.linear_decay == "channel" else 0)
        diff = self.cfg.diff_attention
        # what is not served, each with its reason (the message names the
        # option and the kind of layer)
        refused = {
            # a sliding layer's ring is written over as its window passes,
            # so a finished prompt's keys are no longer there for another
            # request to reuse
            "prefix_cache with sliding_attention layers": (
                prefix_cache and "sliding_attention" in kinds),
            # a hit resumes a prompt at the matched boundary, and a linear
            # layer would need its state AT that boundary: nobody kept it
            "prefix_cache with linear_attention layers (a hit needs the "
            "recurrent state at the matched boundary, which is not kept)": (
                prefix_cache and "linear_attention" in kinds),
            # a verify step writes k drafts into the state; a rejected one
            # cannot be taken out of it again (keys past a context are
            # simply masked)
            "speculative > 0 with linear_attention layers (a rejected "
            "draft cannot be taken out of the recurrent state)": (
                self.speculative > 0 and "linear_attention" in kinds),
            # the adapter pool factorizes the projections of a scanned
            # ``layers`` stack
            "lora_spec for a model with layer_types": (
                lora_spec is not None and bool(kinds)),
            # the expert layer has no form under a mesh: on one chip it
            # runs without its exchange
            "mesh for a model with expert layers": (
                mesh is not None and bool(self.cfg.n_expert_layers)),
            # the state pool and its two kernels have no sharded form
            "mesh for a model with linear_attention layers (the recurrent "
            "state has no sharded form)": (
                mesh is not None and "linear_attention" in kinds),
            # all heads read the ONE latent row a token: there is no KV head
            # to split over the tensor axis, and the latent kernel has no
            # per-shard form
            "mesh for a model with latent_attention layers (one latent row "
            "a token has no head axis to shard)": (
                mesh is not None and "latent_attention" in kinds),
            # int8 pages carry a scale a (token, KV head); a latent row is a
            # normed latent beside a rotated key part, two ranges in one
            # row, and the latent kernel does not dequantize
            "quant_kv with latent_attention layers (a latent row has no "
            "int8 form)": (quant_kv and "latent_attention" in kinds),
            # as for a linear layer: a hit would need the scan's state and
            # the convolution's tail AT the matched boundary
            "prefix_cache with state_space layers (a hit needs the scan's "
            "state at the matched boundary, which is not kept)": (
                prefix_cache and "state_space" in kinds),
            "speculative > 0 with state_space layers (a rejected draft "
            "cannot be taken out of the scan's state)": (
                self.speculative > 0 and "state_space" in kinds),
            "mesh for a model with state_space layers (the scan's state "
            "and its two kernels have no sharded form)": (
                mesh is not None and "state_space" in kinds),
            # the differential pair reads both value heads of a pair of KV
            # heads side by side in a FOLDED page (the MXU kernel at another
            # wiring of its lanes); int8 pages and pages sharded by KV head
            # are kept unfolded, for the VPU kernel, which has no such form
            "quant_kv with diff_attention (the differential pair reads "
            "folded pages; int8 pages have no such form)": (
                quant_kv and diff),
            "mesh with diff_attention (the differential pair reads folded "
            "pages; a pool sharded by KV head has no such form)": (
                mesh is not None and diff)}
        if any(refused.values()):
            raise ValueError("not served: " + "; ".join(
                k for k, v in refused.items() if v))
        self.max_blocks = blocks_for_tokens(max_len, block_size)
        if num_blocks is None:
            # worst case every slot full-length, plus the null block
            num_blocks = n_slots * self.max_blocks + 1
        with _journal.phase(build, "pool", "serve.build.pool"):
            self.pool = PagedKVPool(
                self.cfg, num_blocks=num_blocks, block_size=block_size,
                dtype=cache_dtype, quantize=quant_kv, mesh=mesh,
                n_slots=n_slots, max_blocks=self.max_blocks,
                prefill_chunk=self.prefill_chunk)
        self._win_rows = list(self.pool.win_tables)  # a slot's ring
        self.lora_spec = lora_spec
        self.adapter_pool: AdapterPool | None = None
        if lora_spec is not None:
            self.adapter_pool = AdapterPool(  # reads the stack's shapes
                variables["params"], lora_spec, n_adapters=n_adapters,
                quantize=quant_adapters, mesh=mesh)
        # cross-request prefix caching: radix index over resident
        # prompt-prefix blocks; matched prefixes are ref'd into the new
        # request's table and their chunks skipped (the later chunks
        # attend to the reused blocks through the table, where they lie).
        # Match alignment: block granularity in fp mode; in int8 mode
        # additionally snapped to prefill-chunk boundaries, so the
        # cache-off run's chunk partition of the recomputed suffix is
        # reproduced exactly (bit-identical tokens either way).
        self.journal = journal or _journal.get_default()  # never None
        self._gc = _journal.gc_counter()
        self._phases: dict[str, float] = {}  # this step's, by PHASES name
        self._prefix_cache = None
        match_align = None
        if prefix_cache:
            self._prefix_cache = PrefixCache(
                block_size=block_size, allocator=self.pool.allocator,
                journal=self.journal)
            match_align = (math.lcm(block_size, self.prefill_chunk)
                           if quant_kv else block_size)
        self.prefix_queries = 0
        self.prefix_hits = 0
        self.prefix_cached_tokens = 0
        self.prefix_saved_chunks = 0
        self.cow_forks = 0
        self.scheduler = Scheduler(
            n_slots=n_slots, allocator=self.pool.allocator,
            block_size=block_size, admission=admission,
            adapter_pool=self.adapter_pool,
            spec_lookahead=self.speculative,
            prefix_cache=self._prefix_cache, match_align=match_align)
        self._rng = jax.random.key(0) if rng is None else rng
        # TADNN_DEBUG_INVARIANTS=1: run the scheduler/allocator/adapter
        # invariant audit after EVERY step (CI serve-smoke legs set it;
        # off by default — it walks all slots and the free list)
        self._debug_invariants = (
            os.environ.get("TADNN_DEBUG_INVARIANTS", "") not in ("", "0"))
        self._step_count = 0
        self._occupancy_sum = 0.0
        self.spec_drafted = 0   # lifetime draft-token counters (k > 0)
        self.spec_accepted = 0
        # lifetime generated-token count; step() diffs it to put a
        # per-step new_tokens field on serve.step (the live monitor's
        # smooth tok/s signal — request completions are too lumpy)
        self.tokens_emitted = 0
        self.finished: list[Request] = []
        self._prefill: dict[int, _PrefillState] = {}
        # how many decode steps may be dispatched with their predecessor
        # unread: 1, unless the next step's operands need the tokens' values
        # (drafts are an n-gram lookup over them, and the accepted length
        # sets the next context)
        self._ahead = 0 if self.speculative else 1
        # the newest decode step's output, on the device (the slots' first
        # tokens, the step's, expert counters: ``programs.decode_step``),
        # and what of it the host has yet to read: the step's (slot, request,
        # request.preempted at dispatch) rows, and the same for prompts
        # whose first token ``_first_fn`` has put there since
        self._out = programs.step_output(n_slots, 1 + self.speculative)
        self._rows: list[tuple[int, Request, int]] = []
        self._firsts: list[tuple[int, Request, int]] = []
        self._rows_fused = False  # the rows rode in a chunk (``_rides``)
        # what went out since the last read, which the read that waits for
        # it puts on its call's event (``_sent_program``), and what this
        # call has read so far (``serve.step``'s ``read``)
        # where the model ends in layers that keep no cache, the rows of a
        # program that ran the layers before ``cfg.cross_start`` and those
        # that ran the ones from it on, by program: read off the programs'
        # traces once they are built, below
        self._program_rows: dict[str, tuple[int, int]] = {}
        self._sent = self._nothing_sent()
        # ... after the unread output was made (a chunk in its own place
        # behind an unread step): the read AFTER that one waits for it
        self._sent_late: dict | None = None
        self._step_read: dict | None = None
        # lifetime counts: decode steps dispatched with the step before
        # unread, and slot-steps decoded and thrown away (a step in flight
        # when its request's EOS was read or it was preempted); step()
        # diffs them onto serve.step
        self.steps_ahead = 0
        self.discarded_tokens = 0
        # the engine's programs are jitted from named functions: a
        # trace shows jit_serve_decode_step and jit_serve_prefill_chunk
        # (a functools.partial has no name: jit__unknown).  They close
        # over locals, not self: no cycle keeps a dropped engine alive
        cfg, sample, pool_spec = self.cfg, self.sample, self.pool.spec
        n_tok, max_blocks = 1 + self.speculative, self.max_blocks

        def serve_decode_step(*operands):
            return programs.decode_step(
                *operands, cfg=cfg, sample=sample, n_tok=n_tok,
                attention_impl=attention_impl,
                lora_scaling=(lora_spec.scaling if lora_spec else 1.0),
                mesh=mesh, spec=pool_spec)

        def serve_first_token(*operands):
            return first_token(*operands, sample)

        self._step_fn = jax.jit(serve_decode_step, donate_argnums=(1,))
        self._first_fn = jax.jit(serve_first_token)
        # the engine's chunk program, ONE of two (a trace shows either as
        # jit_serve_prefill_chunk).  Where a step is one token a slot off
        # the base weights, the chunk that may carry the step's decode rows
        # (``_rides``): a chunk with more rows.  A speculative engine (1 + k
        # rows a slot) and one with tenants (their deltas on their rows)
        # hold the chunk alone, and run it and the step as two calls (a
        # tenant's chunk through its own merged weights)
        self._fused_fn = self._prefill_fn = self._prefill_lora_fn = None
        if not self.speculative and lora_spec is None:
            chunk = self.prefill_chunk

            def serve_prefill_chunk(*operands):
                return programs.chunk_and_step(
                    *operands, cfg=cfg, sample=sample, max_blocks=max_blocks,
                    chunk=chunk, attention_impl=attention_impl, mesh=mesh,
                    spec=pool_spec)

            self._fused_fn = jax.jit(serve_prefill_chunk,
                                     donate_argnums=(1,))
        else:
            def serve_prefill_chunk(*operands):
                return programs.prefill_chunk(
                    *operands, cfg=cfg, max_blocks=max_blocks)

            self._prefill_fn = jax.jit(serve_prefill_chunk,
                                       donate_argnums=(1,))
            if lora_spec is not None:
                def serve_prefill_chunk_lora(*operands):
                    return programs.prefill_chunk_lora(
                        *operands, cfg=cfg, max_blocks=max_blocks,
                        lora_spec=lora_spec)

                self._prefill_lora_fn = jax.jit(serve_prefill_chunk_lora,
                                                donate_argnums=(2,))
        if self.cfg.cross_start < self.cfg.n_layers:
            with _journal.phase(build, "programs_traced",
                                "serve.build.programs_traced"):
                for name, fn, operands in (
                        ("step", self._step_fn, self._abstract_decode_args),
                        ("chunk", self._prefill_fn,
                         self._abstract_prefill_args),
                        ("chunk_and_step", self._fused_fn,
                         self._abstract_fused_args)):
                    if fn is not None:
                        took = programs.rows_walked(fn, operands())
                        self._program_rows[name] = (
                            took[0], took[self.cfg.cross_start])
            self._sent = self._nothing_sent()
        # the row tiles the expert layers of a call lay out, whatever lands
        # in them (``moe_tiles_active`` of a step counts those): [a call
        # with a chunk, a decode-only call]
        self._tiles_laid = [
            self.cfg.n_expert_layers * expert_tiles(
                rows, self.cfg.experts_per_token, self.cfg.n_experts_held)[1]
            if self.cfg.n_expert_layers else 0
            for rows in (self.prefill_chunk
                         + n_slots * (self._fused_fn is not None),
                         n_slots * (1 + self.speculative))]
        # how a chunk attends, a kind of layer that keeps pages: what the
        # programs pick from the same inputs (``chunk_attention_form``),
        # asked once here; and the layers whose chunk is ONE kernel call,
        # whose key blocks ``serve.step`` counts (``chunk_key_blocks``)
        paged = programs.page_readers(self.cfg)
        self.chunk_attention = {
            kind or "full_attention": programs.chunk_attention_form(
                self.cfg, kind, self.prefill_chunk, block_size)
            for kind in dict.fromkeys(paged)}
        self._kernel_layers = sum(
            self.chunk_attention[kind or "full_attention"] == "kernel"
            for kind in paged)
        self.chunk_key_blocks = 0  # lifetime; step() diffs it
        # the decode rows of a chunk that carries none: every slot idle
        self._idle_rows = np.zeros((n_slots, max_blocks + 4), np.int32)
        # lifetime counts: steps whose chunk carried the decode rows, and
        # those rows; step() diffs them onto serve.step
        self.fused_steps = 0
        self.fused_decode_rows = 0
        # AOT executable cache (export/): replica spin-up goes
        # cache-first on the two fixed-shape serve traces, so a warm
        # replica deserializes the decode step and the prefill chunk
        # instead of paying their XLA compiles before the first token.
        self.export_info: list[dict] = []
        from ...export import cache as _export_cache_mod

        _cache = _export_cache_mod.resolve(export_cache)
        if _cache is not None:
            with _journal.phase(build, "export", "serve.build.export"):
                self._export_compiled(
                    _cache, num_blocks=num_blocks, block_size=block_size,
                    quant_kv=bool(quant_kv), cache_dtype=cache_dtype,
                    n_adapters=n_adapters,
                    quant_adapters=bool(quant_adapters))
        # the programs' loads, by the name a trace shows without its
        # ``jit_`` (``_dispatched``): the newest ``serve.engine`` carries the
        # table; this step's, [(name, seconds)], a ``compile`` event each;
        # and the readings before the call last dispatched
        self.programs: dict[str, dict] = {}
        self._loads: list[tuple[str, float]] = []
        self._mark: tuple[dict, float] = (self._compiles.loads(), 0.0)
        with _journal.phase(build, "describe", "serve.build.describe"):
            given = jax.tree.leaves(variables["params"])
            held = jax.tree.leaves(self.params)
            weights_cast = sum(a.dtype != b.dtype for a, b in zip(
                given, jax.tree.leaves(jax.eval_shape(
                    lambda: compute_dtype_params(
                        variables["params"], self.cfg)))))
            bytes_compute, bytes_fp32 = (
                sum(x.nbytes for x in held if x.dtype == dtype)
                for dtype in (jnp.dtype(self.cfg.dtype), jnp.float32))
        described = self.journal.event(
            "serve.engine", attention_impl=attention_impl,
            build_s=time.monotonic() - t_build, build_phases=build,
            build_loads=self._compiles.loads(loads_before),
            prefill_chunk=self.prefill_chunk,
            n_slots=n_slots, max_len=max_len, block_size=block_size,
            quant_kv=bool(quant_kv),
            n_adapters=(n_adapters if lora_spec else 0),
            adapter_rank=(lora_spec.rank if lora_spec else None),
            quant_adapters=bool(quant_adapters and lora_spec),
            speculative=self.speculative,
            dispatch_ahead=self._ahead,
            prefix_cache=self._prefix_cache is not None,
            tp=tensor_degree(mesh),
            # of the tree the base programs take: leaves rounded to the
            # compute dtype at construction, and its bytes by dtype
            weights_cast=weights_cast,
            weight_bytes_compute=bytes_compute,
            weight_bytes_fp32=bytes_fp32,
            # the layers by kind and the bytes their caches hold: pages for
            # max_len a slot, the sliding layers' rings, and the linear
            # layers' recurrent states and convolution tails (a row a slot)
            layer_kinds=(list(kinds) or None),
            experts_held=(self.cfg.n_experts_held
                          if self.cfg.n_expert_layers else 0),
            experts_published=(self.cfg.experts_published
                               if self.cfg.n_expert_layers else 0),
            # the block: the router's zero-compute outputs behind the
            # published ones, and whether the expert FFN is a shortcut
            # branch over a pair of sublayers (the plan's open / close)
            zero_experts=self.cfg.zero_experts,
            shortcut_experts=self.cfg.shortcut_experts,
            moe_tiles_laid=self._tiles_laid,
            chunk_attention=self.chunk_attention or None,
            # latent pages are pages for max_len: in kv_bytes_full too
            kv_bytes_full=self.pool.bytes_full,
            kv_bytes_latent=self.pool.bytes_latent,
            # a latent page's row: [the latent's rank, the rotated key part,
            # the numbers stored a token] (None without such a layer)
            latent_row=([self.cfg.latent_kv_rank,
                         self.cfg.latent_rope_head_dim,
                         *stored_row(self.cfg, "latent_attention")]
                        if "latent_attention" in kinds else None),
            kv_bytes_window=self.pool.bytes_window,
            state_bytes_linear=self.pool.bytes_state[0],
            conv_bytes_linear=self.pool.bytes_state[1],
            # which rule the linear layers run: [the rule, whose the decay
            # is: a head's or a channel's] (None without such a layer)
            linear_mixer=(["gated_delta", self.cfg.linear_decay]
                          if "linear_attention" in kinds else None),
            # the upper end of that rule's write strength beta: 2 where the
            # state's transition may have an eigenvalue down to -1
            # (``linear_neg_eigval``), else 1 (None without such a layer)
            linear_write_max=((2 if self.cfg.linear_neg_eigval else 1)
                              if "linear_attention" in kinds else None),
            # the form the attention layers run: "differential" (adjacent
            # head pairs, two softmaxes subtracted, over values twice a
            # key's width; its decode reads a folded page once, the MXU
            # kernel at another wiring) or "softmax"
            attention_form=("differential" if diff else "softmax"),
            # whether a sigmoid gate read from the layer's input multiplies
            # the attention layers' output before its projection
            attn_gate=self.cfg.attn_gate,
            # the layers whose FFN is dense: 0 where every layer has the
            # expert FFN in its place
            dense_layers=sum(not sparse for _, _, sparse, _
                             in layer_plan(self.cfg)),
            # a decoder-hybrid-decoder's layout: the first layer of the
            # run of layers that keep no cache (a chunk's rows but one
            # stop before it), the layers that hold paged keys and values
            # of their own and those that read another layer's
            cross_start=(self.cfg.cross_start
                         if self.cfg.cross_start < self.cfg.n_layers
                         else None),
            paged_sets=(self.pool.n_full + self.pool.ring.count(True)
                        if kinds else None),
            shared_readers=(list(kinds).count("shared_attention") or None))
        # what the event said, less the journal's own stamps: said again,
        # with ``programs``, after a step in which a program loaded (None:
        # a journal that writes nothing)
        self._described = described and {
            k: v for k, v in described.items() if k not in BASE_FIELDS}
        # the counters of the decode step last read (serve.step carries them)
        self._counters: dict[str, int] = {}

    def _export_compiled(self, cache, *, num_blocks: int,
                         block_size: int, quant_kv: bool, cache_dtype,
                         n_adapters: int, quant_adapters: bool) -> None:
        """Cache-first AOT for the fixed-shape serve traces (decode step,
        and the base prefill chunk or, where the engine builds one, the
        chunk that carries a step's decode rows).  Abstract args come from
        ``jax.eval_shape`` over the exact runtime operands — nothing is
        materialized, and the traces match dispatch bit-for-bit.  The
        per-prompt-length LoRA prefill stays lazy (one trace per tenant
        factor tree isn't worth pinning)."""
        from ...export import aot as aot_mod
        from ...export import cache as export_cache_mod
        from ...topology import detect
        from ...tune import cache as tune_cache

        devices = (list(self.mesh.devices.flat)
                   if self.mesh is not None else None)
        topo_fp = tune_cache.topology_fingerprint(detect(devices))
        sig = tune_cache.params_signature(self.params)
        # everything the serve traces close over: two engines that
        # differ in any of these must compile separately
        program = {
            "n_slots": self.n_slots, "max_len": self.max_len,
            "block_size": block_size, "num_blocks": num_blocks,
            "attention_impl": self.attention_impl,
            "speculative": self.speculative,
            "quant_kv": quant_kv,
            "cache_dtype": str(np.dtype(cache_dtype)),
            "sample": dataclasses.asdict(self.sample),
            "prefill_chunk": self.prefill_chunk,
            # prefill writes the request's pages in place: another
            # program than the temp-cache trace of the same options
            "prefill_in_place": True,
            # the decode step takes the output of the step before
            "tokens_on_device": True,
            "lora": ([self.lora_spec.rank, self.lora_spec.scaling,
                      n_adapters, quant_adapters]
                     if self.lora_spec is not None else None),
        }
        res = aot_mod.cached_compile(
            self._step_fn, self._abstract_decode_args(), cache=cache,
            kind="serve_decode",
            key=export_cache_mod.executable_key(
                "serve_decode", sig, topo_fp, program))
        if res is not None:
            self._step_fn = aot_mod.ExportedCallable(
                res.compiled, self._step_fn, "serve_decode")
            self.export_info.append(res.to_json())
        if self._prefill_fn is not None:
            res = aot_mod.cached_compile(
                self._prefill_fn, self._abstract_prefill_args(), cache=cache,
                kind="serve_prefill",
                key=export_cache_mod.executable_key(
                    "serve_prefill", sig, topo_fp, program))
            if res is not None:
                self._prefill_fn = aot_mod.ExportedCallable(
                    res.compiled, self._prefill_fn, "serve_prefill")
                self.export_info.append(res.to_json())
        if self._fused_fn is not None:
            res = aot_mod.cached_compile(
                self._fused_fn, self._abstract_fused_args(), cache=cache,
                kind="serve_fused",
                key=export_cache_mod.executable_key(
                    "serve_fused", sig, topo_fp, program))
            if res is not None:
                self._fused_fn = aot_mod.ExportedCallable(
                    res.compiled, self._fused_fn, "serve_fused")
                self.export_info.append(res.to_json())

    def _abstract_decode_args(self) -> tuple:
        """Abstract operands of the decode step, from ``jax.eval_shape``
        over the exact runtime operands — nothing is materialized."""
        S, MB, T = self.n_slots, self.max_blocks, 1 + self.speculative
        factors = (self.adapter_pool.factors
                   if self.adapter_pool is not None else {})
        return jax.eval_shape(lambda: (
            self.params, self.pool.kv,
            jnp.zeros((S, MB + T + 3), jnp.int32), self._out,
            self.pool.win_tables, factors, self._rng))

    def _abstract_prefill_args(self) -> tuple:
        """Abstract operands of the base prefill chunk."""
        return jax.eval_shape(lambda: (
            self.params, self.pool.kv,
            jnp.zeros((self.max_blocks + self.prefill_chunk + 3,), jnp.int32),
            self._win_rows[0]))

    def _abstract_fused_args(self) -> tuple:
        """Abstract operands of the chunk that carries a step's decode
        rows (``programs.chunk_and_step``)."""
        S, MB = self.n_slots, self.max_blocks
        return jax.eval_shape(lambda: (
            self.params, self.pool.kv,
            jnp.zeros((MB + self.prefill_chunk + 3 + S * (MB + 4),),
                      jnp.int32),
            self._out, self._win_rows[0], self.pool.win_tables, self._rng))

    def compiled_decode_text(self) -> str:
        """Optimized HLO text of the compiled decode step (the serving
        analog of ``AutoDistribute.compiled_step_text``): what shows
        whether the paged kernel is in the executable as a Mosaic
        ``tpu_custom_call`` rather than interpreted."""
        return self._step_fn.lower(
            *self._abstract_decode_args()).compile().as_text()

    # -- request intake ------------------------------------------------------

    def register_adapter(self, name: str, lora_params) -> None:
        """Stage a tenant's LoRA factors for serving (see
        AdapterPool.register).  Requires ``lora_spec`` at construction."""
        if self.adapter_pool is None:
            raise ValueError(
                "engine built without lora_spec — pass lora_spec=... to "
                "serve adapters")
        self.adapter_pool.register(name, lora_params)

    def submit(self, prompt: list[int], max_new_tokens: int,
               eos_id: int | None = None,
               adapter: str | None = None,
               priority: int = 0) -> Request:
        total = len(prompt) + max_new_tokens
        # speculative steps write up to k draft keys past the emitted
        # context — that lookahead must fit the slot's table too
        need_len = total + self.speculative
        if need_len > self.max_len:
            raise ValueError(
                f"prompt {len(prompt)} + max_new_tokens {max_new_tokens} "
                + (f"+ speculative lookahead {self.speculative} "
                   if self.speculative else "")
                + f"= {need_len} exceeds engine max_len {self.max_len}")
        if not prompt:
            raise ValueError("empty prompt")
        if adapter is not None:
            if self.adapter_pool is None:
                raise ValueError(
                    "engine built without lora_spec cannot serve "
                    f"adapter {adapter!r}")
            if not self.adapter_pool.has(adapter):
                raise ValueError(
                    f"unknown adapter {adapter!r} — register_adapter() "
                    "it first")
        need = blocks_for_tokens(need_len, self.pool.block_size)
        if need > self.pool.num_blocks - 1:
            # the pool could NEVER cover this request even alone —
            # admitting it would preempt-thrash forever in optimistic
            # mode and deadlock admission in reserve mode
            raise ValueError(
                f"request needs {need} blocks but the pool has "
                f"{self.pool.num_blocks - 1} allocatable")
        req = Request(prompt=list(map(int, prompt)),
                      max_new_tokens=max_new_tokens, eos_id=eos_id,
                      adapter=adapter, priority=int(priority))
        self.scheduler.submit(req)
        return req

    # -- one serving iteration ----------------------------------------------

    def _phase(self, key: str, **ids) -> _journal.phase:
        """One of ``PHASES``: its seconds land in this step's ``phases``
        and its interval on the profiler's timeline as ``serve.<key>``."""
        return _journal.phase(self._phases, key, "serve." + key,
                              step=self._step_count + 1, **ids)

    def _dispatching(self) -> None:
        """Before a program is called: a reading of the process's compile
        counter and of the clock, for ``_dispatched``.  The pair stands
        BESIDE the call and not round it, and the methods a program is
        called from gain no local for it: a first call traces and lowers
        some hundred Python frames deep, and what lies under it decides
        where those frames cross the interpreter's 16 KiB stack chunks
        (CPython 3.12 maps and unmaps a chunk at EVERY call across such a
        boundary: one frame more under ``solar``'s chunk program tripled a
        kernel's lowering, 0.55 to 1.6 s; PERF.md, PR 52)."""
        self._mark = self._compiles.loads(), time.monotonic()

    def _dispatched(self, name: str) -> None:
        """After the call of the program a trace shows as ``jit_<name>``.
        A program's FIRST call is where it is traced, lowered and fetched
        from the compile cache or built: ``programs[name]`` keeps what the
        two readings say of it, the counter's parts and their sum
        ``load_s``, the call's host seconds ``call_s`` and the step it fell
        in.  A later call in which the counter moved (new shapes, a cache
        dropped: the program was loaded AGAIN, inside somebody's window)
        is added to those sums and dated ``reloaded_at``."""
        call_s = time.monotonic() - self._mark[1]
        seen = self.programs.get(name)
        if seen is None or self._compiles.n != self._mark[0]["n"]:
            load = self._compiles.loads(self._mark[0])
            load["call_s"] = call_s
            self._loads.append((name, load["load_s"]))
            if seen is None:
                self.programs[name] = {**load,
                                       "at_step": self._step_count + 1}
            else:
                seen.update({k: seen[k] + v for k, v in load.items()},
                            reloaded_at=self._step_count + 1)

    def _say_loads(self, loaded: list) -> None:
        """One ``compile`` event for each program loaded in this step (an
        engine's first steps, or a program loaded again): each stalled
        every stream for so long, and ``programs`` has the parts."""
        for name, load_s in loaded:
            self.journal.event("compile", fn="serve", program=name,
                               dur_s=load_s)

    def _describe_again(self) -> None:
        """The engine's description again, with what it has loaded so far:
        the newest ``serve.engine`` is the one readers take."""
        if self._described:
            self.journal.event(
                "serve.engine", **self._described,
                programs={k: dict(v) for k, v in self.programs.items()})

    def _bind_adapter(self, slot: int, req: Request) -> bool:
        """Pin the request's adapter at the transition into decode
        (pins back live decode reads ONLY — prefilling slots reference
        adapters by name).  When every pool slot is pinned by other
        running requests, the request bounces back to the queue
        recompute-style; pins are held by running slots only, so some
        slot is always making progress and the bounce cannot livelock.
        Size ``n_adapters > n_slots`` to never hit this path."""
        info = self.scheduler.pin_adapter(req)
        if info is None:
            self._prefill.pop(req.rid, None)
            self.scheduler.requeue(slot)
            self.journal.event("serve.adapter", kind="stall",
                               rid=req.rid, adapter=req.adapter)
            return False
        if info:
            self.journal.event(
                "serve.adapter", kind="hit" if info["hit"] else "fault",
                rid=req.rid, adapter=req.adapter, idx=info["idx"],
                evicted=info["evicted"])
        return True

    def _req_lora(self, req: Request):
        if req.adapter is None:
            return None
        return self.adapter_pool.effective_lora(req.adapter)

    def _publish_prefill(self, slot: int, req: Request) -> None:
        """A finished prefill: its keys and values already lie in the
        request's blocks (the chunks wrote them there); its full prompt
        blocks are published into the radix index."""
        if self._prefix_cache is not None:
            # publish every FULL prompt block: decode writes start at
            # position n_prompt, so these rows are immutable (CoW
            # guards the manufactured-sharing corner regardless)
            n_pub = req.n_prompt // self.pool.block_size
            new = self._prefix_cache.insert(
                req.prompt[:n_pub * self.pool.block_size],
                req.blocks[:n_pub])
            if new:
                self.journal.event(
                    "serve.prefix", kind="publish", rid=req.rid,
                    n_blocks=new)

    def _start_prefill(self, slot: int, req: Request) -> None:
        """Admission entry point (phase ``admit``): flip the slot to
        "prefilling" so step() streams the prompt through the shared chunk
        trace, interleaved with decode, and seed the prefill's cursor.  A
        prefix-cache hit starts it after the matched blocks — the chunk
        trace then computes only the uncached suffix, attending to the
        reused blocks through the request's table exactly as the original
        prefill's later chunks attended to them."""
        with self._phase("admit", rid=req.rid):
            req.state = "prefilling"
            if self._prefix_cache is not None:
                self.prefix_queries += 1
                if req.cached_tokens:
                    self.prefix_hits += 1
                    self.prefix_cached_tokens += req.cached_tokens
                    C = self.prefill_chunk
                    self.prefix_saved_chunks += (
                        -(-req.n_prompt // C)
                        - -(-(req.n_prompt - req.cached_tokens) // C))
                self.journal.event(
                    "serve.prefix", kind="match", rid=req.rid,
                    hit=bool(req.cached_tokens),
                    cached_tokens=req.cached_tokens,
                    cached_blocks=req.cached_blocks)
            self._prefill[req.rid] = _PrefillState(
                pos=req.cached_tokens, lora=self._req_lora(req))

    def _chunk_operands(self, slot: int,
                        req: Request) -> tuple[np.ndarray, int]:
        """``(pack_chunk's operand, how many real tokens)`` of the chunk of
        ``req``'s prompt at its cursor: one upload (table row, tokens,
        cursor, last real row, slot)."""
        st = self._prefill[req.rid]
        C = self.prefill_chunk
        chunk = req.prompt[st.pos:st.pos + C]
        return programs.pack_chunk(
            self.pool.table_row(req.blocks, self.max_blocks),
            chunk + [0] * (C - len(chunk)), st.pos, len(chunk) - 1,
            slot), len(chunk)

    def _advance_prefill(self, slot: int, req: Request) -> None:
        """One [1, C] chunk of ``req``'s prompt, written into its blocks
        (``_chunk_ran`` is what follows), by whichever chunk program the
        engine holds."""
        st = self._prefill[req.rid]
        with self._phase("prefill_dispatch", rid=req.rid, pos=st.pos):
            packed, n_real = self._chunk_operands(slot, req)
            self._dispatching()
            if st.lora is not None:
                self.pool.kv, logits = self._prefill_lora_fn(
                    self._merge_base, st.lora, self.pool.kv, packed,
                    self._win_rows[slot])
            elif self._fused_fn is not None:
                # nobody decodes (``_rides``): the program that carries a
                # step's decode rows, with none, and not a third program
                self.pool.kv, _, logits = self._fused_fn(
                    self.params, self.pool.kv,
                    programs.pack_chunk_and_step(packed, self._idle_rows),
                    self._out, self._win_rows[slot], self.pool.win_tables,
                    self._rng)
            else:
                self.pool.kv, logits = self._prefill_fn(
                    self.params, self.pool.kv, packed, self._win_rows[slot])
            self._dispatched("serve_prefill_chunk" if st.lora is None
                             else "serve_prefill_chunk_lora")
        self._sent_program(
            chunk=(n_real, st.pos),
            program="chunk" if self._fused_fn is None else "chunk_and_step")
        self._chunk_ran(slot, req, n_real, logits)

    def _chunk_ran(self, slot: int, req: Request, n_real: int,
                   logits) -> None:
        """A chunk of ``n_real`` tokens of ``req``'s prompt is dispatched:
        move the cursor.  On the final chunk: pin the adapter (bouncing the
        request if the pool is full), sample the first token ON THE DEVICE
        from the chunk's ``logits``, into the newest step output, and hand
        the slot to decode: its first step reads the token there, and the
        host fetches it with its next read (at once only where it reads
        before it dispatches)."""
        st = self._prefill[req.rid]
        st.pos += n_real
        req.prefill_chunks += 1
        if st.pos >= req.n_prompt and self._bind_adapter(slot, req):
            with self._phase("prefill_dispatch", rid=req.rid, pos=st.pos):
                self._dispatching()
                self._out = self._first_fn(
                    self._out, logits,
                    np.asarray([slot, req.rid], np.int32), self._rng)
                self._dispatched("serve_first_token")
            if self._sent_late is not None:
                # the unread output now waits for this chunk, and so for
                # every program that went out before it
                self._sent = self._covers(self._sent, self._sent_late)
                self._sent_late = None
            self._publish_prefill(slot, req)
            req.n_inflight = 1
            self._firsts.append((slot, req, req.preempted))
            req.state = "running"
            del self._prefill[req.rid]
            if not self._ahead:
                # no chunk is fenced, and this wait only where the next
                # step's drafts need the token
                self._read("prefill_first_token", self._take_unread())

    def _rides(self, plan: list) -> bool:
        """Whether this call's decode rows ride in the chunk of ``plan``:
        ONE program for both where the engine can run one
        (``_fused_fn``) and some slot decodes.  The chunk is then dispatched
        with the step, after ``grow`` and the copy-on-write guard, and not
        before them.  While nobody decodes a chunk runs in its own place,
        its decode rows idle (``_advance_prefill``)."""
        return (bool(plan) and self._fused_fn is not None
                and self.scheduler.n_decoding > 0)

    def _cow_fork_writes(self) -> None:
        """Copy-on-write guard, run right before the decode step: any
        block this step will WRITE into (positions ctx..ctx+lookahead)
        that is shared (refcount > 1 — some other table or the radix
        index also points at it) is forked to a private copy first, so
        the write can never corrupt another owner's view.  In natural
        traffic this never fires — matches are capped below the prompt
        end and published blocks sit strictly before the first decode
        write — but the guard makes sharing safe by construction, not
        by traffic shape."""
        bs = self.pool.block_size
        alloc = self.pool.allocator
        for req in self.scheduler.slots:
            if req is None or req.state != "running":
                continue
            ctx = req.n_prompt + req.n_dispatched - 1
            for t in range(1 + self.speculative):
                bi = (ctx + t) // bs
                if bi >= len(req.blocks):
                    break  # optimistic growth handles coverage
                b = req.blocks[bi]
                if alloc.refcount(b) <= 1:
                    continue
                nb = self.pool.fork_block(b)
                if (nb is None and self._prefix_cache is not None
                        and self._prefix_cache.evict(1)):
                    nb = self.pool.fork_block(b)
                if nb is None:
                    raise RuntimeError(
                        f"cannot fork shared block {b}: pool exhausted "
                        f"and no evictable index leaf")
                req.blocks[bi] = nb
                alloc.release([b])
                self.cow_forks += 1
                self.journal.event(
                    "serve.prefix", kind="cow", rid=req.rid,
                    block=b, fork=nb)

    def _decode_all(self, rider: tuple | None = None) -> None:
        """Dispatch this call's decode step, THEN read what the host has
        yet to read: the step dispatched a call ago and the first tokens
        of prompts that ended since, which all lie in the output array
        this call's step was handed.  An engine that reads before it
        dispatches (``_ahead`` 0) comes here with nothing unread and reads
        its own step.  A call with no slot to decode drains.  ``rider`` is
        the (slot, request) whose chunk the step's rows ride in
        (``_rides``)."""
        unread = self._take_unread()
        drafts = self._dispatch(rider)
        if drafts is not None and unread[1]:
            self.steps_ahead += 1
        if not self._ahead:
            unread = self._take_unread()
        self._read("decode_wait", unread, drafts)

    def _take_unread(self) -> tuple:
        """(output array, its unread step rows, its unread first tokens,
        whether those rows rode in a chunk, what was dispatched into it
        since the last read), handed over: the engine's lists start anew,
        and so does the account of what went out, unless there is nothing
        to read (what went out then waits for the read that covers it)."""
        unread = (self._out, self._rows, self._firsts, self._rows_fused,
                  self._sent)
        if self._rows or self._firsts:
            self._sent = self._sent_late or self._nothing_sent()
            self._sent_late = None
        self._rows, self._firsts, self._rows_fused = [], [], False
        return unread

    def _nothing_sent(self) -> dict:
        cross = ({"self_rows": 0, "cross_rows": 0} if self._program_rows
                 else {})
        return {"programs": 0, "rows": 0, "ctx_keys": 0, "chunk_rows": 0,
                "chunk_pos": 0, **cross}

    @staticmethod
    def _covers(first: dict, then: dict) -> dict:
        """``then`` with what went out before it, ``first``, added."""
        if not then["chunk_rows"]:
            then["chunk_pos"] = first["chunk_pos"]
        for k in first.keys() - {"chunk_pos"}:
            then[k] += first[k]
        return then

    def _sent_program(self, rows: int = 0, ctx_keys: int = 0,
                      chunk: tuple[int, int] | None = None,
                      program: str = "step") -> None:
        """One program that walks the layers went out (a first token's
        sampler rides with its chunk and is not counted): its decode
        ``rows`` and the sum of their context lengths, the ``(real rows,
        first position)`` of the ``chunk`` it carried.  Host integers, for
        the event of the call that will wait for it (``serve.step``'s
        ``read``): they add up over what one read covers, but for
        ``chunk_pos``, which is the last chunk's.  ``program`` names which
        of the three it was: where the model ends in a cross-decoder, the
        rows that ran the layers before it and the rows that ran it, as its
        walk noted them (``self_rows``, ``cross_rows``).  What goes out
        while an output is unread (a chunk in its own place) is not waited
        for by that output's read, unless it is a prompt's last
        (``_chunk_ran``)."""
        sent = self._sent
        if self._rows or self._firsts:
            if self._sent_late is None:
                self._sent_late = self._nothing_sent()
            sent = self._sent_late
        sent["programs"] += 1
        if self._program_rows:
            sent["self_rows"] += self._program_rows[program][0]
            sent["cross_rows"] += self._program_rows[program][1]
        sent["rows"] += rows
        sent["ctx_keys"] += ctx_keys
        if chunk is not None:
            sent["chunk_rows"] += chunk[0]
            sent["chunk_pos"] = chunk[1]
            # (of what this call DISPATCHED, not of ``read``) the grid
            # steps a group of heads of the chunk's kernel calls ran
            if self._kernel_layers:
                self.chunk_key_blocks += (
                    self._kernel_layers * latent_chunk_key_blocks(
                        chunk[1], self.prefill_chunk, self.max_blocks,
                        self.pool.block_size))

    def _dispatch(self, rider: tuple | None = None) -> np.ndarray | None:
        """One decode step for every running slot, dispatched and not
        waited for.  Nothing here needs a token's VALUE unless the host has
        it already: a slot's context length and pages follow from the count
        of tokens dispatched, and its token is taken on the device from the
        output of the step before (or from the first tokens beside it)
        whenever the host has not read it yet.  With a ``rider`` (slot,
        request) the step's rows go through the layers in that request's
        next chunk, ONE program for both (``programs.chunk_and_step``), and
        the chunk is accounted as ``_advance_prefill`` accounts one: a
        prompt that ends in it decodes from the next call on.  Returns the
        [S, T] tokens of the operand (the drafts a verify step emits
        against), or None where no slot decodes."""
        S, MB = self.n_slots, self.max_blocks
        k_spec = self.speculative
        T = 1 + k_spec
        with self._phase("decode_prepare"):
            tables = np.zeros((S, MB), np.int32)
            ctx = np.zeros((S,), np.int32)
            tok = np.zeros((S, T), np.int32)
            ids = np.zeros((S,), np.int32)
            src = np.zeros((S,), np.int32)
            rows, ctx_keys = [], 0  # (a sum in Python: no reduction's call)
            for s, req in enumerate(self.scheduler.slots):
                if req is None or req.state != "running":
                    # prefilling slots keep an all-null table here: the
                    # step's unconditional KV write lands in the scratch
                    # block instead of their half-filled prompt blocks
                    continue
                tables[s, :len(req.blocks)] = req.blocks
                # this step writes token n_dispatched at absolute position
                # n_prompt + n_dispatched - 1 (the first generated token
                # came from prefill and was never written)
                ctx[s] = keys = req.n_prompt + req.n_dispatched - 1
                ctx_keys += keys
                if not req.n_inflight:
                    src[s] = programs.TOKEN_HOST
                    tok[s, 0] = req.out_tokens[-1]
                    if k_spec:
                        tok[s, 1:] = ngram_propose(
                            req.prompt + req.out_tokens, k_spec)
                else:  # unread: the one token dispatched is a prompt's first
                    src[s] = (programs.TOKEN_FIRST if req.n_dispatched == 1
                              else programs.TOKEN_PREV)
                ids[s] = req.adapter_idx
                rows.append((s, req, req.preempted))
        if not rows and rider is None:
            return None
        with self._phase("decode_upload"):
            # greedy sampling reads no key: no fold a step for it
            step_rng = (self._rng if self.sample.temperature == 0.0
                        else jax.random.fold_in(
                            self._rng, 2**20 + self._step_count))
            factors = (self.adapter_pool.factors
                       if self.adapter_pool is not None else {})
            # one upload a step: tables, tokens, contexts, flags, ids
            packed = programs.pack_step(tables, ctx, tok, src, ids)
            if rider is not None:  # still one upload
                t0 = time.monotonic()
                of_chunk, n_real = self._chunk_operands(*rider)
                packed = programs.pack_chunk_and_step(of_chunk, packed)
        with self._phase("decode_dispatch"):
            self._dispatching()
            if rider is None:
                self.pool.kv, self._out = self._step_fn(
                    self.params, self.pool.kv, packed, self._out,
                    self.pool.win_tables, factors, step_rng)
            else:
                self.pool.kv, self._out, logits = self._fused_fn(
                    self.params, self.pool.kv, packed, self._out,
                    self._win_rows[rider[0]], self.pool.win_tables, step_rng)
            self._dispatched("serve_decode_step" if rider is None
                             else "serve_prefill_chunk")
        for _, req, _ in rows:
            req.n_inflight += 1
        self._sent_program(  # (before the rows are unread ones)
            len(rows), ctx_keys, None if rider is None
            else (n_real, self._prefill[rider[1].rid].pos),
            "step" if rider is None else "chunk_and_step")
        self._rows = rows
        if rider is not None:
            self._rows_fused = True
            self.fused_steps += bool(rows)
            self.fused_decode_rows += len(rows)
            # after the rows are the engine's: the prompt's first token goes
            # into THIS call's output, which holds them
            self._chunk_ran(*rider, n_real, logits)
            # host seconds: packing the chunk's operands and the dispatch
            rider[1].prefill_compute_s += time.monotonic() - t0
        return tok

    def _read(self, wait: str, unread: tuple,
              drafts: np.ndarray | None = None) -> None:
        """Fetch an output array (phase ``wait``) and hand its unread
        tokens to their requests (phase ``emit``); ``unread`` is what
        ``_take_unread`` gave."""
        out, rows, firsts, fused, sent = unread
        if not rows and not firsts:
            return
        if self._step_read is not None:  # a call's second read
            sent = self._covers(self._step_read, sent)
        self._step_read = sent
        S, T = self.n_slots, 1 + self.speculative
        with self._phase(wait):
            # first tokens and the step's counters ride with its tokens:
            # one fetch
            out = np.asarray(jax.device_get(out))
            n = programs.N_COUNTERS
            first, tokens, counters = out[:S], out[S:-n], out[-n:]
            if T > 1:
                tokens = tokens.reshape(S, T)
            if rows:
                # the expert layers' only where there are any, and the
                # first three of a step that decoded alone: where the rows
                # rode in a chunk the layers routed its rows with them (the
                # live tiles are set against the tiles that call laid)
                # (the zero-compute pairs and the rows routed count a
                # call's rows whatever they rode in: on both)
                experts = bool(self.cfg.n_expert_layers)
                skip = 6 if not experts else 3 if fused else 0
                self._counters = dict(zip(
                    ("moe_pairs", "moe_experts_touched",
                     "moe_max_expert_tokens", "moe_tiles_active",
                     "moe_zero_pairs", "moe_rows",
                     "attn_grid_items", "attn_grid_dense",
                     "attn_pages_copied", "attn_pages_live")[skip:],
                    map(int, counters[skip:])))
                if experts:
                    self._counters["moe_tiles_laid"] = self._tiles_laid[
                        0 if fused else 1]
                if self._n_linear:
                    # the slots whose row of a state pool the call's step
                    # kernel read and wrote, over the layers that keep one
                    # (linear_attention, state_space): known
                    # here, so not one more number in every model's output
                    self._counters["state_rows"] = len(rows) * self._n_linear
                    # and the rows those kernels walked for them: the
                    # kernel of a decay a channel takes the step's list of
                    # live slots, the others walk every slot
                    self._counters["state_rows_walked"] = (
                        self._n_kda * step_rows_walked(len(rows), self.n_slots)
                        + (self._n_linear - self._n_kda) * self.n_slots)
        with self._phase("emit"):
            self._emit(tokens, first, rows, firsts, drafts)

    def _emit(self, tokens: np.ndarray, first: np.ndarray, rows: list,
              firsts: list, drafts: np.ndarray | None) -> None:
        """Hand what was read to the requests: ``first[slot]`` to each of
        ``firsts``, the step's ``tokens`` to its ``rows`` (with
        speculation, ``drafts[:, 1:]`` are the drafts it verified).  A
        token is stamped HERE, when the host holds it.  A row whose request
        was preempted since the dispatch, ended at an EOS the host has read
        since, or was taken out of the scheduler's hands (a gateway's
        cancel) is a slot-step thrown away."""
        k_spec = self.speculative
        # one stamp per read: every token it brings shares it (a
        # speculative burst lands together, so its interior ITLs are 0); a
        # request's first token and its next never come in one read
        now = self.scheduler.clock()

        def give(req: Request, new: list[int]) -> None:
            req.n_inflight -= 1
            req.out_tokens.extend(new)
            req.token_walls.extend([now] * len(new))
            self.tokens_emitted += len(new)
            if req.state == "draining" and req.finished():
                self._finish(req)

        def wanted(req: Request, epoch: int) -> bool:
            return (req.preempted == epoch and not req.finished()
                    and req.state in ("running", "draining"))

        for slot, req, epoch in firsts:
            if wanted(req, epoch):
                req.t_first_token = now
                give(req, [int(first[slot])])
        drafted = accepted = n_active = 0
        for slot, req, epoch in rows:
            if not wanted(req, epoch):
                self.discarded_tokens += 1
                continue
            if not k_spec:
                give(req, [int(tokens[slot])])
                continue
            n_active += 1
            tgt = tokens[slot]  # [1+k] target greedy choices over the chunk
            a = accept_length(drafts[slot, 1:], tgt)
            # d_1..d_a agreed; tgt[a] is the target's own next token
            # after them (the free bonus) — 1..k+1 tokens per step
            emit = [int(d) for d in drafts[slot, 1:1 + a]] + [int(tgt[a])]
            drafted += k_spec
            accepted += a
            # clip to the generation budget, and stop at EOS exactly
            # where sequential decode would have
            emit = emit[:req.max_new_tokens - req.n_generated]
            if req.eos_id is not None and req.eos_id in emit:
                emit = emit[:emit.index(req.eos_id) + 1]
            give(req, emit)
        if not n_active:
            return
        self.spec_drafted += drafted
        self.spec_accepted += accepted
        self.journal.event(
            "serve.speculate", step=self._step_count + 1, k=k_spec,
            n_active=n_active, drafted=drafted, accepted=accepted,
            accept_rate=(accepted / drafted if drafted else None))

    def _evict_ended(self, slot: int) -> None:
        """A running request leaves its slot as soon as its END is known:
        an EOS the host has read, or ``max_new_tokens`` DISPATCHED, the
        last of them read or not (the next request takes the slot without
        an empty step between; the one leaving waits in the scheduler's
        ``draining`` for its last read)."""
        req = self.scheduler.slots[slot]
        if req is None or req.state != "running":
            return
        if req.finished():
            self._finish(self.scheduler.vacate(slot))
        elif req.n_dispatched >= req.max_new_tokens:
            self.scheduler.vacate(slot)

    def _finish(self, req: Request) -> None:
        """A vacated request whose every token the host holds."""
        # done() zeroes the prefix-cache accounting; read it first
        cached_tokens = req.cached_tokens
        self.scheduler.done(req)
        self.finished.append(req)
        # phase attribution: queue_s runs submit -> LAST admission (so
        # it absorbs time spent queued again after a preemption; lost_s
        # separates out the thrown-away attempts), prefill_s runs
        # admission -> first token, decode_s first token -> done
        queue_s = (req.t_admit or req.t_submit) - req.t_submit
        prefill_s = ((req.t_first_token - req.t_admit)
                     if req.t_first_token and req.t_admit else None)
        decode_s = ((req.t_done - req.t_first_token)
                    if req.t_first_token else None)
        total_s = req.t_done - req.t_submit
        walls = req.token_walls
        itl_s = [round(b - a, 6) for a, b in zip(walls, walls[1:])]
        self.journal.event(
            "serve.request_done", rid=req.rid, n_prompt=req.n_prompt,
            n_new=req.n_generated, queue_s=queue_s,
            prefill_s=prefill_s, decode_s=decode_s, total_s=total_s,
            tokens_per_s=(req.n_generated / decode_s
                          if decode_s else None),
            preempted=req.preempted,
            ttft_s=((req.t_first_token - req.t_submit)
                    if req.t_first_token else None),
            itl_s=itl_s,
            itl_mean_s=(sum(itl_s) / len(itl_s) if itl_s else None),
            cached_tokens=cached_tokens or None,
            prefill_chunks=req.prefill_chunks or None,
            prefill_compute_s=(round(req.prefill_compute_s, 6)
                               if req.prefill_chunks else None),
            lost_s=req.lost_s or None)

    def step(self) -> None:
        """One serving iteration: evict the ended, admit queued, advance
        the oldest prefill by one chunk, grow/preempt (optimistic),
        dispatch a decode step for every decoding slot and read the one
        dispatched a call ago (a call with nothing to dispatch reads what
        is left).  Prefill chunks INTERLEAVE with decode steps, one a
        call: their time serializes with decode on the one chip."""
        sched = self.scheduler
        tokens_before = self.tokens_emitted
        ahead_before = self.steps_ahead
        discarded_before = self.discarded_tokens
        fused_before = self.fused_steps, self.fused_decode_rows
        key_blocks_before = self.chunk_key_blocks
        compiles = self._compiles.n
        self._loads = loaded = []  # (name, seconds) of this step's loads
        gc_s, gc_full = self._gc.total_s, self._gc.passes[self._gc.FULL]
        self._phases = phases = {}
        self._counters = {}
        self._step_read = None
        whole: dict[str, float] = {}
        n_chunks = 0
        with _journal.phase(whole, "step_s", "serve.step",
                            step=self._step_count + 1):
            with self._phase("evict"):
                for s in range(self.n_slots):
                    self._evict_ended(s)
            with self._phase("admit"):
                admitted = sched.admit()
            for slot, req in admitted:
                self._start_prefill(slot, req)  # more admit, by request
            prefill_s = 0.0
            plan = sched.prefill_plan(1)
            # the chunk waits for the decode rows
            rider = plan.pop() if self._rides(plan) else None
            for slot, req in plan:
                n_chunks += 1
                t0 = time.monotonic()
                self._advance_prefill(slot, req)
                # host seconds: a dispatch (and a reader-first engine's
                # wait on the last chunk)
                chunk_s = time.monotonic() - t0
                prefill_s += chunk_s
                req.prefill_compute_s += chunk_s
                self._evict_ended(slot)  # max_new_tokens == 1
            with self._phase("grow"):
                for victim in sched.grow_for_step():
                    self._prefill.pop(victim.rid, None)
                    self.journal.event("serve.preempt", rid=victim.rid,
                                       n_regenerate=victim.n_prompt)
                if sched.n_decoding and self._prefix_cache is not None:
                    self._cow_fork_writes()
            if rider is not None and rider[1].rid not in self._prefill:
                rider = None  # preempted to grow another: its chunk with it
            n_chunks += rider is not None
            decode_s = 0.0
            if (sched.n_decoding or self._rows or self._firsts
                    or rider is not None):
                t0 = time.monotonic()
                self._decode_all(rider)
                decode_s = time.monotonic() - t0
        t_end = sched.clock()
        # the collector's time lies INSIDE whichever phase allocated: no
        # phase of its own, so that step_s less the phases stays self time
        gc_s = self._gc.total_s - gc_s
        gc_full = self._gc.passes[self._gc.FULL] - gc_full
        self._step_count += 1
        self._occupancy_sum += sched.n_active / self.n_slots
        compiles = self._compiles.n - compiles
        if loaded:
            self._say_loads(loaded)
        if compiles:
            # what a load leaves on the heap (jaxprs, executables, their
            # caches: a quarter of a million objects) lives as long as the
            # engine.  A full pass of Python's collector walks all of it,
            # 100-130 ms inside whichever later step's allocations set the
            # pass off (3-5 steps of a 51 s window; my chip runs, PR 27):
            # collect now, in a step that has stalled anyway, and freeze
            # what is left, so that later passes walk the young objects only
            gc.collect()
            gc.freeze()
        adapter_stats = {}
        if self.adapter_pool is not None:
            alloc = self.adapter_pool.allocator
            adapter_stats = dict(
                adapters_resident=alloc.n_resident,
                adapters_pinned=alloc.n_pinned)
        if self._prefix_cache is not None:
            adapter_stats.update(
                prefix_blocks=self._prefix_cache.n_blocks,
                prefix_hit_tokens=self._prefix_cache.hit_tokens)
        # prefill_s is dispatch time; decode_s is this call's dispatch and
        # the device_get of the step dispatched a call ago (``read`` says
        # what that wait covered).  ahead: this call's step went out with
        # that one unread
        read = {} if self._step_read is None else {"read": self._step_read}
        self.journal.event(
            "serve.step", step=self._step_count,
            n_active=sched.n_active, n_queued=sched.n_queued,
            n_prefilling=sched.n_prefilling,
            new_tokens=self.tokens_emitted - tokens_before,
            occupancy=sched.n_active / self.n_slots,
            free_blocks=self.pool.allocator.n_free,
            prefill_s=prefill_s, decode_s=decode_s,
            phases=phases, step_s=whole["step_s"], t_end=t_end,
            n_prefill_chunks=n_chunks, compiles=compiles,
            gc_s=gc_s, gc_full=gc_full, **read,
            ahead=self.steps_ahead - ahead_before,
            discarded_tokens=self.discarded_tokens - discarded_before,
            fused=self.fused_steps - fused_before[0],
            fused_decode_rows=self.fused_decode_rows - fused_before[1],
            **({"chunk_key_blocks":
                self.chunk_key_blocks - key_blocks_before}
               if self._kernel_layers and n_chunks else {}),
            **adapter_stats, **self._counters)
        if loaded:
            self._describe_again()
        if self._debug_invariants:
            sched.check_invariants()

    @property
    def prefix_cache(self) -> PrefixCache | None:
        """The engine's radix reuse index (None when disabled)."""
        return self._prefix_cache

    @property
    def mean_occupancy(self) -> float | None:
        """Mean active-slot fraction over every step so far."""
        if not self._step_count:
            return None
        return self._occupancy_sum / self._step_count

    def run(self) -> list[Request]:
        """Step until queue and slots drain and the host holds every
        token; returns finished requests (every submitted request, in
        completion order)."""
        while not self.scheduler.idle():
            self.step()
        return list(self.finished)
