"""The serving programs: the decode step, the prefill chunk, and the chunk
that carries a step's decode rows.

All walk the model's layers one by one (``transformer_core.layer_plan``:
a model with one kind of layer is a plan of that one kind), each layer
with its own mixer, window, rotation and FFN, and each with its own pair of
pool arrays (``kv_pool``: pages for ``max_len``, a ring on a
``sliding_attention`` layer, on a ``linear_attention`` layer the
recurrent state and the convolution's tail, a row a slot, or on a
``latent_attention`` layer pages of one latent row a token), so a call
updates every layer's pair in place and copies none.  The per-layer math is
the TRAINING modules applied piecewise, the single-source-of-truth
discipline of ``decode.forward_cached``: ``SelfAttention.qkv`` /
``out_proj``, ``GatedDeltaMixer.qkv`` / ``out_proj``,
``LatentAttention.project`` / ``expand`` / ``absorb`` / ``lift`` /
``out_proj``, ``MLPBlock``, ``SparseMLP``, ``make_norm``.

- The decode step takes a [S, T] token chunk for every slot: T == 1 is
  plain one-token decode, T == 1 + k a speculative verify step.  Positions
  and context lengths are PER-SLOT vectors (requests at different depths
  share a step), KV goes through the paged pool, sampled tokens are masked
  to 0 on inactive slots.
- The prefill chunk takes [1, C] tokens of ONE slot's prompt at positions
  ``pos0 ..``, writes their keys and values straight into the slot's pages
  and attends through the table of its layer's kind, a block of keys at a
  time from the first block its window reaches to the one it wrote.  The
  pages are the only copy (a [1, max_len] cache a request would not fit
  beside the weights at 16 slots of 13k positions), and a prefix the
  radix index matched is read where it lies.
- A ``linear_attention`` layer reads and writes its slot's ROW instead
  (``kv_pool``: row ``slot + 1``; row 0 takes what inactive slots write).
  A chunk runs the gated delta rule's chunk form from the state the chunk
  before left, or from zeros where the prompt starts (``pos0 == 0``: a
  slot's row is never cleared by the host), and keeps the last K - 1 REAL
  rows of its projections as the convolution's tail; the decode step runs
  the step form, one token a slot.  Rows that are no real token (a padded
  chunk's tail, an inactive slot) carry ``beta = 0`` and ``g = 0``, which
  leave a state as it was.

- A ``latent_attention`` layer writes a token's ``[c_kv, k_r]`` row into
  the slot's pages (the same table, the same allocator).  A chunk's queries
  attend the EXPANDED form, a block of keys at a time: the block's latents
  to every head's keys and values (``expand``), then scores and values a
  head (at 512 queries over 16k keys 172 GFLOP of scores and values + 137
  of expansion a layer, where the absorbed form is 584).  On the chip that
  is ONE kernel a layer (``ops/paged_attention``'s ``tadnn_latent_chunk``:
  a key block expanded in VMEM a head at a time, the softmax of one head
  beside the matmuls of the next; 2.04 ms at 16k keys), wherever
  ``chunk_attention_form`` finds shapes it tiles; everywhere else, and as
  the oracle, ``jax.numpy`` over key blocks (``_over_key_blocks``: 2.53 ms
  there, 76 us a key block where its matmuls need 49, because three passes
  of vector work over a block's ``[32, 512, 512]`` float32 scores run
  BETWEEN its matmuls; the scores themselves stay in the chip's VMEM; my
  chip runs, PR 38).  A decode row attends the ABSORBED form: its query
  taken into the latent space, all heads over the one row a key (the
  latent decode kernel: a page read once), the result taken out again
  (``lift``).

- A ``state_space`` layer (``ops/ssm.py``: a selective scan) keeps a row a
  slot too, at other shapes, under the same discipline (``_step_scan``,
  ``_chunk_scan``: the null row, a prompt from zeros, the tail across a chunk
  boundary; rows that are no real token carry ``Delta = 0``).  Its scan
  output, before the gate, is the token's MEMORY, which ``_walk`` hands down
  the plan to the ``gated_memory`` layers behind it (``memory``, beside
  ``carried``).  A ``shared_attention`` layer keeps nothing: its queries
  attend the pages of the ``full_attention`` layer before it
  (``cfg.source_layer``), written earlier in the same call, and write none.

- Where the model ends in layers that keep no cache (``cfg.cross_start``: a
  cross-decoder of ``gated_memory`` and ``shared_attention`` layers), a
  chunk's rows stop before them, but for the row whose logits are wanted
  (``last_idx``): nothing a later token reads is written there.  ``_walk``
  narrows ``x`` and the memory at that layer, in ``prefill_chunk`` to one
  row and in ``chunk_and_step`` to that row and the decode rows (a fixed
  shape; on a chunk that is not a prompt's last the row's result is not
  read).  A decode step runs every layer on every row.

- ``chunk_and_step`` is both in one walk: the chunk's C rows and the S
  decode rows share each layer's norms, projections, FFN and the head (the
  weights are read once), and each group touches the cache as its own
  program does (``_chunk_*``, ``_step_*``: one source for all three).

- Under ``cfg.shortcut_experts`` a plan entry is a SUBLAYER (a mixer and
  its dense FFN) and the expert FFN a branch across a pair of them: the
  entry that opens it routes its FFN's normed input through ``SparseMLP``
  (the subtree ``moe``) and the entry that closes it adds the result behind
  its own FFN (``transformer_core.ffn_sublayer``: the same order as the
  model's forward); ``_walk`` carries the result from the one to the other,
  in all three programs.

A layer is traced once a kind, not once a layer: the walk calls one jitted
function a (kind, FFN, branch) triple, so a model of 24 like layers traces
one.

Host operands go up PACKED, one int32 array a call (``pack_step``,
``pack_chunk``), and the step's tokens come back with its expert counters
in one array: an upload costs about 0.4 ms on a v5e whatever its size.

The step's tokens STAY on the device as well: a decode step takes the
array the call before it returned, and a slot the host marks
``TOKEN_PREV`` or ``TOKEN_FIRST`` decodes the token it finds there, so the
host may dispatch step n + 1 before it has read step n (``engine.py``).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from ...models.transformer_core import (
    GatedDeltaMixer,
    GatedMemory,
    LatentAttention,
    MambaMixer,
    MLPBlock,
    SelfAttention,
    SparseMLP,
    TransformerConfig,
    ffn_sublayer,
    layer_plan,
    make_norm,
    rope,
)
from ...ops.gated_delta import gated_delta_chunk, gated_delta_step, live_slots
from ...ops.ssm import ssm_chunk, ssm_step
from ...training.lora import LoraSpec, merge_lora
from ..decode import (
    SampleConfig,
    _moe_mlp_cached,
    _sample,
    layer_params,
)
from ..quant import (
    dequantize_leaf,
    dequantize_tree,
    embedding_lookup,
    is_quantized_leaf,
    kv_leaf_parts,
)
from .adapters import factor_rows
from .kv_pool import NO_CACHE_KINDS, STATE_KINDS, gather_blocks, \
    read_pages, ring_table, write_chunk, write_token

_NEG_BIG = -0.7 * float(np.finfo(np.float32).max)
# a slot's flag in ``pack_step``: 0 is an inactive slot; otherwise where the
# token it decodes lies.  In the operand itself (the host has read it), in
# the tokens of the step before (``prev[S:2S]``), or among the first tokens
# that the engine's ``first_token`` left before them (``prev[:S]``)
TOKEN_HOST, TOKEN_PREV, TOKEN_FIRST = 1, 2, 3
KEY_BLOCK = 512  # keys a step of the chunk's attention takes
# The parts of a call, as ``jax.named_scope``s: a component of each device
# op's ``op_name`` (a fusion has its root's), by which a trace is read.  A
# contract like the kernels' ``tadnn_*`` names (PERF.md section 3).
SCOPES = (
    "tadnn.embed",         # ``_embed``
    "tadnn.mix_in",        # a layer's first norm and its mixer's projections
    "tadnn.attend_chunk",  # ``_chunk_*``: a chunk's rows against the cache
    "tadnn.attend_step",   # ``_step_*``: the decode rows against the cache
    "tadnn.mix_out",       # the output projection, its norm, the residual
    "tadnn.ffn",           # a dense FFN (and the toy experts)
    "tadnn.ffn_expert",    # ``SparseMLP``: router, layout, kernels, combine
                           # (a shortcut branch's too, inside ``tadnn.ffn``)
    "tadnn.head",          # the final norm, the logits and the sampler
)


@jax.named_scope("tadnn.embed")
def _embed(params, cfg: TransformerConfig, tok, positions):
    x = embedding_lookup(params["embed"]["embedding"], tok, cfg.dtype)
    if cfg.embed_scale:
        x = x * jnp.asarray(np.sqrt(cfg.d_model), x.dtype)
    if cfg.pos == "learned":
        x = x + params["pos_embed"].astype(cfg.dtype)[positions]
    return x


@jax.named_scope("tadnn.head")
def _logits(params, cfg: TransformerConfig, x):
    x = make_norm(cfg).apply({"params": params["final_norm"]}, x)
    feats = x.astype(jnp.float32)
    if cfg.tie_embeddings:
        emb = params["embed"]["embedding"]
        if is_quantized_leaf(emb):
            emb = dequantize_leaf(emb, jnp.float32)
        return feats @ emb.astype(jnp.float32).T
    head = params["lm_head"]["kernel"]
    if is_quantized_leaf(head):
        head = dequantize_leaf(head, jnp.float32)
    return feats @ head.astype(jnp.float32)


def _layer(cfg, lp, kind, sparse, x, positions, valid, attend, *,
           adapted=None, branch=None, carried=None, memory=None, depth=0):
    """One layer, its mixer by ``kind``: ``attend`` is what touches the
    cache, everything else is shared by the rows, whatever call they are
    of.  On an attention layer ``attend(q, k, v)`` writes the new keys and
    values and returns the attention output; on a ``linear_attention``
    layer ``attend(convolve, pre, g, beta)`` is the state update (``pre``,
    ``g``, ``beta`` are the mixer's ``project`` of the layer's input, a row
    at a time; ``convolve(pre, tail)`` its ``convolve`` of one sequence's
    rows behind their tail); on a ``latent_attention`` layer
    ``attend(piece, q_nope, q_rope, latent)`` writes the rows' latents and
    returns the heads' outputs (``piece(name, *a)`` is the mixer's method
    ``name``: ``expand``, ``absorb``, ``lift``); on a ``state_space`` layer
    ``attend(piece, a)`` is the scan over the rows' projections ``a``
    (``piece``: ``convolve``, ``scan_inputs``, ``rates``) and what it
    returns becomes the ``memory``; a ``gated_memory`` layer touches no
    cache and reads ``memory``; a ``shared_attention`` layer's ``attend(q,
    None, None)`` writes nothing.  ``depth`` is the layer's index
    (differential attention's constant: an operand, so that the layers of a
    kind share a trace).
    ``adapted(tensor, site, inp, rotate)`` adds a tenant's low-rank delta at
    a projection (decode steps with tenants).  ``branch`` and ``carried``
    are the plan entry's and what an open shortcut branch holds
    (``transformer_core.ffn_sublayer``, which orders this half of the layer
    for the model's own forward as well).  Returns ``(x, the expert FFN's
    counters or None, what is carried on, the memory)``."""
    dtype = cfg.dtype
    norm = make_norm(cfg)
    # int8 weight-only serving: only this layer's weights convert
    lp = dequantize_tree(lp, dtype)
    own = {"params": lp["attn"]}
    # a mixer in three parts: its projections of the normed input (what
    # ``attend`` takes), ``attend`` (scoped in ``_chunk_*`` / ``_step_*``),
    # and the way back out
    with jax.named_scope("tadnn.mix_in"):
        h = norm.apply({"params": lp["attn_norm"]}, x) if cfg.pre_norm else x
        if kind == "linear_attention":
            mixer = GatedDeltaMixer(cfg)
            rows = (lambda *a: mixer.apply(own, *a, method="convolve"),
                    *mixer.apply(own, h, method="project"))
        elif kind == "latent_attention":
            mixer = LatentAttention(cfg)
            rows = (lambda name, *a: mixer.apply(own, *a, method=name),
                    *mixer.apply(own, h, positions, method="project"))
        elif kind == "state_space":
            mixer = MambaMixer(cfg)
            a, z = mixer.apply(own, h, method="project")
            rows = (lambda name, *a: mixer.apply(own, *a, method=name), a)
        elif kind == "gated_memory":
            rows = None  # no cache to touch
        else:
            mixer = SelfAttention(cfg, kind)
            q, k, v = mixer.apply(own, h, positions, method="qkv")
            if adapted is not None:
                hf = h.astype(jnp.float32)
                q = adapted(q, "q", hf, cfg.layer_rotates(kind))
                k = adapted(k, "k", hf, cfg.layer_rotates(kind))
                v = adapted(v, "v", hf, False)
            rows = (q, k, v)
    o = None if rows is None else attend(*rows)
    with jax.named_scope("tadnn.mix_out"):
        if kind == "linear_attention":
            ao = mixer.apply(own, o, h, method="out_proj")
        elif kind == "latent_attention":
            ao = mixer.apply(own, o.astype(dtype), method="out_proj")
        elif kind == "state_space":
            memory = o  # before the gate: what the memory units read
            ao = mixer.apply(own, o, z, method="out_proj")
        elif kind == "gated_memory":
            ao = GatedMemory(cfg).apply(own, h, memory)
        else:
            if cfg.diff_attention:  # the pairs' difference, their norm
                o = mixer.apply(own, o, depth, method="differ")
            ao = mixer.apply(own, o.astype(dtype), h, method="out_proj")
            if adapted is not None:
                ao = adapted(ao, "o", o.reshape(*o.shape[:2], -1).astype(
                    jnp.float32), False)
        if cfg.sandwich_norm:
            ao = norm.apply({"params": lp["post_attn_norm"]}, ao)
        x = x + ao

    def dense(u):
        if "experts_up" in lp["mlp"]:  # the toy experts, every one run
            return _moe_mlp_cached(lp["mlp"], u, cfg)
        return MLPBlock(cfg).apply({"params": lp["mlp"]}, u)

    def experts(u):
        if sparse:  # in the FFN's place, under its scope below
            return SparseMLP(cfg).apply({"params": lp["mlp"]}, u, valid)
        with jax.named_scope("tadnn.ffn_expert"):  # a shortcut branch
            return SparseMLP(cfg).apply({"params": lp["moe"]}, u, valid)

    with jax.named_scope("tadnn.ffn_expert" if sparse else "tadnn.ffn"):
        return *ffn_sublayer(
            cfg, x, sparse, branch, carried,
            norm=lambda x: norm.apply({"params": lp["mlp_norm"]}, x),
            dense=dense, experts=experts,
            post_norm=lambda h: norm.apply({"params": lp["post_mlp_norm"]},
                                           h)), memory


def _paged(cfg) -> list[int]:
    """The layers that keep keys and values in pages (not those that keep
    a row a slot, nor those that keep nothing)."""
    return [i for i, (_, kind, *_) in enumerate(layer_plan(cfg))
            if kind not in STATE_KINDS + NO_CACHE_KINDS]


def page_readers(cfg) -> list[str | None]:
    """The kind of every layer that attends pages, its own or another
    layer's (``shared_attention``): a paged call each, a decode step."""
    return [kind for _, kind, *_ in layer_plan(cfg)
            if kind not in STATE_KINDS + ("gated_memory",)]


def _pages_of(kind: str | None) -> str:
    """Which of a call's tables a layer of ``kind`` reads: the ring of a
    ``sliding_attention`` layer, or the request's pages for ``max_len`` (a
    ``shared_attention`` layer: its source's, a ``full_attention``
    layer's)."""
    return "ring" if kind == "sliding_attention" else "pages"


def _work_of(kind: str | None) -> str:
    """Which of a step's work lists a layer of ``kind`` runs: a latent
    page's kernel takes its own number of keys a grid step."""
    return "latent" if kind == "latent_attention" else _pages_of(kind)


N_COUNTERS = 10  # what a step's output carries after its tokens


def _moe_counters(stats: list) -> jax.Array:
    """[pairs that landed here, experts touched, most tokens on one expert,
    row tiles that held pairs, pairs on zero-compute experts] over the
    step's expert layers, then the valid rows a layer routed (the same in
    every layer: the call's); zeros for a model without any."""
    stats = [s for s in stats if s is not None]
    if not stats:
        return jnp.zeros((6,), jnp.int32)
    return jnp.stack([
        sum(s["pairs"] for s in stats),
        sum(s["experts_touched"] for s in stats),
        jnp.max(jnp.stack([s["max_expert_tokens"] for s in stats])),
        sum(s["tiles_active"] for s in stats),
        sum(s["zero_pairs"] for s in stats),
        stats[0]["rows"]])


def _walk(cfg, params, kv, x, layer_fn, shared, extras=None, narrow=None):
    """``layer_fn(kind, sparse, branch, narrowed)(lp, k_pages, v_pages, x,
    extra, shared, carried, memory, depth)`` over the plan, one jitted
    function a (kind, FFN, branch, narrowed) entry so that like layers are
    traced once; the pair of arrays is the layer's own (``kv_pool``: pages, a
    ring, or a state and a tail), or on a ``shared_attention`` layer its
    source's as the source left them in this call (it writes nothing);
    ``shared`` is what every layer reads (tables, positions, rows),
    ``extras`` one more operand a layer (a tenant's factors); ``carried`` is
    what an entry that opens a shortcut branch hands to the one that closes
    it (None between any other two), ``memory`` the scan output of the last
    ``state_space`` layer, for the ``gated_memory`` layers behind it.
    ``narrow(rows)``: where the model ends in layers that keep no cache
    (``cfg.cross_start``), the rows that go on through them, of ``x`` and of
    the memory; the layers from there on are built ``narrowed``.  Returns
    ``(x, kv, the layers' counters)``.  Under ``rows_walked`` the rows of
    ``x`` that each layer took are noted as the walk is traced."""
    fns, new_k, new_v, stats = {}, [], [], []
    carried = memory = None
    cut = cfg.n_layers if narrow is None else cfg.cross_start
    took = []
    for i, (name, *entry) in enumerate(layer_plan(cfg)):
        if i == cut:
            x, memory = narrow(x), narrow(memory)
        took.append(math.prod(x.shape[:-1]))
        key = (*entry, i >= cut)
        fn = fns.get(key)
        if fn is None:
            fn = fns[key] = jax.jit(layer_fn(*key))
        src = i if entry[0] != "shared_attention" else cfg.source_layer(i)
        pools = ((kv["k"][i], kv["v"][i]) if src == i
                 else (new_k[src], new_v[src]))
        x, k_l, v_l, counters, carried, memory = fn(
            layer_params(params, name), *pools, x,
            None if extras is None else extras[i], shared, carried, memory,
            jnp.int32(i))
        new_k.append(k_l if src == i else kv["k"][i])
        new_v.append(v_l if src == i else kv["v"][i])
        stats.append(counters)
    if _WALKS is not None:
        _WALKS.append(took)
    return x, {"k": new_k, "v": new_v}, stats


_WALKS: list[list[int]] | None = None


def rows_walked(program, operands) -> list[int]:
    """The rows of ``x`` that each layer of the plan takes in ``program`` (a
    jitted serving program, not yet traced) on abstract ``operands``: read
    off ``_walk`` itself while the program is traced, and not reckoned
    from the program's name and shape: a ``narrow`` that narrows nothing
    shows here."""
    global _WALKS
    _WALKS = []
    try:
        jax.eval_shape(program, *operands)
        (took,) = _WALKS
    finally:
        _WALKS = None
    return took


# -- what touches the cache, a group of rows at a time -------------------------
#
# A call's rows are one prompt's chunk ([C] rows of ONE slot, ``chunk_*``) or
# one token of every slot ([S, T], ``step_*``), or both (``chunk_and_step``).
# Each function below is one group's ``attend`` of ``_layer`` on one layer's
# pair of pool arrays, and returns ``(what the rows read, the pair)``.


def _step_shared(cfg, kv, tables, win_tables, ctx_lens, active, adapter_ids,
                 T: int, attention_impl: str):
    """What every layer of a decode step reads: the tables of each kind of
    page with the null block wherever a slot has no key to read (past the
    newest key, and in a ring before the oldest its window still reaches:
    an item of the null block alone is skipped), the slots' rows of
    a linear layer's state (the null row for a slot that does not decode),
    where its decay is a channel's the list of the slots that decode, which
    is all that step kernel walks (``live_slots``),
    and the MXU kernels' grid, the (slot, first page) items: one list a
    kind of table, built here once a step and not in every layer's call,
    its geometry from the pages' own shape (``item_pages``).  Returns
    ``(shared, [grid steps the step's paged calls run, the slots x items a
    dense grid would, the page copies those steps start, the table entries
    among them that hold a key a slot attends])``."""
    from ...ops.paged_attention import (
        folded_work_list,
        is_folded,
        kernel_pools,
    )

    S, MB = tables.shape
    paged = _paged(cfg)  # none: a model of recurrent states alone
    pages0 = kv["k"][paged[0]] if paged else jnp.zeros((1, 1, 1))
    bs = kv_leaf_parts(pages0)[0].shape[1]
    hi = jnp.where(active, (ctx_lens + T - 1) // bs, -1)
    full = jnp.where(jnp.arange(MB)[None, :] <= hi[:, None], tables, 0)
    shared = {"tables": {"pages": full}, "ctx_lens": ctx_lens,
              "active": active, "adapter_ids": adapter_ids,
              "rows": jnp.where(active, 1 + jnp.arange(S), 0), "work": {}}
    if win_tables.shape[1]:
        lo = (ctx_lens - cfg.sliding_window + 1) // bs
        shared["tables"]["ring"] = ring_table(win_tables, MB, lo, hi)
    if cfg.linear_decay == "channel":  # (the configuration has such layers)
        shared["live"] = live_slots(active)
    kinds = page_readers(cfg)
    grid = jnp.zeros((4,), jnp.int32)
    if attention_impl == "paged" and T == 1 and paged and is_folded(pages0):
        plan = layer_plan(cfg)
        for i in paged:  # in the plan's order: the same text every run
            kind = plan[i][1]
            if _work_of(kind) not in shared["work"]:
                shared["work"][_work_of(kind)] = folded_work_list(
                    ctx_lens, active, max_blocks=MB,
                    pools=kernel_pools(kv["k"][i], kv["v"][i]),
                    window=cfg.layer_window(kind))
        works = [shared["work"][_work_of(kind)] for kind in kinds]
        grid = jnp.stack([sum(w.n_items for w in works),
                          jnp.int32(sum(w.dense for w in works)),
                          sum(w.pages_copied for w in works),
                          sum(w.pages_live for w in works)])
    return shared, grid


@jax.named_scope("tadnn.attend_step")
def _step_attention(cfg, kind, shared, k_l, v_l, q, k, v, *,
                    attention_impl: str, mesh):
    """``q``, ``k``, ``v`` [S, T, heads, hd]: every slot's T tokens written
    at its context's end, then each attends the keys up to itself.  ``k``
    None (a ``shared_attention`` layer): the pair is another layer's, which
    wrote this call's tokens; nothing is written.  With
    ``cfg.diff_attention`` what comes back is [S, T, H, 2 hd], each query
    head over its pair of value heads (``ops.attention.diff_heads``)."""
    from ...ops.attention import diff_plain_heads, xla_attention
    from ...ops.paged_attention import paged_attention

    T = q.shape[1]
    table = shared["tables"][_pages_of(kind)]
    ctx_lens, window = shared["ctx_lens"], cfg.layer_window(kind)
    for t in range(T if k is not None else 0):  # static and small
        k_l = write_token(k_l, table, ctx_lens + t, k[:, t])
        v_l = write_token(v_l, table, ctx_lens + t, v[:, t])
    if attention_impl == "paged" and T == 1:
        return paged_attention(
            q[:, 0], k_l, v_l, table, ctx_lens, window=window, mesh=mesh,
            work=shared["work"].get(_pages_of(kind)),
            diff=cfg.diff_attention)[:, None], k_l, v_l
    # chunk position t writes at positions[s, t] then attends keys
    # 0..positions[s, t] inclusive — the causal triangle across the chunk
    # plus the context below it; table padding beyond a slot's blocks
    # gathers null-block garbage this never admits
    positions = ctx_lens[:, None] + jnp.arange(T)[None, :]  # [S, T]
    kd = gather_blocks(k_l, table, cfg.dtype, cfg.kv_heads)
    vd = gather_blocks(v_l, table, cfg.dtype, cfg.kv_heads)
    key_idx = jnp.arange(kd.shape[1])[None, None, :]
    mask = key_idx <= positions[:, :, None]
    if window is not None:
        mask &= key_idx > positions[:, :, None] - window
    if cfg.diff_attention:  # 2 H plain heads: a query head, a value head
        o = xla_attention(*diff_plain_heads(q, kd, vd), causal=False,
                          mask=mask[:, None])
        return o.reshape(*q.shape[:-1], -1), k_l, v_l
    return xla_attention(q, kd, vd, causal=False,
                         mask=mask[:, None]), k_l, v_l


def _latent_sizes(cfg) -> tuple[int, int, float]:
    """(the latent's rank: a row's first numbers, the value; the rotated
    key part's size behind it; the scale of a score)."""
    r, rot = cfg.latent_kv_rank, cfg.latent_rope_head_dim
    return r, rot, (cfg.latent_nope_head_dim + rot) ** -0.5


@jax.named_scope("tadnn.attend_step")
def _step_latent(cfg, shared, pages, none, piece, q_nope, q_rope, latent, *,
                 attention_impl: str):
    """``q_nope``, ``q_rope`` [S, T, H, .], ``latent`` [S, T, F]: every
    slot's T rows written at its context's end, then each query, absorbed,
    attends the rows up to itself (``none`` is the array of no elements
    that lies beside latent pages)."""
    from ...ops.paged_attention import (
        latent_attention_reference,
        paged_attention,
    )

    T = latent.shape[1]
    table, ctx_lens = shared["tables"]["pages"], shared["ctx_lens"]
    r, _, scale = _latent_sizes(cfg)
    for t in range(T):  # static and small (1 + draft length)
        pages = write_token(pages, table, ctx_lens + t, latent[:, t])
    q = piece("absorb", q_nope, q_rope)
    if attention_impl == "paged" and T == 1:
        o = paged_attention(
            q[:, 0], pages, none, table, ctx_lens, scale=scale, value_dim=r,
            work=shared["work"].get("latent"))[:, None]
    else:  # the dense view: the oracle, and a verify step's T > 1 rows
        o = latent_attention_reference(
            q, gather_blocks(pages, table, cfg.dtype, 1)[:, :, 0], ctx_lens,
            scale=scale, value_dim=r)
    return piece("lift", o), pages


def _where_rows(keep, x):
    """``x`` [rows, ...] with zeros on the rows ``keep`` [rows] leaves out:
    ``beta = 0`` and ``g = 0`` (a head's or a channel's) leave a state as
    it was."""
    return jnp.where(keep.reshape(-1, *(1,) * (x.ndim - 1)), x, 0.0)


@jax.named_scope("tadnn.attend_step")
def _step_state(shared, state, tails, convolve, pre, g, beta):
    """``pre`` [S, 1, D], ``g``, ``beta`` [S, 1, H] (``g`` [S, 1, H, d_k]
    where the decay is a channel's: the rule's forms pick their kernel by
    it): one token a slot, the step form of the rule on the slots' rows of
    ``state`` and ``tails``."""
    rows, live = shared["rows"], shared["active"]
    q, k, v, full = convolve(pre, tails[rows])
    tails = tails.at[rows].set(full[:, 1:].astype(tails.dtype))
    o, state = gated_delta_step(
        q[:, 0], k[:, 0], v[:, 0], _where_rows(live, g[:, 0]),
        _where_rows(live, beta[:, 0]), state, rows, work=shared.get("live"))
    return o[:, None], state, tails


@jax.named_scope("tadnn.attend_step")
def _step_scan(shared, state, tails, piece, a):
    """``a`` [S, 1, d_in], a ``state_space`` layer's projections: one token
    a slot, the step form of the selective scan on the slots' rows of
    ``state`` and ``tails``.  Returns the scan's output [S, 1, d_in]
    float32."""
    rows, live = shared["rows"], shared["active"]
    c, full = piece("convolve", a, tails[rows])
    tails = tails.at[rows].set(full[:, 1:].astype(tails.dtype))
    delta, B, C = piece("scan_inputs", c[:, 0])
    A, D = piece("rates")
    y, state = ssm_step(c[:, 0], _where_rows(live, delta), A, B, C, D, state,
                        rows)
    return y[:, None], state, tails


def _tenant_delta(cfg, ad, adapter_ids, positions, lora_scaling: float):
    """``_layer``'s ``adapted`` for a decode step's rows [S, T]: each slot
    gathers its factors of one layer (``ad``) by its adapter id."""
    if not ad:
        return None

    def adapted(tensor, site, inp, rotate):
        if site not in ad:
            return tensor
        a = factor_rows(ad[site]["a"], adapter_ids)  # [S, d_in, r]
        b = factor_rows(ad[site]["b"], adapter_ids)  # [S, r, d_out]
        d = lora_scaling * jnp.einsum(
            "str,sro->sto", jnp.einsum("std,sdr->str", inp, a), b)
        d = d.reshape(tensor.shape)
        if rotate:
            d = rope(d, positions, cfg.rope_theta)
        return (tensor.astype(jnp.float32) + d).astype(tensor.dtype)

    return adapted


def _chunk_shared(packed, win_row, max_blocks: int):
    """What every layer of a prefill chunk reads, from the operands of
    ``pack_chunk``: the slot's table row a kind of page, the chunk's first
    position and last real row, the slot's row of a linear layer's state,
    and which of its C rows are real.  Under ``"last"`` the same for the
    chunk's last real row ALONE, a chunk of one row at its position: what
    the layers behind ``cfg.cross_start`` run (``_walk``'s ``narrow``)."""
    MB = max_blocks
    C = packed.shape[0] - MB - 3
    table_row, pos0, last_idx = packed[:MB], packed[-3], packed[-2]
    shared = {"rows": {"pages": table_row}, "pos0": pos0,
              "last_idx": last_idx, "row": 1 + packed[-1],
              "real": jnp.arange(C) <= last_idx}
    if win_row.shape[0]:
        shared["rows"]["ring"] = win_row[jnp.arange(MB) % win_row.shape[0]]
    shared["last"] = {**shared, "pos0": pos0 + last_idx,
                      "last_idx": jnp.zeros_like(last_idx),
                      "real": jnp.ones((1,), bool)}
    return shared


def _narrow_chunk(rows, last_idx, C: int):
    """Of ``rows`` [1, C + S, ...] (a chunk's C rows, then S decode rows)
    the chunk's row ``last_idx`` and the decode rows: [1, 1 + S, ...]."""
    if rows is None:
        return None
    return jnp.concatenate([jax.lax.dynamic_slice_in_dim(
        rows, last_idx, 1, axis=1), rows[:, C:]], axis=1)


def chunk_attention_form(cfg, kind: str | None, chunk: int,
                         block_size: int) -> str:
    """How a chunk of ``chunk`` rows attends on a layer of ``kind`` that
    keeps pages of ``block_size``: ``"kernel"`` where it is ONE call of
    ``ops/paged_attention``'s ``tadnn_latent_chunk`` (a latent layer, on the
    chip, at shapes the kernel tiles), else ``"blocks"``, ``jax.numpy`` over
    key blocks (``_over_key_blocks``: the plain form, and the oracle).  By
    what the program sees in its input, nothing else: ``_chunk_latent`` asks
    here, and so does the engine for its ``chunk_attention`` counter."""
    from ...ops.paged_attention import latent_chunk_tiles

    if kind == "latent_attention" and latent_chunk_tiles(
            chunk, block_size, cfg.n_heads, cfg.latent_kv_rank,
            cfg.latent_nope_head_dim, cfg.dtype):
        return "kernel"
    return "blocks"


@jax.named_scope("tadnn.attend_chunk")
def _chunk_attention(cfg, kind, shared, k_l, v_l, q, k, v):
    """``q``, ``k``, ``v`` [C, heads, hd]: the chunk's keys and values
    written into the slot's pages, then its queries over them (``k`` None,
    a ``shared_attention`` layer: over another layer's pages, which hold
    the chunk's already)."""
    row, pos0 = shared["rows"][_pages_of(kind)], shared["pos0"]
    if k is not None:
        k_l = write_chunk(k_l, row, pos0, k)
        v_l = write_chunk(v_l, row, pos0, v)
    return chunk_attention(q, k_l, v_l, row, pos0, cfg.layer_window(kind),
                           cfg.kv_heads, cfg.diff_attention), k_l, v_l


@jax.named_scope("tadnn.attend_chunk")
def _chunk_latent(cfg, shared, pages, piece, q_nope, q_rope, latent):
    """``q_nope``, ``q_rope`` [C, H, .], ``latent`` [C, F]: the chunk's
    latent rows written into the slot's pages, then its queries over the
    rows up to themselves, EXPANDED a block of keys at a time (the module
    docstring has the arithmetic): in one kernel where
    ``chunk_attention_form`` says so, else block by block below."""
    from ...ops.paged_attention import latent_chunk_attention

    row, pos0 = shared["rows"]["pages"], shared["pos0"]
    pages = write_chunk(pages, row, pos0, latent)
    r, rot, scale = _latent_sizes(cfg)
    C, H, _ = q_nope.shape
    if chunk_attention_form(cfg, "latent_attention", C,
                            pages.shape[1]) == "kernel":
        # the pages go on behind the kernel's output: whatever writes them
        # next (the same call's decode rows) waits for the kernel, which
        # reads them where they lie (left free, the compiler once put that
        # write first and gave the kernel a copy of the layer's pool)
        return jax.lax.optimization_barrier((latent_chunk_attention(
            q_nope, q_rope, pages, row, pos0, *piece("up"),
            scale=scale), pages))

    def block(ids):
        rows = read_pages(pages, ids, 1, q_nope.dtype)
        rows = rows.reshape(-1, rows.shape[-1])  # [keys, stored row]
        k_nope, v = piece("expand", rows[:, :r])
        s = (jnp.einsum("chd,thd->hct", q_nope, k_nope,
                        preferred_element_type=jnp.float32)
             + jnp.einsum("chd,td->hct", q_rope, rows[:, r:r + rot],
                          preferred_element_type=jnp.float32)) * scale
        return s, lambda p: jnp.einsum(
            "hct,thd->hcd", p.astype(v.dtype), v,
            preferred_element_type=jnp.float32)

    o = _over_key_blocks(row, pages.shape[1], C, pos0, None, (H,),
                         cfg.latent_value_head_dim, block)
    return o.transpose(1, 0, 2).astype(q_nope.dtype), pages


@jax.named_scope("tadnn.attend_chunk")
def _chunk_state(shared, state, tails, convolve, pre, g, beta):
    """``pre`` [C, D], ``g``, ``beta`` [C, H] (``g`` [C, H, d_k] where the
    decay is a channel's): the chunk form of the rule from the state the chunk before left in the slot's row, or from zeros
    where the prompt starts."""
    row, last_idx = shared["row"], shared["last_idx"]
    fresh = shared["pos0"] == 0  # a prompt starts from zeros
    q, k, v, full = convolve(pre[None],
                             jnp.where(fresh, 0, tails[row])[None])
    # the tail the next call reads: the last K - 1 real rows
    tails = tails.at[row].set(jax.lax.dynamic_slice_in_dim(
        full[0], last_idx + 1, tails.shape[1]).astype(tails.dtype))
    real = shared["real"]
    o, new = gated_delta_chunk(
        q[0], k[0], v[0], _where_rows(real, g), _where_rows(real, beta),
        jnp.where(fresh, 0.0, state[row]))
    return o, state.at[row].set(new), tails


@jax.named_scope("tadnn.attend_chunk")
def _chunk_scan(shared, state, tails, piece, a):
    """``a`` [C, d_in], a ``state_space`` layer's projections of ONE slot's
    chunk: the chunk form of the selective scan from the state the chunk
    before left in the slot's row, or from zeros where the prompt starts.
    Returns the scan's output [C, d_in] float32."""
    row, last_idx = shared["row"], shared["last_idx"]
    fresh = shared["pos0"] == 0  # a prompt starts from zeros
    c, full = piece("convolve", a[None],
                    jnp.where(fresh, 0, tails[row])[None])
    # the tail the next call reads: the last K - 1 real rows
    tails = tails.at[row].set(jax.lax.dynamic_slice_in_dim(
        full[0], last_idx + 1, tails.shape[1]).astype(tails.dtype))
    delta, B, C = piece("scan_inputs", c[0])
    A, D = piece("rates")
    y, new = ssm_chunk(c[0], _where_rows(shared["real"], delta), A, B, C, D,
                       jnp.where(fresh, 0.0, state[row]))
    return y, state.at[row].set(new), tails


def _pool_constraint(kv, mesh, spec):
    """The pool's arrays held to their sharding under a ``mesh``."""
    if mesh is None or spec is None:
        return kv
    from jax.sharding import NamedSharding

    sh = NamedSharding(mesh, spec)
    return jax.tree.map(lambda a: jax.lax.with_sharding_constraint(a, sh), kv)


def decode_logits(params, kv, tables, win_tables, ctx_lens, tok, active,
                  adapters=None, adapter_ids=None, *,
                  cfg: TransformerConfig, attention_impl: str = "paged",
                  lora_scaling: float = 1.0, mesh=None, spec=None):
    """A [S, T] token chunk for every slot (position t attends keys
    0..ctx+t, exactly the sequential semantics).  Static shapes throughout
    (S slots, T chunk, tables [S, max_blocks]) so each engine configuration
    traces exactly once.  ``kv`` is ``{"k": [a layer's pages, ...], "v":
    ...}``, ``tables`` the block tables of the layers that keep pages for
    ``max_len``, ``win_tables`` [S, W] the sliding layers' rings.

    ``attention_impl`` picks the per-layer KV read:

    - ``"paged"`` (default): the fused Pallas kernel
      (ops/paged_attention.py) reads the block table in-kernel — the
      dense gathered view never materializes; single-query only, so T > 1
      verify steps take the dense path below;
    - ``"dense"``: the reference path — ``gather_blocks`` to a dense
      [S, max_len] view, then stock ``xla_attention`` under an explicit
      mask.  Kept as the parity oracle and the fallback.

    ``adapters`` is the AdapterPool's factor pytree (None or {} when
    serving the base model only): per q/k/v/o site, stacked ``a [L, A,
    d_in, r]`` / ``b [L, A, r, d_out]`` factors.  Each slot gathers its
    ``adapter_ids`` row and adds the segmented low-rank delta ``scaling *
    (x @ A) @ B`` to that projection's output — slot 0 holds zero factors
    (IDENTITY_ADAPTER), so base-model slots pay one gather of zeros
    instead of a second trace.  q/k deltas are rope-rotated like the
    projections they perturb (rope is linear, so rotating the delta IS the
    merged-weight semantics).

    Returns ``(kv, logits [S, T, V], counters [N_COUNTERS])``: the expert
    layers' six (``_moe_counters``), then the grid steps the paged calls
    of the step ran, the ``slots x items`` a dense grid would have run, the
    page copies those steps started and the table entries among them that
    held an attendable key, summed over the layers (zeros where no call
    takes a work list)."""
    S, T = tok.shape
    kv = _pool_constraint(kv, mesh, spec)
    shared, grid = _step_shared(cfg, kv, tables, win_tables, ctx_lens,
                                active, adapter_ids, T, attention_impl)
    # per-slot, per-chunk-offset absolute positions
    positions = ctx_lens[:, None] + jnp.arange(T)[None, :]

    def layer_fn(kind, sparse, branch, _narrowed):
        def fn(lp, a, b, x, ad, shared, carried, memory, depth):
            def attend(*rows):  # a, b: the layer's pair
                nonlocal a, b
                if kind == "linear_attention":
                    o, a, b = _step_state(shared, a, b, *rows)
                elif kind == "state_space":
                    o, a, b = _step_scan(shared, a, b, *rows)
                elif kind == "latent_attention":
                    o, a = _step_latent(cfg, shared, a, b, *rows,
                                        attention_impl=attention_impl)
                else:
                    o, a, b = _step_attention(
                        cfg, kind, shared, a, b, *rows,
                        attention_impl=attention_impl, mesh=mesh)
                return o

            x, counters, carried, memory = _layer(
                cfg, lp, kind, sparse, x, positions,
                jnp.broadcast_to(shared["active"][:, None], (S, T)), attend,
                adapted=_tenant_delta(cfg, ad, shared["adapter_ids"],
                                      positions, lora_scaling),
                branch=branch, carried=carried, memory=memory, depth=depth)
            return x, a, b, counters, carried, memory

        return fn

    extras = None
    if adapters:  # one layer's factors a layer
        extras = [jax.tree.map(lambda a: a[i], adapters)
                  for i in range(cfg.n_layers)]
    x = _embed(params, cfg, tok, positions)
    x, kv, stats = _walk(cfg, params, kv, x, layer_fn, shared, extras)
    return kv, _logits(params, cfg, x), jnp.concatenate(
        [_moe_counters(stats), grid])


def pack_step(tables, ctx_lens, tok, source, adapter_ids) -> np.ndarray:
    """A decode step's host operands as ONE int32 array [S, MB + T + 3]
    (tables, then the tokens, the context length, the flag and the adapter
    id of each slot): one upload a step where five cost 0.4 ms each.
    ``source`` is 0 on an inactive slot, else one of ``TOKEN_*`` (a bool
    reads as ``TOKEN_HOST``)."""
    return np.concatenate(
        [tables, tok, ctx_lens[:, None], source[:, None],
         adapter_ids[:, None]], axis=1).astype(np.int32)


def step_output(n_slots: int, n_tok: int = 1) -> jax.Array:
    """What a decode step takes as ``prev`` before there has been one."""
    return jnp.zeros((n_slots + n_slots * n_tok + N_COUNTERS,), jnp.int32)


def _step_operands(packed, prev, n_tok: int = 1):
    """``pack_step``'s [S, MB + T + 3] array taken apart, each slot's token
    from where its flag says it lies (T == 1): ``(tables, ctx_lens, tok
    [S, T], active, the first tokens as prev had them)``."""
    S = packed.shape[0]
    MB = packed.shape[1] - n_tok - 3
    tok, source = packed[:, MB:MB + n_tok], packed[:, -2]
    firsts = prev[:S]
    if n_tok == 1:
        tok = jnp.where(source == TOKEN_PREV, prev[S:2 * S], jnp.where(
            source == TOKEN_FIRST, firsts, tok[:, 0]))[:, None]
    return packed[:, :MB], packed[:, -3], tok, source > 0, firsts


def decode_step(params, kv, packed, prev, win_tables, adapters, rng, *,
                cfg: TransformerConfig, sample: SampleConfig, n_tok: int = 1,
                **kw):
    """``decode_logits`` on the operands of ``pack_step`` (``n_tok`` is its
    T) and on ``prev``, what the call before this one returned
    (``step_output`` before the first).  Returns ``(kv, [S + S * T +
    N_COUNTERS] int32)``: the slots' first tokens, as ``prev`` had them (the
    engine's ``first_token`` writes them); then the step's sampled tokens
    [S] (T == 1), or the target's greedy choices [S, T] flattened (verify
    steps are temperature-0 by contract — sampled speculative needs
    rejection resampling); then the step's counters (``decode_logits``): one
    array, so that one fetch brings all three.  With T == 1 a slot flagged
    ``TOKEN_PREV`` decodes its own token of ``prev`` and one flagged
    ``TOKEN_FIRST`` its first token there, whatever the operand holds: the
    same shapes every step."""
    tables, ctx_lens, tok, active, firsts = _step_operands(packed, prev,
                                                           n_tok)
    kv, logits, counters = decode_logits(
        params, kv, tables, win_tables, ctx_lens, tok, active, adapters,
        packed[:, -1], cfg=cfg, **kw)
    with jax.named_scope("tadnn.head"):
        if n_tok == 1:
            out = jnp.where(active, _sample(logits[:, 0], rng, sample), 0)
        else:  # the all-logits discipline of decode.generate
            tgt = jnp.argmax(logits, axis=-1).astype(jnp.int32)  # [S, T]
            out = jnp.where(active[:, None], tgt, 0).reshape(-1)
    return kv, jnp.concatenate([firsts, out, counters])


def _over_key_blocks(table_row, bs: int, C: int, pos0, window, lead, dv: int,
                     block):
    """The online softmax of ``ops/flash_attention.py`` for a chunk's C
    queries at positions ``pos0 .. pos0 + C`` over one slot's pages, the
    chunk's own keys already written: keys come a block of ``KEY_BLOCK`` at
    a time through the table, from the first block the window reaches to
    the chunk's last, so the work follows the context a request has, not
    ``max_len``.  ``block(page ids) -> (scores [*lead, C, keys] float32,
    values(p) -> [*lead, C, dv] float32)`` is what the layer's kind makes of
    a block's pages.  Returns [*lead, C, dv] float32."""
    ppb = max(1, KEY_BLOCK // bs)
    KB = ppb * bs
    # whole key blocks up to the last a chunk may reach (the null block
    # past the row's end: those keys lie after every query)
    n_kb = -(-(table_row.shape[0] * bs + C) // KB)
    table_row = jnp.pad(table_row, (0, n_kb * ppb - table_row.shape[0]))
    q_pos = pos0 + jnp.arange(C)
    wide = (None,) * len(lead)

    def body(jb, carry):
        acc, m, l = carry
        s, values = block(jax.lax.dynamic_slice_in_dim(table_row, jb * ppb,
                                                       ppb))
        key_pos = jb * KB + jnp.arange(KB)
        ok = key_pos[None, :] <= q_pos[:, None]
        if window is not None:
            ok &= key_pos[None, :] > q_pos[:, None] - window
        s = jnp.where(ok[wide], s, _NEG_BIG)
        m_new = jnp.maximum(m, s.max(-1))
        p = jnp.where(ok[wide], jnp.exp(s - m_new[..., None]), 0.0)
        alpha = jnp.exp(m - m_new)
        l = l * alpha + p.sum(-1)
        acc = acc * alpha[..., None] + values(p)
        return acc, m_new, l

    lo = 0 if window is None else jnp.maximum(pos0 - window + 1, 0) // KB
    hi = (pos0 + C - 1) // KB
    acc, _, l = jax.lax.fori_loop(
        lo, hi + 1, body,
        (jnp.zeros((*lead, C, dv), jnp.float32),
         jnp.full((*lead, C), _NEG_BIG, jnp.float32),
         jnp.zeros((*lead, C), jnp.float32)))
    return acc / l[..., None]  # every row sees at least its own key


def chunk_attention(q, k_layer, v_layer, table_row, pos0, window,
                    kv_heads: int, diff: bool = False):
    """Causal (banded, with ``window``) attention of a chunk's queries
    ``q`` [C, H, hd] at positions ``pos0 .. pos0 + C`` over one slot's
    pages of keys and values (``_over_key_blocks``).  ``diff``:
    differential attention's first half (``ops.attention.diff_heads``): the
    KV heads in pairs, a pair's ``H / kvH`` query-head pairs each head over
    ITS key head of the pair against both value heads, 2 hd wide: [C, H,
    2 hd] float32, for ``SelfAttention.differ``."""
    C, H, hd = q.shape
    bs = kv_leaf_parts(k_layer)[0].shape[1]
    KV = kv_heads
    G = H // KV
    scale = 1.0 / float(np.sqrt(hd))
    if diff:
        qp = q.reshape(C, KV // 2, G, 2, hd)  # group, pair, which of the two

        def pair_block(pages):
            kb = read_pages(k_layer, pages, KV, q.dtype).reshape(
                -1, KV // 2, 2, hd)
            vb = read_pages(v_layer, pages, KV, q.dtype).reshape(
                -1, KV // 2, 2 * hd)
            s = jnp.einsum("cgjid,tgid->gjict", qp, kb,
                           preferred_element_type=jnp.float32) * scale
            return s, lambda p: jnp.einsum(
                "gjict,tgv->gjicv", p.astype(vb.dtype), vb,
                preferred_element_type=jnp.float32)

        o = _over_key_blocks(table_row, bs, C, pos0, window, (KV // 2, G, 2),
                             2 * hd, pair_block)
        return o.transpose(3, 0, 1, 2, 4).reshape(C, H, 2 * hd)
    qg = q.reshape(C, KV, G, hd)

    def block(pages):
        kb = read_pages(k_layer, pages, KV, q.dtype).reshape(-1, KV, hd)
        vb = read_pages(v_layer, pages, KV, q.dtype).reshape(-1, KV, hd)
        s = jnp.einsum("ckgd,tkd->kgct", qg, kb,
                       preferred_element_type=jnp.float32) * scale
        return s, lambda p: jnp.einsum(
            "kgct,tkd->kgcd", p.astype(vb.dtype), vb,
            preferred_element_type=jnp.float32)

    o = _over_key_blocks(table_row, bs, C, pos0, window, (KV, G), hd, block)
    return o.transpose(2, 0, 1, 3).reshape(C, H, hd).astype(q.dtype)


def pack_chunk(table_row, tokens, pos0: int, last_idx: int,
               slot: int = 0) -> np.ndarray:
    """A prefill chunk's host operands as ONE int32 vector [MB + C + 3]:
    the slot's table row, the chunk's tokens, its first position, its last
    real row, and the slot (whose row a ``linear_attention`` layer keeps
    its state in)."""
    return np.asarray([*table_row, *tokens, pos0, last_idx, slot], np.int32)


def prefill_chunk(params, kv, packed, win_row, *, cfg: TransformerConfig,
                  max_blocks: int):
    """The chunk alone, for the engines whose steps cannot carry it
    (speculative, and tenants through ``prefill_chunk_lora``).
    One [1, C] chunk of one slot's prompt (operands of ``pack_chunk``;
    C is what ``packed`` holds beyond ``max_blocks + 3``) at positions
    ``pos0 ..``, written into the slot's pages (the table row [max_blocks]
    on the layers that keep pages for ``max_len``, the ring ``win_row`` [W]
    on the sliding ones).  Every chunk of every prompt reuses ONE jitted
    trace: the chunk length is constant and both cursors are traced
    scalars.  The last chunk of a prompt may be right-padded: ``last_idx``
    is its last real row, whose logits are returned ([1, V]); causal
    masking keeps the pad rows (which sit after it) out of that row, they
    route to no expert, and the keys they write lie past the prompt, where
    decode writes over them before it reads; on a ``linear_attention``
    layer they leave the state alone and stay out of the convolution's
    tail.  An int8 pool quantizes the
    chunk as it lands, so later chunks, decode and any request that reuses
    these pages through the prefix cache all read the same (q, scale)
    pairs.  Returns ``(kv, logits)``."""
    shared = _chunk_shared(packed, win_row, max_blocks)
    C = shared["real"].shape[0]
    tokens, pos0 = packed[max_blocks:max_blocks + C][None], shared["pos0"]
    positions = pos0 + jnp.arange(C)[None, :]

    def layer_fn(kind, sparse, branch, narrowed):
        def fn(lp, a, b, x, _, shared, carried, memory, depth):
            sh = shared["last"] if narrowed else shared  # the rows' own

            def attend(*rows):  # a, b: the layer's pair
                nonlocal a, b
                if kind == "linear_attention":
                    convolve, pre, g, beta = rows
                    o, a, b = _chunk_state(sh, a, b, convolve, pre[0],
                                           g[0], beta[0])
                elif kind == "state_space":
                    o, a, b = _chunk_scan(sh, a, b, rows[0], rows[1][0])
                elif kind == "latent_attention":
                    o, a = _chunk_latent(cfg, sh, a, rows[0],
                                         *(r[0] for r in rows[1:]))
                else:
                    o, a, b = _chunk_attention(
                        cfg, kind, sh, a, b,
                        *(None if r is None else r[0] for r in rows))
                return o[None]

            x, _counters, carried, memory = _layer(
                cfg, lp, kind, sparse, x,
                sh["pos0"] + jnp.arange(x.shape[1])[None, :],
                sh["real"][None], attend, branch=branch, carried=carried,
                memory=memory, depth=depth)
            return x, a, b, None, carried, memory

        return fn

    # behind ``cfg.cross_start`` only the row whose logits are returned
    narrow = lambda rows: None if rows is None else (
        jax.lax.dynamic_slice_in_dim(rows, shared["last_idx"], 1, axis=1))
    x = _embed(params, cfg, tokens, positions)
    x, kv, _ = _walk(cfg, params, kv, x, layer_fn, shared, narrow=narrow)
    if cfg.cross_start < cfg.n_layers:
        return kv, _logits(params, cfg, x[:, 0])
    last = jax.lax.dynamic_index_in_dim(x, shared["last_idx"], axis=1,
                                        keepdims=False)
    return kv, _logits(params, cfg, last)


def pack_chunk_and_step(chunk: np.ndarray, step: np.ndarray) -> np.ndarray:
    """The operands of ``pack_chunk`` and of ``pack_step`` (T == 1) as ONE
    int32 vector: one upload for the call that takes both."""
    return np.concatenate([chunk, step.reshape(-1)])


def chunk_and_step(params, kv, packed, prev, win_row, win_tables, rng, *,
                   cfg: TransformerConfig, sample: SampleConfig,
                   max_blocks: int, chunk: int,
                   attention_impl: str = "paged", mesh=None, spec=None):
    """A prefill chunk of ONE slot and a decode step of the OTHERS in one
    walk of the layers: ``prefill_chunk`` on the first ``max_blocks + chunk
    + 3`` of ``packed`` and ``win_row``, ``decode_step`` (T == 1, no
    tenants) on the rest, ``prev``, ``win_tables`` and ``rng``
    (``pack_chunk_and_step``).  The C + S rows share each layer's norms,
    projections, FFN (an expert layer routes them together: each touched
    expert is read once) and the head; only what touches the cache differs
    by group, and that is the two programs' own (``_chunk_*``, ``_step_*``).
    The chunk's slot is not among the decoding ones (its flag in the step's
    operands is 0: a null table, the null row), so no row reads what
    another row of the call writes.  Returns ``(kv, the step's output as
    decode_step gives it, the chunk's last real row's logits [1, V])``; the
    expert counters in the output are of all the rows."""
    MB, C = max_blocks, chunk
    cut = MB + C + 3
    tables, ctx_lens, tok, active, firsts = _step_operands(
        packed[cut:].reshape(-1, MB + 4), prev)
    S = active.shape[0]
    kv = _pool_constraint(kv, mesh, spec)
    shared = {"chunk": _chunk_shared(packed[:cut], win_row, MB)}
    shared["step"], grid = _step_shared(
        cfg, kv, tables, win_tables, ctx_lens, active, None, 1,
        attention_impl)
    pos0, real = shared["chunk"]["pos0"], shared["chunk"]["real"]
    # one sequence of C + S rows: the chunk's, then a row a slot
    positions = jnp.concatenate([pos0 + jnp.arange(C), ctx_lens])[None]
    valid = jnp.concatenate([real, active])[None]

    last_idx = shared["chunk"]["last_idx"]

    def layer_fn(kind, sparse, branch, narrowed):
        # the chunk's rows of the call's: all C, or behind
        # ``cfg.cross_start`` the one whose logits are wanted
        n = 1 if narrowed else C

        def fn(lp, a, b, x, _, shared, carried, memory, depth):
            chunk, step = shared["chunk"], shared["step"]
            if narrowed:
                chunk = chunk["last"]

            def attend(*rows):  # a, b: the layer's pair
                nonlocal a, b
                ours = lambda rows: (None if r is None else r[0, :n]
                                     for r in rows)
                theirs = lambda rows: (None if r is None else r[0, n:, None]
                                       for r in rows)
                if kind == "linear_attention":
                    convolve, *rows = rows
                    oc, a, b = _chunk_state(chunk, a, b, convolve,
                                            *ours(rows))
                    os_, a, b = _step_state(step, a, b, convolve,
                                            *theirs(rows))
                elif kind == "state_space":
                    piece, *rows = rows
                    oc, a, b = _chunk_scan(chunk, a, b, piece, *ours(rows))
                    os_, a, b = _step_scan(step, a, b, piece, *theirs(rows))
                elif kind == "latent_attention":
                    piece, *rows = rows
                    oc, a = _chunk_latent(cfg, chunk, a, piece, *ours(rows))
                    os_, a = _step_latent(
                        cfg, step, a, b, piece, *theirs(rows),
                        attention_impl=attention_impl)
                else:
                    oc, a, b = _chunk_attention(cfg, kind, chunk, a, b,
                                                *ours(rows))
                    os_, a, b = _step_attention(
                        cfg, kind, step, a, b, *theirs(rows),
                        attention_impl=attention_impl, mesh=mesh)
                return jnp.concatenate(
                    [oc, os_[:, 0].astype(oc.dtype)])[None]

            x, counters, carried, memory = _layer(
                cfg, lp, kind, sparse, x,
                _narrow_chunk(positions, last_idx, C) if narrowed
                else positions,
                _narrow_chunk(valid, last_idx, C) if narrowed else valid,
                attend, branch=branch, carried=carried, memory=memory,
                depth=depth)
            return x, a, b, counters, carried, memory

        return fn

    x = _embed(params, cfg, jnp.concatenate(
        [packed[MB:MB + C], tok[:, 0]])[None], positions)
    x, kv, stats = _walk(
        cfg, params, kv, x, layer_fn, shared,
        narrow=lambda rows: _narrow_chunk(rows, last_idx, C))
    # the head once: the S decode rows, then the chunk's last real row
    if cfg.cross_start < cfg.n_layers:  # narrowed: that row lies first
        C = 1
        last = x[0, :1]
    else:
        last = jax.lax.dynamic_index_in_dim(x[0], last_idx, keepdims=True)
    logits = _logits(params, cfg, jnp.concatenate([x[0, C:], last]))
    with jax.named_scope("tadnn.head"):
        out = jnp.where(active, _sample(logits[:S], rng, sample), 0)
    return kv, jnp.concatenate(
        [firsts, out, _moe_counters(stats), grid]), logits[S:]


def prefill_chunk_lora(params, lora, kv, packed, win_row, *,
                       lora_spec: LoraSpec, **kw):
    """A tenant's prefill chunk through per-tenant merged weights:
    ``merge_lora`` runs INSIDE the jit (the rank-r matmul fuses into the
    weight load), so ONE trace serves every tenant — the factor tree is a
    traced operand and the merged weights never materialize on the
    host."""
    return prefill_chunk(merge_lora(params, lora, lora_spec), kv, packed,
                         win_row, **kw)
