"""Pallas paged-attention decode kernel: block tables read in-kernel.

The serving decode step used to gather every slot's KV blocks into a
dense ``[S, max_len, kvH, hd]`` view (``kv_pool.gather_blocks``) before
stock attention — an O(slots x max_len) HBM materialization per layer
per token, the exact cost ROADMAP's "Serving path, phase 2" calls out.
This kernel eliminates it: the per-request block table is a
scalar-prefetch operand, so each grid step's ``index_map`` reads
``table[slot, j]`` and DMAs block ``j``'s page straight from the paged
pool into VMEM.  No dense view ever exists; HBM traffic is O(tokens
actually cached), the same bytes the pool stores.

Shape of the problem (one decode token per slot):

    q:      [S, Hq, hd]          one query per slot
    k/v:    [NB, bs, kvH * hd]   ONE layer of the paged pool, pages folded
            [NB, bs, kvH, hd]    (int8 pools and pools sharded over a mesh)
    tables: [S, MB] int32        block ids, null-padded (kv_pool)
    ctx:    [S] int32            keys 0..ctx inclusive are valid

ONE entry point for decode, :func:`paged_attention`, and three kernels
chosen by the shape of a page, not by the model (a prefill chunk on latent
pages has a kernel of its own, :func:`latent_chunk_attention`: "the chunk
kernel" below).  A pool of LATENT pages (one row a token that is key and
value at once, ``[NB, bs, kv_rank + rope]``, and no second array) is read
by ``tadnn_paged_decode_latent``, the folded kernel's body at other
numbers: see "the latent kernel" below.  A pool of folded pages is read by the
MXU kernel (``tadnn_paged_decode_folded``, further down: grouped queries
as two plain matmuls, 8 pages a grid step that the kernel copies itself,
the grid a list of the key groups the slots have, of traced length).  A
page kept as ``[bs, kvH, hd]``
is read by the VPU kernel (``tadnn_paged_decode``), which dequantizes int8
pages on load and runs per shard under ``shard_map``; the rest of this text
describes it.

Its grid is ``(S, MB)`` with the block axis innermost ("arbitrary"
semantics); a grid step takes one whole page, every kv head of it (the
block shape the TPU lowering accepts — it refuses one head sliced out
of the second-minor dimension).  VMEM scratch carries flash-style
online-softmax statistics (running max / sum / accumulator, fp32)
across a slot's blocks, exactly the ``ops/flash_attention.py``
discipline.  GQA is native — each kv head serves its ``Hq // kvH``
query group without materializing the head broadcast.  Blocks past a
slot's context (null-table padding) are skipped at the grid level via
the prefetched ``ctx``; a sliding window additionally skips blocks
entirely older than ``ctx - window``.

int8 KV (``inference/quant.quantize_kv``'s ``{"q", "scale"}`` leaves)
is dequantized ON LOAD, fused into the kernel: the int8 payload and its
per-(token, head) fp32 scales stream into VMEM and the multiply happens
right before the MXU dot — the dense bf16 form of a block never touches
HBM either.

CPU fallback follows ``flash_attention.py``: ``interpret=True`` (the
default off-TPU) runs the same kernel in the Pallas interpreter, so the
CPU-sim tests exercise the real kernel logic;
:func:`paged_attention_reference` is the pure-JAX oracle — it IS the
dense ``gather_blocks`` + ``xla_attention`` path the engine's
``attention_impl="dense"`` runs, which is what makes paged-vs-dense
parity a one-assert test.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_BIG = -0.7 * float(np.finfo(np.float32).max)
_LANES = 128  # row stats stored lane-broadcast, as in flash_attention


def _default_interpret() -> bool:
    return jax.default_backend() == "cpu"


@dataclasses.dataclass(frozen=True)
class _Cfg:
    block_size: int
    group: int  # query heads per kv head (Hq // kvH)
    window: int | None
    quantized: bool
    interpret: bool


def _decode_kernel(*refs, cfg: _Cfg, scale: float):
    """One (slot, block) grid step of paged decode attention, all kv
    heads of the block at once.

    A pool page arrives as ``[bs, kvH, hd]`` — its natural layout, heads
    on sublanes and ``hd`` on lanes — so the one-query-row products are
    VPU broadcasts and reductions in that layout (no per-head slice of
    the second-minor dimension, which the TPU lowering refuses, and no
    transpose): scores reduce over lanes, the softmax statistics and the
    value sum reduce over the leading ``bs`` dimension.
    """
    tables_ref, ctx_ref = refs[0], refs[1]
    if cfg.quantized:
        (q_ref, kq_ref, ks_ref, vq_ref, vs_ref,
         o_ref, acc_ref, m_ref, l_ref) = refs[2:]
    else:
        q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref = refs[2:]

    s = pl.program_id(0)
    j = pl.program_id(1)
    nj = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, _NEG_BIG)
        l_ref[:] = jnp.zeros_like(l_ref)

    ctx = ctx_ref[s]
    start = j * cfg.block_size
    # a block is relevant iff it holds any key <= ctx (and, windowed,
    # any key newer than ctx - window) — the table's null padding sits
    # past ctx by construction, so padding blocks are skipped here
    relevant = start <= ctx
    if cfg.window is not None:
        relevant = jnp.logical_and(
            relevant, start + cfg.block_size - 1 > ctx - cfg.window)

    @pl.when(relevant)
    def _block():
        if cfg.quantized:
            # dequantize-on-load: int8 payload x per-(token, head) scale
            # (lane-broadcast) — the dense form never hits HBM
            k = kq_ref[0].astype(jnp.float32) * ks_ref[0]  # [bs, kvH, hd]
            v = vq_ref[0].astype(jnp.float32) * vs_ref[0]
        else:
            k = k_ref[0].astype(jnp.float32)
            v = v_ref[0].astype(jnp.float32)
        pos = start + jax.lax.broadcasted_iota(
            jnp.int32, (k.shape[0], k.shape[1], 1), 0)
        valid = pos <= ctx
        if cfg.window is not None:
            valid = jnp.logical_and(valid, pos > ctx - cfg.window)

        for g in range(cfg.group):  # static: query heads per kv head
            q = q_ref[0, g].astype(jnp.float32)  # [kvH, hd]
            sc = jnp.sum(k * q[None], axis=-1, keepdims=True) * scale
            sc = jnp.where(valid, sc, _NEG_BIG)  # [bs, kvH, 1]

            m_prev = m_ref[g][:, :1]  # [kvH, 1] (lane-broadcast storage)
            l_prev = l_ref[g][:, :1]
            m_new = jnp.maximum(m_prev, jnp.max(sc, axis=0))
            m_new = jnp.maximum(m_new, _NEG_BIG / 2)
            p = jnp.exp(sc - m_new[None])  # [bs, kvH, 1] fp32
            alpha = jnp.exp(m_prev - m_new)
            l_new = l_prev * alpha + jnp.sum(p, axis=0)
            pv = jnp.sum(p * v, axis=0)  # [kvH, hd]
            acc_ref[g] = acc_ref[g] * alpha + pv
            m_ref[g] = jnp.broadcast_to(m_new, m_ref.shape[1:])
            l_ref[g] = jnp.broadcast_to(l_new, l_ref.shape[1:])

    @pl.when(j == nj - 1)
    def _finish():
        l_safe = jnp.maximum(l_ref[:, :, :1], 1e-30)
        o_ref[0] = (acc_ref[:] / l_safe).astype(o_ref.dtype)


def tensor_degree(mesh, axis: str = "tensor") -> int:
    """Size of ``axis`` in ``mesh`` (1 when absent or mesh is None)."""
    if mesh is None:
        return 1
    return dict(zip(mesh.axis_names, mesh.devices.shape)).get(axis, 1)


def is_folded(pool) -> bool:
    """A layer's pages stored ``[NB, bs, kvH * hd]``: the MXU kernel's (a
    latent page, one row a token, is stored so too)."""
    return not isinstance(pool, dict) and pool.ndim == 3


def is_latent(k_pool, v_pool) -> bool:
    """A ``latent_attention`` layer's pair: pages of one row a token under
    ``k`` and an array of no elements under ``v`` (``kv_pool``)."""
    return (is_folded(k_pool) and not isinstance(v_pool, dict)
            and v_pool.size == 0)


def paged_attention(
    q: jax.Array,
    k_pool,
    v_pool,
    tables: jax.Array,
    ctx_lens: jax.Array,
    *,
    window: int | None = None,
    interpret: bool | None = None,
    mesh=None,
    axis: str = "tensor",
    work=None,
    scale: float | None = None,
    value_dim: int | None = None,
    diff: bool = False,
) -> jax.Array:
    """Fused paged decode attention over one layer of the KV pool.

    ``q``: [S, Hq, hd] (one decode token per slot); ``k_pool``/``v_pool``:
    folded [NB, bs, kvH * hd] (the MXU kernel,
    :func:`paged_attention_folded`), or [NB, bs, kvH, hd] or the ``{"q":
    int8, "scale": fp32}`` quantized leaf (the VPU kernel); ``tables``: [S, MB] int32 null-padded block tables; ``ctx_lens``:
    [S] int32, keys ``0..ctx`` inclusive are attendable (the engine's
    decode-step convention: this step's key was just written at ``ctx``).
    Returns [S, Hq, hd] in ``q.dtype``.  The dense gathered view is never
    materialized — block pages stream VMEM-ward via the table prefetch.

    With ``mesh``, kv heads are partitioned over its ``axis`` (the
    ``cache_partition_spec`` rule: only when the head count divides the
    degree): the kernel runs per-shard under ``shard_map``, each device
    holding its head slice of the pool and computing its query group's
    attention — one server's pool HBM and attention FLOPs span the
    axis.  Heads are kv-major (``q.reshape(S, kvH, G, hd)``), so an
    even head split keeps every GQA group intact on one shard and the
    result needs no cross-device combine (attention is head-parallel).
    Tables and context lengths stay replicated — any slot may reference
    any block, exactly like the unsharded pool.

    ``work`` is the MXU kernels' grid (``folded_work_list``), for a caller
    that builds it once for many layers; the VPU kernel has none.

    A pool of latent pages (``is_latent``) takes ``q`` [S, Hq, F] already in
    the latent space (``LatentAttention.absorb``), the ``scale`` of its
    scores and ``value_dim``, how many of a row's first numbers are the
    value; it returns [S, Hq, value_dim]
    (:func:`paged_attention_latent`).

    ``diff``: differential attention's first half on folded pages
    (``ops.attention.diff_heads``): a query head scores ITS key head of a
    pair of KV heads and reads both of the pair's value heads; returns
    [S, Hq, 2 hd] (:func:`paged_attention_folded`).
    """
    from ..inference.quant import kv_leaf_parts

    if interpret is None:
        interpret = _default_interpret()
    if is_latent(k_pool, v_pool):
        return paged_attention_latent(
            q, k_pool, tables, ctx_lens, scale=scale, value_dim=value_dim,
            interpret=interpret, work=work)
    if is_folded(k_pool):
        return paged_attention_folded(
            q, k_pool, v_pool, tables, ctx_lens, window=window,
            interpret=interpret, work=work, diff=diff)
    if diff:
        raise ValueError("differential attention reads folded pages alone "
                         "(no int8 and no sharded pool)")
    t = tensor_degree(mesh, axis)
    kvH_full = kv_leaf_parts(k_pool)[0].shape[2]
    if t > 1 and kvH_full % t == 0:
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        heads = P(None, axis, None)        # [S, Hq, hd] on the head axis
        pool = P(None, None, axis, None)   # [NB, bs, kvH, hd] (+scales)
        local = functools.partial(
            _paged_attention_local, window=window, interpret=interpret)
        return shard_map(
            local, mesh=mesh,
            in_specs=(heads, pool, pool, P(None, None), P(None)),
            out_specs=heads, check_vma=False,
        )(q, k_pool, v_pool, tables, ctx_lens)
    return _paged_attention_local(
        q, k_pool, v_pool, tables, ctx_lens,
        window=window, interpret=interpret)


def _paged_attention_local(
    q: jax.Array,
    k_pool,
    v_pool,
    tables: jax.Array,
    ctx_lens: jax.Array,
    *,
    window: int | None,
    interpret: bool,
) -> jax.Array:
    """One device's (or the whole unsharded) kernel invocation — under
    ``shard_map`` the head axes arrive pre-sliced and the block tables
    replicated, so the body is identical either way."""
    from ..inference.quant import kv_leaf_parts

    k_arr, k_scale = kv_leaf_parts(k_pool)
    v_arr, v_scale = kv_leaf_parts(v_pool)
    quantized = k_scale is not None
    S, Hq, hd = q.shape
    NB, bs, kvH, _ = k_arr.shape
    MB = tables.shape[1]
    if Hq % kvH:
        raise ValueError(f"{Hq} query heads not a multiple of "
                         f"{kvH} kv heads")
    G = Hq // kvH
    cfg = _Cfg(block_size=bs, group=G, window=window,
               quantized=quantized, interpret=interpret)
    # heads are kv-major in q ([S, kvH*G, hd]); the kernel wants one
    # [kvH, hd] tile per group member, so G leads (a tiny XLA transpose)
    qg = q.reshape(S, kvH, G, hd).swapaxes(1, 2)

    q_spec = pl.BlockSpec((1, G, kvH, hd), lambda s, j, t, c: (s, 0, 0, 0))
    # the table read: grid step (s, j) DMAs pool block table[s, j] — a
    # whole page, every kv head (the last two block dims equal the
    # array's, which is what the TPU lowering takes for any kvH and hd)
    kv_spec = pl.BlockSpec(
        (1, bs, kvH, hd), lambda s, j, t, c: (t[s, j], 0, 0, 0))
    scale_spec = pl.BlockSpec(
        (1, bs, kvH, 1), lambda s, j, t, c: (t[s, j], 0, 0, 0))
    if quantized:
        in_specs = [q_spec, kv_spec, scale_spec, kv_spec, scale_spec]
        operands = (qg, k_arr, k_scale, v_arr, v_scale)
    else:
        in_specs = [q_spec, kv_spec, kv_spec]
        operands = (qg, k_arr, v_arr)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(S, MB),
        in_specs=in_specs,
        out_specs=pl.BlockSpec(
            (1, G, kvH, hd), lambda s, j, t, c: (s, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G, kvH, hd), jnp.float32),
            pltpu.VMEM((G, kvH, _LANES), jnp.float32),
            pltpu.VMEM((G, kvH, _LANES), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_decode_kernel, cfg=cfg,
                          scale=1.0 / float(np.sqrt(hd))),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, G, kvH, hd), q.dtype),
        interpret=interpret,
        name="tadnn_paged_decode",
    )(tables.astype(jnp.int32), ctx_lens.astype(jnp.int32), *operands)
    return out.swapaxes(1, 2).reshape(S, Hq, hd)


# -- the folded kernel: grouped-query decode on the MXU --------------------------
#
# ``_decode_kernel`` multiplies one query row a (head, key) pair on the VPU:
# with 48 query heads on 8 KV heads of 128 that is about 800 vector
# operations a 16-key page, ten times what reading the page costs.  Folding
# the KV heads into the feature axis turns the same arithmetic into two plain
# matmuls: a page stored as ``[bs, kvH * hd]`` rows, and the queries as a
# block-diagonal ``[Hq, kvH * hd]`` matrix (query head (h, g) holds its
# ``hd`` numbers in the lanes of KV head h and zeros elsewhere), give
# ``scores[Hq, keys] = Q K^T`` and ``acc[Hq, kvH * hd] += P V``; head
# (h, g)'s output is the lanes of KV head h in its row of ``acc``.  The MXU
# does 8 times the needed products and is still far from busy.  What the
# kernel costs is its grid: about 46 ns a (slot, page) visited, relevant or
# not (my chip run, PR 27: 1.23 ms a layer over 16 x 832 pages whatever the
# contexts).  So the grid is a WORK LIST (``folded_work_list``): one step a
# (slot, first page) ITEM, a run of table entries that holds a key the slot
# may attend, in slot order and ascending inside a slot, its length a traced
# value.  A slot with nothing to attend keeps one item, at entry 0, so that
# every output row is written.  How many pages an item takes and where a
# slot's items start is ``item_pages``'s to say (PR 47), from the pages' own
# shape and the layer's window: below.
#
# An item's pages are FETCHED BY THE KERNEL (PR 44): each pool is one
# operand left in HBM, and at item w the body starts item w + 1's copies
# (one ``make_async_copy`` a page a pool, the page read from the table in
# scalar memory) into the other half of a ``[2, pages, bs, F]`` buffer,
# then waits ONCE a pool for item w's, started a step ago.  As ``pages``
# ``BlockSpec`` operands a pool the pipeline round the body cost 0.046 us
# an operand a step on the scalar core (index map, compare, conditional
# descriptor, wait) and the bytes hid under THAT: us an item on the chip,
# the kernel alone, one layer (my chip runs, PR 44; ``same``: every table
# entry one page, which the pipeline does not copy again; ``stub``: no
# matmuls; bit for bit the same output in every row):
#
#   shape (an item's bytes)           pipeline  same   stub  both | by hand  stub
#   trinity-large-ep8 48/8 (0.64 us)    1.426  1.426  1.166 1.165 |  0.964  0.827
#   gpt2-1p3b 16/16       (1.28 us)    1.599  1.502  1.550 1.144 |  1.531  1.538
#   olmo-hybrid-7b 30/30  (2.40 us)    2.650  1.745  2.647 1.158 |  2.647  2.642
#   latent 32 heads, 8 x 64 (0.80 us)  1.197  1.162  0.980 0.747 |  1.151  0.966
#
# Where the starts stand: at the top of the body; after the wait they cost
# 0.24 us an item more (the copies then begin behind the semaphore's wait
# and no longer under the whole item).  A wait a copy or one a buffer, the
# starts under a branch or unconditional (the last item fetching itself
# again), a buffer ``[2, pages * bs, F]`` or a page a leading index: all
# within 0.5% of each other.  What is left by hand is a step's own latency,
# about 0.68 us whatever it fetches (4 | 8 | 16 pages a step: 0.823 | 0.964
# | 1.489 us on trinity's shape, 0.794 | 1.151 | 1.830 on the latent one):
# twice the keys a step are 23% and 20% less a key there and nothing on the
# two shapes whose bytes bind.  So (PR 47) AN ITEM TAKES THE PAGES ITS BYTES
# ASK FOR (``item_pages``: about ``ITEM_BYTES`` over the pools, never under
# 8 folded pages or 512 latent keys): 16 a step on trinity's pages of 64 KB
# and on latent pages of 82 KB, 8 from gpt2-1p3b's 131 KB on.  In the
# serving programs, 30 s into trinity's window (16 slots full; my chip runs,
# PR 47): a sliding layer's call 334-336 -> 239.5-240.4 us (17 items of 16
# over the band where 33 groups of 8 ran), the full layer's 473.1 -> 355.7,
# the decode program 5.510 -> 5.033 ms.
#
# One thing the pipeline did in silence has to be said by hand: it did not
# copy a block index that repeats, and the table holds the null block
# wherever a slot has no key to read.  By hand every page is a copy, the
# null block like any other.  So an item whose entries are ALL the null
# block (an idle slot's) is skipped, fetch and arithmetic (gpt2-1p3b's
# steady cell, one or two of 8 slots running, read its token gaps 1-3%
# longer without: a layer's call with 1 of 8 slots running 52.1 (pipeline)
# | 52.5 | 50.2 us, and 0.7% dearer where no slot idles).  And (PR 47) A
# WINDOW LAYER'S ITEMS TILE ITS BAND: groups counted from table entry 0
# made a window of 512 keys on pages of 64 tokens (a band of 8-9 entries)
# two groups of 8, 16 copies of 328 KB for 9, and the kernel's time is its
# bytes there; now the band's ``lo .. hi`` is cut into as few items as hold
# it at the pages above, each as small as still does, from ``lo`` on: 2
# items of 5.  Only the last item's tail is dead.  us a layer's call on the
# chip, the kernel alone at that shape (64 slots, 40 heads on 20 of 64 at
# the differential wiring, contexts 600-30k; my chip runs, PR 47), by
# (pages an item, items a slot) and the copies it starts for 573 live pages:
#
#   groups from entry 0 (8, 2)  438.9 (1,000) | from the band's first page:
#   (5, 2) 282.9 (640)   (3, 3) 266.6 (576)   (4, 3) 332.9 (756)
#   (6, 2) 338.3 (768)   (9, 1) 255.5 (576)   (10, 1) 283.3 (640)
#
# 0.44 us a copy of 328 KB whatever the items (742 GB/s: the time IS the
# bytes; 320 keys a step, 2.5 tiles of lanes, cost nothing), and a step's
# own latency shows only between forms that copy alike ((9, 1) under (3,
# 3)).  In phi4's decode program a window layer's call reads 439.2-440.7
# -> 280.9-281.0 us, the program 26.141 -> 24.968 ms.  What is left: a
# running slot's single null pages behind its newest key (a branch and a
# wait a page: not taken), and an item that outgrows its bytes by a page to
# hold a band whole, ``PERF.md`` section 7.4(a).

FOLD_PAGES = 8  # the least pages an item of a full layer takes, folded
LATENT_KEYS = 512  # the least keys an item takes on latent pages
ITEM_BYTES = 2 ** 20  # what an item's pages weigh over the pools, about


class WorkList(NamedTuple):
    """The MXU kernels' grid: item ``w < n_items`` is the ``pages`` table
    entries of slot ``slot_of[w]`` from entry ``page0_of[w]`` on (``pages``:
    ``item_pages`` of the same pools and window); ``first``/``last`` [S] are
    a slot's first and last ITEM (where its sums start and are written)."""
    first: jax.Array
    last: jax.Array
    # [S * steps + 1], one more than a dense grid has steps: the kernel
    # reads item w + 1's indices while it runs item w, the last one too
    slot_of: jax.Array
    page0_of: jax.Array
    n_items: jax.Array   # [] int32
    # [] int32, the mechanism's two counters over the slots that run: the
    # page copies the items start (items x pages: an item is fetched whole)
    # and the table entries among them that hold a key a slot attends
    pages_copied: jax.Array
    pages_live: jax.Array

    @property
    def dense(self) -> int:
        """The steps of a dense ``slots x items`` grid: the list's bound."""
        return self.slot_of.shape[0] - 1


def kernel_pools(k_pool, v_pool) -> tuple:
    """The arrays an MXU kernel reads of a layer's pair: key pages and value
    pages, or the ONE pool of latent pages."""
    return (k_pool,) if is_latent(k_pool, v_pool) else (k_pool, v_pool)


def item_pages(pools, max_blocks: int,
               window: int | None = None) -> tuple[int, int]:
    """(the pages an item of the work list takes, the most items a slot has)
    for one layer's ``pools`` (``kernel_pools``: arrays or shapes ``[NB, bs,
    F]``) under tables of ``max_blocks`` entries: THE geometry, for the list
    and for both kernels, from what the shapes say and nothing else.

    A grid step costs about 0.68 us of its own whatever it fetches, so an
    item takes the pages its BYTES ask for: as many of the least item (8
    folded pages, 512 latent keys) as come nearest ``ITEM_BYTES`` over the
    pools.  A full layer's items are that many entries from entry 0 on.  A
    ``window`` layer's items tile its BAND, entries ``(ctx - window + 1) //
    bs .. ctx // bs`` and at most ``(window - 1) // bs + 2`` of them: as
    few items as hold the longest band at those pages, each as small as
    still does (a band of 9 pages under 8 a step: 2 items of 5)."""
    _, bs, F = pools[0].shape
    page = len(pools) * bs * F * jnp.dtype(pools[0].dtype).itemsize
    least = FOLD_PAGES if len(pools) == 2 else max(1, LATENT_KEYS // bs)
    pages = min(least * max(1, int(ITEM_BYTES / (least * page) + 0.5)),
                max_blocks)
    if window is None:
        return pages, -(-max_blocks // pages)
    band = min((window - 1) // bs + 2, max_blocks)
    items = -(-band // pages)
    return -(-band // items), items


def folded_work_list(ctx_lens: jax.Array, active: jax.Array | None = None, *,
                     pools, max_blocks: int,
                     window: int | None = None) -> WorkList:
    """The items of one decode step for the layers of one ``window`` whose
    pages are ``pools``' (``kernel_pools``; ``item_pages`` sizes an item): a
    slot's table entries ``lo .. ctx // bs`` cut into items of ``pages``
    from ``lo`` on, which is 0 on a full layer and the band's first entry
    with a window; one item, from entry 0, for a slot that is not
    ``active`` (its table holds the null block alone: skipped).  In slot
    order, ascending inside a slot.  A few vector operations on the device:
    built once a step a kind of layer, whatever the number of layers."""
    bs = pools[0].shape[1]
    pages, steps = item_pages(pools, max_blocks, window)
    ctx = jnp.maximum(ctx_lens.astype(jnp.int32), 0)
    hi = jnp.minimum(ctx // bs, max_blocks - 1)
    lo = (jnp.zeros_like(hi) if window is None
          else jnp.minimum(jnp.maximum(ctx - window + 1, 0) // bs, hi))
    runs = jnp.ones_like(hi, bool) if active is None else active
    lo, hi = jnp.where(runs, lo, 0), jnp.where(runs, hi, 0)
    count = (hi - lo) // pages + 1       # a slot's items
    ends = jnp.cumsum(count)             # they end before ends[s]
    first = ends - count
    w = jnp.arange(ctx.shape[0] * steps + 1, dtype=jnp.int32)
    # past n_items the list repeats the last slot's last item: never run
    slot_of = jnp.minimum(jnp.sum(w[:, None] >= ends[None, :], axis=1),
                          ctx.shape[0] - 1).astype(jnp.int32)
    nth = jnp.clip(w - first[slot_of], 0, count[slot_of] - 1)
    return WorkList(
        first, ends - 1, slot_of, lo[slot_of] + nth * pages, ends[-1],
        pages * jnp.sum(jnp.where(runs, count, 0)),
        jnp.sum(jnp.where(runs, hi - lo + 1, 0)))


def _folded_kernel(tables_ref, ctx_ref, first_ref, last_ref, slot_ref,
                   page0_ref, q_ref, *refs, pages: int, bs: int,
                   window: int | None, scale: float,
                   value_dim: int | None = None):
    """One item of the work list: ``pages`` table entries of its slot from
    its first page on.  ``refs``: the pools as they lie in HBM
    (key pages and value pages; ``value_dim``: ONE pool of latent pages,
    whose row is the key and whose first ``value_dim`` numbers are the
    value), the output block, the float32 sums, then a ``[2, pages, bs, F]``
    buffer a pool and the DMA semaphores ``[pools, 2]``.  The kernel fetches
    its pages itself: item w + 1's copies are started before item w's are
    waited for, so they run under item w's matmuls; every copy started is
    waited for in the same call, and none is started past the list.  An
    item whose table entries are all the null block is skipped whole (an
    idle slot's: its row is written as zeros)."""
    n_pools = 1 if value_dim else 2
    pools = refs[:n_pools]
    o_ref, acc_ref, m_ref, l_ref = refs[n_pools:n_pools + 4]
    bufs, sem = refs[n_pools + 4:-1], refs[-1]
    w, n_items = pl.program_id(0), pl.num_programs(0)
    s = slot_ref[w]

    def page_ids(item):
        si, page0 = slot_ref[item], page0_ref[item]
        return [tables_ref[si, page0 + i] for i in range(pages)]

    def holds_a_page(ids):
        # the table has the null block (0) wherever a slot has no key to
        # read: an idle slot's one item is all of it, and is neither
        # fetched nor multiplied (through ``BlockSpec``s a block index that
        # repeats was not copied again; by hand it would be).  Any other
        # item holds a key its slot attends, or is masked whole and leaves
        # the sums zero
        return functools.reduce(jnp.bitwise_or, ids) != 0

    def fetch(item, b):
        ids = page_ids(item)

        @pl.when(holds_a_page(ids))
        def _start():
            for i, page in enumerate(ids):
                for p in range(n_pools):
                    pltpu.make_async_copy(pools[p].at[page], bufs[p].at[b, i],
                                          sem.at[p, b]).start()

    pl.when(w == 0)(lambda: fetch(0, 0))
    # (the list's arrays hold one entry more than the list: PR 30's fault)
    pl.when(w + 1 < n_items)(lambda: fetch(w + 1, (w + 1) % 2))

    @pl.when(w == first_ref[s])
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, _NEG_BIG)
        l_ref[:] = jnp.zeros_like(l_ref)

    @pl.when(holds_a_page(page_ids(w)))
    def _attend():
        ctx = ctx_ref[s]
        start = page0_ref[w] * bs
        b = w % 2
        for p in range(n_pools):  # ONE wait a buffer: its pages' bytes, summed
            pltpu.make_async_copy(bufs[p].at[b], bufs[p].at[b],
                                  sem.at[p, b]).wait()

        k = bufs[0][b].reshape(pages * bs, -1)  # [keys, F]
        v = (k[:, :value_dim] if value_dim
             else bufs[1][b].reshape(pages * bs, -1))
        q = q_ref[0]  # [Hq, F], block-diagonal (a latent query fills its row)
        exact = None
        if q.dtype == jnp.float32:  # float32 queries ask for float32 math:
            # operands AND products (a float32 matmul is one bfloat16 pass by
            # default, 1e-3 of the result: my chip run, PR 30)
            k, v = k.astype(jnp.float32), v.astype(jnp.float32)
            exact = jax.lax.Precision.HIGHEST
        sc = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), precision=exact,
            preferred_element_type=jnp.float32) * scale  # [Hq, keys]
        pos = start + jax.lax.broadcasted_iota(jnp.int32, sc.shape, 1)
        valid = pos <= ctx
        if window is not None:
            valid = jnp.logical_and(valid, pos > ctx - window)
        sc = jnp.where(valid, sc, _NEG_BIG)
        m_prev, l_prev = m_ref[:, :1], l_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(sc, axis=-1, keepdims=True))
        m_new = jnp.maximum(m_new, _NEG_BIG / 2)
        p = jnp.exp(sc - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[:] = acc_ref[:] * alpha + jnp.dot(
            p.astype(v.dtype), v, precision=exact,
            preferred_element_type=jnp.float32)
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(w == last_ref[s])
    def _finish():
        o_ref[0] = (acc_ref[:] / jnp.maximum(l_ref[:, :1], 1e-30)
                    ).astype(o_ref.dtype)


def _fetching_call(q, pools, tables, ctx_lens, work: WorkList, *, pages: int,
                   out_width: int, interpret, window: int | None, **kernel):
    """``_folded_kernel`` over ``work``: ``q`` [S, Hq, F] and the output
    [S, Hq, out_width] are blocks that follow an item's slot; each of
    ``pools`` [NB, bs, F] is ONE operand left where it lies; ``tables`` [S,
    MB] is padded with the null block as far as an item reads: to whole
    items on a full layer (nothing where ``pages`` divides MB: the operand
    keeps the shape ``benchmark/metrics/paged_attn_roofline.py`` tells the
    kernel by), by one item's ``pages`` under a window, whose items start
    wherever a band does."""
    S, Hq, F = q.shape
    bs, MB = pools[0].shape[1], tables.shape[1]
    tables = jnp.pad(tables.astype(jnp.int32), ((0, 0), (
        0, -MB % pages if window is None else pages)))

    def rows(width):
        return pl.BlockSpec((1, Hq, width), lambda w, t, c, f, l, so, po: (
            so[w], 0, 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,
        grid=(work.n_items,),
        in_specs=[rows(F)] + [pl.BlockSpec(memory_space=pl.ANY)] * len(pools),
        out_specs=rows(out_width),
        scratch_shapes=[
            pltpu.VMEM((Hq, out_width), jnp.float32),
            pltpu.VMEM((Hq, _LANES), jnp.float32),
            pltpu.VMEM((Hq, _LANES), jnp.float32),
            *[pltpu.VMEM((2, pages, bs, F), pool.dtype) for pool in pools],
            pltpu.SemaphoreType.DMA((len(pools), 2)),
        ],
    )
    return pl.pallas_call(
        functools.partial(_folded_kernel, pages=pages, bs=bs, window=window,
                          **kernel),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, Hq, out_width), q.dtype),
        # item w + 1's pages land while item w runs: the items in order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name=("tadnn_paged_decode_latent" if kernel.get("value_dim")
              else "tadnn_paged_decode_folded"),
    )(tables, ctx_lens, *work[:4], q, *pools)


def paged_attention_folded(
    q: jax.Array,
    k_pool: jax.Array,
    v_pool: jax.Array,
    tables: jax.Array,
    ctx_lens: jax.Array,
    *,
    window: int | None = None,
    interpret: bool | None = None,
    work: WorkList | None = None,
    diff: bool = False,
) -> jax.Array:
    """What ``paged_attention`` runs over one layer of a pool stored folded,
    ``[NB, bs, kvH * hd]`` (see above): ``q`` [S, Hq, hd] with kv-major
    heads, ``tables`` [S, MB], keys ``0..ctx`` attendable, a band of
    ``window`` where given.  ``work`` is ``folded_work_list`` of the same
    contexts and window (a caller with many layers builds it once); every
    slot counts as running where it is built here.  Returns [S, Hq, hd] in
    ``q.dtype``.

    ``diff`` (differential attention, ``ops.attention.diff_heads``) is the
    SAME kernel call at another wiring of the lanes, and reads every page
    once, as grouped queries do: a query head's row lies in the lanes of
    the key head it scores (head ``2j + i`` on key head ``2g + i``), and
    what is taken of the kernel's ``[Hq, kvH * hd]`` product is the 2 hd
    lanes of the group's TWO value heads, which lie side by side in a
    folded page.  Returns [S, Hq, 2 hd]: scores over hd against values of
    2 hd that a pair of key heads share."""
    if interpret is None:
        interpret = _default_interpret()
    S, Hq, hd = q.shape
    NB, bs, F = k_pool.shape
    kvH = F // hd
    if Hq % kvH:
        raise ValueError(f"{Hq} query heads not a multiple of "
                         f"{kvH} kv heads")
    G = Hq // kvH
    MB = tables.shape[1]
    pools = (k_pool, v_pool)
    pages = item_pages(pools, MB, window)[0]
    ctx_lens = ctx_lens.astype(jnp.int32)
    if work is None:
        work = folded_work_list(ctx_lens, pools=pools, max_blocks=MB,
                                window=window)
    # query head (h, g) in the lanes of KV head h, zeros elsewhere; its
    # output in the lanes of the value heads it reads (one, or a pair)
    key_of, wide = np.arange(Hq) // G, 1
    if diff:
        from .attention import diff_heads

        key_of, wide = diff_heads(Hq, kvH)[0], 2
    own = jnp.asarray(key_of[:, None] == np.arange(kvH)[None, :], q.dtype)
    mine = own if not diff else jnp.asarray(
        key_of[:, None] // 2 == np.arange(kvH // 2)[None, :], q.dtype)
    qf = jnp.einsum("shd,hk->shkd", q, own).reshape(S, Hq, F)
    out = _fetching_call(
        qf, pools, tables, ctx_lens, work, pages=pages,
        out_width=F, interpret=interpret, window=window,
        scale=1.0 / float(np.sqrt(hd)))
    # float32 here too (the default rounds this sum over the 0/1 ``own``)
    return jnp.einsum("shkd,hk->shd",
                      out.reshape(S, Hq, kvH // wide, wide * hd), mine,
                      precision="highest" if q.dtype == jnp.float32 else None)


# -- the latent kernel: every head over ONE row a key -----------------------------
#
# Absorbed latent attention (``models/transformer_core.LatentAttention``) is
# multi-query attention with one twist: all ``Hq`` heads read ONE row a key
# (``F = kv_rank + rope`` numbers: 576), and the row's first ``value_dim``
# numbers (512) are the value as well.  So a page is read once, the queries
# need no block-diagonal form, and the arithmetic a byte is ten times the
# grouped-query kernel's: ``Hq (F + value_dim) 2`` operations over ``2 F``
# bytes, 60 FLOP/B at 32 heads, still under the v5e's 240.  What it costs
# is again its grid: a step costs about 0.7 us of its own whatever it
# fetches (the table above), and 128 latent keys are 164 KB, 0.20 us of
# HBM time.  So a step takes ``LATENT_KEYS`` keys AT LEAST (655 KB in rows
# of 640 lanes, 0.80 us), in as few page copies as the pool's block size
# allows: 1.15 us an item by hand at 24 slots x 32 heads x 5.5k keys (1.20
# through the pipeline; 1.19 | 1.25 at 64 heads), of it the matmuls' 0.19
# that the copies do not cover (my chip runs, PR 44); and since PR 47 the
# pages its bytes ask for (``item_pages``): 16 pages of 64 rows, 1,024
# keys and 1.3 MB a step, 1.83 us an item and 15% less a key at 5.5k keys
# (310.7 -> 263.6 us a layer's call; my chip runs, PR 44).  The chunk
# kernel below keeps a key block of its own (``LATENT_CHUNK_KEYS``).


def paged_attention_latent(
    q: jax.Array,
    pool: jax.Array,
    tables: jax.Array,
    ctx_lens: jax.Array,
    *,
    scale: float,
    value_dim: int,
    interpret: bool | None = None,
    work: WorkList | None = None,
) -> jax.Array:
    """What ``paged_attention`` runs over one layer of latent pages ``pool``
    [NB, bs, F]: ``q`` [S, Hq, <= F] in the latent space (a stored row ends
    in zeros where it is wider: ``kv_pool.stored_row``), ``tables`` [S, MB],
    keys ``0..ctx`` attendable, scores ``q . row * scale``, values a row's
    first ``value_dim`` numbers.  ``work`` is ``folded_work_list`` of the
    same contexts and ``pools=(pool,)``.  Returns [S, Hq,
    value_dim] in ``q.dtype``: the probabilities over the cached latents,
    which ``LatentAttention.lift`` takes to the heads' outputs."""
    if interpret is None:
        interpret = _default_interpret()
    F = pool.shape[2]
    q = jnp.pad(q, ((0, 0), (0, 0), (0, F - q.shape[2])))
    MB = tables.shape[1]
    pages = item_pages((pool,), MB)[0]
    ctx_lens = ctx_lens.astype(jnp.int32)
    if work is None:
        work = folded_work_list(ctx_lens, pools=(pool,), max_blocks=MB)
    return _fetching_call(
        q, (pool,), tables, ctx_lens, work, pages=pages, out_width=value_dim,
        interpret=interpret, window=None, scale=float(scale),
        value_dim=value_dim)


# -- the chunk kernel: a prompt chunk's queries over the slot's latent pages ------
#
# A chunk's C queries attend the EXPANDED form (at 512 queries x 32 heads the
# absorbed form is twice the work), ``LATENT_CHUNK_KEYS`` keys at a time.
# In ``jax.numpy`` (``programs._over_key_blocks``) a key block is six fusions:
# the expansion, two score products, and three passes of vector work over
# the block's ``[32, 512, 512]`` float32 scores (mask and row maximum; ``exp``
# and row sum; ``exp`` AGAIN inside the values' product).  The 32 MiB of
# scores never reach HBM (the compiler keeps them in the chip's 128 MiB of
# VMEM between the fusions), but the matmuls WAIT for the vector passes: 76
# us a key block where the matmuls alone take 49 (my chip runs, PR 38:
# PERF.md section 6).  ``tadnn_latent_chunk`` is the same arithmetic as ONE
# program, so that a head's softmax runs beside the next head's matmuls: its
# grid is (groups of ``LATENT_CHUNK_HEADS`` heads) x (the key blocks up to the
# chunk's last: a traced number), a group's slices of ``kv_b_proj`` stay in
# VMEM over its key blocks, a block's latents come through the table as the
# decode kernel's do and are expanded there a head at a time, and scores,
# running maximum, sum and accumulator are float32 in VMEM.  Scores are held
# TRANSPOSED, ``[keys, C]``: the softmax's maximum and sum over the keys are
# then plain vector operations down the sublanes (across lanes they took
# twice the vector time), and a row's statistics are one lane-dense ``[1,
# C]``.  62 us a key block, 2.04 ms where the plain form takes 2.53 at 16k
# keys; the matmuls alone, in whole passes of the MXU, take 59.  The same
# operations in the same precision as the plain form, which stays the oracle
# and the path of every shape the kernel does not tile.

LATENT_CHUNK_KEYS = 512  # keys a key block of the chunk kernel holds (its
# own number: the decode list's items follow their bytes, ``item_pages``)
LATENT_CHUNK_HEADS = 8  # heads a grid step of the chunk kernel takes (16
# were slower by 1.7x: the unrolled program outgrows the instruction memory)
_LATENT_CHUNK_VMEM = 64 * 2**20  # of a v5e's 128 MiB; the default 16 hold no step


def latent_chunk_tiles(chunk: int, block_size: int, heads: int, rank: int,
                       nope: int, dtype) -> bool:
    """Whether ``latent_chunk_attention`` takes a chunk of these shapes
    here: compiled (not on the CPU), whole tiles of queries, pages that
    make up a key block, whole groups of heads, a latent and a head's
    unrotated part in whole tiles of lanes, in bfloat16 (the kernel
    multiplies float32 too, which the interpreter's tests use; compiled, its
    HIGHEST products unroll to 15 MB of program: the plain form's)."""
    return (not _default_interpret() and chunk % _LANES == 0
            and LATENT_CHUNK_KEYS % block_size == 0
            and heads % LATENT_CHUNK_HEADS == 0
            and rank % _LANES == 0 and nope % _LANES == 0
            and jnp.dtype(dtype) == jnp.bfloat16)


def latent_chunk_pages(max_blocks: int, block_size: int) -> int:
    """Pages a key block of the chunk kernel takes."""
    return max(1, min(LATENT_CHUNK_KEYS // block_size, max_blocks))


def latent_chunk_key_blocks(pos0, chunk: int, max_blocks: int,
                            block_size: int):
    """The key blocks a chunk of ``chunk`` rows at ``pos0`` attends, the
    first to the one it wrote: the kernel's grid steps a group of heads
    (``pos0`` an int for the engine's counter, traced for the grid)."""
    keys = latent_chunk_pages(max_blocks, block_size) * block_size
    return (pos0 + chunk - 1) // keys + 1


def _expand_latent(lat, wk, wvt, rank: int, exact):
    """What a block's keys and values are on a latent layer, for one head:
    ``lat`` [keys, F] rows as stored, ``wk`` [rank, nope] and ``wvt`` [dv,
    rank] the head's slices of ``kv_b_proj``.  Keys ``[k_nope | the rotated
    part and the row's zeros]`` [keys, nope + F - rank] and values
    TRANSPOSED [dv, keys], both rounded as ``LatentAttention.expand`` rounds
    them."""
    c = lat[:, :rank]
    k = jnp.dot(c, wk, precision=exact,
                preferred_element_type=jnp.float32).astype(lat.dtype)
    vt = jax.lax.dot_general(wvt, c, (((1,), (1,)), ((), ())),
                             precision=exact,
                             preferred_element_type=jnp.float32)
    return jnp.concatenate([k, lat[:, rank:]], axis=1), vt.astype(lat.dtype)


def _latent_chunk_kernel(table_ref, pos_ref, qt_ref, wk_ref, wvt_ref, *refs,
                         pages: int, rank: int, scale: float):
    """One (group of heads, key block) step: ``qt_ref`` [heads, nope + F -
    rank, C] (a head's unrotated and rotated parts, zeros behind them,
    queries in the lanes), ``wk_ref`` [rank, heads * nope], ``wvt_ref``
    [heads, dv, rank], then the block's ``pages`` pages, the output [heads,
    dv, C] and the float32 scratch: the accumulator [heads, dv, C], the
    running maximum and sum [heads, 8, C] (a row each, sublane-broadcast)."""
    del table_ref
    page_refs = refs[:pages]
    o_ref, acc_ref, m_ref, l_ref = refs[pages:]
    j, nj = pl.program_id(1), pl.num_programs(1)
    heads, dt = qt_ref.shape[0], qt_ref.dtype
    nope = wk_ref.shape[1] // heads
    exact = jax.lax.Precision.HIGHEST if dt == jnp.float32 else None

    @pl.when(j == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, _NEG_BIG)
        l_ref[:] = jnp.zeros_like(l_ref)

    def block(masked: bool):
        lat = jnp.concatenate([r[0] for r in page_refs], axis=0).astype(dt)
        keys = lat.shape[0]
        for h in range(heads):  # static: a head's softmax runs beside the
            # next head's matmuls
            kx, vt = _expand_latent(
                lat, wk_ref[:, h * nope:(h + 1) * nope], wvt_ref[h], rank,
                exact)
            st = jnp.dot(kx, qt_ref[h], precision=exact,
                         preferred_element_type=jnp.float32) * scale
            if masked:  # [keys, C]: key k of the block against query c
                k_pos = j * keys + jax.lax.broadcasted_iota(
                    jnp.int32, st.shape, 0)
                q_pos = pos_ref[0] + jax.lax.broadcasted_iota(
                    jnp.int32, st.shape, 1)
                st = jnp.where(k_pos <= q_pos, st, _NEG_BIG)
            m_prev, l_prev = m_ref[h][:1], l_ref[h][:1]  # [1, C]
            # (block 0 comes first and every query sees key 0: a masked
            # score's exp underflows to 0 against a real maximum)
            m_new = jnp.maximum(m_prev, jnp.max(st, axis=0, keepdims=True))
            pt = jnp.exp(st - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_new = l_prev * alpha + jnp.sum(pt, axis=0, keepdims=True)
            acc_ref[h] = acc_ref[h] * alpha + jnp.dot(
                vt, pt.astype(dt), precision=exact,
                preferred_element_type=jnp.float32)
            m_ref[h] = jnp.broadcast_to(m_new, m_ref.shape[1:])
            l_ref[h] = jnp.broadcast_to(l_new, l_ref.shape[1:])

    # only a block that holds a key after the chunk's first query needs the
    # causal compare; the blocks before it are visible to every query
    reaches = (j + 1) * pages * page_refs[0].shape[1] - 1 > pos_ref[0]
    pl.when(reaches)(lambda: block(True))
    pl.when(jnp.logical_not(reaches))(lambda: block(False))

    @pl.when(j == nj - 1)
    def _finish():
        o_ref[:] = (acc_ref[:] / l_ref[:, :1]).astype(o_ref.dtype)


def latent_chunk_attention(q_nope, q_rope, pool, table_row, pos0, w_uk, w_uv,
                           *, scale: float,
                           interpret: bool | None = None) -> jax.Array:
    """A prefill chunk's attention on a ``latent_attention`` layer, the
    chunk's own rows already written: ``q_nope`` [C, H, nope], ``q_rope``
    [C, H, rope] at positions ``pos0 .. pos0 + C`` over ONE slot's latent
    pages ``pool`` [NB, bs, F] through its ``table_row`` [MB], each key
    block expanded on the chip by ``w_uk`` [rank, H, nope], ``w_uv`` [rank,
    H, dv] (``LatentAttention.up``).  Query ``c`` attends keys ``0 .. pos0 +
    c``; scores ``(q_nope . k_nope + q_rope . k_r) * scale``.  Returns [C,
    H, dv] in the queries' dtype."""
    if interpret is None:
        interpret = _default_interpret()
    C, H, nope = q_nope.shape
    NB, bs, F = pool.shape
    rank, dv = w_uk.shape[0], w_uv.shape[2]
    heads = min(LATENT_CHUNK_HEADS, H)
    MB = table_row.shape[0]
    pages = latent_chunk_pages(MB, bs)
    # whole key blocks up to the last a chunk may reach (the null block past
    # the row's end: those keys lie after every query)
    n_kb = -(-(MB * bs + C) // (pages * bs))
    table_row = jnp.pad(table_row.astype(jnp.int32),
                        (0, n_kb * pages - MB))
    pos0 = jnp.asarray(pos0, jnp.int32).reshape(1)
    # a head's query one COLUMN of [nope | rope | zeros] against a key's row
    # [k_nope | the stored row past the latent]
    dt = q_nope.dtype
    q = jnp.concatenate([q_nope, q_rope.astype(dt)], axis=-1)
    q = jnp.pad(q, ((0, 0), (0, 0), (0, F - rank - q_rope.shape[2])))
    qt = q.transpose(1, 2, 0)  # [H, nope + F - rank, C]

    def group(a, b):
        return pl.BlockSpec((heads, a, b), lambda g, j, t, p: (g, 0, 0))

    def page(i):
        return pl.BlockSpec((1, bs, F), lambda g, j, t, p: (
            t[j * pages + i], 0, 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(H // heads, latent_chunk_key_blocks(pos0[0], C, MB, bs)),
        in_specs=[group(*qt.shape[1:]),
                  pl.BlockSpec((rank, heads * nope),
                               lambda g, j, t, p: (0, g)),
                  group(dv, rank)] + [page(i) for i in range(pages)],
        out_specs=group(dv, C),
        scratch_shapes=[
            pltpu.VMEM((heads, dv, C), jnp.float32),
            pltpu.VMEM((heads, 8, C), jnp.float32),
            pltpu.VMEM((heads, 8, C), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_latent_chunk_kernel, pages=pages, rank=rank,
                          scale=float(scale)),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((H, dv, C), dt),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_LATENT_CHUNK_VMEM),
        interpret=interpret,
        name="tadnn_latent_chunk",
    )(table_row, pos0, qt, w_uk.astype(dt).reshape(rank, H * nope),
      w_uv.astype(dt).transpose(1, 2, 0), *([pool] * pages))
    return out.transpose(2, 0, 1)


def latent_attention_reference(q, rows, ctx_lens, *, scale: float,
                               value_dim: int):
    """Pure-JAX oracle of the latent kernel, and the engine's
    ``attention_impl="dense"`` path: ``q`` [S, T, Hq, F] over each slot's
    dense ``rows`` [S, L, >= F], query t of slot s attending keys ``0 ..
    ctx_lens[s] + t``.  Returns [S, T, Hq, value_dim] in ``q.dtype``."""
    rows = rows[..., :q.shape[-1]].astype(q.dtype)
    exact = "highest" if q.dtype == jnp.float32 else None
    sc = jnp.einsum("sthf,slf->shtl", q, rows, precision=exact,
                    preferred_element_type=jnp.float32) * scale
    last = ctx_lens[:, None] + jnp.arange(q.shape[1])[None, :]  # [S, T]
    ok = jnp.arange(rows.shape[1])[None, None, :] <= last[:, :, None]
    p = jax.nn.softmax(jnp.where(ok[:, None], sc, _NEG_BIG), axis=-1)
    return jnp.einsum("shtl,slv->sthv", p.astype(rows.dtype),
                      rows[..., :value_dim], precision=exact,
                      preferred_element_type=jnp.float32).astype(q.dtype)


def paged_attention_reference(
    q: jax.Array,
    k_pool,
    v_pool,
    tables: jax.Array,
    ctx_lens: jax.Array,
    *,
    window: int | None = None,
    dtype=None,
) -> jax.Array:
    """Pure-JAX oracle: the dense decode path, verbatim.

    Gathers the block table into the dense view with
    ``kv_pool.gather_blocks`` (the engine's ``attention_impl="dense"``
    reference path) and runs ``xla_attention`` under the same
    ctx/window mask the engine builds — so kernel-vs-reference parity
    IS paged-vs-dense parity.
    """
    from ..inference.serve.kv_pool import gather_blocks
    from .attention import xla_attention

    if dtype is None:
        dtype = q.dtype
    kv_heads = k_pool.shape[2] // q.shape[2] if is_folded(k_pool) else None
    kd = gather_blocks(k_pool, tables, dtype, kv_heads)
    vd = gather_blocks(v_pool, tables, dtype, kv_heads)
    key_idx = jnp.arange(kd.shape[1])[None, :]
    mask = key_idx <= ctx_lens[:, None]
    if window is not None:
        mask &= key_idx > ctx_lens[:, None] - window
    o = xla_attention(q[:, None], kd, vd, causal=False,
                      mask=mask[:, None, None, :])
    return o[:, 0]
