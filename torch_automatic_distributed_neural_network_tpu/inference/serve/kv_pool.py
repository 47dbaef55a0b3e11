"""Paged KV cache: block-granular storage with per-request block tables.

``decode.KVCache`` reserves a contiguous [B, S_max] strip per row, so a
batch of mixed-length requests pays worst-case memory for every slot.
Serving flips that: the pool owns one block-granular store A LAYER,

    k, v: a list of n_layers arrays, each [num_blocks, block_size, kvH * hd]

and each live request holds an ordered list of block ids (its *block
table*).  Token ``p`` of a request lives at ``(table[p // bs], p % bs)``
— the classic paged layout.  Memory is O(tokens actually cached), blocks
return to the free list the step a request finishes, and a new prefill
can reuse them immediately (iteration-level batching never drains).
One array a layer, not one stacked over the layers: the serving programs
walk the layers one by one and update each layer's pages in place (the
arrays are donated), where a stack threaded through a layer scan was
copied whole every step (60% of the GPT-2 1.3B decode program; my chip
run, PR 26).

A page is stored FOLDED, its KV heads side by side in one row a token
(``[bs, kvH * hd]``): the layout the MXU decode kernel
(``ops/paged_attention.py``) multiplies.  The two pools that kernel does
not serve keep a page as ``[bs, kvH, hd]``, which is what the VPU kernel
takes: int8 pools (``{"q", "scale"}`` leaves, below) and pools sharded
over a mesh (kv heads on the tensor axis).  Everything here takes either
(a written row is reshaped to the page's trailing shape).

Block 0 is the **null block**: never allocated, never read through an
active mask.  Inactive decode slots keep a table of zeros, so the fully
vectorized slot-padded decode step can scatter their (garbage) token
writes somewhere harmless without per-slot branching.

int8 mode (``quantize=True``) stores ``{"q": int8, "scale": fp32}``
per side via :func:`..quant.quantize_kv` — per-token-per-head scales,
written at the same (block, offset) the token lands in, so a block's
tokens quantize independently and freeing/reusing a block needs no
scale bookkeeping.  ~2x KV capacity per byte of HBM; the numerics bound
is pinned in tests/test_quant.py.

Reads inside the jitted decode step go through the paged kernel, or
through :func:`gather_blocks` (table-indexed gather to a dense
[S, max_len, kvH, hd] view feeding the stock ``xla_attention``): the
engine's ``attention_impl="dense"`` path and the oracle of every kernel
parity test.

Seven kinds of layer (``cfg.layer_types``), one pair of arrays each (the
last three kinds are described at the end of this paragraph).  A
full-attention layer, and every layer of a model with one kind of layer,
has the pages above: the allocator's ids, a request's table, room for
``max_len`` a slot.  A ``latent_attention`` layer has the same pages, ids
and table, but a page holds ONE row a token, ``[bs, kv_rank + rope]``: the
key-value latent with the rotated key part behind it, which is key and
value at once (``cfg.page_row``), stored in whole tiles of 128 lanes
(``stored_row``: 576 numbers in 640, the rest zeros).  It lies under
``"k"``; the layer's ``"v"`` is an array of no elements.  One array and not
the two parts apart: 512 and 64 apart are 512 + 128 lanes, the same bytes
for twice the page copies.  And whole tiles spelled out, because the chip's
own layout for an array whose rows are 576 wide puts another axis in the
lanes (the compiler then copies the pool before every kernel call; my
compile for a described v5e, PR 34).  A ``sliding_attention`` layer needs only the last
``window`` keys, so it keeps a RING of ``window_pages`` pages a slot, owned
by the slot for the engine's life: position ``p`` lives in ring page ``(p
// bs) % window_pages``, and a page is written over once the window has
passed it.  The ring is ``ceil((window + prefill_chunk) / bs) + 1`` pages,
enough for a prefill chunk to land while every key its first row may see is
still there.  The kernels see a ring through an ordinary table,
``ring_table``: logical block ``j`` at the ring page that holds it, null
outside the live band.  A ``linear_attention`` layer has no keys and values
at all: its pair is the recurrent STATE ``[n_slots + 1, H, d_k, d_v]`` in
float32 (under ``"k"``) and the short convolution's TAIL ``[n_slots + 1, K -
1, 2 H d_k + H d_v]`` in the compute dtype (under ``"v"``), a row a slot,
owned by the slot like a ring; row 0 is the null row that inactive slots
write to, as ``NULL_BLOCK`` is for pages.  A slot's row is not cleared
between requests by the host: the chunk program starts a prompt at position
0 from zeros.  A ``state_space`` layer has the same two rows a slot at other
shapes: the selective scan's state ``[n_slots + 1, N, d_in]`` in float32
(channels in the lanes: the published ``[d_in, N]`` transposed, or a row of
16 numbers would be stored in a tile of 128) and the convolution's tail
``[n_slots + 1, K - 1, d_in]``.  A ``gated_memory`` and a
``shared_attention`` layer keep NOTHING: both of their entries are arrays of
no elements, and a ``shared_attention`` layer reads the pages of the
``full_attention`` layer before it (``cfg.source_layer``), which are counted
once, wherever bytes are counted.  The scheduler's block accounting is the
full layers' alone.

Sharding: a leaf's spec is ``cache_partition_spec`` less its layer and
batch axes (blocks are a global resource, any slot may use any block) —
kv heads split over the tensor axis exactly like the dense decode cache.
"""

from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp

from ...models.transformer_core import SSM_CONV_TAPS, TransformerConfig
from ..decode import cache_partition_spec
from ..quant import is_quantized_leaf, kv_leaf_parts, quantize_kv

NULL_BLOCK = 0  # reserved scratch target for inactive-slot writes
LANES = 128  # a latent row is stored in whole tiles of this many numbers


def stored_row(cfg: TransformerConfig, kind: str | None) -> tuple[int, ...]:
    """``cfg.page_row(kind)`` as a page stores it: a latent row in whole
    tiles of ``LANES``."""
    row = cfg.page_row(kind)
    if kind == "latent_attention":
        return tuple(-(-n // LANES) * LANES for n in row)
    return row


def _page_rows(new: jax.Array, payload: jax.Array) -> jax.Array:
    """``new`` [n, ...] in the trailing shape of a page of ``payload``; a
    row narrower than a folded page's (a latent row) ends in zeros."""
    if payload.ndim == 3:
        new = new.reshape(new.shape[0], -1)
        new = jnp.pad(new, ((0, 0), (0, payload.shape[2] - new.shape[1])))
    return new.reshape(-1, *payload.shape[2:]).astype(payload.dtype)


def blocks_for_tokens(n_tokens: int, block_size: int) -> int:
    """Blocks needed to hold ``n_tokens`` cache entries."""
    return max(1, math.ceil(n_tokens / block_size))


class BlockAllocator:
    """Ref-counted free-list allocator over ``num_blocks`` block ids.

    Block 0 (:data:`NULL_BLOCK`) is reserved and never handed out.
    ``acquire`` is all-or-nothing (returns None rather than a partial
    grant — admission control wants a clean fit check) and hands out
    blocks at refcount 1; ``ref`` adds a reference so a block can back
    several owners at once (cross-request prefix sharing: many block
    tables plus the radix index may all point at one block);
    ``release`` decrements and returns the block to the free list only
    at refcount 0.  A release of a block with no outstanding reference
    still raises loudly — a double-release from the same owner is the
    refcount-era shape of the double-free bug, and silent over-release
    is cross-request cache corruption, the one failure mode a paged
    cache must make impossible.  ``alloc``/``free`` remain as aliases
    for the single-owner call sites.
    """

    def __init__(self, num_blocks: int):
        if num_blocks < 2:
            raise ValueError(
                f"need >= 2 blocks (one is the reserved null block), "
                f"got {num_blocks}")
        self.num_blocks = num_blocks
        # LIFO free list: recently-freed blocks are re-used first (their
        # pool pages are the ones still warm in cache on real hardware)
        self._free = list(range(num_blocks - 1, 0, -1))
        self._refs: dict[int, int] = {}

    @property
    def n_free(self) -> int:
        return len(self._free)

    @property
    def n_live(self) -> int:
        return len(self._refs)

    @property
    def _live(self) -> set[int]:
        """Live block ids (refcount >= 1) — invariant-check view."""
        return set(self._refs)

    def refcount(self, block: int) -> int:
        """Outstanding references on ``block`` (0 when free)."""
        return self._refs.get(block, 0)

    def acquire(self, n: int) -> list[int] | None:
        """``n`` fresh block ids at refcount 1, or None if the pool
        cannot cover them."""
        if n < 0:
            raise ValueError(f"acquire({n})")
        if n > len(self._free):
            return None
        got = [self._free.pop() for _ in range(n)]
        for b in got:
            self._refs[b] = 1
        return got

    def ref(self, block: int) -> None:
        """Add a reference to an already-live block (a new owner)."""
        if block not in self._refs:
            raise ValueError(
                f"ref of block {block} not currently allocated")
        self._refs[block] += 1

    def release(self, blocks: list[int]) -> None:
        for b in blocks:
            n = self._refs.get(b, 0)
            if n <= 0:
                raise ValueError(
                    f"release of block {b} with no outstanding "
                    f"reference (double-free or foreign id)")
            if n == 1:
                del self._refs[b]
                self._free.append(b)
            else:
                self._refs[b] = n - 1

    # single-owner aliases (the pre-refcount API)
    alloc = acquire
    free = release


def window_pages(window: int, prefill_chunk: int, block_size: int) -> int:
    """Pages of a sliding-attention layer's ring, a slot (see the module
    docstring)."""
    return math.ceil((window + prefill_chunk) / block_size) + 1


def ring_table(win_rows: jax.Array, max_blocks: int, lo: jax.Array,
               hi: jax.Array) -> jax.Array:
    """A window layer's table as the kernels take one: ``[S, max_blocks]``,
    logical block ``j`` of slot ``s`` at ring page ``win_rows[s, j % W]``
    for ``lo[s] <= j <= hi[s]`` and the null block elsewhere.  A dead entry
    inside an item of the kernel's work list IS copied (since PR 44 the
    kernel fetches an item's pages by hand, the null block like any other);
    what keeps that cheap is that a window layer's items tile the band
    ``lo .. hi`` from ``lo`` on (``ops.paged_attention.item_pages``), so only
    the last item's tail is dead; nothing outside the band is in the list."""
    j = jnp.arange(max_blocks)[None, :]
    live = (j >= lo[:, None]) & (j <= hi[:, None])
    return jnp.where(live, win_rows[:, j[0] % win_rows.shape[1]], NULL_BLOCK)


def pool_kv_bytes(cfg: TransformerConfig, num_blocks: int, block_size: int,
                  dtype=jnp.bfloat16, quantize: bool = False, *,
                  n_layers: int | None = None, kind: str | None = None
                  ) -> int:
    """Global bytes of the k+v pool arrays (scales included in int8
    mode) — the static number admission control and `check --serving`
    budget against.  ``n_layers``: of that many layers alone, of ``kind``
    (``stored_row``: what a token takes in a layer's pages)."""
    n_layers = cfg.n_layers if n_layers is None else n_layers
    n_tokens = n_layers * num_blocks * block_size
    row = sum(stored_row(cfg, kind))
    if quantize:  # int8 payload + an fp32 scale a head
        return n_tokens * (row + 2 * cfg.kv_heads * 4)
    return n_tokens * row * jnp.dtype(dtype).itemsize


def state_rows(cfg: TransformerConfig, kind: str = "linear_attention"
               ) -> tuple[tuple[int, ...], tuple[int, ...]]:
    """(state, convolution tail): the shapes of ONE slot's row in ONE layer
    of ``kind`` that keeps a recurrent state (``STATE_KINDS``)."""
    if kind == "state_space":
        return ((cfg.ssm_state, cfg.ssm_inner),
                (SSM_CONV_TAPS - 1, cfg.ssm_inner))
    H, dk, dv = (cfg.linear_value_heads, cfg.linear_key_head_dim,
                 cfg.linear_value_head_dim)
    return (H, dk, dv), (cfg.linear_conv_kernel - 1, H * (2 * dk + dv))


def state_row_bytes(cfg: TransformerConfig, dtype=jnp.bfloat16,
                    kind: str = "linear_attention") -> tuple[int, int]:
    """(state, convolution tail) bytes of ONE slot's row in ONE layer of
    ``kind``: the state float32, the tail in ``dtype``."""
    state, tail = state_rows(cfg, kind)
    return (math.prod(state) * 4,
            math.prod(tail) * jnp.dtype(dtype).itemsize)


STATE_KINDS = ("linear_attention", "state_space")  # a row a slot, no pages
NO_CACHE_KINDS = ("gated_memory", "shared_attention")  # nothing at all


def _zeros_side(shape, dtype, quantize: bool):
    if not quantize:
        return jnp.zeros(shape, dtype)
    return {
        "q": jnp.zeros(shape, jnp.int8),
        "scale": jnp.ones(shape[:-1] + (1,), jnp.float32),
    }


def read_pages(kv_layer: Any, pages: jax.Array, kv_heads: int,
               dtype=jnp.bfloat16) -> jax.Array:
    """The pages ``pages`` (any shape of block ids) of one layer, dense and
    dequantized: ``[*pages.shape, bs, kvH, hd]`` in ``dtype``.  Dequantize
    on gather: only what is read converts, and an fp pool that already
    stores ``dtype`` converts nothing."""
    payload, scale = kv_leaf_parts(kv_layer)
    g = payload[pages]
    if scale is not None:
        g = (g.astype(jnp.float32) * scale[pages]).astype(dtype)
    elif g.dtype != dtype:
        g = g.astype(dtype)
    return g.reshape(*pages.shape, payload.shape[1], kv_heads, -1)


def gather_blocks(kv_layer: Any, table: jax.Array, dtype=jnp.bfloat16,
                  kv_heads: int | None = None) -> jax.Array:
    """Dense per-slot view of one layer's paged KV — the REFERENCE path.

    ``kv_layer``: [NB, bs, kvH, hd], folded [NB, bs, kvH * hd] (give
    ``kv_heads`` then), or the ``{"q","scale"}`` int8 form; ``table``:
    [S, max_blocks] int32 —> [S, max_blocks*bs, kvH, hd].  Table rows are
    padded with :data:`NULL_BLOCK`; the garbage gathered from those pages
    sits beyond each slot's context length and the attention mask never
    admits it.

    This materialized view is what the fused kernel
    (ops/paged_attention.py) exists to eliminate; it stays as the
    engine's ``attention_impl="dense"`` path and as the oracle every
    kernel parity test compares against.
    """
    payload = kv_leaf_parts(kv_layer)[0]
    if kv_heads is None:
        if payload.ndim != 4:
            raise ValueError("a folded page needs kv_heads")
        kv_heads = payload.shape[2]
    g = read_pages(kv_layer, table, kv_heads, dtype)
    S, MB, bs, H, hd = g.shape
    return g.reshape(S, MB * bs, H, hd)


def write_token(kv_layer: Any, table: jax.Array, pos: jax.Array,
                new: jax.Array) -> Any:
    """Scatter one token per slot into its paged position.

    ``new``: [S, kvH, hd] (this step's k or v), ``pos``: [S] absolute
    context positions.  The target is ``(table[s, pos // bs], pos % bs)``
    per slot; inactive slots carry all-null tables so their writes land
    in the scratch block.  int8 mode quantizes the token in place with
    its own per-head scale.
    """
    payload = kv_leaf_parts(kv_layer)[0]
    bs = payload.shape[1]
    blk = jnp.take_along_axis(
        table, (pos // bs)[:, None].astype(jnp.int32), axis=1)[:, 0]
    off = pos % bs
    if is_quantized_leaf(kv_layer):
        q = quantize_kv(new)
        return {
            "q": kv_layer["q"].at[blk, off].set(q["q"]),
            "scale": kv_layer["scale"].at[blk, off].set(q["scale"]),
        }
    return kv_layer.at[blk, off].set(_page_rows(new, payload))


def write_chunk(kv_layer: Any, table_row: jax.Array, pos0: jax.Array,
                rows: jax.Array) -> Any:
    """``rows`` [C, kvH, hd] at positions ``pos0 .. pos0 + C`` of ONE slot,
    through its table row (the engine's prefill chunk, written where decode
    will read it).  A chunk of whole pages
    that starts on a page boundary (``C % bs == 0``; the engine's cursor
    then only ever stands on one) is written a page at a time; any other a
    token at a time.  Positions past the row's last block land in the null
    block, like a padded chunk's tail past the request's own blocks."""
    bs = kv_leaf_parts(kv_layer)[0].shape[1]
    C = rows.shape[0]
    # a padded last chunk may reach past the table: the null block there,
    # not the clamped start of a dynamic_slice
    table_row = jnp.pad(table_row, (0, -(-C // bs) + 1))
    if C % bs == 0:
        idx = (jax.lax.dynamic_slice_in_dim(table_row, pos0 // bs, C // bs),)
        lead = (C // bs, bs)
    else:
        pos = pos0 + jnp.arange(C)
        idx, lead = (table_row[pos // bs], pos % bs), (C,)

    def put(leaf, new):  # in the page's own trailing shape
        return leaf.at[idx].set(
            _page_rows(new, leaf).reshape(*lead, *leaf.shape[2:]))

    if is_quantized_leaf(kv_layer):
        new = quantize_kv(rows)
        return {k: put(kv_layer[k], new[k]) for k in ("q", "scale")}
    return put(kv_layer, rows)


class PagedKVPool:
    """Device storage + allocator + host-side table building.

    The arrays live as a pytree ``{"k": [..], "v": [..]}``, one leaf a
    layer (see the module docstring).  The pool object itself is host
    state (free list, shapes); the arrays are swapped wholesale through
    the jitted programs (donated), so there is no device<->host copy per
    token.

    ``n_slots``, ``max_blocks`` and ``prefill_chunk`` size the rings of a
    model's ``sliding_attention`` layers and ``n_slots`` the rows of its
    ``linear_attention`` layers; a model without them needs none.
    """

    def __init__(self, cfg: TransformerConfig, *, num_blocks: int,
                 block_size: int, dtype=jnp.bfloat16,
                 quantize: bool = False, mesh=None,
                 n_slots: int | None = None, max_blocks: int | None = None,
                 prefill_chunk: int | None = None):
        self.cfg = cfg
        self.block_size = bs = int(block_size)
        self.dtype = dtype
        self.quantize = bool(quantize)
        self.allocator = BlockAllocator(num_blocks)
        self.spec = None
        # the kind of every layer's pair: True where the layer keeps a ring,
        # True where it keeps a recurrent state and no pages at all
        kinds = cfg.layer_types or (None,) * cfg.n_layers
        self.kinds = kinds
        self.ring = [kind == "sliding_attention" for kind in kinds]
        self.state = [kind in STATE_KINDS for kind in kinds]
        self.latent = [kind == "latent_attention" for kind in kinds]
        # no arrays of its own (a shared layer reads its source's pages)
        self.none = [kind in NO_CACHE_KINDS for kind in kinds]
        if any(self.state) and mesh is not None:
            raise ValueError("a recurrent state has no sharded form")
        if any(self.latent) and (mesh is not None or self.quantize):
            raise ValueError("a latent page has no sharded and no int8 form")
        # a slot's ring, [n_slots, W] page ids (W 0: no layer has one)
        W = 0
        if any(self.ring):
            W = min(max_blocks, window_pages(
                cfg.sliding_window, prefill_chunk or max_blocks * bs, bs))
        self.n_slots = n_slots = n_slots or 0
        self.win_tables = (
            1 + W * jnp.arange(n_slots, dtype=jnp.int32)[:, None]
            + jnp.arange(W, dtype=jnp.int32)[None, :])
        self.n_window_blocks = n_slots * W + 1 if W else 0
        # folded pages wherever the MXU kernel reads them (module docstring)
        folded = not self.quantize and mesh is None
        page = ((bs, cfg.kv_heads * cfg.head_dim) if folded
                else (bs, cfg.kv_heads, cfg.head_dim))

        def side(name):
            def one(kind, ring, state, latent, none):
                if none:
                    return jnp.zeros((0,), dtype)
                if state:  # the state under "k", the tail under "v"
                    row = state_rows(cfg, kind)[name == "v"]
                    return jnp.zeros(
                        (n_slots + 1, *row),
                        jnp.float32 if name == "k" else cfg.dtype)
                if latent:  # one row a token, under "k" (module docstring)
                    return jnp.zeros(
                        (num_blocks, bs, *stored_row(cfg, kind))
                        if name == "k" else (0,), dtype)
                return _zeros_side(
                    (self.n_window_blocks if ring else num_blocks, *page),
                    dtype, self.quantize)

            return [one(*a) for a in zip(kinds, self.ring, self.state,
                                         self.latent, self.none)]

        self.kv = {"k": side("k"), "v": side("v")}
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec

            spec = cache_partition_spec(cfg, mesh, batch_axes=())
            self.spec = PartitionSpec(*spec[1:])  # a leaf has no layer axis
            sh = NamedSharding(mesh, self.spec)
            self.kv = jax.tree.map(lambda x: jax.device_put(x, sh), self.kv)

    @property
    def num_blocks(self) -> int:
        return self.allocator.num_blocks

    @property
    def n_full(self) -> int:
        """Layers that keep pages for ``max_len`` (a layer that reads
        another's pages keeps none)."""
        return self.cfg.n_layers - sum(
            map(any, zip(self.ring, self.state, self.none)))

    def _bytes_paged(self, num_blocks: int) -> tuple[int, int]:
        """Bytes of ``num_blocks`` pages in every layer that keeps pages for
        ``max_len``: (all of them, the ``latent_attention`` layers')."""
        n_latent = self.latent.count(True)
        latent = n_latent and pool_kv_bytes(
            self.cfg, num_blocks, self.block_size, self.dtype, self.quantize,
            n_layers=n_latent, kind="latent_attention")
        return latent + pool_kv_bytes(
            self.cfg, num_blocks, self.block_size, self.dtype, self.quantize,
            n_layers=self.n_full - n_latent), latent

    @property
    def bytes_full(self) -> int:
        """Bytes of the layers that keep pages for ``max_len`` (every
        layer of a model with one kind of layer), latent pages included."""
        return self._bytes_paged(self.num_blocks)[0]

    @property
    def bytes_latent(self) -> int:
        """What of ``bytes_full`` the ``latent_attention`` layers hold."""
        return self._bytes_paged(self.num_blocks)[1]

    @property
    def bytes_window(self) -> int:
        """Bytes of the sliding layers' rings (0 without such layers)."""
        return pool_kv_bytes(
            self.cfg, self.n_window_blocks, self.block_size, self.dtype,
            self.quantize, n_layers=self.ring.count(True))

    @property
    def bytes_state(self) -> tuple[int, int]:
        """(recurrent states, convolution tails) bytes of the layers that
        keep a row a slot (``STATE_KINDS``), the null row included ((0, 0)
        without such layers)."""
        rows = [state_row_bytes(self.cfg, self.cfg.dtype, kind)
                for kind, state in zip(self.kinds, self.state) if state]
        return tuple((self.n_slots + 1) * sum(r[i] for r in rows)
                     for i in (0, 1))

    @property
    def total_bytes(self) -> int:
        return self.bytes_full + self.bytes_window + sum(self.bytes_state)

    @property
    def bytes_per_block(self) -> int:
        """Global bytes one block id holds across the layers that keep
        pages for ``max_len``, k and v (scales included in int8 mode)."""
        return self._bytes_paged(1)[0]

    def alloc(self, n: int) -> list[int] | None:
        return self.allocator.alloc(n)

    def free(self, blocks: list[int]) -> None:
        self.allocator.free(blocks)

    def fork_block(self, src: int) -> int | None:
        """Copy-on-write fork: acquire a fresh block, copy ``src``'s
        device content into it, return the new id (None when the pool
        is exhausted — the caller must evict or preempt first).  The
        caller owns the table update and the release of its reference
        on ``src``; the copy itself is one scatter a leaf, no host
        round-trip.  A ring and a recurrent state have no block ids to
        share, and are left alone, as are the arrays of no elements (beside
        a layer's latent pages; of a layer that keeps nothing)."""
        got = self.allocator.acquire(1)
        if got is None:
            return None
        dst = got[0]
        for side, layers in self.kv.items():
            self.kv[side] = [
                leaf if ring or state or none or (latent and side == "v")
                else jax.tree.map(lambda x: x.at[dst].set(x[src]), leaf)
                for leaf, ring, state, latent, none in zip(
                    layers, self.ring, self.state, self.latent, self.none)]
        return dst

    def table_row(self, blocks: list[int], max_blocks: int) -> list[int]:
        """Fixed-width table row: allocated ids then null padding."""
        if len(blocks) > max_blocks:
            raise ValueError(
                f"{len(blocks)} blocks exceed table width {max_blocks}")
        return list(blocks) + [NULL_BLOCK] * (max_blocks - len(blocks))

