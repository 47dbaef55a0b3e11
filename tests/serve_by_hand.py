"""The three serving programs over a small pool, driven by hand as the
engine drives them: what the reference tests of the models with
``layer_types`` share (``test_joyai_flash_reference.py``,
``test_longcat_flash_reference.py``, ``test_kimi_linear_reference.py``: a
pool of pages, of a linear layer's state rows and tails, or of both; the
slot's row goes up with a chunk's operands)."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from torch_automatic_distributed_neural_network_tpu.inference import decode
from torch_automatic_distributed_neural_network_tpu.inference.serve import (
    programs,
)
from torch_automatic_distributed_neural_network_tpu.inference.serve.kv_pool import (
    PagedKVPool,
    blocks_for_tokens,
)
from torch_automatic_distributed_neural_network_tpu.models.transformer_core import (
    TransformerConfig,
)


class Served:
    """The programs over a pool of ``n_slots`` slots, as the engine drives
    them: ``prefill(slot, tokens)`` in chunks, ``decode({slot: token})`` one
    step, ``fused(slot, chunk, {slot: token})`` both in one call."""

    def __init__(self, keys: dict, params: dict, *, chunk: int, block: int,
                 n_slots: int = 3, max_len: int = 96, cache=jnp.float32,
                 impl: str = "paged"):
        self.cfg = TransformerConfig(**keys, dtype=jnp.float32, remat=False)
        self.params, self.chunk = params, chunk
        self.n_slots, self.MB = n_slots, blocks_for_tokens(max_len, block)
        self.pool = PagedKVPool(
            self.cfg, num_blocks=n_slots * self.MB + 1, block_size=block,
            dtype=cache, n_slots=n_slots, max_blocks=self.MB,
            prefill_chunk=chunk)
        self.kv = self.pool.kv
        self.rows = {s: self.pool.table_row(self.pool.alloc(self.MB), self.MB)
                     for s in range(n_slots)}
        self.ctx = {}
        self.counters = None  # of the last step or fused call
        self._chunk = jax.jit(lambda *a: programs.prefill_chunk(
            *a, cfg=self.cfg, max_blocks=self.MB))
        self._step = jax.jit(lambda *a: programs.decode_logits(
            *a, cfg=self.cfg, attention_impl=impl))
        self._fused = jax.jit(lambda *a: programs.chunk_and_step(
            *a, cfg=self.cfg, max_blocks=self.MB, chunk=chunk,
            sample=decode.SampleConfig(temperature=0.0),
            attention_impl=impl))

    def _packed_chunk(self, slot, tokens, pos):
        part = list(tokens)
        return programs.pack_chunk(
            self.rows[slot], part + [0] * (self.chunk - len(part)), pos,
            len(part) - 1, slot)

    def chunks(self, slot: int, tokens):
        """A chunk a ``next``: ``{its last real position: logits}``."""
        tokens = list(tokens)
        self.ctx[slot] = len(tokens)
        for pos in range(0, len(tokens), self.chunk):
            part = tokens[pos:pos + self.chunk]
            self.kv, lg = self._chunk(
                self.params, self.kv, self._packed_chunk(slot, part, pos),
                self.pool.win_tables[slot])
            yield {pos + len(part) - 1: np.asarray(lg[0])}

    def prefill(self, slot: int, tokens) -> dict:
        return {p: r for c in self.chunks(slot, tokens) for p, r in c.items()}

    def _step_operands(self, toks: dict):
        S = self.n_slots
        tables = np.zeros((S, self.MB), np.int32)
        ctx, tok = np.zeros((S,), np.int32), np.zeros((S, 1), np.int32)
        active = np.zeros((S,), bool)
        for s, t in toks.items():
            tables[s], ctx[s], tok[s, 0], active[s] = (
                self.rows[s], self.ctx[s], t, True)
            self.ctx[s] += 1
        return tables, ctx, tok, active

    def decode(self, toks: dict) -> dict:
        tables, ctx, tok, active = self._step_operands(toks)
        self.kv, lg, counters = self._step(
            self.params, self.kv, jnp.asarray(tables), self.pool.win_tables,
            jnp.asarray(ctx), jnp.asarray(tok), jnp.asarray(active))
        self.counters = np.asarray(counters)
        return {s: np.asarray(lg[s, 0]) for s in toks}

    def fused(self, slot: int, part, pos: int, toks: dict):
        """One chunk of ``slot`` at ``pos`` with the decode rows ``toks`` of
        other slots in it: ``(the chunk's last row's logits, {slot: the
        token its decode row was served})``."""
        tables, ctx, tok, active = self._step_operands(toks)
        step = programs.pack_step(
            tables, ctx, tok, active.astype(np.int32),
            np.zeros((self.n_slots,), np.int32))
        self.ctx[slot] = pos + len(part)
        self.kv, out, lg = self._fused(
            self.params, self.kv, programs.pack_chunk_and_step(
                self._packed_chunk(slot, part, pos), step),
            programs.step_output(self.n_slots), self.pool.win_tables[slot],
            self.pool.win_tables, jax.random.key(0))
        out = np.asarray(out)
        self.counters = out[-programs.N_COUNTERS:]
        return np.asarray(lg[0]), {
            s: int(out[self.n_slots + s]) for s in toks}

    def sequence(self, slot: int, seq, n_prompt: int) -> dict:
        out = self.prefill(slot, seq[:n_prompt])
        for pos in range(n_prompt, len(seq)):
            out[pos] = self.decode({slot: seq[pos]})[slot]
        return out


def chunk_alone(eng):
    """``programs.prefill_chunk`` at ``eng``'s sizes, jitted as a
    speculative or a tenant engine holds it (operands:
    ``eng._abstract_prefill_args()``)."""
    cfg, max_blocks = eng.cfg, eng.max_blocks

    def serve_prefill_chunk(*operands):
        return programs.prefill_chunk(*operands, cfg=cfg,
                                      max_blocks=max_blocks)

    return jax.jit(serve_prefill_chunk, donate_argnums=(1,))


def as_two_calls(eng):
    """A plain engine made to run its chunk and its step as two calls: the
    fused program taken away and the chunk alone put beside the step (the
    twin that a fused engine's tokens are compared with)."""
    eng._fused_fn, eng._prefill_fn = None, chunk_alone(eng)
    return eng
