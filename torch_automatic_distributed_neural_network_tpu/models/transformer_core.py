"""Decoder-only transformer core shared by the GPT-2 and Llama families
(component C12).

One config-driven module covers both: GPT-2 = LayerNorm + learned positions
+ GELU MLP; Llama = RMSNorm + RoPE + SwiGLU + GQA.  Design choices are
TPU-first:

- bfloat16 compute / fp32 params by default (MXU-native);
- ``nn.scan`` over layers: one traced layer compiled once (compile time
  O(1) in depth) and a natural substrate for pipeline stage loops;
- per-layer ``nn.remat`` so FSDP configs recompute activations
  (BASELINE.json:11 pairs FSDP with gradient checkpointing);
- parameter names (q_proj/o_proj/up_proj/down_proj/embed/lm_head) line up
  with the planner's Megatron TP rules, which anchor on *trailing* dims so
  scanned [layer, ...] stacking keeps the same specs.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Literal

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from ..ops.attention import attention
from ..parallel.context import shard_activations


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32000
    d_model: int = 768
    n_layers: int = 12
    n_heads: int = 12
    n_kv_heads: int | None = None  # None -> MHA; < n_heads -> GQA
    d_ff: int | None = None  # None -> 4*d_model (gelu) / 8/3*d_model (swiglu)
    max_seq_len: int = 1024
    norm: Literal["layernorm", "rmsnorm"] = "layernorm"
    norm_eps: float = 1e-5  # HF BERT uses 1e-12; GPT-2/Llama 1e-5
    # 'gelu_exact' is the erf formulation (HF BERT's hidden_act='gelu');
    # plain 'gelu' is the tanh approximation (GPT-2's gelu_new)
    act: Literal["gelu", "gelu_exact", "swiglu"] = "gelu"
    pos: Literal["learned", "rope"] = "learned"
    # False -> bidirectional self-attention: the same backbone serves
    # encoder-only families (BERT, models/bert.py)
    causal: bool = True
    # Mistral-style sliding-window attention: position q attends keys in
    # (q - window, q].  None = full causal.  Native in the Pallas flash
    # kernel (out-of-band blocks skipped at the grid level) and the
    # xla/chunked paths; KV-cache decode bands the cached mask, exact at
    # any total length.  Unsupported under cp (ring/ulysses) — raises.
    sliding_window: int | None = None
    # 'post' = original-transformer/BERT residual order
    # (norm AFTER the residual add); 'pre' = GPT-2/Llama
    norm_order: Literal["pre", "post"] = "pre"
    embed_norm: bool = False  # LayerNorm on embeddings (BERT)
    final_norm: bool = True  # post-norm stacks end already normalized
    type_vocab_size: int = 0  # >0 -> segment embeddings (BERT NSP-style)
    tie_embeddings: bool = True
    dropout_rate: float = 0.0
    # compute dtype.  model.init gives fp32 params and training keeps
    # them so (or as the plan's precision says); ServeEngine is handed
    # them as they are and holds the layers' weights rounded to this
    # dtype once (inference/decode.compute_dtype_params)
    dtype: Any = jnp.bfloat16
    attention_impl: str = "auto"
    scan_layers: bool = True
    remat: bool = True
    # 'dots' saves matmul outputs (cheap recompute, more HBM); 'nothing'
    # recomputes the whole layer in backward (Megatron full activation
    # checkpointing — only the residual stream is saved per layer), the
    # difference between fitting and OOMing GPT-2 1.3B on one 16 GB chip.
    remat_policy: Literal["dots", "nothing"] = "dots"
    rope_theta: float = 10000.0

    def __post_init__(self):
        if self.sliding_window is not None:
            if not self.causal:
                raise ValueError(
                    "sliding_window requires causal=True — a windowed "
                    "bidirectional encoder would silently run FULL "
                    "attention (the ops layer only bands causal scores)"
                )
            if self.sliding_window < 1:
                raise ValueError(
                    f"sliding_window must be >= 1, got {self.sliding_window}"
                )

    @property
    def kv_heads(self) -> int:
        return self.n_kv_heads or self.n_heads

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    @property
    def ff_dim(self) -> int:
        if self.d_ff is not None:
            return self.d_ff
        if self.act == "swiglu":
            # Llama convention: 2/3 * 4d rounded to a multiple of 256
            d = int(8 * self.d_model / 3)
            return (d + 255) // 256 * 256
        return 4 * self.d_model

    def num_params(self) -> int:
        """Analytic parameter count (embedding included once if tied)."""
        d, f, L, v = self.d_model, self.ff_dim, self.n_layers, self.vocab_size
        hd = self.head_dim
        attn = d * (self.n_heads * hd) + 2 * d * (self.kv_heads * hd) + (
            self.n_heads * hd) * d
        mlp = (3 if self.act == "swiglu" else 2) * d * f
        norms = (2 * d) * L + (d if self.final_norm else 0) + (
            d if self.embed_norm else 0)
        emb = v * d * (1 if self.tie_embeddings else 2)
        emb += self.type_vocab_size * d
        pos = self.max_seq_len * d if self.pos == "learned" else 0
        return L * (attn + mlp) + norms + emb + pos


def make_norm(cfg: TransformerConfig, name: str | None = None):
    if cfg.norm == "rmsnorm":
        return nn.RMSNorm(epsilon=cfg.norm_eps, dtype=cfg.dtype, name=name)
    return nn.LayerNorm(epsilon=cfg.norm_eps, dtype=cfg.dtype, name=name)


def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Rotary position embedding on [B, S, H, D] (rotate-half formulation)."""
    d = x.shape[-1]
    freqs = 1.0 / (theta ** (np.arange(0, d, 2, dtype=np.float32) / d))
    angles = positions[..., None].astype(jnp.float32) * freqs  # [B, S, D/2]
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


class SelfAttention(nn.Module):
    """setup()-style so the decode path (inference/decode.py) can apply
    the q/k/v and output projections piecewise (``method='qkv'`` /
    ``method='out_proj'``) against a KV cache — ONE implementation of the
    projection + rope math for train and decode."""

    cfg: TransformerConfig

    def setup(self):
        cfg = self.cfg
        hd = cfg.head_dim
        bias = cfg.norm == "layernorm"
        dense = lambda feats: nn.DenseGeneral(
            feats, axis=-1, dtype=cfg.dtype, use_bias=bias
        )
        self.q_proj = dense((cfg.n_heads, hd))
        self.k_proj = dense((cfg.kv_heads, hd))
        self.v_proj = dense((cfg.kv_heads, hd))
        self.o_proj = nn.DenseGeneral(
            cfg.d_model, axis=(-2, -1), dtype=cfg.dtype, use_bias=bias
        )

    def qkv(self, x, positions):
        """Projected (and rope-rotated) q/k/v for a chunk at ``positions``."""
        cfg = self.cfg
        q, k, v = self.q_proj(x), self.k_proj(x), self.v_proj(x)
        if cfg.pos == "rope":
            q = rope(q, positions, cfg.rope_theta)
            k = rope(k, positions, cfg.rope_theta)
        return q, k, v

    def out_proj(self, out):
        return self.o_proj(out)

    def __call__(self, x, positions, mask=None):
        q, k, v = self.qkv(x, positions)
        out = attention(
            q, k, v, causal=self.cfg.causal,
            window=self.cfg.sliding_window,
            mask=mask, impl=self.cfg.attention_impl,
        )
        return self.out_proj(out)


class MLPBlock(nn.Module):
    """setup()-style so decode applies it directly on cached-path chunks
    — the gelu/SwiGLU feed-forward math lives here and only here."""

    cfg: TransformerConfig

    def setup(self):
        cfg = self.cfg
        bias = cfg.norm == "layernorm"
        dense = lambda feats: nn.Dense(feats, dtype=cfg.dtype, use_bias=bias)
        if cfg.act == "swiglu":
            self.gate_proj = dense(cfg.ff_dim)
        self.up_proj = dense(cfg.ff_dim)
        self.down_proj = dense(cfg.d_model)

    def __call__(self, x):
        if self.cfg.act == "swiglu":
            h = nn.silu(self.gate_proj(x)) * self.up_proj(x)
        else:
            h = nn.gelu(self.up_proj(x),
                        approximate=self.cfg.act != "gelu_exact")
        return self.down_proj(h)


class DecoderLayer(nn.Module):
    """Pre-norm attention + MLP block.  ``mlp_cls`` swaps the feed-forward
    (MLPBlock dense; models/moe.py MoEMlp routed): an MLP returning
    ``(h, aux)`` makes the layer return ``(x, aux)`` for the backbone's
    aux-carry."""

    cfg: TransformerConfig
    mlp_cls: type[nn.Module] = MLPBlock

    @nn.compact
    def __call__(self, x, positions, mask=None):
        # Residual-stream boundaries carry the Megatron-SP / CP activation
        # sharding (seq dim over tensor and/or seq axes): the norms and
        # residual adds run sequence-sharded, and GSPMD materializes the
        # full sequence only inside the attention/MLP matmul regions.
        cfg = self.cfg
        post = cfg.norm_order == "post"
        x = shard_activations(x)
        # post-norm (original transformer / BERT): sublayer on the raw
        # stream, norm AFTER the residual add; pre-norm: norm first
        h = x if post else make_norm(cfg, "attn_norm")(x)
        h = SelfAttention(cfg, name="attn")(h, positions, mask)
        if cfg.dropout_rate:
            h = nn.Dropout(cfg.dropout_rate, deterministic=not self.has_rng("dropout"))(h)
        x = x + h
        if post:
            x = make_norm(cfg, "attn_norm")(x)
        x = shard_activations(x)
        h = x if post else make_norm(cfg, "mlp_norm")(x)
        h = self.mlp_cls(cfg, name="mlp")(h)
        aux = None
        if isinstance(h, tuple):
            h, aux = h
        if cfg.dropout_rate:
            h = nn.Dropout(cfg.dropout_rate, deterministic=not self.has_rng("dropout"))(h)
        out = x + h
        if post:
            out = make_norm(cfg, "mlp_norm")(out)
        out = shard_activations(out)
        return out if aux is None else (out, aux)


def apply_decoder_backbone(
    self: nn.Module,
    cfg: TransformerConfig,
    tokens,
    positions,
    mask,
    layer_base: type[nn.Module],
    return_features: bool = False,
    segment_ids=None,
    head=None,
    inputs_embeds=None,
):
    """Shared decoder body: embed -> (remat'd, scanned) layer stack -> norm
    -> tied/untied head.

    Called from a ``@nn.compact`` ``__call__`` of the owning module so the
    parameter tree ("embed", "pos_embed", "layers", "final_norm",
    "lm_head") is identical for every family.  ``layer_base`` may return
    either ``x`` (dense layers) or ``(x, aux)`` (MoE layers — aux router
    losses); the scan carry threads the aux sum functionally either way.
    Returns ``(logits, aux_total)`` — or, with ``return_features=True``,
    ``(post-final-norm hidden states, aux_total)`` WITHOUT applying the
    LM head: the fp32 ``[B,S,V]`` logits tensor is the dominant memory
    temp at large vocab (Llama-3: 128k), and ``training.losses.
    blockwise_next_token_loss`` consumes features + head weights to
    compute the loss without ever materializing it.

    ``segment_ids`` adds BERT-style token-type embeddings (requires
    ``cfg.type_vocab_size > 0``); ``head`` is an optional callable
    ``head(features, embed) -> logits`` replacing the default tied /
    untied LM head — encoder families use it for the MLM transform
    (models/bert.py) without duplicating the "embed" module name.

    ``inputs_embeds`` [B, S, d] bypasses the token embedding entirely
    (and skips creating it, so no phantom [V, d] param) — continuous-
    input families (ViT patch embeddings, models/vit.py) enter here.
    """
    if inputs_embeds is not None:
        if tokens is not None:
            raise ValueError("pass tokens or inputs_embeds, not both")
        embed = None
        x = inputs_embeds.astype(cfg.dtype)
        lead = x.shape[:2]
    else:
        embed = nn.Embed(
            cfg.vocab_size, cfg.d_model, dtype=cfg.dtype,
            embedding_init=nn.initializers.normal(0.02), name="embed",
        )
        x = embed(tokens)
        lead = tokens.shape
    if positions is None:
        positions = jnp.arange(lead[1])[None, :]
        positions = jnp.broadcast_to(positions, lead)
    if cfg.pos == "learned":
        pos_emb = self.param(
            "pos_embed", nn.initializers.normal(0.02),
            (cfg.max_seq_len, cfg.d_model), jnp.float32,
        )
        x = x + pos_emb[None, : lead[1]].astype(cfg.dtype)
    if cfg.type_vocab_size:
        if segment_ids is None:
            segment_ids = jnp.zeros(lead, jnp.int32)
        x = x + nn.Embed(
            cfg.type_vocab_size, cfg.d_model, dtype=cfg.dtype,
            embedding_init=nn.initializers.normal(0.02), name="seg_embed",
        )(segment_ids)
    if cfg.embed_norm:
        x = make_norm(cfg, "embed_norm")(x)
    x = shard_activations(x)

    layer_cls = layer_base
    if cfg.remat:
        layer_cls = nn.remat(
            layer_base,
            policy=(
                jax.checkpoint_policies.nothing_saveable
                if cfg.remat_policy == "nothing"
                else jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims
            ),
            prevent_cse=not cfg.scan_layers,
        )

    def run_layer(mdl, x, aux_acc):
        out = mdl(x, positions, mask)
        if isinstance(out, tuple):
            x, aux = out
            return x, aux_acc + aux
        return out, aux_acc

    aux_total = jnp.zeros((), jnp.float32)
    if cfg.scan_layers:
        def body(mdl, carry, _):
            return run_layer(mdl, *carry), None

        (x, aux_total), _ = nn.scan(
            body,
            variable_axes={"params": 0},
            split_rngs={"params": True, "dropout": True},
            length=cfg.n_layers,
            metadata_params={nn.PARTITION_NAME: "layers"},
        )(layer_cls(cfg, name="layers"), (x, aux_total), None)
    else:
        for i in range(cfg.n_layers):
            x, aux_total = run_layer(
                layer_cls(cfg, name=f"layers_{i}"), x, aux_total
            )

    if cfg.final_norm:
        x = make_norm(cfg, "final_norm")(x)
    if return_features:
        return x, aux_total
    if head is not None:
        return head(x, embed), aux_total
    if embed is None:
        raise ValueError(
            "inputs_embeds has no token embedding to tie an LM head to; "
            "use return_features=True or pass head="
        )
    if cfg.tie_embeddings:
        logits = embed.attend(x.astype(jnp.float32))
    else:
        logits = nn.Dense(
            cfg.vocab_size, dtype=jnp.float32, use_bias=False,
            name="lm_head",
        )(x)
    return logits.astype(jnp.float32), aux_total


class DecoderLM(nn.Module):
    """Causal language model: GPT-2 / Llama families by config."""

    cfg: TransformerConfig

    @nn.compact
    def __call__(self, tokens, positions=None, mask=None,
                 return_features: bool = False):
        out, _ = apply_decoder_backbone(
            self, self.cfg, tokens, positions, mask, DecoderLayer,
            return_features=return_features,
        )
        return out
