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

from ..ops.attention import attention, diff_plain_heads
from ..ops.gated_delta import causal_conv, gated_delta_chunk, l2norm
from ..ops.ssm import ssm_chunk
from ..parallel.context import shard_activations
from ..parallel.expert import held_expert_ffn, route_top_k


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
    # "none": no positional signal at all (a model whose recurrent layers
    # carry the order: nothing is added to the embedding, nothing rotated)
    pos: Literal["learned", "rope", "none"] = "learned"
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
    # -- layer kinds as data: a model whose layers differ -------------------
    # One entry a layer: "sliding_attention" (the causal band of
    # ``sliding_window``), "full_attention" (plain causal) or
    # "linear_attention" (no keys and values: a gated delta rule over a
    # recurrent state, ``GatedDeltaMixer``, whose decay is a head's or a
    # channel's by the ``linear_*`` keys below) or "latent_attention" (one
    # low-rank latent a token in the place of per-head keys and values,
    # ``LatentAttention``), or one of a decoder-hybrid-decoder's three:
    # "state_space" (a selective scan over a diagonal state, ``MambaMixer``,
    # the ``ssm_*`` keys), "gated_memory" (no cache at all: the scan output
    # of the nearest ``state_space`` layer BEFORE it, of the same token,
    # gated by the layer's own input, ``GatedMemory``) and
    # "shared_attention" (queries and an output projection alone: it
    # attends the keys and values of the nearest ``full_attention`` layer
    # before it, whose cache is the only one).  Given, the layers are built
    # one by one (``layers_0`` .. in the parameter tree, no ``nn.scan``),
    # each of its own kind, and the keys below apply; None keeps the one
    # scanned layer every other model here has.
    layer_types: tuple[str, ...] | None = None
    # which layers rotate q and k when ``pos == "rope"``: "sliding" leaves
    # the full-attention layers without any positional rotation
    rope_layers: Literal["all", "sliding"] = "all"
    head_size: int | None = None  # None -> d_model // n_heads
    qk_norm: bool = False  # RMSNorm over head_dim on q and k, before rope
    # ... or, "projection", over a token's whole q and k projection (all
    # the heads at once, one gain a channel: OLMo 2/3)
    qk_norm_over: Literal["head", "projection"] = "head"
    attn_gate: bool = False  # out = (attn * sigmoid(x Wg)) Wo
    sandwich_norm: bool = False  # a norm after attention and after the FFN
    # False: no norm BEFORE a sublayer, so with ``sandwich_norm`` the two
    # norms of a layer sit on each sublayer's output (OLMo 2/3)
    pre_norm: bool = True
    embed_scale: bool = False  # h0 = E[tok] * sqrt(d_model)
    # the first ``n_dense_layers`` layers have a dense FFN of ``d_ff``, the
    # others the expert FFN below; None -> every layer is dense
    n_dense_layers: int | None = None
    experts_published: int = 0  # the router's width
    experts_held: int | None = None  # held here (None -> all of them) ...
    first_expert: int = 0  # ... starting at this one
    experts_per_token: int = 1
    shared_experts: int = 0
    expert_d_ff: int | None = None  # width of one expert (and a shared one)
    score_func: Literal["sigmoid", "softmax"] = "sigmoid"
    route_norm: bool = True  # weights of the chosen experts sum to 1 ...
    route_scale: float = 1.0  # ... times this
    # zero-compute experts: the router's LAST ``zero_experts`` outputs (its
    # width is ``experts_published + zero_experts``) have no weights; a token
    # that picks one gets ``w * u``, its own normed input.  Nobody's share
    # of an expert-parallel layer: every chip adds them for its own tokens
    zero_experts: int = 0
    # the expert FFN as a SHORTCUT branch over a pair of sublayers: the plan
    # has one entry a sublayer (mixer + dense FFN); entry 2j's experts read
    # its FFN's normed input and their result is added after entry 2j + 1's
    # FFN (``layer_plan``: "open", "close").  ``n_dense_layers`` then counts
    # the entries without experts of their own, the closing ones: n_layers / 2
    shortcut_experts: bool = False
    # the ``linear_attention`` layers (valid only with one): heads of keys
    # and of values (equal here: no grouping), their sizes, the taps of the
    # short causal convolution on q, k and v, and whether beta reaches 2
    # (a state transition with eigenvalues down to -1)
    linear_key_heads: int | None = None
    linear_value_heads: int | None = None
    linear_key_head_dim: int | None = None
    linear_value_head_dim: int | None = None
    linear_conv_kernel: int = 4
    linear_neg_eigval: bool = False
    # which gated delta rule: the log-decay one number a head (Gated
    # DeltaNet) or one a key CHANNEL of a head (Kimi Delta Attention: the
    # state's rows forget each at its own rate); the decay's and the output
    # gate's projections through a bottleneck of this width (None: one
    # matrix each); and the output gate's activation
    linear_decay: Literal["head", "channel"] = "head"
    linear_decay_rank: int | None = None
    linear_gate_rank: int | None = None
    linear_gate_act: Literal["silu", "sigmoid"] = "silu"
    # the ``latent_attention`` layers (valid only with one): the ranks of
    # the query's (None: no bottleneck, one ``q_proj``) and of the key-value
    # latent, and a head's three sizes: the part of a query and a key that
    # is not rotated, the rotated part (ONE key part for all heads, beside
    # the latent; on a layer that does not rotate, ``layer_rotates``, it is
    # one more unrotated part that the heads share), and a value
    latent_q_rank: int | None = None
    latent_kv_rank: int | None = None
    latent_nope_head_dim: int | None = None
    latent_rope_head_dim: int | None = None
    latent_value_head_dim: int | None = None
    # constants on the two latents' norms (``mla_scale_q_lora``,
    # ``mla_scale_kv_lora``: sqrt(d_model / rank)); what a page stores is
    # the scaled latent
    latent_q_scale: float = 1.0
    latent_kv_scale: float = 1.0
    # the ``state_space`` layers (valid only with one; a ``gated_memory``
    # layer is as wide as their scan): the inner channels, the numbers a
    # channel's state holds and the bottleneck of the step size's
    # projection (the causal depthwise convolution has ``SSM_CONV_TAPS``
    # taps and a bias)
    ssm_inner: int | None = None
    ssm_state: int | None = None
    ssm_dt_rank: int | None = None
    # differential attention on every attention layer (sliding, full,
    # shared): query heads in ADJACENT pairs, a pair's two softmaxes (each
    # over its own key head of a pair of KV heads) subtracted, over values
    # twice a key's width (the pair's two value heads side by side);
    # ``SelfAttention.differ`` has the rest
    diff_attention: bool = False
    # biases in the FFN: None -> a model with LayerNorm has them (GPT-2),
    # one with RMSNorm has none; the attention projections' go by the norm
    mlp_bias: bool | None = None

    def __post_init__(self):
        kinds = self.layer_types
        if kinds is not None:
            object.__setattr__(self, "layer_types", tuple(kinds))
            if set(kinds) - set(LAYER_KINDS) or len(kinds) != self.n_layers:
                raise ValueError(
                    f"layer_types needs n_layers={self.n_layers} entries of "
                    f"{LAYER_KINDS}, got {self.layer_types}")
        sizes = ("linear_key_heads", "linear_value_heads",
                 "linear_key_head_dim", "linear_value_head_dim")
        given = [k for k in sizes + ("linear_neg_eigval", "linear_decay_rank",
                                     "linear_gate_rank") if getattr(self, k)]
        given += [k for k, v in (("linear_decay", "head"),
                                 ("linear_gate_act", "silu"))
                  if getattr(self, k) != v]
        if "linear_attention" not in (kinds or ()):
            if given:
                raise ValueError(f"{given} describe linear_attention "
                                 f"layers: layer_types has none")
        elif (not all(getattr(self, k) for k in sizes)
              or self.linear_key_heads != self.linear_value_heads
              or self.linear_conv_kernel < 2):
            raise ValueError(
                "a linear_attention layer needs linear_key_heads == "
                "linear_value_heads, linear_key_head_dim, "
                "linear_value_head_dim and linear_conv_kernel >= 2")
        given = [k for k in ("latent_q_rank",) + LATENT_SIZES
                 if getattr(self, k)]
        if "latent_attention" not in (kinds or ()):
            if given:
                raise ValueError(f"{given} describe latent_attention "
                                 f"layers: layer_types has none")
        elif not all(getattr(self, k) for k in LATENT_SIZES):
            raise ValueError(
                f"a latent_attention layer needs {LATENT_SIZES} "
                f"(latent_q_rank None: queries without a bottleneck)")
        elif (self.layer_rotates("latent_attention")
              and self.latent_rope_head_dim % 2):
            raise ValueError(
                "a latent_attention layer that rotates (pos='rope', "
                "rope_layers='all') needs an even latent_rope_head_dim; "
                "rope_layers='sliding' leaves it without any rotation")
        given = [k for k in SSM_SIZES if getattr(self, k)]
        if "state_space" not in (kinds or ()):
            if given:
                raise ValueError(f"{given} describe state_space layers: "
                                 f"layer_types has none")
        elif not all(getattr(self, k) for k in SSM_SIZES):
            raise ValueError(f"a state_space layer needs {SSM_SIZES}")
        for i, kind in enumerate(kinds or ()):
            # what a layer without a cache of its own reads lies BEFORE it
            if kind in SOURCE_KINDS and self.source_layer(i) is None:
                raise ValueError(
                    f"layer {i} ({kind}) needs a {SOURCE_KINDS[kind]} layer "
                    f"before it")
        if self.diff_attention and (
                kinds is None or self.n_heads % 2 or self.kv_heads % 2
                or self.n_heads % self.kv_heads or self.qk_norm
                or self.attn_gate):
            raise ValueError(
                "diff_attention pairs adjacent heads of a model with "
                "layer_types: even n_heads and n_kv_heads, no qk_norm, no "
                "attn_gate")
        if kinds is not None:
            if not self.pre_norm and not self.sandwich_norm:
                raise ValueError("pre_norm=False leaves a layer without "
                                 "norms: give sandwich_norm too")
            if ("sliding_attention" in self.layer_types
                    and self.sliding_window is None):
                raise ValueError("a sliding_attention layer needs "
                                 "sliding_window")
            if self.n_expert_layers and not (
                    0 <= self.first_expert
                    and self.first_expert + self.n_experts_held
                    <= self.experts_published
                    and self.experts_per_token <= self.router_width):
                raise ValueError(
                    f"experts {self.first_expert}.."
                    f"{self.first_expert + self.n_experts_held} held, "
                    f"{self.experts_per_token} a token, of "
                    f"{self.experts_published} published")
            if self.zero_experts and not self.n_expert_layers:
                raise ValueError("zero_experts widen a router: the model "
                                 "has no expert layer")
            if self.shortcut_experts and (
                    self.n_layers % 2
                    or self.n_dense_layers != self.n_layers // 2):
                raise ValueError(
                    "shortcut_experts pairs the sublayers: an even n_layers "
                    "and n_dense_layers == n_layers // 2 (the closing "
                    "sublayers have no experts of their own)")
        else:
            given = [k for k in ("qk_norm", "attn_gate", "sandwich_norm",
                                 "embed_scale", "head_size",
                                 "n_dense_layers", "experts_published",
                                 "zero_experts", "shortcut_experts",
                                 "diff_attention")
                     if getattr(self, k)] + ["pre_norm"] * (not self.pre_norm)
            if given:
                raise ValueError(
                    f"{given} describe a model built layer by layer: give "
                    f"layer_types too (one kind a layer)")
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
        return self.head_size or self.d_model // self.n_heads

    @property
    def n_expert_layers(self) -> int:
        if self.layer_types is None or self.n_dense_layers is None:
            return 0
        return self.n_layers - self.n_dense_layers

    @property
    def n_experts_held(self) -> int:
        return (self.experts_published if self.experts_held is None
                else self.experts_held)

    @property
    def router_width(self) -> int:
        """The router's outputs: the published experts, then the
        zero-compute ones."""
        return self.experts_published + self.zero_experts

    @property
    def has_mlp_bias(self) -> bool:
        return (self.norm == "layernorm" if self.mlp_bias is None
                else self.mlp_bias)

    def source_layer(self, i: int) -> int | None:
        """The layer whose cache (a ``shared_attention`` layer: the pages of
        the nearest ``full_attention`` layer before it) or whose scan output
        of the same token (a ``gated_memory`` layer: the nearest
        ``state_space`` layer before it) layer ``i`` reads; None for a layer
        that reads its own, or where no such layer lies before it."""
        want = SOURCE_KINDS.get(self.layer_types[i])
        return next((j for j in range(i - 1, -1, -1)
                     if self.layer_types[j] == want), None)

    @property
    def cross_start(self) -> int:
        """The first layer of the model's last run of layers that keep NO
        cache of their own (``gated_memory``, ``shared_attention``: a
        cross-decoder); ``n_layers`` where the last layer keeps one.  A
        prompt's rows need not run these layers, but for the row whose
        logits are wanted: nothing a later token reads is written there."""
        kinds = self.layer_types or ()
        i = len(kinds)
        while i and kinds[i - 1] in SOURCE_KINDS:
            i -= 1
        return i if kinds else self.n_layers

    def lambda_init(self, depth):
        """Differential attention's constant of layer ``depth`` (0-based; a
        number or a traced scalar): ``0.8 - 0.6 exp(-0.3 depth)``."""
        return 0.8 - 0.6 * jnp.exp(-0.3 * jnp.asarray(depth, jnp.float32))

    def layer_window(self, kind: str | None) -> int | None:
        """The causal band of a layer of ``kind`` (None: the model's one
        kind of layer)."""
        if kind in (None, "sliding_attention"):
            return self.sliding_window
        return None

    def page_row(self, kind: str | None) -> tuple[int, ...]:
        """The numbers a token takes in each array of the pair that a paged
        layer of ``kind`` keeps: its keys and its values, the KV heads side
        by side; on a ``latent_attention`` layer ONE array, the key-value
        latent and the rotated key part behind it."""
        if kind == "latent_attention":
            return (self.latent_kv_rank + self.latent_rope_head_dim,)
        return (self.kv_heads * self.head_dim,) * 2

    def layer_rotates(self, kind: str | None) -> bool:
        return self.pos == "rope" and (
            kind in (None, "sliding_attention") or self.rope_layers == "all")

    @property
    def ff_dim(self) -> int:
        if self.d_ff is not None:
            return self.d_ff
        if self.act == "swiglu":
            # Llama convention: 2/3 * 4d rounded to a multiple of 256
            d = int(8 * self.d_model / 3)
            return (d + 255) // 256 * 256
        return 4 * self.d_model

    def mixer_params(self, kind: str) -> int:
        """Parameters of the mixer of a layer of ``kind`` (a model with
        ``layer_types``): attention, or the gated delta rule's projections,
        filters, decay and output norm."""
        d, hd = self.d_model, self.head_dim
        if kind == "state_space":
            n, N, R = self.ssm_inner, self.ssm_state, self.ssm_dt_rank
            return (d * 2 * n + n * d  # in, out
                    + (SSM_CONV_TAPS + 1) * n  # filters, their bias
                    + n * (R + 2 * N) + R * n  # the token's maps, the step
                    + n + N * n + n)  # dt_bias, A_log, D
        if kind == "gated_memory":
            return 2 * d * self.ssm_inner
        if kind == "linear_attention":
            LH = self.linear_value_heads
            qk = self.linear_key_heads * self.linear_key_head_dim
            vo = LH * self.linear_value_head_dim
            # a decay a head or a channel, each map whole or through a
            # bottleneck
            decays = qk if self.linear_decay == "channel" else LH
            through = lambda rank, out: (d * out if rank is None
                                         else rank * (d + out))
            return (2 * d * qk + d * vo + vo * d  # q k, v, out
                    + through(self.linear_gate_rank, vo)  # the output gate
                    + through(self.linear_decay_rank, decays)
                    + d * LH  # beta
                    + LH + decays  # A_log, dt_bias
                    + self.linear_conv_kernel * (2 * qk + vo)
                    + self.linear_value_head_dim)  # the output norm's gain
        if kind == "latent_attention":
            H, rq, rkv = self.n_heads, self.latent_q_rank, self.latent_kv_rank
            nope, rot, dv = (self.latent_nope_head_dim,
                             self.latent_rope_head_dim,
                             self.latent_value_head_dim)
            queries = (d * H * (nope + rot) if rq is None
                       else d * rq + rq + rq * H * (nope + rot))
            return (queries  # whole, or through a normed bottleneck
                    + d * (rkv + rot) + rkv  # the latent and the rotated key
                    + rkv * H * (nope + dv) + H * dv * d)  # up, out
        q, kv = self.n_heads * hd, self.kv_heads * hd
        if kind == "shared_attention":
            kv = 0  # queries and the way out alone
        normed = {"head": 2 * hd, "projection": q + kv}[self.qk_norm_over]
        return (2 * d * q + 2 * d * kv + (d * q if self.attn_gate else 0)
                + (normed if self.qk_norm else 0)
                + (q + 2 * kv + d if self.norm == "layernorm" else 0)
                # four vectors of lambda, the pair's norm over 2 hd
                + (6 * hd if self.diff_attention else 0))

    def num_params(self) -> int:
        """Analytic parameter count (embedding included once if tied);
        for a model with ``layer_types``, of what is HELD here."""
        d, f, L, v = self.d_model, self.ff_dim, self.n_layers, self.vocab_size
        hd = self.head_dim
        attn = d * (self.n_heads * hd) + 2 * d * (self.kv_heads * hd) + (
            self.n_heads * hd) * d
        if self.layer_types is not None:
            # a layer's mixer by kind, its norms, its FFN by position
            n_sparse = self.n_expert_layers
            fe, E = self.expert_d_ff, self.router_width
            sparse = n_sparse and (d * E + E + 3 * d * fe * (
                self.n_experts_held + self.shared_experts))
            mixers = sum(self.mixer_params(kind) for kind in self.layer_types)
            a_norm = d * (2 if self.norm == "layernorm" else 1)  # its bias
            norms = (2 * self.sandwich_norm + 2 * self.pre_norm) * a_norm
            # a shortcut branch lies BESIDE its sublayers' dense FFNs
            n_dense = L if self.shortcut_experts else L - n_sparse
            dense = 3 * d * f + (2 * f + d if self.has_mlp_bias else 0)
            return (mixers + L * norms + n_dense * dense
                    + n_sparse * sparse
                    + v * d * (1 if self.tie_embeddings else 2) + a_norm)
        mlp = (3 if self.act == "swiglu" else 2) * d * f
        norms = (2 * d) * L + (d if self.final_norm else 0) + (
            d if self.embed_norm else 0)
        emb = v * d * (1 if self.tie_embeddings else 2)
        emb += self.type_vocab_size * d
        pos = self.max_seq_len * d if self.pos == "learned" else 0
        return L * (attn + mlp) + norms + emb + pos


LAYER_KINDS = ("sliding_attention", "full_attention", "linear_attention",
               "latent_attention", "state_space", "gated_memory",
               "shared_attention")
# a kind that keeps no cache of its own -> the kind of layer it reads
SOURCE_KINDS = {"gated_memory": "state_space",
                "shared_attention": "full_attention"}
SSM_SIZES = ("ssm_inner", "ssm_state", "ssm_dt_rank")
SSM_CONV_TAPS = 4  # Mamba-1's; a key the day a configuration gives another
LATENT_SIZES = ("latent_kv_rank", "latent_nope_head_dim",
                "latent_rope_head_dim", "latent_value_head_dim")


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


def deinterleave(x: jax.Array) -> jax.Array:
    """Rotary pairs laid out side by side, ``(x0, x1), (x2, x3), ..``
    (``rope_interleave``), brought to the halves ``rope`` rotates: ``x0, x2,
    .., x1, x3, ..``.  A dot product of two vectors so permuted is what it
    was, so scores do not care in which layout rotated parts are kept."""
    d = x.shape[-1]
    return jnp.swapaxes(x.reshape(*x.shape[:-1], d // 2, 2), -1, -2).reshape(
        x.shape)


class SelfAttention(nn.Module):
    """setup()-style so the decode path (inference/decode.py) can apply
    the q/k/v and output projections piecewise (``method='qkv'`` /
    ``method='out_proj'``) against a KV cache — ONE implementation of the
    projection + rope math for train and decode.

    ``kind`` is the layer's entry of ``cfg.layer_types`` (None where the
    model has one kind of layer): it decides the window and whether q and
    k are rotated."""

    cfg: TransformerConfig
    kind: str | None = None

    def setup(self):
        cfg = self.cfg
        hd = cfg.head_dim
        bias = cfg.norm == "layernorm"
        dense = lambda feats: nn.DenseGeneral(
            feats, axis=-1, dtype=cfg.dtype, use_bias=bias
        )
        self.q_proj = dense((cfg.n_heads, hd))
        if self.kind != "shared_attention":  # another layer's keys and values
            self.k_proj = dense((cfg.kv_heads, hd))
            self.v_proj = dense((cfg.kv_heads, hd))
        if cfg.qk_norm:
            self.q_norm = nn.RMSNorm(epsilon=cfg.norm_eps, dtype=cfg.dtype)
            self.k_norm = nn.RMSNorm(epsilon=cfg.norm_eps, dtype=cfg.dtype)
        if cfg.attn_gate:
            self.gate_proj = dense((cfg.n_heads, hd))
        if cfg.diff_attention:
            vec = lambda name: self.param(
                name, nn.initializers.normal(0.1), (hd,), jnp.float32)
            self.lambdas = tuple(vec(f"lambda_{n}")
                                 for n in ("q1", "k1", "q2", "k2"))
            self.sub_norm = nn.RMSNorm(epsilon=cfg.norm_eps,
                                       dtype=jnp.float32)
        self.o_proj = nn.DenseGeneral(
            cfg.d_model, axis=(-2, -1), dtype=cfg.dtype, use_bias=bias
        )

    def qkv(self, x, positions):
        """Projected (normed, rope-rotated) q/k/v for a chunk at
        ``positions``; on a ``shared_attention`` layer, which has no keys
        and values of its own, ``(q, None, None)``."""
        cfg = self.cfg
        q = self.q_proj(x)
        if self.kind == "shared_attention":
            return q, None, None
        k, v = self.k_proj(x), self.v_proj(x)
        if cfg.qk_norm and cfg.qk_norm_over == "projection":
            q = self.q_norm(q.reshape(*q.shape[:-2], -1)).reshape(q.shape)
            k = self.k_norm(k.reshape(*k.shape[:-2], -1)).reshape(k.shape)
        elif cfg.qk_norm:
            q, k = self.q_norm(q), self.k_norm(k)
        if cfg.layer_rotates(self.kind):
            q = rope(q, positions, cfg.rope_theta)
            k = rope(k, positions, cfg.rope_theta)
        return q, k, v

    def differ(self, o, depth):
        """Differential attention's second half.  ``o`` [..., H, 2 hd]
        float32 is what each query head's softmax gave over its pair of
        value heads (``diff_heads``); heads ``2j`` and ``2j + 1`` are pair
        ``j``: ``(o_2j - lambda o_2j+1)`` through an RMSNorm over the
        ``2 hd`` (one gain for all pairs), times ``1 - lambda_init``;
        ``lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init`` with
        ``lambda_init`` the layer's (``cfg.lambda_init(depth)``).  Returns
        [..., H, hd], the pairs' results as ``o_proj`` takes them."""
        cfg = self.cfg
        q1, k1, q2, k2 = self.lambdas
        init = cfg.lambda_init(depth)
        lam = jnp.exp(jnp.sum(q1 * k1)) - jnp.exp(jnp.sum(q2 * k2)) + init
        o = o.astype(jnp.float32).reshape(*o.shape[:-2], -1, 2, o.shape[-1])
        d = self.sub_norm(o[..., 0, :] - lam * o[..., 1, :]) * (1.0 - init)
        return d.reshape(*d.shape[:-2], cfg.n_heads, cfg.head_dim).astype(
            cfg.dtype)

    def out_proj(self, out, x=None):
        """``x`` is the layer's normed input, which the output gate reads
        (``cfg.attn_gate``)."""
        if self.cfg.attn_gate:
            gate = nn.sigmoid(self.gate_proj(x).astype(jnp.float32))
            out = (out.astype(jnp.float32) * gate).astype(out.dtype)
        return self.o_proj(out)

    def __call__(self, x, positions, mask=None, kv=None, depth=0):
        """``kv``: the keys and values a ``shared_attention`` layer attends
        (its source layer's).  Returns ``(output, (k, v))``: the keys and
        values attended, for the layers that share them."""
        cfg = self.cfg
        q, k, v = self.qkv(x, positions)
        if self.kind == "shared_attention":
            k, v = kv
        heads = diff_plain_heads(q, k, v) if cfg.diff_attention else (q, k, v)
        out = attention(
            *heads, causal=cfg.causal, window=cfg.layer_window(self.kind),
            mask=mask, impl=cfg.attention_impl)
        if cfg.diff_attention:
            out = self.differ(
                out.reshape(*q.shape[:-1], 2 * cfg.head_dim), depth)
        return self.out_proj(out, x), (k, v)


class MambaMixer(nn.Module):
    """The mixer of a ``state_space`` layer (Mamba-1; ``ops/ssm.py`` has the
    recurrence): ``[a, z] = W_in x``; ``c = silu(conv(a) + b)``, a causal
    depthwise convolution of ``SSM_CONV_TAPS`` taps; ``[delta, B, C] = W_x
    c``; ``Delta = softplus(W_dt delta + dt_bias)``; the selective scan
    gives ``y``; out ``= W_out(y * silu(z))``.  ``y``, before the gate, is
    also the token's MEMORY that later ``gated_memory`` layers read.
    setup()-style: the serving programs apply the pieces (``method=``)
    round their own read and write of the cached state and tail.

    Parameters that stay float32 when the serving engine rounds the rest
    (``decode.compute_dtype_params``): ``A_log`` [N, d_in] (the published
    ``[d_in, N]`` transposed: channels in the lanes), ``dt_bias``, ``D``,
    the filters ``conv`` and ``conv_bias``."""

    cfg: TransformerConfig

    def setup(self):
        cfg = self.cfg
        n, N, R = cfg.ssm_inner, cfg.ssm_state, cfg.ssm_dt_rank
        dense = lambda feats: nn.Dense(feats, dtype=cfg.dtype, use_bias=False)
        self.in_proj, self.x_proj = dense(2 * n), dense(R + 2 * N)
        self.dt_proj, self.o_proj = dense(n), dense(cfg.d_model)
        self.conv = self.param("conv", nn.initializers.normal(0.02),
                               (SSM_CONV_TAPS, n), jnp.float32)
        self.conv_bias = self.param("conv_bias", nn.initializers.zeros, (n,),
                                    jnp.float32)

        def a_log(key, shape):  # A = 1 .. N a channel (S4D-real)
            del key
            return jnp.log(jnp.broadcast_to(
                jnp.arange(1, N + 1, dtype=jnp.float32)[:, None], shape))

        self.A_log = self.param("A_log", a_log, (N, n))
        self.dt_bias = self.param("dt_bias", dt_bias_init, (n,))
        self.D = self.param("D", nn.initializers.ones, (n,), jnp.float32)

    def project(self, x):
        """``x`` [..., d] -> ``(a, z)`` [..., d_in] each: the scan's input
        before its convolution, and the gate."""
        a, z = jnp.split(self.in_proj(x), 2, axis=-1)
        return a, z

    def convolve(self, a, tail=None):
        """``a`` [B, T, d_in] of ``project`` and ``tail`` [B, K - 1, d_in],
        the K - 1 rows before it (None: the sequence starts here).  Returns
        ``(c [B, T, d_in], full [B, K - 1 + T, d_in])``; ``full``'s last K -
        1 rows are the next call's tail."""
        cfg = self.cfg
        if tail is None:
            tail = jnp.zeros((a.shape[0], SSM_CONV_TAPS - 1, a.shape[-1]),
                             a.dtype)
        full = jnp.concatenate([tail.astype(a.dtype), a], axis=1)
        c = causal_conv(full, self.conv, a.shape[1], self.conv_bias)
        return c.astype(cfg.dtype), full

    def scan_inputs(self, c):
        """``c`` [..., d_in] -> ``(Delta [..., d_in], B, C [..., N])``, all
        float32: what the scan takes of a token beside ``c`` itself."""
        cfg = self.cfg
        N, R = cfg.ssm_state, cfg.ssm_dt_rank
        dbc = self.x_proj(c)
        delta = nn.softplus(self.dt_proj(dbc[..., :R]).astype(jnp.float32)
                            + self.dt_bias)
        B, C = jnp.split(dbc[..., R:].astype(jnp.float32), [N], axis=-1)
        return delta, B, C

    def rates(self):
        """``(A [N, d_in] < 0, D [d_in])``."""
        return -jnp.exp(self.A_log), self.D

    def out_proj(self, y, z):
        """``y`` [..., d_in] float32, what the scan gave; ``z`` the gate."""
        return self.o_proj((y * nn.silu(z.astype(jnp.float32))).astype(
            self.cfg.dtype))

    def __call__(self, x):
        """``(the layer's output [B, T, d], y [B, T, d_in] float32)``."""
        a, z = self.project(x)
        c, _ = self.convolve(a)
        delta, B, C = self.scan_inputs(c)
        A, D = self.rates()
        h0 = jnp.zeros(A.shape, jnp.float32)
        y = jax.vmap(lambda c, dt, b, cc: ssm_chunk(c, dt, A, b, cc, D, h0)[0]
                     )(c, delta, B, C)
        return self.out_proj(y, z), y


class GatedMemory(nn.Module):
    """The mixer of a ``gated_memory`` layer (a Gated Memory Unit): the
    memory ``m`` [..., d_in] float32, the scan output of the nearest
    ``state_space`` layer before this one for the SAME token, gated by the
    layer's own input: ``W_2(m * silu(W_1 x))``.  No state, no cache."""

    cfg: TransformerConfig

    def setup(self):
        cfg = self.cfg
        dense = lambda feats: nn.Dense(feats, dtype=cfg.dtype, use_bias=False)
        self.in_proj, self.o_proj = dense(cfg.ssm_inner), dense(cfg.d_model)

    def __call__(self, x, m):
        gate = nn.silu(self.in_proj(x).astype(jnp.float32))
        return self.o_proj((m * gate).astype(self.cfg.dtype))


def dt_bias_init(key, shape):
    """A recurrent mixer's ``dt_bias``: steps log-uniform in (0.001, 0.1),
    through the inverse of the softplus that reads them."""
    dt = jnp.exp(jax.random.uniform(
        key, shape, jnp.float32, np.log(1e-3), np.log(0.1)))
    return dt + jnp.log(-jnp.expm1(-dt))


class GatedDeltaMixer(nn.Module):
    """The mixer of a ``linear_attention`` layer: a gated delta rule over a
    recurrent state a head (``ops/gated_delta.py`` has the equations) in
    the place of attention over keys and values.  Two published mixers by
    the configuration's data: Gated DeltaNet (``linear_decay == "head"``:
    one log-decay a head a token, ``a_proj``; the output gate one matrix
    and SiLU) and Kimi Delta Attention (``"channel"``: a log-decay a key
    channel, ``g`` [B, T, H, d_k], from a low-rank pair ``f_a_proj``,
    ``f_b_proj`` of ``linear_decay_rank``, ``dt_bias`` a channel; the output
    gate a low-rank pair ``g_a_proj``, ``g_b_proj`` and a sigmoid).  The
    state, the tail and every method's signature are the same: the serving
    programs do not know which they apply.  setup()-style, like
    ``SelfAttention``, so that the serving programs apply the same
    projections piecewise (``method="qkv"`` / ``"out_proj"``) round their
    own read and write of the cached state.

    Parameters that stay float32 when the serving engine rounds the rest
    (``decode.compute_dtype_params``): ``A_log``, ``dt_bias``, the filters
    ``conv`` and the output norm's gain."""

    cfg: TransformerConfig

    def setup(self):
        cfg = self.cfg
        H, dk, dv = (cfg.linear_value_heads, cfg.linear_key_head_dim,
                     cfg.linear_value_head_dim)
        dense = lambda feats: nn.DenseGeneral(
            feats, axis=-1, dtype=cfg.dtype, use_bias=False)
        self.q_proj, self.k_proj = dense((H, dk)), dense((H, dk))
        self.v_proj, self.b_proj = dense((H, dv)), dense(H)
        decays = (H, dk) if cfg.linear_decay == "channel" else (H,)
        if cfg.linear_decay_rank is None:
            self.a_proj = dense(decays)
        else:
            self.f_a_proj = dense(cfg.linear_decay_rank)
            self.f_b_proj = dense(decays)
        if cfg.linear_gate_rank is None:
            self.gate_proj = dense((H, dv))
        else:
            self.g_a_proj = dense(cfg.linear_gate_rank)
            self.g_b_proj = dense((H, dv))
        self.o_proj = nn.DenseGeneral(cfg.d_model, axis=(-2, -1),
                                      dtype=cfg.dtype, use_bias=False)
        # one filter of K taps a channel of q, k and v, side by side
        self.conv = self.param("conv", nn.initializers.normal(0.02), (
            cfg.linear_conv_kernel, H * (2 * dk + dv)), jnp.float32)

        def a_log(key, shape):  # decay rates A uniform in (0, 16)
            return jnp.log(jax.random.uniform(key, shape, jnp.float32,
                                              1e-3, 16.0))

        self.A_log = self.param("A_log", a_log, (H,))
        self.dt_bias = self.param("dt_bias", dt_bias_init,
                                  (int(np.prod(decays)),))
        self.o_norm = nn.RMSNorm(epsilon=cfg.norm_eps, dtype=jnp.float32)

    def project(self, x):
        """What the mixer takes from each row of ``x`` [B, T, d] alone:
        ``(pre [B, T, D], g, beta [B, T, H] float32)``, the q, k and v
        projections side by side before the convolution, the log-decay
        (``[B, T, H, d_k]`` where it is a channel's) and the write
        strength."""
        cfg = self.cfg
        pre = jnp.concatenate(
            [p(x).reshape(*x.shape[:2], -1)
             for p in (self.q_proj, self.k_proj, self.v_proj)], -1)
        beta = nn.sigmoid(self.b_proj(x).astype(jnp.float32))
        if cfg.linear_neg_eigval:
            beta = beta * 2.0
        a = (self.a_proj(x) if cfg.linear_decay_rank is None
             else self.f_b_proj(self.f_a_proj(x))).astype(jnp.float32)
        rate = jnp.exp(self.A_log).reshape(-1, *(1,) * (a.ndim - 3))
        g = -rate * nn.softplus(a + self.dt_bias.reshape(a.shape[2:]))
        return pre, g, beta

    def convolve(self, pre, tail=None):
        """``pre`` [B, T, D] of ``project`` and ``tail`` [B, K - 1, D], the
        projections of the K - 1 tokens before it as ``full`` had them
        (None: the sequence starts here).  Returns ``(q, k [B, T, H, d_k],
        v [B, T, H, d_v], full [B, K - 1 + T, D])``: q and k convolved,
        normalised a head, q scaled; ``full`` the tail and this call's
        projections, whose last K - 1 rows are the next call's tail."""
        cfg = self.cfg
        H, dk = cfg.linear_value_heads, cfg.linear_key_head_dim
        lead = pre.shape[:2]
        if tail is None:
            tail = jnp.zeros((lead[0], cfg.linear_conv_kernel - 1,
                              pre.shape[-1]), pre.dtype)
        full = jnp.concatenate([tail.astype(pre.dtype), pre], axis=1)
        y = causal_conv(full, self.conv, lead[1])
        q, k, v = jnp.split(y, [H * dk, 2 * H * dk], axis=-1)
        q = l2norm(q.reshape(*lead, H, dk)) * dk ** -0.5
        k = l2norm(k.reshape(*lead, H, dk))
        v = v.reshape(*lead, H, -1)
        return (q.astype(cfg.dtype), k.astype(cfg.dtype),
                v.astype(cfg.dtype), full)

    def qkv(self, x, tail=None):
        """``project`` then ``convolve``, for one sequence a batch row:
        ``(q, k, v, g, beta, full)``."""
        pre, g, beta = self.project(x)
        q, k, v, full = self.convolve(pre, tail)
        return q, k, v, g, beta, full

    def out_proj(self, o, x):
        """``o`` [B, T, H, d_v] float32, what the state gave; ``x`` the
        layer's input, which the output gate reads."""
        cfg = self.cfg
        gate = (self.gate_proj(x) if cfg.linear_gate_rank is None
                else self.g_b_proj(self.g_a_proj(x))).astype(jnp.float32)
        act = nn.sigmoid if cfg.linear_gate_act == "sigmoid" else nn.silu
        return self.o_proj((self.o_norm(o) * act(gate)).astype(cfg.dtype))

    def __call__(self, x):
        q, k, v, g, beta, _ = self.qkv(x)
        B, T, H, dk = q.shape
        # a batch folds into the heads: [T, B * H, .]
        fold = lambda a: jnp.moveaxis(a, 0, 1).reshape(T, B * H, *a.shape[3:])
        o, _ = gated_delta_chunk(
            fold(q), fold(k), fold(v), fold(g), fold(beta),
            jnp.zeros((B * H, dk, v.shape[-1]), jnp.float32))
        return self.out_proj(jnp.moveaxis(o.reshape(T, B, H, -1), 0, 1), x)


class LatentAttention(nn.Module):
    """The mixer of a ``latent_attention`` layer (multi-head latent
    attention, the DeepSeek-V3 block): queries through a low-rank
    bottleneck (or, ``latent_q_rank`` None, one ``q_proj``), and ONE latent ``c_kv`` a token from which every head's
    unrotated key part and value are expanded (``kv_b_proj``), beside one
    rotated key part ``k_r`` that all heads share.  A head's score is
    ``(q_nope . k_nope + q_rope . k_r) / sqrt(nope + rope)``.  On a layer
    that does not rotate (``cfg.layer_rotates``) the two "rotated" parts are
    used as they are projected: the layer sees no position at all.

    What a cache keeps is the token's ``[RMSNorm(c_kv), rotated k_r]`` alone
    (``cfg.page_row``: 576 numbers where 32 heads of keys and values are
    10,240), so the serving programs apply the pieces one by one
    (``method=``), like ``SelfAttention``'s: ``project`` (both low-rank
    projections, their norms, the rotation of the two rotated parts),
    ``expand`` (cached latents to per-head keys and values: a prefill chunk,
    a key block at a time), or ``absorb`` and ``lift`` (decode: the queries
    taken INTO the latent space, ``q_nope W_UK^T``, attention of all heads
    over the one latent row as key and value, and the result taken out
    again, ``. W_UV``: the same numbers, no per-head key ever built), then
    ``out_proj``.  ``__call__`` is the expanded form over a whole
    sequence."""

    cfg: TransformerConfig

    def setup(self):
        cfg = self.cfg
        H, rot = cfg.n_heads, cfg.latent_rope_head_dim
        dense = lambda feats: nn.DenseGeneral(
            feats, axis=-1, dtype=cfg.dtype, use_bias=False)
        # a norm followed by a constant gives float32, which ``_scaled``
        # rounds once, with the constant
        norm = lambda scale: nn.RMSNorm(
            epsilon=cfg.norm_eps,
            dtype=cfg.dtype if scale == 1.0 else jnp.float32)
        if cfg.latent_q_rank is None:
            self.q_proj = dense((H, cfg.latent_nope_head_dim + rot))
        else:
            self.q_a_proj = dense(cfg.latent_q_rank)
            self.q_a_norm = norm(cfg.latent_q_scale)
            self.q_b_proj = dense((H, cfg.latent_nope_head_dim + rot))
        self.kv_a_proj = dense(cfg.latent_kv_rank + rot)
        self.kv_a_norm = norm(cfg.latent_kv_scale)
        self.kv_b_proj = dense(
            (H, cfg.latent_nope_head_dim + cfg.latent_value_head_dim))
        self.o_proj = nn.DenseGeneral(cfg.d_model, axis=(-2, -1),
                                      dtype=cfg.dtype, use_bias=False)

    def _scaled(self, y, scale: float):
        """A latent's norm times its constant (``latent_q_scale``,
        ``latent_kv_scale``), in the compute dtype."""
        return y if scale == 1.0 else (y * scale).astype(self.cfg.dtype)

    def project(self, x, positions):
        """``x`` [B, T, d] at ``positions`` [B, T]: ``(q_nope [B, T, H,
        nope], q_rope [B, T, H, rope], latent [B, T, kv_rank + rope])``,
        the rotated parts rotated (pairs side by side as published,
        ``deinterleave``) where the layer rotates, the latent normed and
        scaled: the row a cache keeps."""
        cfg = self.cfg
        if cfg.latent_q_rank is None:
            q = self.q_proj(x)
        else:
            q = self.q_b_proj(self._scaled(
                self.q_a_norm(self.q_a_proj(x)), cfg.latent_q_scale))
        q_nope, q_rope = jnp.split(q, [cfg.latent_nope_head_dim], axis=-1)
        c, k_r = jnp.split(self.kv_a_proj(x), [cfg.latent_kv_rank], axis=-1)
        c = self._scaled(self.kv_a_norm(c), cfg.latent_kv_scale)
        if cfg.layer_rotates("latent_attention"):
            q_rope = rope(deinterleave(q_rope), positions, cfg.rope_theta)
            k_r = rope(deinterleave(k_r)[:, :, None], positions,
                       cfg.rope_theta)[:, :, 0]
        return q_nope, q_rope, jnp.concatenate([c, k_r], -1)

    def up(self):
        """``kv_b_proj``'s kernel [kv_rank, H, nope + value] apart: (W_UK,
        W_UV), in the compute dtype."""
        w = self.kv_b_proj.variables["params"]["kernel"].astype(self.cfg.dtype)
        return jnp.split(w, [self.cfg.latent_nope_head_dim], axis=-1)

    def expand(self, c):
        """Normed latents ``c`` [..., kv_rank] to ``(k_nope [..., H, nope],
        v [..., H, value])``."""
        return tuple(jnp.split(self.kv_b_proj(c),
                               [self.cfg.latent_nope_head_dim], axis=-1))

    def absorb(self, q_nope, q_rope):
        """A query in the latent space: ``[q_nope W_UK^T, q_rope]`` [..., H,
        kv_rank + rope], whose product with a cached row is the head's
        score."""
        q_lat = jnp.einsum("...hn,chn->...hc", q_nope, self.up()[0])
        return jnp.concatenate([q_lat, q_rope.astype(q_lat.dtype)], -1)

    def lift(self, o_lat):
        """``o_lat`` [..., H, kv_rank], a head's probabilities over the
        cached latents, to the head's output [..., H, value]."""
        return jnp.einsum("...hc,chv->...hv", o_lat.astype(self.cfg.dtype),
                          self.up()[1])

    def out_proj(self, out, x=None):
        del x  # no output gate
        return self.o_proj(out)

    def __call__(self, x, positions, mask=None):
        cfg = self.cfg
        q_nope, q_rope, latent = self.project(x, positions)
        c, k_r = jnp.split(latent, [cfg.latent_kv_rank], axis=-1)
        k_nope, v = self.expand(c)
        q = jnp.concatenate([q_nope, q_rope], -1)
        k = jnp.concatenate([k_nope, jnp.broadcast_to(
            k_r[:, :, None], (*k_nope.shape[:-1], k_r.shape[-1]))], -1)
        # values are narrower than keys: padded for the one attention entry
        pad = q.shape[-1] - v.shape[-1]
        out = attention(
            q, k, jnp.pad(v, ((0, 0),) * 3 + ((0, pad),)), causal=cfg.causal,
            mask=mask, impl=cfg.attention_impl)
        return self.out_proj(out[..., :v.shape[-1]])


class MLPBlock(nn.Module):
    """setup()-style so decode applies it directly on cached-path chunks
    — the gelu/SwiGLU feed-forward math lives here and only here."""

    cfg: TransformerConfig
    width: int | None = None  # None -> cfg.ff_dim (a shared expert's differs)

    def setup(self):
        cfg = self.cfg
        bias = cfg.has_mlp_bias
        dense = lambda feats: nn.Dense(feats, dtype=cfg.dtype, use_bias=bias)
        width = self.width or cfg.ff_dim
        if cfg.act == "swiglu":
            self.gate_proj = dense(width)
        self.up_proj = dense(width)
        self.down_proj = dense(cfg.d_model)

    def __call__(self, x):
        if self.cfg.act == "swiglu":
            h = nn.silu(self.gate_proj(x)) * self.up_proj(x)
        else:
            h = nn.gelu(self.up_proj(x),
                        approximate=self.cfg.act != "gelu_exact")
        return self.down_proj(h)


class Router(nn.Module):
    """Scores over ALL the published experts and the zero-compute ones
    behind them (``cfg.router_width``), in float32 (a chip that holds a share
    of the experts still routes over every one of them), and the per-expert
    bias that takes part in the choice and never in the weight."""

    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        kernel = self.param("kernel", nn.initializers.normal(0.02),
                            (cfg.d_model, cfg.router_width), jnp.float32)
        e_bias = self.param("e_bias", nn.initializers.zeros,
                            (cfg.router_width,), jnp.float32)
        logits = jnp.dot(x.astype(jnp.float32), kernel,
                         precision=jax.lax.Precision.HIGHEST)
        return route_top_k(
            logits, e_bias, cfg.experts_per_token, score_func=cfg.score_func,
            route_norm=cfg.route_norm, route_scale=cfg.route_scale)


class SparseMLP(nn.Module):
    """The expert FFN of a model built layer by layer: shared expert(s)
    plus the chosen routed experts, no capacity and no dropped token
    (``parallel/expert.held_expert_ffn``).  It holds experts
    ``first_expert .. first_expert + experts_held`` of the published ones
    and adds what those give; on one chip there is no exchange.  A pair on
    a zero-compute expert (``cfg.zero_experts``) adds ``w * x`` for the
    chip's own tokens and reads no weight.

    ``valid`` [..., tokens] marks the rows that are real (a padded chunk's
    tail and an empty slot route nowhere, so they read no expert and add no
    zero-compute term).  Returns ``(y, stats)``: the step counters of
    ``held_expert_ffn``."""

    cfg: TransformerConfig

    def setup(self):
        cfg = self.cfg
        held, d, f = cfg.n_experts_held, cfg.d_model, cfg.expert_d_ff
        init = nn.initializers.normal(0.02)
        self.router = Router(cfg)
        self.experts_gate = self.param("experts_gate", init, (held, d, f),
                                       jnp.float32)
        self.experts_up = self.param("experts_up", init, (held, d, f),
                                     jnp.float32)
        self.experts_down = self.param("experts_down", init, (held, f, d),
                                       jnp.float32)
        if cfg.shared_experts:
            self.shared = MLPBlock(cfg, width=cfg.shared_experts * f)

    def __call__(self, x, valid=None):
        cfg = self.cfg
        lead = x.shape[:-1]
        rows = x.reshape(-1, x.shape[-1])
        chosen, weights = self.router(rows)
        cast = lambda w: w.astype(cfg.dtype)
        y, stats = held_expert_ffn(
            rows, chosen, weights, cast(self.experts_gate),
            cast(self.experts_up), cast(self.experts_down),
            first_expert=cfg.first_expert,
            valid=None if valid is None else valid.reshape(-1),
            n_experts=cfg.experts_published if cfg.zero_experts else None)
        y = y.reshape(*lead, -1)
        if cfg.shared_experts:
            y = y + self.shared(x)
        return y, stats


def ffn_sublayer(cfg: TransformerConfig, x, sparse: bool, branch, carried,
                 *, norm, dense, experts, post_norm):
    """The second half of a layer of a model with ``layer_types``, from the
    stream ``x`` behind the mixer's residual add: ONE description of the
    order for ``KindDecoderLayer`` and the serving programs' ``_layer``,
    which hand in their own way of applying each part.  ``norm(x)`` is the
    FFN's norm, ``dense(u)`` the dense FFN, ``experts(u) -> (y, stats)`` the
    expert FFN, ``post_norm(h)`` the sandwich norm behind the FFN.

    ``sparse``: the expert FFN stands in the dense one's place.  ``branch``
    (``layer_plan``): an entry that OPENS a shortcut branch also hands its
    FFN's normed input to the experts and carries their result; the entry
    that CLOSES it adds what was carried behind its own FFN, so the branch
    spans the mixer and the FFN between them.  Returns ``(x, the experts'
    stats or None, what is carried on)``."""
    u = norm(x) if cfg.pre_norm else x
    stats = None
    if branch == "open":
        carried, stats = experts(u)
    if sparse:
        h, stats = experts(u)
    else:
        h = dense(u)
    if cfg.sandwich_norm:
        h = post_norm(h)
    x = x + h
    if branch == "close":
        x, carried = x + carried, None
    return x, stats, carried


class KindDecoderLayer(nn.Module):
    """One layer of a model whose layers differ (``cfg.layer_types``): the
    mixer of this layer's ``kind`` (attention, the gated delta rule of a
    ``linear_attention`` layer, or the latent attention of a
    ``latent_attention`` one), a dense or an expert FFN, and the norms
    where ``cfg.pre_norm`` and ``cfg.sandwich_norm`` put them (before each
    sublayer, after it, or both).  With a ``branch`` (``layer_plan``) the
    layer takes and returns what a shortcut branch carries beside ``x``;
    the branch's experts are the subtree ``moe``."""

    cfg: TransformerConfig
    kind: str
    sparse: bool
    branch: str | None = None
    depth: int = 0  # the layer's index (differential attention's constant)

    @nn.compact
    def __call__(self, x, positions, mask=None, carried=None, passed=None):
        """``passed``: what layers before this one hand to those that keep
        nothing of their own, ``{"memory": a state_space layer's scan
        output, "kv": a full_attention layer's keys and values}``; the
        layer returns it with its own in.  Returns ``(x, carried,
        passed)``."""
        cfg = self.cfg
        passed = dict(passed or {})
        h = make_norm(cfg, "attn_norm")(x) if cfg.pre_norm else x
        if self.kind == "linear_attention":
            h = GatedDeltaMixer(cfg, name="attn")(h)
        elif self.kind == "latent_attention":
            h = LatentAttention(cfg, name="attn")(h, positions, mask)
        elif self.kind == "state_space":
            h, passed["memory"] = MambaMixer(cfg, name="attn")(h)
        elif self.kind == "gated_memory":
            h = GatedMemory(cfg, name="attn")(h, passed["memory"])
        else:
            h, kv = SelfAttention(cfg, self.kind, name="attn")(
                h, positions, mask, passed.get("kv"), self.depth)
            if self.kind == "full_attention":
                passed["kv"] = kv
        if cfg.sandwich_norm:
            h = make_norm(cfg, "post_attn_norm")(h)
        x, _, carried = ffn_sublayer(
            cfg, x + h, self.sparse, self.branch, carried,
            norm=lambda x: make_norm(cfg, "mlp_norm")(x),
            dense=lambda u: MLPBlock(cfg, name="mlp")(u),
            experts=lambda u: SparseMLP(
                cfg, name="mlp" if self.sparse else "moe")(u),
            post_norm=lambda h: make_norm(cfg, "post_mlp_norm")(h))
        return x, carried, passed


def layer_plan(cfg: TransformerConfig
               ) -> list[tuple[str, str | None, bool, str | None]]:
    """(parameter name, kind, has an expert FFN in the dense one's place,
    branch) of every layer, in order.  A model with one kind of layer (no
    ``layer_types``) is a plan of that one kind, None: its ``layers_i`` is
    layer ``i`` of the scanned ``layers`` stack
    (``inference/decode.layer_params``).  ``branch`` is None but under
    ``cfg.shortcut_experts``, whose entries are SUBLAYERS (a mixer and its
    dense FFN each): the even ones "open" an expert branch and the odd ones
    "close" it (``ffn_sublayer``)."""
    if cfg.layer_types is None:
        return [(f"layers_{i}", None, False, None)
                for i in range(cfg.n_layers)]
    if cfg.shortcut_experts:
        return [(f"layers_{i}", kind, False, ("open", "close")[i % 2])
                for i, kind in enumerate(cfg.layer_types)]
    n_dense = (cfg.n_layers if cfg.n_dense_layers is None
               else cfg.n_dense_layers)
    return [(f"layers_{i}", kind, i >= n_dense, None)
            for i, kind in enumerate(cfg.layer_types)]


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
        h, _ = SelfAttention(cfg, name="attn")(h, positions, mask)
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
    if cfg.embed_scale:
        x = x * jnp.asarray(np.sqrt(cfg.d_model), x.dtype)
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
    if cfg.layer_types is not None:
        # layers that differ: one module a layer, each of its own kind
        carried = None  # what an open shortcut branch holds
        passed = None  # a scan's output, a full layer's keys and values
        for i, (name, kind, sparse, branch) in enumerate(layer_plan(cfg)):
            x, carried, passed = KindDecoderLayer(
                cfg, kind, sparse, branch, depth=i, name=name)(
                    x, positions, mask, carried, passed)
    elif cfg.scan_layers:
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
