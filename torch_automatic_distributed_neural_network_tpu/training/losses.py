"""Standard loss functions with the AutoDistribute loss_fn signature.

``loss_fn(params, batch, rng, apply_fn) -> (loss, aux_dict)``.
Batches are dicts; classification expects ``x``/``label``, LM expects
``input_ids`` (next-token target derived by shifting).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import optax


def softmax_xent_loss(params, batch, rng, apply_fn):
    """Image/sequence classification: logits vs integer labels."""
    x = batch.get("x", batch.get("image"))
    labels = batch.get("label", batch.get("y"))
    logits = apply_fn(params, x, rngs={"dropout": rng} if rng is not None else None)
    loss = optax.softmax_cross_entropy_with_integer_labels(logits, labels).mean()
    acc = jnp.mean(jnp.argmax(logits, -1) == labels)
    return loss, {"accuracy": acc}


def _shifted_xent(logits, tokens, mask):
    """Next-token cross-entropy on already-shifted logits; returns
    (mean loss, token count), padding-masked when ``mask`` is given.
    Shared by the dense and MoE LM losses so the conventions can't
    diverge."""
    targets = tokens[:, 1:]
    losses = optax.softmax_cross_entropy_with_integer_labels(logits, targets)
    if mask is not None:
        mask = mask[:, 1:]
        denom = jnp.maximum(mask.sum(), 1)
        return (losses * mask).sum() / denom, denom
    return losses.mean(), jnp.asarray(targets.size, jnp.float32)


def next_token_loss(params, batch, rng, apply_fn):
    """Causal LM: predict token t+1 from tokens <= t; ignores padding 0s
    if an explicit ``mask`` is present."""
    tokens = batch.get("input_ids", batch.get("tokens"))
    logits = apply_fn(
        params, tokens[:, :-1],
        rngs={"dropout": rng} if rng is not None else None,
    )
    loss, denom = _shifted_xent(logits, tokens, batch.get("mask"))
    return loss, {"tokens": denom}


def softmax_xent_loss_mutable(params, model_state, batch, rng, apply_fn):
    """Classification loss for stateful models (BatchNorm): threads the
    mutable collections through and returns the updated ones in aux."""
    x = batch.get("x", batch.get("image"))
    labels = batch.get("label", batch.get("y"))
    variables = {"params": params, **model_state}
    logits, updates = apply_fn(
        variables, x, train=True, mutable=list(model_state.keys()),
        rngs={"dropout": rng} if rng is not None else None,
    )
    loss = optax.softmax_cross_entropy_with_integer_labels(logits, labels).mean()
    acc = jnp.mean(jnp.argmax(logits, -1) == labels)
    return loss, {"accuracy": acc, "model_state": updates}


def next_token_loss_mutable(params, model_state, batch, rng, apply_fn):
    """Causal LM loss for stateful/bridged models (from_torch graphs
    carry buffers in 'constants' and BatchNorm stats in 'batch_stats'):
    threads the mutable collections through apply with train=True and
    returns the updated ones in aux — the LM twin of
    softmax_xent_loss_mutable.  Padding masks work as in
    next_token_loss."""
    tokens = batch.get("input_ids", batch.get("tokens"))
    variables = {"params": params, **model_state}
    logits, updates = apply_fn(
        variables, tokens[:, :-1], train=True,
        mutable=list(model_state.keys()),
        rngs={"dropout": rng} if rng is not None else None,
    )
    loss, denom = _shifted_xent(logits, tokens, batch.get("mask"))
    return loss, {"tokens": denom, "model_state": updates}


def moe_next_token_loss(params, batch, rng, apply_fn):
    """Causal LM loss for MoE models whose apply returns (logits, aux):
    next_token_loss's cross-entropy plus the router load-balance/z losses
    (models/moe.py)."""
    tokens = batch.get("input_ids", batch.get("tokens"))
    logits, aux_loss = apply_fn(
        params, tokens[:, :-1],
        rngs={"dropout": rng} if rng is not None else None,
    )
    xent, _ = _shifted_xent(logits, tokens, batch.get("mask"))
    return xent + aux_loss, {"xent": xent, "router_loss": aux_loss}


def seq2seq_loss(params, batch, rng, apply_fn):
    """Teacher-forced MT loss: predict tgt[t+1] from src + tgt[<=t];
    target positions equal to 0 are treated as padding."""
    src, tgt = batch["src"], batch["tgt"]
    logits = apply_fn(
        params, src, tgt[:, :-1],
        rngs={"dropout": rng} if rng is not None else None,
    )
    targets = tgt[:, 1:]
    losses = optax.softmax_cross_entropy_with_integer_labels(logits, targets)
    mask = (targets != 0).astype(losses.dtype)
    loss = (losses * mask).sum() / jnp.maximum(mask.sum(), 1)
    return loss, {"tokens": mask.sum()}


def masked_lm_loss(params, batch, rng, apply_fn):
    """BERT-style masked-LM: cross-entropy only at masked positions.

    Batch: ``input_ids`` [B, S] (with mask tokens substituted in),
    ``labels`` [B, S] (original token at masked positions, -100
    elsewhere — the HF ignore-index convention), optional
    ``segment_ids`` and ``attn_mask`` ([B, S] keep-mask over padding).
    """
    tokens, labels = batch["input_ids"], batch["labels"]
    logits = apply_fn(
        params, tokens,
        segment_ids=batch.get("segment_ids"),
        attn_mask=batch.get("attn_mask"),
        rngs={"dropout": rng} if rng is not None else None,
    )
    keep = labels >= 0
    losses = optax.softmax_cross_entropy_with_integer_labels(
        logits, jnp.where(keep, labels, 0)
    )
    denom = jnp.maximum(keep.sum(), 1)
    loss = (losses * keep).sum() / denom
    return loss, {"tokens": denom.astype(jnp.float32)}


def mse_loss(params, batch, rng, apply_fn):
    x = batch.get("x")
    y = batch.get("y", batch.get("label"))
    pred = apply_fn(params, x)
    loss = jnp.mean((pred - y) ** 2)
    return loss, {}


# ---------------------------------------------------------------------------
# Blockwise / vocab-sharded cross-entropy (VERDICT r3 #5)
# ---------------------------------------------------------------------------
#
# The fp32 [B,S,V] logits tensor (plus its grad twin) dominates peak HBM
# for large-vocab models (not measured on the current code).  This loss asks
# the model for post-final-norm FEATURES (return_features=True), then
# folds the LM head into the loss blockwise along the sequence under
# jax.checkpoint: peak temp is [B, block, V] instead of [B, S, V], and
# the backward rematerializes each block's logits instead of storing
# them.  With the head weight vocab-sharded over 'tensor' (the planner's
# lm_head rule), each device materializes only its vocab shard of a
# block and the log-sum-exp/correct-logit reductions psum across shards
# — correct-logit extraction uses an iota-select-sum (elementwise +
# reduce, which GSPMD lowers to a local reduce + psum) instead of
# take_along_axis (a gather that would force a full-vocab allgather).


def _head_weight(params):
    """[d_model, V] head weight from an (untied or tied) param tree."""
    if "lm_head" in params:
        return params["lm_head"]["kernel"]
    return params["embed"]["embedding"].T


def _blockwise_xent(features, head_w, targets, mask, block_size):
    """Mean next-token CE without materializing [B,S,V] logits.

    features: [B,S,d] (compute dtype); head_w: [d,V] (fp32);
    targets: [B,S] int; mask: [B,S] float or None.
    """
    b, s, d = features.shape
    if mask is None:
        mask = jnp.ones((b, s), jnp.float32)
    mask = mask.astype(jnp.float32)
    n_blocks = -(-s // block_size)
    pad = n_blocks * block_size - s
    if pad:
        features = jnp.pad(features, ((0, 0), (0, pad), (0, 0)))
        targets = jnp.pad(targets, ((0, 0), (0, pad)))
        mask = jnp.pad(mask, ((0, 0), (0, pad)))
    # [n_blocks, B, block, ...] scan layout
    f_blocks = features.reshape(b, n_blocks, block_size, d).swapaxes(0, 1)
    t_blocks = targets.reshape(b, n_blocks, block_size).swapaxes(0, 1)
    m_blocks = mask.reshape(b, n_blocks, block_size).swapaxes(0, 1)

    @jax.checkpoint
    def block_nll(f, t, m):
        logits = f.astype(jnp.float32) @ head_w  # [B, block, V]
        lse = jax.nn.logsumexp(logits, axis=-1)
        iota = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 2)
        correct = jnp.sum(
            jnp.where(iota == t[..., None], logits, 0.0), axis=-1)
        return ((lse - correct) * m).sum()

    def body(acc, inp):
        f, t, m = inp
        return acc + block_nll(f, t, m), None

    total, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32),
                            (f_blocks, t_blocks, m_blocks))
    return total / jnp.maximum(mask.sum(), 1)


def blockwise_next_token_loss(block_size: int = 512):
    """Factory: a drop-in replacement for ``next_token_loss`` that never
    materializes the full-vocab logits (see module comment above).  The
    model's ``apply`` must accept ``return_features=True`` (DecoderLM and
    MoELM do); MoE aux losses are added when the model returns them."""

    def loss_fn(params, batch, rng, apply_fn):
        tokens = batch.get("input_ids", batch.get("tokens"))
        out = apply_fn(
            params, tokens[:, :-1], return_features=True,
            rngs={"dropout": rng} if rng is not None else None,
        )
        aux_loss = None
        if isinstance(out, tuple):
            features, aux_loss = out
        else:
            features = out
        mask = batch.get("mask")
        xent = _blockwise_xent(
            features, _head_weight(params), tokens[:, 1:],
            None if mask is None else mask[:, 1:], block_size,
        )
        if aux_loss is not None:
            return xent + aux_loss, {"xent": xent, "router_loss": aux_loss}
        return xent, {}

    # consumed by AutoDistribute validation: the pipelined apply has no
    # features path (it applies the lm_head itself), so blockwise CE
    # cannot run under pipeline parallelism
    loss_fn.requires_features = True
    return loss_fn
