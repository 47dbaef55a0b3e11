"""Plain reference for decoders whose layers differ (``model_type: afmoe``,
Arcee Trinity): window and full attention mixed, rotary positions on the
window layers only, QK-norm, a sigmoid output gate, four RMSNorms a layer,
dense FFNs first and then sigmoid-routed experts beside a shared expert.

Written from the equations in float32 ``jax.numpy``: no Pallas, no cache, no
sorting or grouping of tokens, every product at ``highest`` precision.  It
imports nothing of the program under test.  Weights come from
``benchmark/lib/weights.py`` keyed by the paths of ``param_shapes``; a leaf
may arrive in bfloat16 (the values are the same) and is widened where it is
used, a layer and an expert at a time, so that no float32 copy of the expert
stacks is ever held.

The equations (d = ``d_model``, H query and KV key/value heads of
``head_size``; forms as in the published ``afmoe`` modelling code):

- ``h0 = E[tok] * sqrt(d)`` (``embed_scale``).
- layer: ``a = h + RMS_post_attn(Attn(RMS_in(h)))``,
  ``h' = a + RMS_post_mlp(FFN(RMS_pre_mlp(a)))`` (``sandwich_norm``).
- Attn(x): ``q = RMS_q(x Wq)``, ``k = RMS_k(x Wk)`` over ``head_size``
  (``qk_norm``), ``v = x Wv``, ``g = sigmoid(x Wg)`` (``attn_gate``).  A
  ``sliding_attention`` layer rotates q and k (rotate-half, ``rope_theta``)
  and key j is visible to query i iff ``i - window < j <= i``; a
  ``full_attention`` layer has no positional rotation (``rope_layers ==
  "sliding"``) and a plain causal mask.
  ``out = (softmax(q k^T / sqrt(head_size)) v * g) Wo``.  No biases.
- FFN of the first ``n_dense_layers`` layers: SwiGLU of width ``d_ff``.
- FFN of the others: ``s = sigmoid(x Wr)``; the ``experts_per_token``
  largest of ``s + b`` are chosen; ``w = s[chosen]``,
  ``w <- w / (sum w + 1e-20)`` (``route_norm``), ``w <- route_scale * w``;
  ``y = Shared(x) + sum_e w_e Expert_e(x)``, every expert a SwiGLU of
  ``expert_d_ff``.  No capacity, no dropped token.
- ``logits = RMS_final(h) W_head``.

The chip's share of an expert-parallel deployment: the router keeps its
``experts_published`` outputs and its top k; of the chosen experts only
``first_expert .. first_expert + experts_held`` are held, and what the
others would add is left out (here as in the program).

Departures from the published code: none known.  Not read from the config
and therefore ``assumed`` in the configuration file: rotate-half pairing of
the rotary dimensions, the order norm-then-rotate of q and k, the 1e-20 in
``route_norm``.

``prec`` picks the precision of every product: ``"f32"`` (the reference),
``"fp8"`` (both operands rounded through ``float8_e4m3fn``, one amax scale a
tensor: the control), ``"bf16"`` (a diagnostic).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
F32 = jnp.float32


# -- shapes ------------------------------------------------------------------


def plan(cfg: dict) -> list[tuple[str, str, bool]]:
    """(parameter prefix, kind, has an expert FFN) of every layer."""
    n_dense = cfg.get("n_dense_layers")
    n_dense = cfg["n_layers"] if n_dense is None else n_dense
    return [(f"layers_{i}", kind, i >= n_dense)
            for i, kind in enumerate(cfg["layer_types"])]


def held(cfg: dict) -> int:
    n = cfg.get("experts_held")
    return cfg["experts_published"] if n is None else n


def param_shapes(cfg: dict) -> dict[str, tuple[int, ...]]:
    """Every parameter by path; layers are apart (``layers_0`` ..)."""
    d, H = cfg["d_model"], cfg["n_heads"]
    KV = cfg.get("n_kv_heads") or H
    hd = cfg.get("head_size") or d // H
    shapes: dict[str, tuple[int, ...]] = {
        "embed/embedding": (cfg["vocab_size"], d)}
    for name, _, sparse in plan(cfg):
        layer = {
            "attn_norm/scale": (d,), "mlp_norm/scale": (d,),
            "attn/q_proj/kernel": (d, H, hd),
            "attn/k_proj/kernel": (d, KV, hd),
            "attn/v_proj/kernel": (d, KV, hd),
            "attn/o_proj/kernel": (H, hd, d),
        }
        if cfg.get("sandwich_norm"):
            layer.update({"post_attn_norm/scale": (d,),
                          "post_mlp_norm/scale": (d,)})
        if cfg.get("qk_norm"):
            layer.update({"attn/q_norm/scale": (hd,),
                          "attn/k_norm/scale": (hd,)})
        if cfg.get("attn_gate"):
            layer["attn/gate_proj/kernel"] = (d, H, hd)
        if sparse:
            E, f = cfg["experts_published"], cfg["expert_d_ff"]
            layer.update({
                "mlp/router/kernel": (d, E), "mlp/router/e_bias": (E,),
                "mlp/experts_gate": (held(cfg), d, f),
                "mlp/experts_up": (held(cfg), d, f),
                "mlp/experts_down": (held(cfg), f, d)})
            if cfg.get("shared_experts"):
                fs = cfg["shared_experts"] * f
                layer.update({"mlp/shared/gate_proj/kernel": (d, fs),
                              "mlp/shared/up_proj/kernel": (d, fs),
                              "mlp/shared/down_proj/kernel": (fs, d)})
        else:
            F = cfg["d_ff"]
            layer.update({"mlp/gate_proj/kernel": (d, F),
                          "mlp/up_proj/kernel": (d, F),
                          "mlp/down_proj/kernel": (F, d)})
        for k, s in layer.items():
            shapes[f"{name}/{k}"] = s
    shapes["final_norm/scale"] = (d,)
    shapes["lm_head/kernel"] = (d, cfg["vocab_size"])
    return shapes


# -- arithmetic --------------------------------------------------------------


def _round(x, prec: str):
    x = x.astype(F32)
    if prec == "f32":
        return x
    if prec == "bf16":
        return x.astype(jnp.bfloat16).astype(F32)
    if prec == "fp8":
        # one scale per tensor, to the format's largest finite value
        fp8 = jnp.float8_e4m3fn
        s = float(jnp.finfo(fp8).max) / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
        return (x * s).astype(fp8).astype(F32) / s
    raise ValueError(f"unknown precision {prec!r}")


def mm(spec: str, a, b, prec: str):
    return jnp.einsum(spec, _round(a, prec), _round(b, prec), precision=HI,
                      preferred_element_type=F32)


def rms(x, scale, eps: float):
    y = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
    return y * scale.astype(F32)


def rope(x, theta: float):
    """Rotate-half RoPE on [S, H, hd] at positions 0 .. S."""
    hd = x.shape[-1]
    freqs = 1.0 / (theta ** (np.arange(0, hd, 2, dtype=np.float32) / hd))
    ang = jnp.arange(x.shape[0], dtype=F32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def swiglu(x, gate, up, down, prec: str):
    h = jax.nn.silu(mm("sd,df->sf", x, gate, prec)) * mm("sd,df->sf", x, up,
                                                         prec)
    return mm("sf,fd->sd", h, down, prec)


def attention(p: dict, x, cfg: dict, kind: str, prec: str):
    """Attn(x) on x [S, d], in blocks of queries: a block's scores over all
    the keys are [H, block, S]."""
    S = x.shape[0]
    H = cfg["n_heads"]
    KV = cfg.get("n_kv_heads") or H
    q = mm("sd,dhk->shk", x, p["attn/q_proj/kernel"], prec)
    k = mm("sd,dhk->shk", x, p["attn/k_proj/kernel"], prec)
    v = mm("sd,dhk->shk", x, p["attn/v_proj/kernel"], prec)
    if cfg.get("qk_norm"):
        q = rms(q, p["attn/q_norm/scale"], cfg["norm_eps"])
        k = rms(k, p["attn/k_norm/scale"], cfg["norm_eps"])
    sliding = kind == "sliding_attention"
    if cfg["pos"] == "rope" and (sliding
                                 or cfg.get("rope_layers", "all") == "all"):
        q, k = rope(q, cfg["rope_theta"]), rope(k, cfg["rope_theta"])
    k, v = jnp.repeat(k, H // KV, axis=1), jnp.repeat(v, H // KV, axis=1)
    hd = q.shape[-1]
    qb = math.gcd(S, 256)
    ti = jnp.arange(S)[None, :]

    def block(args):
        qi, q_blk = args  # [qb], [qb, H, hd]
        s = mm("qhk,thk->hqt", q_blk, k, prec) / math.sqrt(hd)
        ok = ti <= qi[:, None]
        if sliding:
            ok &= ti > qi[:, None] - cfg["sliding_window"]
        w = jax.nn.softmax(jnp.where(ok[None], s, -jnp.inf), axis=-1)
        return mm("hqt,thk->qhk", w, v, prec)

    o = jax.lax.map(block, (jnp.arange(S).reshape(-1, qb),
                            q.reshape(S // qb, qb, H, hd)))
    o = o.reshape(S, H, hd)
    if cfg.get("attn_gate"):
        o = o * jax.nn.sigmoid(
            mm("sd,dhk->shk", x, p["attn/gate_proj/kernel"], prec))
    return mm("shk,hkd->sd", o, p["attn/o_proj/kernel"], prec)


def route(p: dict, x, cfg: dict, prec: str):
    """``(chosen [S, k], weights [S, k])`` over the published experts."""
    logits = mm("sd,de->se", x, p["mlp/router/kernel"], prec)
    if cfg.get("score_func", "sigmoid") == "sigmoid":
        s = jax.nn.sigmoid(logits)
    else:
        s = jax.nn.softmax(logits, axis=-1)
    _, chosen = jax.lax.top_k(s + p["mlp/router/e_bias"].astype(F32),
                              cfg["experts_per_token"])
    w = jnp.take_along_axis(s, chosen, axis=-1)
    if cfg.get("route_norm", True):
        w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
    return chosen, w * cfg.get("route_scale", 1.0)


def routed(p: dict, x, cfg: dict, prec: str):
    """``sum_e w_e Expert_e(x)`` over the chosen experts that are held:
    every held expert on every row, weighted by 0 where it was not chosen."""
    chosen, w = route(p, x, cfg, prec)
    first = cfg.get("first_expert", 0)

    def one(y, ew):
        e, gate, up, down = ew
        w_e = jnp.sum(jnp.where(chosen == first + e, w, 0.0), -1)
        return y + w_e[:, None] * swiglu(x, gate, up, down, prec), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(x), (
        jnp.arange(held(cfg)), p["mlp/experts_gate"], p["mlp/experts_up"],
        p["mlp/experts_down"]))
    return y


def shared(p: dict, x, prec: str):
    return swiglu(x, p["mlp/shared/gate_proj/kernel"],
                  p["mlp/shared/up_proj/kernel"],
                  p["mlp/shared/down_proj/kernel"], prec)


def ffn(p: dict, x, cfg: dict, sparse: bool, prec: str):
    if not sparse:
        return swiglu(x, p["mlp/gate_proj/kernel"], p["mlp/up_proj/kernel"],
                      p["mlp/down_proj/kernel"], prec)
    y = routed(p, x, cfg, prec)
    if cfg.get("shared_experts"):
        y = y + shared(p, x, prec)
    return y


def layer(p: dict, x, cfg: dict, kind: str, sparse: bool, prec: str):
    """One layer on x [S, d]; ``p`` holds its leaves without the prefix."""
    eps, sandwich = cfg["norm_eps"], cfg.get("sandwich_norm")
    h = attention(p, rms(x, p["attn_norm/scale"], eps), cfg, kind, prec)
    if sandwich:
        h = rms(h, p["post_attn_norm/scale"], eps)
    x = x + h
    h = ffn(p, rms(x, p["mlp_norm/scale"], eps), cfg, sparse, prec)
    if sandwich:
        h = rms(h, p["post_mlp_norm/scale"], eps)
    return x + h


# -- forward -------------------------------------------------------------------


def cfg_key(cfg: dict) -> tuple:
    def atom(v):
        return tuple(v) if isinstance(v, (list, tuple)) else v

    return tuple(sorted((k, atom(v)) for k, v in cfg.items()
                        if isinstance(v, (int, float, str, bool, type(None),
                                          list, tuple))))


@functools.partial(jax.jit, static_argnames=("key", "kind", "sparse", "prec"))
def _layer(p, x, key, kind, sparse, prec):
    return layer(p, x, dict(key), kind, sparse, prec)


@functools.partial(jax.jit, static_argnames=("scale",))
def _embed(table, tokens, scale):
    return table[tokens].astype(F32) * scale


@functools.partial(jax.jit, static_argnames=("eps", "prec"))
def _head(x, scale, kernel, eps, prec):
    return mm("sd,dv->sv", rms(x, scale, eps), kernel, prec)


def sub(params: dict, name: str) -> dict:
    """One layer's leaves, without the prefix."""
    return {k[len(name) + 1:]: v for k, v in params.items()
            if k.startswith(name + "/")}


def forward_logits(params: dict, cfg: dict, tokens, prec: str = "f32"):
    """Logits [B, S, V] of a full forward pass over ``tokens`` [B, S], a
    sequence and a layer at a time."""
    key = cfg_key(cfg)
    scale = math.sqrt(cfg["d_model"]) if cfg.get("embed_scale") else 1.0
    out = []
    for row in jnp.asarray(tokens, jnp.int32):
        x = _embed(params["embed/embedding"], row, scale)
        for name, kind, sparse in plan(cfg):
            x = _layer(sub(params, name), x, key, kind, sparse, prec)
        out.append(_head(x, params["final_norm/scale"],
                         params["lm_head/kernel"], cfg["norm_eps"], prec))
    # one sequence: its logits as they are (13k x 25k is 1.3 GB; no copy)
    return out[0][None] if len(out) == 1 else jnp.stack(out)
