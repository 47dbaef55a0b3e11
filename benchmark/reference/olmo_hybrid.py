"""Plain reference for a hybrid decoder (``model_type: olmo_hybrid``, AllenAI
Olmo Hybrid): ``linear_attention`` layers, whose mixer is a gated delta rule
over a recurrent state, with a ``full_attention`` layer among every few;
the two RMSNorms of a layer on its sublayers' OUTPUTS; SwiGLU FFNs; an
untied head.

Written from the equations in float32 ``jax.numpy``: no Pallas, no cache, no
chunked form of the recurrence (it is a ``lax.scan`` over the tokens, one
state update a step), every product at ``highest`` precision.  It imports
nothing of the program under test.  Weights come from the benchmark keyed
by the paths of ``param_shapes``; a leaf may arrive in bfloat16 (the values
are the same) and is widened where it is used.  Projections, attention and
the FFN run over blocks of positions, so that a sequence of 33,792
positions fits beside 9.7 GB of held weights.

The equations (d = ``d_model``; forms as the issue that brought this
configuration wrote them down; what the source's ``config.json`` does not
say is ``assumed`` in the configuration file):

- ``h0 = E[tok]``.
- layer: ``a = h + RMS_post_attn(Mixer(h))``, ``h' = a + RMS_post_mlp(
  FFN(a))``: no norm before a sublayer (``pre_norm`` false,
  ``sandwich_norm`` true).
- Mixer of a ``full_attention`` layer: ``q = RMS(h Wq)``, ``k = RMS(h Wk)``
  over the whole projection (``qk_norm_over == "projection"``), ``v = h
  Wv``; no positional rotation (``rope_layers == "sliding"`` and no sliding
  layer: ``rope_theta`` is null at the source); plain causal softmax over
  ``n_heads`` heads of ``head_size``; ``out = o Wo``.  No biases.
- Mixer of a ``linear_attention`` layer, H = ``linear_value_heads`` heads,
  ``d_k = linear_key_head_dim``, ``d_v = linear_value_head_dim``, K =
  ``linear_conv_kernel``:
  ``q~, k~, v~ = h Wq, h Wk, h Wv``; each channel through a causal depthwise
  convolution of K taps (tap K - 1 on the token itself, no bias) and SiLU;
  ``q_h <- q_h / ||q_h|| * d_k^-1/2``, ``k_h <- k_h / ||k_h||`` (the norm
  ``sqrt(sum x^2 + 1e-6)``); ``beta = sigmoid(h Wb)`` (times 2 with
  ``linear_neg_eigval``); ``g = -exp(A_log) * softplus(h Wa + dt_bias)``,
  ``alpha = exp(g)``;
  ``S_t = alpha_t S_{t-1} + beta_t k_t (v_t - alpha_t S_{t-1}^T k_t)^T``,
  ``o_t = S_t^T q_t`` from ``S = 0``;
  ``y = RMS_{d_v}(o_t) * silu(h Wg)``; ``out = y Wo``.
- FFN: SwiGLU of width ``d_ff``.
- ``logits = RMS_final(h) W_head``.

``prec`` picks the precision of every matmul's operands and of the
recurrence's q, k and v: ``"f32"`` (the reference), ``"fp8"`` (rounded
through ``float8_e4m3fn``, one amax scale a tensor: the control), ``"bf16"``
(a diagnostic).  The state, the decay and beta stay float32 in each.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
F32 = jnp.float32
BLOCK = 512  # positions a step of a blocked map takes


# -- shapes ------------------------------------------------------------------


def plan(cfg: dict) -> list[tuple[str, str]]:
    """(parameter prefix, kind) of every layer."""
    return [(f"layers_{i}", kind) for i, kind in enumerate(cfg["layer_types"])]


def linear_dims(cfg: dict) -> tuple[int, int, int, int]:
    """(heads, d_k, d_v, taps) of a ``linear_attention`` layer."""
    return (cfg["linear_value_heads"], cfg["linear_key_head_dim"],
            cfg["linear_value_head_dim"], cfg.get("linear_conv_kernel", 4))


def param_shapes(cfg: dict) -> dict[str, tuple[int, ...]]:
    """Every parameter by path; layers are apart (``layers_0`` ..)."""
    d, H, F = cfg["d_model"], cfg["n_heads"], cfg["d_ff"]
    KV = cfg.get("n_kv_heads") or H
    hd = cfg.get("head_size") or d // H
    shapes: dict[str, tuple[int, ...]] = {
        "embed/embedding": (cfg["vocab_size"], d)}
    for name, kind in plan(cfg):
        layer = {"post_attn_norm/scale": (d,), "post_mlp_norm/scale": (d,),
                 "mlp/gate_proj/kernel": (d, F), "mlp/up_proj/kernel": (d, F),
                 "mlp/down_proj/kernel": (F, d)}
        if kind == "linear_attention":
            LH, dk, dv, K = linear_dims(cfg)
            layer.update({
                "attn/q_proj/kernel": (d, LH, dk),
                "attn/k_proj/kernel": (d, LH, dk),
                "attn/v_proj/kernel": (d, LH, dv),
                "attn/gate_proj/kernel": (d, LH, dv),
                "attn/a_proj/kernel": (d, LH), "attn/b_proj/kernel": (d, LH),
                "attn/o_proj/kernel": (LH, dv, d),
                "attn/conv": (K, LH * (2 * dk + dv)),
                "attn/A_log": (LH,), "attn/dt_bias": (LH,),
                "attn/o_norm/scale": (dv,)})
        else:
            layer.update({
                "attn/q_proj/kernel": (d, H, hd),
                "attn/k_proj/kernel": (d, KV, hd),
                "attn/v_proj/kernel": (d, KV, hd),
                "attn/o_proj/kernel": (H, hd, d),
                "attn/q_norm/scale": (H * hd,),
                "attn/k_norm/scale": (KV * hd,)})
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


def blocked(fn, *xs):
    """``fn`` over blocks of ``BLOCK`` positions of every ``xs`` [S, ...]
    (one block where the sequence is no whole number of them)."""
    S = xs[0].shape[0]
    if S % BLOCK:
        return fn(*xs)
    out = jax.lax.map(lambda b: fn(*b), tuple(
        x.reshape(S // BLOCK, BLOCK, *x.shape[1:]) for x in xs))
    return jax.tree.map(lambda y: y.reshape(S, *y.shape[2:]), out)


def swiglu(p: dict, x, prec: str):
    def block(x):
        h = jax.nn.silu(mm("sd,df->sf", x, p["mlp/gate_proj/kernel"], prec)
                        ) * mm("sd,df->sf", x, p["mlp/up_proj/kernel"], prec)
        return mm("sf,fd->sd", h, p["mlp/down_proj/kernel"], prec)

    return blocked(block, x)


def full_attention(p: dict, x, cfg: dict, prec: str):
    """Plain causal attention on x [S, d], a block of queries at a time: a
    block's scores over all the keys are [H, block, S]."""
    S = x.shape[0]
    H = cfg["n_heads"]
    KV = cfg.get("n_kv_heads") or H
    eps = cfg["norm_eps"]

    def project(x):
        q = mm("sd,dhk->shk", x, p["attn/q_proj/kernel"], prec)
        k = mm("sd,dhk->shk", x, p["attn/k_proj/kernel"], prec)
        v = mm("sd,dhk->shk", x, p["attn/v_proj/kernel"], prec)
        # the norm runs over a token's whole projection, all heads at once
        q = rms(q.reshape(len(x), -1), p["attn/q_norm/scale"], eps)
        k = rms(k.reshape(len(x), -1), p["attn/k_norm/scale"], eps)
        return q.reshape(len(x), H, -1), k.reshape(len(x), KV, -1), v

    q, k, v = blocked(project, x)
    k, v = jnp.repeat(k, H // KV, axis=1), jnp.repeat(v, H // KV, axis=1)
    hd = q.shape[-1]
    qb = math.gcd(S, 256)
    ti = jnp.arange(S)[None, :]

    def block(args):
        qi, q_blk = args  # [qb], [qb, H, hd]
        s = mm("qhk,thk->hqt", q_blk, k, prec) / math.sqrt(hd)
        w = jax.nn.softmax(jnp.where((ti <= qi[:, None])[None], s, -jnp.inf),
                           axis=-1)
        o = mm("hqt,thk->qhk", w, v, prec)
        return mm("qhk,hkd->qd", o, p["attn/o_proj/kernel"], prec)

    return jax.lax.map(block, (jnp.arange(S).reshape(-1, qb), q.reshape(
        S // qb, qb, H, hd))).reshape(S, -1)


def delta_rule(q, k, v, g, beta):
    """The recurrence, a token a scan step from a state of zeros: ``q, k``
    [S, H, d_k], ``v`` [S, H, d_v], ``g, beta`` [S, H].  [S, H, d_v]."""
    def step(S, x):
        q, k, v, g, beta = x
        a = jnp.exp(g)
        Sk = jnp.einsum("hkv,hk->hv", S, k, precision=HI)
        u = beta[:, None] * (v - a[:, None] * Sk)
        S = a[:, None, None] * S + k[:, :, None] * u[:, None, :]
        return S, jnp.einsum("hkv,hk->hv", S, q, precision=HI)

    S0 = jnp.zeros((q.shape[1], q.shape[2], v.shape[2]), F32)
    return jax.lax.scan(step, S0, (q, k, v, g, beta))[1]


def linear_attention(p: dict, x, cfg: dict, prec: str):
    """The gated-delta-rule mixer on x [S, d].  q, k and v are projected,
    convolved and shaped one after the other (the filter's columns are q's,
    then k's, then v's), so that only one of them is ever held twice."""
    S = x.shape[0]
    H, dk, dv, K = linear_dims(cfg)
    w = p["attn/conv"].astype(F32)

    def path(name: str, lo: int, width: int):
        pre = blocked(lambda x: mm(
            "sd,dhk->shk", x, p[f"attn/{name}_proj/kernel"], prec).reshape(
                len(x), -1), x)
        pad = jnp.concatenate([jnp.zeros((K - 1, pre.shape[1]), F32), pre])
        y = jax.nn.silu(sum(w[i, lo:lo + H * width] * pad[i:i + S]
                            for i in range(K)))
        return y.reshape(S, H, width)

    def unit(x):
        return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)

    def gates(x):
        beta = jax.nn.sigmoid(mm("sd,dh->sh", x, p["attn/b_proj/kernel"],
                                 prec))
        if cfg.get("linear_neg_eigval"):
            beta = 2.0 * beta
        g = -jnp.exp(p["attn/A_log"].astype(F32)) * jax.nn.softplus(
            mm("sd,dh->sh", x, p["attn/a_proj/kernel"], prec)
            + p["attn/dt_bias"].astype(F32))
        return g, beta

    g, beta = blocked(gates, x)
    o = delta_rule(_round(unit(path("q", 0, dk)) * dk ** -0.5, prec),
                   _round(unit(path("k", H * dk, dk)), prec),
                   _round(path("v", 2 * H * dk, dv), prec), g, beta)

    def out(o, x):
        y = rms(o, p["attn/o_norm/scale"], cfg["norm_eps"]) * jax.nn.silu(
            mm("sd,dhk->shk", x, p["attn/gate_proj/kernel"], prec))
        return mm("shk,hkd->sd", y, p["attn/o_proj/kernel"], prec)

    return blocked(out, o, x)


def layer(p: dict, x, cfg: dict, kind: str, prec: str):
    """One layer on x [S, d]; ``p`` holds its leaves without the prefix."""
    eps = cfg["norm_eps"]
    mixer = (linear_attention if kind == "linear_attention"
             else full_attention)
    x = x + rms(mixer(p, x, cfg, prec), p["post_attn_norm/scale"], eps)
    return x + rms(swiglu(p, x, prec), p["post_mlp_norm/scale"], eps)


# -- forward -------------------------------------------------------------------


def cfg_key(cfg: dict) -> tuple:
    def atom(v):
        return tuple(v) if isinstance(v, (list, tuple)) else v

    return tuple(sorted((k, atom(v)) for k, v in cfg.items()
                        if isinstance(v, (int, float, str, bool, type(None),
                                          list, tuple))))


@functools.partial(jax.jit, static_argnames=("key", "kind", "prec"))
def _layer(p, x, key, kind, prec):
    return layer(p, x, dict(key), kind, prec)


@jax.jit
def _embed(table, tokens):
    return table[tokens].astype(F32)


@functools.partial(jax.jit, static_argnames=("n", "eps", "prec"))
def _head(x, lo, scale, kernel, n, eps, prec):
    x = jax.lax.dynamic_slice_in_dim(x, lo, n, axis=0)
    return mm("sd,dv->sv", rms(x, scale, eps), kernel, prec)


def sub(params: dict, name: str) -> dict:
    """One layer's leaves, without the prefix."""
    return {k[len(name) + 1:]: v for k, v in params.items()
            if k.startswith(name + "/")}


def forward_logits_at(params: dict, cfg: dict, tokens, lo, n: int,
                      prec: str = "f32"):
    """Logits [n, V] at positions ``lo .. lo + n`` of a full forward pass
    over ONE sequence ``tokens`` [S], a layer at a time.  ``n`` is static
    (a compiled shape), ``lo`` is not."""
    key = cfg_key(cfg)
    x = _embed(params["embed/embedding"], jnp.asarray(tokens, jnp.int32))
    for name, kind in plan(cfg):
        x = _layer(sub(params, name), x, key, kind, prec)
    return _head(x, jnp.int32(lo), params["final_norm/scale"],
                 params["lm_head/kernel"], n, cfg["norm_eps"], prec)


def forward_logits(params: dict, cfg: dict, tokens, prec: str = "f32"):
    """Logits [B, S, V] of a full forward pass over ``tokens`` [B, S]."""
    tokens = jnp.asarray(tokens, jnp.int32)
    return jnp.stack([forward_logits_at(params, cfg, row, 0, row.shape[0],
                                        prec) for row in tokens])
