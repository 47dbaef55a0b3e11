"""Plain reference for a hybrid decoder of Kimi Delta Attention and latent
attention with sigmoid-routed experts (``model_type: kimi_linear``, Moonshot AI
Kimi-Linear-48B-A3B): three ``linear_attention`` layers, whose mixer is a
gated delta rule with a decay a KEY CHANNEL, to one ``latent_attention``
layer (the DeepSeek-V3 mixer without a query bottleneck and without any
rotation); a dense SwiGLU in the first layer and experts beside a shared one
in the others; an untied head.

Written from the equations in float32 ``jax.numpy``: no Pallas, no cache, no
chunked form of the recurrence (it is a ``lax.scan`` over the tokens, one
state update a step), no absorption (every head's keys and values are
expanded from the latent), no sorting or grouping of tokens, every product at
``highest`` precision.  It imports nothing of the program under test.
Weights come from the benchmark keyed by the paths of ``param_shapes``; a
leaf may arrive in bfloat16 (the values are the same) and is widened where it
is used.  Projections, attention (a head and a block of queries at a time)
and the FFNs run over blocks of positions, so that a sequence of 36,864
positions fits beside 8.5 GB of held weights.

No network here: the published modelling code is not at hand, and where it
differs from what follows, IT wins; every departure that is known or
possible is an entry of ``assumed`` in the configuration file.

The equations (d = ``d_model``):

- ``h0 = E[tok]``.
- layer: ``a = h + Mixer(RMS_in(h))``, ``h' = a + FFN(RMS_pre_mlp(a))``.
- Mixer of a ``linear_attention`` layer (KDA), H = ``linear_value_heads``
  heads, ``d_k = linear_key_head_dim``, ``d_v = linear_value_head_dim``, K =
  ``linear_conv_kernel``, x the normed input:
  ``q~, k~, v~ = x Wq, x Wk, x Wv``; each channel through a causal depthwise
  convolution of K taps (tap K - 1 on the token itself, no bias) and SiLU;
  ``q_h <- q_h / ||q_h|| * d_k^-1/2``, ``k_h <- k_h / ||k_h||`` (the norm
  ``sqrt(sum x^2 + 1e-6)``); ``beta = sigmoid(x Wb)`` [H];
  ``g = -exp(A_log[h]) * softplus(x Wf_down Wf_up + dt_bias)`` in R^{H x d_k},
  a log-decay a channel (``A_log`` [H], ``dt_bias`` [H d_k]), ``a = exp(g)``;

      S_t = (I - beta_t k_t k_t^T) Diag(a_t) S_{t-1} + beta_t k_t v_t^T
          = Diag(a_t) S_{t-1} + beta_t k_t (v_t - (Diag(a_t) S_{t-1})^T k_t)^T
      o_t = S_t^T q_t                                  from S = 0, float32;

  ``y = RMS_{d_v}(o_t) * sigmoid(x Wg_down Wg_up)``; ``out = y Wo``.
- Mixer of a ``latent_attention`` layer, H = ``n_heads``, r =
  ``latent_kv_rank``, n / p / v = ``latent_nope_head_dim`` /
  ``latent_rope_head_dim`` / ``latent_value_head_dim``: ``q = x W_q``, a head
  ``[q_nope (n), q_r (p)]`` (no bottleneck); ``[c (r), k_r (p)] = x W_kva``;
  ``c <- RMS(c)``; a head's ``[k_nope (n), v (v)] = c W_kvb``; NO rotation of
  ``q_r`` or ``k_r`` (``mla_use_nope``); ``score_h(t, s) = (q_nope . k_nope +
  q_r . k_r) / sqrt(n + p)``, causal softmax, ``o_h = sum_s p v``, ``out =
  concat_h(o_h) W_o``.  No biases.
- FFN of the first ``n_dense_layers`` layers: SwiGLU of width ``d_ff``.
- FFN of the others: ``s = sigmoid(x W_r)`` over all ``experts_published``;
  the ``experts_per_token`` largest of ``s + b`` are chosen; ``w =
  s[chosen]``, ``w <- w / (sum w + 1e-20)``, ``w <- route_scale * w``; ``y =
  Shared(x) + sum_e w_e Expert_e(x)``, every expert a SwiGLU of
  ``expert_d_ff``, the shared one unweighted.  No capacity, no dropped token.
- ``logits = RMS_final(h) W_head``.

The chip's share of an expert-parallel deployment, as ``reference/afmoe.py``
takes it: the router keeps its ``experts_published`` outputs and its top k;
of the chosen experts only ``first_expert .. first_expert + experts_held`` are
held, and what the others would add is left out (here as in the program).

``prec`` picks the precision of every product's operands and of the
recurrence's q, k and v: ``"f32"`` (the reference), ``"fp8"`` (rounded through
``float8_e4m3fn``, one amax scale a tensor: the control), ``"bf16"`` (a
diagnostic).  The state, the decays and beta stay float32 in each.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
F32 = jnp.float32
BLOCK = 512  # positions a step of a blocked map takes
QUERIES = 512  # queries a step of the attention takes
SEGMENTS = 8  # runs of query blocks, each over the keys up to its end


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


def linear_dims(cfg: dict) -> tuple[int, int, int, int]:
    """(heads, d_k, d_v, taps) of a ``linear_attention`` layer."""
    return (cfg["linear_value_heads"], cfg["linear_key_head_dim"],
            cfg["linear_value_head_dim"], cfg.get("linear_conv_kernel", 4))


def latent_dims(cfg: dict) -> tuple[int, int, int, int]:
    """(r, n, p, v) of a ``latent_attention`` layer."""
    return (cfg["latent_kv_rank"], cfg["latent_nope_head_dim"],
            cfg["latent_rope_head_dim"], cfg["latent_value_head_dim"])


def param_shapes(cfg: dict) -> dict[str, tuple[int, ...]]:
    """Every parameter by path; layers are apart (``layers_0`` ..)."""
    d, H = cfg["d_model"], cfg["n_heads"]
    shapes: dict[str, tuple[int, ...]] = {
        "embed/embedding": (cfg["vocab_size"], d)}
    for name, kind, sparse in plan(cfg):
        layer = {"attn_norm/scale": (d,), "mlp_norm/scale": (d,)}
        if kind == "linear_attention":
            LH, dk, dv, K = linear_dims(cfg)
            rf, rg = cfg["linear_decay_rank"], cfg["linear_gate_rank"]
            layer.update({
                "attn/q_proj/kernel": (d, LH, dk),
                "attn/k_proj/kernel": (d, LH, dk),
                "attn/v_proj/kernel": (d, LH, dv),
                "attn/b_proj/kernel": (d, LH),
                "attn/f_a_proj/kernel": (d, rf),
                "attn/f_b_proj/kernel": (rf, LH, dk),
                "attn/g_a_proj/kernel": (d, rg),
                "attn/g_b_proj/kernel": (rg, LH, dv),
                "attn/o_proj/kernel": (LH, dv, d),
                "attn/conv": (K, LH * (2 * dk + dv)),
                "attn/A_log": (LH,), "attn/dt_bias": (LH * dk,),
                "attn/o_norm/scale": (dv,)})
        else:
            r, n, p, v = latent_dims(cfg)
            layer.update({
                "attn/q_proj/kernel": (d, H, n + p),
                "attn/kv_a_proj/kernel": (d, r + p),
                "attn/kv_a_norm/scale": (r,),
                "attn/kv_b_proj/kernel": (r, H, n + v),
                "attn/o_proj/kernel": (H, v, d)})
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


def blocked(fn, *xs):
    """``fn`` over blocks of ``BLOCK`` positions of ``xs`` [S, ...] (S a
    multiple of the block, or shorter than one)."""
    S = xs[0].shape[0]
    b = math.gcd(S, BLOCK)
    out = jax.lax.map(lambda a: fn(*a), tuple(
        x.reshape(S // b, b, *x.shape[1:]) for x in xs))
    return jax.tree.map(lambda y: y.reshape(S, *y.shape[2:]), out)


def swiglu(x, gate, up, down, prec: str):
    h = jax.nn.silu(mm("sd,df->sf", x, gate, prec)) * mm("sd,df->sf", x, up,
                                                         prec)
    return mm("sf,fd->sd", h, down, prec)


# -- the KDA mixer -----------------------------------------------------------


def delta_rule(q, k, v, g, beta):
    """The recurrence, a token a scan step from a state of zeros: ``q, k,
    g`` [S, H, d_k], ``v`` [S, H, d_v], ``beta`` [S, H].  [S, H, d_v]."""
    def step(S, x):
        q, k, v, g, beta = x
        S = jnp.exp(g)[:, :, None] * S  # Diag(a_t) S_{t-1}: a row a channel
        u = beta[:, None] * (v - jnp.einsum("hkv,hk->hv", S, k, precision=HI))
        S = S + k[:, :, None] * u[:, None, :]
        return S, jnp.einsum("hkv,hk->hv", S, q, precision=HI)

    S0 = jnp.zeros((q.shape[1], q.shape[2], v.shape[2]), F32)
    return jax.lax.scan(step, S0, (q, k, v, g, beta))[1]


def linear_attention(p: dict, x, cfg: dict, prec: str):
    """The KDA mixer on the normed x [S, d].  q, k and v are projected,
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
        f = mm("sr,rhk->shk", mm("sd,dr->sr", x, p["attn/f_a_proj/kernel"],
                                 prec), p["attn/f_b_proj/kernel"], prec)
        g = -jnp.exp(p["attn/A_log"].astype(F32))[:, None] * jax.nn.softplus(
            f + p["attn/dt_bias"].astype(F32).reshape(H, dk))
        return g, beta

    g, beta = blocked(gates, x)
    o = delta_rule(_round(unit(path("q", 0, dk)) * dk ** -0.5, prec),
                   _round(unit(path("k", H * dk, dk)), prec),
                   _round(path("v", 2 * H * dk, dv), prec), g, beta)

    def out(o, x):
        gate = mm("sr,rhk->shk", mm("sd,dr->sr", x, p["attn/g_a_proj/kernel"],
                                    prec), p["attn/g_b_proj/kernel"], prec)
        y = rms(o, p["attn/o_norm/scale"], cfg["norm_eps"]) * jax.nn.sigmoid(
            gate)
        return mm("shk,hkd->sd", y, p["attn/o_proj/kernel"], prec)

    return blocked(out, o, x)


# -- the latent mixer --------------------------------------------------------


def latent_attention(p: dict, x, cfg: dict, prec: str):
    """The latent mixer on the normed x [S, d]: the projections in blocks of
    positions, then a head at a time (its keys and values expanded from the
    latent) and a block of queries at a time inside a head.  No rotation."""
    S = x.shape[0]
    r, n, rot, _ = latent_dims(cfg)
    eps = cfg["norm_eps"]
    pos = jnp.arange(S)

    def project(x):
        q = mm("sd,dhk->shk", x, p["attn/q_proj/kernel"], prec)
        kv = mm("sd,dr->sr", x, p["attn/kv_a_proj/kernel"], prec)
        return (q[..., :n], q[..., n:],
                rms(kv[:, :r], p["attn/kv_a_norm/scale"], eps), kv[:, r:])

    q_nope, q_r, c, k_r = blocked(project, x)
    qb = math.gcd(S, QUERIES)
    scale = 1.0 / math.sqrt(n + rot)
    n_blocks = S // qb
    # causal: the queries of each of up to SEGMENTS runs of whole blocks see
    # the keys up to their run's end, and none behind it is multiplied
    cuts = sorted({(j * n_blocks // SEGMENTS) * qb
                   for j in range(1, SEGMENTS)} | {0, S})

    def head(args):
        qn, qr, w_kvb = args  # [S, n], [S, p], [r, n + v]
        kv = blocked(lambda c: mm("sr,rk->sk", c, w_kvb, prec), c)
        k_nope, v = kv[:, :n], kv[:, n:]

        def run(lo, hi):
            def block(a):
                qi, qn, qr = a
                s = (mm("qk,tk->qt", qn, k_nope[:hi], prec)
                     + mm("qk,tk->qt", qr, k_r[:hi], prec)) * scale
                w = jax.nn.softmax(jnp.where(
                    pos[None, :hi] <= qi[:, None], s, -jnp.inf), axis=-1)
                return mm("qt,tk->qk", w, v[:hi], prec)

            return jax.lax.map(block, (
                pos[lo:hi].reshape(-1, qb), qn[lo:hi].reshape(-1, qb, n),
                qr[lo:hi].reshape(-1, qb, rot))).reshape(hi - lo, -1)

        return jnp.concatenate([run(lo, hi)
                                for lo, hi in zip(cuts, cuts[1:])])

    o = jax.lax.map(head, (
        jnp.moveaxis(q_nope, 1, 0), jnp.moveaxis(q_r, 1, 0),
        jnp.moveaxis(p["attn/kv_b_proj/kernel"], 1, 0)))  # [H, S, v]
    return blocked(lambda o: mm("shk,hkd->sd", o, p["attn/o_proj/kernel"],
                                prec), jnp.moveaxis(o, 0, 1))


# -- the FFNs ----------------------------------------------------------------


def route(p: dict, x, cfg: dict, prec: str):
    """``(chosen [S, k], weights [S, k])`` over the published experts."""
    s = jax.nn.sigmoid(mm("sd,de->se", x, p["mlp/router/kernel"], prec))
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
    eps = cfg["norm_eps"]
    mixer = (linear_attention if kind == "linear_attention"
             else latent_attention)
    x = x + mixer(p, rms(x, p["attn_norm/scale"], eps), cfg, prec)
    return x + blocked(
        lambda x: ffn(p, rms(x, p["mlp_norm/scale"], eps), cfg, sparse, prec),
        x)


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
    for name, kind, sparse in plan(cfg):
        x = _layer(sub(params, name), x, key, kind, sparse, prec)
    return _head(x, jnp.int32(lo), params["final_norm/scale"],
                 params["lm_head/kernel"], n, cfg["norm_eps"], prec)


def forward_logits(params: dict, cfg: dict, tokens, prec: str = "f32"):
    """Logits [B, S, V] of a full forward pass over ``tokens`` [B, S]."""
    tokens = jnp.asarray(tokens, jnp.int32)
    return jnp.stack([forward_logits_at(params, cfg, row, 0, row.shape[0],
                                        prec) for row in tokens])
