"""Plain reference for a hybrid decoder of gated grouped-query attention and
Kimi Delta Attention with sigmoid-routed experts in EVERY layer
(``model_type: solar_open2``, Upstage Solar Open 2 250B-A15B): a period of
one ``full_attention`` layer (softmax attention of 64 query heads over 8
key/value heads, NO rotation, a sigmoid gate a channel on its output) and
three ``linear_attention`` layers (a gated delta rule whose decay is a vector
a head and whose write strength ``beta`` reaches 2); the attention layer comes
FIRST, over the bare embeddings; no dense FFN anywhere; an untied head.

Written from the equations in float32 ``jax.numpy``: no Pallas, no cache, no
chunked form of the recurrence (a ``lax.scan`` over the tokens, one state
update a step), no sorting or grouping of tokens by expert, every product at
``highest`` precision.  It imports nothing of the program under test and
nothing of the other references.  Weights come from the benchmark keyed by the
paths of ``param_shapes``; a leaf may arrive in bfloat16 (the values are the
same) and is widened where it is used.

So that 36,864 positions fit beside 7 GB of held weights, nothing of a whole
layer's width is ever held for the whole sequence but the stream ``[S, d]``:
what acts on a row alone runs over blocks of ``ROWS`` positions; the attention
layer runs a key/value head at a time (its 8 query heads with it) and a block
of ``QUERIES`` queries at a time inside one, every block against ALL the keys
under the causal mask; a KDA layer runs ``HEAD_GROUP`` heads at a time (a
head's projections, filter, norms, decay, state, gate and its rows of ``W_o``
are its own, so the layer's output is the sum of the groups').

No network here: the published modelling code is not at hand, and where it
differs from what follows, IT wins; every departure that is known or possible
is an entry of ``assumed`` in the configuration file.  Departures from the
published description: none known.

The equations (d = ``d_model``; x the layer's normed input):

- ``h0 = E[tok]``; layer: ``a = h + Mixer(RMS_in(h))``, ``h' = a +
  FFN(RMS_pre_mlp(a))``; ``logits = RMS_final(h) W_head``.  RMSNorm with a
  gain, eps ``norm_eps``; no bias anywhere.
- ``full_attention``: ``q = x W_q`` [H, c], ``k = x W_k``, ``v = x W_v``
  [KV, c] (H = ``n_heads``, KV = ``n_kv_heads``, c = ``head_size``); nothing
  is rotated and nothing normed; query head h reads KV head ``h // (H /
  KV)``; ``p = softmax_{s <= t}(q_t . k_s / sqrt(c))``, ``o_t = sum_s p
  v_s``; ``out = (o * sigmoid(x W_g)) W_o`` with ``W_g`` d -> H c, one gate
  a channel.
- ``linear_attention`` (KDA), H = ``linear_value_heads`` heads of ``d_k`` /
  ``d_v``, K = ``linear_conv_kernel``: ``q~, k~, v~ = x Wq, x Wk, x Wv``;
  each channel through a causal depthwise filter of K taps (tap K - 1 on the
  token itself, no bias) and SiLU; ``q_h <- q_h / ||q_h|| * d_k^-1/2``,
  ``k_h <- k_h / ||k_h||`` (``||.|| = sqrt(sum x^2 + 1e-6)``); ``beta = 2
  sigmoid(x Wb)`` [H] in (0, 2) (``linear_neg_eigval``; without it no 2);
  ``g = -exp(A_log[h]) softplus(x Wf_down Wf_up + dt_bias)`` in R^{H x d_k},
  ``a = exp(g)``;

      S_t = (I - beta_t k_t k_t^T) Diag(a_t) S_{t-1} + beta_t k_t v_t^T
      o_t = S_t^T q_t                        S_0 = 0 [d_k, d_v], float32;

  ``out = (RMS_{d_v}(o) * sigmoid(x Wg_down Wg_up)) W_o``, the norm's one
  gain vector shared by the heads.
- FFN, every layer: ``s = sigmoid(u W_r)`` over all ``experts_published``;
  the ``experts_per_token`` largest of ``s + b`` are chosen; ``w =
  s[chosen] / (sum s[chosen] + 1e-20)`` (``route_norm``) times
  ``route_scale``; ``y = Shared(u) + sum_e w_e Expert_e(u)``, every expert
  and the shared one a SwiGLU of ``expert_d_ff``, the shared one unweighted.
  No capacity, no dropped token.

The chip's share of an expert-parallel deployment: the router keeps its
``experts_published`` outputs and its top k; of the chosen experts only
``first_expert .. first_expert + experts_held`` are held, and what the others
would add is left out (here as in the program).

``prec`` picks the precision of every product's operands and of the
recurrence's q, k and v: ``"f32"`` (the reference), ``"fp8"`` (rounded through
``float8_e4m3fn``, one amax scale a tensor: the control), ``"bf16"`` (a
diagnostic).  The state, the decays, beta, norms, softmax and gates stay
float32 in each.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
F32 = jnp.float32
ROWS = 512  # positions a block of a row-wise map takes
QUERIES = 256  # queries a block of the attention takes, against all keys
HEAD_GROUP = 16  # KDA heads whose recurrence runs together


# -- shapes ------------------------------------------------------------------


def layer_kinds(cfg: dict) -> list[tuple[str, str]]:
    """(parameter prefix, kind) of every layer; each has the expert FFN."""
    if cfg.get("n_dense_layers") != 0:
        raise ValueError("this model has an expert FFN in every layer: "
                         "n_dense_layers must be 0")
    return [(f"layers_{i}", kind) for i, kind in enumerate(cfg["layer_types"])]


def experts_held(cfg: dict) -> int:
    n = cfg.get("experts_held")
    return cfg["experts_published"] if n is None else n


def param_shapes(cfg: dict) -> dict[str, tuple[int, ...]]:
    """Every parameter by path; layers are apart (``layers_0`` ..)."""
    d, H, KV, c = (cfg["d_model"], cfg["n_heads"], cfg["n_kv_heads"],
                   cfg["head_size"])
    LH, dk, dv = (cfg["linear_value_heads"], cfg["linear_key_head_dim"],
                  cfg["linear_value_head_dim"])
    rf, rg = cfg["linear_decay_rank"], cfg["linear_gate_rank"]
    E, n, f = cfg["experts_published"], experts_held(cfg), cfg["expert_d_ff"]
    fs = cfg["shared_experts"] * f
    mixers = {
        "full_attention": {
            "q_proj/kernel": (d, H, c), "k_proj/kernel": (d, KV, c),
            "v_proj/kernel": (d, KV, c), "gate_proj/kernel": (d, H, c),
            "o_proj/kernel": (H, c, d)},
        "linear_attention": {
            "q_proj/kernel": (d, LH, dk), "k_proj/kernel": (d, LH, dk),
            "v_proj/kernel": (d, LH, dv), "b_proj/kernel": (d, LH),
            "f_a_proj/kernel": (d, rf), "f_b_proj/kernel": (rf, LH, dk),
            "g_a_proj/kernel": (d, rg), "g_b_proj/kernel": (rg, LH, dv),
            "o_proj/kernel": (LH, dv, d),
            "conv": (cfg["linear_conv_kernel"], LH * (2 * dk + dv)),
            "A_log": (LH,), "dt_bias": (LH * dk,), "o_norm/scale": (dv,)}}
    ffn = {"router/kernel": (d, E), "router/e_bias": (E,),
           "experts_gate": (n, d, f), "experts_up": (n, d, f),
           "experts_down": (n, f, d),
           "shared/gate_proj/kernel": (d, fs),
           "shared/up_proj/kernel": (d, fs),
           "shared/down_proj/kernel": (fs, d)}
    shapes = {"embed/embedding": (cfg["vocab_size"], d)}
    for name, kind in layer_kinds(cfg):
        shapes[f"{name}/attn_norm/scale"] = (d,)
        shapes[f"{name}/mlp_norm/scale"] = (d,)
        shapes.update({f"{name}/attn/{k}": s for k, s in mixers[kind].items()})
        shapes.update({f"{name}/mlp/{k}": s for k, s in ffn.items()})
    shapes["final_norm/scale"] = (d,)
    shapes["lm_head/kernel"] = (d, cfg["vocab_size"])
    return shapes


# -- arithmetic --------------------------------------------------------------


def lowered(x, prec: str):
    """``x`` in float32 holding only what ``prec`` can hold."""
    x = x.astype(F32)
    if prec == "f32":
        return x
    if prec == "bf16":
        return x.astype(jnp.bfloat16).astype(F32)
    if prec == "fp8":
        # one scale a tensor, to the format's largest finite value
        fp8 = jnp.float8_e4m3fn
        s = float(jnp.finfo(fp8).max) / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
        return (x * s).astype(fp8).astype(F32) / s
    raise ValueError(f"unknown precision {prec!r}")


def dot(spec: str, a, b, prec: str):
    return jnp.einsum(spec, lowered(a, prec), lowered(b, prec), precision=HI,
                      preferred_element_type=F32)


def rms_norm(x, gain, eps: float):
    return (x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
            * gain.astype(F32))


def by_rows(fn, *xs):
    """``fn`` over blocks of positions of ``xs`` [S, ...]: blocks of ``ROWS``
    where S is a multiple of it, else of their greatest common divisor."""
    S = xs[0].shape[0]
    b = math.gcd(S, ROWS)
    out = jax.lax.map(lambda block: fn(*block), tuple(
        x.reshape(S // b, b, *x.shape[1:]) for x in xs))
    return jax.tree.map(lambda y: y.reshape(S, *y.shape[2:]), out)


def swiglu(u, gate, up, down, prec: str):
    h = jax.nn.silu(dot("sd,df->sf", u, gate, prec)) * dot("sd,df->sf", u, up,
                                                           prec)
    return dot("sf,fd->sd", h, down, prec)


def summed(fn, S: int, d: int, parts):
    """``sum_j fn(parts[j])``, each term [S, d], one term alive at a time."""
    total, _ = jax.lax.scan(lambda acc, part: (acc + fn(part), None),
                            jnp.zeros((S, d), F32), parts)
    return total


# -- the attention layer -----------------------------------------------------


def gated_attention(p: dict, x, cfg: dict, prec: str):
    """Gated grouped-query attention on the normed x [S, d], a key/value
    head (and the ``H / KV`` query heads that read it) at a time; nothing is
    rotated."""
    S, d = x.shape
    H, KV, c = cfg["n_heads"], cfg["n_kv_heads"], cfg["head_size"]
    r = H // KV
    pos = jnp.arange(S)
    qb = math.gcd(S, QUERIES)

    def grouped(w, axis: int):  # the head axis -> [KV, .., r, ..]
        w = w.reshape(*w.shape[:axis], KV, r, *w.shape[axis + 1:])
        return jnp.moveaxis(w, axis, 0)

    def one(part):
        wq, wk, wv, wg, wo = part  # [d, r, c], [d, c], [d, c], .., [r, c, d]
        k = by_rows(lambda x: dot("sd,dc->sc", x, wk, prec), x)
        v = by_rows(lambda x: dot("sd,dc->sc", x, wv, prec), x)

        def block(args):
            at, x = args  # the block's positions [qb] and rows [qb, d]
            q = dot("sd,drc->src", x, wq, prec)
            s = dot("src,tc->srt", q, k, prec) / math.sqrt(c)
            seen = (pos[None, :] <= at[:, None])[:, None, :]
            w = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
            o = dot("srt,tc->src", w, v, prec)
            gate = jax.nn.sigmoid(dot("sd,drc->src", x, wg, prec))
            return dot("src,rcd->sd", o * gate, wo, prec)

        return jax.lax.map(block, (pos.reshape(-1, qb),
                                   x.reshape(-1, qb, d))).reshape(S, d)

    return summed(one, S, d, (
        grouped(p["attn/q_proj/kernel"], 1),
        jnp.moveaxis(p["attn/k_proj/kernel"], 1, 0),
        jnp.moveaxis(p["attn/v_proj/kernel"], 1, 0),
        grouped(p["attn/gate_proj/kernel"], 1),
        grouped(p["attn/o_proj/kernel"], 0)))


# -- the KDA layer -----------------------------------------------------------


def delta_rule(q, k, v, g, beta):
    """The recurrence, a token a scan step from a state of zeros: ``q, k,
    g`` [S, G, d_k], ``v`` [S, G, d_v], ``beta`` [S, G] -> [S, G, d_v]."""
    def token(state, x):
        q, k, v, g, beta = x
        state = jnp.exp(g)[:, :, None] * state  # Diag(a_t) S_{t-1}
        seen = jnp.einsum("gkv,gk->gv", state, k, precision=HI)
        state = state + k[:, :, None] * (beta[:, None] * (v - seen))[:, None]
        return state, jnp.einsum("gkv,gk->gv", state, q, precision=HI)

    zeros = jnp.zeros((q.shape[1], q.shape[2], v.shape[2]), F32)
    return jax.lax.scan(token, zeros, (q, k, v, g, beta))[1]


def short_conv(pre, taps):
    """``pre`` [S, G, c] through the causal depthwise filter ``taps`` [K, G,
    c] (tap K - 1 on the token itself) and SiLU."""
    K, S = taps.shape[0], pre.shape[0]
    padded = jnp.concatenate([jnp.zeros((K - 1, *pre.shape[1:]), F32), pre])
    return jax.nn.silu(sum(taps[i] * padded[i:i + S] for i in range(K)))


def unit(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)


def kimi_delta_attention(p: dict, x, cfg: dict, prec: str):
    """The KDA mixer on the normed x [S, d], ``HEAD_GROUP`` heads at a
    time."""
    S, d = x.shape
    H, dk, dv = (cfg["linear_value_heads"], cfg["linear_key_head_dim"],
                 cfg["linear_value_head_dim"])
    G = math.gcd(H, HEAD_GROUP)
    top = 2.0 if cfg.get("linear_neg_eigval") else 1.0
    eps = cfg["norm_eps"]
    # the two bottlenecks' first halves are the layer's, not a head's
    f_low = by_rows(lambda x: dot("sd,dr->sr", x, p["attn/f_a_proj/kernel"],
                                  prec), x)
    g_low = by_rows(lambda x: dot("sd,dr->sr", x, p["attn/g_a_proj/kernel"],
                                  prec), x)

    def groups(w, axis: int):  # the head axis -> [H / G, .., G, ..]
        w = w.reshape(*w.shape[:axis], H // G, G, *w.shape[axis + 1:])
        return jnp.moveaxis(w, axis, 0)

    # the filter's columns are q's, then k's, then v's, each [H, width]
    taps = p["attn/conv"].astype(F32)
    fq, fk, fv = (groups(t.reshape(t.shape[0], H, -1), 1) for t in jnp.split(
        taps, [H * dk, 2 * H * dk], axis=1))

    def one(part):
        (wq, wk, wv, wb, f_up, g_up, wo, fq, fk, fv, a_log, dt_bias) = part

        def through(w, taps):
            return short_conv(by_rows(
                lambda x: dot("sd,dgc->sgc", x, w, prec), x), taps)

        def gates(x, f_low):
            beta = top * jax.nn.sigmoid(dot("sd,dg->sg", x, wb, prec))
            f = dot("sr,rgk->sgk", f_low, f_up, prec)
            g = -jnp.exp(a_log.astype(F32))[:, None] * jax.nn.softplus(
                f + dt_bias.astype(F32))
            return g, beta

        g, beta = by_rows(gates, x, f_low)
        o = delta_rule(lowered(unit(through(wq, fq)) * dk ** -0.5, prec),
                       lowered(unit(through(wk, fk)), prec),
                       lowered(through(wv, fv), prec), g, beta)

        def out(o, g_low):
            gate = jax.nn.sigmoid(dot("sr,rgv->sgv", g_low, g_up, prec))
            y = rms_norm(o, p["attn/o_norm/scale"], eps) * gate
            return dot("sgv,gvd->sd", y, wo, prec)

        return by_rows(out, o, g_low)

    return summed(one, S, d, (
        groups(p["attn/q_proj/kernel"], 1), groups(p["attn/k_proj/kernel"], 1),
        groups(p["attn/v_proj/kernel"], 1), groups(p["attn/b_proj/kernel"], 1),
        groups(p["attn/f_b_proj/kernel"], 1),
        groups(p["attn/g_b_proj/kernel"], 1),
        groups(p["attn/o_proj/kernel"], 0), fq, fk, fv,
        groups(p["attn/A_log"], 0),
        groups(p["attn/dt_bias"].reshape(H, dk), 0)))


# -- the FFN -----------------------------------------------------------------


def route(p: dict, u, cfg: dict, prec: str):
    """``(chosen [S, k], weights [S, k])`` over the published experts."""
    s = jax.nn.sigmoid(dot("sd,de->se", u, p["mlp/router/kernel"], prec))
    _, chosen = jax.lax.top_k(s + p["mlp/router/e_bias"].astype(F32),
                              cfg["experts_per_token"])
    w = jnp.take_along_axis(s, chosen, axis=-1)
    if cfg.get("route_norm", True):
        w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
    return chosen, w * cfg.get("route_scale", 1.0)


def expert_ffn(p: dict, u, cfg: dict, prec: str):
    """The shared expert, and every HELD expert on every row weighted by 0
    where the row did not choose it."""
    chosen, w = route(p, u, cfg, prec)
    first = cfg.get("first_expert", 0)

    def one(part):
        e, gate, up, down = part
        mine = jnp.sum(jnp.where(chosen == first + e, w, 0.0), -1)
        return mine[:, None] * swiglu(u, gate, up, down, prec)

    y = summed(one, *u.shape, (
        jnp.arange(experts_held(cfg)), p["mlp/experts_gate"],
        p["mlp/experts_up"], p["mlp/experts_down"]))
    return y + swiglu(u, p["mlp/shared/gate_proj/kernel"],
                      p["mlp/shared/up_proj/kernel"],
                      p["mlp/shared/down_proj/kernel"], prec)


def layer(p: dict, x, cfg: dict, kind: str, prec: str):
    """One layer on x [S, d]; ``p`` holds its leaves without the prefix."""
    eps = cfg["norm_eps"]
    mixer = {"full_attention": gated_attention,
             "linear_attention": kimi_delta_attention}[kind]
    x = x + mixer(p, rms_norm(x, p["attn_norm/scale"], eps), cfg, prec)
    return x + by_rows(lambda x: expert_ffn(
        p, rms_norm(x, p["mlp_norm/scale"], eps), cfg, prec), x)


# -- forward -----------------------------------------------------------------


def frozen(cfg: dict) -> tuple:
    """The configuration as a static argument."""
    return tuple(sorted(
        (k, tuple(v) if isinstance(v, list) else v) for k, v in cfg.items()
        if isinstance(v, (int, float, str, bool, type(None), list, tuple))))


@functools.partial(jax.jit, static_argnames=("cfg", "kind", "prec"))
def _layer(p, x, cfg, kind, prec):
    return layer(p, x, dict(cfg), kind, prec)


@jax.jit
def _embed(table, tokens):
    return table[tokens].astype(F32)


@functools.partial(jax.jit, static_argnames=("n", "eps", "prec"))
def _head(x, lo, gain, kernel, n, eps, prec):
    x = jax.lax.dynamic_slice_in_dim(x, lo, n, axis=0)
    return dot("sd,dv->sv", rms_norm(x, gain, eps), kernel, prec)


def leaves_of(params: dict, name: str) -> dict:
    """One layer's leaves, without the prefix."""
    return {k[len(name) + 1:]: v for k, v in params.items()
            if k.startswith(name + "/")}


def forward_logits_at(params: dict, cfg: dict, tokens, lo, n: int,
                      prec: str = "f32"):
    """Logits [n, V] at positions ``lo .. lo + n`` of a full forward pass
    over ONE sequence ``tokens`` [S], a layer at a time.  ``n`` is static
    (a compiled shape), ``lo`` is not."""
    static = frozen(cfg)
    x = _embed(params["embed/embedding"], jnp.asarray(tokens, jnp.int32))
    for name, kind in layer_kinds(cfg):
        x = _layer(leaves_of(params, name), x, static, kind, prec)
    return _head(x, jnp.int32(lo), params["final_norm/scale"],
                 params["lm_head/kernel"], n, cfg["norm_eps"], prec)


def forward_logits(params: dict, cfg: dict, tokens, prec: str = "f32"):
    """Logits [B, S, V] of a full forward pass over ``tokens`` [B, S]."""
    tokens = jnp.asarray(tokens, jnp.int32)
    return jnp.stack([forward_logits_at(params, cfg, row, 0, row.shape[0],
                                        prec) for row in tokens])
