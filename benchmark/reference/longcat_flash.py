"""Plain reference for the language model of LongCat-Flash-Omni (Meituan,
560B-A27B; the audio and vision encoders and the codec decoder are outside
it): the SHORTCUT-CONNECTED double layer (two latent-attention mixers, two
dense FFNs, one expert branch that spans them), zero-compute experts in a
softmax router, multi-head latent attention with a constant behind each of
the two latents' norms.

Written from the equations in float32 ``jax.numpy``: no Pallas, no cache, no
absorption (every head's keys and values are expanded from the latent), no
sorting or grouping of tokens, every product at ``highest`` precision.  It
imports nothing of the program under test.  Weights come from the benchmark
keyed by the paths of ``param_shapes``; a leaf may arrive in bfloat16 (the
values are the same) and is widened where it is used.  Projections,
attention (a head and a block of queries at a time, over the keys up to the
end of the block's eighth of the sequence; a head's queries, keys, values
and its part of the output projection made inside its step) and the FFNs
run over blocks of positions, so that a sequence of 34,816 positions at
d 6,144 fits beside 10 GiB of held weights.

No network here: the published modelling code is not at hand, and where it
differs from what follows, IT wins; every departure that is known or
possible is an entry of ``assumed`` in the configuration file.

The equations (d = ``d_model``, H = ``n_heads``, r_q = ``latent_q_rank``,
r = ``latent_kv_rank``, n / p / v = ``latent_nope_head_dim`` /
``latent_rope_head_dim`` / ``latent_value_head_dim``; norms RMSNorm, no bias
anywhere):

- ``h0 = E[tok]``.
- A PUBLISHED layer is two sublayers i = 0, 1, each with its own norms,
  mixer and dense FFN, and ONE expert branch::

      for i in (0, 1):
          x = x + MLA_i(norm_in_i(x))
          u = norm_post_i(x)
          if i == 0: s = MoE(u)      # reads sublayer 0's normed FFN input
          x = x + SwiGLU_i(u)        # dense, width d_ff
          if i == 1: x = x + s       # and is added after sublayer 1's FFN

  The parameters name the SUBLAYERS: ``layers_{2j}`` and ``layers_{2j+1}``
  are published layer j's two (``n_layers`` counts sublayers), and the
  branch's router and experts are ``layers_{2j}/moe``.
- MLA(x): ``c_q = q_scale * RMS(x W_qa)`` [r_q]; ``q = c_q W_qb``, a head
  ``[q_nope (n), q_rope (p)]``; ``[c (r), k_r (p)] = x W_kva``; ``c_kv =
  kv_scale * RMS(c)``; a head's ``[k_nope (n), v (v)] = c_kv W_kvb``.
  ``q_scale = sqrt(d / r_q)`` and ``kv_scale = sqrt(d / r)``
  (``mla_scale_q_lora``, ``mla_scale_kv_lora``), given as the numbers
  ``latent_q_scale`` and ``latent_kv_scale``.  ``q_rope`` of every head and
  the ONE ``k_r`` are rotated at the token's position, the rotary pairs side
  by side, ``(x0, x1), (x2, x3), ..``, pair i by ``position *
  theta^(-2i/p)``, no scaling.  ``score_h(t, s) = (q_nope . k_nope + q_rope
  . k_r) / sqrt(n + p)``, causal softmax, ``o_h = sum_s p v``, ``out =
  concat_h(o_h) W_o``.
- MoE(u): ``p = softmax(u W_g)`` in float32 over ``experts_published +
  zero_experts`` outputs; the ``experts_per_token`` largest of ``p + b`` are
  chosen; ``w = route_scale * p[chosen]``, NOT normalised; a chosen ``e <
  experts_published`` adds ``w_e * SwiGLU_e(u)`` (width ``expert_d_ff``), a
  chosen ``e >= experts_published`` is a zero-compute expert
  (``zero_expert_type: identity``) and adds ``w_e * u``.  No capacity, no
  dropped token, no shared expert.
- ``logits = RMS_final(h) W_head``.

The chip's share of an expert-parallel deployment, as ``reference/afmoe.py``
takes it: the router keeps all its outputs and its top k; of the chosen
experts with weights only ``first_expert .. first_expert + experts_held`` are
held, and what the others would add is left out (here as in the program).
The zero-compute experts are nobody's share: every chip adds them for its
own tokens, so they are whole here.

``prec`` picks the precision of every product's operands: ``"f32"`` (the
reference), ``"fp8"`` (both operands rounded through ``float8_e4m3fn``, one
amax scale a tensor: the control), ``"bf16"`` (a diagnostic).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
F32 = jnp.float32
BLOCK = 512  # positions a step of a blocked map takes
QUERIES = 512  # queries a step of the attention takes
SEGMENTS = 8  # runs of query blocks, each over the keys up to its end


# -- shapes ------------------------------------------------------------------


def plan(cfg: dict) -> list[tuple[str, str]]:
    """(the two sublayers' parameter prefixes) of every published layer."""
    return [(f"layers_{2 * j}", f"layers_{2 * j + 1}")
            for j in range(cfg["n_layers"] // 2)]


def held(cfg: dict) -> int:
    n = cfg.get("experts_held")
    return cfg["experts_published"] if n is None else n


def router_width(cfg: dict) -> int:
    return cfg["experts_published"] + cfg.get("zero_experts", 0)


def latent_dims(cfg: dict) -> tuple[int, int, int, int, int]:
    """(r_q, r, n, p, v) of a ``latent_attention`` layer."""
    return (cfg["latent_q_rank"], cfg["latent_kv_rank"],
            cfg["latent_nope_head_dim"], cfg["latent_rope_head_dim"],
            cfg["latent_value_head_dim"])


def param_shapes(cfg: dict) -> dict[str, tuple[int, ...]]:
    """Every parameter by path; sublayers are apart (``layers_0`` ..)."""
    d, H, F = cfg["d_model"], cfg["n_heads"], cfg["d_ff"]
    rq, r, n, p, v = latent_dims(cfg)
    W, f = router_width(cfg), cfg["expert_d_ff"]
    shapes: dict[str, tuple[int, ...]] = {
        "embed/embedding": (cfg["vocab_size"], d)}
    sub = {
        "attn_norm/scale": (d,), "mlp_norm/scale": (d,),
        "attn/q_a_proj/kernel": (d, rq), "attn/q_a_norm/scale": (rq,),
        "attn/q_b_proj/kernel": (rq, H, n + p),
        "attn/kv_a_proj/kernel": (d, r + p),
        "attn/kv_a_norm/scale": (r,),
        "attn/kv_b_proj/kernel": (r, H, n + v),
        "attn/o_proj/kernel": (H, v, d),
        "mlp/gate_proj/kernel": (d, F), "mlp/up_proj/kernel": (d, F),
        "mlp/down_proj/kernel": (F, d)}
    branch = {
        "moe/router/kernel": (d, W), "moe/router/e_bias": (W,),
        "moe/experts_gate": (held(cfg), d, f),
        "moe/experts_up": (held(cfg), d, f),
        "moe/experts_down": (held(cfg), f, d)}
    for first, second in plan(cfg):
        for k, s in {**sub, **branch}.items():
            shapes[f"{first}/{k}"] = s
        for k, s in sub.items():
            shapes[f"{second}/{k}"] = s
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


def rope_pairs(x, positions, theta: float):
    """``x`` [S, ..., p] at ``positions`` [S], the rotary pairs side by
    side: pair i is ``(x[2i], x[2i + 1])``, rotated by ``position *
    theta^(-2i/p)``."""
    p = x.shape[-1]
    freqs = 1.0 / (theta ** (np.arange(0, p, 2, dtype=np.float32) / p))
    ang = positions.astype(F32)[:, None] * freqs  # [S, p/2]
    ang = ang.reshape(ang.shape[0], *(1,) * (x.ndim - 2), p // 2)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     axis=-1).reshape(x.shape)


def swiglu(x, gate, up, down, prec: str):
    h = jax.nn.silu(mm("sd,df->sf", x, gate, prec)) * mm("sd,df->sf", x, up,
                                                         prec)
    return mm("sf,fd->sd", h, down, prec)


def attention(p: dict, x, cfg: dict, prec: str):
    """``x + MLA(norm_in(x))`` on the stream x [S, d]: the norm and the two
    low-rank projections in blocks of positions, then a head at a time (its
    queries from the query latent, its keys and values expanded from the
    key-value latent, a block of queries at a time inside it), each head's
    part of the output projection added to the stream as it comes: no array
    of all heads' queries or outputs exists (at 34,816 positions and 64
    heads they would be 4 GB beside 10 GiB of weights)."""
    S = x.shape[0]
    _, r, n, rot, _ = latent_dims(cfg)
    eps, theta = cfg["norm_eps"], cfg["rope_theta"]
    q_scale = cfg.get("latent_q_scale", 1.0)
    kv_scale = cfg.get("latent_kv_scale", 1.0)
    pos = jnp.arange(S)

    def project(x, pos):
        x = rms(x, p["attn_norm/scale"], eps)
        cq = q_scale * rms(mm("sd,dr->sr", x, p["attn/q_a_proj/kernel"],
                              prec), p["attn/q_a_norm/scale"], eps)
        kv = mm("sd,dr->sr", x, p["attn/kv_a_proj/kernel"], prec)
        c = kv_scale * rms(kv[:, :r], p["attn/kv_a_norm/scale"], eps)
        return cq, c, rope_pairs(kv[:, r:], pos, theta)

    cq, c, k_r = blocked(project, x, pos)
    qb = math.gcd(S, QUERIES)
    scale = 1.0 / math.sqrt(n + rot)

    n_blocks = S // qb
    # causal: the queries of each of up to SEGMENTS runs of whole blocks see
    # the keys up to their run's end, and none behind it is multiplied
    cuts = sorted({(j * n_blocks // SEGMENTS) * qb
                   for j in range(1, SEGMENTS)} | {0, S})

    def head(out, w):
        w_qb, w_kvb, w_o = w  # [r_q, n + p], [r, n + v], [v, d]
        q = blocked(lambda cq: mm("sr,rk->sk", cq, w_qb, prec), cq)
        qn, qr = q[:, :n], rope_pairs(q[:, n:], pos, theta)
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

        o = jnp.concatenate([run(lo, hi) for lo, hi in zip(cuts, cuts[1:])])
        return out + blocked(lambda o: mm("sk,kd->sd", o, w_o, prec), o), None

    out, _ = jax.lax.scan(head, x, (
        jnp.moveaxis(p["attn/q_b_proj/kernel"], 1, 0),
        jnp.moveaxis(p["attn/kv_b_proj/kernel"], 1, 0),
        p["attn/o_proj/kernel"]))
    return out


def route(p: dict, u, cfg: dict, prec: str):
    """``(chosen [S, k], weights [S, k])`` over the router's whole width:
    the published experts, then the zero-compute ones."""
    logits = mm("sd,de->se", u, p["moe/router/kernel"], prec)
    if cfg.get("score_func", "softmax") == "softmax":
        s = jax.nn.softmax(logits, axis=-1)
    else:
        s = jax.nn.sigmoid(logits)
    _, chosen = jax.lax.top_k(s + p["moe/router/e_bias"].astype(F32),
                              cfg["experts_per_token"])
    w = jnp.take_along_axis(s, chosen, axis=-1)
    if cfg.get("route_norm", False):
        w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
    return chosen, w * cfg.get("route_scale", 1.0)


def routed(p: dict, u, cfg: dict, prec: str):
    """``sum_e w_e Expert_e(u)`` over the chosen experts that are held:
    every held expert on every row, weighted by 0 where it was not chosen."""
    chosen, w = route(p, u, cfg, prec)
    first = cfg.get("first_expert", 0)

    def one(y, ew):
        e, gate, up, down = ew
        w_e = jnp.sum(jnp.where(chosen == first + e, w, 0.0), -1)
        return y + w_e[:, None] * swiglu(u, gate, up, down, prec), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(u), (
        jnp.arange(held(cfg)), p["moe/experts_gate"], p["moe/experts_up"],
        p["moe/experts_down"]))
    return y


def zero_compute(p: dict, u, cfg: dict, prec: str):
    """What the chosen zero-compute experts add: each its weight times its
    input, ``(sum of the weights on ids >= experts_published) * u``."""
    chosen, w = route(p, u, cfg, prec)
    w_zero = jnp.sum(jnp.where(chosen >= cfg["experts_published"], w, 0.0),
                     -1)
    return w_zero[:, None] * u


def moe(p: dict, u, cfg: dict, prec: str):
    """MoE(u) on u [S, d]: the held experts' part and the zero-compute one."""
    return routed(p, u, cfg, prec) + zero_compute(p, u, cfg, prec)


def dense(p: dict, u, prec: str):
    return swiglu(u, p["mlp/gate_proj/kernel"], p["mlp/up_proj/kernel"],
                  p["mlp/down_proj/kernel"], prec)


def layer(p0: dict, p1: dict, x, cfg: dict, prec: str):
    """One PUBLISHED layer on x [S, d]: ``p0`` and ``p1`` hold its two
    sublayers' leaves without their prefixes."""
    eps = cfg["norm_eps"]
    x = attention(p0, x, cfg, prec)

    def first(x):
        u = rms(x, p0["mlp_norm/scale"], eps)
        return x + dense(p0, u, prec), moe(p0, u, cfg, prec)

    x, s = blocked(first, x)
    x = attention(p1, x, cfg, prec)
    return blocked(
        lambda x, s: x + dense(p1, rms(x, p1["mlp_norm/scale"], eps), prec)
        + s, x, s)


# -- forward -------------------------------------------------------------------


def cfg_key(cfg: dict) -> tuple:
    def atom(v):
        return tuple(v) if isinstance(v, (list, tuple)) else v

    return tuple(sorted((k, atom(v)) for k, v in cfg.items()
                        if isinstance(v, (int, float, str, bool, type(None),
                                          list, tuple))))


def _one_layer(p0, p1, x, key, prec):
    return layer(p0, p1, x, dict(key), prec)


_layer = jax.jit(_one_layer, static_argnames=("key", "prec"))
# the stream's buffer handed on from layer to layer (the CPU cannot donate)
_layer_in_place = jax.jit(_one_layer, static_argnames=("key", "prec"),
                          donate_argnames=("x",))


@jax.jit
def _embed(table, tokens):
    return table[tokens].astype(F32)


@functools.partial(jax.jit, static_argnames=("n", "eps", "prec"))
def _head(x, lo, scale, kernel, n, eps, prec):
    x = jax.lax.dynamic_slice_in_dim(x, lo, n, axis=0)
    return mm("sd,dv->sv", rms(x, scale, eps), kernel, prec)


def sub(params: dict, name: str) -> dict:
    """One sublayer's leaves, without the prefix."""
    return {k[len(name) + 1:]: v for k, v in params.items()
            if k.startswith(name + "/")}


def forward_logits_at(params: dict, cfg: dict, tokens, lo, n: int,
                      prec: str = "f32"):
    """Logits [n, V] at positions ``lo .. lo + n`` of a full forward pass
    over ONE sequence ``tokens`` [S], a published layer at a time.  ``n`` is
    static (a compiled shape), ``lo`` is not."""
    key = cfg_key(cfg)
    x = _embed(params["embed/embedding"], jnp.asarray(tokens, jnp.int32))
    step = _layer if jax.default_backend() == "cpu" else _layer_in_place
    for first, second in plan(cfg):
        x = step(sub(params, first), sub(params, second), x, key, prec)
    return _head(x, jnp.int32(lo), params["final_norm/scale"],
                 params["lm_head/kernel"], n, cfg["norm_eps"], prec)


def forward_logits(params: dict, cfg: dict, tokens, prec: str = "f32"):
    """Logits [B, S, V] of a full forward pass over ``tokens`` [B, S]."""
    tokens = jnp.asarray(tokens, jnp.int32)
    return jnp.stack([forward_logits_at(params, cfg, row, 0, row.shape[0],
                                        prec) for row in tokens])
