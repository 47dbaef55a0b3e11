"""Plain reference for a decoder with multi-head latent attention and
sigmoid-routed experts (``model_type: joyai_llm_flash``, JD JoyAI-LLM-Flash:
the DeepSeek-V3 block at other numbers, ``n_group = topk_group = 1``, so the
group-limited choice of experts is the plain top k).

Written from the equations in float32 ``jax.numpy``: no Pallas, no cache, no
absorption (every head's keys and values are expanded from the latent, as
published), no sorting or grouping of tokens, every product at ``highest``
precision.  It imports nothing of the program under test.  Weights come from
the benchmark keyed by the paths of ``param_shapes``; a leaf may arrive in
bfloat16 (the values are the same) and is widened where it is used.
Projections, attention (a head and a block of queries at a time, over the
keys up to the end of the block's eighth of the sequence) and the FFNs run
over blocks of positions, so that a sequence of 34,816 positions fits beside
7.4 GB of held weights.

No network here: the published modelling code is not at hand, and where it
differs from what follows, IT wins; every departure that is known or
possible is an entry of ``assumed`` in the configuration file.

The equations (d = ``d_model``, H = ``n_heads``, r_q = ``latent_q_rank``,
r = ``latent_kv_rank``, n / p / v = ``latent_nope_head_dim`` /
``latent_rope_head_dim`` / ``latent_value_head_dim``):

- ``h0 = E[tok]``.
- layer: ``a = h + Attn(RMS_in(h))``, ``h' = a + FFN(RMS_pre_mlp(a))``.
- Attn(x): ``c_q = RMS(x W_qa)`` [r_q]; ``q = c_q W_qb``, a head ``[q_nope
  (n), q_rope (p)]``; ``[c_kv (r), k_r (p)] = x W_kva``; ``c_kv <-
  RMS(c_kv)``; a head's ``[k_nope (n), v (v)] = c_kv W_kvb``.  ``q_rope`` of
  every head and the ONE ``k_r`` are rotated at the token's position, the
  rotary pairs side by side, ``(x0, x1), (x2, x3), ..``
  (``rope_interleave: true``), pair i by ``position * theta^(-2i/p)``, no
  scaling (``rope_scaling: null``).
  ``score_h(t, s) = (q_nope . k_nope + q_rope . k_r) / sqrt(n + p)``,
  causal softmax, ``o_h = sum_s p v``, ``out = concat_h(o_h) W_o``.  No
  biases.
- FFN of the first ``n_dense_layers`` layers: SwiGLU of width ``d_ff``.
- FFN of the others: ``s = sigmoid(x W_g)`` over all ``experts_published``;
  the ``experts_per_token`` largest of ``s + b`` (``e_score_correction_bias``)
  are chosen; ``w = s[chosen]``, ``w <- w / (sum w + 1e-20)``
  (``norm_topk_prob``), ``w <- route_scale * w``; ``y = Shared(x) + sum_e w_e
  Expert_e(x)``, every expert a SwiGLU of ``expert_d_ff``, the shared one
  unweighted.  No capacity, no dropped token.
- ``logits = RMS_final(h) W_head``.

The chip's share of an expert-parallel deployment, as ``reference/afmoe.py``
takes it: the router keeps its ``experts_published`` outputs and its top k;
of the chosen experts only ``first_expert .. first_expert + experts_held`` are
held, and what the others would add is left out (here as in the program).

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


def plan(cfg: dict) -> list[tuple[str, bool]]:
    """(parameter prefix, has an expert FFN) of every layer."""
    n_dense = cfg.get("n_dense_layers")
    n_dense = cfg["n_layers"] if n_dense is None else n_dense
    return [(f"layers_{i}", i >= n_dense) for i in range(cfg["n_layers"])]


def held(cfg: dict) -> int:
    n = cfg.get("experts_held")
    return cfg["experts_published"] if n is None else n


def latent_dims(cfg: dict) -> tuple[int, int, int, int, int]:
    """(r_q, r, n, p, v) of a ``latent_attention`` layer."""
    return (cfg["latent_q_rank"], cfg["latent_kv_rank"],
            cfg["latent_nope_head_dim"], cfg["latent_rope_head_dim"],
            cfg["latent_value_head_dim"])


def param_shapes(cfg: dict) -> dict[str, tuple[int, ...]]:
    """Every parameter by path; layers are apart (``layers_0`` ..)."""
    d, H = cfg["d_model"], cfg["n_heads"]
    rq, r, n, p, v = latent_dims(cfg)
    shapes: dict[str, tuple[int, ...]] = {
        "embed/embedding": (cfg["vocab_size"], d)}
    for name, sparse in plan(cfg):
        layer = {
            "attn_norm/scale": (d,), "mlp_norm/scale": (d,),
            "attn/q_a_proj/kernel": (d, rq), "attn/q_a_norm/scale": (rq,),
            "attn/q_b_proj/kernel": (rq, H, n + p),
            "attn/kv_a_proj/kernel": (d, r + p),
            "attn/kv_a_norm/scale": (r,),
            "attn/kv_b_proj/kernel": (r, H, n + v),
            "attn/o_proj/kernel": (H, v, d),
        }
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
    """Attn(x) on x [S, d]: the projections in blocks of positions, then a
    head at a time (its keys and values expanded from the latent, as
    published) and a block of queries at a time inside a head."""
    S = x.shape[0]
    _, r, n, rot, _ = latent_dims(cfg)
    eps, theta = cfg["norm_eps"], cfg["rope_theta"]
    pos = jnp.arange(S)

    def project(x, pos):
        cq = rms(mm("sd,dr->sr", x, p["attn/q_a_proj/kernel"], prec),
                 p["attn/q_a_norm/scale"], eps)
        q = mm("sr,rhk->shk", cq, p["attn/q_b_proj/kernel"], prec)
        kv = mm("sd,dr->sr", x, p["attn/kv_a_proj/kernel"], prec)
        c = rms(kv[:, :r], p["attn/kv_a_norm/scale"], eps)
        return (q[..., :n], rope_pairs(q[..., n:], pos, theta), c,
                rope_pairs(kv[:, r:], pos, theta))

    q_nope, q_rope, c, k_r = blocked(project, x, pos)
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
        jnp.moveaxis(q_nope, 1, 0), jnp.moveaxis(q_rope, 1, 0),
        jnp.moveaxis(p["attn/kv_b_proj/kernel"], 1, 0)))  # [H, S, v]
    return blocked(lambda o: mm("shk,hkd->sd", o, p["attn/o_proj/kernel"],
                                prec), jnp.moveaxis(o, 0, 1))


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


def layer(p: dict, x, cfg: dict, sparse: bool, prec: str):
    """One layer on x [S, d]; ``p`` holds its leaves without the prefix."""
    eps = cfg["norm_eps"]
    x = x + attention(p, rms(x, p["attn_norm/scale"], eps), cfg, prec)
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


@functools.partial(jax.jit, static_argnames=("key", "sparse", "prec"))
def _layer(p, x, key, sparse, prec):
    return layer(p, x, dict(key), sparse, prec)


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
    for name, sparse in plan(cfg):
        x = _layer(sub(params, name), x, key, sparse, prec)
    return _head(x, jnp.int32(lo), params["final_norm/scale"],
                 params["lm_head/kernel"], n, cfg["norm_eps"], prec)


def forward_logits(params: dict, cfg: dict, tokens, prec: str = "f32"):
    """Logits [B, S, V] of a full forward pass over ``tokens`` [B, S]."""
    tokens = jnp.asarray(tokens, jnp.int32)
    return jnp.stack([forward_logits_at(params, cfg, row, 0, row.shape[0],
                                        prec) for row in tokens])
