"""Plain reference for a decoder-hybrid-decoder of Mamba layers, windowed and
full differential attention, Gated Memory Units and cross attention over ONE
shared cache (``model_type: phi4flash``, Microsoft Phi-4-mini-flash-reasoning,
the SambaY architecture of arXiv:2507.06607).

Written from the equations in float32 ``jax.numpy``: no Pallas, no cache, no
chunked scan (the state-space recurrence is a ``lax.scan`` over the tokens,
one state update a step), no skipped layer (EVERY layer runs on EVERY
position: the program's prefill stops at the self-decoder's last layer for
all rows but one, and is checked against this), every product at ``highest``
precision.  It imports nothing of the program under test.  Weights come from
the benchmark keyed by the paths of ``param_shapes``; a leaf may arrive in
bfloat16 (the values are the same) and is widened where it is used.
Projections, FFNs and attention (a group of KV heads and a block of queries
at a time) run over blocks of positions, so that a sequence of 34,816
positions fits beside 8.7 GB of held weights.

No network here: the published modelling code is not at hand, and where it
differs from what follows, IT wins; every departure that is known or
possible is an entry of ``assumed`` in the configuration file.

The equations (d = ``d_model``, l the 0-based layer index):

- ``h0 = E[tok]``, unscaled; no positional term anywhere (``pos: none``).
- layer: ``a = h + Mixer_l(LN(h))``, ``h' = a + W_down(silu(W_gate u) *
  W_up u)`` with ``u = LN(a)``; LayerNorm with gain and bias; no bias in the
  FFN.
- ``state_space`` (Mamba-1), ``n = ssm_inner``, ``N = ssm_state``, ``R =
  ssm_dt_rank``, K = ``TAPS`` = 4: ``[a, z] = W_in x``; ``c_t =
  silu(sum_j w[j] * a_{t-K+1+j} + b)`` (causal, depthwise, zeros before
  position 0); ``[delta, B_t, C_t] = W_x c_t``; ``Delta_t = softplus(W_dt
  delta + dt_bias)``; ``A = -exp(A_log)`` [N, n] (the parameter is held
  ``[N, n]``, the published ``[n, N]`` transposed); ``h_t = exp(Delta_t A)
  * h_{t-1} + (Delta_t * c_t) B_t^T`` from zeros, float32; ``y_t = C_t . h_t
  + D * c_t``; out ``= W_out(y_t * silu(z_t))``.  ``y_t`` (before the gate)
  is the token's MEMORY for the ``gated_memory`` layers behind it.
- ``gated_memory`` (GMU): out ``= W_2(m_t * silu(W_1 x_t))``, ``m`` the
  memory of the nearest ``state_space`` layer before it.
- differential attention (``sliding_attention``, ``full_attention``,
  ``shared_attention``), H query heads and KV key-value heads of hd: ``q =
  W_q x + b_q``; ``k, v = W_k x + b_k, W_v x + b_v``, or on a
  ``shared_attention`` layer the k, v of the nearest ``full_attention``
  layer before it (no ``W_k``, ``W_v``).  Head pair j: ``q1 = q[2j]``, ``q2
  = q[2j + 1]``; KV group ``g = j // (H / KV)``: ``k1 = k[2g]``, ``k2 = k[2g
  + 1]``, ``V_g = [v[2g]; v[2g + 1]]`` (2 hd wide).  ``A^i = softmax(q^i .
  k^i / sqrt(hd))`` over keys in ``(t - window, t]`` on a sliding layer, ``<=
  t`` elsewhere.  ``lambda = exp(lq1 . lk1) - exp(lq2 . lk2) +
  lambda_init(l)``, ``lambda_init(l) = 0.8 - 0.6 exp(-0.3 l)``.  ``o_j =
  RMSNorm_{2 hd}((A^1 - lambda A^2) V_g) * (1 - lambda_init(l))``; out ``=
  W_o concat_j o_j + b_o``.
- ``logits = LN_final(h) E^T`` (tied, no bias).

``prec`` picks the precision of every product's operands and of the scan's
``c``, ``B`` and ``C``: ``"f32"`` (the reference), ``"fp8"`` (rounded through
``float8_e4m3fn``, one amax scale a tensor: the control), ``"bf16"`` (a
diagnostic).  The state, ``Delta``, the exponential, the norms' statistics,
the softmax and lambda stay float32 in each.
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
TAPS = 4  # of the causal depthwise convolution (Mamba-1's; not a key)



# -- shapes ------------------------------------------------------------------


def plan(cfg: dict) -> list[tuple[str, str]]:
    """(parameter prefix, kind) of every layer."""
    return [(f"layers_{i}", kind) for i, kind in enumerate(cfg["layer_types"])]


def heads(cfg: dict) -> tuple[int, int, int]:
    """(query heads, KV heads, head size)."""
    H = cfg["n_heads"]
    return H, cfg.get("n_kv_heads") or H, cfg.get("head_size") or (
        cfg["d_model"] // H)


def ssm_dims(cfg: dict) -> tuple[int, int, int, int]:
    """(inner channels, state size, the step's rank, taps)."""
    return (cfg["ssm_inner"], cfg["ssm_state"], cfg["ssm_dt_rank"], TAPS)


def param_shapes(cfg: dict) -> dict[str, tuple[int, ...]]:
    """Every parameter by path; layers are apart (``layers_0`` ..)."""
    d, f = cfg["d_model"], cfg["d_ff"]
    H, KV, hd = heads(cfg)
    norm = lambda: {"scale": (d,), "bias": (d,)}
    shapes: dict[str, tuple[int, ...]] = {
        "embed/embedding": (cfg["vocab_size"], d)}
    for name, kind in plan(cfg):
        layer = {f"attn_norm/{k}": v for k, v in norm().items()}
        layer.update({f"mlp_norm/{k}": v for k, v in norm().items()})
        layer.update({"mlp/gate_proj/kernel": (d, f),
                      "mlp/up_proj/kernel": (d, f),
                      "mlp/down_proj/kernel": (f, d)})
        if kind == "state_space":
            n, N, R, K = ssm_dims(cfg)
            layer.update({
                "attn/in_proj/kernel": (d, 2 * n),
                "attn/conv": (K, n), "attn/conv_bias": (n,),
                "attn/x_proj/kernel": (n, R + 2 * N),
                "attn/dt_proj/kernel": (R, n), "attn/dt_bias": (n,),
                "attn/A_log": (N, n), "attn/D": (n,),
                "attn/o_proj/kernel": (n, d)})
        elif kind == "gated_memory":
            n = cfg["ssm_inner"]
            layer.update({"attn/in_proj/kernel": (d, n),
                          "attn/o_proj/kernel": (n, d)})
        else:
            layer.update({
                "attn/q_proj/kernel": (d, H, hd), "attn/q_proj/bias": (H, hd),
                "attn/o_proj/kernel": (H, hd, d), "attn/o_proj/bias": (d,),
                "attn/sub_norm/scale": (2 * hd,),
                **{f"attn/lambda_{n}": (hd,)
                   for n in ("q1", "k1", "q2", "k2")}})
            if kind != "shared_attention":
                for p in ("k_proj", "v_proj"):
                    layer[f"attn/{p}/kernel"] = (d, KV, hd)
                    layer[f"attn/{p}/bias"] = (KV, hd)
        shapes.update({f"{name}/{k}": v for k, v in layer.items()})
    shapes.update({f"final_norm/{k}": v for k, v in norm().items()})
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


def layer_norm(x, scale, bias, eps: float):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return ((x - mu) * jax.lax.rsqrt(var + eps) * scale.astype(F32)
            + bias.astype(F32))


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


# -- the state-space mixer and the memory unit -----------------------------------


def selective_scan(c, delta, A, B, C, D):
    """The recurrence, a token a scan step from a state of zeros: ``c``,
    ``delta`` [S, n], ``A`` [N, n], ``B``, ``C`` [S, N], ``D`` [n].
    [S, n]."""
    def step(h, x):
        c, dt, b, cc = x
        h = jnp.exp(dt[None, :] * A) * h + (dt * c)[None, :] * b[:, None]
        return h, jnp.sum(h * cc[:, None], axis=0) + D * c

    return jax.lax.scan(step, jnp.zeros(A.shape, F32), (c, delta, B, C))[1]


def state_space(p: dict, x, cfg: dict, prec: str):
    """The Mamba mixer on the normed x [S, d]: ``(out [S, d], y [S, n])``,
    ``y`` the scan's output before the gate (the memory)."""
    n, N, R, K = ssm_dims(cfg)
    S = x.shape[0]
    az = blocked(lambda x: mm("sd,de->se", x, p["attn/in_proj/kernel"], prec),
                 x)
    a, z = az[:, :n], az[:, n:]
    full = jnp.concatenate([jnp.zeros((K - 1, n), F32), a])
    w = p["attn/conv"].astype(F32)
    c = jax.nn.silu(sum(w[j] * full[j:j + S] for j in range(K))
                    + p["attn/conv_bias"].astype(F32))

    def maps(c):
        dbc = mm("sn,nr->sr", c, p["attn/x_proj/kernel"], prec)
        delta = jax.nn.softplus(
            mm("sr,rn->sn", dbc[:, :R], p["attn/dt_proj/kernel"], prec)
            + p["attn/dt_bias"].astype(F32))
        return delta, dbc[:, R:R + N], dbc[:, R + N:]

    delta, B, C = blocked(maps, c)
    y = selective_scan(_round(c, prec), delta, -jnp.exp(p["attn/A_log"].astype(
        F32)), _round(B, prec), _round(C, prec), p["attn/D"].astype(F32))
    out = blocked(lambda y, z: mm("sn,nd->sd", y * jax.nn.silu(z),
                                  p["attn/o_proj/kernel"], prec), y, z)
    return out, y


def gated_memory(p: dict, x, m, prec: str):
    """The GMU on the normed x [S, d] and the memory m [S, n]."""
    return blocked(lambda x, m: mm(
        "sn,nd->sd",
        m * jax.nn.silu(mm("sd,dn->sn", x, p["attn/in_proj/kernel"], prec)),
        p["attn/o_proj/kernel"], prec), x, m)


# -- differential attention -----------------------------------------------------


def lambda_init(depth):
    """``depth``: the 0-based layer index (a traced scalar: the layers of a
    kind share one compiled program)."""
    return 0.8 - 0.6 * jnp.exp(-0.3 * jnp.asarray(depth, F32))


def keys_values(p: dict, x, prec: str):
    """k, v [S, KV, hd] of the normed x [S, d]."""
    def project(x):
        return tuple(mm("sd,dhk->shk", x, p[f"attn/{n}/kernel"], prec)
                     + p[f"attn/{n}/bias"].astype(F32)
                     for n in ("k_proj", "v_proj"))

    return blocked(project, x)


def diff_attention(p: dict, x, kv, cfg: dict, depth, window, prec: str):
    """Differential attention of the normed x [S, d] over the keys and
    values ``kv`` (its own, or its source layer's): a group of two KV heads
    at a time (the ``H / KV`` head pairs they serve), a block of queries at
    a time inside a group."""
    H, KV, hd = heads(cfg)
    S = x.shape[0]
    per = H // KV  # head pairs a KV group serves
    k, v = kv
    q = blocked(lambda x: mm("sd,dhk->shk", x, p["attn/q_proj/kernel"], prec)
                + p["attn/q_proj/bias"].astype(F32), x)
    pos = jnp.arange(S)
    qb = math.gcd(S, QUERIES)
    n_blocks = S // qb
    scale = 1.0 / math.sqrt(hd)
    lam0 = lambda_init(depth)
    l = {n: p[f"attn/lambda_{n}"].astype(F32) for n in ("q1", "k1", "q2", "k2")}
    lam = (jnp.exp(jnp.sum(l["q1"] * l["k1"]))
           - jnp.exp(jnp.sum(l["q2"] * l["k2"])) + lam0)
    if window is None:
        # causal: the queries of each of up to SEGMENTS runs of whole blocks
        # see the keys up to their run's end, and none behind it is multiplied
        cuts = sorted({(j * n_blocks // SEGMENTS) * qb
                       for j in range(1, SEGMENTS)} | {0, S})
    else:
        cuts = [0, S]
        back = -(-window // qb) * qb  # whole blocks the band reaches back

    def group(args):
        qg, kg, vg = args  # [S, per, 2, hd], [S, 2, hd], [S, 2 hd]

        def run(lo, hi):
            def block(a):
                qi, qq = a  # [qb], [qb, per, 2, hd]
                if window is None:
                    kk, vv, kp = kg[:hi], vg[:hi], pos[:hi]
                else:  # the keys from ``back`` before the block to its end
                    first = qi[0] - back
                    take = lambda z: jax.lax.dynamic_slice_in_dim(
                        jnp.pad(z, ((back, 0),) + ((0, 0),) * (z.ndim - 1)),
                        first + back, back + qb)
                    kk, vv, kp = take(kg), take(vg), first + jnp.arange(
                        back + qb)
                s = mm("qjik,tik->jiqt", qq, kk, prec) * scale
                ok = (kp[None, :] <= qi[:, None]) & (kp[None, :] >= 0)
                if window is not None:
                    ok &= kp[None, :] > qi[:, None] - window
                w = jax.nn.softmax(jnp.where(ok, s, -jnp.inf), axis=-1)
                o = mm("jiqt,tv->jiqv", w, vv, prec)  # [per, 2, qb, 2 hd]
                return jnp.moveaxis(o[:, 0] - lam * o[:, 1], 0, 1)

            return jax.lax.map(block, (
                pos[lo:hi].reshape(-1, qb),
                qg[lo:hi].reshape(-1, qb, per, 2, hd))).reshape(
                    hi - lo, per, 2 * hd)

        return jnp.concatenate([run(lo, hi)
                                for lo, hi in zip(cuts, cuts[1:])])

    o = jax.lax.map(group, (
        jnp.moveaxis(q.reshape(S, KV // 2, per, 2, hd), 1, 0),
        jnp.moveaxis(k.reshape(S, KV // 2, 2, hd), 1, 0),
        jnp.moveaxis(v.reshape(S, KV // 2, 2 * hd), 1, 0)))  # [G, S, per, 2hd]
    o = jnp.moveaxis(o, 0, 1).reshape(S, H // 2, 2 * hd)

    def out(o):
        o = rms(o, p["attn/sub_norm/scale"], cfg["norm_eps"]) * (1.0 - lam0)
        return (mm("shk,hkd->sd", o.reshape(-1, H, hd),
                   p["attn/o_proj/kernel"], prec)
                + p["attn/o_proj/bias"].astype(F32))

    return blocked(out, o)


# -- a layer ---------------------------------------------------------------------


def ffn(p: dict, x, prec: str):
    h = (jax.nn.silu(mm("sd,df->sf", x, p["mlp/gate_proj/kernel"], prec))
         * mm("sd,df->sf", x, p["mlp/up_proj/kernel"], prec))
    return mm("sf,fd->sd", h, p["mlp/down_proj/kernel"], prec)


def layer(p: dict, x, passed, cfg: dict, kind: str, depth, prec: str):
    """One layer on x [S, d]; ``p`` holds its leaves without the prefix;
    ``passed`` = (the memory, the shared keys and values) as the layers
    before left them.  Returns ``(x, passed)``."""
    eps = cfg["norm_eps"]
    memory, kv = passed
    u = blocked(lambda x: layer_norm(x, p["attn_norm/scale"],
                                     p["attn_norm/bias"], eps), x)
    if kind == "state_space":
        h, memory = state_space(p, u, cfg, prec)
    elif kind == "gated_memory":
        h = gated_memory(p, u, memory, prec)
    else:
        own = kv if kind == "shared_attention" else keys_values(p, u, prec)
        if kind == "full_attention":
            kv = own
        h = diff_attention(
            p, u, own, cfg, depth,
            cfg["sliding_window"] if kind == "sliding_attention" else None,
            prec)
    x = x + h
    x = x + blocked(lambda x: ffn(p, layer_norm(
        x, p["mlp_norm/scale"], p["mlp_norm/bias"], eps), prec), x)
    return x, (memory, kv)


# -- forward -------------------------------------------------------------------


def cfg_key(cfg: dict) -> tuple:
    def atom(v):
        return tuple(v) if isinstance(v, (list, tuple)) else v

    return tuple(sorted((k, atom(v)) for k, v in cfg.items()
                        if isinstance(v, (int, float, str, bool, type(None),
                                          list, tuple))))


@functools.partial(jax.jit, static_argnames=("key", "kind", "prec"))
def _layer(p, x, passed, key, kind, depth, prec):
    return layer(p, x, passed, dict(key), kind, depth, prec)


@jax.jit
def _embed(table, tokens):
    return table[tokens].astype(F32)


@functools.partial(jax.jit, static_argnames=("n", "eps", "prec"))
def _head(x, lo, scale, bias, table, n, eps, prec):
    x = jax.lax.dynamic_slice_in_dim(x, lo, n, axis=0)
    return mm("sd,vd->sv", layer_norm(x, scale, bias, eps), table, prec)


def sub(params: dict, name: str) -> dict:
    """One layer's leaves, without the prefix."""
    return {k[len(name) + 1:]: v for k, v in params.items()
            if k.startswith(name + "/")}


def forward_logits_at(params: dict, cfg: dict, tokens, lo, n: int,
                      prec: str = "f32"):
    """Logits [n, V] at positions ``lo .. lo + n`` of a full forward pass
    over ONE sequence ``tokens`` [S], a layer at a time, every layer on
    every position.  ``n`` is static (a compiled shape), ``lo`` is not."""
    key = cfg_key(cfg)
    x = _embed(params["embed/embedding"], jnp.asarray(tokens, jnp.int32))
    passed = (None, None)
    for i, (name, kind) in enumerate(plan(cfg)):
        # only the kinds that read them take what the layers before passed
        # on (a compiled layer is shared by the layers of its kind)
        memory, kv = passed
        given = (memory if kind == "gated_memory" else None,
                 kv if kind == "shared_attention" else None)
        x, (m2, kv2) = _layer(sub(params, name), x, given, key, kind,
                              jnp.int32(i), prec)
        passed = (m2 if kind == "state_space" else memory,
                  kv2 if kind == "full_attention" else kv)
    return _head(x, jnp.int32(lo), params["final_norm/scale"],
                 params["final_norm/bias"], params["embed/embedding"], n,
                 cfg["norm_eps"], prec)


def forward_logits(params: dict, cfg: dict, tokens, prec: str = "f32"):
    """Logits [B, S, V] of a full forward pass over ``tokens`` [B, S]."""
    tokens = jnp.asarray(tokens, jnp.int32)
    return jnp.stack([forward_logits_at(params, cfg, row, 0, row.shape[0],
                                        prec) for row in tokens])
