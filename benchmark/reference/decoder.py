"""Plain reference for the decoder-only configurations.

Written from the architecture's equations in float32 ``jax.numpy``: no
Pallas, no KV cache, no flash attention, no batching tricks, every product
at ``highest`` matmul precision.  It imports nothing of the program under
test and takes nothing the program has made: its weights come from
``benchmark/lib/weights.py`` (the same seeded generator that hands the
program its weights), keyed by the parameter paths of ``param_shapes``.

A configuration is a plain dict with the keys of its file under
``benchmark/configs/`` (``model`` group).  Covered: LayerNorm or RMSNorm,
learned positions or RoPE (rotate-half), GELU (tanh) or SwiGLU, MHA or
GQA, a causal sliding window, tied or untied head, biases iff LayerNorm.

``prec`` picks the precision of every product:

- ``"f32"``  float32 operands, ``highest`` (the reference itself);
- ``"fp8"``  both operands of every product rounded through
  ``float8_e4m3fn`` (their cotangents through ``float8_e5m2``), one amax
  scale per tensor, float32 accumulation: the control, the step below
  bfloat16 that a later PR might be tempted by;
- ``"bf16"`` operands rounded through bfloat16 (a diagnostic).

Training follows the optimizer the traffic file states (AdamW as optax
defines it) for a few steps, layer by layer and in blocks of rows so that a
1.3B-parameter model at batch 16 x 1024 fits one 16 GB chip in float32:
parameters and one gradient on the device, earlier gradients on the host.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST


# -- shapes ------------------------------------------------------------------


def dims(cfg: dict) -> dict:
    d, H = cfg["d_model"], cfg["n_heads"]
    KV = cfg.get("n_kv_heads") or H
    F = cfg.get("d_ff") or 4 * d
    return dict(d=d, H=H, KV=KV, hd=d // H, F=F, L=cfg["n_layers"],
                V=cfg["vocab_size"], P=cfg["max_seq_len"])


def param_shapes(cfg: dict) -> dict[str, tuple[int, ...]]:
    """Every parameter by path, layers stacked on a leading ``L`` axis."""
    m = dims(cfg)
    d, H, KV, hd, F, L = m["d"], m["H"], m["KV"], m["hd"], m["F"], m["L"]
    bias = cfg["norm"] == "layernorm"
    shapes: dict[str, tuple[int, ...]] = {"embed/embedding": (m["V"], d)}
    if cfg["pos"] == "learned":
        shapes["pos_embed"] = (m["P"], d)
    layer = {
        "attn_norm/scale": (d,),
        "attn/q_proj/kernel": (d, H, hd),
        "attn/k_proj/kernel": (d, KV, hd),
        "attn/v_proj/kernel": (d, KV, hd),
        "attn/o_proj/kernel": (H, hd, d),
        "mlp_norm/scale": (d,),
        "mlp/up_proj/kernel": (d, F),
        "mlp/down_proj/kernel": (F, d),
    }
    if cfg["act"] == "swiglu":
        layer["mlp/gate_proj/kernel"] = (d, F)
    if bias:
        layer.update({
            "attn_norm/bias": (d,), "mlp_norm/bias": (d,),
            "attn/q_proj/bias": (H, hd), "attn/k_proj/bias": (KV, hd),
            "attn/v_proj/bias": (KV, hd), "attn/o_proj/bias": (d,),
            "mlp/up_proj/bias": (F,), "mlp/down_proj/bias": (d,),
        })
        if cfg["act"] == "swiglu":
            layer["mlp/gate_proj/bias"] = (F,)
    for k, s in layer.items():
        shapes["layers/" + k] = (L,) + s
    shapes["final_norm/scale"] = (d,)
    if bias:
        shapes["final_norm/bias"] = (d,)
    if not cfg["tie_embeddings"]:
        shapes["lm_head/kernel"] = (d, m["V"])
    return shapes


# -- arithmetic --------------------------------------------------------------


def _quantize(x, prec: str, fp8):
    if prec == "bf16":
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    if prec == "fp8":
        # one scale per tensor, to the format's largest finite value
        s = float(jnp.finfo(fp8).max) / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
        return (x * s).astype(fp8).astype(jnp.float32) / s
    raise ValueError(f"unknown precision {prec!r}")


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _round(x, prec: str):
    """Round a product's operand to ``prec``.  Going back, the cotangent is
    rounded too, as a lower-precision training step would: e4m3 forward and
    e5m2 backward for fp8, each with its own per-tensor scale."""
    return x if prec == "f32" else _quantize(x, prec, jnp.float8_e4m3fn)


def _round_fwd(x, prec):
    return _round(x, prec), None


def _round_bwd(prec, _, g):
    return (g if prec == "f32" else _quantize(g, prec, jnp.float8_e5m2),)


_round.defvjp(_round_fwd, _round_bwd)


def mm(spec: str, a, b, prec: str):
    return jnp.einsum(spec, _round(a, prec), _round(b, prec), precision=HI,
                      preferred_element_type=jnp.float32)


def norm(x, p: dict, which: str, cfg: dict):
    eps = cfg["norm_eps"]
    if cfg["norm"] == "rmsnorm":
        y = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
        return y * p[which + "/scale"]
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    y = (x - mu) * jax.lax.rsqrt(var + eps)
    return y * p[which + "/scale"] + p[which + "/bias"]


def rope(x, positions, theta: float):
    """Rotate-half RoPE on [B, S, H, hd]."""
    hd = x.shape[-1]
    freqs = 1.0 / (theta ** (np.arange(0, hd, 2, dtype=np.float32) / hd))
    ang = positions[..., None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[:, :, None], jnp.sin(ang)[:, :, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def layer(p: dict, x, cfg: dict, prec: str):
    """One pre-norm block on x [B, S, d]; ``p`` holds one layer's leaves."""
    m = dims(cfg)
    B, S, _ = x.shape
    bias = cfg["norm"] == "layernorm"

    def lin(h, name, spec):
        y = mm(spec, h, p[name + "/kernel"], prec)
        return y + p[name + "/bias"] if bias else y

    h = norm(x, p, "attn_norm", cfg)
    q = lin(h, "attn/q_proj", "bsd,dhk->bshk")
    k = lin(h, "attn/k_proj", "bsd,dhk->bshk")
    v = lin(h, "attn/v_proj", "bsd,dhk->bshk")
    if cfg["pos"] == "rope":
        pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
        q, k = rope(q, pos, cfg["rope_theta"]), rope(k, pos, cfg["rope_theta"])
    rep = m["H"] // m["KV"]
    if rep > 1:
        k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    s = mm("bqhk,bthk->bhqt", q, k, prec) / math.sqrt(m["hd"])
    qi, ti = jnp.arange(S)[:, None], jnp.arange(S)[None, :]
    ok = ti <= qi
    if cfg.get("sliding_window"):
        ok &= ti > qi - cfg["sliding_window"]
    s = jnp.where(ok[None, None], s, -jnp.inf)
    w = jax.nn.softmax(s, axis=-1)
    o = mm("bhqt,bthk->bqhk", w, v, prec)
    x = x + lin(o, "attn/o_proj", "bqhk,hkd->bqd")

    h = norm(x, p, "mlp_norm", cfg)
    up = lin(h, "mlp/up_proj", "bsd,df->bsf")
    if cfg["act"] == "swiglu":
        up = jax.nn.silu(lin(h, "mlp/gate_proj", "bsd,df->bsf")) * up
    elif cfg["act"] == "gelu":
        up = jax.nn.gelu(up, approximate=True)
    else:
        raise ValueError(f"unknown act {cfg['act']!r}")
    return x + lin(up, "mlp/down_proj", "bsf,fd->bsd")


def _split(params: dict) -> tuple[dict, dict]:
    """(one dict of stacked layer leaves without the prefix, the rest)."""
    lay = {k[len("layers/"):]: v for k, v in params.items()
           if k.startswith("layers/")}
    rest = {k: v for k, v in params.items() if not k.startswith("layers/")}
    return lay, rest


def embed(rest: dict, tokens, cfg: dict):
    x = rest["embed/embedding"][tokens]
    if cfg["pos"] == "learned":
        x = x + rest["pos_embed"][None, : tokens.shape[1]]
    return x


def head_logits(rest: dict, x, cfg: dict, prec: str):
    h = norm(x, rest, "final_norm", cfg)
    if cfg["tie_embeddings"]:
        return mm("bsd,vd->bsv", h, rest["embed/embedding"], prec)
    return mm("bsd,dv->bsv", h, rest["lm_head/kernel"], prec)


# -- forward only (serving check) ---------------------------------------------


@functools.partial(jax.jit, static_argnames=("cfg_key", "prec"))
def _forward(params, tokens, cfg_key, prec):
    cfg = dict(cfg_key)
    lay, rest = _split(params)
    x = embed(rest, tokens, cfg)
    x, _ = jax.lax.scan(lambda x, p: (layer(p, x, cfg, prec), None), x, lay)
    return head_logits(rest, x, cfg, prec)


def cfg_key(cfg: dict) -> tuple:
    return tuple(sorted((k, v) for k, v in cfg.items()
                        if isinstance(v, (int, float, str, bool, type(None)))))


def forward_logits(params: dict, cfg: dict, tokens, prec: str = "f32"):
    """Logits [B, S, V] of a full forward pass over ``tokens`` [B, S]."""
    return _forward(params, jnp.asarray(tokens, jnp.int32), cfg_key(cfg), prec)


# -- loss and gradients, layer by layer ----------------------------------------


def _blocks(x, rows: int):
    return x.reshape((x.shape[0] // rows, rows) + x.shape[1:])


@functools.partial(jax.jit, static_argnames=("cfg_key", "prec", "rows"))
def _fwd_layers(params, tokens, cfg_key, prec, rows):
    """Every layer's input, [L, B, S, d], and the last layer's output."""
    cfg = dict(cfg_key)
    lay, rest = _split(params)
    x = embed(rest, tokens, cfg)

    def body(x, p):
        y = jax.lax.map(lambda xb: layer(p, xb, cfg, prec), _blocks(x, rows))
        return y.reshape(x.shape), x

    return jax.lax.scan(body, x, lay)


@functools.partial(jax.jit, static_argnames=("cfg_key", "prec", "rows"))
def _head(params, x_last, targets, cfg_key, prec, rows):
    """Mean next-token loss, its gradient for the head's own leaves and for
    the last layer's output, in blocks of rows (the logits of 16 x 1024 x
    50257 do not fit at once)."""
    cfg = dict(cfg_key)
    _, rest = _split(params)
    n = targets.size

    def loss_sum(rest, xb, tb):
        lg = head_logits(rest, xb, cfg, prec)
        lse = jax.nn.logsumexp(lg, axis=-1)
        return jnp.sum(lse - jnp.take_along_axis(lg, tb[..., None], -1)[..., 0]) / n

    def body(acc, xt):
        xb, tb = xt
        l, (g_rest, g_x) = jax.value_and_grad(loss_sum, (0, 1))(rest, xb, tb)
        return (acc[0] + l, jax.tree.map(jnp.add, acc[1], g_rest)), g_x

    zero = (jnp.zeros((), jnp.float32), jax.tree.map(jnp.zeros_like, rest))
    (loss, g_rest), g_x = jax.lax.scan(
        body, zero, (_blocks(x_last, rows), _blocks(targets, rows)))
    return loss, g_rest, g_x.reshape(x_last.shape)


@functools.partial(jax.jit, static_argnames=("cfg_key", "prec", "rows"),
                   donate_argnames=("xs", "g_x"))
def _bwd_layers(params, xs, g_x, cfg_key, prec, rows):
    """Back through the layers, last to first: each layer's gradient is
    summed over the blocks of rows before the next layer is touched."""
    cfg = dict(cfg_key)
    lay, _ = _split(params)

    def body(g_x, px):
        p, x = px

        def block(acc, xg):
            xb, gb = xg
            _, vjp = jax.vjp(lambda p, xb: layer(p, xb, cfg, prec), p, xb)
            g_p, g_xb = vjp(gb)
            return jax.tree.map(jnp.add, acc, g_p), g_xb

        g_p, g_in = jax.lax.scan(block, jax.tree.map(jnp.zeros_like, p),
                                 (_blocks(x, rows), _blocks(g_x, rows)))
        return g_in.reshape(x.shape), g_p

    return jax.lax.scan(body, g_x, (lay, xs), reverse=True)


@functools.partial(jax.jit, static_argnames=("cfg_key",),
                   donate_argnames=("g_rest",))
def _bwd_embed(g_rest, g_x0, tokens, cfg_key):
    cfg = dict(cfg_key)
    g = dict(g_rest)
    g["embed/embedding"] = g["embed/embedding"].at[tokens].add(g_x0)
    if cfg["pos"] == "learned":
        S = tokens.shape[1]
        g["pos_embed"] = g["pos_embed"].at[:S].add(jnp.sum(g_x0, 0))
    return g


def loss_and_grads(params: dict, cfg: dict, input_ids, *, prec: str = "f32",
                   rows: int = 2):
    """Mean next-token loss over ``input_ids`` [B, S+1] and its gradient for
    every leaf of ``params`` (a flat dict by path)."""
    key = cfg_key(cfg)
    ids = jnp.asarray(input_ids, jnp.int32)
    tokens, targets = ids[:, :-1], ids[:, 1:]
    rows = math.gcd(rows, tokens.shape[0])
    x_last, xs = _fwd_layers(params, tokens, key, prec, rows)
    loss, g_rest, g_x = _head(params, x_last, targets, key, prec, rows)
    del x_last
    g_x0, g_lay = _bwd_layers(params, xs, g_x, key, prec, rows)
    g_rest = _bwd_embed(g_rest, g_x0, tokens, key)
    grads = {"layers/" + k: v for k, v in g_lay.items()}
    grads.update(g_rest)
    return loss, grads


# -- AdamW, from the history of gradients --------------------------------------


@functools.partial(jax.jit, static_argnames=("hp",), donate_argnames=("theta",))
def _adamw_leaf(theta, grads, hp):
    """One AdamW step (optax's: decoupled decay, bias-corrected moments) on
    one leaf; ``grads`` is every gradient so far, oldest first."""
    lr, b1, b2, eps, wd = hp
    t = len(grads)
    m = sum((1 - b1) * b1 ** (t - 1 - i) * g for i, g in enumerate(grads))
    v = sum((1 - b2) * b2 ** (t - 1 - i) * g * g for i, g in enumerate(grads))
    m_hat, v_hat = m / (1 - b1 ** t), v / (1 - b2 ** t)
    return theta - lr * (m_hat / (jnp.sqrt(v_hat) + eps) + wd * theta)


def _norm(x) -> float:
    return float(jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))))


def train_steps(params: dict, cfg: dict, batches: list, opt: dict, *,
                make_leaf, prec: str = "f32", rows: int = 2) -> dict:
    """Follow ``len(batches)`` optimizer steps from ``params`` (consumed).

    Returns each step's loss, the per-leaf norm of the first gradient, and
    the per-leaf norm of the parameters' change after the last step.
    ``make_leaf(path)`` regenerates a leaf of the initial parameters (so no
    second copy of them is held); earlier gradients wait on the host.
    """
    hp = (opt["lr"], opt["b1"], opt["b2"], opt["eps"], opt["weight_decay"])
    history: list[dict] = []  # host copies of earlier gradients
    losses, grad_norms, delta_norms = [], {}, {}
    n = len(batches)
    for t, batch in enumerate(batches):
        loss, grads = loss_and_grads(params, cfg, batch["input_ids"],
                                     prec=prec, rows=rows)
        losses.append(float(loss))
        if t == 0:
            grad_norms = {k: _norm(g) for k, g in grads.items()}
        last = t == n - 1
        for k in sorted(params):
            past = [jnp.asarray(h[k]) for h in history]
            new = _adamw_leaf(params.pop(k), tuple(past + [grads[k]]), hp)
            if last:
                delta_norms[k] = _norm(new - make_leaf(k))
                del new, grads[k]
            else:
                params[k] = new
        if not last:
            history.append({k: np.asarray(g) for k, g in grads.items()})
        del grads
    return {"losses": losses, "grad_norms": grad_norms,
            "delta_norms": delta_norms}
