"""The tiny hybrid of gated grouped-query attention and Kimi Delta Attention
with an expert FFN in every layer that ``test_solar_open2_reference.py`` (the
model, the share, the parameter count) and ``test_solar_open2_engine.py``
(``ServeEngine``) hold against ``benchmark/reference/solar_open2.py``: its
keys (the published plan: attention FIRST, then three KDA layers, twice;
``beta`` to 2; nothing rotated; no dense layer), seeded weights, the
reference's logits and an engine over it.  Two files, because a test run is
no shorter than its longest file."""

from __future__ import annotations

import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torch_automatic_distributed_neural_network_tpu.inference.serve import (
    ServeEngine,
)
from torch_automatic_distributed_neural_network_tpu.models.transformer_core import (
    DecoderLM,
    TransformerConfig,
)

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
ATOL = 2e-5


def _load(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load(os.path.join(BENCH, "reference", "solar_open2.py"),
            "solar_open2_reference")
weights = _load(os.path.join(BENCH, "lib", "weights.py"), "bench_weights")
weights_gdn = _load(os.path.join(BENCH, "lib", "weights_gdn.py"),
                    "bench_weights_gdn")

CHUNK, BS = 8, 4
KEYS = dict(
    vocab_size=96, d_model=48, n_layers=8, n_heads=8, n_kv_heads=2,
    head_size=8, d_ff=80, max_seq_len=128, norm="rmsnorm", norm_eps=1e-5,
    act="swiglu", pos="none", tie_embeddings=False, attn_gate=True,
    layer_types=(["full_attention"] + ["linear_attention"] * 3) * 2,
    linear_key_heads=4, linear_value_heads=4, linear_key_head_dim=8,
    linear_value_head_dim=16, linear_conv_kernel=4, linear_neg_eigval=True,
    linear_decay="channel", linear_decay_rank=6, linear_gate_rank=6,
    linear_gate_act="sigmoid", n_dense_layers=0, experts_published=16,
    experts_held=4, first_expert=4, experts_per_token=2, shared_experts=1,
    expert_d_ff=24, score_func="sigmoid", route_norm=True, route_scale=1.0)
LINEAR = [i for i, k in enumerate(KEYS["layer_types"])
          if k == "linear_attention"]
FULL = [i for i, k in enumerate(KEYS["layer_types"]) if k == "full_attention"]


def _params(keys: dict = KEYS, seed: int = 3) -> dict:
    key = weights.seed_key(seed)
    shapes = ref.param_shapes(keys)
    flat = weights.flat(key, shapes)
    for path, shape in shapes.items():
        special = weights_gdn.decay_leaf(key, path, shape)
        if special is not None:
            flat[path] = special
    return flat


@pytest.fixture
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


def _tokens(n: int, seed: int = 0):
    return np.random.RandomState(seed).randint(1, KEYS["vocab_size"], size=n)


def _model(keys: dict = KEYS, dtype=jnp.float32):
    return DecoderLM(TransformerConfig(**keys, remat=False, dtype=dtype))


def _want(flat: dict, seq, keys: dict = KEYS) -> np.ndarray:
    return np.asarray(ref.forward_logits(flat, keys, np.asarray(seq)[None]))[0]


def _published() -> dict:
    with open(os.path.join(BENCH, "configs",
                           "solar-open2-250b-ep8.json")) as f:
        return json.load(f)


def _engine(flat, journal=None, **kw):
    return ServeEngine(_model(), {"params": weights.nest(flat)}, **{
        "n_slots": 3, "max_len": 96, "block_size": BS, "prefill_chunk": CHUNK,
        "cache_dtype": jnp.float32, "export_cache": False,
        "journal": journal, **kw})


def _gaps(flat, req) -> np.ndarray:
    """How far each served token lies below the reference's best logit at
    its position."""
    lg = _want(flat, req.prompt + req.out_tokens)
    n, m = len(req.prompt), len(req.out_tokens)
    rows = lg[n - 1:n - 1 + m]
    return rows.max(-1) - rows[np.arange(m), req.out_tokens]
