"""The tiny model of ``latent_attention`` layers that
``test_joyai_flash_reference.py`` (the model), ``test_joyai_flash_programs.py``
(the serving programs by hand) and ``test_joyai_flash_engine.py``
(``ServeEngine``) hold against ``benchmark/reference/joyai_flash.py``: its
keys, seeded weights, the reference's logits and an engine over it.  Three
files, because a test run is no shorter than its longest file."""

from __future__ import annotations

import importlib.util
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


ref = _load(os.path.join(BENCH, "reference", "joyai_flash.py"),
            "joyai_flash_reference")
weights = _load(os.path.join(BENCH, "lib", "weights.py"), "bench_weights")

CHUNK, BS = 8, 4
KEYS = dict(
    vocab_size=96, d_model=48, n_layers=4, n_heads=4, d_ff=80,
    max_seq_len=128, norm="rmsnorm", norm_eps=1e-6, act="swiglu", pos="rope",
    rope_theta=32e6, tie_embeddings=False,
    layer_types=["latent_attention"] * 4, latent_q_rank=24, latent_kv_rank=16,
    latent_nope_head_dim=8, latent_rope_head_dim=4, latent_value_head_dim=8,
    n_dense_layers=1, experts_published=16, experts_held=4, first_expert=4,
    experts_per_token=4, shared_experts=1, expert_d_ff=24,
    score_func="sigmoid", route_norm=True, route_scale=2.5)
RANK, ROT = KEYS["latent_kv_rank"], KEYS["latent_rope_head_dim"]


def _params(keys: dict = KEYS, seed: int = 3, *, rope_scale: float = 8.0
            ) -> dict:
    """Seeded leaves; the columns that give the rotated parts are made
    ``rope_scale`` times larger, so that position carries a share of a score
    that a test can see (at 0.02 n the rotated part is a 64th of it)."""
    flat = weights.flat(weights.seed_key(seed), ref.param_shapes(keys))
    r, n = keys["latent_kv_rank"], keys["latent_nope_head_dim"]
    for path in flat:
        if path.endswith("attn/kv_a_proj/kernel"):
            flat[path] = flat[path].at[:, r:].multiply(rope_scale)
        if path.endswith("attn/q_b_proj/kernel"):
            flat[path] = flat[path].at[:, :, n:].multiply(rope_scale)
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


def _without_rotated_key(flat: dict) -> dict:
    """The same leaves with the rotated key part zeroed: a model that sees a
    position only through the causal mask."""
    return {k: v.at[:, RANK:].set(0.0) if k.endswith("attn/kv_a_proj/kernel")
            else v for k, v in flat.items()}


def _engine(flat, journal=None, **kw):
    return ServeEngine(_model(), {"params": weights.nest(flat)}, **{
        "n_slots": 3, "max_len": 96, "block_size": BS, "prefill_chunk": CHUNK,
        "cache_dtype": jnp.float32, "export_cache": False,
        "journal": journal, **kw})


def _regret(flat, req) -> float:
    lg = _want(flat, req.prompt + req.out_tokens)
    n, m = len(req.prompt), len(req.out_tokens)
    rows = lg[n - 1:n - 1 + m]
    return float((rows.max(-1) - rows[np.arange(m), req.out_tokens]).max())
