"""The tiny decoder-hybrid-decoder (Mamba layers beside windowed differential
attention, one full-attention layer whose cache the cross layers read again,
Gated Memory Units) that ``test_phi4_flash_reference.py`` holds against
``benchmark/reference/phi4_flash.py``: its keys, seeded weights, the
reference's logits and an engine over it."""

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
ATOL = 5e-6


def _load(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load(os.path.join(BENCH, "reference", "phi4_flash.py"),
            "phi4_flash_reference")
weights = _load(os.path.join(BENCH, "lib", "weights.py"), "bench_weights")
weights_gdn = _load(os.path.join(BENCH, "lib", "weights_gdn.py"),
                    "bench_weights_gdn")

CHUNK, BS, WINDOW = 8, 4, 12
KEYS = dict(
    vocab_size=96, d_model=48, n_layers=8, n_heads=8, n_kv_heads=4, d_ff=80,
    max_seq_len=128, norm="layernorm", norm_eps=1e-5, act="swiglu",
    pos="none", tie_embeddings=True, sliding_window=WINDOW,
    layer_types=["state_space", "sliding_attention", "state_space",
                 "full_attention", "gated_memory", "shared_attention",
                 "gated_memory", "shared_attention"],
    ssm_inner=96, ssm_state=8, ssm_dt_rank=3,
    diff_attention=True, mlp_bias=False)
SCANS = [i for i, k in enumerate(KEYS["layer_types"]) if k == "state_space"]


def _params(keys: dict = KEYS, seed: int = 3) -> dict:
    """The benchmark's draw (``weights.leaf``, the two decay leaves by the
    family's initialisation), with a state-space layer's two input maps and
    its output map times 8: at d 48 a draw of 0.02 n gives the scan inputs of 0.05 and a
    state of 1e-5 beside the skip ``D c``, so that a lost carry would move
    the logits by 2e-6; at the published d 2,560 the same draw gives inputs
    of order one, which is what the factor restores here."""
    key = weights.seed_key(seed)
    shapes = ref.param_shapes(keys)
    flat = weights.flat(key, shapes)
    for path, shape in shapes.items():
        special = weights_gdn.decay_leaf(key, path, shape)
        if special is not None:
            flat[path] = special
        if path.rsplit("/", 2)[0] + "/A_log" in shapes and path.endswith((
                "in_proj/kernel", "x_proj/kernel", "o_proj/kernel")):
            flat[path] = 8.0 * flat[path]
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
    with open(os.path.join(BENCH, "configs", "phi4-mini-flash-3p8b.json")) as f:
        return json.load(f)


def _engine(flat, journal=None, keys: dict = KEYS, **kw):
    return ServeEngine(_model(keys), {"params": weights.nest(flat)}, **{
        "n_slots": 3, "max_len": 96, "block_size": BS, "prefill_chunk": CHUNK,
        "cache_dtype": jnp.float32, "export_cache": False,
        "journal": journal, **kw})


def _regret(flat, req, keys: dict = KEYS) -> float:
    lg = _want(flat, req.prompt + req.out_tokens, keys)
    n, m = len(req.prompt), len(req.out_tokens)
    rows = lg[n - 1:n - 1 + m]
    return float((rows.max(-1) - rows[np.arange(m), req.out_tokens]).max())
