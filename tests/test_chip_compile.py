"""The main path's Pallas kernels, compiled by the TPU's own compiler.

The interpret-mode tests (test_flash_attention.py, test_paged_attention.py)
check what the kernels compute; they cannot see what Mosaic refuses —
block shapes off the (8, 128) tiling, VMEM overflows, unaligned slices.
The chip's compiler is installed in the sandbox and compiles for a chip
that is *described* (``v5e:2x2``) rather than attached, so these tests
ask it directly, at the GPT-2 1.3B widths ``chip_smoke.py`` runs:
16 heads x head_dim 128, batch 16 x seq 1024 for training, 4 slots over
a [128, 16, kvH, 128] paged pool for serving.  Nothing executes — a
compile that passes is not a chip run.
"""

import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # keep libtpu's logs out of /tmp

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from torch_automatic_distributed_neural_network_tpu.ops.flash_attention import (
    flash_attention,
)
from torch_automatic_distributed_neural_network_tpu.ops.paged_attention import (
    paged_attention,
)


@pytest.fixture(scope="module")
def v5e():
    """Four described v5e devices (no hardware)."""
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no libtpu on this machine
        pytest.skip(f"cannot describe a v5e topology here: {e}")
    return topo.devices


@pytest.fixture(autouse=True)
def _no_compile_cache():
    """A described-device executable can be written to the persistent
    cache but not read back without a chip (the next compile warns and
    redoes it), so the cache stays off around these compiles."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _compile(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text, "no Mosaic kernel in the executable"
    return text


# -- flash attention: the train step's kernel --------------------------------

_B, _S, _H, _D = 16, 1024, 16, 128  # GPT-2 1.3B, the smoke's batch x seq


def _qkv(dev):
    one = SingleDeviceSharding(dev)
    x = jax.ShapeDtypeStruct((_B, _S, _H, _D), jnp.bfloat16, sharding=one)
    return x, x, x


@pytest.mark.parametrize("window", [None, 256], ids=["causal", "window256"])
def test_flash_forward_compiles_for_v5e(v5e, window):
    _compile(lambda q, k, v: flash_attention(
        q, k, v, causal=True, window=window, interpret=False), *_qkv(v5e[0]))


@pytest.mark.parametrize("window", [None, 256], ids=["causal", "window256"])
def test_flash_backward_compiles_for_v5e(v5e, window):
    def loss(q, k, v):
        return jnp.sum(flash_attention(
            q, k, v, causal=True, window=window,
            interpret=False).astype(jnp.float32))

    text = _compile(jax.grad(loss, argnums=(0, 1, 2)), *_qkv(v5e[0]))
    # forward + the dq and the dk/dv kernels
    assert text.count("tpu_custom_call") >= 3


# -- paged decode attention: the server's kernel -----------------------------

_SLOTS, _NB, _BS, _MB = 4, 128, 16, 16


def _paged_args(sharding_of, hq, kvh, quantized):
    def sds(shape, dtype, spec=P()):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding_of(spec))

    heads = P(None, None, "tensor", None)
    if quantized:
        pool = {"q": sds((_NB, _BS, kvh, _D), jnp.int8, heads),
                "scale": sds((_NB, _BS, kvh, 1), jnp.float32, heads)}
    else:
        pool = sds((_NB, _BS, kvh, _D), jnp.bfloat16, heads)
    q = sds((_SLOTS, hq, _D), jnp.bfloat16, P(None, "tensor", None))
    return (q, pool, pool, sds((_SLOTS, _MB), jnp.int32),
            sds((_SLOTS,), jnp.int32))


@pytest.mark.parametrize("hq,kvh", [(16, 16), (32, 8)], ids=["mha", "gqa"])
@pytest.mark.parametrize("quantized", [False, True], ids=["bf16", "int8"])
def test_paged_decode_compiles_for_v5e(v5e, hq, kvh, quantized):
    one = SingleDeviceSharding(v5e[0])
    _compile(
        lambda q, k, v, t, c: paged_attention(q, k, v, t, c,
                                              interpret=False),
        *_paged_args(lambda spec: one, hq, kvh, quantized))


@pytest.mark.parametrize("quantized", [False, True], ids=["bf16", "int8"])
def test_paged_decode_tp_compiles_for_v5e(v5e, quantized):
    """The tensor-parallel path: the kernel per head shard under
    shard_map, over two of the described chips."""
    mesh = Mesh(np.asarray(v5e[:2]), ("tensor",))
    _compile(
        lambda q, k, v, t, c: paged_attention(q, k, v, t, c,
                                              interpret=False, mesh=mesh),
        *_paged_args(lambda spec: NamedSharding(mesh, spec), 16, 16,
                     quantized))


# -- kernel names: what a device trace tells the kernels apart by ------------


@pytest.fixture(scope="module")
def kernel_texts(v5e):
    """Compiled text of a flash forward + backward and of a paged decode,
    for one described chip (the cache is off around module fixtures too)."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        def loss(q, k, v):
            return jnp.sum(flash_attention(
                q, k, v, causal=True, interpret=False).astype(jnp.float32))

        one = SingleDeviceSharding(v5e[0])
        return (
            _compile(jax.grad(loss, argnums=(0, 1, 2)), *_qkv(v5e[0])),
            _compile(
                lambda q, k, v, t, c: paged_attention(q, k, v, t, c,
                                                      interpret=False),
                *_paged_args(lambda spec: one, 16, 16, False)))
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        cc.reset_cache()


@pytest.mark.parametrize("name,where", [
    ("tadnn_flash_fwd", 0), ("tadnn_flash_bwd_dkv", 0),
    ("tadnn_flash_bwd_dq", 0), ("tadnn_paged_decode", 1)])
def test_kernel_is_named_in_the_compiled_text(kernel_texts, name, where):
    """Each ``pallas_call`` carries a ``name``: it becomes part of the
    Mosaic custom call's instruction name, which is what a profile of the
    chip shows for the kernel (``%jvp_tadnn_flash_fwd_.1 = ...``)."""
    calls = [l.split(" = ")[0] for l in kernel_texts[where].splitlines()
             if 'custom_call_target="tpu_custom_call"' in l]
    assert any(name in c for c in calls), (name, calls)


# -- the two base serving programs, for the tree the engine holds -------------


@pytest.mark.parametrize("program", ["decode_step", "prefill_chunk"])
def test_serving_program_holds_no_convert_of_a_weight_stack(
        v5e, monkeypatch, program):
    """``jit_serve_decode_step`` and ``jit_serve_prefill_chunk`` at the
    GPT-2 1.3B geometry of the benchmark's serving cells (8 slots, 1024
    positions, blocks of 16, chunks of 128), compiled for one described
    v5e with the operands ``ServeEngine`` hands them: the layers' weights
    already in bf16 (``decode.compute_dtype_params``).  Handed float32
    weights, this compiler moves their rounding out of the layer scan and
    converts each whole ``[24, ...]`` stack in every call, beside a bf16
    copy of all of them among the temporaries; here no layer's weight
    is converted at all and the temporaries are the KV pool's copy
    (decode) or nothing (chunk)."""
    import re

    from torch_automatic_distributed_neural_network_tpu.inference import (
        decode,
    )
    from torch_automatic_distributed_neural_network_tpu.inference.serve import (
        engine,
    )
    from torch_automatic_distributed_neural_network_tpu.models.transformer_core import (
        DecoderLM,
        TransformerConfig,
    )
    from torch_automatic_distributed_neural_network_tpu.ops import (
        paged_attention as paged,
    )

    # the default backend is the CPU here: ask for the kernel, not its
    # interpreter, as the chip would
    monkeypatch.setattr(paged, "_default_interpret", lambda: False)
    cfg = TransformerConfig(
        vocab_size=50257, d_model=2048, n_layers=24, n_heads=16, d_ff=8192,
        max_seq_len=2048, dtype=jnp.bfloat16, remat=False)
    given = jax.eval_shape(DecoderLM(cfg).init, jax.random.key(0),
                           np.zeros((1, 8), np.int32))["params"]
    params = jax.eval_shape(
        lambda p: decode.compute_dtype_params(p, cfg), given)
    slots, max_len, block, chunk = 8, 1024, 16, 128
    if program == "decode_step":
        pool = jax.ShapeDtypeStruct(
            (cfg.n_layers, slots * max_len // block + 1, block,
             cfg.kv_heads, cfg.head_dim), jnp.bfloat16)
        i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
        operands = (
            params, {"k": pool, "v": pool}, i32(slots, max_len // block),
            i32(slots), i32(slots, 1),
            jax.ShapeDtypeStruct((slots,), jnp.bool_), {}, i32(slots),
            jax.eval_shape(lambda: jax.random.key(0)))

        def step(params, *rest):
            return engine._paged_decode_step(
                params, *rest, cfg=cfg, moe_decode="dense",
                sample=decode.SampleConfig(temperature=0.0))

        fn = jax.jit(step, donate_argnums=(1,))
    else:
        operands = (
            params, jax.ShapeDtypeStruct((1, chunk), jnp.int32),
            jax.eval_shape(lambda: decode.KVCache.init(
                cfg, 1, max_len, dtype=jnp.bfloat16)),
            jax.ShapeDtypeStruct((), jnp.int32))

        def step(params, *rest):
            return engine._prefill_chunk_step(
                params, *rest, cfg=cfg, moe_decode="dense")

        fn = jax.jit(step)
    one = SingleDeviceSharding(v5e[0])
    compiled = fn.lower(*jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one),
        operands)).compile()
    text = compiled.as_text()
    assert ("tpu_custom_call" in text) == (program == "decode_step")
    # a weight is an entry parameter named for its path in ``params``
    assert re.search(r"%params__layers____mlp____up_proj____kernel__\S* = "
                     r"bf16\[24,2048,8192\]\S* parameter\(", text)
    to_bf16 = [l.strip()[:120] for l in text.splitlines()
               if re.search(r"= bf16\[[^\]]*\]\S* convert\(%params__layers", l)]
    assert to_bf16 == []
    temp_gib = compiled.memory_analysis().temp_size_in_bytes / 2**30
    assert temp_gib < (1.6 if program == "decode_step" else 0.1), temp_gib
