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
