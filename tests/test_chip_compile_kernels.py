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
import re

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

pytestmark = pytest.mark.usefixtures("_no_compile_cache")


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


# -- a chunk's latent attention: one kernel a layer ---------------------------


def _latent_chunk(q_nope, q_rope, pool, row, pos0, w_uk, w_uv):
    from torch_automatic_distributed_neural_network_tpu.ops.paged_attention import (
        latent_chunk_attention,
    )

    return latent_chunk_attention(q_nope, q_rope, pool, row, pos0, w_uk,
                                  w_uv, scale=192 ** -0.5, interpret=False)


def _latent_chunk_args(one, dtype):
    """The cell's shapes: a chunk of 512 rows of 32 heads (128 + 64), 544
    table entries over 4,097 pages of 64 rows stored in 640 lanes, and
    ``kv_b_proj``'s two halves [512, 32, 128]."""
    sds = lambda s, d: jax.ShapeDtypeStruct(s, d, sharding=one)  # noqa: E731
    return (sds((512, 32, 128), dtype), sds((512, 32, 64), dtype),
            sds((4097, 64, 640), jnp.bfloat16), sds((544,), jnp.int32),
            sds((), jnp.int32), sds((512, 32, 128), dtype),
            sds((512, 32, 128), dtype))


def test_latent_chunk_kernel_compiles_for_v5e(v5e):
    """``tadnn_latent_chunk`` at the cell's shapes, in serving's bfloat16
    (float32 chunks take the plain form): 8 page copies a key block through
    the table row, a group of heads' weights, scores and sums in VMEM under
    the limit the call sets.  The pool reaches the kernel as it lies, the
    scores are no array of the program: its temporaries are the transposed
    queries, weights and output, a few MB."""
    compiled = jax.jit(_latent_chunk).lower(*_latent_chunk_args(
        SingleDeviceSharding(v5e[0]), jnp.bfloat16)).compile()
    (kernel,) = [l for l in compiled.as_text().splitlines()
                 if 'custom_call_target="tpu_custom_call"' in l]
    assert "tadnn_latent_chunk" in kernel.split(" = ")[0]
    assert kernel.count("bf16[4097,64,640]") >= 8
    assert "[32,512,512]" not in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 2**25


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
                *_paged_args(lambda spec: one, 16, 16, False)),
            _compile(_latent_chunk, *_latent_chunk_args(one, jnp.bfloat16)))
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        cc.reset_cache()


@pytest.mark.parametrize("name,where", [
    ("tadnn_flash_fwd", 0), ("tadnn_flash_bwd_dkv", 0),
    ("tadnn_flash_bwd_dq", 0), ("tadnn_paged_decode", 1),
    ("tadnn_latent_chunk", 2)])
def test_kernel_is_named_in_the_compiled_text(kernel_texts, name, where):
    """Each ``pallas_call`` carries a ``name``: it becomes part of the
    Mosaic custom call's instruction name, which is what a profile of the
    chip shows for the kernel (``%jvp_tadnn_flash_fwd_.1 = ...``)."""
    calls = [l.split(" = ")[0] for l in kernel_texts[where].splitlines()
             if 'custom_call_target="tpu_custom_call"' in l]
    assert any(name in c for c in calls), (name, calls)


def _operands(line: str) -> int:
    """Operands of a custom call, counted by their ``%`` references, as
    ``benchmark/lib/trace.n_operands`` counts them in a trace."""
    body = line[line.index("custom-call(") + len("custom-call("):]
    depth = 1
    for i, ch in enumerate(body):
        depth += (ch == "(") - (ch == ")")
        if not depth:
            return body[:i].count("%")
    raise AssertionError(line)


def test_flash_calls_keep_the_shape_the_roofline_metric_reads(kernel_texts):
    """The cell's own call (``[16, 1024, 16, 128]`` bf16, causal), forward
    and backward, compiled for the v5e: the contract that
    ``benchmark/metrics/flash_attn_roofline.py`` reads.  That reader tells
    a forward call by its THREE operands and gives every other Mosaic call
    half of a backward pass's least time, so the forward takes q, k, v and
    nothing else (no scalar-prefetch operand), and the backward is TWO
    calls (dk/dv and dq, six operands each), not one fused kernel."""
    calls = {l.split(" = ")[0].strip().lstrip("%"): _operands(l)
             for l in kernel_texts[0].splitlines()
             if 'custom_call_target="tpu_custom_call"' in l}
    by_kernel = {name: [n for call, n in calls.items() if name in call]
                 for name in ("tadnn_flash_fwd", "tadnn_flash_bwd_dkv",
                              "tadnn_flash_bwd_dq")}
    assert by_kernel == {"tadnn_flash_fwd": [3], "tadnn_flash_bwd_dkv": [6],
                         "tadnn_flash_bwd_dq": [6]}, calls
    assert len(calls) == 3, calls


# -- a model whose layers differ: the folded decode kernel and the grouped
# matmuls at the published widths (the serving programs:
# ``test_chip_compile_serving.py``) -----------------------------------------


# (slots, query heads, kv heads, blocks of 16 in max_len) of the two models
_FOLDED = {"gpt2-1p3b": (8, 16, 16, 64), "trinity-large-ep8": (16, 48, 8, 832),
           "olmo-hybrid-7b-pp2": (8, 30, 30, 2112)}


def _operand_shapes(custom_call: str) -> list[str]:
    """The shapes of a ``tpu_custom_call``'s operands, in order."""
    return re.findall(r"\w+\[[\d,]*\]", custom_call.split(
        "operand_layout_constraints={")[1].split("}}")[0])


@pytest.mark.parametrize("window", [None, 4096], ids=["full", "window4096"])
@pytest.mark.parametrize("config", sorted(_FOLDED))
def test_folded_paged_decode_compiles_for_v5e(v5e, config, window):
    """The MXU form of the decode kernel at both serving cells' shapes, its
    grid a work list of traced length built from the contexts, the slots'
    flags and the pools' own shape (``item_pages``: 16 pages a step on
    trinity-large-ep8's pages of 64 KB and 17 items of 16 over its window's
    band, 8 on the other two: the geometry the cells run).  The block tables stay a scalar-prefetch operand in their
    own shape, ``s32[slots, max_len / block]``: what
    ``benchmark/metrics/paged_attn_roofline.py`` tells the kernel by."""
    from torch_automatic_distributed_neural_network_tpu.ops.paged_attention import (
        folded_work_list,
        item_pages,
    )

    slots, hq, kvh, mb = _FOLDED[config]
    if config == "olmo-hybrid-7b-pp2" and window:
        pytest.skip("no sliding layer in this model")
    one = SingleDeviceSharding(v5e[0])
    sds = lambda s, d: jax.ShapeDtypeStruct(s, d, sharding=one)  # noqa: E731
    # (a pool of slots x max_len pages at 30 heads would be 8 GB: the
    # cell's own 4,609 pages)
    pool = sds((min(slots * mb + 1, 4609), 16, kvh * 128), jnp.bfloat16)

    def call(q, k, v, t, c, active):
        work = folded_work_list(c, active, pools=(k, v), max_blocks=mb,
                                window=window)
        return paged_attention(q, k, v, t, c, window=window, work=work,
                               interpret=False)

    compiled = jax.jit(call).lower(
        sds((slots, hq, 128), jnp.bfloat16), pool, pool,
        sds((slots, mb), jnp.int32), sds((slots,), jnp.int32),
        sds((slots,), jnp.bool_)).compile()
    (kernel,) = [l for l in compiled.as_text().splitlines()
                 if 'custom_call_target="tpu_custom_call"' in l]
    assert "tadnn_paged_decode_folded" in kernel.split(" = ")[0]
    operands = _operand_shapes(kernel)
    # (a window's items start wherever a band does: its table is padded by
    # one item's pages, and ``paged_attn_roofline.by_kind`` tells the kernel
    # by its name)
    pages, items = item_pages((pool, pool), mb, window)
    assert (pages, items) == {
        ("gpt2-1p3b", None): (8, 8), ("gpt2-1p3b", 4096): (8, 8),
        ("trinity-large-ep8", None): (16, 52),
        ("trinity-large-ep8", 4096): (16, 17),
        ("olmo-hybrid-7b-pp2", None): (8, 264)}[config, window]
    assert f"s32[{slots},{mb + pages * bool(window)}]" in operands
    # each pool ONE operand, left where it lies: the kernel copies an item's
    # pages itself, into buffers that fit the default VMEM limit (no
    # ``vmem_limit_bytes``) at olmo-hybrid-7b-pp2's page of 3,840 lanes too
    assert operands.count(f"bf16[{pool.shape[0]},16,{kvh * 128}]") == 2
    assert compiled.memory_analysis().temp_size_in_bytes < 2**22


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "float32_queries"])
def test_latent_paged_decode_compiles_for_v5e(v5e, dtype):
    """The latent kernel at the cell's shape: 32 heads, 24 slots of 544
    pages of 64 rows stored in 640 lanes (512 + 64 numbers and zeros), 16
    page copies a grid step made by the kernel itself, its grid a work list
    of traced length; in serving's bfloat16 and with ``chip_smoke.py``'s
    float32 queries.  The pool reaches the kernel as it lies, ONE operand:
    no copy of it among the temporaries (rows of 576 did get one: the
    chip's layout for such an array puts another axis in the lanes)."""
    from torch_automatic_distributed_neural_network_tpu.ops.paged_attention import (
        folded_work_list,
    )

    slots, heads, mb, bs = 24, 32, 544, 64
    one = SingleDeviceSharding(v5e[0])
    sds = lambda s, d: jax.ShapeDtypeStruct(s, d, sharding=one)  # noqa: E731
    pool = sds((4097, bs, 640), jnp.bfloat16)

    def call(q, k, t, c, active):
        work = folded_work_list(c, active, pools=(k,), max_blocks=mb)
        return paged_attention(q, k, jnp.zeros((0,), k.dtype), t, c,
                               work=work, scale=192 ** -0.5, value_dim=512,
                               interpret=False)

    compiled = jax.jit(call).lower(
        sds((slots, heads, 576), dtype), pool, sds((slots, mb), jnp.int32),
        sds((slots,), jnp.int32), sds((slots,), jnp.bool_)).compile()
    (kernel,) = [l for l in compiled.as_text().splitlines()
                 if 'custom_call_target="tpu_custom_call"' in l]
    assert "tadnn_paged_decode_latent" in kernel.split(" = ")[0]
    operands = _operand_shapes(kernel)
    assert operands.count("bf16[4097,64,640]") == 1
    assert f"s32[{slots},{mb}]" in operands
    assert compiled.memory_analysis().temp_size_in_bytes < 2**24


def test_folded_paged_decode_compiles_for_float32_queries(v5e):
    """float32 queries over a bf16 pool (``chip_smoke.py``'s comparison with
    the reference, at GPT-2 1.3B's 16 heads of 128): float32 products, the
    work list built by the kernel's own entry."""
    one = SingleDeviceSharding(v5e[0])
    sds = lambda s, d: jax.ShapeDtypeStruct(s, d, sharding=one)  # noqa: E731
    pool = sds((8 * 64 + 1, 16, 16 * 128), jnp.bfloat16)
    _compile(lambda q, k, v, t, c: paged_attention(
        q, k, v, t, c, interpret=False),
        sds((8, 16, 128), jnp.float32), pool, pool,
        sds((8, 64), jnp.int32), sds((8,), jnp.int32))


@pytest.mark.parametrize("pairs", [64, 2048], ids=["decode", "chunk"])
def test_grouped_matmul_compiles_for_v5e(v5e, pairs):
    """The expert FFN's two kernels over 32 held experts of 3072 x 3072:
    16-row tiles for a decode step's 64 pairs, 128-row tiles for a
    chunk's 2,048."""
    from torch_automatic_distributed_neural_network_tpu.ops.grouped_matmul import (
        grouped_matmul,
    )

    one = SingleDeviceSharding(v5e[0])
    sds = lambda s, d: jax.ShapeDtypeStruct(s, d, sharding=one)  # noqa: E731
    tm = 16 if pairs <= 256 else 128
    n_tiles = pairs // tm + 32
    w = sds((32, 3072, 3072), jnp.bfloat16)

    def ffn(rows, wg, wu, wd, tg, na):
        h = grouped_matmul(rows, wu, tg, na, tm=tm, w_gate=wg,
                           interpret=False)
        return grouped_matmul(h, wd, tg, na, tm=tm, interpret=False)

    text = _compile(ffn, sds((n_tiles * tm, 3072), jnp.bfloat16), w, w, w,
                    sds((n_tiles,), jnp.int32), sds((), jnp.int32))
    assert "tadnn_moe_grouped_mm_gate_up" in text
    assert "tadnn_moe_grouped_mm_down" in text


@pytest.mark.parametrize("tokens,top_k,d,f", [
    (528, 4, 3072, 3072), (16, 4, 3072, 3072),
    (536, 8, 2048, 768), (24, 8, 2048, 768)],
    ids=["trinity_chunk", "trinity_step", "joyai_chunk", "joyai_step"])
def test_grouped_matmul_gathers_its_rows_for_v5e(v5e, tokens, top_k, d, f):
    """The gate-up kernel picking a tile's rows out of the tokens, which it
    holds whole in VMEM in one buffer beside the experts' slabs, at the two
    expert models' widths and both tile sizes (a chunk with the step's
    rows, a decode step): 32 held experts."""
    from torch_automatic_distributed_neural_network_tpu.ops.grouped_matmul import (
        grouped_matmul,
    )
    from torch_automatic_distributed_neural_network_tpu.parallel.expert import (
        expert_tiles,
    )

    one = SingleDeviceSharding(v5e[0])
    sds = lambda s, t: jax.ShapeDtypeStruct(s, t, sharding=one)  # noqa: E731
    tm, n_tiles = expert_tiles(tokens, top_k, 32)

    def ffn(x, src, wg, wu, wd, tg, na):
        h = grouped_matmul(x, wu, tg, na, tm=tm, w_gate=wg, src=src,
                           interpret=False)
        return grouped_matmul(h, wd, tg, na, tm=tm, interpret=False)

    text = _compile(
        ffn, sds((tokens, d), jnp.bfloat16), sds((n_tiles * tm,), jnp.int32),
        sds((32, d, f), jnp.bfloat16), sds((32, d, f), jnp.bfloat16),
        sds((32, f, d), jnp.bfloat16), sds((n_tiles,), jnp.int32),
        sds((), jnp.int32))
    assert "tadnn_moe_grouped_mm_gate_up" in text
    assert "tadnn_moe_grouped_mm_down" in text


# -- the gated delta rule's two kernels, at Olmo-Hybrid-7B's widths -----------


@pytest.mark.parametrize("rule", ["gdn", "kda"])
@pytest.mark.parametrize("form,dtype,T", [
    ("chunk", jnp.bfloat16, 512), ("chunk", jnp.float32, 512),
    ("chunk", jnp.bfloat16, 192), ("chunk", jnp.float32, 192),
    ("step", jnp.bfloat16, None)])
def test_gated_delta_kernels_compile_for_v5e(v5e, form, dtype, T, rule):
    """``gdn``: 30 heads, keys of 96 and values of 192 (neither a multiple
    of the lane width): the chunk kernel over a prefill chunk of 512 (eight
    sub-chunks a head, solved as four pairs: two ``[64, 64]`` float32
    systems on the diagonal of a ``[128, 128]`` one, the rows behind them
    stacked) and over 192 rows (a pair and a single) in serving's bfloat16
    and in ``chip_smoke.py``'s float32, the step kernel over 8 slots of a
    pool of 9 rows, which it reads and writes in place.  ``kda``: the
    kernels of a decay a channel at Kimi-Linear's widths, 32 heads of 128
    and 128, 96 slots of a pool of 97 rows (203 MB)."""
    from torch_automatic_distributed_neural_network_tpu.ops import gated_delta as gd

    one = SingleDeviceSharding(v5e[0])
    sds = lambda s, d: jax.ShapeDtypeStruct(s, d, sharding=one)  # noqa: E731
    H, dk, dv, S = (30, 96, 192, 8) if rule == "gdn" else (32, 128, 128, 96)
    decays = lambda n: (n, H) if rule == "gdn" else (n, H, dk)  # noqa: E731
    chunk, step = ((gd.gated_delta_chunk_pallas, gd.gated_delta_step_pallas)
                   if rule == "gdn" else
                   (gd.kda_chunk_pallas, gd.kda_step_pallas))
    if form == "chunk":
        text = _compile(
            chunk, sds((T, H, dk), dtype),
            sds((T, H, dk), dtype), sds((T, H, dv), dtype),
            sds(decays(T), jnp.float32), sds((T, H), jnp.float32),
            sds((H, dk, dv), jnp.float32))
        assert f"tadnn_{rule}_chunk" in text
        return
    compiled = jax.jit(step, donate_argnums=(5,)).lower(
        sds((S, H, dk), dtype), sds((S, H, dk), dtype), sds((S, H, dv), dtype),
        sds(decays(S), jnp.float32), sds((S, H), jnp.float32),
        sds((S + 1, H, dk, dv), jnp.float32), sds((S,), jnp.int32)).compile()
    assert f"tadnn_{rule}_step" in compiled.as_text()
    assert not [l for l in compiled.as_text().splitlines()
                if " copy(" in l and f"f32[{S + 1},{H},{dk},{dv}]" in l]
    # the pool is the output: no second copy of it
    assert compiled.memory_analysis().alias_size_in_bytes \
        >= (S + 1) * H * dk * dv * 4


@pytest.mark.parametrize("T", [512, 454])
def test_kda_chunk_is_one_kernel_with_nothing_prepared_for_it(v5e, T):
    """A decay a channel at Kimi-Linear's widths, a whole prefill chunk and
    the traced window's mean one (a padded tail), with q, k, v and g as the
    mixer's convolution and projections leave them (``[T, 32 x 128]``, cut
    into heads by a reshape) and ``o`` as its output projection takes it:
    the compiled chunk form is ONE ``tadnn_kda_chunk`` call, with no
    pairwise ``[.., 16, 16, 128]`` value (``kda_products`` is the CPU
    path's), no copy or transpose of the rows on either side of the kernel,
    and no other op but the padding of a tail."""
    import re

    from torch_automatic_distributed_neural_network_tpu.ops import gated_delta as gd

    one = SingleDeviceSharding(v5e[0])
    sds = lambda s, d: jax.ShapeDtypeStruct(s, d, sharding=one)  # noqa: E731
    H, dk, dv = 32, 128, 128

    def mixer(q, k, v, g, beta, state):
        heads = lambda x: x.reshape(T, H, -1)  # noqa: E731
        o, state = gd.kda_chunk_pallas(heads(q), heads(k), heads(v), heads(g),
                                       beta, state)
        return o.reshape(T, H * dv), state

    text = _compile(
        mixer, sds((T, H * dk), jnp.bfloat16), sds((T, H * dk), jnp.bfloat16),
        sds((T, H * dv), jnp.bfloat16), sds((T, H * dk), jnp.float32),
        sds((T, H), jnp.float32), sds((H, dk, dv), jnp.float32))
    lines = text.splitlines()
    assert len(re.findall(r"^\s*(?:ROOT )?%tadnn_kda_chunk[.\d]* = ", text,
                          re.M)) == 1
    assert not re.search(r"f32\[[\d,]*16,16,128\]", text)
    # (beta [T, 32] aside: the compiler lays that entry parameter out
    # column-major and turns it round, 64 kB)
    moved = [l.strip()[:160] for l in lines
             if re.search(r"(copy|transpose)\S*\(", l.split(" = ")[-1][:80])
             and not re.search(rf"= f32\[\d+,{H}\]", l)]
    assert not moved, moved
    others = [l.strip()[:160] for l in lines if re.search(
        r" = \S+ (fusion|pad|slice|concatenate)\(", l)]
    # a tail: five operands padded to 512 rows, the output cut to 454
    assert len(others) <= (0 if T % gd.SUB_CHUNK == 0 else 6), others


# -- the selective scan's two kernels, at Phi-4-mini-flash's widths -----------


@pytest.mark.parametrize("form", ["chunk", "step"])
def test_ssm_kernels_compile_for_v5e(v5e, form):
    """5,120 channels of 16 state numbers: the chunk kernel over a prefill
    chunk of 512 tokens (ten blocks of 512 lanes, the chunk's ``B`` and ``C``
    as columns), the step kernel over 64 slots of a pool of 65 rows (21 MB),
    which it reads and writes in place."""
    from torch_automatic_distributed_neural_network_tpu.ops import ssm

    one = SingleDeviceSharding(v5e[0])
    sds = lambda s, d=jnp.float32: jax.ShapeDtypeStruct(  # noqa: E731
        s, d, sharding=one)
    n, N, S, T = 5120, 16, 64, 512
    if form == "chunk":
        text = _compile(
            ssm.ssm_chunk_pallas, sds((T, n), jnp.bfloat16), sds((T, n)),
            sds((N, n)), sds((T, N)), sds((T, N)), sds((n,)), sds((N, n)))
        assert "tadnn_ssm_chunk" in text
        return
    compiled = jax.jit(ssm.ssm_step_pallas, donate_argnums=(6,)).lower(
        sds((S, n), jnp.bfloat16), sds((S, n)), sds((N, n)), sds((S, N)),
        sds((S, N)), sds((n,)), sds((S + 1, N, n)),
        sds((S,), jnp.int32)).compile()
    text = compiled.as_text()
    assert "tadnn_ssm_step" in text
    assert not [l for l in text.splitlines()
                if " copy(" in l and f"f32[{S + 1},{N},{n}]" in l]
    # the pool is the output: no second copy of it
    assert compiled.memory_analysis().alias_size_in_bytes \
        >= (S + 1) * N * n * 4


def test_differential_paged_decode_compiles_for_v5e(v5e):
    """Differential attention's decode at Phi-4-mini-flash's widths: 40
    query heads on 20 KV heads of 64, a folded page of 64 tokens x 1,280
    lanes, 64 slots of 544 pages: the folded MXU kernel at another wiring
    of its lanes (a query head in the lanes of ITS key head; the 128 lanes
    of the pair's two value heads taken of the product), ONE call that
    reads a page once; full attention and the window of 512."""
    from torch_automatic_distributed_neural_network_tpu.ops.paged_attention import (
        paged_attention_folded,
    )

    one = SingleDeviceSharding(v5e[0])
    sds = lambda s, d: jax.ShapeDtypeStruct(s, d, sharding=one)  # noqa: E731
    S, H, KV, hd, bs, MB, NB = 64, 40, 20, 64, 64, 544, 6145
    for window in (None, 512):
        text = _compile(
            lambda q, k, v, t, c: paged_attention_folded(
                q, k, v, t, c, window=window, interpret=False, diff=True),
            sds((S, H, hd), jnp.bfloat16), sds((NB, bs, KV * hd), jnp.bfloat16),
            sds((NB, bs, KV * hd), jnp.bfloat16), sds((S, MB), jnp.int32),
            sds((S,), jnp.int32))
        assert len(re.findall(r"^\s*%tadnn_paged_decode_folded[.\d]* = ",
                              text, re.M)) == 1
        assert not [l for l in text.splitlines()
                    if " copy(" in l and f"bf16[{NB},{bs},{KV * hd}]" in l]
