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


# -- the two serving programs, for the tree and the pool the engine holds ------

_GPT2_1P3B = dict(
    vocab_size=50257, d_model=2048, n_layers=24, n_heads=16, d_ff=8192,
    max_seq_len=2048, remat=False)
# (slots, max_len, block, chunk, pages in the pool: None for slots x max_len)
# of the benchmark's serving cells
_SERVING = {"gpt2-1p3b": (8, 1024, 16, 128, None),
            "trinity-large-ep8": (16, 13312, 16, 512, None),
            "olmo-hybrid-7b-pp2": (8, 33792, 16, 512, 4609),
            "joyai-llm-flash-ep8": (24, 34816, 64, 512, 4097),
            "longcat-flash-omni-ep32": (24, 34816, 64, 512, 4097),
            "kimi-linear-48b-ep8": (96, 36864, 64, 512, 6145)}


@pytest.mark.parametrize("program", ["decode_step", "prefill_chunk",
                                     "chunk_and_step"])
@pytest.mark.parametrize("config", sorted(_SERVING))
def test_serving_programs_update_the_pool_in_place(
        v5e, monkeypatch, config, program):
    """``jit_serve_decode_step`` and ``jit_serve_prefill_chunk``, and the
    chunk that carries a step's decode rows (``chunk_and_step``)
    (``inference/serve/programs.py``: the same three for every model) at the
    geometry of the benchmark's serving cells, compiled for one described
    v5e with the operands ``ServeEngine`` hands them: the layers' weights
    already in bf16 and a subtree a layer (``decode.compute_dtype_params``,
    ``per_layer_params``), one pair of pool arrays a layer.  GPT-2 1.3B (24
    like layers, 8 slots of 1,024) and ``trinity-large-ep8`` (5 layers of
    two kinds, 32 of 256 experts, 16 slots of 13,312 beside 8.3 GiB of
    weights) and ``olmo-hybrid-7b-pp2`` (16 layers, 12 of them linear: a
    recurrent state and a convolution tail a slot beside 4,609 pages of
    keys and values for the 4 full layers, 8 slots of 33,792 beside 9.1 GiB
    of weights) and ``joyai-llm-flash-ep8`` (20 latent layers, 19 of them
    with 32 of 256 experts: 4,097 pages of 64 latent rows stored in 640
    lanes, one array a layer, 24 slots of 34,816 beside 6.9 GiB of weights)
    and ``longcat-flash-omni-ep32`` (8 sublayers of 64 latent heads at d 6,144
    with a dense FFN each, an expert branch of 16 of 512 experts across each
    pair: 2.5 GiB of latent pages beside 10.1 GiB of weights) and
    ``kimi-linear-48b-ep8`` (12 KDA layers, each a state pool of 97 rows of
    [32, 128, 128] float32, 203 MB, and 4 latent layers of 6,145 pages, 15
    of the 16 with 32 of 256 experts: 96 slots of 36,864 beside 7.9 GiB of
    weights; Step 0 of ISSUE 41: no copy of a 203 MB pool round its step
    kernel)
    alike: no layer's weight is converted, the pool is updated
    in place (the output aliases it: pages, states and tails), and no copy
    of a layer's pages or states is among the temporaries (threaded through
    a layer scan, the pool was copied whole every step)."""
    import json
    import os
    import re

    from torch_automatic_distributed_neural_network_tpu.inference import decode
    from torch_automatic_distributed_neural_network_tpu.inference.serve import (
        programs,
    )
    from torch_automatic_distributed_neural_network_tpu.inference.serve.kv_pool import (
        PagedKVPool,
        blocks_for_tokens,
    )
    from torch_automatic_distributed_neural_network_tpu.models.transformer_core import (
        DecoderLM,
        TransformerConfig,
    )
    from torch_automatic_distributed_neural_network_tpu.ops import (
        gated_delta as gdn,
        grouped_matmul as gmm,
        paged_attention as paged,
    )

    # the default backend is the CPU here: ask for the kernels, not their
    # interpreter (or their plain form), as the chip would
    monkeypatch.setattr(paged, "_default_interpret", lambda: False)
    monkeypatch.setattr(gmm, "_default_interpret", lambda: False)
    monkeypatch.setattr(gdn, "_on_tpu", lambda: True)
    keys = _GPT2_1P3B
    if config != "gpt2-1p3b":
        path = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "benchmark", "configs",
            config + ".json")
        with open(path) as f:
            keys = json.load(f)["model"]
    cfg = TransformerConfig(**keys, dtype=jnp.bfloat16)
    given = jax.eval_shape(DecoderLM(cfg).init, jax.random.key(0),
                           np.zeros((1, 8), np.int32))["params"]
    params = jax.eval_shape(lambda p: decode.per_layer_params(
        decode.compute_dtype_params(p, cfg), cfg), given)
    slots, max_len, block, chunk, pages = _SERVING[config]
    MB = blocks_for_tokens(max_len, block)
    made = {}

    def arrays():
        made["pool"] = PagedKVPool(
            cfg, num_blocks=pages or slots * MB + 1, block_size=block,
            n_slots=slots, max_blocks=MB, prefill_chunk=chunk)
        return made["pool"].kv, made["pool"].win_tables

    kv, win = jax.eval_shape(arrays)
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
    if program == "decode_step":
        operands = (params, kv, i32(slots, MB + 4),
                    i32(2 * slots + programs.N_COUNTERS), win,
                    {}, jax.eval_shape(lambda: jax.random.key(0)))

        def step(params, *a):
            return programs.decode_step(
                params, *a, cfg=cfg,
                sample=decode.SampleConfig(temperature=0.0))
    elif program == "chunk_and_step":
        operands = (params, kv, i32(MB + chunk + 3 + slots * (MB + 4)),
                    i32(2 * slots + programs.N_COUNTERS), i32(win.shape[1]),
                    win, jax.eval_shape(lambda: jax.random.key(0)))

        def step(params, *a):
            return programs.chunk_and_step(
                params, *a, cfg=cfg, max_blocks=MB, chunk=chunk,
                sample=decode.SampleConfig(temperature=0.0))
    else:
        operands = (params, kv, i32(MB + chunk + 3), i32(win.shape[1]))

        def step(params, *a):
            return programs.prefill_chunk(params, *a, cfg=cfg, max_blocks=MB)

    one = SingleDeviceSharding(v5e[0])
    compiled = jax.jit(step, donate_argnums=(1,)).lower(*jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one),
        operands)).compile()
    text, mem = compiled.as_text(), compiled.memory_analysis()
    # the parts of a call (``programs.SCOPES``) stand in the ``op_name`` of
    # the chip's own instructions: what a trace of the chip is read by
    scoped = {c for name in re.findall(r'op_name="([^"]*)"', text)
              for c in name.split("/") if c.startswith("tadnn.")}
    absent = {"decode_step": {"tadnn.attend_chunk"},
              "prefill_chunk": {"tadnn.attend_step"}}.get(program, set())
    if not cfg.n_expert_layers:
        absent.add("tadnn.ffn_expert")
    assert scoped == set(programs.SCOPES) - absent
    latent = "latent_attention" in (cfg.layer_types or ())
    mine, other = (("tadnn_paged_decode_latent", "tadnn_paged_decode_folded")
                   if latent else
                   ("tadnn_paged_decode_folded", "tadnn_paged_decode_latent"))
    assert (mine in text) == (program != "prefill_chunk")
    assert other not in text
    assert "tadnn_paged_decode." not in text  # one kernel a kind of page
    # a weight is an entry parameter named for its path in ``params``, read
    # as it is: not converted, not copied
    weight = ("kv_b_proj" if latent and "linear_attention" not in
              cfg.layer_types else "q_proj")
    assert re.search(r"%%params__layers_1____attn____%s____kernel__\S* = "
                     r"bf16\[\S* parameter\(" % weight, text)
    assert not [l.strip()[:120] for l in text.splitlines() if re.search(
        r"= bf16\[[^\]]*\]\S* convert\(%params__layers", l)]
    pool_bytes = made["pool"].total_bytes
    assert mem.alias_size_in_bytes >= pool_bytes  # updated in place
    # (a chunk's activations at d 3,840 beside 11,520 convolved channels
    # are 0.22 GiB; a copy of the 4.5 GB of pages would be twenty times it)
    # (kimi: 96 + 512 rows at d 2,304 through 15 expert layers and 12 KDA
    # layers: 0.31 GiB in the chunk that carries the rows, 0.51 while
    # ``kda_products`` ran before the kernel; a copy of ONE state pool would
    # be 0.19 more)
    roomy = {"olmo-hybrid-7b-pp2": 0.25,
             "kimi-linear-48b-ep8": 0.35}.get(config, 0.2)
    assert mem.temp_size_in_bytes < roomy * 2**30, mem.temp_size_in_bytes
    page_arrays = {("f32" if x.dtype == jnp.float32 else "bf16")
                   + "[%s]" % ",".join(map(str, x.shape))
                   for x in jax.tree.leaves(kv) if x.size}
    # (the 7 MB of a KDA layer's convolution tails at 97 rows the compiler
    # moves into on-chip memory round their gather, ``S(1)``, and lays out
    # anew behind their scatter: 0.3 ms a call over 12 layers, PERF.md
    # section 7; its 203 MB state pool it does not copy: Step 0 of ISSUE 41)
    staged = {"bf16[97,3,12288]"} & page_arrays
    assert not [l[:100] for l in text.splitlines()
                if " copy(" in l and any(a in l for a in page_arrays - staged)]
    if cfg.n_expert_layers:
        # the expert layer's glue: no scatter (the chip runs one an element
        # at a time) and no loop, no copy of a padded [rows, d] array to
        # append a row to it, and ONE pair of kernels a layer (no second,
        # smaller copy of the layer beside the first)
        glue = [l for l in text.splitlines() if "SparseMLP" in l]
        assert glue
        assert not [l.strip()[:120] for l in glue
                    if " scatter(" in l or " while(" in l]
        from torch_automatic_distributed_neural_network_tpu.parallel.expert import (
            expert_tiles,
        )
        rows = {"decode_step": slots, "prefill_chunk": chunk,
                "chunk_and_step": chunk + slots}[program]
        tm, n_tiles = expert_tiles(rows, cfg.experts_per_token,
                                   cfg.n_experts_held)
        padded = [f"bf16[{n_tiles * tm + more},{cfg.d_model}]"
                  for more in (0, 1)]
        assert not [l.strip()[:120] for l in text.splitlines()
                    if re.search(r" (pad|concatenate)\(", l)
                    and any(a in l.split(" = ")[1][:40] for a in padded)]
        for kernel in ("gate_up", "down"):
            assert len(re.findall(
                r"^\s*%tadnn_moe_grouped_mm_" + kernel + r"[.\d]* = ", text,
                re.M)) == cfg.n_expert_layers
    if config == "olmo-hybrid-7b-pp2":
        # 12 linear layers: the step kernel in the one, the chunk kernel in
        # the other; 4.53 GB of pages and 0.25 GB of states and tails
        mine, other = (("tadnn_gdn_step", "tadnn_gdn_chunk")
                       if program == "decode_step"
                       else ("tadnn_gdn_chunk", "tadnn_gdn_step"))
        assert text.count(mine) >= 12
        # both kernels where a chunk carries the decode rows
        assert (text.count(other) >= 12) == (program == "chunk_and_step")
        assert "tadnn_moe_grouped_mm" not in text
        assert round(made["pool"].bytes_full / 1e9, 2) == 4.53
        assert round(sum(made["pool"].bytes_state) / 1e9, 2) == 0.25
        assert mem.argument_size_in_bytes < 14.0 * 2**30
    elif config == "kimi-linear-48b-ep8":
        # 12 KDA layers and 4 latent ones: the step kernel wherever rows
        # decode, the chunk kernel wherever a chunk runs, and the latent
        # layers' own two; the scalar rule's kernels nowhere
        steps = len(re.findall(r"^\s*%tadnn_kda_step[.\d]* = ", text, re.M))
        chunks = len(re.findall(r"^\s*%tadnn_kda_chunk[.\d]* = ", text,
                                re.M))
        assert steps == 12 * (program != "prefill_chunk")
        assert chunks == 12 * (program != "decode_step")
        assert "tadnn_gdn" not in text
        # the chunk kernel forms the channel-wise decays' products itself,
        # from q, k, v as the convolution leaves them, [512, 32 x 128]: no
        # pairwise value of ``kda_products`` in the program, and no copy or
        # transpose of such rows (the latent layers' [512, 32, 128] aside).
        # (In the chunk ALONE the compiler writes a layer's log-decays out
        # of their projection column-major and turns them round, 8 MB a
        # layer; in the chunk that carries the decode rows, the one a full
        # engine runs, it does not.)
        assert not re.search(r"f32\[[\d,]*16,16,128\]", text)
        moved = [l.strip()[:160] for l in text.splitlines() if re.search(
            r"= \w+\[512,4096\]\S* (copy|transpose)\(", l)]
        assert not [l for l in moved if "= bf16" in l], moved
        assert len(moved) <= 12 * (program == "prefill_chunk"), moved
        assert len(re.findall(r"^\s*%tadnn_latent_chunk[.\d]* = ", text,
                              re.M)) == 4 * (program != "decode_step")
        assert text.count("tadnn_paged_decode_latent") >= 4 * (
            program != "prefill_chunk")
        assert text.count("tadnn_moe_grouped_mm") >= 2 * 15
        # the pool: 2.01 GB of latent pages, 2.53 GB of states and tails
        assert made["pool"].bytes_latent == made["pool"].bytes_full
        assert round(made["pool"].bytes_full / 1e9, 2) == 2.01
        assert round(sum(made["pool"].bytes_state) / 1e9, 2) == 2.53
        assert f"f32[{slots + 1},32,128,128]" in page_arrays
        assert mem.argument_size_in_bytes < 12.6 * 2**30
    elif latent:
        # the latent layers' kernel calls (none in the chunk alone: 20, or
        # 8 sublayers), the grouped matmuls of the expert layers (19, or 4
        # branches); 6.25 or 2.5 GiB of latent pages
        n_latent, heads = cfg.n_layers, cfg.n_heads
        assert text.count("tadnn_paged_decode_latent") >= n_latent * (
            program != "prefill_chunk")
        # a chunk's attention is ONE kernel a layer: no loop over key
        # blocks, no [heads, 512, 512] scores among the program's arrays
        chunks = len(re.findall(r"^\s*%tadnn_latent_chunk[.\d]* = ", text,
                                re.M))
        assert chunks == n_latent * (program != "decode_step")
        assert f"[{heads},512,512]" not in text
        assert not [l.strip()[:120] for l in text.splitlines()
                    if " while(" in l and "attend_chunk" in l]
        assert text.count("tadnn_moe_grouped_mm") >= 2 * cfg.n_expert_layers
        assert "tadnn_gdn" not in text
        assert made["pool"].bytes_latent == pool_bytes
        gib, held = {"joyai-llm-flash-ep8": (6.25, 13.3),
                     "longcat-flash-omni-ep32": (2.5, 12.8)}[config]
        assert round(pool_bytes / 2**30, 2) == gib
        assert mem.argument_size_in_bytes < held * 2**30
    elif config == "trinity-large-ep8":
        assert text.count("tadnn_moe_grouped_mm") >= 8  # 2 kernels, 4 layers
        assert round(pool_bytes / 2**30, 2) == 1.94
        assert mem.argument_size_in_bytes < 10.5 * 2**30
    else:
        assert "tadnn_moe_grouped_mm" not in text


# -- a model whose layers differ: the folded decode kernel, the grouped
# matmuls, and the two serving programs at the published widths -------------


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
    grid a work list of traced length built from the contexts and the
    slots' flags.  The block tables stay a scalar-prefetch operand in their
    own shape, ``s32[slots, max_len / block]``: what
    ``benchmark/metrics/paged_attn_roofline.py`` tells the kernel by."""
    from torch_automatic_distributed_neural_network_tpu.ops.paged_attention import (
        folded_work_list,
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
        work = folded_work_list(c, active, max_blocks=mb, block_size=16,
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
    assert f"s32[{slots},{mb}]" in operands
    # each pool ONE operand, left where it lies: the kernel copies an item's
    # pages itself, into buffers that fit the default VMEM limit (no
    # ``vmem_limit_bytes``) at olmo-hybrid-7b-pp2's page of 3,840 lanes too
    assert operands.count(f"bf16[{pool.shape[0]},16,{kvh * 128}]") == 2
    assert compiled.memory_analysis().temp_size_in_bytes < 2**22


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "float32_queries"])
def test_latent_paged_decode_compiles_for_v5e(v5e, dtype):
    """The latent kernel at the cell's shape: 32 heads, 24 slots of 544
    pages of 64 rows stored in 640 lanes (512 + 64 numbers and zeros), 8
    page copies a grid step made by the kernel itself, its grid a work list
    of traced length; in serving's bfloat16 and with ``chip_smoke.py``'s
    float32 queries.  The pool reaches the kernel as it lies, ONE operand:
    no copy of it among the temporaries (rows of 576 did get one: the
    chip's layout for such an array puts another axis in the lanes)."""
    from torch_automatic_distributed_neural_network_tpu.ops.paged_attention import (
        folded_work_list,
        latent_pages,
    )

    slots, heads, mb, bs = 24, 32, 544, 64
    one = SingleDeviceSharding(v5e[0])
    sds = lambda s, d: jax.ShapeDtypeStruct(s, d, sharding=one)  # noqa: E731
    pool = sds((4097, bs, 640), jnp.bfloat16)

    def call(q, k, t, c, active):
        work = folded_work_list(c, active, max_blocks=mb, block_size=bs,
                                pages=latent_pages(mb, bs))
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
@pytest.mark.parametrize("form,dtype", [
    ("chunk", jnp.bfloat16), ("chunk", jnp.float32), ("step", jnp.bfloat16)])
def test_gated_delta_kernels_compile_for_v5e(v5e, form, dtype, rule):
    """``gdn``: 30 heads, keys of 96 and values of 192 (neither a multiple
    of the lane width): the chunk kernel over a prefill chunk of 512 in
    serving's bfloat16 and in ``chip_smoke.py``'s float32, the step kernel
    over 8 slots of a pool of 9 rows, which it reads and writes in place.
    ``kda``: the kernels of a decay a channel at Kimi-Linear's widths, 32
    heads of 128 and 128, 96 slots of a pool of 97 rows (203 MB)."""
    from torch_automatic_distributed_neural_network_tpu.ops import gated_delta as gd

    one = SingleDeviceSharding(v5e[0])
    sds = lambda s, d: jax.ShapeDtypeStruct(s, d, sharding=one)  # noqa: E731
    H, dk, dv, S = (30, 96, 192, 8) if rule == "gdn" else (32, 128, 128, 96)
    decays = lambda n: (n, H) if rule == "gdn" else (n, H, dk)  # noqa: E731
    chunk, step = ((gd.gated_delta_chunk_pallas, gd.gated_delta_step_pallas)
                   if rule == "gdn" else
                   (gd.kda_chunk_pallas, gd.kda_step_pallas))
    if form == "chunk":
        T = 512
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
