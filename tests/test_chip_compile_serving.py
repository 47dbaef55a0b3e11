"""The serving programs, compiled by the TPU's own compiler for a described
v5e (``test_chip_compile_kernels.py`` says what that is) at the geometry of
the benchmark's serving cells, for the tree and the pool the engine holds.
The cases of ``joyai-llm-flash-ep8`` and ``kimi-linear-48b-ep8`` run from
``test_chip_compile_serving_joyai_kimi.py``: a test run is no shorter than
its longest file.
"""

import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # keep libtpu's logs out of /tmp

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

pytestmark = pytest.mark.usefixtures("_no_compile_cache")

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
            "kimi-linear-48b-ep8": (96, 36864, 64, 512, 6145),
            "phi4-mini-flash-3p8b": (64, 34816, 64, 512, 6145),
            "solar-open2-250b-ep8": (128, 36864, 64, 512, 8601)}


# the chunk alone is a program of the speculative and the tenant engines: a
# configuration that refuses both (``lora_spec`` with ``layer_types``,
# ``speculative`` with linear layers) has no engine that runs it
_NO_CHUNK_ALONE = ("olmo-hybrid-7b-pp2", "kimi-linear-48b-ep8",
                   "phi4-mini-flash-3p8b", "solar-open2-250b-ep8")


def kda_step_takes_its_operands_as_they_lie(text: str, slots: int,
                                            heads: int, layers: int) -> None:
    """``tadnn_kda_step`` in a compiled serving program (PR 51): its keys,
    queries, decays and values go in as the projections leave them,
    ``f32[slots, heads, 128]``, so the program holds no array relaid for it
    (before: a layer's three ``f32[S, G, 8, 128]{2,3,1,0}`` copies,
    physically ``[S, G, 128, 8]`` and fifteen sixteenths padding, and a
    stacked ``f32[S, G, 24, 128]``), no operand of the call has a minor axis
    of 8, and the state pool is still the call's aliased operand in HBM."""
    import re

    G = heads // 8
    gone = [rf"f32\[{slots},{G},8,128\]\{{2,3,1,0", rf"f32\[{slots},{G},128,8\]",
            rf"f32\[{slots},{G},24,128\]"]
    assert not [l.strip()[:160] for l in text.splitlines()
                if any(re.search(a, l) for a in gone)]
    calls = [l for l in text.splitlines()
             if re.match(r"\s*%tadnn_kda_step[.\d]* = ", l)]
    assert len(calls) == layers
    pool = f"f32[{slots + 1},{heads},128,128]"
    for call in calls:
        given = re.search(r"operand_layout_constraints=\{(.*?)\}\}", call)
        shapes = re.findall(r"\w+\[([\d,]*)\]", given.group(1))
        assert shapes and not [x for x in shapes if x.endswith(",8")], shapes
        assert given.group(1).count(f"f32[{slots},{heads},128]") == 4
        assert given.group(1).endswith(pool + "{3,2,1,0")  # the last
        # the pool in, the pool out: aliased, in HBM (no ``S(1)``)
        assert re.search(re.escape(pool) + r"\{3,2,1,0:T\(8,128\)\}\) custom-call",
                         call), call[:300]
        assert "output_to_operand_aliasing" in call


def cases(configs) -> dict:
    """``pytest.mark.parametrize``'s arguments: the programs an engine of
    each of ``configs`` can run."""
    pairs = [(c, p) for c in configs
             for p in ("decode_step", "prefill_chunk", "chunk_and_step")
             if not (p == "prefill_chunk" and c in _NO_CHUNK_ALONE)]
    return dict(argnames="config,program", argvalues=pairs,
                ids=["-".join(pair) for pair in pairs])


@pytest.mark.parametrize(**cases([
    "gpt2-1p3b", "longcat-flash-omni-ep32", "olmo-hybrid-7b-pp2",
    "trinity-large-ep8"]))
def test_serving_programs_update_the_pool_in_place(
        v5e, monkeypatch, config, program):
    serving_program_updates_the_pool_in_place(
        v5e, monkeypatch, config, program)


def serving_program_updates_the_pool_in_place(
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
        ssm,
    )

    # the default backend is the CPU here: ask for the kernels, not their
    # interpreter (or their plain form), as the chip would
    monkeypatch.setattr(paged, "_default_interpret", lambda: False)
    monkeypatch.setattr(gmm, "_default_interpret", lambda: False)
    monkeypatch.setattr(gdn, "_on_tpu", lambda: True)
    monkeypatch.setattr(ssm, "_on_tpu", lambda: True)
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
    elif cfg.n_dense_layers == 0:  # an expert FFN in EVERY layer
        absent.add("tadnn.ffn")
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
    # (solar: 128 + 512 rows at d 4,096; 0.36 GiB in the step and in the
    # chunk alike, most of it 64 MB projections that the compiler stages
    # through on-chip memory, ``S(1)``, ahead of their products; a copy of
    # ONE state pool would be 0.50 more and of the K/V pages 1.05)
    roomy = {"olmo-hybrid-7b-pp2": 0.25, "kimi-linear-48b-ep8": 0.35,
             "solar-open2-250b-ep8": 0.45}.get(config, 0.2)
    assert mem.temp_size_in_bytes < roomy * 2**30, mem.temp_size_in_bytes
    page_arrays = {("f32" if x.dtype == jnp.float32 else "bf16")
                   + "[%s]" % ",".join(map(str, x.shape))
                   for x in jax.tree.leaves(kv) if x.size}
    # (the 7 MB of a KDA layer's convolution tails at 97 rows the compiler
    # moves into on-chip memory round their gather, ``S(1)``, and lays out
    # anew behind their scatter: 0.3 ms a call over 12 layers, PERF.md
    # section 7; its 203 MB state pool it does not copy: Step 0 of ISSUE 41)
    # (a state-space layer's 2 MB of tails at 65 rows likewise: 9 layers)
    # (64-head KDA layers' 19 MB of tails at 129 rows likewise: 3 layers)
    staged = {"bf16[97,3,12288]", "bf16[65,3,5120]",
              "bf16[129,3,24576]"} & page_arrays
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
    count = lambda name: len(re.findall(  # noqa: E731: a kernel's calls
        r"^\s*%" + name + r"[.\d]* = ", text, re.M))
    if config == "phi4-mini-flash-3p8b":
        # 9 state-space layers: the step kernel wherever rows decode, the
        # chunk kernel wherever a chunk runs; 16 attention layers: ONE
        # folded decode call each (the differential pair costs no second
        # read of a page), on 9 sets of pages
        assert count("tadnn_ssm_step") == 9
        assert count("tadnn_ssm_chunk") == 9 * (program != "decode_step")
        assert count("tadnn_paged_decode_folded") == 16
        assert "tadnn_gdn" not in text and "tadnn_kda" not in text
        assert "tadnn_moe_grouped_mm" not in text
        pool = made["pool"]
        assert (pool.n_full, pool.ring.count(True), pool.state.count(True),
                pool.none.count(True)) == (1, 8, 9, 14)
        # a token costs 5,120 B of pages (layer 17 alone); a slot 44.6 MB of
        # rings and 3.2 MB of state and tails
        assert pool.bytes_per_block == 64 * 5120
        assert round(pool.bytes_full / 1e9, 2) == 2.01
        assert round(pool.bytes_window / 1e9, 2) == 2.85
        assert round(pool.bytes_window / slots / 1e6, 1) == 44.6
        assert round(sum(pool.bytes_state) / (slots + 1) / 1e6, 1) == 3.2
        assert f"f32[{slots + 1},16,5120]" in page_arrays
        assert mem.argument_size_in_bytes < 14.2 * 2**30
        # a chunk's rows but one stop before layer 18: the cross-decoder's
        # FFNs multiply slots + 1 rows, no matrix of the chunk's 576
        if program == "chunk_and_step":
            assert f"bf16[{slots + 1},10240]" in text
    elif config == "olmo-hybrid-7b-pp2":
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
        if program != "prefill_chunk":
            kda_step_takes_its_operands_as_they_lie(text, slots, 32, 12)
    elif config == "solar-open2-250b-ep8":
        # 3 KDA layers of 64 heads and ONE attention layer, the first: the
        # KDA kernels as above, one folded decode call, no latent kernel,
        # and a pair of grouped matmuls in EVERY layer (no dense FFN)
        assert count("tadnn_kda_step") == 3
        assert count("tadnn_kda_chunk") == 3 * (program != "decode_step")
        assert count("tadnn_paged_decode_folded") == 1
        assert "tadnn_gdn" not in text and "tadnn_latent_chunk" not in text
        assert cfg.n_expert_layers == cfg.n_layers == 4
        assert not re.search(r"f32\[[\d,]*16,16,128\]", text)
        # the pool: 2.25 GB of K/V pages in the one attention layer (4,096
        # B a token), 1.68 GB of states and tails (13.0 MB a slot)
        pool = made["pool"]
        assert (pool.n_full, pool.state.count(True)) == (1, 3)
        assert pool.bytes_per_block == 64 * 4096
        assert round(pool.bytes_full / 1e9, 2) == 2.25
        assert round(sum(pool.bytes_state) / 1e9, 2) == 1.68
        assert round(sum(pool.bytes_state) / (slots + 1) / 1e6, 1) == 13.0
        assert f"f32[{slots + 1},64,128,128]" in page_arrays
        assert mem.argument_size_in_bytes < 10.3 * 2**30
        kda_step_takes_its_operands_as_they_lie(text, slots, 64, 3)
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
