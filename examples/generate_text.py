"""Autoregressive generation demo: KV-cached decode on the GPT-2 family.

With random init the output is noise; the point is the decode path and
its throughput — one compiled prefill + a single-program lax.scan decode
loop (inference/decode.py).

Usage::

    python examples/generate_text.py model.size=small run.new_tokens=64
    python examples/generate_text.py run.quant=int8       # int8 weights
    python examples/generate_text.py run.speculative=1    # draft+verify
"""

import dataclasses
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from torch_automatic_distributed_neural_network_tpu.inference import (
    SampleConfig,
    generate,
)
from torch_automatic_distributed_neural_network_tpu.models import GPT2
from torch_automatic_distributed_neural_network_tpu.utils import config as cfglib


@dataclasses.dataclass(frozen=True)
class ModelCfg:
    size: str = "small"
    vocab_size: int = 50257


@dataclasses.dataclass(frozen=True)
class RunCfg:
    batch_size: int = 4
    prompt_len: int = 32
    new_tokens: int = 64
    temperature: float = 0.8
    top_k: int = 40
    top_p: float = 1.0  # nucleus sampling; 1.0 = off
    eos_id: int = -1  # >= 0: rows finalize after emitting this token
    # 'none' -> plain single-program decode; any planner strategy
    # ('tp', 'tp_fsdp', 'fsdp', 'dp') -> plan-aware sharded decode
    # (AutoDistribute.generate: sharded params, KV cache on the mesh)
    strategy: str = "none"
    quant: str = "none"  # 'int8': weight-only quantized decode
    # 1: greedy speculative decoding (batch 1, temperature ignored) —
    # a 1-layer draft proposes, the full model verifies; output is
    # bit-identical to plain greedy decoding of the full model
    speculative: int = 0
    spec_k: int = 4


@dataclasses.dataclass(frozen=True)
class Cfg:
    model: ModelCfg = ModelCfg()
    run: RunCfg = RunCfg()


def main():
    cfg: Cfg = cfglib.apply_overrides(Cfg(), sys.argv[1:])
    print(cfglib.to_json(cfg))
    r = cfg.run
    if r.quant not in ("none", "int8"):
        raise SystemExit(f"unknown run.quant={r.quant!r}; "
                         "supported: none, int8")
    if r.speculative and (r.strategy != "none" or r.quant != "none"
                          or r.eos_id >= 0):
        # a silently-dropped flag would attribute the tok/s line to a
        # config that never ran
        raise SystemExit("run.speculative=1 is plain greedy decode: it "
                         "does not compose with run.strategy / "
                         "run.quant / run.eos_id")
    # speculative rounds need k+1 positions of headroom past the last
    # emitted token; build the model ONCE with the right table size
    seq_budget = r.prompt_len + r.new_tokens + (
        r.spec_k + 1 if r.speculative else 0)
    batch = 1 if r.speculative else r.batch_size
    r = dataclasses.replace(r, batch_size=batch)
    model = GPT2(cfg.model.size, vocab_size=cfg.model.vocab_size,
                 max_seq_len=seq_budget)
    prompt = jnp.asarray(
        np.random.RandomState(0).randint(
            0, cfg.model.vocab_size, size=(batch, r.prompt_len)),
        jnp.int32,
    )
    variables = model.init(jax.random.key(0), prompt)
    eos = r.eos_id if r.eos_id >= 0 else None
    sample = SampleConfig(temperature=r.temperature, top_k=r.top_k,
                          top_p=r.top_p)

    if r.speculative:
        from torch_automatic_distributed_neural_network_tpu.inference import (
            speculative_generate,
        )

        draft = GPT2(cfg.model.size, vocab_size=cfg.model.vocab_size,
                     max_seq_len=seq_budget, n_layers=1)
        dv = draft.init(jax.random.key(7), prompt)
        gen = jax.jit(lambda v, p, k: speculative_generate(
            model, v, draft, dv, p, max_new_tokens=r.new_tokens,
            k=r.spec_k))
    elif r.strategy != "none":
        import optax

        import torch_automatic_distributed_neural_network_tpu as tad
        from torch_automatic_distributed_neural_network_tpu.training import (
            next_token_loss,
        )

        ad = tad.AutoDistribute(
            model, optimizer=optax.sgd(0.1), loss_fn=next_token_loss,
            strategy=r.strategy,
        )
        ad.build_plan(
            jax.random.key(0),
            {"input_ids": np.zeros(
                (r.batch_size, r.prompt_len + 1), np.int32)},
        )
        print(f"plan: strategy={ad.plan.strategy} "
              f"mesh={tad.mesh_degrees(ad.plan.mesh)}")
        gen = lambda v, p, k: ad.generate(
            v, p, max_new_tokens=r.new_tokens, sample=sample, rng=k,
            eos_id=eos, quant=None if r.quant == "none" else r.quant)
    else:
        if r.quant == "int8":
            from torch_automatic_distributed_neural_network_tpu.inference import (  # noqa: E501
                quantize_for_decode,
            )

            variables = quantize_for_decode(variables)
        gen = jax.jit(lambda v, p, k: generate(
            model, v, p, max_new_tokens=r.new_tokens, sample=sample, rng=k,
            eos_id=eos))
    t0 = time.perf_counter()
    out = jax.block_until_ready(gen(variables, prompt, jax.random.key(1)))
    print(f"compile + first generate: {time.perf_counter()-t0:.1f}s")
    t0 = time.perf_counter()
    out = jax.block_until_ready(gen(variables, prompt, jax.random.key(2)))
    dt = time.perf_counter() - t0
    total_new = r.batch_size * r.new_tokens
    print(f"generated {total_new} tokens in {dt*1e3:.0f}ms "
          f"({total_new/dt:,.0f} tok/s)")
    print("sample token ids:", np.asarray(out[0, r.prompt_len:])[:16])


if __name__ == "__main__":
    main()
