"""Migrate from the torch reference in one script: HF/torch weights in,
TPU-sharded finetuning + generation out.

The workflow a reference user follows to switch (README "Migrating from
torch"): build or load a transformers model (any GPT-2/Llama/Mixtral
checkpoint; this example constructs one offline so it runs with zero
network), import its weights into this framework's parameter tree, keep
the torch Dataset too (data/torch_adapter.py), and hand both to
``AutoDistribute``.

Two sources:

- ``model.source=hf`` (default): a transformers checkpoint via
  ``import_hf_gpt2`` — the curated-architecture path.
- ``model.source=torch``: a HAND-WRITTEN ``torch.nn.Module`` (defined
  below, attention and all) converted by ``models.from_torch`` — the
  reference's "AutoDistribute(model) runs an unmodified nn.Module"
  promise (BASELINE.json:5), with no HF involvement.

Run (CPU sim)::

    JAX_PLATFORMS=cpu \
      XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      python examples/finetune_from_torch.py run.steps=30
    # or the hand-written torch model:
    ... examples/finetune_from_torch.py model.source=torch run.steps=30

With a real checkpoint directory::

    python examples/finetune_from_torch.py model.path=/path/to/hf_gpt2
"""

import dataclasses
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np
import optax

from torch_automatic_distributed_neural_network_tpu import AutoDistribute
from torch_automatic_distributed_neural_network_tpu.data import (
    TorchDatasetAdapter,
)
from torch_automatic_distributed_neural_network_tpu.models import (
    import_hf_gpt2,
)
from torch_automatic_distributed_neural_network_tpu.training import (
    Trainer,
    TrainerConfig,
    next_token_loss,
    next_token_loss_mutable,
)
from torch_automatic_distributed_neural_network_tpu.utils import config as cfglib


@dataclasses.dataclass(frozen=True)
class ModelCfg:
    source: str = "hf"  # 'hf' | 'torch' (hand-written nn.Module below)
    path: str = ""  # HF checkpoint dir; "" = build a small random one
    seq_len: int = 64


@dataclasses.dataclass(frozen=True)
class RunCfg:
    steps: int = 30
    batch_size: int = 16
    lr: float = 1e-4
    log_every: int = 10


@dataclasses.dataclass(frozen=True)
class ParallelCfg:
    strategy: str = "auto"


@dataclasses.dataclass(frozen=True)
class Cfg:
    model: ModelCfg = ModelCfg()
    run: RunCfg = RunCfg()
    parallel: ParallelCfg = ParallelCfg()


class TokenDataset:
    """A torch-style map Dataset of token windows (stands in for the
    user's own torch.utils.data pipeline)."""

    def __init__(self, vocab: int, seq_len: int, n: int = 2048):
        rng = np.random.RandomState(0)
        first = rng.randint(0, vocab, (n, 1))
        steps = rng.randint(0, 7, (n, seq_len))
        self._tok = (np.concatenate(
            [first, np.cumsum(steps, -1) + first], -1
        ) % vocab).astype(np.int32)

    def __len__(self):
        return len(self._tok)

    def __getitem__(self, i):
        return {"tokens": self._tok[i]}


def build_handwritten_torch_lm(vocab: int, seq: int):
    """An ordinary from-scratch torch LM — nothing framework-specific.
    ``from_torch`` traces it (attention, mask buffer, weight plumbing)
    and converts the weights; this is the path a user with their own
    torch codebase takes."""
    import torch
    import torch.nn as tnn

    class HandWrittenLM(tnn.Module):
        def __init__(self, d=128, heads=4):
            super().__init__()
            self.emb = tnn.Embedding(vocab, d)
            self.pos = tnn.Parameter(torch.randn(1, seq, d) * 0.02)
            self.ln1 = tnn.LayerNorm(d)
            self.qkv = tnn.Linear(d, 3 * d)
            self.proj = tnn.Linear(d, d)
            self.ln2 = tnn.LayerNorm(d)
            self.mlp_up = tnn.Linear(d, 4 * d)
            self.mlp_down = tnn.Linear(4 * d, d)
            self.ln_f = tnn.LayerNorm(d)
            self.head = tnn.Linear(d, vocab, bias=False)
            self.heads = heads
            self.register_buffer(
                "mask", torch.tril(torch.ones(seq, seq)))

        def forward(self, idx):
            b, t = idx.size(0), idx.size(1)
            x = self.emb(idx) + self.pos[:, :t]
            h = self.ln1(x)
            q, k, v = self.qkv(h).chunk(3, dim=-1)
            hd = q.size(-1) // self.heads
            q = q.view(b, t, self.heads, hd).transpose(1, 2)
            k = k.view(b, t, self.heads, hd).transpose(1, 2)
            v = v.view(b, t, self.heads, hd).transpose(1, 2)
            att = torch.matmul(q, k.transpose(-2, -1)) / (hd ** 0.5)
            att = att.masked_fill(self.mask[:t, :t] == 0, float("-inf"))
            att = torch.softmax(att, dim=-1)
            o = torch.matmul(att, v).transpose(1, 2).contiguous()
            x = x + self.proj(o.view(b, t, -1))
            h = self.ln2(x)
            x = x + self.mlp_down(torch.nn.functional.gelu(self.mlp_up(h)))
            return self.head(self.ln_f(x))

    torch.manual_seed(0)
    return HandWrittenLM()


def main() -> None:
    cfg: Cfg = cfglib.apply_overrides(Cfg(), sys.argv[1:])
    print(cfglib.to_json(cfg))

    if cfg.model.source == "torch":
        from torch_automatic_distributed_neural_network_tpu.models import (
            from_torch,
        )

        net = build_handwritten_torch_lm(512, cfg.model.seq_len)
        model, variables = from_torch(net)
        n_params = sum(p.numel() for p in net.parameters())
        print(f"bridged hand-written torch LM: {n_params/1e6:.1f}M params")
    else:
        import transformers

        if cfg.model.path:
            hf = transformers.GPT2LMHeadModel.from_pretrained(cfg.model.path)
        else:
            # offline stand-in for a real checkpoint
            hf = transformers.GPT2LMHeadModel(transformers.GPT2Config(
                vocab_size=512, n_positions=cfg.model.seq_len,
                n_embd=128, n_layer=4, n_head=2,
            ))
        model, variables = import_hf_gpt2(hf)
        print(f"imported: {model.cfg.n_layers}L d={model.cfg.d_model} "
              f"vocab={model.cfg.vocab_size}")

    bridged = cfg.model.source == "torch"
    data = TorchDatasetAdapter(
        TokenDataset(512 if bridged else model.cfg.vocab_size,
                     cfg.model.seq_len),
        batch_size=cfg.run.batch_size,
    )
    ad = AutoDistribute(
        model,
        optimizer=optax.adamw(cfg.run.lr),
        loss_fn=next_token_loss_mutable if bridged else next_token_loss,
        strategy=cfg.parallel.strategy,
        init_fn=lambda rng, batch: variables,  # imported weights
    )
    trainer = Trainer(
        ad, TrainerConfig(steps=cfg.run.steps,
                          log_every=cfg.run.log_every),
    )
    state = trainer.fit(data)
    print(f"plan: {ad.plan.strategy} "
          f"mesh={dict(zip(ad.plan.mesh.axis_names, ad.plan.mesh.devices.shape))} "
          f"final_step={int(state.step)}")

    if bridged:
        # greedy sampling needs the framework's decode cache — the
        # bridged graph is a straight re-execution of the torch forward,
        # so sample by full-context argmax instead
        import jax.numpy as jnp

        toks = np.asarray(data.batch(0)["tokens"][:1, :8])
        for _ in range(8):
            logits = model.apply(
                {"params": state.params, **state.model_state},
                jnp.asarray(toks))
            nxt = np.asarray(logits)[:, -1].argmax(-1)[:, None]
            toks = np.concatenate([toks, nxt], axis=1)
        print("generated ids:", toks[0].tolist())
    else:
        # greedy sample from the finetuned weights
        prompt = data.batch(0)["tokens"][:1, :8]
        out = ad.generate(state, prompt, max_new_tokens=16)
        print("generated ids:", np.asarray(out)[0].tolist())


if __name__ == "__main__":
    main()
