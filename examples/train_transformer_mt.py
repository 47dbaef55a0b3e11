"""Train a transformer-base MT model (synthetic WMT14-shaped data).

The reference's third example config (BASELINE.json:9): "Transformer-base
MT / WMT14 en-de (bucketed DDP path)".  On TPU the bucketed-allreduce
overlap is XLA's latency-hiding scheduler's job — this config is plain DP
and the collectives microbench (``tadnn bench``) quantifies overlap.

Usage::

    python examples/train_transformer_mt.py run.steps=50
    python examples/train_transformer_mt.py model.size=test   # CPU-sim scale
"""

import dataclasses
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import optax

import torch_automatic_distributed_neural_network_tpu as tad
from torch_automatic_distributed_neural_network_tpu.data import (
    ArraySeq2Seq,
    load_seq2seq,
)
from torch_automatic_distributed_neural_network_tpu.data.synthetic import (
    SyntheticSeq2Seq,
)
from torch_automatic_distributed_neural_network_tpu.models import TransformerMT
from torch_automatic_distributed_neural_network_tpu.training import (
    MetricsLogger,
    Trainer,
    TrainerConfig,
    seq2seq_loss,
)
from torch_automatic_distributed_neural_network_tpu.utils import config as cfglib


@dataclasses.dataclass(frozen=True)
class ModelCfg:
    size: str = "base"
    src_len: int = 64
    tgt_len: int = 64
    vocab_size: int = 32000


@dataclasses.dataclass(frozen=True)
class RunCfg:
    steps: int = 50
    batch_size: int = 64
    lr: float = 1e-3
    log_every: int = 10
    metrics_path: str = ""
    # dir with src[_train].npy / tgt[_train].npy token ids;
    # synthetic WMT14-shaped fallback when empty/absent
    data_dir: str = ""


@dataclasses.dataclass(frozen=True)
class ParallelCfg:
    strategy: str = "dp"


@dataclasses.dataclass(frozen=True)
class Cfg:
    model: ModelCfg = ModelCfg()
    run: RunCfg = RunCfg()
    parallel: ParallelCfg = ParallelCfg()


def main():
    cfg: Cfg = cfglib.apply_overrides(Cfg(), sys.argv[1:])
    print(cfglib.to_json(cfg))
    print(f"devices: {jax.device_count()} x {jax.devices()[0].device_kind}")

    vocab = 512 if cfg.model.size == "test" else cfg.model.vocab_size
    model = TransformerMT(cfg.model.size, vocab_size=vocab,
                          max_seq_len=max(cfg.model.src_len, cfg.model.tgt_len))
    loaded = load_seq2seq(cfg.run.data_dir) if cfg.run.data_dir else None
    if loaded is not None:
        src, tgt = loaded
        print(f"data: {len(src)} pairs from {cfg.run.data_dir}")
        data = ArraySeq2Seq(src, tgt, cfg.run.batch_size)
    else:
        if cfg.run.data_dir:
            print(f"data: nothing loadable in {cfg.run.data_dir!r}; "
                  "using synthetic")
        data = SyntheticSeq2Seq(
            vocab_size=vocab, src_len=cfg.model.src_len,
            tgt_len=cfg.model.tgt_len, batch_size=cfg.run.batch_size,
        )
    ad = tad.AutoDistribute(
        model,
        optimizer=optax.adam(cfg.run.lr),
        loss_fn=seq2seq_loss,
        strategy=cfg.parallel.strategy,
    )
    metrics = MetricsLogger(
        cfg.run.metrics_path or None,
        items_name="tokens",
        console_every=cfg.run.log_every,
    )
    trainer = Trainer(
        ad,
        TrainerConfig(steps=cfg.run.steps, log_every=cfg.run.log_every),
        metrics=metrics,
        items_per_step=cfg.run.batch_size * cfg.model.tgt_len,
        run_config=cfglib.to_dict(cfg),
    )
    trainer.fit(iter(data))
    print(f"plan: {ad.plan.strategy} mesh={tad.mesh_degrees(ad.plan.mesh)}")


if __name__ == "__main__":
    main()
