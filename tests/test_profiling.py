"""Profiling helpers (SURVEY.md §5 tracing row): cost analysis + memory
analysis wrappers used for MFU and HBM accounting."""

import jax
import jax.numpy as jnp

from torch_automatic_distributed_neural_network_tpu.utils.profiling import (
    compiled_flops,
    compiled_memory,
)


def test_compiled_flops_matmul(devices8):
    f = jax.jit(lambda a, b: a @ b)
    a = jnp.ones((64, 128))
    b = jnp.ones((128, 32))
    flops = compiled_flops(f, a, b)
    # 2*M*K*N = 2*64*128*32; cost analysis may add epsilon overhead
    assert flops is not None and flops >= 2 * 64 * 128 * 32


def test_compiled_memory_step(devices8):
    f = jax.jit(lambda x: (x @ x.T).sum())
    mem = compiled_memory(f, jnp.ones((256, 256)))
    assert mem is not None
    assert mem["argument_size"] == 256 * 256 * 4
    assert mem["temp_size"] > 0


def test_search_strategy_small_model_picks_dp(devices8):
    """strategy='search' on a model that trivially fits: the first ladder
    candidate (dp) must be accepted, with the measurement recorded."""
    import numpy as np
    import optax

    import torch_automatic_distributed_neural_network_tpu as tad
    from torch_automatic_distributed_neural_network_tpu.models import GPT2
    from torch_automatic_distributed_neural_network_tpu.training import (
        next_token_loss,
    )

    ad = tad.AutoDistribute(
        GPT2("test", vocab_size=512, max_seq_len=64),
        optimizer=optax.adamw(1e-4),
        loss_fn=next_token_loss,
        strategy="search",
    )
    sample = {"tokens": np.zeros((8, 65), np.int32)}
    plan = ad.build_plan(jax.random.key(0), sample)
    assert plan.strategy == "dp"
    assert ad.search_report[0]["fits"] is True
    # and the searched plan trains
    state = ad.init(jax.random.key(0), sample)
    state, m = ad.step(state, sample)
    assert np.isfinite(float(m["loss"]))


def test_search_strategy_single_device_noop(devices8):
    """search on 1 device degrades to the no-op dp path and still leaves
    an (empty) search_report, per the documented contract."""
    import numpy as np
    import optax

    import torch_automatic_distributed_neural_network_tpu as tad
    from torch_automatic_distributed_neural_network_tpu.models import GPT2
    from torch_automatic_distributed_neural_network_tpu.training import (
        next_token_loss,
    )

    ad = tad.AutoDistribute(
        GPT2("test", vocab_size=512, max_seq_len=64),
        optimizer=optax.adamw(1e-4),
        loss_fn=next_token_loss,
        strategy="search",
        devices=jax.devices()[:1],
    )
    sample = {"tokens": np.zeros((8, 65), np.int32)}
    plan = ad.build_plan(jax.random.key(0), sample)
    assert plan.strategy == "dp"
    assert ad.search_report == []


def test_search_strategy_escalates_on_memory(devices8):
    """strategy='search' must reject a candidate whose MEASURED peak
    exceeds the budget and escalate: GPT-2 large (774M) in fp32 is
    ~12.4 GiB of train state — over the 8 GiB cpu-sim budget for dp
    (replicated), under it for fsdp (ZeRO-3 over 8).  Abstract AOT
    compiles only; nothing is materialized."""
    import numpy as np
    import optax

    import torch_automatic_distributed_neural_network_tpu as tad
    from torch_automatic_distributed_neural_network_tpu.models import GPT2
    from torch_automatic_distributed_neural_network_tpu.training import (
        next_token_loss,
    )

    ad = tad.AutoDistribute(
        GPT2("large", max_seq_len=64),
        optimizer=optax.adamw(1e-4),
        loss_fn=next_token_loss,
        strategy="search",
    )
    sample = {"tokens": np.zeros((8, 65), np.int32)}
    plan = ad.build_plan(jax.random.key(0), sample)
    assert plan.strategy != "dp"
    assert ad.search_report[0]["strategy"] == "dp"
    assert ad.search_report[0]["fits"] is False
    assert ad.search_report[-1]["fits"] is True


def test_search_strategy_moe_ladder(devices8):
    """MoE models search the expert ladder: the accepted entry is an
    ep-family strategy and error entries (if any) carry the same schema
    as measured ones (uniformly indexable report)."""
    import numpy as np
    import optax

    import torch_automatic_distributed_neural_network_tpu as tad
    from torch_automatic_distributed_neural_network_tpu.models import MoE
    from torch_automatic_distributed_neural_network_tpu.training import (
        moe_next_token_loss,
    )

    ad = tad.AutoDistribute(
        MoE("test", vocab_size=256, max_seq_len=32),
        optimizer=optax.adamw(1e-4),
        loss_fn=moe_next_token_loss,
        strategy="search",
    )
    sample = {"tokens": np.zeros((8, 33), np.int32)}
    plan = ad.build_plan(jax.random.key(0), sample)
    assert plan.strategy.startswith("ep")
    for entry in ad.search_report:
        assert {"strategy", "remat", "peak_bytes", "budget_bytes",
                "fits", "flops"} <= set(entry)
    assert ad.search_report[-1]["fits"] is True


def test_compile_report_abstract_only(devices8):
    """compile_report AOT-compiles the sharded step without materializing
    any state: per-device argument bytes must reflect the fsdp=8 shard,
    not the full model."""
    import numpy as np
    import optax

    import torch_automatic_distributed_neural_network_tpu as tad
    from torch_automatic_distributed_neural_network_tpu.models import GPT2
    from torch_automatic_distributed_neural_network_tpu.training import (
        next_token_loss,
    )

    ad = tad.AutoDistribute(
        GPT2("test", vocab_size=512, max_seq_len=64),
        optimizer=optax.adamw(1e-4),
        loss_fn=next_token_loss,
        strategy="fsdp",
        precision="mixed",
    )
    sample = {"tokens": np.zeros((8, 65), np.int32)}
    report = ad.compile_report(jax.random.key(0), sample)
    assert report is not None
    assert report["per_device_peak_bytes"] > 0
    n_params = ad.model.cfg.num_params()
    # mixed precision state: fp32 master + bf16 moments = 8 B/param, all
    # fsdp-sharded 8 ways; argument_size is per-device and must sit well
    # under the unsharded total (padding/replicated odds allow 2x the
    # ideal shard but not the full tree)
    per_dev = report["memory"]["argument_size"]
    assert per_dev < (8 * n_params) / 8 * 2 + 2**20
    # the step must still run after the report (init path unaffected)
    state = ad.init(jax.random.key(0), sample)
    state, m = ad.step(state, sample)
    assert np.isfinite(float(m["loss"]))
