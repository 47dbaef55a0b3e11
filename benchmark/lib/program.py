"""Building the system under test from a configuration's keys.  The only
file of the benchmark that knows how the program spells its model."""

from __future__ import annotations

def model_keys(config: dict) -> dict:
    """The architecture's keys alone (what the reference reads)."""
    return dict(config["model"])


def build_model(config: dict, options: dict):
    """``DecoderLM(TransformerConfig(**keys))``: the configuration's
    ``model`` group, its compute dtype, and the mix's model options."""
    import jax.numpy as jnp

    from torch_automatic_distributed_neural_network_tpu.models.transformer_core import (
        DecoderLM,
        TransformerConfig,
    )

    keys = model_keys(config)
    keys["dtype"] = jnp.dtype(config["compute_dtype"])
    keys.update(options or {})
    return DecoderLM(TransformerConfig(**keys))


def check_shapes(model, config: dict, sample_tokens) -> dict:
    """The reference's parameter paths and shapes, after checking that the
    program's model has exactly the same ones."""
    import jax

    from lib import weights
    from reference import decoder

    shapes = decoder.param_shapes(model_keys(config))
    abstract = jax.eval_shape(model.init, jax.random.key(0), sample_tokens)
    prog = {k: tuple(v.shape)
            for k, v in weights.unnest(abstract["params"]).items()}
    if prog != shapes:
        diff = sorted(set(prog.items()) ^ set(shapes.items()))
        raise RuntimeError(
            f"the program's parameters differ from the reference's: {diff}")
    return shapes
