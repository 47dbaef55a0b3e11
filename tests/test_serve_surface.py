"""``ServeEngine``'s surface is what somebody runs: every option has a caller
that is no test, which programs an engine holds follows from what a step's
rows are, and a journal event names no option the engine lacks.  Nothing
here compiles: engines are built and not run.
"""

from __future__ import annotations

import ast
import glob
import inspect
import json
import os

import jax
import jax.numpy as jnp
import pytest

from torch_automatic_distributed_neural_network_tpu.inference.serve import (
    ServeEngine,
)
from torch_automatic_distributed_neural_network_tpu.models import GPT2
from torch_automatic_distributed_neural_network_tpu.obs import schema
from torch_automatic_distributed_neural_network_tpu.training.lora import (
    LoraSpec,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PKG = os.path.join(REPO, "torch_automatic_distributed_neural_network_tpu")
OPTIONS = [name for name, p in inspect.signature(
    ServeEngine.__init__).parameters.items() if p.kind is p.KEYWORD_ONLY]
# who may call for an option to count: the benchmark's cells, the CLI, the
# smoke, the trace lint, the gateway and the examples.  No test
CALLERS = (glob.glob(os.path.join(REPO, "benchmark", "lib", "serving*.py"))
           + [os.path.join(PKG, "cli.py"), os.path.join(REPO, "chip_smoke.py"),
              os.path.join(PKG, "analysis", "serve_trace.py")]
           + glob.glob(os.path.join(PKG, "inference", "gateway", "*.py"))
           + glob.glob(os.path.join(REPO, "examples", "*.py")))
# the options only tests pass today (ROADMAP.md Queue 3 item 2).  A member
# FAILS once it has a caller: take it out then, so the set can only shrink
NO_CALLER_YET = {"cache_dtype", "rng", "sample"}


def _passed() -> dict:
    """keyword -> the files whose ``ServeEngine(...)`` call passes it; the
    cells' ``ServeEngine(**mix["engine"])`` passes the keys of ``engine`` in
    ``benchmark/traffic/*.json``."""
    passed: dict = {}
    for path in CALLERS:
        with open(path) as f:
            tree = ast.parse(f.read())
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            callee = node.func
            name = (callee.id if isinstance(callee, ast.Name)
                    else getattr(callee, "attr", None))
            if name != "ServeEngine":
                continue
            for kw in node.keywords:
                if kw.arg is not None:
                    passed.setdefault(kw.arg, set()).add(
                        os.path.relpath(path, REPO))
    for path in glob.glob(os.path.join(REPO, "benchmark", "traffic",
                                       "*.json")):
        with open(path) as f:
            for key in json.load(f).get("engine", {}):
                passed.setdefault(key, set()).add(os.path.relpath(path, REPO))
    return passed


PASSED = _passed()


@pytest.mark.parametrize("option", OPTIONS)
def test_an_engine_option_has_a_caller(option):
    callers = sorted(PASSED.get(option, ()))
    if option in NO_CALLER_YET:
        assert not callers, (
            f"{option} has a caller now ({callers}): take it out of "
            "NO_CALLER_YET")
    else:
        assert callers, (
            f"only tests pass ServeEngine(..., {option}=): give it a caller "
            "or delete it with its tests")


def test_the_callers_were_read():
    assert len(OPTIONS) == 19 and NO_CALLER_YET < set(OPTIONS)
    assert any(p.startswith("benchmark/traffic/") for p in PASSED["n_slots"])
    assert any(p.endswith("cli.py") for p in PASSED["speculative"])


@pytest.fixture(scope="module")
def tiny():
    model = GPT2("test", vocab_size=128, max_seq_len=64, dtype=jnp.float32,
                 remat=False)
    variables = jax.eval_shape(model.init, jax.random.key(1),
                               jnp.ones((1, 8), jnp.int32))
    return model, jax.tree.map(lambda a: jnp.zeros(a.shape, a.dtype),
                               variables)


def _engine(tiny, **kw):
    model, variables = tiny
    return ServeEngine(model, variables, **{
        "n_slots": 2, "max_len": 64, "block_size": 8, "prefill_chunk": 8,
        "export_cache": False, **kw})


def test_an_engine_fuses_unless_its_rows_differ(tiny):
    """An engine holds the decode step and ONE chunk program: the chunk
    that carries a step's decode rows, unless a step's rows are not one
    token a slot off the base weights (1 + k rows a slot; a tenant's delta
    on its rows), and then the chunk alone."""
    for kw in ({}, {"quant_kv": True}, {"attention_impl": "dense"},
               {"prefix_cache": True}):
        eng = _engine(tiny, **kw)
        assert eng._fused_fn is not None and eng._prefill_fn is None, kw
        assert eng._prefill_lora_fn is None
    for kw in ({"speculative": 2}, {"lora_spec": LoraSpec(rank=4)}):
        eng = _engine(tiny, **kw)
        assert eng._fused_fn is None and eng._prefill_fn is not None, kw
        assert (eng._prefill_lora_fn is not None) == ("lora_spec" in kw)


@pytest.mark.parametrize("event", ["serve.engine", "serve.step"])
def test_a_deleted_option_is_no_field_of_an_event(event):
    """The two events of every cell name no option the signature lacks:
    what PR 45 deleted, and the fields that existed for it alone."""
    gone = {"disaggregate", "prefill_chunks_per_step", "moe_decode",
            "export_tags", "prefix_ttl_s", "mode", "overlap_s"}
    assert not gone & set(OPTIONS)
    assert not gone & set(schema.REGISTRY[event].fields())
    assert "serve.kv_ship" not in schema.REGISTRY
    assert "kv_ship_s" not in schema.REGISTRY["serve.request_done"].fields()


@pytest.mark.parametrize("chunk", [0, -8])
def test_a_chunk_under_one_is_refused(tiny, chunk):
    with pytest.raises(ValueError, match="prefill_chunk"):
        _engine(tiny, prefill_chunk=chunk)
    assert inspect.signature(ServeEngine.__init__).parameters[
        "prefill_chunk"].annotation == "int"
