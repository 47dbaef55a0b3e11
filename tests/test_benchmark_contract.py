"""What ``benchmark/`` takes from the program BY NAME, held in tier-1.

The driver measures a PR by running ``benchmark/run.py``, and only a
``benchmark`` PR may edit that directory.  The benchmark reads the program
by name: engine keywords from ``benchmark/traffic/*.json``, model keys from
``benchmark/configs/*.json``, fields of ``serve.step`` and ``serve.engine``
events, phases, the names of the two serving programs and of the Pallas
kernels.  A PR that renames one of them passes every other test here and
the driver then records a ``null`` metric, or no run at all.  Each case
below holds ONE name, so that it fails alone and says which file reads it.

The names are read from the benchmark's own files when this module is
collected (JSON, and the ``.py`` sources through ``ast``: no benchmark code
runs then); the literal lists further down are the fields its readers
index, each beside the file and line that does.  Nothing is written, and
nothing here is a device number: the engines are the cells' own
``rehearsal`` sizes on the CPU.
"""

from __future__ import annotations

import ast
import dataclasses
import functools
import importlib
import importlib.util
import inspect
import json
import math
import os
import re
import sys

import jax
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmark")
PKG = "torch_automatic_distributed_neural_network_tpu"


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


BENCHMARK = _json(os.path.join(REPO, "BENCHMARK.json"))
CONFIGS = {c["name"]: _json(os.path.join(REPO, c["file"]))
           for c in BENCHMARK["configs"]}
MIXES = {t: _json(os.path.join(BENCH, "traffic", t + ".json"))
         for t in sorted({w["traffic"] for w in BENCHMARK["workloads"]})}
# what runs in a cell: not the benchmark's own tests
SOURCES = sorted(
    os.path.join(BENCH, sub, f)
    for sub in ("", "lib", "generators", "metrics", "reference")
    for f in os.listdir(os.path.join(BENCH, sub)) if f.endswith(".py"))
METRIC_FILES = sorted(f for f in os.listdir(os.path.join(BENCH, "metrics"))
                      if f.endswith(".py"))


def _rel(path: str) -> str:
    return os.path.relpath(path, REPO)


def _trees():
    for path in SOURCES:
        with open(path) as f:
            yield path, ast.parse(f.read())


def _callee(node: ast.Call) -> str | None:
    f = node.func
    return f.id if isinstance(f, ast.Name) else \
        f.attr if isinstance(f, ast.Attribute) else None


def _named(table: dict, name, where: str) -> None:
    table.setdefault(name, []).append(where)


# -- 1. keywords the benchmark passes to the program's callables -------------

# callee as the benchmark's sources spell it -> where the program keeps it
CALLEES = {
    "ServeEngine": (PKG + ".inference.serve", "ServeEngine"),
    "submit": (PKG + ".inference.serve", "ServeEngine.submit"),
    "AutoDistribute": (PKG, "AutoDistribute"),
    "Trainer": (PKG + ".training", "Trainer"),
    "TrainerConfig": (PKG + ".training", "TrainerConfig"),
    "fit": (PKG + ".training", "Trainer.fit"),
    "SyntheticLM": (PKG + ".data.synthetic", "SyntheticLM"),
    "Journal": (PKG + ".obs.journal", "Journal"),
    "TransformerConfig": (PKG + ".models.transformer_core",
                          "TransformerConfig"),
}


def _keywords() -> dict:
    """(callee, keyword) -> the places of the benchmark that pass it: the
    explicit keywords of each call in its sources, and the keys of the
    groups of its data files that a call takes with ``**``."""
    kws: dict = {}
    for path, tree in _trees():
        for node in ast.walk(tree):
            if isinstance(node, ast.Call) and _callee(node) in CALLEES:
                for kw in node.keywords:
                    if kw.arg is not None:
                        _named(kws, (_callee(node), kw.arg),
                               f"{_rel(path)}:{node.lineno}")
    for name, mix in MIXES.items():
        where = f"benchmark/traffic/{name}.json"
        # lib/serving.py, lib/serving_large.py: ServeEngine(**mix["engine"]);
        # generators/train.py: AutoDistribute(**mix["autodistribute"]),
        # TrainerConfig(**mix["trainer"]); lib/program.py: the mix's
        # model_options go into TransformerConfig(**keys)
        for group, callee in (("engine", "ServeEngine"),
                              ("autodistribute", "AutoDistribute"),
                              ("trainer", "TrainerConfig"),
                              ("model_options", "TransformerConfig")):
            for doc, tag in ((mix, group),
                             (mix.get("rehearsal") or {},
                              "rehearsal." + group)):
                for k in doc.get(group) or ():
                    _named(kws, (callee, k), f"{where} {tag}")
    for name, cfg in CONFIGS.items():
        where = f"benchmark/configs/{name}.json"
        for doc, tag in ((cfg, "model"),
                         (cfg.get("rehearsal") or {}, "rehearsal.model")):
            for k in doc.get("model") or ():
                _named(kws, ("TransformerConfig", k), f"{where} {tag}")
    # lib/program.py:22: keys["dtype"] = jnp.dtype(config["compute_dtype"])
    _named(kws, ("TransformerConfig", "dtype"), "benchmark/lib/program.py:22")
    return kws


KEYWORDS = _keywords()


def _resolve(callee: str):
    module, path = CALLEES[callee]
    obj = importlib.import_module(module)
    for part in path.split("."):
        obj = getattr(obj, part)
    return obj


@pytest.mark.parametrize(
    "callee,keyword", sorted(KEYWORDS),
    ids=[f"{c}-{k}" for c, k in sorted(KEYWORDS)])
def test_a_keyword_the_benchmark_passes_is_a_parameter(callee, keyword):
    params = inspect.signature(_resolve(callee)).parameters
    assert not any(p.kind is p.VAR_KEYWORD for p in params.values()), \
        f"{callee} takes **kwargs: this test can no longer hold its names"
    assert keyword in params, (
        f"{CALLEES[callee][1]} has no parameter {keyword!r}; passed by "
        + ", ".join(KEYWORDS[(callee, keyword)]))


def test_the_cells_engine_keywords_were_found():
    """The cases above are as many as the benchmark's files name; that the
    reading itself works is held here, against the two every cell has."""
    got = {k for c, k in KEYWORDS if c == "ServeEngine"}
    assert {"n_slots", "max_len", "journal", "export_cache"} <= got
    assert all(MIXES[w["traffic"]].get("kind") for w in BENCHMARK["workloads"])


# -- 2. names the benchmark imports from the program -------------------------


def _imports() -> dict:
    names: dict = {}
    for path, tree in _trees():
        for node in ast.walk(tree):
            if (isinstance(node, ast.ImportFrom) and node.module
                    and node.module.split(".")[0] == PKG):
                for a in node.names:
                    _named(names, (node.module, a.name),
                           f"{_rel(path)}:{node.lineno}")
    return names


IMPORTS = _imports()


@pytest.mark.parametrize(
    "module,name", sorted(IMPORTS),
    ids=[f"{m.removeprefix(PKG).lstrip('.') or 'package'}-{n}"
         for m, n in sorted(IMPORTS)])
def test_a_name_the_benchmark_imports_is_there(module, name):
    mod = importlib.import_module(module)
    assert hasattr(mod, name), (
        f"{module} has no {name!r}; imported by "
        + ", ".join(IMPORTS[(module, name)]))


# -- 3. the cells' own rehearsal engines, served on the CPU ------------------


@pytest.fixture(scope="module")
def bench():
    """``benchmark/`` importable as the benchmark imports itself (``lib``,
    ``reference`` at top level), for this module only."""
    before = set(sys.modules)
    sys.path.insert(0, BENCH)
    try:
        yield importlib.import_module("lib.harness")
    finally:
        sys.path.remove(BENCH)
        for m in set(sys.modules) - before:
            if m.split(".")[0] in ("lib", "reference") \
                    or m.startswith("bench_"):
                del sys.modules[m]


def _serve(bench, workload: str) -> dict:
    """The cell at its ``rehearsal`` sizes, built as ``lib/serving.py``
    builds it (weights from ``model.init``: no number is compared), a few
    prompts served, and the record in the shape ``lib/serving.py:232-238``
    and ``lib/serving_large.py:297-304`` give the readers.  No device
    trace."""
    from lib import program

    from torch_automatic_distributed_neural_network_tpu.inference.serve import (
        ServeEngine,
    )
    from torch_automatic_distributed_neural_network_tpu.obs.journal import (
        Journal,
    )

    cell = bench.Cell(workload)
    bench.apply_rehearsal(cell)
    mix, keys = cell.mix, program.model_keys(cell.config)
    model = program.build_model(cell.config, mix.get("model_options"))
    variables = model.init(jax.random.key(0), np.zeros((1, 8), np.int32))
    journal = Journal(None, host0_only=False)
    eng = ServeEngine(model, variables, journal=journal, export_cache=False,
                      **mix["engine"])
    rs = np.random.RandomState(0)
    chunk = mix["engine"]["prefill_chunk"]
    reqs = [eng.submit([int(t) for t in rs.randint(1, keys["vocab_size"],
                                                   size=n)],
                       max_new_tokens=m)
            # under a chunk, over one, over two: steps with and without one
            for n, m in ((chunk // 3, 4), (chunk + 4, 6), (2 * chunk + 5, 3))]
    eng.run()
    record = {
        "cell": cell, "chips": 1,
        "serve_steps": journal.named("serve.step"),
        "requests": [{
            "t_admit": r.t_admit, "t_first": r.t_first_token,
            "walls": list(r.token_walls), "prompt": list(r.prompt),
            "out": list(r.out_tokens), "max_new": r.max_new_tokens,
            "done": r.t_done is not None} for r in reqs],
        "serve_engine": (journal.named("serve.engine") or [None])[-1],
        "model_keys": keys, "engine": mix["engine"],
    }
    return {"eng": eng, "journal": journal, "requests": reqs,
            "record": record}


def _cell_of(config: str) -> str:
    """The first serving cell of a configuration, by BENCHMARK.json."""
    return next(w["name"] for w in BENCHMARK["workloads"]
                if w["config"] == config
                and MIXES[w["traffic"]]["kind"].startswith("serve"))


@pytest.fixture(scope="module")
def dense(bench):
    return _serve(bench, _cell_of("gpt2-1p3b"))


@pytest.fixture(scope="module")
def experts(bench):
    """A ``layer_types`` model with held experts."""
    return _serve(bench, _cell_of("trinity-large-ep8"))


@pytest.fixture(scope="module")
def hybrid(bench):
    """A ``layer_types`` model with ``linear_attention`` layers."""
    return _serve(bench, _cell_of("olmo-hybrid-7b-pp2"))


@pytest.fixture(scope="module")
def latent(bench):
    """A ``layer_types`` model of ``latent_attention`` layers with held
    experts."""
    return _serve(bench, _cell_of("joyai-llm-flash-ep8"))


@pytest.fixture(scope="module")
def shortcut(bench):
    """A model of shortcut-connected double layers: latent mixers, dense
    FFNs, an expert branch across each pair, zero-compute experts."""
    return _serve(bench, _cell_of("longcat-flash-omni-ep32"))


@pytest.fixture(scope="module")
def mixed(bench):
    """A model of KDA layers (``linear_attention``, a decay a channel) and
    latent layers with held experts: state rows AND latent pages."""
    return _serve(bench, _cell_of("kimi-linear-48b-ep8"))


# field of a ``serve.step`` event -> who indexes it (benchmark/ paths)
STEP_FIELDS = {
    "decode_s": "lib/readers.py:13 lib/serve_phases.py:77 lib/counts_moe.py:47",
    "step_s": "lib/serve_phases.py:48,76 lib/serving_large.py:249",
    "phases": "lib/serve_phases.py:46,76 lib/serving_large.py:256,278",
    "t_end": "lib/serve_phases.py:66-67 lib/counts_moe.py:47-48",
    "occupancy": "metrics/slot_occupancy.py:9",
    "new_tokens": "lib/serving_large.py:264 metrics/gdn_step_roofline.py:17",
    "t": "sweep.py:63",
    "n_queued": "sweep.py:66,78",
    "n_active": "sweep.py:66",
}
# the same, on a model with expert layers only
STEP_FIELDS_EXPERTS = {
    "moe_pairs": "lib/serving_large.py:263,271 metrics/moe_grouped_mm_roofline.py:26",
    "moe_experts_touched": "lib/serving_large.py:276 metrics/moe_grouped_mm_roofline.py:18,25",
    "moe_max_expert_tokens": "metrics/moe_grouped_mm_roofline.py:33",
}
# field of the ``serve.engine`` event (the record's ``serve_engine``)
ENGINE_FIELDS = {
    "kv_bytes_full": "metrics/kv_pool_gib.py:10,13,16",
    "kv_bytes_window": "metrics/kv_pool_gib.py:10,14,16",
    "layer_kinds": "metrics/kv_pool_gib.py:15",
    "experts_held": "lib/serving_large.py:39-40,300 (the record's serve_engine)",
    "experts_published": "lib/serving_large.py:39-40,300",
    # start-up's own account (PR 52): the constructor's seconds and their
    # parts, and the table of the programs' first calls
    "build_s": "metrics/engine_build_s.py:12,17",
    "build_phases": "metrics/engine_build_s.py:15 (printed beside it)",
    "programs": "metrics/program_load_s.py:14,18 "
                "metrics/program_compile_s.py:11,14",
}
# the same, on a model with linear layers only
ENGINE_FIELDS_LINEAR = {
    "state_bytes_linear": "metrics/state_pool_gib.py:12,15,19",
    "conv_bytes_linear": "metrics/state_pool_gib.py:16,19",
    "kv_bytes_full": "metrics/state_pool_gib.py:17 metrics/kv_pool_gib.py:10",
    "layer_kinds": "metrics/kv_pool_gib.py:15",
}
# the same, on a model with latent layers only: what ``kv_pool_gib`` adds up
# (latent pages are pages for ``max_len``: in ``kv_bytes_full``), and what
# says how much of it is latent and what a page's row is (``tadnn report``)
ENGINE_FIELDS_LATENT = {
    "kv_bytes_full": "metrics/kv_pool_gib.py:10,13,16",
    "kv_bytes_window": "metrics/kv_pool_gib.py:10,14,16",
    "kv_bytes_latent": "obs/report.py (the latent line beside the pool's)",
    "latent_row": "obs/report.py (the latent line beside the pool's)",
    "layer_kinds": "metrics/kv_pool_gib.py:15",
    "experts_held": "lib/serving_large.py:39-40,300",
}
# attribute of the engine -> who takes it
ENGINE_ATTRS = {
    "submit": "lib/serving.py:63,91",
    "run": "lib/serving.py:65",
    "step": "lib/serving.py:101",
    "finished": "lib/serving.py:66 sweep.py:60",
    "scheduler": "lib/serving.py:75 (.idle(): :85,:95)",
}
# attribute of a request that ``submit`` returned
REQUEST_ATTRS = {
    "t_admit": "lib/serving.py:191 lib/serving_large.py:227",
    "t_first_token": "lib/serving.py:191 sweep.py:70",
    "token_walls": "lib/serving.py:192 sweep.py:73",
    "prompt": "lib/serving.py:192",
    "out_tokens": "lib/serving.py:193",
    "max_new_tokens": "lib/serving.py:193",
    "t_done": "lib/serving.py:194",
}


def _steps(run: dict) -> list[dict]:
    steps = run["journal"].named("serve.step")
    assert len(steps) >= 6, "the engine hardly stepped"
    return steps


@pytest.mark.parametrize("field", sorted(STEP_FIELDS))
def test_serve_step_carries_a_field_the_benchmark_indexes(dense, experts,
                                                          hybrid, field):
    for run in (dense, experts, hybrid):
        for s in _steps(run):
            assert s.get(field) is not None, (
                f"serve.step has no {field!r}; read by benchmark/ "
                + STEP_FIELDS[field])


@pytest.mark.parametrize("field", sorted(STEP_FIELDS_EXPERTS))
def test_serve_step_of_an_expert_model_carries_its_counters(experts, field):
    # they come back with the tokens of a step that decoded ALONE, so not
    # on every step: a step whose rows rode in a prefill chunk routed the
    # chunk's rows with them, and its counters are left out (the readers
    # set them against the kernels inside ``jit_serve_decode_step``)
    steps = _steps(experts)
    got = [s.get(field) for s in steps]
    alone = sum("decode_dispatch" in s["phases"] and not s["fused"]
                for s in steps)
    assert sum(v is not None for v in got) == alone >= 2, (
        f"serve.step has no {field!r}; read by benchmark/ "
        + STEP_FIELDS_EXPERTS[field])
    assert all(isinstance(v, int) and v >= 0 for v in got if v is not None)


@pytest.mark.parametrize("field", sorted(ENGINE_FIELDS))
def test_serve_engine_carries_a_field_the_benchmark_indexes(experts, field):
    ev = experts["record"]["serve_engine"]
    assert ev is not None, "no serve.engine event (Journal.named)"
    assert ev.get(field) is not None, (
        f"serve.engine has no {field!r}; read by benchmark/ "
        + ENGINE_FIELDS[field])


@pytest.mark.parametrize("field", sorted(ENGINE_FIELDS_LINEAR))
def test_serve_engine_of_a_hybrid_model_carries_its_counters(hybrid, field):
    ev = hybrid["record"]["serve_engine"]
    assert ev is not None, "no serve.engine event (Journal.named)"
    assert ev.get(field) is not None, (
        f"serve.engine has no {field!r}; read by benchmark/ "
        + ENGINE_FIELDS_LINEAR[field])


def test_serve_engine_says_what_a_hybrid_cell_is_about(hybrid):
    """``state_pool_gib`` adds the states and the tails, ``kv_pool_gib`` the
    full layers' pages: a hybrid model has both and no ring, and the
    program's count of its layers by kind is the configuration's (what
    ``paged_attn_roofline.by_kind`` multiplies by)."""
    ev, eng = hybrid["record"]["serve_engine"], hybrid["eng"]
    assert ev["state_bytes_linear"] > ev["conv_bytes_linear"] > 0
    assert ev["kv_bytes_full"] > 0 and ev["kv_bytes_window"] == 0
    kinds = hybrid["record"]["model_keys"]["layer_types"]
    assert ev["layer_kinds"] == list(kinds)
    assert eng.pool.n_full == list(kinds).count("full_attention") > 0
    assert eng.pool.state.count(True) == list(kinds).count("linear_attention")


@pytest.mark.parametrize("field", sorted(ENGINE_FIELDS_LATENT))
def test_serve_engine_of_a_latent_model_carries_its_counters(latent, field):
    ev = latent["record"]["serve_engine"]
    assert ev is not None, "no serve.engine event (Journal.named)"
    assert ev.get(field) is not None, (
        f"serve.engine has no {field!r}; read by " + ENGINE_FIELDS_LATENT[field])


def test_serve_engine_says_what_a_latent_cell_is_about(latent):
    """Latent pages are the allocator's pages for ``max_len``: all of
    ``kv_bytes_full`` here, which ``kv_pool_gib`` reads unedited; a row is
    the latent and the rotated key part, stored in whole tiles of lanes; the
    step's counters hold the latent kernel's grid and the expert layer's
    pairs."""
    ev, eng = latent["record"]["serve_engine"], latent["eng"]
    keys = latent["record"]["model_keys"]
    assert ev["kv_bytes_full"] == ev["kv_bytes_latent"] > 0
    assert ev["kv_bytes_window"] == 0 == ev["state_bytes_linear"]
    assert ev["latent_row"][:2] == [keys["latent_kv_rank"],
                                    keys["latent_rope_head_dim"]]
    assert ev["latent_row"][2] % 128 == 0
    assert set(ev["layer_kinds"]) == {"latent_attention"}
    assert eng.pool.n_full == keys["n_layers"] == eng.pool.latent.count(True)
    steps = _steps(latent)
    assert any(s.get("attn_grid_items") for s in steps)
    assert any(s.get("moe_pairs") is not None for s in steps)


def test_serve_engine_says_what_the_cell_is_about(experts):
    """``kv_pool_gib`` adds the two kinds of pages; a model with sliding
    layers has both, and holds fewer experts than are published."""
    ev = experts["record"]["serve_engine"]
    assert ev["kv_bytes_full"] > 0 and ev["kv_bytes_window"] > 0
    assert 0 < ev["experts_held"] < ev["experts_published"]
    assert set(ev["layer_kinds"]) == set(
        experts["record"]["model_keys"]["layer_types"])


@pytest.mark.parametrize("attr", sorted(ENGINE_ATTRS))
def test_the_engine_has_what_the_generators_take(dense, attr):
    assert hasattr(dense["eng"], attr), (
        f"ServeEngine has no {attr!r}; taken by benchmark/ "
        + ENGINE_ATTRS[attr])


def test_the_engine_drains_as_the_generators_expect(dense):
    """``_drive`` steps until ``scheduler.idle()``; ``_warm`` clears
    ``finished``; ``Journal.named`` gives the window's events."""
    eng = dense["eng"]
    assert eng.scheduler.idle() is True
    assert {id(r) for r in dense["requests"]} <= {id(r) for r in eng.finished}
    assert callable(dense["journal"].named)
    assert dense["journal"].named("serve.engine")


@pytest.mark.parametrize("attr", sorted(REQUEST_ATTRS))
def test_a_request_has_what_the_generators_take(dense, attr):
    for r in dense["requests"]:
        assert getattr(r, attr, None) is not None, (
            f"a finished request has no {attr!r}; taken by benchmark/ "
            + REQUEST_ATTRS[attr])


def test_a_requests_stamps_mean_what_the_generators_compute(dense):
    """``itl_p95_ms`` is the gaps of ``token_walls``; ``serve_tokens_per_s``
    counts them; a finished request has ``max_new_tokens`` of them."""
    for r in dense["requests"]:
        assert len(r.token_walls) == len(r.out_tokens) == r.max_new_tokens
        assert list(r.token_walls) == sorted(r.token_walls)
        assert r.t_admit <= r.t_first_token == r.token_walls[0] <= r.t_done


# -- 4. phases, program names, kernel names -----------------------------------


def _load(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# stdlib imports only: safe to run while this module is collected
WAITS = _load(os.path.join(BENCH, "lib", "serve_phases.py"),
              "benchmark_serve_phases").WAITS


@pytest.mark.parametrize("wait", WAITS)
def test_a_wait_the_benchmark_subtracts_is_a_phase_of_the_engine(wait):
    from torch_automatic_distributed_neural_network_tpu.inference.serve.engine import (
        PHASES,
    )

    assert wait in PHASES, (
        f"benchmark/lib/serve_phases.py:18 WAITS names {wait!r}, which the "
        f"engine does not declare ({PHASES}): serve_host_ms would count "
        "the wait as the host's own time")


def test_decode_wait_is_timed_on_every_step_that_reads(dense, experts,
                                                       hybrid):
    # lib/serving_large.py:278 and serve_host_ms take it from ``phases``
    for run in (dense, experts, hybrid):
        waits = [s["phases"].get("decode_wait") for s in _steps(run)]
        assert sum(w is not None for w in waits) >= len(waits) // 2


def _strings(pattern: str) -> dict:
    """Every match of ``pattern`` in the text (code and docstrings) of the
    files under benchmark/lib, metrics and generators -> where."""
    found: dict = {}
    for path in SOURCES:
        if os.path.basename(os.path.dirname(path)) not in (
                "lib", "metrics", "generators"):
            continue
        with open(path) as f:
            for n, line in enumerate(f, 1):
                for m in re.findall(pattern, line):
                    _named(found, m, f"{_rel(path)}:{n}")
    return found


KERNELS = _strings(r"tadnn_[a-z0-9_]*[a-z0-9]")
PROGRAMS = _strings(r"jit_serve_[a-z0-9_]*[a-z0-9]")


@functools.cache
def _pallas_names() -> frozenset[str]:
    """The ``name=`` of every ``pallas_call`` under ``ops/`` (a name chosen
    by a condition gives each of its strings)."""
    names = set()
    ops = os.path.join(REPO, PKG, "ops")
    for f in sorted(os.listdir(ops)):
        if not f.endswith(".py"):
            continue
        with open(os.path.join(ops, f)) as fh:
            tree = ast.parse(fh.read())
        for node in ast.walk(tree):
            if isinstance(node, ast.Call) and _callee(node) == "pallas_call":
                for kw in node.keywords:
                    if kw.arg == "name":
                        names |= {c.value for c in ast.walk(kw.value)
                                  if isinstance(c, ast.Constant)
                                  and isinstance(c.value, str)}
    return frozenset(names)


@pytest.mark.parametrize("kernel", sorted(KERNELS))
def test_a_kernel_the_benchmark_tells_by_name_is_a_pallas_call(kernel):
    # the readers match by substring (``"tadnn_moe_grouped_mm" in name``)
    have = _pallas_names()
    assert any(kernel in n for n in have), (
        f"no pallas_call under ops/ is named {kernel}* (have "
        f"{sorted(have)}); named by " + ", ".join(KERNELS[kernel]))


@pytest.mark.parametrize("program", sorted(PROGRAMS))
def test_a_program_the_benchmark_names_is_a_serving_program(dense, program):
    eng = dense["eng"]
    heads = [eng.compiled_decode_text().splitlines()[0],
             eng._fused_fn.lower(
                 *eng._abstract_fused_args()).as_text().splitlines()[0]]
    names = set(re.findall(r"jit_serve_[a-z0-9_]+", " ".join(heads)))
    assert program in names, (
        f"the engine's programs are {sorted(names)}, not {program}; named "
        "by " + ", ".join(PROGRAMS[program]))


def test_the_names_were_found():
    assert any(k.startswith("tadnn_paged_decode") for k in KERNELS)
    assert any(k.startswith("tadnn_moe_grouped_mm") for k in KERNELS)
    assert {"tadnn_gdn_chunk", "tadnn_gdn_step"} <= set(KERNELS)
    assert {"tadnn_kda_chunk", "tadnn_kda_step"} <= set(KERNELS)
    assert "tadnn_paged_decode_latent" in KERNELS
    assert len(PROGRAMS) >= 2 and len(WAITS) >= 1


# -- 5. every reader under benchmark/metrics/ reads the CPU run's record ----


@pytest.mark.parametrize("metric_file", METRIC_FILES)
def test_a_metric_reader_reads_the_programs_record(bench, dense, experts,
                                                   hybrid, latent, mixed,
                                                   metric_file, capsys):
    reader = _load(os.path.join(BENCH, "metrics", metric_file),
                   "bench_metric")
    assert callable(getattr(reader, "read", None)), metric_file
    name = metric_file.removesuffix(".py")
    entry = next(m for m in BENCHMARK["per_layer"] if m["name"] == name)
    configs = {w["config"] for w in BENCHMARK["workloads"]
               if w["name"] in entry["workloads"]}
    for config, run in (("gpt2-1p3b", dense), ("trinity-large-ep8", experts),
                        ("olmo-hybrid-7b-pp2", hybrid),
                        ("joyai-llm-flash-ep8", latent),
                        ("kimi-linear-48b-ep8", mixed)):
        if config not in configs:
            continue
        value = reader.read(run["record"])
        assert value is None or math.isfinite(value), (metric_file, value)
    capsys.readouterr()  # readers print their working


# what the program's events make readable without a device trace
READS_ON_CPU = ("decode_step_ms.backlog", "decode_step_ms.steady",
                "serve_host_ms.backlog", "serve_host_ms.steady",
                "slot_occupancy", "kv_pool_gib")


@pytest.mark.parametrize("name", READS_ON_CPU)
def test_a_reader_of_events_alone_gives_a_number(bench, experts, name,
                                                 capsys):
    reader = _load(os.path.join(BENCH, "metrics", name + ".py"),
                   "bench_metric")
    value = reader.read(experts["record"])
    capsys.readouterr()
    assert value is not None and math.isfinite(value) and value > 0, name


@pytest.mark.parametrize("name", READS_ON_CPU[::2] + ("state_pool_gib",))
def test_a_reader_of_events_alone_gives_a_number_on_a_hybrid_model(
        bench, hybrid, name, capsys):
    reader = _load(os.path.join(BENCH, "metrics", name + ".py"),
                   "bench_metric")
    value = reader.read(hybrid["record"])
    capsys.readouterr()
    assert value is not None and math.isfinite(value) and value > 0, name


@pytest.mark.parametrize("name", READS_ON_CPU[::2] + ("kv_pool_gib",))
def test_a_reader_of_events_alone_gives_a_number_on_a_latent_model(
        bench, latent, name, capsys):
    reader = _load(os.path.join(BENCH, "metrics", name + ".py"),
                   "bench_metric")
    value = reader.read(latent["record"])
    capsys.readouterr()
    assert value is not None and math.isfinite(value) and value > 0, name


@pytest.mark.parametrize("name", ["latent_attn_roofline",
                                  "latent_attn_decode_ms"])
def test_a_latent_reader_finds_nothing_where_there_is_nothing(
        bench, latent, experts, name, capsys):
    """No device trace on the CPU, and no latent layer in another model: the
    readers this configuration brought return None and do not raise (what a
    parent commit's traced run of the cell's readers has to do)."""
    reader = _load(os.path.join(BENCH, "metrics", name + ".py"),
                   "bench_metric")
    for run in (latent, experts):
        assert reader.read(run["record"]) is None
        assert reader.read({**run["record"], "trace": {"n_devices": 0}}) is None
    capsys.readouterr()


# -- 5b. a call by what it read (PR 37) ----------------------------------------

NEW_SPAN_METRICS = ("chunk_call_ms.backlog", "decode_call_ms.backlog",
                    "chunk_call_deep_ms.backlog", "serve_stall_ms.backlog")


def _call(step, step_s, *, programs=1, rows=8, chunk_rows=0, chunk_pos=0,
          **more):
    return {"step": step, "step_s": step_s, "decode_s": step_s * 0.9,
            "phases": {"decode_wait": 0.8 * step_s, "emit": 1e-4},
            "gc_s": 0.0, "gc_full": 0, "compiles": 0,
            "read": {"programs": programs, "rows": rows,
                     "ctx_keys": 100 * rows, "chunk_rows": chunk_rows,
                     "chunk_pos": chunk_pos}, **more}


def _made_steps() -> list[dict]:
    """A window by hand: nine shallow and six deep chunk calls, ten
    decode-only calls, a read that covers 17 programs (long, and no stall),
    one stalled decode-only call with the collector inside it, and calls
    that read nothing."""
    steps = [{"step": i, "step_s": 0.003, "decode_s": 0.0,
              "phases": {"prefill_dispatch": 0.002}, "gc_s": 0.0,
              "gc_full": 0, "compiles": 0} for i in range(1, 3)]
    steps.append(_call(3, 0.5, programs=17, rows=0, chunk_rows=17 * 512,
                       chunk_pos=16 * 512))
    n = 3
    for i in range(9):
        n += 1
        steps.append(_call(n, 0.024 + 0.0005 * i, chunk_rows=512,
                           chunk_pos=512 * (i % 8)))
    for i in range(6):
        n += 1
        steps.append(_call(n, 0.068 + 0.001 * i, chunk_rows=512,
                           chunk_pos=8192 + 512 * i))
    for i in range(10):
        n += 1
        steps.append(_call(n, 0.0120 + 0.0001 * i))
    steps.append(_call(n + 1, 2.0, gc_s=1.9, gc_full=1))
    return steps


def _span_reader(name: str):
    return _load(os.path.join(BENCH, "metrics", name + ".py"),
                 "bench_metric").read


def test_the_call_readers_read_a_hand_made_window(bench, capsys):
    import statistics

    rec = {"serve_steps": _made_steps()}
    chunk = [s["step_s"] for s in rec["serve_steps"]
             if s.get("read", {}).get("programs") == 1
             and s["read"]["chunk_rows"]]
    assert len(chunk) == 15
    assert _span_reader("chunk_call_ms.backlog")(rec) == pytest.approx(
        1e3 * statistics.median(chunk))
    assert _span_reader("chunk_call_deep_ms.backlog")(rec) == pytest.approx(
        1e3 * statistics.median(chunk[9:]))
    decode = [0.0120 + 0.0001 * i for i in range(10)] + [2.0]
    m = statistics.median(decode)
    assert _span_reader("decode_call_ms.backlog")(rec) == pytest.approx(
        1e3 * m)
    capsys.readouterr()
    # ONE stall: the read of 17 programs is none, however long it took
    assert _span_reader("serve_stall_ms.backlog")(rec) == pytest.approx(
        1e3 * (2.0 - m))
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    stalls = line["serve_stalls"]
    assert stalls["n"] == 1 and stalls["window_gc_full"] == 1
    assert stalls["window_gc_s"] == pytest.approx(1.9)
    (call,) = stalls["calls"]
    assert call["kind"] == "decode" and call["phase"] == "decode_wait"
    assert call["gc_s"] == 1.9 and call["gc_full"] == 1
    assert call["compiles"] == 0 and call["step_s"] == 2.0


def test_a_window_with_four_deep_calls_has_no_deep_median(bench):
    steps = [s for s in _made_steps()
             if s.get("read", {}).get("chunk_pos", 0) < 8192 + 4 * 512]
    assert _span_reader("chunk_call_deep_ms.backlog")(
        {"serve_steps": steps}) is None
    assert _span_reader("chunk_call_ms.backlog")(
        {"serve_steps": steps}) is not None


@pytest.mark.parametrize("name", NEW_SPAN_METRICS)
def test_a_call_reader_finds_nothing_on_a_program_without_read(bench, name,
                                                               capsys):
    """What the parent commit's traced run of these readers has to do."""
    old = [{k: v for k, v in s.items() if k not in ("read", "gc_s",
                                                      "gc_full")}
           for s in _made_steps()]
    assert _span_reader(name)({"serve_steps": old}) is None
    assert _span_reader(name)({"serve_steps": []}) is None
    assert _span_reader(name)({}) is None
    assert capsys.readouterr().out == ""


@pytest.mark.parametrize("name", NEW_SPAN_METRICS[:2] + NEW_SPAN_METRICS[3:])
def test_a_call_reader_reads_the_rehearsal_engines_events(
        bench, dense, experts, hybrid, latent, name, capsys):
    for run in (dense, experts, hybrid, latent):
        value = _span_reader(name)(run["record"])
        assert value is not None and math.isfinite(value) and value >= 0
        if "stall" not in name:
            assert value > 0
    capsys.readouterr()


def test_the_report_counts_calls_as_the_readers_do(bench):
    """``tadnn report`` cannot import ``benchmark/``: its two lines are the
    readers' arithmetic in the package's own words."""
    from torch_automatic_distributed_neural_network_tpu.obs import report

    steps = _made_steps()
    got, rec = report._calls_by_read(steps), {"serve_steps": steps}
    for key, name in (("chunk", "chunk_call_ms.backlog"),
                      ("chunk_deep", "chunk_call_deep_ms.backlog"),
                      ("decode", "decode_call_ms.backlog")):
        assert 1e3 * got["calls"][key][0] == pytest.approx(
            _span_reader(name)(rec))
    assert [got["calls"][k][1] for k in ("chunk", "chunk_deep", "decode")] \
        == [15, 6, 11]
    assert 1e3 * got["stalls"]["lost_s"] == pytest.approx(
        _span_reader("serve_stall_ms.backlog")(rec))
    assert got["stalls"]["n"] == 1 and got["stalls"]["gc_full"] == 1
    assert got["stalls"]["worst"]["phase"] == "decode_wait"
    assert report._calls_by_read(
        [{"step": 1, "step_s": 0.1, "phases": {}}]) == {}


@pytest.mark.parametrize("name", NEW_SPAN_METRICS)
def test_a_new_metric_has_its_file_and_its_cells(name):
    entry = next(m for m in BENCHMARK["per_layer"] if m["name"] == name)
    assert name + ".py" in METRIC_FILES
    cells = {w["name"] for w in BENCHMARK["workloads"]}
    assert set(entry["workloads"]) <= cells and entry["workloads"]
    e2e = next(m for m in BENCHMARK["end_to_end"]
               if m["name"] == entry["moves"])
    assert set(entry["workloads"]) <= set(e2e["workloads"])
    assert entry["source"] == "program_span" and entry["layer"] == "serve step"


# -- 5b'. zero-compute experts and the shortcut branch (PR 39) ------------------

ROUTED_METRICS = ("zero_expert_share", "moe_live_pairs_per_row")


@pytest.mark.parametrize("name", ROUTED_METRICS)
def test_a_routed_metric_has_its_file_and_its_cell(name):
    entry = next(m for m in BENCHMARK["per_layer"] if m["name"] == name)
    assert name + ".py" in METRIC_FILES
    assert entry == {
        "name": name, "unit": {"zero_expert_share": "%"}.get(name, "pairs"),
        "better": "higher", "source": "program_counter",
        "layer": "expert layer", "moves": "serve_tokens_per_s",
        "workloads": ["longcat-flash-omni-ep32.serve-backlog-deep-routed"]}
    assert entry in BENCHMARK["per_layer"][33:35]  # appended, nothing moved


def test_the_routed_readers_read_the_programs_counters(bench, shortcut,
                                                        capsys):
    """``moe_zero_pairs`` and ``moe_rows`` on every ``serve.step`` that read
    a step of a model with expert layers (``moe_pairs`` on those whose rows
    decoded alone), and what the two readers make of them: at the
    rehearsal's sizes (top 3 of 16 + 8, 2 expert branches) a share between
    0 and 100% and at most 3 live pairs a row."""
    rec = shortcut["record"]
    read = [s for s in rec["serve_steps"] if "moe_rows" in s]
    assert read and all("moe_zero_pairs" in s for s in read)
    assert any("moe_pairs" in s for s in read)
    assert not [s for s in read if "moe_pairs" in s and s["moe_rows"] > 4]
    ev = rec["serve_engine"]
    assert (ev["zero_experts"], ev["shortcut_experts"]) == (8, True)
    share = _span_reader("zero_expert_share")(rec)
    line = json.loads(capsys.readouterr().out.strip())["zero_experts"]
    assert line["expert_layers"] == 2 and line["rows"] == sum(
        s["moe_rows"] for s in read)
    assert share == pytest.approx(
        100 * line["zero_pairs"] / (3 * 2 * line["rows"])) and 0 < share < 100
    live = _span_reader("moe_live_pairs_per_row")(rec)
    line = json.loads(capsys.readouterr().out.strip())["moe_live_pairs"]
    assert line["due_a_row"] == 3 * 4 / 24
    assert 0 <= live <= 3 and live == line["pairs"] / (2 * line["rows"])


@pytest.mark.parametrize("name", ROUTED_METRICS)
def test_a_routed_reader_finds_nothing_where_there_is_nothing(
        bench, shortcut, experts, dense, name, capsys):
    """No zero-compute expert (a model that routes over its experts alone
    reads no share, and its live pairs only where the program counts rows),
    no expert layer, a program without the counters (a parent commit's
    events): ``None``, and no error."""
    reader = _span_reader(name)
    assert reader(dense["record"]) is None
    if name == "zero_expert_share":
        assert reader(experts["record"]) is None
    rec = shortcut["record"]
    old = [{k: v for k, v in s.items()
            if k not in ("moe_zero_pairs", "moe_rows")}
           for s in rec["serve_steps"]]
    assert reader({**rec, "serve_steps": old}) is None
    assert reader({**rec, "serve_steps": []}) is None
    capsys.readouterr()


# -- 5c. the chunk's latent attention as one kernel (PR 38) ---------------------


def test_the_chunk_kernels_metric_has_its_file_and_its_cell(bench, latent,
                                                            experts, capsys):
    """``latent_chunk_attn_ms``: its reader, its ``per_layer`` entry (the
    attention kernels' layer, the device trace, moves the cell's one
    end-to-end metric) and the latent cell alone on its list; like its
    neighbours it finds nothing where there is nothing (no trace on the CPU,
    no such kernel on a parent commit) and does not raise."""
    name = "latent_chunk_attn_ms"
    entry = next(m for m in BENCHMARK["per_layer"] if m["name"] == name)
    assert name + ".py" in METRIC_FILES
    assert entry == {
        "name": name, "unit": "ms", "better": "lower",
        "source": "device_trace", "layer": "attention kernels",
        "moves": "serve_tokens_per_s",
        "workloads": ["joyai-llm-flash-ep8.serve-backlog-deep",
                      "longcat-flash-omni-ep32.serve-backlog-deep-routed",
                      "kimi-linear-48b-ep8.serve-backlog-reasoning"]}
    # appended, nothing moved (PR 39 appended its two behind it)
    assert BENCHMARK["per_layer"][32] == entry
    reader = _span_reader(name)
    for run in (latent, experts):
        assert reader(run["record"]) is None
        assert reader({**run["record"], "trace": {"n_devices": 0}}) is None
    capsys.readouterr()


def test_the_chunk_kernels_name_is_its_own(bench):
    """``latent_attn_roofline`` and ``paged_attn_roofline*`` tell the decode
    kernels by substring: the chunk kernel's name matches none of them, and
    its reader's pattern matches no decode kernel."""
    from lib import counts_mla

    have = _pallas_names()
    assert "tadnn_latent_chunk" in have and "tadnn_latent_chunk" in KERNELS
    assert counts_mla.KERNEL not in "tadnn_latent_chunk"
    assert "tadnn_paged_decode" not in "tadnn_latent_chunk"
    assert not [n for n in have - {"tadnn_latent_chunk"}
                if "tadnn_latent_chunk" in n]


def test_the_chunk_kernels_counters_have_their_reader(latent, dense,
                                                      tmp_path):
    """``chunk_attention`` on ``serve.engine`` and ``chunk_key_blocks`` on
    ``serve.step`` are in the schema, and ``tadnn report`` is their reader.
    On the CPU the plain form is what an engine runs: every kind says
    ``"blocks"`` and no call counts a key block."""
    from torch_automatic_distributed_neural_network_tpu.obs import (
        report,
        schema,
    )

    specs = schema.REGISTRY
    assert specs["serve.engine"].optional["chunk_attention"] == "dict?"
    assert specs["serve.step"].optional["chunk_key_blocks"] == "int"
    ev = latent["record"]["serve_engine"]
    assert ev["chunk_attention"] == {"latent_attention": "blocks"}
    assert latent["eng"].chunk_attention == ev["chunk_attention"]
    assert dense["record"]["serve_engine"]["chunk_attention"] \
        == {"full_attention": "blocks"}
    assert not [s for s in _steps(latent) if "chunk_key_blocks" in s]
    # a journal of an engine whose chunks ran the kernel: two chunk calls
    steps = [dict(s) for s in _steps(latent)]
    chunked = [s for s in steps if s.get("n_prefill_chunks")][:2]
    for s, blocks in zip(chunked, (20, 60)):
        s["chunk_key_blocks"] = blocks
    path = tmp_path / "journal.jsonl"
    path.write_text("".join(json.dumps(r) + "\n" for r in (
        {**ev, "chunk_attention": {"latent_attention": "kernel"}}, *steps)))
    made = report.generate(str(path))
    assert made["serving"]["chunk_key_blocks"] == [2, 80]
    assert ("a chunk's attention in a kernel (latent_attention layers): "
            "40.0 key blocks a chunk call over the layers (2 calls)"
            ) in report.format_report(made)


def test_the_latent_counts_are_the_arithmetic(bench):
    """69.6 kFLOP and 1,152 B a key a layer at the published widths: 60
    FLOP/B, under the v5e's ridge of 240."""
    from lib import counts_mla

    keys = CONFIGS["joyai-llm-flash-ep8"]["model"]
    n, heads, row, value = counts_mla.latent_layers(keys)
    assert (n, heads, row, value) == (20, 32, 576, 512)
    flops = counts_mla.latent_attention_flops(1, heads, row, value)
    bytes_ = counts_mla.latent_attention_bytes(1, row, itemsize=2)
    assert (flops, bytes_) == (69632.0, 1152.0)
    assert counts_mla.latent_layers(CONFIGS["gpt2-1p3b"]["model"]) \
        == (0, 0, 0, 0)


def test_a_kind_that_exchanges_names_finds_them(bench):
    """``lib/serving_long.py`` runs ``lib/serving_large.run`` with three of
    that module's names bound to its own: they have to be there, under the
    same parameters."""
    large = importlib.import_module("lib.serving_large")
    long_ = importlib.import_module("lib.serving_long")
    for name, mine in long_.EXCHANGED.items():
        theirs = getattr(large, name)
        assert list(inspect.signature(theirs).parameters) \
            == list(inspect.signature(mine).parameters), name
    src = inspect.getsource(large.run) + inspect.getsource(large.build)
    assert all(re.search(rf"\b{name}\(", src) for name in long_.EXCHANGED)


# -- 6. the train cell's calls into AutoDistribute and Trainer ---------------


def test_the_train_generators_calls_hold_on_a_tiny_model(bench):
    """``generators/train.py`` closes its window by raising StopIteration
    from a step-indexed feed, hands ``fit`` a state, reads the plan, the
    precision and the compile report, and is called back with (step, state,
    metrics) where ``metrics["loss"]`` is the step's loss."""
    import optax

    import torch_automatic_distributed_neural_network_tpu as tad
    from lib import program
    from torch_automatic_distributed_neural_network_tpu.data.synthetic import (
        SyntheticLM,
    )
    from torch_automatic_distributed_neural_network_tpu.obs.journal import (
        Journal,
    )
    from torch_automatic_distributed_neural_network_tpu.training import (
        Trainer,
        TrainerConfig,
        next_token_loss,
    )

    cell = bench.Cell(next(
        w["name"] for w in BENCHMARK["workloads"]
        if MIXES[w["traffic"]]["kind"] == "train"))
    bench.apply_rehearsal(cell)
    mix, keys = cell.mix, program.model_keys(cell.config)
    model = program.build_model(cell.config, mix.get("model_options"))
    data = SyntheticLM(vocab_size=keys["vocab_size"],
                       seq_len=mix["seq_len"] + 1,
                       batch_size=mix["batch_size"], seed=3)

    class Feed:
        step_indexed = True

        def batch(self, i):
            if i >= 3:
                raise StopIteration
            return data.batch(i)

    ad = tad.AutoDistribute(
        model, optimizer=optax.adamw(1e-3), loss_fn=next_token_loss,
        devices=jax.devices()[:1], export_cache=False,
        **mix["autodistribute"])
    rng = jax.random.key(0)
    state = ad.init(rng, data.batch(0))
    assert ad.precision.param_dtype is not None
    assert isinstance(ad.plan.strategy, str) and ad.plan.remat is not None
    assert dataclasses.is_dataclass(state) and state.params is not None \
        and state.opt_state is not None
    seen = []
    trainer = Trainer(
        ad, TrainerConfig(steps=10**9, log_every=0, **mix["trainer"]),
        callbacks=[lambda i, st, m: seen.append((i, float(m["loss"])))],
        items_per_step=mix["batch_size"] * mix["seq_len"],
        journal=Journal(None, host0_only=False))
    trainer.fit(Feed(), state=state)
    assert [i for i, _ in seen] == [1, 2, 3]
    assert all(math.isfinite(x) for _, x in seen)
    report = ad.compile_report(rng, data.batch(0))
    assert (report or {}).get("per_device_peak_bytes"), \
        "train_step_hbm_gib reads per_device_peak_bytes of compile_report"


# -- 9. a pool of state rows and latent pages; the KDA kernels' readers (PR 41) --

KDA_METRICS = ("kda_chunk_ms", "kda_step_ms", "kda_chunk_roofline",
               "kda_step_roofline")
KDA_CELL = "kimi-linear-48b-ep8.serve-backlog-reasoning"
# the second shape the KDA kernels are judged at (section 11)
GATED_CELL = "solar-open2-250b-ep8.serve-backlog-reasoning-s128"
# what the cell's readers index on the events of a model of both kinds
MIXED_FIELDS = {
    "linear_mixer": "obs/report.py (the state line: whose the decay is)",
    "state_bytes_linear": "metrics/state_pool_gib.py:12,15,19",
    "conv_bytes_linear": "metrics/state_pool_gib.py:16,19",
    "kv_bytes_latent": "obs/report.py (the latent line beside the pool's)",
    "latent_row": "obs/report.py (the latent line beside the pool's)",
    "kv_bytes_full": "metrics/kv_pool_gib.py:10,13,16",
    "layer_kinds": "metrics/kv_pool_gib.py:15",
}


@pytest.mark.parametrize("field", sorted(MIXED_FIELDS))
def test_serve_engine_of_a_mixed_pool_carries_its_counters(mixed, field):
    ev = mixed["record"]["serve_engine"]
    assert ev is not None and ev.get(field) is not None, (
        f"serve.engine has no {field!r}; read by " + MIXED_FIELDS[field])


def test_serve_engine_says_what_a_mixed_cell_is_about(mixed, hybrid):
    """Rows and latent pages in one pool: the pages for ``max_len`` are the
    latent layers' alone, the states and tails the linear layers'; the rule
    is the gated delta rule with a decay a CHANNEL (a head's on the hybrid
    model); and ``state_rows`` rides on the steps that read decode rows: the
    slots that decoded times the linear layers."""
    ev, eng = mixed["record"]["serve_engine"], mixed["eng"]
    kinds = list(mixed["record"]["model_keys"]["layer_types"])
    n_lin = kinds.count("linear_attention")
    assert ev["linear_mixer"] == ["gated_delta", "channel"]
    assert hybrid["record"]["serve_engine"]["linear_mixer"] == [
        "gated_delta", "head"]
    assert ev["kv_bytes_full"] == ev["kv_bytes_latent"] > 0
    assert ev["state_bytes_linear"] > ev["conv_bytes_linear"] > 0
    assert ev["layer_kinds"] == kinds
    assert eng.pool.n_full == kinds.count("latent_attention") > 0
    assert eng.pool.state.count(True) == n_lin > 0
    rows = [s["state_rows"] for s in _steps(mixed) if "state_rows" in s]
    assert rows and all(r % n_lin == 0 and r > 0 for r in rows)
    assert max(rows) <= n_lin * mixed["record"]["engine"]["n_slots"]
    kinds = hybrid["record"]["model_keys"]["layer_types"]
    rows = [s["state_rows"] for s in _steps(hybrid) if "state_rows" in s]
    assert rows and all(
        r % list(kinds).count("linear_attention") == 0 for r in rows)


@pytest.mark.parametrize("field", ["linear_mixer", "state_rows"])
def test_the_new_fields_are_in_the_schema(field):
    from torch_automatic_distributed_neural_network_tpu.obs import schema

    with open(schema.__file__) as f:
        assert f'"{field}"' in f.read()


@pytest.mark.parametrize("name", KDA_METRICS)
def test_a_kda_metric_has_its_file_and_its_cell(name):
    entry = next(m for m in BENCHMARK["per_layer"] if m["name"] == name)
    assert entry["workloads"] == [KDA_CELL, GATED_CELL]
    assert entry["moves"] == "serve_tokens_per_s"
    assert entry["layer"] == "linear attention"
    assert entry["source"] == "device_trace"
    assert (entry["unit"], entry["better"]) == (
        ("%", "higher") if name.endswith("roofline") else ("ms", "lower"))
    assert name + ".py" in METRIC_FILES
    assert entry in BENCHMARK["per_layer"][35:39]  # appended, nothing moved
    serve = next(m for m in BENCHMARK["end_to_end"]
                 if m["name"] == "serve_tokens_per_s")
    assert KDA_CELL in serve["workloads"]


@pytest.mark.parametrize("name", KDA_METRICS)
def test_a_kda_reader_finds_nothing_where_there_is_nothing(
        bench, mixed, hybrid, name, capsys):
    """No device trace on the CPU, a trace of no device, and a model whose
    linear layers run the scalar rule's kernels: ``None``, and no raise."""
    reader = _load(os.path.join(BENCH, "metrics", name + ".py"),
                   "bench_metric")
    for run in (mixed, hybrid):
        assert reader.read(run["record"]) is None
        assert reader.read({**run["record"], "trace": {"n_devices": 0}}) is None
    other = ("%tadnn_gdn_step.1 = f32[8,3,10,192] custom-call()", 10, 500)
    rec = {**mixed["record"], "peaks": {"flops_per_s": 197e12,
                                        "hbm_bytes_per_s": 819e9},
           "trace_mono": (0.0, 1e9),
           "trace": {"n_devices": 1, "ops": {"d": [other]},
                     "modules": {"d": [("jit_serve_prefill_chunk(1)", 0,
                                        1000)]},
                     "module_seconds": {"jit_serve_prefill_chunk": [1e-6]}}}
    assert reader.read(rec) is None
    capsys.readouterr()


@pytest.mark.parametrize("model", ["mixed", "gated"])
def test_the_kda_readers_read_a_hand_made_trace(bench, model, request,
                                                capsys):
    """On each of the two models with KDA layers (three each at the
    rehearsal's sizes), whatever else their pools hold: two runs of the chunk's program and one decode step; in each the
    three linear layers' step kernel takes 40 us a layer, with a staged
    copy of a layer's pool open for 60 us round the first (the union is
    counted: 100 us in that run), and the chunk kernel 200 us a layer.  The
    shares are the least time of ``counts_kda`` over those times."""
    from lib import counts_kda

    mixed = request.getfixturevalue(model)
    rec0, keys = mixed["record"], mixed["record"]["model_keys"]
    n, H, dk, dv = 3, keys["linear_value_heads"], \
        keys["linear_key_head_dim"], keys["linear_value_head_dim"]
    S, C = rec0["engine"]["n_slots"], rec0["engine"]["prefill_chunk"]
    pool = f"f32[{S + 1},{H},{dk},{dv}]"
    us = 1000
    mods = [("jit_serve_prefill_chunk(7)", 0, 2000 * us),
            ("jit_serve_prefill_chunk(7)", 3000 * us, 5000 * us),
            ("jit_serve_decode_step(9)", 6000 * us, 7000 * us)]
    ops = []
    for m, (_, lo, _hi) in enumerate(mods):
        for i in range(n):
            at = lo + (100 + 300 * i) * us
            ops.append((f"%tadnn_kda_step.{i} = (f32[{S},4,8,{dv}], {pool}) "
                        f"custom-call()", at, at + 40 * us))
            if m < 2:
                ops.append((f"%tadnn_kda_chunk.{i} = f32[{H},1,{C},{dv}] "
                            f"custom-call()", at + 50 * us, at + 250 * us))
        ops.append((f"%copy-start.{m} = ({pool}, {pool}) copy-start()",
                    lo + 80 * us, lo + 81 * us))
        ops.append((f"%copy-done.{m} = {pool} copy-done()", lo + 139 * us,
                    lo + 140 * us))
    steps = [{"state_rows": n * rows, "t_end": t}
             for rows, t in ((2, 0.5), (4, 1.5), (3, 99.0))]
    rec = {**rec0, "peaks": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
           "trace_mono": (0.0, 2.0), "serve_steps": steps,
           "trace": {"n_devices": 1, "ops": {"d": ops}, "modules": {"d": mods},
                     "module_seconds": {
                         "jit_serve_prefill_chunk": [2e-3, 2e-3],
                         "jit_serve_decode_step": [1e-3]}}}
    read = lambda name: _load(os.path.join(  # noqa: E731
        BENCH, "metrics", name + ".py"), "bench_metric").read(rec)
    assert read("kda_chunk_ms") == pytest.approx(3 * 0.2)
    # a run: 40 + (the copy's window 80..140 joined to the kernel's
    # 100..140) + 40 + 40 = 140 us over the three layers
    assert read("kda_step_ms") == pytest.approx(0.14)
    assert counts_kda.traced_state_rows(rec) == (3.0, 2)  # the third is outside
    prompts = [len(q["prompt"]) for q in rec0["requests"]]
    fill = sum(prompts) / (C * sum(-(-p // C) for p in prompts))
    chunk = read("kda_chunk_roofline")
    step = read("kda_step_roofline")
    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines()
             if l.startswith("{")]
    least = lambda tokens, seqs: max(  # noqa: E731
        n * counts_kda.recurrence_flops(tokens, H, dk, dv) / 197e12,
        n * counts_kda.recurrence_bytes(tokens, seqs, H, dk, dv, itemsize=2)
        / 819e9)
    assert chunk == pytest.approx(100 * least(C * fill, 1.0) / 0.6e-3)
    assert step == pytest.approx(100 * least(3.0, 3.0) / 0.14e-3)
    assert any("kda_chunk" in l for l in lines)
    assert any(l.get("kda_step", {}).get("steps") == 2 for l in lines)
    assert 0 < chunk < 100 and 0 < step < 100


def test_the_kda_counts_are_the_arithmetic(bench):
    """7 d_k d_v operations a token a head; q, k, v, the output, beta and
    the d_k float32 decays once a token a head; the state in and out once a
    sequence: at the cell's widths a decode row is bound by its state."""
    from lib import counts_gdn, counts_kda

    keys = CONFIGS["kimi-linear-48b-ep8"]["model"]
    assert counts_gdn.linear_layers(keys) == (12, 32, 128, 128)
    assert counts_kda.recurrence_flops(1, 32, 128, 128) == 7 * 32 * 128 * 128
    per_token = 32 * (4 * 128 * 2 + 128 * 4 + 4)
    state = 2 * 32 * 128 * 128 * 4
    assert counts_kda.recurrence_bytes(1, 1, 32, 128, 128, itemsize=2) \
        == per_token + state == 49_280 + 4_194_304
    # the scalar rule's count reads ONE decay a head where this reads d_k
    assert counts_kda.recurrence_bytes(5, 0, 32, 128, 128, itemsize=2) \
        - counts_gdn.recurrence_bytes(5, 0, 32, 128, 128, itemsize=2) \
        == 5 * 32 * 127 * 4
    # 0.87 operations a byte a decode row: memory binds it
    assert counts_kda.recurrence_flops(1, 32, 128, 128) / (
        per_token + state) < 1


# -- 10. a decoder-hybrid-decoder: scan rows, ONE shared full cache (PR 46) -------

FLASH_METRICS = ("ssm_chunk_ms", "ssm_chunk_roofline", "ssm_step_ms",
                 "ssm_step_roofline", "diff_attn_decode_ms",
                 "diff_attn_roofline", "cross_rows_share")
FLASH_CELL = "phi4-mini-flash-3p8b.serve-backlog-reasoning-s64"
# what the cell's readers index on the events of such a model
FLASH_FIELDS = {
    "state_bytes_linear": "metrics/state_pool_gib.py:12,15,19",
    "conv_bytes_linear": "metrics/state_pool_gib.py:16,19",
    "kv_bytes_full": "metrics/kv_pool_gib.py:10,13,16",
    "kv_bytes_window": "metrics/kv_pool_gib.py:10,14,16",
    "layer_kinds": "metrics/kv_pool_gib.py:15",
    "attention_form": "obs/report.py (the attention line)",
    "cross_start": "obs/report.py (the cross-decoder line)",
    "paged_sets": "obs/report.py (the cross-decoder line)",
    "shared_readers": "obs/report.py (the cross-decoder line)",
}
PEAKS = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


@pytest.fixture(scope="module")
def flash(bench):
    """A model of state-space layers, windowed and full differential
    attention, memory units and layers that read another layer's pages."""
    return _serve(bench, _cell_of("phi4-mini-flash-3p8b"))


@pytest.mark.parametrize("field", sorted(FLASH_FIELDS))
def test_serve_engine_of_a_hybrid_decoder_carries_its_counters(flash, field):
    ev = flash["record"]["serve_engine"]
    assert ev is not None and ev.get(field) is not None, (
        f"serve.engine has no {field!r}; read by " + FLASH_FIELDS[field])


def test_serve_engine_says_what_the_hybrid_decoder_cell_is_about(flash):
    """Nine kinds of cache would be the naive count for the eight layers of
    the rehearsal; the pool holds two paged sets (a ring, one full layer's
    pages) and two state rows, the cross-decoder's four layers nothing; the
    bytes say so; ``state_rows`` counts the decode rows over the state-space
    layers, and ``read`` the rows that ran each decoder."""
    ev, eng = flash["record"]["serve_engine"], flash["eng"]
    kinds = list(flash["record"]["model_keys"]["layer_types"])
    n_scan = kinds.count("state_space")
    assert ev["attention_form"] == "differential"
    assert ev["cross_start"] == 4 and ev["linear_mixer"] is None
    assert (ev["paged_sets"], ev["shared_readers"]) == (2, 2)
    pool = eng.pool
    assert (pool.n_full, pool.state.count(True), pool.none.count(True)) \
        == (1, n_scan, 4)
    assert ev["kv_bytes_full"] == pool.bytes_per_block * pool.num_blocks
    assert ev["state_bytes_linear"] == n_scan * (
        flash["record"]["engine"]["n_slots"] + 1) * 8 * 128 * 4
    rows = [s["state_rows"] for s in _steps(flash) if "state_rows" in s]
    assert rows and all(r % n_scan == 0 and r > 0 for r in rows)
    S, C = (flash["record"]["engine"][k] for k in ("n_slots",
                                                   "prefill_chunk"))
    reads = [s["read"] for s in _steps(flash)
             if s.get("read") and s["read"]["programs"] == 1]
    assert {(r["self_rows"], r["cross_rows"]) for r in reads} \
        == {(S, S), (C + S, 1 + S)}


@pytest.mark.parametrize("field", ["attention_form", "cross_start",
                                   "paged_sets", "shared_readers",
                                   "cross_rows", "self_rows"])
def test_the_hybrid_decoders_fields_are_in_the_schema(field):
    from torch_automatic_distributed_neural_network_tpu.obs import schema

    with open(schema.__file__) as f:
        assert f'"{field}"' in f.read()


@pytest.mark.parametrize("name", FLASH_METRICS)
def test_a_hybrid_decoder_metric_has_its_file_and_its_cell(name):
    entry = next(m for m in BENCHMARK["per_layer"] if m["name"] == name)
    assert entry["workloads"] == [FLASH_CELL]
    assert entry["moves"] == "serve_tokens_per_s"
    layer, source = {
        "ssm": ("state-space scan", "device_trace"),
        "dif": ("attention kernels", "device_trace"),
        "cro": ("serve step", "program_counter")}[name[:3]]
    assert (entry["layer"], entry["source"]) == (layer, source)
    assert (entry["unit"], entry["better"]) == (
        ("%", "higher") if name.endswith("roofline")
        else ("%", "lower") if name == "cross_rows_share" else ("ms", "lower"))
    assert name + ".py" in METRIC_FILES
    assert entry in BENCHMARK["per_layer"][39:46]  # appended, nothing moved
    serve = next(m for m in BENCHMARK["end_to_end"]
                 if m["name"] == "serve_tokens_per_s")
    assert FLASH_CELL in serve["workloads"]
    assert next(w for w in BENCHMARK["workloads"]
                if w["name"] == FLASH_CELL)["chips"] == 1
    # the engine-wide metrics whose readers find something there; neither
    # count of the paged kernel's bytes holds for a shared cache
    listed = {m["name"] for m in BENCHMARK["per_layer"]
              if FLASH_CELL in m.get("workloads", ())}
    assert listed == set(FLASH_METRICS) | {
        "slot_occupancy", "decode_step_ms.backlog", "device_idle_share.serve",
        "prefill_chunk_device_ms.backlog", "serve_host_ms.backlog",
        "kv_pool_gib", "state_pool_gib", "chunk_call_ms.backlog",
        "decode_call_ms.backlog", "serve_stall_ms.backlog",
        *STARTUP_METRICS}


def _flash_reader(name):
    return _load(os.path.join(BENCH, "metrics", name + ".py"), "bench_metric")


@pytest.mark.parametrize("name", FLASH_METRICS)
def test_a_hybrid_decoder_reader_finds_nothing_where_there_is_nothing(
        bench, flash, mixed, dense, name, capsys):
    """No device trace on the CPU, a trace of no device, a trace without the
    kernels (the parent's program could not build this model; another
    model's record has no scan layers, no differential attention and no
    ``self_rows``): ``None``, and no raise."""
    reader = _flash_reader(name)
    other = ("%tadnn_kda_step.1 = f32[8,3,10,192] custom-call()", 10, 500)
    traced = {"peaks": PEAKS, "trace_mono": (0.0, 1e9),
              "trace": {"n_devices": 1, "ops": {"d": [other]},
                        "modules": {"d": [("jit_serve_prefill_chunk(1)", 0,
                                           1000)]},
                        "module_seconds": {"jit_serve_prefill_chunk": [1e-6]}}}
    for run in (mixed, dense):
        assert reader.read(run["record"]) is None
        assert reader.read({**run["record"], **traced}) is None
    if name != "cross_rows_share":  # a counter: read off the chip too
        assert reader.read(flash["record"]) is None
        assert reader.read({**flash["record"],
                            "trace": {"n_devices": 0}}) is None
        assert reader.read({**flash["record"], **traced}) is None
    capsys.readouterr()


def test_the_events_readers_give_numbers_on_the_hybrid_decoder(bench, flash):
    rec = flash["record"]
    got = _flash_reader("cross_rows_share").read(rec)
    reads = [s["read"] for s in rec["serve_steps"] if s.get("read")]
    assert got == pytest.approx(100 * sum(r["cross_rows"] for r in reads)
                                / sum(r["self_rows"] for r in reads))
    assert 0 < got < 100
    assert _flash_reader("kv_pool_gib").read(rec) == pytest.approx(
        (flash["eng"].pool.bytes_full + flash["eng"].pool.bytes_window)
        / 2**30)
    assert _flash_reader("state_pool_gib").read(rec) == pytest.approx(
        sum(flash["eng"].pool.bytes_state) / 2**30)


def test_the_hybrid_decoders_readers_read_a_hand_made_trace(bench, flash,
                                                            capsys):
    """Two runs of the chunk's program and one decode step; in each the two
    state-space layers' step kernel takes 40 us a layer, with a staged copy
    of a layer's pool open for 60 us round the first (the union is counted:
    100 us in that run), the chunk kernel 200 us a layer, and the four
    layers that attend pages 30 us each.  The shares are the least time of
    ``counts_ssm`` and ``counts_diff_attn`` over those times."""
    from lib import counts_diff_attn, counts_ssm

    rec0, keys = flash["record"], flash["record"]["model_keys"]
    n, d_in, N = counts_ssm.scan_layers(keys)
    assert (n, d_in, N) == (2, 128, 8)
    S, C = rec0["engine"]["n_slots"], rec0["engine"]["prefill_chunk"]
    pool = f"f32[{S + 1},{N},{d_in}]"
    us = 1000
    mods = [("jit_serve_prefill_chunk(7)", 0, 2000 * us),
            ("jit_serve_prefill_chunk(7)", 3000 * us, 5000 * us),
            ("jit_serve_decode_step(9)", 6000 * us, 7000 * us)]
    ops = []
    for m, (_, lo, _hi) in enumerate(mods):
        for i in range(n):
            at = lo + (100 + 300 * i) * us
            ops.append((f"%tadnn_ssm_step.{i} = (f32[{S},1,{d_in}], {pool}) "
                        f"custom-call()", at, at + 40 * us))
            if m < 2:
                ops.append((f"%tadnn_ssm_chunk.{i} = (f32[{C},{d_in}], "
                            f"f32[{N},{d_in}]) custom-call()", at + 50 * us,
                            at + 250 * us))
        for i in range(4):
            at = lo + (1000 + 100 * i) * us
            ops.append((f"%tadnn_paged_decode_folded.{i} = bf16[{S},4,32] "
                        f"custom-call()", at, at + 30 * us))
        ops.append((f"%copy-start.{m} = ({pool}, {pool}) copy-start()",
                    lo + 80 * us, lo + 81 * us))
        ops.append((f"%copy-done.{m} = {pool} copy-done()", lo + 139 * us,
                    lo + 140 * us))
    steps = [{"state_rows": n * rows, "t_end": t}
             for rows, t in ((2, 0.5), (4, 1.5), (3, 99.0))]
    reqs = [{"prompt": [1] * 20, "walls": [0.1, 0.5, 1.0, 50.0],
             "t_admit": 0.0}]
    rec = {**rec0, "peaks": PEAKS, "trace_mono": (0.0, 2.0),
           "serve_steps": steps, "requests": reqs,
           "trace": {"n_devices": 1, "ops": {"d": ops}, "modules": {"d": mods},
                     "module_seconds": {
                         "jit_serve_prefill_chunk": [2e-3, 2e-3],
                         "jit_serve_decode_step": [1e-3]}}}
    read = lambda name: _flash_reader(name).read(rec)  # noqa: E731
    assert read("ssm_chunk_ms") == pytest.approx(2 * 0.2)
    # a run: (the copy's window 80..140 joined to the kernel's 100..140) +
    # 40 = 100 us over the two layers
    assert read("ssm_step_ms") == pytest.approx(0.1)
    assert counts_ssm.traced_state_rows(rec) == (3.0, 2)
    assert read("diff_attn_decode_ms") == pytest.approx(4 * 0.03)
    chunk, step = read("ssm_chunk_roofline"), read("ssm_step_roofline")
    attn = read("diff_attn_roofline")
    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines()
             if l.startswith("{")]
    least = lambda tokens, seqs: max(  # noqa: E731
        n * counts_ssm.scan_flops(tokens, d_in, N) / 197e12,
        n * counts_ssm.scan_bytes(tokens, seqs, d_in, N, itemsize=2) / 819e9)
    fill = 20 / (C * -(-20 // C))  # one prompt of 20, its last chunk padded
    assert chunk == pytest.approx(100 * least(C * fill, 1.0) / 0.4e-3)
    assert step == pytest.approx(100 * least(3.0, 3.0) / 0.1e-3)
    # two decode tokens inside the traced part, at contexts 21 and 22: the
    # full layer and its two readers read all of them, the ring min(c, 16)
    assert counts_diff_attn.traced_contexts(rec) == [21, 22]
    assert counts_diff_attn.layers(keys) == (3, 1, 16)
    read_keys = 3 * 43 + 1 * 32
    assert counts_diff_attn.keys_read([21, 22], keys) == read_keys
    H, KV, hd = keys["n_heads"], keys["n_kv_heads"], 16
    assert attn == pytest.approx(100 * max(
        6 * read_keys * H * hd / 197e12,
        2 * read_keys * KV * hd * 2 / 819e9) / (12 * 30e-6))
    assert any("ssm_chunk" in l for l in lines)
    assert any(l.get("ssm_step", {}).get("steps") == 2 for l in lines)
    assert any(l.get("diff_attn", {}).get("calls") == 12 for l in lines)
    assert 0 < chunk < 100 and 0 < step < 100 and 0 < attn < 100


def test_the_hybrid_decoders_counts_are_the_arithmetic(bench):
    """7 N + 3 operations a token a channel; c, Delta and the output once a
    token, the state in and out once a sequence: at the cell's widths a
    decode row's nine scans move 5.9 MB and are bound by their states.  A
    decode token at context c reads 8 c + 8 min(c, 512) keys of 5,120 B."""
    from lib import counts_diff_attn, counts_ssm

    keys = CONFIGS["phi4-mini-flash-3p8b"]["model"]
    assert counts_ssm.scan_layers(keys) == (9, 5120, 16)
    assert counts_ssm.scan_flops(1, 5120, 16) == 5120 * 115
    per_token, state = 5120 * 10 + 128, 2 * 16 * 5120 * 4
    assert counts_ssm.scan_bytes(1, 1, 5120, 16, itemsize=2) \
        == per_token + state == 51_328 + 655_360
    assert round(9 * (per_token + state) / 1e6, 1) == 6.4
    assert counts_ssm.scan_flops(1, 5120, 16) / (per_token + state) < 1
    assert counts_diff_attn.layers(keys) == (8, 8, 512)
    assert counts_diff_attn.keys_read([100, 2700], keys) \
        == 8 * 2800 + 8 * (100 + 512)
    assert counts_diff_attn.decode_bytes(1, 20, 64, itemsize=2) == 5120
    assert counts_diff_attn.decode_flops(1, 40, 64) == 6 * 40 * 64


# -- 11. gated attention FIRST, 64-head KDA with beta to 2, no dense layer (PR 49) --

# what tells such a model apart on its ``serve.engine`` event, and who reads it
GATED_FIELDS = {
    "linear_write_max": "obs/report.py (the state line: beta up to)",
    "attn_gate": "obs/report.py (the attention line)",
    "dense_layers": "obs/report.py (the experts line: in EVERY layer)",
    "linear_mixer": "obs/report.py (the state line: whose the decay is)",
    "state_bytes_linear": "metrics/state_pool_gib.py:12,15,19",
    "conv_bytes_linear": "metrics/state_pool_gib.py:16,19",
    "kv_bytes_full": "metrics/kv_pool_gib.py:10,13,16",
    "layer_kinds": "metrics/kv_pool_gib.py:15",
}


@pytest.fixture(scope="module")
def gated(bench):
    """A model of a gated ``full_attention`` layer and three KDA layers
    whose beta reaches 2, an expert FFN in every one: K/V pages AND state
    rows."""
    return _serve(bench, _cell_of("solar-open2-250b-ep8"))


@pytest.mark.parametrize("field", sorted(GATED_FIELDS))
def test_serve_engine_of_a_gated_hybrid_carries_its_counters(gated, field):
    ev = gated["record"]["serve_engine"]
    assert ev is not None and ev.get(field) is not None, (
        f"serve.engine has no {field!r}; read by " + GATED_FIELDS[field])


def test_serve_engine_tells_the_gated_hybrid_apart(gated, mixed, hybrid,
                                                   flash, dense):
    """The write strength's upper end, the gate on the softmax layers and
    the count of dense layers, in fields of their own: ``linear_mixer``
    stays the two-element list it was, the same on both KDA models."""
    ev, eng = gated["record"]["serve_engine"], gated["eng"]
    kinds = list(gated["record"]["model_keys"]["layer_types"])
    assert kinds[0] == "full_attention" and kinds.count(
        "linear_attention") == 3
    assert (ev["linear_write_max"], ev["attn_gate"], ev["dense_layers"]) \
        == (2, True, 0)
    assert ev["linear_mixer"] == ["gated_delta", "channel"] \
        == mixed["record"]["serve_engine"]["linear_mixer"]
    assert ev["attention_form"] == "softmax" and ev["kv_bytes_latent"] == 0
    other = mixed["record"]["serve_engine"]
    assert (other["linear_write_max"], other["attn_gate"],
            other["dense_layers"]) == (1, False, 1)
    # the scalar rule's model doubles beta too; a model without a linear
    # layer has no write strength; one scanned layer kind is all dense
    assert hybrid["record"]["serve_engine"]["linear_write_max"] == 2
    assert flash["record"]["serve_engine"]["linear_write_max"] is None
    assert dense["record"]["serve_engine"]["dense_layers"] \
        == dense["record"]["model_keys"]["n_layers"]
    # K/V pages of ONE layer and state rows of three in one pool
    assert (eng.pool.n_full, eng.pool.state.count(True)) == (1, 3)
    assert ev["kv_bytes_full"] == eng.pool.bytes_per_block \
        * eng.pool.num_blocks > 0
    rows = [s["state_rows"] for s in _steps(gated) if "state_rows" in s]
    assert rows and all(r % 3 == 0 and r > 0 for r in rows)
    # every plan entry an expert FFN: a decode step's pairs over four layers
    assert gated["eng"].cfg.n_expert_layers == len(kinds)


@pytest.mark.parametrize("field", ["linear_write_max", "attn_gate",
                                   "dense_layers"])
def test_the_gated_hybrids_fields_are_in_the_schema(field):
    from torch_automatic_distributed_neural_network_tpu.obs import schema

    with open(schema.__file__) as f:
        assert f'"{field}"' in f.read()


def test_the_report_tells_the_gated_hybrid_apart(gated, mixed, tmp_path):
    from torch_automatic_distributed_neural_network_tpu.obs import (
        report as obs_report,
    )

    def text_of(run, name):
        path = tmp_path / name
        path.write_text("".join(json.dumps(r) + "\n"
                                for r in run["journal"].records))
        return obs_report.format_report(obs_report.generate(str(path)))

    text = text_of(gated, "gated.jsonl")
    assert "gated_delta: a decay a channel, beta up to 2" in text
    assert "attention: a sigmoid gate on its output" in text
    assert "experts 4 held of 16 in EVERY layer (no dense FFN)" in text
    text = text_of(mixed, "mixed.jsonl")
    assert "gated_delta: a decay a channel, beta up to 1" in text
    assert "sigmoid gate" not in text and "EVERY layer" not in text


def test_the_gated_hybrids_cell_is_appended_and_listed():
    """One configuration and one cell, the last of their lists; the cell on
    every reader that finds a number there on the chip, each list's last."""
    assert BENCHMARK["workloads"][-1] == {
        **BENCHMARK["workloads"][-1], "name": GATED_CELL,
        "config": "solar-open2-250b-ep8",
        "traffic": "serve-backlog-reasoning-s128", "chips": 1}
    assert BENCHMARK["configs"][-1]["name"] == "solar-open2-250b-ep8"
    assert BENCHMARK["configs"][-1]["reduced"] == [
        "num_hidden_layers", "gqa_layers", "n_routed_experts", "vocab_size"]
    serve = next(m for m in BENCHMARK["end_to_end"]
                 if m["name"] == "serve_tokens_per_s")
    assert serve["workloads"][-1] == GATED_CELL
    listed = {m["name"] for m in BENCHMARK["per_layer"]
              if GATED_CELL in m.get("workloads", ())}
    assert listed == set(KDA_METRICS) | {
        "slot_occupancy", "decode_step_ms.backlog", "device_idle_share.serve",
        "prefill_chunk_device_ms.backlog", "serve_host_ms.backlog",
        "serve_stall_ms.backlog", "chunk_call_ms.backlog",
        "decode_call_ms.backlog", "kv_pool_gib", "state_pool_gib",
        "moe_grouped_mm_chunk_ms", "paged_attn_roofline.by_kind",
        *STARTUP_METRICS}
    for m in BENCHMARK["per_layer"]:
        if m["name"] in listed:
            assert m["workloads"][-1] == GATED_CELL, m["name"]
    # the traffic is the other reasoning mix's, letter for letter, at 128
    # slots and twice the list
    mine, theirs = (MIXES[t] for t in ("serve-backlog-reasoning-s128",
                                       "serve-backlog-reasoning"))
    assert mine["lengths"] == theirs["lengths"]
    assert mine["traffic_seed"] == theirs["traffic_seed"] == 1442695040
    assert {**theirs["engine"], "n_slots": 128, "num_blocks": 8601} \
        == mine["engine"]
    assert mine["arrivals"] == {"kind": "backlog", "requests_per_second": 16}


@pytest.mark.parametrize("name", KDA_METRICS + (
    "paged_attn_roofline.by_kind", "moe_grouped_mm_chunk_ms"))
def test_a_device_reader_finds_nothing_on_the_gated_hybrid_off_the_chip(
        bench, gated, name, capsys):
    """No device trace on the CPU, and a trace of no device: ``None``, and
    no raise (what the parent's program gives the driver's traced runs)."""
    reader = _load(os.path.join(BENCH, "metrics", name + ".py"),
                   "bench_metric")
    assert reader.read(gated["record"]) is None
    assert reader.read({**gated["record"], "trace": {"n_devices": 0}}) is None
    capsys.readouterr()


def test_the_events_readers_give_numbers_on_the_gated_hybrid(bench, gated):
    rec, pool = gated["record"], gated["eng"].pool
    read = lambda name: _load(os.path.join(  # noqa: E731
        BENCH, "metrics", name + ".py"), "bench_metric").read(rec)
    assert read("kv_pool_gib") == pytest.approx(pool.bytes_full / 2**30)
    assert read("state_pool_gib") == pytest.approx(
        sum(pool.bytes_state) / 2**30)
    assert 0 < read("slot_occupancy") <= 100


def test_the_kda_counts_at_the_second_shape_are_the_arithmetic(bench):
    """The count functions take the shape from the configuration: 3 layers
    of 64 heads of 128 x 128; a decode row moves 8.5 MB of state a layer
    (twice the other KDA model's) and is bound by it; a decode token at
    context c reads c keys and values of 8 heads of 128 in the ONE
    attention layer, 4,096 B a key."""
    from lib import counts_gdn, counts_kda, counts_moe

    keys = CONFIGS["solar-open2-250b-ep8"]["model"]
    assert counts_gdn.linear_layers(keys) == (3, 64, 128, 128)
    per_token = 64 * (4 * 128 * 2 + 128 * 4 + 4)
    state = 2 * 64 * 128 * 128 * 4
    assert counts_kda.recurrence_bytes(1, 1, 64, 128, 128, itemsize=2) \
        == per_token + state == 98_560 + 8_388_608
    assert counts_kda.recurrence_flops(1, 64, 128, 128) == 7 * 64 * 128 * 128
    assert counts_kda.recurrence_flops(1, 64, 128, 128) / (
        per_token + state) < 1
    kinds = list(keys["layer_types"])
    flops, moved = counts_moe.paged_attention_by_kind(
        [1000], n_full=kinds.count("full_attention"),
        n_window=kinds.count("sliding_attention"), window=None,
        heads=keys["n_heads"], kv_heads=keys["n_kv_heads"],
        head_dim=keys["head_size"], itemsize=2)
    assert (flops, moved) == (4 * 1000 * 64 * 128, 1000 * 4096)
    assert counts_moe.grouped_mm_bytes(40, 4096, 1280, itemsize=2) \
        == 40 * 3 * 4096 * 1280 * 2


# -- 9. start-up accounts for itself (PR 52) -----------------------------------

STARTUP_METRICS = ("engine_build_s", "program_load_s", "program_compile_s")
STARTUP_CELLS = [w["name"] for w in BENCHMARK["workloads"]
                 if MIXES[w["traffic"]]["kind"] in (
                     "serve-large", "serve-long", "serve-long-routed")]


@pytest.mark.parametrize("name", STARTUP_METRICS)
def test_a_startup_metric_has_its_file_and_its_cells(name):
    """In the cells whose record holds ``serve_engine`` (the kinds that
    read ``lib/serving_large.py``'s), and moving ``setup_s``."""
    entry = next(m for m in BENCHMARK["per_layer"] if m["name"] == name)
    assert name + ".py" in METRIC_FILES
    assert entry == {
        "name": name, "unit": "s", "better": "lower",
        "source": "program_counter", "layer": "start-up", "moves": "setup_s",
        "workloads": STARTUP_CELLS}
    assert entry in BENCHMARK["per_layer"][46:49]  # appended, nothing moved
    assert len(STARTUP_CELLS) == 7


@pytest.mark.parametrize("name", STARTUP_METRICS)
def test_a_startup_reader_reads_the_engines_own_account(bench, experts,
                                                        hybrid, name, capsys):
    """Over a rehearsal record: the constructor's seconds, the sum of the
    programs' loads (no more than their first calls took), and what of the
    backend's time was no read of the compile cache.  A tree without the
    fields (the parent's event) reads ``None``."""
    read = _span_reader(name)
    for run in (experts, hybrid):
        ev = run["record"]["serve_engine"]
        value = read(run["record"])
        assert math.isfinite(value) and value >= 0
        if name == "engine_build_s":
            assert value == ev["build_s"] > 0
        else:
            assert set(ev["programs"]) == set(run["eng"].programs)
            assert value <= sum(p["call_s"] for p in ev["programs"].values())
        if name == "program_load_s":
            assert value >= _span_reader("program_compile_s")(
                run["record"]) and value > 0
        old = {k: v for k, v in ev.items()
               if k not in ("build_s", "build_phases", "build_loads",
                            "programs")}
        assert read({**run["record"], "serve_engine": old}) is None
    assert read({"serve_engine": None}) is None
    capsys.readouterr()
