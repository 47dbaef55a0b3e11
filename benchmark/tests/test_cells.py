"""Every ``workloads`` entry resolves to its files; names and units hold
only what the driver allows; no cell, configuration or mix is named in code."""
import json
import os
import re

import pytest

from lib import harness

BENCH = harness.load_json(os.path.join(harness.REPO, "BENCHMARK.json"))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def test_top_level_keys_are_exactly_the_contract():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    assert BENCH["paths"] == ["benchmark"]
    assert all(isinstance(w, str) and 1 <= len(w) <= 200
               for w in BENCH["command"])


@pytest.mark.parametrize("w", BENCH["workloads"], ids=lambda w: w["name"])
def test_cell_resolves_to_its_files(w):
    assert set(w) == {"name", "config", "traffic", "chips", "why"}
    assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
    cell = harness.Cell(w["name"], BENCH)
    assert cell.config["name"] == w["config"]
    assert os.path.isfile(os.path.join(
        harness.BENCH_DIR, "generators", cell.mix["kind"] + ".py"))
    assert os.path.isfile(os.path.join(harness.REPO,
                                       cell.config["reference"]))
    assert any(m["name"] == "setup_s" for m in cell.metrics("end_to_end"))
    assert len(cell.metrics("end_to_end")) >= 2
    assert cell.metrics("per_layer")
    for m in cell.metrics("per_layer"):
        assert os.path.isfile(os.path.join(
            harness.BENCH_DIR, "metrics", m["name"] + ".py")), m["name"]
        # a per-layer metric moves an end-to-end metric this cell reports
        assert m["moves"] in {e["name"] for e in cell.metrics("end_to_end")}
    gen = cell.generator()
    assert callable(gen.run) and callable(gen.control)
    for check, body in cell.limits.items():
        if isinstance(body, dict) and "limit" in body:
            assert body["limit"] < 1e6, f"{check}: a placeholder limit"


def test_names_units_and_keys():
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in BENCH[group]:
            assert NAME.match(e["name"]), e["name"]
            names.append((group in ("end_to_end", "per_layer"), e["name"]))
    assert len(names) == len(set(names))
    for w in BENCH["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
    for e in BENCH["end_to_end"]:
        assert set(e) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert e["source"] in ("host_clock", "device_trace")
        assert 0 < e["bound"] <= 0.1
    for e in BENCH["per_layer"]:
        assert set(e) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert e["source"] in SOURCES
    for e in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(e["unit"]), e["unit"]
        assert e["better"] in ("lower", "higher")
    cells = {w["name"] for w in BENCH["workloads"]}
    for e in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert set(e.get("workloads", [])) <= cells
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("benchmark/")
        doc = json.load(open(os.path.join(harness.REPO, c["file"])))
        assert sorted(doc["reduced"]) == sorted(c["reduced"])
        assert doc["source"] == c["source"]
        assert any(w["config"] == c["name"] for w in BENCH["workloads"])
    assert len(json.dumps(BENCH)) < 64 * 1024


def test_no_cell_config_or_mix_is_named_in_code():
    words = ({w["name"] for w in BENCH["workloads"]}
             | {c["name"] for c in BENCH["configs"]}
             | {w["traffic"] for w in BENCH["workloads"]})
    for root, _dirs, files in os.walk(harness.BENCH_DIR):
        if os.sep + "tests" in root or "__pycache__" in root:
            continue
        for f in files:
            if f.endswith(".py"):
                text = open(os.path.join(root, f)).read()
                for w in words:
                    assert w not in text, f"{w!r} in {os.path.join(root, f)}"


def test_files_are_named_from_allowed_characters():
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for root, dirs, files in os.walk(harness.BENCH_DIR):
        dirs[:] = [d for d in dirs if d not in ("__pycache__", ".trace",
                                                ".pytest_cache")]
        for f in files:
            rel = os.path.relpath(os.path.join(root, f), harness.REPO)
            assert ok.match(rel) and len(rel) <= 200, rel
