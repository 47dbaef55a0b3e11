"""Telemetry contract tests (ISSUE 20): the event schema registry
(obs/schema.py), the JL001–JL007 producer/consumer lint
(analysis/journal_lint.py), runtime enforcement
(``Journal(validate=True)`` / ``TADNN_JOURNAL_VALIDATE``), the
journal-file auditor, and round-trip validation of journals produced
by live smoke runs.  The lint self-validates via the planted-mutation
harness, PR-19 style."""

import json

import pytest

from torch_automatic_distributed_neural_network_tpu import analysis
from torch_automatic_distributed_neural_network_tpu.analysis import (
    journal_lint,
)
from torch_automatic_distributed_neural_network_tpu.obs import schema
from torch_automatic_distributed_neural_network_tpu.obs.journal import (
    Journal,
)


# -- registry ----------------------------------------------------------------

def test_rules_table_has_all_jl_codes():
    for code in ("JL001", "JL002", "JL003", "JL004", "JL005", "JL006",
                 "JL007"):
        assert code in analysis.RULES
        assert analysis.RULES[code].layer == "journal"


def test_every_typespec_in_registry_is_well_formed():
    # check_value raises ValueError on an unknown spec string — probing
    # every declared spec proves the registry parses end to end
    for s in schema.REGISTRY.values():
        for spec in s.fields().values():
            schema.check_value(None, spec)


def test_alias_resolution_and_names_for():
    assert schema.canonical("serve.request") == "serve.request_done"
    assert schema.canonical("serve.step") == "serve.step"
    assert schema.names_for("serve.request_done") == (
        "serve.request_done", "serve.request")
    # an alias resolves to the canonical schema
    assert schema.get("serve.request") is schema.get("serve.request_done")
    assert schema.get("no.such.kind") is None


def test_registry_markdown_lists_kinds_and_aliases():
    md = schema.registry_markdown()
    assert "| `serve.request_done` | 3 |" in md
    assert "`serve.request`" in md  # the alias note
    assert "`gateway.replan`" in md


def test_check_value_type_grammar():
    assert schema.check_value(3, "int")
    assert not schema.check_value(True, "int")  # bool is not an int
    assert schema.check_value(3, "float")  # JSON loses int/float
    assert not schema.check_value("3", "float")
    assert schema.check_value(None, "str?")
    assert not schema.check_value(None, "str")
    assert schema.check_value([1], "list")
    assert schema.check_value({}, "dict")
    assert schema.check_value(object(), "any")
    with pytest.raises(ValueError):
        schema.check_value(1, "complex128")


# -- record validation (the runtime half) ------------------------------------

def _rec(name, **fields):
    return {"kind": "event", "name": name, "t": 0.0, "wall": 0.0,
            "depth": 0, **fields}


def test_validate_record_clean():
    assert schema.validate_record(
        _rec("serve.preempt", rid=3, n_regenerate=2)) == []


def test_validate_record_unknown_kind_jl001():
    codes = [c for c, _ in schema.validate_record(_rec("serve.bogus"))]
    assert codes == ["JL001"]


def test_validate_record_missing_required_jl002():
    codes = [c for c, _ in schema.validate_record(
        _rec("serve.preempt", rid=3))]
    assert codes == ["JL002"]


def test_validate_record_type_mismatch_jl003():
    codes = [c for c, _ in schema.validate_record(
        _rec("serve.preempt", rid="three", n_regenerate=2))]
    assert codes == ["JL003"]


def test_validate_record_undeclared_field_jl004():
    codes = [c for c, _ in schema.validate_record(
        _rec("serve.preempt", rid=3, n_regenerate=2, slot=1))]
    assert codes == ["JL004"]


def test_validate_record_open_schema_tolerates_extras():
    assert schema.validate_record(
        _rec("tune.decision", key="k", source="measured",
             anything_else={"deep": 1})) == []


def test_validate_record_deprecated_alias_jl007():
    codes = [c for c, _ in schema.validate_record(
        _rec("serve.request", rid=1, n_prompt=1, n_new=1, queue_s=0.0,
             total_s=0.1, tokens_per_s=10.0, preempted=0, ttft_s=0.05,
             itl_s=[]))]
    assert codes == ["JL007"]


def test_validate_record_kind_collision_is_payload():
    # payload fields named ``kind`` overwrite the journal's own
    # event/span discriminator (the established on-disk format); the
    # schema must check them as payload, not strip them as base fields
    rec = _rec("serve.prefix", rid=1, n_blocks=2)
    rec["kind"] = "publish"
    assert schema.validate_record(rec) == []
    rec["kind"] = 7  # and still type-check them
    assert [c for c, _ in schema.validate_record(rec)] == ["JL003"]


# -- static lint: per-rule fixtures ------------------------------------------

def _lint(src, **kw):
    findings, _ = journal_lint.lint_sources([("<t>", src)], **kw)
    return [f.code for f in findings]


def test_jl001_unknown_kind_positive_and_negative():
    assert _lint('def f(j): j.event("serve.bogus", x=1)') == ["JL001"]
    assert _lint(
        'def f(j): j.event("serve.preempt", rid=1, n_regenerate=2)') == []


def test_jl002_missing_required_field():
    assert _lint('def f(j): j.event("serve.preempt", rid=1)') == ["JL002"]
    # a **splat may supply anything: the site is not checkable
    assert _lint(
        'def f(j, kw): j.event("serve.preempt", rid=1, **kw)') == []


def test_jl003_literal_type_mismatch():
    assert _lint('def f(j): j.event("serve.preempt", rid="x", '
                 'n_regenerate=2)') == ["JL003"]


def test_jl004_undeclared_field_closed_vs_open():
    assert _lint('def f(j): j.event("serve.preempt", rid=1, '
                 'n_regenerate=2, extra=1)') == ["JL004"]
    assert _lint('def f(j): j.event("tune.decision", key="k", '
                 'source="s", extra=1)') == []


def test_jl005_dead_optional_field_full_scan_only():
    src = ('def f(j): j.event("gateway.hedge", kind="fire", rid=1, '
           'primary="a", replica="b")')
    assert _lint(src, full_scan=True) == ["JL005"]  # winner never emitted
    assert _lint(src, full_scan=False) == []


def test_jl006_consumer_reads_undeclared_field():
    src = (
        "def f(events):\n"
        '    xs = [e for e in events if e.get("name") == "serve.step"]\n'
        '    return [e.get("occupancyy") for e in xs]\n')
    assert _lint(src) == ["JL006"]
    assert _lint(src.replace("occupancyy", "occupancy")) == []


def test_jl006_if_chain_and_name_binding():
    src = (
        "def f(rec):\n"
        '    name = rec.get("name")\n'
        '    if name == "serve.speculate":\n'
        '        return rec.get("drafted"), rec.get("acceptedd")\n')
    assert _lint(src) == ["JL006"]


def test_jl007_emission_under_alias():
    src = ('def f(j): j.event("serve.request", rid=1, n_prompt=1, '
           'n_new=1, queue_s=0.0, total_s=0.1, tokens_per_s=1.0, '
           'preempted=0, ttft_s=0.1, itl_s=[])')
    assert _lint(src) == ["JL007"]


def test_jl007_consumer_hardcoded_alias_vs_names_for():
    hard = ('def f(events):\n'
            '    return [e for e in events if e.get("name") in '
            '("serve.request", "serve.request_done")]\n')
    assert _lint(hard) == ["JL007"]
    sanctioned = (
        'from torch_automatic_distributed_neural_network_tpu.obs.schema '
        'import names_for\n'
        'def f(events):\n'
        '    return [e for e in events if e.get("name") in '
        'names_for("serve.request_done")]\n')
    assert _lint(sanctioned) == []


def test_span_attachment_fields_are_resolved():
    src = ("def f(j):\n"
           '    with j.span("ckpt.wait") as rec:\n'
           '        rec["sharded"] = True\n')
    assert _lint(src) == []
    assert _lint(src.replace('"sharded"', '"shardedd"')) == ["JL004"]


def test_primitive_name_comparisons_are_not_name_tests():
    # jaxpr walkers compare `name` against primitive strings; none are
    # registry kinds, so no JL001 and no read attribution
    src = ("def f(eqn, name):\n"
           '    if name == "convert_element_type":\n'
           '        return eqn.get("params")\n')
    assert _lint(src) == []


def test_suppression_comment_with_reason():
    src = ('def f(j):\n'
           '    j.event("serve.bogus")  '
           '# tadnn: lint-ok(JL001) synthetic fixture kind\n')
    assert _lint(src) == []


# -- the mutation harness (self-validation) ----------------------------------

def test_mutation_harness_clean_and_planted_drifts():
    assert len(journal_lint.MUTATIONS) >= 8
    assert {m[2] for m in journal_lint.MUTATIONS} == {
        "JL001", "JL002", "JL003", "JL004", "JL005", "JL006", "JL007"}
    assert journal_lint.self_check() == []


# -- the repo-wide gate ------------------------------------------------------

def test_repo_journal_contract_is_clean():
    """The standing gate: zero findings over the package and 100%
    registry coverage of statically-discovered emission kinds (the
    ``tadnn check --journal --strict`` CI leg, as a tier-1 test)."""
    findings, stats = journal_lint.lint_paths()
    assert findings == [], "\n".join(f.format() for f in findings)
    assert stats["coverage"] == 1.0
    assert stats["kinds_emitted"] > 80
    assert stats["sites"] > 100


# -- runtime enforcement -----------------------------------------------------

def test_journal_validate_raises_on_contract_violation():
    j = Journal(validate=True)
    j.event("serve.preempt", rid=1, n_regenerate=2)  # clean
    with pytest.raises(schema.JournalContractError, match="JL002"):
        j.event("serve.preempt", rid=1)
    with pytest.raises(schema.JournalContractError, match="JL001"):
        j.event("serve.bogus")
    with pytest.raises(schema.JournalContractError, match="JL003"):
        j.event("serve.preempt", rid="x", n_regenerate=2)


def test_journal_validate_spans_checked_at_exit():
    j = Journal(validate=True)
    with j.span("ckpt.wait") as rec:
        rec["sharded"] = True
    with pytest.raises(schema.JournalContractError, match="JL004"):
        with j.span("ckpt.wait") as rec:
            rec["undeclared_field"] = 1


def test_journal_validate_env_gate(monkeypatch):
    monkeypatch.setenv("TADNN_JOURNAL_VALIDATE", "1")
    j = Journal()
    assert j.validate
    with pytest.raises(schema.JournalContractError):
        j.event("serve.bogus")
    monkeypatch.setenv("TADNN_JOURNAL_VALIDATE", "0")
    assert not Journal().validate
    # explicit argument beats the environment
    assert Journal(validate=True).validate


def test_journal_validate_off_by_default():
    j = Journal()
    assert not j.validate
    j.event("whatever.goes")  # un-validated journals accept anything


# -- journal-file audit ------------------------------------------------------

def test_audit_journal_flags_bad_records(tmp_path):
    p = tmp_path / "j.jsonl"
    good = _rec("serve.preempt", rid=1, n_regenerate=2)
    bad = _rec("serve.preempt", rid=1)  # missing n_regenerate
    unknown = _rec("serve.bogus")
    p.write_text(json.dumps(good) + "\n" + json.dumps(bad) + "\n"
                 + json.dumps(unknown) + "\n" + '{"torn...\n')
    findings, stats = journal_lint.audit_journal(str(p))
    assert stats == {"records": 3, "torn": 1}
    assert [f.code for f in findings] == ["JL002", "JL001"]
    assert findings[0].where.endswith(":2")
    assert findings[1].where.endswith(":3")


# -- consumer alias satellite ------------------------------------------------

def test_live_aggregator_accepts_pre_rename_records():
    from torch_automatic_distributed_neural_network_tpu.obs.live import (
        LiveAggregator,
    )

    agg = LiveAggregator(window_s=10.0, clock=None)
    # one record under the old name, one under the new: both must fold
    for t, name in ((1.0, "serve.request"), (2.0, "serve.request_done")):
        agg.add({"kind": "event", "name": name, "t": t, "wall": t,
                 "depth": 0, "rid": 1, "n_prompt": 4, "n_new": 8,
                 "queue_s": 0.0, "total_s": 0.5, "tokens_per_s": 16.0,
                 "preempted": 0, "ttft_s": 0.1, "itl_s": [0.05]})
    agg.flush()
    assert agg.totals["n_done"] == 2


# -- round trips over live smoke journals ------------------------------------

def test_gateway_chaos_round_trip_validates(tmp_path):
    """A live gateway chaos run's journal must audit clean against the
    registry — the in-process half of the CI smoke round trip."""
    from torch_automatic_distributed_neural_network_tpu.inference \
        .gateway.chaos import chaos_smoke

    path = str(tmp_path / "chaos.journal.jsonl")
    out = chaos_smoke(journal_path=path, scale="light", max_replicas=4)
    assert out["ok"]
    findings, stats = journal_lint.audit_journal(path)
    assert stats["records"] > 100
    assert findings == [], "\n".join(f.format() for f in findings)


@pytest.mark.slow
def test_serve_smoke_round_trip_validates(tmp_path, monkeypatch):
    from torch_automatic_distributed_neural_network_tpu import cli

    monkeypatch.setenv("TADNN_JOURNAL_VALIDATE", "1")
    path = str(tmp_path / "serve.journal.jsonl")
    rc = cli.main(["serve", "--smoke", "--journal", path])
    assert rc == 0
    findings, stats = journal_lint.audit_journal(path)
    assert stats["records"] > 10
    assert findings == [], "\n".join(f.format() for f in findings)


@pytest.mark.slow
def test_launch_smoke_round_trip_validates(tmp_path, monkeypatch):
    from torch_automatic_distributed_neural_network_tpu import cli

    monkeypatch.setenv("TADNN_JOURNAL_VALIDATE", "1")
    d = tmp_path / "launch-smoke"
    rc = cli.main(["launch", "--launch-dir", str(d), "--hosts", "2",
                   "--local-devices", "2", "--steps", "4",
                   "--ckpt-every", "2", "--smoke", "--json"])
    assert rc == 0
    merged = sorted(d.glob("*/journal.merged.jsonl"))
    assert merged
    for m in merged:
        findings, _ = journal_lint.audit_journal(str(m))
        assert findings == [], "\n".join(f.format() for f in findings)
