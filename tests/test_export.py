"""AOT export subsystem tests (export/, ISSUE 14 acceptance).

The contract under test, end to end on the CPU sim:

- cold start compiles + serializes (``export.miss`` -> ``export.store``),
  warm start deserializes (``export.hit``) with ZERO train-step XLA
  compiles (asserted via the PR-1 recompile-detection journal events)
  and bitwise-identical step outputs;
- cache keys separate across plans and topologies; env/version drift is
  skipped LOUDLY (``export.stale``) and recompiled, never crashes;
- the serve decode/prefill traces round-trip the same way with
  token-identical output;
- the elastic launcher's workers go cache-first across cohorts;
- the tune-cache JSONL compaction contract (size cap, last-match-wins)
  shared by the export index;
- ``utils.profiling.compiled_cost`` memoizes on the lowered-HLO digest.
"""

import json
import os
import pickle

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import torch_automatic_distributed_neural_network_tpu as tad
from torch_automatic_distributed_neural_network_tpu import cli
from torch_automatic_distributed_neural_network_tpu.export import (
    ExecutableCache,
    executable_key,
)
from torch_automatic_distributed_neural_network_tpu.models import MLP
from torch_automatic_distributed_neural_network_tpu.obs import (
    journal as obs_journal,
)
from torch_automatic_distributed_neural_network_tpu.training import (
    softmax_xent_loss,
)
from torch_automatic_distributed_neural_network_tpu.tune import (
    cache as tune_cache,
)


def toy_batch(seed=0, batch=16, dim=8):
    rng = np.random.RandomState(seed)
    return {
        "x": jnp.asarray(rng.randn(batch, dim), jnp.float32),
        "label": jnp.asarray(rng.randint(0, 10, size=(batch,))),
    }


def make_ad(cache=None, strategy="auto", **kw):
    return tad.AutoDistribute(
        MLP(features=(32, 16, 10)),
        optimizer=optax.sgd(0.1),
        loss_fn=softmax_xent_loss,
        strategy=strategy,
        export_cache=cache,
        **kw,
    )


def train_run(cache, n_steps=3, strategy="auto"):
    """One fresh AutoDistribute trained n_steps against the cache.
    Returns (losses, final_params, journal_records, ad)."""
    j = obs_journal.Journal(path=None)
    with obs_journal.as_default(j):
        ad = make_ad(cache=cache, strategy=strategy)
        state = ad.init(jax.random.key(0), toy_batch())
        losses = []
        for i in range(n_steps):
            state, metrics = ad.step(state, toy_batch(seed=i))
            losses.append(float(metrics["loss"]))
    return losses, jax.device_get(state.params), j.records, ad


def names(records, prefix="export."):
    return [r["name"] for r in records if r["name"].startswith(prefix)]


# -- train step: cold/warm parity, zero warm compiles -------------------------


def test_train_cold_warm_bitwise_parity_and_zero_compiles(tmp_path):
    cache = str(tmp_path / "exe")
    cold_losses, cold_params, cold_rec, cold_ad = train_run(cache)
    assert names(cold_rec)[:2] == ["export.miss", "export.store"]
    assert cold_ad.n_compiles == 1  # the AOT compile, journaled normally
    assert cold_ad._export_info["source"] == "compile"

    warm_losses, warm_params, warm_rec, warm_ad = train_run(cache)
    assert names(warm_rec) == ["export.hit"]
    # the acceptance bar: a warm start performs ZERO XLA train-step
    # compiles — no compile/recompile events, empty compile accounting
    assert warm_ad.n_compiles == 0
    assert warm_ad.recompile_count == 0
    assert not [r for r in warm_rec
                if r["name"] in ("compile", "recompile")
                and r.get("fn") == "train_step"]
    # and the deserialized executable is bit-for-bit the compiled one
    assert cold_losses == warm_losses
    flat_c = jax.tree_util.tree_leaves(cold_params)
    flat_w = jax.tree_util.tree_leaves(warm_params)
    for a, b in zip(flat_c, flat_w):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    hit = next(r for r in warm_rec if r["name"] == "export.hit")
    store = next(r for r in cold_rec if r["name"] == "export.store")
    assert hit["deserialize_s"] < store["compile_s"]
    assert hit["payload_bytes"] == store["payload_bytes"]


def test_export_step_prewarms_a_fresh_autodistribute(tmp_path):
    cache = str(tmp_path / "exe")
    j = obs_journal.Journal(path=None)
    with obs_journal.as_default(j):
        info = make_ad().export_step(jax.random.key(0), toy_batch(),
                                     cache=cache)
    assert info["source"] == "compile"
    assert os.path.isfile(os.path.join(cache, info["key"] + ".aotx"))
    # a different process/object with the same config opens on a hit
    _, _, warm_rec, warm_ad = train_run(cache)
    assert names(warm_rec) == ["export.hit"]
    assert warm_ad.n_compiles == 0
    assert warm_ad._export_info["key"] == info["key"]


def test_export_disabled_by_default_and_off_spec(tmp_path):
    _, _, rec, ad = train_run(cache=None)
    assert not names(rec)  # opt-in: no cache spec, no env -> no events
    assert ad._export_info is None
    with pytest.raises(ValueError, match="disabled"):
        make_ad(cache=False).export_step(jax.random.key(0), toy_batch(),
                                         cache=False)


# -- key separation -----------------------------------------------------------


def test_keys_separate_across_plans_and_batches(tmp_path):
    cache = str(tmp_path / "exe")
    a = make_ad(cache=cache, strategy="dp")
    a.init(jax.random.key(0), toy_batch())
    b = make_ad(cache=cache, strategy="fsdp")
    b.init(jax.random.key(0), toy_batch())
    assert a._export_info["key"] != b._export_info["key"]
    # same plan, different batch shape -> different executable
    c = make_ad(cache=cache, strategy="dp")
    c.init(jax.random.key(0), toy_batch(batch=8))
    assert c._export_info["key"] != a._export_info["key"]
    assert len(ExecutableCache(cache).entries()) == 3


def test_keys_separate_across_topologies():
    topo_a = {"num_devices": 8, "num_hosts": 1, "platform": "tpu",
              "device_kind": "v5p", "num_slices": 1}
    topo_b = dict(topo_a, num_hosts=2)
    topo_c = dict(topo_a, device_kind="v5e")
    program = {"plan": {"strategy": "dp"}, "batch": "f32[16,8]"}
    keys = {executable_key("train_step", "sig0", t, program)
            for t in (topo_a, topo_b, topo_c)}
    assert len(keys) == 3
    assert executable_key("train_step", "sig0", topo_a, program) != \
        executable_key("serve_decode", "sig0", topo_a, program)


# -- stale fallback -----------------------------------------------------------


def _tamper_env_field(cache_dir, field="jax", value="0.0.0-elsewhere"):
    """Rewrite every index record as if it came from another env."""
    index = os.path.join(cache_dir, "index.jsonl")
    lines = []
    with open(index) as f:
        for line in f:
            rec = json.loads(line)
            rec["record"]["env"][field] = value
            lines.append(json.dumps(rec))
    with open(index, "w") as f:
        f.write("\n".join(lines) + "\n")


def test_stale_version_falls_back_loudly_and_recompiles(tmp_path):
    cache = str(tmp_path / "exe")
    cold_losses, _, _, _ = train_run(cache)
    _tamper_env_field(cache, "jax")

    report = ExecutableCache(cache).verify()
    assert len(report) == 1 and not report[0]["live"]
    assert "jax" in report[0]["reason"]

    losses, _, rec, ad = train_run(cache)
    ev = names(rec)
    assert ev[0] == "export.stale"
    assert "export.store" in ev  # recompiled AND overwrote the entry
    stale = next(r for r in rec if r["name"] == "export.stale")
    assert "0.0.0-elsewhere" in stale["reason"]
    assert losses == cold_losses  # the run itself is unharmed
    # the overwrite healed the cache: next start hits again
    _, _, rec2, _ = train_run(cache)
    assert names(rec2) == ["export.hit"]


def test_torn_payload_is_stale_not_fatal(tmp_path):
    cache = str(tmp_path / "exe")
    train_run(cache)
    exe = ExecutableCache(cache)
    (key, rec), = exe.entries().items()
    with open(exe.payload_path(key), "wb") as f:
        f.write(b"\x80\x04 not a pickle")
    losses, _, recs, _ = train_run(cache)
    ev = names(recs)
    assert "export.stale" in ev and "export.store" in ev
    assert losses  # trained through the recompile


def test_missing_payload_is_stale(tmp_path):
    cache = str(tmp_path / "exe")
    train_run(cache)
    exe = ExecutableCache(cache)
    (key, _), = exe.entries().items()
    os.remove(exe.payload_path(key))
    report = exe.verify()
    assert not report[0]["live"]
    assert "missing" in report[0]["reason"]


# -- serve traces -------------------------------------------------------------


def serve_tokens(cache, model, variables):
    from torch_automatic_distributed_neural_network_tpu.inference.serve \
        import ServeEngine

    j = obs_journal.Journal(path=None)
    with obs_journal.as_default(j):
        eng = ServeEngine(model, variables, n_slots=4, max_len=64,
                          block_size=8, journal=j, export_cache=cache)
        eng.submit([5, 6, 7, 8, 9], max_new_tokens=8, eos_id=None)
        eng.submit([11, 12, 13], max_new_tokens=5, eos_id=None)
        done = eng.run()
    return [r.out_tokens for r in done], j.records, eng


def test_serve_cold_warm_token_parity(tmp_path):
    from torch_automatic_distributed_neural_network_tpu.models import GPT2

    cache = str(tmp_path / "exe")
    model = GPT2("test", vocab_size=128, max_seq_len=64)
    variables = model.init(jax.random.key(1), jnp.zeros((1, 8), jnp.int32))

    cold_toks, cold_rec, cold_eng = serve_tokens(cache, model, variables)
    assert sorted(names(cold_rec)) == ["export.miss", "export.miss",
                                       "export.store", "export.store"]
    # the chunk program of an engine whose chunks carry the decode rows
    assert {i["kind"] for i in cold_eng.export_info} == \
        {"serve_decode", "serve_fused"}

    warm_toks, warm_rec, warm_eng = serve_tokens(cache, model, variables)
    assert names(warm_rec) == ["export.hit", "export.hit"]
    assert all(i["source"] == "hit" for i in warm_eng.export_info)
    assert cold_toks == warm_toks


# -- launcher: warm restart skips the step compile ----------------------------


@pytest.mark.slow
def test_launcher_second_run_zero_step_compiles(tmp_path):
    from torch_automatic_distributed_neural_network_tpu.training import (
        launch,
    )

    cache = str(tmp_path / "exe")

    def run(d):
        cfg = launch.LaunchConfig(
            launch_dir=str(tmp_path / d), hosts=1, local_devices=4,
            steps=2, ckpt_every=2, seed=0, max_restarts=1,
            heartbeat_interval_s=0.25, export_cache=cache)
        out = launch.Launcher(cfg).run()
        assert out["ok"], out
        host0 = os.path.join(str(tmp_path / d), "journal_host0.jsonl")
        return out, obs_journal.Journal.read(host0)

    first, rec1 = run("first")
    assert "export.store" in names(rec1)
    second, rec2 = run("second")
    # warm cohort: deserialized step, zero train-step XLA compiles
    # (the PR-1 recompile-detection events are the assertion mechanism)
    assert "export.hit" in names(rec2)
    assert not [r for r in rec2
                if r["name"] in ("compile", "recompile")
                and r.get("fn") == "train_step"]
    assert first["losses"] == second["losses"]  # and bitwise parity


# -- CLI ----------------------------------------------------------------------


def test_cli_export_json_smoke(tmp_path, capsys):
    cache = str(tmp_path / "exe")
    argv = ["export", "--family", "mlp", "--size", "32,16,10", "--seq", "4",
            "--batch", "8", "--strategy", "dp", "--cache", cache, "--json"]
    assert cli.main(argv) == 0
    cold = [json.loads(ln) for ln in
            capsys.readouterr().out.strip().splitlines()]
    assert cold[0]["kind"] == "train_step"
    assert cold[0]["source"] == "compile"

    assert cli.main(argv) == 0
    warm = [json.loads(ln) for ln in
            capsys.readouterr().out.strip().splitlines()]
    assert warm[0]["source"] == "hit"
    assert warm[0]["key"] == cold[0]["key"]

    assert cli.main(["export", "--verify", "--cache", cache,
                     "--json"]) == 0
    ver = json.loads(capsys.readouterr().out.strip())
    assert ver["cache"] == cache
    assert [e["live"] for e in ver["entries"]] == [True]


def test_cli_export_serve_and_report_render(tmp_path, capsys):
    from torch_automatic_distributed_neural_network_tpu.obs import report

    cache = str(tmp_path / "exe")
    jpath = str(tmp_path / "journal.jsonl")
    argv = ["export", "--family", "gpt2", "--size", "test", "--serve",
            "--batch", "8", "--seq", "16", "--strategy", "dp",
            "--cache", cache, "--journal", jpath, "--json"]
    assert cli.main(argv) == 0
    out = [json.loads(ln) for ln in
           capsys.readouterr().out.strip().splitlines()]
    assert {r["kind"] for r in out} == {"train_step", "serve_decode",
                                        "serve_fused"}
    rep = report.generate(jpath)
    assert rep["export"]["stores"] == 3
    text = report.format_report(rep)
    assert "export cache" in text


# -- shared JSONL compaction (tune cache + export index) ----------------------


def test_tune_cache_size_cap_compacts(tmp_path):
    path = str(tmp_path / "tune_cache.jsonl")
    # many rewrites of few keys: compaction must keep ONLY the latest
    # record per key, and lookup must answer identically before/after
    for i in range(200):
        tune_cache.store(f"key{i % 4}", {"winner": i}, path=path,
                         max_bytes=0)
    before = {k: tune_cache.lookup(f"key{k}", path=path) for k in range(4)}
    stats = tune_cache.compact_jsonl(path)
    assert stats["kept"] == 4 and stats["dropped"] == 196
    assert stats["after_bytes"] < stats["before_bytes"]
    for k in range(4):
        assert tune_cache.lookup(f"key{k}", path=path) == before[k]
    # the cap sheds oldest-first when dedup alone is not enough
    tune_cache.compact_jsonl(path, max_bytes=80)
    assert os.path.getsize(path) <= 80
    assert tune_cache.lookup("key3", path=path) == before[3]


def test_store_triggers_compaction_over_cap(tmp_path):
    path = str(tmp_path / "tune_cache.jsonl")
    for i in range(50):
        tune_cache.store("hot", {"winner": i}, path=path, max_bytes=500)
    assert os.path.getsize(path) < 500
    assert tune_cache.lookup("hot", path=path) == {"winner": 49}


def test_export_index_compaction_deletes_orphan_payloads(tmp_path):
    cache = ExecutableCache(str(tmp_path / "exe"), max_index_bytes=0)
    os.makedirs(cache.root, exist_ok=True)
    cache.put_record("k1", {"kind": "train_step", "file": "k1.aotx",
                            "env": {}})
    with open(cache.payload_path("k1"), "wb") as f:
        f.write(pickle.dumps("payload"))
    with open(cache.payload_path("orphan"), "wb") as f:
        f.write(b"dead")  # no index record points here
    stats = cache.compact()
    assert stats["orphan_payloads_removed"] == 1
    assert os.path.isfile(cache.payload_path("k1"))
    assert not os.path.isfile(cache.payload_path("orphan"))


# -- cost-analysis memoization ------------------------------------------------


def test_compiled_cost_memoizes_on_hlo_digest(tmp_path, monkeypatch):
    from torch_automatic_distributed_neural_network_tpu.utils import (
        profiling,
    )

    monkeypatch.setenv("TADNN_EXPORT_CACHE", str(tmp_path / "exe"))
    profiling._cost_memo.clear()
    fn = jax.jit(lambda x: (x @ x.T).sum())
    x = jnp.ones((8, 8), jnp.float32)
    j = obs_journal.Journal(path=None)
    with obs_journal.as_default(j):
        first = profiling.compiled_cost(fn, x)
        second = profiling.compiled_cost(fn, x)  # in-process memo
        profiling._cost_memo.clear()
        third = profiling.compiled_cost(fn, x)  # disk tier
    assert "error" not in first
    assert first == second == third
    tiers = [r["tier"] for r in j.records
             if r["name"] == "cost_analysis.cached"]
    assert tiers == ["memory", "disk"]
    # only ONE real compile paid across the three calls
    compiles = [r for r in j.records if r["name"] == "compile.end"
                or (r["name"] == "compile"
                    and r.get("fn") == "aot_cost_analysis")]
    assert len(compiles) <= 2  # span start/end records of one compile


def test_compiled_cost_failure_not_cached(tmp_path, monkeypatch):
    from torch_automatic_distributed_neural_network_tpu.utils import (
        profiling,
    )

    monkeypatch.setenv("TADNN_EXPORT_CACHE", str(tmp_path / "exe"))
    profiling._cost_memo.clear()

    class Boom:
        def lower(self, *a, **k):
            raise RuntimeError("no lowering today")

    j = obs_journal.Journal(path=None)
    with obs_journal.as_default(j):
        out = profiling.compiled_cost(Boom())
        out2 = profiling.compiled_cost(Boom())
    assert "no lowering today" in out["error"]
    assert "no lowering today" in out2["error"]
    assert not profiling._cost_memo
    assert not [r for r in j.records
                if r["name"] == "cost_analysis.cached"]


# -- GC by last-hit age (tadnn export --gc) -----------------------------------


def _entry(cache_dir):
    c = ExecutableCache(cache_dir)
    (key, rec), = c.entries().items()
    return c, key, rec


def test_gc_drops_cold_entries_and_keeps_fresh(tmp_path):
    cache = str(tmp_path / "exe")
    j = obs_journal.Journal(path=None)
    with obs_journal.as_default(j):
        make_ad().export_step(jax.random.key(0), toy_batch(), cache=cache)
        c, key, rec = _entry(cache)
        payload = c.payload_path(key)
        assert os.path.isfile(payload)
        # fresh entry survives any sane window ...
        assert c.gc(max_age_s=3600.0)["dropped"] == 0
        # ... and a zero window reaps it: payload gone, index rewritten
        stats = c.gc(max_age_s=0.0)
    assert stats["dropped"] == 1 and stats["kept"] == 0
    assert stats["payload_bytes_freed"] > 0
    assert not os.path.isfile(payload)
    assert c.entries() == {}
    gcs = [r for r in j.records if r["name"] == "export.gc"]
    assert len(gcs) == 2 and gcs[-1]["dropped"] == 1


def test_hit_refreshes_last_hit_so_hot_entries_survive_gc(tmp_path):
    cache = str(tmp_path / "exe")
    train_run(cache)  # cold: compile + store
    c, key, rec = _entry(cache)
    # backdate the store far past any retention window
    rec = dict(rec)
    rec["created"] = 1.0
    rec.pop("last_hit", None)
    c.put_record(key, rec)
    # a warm run hits the entry, and the hit must refresh last_hit
    _, _, warm_rec, _ = train_run(cache)
    assert names(warm_rec) == ["export.hit"]
    refreshed = c.entries()[key]
    assert refreshed.get("last_hit", 0.0) > 1.0
    j = obs_journal.Journal(path=None)
    with obs_journal.as_default(j):
        assert c.gc(max_age_s=3600.0)["dropped"] == 0  # hot: kept
    assert os.path.isfile(c.payload_path(key))
    # without the touch the same window would have reaped it
    stale = dict(refreshed)
    stale["created"] = 1.0
    stale["last_hit"] = 1.0
    c.put_record(key, stale)
    with obs_journal.as_default(j):
        assert c.gc(max_age_s=3600.0)["dropped"] == 1


def test_cli_export_gc(tmp_path, capsys):
    cache = str(tmp_path / "exe")
    argv = ["export", "--family", "mlp", "--size", "32,16,10", "--seq", "4",
            "--batch", "8", "--strategy", "dp", "--cache", cache, "--json"]
    assert cli.main(argv) == 0
    capsys.readouterr()
    # retention window large: nothing dropped, entry still verifies live
    assert cli.main(["export", "--gc", "--max-age-days", "30",
                     "--cache", cache, "--json"]) == 0
    out = json.loads(capsys.readouterr().out.strip())
    assert out["dropped"] == 0 and out["kept"] == 1
    # zero-day retention: reaped via the CLI path
    assert cli.main(["export", "--gc", "--max-age-days", "0",
                     "--cache", cache, "--json"]) == 0
    out = json.loads(capsys.readouterr().out.strip())
    assert out["dropped"] == 1 and out["payload_bytes_freed"] > 0
    assert cli.main(["export", "--verify", "--cache", cache,
                     "--json"]) == 0
    ver = json.loads(capsys.readouterr().out.strip())
    assert ver["entries"] == []
