"""``ServeEngine`` itself over the tiny decoder-hybrid-decoder
(``phi4_flash_tiny.py``): each served token the first choice of
``benchmark/reference/phi4_flash.py`` at its position (tolerance:
``test_phi4_flash_reference.py``), and what breaks it."""

from __future__ import annotations

import json

import jax.numpy as jnp
import pytest

from torch_automatic_distributed_neural_network_tpu.inference.serve import (
    ServeEngine,
)
from torch_automatic_distributed_neural_network_tpu.models.transformer_core import (
    TransformerConfig,
)
from torch_automatic_distributed_neural_network_tpu.obs.journal import Journal

from phi4_flash_tiny import (
    ATOL,
    BS,
    CHUNK,
    _engine,
    _highest,  # noqa: F401
    KEYS,
    _model,
    _params,
    _regret,
    _tokens,
)

pytestmark = pytest.mark.usefixtures("_highest")

# prompts of one chunk, of several (the state and the tail cross a chunk
# boundary), past the ring (window 12, chunk 8, pages of 4: a ring of 6 pages,
# 24 tokens, wraps under the 41- and the 30-token prompts and under the
# decoding of every long answer), and six requests over three slots (a slot's
# state row is reused, and starts from zeros)
SHAPES = [(5, 20), (23, 30), (14, 17), (30, 8), (3, 3), (41, 40)]
SERVED = {"reserve": {},
          "optimistic": {"admission": "optimistic"},
          "dense": {"attention_impl": "dense"}}


def _serve(flat, journal=None, shapes=SHAPES, keys=KEYS, **kw):
    eng = _engine(flat, journal, keys, **kw)
    reqs = [eng.submit([int(t) for t in _tokens(n, 10 + i)], max_new_tokens=m)
            for i, (n, m) in enumerate(shapes)]
    eng.run()
    eng.scheduler.check_invariants()
    return eng, reqs


@pytest.mark.parametrize("option", sorted(SERVED))
def test_engine_serves_the_references_first_choice(option, tmp_path):
    flat = _params()
    journal = Journal(None, host0_only=False)
    eng, reqs = _serve(flat, journal, **SERVED[option])
    for r, (n, m) in zip(reqs, SHAPES):
        assert len(r.out_tokens) == m
        assert _regret(flat, r) <= ATOL, (option, n, m)
    steps = journal.named("serve.step")
    assert sum(s.get("fused", 0) for s in steps) > 3
    # the state rows a call's step kernels read and wrote: its decode rows
    # over the two state-space layers
    counted = [s["state_rows"] for s in steps if "state_rows" in s]
    assert counted and all(n % 2 == 0 and 0 < n <= 6 for n in counted)
    # the rows that ran the self-decoder and the cross-decoder, read off
    # the programs' own walks as they are traced: a step 3 and 3, a chunk
    # that carries a step 8 + 3 and 1 + 3
    reads = [s["read"] for s in steps if s.get("read")]
    assert {(r["self_rows"], r["cross_rows"]) for r in reads
            if r["programs"] == 1} == {(3, 3), (CHUNK + 3, 4)}
    if option != "reserve":
        return
    ev = journal.named("serve.engine")[-1]
    assert ev["layer_kinds"] == KEYS["layer_types"]
    assert ev["attention_form"] == "differential"
    assert (ev["cross_start"], ev["paged_sets"], ev["shared_readers"]) \
        == (4, 2, 2)
    assert ev["linear_mixer"] is None
    assert (ev["state_bytes_linear"], ev["conv_bytes_linear"]) \
        == eng.pool.bytes_state == (2 * 4 * 8 * 96 * 4, 2 * 4 * 3 * 96 * 4)
    # pages for max_len are the ONE full layer's, whoever reads them
    assert ev["kv_bytes_full"] == eng.pool.bytes_full == 73 * BS * 48 * 4
    assert ev["kv_bytes_window"] == eng.pool.bytes_window \
        == (3 * 6 + 1) * BS * 48 * 4
    from torch_automatic_distributed_neural_network_tpu.obs import (
        report as obs_report,
    )

    path = tmp_path / "journal.jsonl"
    path.write_text("".join(json.dumps(r) + "\n" for r in journal.records))
    text = obs_report.format_report(obs_report.generate(str(path)))
    assert "cross-decoder from layer 4: 2 paged sets for 4 attention" in text
    assert "read.cross_rows / read.self_rows" in text
    assert "attention: differential" in text
    assert "(2 state-space layers)" in text


def test_the_rows_a_layer_took_are_read_off_the_walk():
    """``serve.step``'s ``self_rows`` / ``cross_rows`` are what ``_walk``
    noted while the program was traced: a ``narrow`` that narrows nothing
    reads as every row through every layer, whatever the program's name."""
    import jax
    import jax.numpy as jnp

    from torch_automatic_distributed_neural_network_tpu.inference import (
        serve,
    )

    programs = serve.programs
    cfg = _model().cfg
    assert cfg.cross_start == 4
    params = {f"layers_{i}": {} for i in range(cfg.n_layers)}
    kv = {"k": [None] * cfg.n_layers, "v": [None] * cfg.n_layers}

    def layer_fn(*_key):
        return lambda lp, k, v, x, extra, shared, carried, memory, depth: (
            x, k, v, {}, carried, memory)

    def program(narrow):
        return jax.jit(lambda x: programs._walk(
            cfg, params, kv, x, layer_fn, {}, narrow=narrow)[0])

    x = jax.ShapeDtypeStruct((1, CHUNK + 3, 48), jnp.float32)
    last_and_steps = lambda rows: None if rows is None else rows[:, -4:]
    assert programs.rows_walked(program(last_and_steps), (x,)) \
        == [CHUNK + 3] * 4 + [4] * 4
    assert programs.rows_walked(program(lambda rows: rows), (x,)) \
        == [CHUNK + 3] * 8
    assert programs.rows_walked(program(None), (x,)) == [CHUNK + 3] * 8
    eng = _engine(_params())
    assert eng._program_rows == {"step": (3, 3),
                                 "chunk_and_step": (CHUNK + 3, 4)}


PLAIN = {**KEYS, "diff_attention": False}


def test_a_cross_layer_without_differential_attention():
    """``shared_attention`` is no part of ``diff_attention``: with plain
    grouped attention the full forward hands layer 3's keys and values to
    the cross layers too, and the engine serves that forward's first
    choice at every position."""
    import jax
    import numpy as np

    model = _model(PLAIN)
    toks = _tokens(31, 5)
    variables = model.init(jax.random.key(7), toks[None, :8])
    eng = ServeEngine(model, variables, n_slots=2, max_len=64, block_size=BS,
                      prefill_chunk=CHUNK, cache_dtype=jnp.float32,
                      export_cache=False)
    req = eng.submit([int(t) for t in toks[:19]], max_new_tokens=12)
    eng.run()
    seq = np.asarray(req.prompt + req.out_tokens)
    lg = np.asarray(model.apply(variables, seq[None]))[0][18:18 + 12]
    assert float((lg.max(-1) - lg[np.arange(12), req.out_tokens]).max()) \
        <= ATOL


TWO_FULL = {**KEYS, "layer_types": [
    "state_space", "full_attention", "state_space", "full_attention",
    "gated_memory", "shared_attention", "gated_memory", "shared_attention"]}


def test_a_cross_layer_reads_the_nearest_full_layers_pages(monkeypatch):
    """With two full-attention layers the cross layers read the NEAREST one
    before them (layer 3), as the reference does; pointed at the other one
    (layer 1) the engine serves other tokens."""
    flat = _params(TWO_FULL)
    cfg = _model(TWO_FULL).cfg
    assert [cfg.source_layer(i) for i in (4, 5, 6, 7)] == [2, 3, 2, 3]
    shapes = [(23, 12), (14, 9)]
    _, reqs = _serve(flat, shapes=shapes, keys=TWO_FULL)
    assert max(_regret(flat, r, TWO_FULL) for r in reqs) <= ATOL
    real = TransformerConfig.source_layer
    monkeypatch.setattr(
        TransformerConfig, "source_layer", lambda self, i: (
            1 if self.layer_types[i] == "shared_attention" else real(self, i)))
    _, reqs = _serve(flat, shapes=shapes, keys=TWO_FULL)
    assert max(_regret(flat, r, TWO_FULL) for r in reqs) > 100 * ATOL


@pytest.mark.parametrize("option,reason", [
    ({"prefix_cache": True}, "prefix_cache with state_space layers"),
    ({"speculative": 2}, "speculative > 0 with state_space layers"),
    ({"quant_kv": True}, "quant_kv with diff_attention"),
])
def test_what_the_new_kinds_cannot_take_is_refused(option, reason):
    with pytest.raises(ValueError, match="not served: .*" + reason):
        _engine(_params(), **option)


def test_a_mesh_is_refused_for_the_new_kinds():
    import jax
    from jax.sharding import Mesh

    mesh = Mesh(jax.devices()[:1], ("tensor",))
    with pytest.raises(ValueError) as e:
        _engine(_params(), mesh=mesh)
    assert "mesh for a model with state_space layers" in str(e.value)
    assert "mesh with diff_attention" in str(e.value)
