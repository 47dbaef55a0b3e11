"""``ServeEngine`` itself over the tiny hybrid of gated grouped-query
attention and Kimi Delta Attention (``solar_open2_tiny.py``): prefill in
chunks and then decode through the cache, K/V pages of the two attention
layers and state rows of the six KDA layers in one pool, an expert FFN in
every layer; each served token the first choice of
``benchmark/reference/solar_open2.py``'s full forward pass at its position
(logits, not tokens: the gap between the served token's logit and the
reference's best; tolerance: ``test_solar_open2_reference.py``)."""

from __future__ import annotations

import json

import jax
import pytest

from torch_automatic_distributed_neural_network_tpu.models import (
    transformer_core,
)
from torch_automatic_distributed_neural_network_tpu.obs.journal import Journal

from solar_open2_tiny import (
    ATOL,
    BS,
    _engine,
    _gaps,
    _highest,
    KEYS,
    _params,
    _tokens,
)

pytestmark = pytest.mark.usefixtures("_highest")


SHAPES = [(5, 20), (23, 30), (14, 17), (30, 8), (3, 3), (41, 12)]
SERVED = {"reserve": {},
          "optimistic": {"admission": "optimistic"},
          "dense": {"attention_impl": "dense"},
          # plain K/V pages take the int8 form beside float32 state rows (a
          # latent page does not): the tokens are still the reference's
          "int8_pages": {"quant_kv": True}}


@pytest.mark.parametrize("option", sorted(SERVED))
def test_engine_serves_the_references_first_choice(option, tmp_path):
    """The engine itself, scheduler and all: six requests over three slots
    (slots are reused at different depths, chunks and decode steps
    interleave and ride in one call, the last chunks are padded), each
    served token the reference's first choice at its position, under every
    engine option this model is served with."""
    flat = _params()
    journal = Journal(None, host0_only=False)
    eng = _engine(flat, journal, **SERVED[option])
    reqs = [eng.submit([int(t) for t in _tokens(n, 10 + i)], max_new_tokens=m)
            for i, (n, m) in enumerate(SHAPES)]
    eng.run()
    eng.scheduler.check_invariants()
    for r, (n, m) in zip(reqs, SHAPES):
        assert len(r.out_tokens) == m
        assert _gaps(flat, r).max() <= ATOL, (option, n, m)
    steps = journal.named("serve.step")
    assert sum(s.get("fused", 0) for s in steps) > 3
    # the state rows a call's step kernels read and wrote: its decode rows
    # over the six linear layers
    counted = [s["state_rows"] for s in steps if "state_rows" in s]
    assert counted and all(n % 6 == 0 and 0 < n <= 18 for n in counted)
    # every plan entry an expert FFN: pairs counted over all eight layers
    assert eng.cfg.n_expert_layers == eng.cfg.n_layers == 8
    assert max(s.get("moe_pairs", 0) for s in steps) > 0
    if option != "reserve":
        return
    ev = journal.named("serve.engine")[-1]
    assert ev["layer_kinds"] == KEYS["layer_types"]
    assert ev["layer_kinds"][0] == "full_attention"
    # what tells this model apart, in fields of their own
    assert ev["linear_mixer"] == ["gated_delta", "channel"]
    assert (ev["linear_write_max"], ev["attn_gate"], ev["dense_layers"]) \
        == (2, True, 0)
    assert ev["attention_form"] == "softmax"
    assert (ev["state_bytes_linear"], ev["conv_bytes_linear"]) \
        == eng.pool.bytes_state == (6 * 4 * 4 * 8 * 16 * 4,
                                    6 * 4 * 3 * 4 * 32 * 4)
    # pages for max_len are the two attention layers' keys and values: 2
    # arrays of 2 KV heads of 8 a token
    assert ev["kv_bytes_full"] == eng.pool.bytes_full \
        == 2 * 73 * BS * 2 * 16 * 4
    assert ev["kv_bytes_latent"] == 0 and ev["latent_row"] is None
    assert ev["kv_bytes_window"] == 0
    assert (ev["experts_held"], ev["experts_published"]) == (4, 16)
    from torch_automatic_distributed_neural_network_tpu.obs import (
        report as obs_report,
    )

    path = tmp_path / "journal.jsonl"
    path.write_text("".join(json.dumps(r) + "\n" for r in journal.records))
    text = obs_report.format_report(obs_report.generate(str(path)))
    assert "(2 full, 0 sliding layers)" in text
    assert "of recurrent state" in text and "(6 linear layers)" in text
    assert "gated_delta: a decay a channel, beta up to 2" in text
    assert "attention: a sigmoid gate on its output" in text
    assert "experts 4 held of 16 in EVERY layer (no dense FFN)" in text


def test_beta_passes_one_on_the_served_path(monkeypatch):
    """The write strength the serving programs' mixers compute reaches past
    1 (``beta = 2 sigmoid(.)``), in chunks and in decode steps alike; and an
    engine whose mixers clip it at 1 serves tokens the reference does not
    put first: a change that clips it fails."""
    flat = _params()
    seen = []
    real = transformer_core.GatedDeltaMixer.project

    def watched(self, x):
        pre, g, beta = real(self, x)
        jax.debug.callback(lambda b: seen.append(
            (b.shape[1], float(b.max()), float(b.min()))), beta)
        return pre, g, beta

    monkeypatch.setattr(transformer_core.GatedDeltaMixer, "project", watched)
    eng = _engine(flat)
    r = eng.submit([int(t) for t in _tokens(23, 3)], max_new_tokens=12)
    eng.run()
    jax.effects_barrier()
    assert _gaps(flat, r).max() <= ATOL
    rows = {n for n, _, _ in seen}
    assert len(rows) >= 2  # a chunk's rows and a decode step's
    for n in rows:
        assert max(hi for m, hi, _ in seen if m == n) > 1.0, n
    assert max(hi for _, hi, _ in seen) < 2.0
    assert min(lo for _, _, lo in seen) > 0.0

    def clipped(self, x):
        pre, g, beta = real(self, x)
        return pre, g, jax.numpy.minimum(beta, 1.0)

    monkeypatch.setattr(transformer_core.GatedDeltaMixer, "project", clipped)
    eng = _engine(flat)
    r = eng.submit([int(t) for t in _tokens(23, 3)], max_new_tokens=12)
    eng.run()
    # logits, not tokens: the clipped engine's choices lie below the
    # reference's best by far more than the tolerance somewhere
    assert _gaps(flat, r).max() > 100 * ATOL


def test_a_slot_reused_at_another_depth_starts_from_zeros():
    """One slot, three requests one after the other, long then short then
    long: each starts from a state of zeros and an empty convolution tail
    in the slot the one before left, and reads only its own pages."""
    flat = _params()
    eng = _engine(flat, n_slots=1)
    reqs = [eng.submit([int(t) for t in _tokens(n, 60 + i)], max_new_tokens=m)
            for i, (n, m) in enumerate([(37, 9), (4, 14), (29, 6)])]
    eng.run()
    eng.scheduler.check_invariants()
    assert max(_gaps(flat, r).max() for r in reqs) <= ATOL


def test_a_preempted_request_restarts_and_serves_the_same_tokens():
    """A pool too small for three growing requests under optimistic
    admission (the pages are the attention layers'): one is preempted,
    queued again and prefilled again from position 0, where its slot's
    states start from zeros; every request serves what it serves alone."""
    flat = _params()
    shapes = [(20, 30), (22, 28), (18, 30)]
    alone = []
    eng = _engine(flat)  # one engine, a request at a time: each alone in it
    for i, (n, m) in enumerate(shapes):
        r = eng.submit([int(t) for t in _tokens(n, 40 + i)], max_new_tokens=m)
        eng.run()
        alone.append(r.out_tokens)
    eng = _engine(flat, admission="optimistic", num_blocks=28)
    reqs = [eng.submit([int(t) for t in _tokens(n, 40 + i)], max_new_tokens=m)
            for i, (n, m) in enumerate(shapes)]
    eng.run()
    eng.scheduler.check_invariants()
    assert sum(r.preempted for r in reqs) >= 1
    assert [r.out_tokens for r in reqs] == alone
    assert max(_gaps(flat, r).max() for r in reqs) <= ATOL


REFUSED = {
    "prefix_cache": ({"prefix_cache": True},
                     "state at the matched boundary"),
    "speculative": ({"speculative": 2}, "cannot be taken out"),
    "mesh": ({"mesh": "a mesh"}, "expert layers.*no sharded form"),
    "lora_spec": ({"lora_spec": "a spec"}, "layer_types"),
}


@pytest.mark.parametrize("option", sorted(REFUSED))
def test_unsupported_options_are_refused_at_construction(option):
    """What a recurrent state and an expert layer refuse, each with its
    reason, stays refused for a model that has both beside plain K/V pages:
    none is loosened (PERF.md section 7.1)."""
    kw, reason = REFUSED[option]
    with pytest.raises(ValueError, match=f"{option}.*{reason}"):
        _engine(_params(), **kw)
