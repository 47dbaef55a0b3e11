"""``ServeEngine`` itself over the tiny Kimi-Linear hybrid
(``kimi_linear_tiny.py``): each served token the first choice of
``benchmark/reference/kimi_linear.py`` at its position (tolerance:
``test_kimi_linear_reference.py``)."""

from __future__ import annotations

import json

import pytest

from torch_automatic_distributed_neural_network_tpu.obs.journal import Journal

from kimi_linear_tiny import (
    ATOL,
    BS,
    _engine,
    _highest,
    KEYS,
    _params,
    RANK,
    _regret,
    ROT,
    _tokens,
)

pytestmark = pytest.mark.usefixtures("_highest")


SHAPES = [(5, 20), (23, 30), (14, 17), (30, 8), (3, 3), (41, 12)]
SERVED = {"reserve": {},
          "optimistic": {"admission": "optimistic"},
          "dense": {"attention_impl": "dense"}}


@pytest.mark.parametrize("option", sorted(SERVED))
def test_engine_serves_the_references_first_choice(option, tmp_path):
    """The engine itself, scheduler and all: six requests over three slots
    (slots are reused, chunks and decode steps interleave and ride in one
    call, the last chunks are padded), each served token the reference's
    first choice at its position, under every engine option this model is
    served with."""
    flat = _params()
    journal = Journal(None, host0_only=False)
    eng = _engine(flat, journal, **SERVED[option])
    reqs = [eng.submit([int(t) for t in _tokens(n, 10 + i)], max_new_tokens=m)
            for i, (n, m) in enumerate(SHAPES)]
    eng.run()
    eng.scheduler.check_invariants()
    for r, (n, m) in zip(reqs, SHAPES):
        assert len(r.out_tokens) == m
        assert _regret(flat, r) <= ATOL, (option, n, m)
    steps = journal.named("serve.step")
    assert sum(s.get("fused", 0) for s in steps) > 3
    # the state rows a call's step kernels read and wrote: its decode rows
    # over the six linear layers
    counted = [s["state_rows"] for s in steps if "state_rows" in s]
    assert counted and all(n % 6 == 0 and 0 < n <= 18 for n in counted)
    # and the rows those kernels walked, on the same calls: here, off the
    # chip, the plain form's gather and scatter of every slot's row
    assert [s["state_rows_walked"] for s in steps if "state_rows" in s] \
        == [3 * 6] * len(counted)
    assert not [s for s in steps
                if "state_rows_walked" in s and "state_rows" not in s]
    if option != "reserve":
        return
    ev = journal.named("serve.engine")[-1]
    assert ev["layer_kinds"] == KEYS["layer_types"]
    assert ev["linear_mixer"] == ["gated_delta", "channel"]
    assert (ev["state_bytes_linear"], ev["conv_bytes_linear"]) \
        == eng.pool.bytes_state == (6 * 4 * 4 * 8 * 16 * 4,
                                    6 * 4 * 3 * 4 * 32 * 4)
    # pages for max_len are the two latent layers' alone
    assert ev["kv_bytes_full"] == ev["kv_bytes_latent"] \
        == eng.pool.bytes_latent == 2 * 73 * BS * 128 * 4
    assert ev["latent_row"] == [RANK, ROT, 128]
    assert ev["kv_bytes_window"] == 0
    assert (ev["experts_held"], ev["experts_published"]) == (4, 16)
    from torch_automatic_distributed_neural_network_tpu.obs import (
        report as obs_report,
    )

    path = tmp_path / "journal.jsonl"
    path.write_text("".join(json.dumps(r) + "\n" for r in journal.records))
    text = obs_report.format_report(obs_report.generate(str(path)))
    assert "latent: one row a token of 16 + 4 numbers, stored in 128" in text
    assert "(2 latent layers)" in text
    assert "of recurrent state" in text and "(6 linear layers)" in text
    assert "gated_delta: a decay a channel" in text
    share = sum(counted) / (18 * len(counted))
    assert (f"state rows a call ({share:.0%} of the rows the step kernels "
            f"walked)") in text


@pytest.mark.parametrize("live,on_chip,walked", [
    (5, True, 5), (0, True, 1), (12, True, 12), (5, False, 12),
    (0, False, 12)])
def test_the_kernel_walks_the_live_slots_and_the_plain_form_all(
        monkeypatch, live, on_chip, walked):
    """``state_rows_walked``'s count a layer of a decay a channel: on the
    chip the kernel's grid, the live slots (one item where none decodes);
    off it every slot's row."""
    from torch_automatic_distributed_neural_network_tpu.ops import (
        gated_delta as gd,
    )

    monkeypatch.setattr(gd, "_on_tpu", lambda: on_chip)
    assert gd.step_rows_walked(live, 12) == walked


def test_a_preempted_request_restarts_and_serves_the_same_tokens():
    """A pool too small for three growing requests under optimistic
    admission (the pages are the latent layers'): one is preempted, queued
    again and prefilled again from position 0, where its slot's states
    start from zeros; every request serves what it serves alone."""
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
    assert max(_regret(flat, r) for r in reqs) <= ATOL


REFUSED = {
    "prefix_cache": ({"prefix_cache": True},
                     "state at the matched boundary"),
    "speculative": ({"speculative": 2}, "cannot be taken out"),
    "mesh": ({"mesh": "a mesh"},
             "expert layers.*no sharded form.*no head axis to shard"),
    "quant_kv": ({"quant_kv": True}, "no int8 form"),
    "lora_spec": ({"lora_spec": "a spec"}, "layer_types"),
}


@pytest.mark.parametrize("option", sorted(REFUSED))
def test_unsupported_options_are_refused_at_construction(option):
    """What a recurrent state and a latent page refuse, each with its
    reason, stays refused for a model that has both: none is loosened."""
    kw, reason = REFUSED[option]
    with pytest.raises(ValueError, match=f"{option}.*{reason}"):
        _engine(_params(), **kw)
