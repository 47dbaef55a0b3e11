"""``ServeEngine`` itself over the tiny model of latent layers
(``joyai_flash_tiny.py``): each served token the first choice of
``benchmark/reference/joyai_flash.py`` at its position (tolerance:
``test_joyai_flash_reference.py``)."""

from __future__ import annotations

import json

import numpy as np
import pytest

from torch_automatic_distributed_neural_network_tpu.obs.journal import Journal

from joyai_flash_tiny import (
    ATOL,
    BS,
    CHUNK,
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
SERVED = {"chunked": {},
          "optimistic": {"admission": "optimistic"},
          "dense": {"attention_impl": "dense"},
          "speculative": {"speculative": 2},
          "prefix_cache": {"prefix_cache": True},
          # the chunk's attention as ONE kernel a layer, as on the chip (the
          # interpreter here; key blocks of 8 so that chunks cross them)
          "chunk_kernel": {}}


@pytest.mark.parametrize("option", sorted(SERVED))
def test_engine_serves_the_references_first_choice(option, tmp_path,
                                                   monkeypatch):
    """The engine itself, scheduler and all: six requests over three slots
    (slots are reused, chunks and decode steps interleave and ride in one
    call, the last chunks are padded), each served token the reference's
    first choice at its position, under every engine option a latent model
    is served with."""
    flat = _params()
    journal = Journal(None, host0_only=False)
    if option == "chunk_kernel":
        from torch_automatic_distributed_neural_network_tpu.ops import (
            paged_attention as paged,
        )

        monkeypatch.setattr(paged, "LATENT_KEYS", 8)
        monkeypatch.setattr(paged, "LATENT_CHUNK_KEYS", 8)
        monkeypatch.setattr(paged, "ITEM_BYTES", 1)
        monkeypatch.setattr(paged, "latent_chunk_tiles", lambda *a: True)
    eng = _engine(flat, journal, **SERVED[option])
    reqs = [eng.submit([int(t) for t in _tokens(n, 10 + i)], max_new_tokens=m)
            for i, (n, m) in enumerate(SHAPES)]
    eng.run()
    eng.scheduler.check_invariants()
    for r, (n, m) in zip(reqs, SHAPES):
        assert len(r.out_tokens) == m
        assert _regret(flat, r) <= ATOL, (option, n, m)
    steps = journal.named("serve.step")
    fuses = option != "speculative"
    assert (sum(s.get("fused", 0) for s in steps) > 3) == fuses
    if option == "speculative":
        assert eng.spec_accepted > 0
    # the counters the kernel brought: which form a chunk attends in, and
    # on every call that dispatched a chunk the key blocks its four layers'
    # kernel calls ran (blocks of 8 keys: the chunk's last position's)
    ev = journal.named("serve.engine")[-1]
    form = "kernel" if option == "chunk_kernel" else "blocks"
    assert ev["chunk_attention"] == {"latent_attention": form}
    counted = [s for s in steps if "chunk_key_blocks" in s]
    if option == "chunk_kernel":
        chunks = sum(-(-n // CHUNK) for n, _ in SHAPES)
        assert sum(s["n_prefill_chunks"] for s in counted) == chunks \
            == sum(s.get("n_prefill_chunks", 0) for s in steps)
        assert sum(s["chunk_key_blocks"] for s in counted) == 4 * sum(
            (pos + CHUNK - 1) // 8 + 1
            for n, _ in SHAPES for pos in range(0, n, CHUNK))
    else:
        assert not counted
    if option != "chunked":
        return
    # the kernel's grid: work lists of the live (slot, 512-key group) items
    assert sum(s.get("attn_grid_items", 0) for s in steps) > 0
    assert all(s["attn_grid_items"] <= s["attn_grid_dense"]
               for s in steps if s.get("attn_grid_dense"))
    assert ev["layer_kinds"] == KEYS["layer_types"]
    assert ev["kv_bytes_full"] == ev["kv_bytes_latent"] \
        == eng.pool.bytes_latent == 4 * 73 * BS * 128 * 4
    assert ev["latent_row"] == [RANK, ROT, 128]
    assert (ev["kv_bytes_window"], ev["state_bytes_linear"]) == (0, 0)
    assert (ev["experts_held"], ev["experts_published"]) == (4, 16)
    from torch_automatic_distributed_neural_network_tpu.obs import (
        report as obs_report,
    )

    path = tmp_path / "journal.jsonl"
    path.write_text("".join(json.dumps(r) + "\n" for r in journal.records))
    text = obs_report.format_report(obs_report.generate(str(path)))
    assert "latent: one row a token of 16 + 4 numbers, stored in 128" in text
    assert "(4 latent layers)" in text
    assert "paged attention grid:" in text


def test_a_shared_prefix_is_read_where_it_lies():
    """Prefix reuse over latent pages: requests that share a prompt's first
    24 tokens match its pages in the radix index, skip those chunks and
    attend the shared rows through their own tables (a rotated key part
    holds its ABSOLUTE position, which a shared prefix shares).  Every token
    is the reference's first choice, and the tokens are those of an engine
    without the cache."""
    flat = _params()
    head = [int(t) for t in _tokens(24, 70)]
    prompts = [head + [int(t) for t in _tokens(n, 71 + n)]
               for n in (9, 5, 14)] + [head[:22], head]

    def serve(**kw):
        eng = _engine(flat, n_slots=2, **kw)
        reqs = []
        for p in prompts:  # one after the other: the index fills first
            reqs.append(eng.submit(list(p), max_new_tokens=7))
            eng.run()
        return eng, reqs

    eng, reqs = serve(prefix_cache=True)
    _, plain = serve()
    assert [r.out_tokens for r in reqs] == [r.out_tokens for r in plain]
    assert max(_regret(flat, r) for r in reqs) <= ATOL
    assert eng.prefix_hits >= 3 and eng.prefix_saved_chunks >= 6
    eng.scheduler.check_invariants()


def test_a_shared_page_is_forked_before_it_is_written():
    """Copy-on-write over latent pages: the page a running request's next
    row lands in gets a second owner (``allocator.ref``); the engine copies
    the page (the one array; the array of no elements beside it is left
    alone) and writes the copy.  What is served is the reference's first
    choice, and the first owner's page keeps its rows."""
    flat = _params()
    eng = _engine(flat, n_slots=1, prefix_cache=True)
    req = eng.submit([int(t) for t in _tokens(10, 80)], max_new_tokens=9)
    while req.state != "running":
        eng.step()
    bi = (req.n_prompt + req.n_generated - 1) // BS
    shared = req.blocks[bi]
    eng.pool.allocator.ref(shared)  # a second owner
    kept = np.asarray(eng.pool.kv["k"][1][shared])
    eng.step()
    assert eng.cow_forks == 1 and req.blocks[bi] != shared
    eng.pool.allocator.release([shared])
    eng.run()
    np.testing.assert_array_equal(np.asarray(eng.pool.kv["k"][1][shared]),
                                  kept)
    assert len(req.out_tokens) == 9 and _regret(flat, req) <= ATOL
    eng.scheduler.check_invariants()


def test_a_preempted_request_restarts_and_serves_the_same_tokens():
    """A pool too small for three growing requests under optimistic
    admission: one is preempted, queued again and prefilled again from
    position 0; every request serves what it serves alone."""
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


def test_a_slot_decoded_one_step_too_far_spoils_nothing():
    """The dispatch-ahead: a request that ends at an EOS is decoded once
    more before the host reads the EOS, which writes one row too many into
    a page it owned.  The requests that take the slot afterwards serve the
    reference's first choice."""
    flat = _params()
    eng = _engine(flat, n_slots=1)
    probe = eng.submit([int(t) for t in _tokens(12, 50)], max_new_tokens=8)
    eng.run()
    eos = probe.out_tokens[3]
    first = eng.submit(list(probe.prompt), max_new_tokens=8, eos_id=eos)
    later = [eng.submit([int(t) for t in _tokens(n, 60 + n)],
                        max_new_tokens=6) for n in (9, 17)]
    eng.run()
    assert first.out_tokens == probe.out_tokens[:probe.out_tokens.index(eos) + 1]
    assert eng.discarded_tokens >= 1
    assert max(_regret(flat, r) for r in later) <= ATOL


REFUSED = {
    "mesh": ({"mesh": "a mesh"}, "no head axis to shard"),
    "quant_kv": ({"quant_kv": True}, "no int8 form"),
    "lora_spec": ({"lora_spec": "a spec"}, "layer_types"),
}


@pytest.mark.parametrize("option", sorted(REFUSED))
def test_unsupported_options_are_refused_at_construction(option):
    """What a model with latent layers is not served with, each refusal
    with its reason."""
    kw, reason = REFUSED[option]
    with pytest.raises(ValueError, match=f"{option}.*{reason}"):
        _engine(_params(), **kw)
