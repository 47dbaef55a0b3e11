import collections
import json
import os
import statistics

import pytest

from lib import arrivals, stats

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def mixes():
    out = []
    for f in sorted(os.listdir(os.path.join(BENCH, "traffic"))):
        mix = json.load(open(os.path.join(BENCH, "traffic", f)))
        if "arrivals" in mix:
            out.append(pytest.param(mix, id=f))
    return out


@pytest.mark.parametrize("mix", mixes())
def test_plan_is_deterministic_in_seed(mix):
    a = arrivals.plan(mix, 2**31 + 5, 20.0, 50257)
    b = arrivals.plan(mix, 2**31 + 5, 20.0, 50257)
    c = arrivals.plan(mix, 2**31 + 6, 20.0, 50257)
    assert a == b
    assert a != c


@pytest.mark.parametrize("mix", mixes())
def test_every_seed_holds_the_same_work(mix):
    """Same multiset of lengths (block by block) and of arrival gaps for
    every seed, the same number due in the window, every due time inside."""
    plans = [arrivals.plan(mix, s, 60.0, 1000) for s in (1, 2, 3)]
    k = mix["lengths"]["strata"]
    whole = len(plans[0]) // k * k  # lengths repeat block by block
    assert whole >= k
    lens = [collections.Counter(len(p.prompt) for p in pl[:whole])
            for pl in plans]
    outs = [collections.Counter(p.max_new for p in pl[:whole]) for pl in plans]
    assert lens[0] == lens[1] == lens[2]
    assert outs[0] == outs[1] == outs[2]
    for pl in plans:  # and every block holds each stratum once
        assert (collections.Counter(len(p.prompt) for p in pl[:k])
                == collections.Counter(len(p.prompt) for p in pl[k:2 * k]))
    assert [len(p.prompt) for p in plans[0]] != [len(p.prompt) for p in plans[1]]
    assert len({len(pl) for pl in plans}) == 1
    for pl in plans:
        assert all(0.0 <= p.due_s < 60.0 for p in pl)
        assert [p.due_s for p in pl] == sorted(p.due_s for p in pl)
    if mix["arrivals"]["kind"] == "poisson":
        assert len(plans[0]) == round(mix["arrivals"]["rate_per_s"] * 60.0)
        gaps = [sorted(round(b.due_s - a.due_s, 9)
                       for a, b in zip(pl, pl[1:])) for pl in plans]
        # one gap (the first or the last) falls outside the window's n - 1
        assert sum(abs(x - y) for x, y in zip(gaps[0], gaps[1])) < 0.2 * 60.0


@pytest.mark.parametrize("mix", mixes())
def test_lengths_keep_their_distribution(mix):
    L = mix["lengths"]
    for side in ("prompt", "output"):
        vals = arrivals.lognormal_strata(**L[side], strata=L["strata"])
        assert len(vals) == L["strata"]
        assert min(vals) >= L[side]["lo"] and max(vals) <= L[side]["hi"]
        assert vals == sorted(vals)
        med = statistics.median(vals)
        assert abs(med - L[side]["median"]) <= 0.05 * L[side]["median"] + 1
    eng = mix["engine"]
    assert L["prompt"]["hi"] + L["output"]["hi"] <= eng["max_len"]
    assert len(arrivals.distinct_prompt_lengths(L)) <= L["strata"]


def test_prompts_avoid_the_pad_token():
    mix = mixes()[0].values[0]
    for p in arrivals.plan(mix, 7, 5.0, 64):
        assert min(p.prompt) >= 1 and max(p.prompt) < 64


@pytest.mark.parametrize("q,want", [(0.5, 3), (0.95, 5), (0.2, 1), (1.0, 5),
                                     (0.0, 1)])
def test_percentile_is_nearest_rank(q, want):
    assert stats.percentile([5, 1, 4, 2, 3], q) == want


def test_percentile_of_nothing_raises():
    with pytest.raises(ValueError):
        stats.percentile([], 0.95)


def test_p95_of_200_has_ten_beyond():
    vals = list(range(1, 201))
    assert stats.percentile(vals, 0.95) == 190
    assert sum(v > 190 for v in vals) == 10


def test_iqr_share_matches_statistics_quantiles():
    vals = [10.0, 10.2, 9.9, 10.1, 10.4, 9.8]
    q1, _, q3 = statistics.quantiles(vals, n=4)
    assert stats.iqr_share(vals) == (q3 - q1) / statistics.median(vals)
