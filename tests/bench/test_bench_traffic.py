"""Traffic from a mix file and a seed."""
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from bench import spec, traffic  # noqa: E402

MIXES = ["randtopk-chat", "mixed-chat", "randtopk-longgen"]


def _mix(name):
    return spec.load_json(os.path.join(spec.BENCH, "traffic", name + ".json"))


def _plan(mix, seed, horizon=14.0):
    return traffic.plan(mix, vocab=1000, max_len=1024, seed=seed,
                        horizon_s=horizon)


@pytest.mark.parametrize("name", MIXES)
def test_one_seed_gives_one_plan(name):
    a, b = _plan(_mix(name), 2 ** 31 + 7), _plan(_mix(name), 2 ** 31 + 7)
    for f in ("t_due", "tokens", "prompt_len", "answer_len", "comp"):
        assert np.array_equal(getattr(a, f), getattr(b, f))


@pytest.mark.parametrize("name", MIXES)
def test_seeds_reorder_the_same_work(name):
    a, b = _plan(_mix(name), 1), _plan(_mix(name), 2)
    assert not np.array_equal(a.tokens, b.tokens)
    assert not np.array_equal(a.t_due, b.t_due)
    assert not np.array_equal(a.prompt_len, b.prompt_len)
    # the same multisets of lengths, compressors and arrival count
    for f in ("prompt_len", "answer_len", "comp"):
        assert np.array_equal(np.sort(getattr(a, f)), np.sort(getattr(b, f)))
    assert len(a.t_due) == len(b.t_due)


@pytest.mark.parametrize("name", MIXES)
def test_plan_follows_the_mix(name):
    mix = _mix(name)
    p = _plan(mix, 5, horizon=60.0)
    rate = mix["arrivals"]["sessions_per_s"]
    assert len(p.t_due) == pytest.approx(rate * 60.0, abs=2)
    assert np.all(np.diff(p.t_due) >= 0) and p.t_due[-1] < 60.0
    pr, an = mix["prompt_tokens"], mix["answer_tokens"]
    assert pr["min"] <= p.prompt_len.min() and p.prompt_len.max() <= pr["max"]
    assert an["min"] <= p.answer_len.min() and p.answer_len.max() <= an["max"]
    assert abs(np.median(p.prompt_len) - pr["median"]) <= 2
    shares = np.bincount(p.comp) / len(p.comp)
    assert shares == pytest.approx([c["share"] for c in mix["compressors"]])
    assert p.steps(0) == p.prompt_len[0] + p.answer_len[0] - 1


def test_mmpp_bursts():
    mix = dict(_mix("randtopk-chat"), arrivals={
        "process": "mmpp", "sessions_per_s": 10.0, "burst_per_s": 20.0,
        "calm_s": 4.0, "burst_s": 2.0})
    t = _plan(mix, 3, horizon=600.0).t_due
    phase = t % 6.0
    calm, burst = (phase < 4.0).sum() / 400.0, (phase >= 4.0).sum() / 200.0
    assert burst / calm == pytest.approx(2.0, rel=0.1)


def test_share_counts():
    assert traffic.share_counts([0.25] * 4, 10) in ([3, 3, 2, 2],
                                                    [3, 2, 3, 2],
                                                    [2, 3, 3, 2],
                                                    [3, 2, 2, 3],
                                                    [2, 3, 2, 3],
                                                    [2, 2, 3, 3])
    assert sum(traffic.share_counts([1, 2, 3], 17)) == 17
