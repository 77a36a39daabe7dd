"""The traffic generator: deterministic per seed, true to its mix."""
import numpy as np
import pytest

from chipbench import traffic

BIG_SEED = 2**31 + 977


@pytest.mark.parametrize("name", ["chat", "offline"])
def test_same_seed_same_requests(name):
    mix = traffic.load_mix(name)
    a = traffic.make_requests(mix, BIG_SEED, 20, vocab=1000)
    b = traffic.make_requests(mix, BIG_SEED, 20, vocab=1000)
    assert [(x.due, x.max_new) for x in a] == [(x.due, x.max_new) for x in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    c = traffic.make_requests(mix, BIG_SEED + 1, 20, vocab=1000)
    assert any(not np.array_equal(x.prompt, y.prompt) for x, y in zip(a, c))


def test_chat_rate_and_lengths_match_the_mix():
    mix = traffic.load_mix("chat")
    seconds = 400
    reqs = traffic.make_requests(mix, 5, seconds, vocab=1000)
    due = np.array([r.due for r in reqs])
    assert len(reqs) == round(mix["rate_per_s"] * seconds)
    assert due[0] == 0 and np.all(np.diff(due) > 0) and due[-1] < seconds
    gaps = np.diff(due)
    assert abs(gaps.mean() * mix["rate_per_s"] - 1) < 0.05
    # exponential gaps: the standard deviation equals the mean
    assert abs(gaps.std() / gaps.mean() - 1) < 0.1
    for key, lens in (("prompt_len", [len(r.prompt) for r in reqs]),
                      ("output_len", [r.max_new for r in reqs])):
        d = mix[key]
        assert min(lens) >= d["min"] and max(lens) <= d["max"]
        assert abs(np.median(lens) / d["median"] - 1) < 0.02


def test_every_seed_gets_the_same_work():
    mix = traffic.load_mix("chat")
    a = traffic.make_requests(mix, 1, 50, vocab=1000)
    b = traffic.make_requests(mix, 2**40 + 3, 50, vocab=1000)
    assert sorted(len(r.prompt) for r in a) == sorted(len(r.prompt) for r in b)
    assert sorted(r.max_new for r in a) == sorted(r.max_new for r in b)
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in b]
    # the same gaps in another order: all but one of them are shared
    ga = np.sort(np.diff([r.due for r in a]))
    gb = np.sort(np.diff([r.due for r in b]))
    assert np.isin(np.round(ga, 9), np.round(gb, 9)).sum() >= len(ga) - 1


def test_offline_backlog_lengths():
    mix = traffic.load_mix("offline")
    reqs = traffic.make_requests(mix, 9, 51, vocab=1000)
    assert all(r.due == 0 for r in reqs)
    first = reqs[:mix["pool"]]
    outs = sorted(r.max_new for r in first)
    assert outs == sorted(traffic.quantiles(mix["output_len"], mix["pool"]))
    assert abs(np.median(outs) / 129 - 1) < 0.05
    plens = np.array([len(r.prompt) for r in first])
    assert plens.min() >= 16 and plens.max() <= 1024
    # the cut at the engine's prefill length takes half of the prompts
    assert np.mean(plens == 1024) == pytest.approx(0.5, abs=0.02)


def test_token_ids_lie_in_the_vocabulary():
    reqs = traffic.make_requests(traffic.load_mix("chat"), 3, 30, vocab=77)
    ids = np.concatenate([r.prompt for r in reqs])
    assert ids.min() >= 0 and ids.max() < 77 and ids.dtype == np.int32
