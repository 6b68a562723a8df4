"""The traffic generator: the same seed gives the same requests, and every
seed gives the same multiset of sizes and gaps."""
import collections

import numpy as np
import pytest

from bench import traffic

MIXES = ["zipf64-poisson", "longctx-backlog", "resident-backlog"]
BIG = 2 ** 31 + 12345


def _key(items):
    return [(it.due_s, it.adapter, it.prompt.tobytes(), it.max_new)
            for it in items]


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_requests(name):
    mix = traffic.load(name)
    a = traffic.generate(mix, BIG, 30.0, 32000)
    b = traffic.generate(mix, BIG, 30.0, 32000)
    assert _key(a) == _key(b)
    c = traffic.generate(mix, BIG + 1, 30.0, 32000)
    assert _key(a) != _key(c)


@pytest.mark.parametrize("name", MIXES)
def test_every_seed_same_work(name):
    mix = traffic.load(name)
    runs = [traffic.generate(mix, s, 30.0, 32000) for s in (1, 2, BIG)]
    sizes = [sorted((len(it.prompt), it.max_new) for it in r) for r in runs]
    plens = [sorted(len(it.prompt) for it in r) for r in runs]
    olens = [sorted(it.max_new for it in r) for r in runs]
    assert plens[0] == plens[1] == plens[2]
    assert olens[0] == olens[1] == olens[2]
    counts = [sorted(collections.Counter(it.adapter for it in r).values())
              for r in runs]
    assert counts[0] == counts[1] == counts[2]
    gaps = [sorted(np.round(np.diff([0.0] + [it.due_s for it in r]), 9))
            for r in runs]
    assert gaps[0] == gaps[1] == gaps[2]
    assert len(sizes[0]) % traffic.BLOCK == 0


def test_lengths_in_range_and_poisson_rate():
    mix = traffic.load("zipf64-poisson")
    items = traffic.generate(mix, 5, 60.0, 64000)
    p = [len(it.prompt) for it in items]
    o = [it.max_new for it in items]
    assert min(p) >= 32 and max(p) <= 768
    assert min(o) >= 16 and max(o) <= 256
    assert abs(np.median(p) - 256) < 16
    rate = mix["arrival"]["rate_per_s"]
    assert abs(items[-1].due_s - len(items) / rate) < 0.15 * len(items) / rate


def test_zipf_top16_share():
    mix = traffic.load("zipf64-poisson")
    p = traffic.popularity(mix)
    assert 0.70 < p[:16].sum() < 0.80
    ranks = [r for _, r in traffic.adapters(mix)]
    assert sorted(collections.Counter(ranks[:16]).values()) == [4, 4, 4, 4]


def test_backlog_prefix_balanced():
    mix = traffic.load("longctx-backlog")
    items = traffic.generate(mix, 9, 30.0, 32064)
    block = items[:traffic.BLOCK]
    assert len({it.adapter for it in block}) == 4
    assert all(it.due_s == 0.0 for it in items)


BIMODAL = {"name": "bimodal", "adapters": {"count": 8, "ranks": [8, 64]},
           "prompt": {"dist": "mixture", "min": 32, "max": 4096,
                      "parts": [{"weight": 0.8, "dist": "lognormal",
                                 "median": 128, "sigma": 0.5, "min": 32,
                                 "max": 512},
                                {"weight": 0.2, "dist": "uniform",
                                 "min": 2048, "max": 4096}]},
           "output": {"dist": "uniform", "min": 16, "max": 64},
           "arrival": {"kind": "bursts", "rate_on_per_s": 8.0, "on_s": 2.0,
                       "off_s": 8.0}}


def test_mixture_and_bursts_laws():
    a = traffic.generate(BIMODAL, BIG, 40.0, 32000)
    b = traffic.generate(BIMODAL, 3, 40.0, 32000)
    assert len(a) == len(b) == 64       # 4 bursts of 2 s at 8 per second
    assert sorted(len(it.prompt) for it in a) == \
        sorted(len(it.prompt) for it in b)
    long = [len(it.prompt) for it in a if len(it.prompt) >= 2048]
    assert len(long) == round(0.2 * 64) and max(long) <= 4096
    due = np.array([it.due_s for it in a])
    assert np.all(due % 10.0 < 2.0)     # every request inside a burst
    assert due.max() < 40.0


def test_unknown_law_is_named():
    mix = dict(BIMODAL, arrival={"kind": "trickle"})
    with pytest.raises(ValueError, match="no arrival law 'trickle'"):
        traffic.generate(mix, 1, 10.0, 32000)
