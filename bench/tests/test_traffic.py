"""The traffic generator: deterministic per seed, inside its clips, and
the same work for every seed."""
import collections

import numpy as np
import pytest

from bench import catalog
from bench.traffic import Traffic, quantiles

MIXES = ["chat-backlog"]


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_requests(name):
    mix = catalog.traffic(name)
    a, b = Traffic(mix, 2**31 + 11, 1000), Traffic(mix, 2**31 + 11, 1000)
    for i in (0, 5, 200):
        assert a.spec(i) == b.spec(i)
        assert np.array_equal(a.prompt(i), b.prompt(i))
    c = Traffic(mix, 12, 1000)
    assert [c.spec(i) for i in range(64)] != [a.spec(i) for i in range(64)]


@pytest.mark.parametrize("name", MIXES)
def test_lengths_inside_their_clips(name):
    mix = catalog.traffic(name)
    t = Traffic(mix, 3, 97)
    for i in range(300):
        s = t.spec(i)
        assert mix["prompt_len"]["min"] <= s.prompt_len <= \
            mix["prompt_len"]["max"]
        assert mix["output_len"]["min"] <= s.output_len <= \
            mix["output_len"]["max"]
        p = t.prompt(i)
        assert len(p) == s.prompt_len and p.min() >= 0 and p.max() < 97
        assert s.prompt_len + s.output_len <= 1024


@pytest.mark.parametrize("name", MIXES)
def test_every_seed_offers_the_same_blocks(name):
    mix = catalog.traffic(name)
    blk = mix["block"]

    def block(seed, b):
        t = Traffic(mix, seed, 97)
        return collections.Counter((t.spec(i).prompt_len,)
                                   for i in range(b * blk, (b + 1) * blk))

    assert block(1, 0) == block(2, 0) == block(3, 2)


def test_poisson_rate_is_the_files():
    mix = dict(catalog.traffic("chat-backlog"),
               arrival={"kind": "poisson", "rate_per_s": 2.5})
    t = Traffic(mix, 5, 97)
    n = 4 * mix["block"]
    rate = n / t.spec(n - 1).arrival_s
    assert rate == pytest.approx(mix["arrival"]["rate_per_s"], rel=0.03)
    gaps = np.diff([t.spec(i).arrival_s for i in range(n)])
    assert (gaps > 0).all()


def test_quantiles_are_clipped_and_sorted():
    q = quantiles({"dist": "lognormal", "median": 256, "sigma": 0.8,
                   "min": 32, "max": 768}, 64)
    assert q.min() >= 32 and q.max() == 768 and (np.diff(q) >= 0).all()
    assert np.median(q) == pytest.approx(256, rel=0.05)
