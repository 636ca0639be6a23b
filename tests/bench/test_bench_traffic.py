"""The traffic generator: exact repeats from --seed, the stated clipped
length distributions, and the same work, laid out alike, for every seed."""
import numpy as np
import pytest

import benchroot  # noqa: F401  (puts bench/ on the path)
from harness.traffic import (Traffic, clipped_lognormal_quantiles, layout,
                             swap_neighbours)

MIX = {"arrivals": "poisson",
       "prompt_tokens": {"median": 1020, "sigma": 0.5, "min": 32, "max": 1792},
       "output_tokens": {"median": 129, "sigma": 0.6, "min": 4, "max": 256},
       "layout_every": 4, "swap_within": 3}
BACKLOG = dict(MIX, arrivals="backlog", block=64)
BIG_SEED = 2**31 + 12345


def first(traffic, n):
    it = iter(traffic)
    return [next(it) for _ in range(n)]


@pytest.mark.parametrize("mix", [MIX, BACKLOG], ids=["poisson", "backlog"])
def test_same_seed_same_requests(mix):
    a = first(Traffic(mix, BIG_SEED, 49152, window_s=30, rate=3.0), 150)
    b = first(Traffic(mix, BIG_SEED, 49152, window_s=30, rate=3.0), 150)
    assert [(x.due, x.prompt, x.max_new_tokens) for x in a] == \
           [(x.due, x.prompt, x.max_new_tokens) for x in b]
    c = first(Traffic(mix, BIG_SEED + 1, 49152, window_s=30, rate=3.0), 150)
    assert [x.prompt for x in a] != [x.prompt for x in c]


def test_every_seed_offers_the_same_lengths_in_another_order():
    blocks = [Traffic(MIX, s, 49152, window_s=30, rate=3.0).block(0)
              for s in (1, 2, BIG_SEED)]
    sizes = [sorted((len(x.prompt), x.max_new_tokens) for x in b) for b in blocks]
    lens = [sorted(len(x.prompt) for x in b) for b in blocks]
    assert lens[0] == lens[1] == lens[2]
    assert [len(x.prompt) for x in blocks[0]] != [len(x.prompt) for x in blocks[1]]
    assert len(sizes[0]) == 90


def test_open_loop_block_holds_rate_times_window_arrivals():
    t = Traffic(MIX, 7, 49152, window_s=20, rate=2.5)
    b0, b1 = t.block(0), t.block(1)
    due0 = np.array([x.due for x in b0])
    assert len(b0) == 50 and (np.diff(due0) > 0).all()
    assert 0 < due0.min() and due0.max() < 20
    assert all(20 < x.due < 40 for x in b1)


def test_seeds_move_a_request_only_among_neighbours_in_rank():
    """The layout is fixed; a seed swaps ranks inside groups of
    ``swap_within``, so each position's length and gap stay close."""
    blocks = [Traffic(MIX, s, 49152, window_s=30, rate=3.0).block(0)
              for s in (1, 2, BIG_SEED)]
    dues = [np.array([x.due for x in b]) for b in blocks]
    prompts = [np.array([len(x.prompt) for x in b]) for b in blocks]
    ranks = np.sort(prompts[0])
    for p in prompts[1:]:
        # the rank at each position differs by less than swap_within
        r0 = np.searchsorted(ranks, prompts[0])
        r1 = np.searchsorted(ranks, p)
        assert np.abs(r0 - r1).max() < 2 * MIX["swap_within"]
    for d in dues[1:]:
        assert np.abs(d - dues[0]).max() < 0.1 * 30


@pytest.mark.parametrize("n,every", [(64, 4), (60, 3), (59, 4), (7, 4)])
def test_layout_deals_every_band_to_every_segment(n, every):
    ranks = layout(n, every, np.random.default_rng(0))
    assert sorted(ranks.tolist()) == list(range(n))
    if n % every == 0:
        # every run of `every` positions holds one rank of each band
        k = n // every
        for seg in ranks.reshape(k, every):
            assert sorted((seg // k).tolist()) == list(range(every))


def test_swap_neighbours_stays_in_its_group():
    perm = swap_neighbours(100, 3, np.random.default_rng(5))
    assert sorted(perm.tolist()) == list(range(100))
    assert (perm // 3 == np.arange(100) // 3).all()


@pytest.mark.parametrize("key", ["prompt_tokens", "output_tokens"])
def test_clipped_lognormal_holds(key):
    d = MIX[key]
    v = clipped_lognormal_quantiles(2000, d["median"], d["sigma"], d["min"], d["max"])
    assert v.min() >= d["min"] and v.max() <= d["max"]
    assert abs(np.median(v) - d["median"]) <= 1
    # the share clipped at the top is the lognormal's tail beyond max
    from statistics import NormalDist
    tail = 1 - NormalDist().cdf(np.log(d["max"] / d["median"]) / d["sigma"])
    assert abs((v == d["max"]).mean() - tail) < 0.01
    assert (MIX["prompt_tokens"]["max"] + MIX["output_tokens"]["max"]) <= 2048


def test_token_ids_stay_in_vocabulary():
    items = first(Traffic(BACKLOG, 3, 1000), 64)
    assert all(0 <= t < 1000 for x in items for t in x.prompt)
