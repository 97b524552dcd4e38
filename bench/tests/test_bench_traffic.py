"""Traffic generator: seed determinism and the mix's stated parameters."""
import numpy as np
import pytest

from bench.traffic import gaps, load_mix, make_plan, order, rng_for, sizes

VOCAB, MAX_LEN = 1000, 3584


def test_same_seed_same_plan_and_other_seed_same_sizes():
    mix = load_mix("chat-poisson")
    a = make_plan(mix, 2**33 + 5, 30, VOCAB, MAX_LEN)
    b = make_plan(mix, 2**33 + 5, 30, VOCAB, MAX_LEN)
    c = make_plan(mix, 17, 30, VOCAB, MAX_LEN)
    assert [(r.prompt, r.max_tokens, r.due) for r in a.requests] == \
        [(r.prompt, r.max_tokens, r.due) for r in b.requests]
    assert [r.prompt for r in a.requests] != [r.prompt for r in c.requests]
    # another seed: the same multiset of sizes and gaps, in another order
    assert sorted(len(r.prompt) for r in a.requests) == \
        sorted(len(r.prompt) for r in c.requests)
    assert sorted(r.max_tokens for r in a.requests) == \
        sorted(r.max_tokens for r in c.requests)
    ga = np.diff([r.due for r in a.requests])
    gc = np.diff([r.due for r in c.requests])
    # the schedules span the same gaps but one (the gap before the first)
    assert abs(ga.sum() - gc.sum()) <= max(ga.max(), gc.max())


@pytest.mark.parametrize("key", ["prompt", "output"])
def test_lengths_clipped_and_median(key):
    d = load_mix("chat-poisson")[key]
    v = sizes(d, 2001, rng_for(1, 0))
    assert v.min() >= d["min"] and v.max() <= d["max"]
    assert abs(np.median(v) - d["median"]) <= 1


def test_lognormal_spread_follows_sigma():
    d = {"dist": "lognormal", "median": 512, "sigma": 0.9, "min": 1,
         "max": 10**9}
    v = np.log(sizes(d, 4001, rng_for(0, 0)))
    assert abs(v.std() - 0.9) < 0.02


def test_gaps_mean():
    g = gaps(2.0, 1000, rng_for(3, 1))
    assert abs(g.mean() - 0.5) < 0.02 and g.min() > 0


@pytest.mark.parametrize("n", [61, 64, 200])
def test_balanced_order_spreads_every_band(n):
    """Every stretch of ``bands`` consecutive draws holds at most two of any
    band, and each band's members sit near their even places."""
    v = np.arange(n) * 3.0
    out = order(v, rng_for(2**40 + 9, 2), bands=8)
    assert sorted(out) == sorted(v)
    band = np.searchsorted(np.sort(v), out) * 8 // n
    for i in range(n - 7):
        assert np.bincount(band[i:i + 8], minlength=8).max() <= 2
    for b in range(8):
        at = np.flatnonzero(band == b)
        even = (np.arange(len(at)) + 0.5) * n / len(at)
        assert np.abs(at - even).max() <= n / len(at) + 1


def test_balanced_order_differs_by_seed_and_plain_shuffle_is_default():
    v = np.arange(64.0)
    a = order(v, rng_for(1, 2), bands=8)
    b = order(v, rng_for(2, 2), bands=8)
    assert not np.array_equal(a, b)
    assert np.array_equal(order(v, rng_for(1, 2)),
                          rng_for(1, 2).permutation(v))


def test_committed_mix_is_balanced_open_loop():
    """The cell's mix: an open loop whose prompts are spread so that the
    longest eighth of them is never bunched into one stretch of the run."""
    mix = load_mix("chat-poisson")
    assert mix["loop"] == "open" and mix["bands"] == 8
    p = make_plan(mix, 2**31 + 77, 51, VOCAB, MAX_LEN)
    lens = np.array([len(r.prompt) for r in p.requests])
    top = lens >= np.sort(lens)[-(len(lens) // 8)]
    for i in range(len(lens) - 7):
        assert top[i:i + 8].sum() <= 2
