"""Traffic generator: seed determinism and the mix's stated parameters."""
import hashlib
import json

import numpy as np
import pytest

from bench.traffic import (ClosedPlan, SessionsPlan, gaps, load_mix,
                           make_plan, order, rng_for, sizes)

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


@pytest.mark.parametrize("seed,digest", [
    (1, "0f8cb72b8ea56fa31b1d0b2f32b671f1f7ee14c5f21a68193071b203320e408e"),
    (2**31 + 77,
     "fa45c8262542f4513e4128ba343b9a54c049479de2a4d2a9de265e0b6c3c00cb"),
    (2**33 + 5,
     "89144d5b97630867e183cdaff66c62318b430730bc089ad5be73ee55676ea9d8"),
])
def test_chat_poisson_plan_is_pinned(seed, digest):
    """The open loop's plan for qwen2-7b's window, request for request, as
    the benchmark first generated it: ids, prompts, lengths and due times."""
    p = make_plan(load_mix("chat-poisson"), seed, 51, 152064, MAX_LEN)
    got = json.dumps([[r.rid, r.prompt, r.max_tokens, repr(r.due)]
                      for r in p.requests]).encode()
    assert len(p.requests) == 61
    assert hashlib.sha256(got).hexdigest() == digest


def test_closed_plan_sizes_and_clients():
    mix = load_mix("longdoc-batch")
    a = make_plan(mix, 2**35 + 3, 51, VOCAB, 4096)
    b = make_plan(mix, 9, 51, VOCAB, 4096)
    assert isinstance(a, ClosedPlan) and a.clients == 16
    assert len(a.requests) == mix["requests"]
    p = np.array([len(r.prompt) for r in a.requests])
    o = np.array([r.max_tokens for r in a.requests])
    assert p.min() >= 1024 and p.max() <= 3584 and o.min() >= 64
    assert o.max() <= 448 and (p + o).max() <= 4096
    assert abs(np.median(p) - 2048) <= 16 and abs(np.median(o) - 192) <= 2
    # every block of `clients` requests holds the same sizes, in every seed
    for r in (a.requests, b.requests):
        pb = np.reshape([len(x.prompt) for x in r], (-1, 16))
        ob = np.reshape([x.max_tokens for x in r], (-1, 16))
        assert all(sorted(k) == sorted(p[:16]) for k in pb)
        assert all(sorted(k) == sorted(o[:16]) for k in ob)
    # another seed: the same sizes in another order
    assert sorted(p) == sorted(len(r.prompt) for r in b.requests)
    assert list(p) != [len(r.prompt) for r in b.requests]
    # the first `clients` are due at once, when the warm-up starts; each
    # finish makes the next due
    first = a.first()
    assert [r.rid for r in first] == list(range(16))
    assert a.warm_s == mix["warm_s"] > 0
    assert all(r.due == -a.warm_s for r in first)
    (nxt,) = a.after(first[3], [0] * first[3].max_tokens, 7.5)
    assert nxt.rid == 16 and nxt.due == 7.5
    assert nxt.prompt == a.requests[16].prompt
    a.sent = len(a.requests)
    assert a.after(first[0], None, 9.0) == []


def test_sessions_plan_turns_think_and_growth():
    mix = load_mix("sessions-prefix")
    max_len = 3584
    a = make_plan(mix, 2**34 + 1, 51, VOCAB, max_len)
    b = make_plan(mix, 5, 51, VOCAB, max_len)
    assert isinstance(a, SessionsPlan)
    assert len(a.sessions) == round(mix["rate_per_s"] * 51)
    assert len(a.systems) == 8 and {len(s) for s in a.systems} == {1024}
    # Zipf: the first system prompt is the most used; every seed uses each
    # as often
    use = np.bincount([s.system for s in a.sessions], minlength=8)
    assert use[0] == use.max() and use[0] > 2 * use[3]
    assert list(use) == list(np.bincount([s.system for s in b.sessions],
                                         minlength=8))
    for s in a.sessions:
        assert 1 <= len(s.users) <= 6 and s.thinks[0] == 0.0
        assert all(32 <= len(u) <= 256 for u in s.users)
        assert all(16 <= n <= 384 for n in s.answers)
    thinks = [t for s in a.sessions for t in s.thinks[1:]]
    assert 2.0 < np.mean(thinks) < 4.0 and min(thinks) > 0
    # a turn: the previous prompt, its served answer, a new user message,
    # due the think time after the answer came back
    t0 = a.first()[0]
    s = a.sessions[0]
    assert t0.prompt == a.systems[s.system] + s.users[0]
    out = list(range(t0.max_tokens))
    (t1,) = a.after(t0, out, 10.0)
    assert t1.prompt == t0.prompt + out + s.users[1]
    assert t1.due == pytest.approx(10.0 + s.thinks[1])
    assert t1.max_tokens == s.answers[1] and t1.turn == 1
    assert a.after(t0, None, 10.0) == []          # refused: session ends


def test_sessions_end_where_the_next_turn_would_not_fit():
    mix = dict(load_mix("sessions-prefix"), rate_per_s=4.0)
    mix["turns"] = {"dist": "uniform", "min": 6, "max": 6}
    p = make_plan(mix, 3, 51, VOCAB, 1800)
    cut = 0
    for s in p.sessions:
        length = 1024
        for u, o in zip(s.users, s.answers):
            length += len(u) + o
            assert length <= 1800
        cut += len(s.users) < 6
    assert cut > len(p.sessions) // 2
    with pytest.raises(ValueError, match="exceeds max_len"):
        make_plan(mix, 3, 51, VOCAB, 1100)
