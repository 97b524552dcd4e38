"""One general traffic generator; each mix is a data file in ``bench/traffic``.

A mix file (``<mix>.json``) gives the arrivals and the length
distributions of one of three loops (its ``loop``):

- ``open``: independent requests due on a Poisson schedule (exponential
  gaps at ``rate_per_s``), each with a prompt of random token ids and an
  answer length.
- ``closed``: ``clients`` clients, each sending its next request the
  moment its previous one finishes; the requests (``requests`` of them,
  rounded up to whole blocks of ``clients``, more than a window finishes)
  are taken in their planned order. Each block of ``clients`` consecutive
  requests holds the same stratified sizes, so that every seed's window,
  which takes a few blocks, does the same work. The clients start
  ``warm_s`` seconds before the window opens, so that the window does not
  measure them all starting at once.
- ``sessions``: conversations that open on a Poisson schedule
  (``rate_per_s``). Each opens on one of ``system.count`` system prompts
  of ``system.tokens`` tokens, chosen by a Zipf law of exponent
  ``system.zipf``, and runs ``turns`` turns. A turn is sent ``think_s``
  seconds (exponential, that mean) after the previous answer came back;
  its prompt is the previous prompt, the served answer and a new user
  message (``user`` tokens). A session ends early where its next turn
  would not fit ``max_len``.

Every seed gets the same multiset of sizes and gaps (stratified quantiles
of each distribution, drawn from the mix's own parameters) and its own
token ids: runs with different seeds do the same amount of work in
another order. With ``bands`` set, that order is balanced: the values are
cut into ``bands`` equal bands by size, and each band's members are
spread evenly over the run, so every stretch of ``bands`` consecutive
draws holds about one of each band and no seed piles its longest prompts
into one part of the window.
"""
from __future__ import annotations

import json
import pathlib
from dataclasses import dataclass, field, replace
from statistics import NormalDist
from typing import List, Optional

import numpy as np

TRAFFIC_DIR = pathlib.Path(__file__).resolve().parent / "traffic"


def load_mix(name: str, directory: Optional[pathlib.Path] = None) -> dict:
    return json.loads(((directory or TRAFFIC_DIR) / f"{name}.json").read_text())


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """Independent generator per (seed, stream); any non-negative seed."""
    return np.random.default_rng([int(seed) & (2**63 - 1), stream])


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def order(v: np.ndarray, rng: np.random.Generator, bands: int = 0
          ) -> np.ndarray:
    """``v`` in an order drawn from ``rng``: a plain shuffle, or with
    ``bands`` a balanced one. Balanced: the values, sorted, are cut into
    ``bands`` equal bands; each band's members, shuffled, take evenly
    spaced places (member j of a band of m at (j + u) / m, u uniform in
    [0, 1)), and the places of all bands are merged."""
    if not bands:
        return rng.permutation(v)
    v = np.sort(v)
    band = np.arange(len(v)) * bands // len(v)
    key = np.empty(len(v))
    for b in range(bands):
        idx = np.flatnonzero(band == b)
        key[rng.permutation(idx)] = (np.arange(len(idx))
                                     + rng.random(len(idx))) / len(idx)
    return v[np.argsort(key, kind="stable")]


def sizes(dist: dict, n: int, rng: np.random.Generator,
          bands: int = 0) -> np.ndarray:
    """``n`` stratified draws of an integer length distribution, in an
    :func:`order` drawn from ``rng``.

    ``lognormal``: median, sigma; ``uniform``: min..max inclusive; both
    clipped to [min, max]."""
    q = _quantiles(n)
    kind = dist["dist"]
    if kind == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(x)) for x in q])
        v = dist["median"] * np.exp(dist["sigma"] * z)
    elif kind == "uniform":
        v = dist["min"] + q * (dist["max"] + 1 - dist["min"])
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    v = np.clip(np.floor(v), dist["min"], dist["max"]).astype(np.int64)
    return order(v, rng, bands)


def gaps(rate_per_s: float, n: int, rng: np.random.Generator,
         bands: int = 0) -> np.ndarray:
    """``n`` stratified exponential gaps of mean 1/rate, in an
    :func:`order` drawn from ``rng``."""
    return order(-np.log1p(-_quantiles(n)) / rate_per_s, rng, bands)


def zipf_ranks(count: int, exponent: float, n: int,
               rng: np.random.Generator, bands: int = 0) -> np.ndarray:
    """``n`` stratified draws of a Zipf law over ranks 0..count-1 (rank k
    with weight (k + 1) ** -exponent), in an :func:`order` drawn from
    ``rng``."""
    w = np.arange(1, count + 1, dtype=float) ** -exponent
    cdf = np.cumsum(w / w.sum())
    v = np.minimum(np.searchsorted(cdf, _quantiles(n)), count - 1)
    return order(v, rng, bands)


@dataclass
class Req:
    """One request as the harness sends it."""
    rid: int
    prompt: List[int]
    max_tokens: int
    due: float                       # seconds after the window opens
    session: int = -1                # sessions: which one, and its turn
    turn: int = 0


@dataclass
class Plan:
    """The generated traffic of one run: an open loop, every request due
    at a time fixed in advance. ``warm_s``: seconds the loop runs before
    the window opens (``bench/loop.py``)."""
    requests: List[Req] = field(default_factory=list)
    warm_s: float = 0.0

    def first(self) -> List[Req]:
        """The requests that are due before any has finished."""
        return list(self.requests)

    def after(self, req: Req, out: Optional[List[int]], t: float
              ) -> List[Req]:
        """The requests that become due when ``req`` finishes at window
        time ``t`` with the served tokens ``out`` (None: it was refused)."""
        return []


@dataclass
class ClosedPlan(Plan):
    """A closed loop: ``clients`` requests in flight. Each one that
    finishes or is refused makes the next of ``requests`` due at once."""
    clients: int = 1
    sent: int = 0                    # requests handed out so far

    def first(self) -> List[Req]:
        self.sent = min(self.clients, len(self.requests))
        return [replace(r, due=-self.warm_s)
                for r in self.requests[:self.sent]]

    def after(self, req, out, t):
        if self.sent == len(self.requests):
            return []
        self.sent += 1
        return [replace(self.requests[self.sent - 1], due=t)]


@dataclass
class Session:
    start: float                     # seconds after the window opens
    system: int                      # the system prompt it opens on
    users: List[List[int]]           # each turn's new user message
    answers: List[int]               # each turn's answer length
    thinks: List[float]              # seconds before each turn (first: 0)
    rids: List[int]                  # each turn's request id


@dataclass
class SessionsPlan(Plan):
    """Sessions: a turn is due ``thinks[k]`` after the previous turn's
    answer came back, and its prompt holds that prompt and answer."""
    systems: List[List[int]] = field(default_factory=list)
    sessions: List[Session] = field(default_factory=list)

    def first(self) -> List[Req]:
        return [Req(s.rids[0], self.systems[s.system] + s.users[0],
                    s.answers[0], s.start, i, 0)
                for i, s in enumerate(self.sessions)]

    def after(self, req, out, t):
        s, k = self.sessions[req.session], req.turn + 1
        if out is None or k == len(s.users):
            return []
        return [Req(s.rids[k], req.prompt + list(out) + s.users[k],
                    s.answers[k], t + s.thinks[k], req.session, k)]


def make_plan(mix: dict, seed: int, seconds: float, vocab: int,
              max_len: int) -> Plan:
    """The run's traffic for ``--seed`` and a window of ``seconds``."""
    kinds = {"open": _open, "closed": _closed, "sessions": _sessions}
    if mix["loop"] not in kinds:
        raise ValueError(f"unknown loop {mix['loop']!r}")
    return kinds[mix["loop"]](mix, seed, seconds, vocab, max_len)


def _open(mix: dict, seed: int, seconds: float, vocab: int,
          max_len: int) -> Plan:
    bands = int(mix.get("bands", 0))
    n = max(1, int(round(mix["rate_per_s"] * seconds)))
    due = np.cumsum(gaps(mix["rate_per_s"], n, rng_for(seed, 1), bands))
    p = sizes(mix["prompt"], n, rng_for(seed, 2), bands)
    o = sizes(mix["output"], n, rng_for(seed, 3), bands)
    ids = rng_for(seed, 0)
    plan = Plan()
    for i in range(n):
        _fits(mix, p[i], o[i], max_len)
        plan.requests.append(Req(i, ids.integers(0, vocab, size=int(p[i]))
                                 .tolist(), int(o[i]), float(due[i] - due[0])))
    return plan


def _closed(mix: dict, seed: int, seconds: float, vocab: int,
            max_len: int) -> ClosedPlan:
    bands = int(mix.get("bands", 0))
    c = int(mix["clients"])
    n = -(-int(mix["requests"]) // c)
    rp, ro = rng_for(seed, 2), rng_for(seed, 3)
    p = np.concatenate([sizes(mix["prompt"], c, rp, bands) for _ in range(n)])
    o = np.concatenate([sizes(mix["output"], c, ro, bands) for _ in range(n)])
    ids = rng_for(seed, 0)
    plan = ClosedPlan(clients=c, warm_s=float(mix.get("warm_s", 0)))
    for i in range(n * c):
        _fits(mix, p[i], o[i], max_len)
        plan.requests.append(Req(i, ids.integers(0, vocab, size=int(p[i]))
                                 .tolist(), int(o[i]), 0.0))
    return plan


def _sessions(mix: dict, seed: int, seconds: float, vocab: int,
              max_len: int) -> SessionsPlan:
    bands = int(mix.get("bands", 0))
    sy = mix["system"]
    n = max(1, int(round(mix["rate_per_s"] * seconds)))
    start = np.cumsum(gaps(mix["rate_per_s"], n, rng_for(seed, 1), bands))
    turns = sizes(mix["turns"], n, rng_for(seed, 4), bands)
    system = zipf_ranks(sy["count"], sy["zipf"], n, rng_for(seed, 7), bands)
    t = int(turns.sum())
    user = sizes(mix["user"], t, rng_for(seed, 5), bands)
    o = sizes(mix["output"], t, rng_for(seed, 3), bands)
    think = gaps(1.0 / mix["think_s"], t, rng_for(seed, 6), bands)
    ids = rng_for(seed, 0)
    plan = SessionsPlan(systems=[ids.integers(0, vocab,
                                              size=int(sy["tokens"])).tolist()
                                 for _ in range(sy["count"])])
    j = 0
    for i in range(n):
        k = range(j, j + int(turns[i]))
        j += int(turns[i])
        s = Session(float(start[i] - start[0]), int(system[i]), [], [], [],
                    [])
        length = int(sy["tokens"])
        for x in k:
            length += int(user[x])
            if length + o[x] > max_len:
                break
            s.users.append(ids.integers(0, vocab, size=int(user[x]))
                           .tolist())
            s.answers.append(int(o[x]))
            s.thinks.append(float(think[x]) if s.rids else 0.0)
            s.rids.append(x)
            length += int(o[x])
        if not s.rids:
            _fits(mix, length, o[k[0]], max_len)
        plan.sessions.append(s)
    return plan


def _fits(mix: dict, prompt: int, output: int, max_len: int) -> None:
    if prompt + output > max_len:
        raise ValueError(f"mix {mix.get('name')}: request of "
                         f"{prompt}+{output} tokens exceeds max_len {max_len}")
