"""One general traffic generator; each mix is a data file in ``bench/traffic``.

A mix file (``<mix>.json``) gives the arrivals and the length
distributions of an open loop: independent requests due on a Poisson
schedule (exponential gaps at ``rate_per_s``), each with a prompt of
random token ids and an answer length. Every seed gets the same multiset
of sizes and gaps (stratified quantiles of each distribution, drawn from
the mix's own parameters) and its own token ids: runs with different
seeds do the same amount of work in another order. With ``bands`` set,
that order is balanced: the values are cut into ``bands`` equal bands by
size, and each band's members are spread evenly over the run, so every
stretch of ``bands`` consecutive draws holds about one of each band and
no seed piles its longest prompts into one part of the window.
"""
from __future__ import annotations

import json
import pathlib
from dataclasses import dataclass, field
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


@dataclass
class Req:
    """One request as the harness sends it."""
    rid: int
    prompt: List[int]
    max_tokens: int
    due: float                       # seconds after the window opens


@dataclass
class Plan:
    """The generated traffic of one run."""
    requests: List[Req] = field(default_factory=list)


def make_plan(mix: dict, seed: int, seconds: float, vocab: int,
              max_len: int) -> Plan:
    """The run's traffic for ``--seed`` and a window of ``seconds``."""
    if mix["loop"] != "open":
        raise ValueError(f"unknown loop {mix['loop']!r}")
    bands = int(mix.get("bands", 0))
    n = max(1, int(round(mix["rate_per_s"] * seconds)))
    due = np.cumsum(gaps(mix["rate_per_s"], n, rng_for(seed, 1), bands))
    p = sizes(mix["prompt"], n, rng_for(seed, 2), bands)
    o = sizes(mix["output"], n, rng_for(seed, 3), bands)
    ids = rng_for(seed, 0)
    plan = Plan()
    for i in range(n):
        if p[i] + o[i] > max_len:
            raise ValueError(f"mix {mix.get('name')}: request of "
                             f"{p[i]}+{o[i]} tokens exceeds max_len {max_len}")
        plan.requests.append(Req(i, ids.integers(0, vocab, size=int(p[i]))
                                 .tolist(), int(o[i]), float(due[i] - due[0])))
    return plan
