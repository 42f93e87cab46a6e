"""The one traffic generator.  A mix is a data file under ``traffic/``;
this module turns its parameters and a seed into requests.

Every seed gets the same prompt lengths, output lengths and inter-arrival
gaps (stratified quantiles of the stated distributions, in one fixed
shuffled order); the seed draws the token ids (and the harness draws the
weights from it).  So two seeds do the same work on the same schedule.
With about a dozen requests in a window, the arrangement alone moved the
median time to first token by 45% from seed to seed, against 0.5-10%
between runs of one seed, so the arrangement is not left to the seed.

Mix keys:
  loop            "open": Poisson arrivals on a fixed schedule
  rate_req_s      the arrival rate
  prompt, output  {"dist": "lognormal", "median", "sigma", "min", "max"}
  lead_s          seconds of arrivals before the window opens, so that it
                  opens on a loaded engine; about one mean service time.
                  They are served and never counted
  drain_s         seconds after the window for the window's requests to
                  finish; a request unfinished by then has failed
"""
from __future__ import annotations

import math
import statistics
from dataclasses import dataclass
from typing import Dict, List

import numpy as np

_NORMAL = statistics.NormalDist()


@dataclass
class Request:
    index: int
    arrival_s: float          # scheduled offset from the window start;
                              # below 0 for the lead-in
    prompt: np.ndarray        # int32 token ids
    max_new_tokens: int


def quantile_lengths(spec: Dict, n: int) -> np.ndarray:
    """``n`` stratified draws from the length distribution ``spec``: the
    (i + 0.5) / n quantiles, rounded and clipped to [min, max]."""
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    z = np.array([_NORMAL.inv_cdf((i + 0.5) / n) for i in range(n)])
    x = np.exp(math.log(spec["median"]) + spec["sigma"] * z)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def exponential_gaps(rate: float, n: int) -> np.ndarray:
    """``n`` stratified inter-arrival gaps of a Poisson process, scaled so
    their mean is exactly 1 / rate."""
    g = -np.log1p(-(np.arange(n) + 0.5) / n)
    return g / g.mean() / rate


#: The fixed arrangement's generator.
ORDER_SEED = 20231118


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) & (2**64 - 1), stream])


def open_loop(mix: Dict, seed: int, seconds: float, vocab: int
              ) -> List[Request]:
    """The lead-in's arrivals, then the window's: floor(rate * seconds)
    requests, the first at 0 and the rest after the fixed arrangement of
    the gaps.  The lead-in is floor(rate * lead_s) requests of its own
    stratified sample, the last one gap before 0; their indices follow
    the window's."""
    rate = mix["rate_req_s"]
    n = max(1, int(rate * seconds))
    order = _rng(ORDER_SEED, 0)
    gaps = order.permutation(exponential_gaps(rate, n))
    arrivals = np.concatenate([[0.0], np.cumsum(gaps[:-1])])
    window = _build(mix, seed, arrivals, n, vocab, order, ids_stream=10_000)
    k = int(rate * mix.get("lead_s", 0.0))
    if k == 0:
        return window
    order = _rng(ORDER_SEED, 1)
    lead = -np.cumsum(order.permutation(exponential_gaps(rate, k)))[::-1]
    early = _build(mix, seed, lead, k, vocab, order, ids_stream=10_001)
    for r in early:
        r.index += n
    return early + window


def lead_s(mix: Dict) -> float:
    """Seconds from the first arrival of the lead-in to the window's
    start (0 without a lead-in)."""
    return int(mix["rate_req_s"] * mix.get("lead_s", 0.0)) \
        / mix["rate_req_s"]


def requests(mix: Dict, seed: int, seconds: float, vocab: int
             ) -> List[Request]:
    """The mix's requests for a window of ``seconds``, in sending order."""
    if mix["loop"] != "open":
        raise ValueError(f"unknown loop {mix['loop']!r}")
    return open_loop(mix, seed, seconds, vocab)


def _build(mix, seed, arrivals, n, vocab, order, ids_stream: int
           ) -> List[Request]:
    plen = order.permutation(quantile_lengths(mix["prompt"], n))
    olen = order.permutation(quantile_lengths(mix["output"], n))
    ids = _rng(seed, ids_stream)
    return [Request(i, float(arrivals[i]),
                    ids.integers(1, vocab, int(plen[i])).astype(np.int32),
                    int(olen[i])) for i in range(n)]
