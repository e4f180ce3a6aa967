"""The one generator of serving traffic, driven by a traffic file.

A traffic file (``bench/traffic/<name>.json``) gives the arrival process and
rate, and the distributions of prompt and output lengths.  Every seed gets the
same *set* of sizes and inter-arrival gaps, taken at evenly spaced quantiles
of the stated distributions; the seed only orders them, and draws the prompt
tokens.  The order is stratified: each run of ``BANDS`` consecutive requests
takes one size from each of the ``BANDS`` quantile bands (and one gap from
each band of gaps), the seed choosing which.  So two seeds offer the same
work in another order, long prompts or answers never bunch up more than
twice in a row, and the spread between seeds is the system's, not the
generator's.

    {"arrivals": "poisson", "rate_per_s": 7.0,
     "prompt_len": {"dist": "lognormal", "median": 512, "sigma": 1.0,
                    "min": 32, "max": 4096},
     "output_len": {"dist": "lognormal", "median": 128, "sigma": 0.8,
                    "min": 16, "max": 512}}
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist

import numpy as np


@dataclass
class Req:
    rid: int
    arrival: float        # seconds after the window opens
    prompt: list[int]
    max_new: int


def quantile_lengths(spec: dict, n: int) -> list[int]:
    """``n`` lengths at the quantiles (i + 1/2) / n of ``spec``'s distribution."""
    kind = spec["dist"]
    if kind == "fixed":
        return [int(spec["value"])] * n
    if kind != "lognormal":
        raise ValueError(f"unknown length distribution {kind!r}")
    nd = NormalDist()
    lo, hi = int(spec["min"]), int(spec["max"])
    out = []
    for i in range(n):
        z = nd.inv_cdf((i + 0.5) / n)
        v = round(float(spec["median"]) * math.exp(float(spec["sigma"]) * z))
        out.append(min(max(v, lo), hi))
    return out


def quantile_gaps(traffic: dict, n: int) -> list[float]:
    kind = traffic["arrivals"]
    rate = float(traffic["rate_per_s"])
    if kind == "uniform":
        return [1.0 / rate] * n
    if kind != "poisson":
        raise ValueError(f"unknown arrival process {kind!r}")
    # exponential quantiles: the gaps of a Poisson process at this rate
    return [-math.log(1.0 - (i + 0.5) / n) / rate for i in range(n)]


BANDS = 4


def stratified_order(n: int, rng: np.random.Generator) -> list[int]:
    """A permutation of ``range(n)`` (ranks of sorted values), in groups of
    consecutive positions that each hold one rank from every one of the
    ``BANDS`` equal bands of ranks (the last band may run out first), the
    ranks chosen and placed by ``rng``."""
    width = -(-n // BANDS)
    bands = [list(rng.permutation(np.arange(b * width, min((b + 1) * width, n))))
             for b in range(BANDS)]
    out: list[int] = []
    for _ in range(width):
        group = [band.pop() for band in bands if band]
        out += [group[i] for i in rng.permutation(len(group))]
    return [int(i) for i in out]


def n_requests(traffic: dict, seconds: float) -> int:
    return max(1, round(float(traffic["rate_per_s"]) * seconds))


def serve_requests(traffic: dict, seed: int, seconds: float,
                   vocab: int) -> list[Req]:
    """The requests due in a window of ``seconds``, sorted by arrival."""
    n = n_requests(traffic, seconds)
    rng = np.random.default_rng(seed)
    prompts = quantile_lengths(traffic["prompt_len"], n)
    outs = quantile_lengths(traffic["output_len"], n)
    gaps = quantile_gaps(traffic, n)
    prompts = [prompts[i] for i in stratified_order(n, rng)]
    outs = [outs[i] for i in stratified_order(n, rng)]
    gaps = [gaps[i] for i in stratified_order(n, rng)]
    # the stratified gaps sum to about n / rate; stretch them so the last
    # request is due just inside the window whatever the seed
    scale = seconds * (n - 0.5) / n / sum(gaps)
    t, reqs = 0.0, []
    for i in range(n):
        t += gaps[i] * scale
        toks = rng.integers(2, vocab, size=prompts[i]).tolist()
        reqs.append(Req(rid=i, arrival=t, prompt=toks, max_new=outs[i]))
    return reqs
