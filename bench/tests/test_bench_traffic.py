"""The traffic generator: every seed offers the same work, in an order that
keeps long requests from bunching up."""

from __future__ import annotations

import numpy as np
import pytest

from benchlib.traffic import BANDS, quantile_lengths, serve_requests, stratified_order

CHAT = {"arrivals": "poisson", "rate_per_s": 1.3,
        "prompt_len": {"dist": "lognormal", "median": 512, "sigma": 1.0,
                       "min": 32, "max": 4096},
        "output_len": {"dist": "lognormal", "median": 128, "sigma": 0.8,
                       "min": 16, "max": 256}}


@pytest.mark.parametrize("n", [1, 3, 4, 13, 66])
def test_stratified_order_is_a_permutation_of_balanced_groups(n):
    order = stratified_order(n, np.random.default_rng(n))
    assert sorted(order) == list(range(n))
    width = -(-n // BANDS)
    sizes = [len(range(b * width, min((b + 1) * width, n))) for b in range(BANDS)]
    at = 0
    for k in range(width):          # group k: one rank from each band not yet spent
        live = [b for b in range(BANDS) if sizes[b] > k]
        assert sorted(i // width for i in order[at:at + len(live)]) == live
        at += len(live)
    assert at == n


def test_every_seed_offers_the_same_work():
    a = serve_requests(CHAT, 2**33 + 1, 51.0, 1000)
    b = serve_requests(CHAT, 7, 51.0, 1000)
    assert len(a) == len(b) == round(1.3 * 51)
    assert sorted(len(r.prompt) for r in a) == sorted(len(r.prompt) for r in b)
    assert sorted(r.max_new for r in a) == sorted(r.max_new for r in b)
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in b]
    assert a[-1].arrival == pytest.approx(b[-1].arrival) and a[-1].arrival < 51.0
    again = serve_requests(CHAT, 7, 51.0, 1000)
    assert [r.prompt for r in again] == [r.prompt for r in b]


def test_quantile_lengths_follow_the_stated_distribution():
    lens = quantile_lengths(CHAT["prompt_len"], 1001)
    assert lens[500] == 512 and min(lens) >= 32 and max(lens) == 4096
