"""Each mix's inputs are a function of the seed alone."""

from __future__ import annotations

import json

import numpy as np

from conftest import CHIP

import traffic

sweep = traffic.load_kind("sweep")
replay = traffic.load_kind("replay")
SWEEP = json.loads((CHIP / "traffic" / "sweep_fig8.json").read_text())
BIG = 2**31 + 977  # seeds may pass 32 signed bits


def test_sweep_order_repeats_with_the_seed():
    a = sweep.order(5248, BIG, 3)
    assert np.array_equal(a, sweep.order(5248, BIG, 3))
    assert not np.array_equal(a, sweep.order(5248, BIG + 1, 3))
    assert not np.array_equal(a, sweep.order(5248, BIG, 4))
    # every job holds each design of the grid once
    assert np.array_equal(np.sort(a), np.arange(5248))


def test_sweep_budgets_are_every_whole_pe_count_once():
    assert sweep.budgets(SWEEP, 86) == range(86, 216)
    assert sweep.budgets(SWEEP, 22) == range(22, 56)


def test_replay_arrivals_repeat_with_the_seed():
    rates = np.array([1e-5, 2e-5, 3e-5, 4e-5])
    t, s = replay.poisson_arrivals(rates, 8, BIG, 2)
    t2, s2 = replay.poisson_arrivals(rates, 8, BIG, 2)
    assert np.array_equal(t, t2) and s == s2
    t3, s3 = replay.poisson_arrivals(rates, 8, BIG + 1, 2)
    assert not np.array_equal(t, t3) and s != s3
    assert t.shape == (4, 8) and (np.diff(t, axis=1) > 0).all()
    # common gaps: every design sees the same trace, scaled to its rate
    assert np.allclose(t[0] * rates[0], t[3] * rates[3], rtol=1e-12)
