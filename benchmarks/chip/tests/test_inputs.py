"""Each mix's inputs are a function of the seed alone."""

from __future__ import annotations

import json
from types import SimpleNamespace

import numpy as np
import pytest
from conftest import CHIP, TINY_SWEEP

import bench
import reference
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


@pytest.mark.parametrize("name, designs", [("resnet18", 5248), ("vgg11", 4352)])
def test_sweep_grid_without_pe_points_is_every_whole_count(name, designs):
    """Each variant's grid is the range of whole PE counts, ``sweep_fig8``'s
    designs a job as many as ever."""
    config = json.loads((CHIP / "configs" / f"{name}.json").read_text())
    base = reference.Array(**config["array"])
    total = 0
    for rows in SWEEP["rows"]:
        for adc in SWEEP["adc_bits"]:
            m = reference.min_pes(config, base.variant(rows, adc))
            grid = sweep.budgets(SWEEP, m)
            assert grid == range(m, int(np.ceil(2.5 * m)) + 1)
            total += len(grid) * len(SWEEP["policies"])
    assert total == designs


@pytest.mark.parametrize("points, lo, hi, want", [(5, 86, 215, 5), (2, 86, 215, 2), (10, 4, 10, 7)])
def test_sweep_grid_with_pe_points_is_spaced_with_both_ends(points, lo, hi, want):
    """That many distinct counts, fewer where rounding merges them, from
    the same two ends as the whole range."""
    grid = sweep.budgets({**SWEEP, "pe_points": points}, lo)
    assert len(grid) == want == len(set(grid))
    assert grid[0] == lo and grid[-1] == hi and grid == sorted(grid)
    assert set(grid) <= set(sweep.budgets(SWEEP, lo))


@pytest.mark.parametrize("points", [1, 2.5, "5"])
def test_sweep_grid_refuses_a_pe_points_that_is_not_a_count(points):
    with pytest.raises(SystemExit, match="pe_points"):
        sweep.budgets({**SWEEP, "pe_points": points}, 86)


def test_spaced_grid_is_what_the_job_runs_counts_and_checks():
    """The job's designs, its eval families (which the roofline reads) and
    the designs its comparison draws all come from the spaced grid."""
    vgg11 = json.loads((CHIP / "configs" / "vgg11.json").read_text())
    mix = {**TINY_SWEEP, "pe_multiplier": [1.0, 2.5], "pe_points": 3}
    job = traffic.make(vgg11, mix, BIG)
    assert [list(b) for b in job.budgets] == [[71, 124, 178], [71, 124, 178]]
    assert sum(n for n, _ in job.families(1)) == len(job.points) == 2 * 4 * 3
    inp = job.inputs(0)
    out = job.run(inp)
    assert job.work(out) == len(job.points) and job.sound(out) == 0
    numbers, limits = traffic.compare(job, [(0, inp, out)])
    assert numbers["checked"] == 2 * 4 * mix["check_configs_per_variant_policy"]
    assert all(numbers[k] <= lim for k, lim in limits.items()), numbers
    ctx = SimpleNamespace(trace=SimpleNamespace(modules={"jit_fused": 1e-3}), job=job, traced_jobs=1,
                          peaks={"hbm_bytes_per_s": 1e9}, config=vgg11)
    share = bench.reader("sweep.eval_roofline")(ctx)
    least = sum(8 * n * (r + 1 + 2 * 8) for n, r in job.families(1))
    assert share == pytest.approx(100.0 * least / 1e9 / 1e-3)


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
