"""greedy_allocate warm-start (initial_replicas=) invariants +
proportional_allocate edge cases — the online re-allocation path — plus
the ``greedy_event_schedule`` exactness contract (the static grant-event
table the fused DSE pipeline replays instead of re-running the greedy).

These must run in the minimal environment: the one hypothesis property at
the end of the event-schedule section is defined only when hypothesis is
installed."""

import numpy as np
import pytest

from repro.core.alloc.greedy import (
    greedy_allocate,
    greedy_event_schedule,
    proportional_allocate,
)


def _units(seed=0, n=24):
    rng = np.random.default_rng(seed)
    lat = rng.exponential(100.0, size=n) + 1.0
    cost = rng.integers(1, 9, size=n).astype(np.float64)
    return lat, cost


# ------------------------------------------------------------- warm start
def test_warm_start_never_decreases_replicas():
    lat, cost = _units()
    init = np.ones(lat.size, dtype=np.int64)
    init[::3] = 4
    res = greedy_allocate(lat, cost, budget=60.0, initial_replicas=init)
    assert np.all(res.replicas >= init)
    assert res.spent <= 60.0 + 1e-9
    assert res.spent + res.leftover == pytest.approx(60.0)


def test_warm_start_equals_cold_start_from_ones():
    lat, cost = _units(1)
    cold = greedy_allocate(lat, cost, budget=100.0)
    warm = greedy_allocate(
        lat, cost, budget=100.0, initial_replicas=np.ones(lat.size, dtype=np.int64)
    )
    np.testing.assert_array_equal(cold.replicas, warm.replicas)


def test_warm_start_same_stopping_rule():
    """The loop must stop exactly when the *current slowest* unit cannot be
    afforded — not skip to a cheaper faster unit."""
    lat, cost = _units(2)
    init = 1 + (np.arange(lat.size) % 3).astype(np.int64)
    res = greedy_allocate(lat, cost, budget=35.0, initial_replicas=init)
    slowest = int(np.argmax(res.latency))
    assert cost[slowest] > res.leftover


def test_warm_start_zero_budget_is_identity():
    lat, cost = _units(3)
    init = np.full(lat.size, 2, dtype=np.int64)
    res = greedy_allocate(lat, cost, budget=0.0, initial_replicas=init)
    np.testing.assert_array_equal(res.replicas, init)
    assert res.spent == 0.0
    np.testing.assert_allclose(res.latency, lat / init)


def test_warm_start_reduces_makespan_when_affordable():
    lat, cost = _units(4)
    init = np.ones(lat.size, dtype=np.int64)
    before = (lat / init).max()
    res = greedy_allocate(lat, cost, budget=200.0, initial_replicas=init)
    assert res.makespan < before


def test_warm_start_rejects_invalid_initials():
    lat, cost = _units(5)
    bad = np.ones(lat.size, dtype=np.int64)
    bad[0] = 0
    with pytest.raises(ValueError, match="at least one replica"):
        greedy_allocate(lat, cost, budget=10.0, initial_replicas=bad)


def test_incremental_warm_start_tracks_cold_total():
    """Spending a budget in two warm-started installments can't beat the
    greedy one-shot makespan, and lands within one replica-step of it."""
    lat, cost = _units(6)
    one_shot = greedy_allocate(lat, cost, budget=120.0)
    first = greedy_allocate(lat, cost, budget=60.0)
    second = greedy_allocate(
        lat, cost, budget=60.0 + first.leftover, initial_replicas=first.replicas
    )
    assert second.makespan >= one_shot.makespan - 1e-9
    assert np.all(second.replicas >= first.replicas)


# ------------------------------------------------------- event schedule
def test_event_schedule_matches_heap_randomized():
    """The schedule replays the scalar heap greedy exactly — replicas,
    spent, leftover — across random integer problems, warm starts and
    budget-0 edges included (the hypothesis suite widens this when the
    dev deps are installed)."""
    rng = np.random.default_rng(11)
    for trial in range(40):
        n = int(rng.integers(1, 10))
        base = rng.integers(1, 12, size=n).astype(np.float64)
        cost = rng.integers(1, 4, size=n).astype(np.float64)
        r0 = (
            rng.integers(1, 3, size=n).astype(np.int64)
            if trial % 2
            else None
        )
        budgets = rng.integers(0, 40, size=5).astype(np.float64)
        sched = greedy_event_schedule(
            base, cost, float(budgets.max()), initial_replicas=r0
        )
        got = sched.replicas_at(budgets)
        for i, b in enumerate(budgets):
            want = greedy_allocate(base, cost, float(b), initial_replicas=r0)
            np.testing.assert_array_equal(
                got.replicas[i], want.replicas, err_msg=f"trial {trial} b {b}"
            )
            assert got.spent[i] == want.spent
            assert got.leftover[i] == want.leftover


def test_event_schedule_tie_order_matches_heap():
    """Equal priorities must grant the LOWEST unit index first — heapq
    tuple order — observable when the budget cuts inside a tie run."""
    base = np.array([6.0, 6.0, 6.0])
    cost = np.array([2.0, 2.0, 2.0])
    for b in (2.0, 4.0):  # budget affords 1 (then 2) of the 3 tied grants
        want = greedy_allocate(base, cost, b)
        got = greedy_event_schedule(base, cost, b).replicas_at([b])
        np.testing.assert_array_equal(got.replicas[0], want.replicas)


def test_event_schedule_rejects_uncovered_budget():
    sched = greedy_event_schedule(np.array([5.0, 3.0]), np.array([1.0, 1.0]), 10.0)
    with pytest.raises(ValueError, match="coverage"):
        sched.replicas_at(np.array([11.0]))


def test_event_schedule_rejects_fractional_inputs():
    with pytest.raises(ValueError, match="integral"):
        greedy_event_schedule(np.array([5.0]), np.array([1.5]), 10.0)
    sched = greedy_event_schedule(np.array([5.0]), np.array([1.0]), 10.0)
    with pytest.raises(ValueError, match="integral"):
        sched.replicas_at(np.array([2.5]))


def test_event_schedule_zero_and_tiny_budgets():
    base = np.array([9.0, 4.0])
    cost = np.array([3.0, 5.0])
    sched = greedy_event_schedule(base, cost, 2.0)  # < min cost: empty table
    assert len(sched) == 0
    got = sched.replicas_at(np.array([0.0, 2.0]))
    np.testing.assert_array_equal(got.replicas, np.ones((2, 2), dtype=np.int64))
    np.testing.assert_array_equal(got.spent, [0.0, 0.0])
    np.testing.assert_array_equal(got.leftover, [0.0, 2.0])


def test_event_schedule_budget_zero_keeps_warm_start():
    """Budget 0 returns a multi-unit warm start untouched, with nothing
    spent or left over — from an empty table and from a full one."""
    rng = np.random.default_rng(5)
    for trial in range(20):
        n = int(rng.integers(2, 16))
        base = rng.integers(1, 500, size=n).astype(np.float64)
        cost = rng.integers(1, 6, size=n).astype(np.float64)
        r0 = rng.integers(1, 6, size=n).astype(np.int64)
        for W in (0.0, float(rng.integers(10, 200))):
            sched = greedy_event_schedule(base, cost, W, initial_replicas=r0)
            got = sched.replicas_at(np.zeros(3))
            np.testing.assert_array_equal(
                got.replicas, np.broadcast_to(r0, (3, n)), err_msg=f"trial {trial}"
            )
            np.testing.assert_array_equal(got.spent, np.zeros(3))
            np.testing.assert_array_equal(got.leftover, np.zeros(3))


def _replicas_at_walk(sched, budgets):
    """The incremental walk ``replicas_at`` answered with before its
    segmented count: one ``bincount`` and one snapshot per distinct stop."""
    b = np.atleast_1d(np.asarray(budgets, dtype=np.float64))
    n = sched.n_units
    m = np.searchsorted(sched.cum_cost, b, side="right")
    uniq, inv = np.unique(m, return_inverse=True)
    snaps = np.empty((uniq.size, n), dtype=np.int64)
    counts = sched.r0.copy()
    prev = 0
    for j, stop in enumerate(uniq):
        if stop > prev:
            counts = counts + np.bincount(sched.unit[prev:stop], minlength=n)
            prev = int(stop)
        snaps[j] = counts
    replicas = snaps[inv]
    spent = (
        np.where(m > 0, sched.cum_cost[np.maximum(m - 1, 0)], 0.0)
        if len(sched)
        else np.zeros(b.size)
    )
    return replicas, spent, b - spent, sched.base / replicas


def _walk_case(case, rng):
    """(schedule, budgets) for one edge of the segmented count."""
    n = int(rng.integers(1, 30))
    base = rng.integers(1, 200, size=n).astype(np.float64)
    cost = rng.integers(1, 6, size=n).astype(np.float64)
    W = float(rng.integers(20, 300))
    r0 = rng.integers(1, 4, size=n) if case == "warm_start" else None
    if case == "empty_table":
        W = float(cost.min()) - 1.0
    sched = greedy_event_schedule(base, cost, W, initial_replicas=r0)
    budgets = rng.integers(0, int(W) + 1, size=int(rng.integers(1, 40)))
    if case == "repeated_budgets":
        budgets = rng.choice(budgets[:3], size=50)
    elif case == "budget_on_cum_cost" and len(sched):
        budgets = rng.choice(sched.cum_cost, size=20)
    elif case == "budget_zero":
        budgets = np.concatenate([[0], budgets, [0]])
    return sched, budgets.astype(np.float64)


_WALK_CASES = (
    "cold", "warm_start", "repeated_budgets", "budget_on_cum_cost",
    "budget_zero", "empty_table",
)


@pytest.mark.parametrize("case", _WALK_CASES)
def test_replicas_at_equals_incremental_walk(case):
    """The segmented count answers every budget exactly as the per-stop
    walk does: replicas, spent, remaining and latency, bit for bit."""
    rng = np.random.default_rng(_WALK_CASES.index(case))
    for trial in range(25):
        sched, budgets = _walk_case(case, rng)
        if case == "empty_table":
            assert len(sched) == 0
        got = sched.replicas_at(budgets)
        want = _replicas_at_walk(sched, budgets)
        for name, g, w in zip(
            ("replicas", "spent", "leftover", "latency"),
            (got.replicas, got.spent, got.leftover, got.latency),
            want,
        ):
            assert g.dtype == w.dtype, f"trial {trial} {name}"
            np.testing.assert_array_equal(g, w, err_msg=f"trial {trial} {name}")


try:
    from hypothesis import given, settings, strategies as st

    @st.composite
    def _problem(draw, max_units=8):
        n = draw(st.integers(1, max_units))
        # small integer pools force cross-unit priority ties, the regime
        # where heap tie order (lowest unit index first) is observable
        base = np.array(
            draw(st.lists(st.integers(1, 12), min_size=n, max_size=n)),
            dtype=np.float64,
        )
        cost = np.array(
            draw(st.lists(st.integers(1, 4), min_size=n, max_size=n)),
            dtype=np.float64,
        )
        r0 = (
            np.array(
                draw(st.lists(st.integers(1, 3), min_size=n, max_size=n)),
                dtype=np.int64,
            )
            if draw(st.booleans())
            else None
        )
        budgets = np.array(
            draw(st.lists(st.integers(0, 40), min_size=1, max_size=6)),
            dtype=np.float64,
        )
        return base, cost, r0, budgets

    @given(_problem())
    @settings(max_examples=60, deadline=None)
    def test_event_schedule_matches_scalar_heap(problem):
        base, cost, r0, budgets = problem
        sched = greedy_event_schedule(
            base, cost, float(budgets.max()), initial_replicas=r0
        )
        got = sched.replicas_at(budgets)
        for i, b in enumerate(budgets):
            want = greedy_allocate(base, cost, float(b), initial_replicas=r0)
            np.testing.assert_array_equal(
                got.replicas[i], want.replicas, err_msg=f"budget {b}"
            )
            assert got.spent[i] == want.spent
            assert got.leftover[i] == want.leftover

except ImportError:  # pragma: no cover - optional dev dep
    pass


# ------------------------------------------------------- proportional edges
def test_proportional_zero_budget():
    w = np.array([5.0, 1.0, 3.0])
    c = np.array([2.0, 2.0, 2.0])
    res = proportional_allocate(w, c, budget=0.0)
    np.testing.assert_array_equal(res.replicas, [1, 1, 1])
    assert res.spent == 0.0 and res.leftover == 0.0


def test_proportional_negative_budget_clamps_to_ones():
    w = np.array([5.0, 1.0])
    res = proportional_allocate(w, np.array([1.0, 1.0]), budget=-7.0)
    np.testing.assert_array_equal(res.replicas, [1, 1])


def test_proportional_single_unit():
    res = proportional_allocate(np.array([10.0]), np.array([3.0]), budget=10.0)
    # floor(10/3) = 3 extra, remainder 1 < 3 -> no top-up
    np.testing.assert_array_equal(res.replicas, [4])
    assert res.spent == pytest.approx(9.0)
    assert res.leftover == pytest.approx(1.0)


def test_proportional_empty():
    res = proportional_allocate(np.array([]), np.array([]), budget=5.0)
    assert res.replicas.size == 0
    assert res.makespan == 0.0


def test_proportional_never_overspends():
    rng = np.random.default_rng(7)
    for _ in range(20):
        n = rng.integers(1, 12)
        w = rng.exponential(1.0, n) + 1e-3
        c = rng.integers(1, 6, n).astype(np.float64)
        b = float(rng.integers(0, 40))
        res = proportional_allocate(w, c, b)
        assert res.spent <= b + 1e-9
        assert np.all(res.replicas >= 1)
        assert res.spent + res.leftover == pytest.approx(b)
