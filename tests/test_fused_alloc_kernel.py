"""Property suites for the two allocation fast paths the fused DSE
pipeline leans on:

  * ``greedy_event_schedule`` — the static grant-event table must answer
    EVERY budget with replica vectors element-wise identical to the scalar
    heap greedy (``greedy_allocate``) and the lock-step batch kernel
    (``greedy_allocate_batch``), warm starts and ties included.  The
    schedule's exactness argument (priorities are the heap's own float64
    quotients; integer costs make prefix sums exact; ``searchsorted`` IS
    the stopping rule) lives in ``core/alloc/greedy.py`` — these
    properties are its enforcement.
  * ``kernels.fused_alloc_eval`` — the in-kernel greedy must return the
    same replicas as ``greedy_allocate_batch`` on random profiles (it
    calls the same kernel body; interpret mode, float64).

Hypothesis draws integer-valued bases from a SMALL pool so priority ties
across units are common — the regime where heap tie-order (lowest unit
index first) is actually observable.  The no-hypothesis (minimal-env)
deterministic counterparts live in ``test_alloc_warmstart.py`` and
``test_kernels.py``.
"""

import numpy as np
import pytest

pytest.importorskip("hypothesis")  # optional dev dep: pip install .[dev]
from hypothesis import given, settings, strategies as st

from repro.core.alloc.greedy import (
    greedy_allocate,
    greedy_allocate_batch,
    greedy_event_schedule,
)


@st.composite
def _problem(draw, max_units=8):
    n = draw(st.integers(1, max_units))
    # small integer pools force cross-unit priority ties
    base = np.array(
        draw(st.lists(st.integers(1, 12), min_size=n, max_size=n)), dtype=np.float64
    )
    cost = np.array(
        draw(st.lists(st.integers(1, 4), min_size=n, max_size=n)), dtype=np.float64
    )
    warm = draw(st.booleans())
    r0 = (
        np.array(
            draw(st.lists(st.integers(1, 3), min_size=n, max_size=n)),
            dtype=np.int64,
        )
        if warm
        else None
    )
    budgets = np.array(
        draw(st.lists(st.integers(0, 40), min_size=1, max_size=6)),
        dtype=np.float64,
    )
    return base, cost, r0, budgets


# --------------------------------------------------- event schedule == heap
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


@given(_problem())
@settings(max_examples=30, deadline=None)
def test_event_schedule_matches_batch_kernel(problem):
    base, cost, r0, budgets = problem
    sched = greedy_event_schedule(
        base, cost, float(budgets.max()), initial_replicas=r0
    )
    got = sched.replicas_at(budgets)
    want = greedy_allocate_batch(base, cost, budgets, initial_replicas=r0)
    np.testing.assert_array_equal(got.replicas, want.replicas)
    np.testing.assert_array_equal(got.leftover, want.leftover)


# ------------------------------------------- in-kernel greedy == batch kernel
@given(_problem(max_units=5), st.integers(0, 2 ** 31 - 1))
@settings(max_examples=10, deadline=None)
def test_fused_kernel_greedy_matches_batch(problem, seed):
    from repro.core.precision import x64

    from repro.kernels.fused_alloc_eval import fused_alloc_eval

    base, cost, r0, budgets = problem
    n = base.size
    c = budgets.size
    rng = np.random.default_rng(seed)
    a = rng.integers(1, 3)
    l, b = rng.integers(1, 4), rng.integers(1, 4)
    bases = np.broadcast_to(base, (a, n)).copy()
    owner = rng.integers(0, n, size=(l, b))
    umap = np.zeros((n, l, b))
    umap[owner, np.arange(l)[:, None], np.arange(b)[None, :]] = 1.0
    v = 2 * a
    banks = (
        rng.integers(1, 50, size=(v, l, b)).astype(np.float64),
        rng.integers(50, 99, size=(v, l, b)).astype(np.float64),
        rng.integers(1, 50, size=(v, l)).astype(np.float64),
        rng.integers(50, 99, size=(v, l)).astype(np.float64),
        rng.integers(1, 50, size=(v, l)).astype(np.float64),
    )
    a_idx = rng.integers(0, a, size=c).astype(np.int32)
    r0_b = np.ones((c, n)) if r0 is None else np.broadcast_to(r0, (c, n)).copy()
    with x64():
        *_, r, rem = fused_alloc_eval(
            bases, cost, umap, banks, np.ones((l, b), bool),
            np.ones(l), np.ones(l), np.ones(l),
            budgets, a_idx, a_idx.copy(),
            rng.integers(0, 2, size=c).astype(bool), r0_b,
            block_configs=max(1, c // 2),
        )
    want = greedy_allocate_batch(base, cost, budgets, initial_replicas=r0_b)
    np.testing.assert_array_equal(np.asarray(r), want.replicas)
    np.testing.assert_array_equal(np.asarray(rem), want.leftover)
