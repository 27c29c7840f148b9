"""Property tests for the DSE building blocks: the blockwise flatten /
unflatten round-trip and the batched greedy allocator (the event schedule,
``greedy_event_schedule(...).replicas_at``) vs the scalar heap."""

import numpy as np
import pytest

pytest.importorskip("hypothesis")  # optional dev dep: pip install .[dev]
from hypothesis import given, settings, strategies as st

from repro.core.alloc.greedy import (
    greedy_allocate,
    greedy_event_schedule,
    proportional_allocate,
    proportional_allocate_batch,
)
from repro.core.cim import LayerSpec, NetworkSpec
from repro.core.cim.simulate import blockwise_units, split_block_dups

N_UNITS = 16
N_CONFIGS = 4


# ------------------------------------------------- flatten/unflatten round-trip
layer_st = st.tuples(
    st.sampled_from([1, 3, 5]),  # kernel
    st.integers(1, 64),  # cin
    st.integers(1, 300),  # cout
    st.integers(1, 32),  # out_hw
)
spec_st = st.lists(layer_st, min_size=1, max_size=6).map(
    lambda ls: NetworkSpec(
        "prop",
        tuple(
            LayerSpec(f"l{i}", k, cin, cout, hw) for i, (k, cin, cout, hw) in enumerate(ls)
        ),
    )
)


@given(spec_st, st.integers(0, 2**31 - 1))
@settings(max_examples=60, deadline=None)
def test_blockwise_units_split_round_trip(spec, seed):
    rng = np.random.default_rng(seed)
    means = [rng.uniform(8, 1024, l.n_blocks) for l in spec.layers]
    base_lat, cost = blockwise_units(spec, means)
    assert base_lat.shape == cost.shape == (spec.n_blocks,)
    # flat order is layers-then-blocks with the documented contents
    k = 0
    for i, layer in enumerate(spec.layers):
        for b in range(layer.n_blocks):
            assert base_lat[k] == means[i][b] * layer.patches_per_image
            assert cost[k] == layer.arrays_per_block
            k += 1
    # split is the exact inverse of the flattening
    flat = rng.integers(1, 50, spec.n_blocks)
    per_layer = split_block_dups(spec, flat)
    assert [d.size for d in per_layer] == [l.n_blocks for l in spec.layers]
    np.testing.assert_array_equal(np.concatenate(per_layer), flat)
    # and the split views are copies, not aliases into the flat vector
    per_layer[0][0] += 1
    assert flat[0] == per_layer[0][0] - 1


# ---------------------------------------------------- batched greedy == scalar
def _schedule_at(base, cost, budgets, initial_replicas=None):
    """The batched greedy: one event schedule answering every budget."""
    budgets = np.asarray(budgets, dtype=np.float64)
    return greedy_event_schedule(
        base, cost, float(budgets.max()), initial_replicas=initial_replicas
    ).replicas_at(budgets)


def _units(draw_ints, draw_floats):
    return st.tuples(
        st.lists(draw_floats, min_size=N_UNITS, max_size=N_UNITS),
        st.lists(draw_ints, min_size=N_UNITS, max_size=N_UNITS),
        st.lists(st.integers(0, 400), min_size=N_CONFIGS, max_size=N_CONFIGS),
    )


@given(_units(st.integers(1, 8), st.floats(1, 1e4)))
@settings(max_examples=60, deadline=None)
def test_greedy_batch_matches_scalar_loop(args):
    lats, costs, budgets = args
    base = np.asarray(lats)
    cost = np.asarray(costs, dtype=np.float64)
    budgets = np.asarray(budgets, dtype=np.float64)
    batch = _schedule_at(base, cost, budgets)
    for c, budget in enumerate(budgets):
        ref = greedy_allocate(base, cost, budget)
        np.testing.assert_array_equal(batch.replicas[c], ref.replicas)
        np.testing.assert_allclose(batch.spent[c], ref.spent, rtol=1e-9, atol=1e-9)
        np.testing.assert_allclose(batch.leftover[c], ref.leftover, rtol=1e-9, atol=1e-9)
        np.testing.assert_allclose(batch.latency[c], ref.latency, rtol=1e-12)


@given(
    _units(st.integers(1, 8), st.floats(1, 1e4)),
    st.lists(st.integers(1, 5), min_size=N_UNITS, max_size=N_UNITS),
)
@settings(max_examples=40, deadline=None)
def test_greedy_batch_warm_start_matches_scalar(args, r0):
    lats, costs, budgets = args
    base = np.asarray(lats)
    cost = np.asarray(costs, dtype=np.float64)
    r0 = np.asarray(r0, dtype=np.int64)
    batch = _schedule_at(base, cost, budgets, initial_replicas=r0)
    for c, budget in enumerate(budgets):
        ref = greedy_allocate(base, cost, float(budget), initial_replicas=r0)
        np.testing.assert_array_equal(batch.replicas[c], ref.replicas)
        # warm start invariant: replicas never drop below the starting point
        assert (batch.replicas[c] >= r0).all()


@given(
    st.integers(1, 20).flatmap(
        lambda n: st.tuples(
            st.lists(st.floats(0.1, 1e6), min_size=n, max_size=n),
            st.lists(st.integers(1, 8), min_size=n, max_size=n),
            st.lists(st.integers(-5, 300), min_size=1, max_size=6),
        )
    )
)
@settings(max_examples=60, deadline=None)
def test_proportional_batch_matches_scalar_loop(args):
    """Vectorized shares + lock-step top-up == scalar per-config routine,
    including argsort tie order and the budget<=0 early return."""
    weights, costs, budgets = args
    w = np.asarray(weights)
    cost = np.asarray(costs, dtype=np.float64)
    batch = proportional_allocate_batch(w, cost, np.asarray(budgets, dtype=np.float64))
    for c, budget in enumerate(budgets):
        ref = proportional_allocate(w, cost, float(budget))
        np.testing.assert_array_equal(batch.replicas[c], ref.replicas)
        np.testing.assert_allclose(batch.spent[c], ref.spent, rtol=1e-12, atol=1e-12)
        np.testing.assert_allclose(batch.leftover[c], ref.leftover, rtol=1e-12, atol=1e-12)


def test_greedy_batch_tie_breaking_matches_heap():
    """Equal latencies and power-of-two ratios — the adversarial tie cases
    for the event order — still match the scalar heap exactly."""
    base = np.array([4.0, 2.0, 2.0, 1.0, 1.0, 8.0])
    cost = np.array([1.0, 2.0, 1.0, 1.0, 3.0, 2.0])
    for budget in range(0, 30):
        batch = _schedule_at(base, cost, [float(budget)])
        ref = greedy_allocate(base, cost, float(budget))
        np.testing.assert_array_equal(batch.replicas[0], ref.replicas)


def test_greedy_batch_validation():
    with pytest.raises(ValueError, match="strictly positive"):
        _schedule_at([1.0, 2.0], [1.0, 0.0], [5.0])
    with pytest.raises(ValueError, match="base_latency"):
        _schedule_at([1.0, 2.0], [1.0, 1.0, 1.0], [5.0])
    with pytest.raises(ValueError, match="at least one replica"):
        _schedule_at([1.0, 2.0], [1.0, 1.0], [5.0], initial_replicas=[0, 1])


def test_greedy_batch_empty_units():
    res = _schedule_at(np.zeros(0), np.zeros(0), [7.0, 0.0])
    assert res.replicas.shape == (2, 0)
    np.testing.assert_array_equal(res.leftover, [7.0, 0.0])
    np.testing.assert_array_equal(res.makespan, [0.0, 0.0])  # (C,) like scalar
