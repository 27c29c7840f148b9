"""Property suite pinning the streaming latency sketch to the exact
reductions: for arbitrary positive latency populations the sketch quantile
must stay within ``SketchConfig.rel_error`` of ``percentile_kernel`` /
``np.percentile``, extremes and moments must be exact, and the sequential
fold must equal the vectorized reference count-for-count — the streaming
mirror of ``test_percentile_property.py``.

Standalone module: the tier-1 minimal CI image has no hypothesis, so the
whole file skips at import."""

import numpy as np
import pytest

hypothesis = pytest.importorskip("hypothesis")

from hypothesis import given, settings
from hypothesis import strategies as st
from hypothesis.extra import numpy as hnp

from repro.core.precision import x64
from repro.fabric.metrics import (
    LatencySketch,
    SketchConfig,
    percentile_kernel,
    sketch_init,
    sketch_update,
)

CFG = SketchConfig()

# in-range positive latencies: [2^min_exp, 2^(min_exp + n_octaves)) is the
# sketch's documented accuracy domain (cycles are >= 1 in practice)
_lat = st.floats(min_value=1.0, max_value=1e12, allow_nan=False, allow_infinity=False)
_arrays = hnp.arrays(
    dtype=np.float64, shape=st.integers(min_value=1, max_value=300), elements=_lat
)


@settings(max_examples=200, deadline=None)
@given(
    lat=_arrays,
    qs=st.lists(
        st.floats(min_value=0.0, max_value=100.0, allow_nan=False),
        min_size=1,
        max_size=6,
    ),
)
def test_quantiles_within_relative_bucket_error(lat, qs):
    sk = LatencySketch.from_latencies(lat, CFG)
    got = sk.percentiles(tuple(qs))
    want = percentile_kernel(np, lat, tuple(qs))
    np.testing.assert_array_equal(want, np.percentile(lat, qs))
    rel = np.abs(got - want) / np.maximum(np.abs(want), 1e-300)
    assert rel.max() <= CFG.rel_error


@settings(max_examples=200, deadline=None)
@given(lat=_arrays)
def test_extremes_and_mean_exact(lat):
    sk = LatencySketch.from_latencies(lat, CFG)
    assert sk.min == lat.min() and sk.max == lat.max()
    assert sk.percentiles((0.0, 100.0))[0] == lat.min()
    assert sk.percentiles((0.0, 100.0))[1] == lat.max()
    np.testing.assert_allclose(sk.mean, lat.mean(), rtol=1e-9)


@settings(max_examples=100, deadline=None)
@given(lat=_arrays)
def test_sequential_fold_equals_vectorized(lat):
    state = sketch_init(np, CFG)
    for v in lat:
        state = sketch_update(np, state, v, CFG)
    seq = LatencySketch.from_state(CFG, state)
    ref = LatencySketch.from_latencies(lat, CFG)
    np.testing.assert_array_equal(seq.counts, ref.counts)
    assert seq.n == ref.n and seq.min == ref.min and seq.max == ref.max


@settings(max_examples=100, deadline=None)
@given(value=_lat, n=st.integers(min_value=1, max_value=50))
def test_all_ties_stay_within_one_bucket(value, n):
    lat = np.full(n, value)
    got = LatencySketch.from_latencies(lat, CFG).percentiles((0.0, 50.0, 99.9, 100.0))
    assert got[0] == value and got[3] == value  # extremes exact
    rel = np.abs(got - value) / value
    assert rel.max() <= CFG.rel_error


@settings(max_examples=100, deadline=None)
@given(
    a=_arrays,
    b=_arrays,
    q=st.floats(min_value=0.0, max_value=100.0, allow_nan=False),
)
def test_merge_quantiles_match_pooled_population(a, b, q):
    merged = LatencySketch.from_latencies(a, CFG).merge(
        LatencySketch.from_latencies(b, CFG)
    )
    pooled = np.concatenate([a, b])
    got = merged.percentiles((q,))[0]
    want = np.percentile(pooled, q)
    assert abs(got - want) / max(abs(want), 1e-300) <= CFG.rel_error


def test_jit_fold_matches_numpy_on_representative_population():
    """Cross-``xp`` half of the pin (hypothesis drives numpy; the jit scan
    fold is pinned bit-identical on one representative draw)."""
    jax = pytest.importorskip("jax")
    import jax.numpy as jnp

    rng = np.random.default_rng(0)
    lat = rng.lognormal(10, 1.5, 513)
    state = sketch_init(np, CFG)
    for v in lat:
        state = sketch_update(np, state, v, CFG)

    def step(s, v):
        return sketch_update(jnp, s, v, CFG), None

    with x64():
        out, _ = jax.jit(lambda s, x: jax.lax.scan(step, s, x))(
            tuple(jnp.asarray(a) for a in sketch_init(jnp, CFG)), jnp.asarray(lat)
        )
    for a, b in zip(state, out):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
