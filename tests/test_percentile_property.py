"""Property test pinning ``fabric.metrics.percentile_kernel`` to
``np.percentile``: the one shared reduction the jitted fabric kernel and the
scalar accounting path both use must agree with the numpy reference on
arbitrary shapes and percentile levels, including the degenerate cases
(single element, all-ties) where interpolation definitions diverge.

Standalone module: the tier-1 minimal CI image has no hypothesis, so the
whole file skips at import."""

import numpy as np
import pytest

hypothesis = pytest.importorskip("hypothesis")

from hypothesis import given, settings
from hypothesis import strategies as st
from hypothesis.extra import numpy as hnp

from repro.core.precision import x64
from repro.fabric.metrics import percentile_kernel

_floats = st.floats(
    min_value=-1e12, max_value=1e12, allow_nan=False, allow_infinity=False
)


@settings(max_examples=200, deadline=None)
@given(
    lat=hnp.arrays(
        dtype=np.float64, shape=st.integers(min_value=1, max_value=400),
        elements=_floats,
    ),
    qs=st.lists(
        st.floats(min_value=0.0, max_value=100.0, allow_nan=False),
        min_size=1,
        max_size=6,
    ),
)
def test_matches_numpy_percentile(lat, qs):
    got = percentile_kernel(np, lat, qs)
    want = np.percentile(lat, qs)
    np.testing.assert_array_equal(got, want)


@settings(max_examples=100, deadline=None)
@given(value=_floats, n=st.integers(min_value=1, max_value=50))
def test_all_ties_collapse_to_the_value(value, n):
    lat = np.full(n, value)
    got = percentile_kernel(np, lat, (0.0, 50.0, 99.9, 100.0))
    np.testing.assert_array_equal(got, np.full(4, value))


@settings(max_examples=100, deadline=None)
@given(value=_floats, q=st.floats(min_value=0.0, max_value=100.0, allow_nan=False))
def test_single_element_is_that_element(value, q):
    got = percentile_kernel(np, np.array([value]), (q,))
    np.testing.assert_array_equal(got, np.array([value]))


def test_jax_path_matches_numpy_reference():
    """The same kernel under jit (float64) equals the numpy evaluation on a
    representative latency vector — the cross-``xp`` half of the pin."""
    jax = pytest.importorskip("jax")
    import jax.numpy as jnp

    rng = np.random.default_rng(0)
    lat = rng.gamma(2.0, 1e4, size=257)
    qs = (0.0, 12.5, 50.0, 95.0, 99.0, 100.0)
    with x64():
        got = np.asarray(
            jax.jit(lambda x: percentile_kernel(jnp, x, qs))(jnp.asarray(lat))
        )
    np.testing.assert_allclose(got, np.percentile(lat, qs), rtol=1e-12)
