"""The shared in-kernel percentile helper (fabric/metrics.percentile_kernel)
is the single implementation behind latency_stats AND the virtual-time
kernel's in-jit reduction — pinned here on the edge cases that historically
diverge between scalar and batch paths: empty batch, a single request, and
all-tied latencies.
"""

import numpy as np
import pytest

from repro.fabric.metrics import latency_stats, percentile_kernel

QS = (50.0, 95.0, 99.0)


def _jnp():
    jax = pytest.importorskip("jax")
    from repro.core.precision import x64

    return jax, x64


def test_single_request_scalar_equals_batch():
    lat = np.asarray([1234.5])
    ref = percentile_kernel(np, lat, QS)
    np.testing.assert_array_equal(ref, [1234.5] * 3)
    jax, x64 = _jnp()
    import jax.numpy as jnp

    with x64():
        out = np.asarray(jax.jit(lambda x: percentile_kernel(jnp, x, QS))(lat))
    np.testing.assert_array_equal(out, ref)


def test_all_ties_scalar_equals_batch():
    lat = np.full(37, 42.0)
    ref = percentile_kernel(np, lat, QS)
    np.testing.assert_array_equal(ref, [42.0] * 3)
    jax, x64 = _jnp()
    import jax.numpy as jnp

    with x64():
        out = np.asarray(jax.jit(lambda x: percentile_kernel(jnp, x, QS))(lat))
    np.testing.assert_array_equal(out, ref)


def test_general_batch_matches_scalar_bitwise():
    rng = np.random.default_rng(0)
    lat = rng.exponential(100.0, size=501)
    ref = percentile_kernel(np, lat, QS)
    np.testing.assert_array_equal(ref, np.percentile(lat, [50, 95, 99]))
    jax, x64 = _jnp()
    import jax.numpy as jnp

    with x64():
        out = np.asarray(jax.jit(lambda x: percentile_kernel(jnp, x, QS))(lat))
    np.testing.assert_allclose(out, ref, rtol=1e-12)


def test_empty_batch_contract():
    """Zero requests: the result-container level defines the stats as zeros
    (the helper itself is never called on empty input — latency_stats
    guards, and VirtualTimeFabric.run_batch early-returns)."""
    st = latency_stats(np.asarray([]))
    assert (st.n, st.mean, st.p50, st.p95, st.p99, st.max) == (0, 0, 0, 0, 0, 0)


def test_latency_stats_uses_the_shared_kernel():
    lat = np.asarray([3.0, 1.0, 2.0, 10.0])
    st = latency_stats(lat)
    p50, p95, p99 = percentile_kernel(np, lat, QS)
    assert (st.p50, st.p95, st.p99) == (p50, p95, p99)
    assert st.n == 4 and st.max == 10.0
