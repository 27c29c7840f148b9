"""``fabric.metrics`` streaming latency sketch: the fixed-size log-bucket
summary that replaces the (configs, requests) latency matrix at fleet scale.

Contracts:

  * quantile estimates land within the documented relative-bucket error
    (``SketchConfig.rel_error = 1 / bins_per_octave``) of the exact
    ``percentile_kernel`` / ``np.percentile`` values for in-range data;
  * min / max / mean / variance are EXACT (tracked outside the buckets:
    p0 and p100 return the true extremes even for out-of-range data);
  * the sequential in-carry update (numpy fold and jit ``lax.scan`` fold)
    is bit-identical to the vectorized ``from_latencies`` reference;
  * merging sketches is exact on counts and moments.
"""

import numpy as np
import pytest

from repro.core.precision import x64
from repro.fabric.metrics import (
    LatencySketch,
    SketchConfig,
    percentile_kernel,
    sketch_bucket,
    sketch_init,
    sketch_update,
)

CFG = SketchConfig()
QS = (0.0, 50.0, 95.0, 99.0, 100.0)


def _seq_sketch(lat, cfg=CFG):
    state = sketch_init(np, cfg)
    for v in lat:
        state = sketch_update(np, state, v, cfg)
    return LatencySketch.from_state(cfg, state)


@pytest.mark.parametrize(
    "name,lat",
    [
        ("lognormal", np.random.default_rng(0).lognormal(10, 1.5, 4000)),
        ("heavy_tail", np.random.default_rng(1).pareto(1.5, 4000) * 1e4 + 1.0),
        ("ties", np.repeat([3.0, 17.0, 1e6], 500)),
        ("single", np.array([12345.6])),
        ("two", np.array([2.0, 9.0])),
    ],
)
def test_quantiles_within_documented_error(name, lat):
    sk = LatencySketch.from_latencies(lat, CFG)
    ref = np.percentile(lat, QS)
    got = sk.percentiles(QS)
    rel = np.abs(got - ref) / np.maximum(np.abs(ref), 1e-300)
    assert rel.max() <= CFG.rel_error, (name, rel.max())


def test_extremes_and_moments_exact():
    rng = np.random.default_rng(2)
    lat = rng.gamma(2.0, 3e4, 2000)
    sk = LatencySketch.from_latencies(lat, CFG)
    assert sk.min == lat.min() and sk.max == lat.max()
    assert sk.percentiles((0.0,))[0] == lat.min()
    assert sk.percentiles((100.0,))[0] == lat.max()
    np.testing.assert_allclose(sk.mean, lat.mean(), rtol=1e-12)
    np.testing.assert_allclose(sk.variance, lat.var(), rtol=1e-9)


def test_out_of_range_values_keep_exact_extremes():
    """Values below 2^min_exp clamp into bucket 0, but p0/p100 still report
    the tracked true extremes, never a bucket midpoint."""
    lat = np.array([1e-6, 0.25, 3.0, 9.0])
    sk = LatencySketch.from_latencies(lat, CFG)
    assert sk.min == 1e-6 and sk.percentiles((0.0,))[0] == 1e-6
    assert sk.max == 9.0 and sk.percentiles((100.0,))[0] == 9.0


def test_sequential_update_equals_vectorized_reference():
    rng = np.random.default_rng(3)
    lat = rng.lognormal(8, 2.0, 300)
    seq = _seq_sketch(lat)
    ref = LatencySketch.from_latencies(lat, CFG)
    np.testing.assert_array_equal(seq.counts, ref.counts)
    assert seq.n == ref.n and seq.min == ref.min and seq.max == ref.max
    np.testing.assert_allclose(seq.mean, ref.mean, rtol=1e-12)
    np.testing.assert_allclose(seq.m2, ref.m2, rtol=1e-9)


def test_jit_scan_fold_bit_identical_to_numpy():
    jax = pytest.importorskip("jax")
    import jax.numpy as jnp

    rng = np.random.default_rng(4)
    lat = rng.lognormal(9, 1.2, 257)
    with x64():
        bnp = sketch_bucket(np, lat, CFG)
        bjx = np.asarray(sketch_bucket(jnp, jnp.asarray(lat), CFG))
        np.testing.assert_array_equal(bnp, bjx)

        def step(state, v):
            return sketch_update(jnp, state, v, CFG), None

        state0 = tuple(jnp.asarray(a) for a in sketch_init(jnp, CFG))
        out, _ = jax.jit(lambda s, x: jax.lax.scan(step, s, x))(
            state0, jnp.asarray(lat)
        )
    ref = _seq_sketch(lat)
    got = LatencySketch.from_state(CFG, tuple(np.asarray(a) for a in out))
    np.testing.assert_array_equal(got.counts, ref.counts)
    assert (got.n, got.min, got.max) == (ref.n, ref.min, ref.max)
    assert got.mean == ref.mean and got.m2 == ref.m2  # bit-identical Welford


def test_merge_is_exact():
    rng = np.random.default_rng(5)
    a, b = rng.lognormal(8, 1.0, 400), rng.lognormal(10, 0.5, 300)
    merged = LatencySketch.from_latencies(a, CFG).merge(
        LatencySketch.from_latencies(b, CFG)
    )
    both = LatencySketch.from_latencies(np.concatenate([a, b]), CFG)
    np.testing.assert_array_equal(merged.counts, both.counts)
    assert merged.min == both.min and merged.max == both.max
    np.testing.assert_allclose(merged.mean, both.mean, rtol=1e-12)
    np.testing.assert_allclose(merged.m2, both.m2, rtol=1e-9)


def test_empty_sketch_is_defined():
    sk = LatencySketch.from_latencies([], CFG)
    assert sk.n == 0
    assert np.all(sk.counts == 0)


def test_config_validation():
    with pytest.raises(ValueError):
        SketchConfig(bins_per_octave=12)  # not a power of two
    assert SketchConfig(bins_per_octave=64).rel_error == 1.0 / 64


def test_stats_view_matches_percentile_kernel_within_bound():
    """The LatencyStats adapter (p50/p95/p99 via the sketch) stays within
    rel_error of the exact shared reduction."""
    rng = np.random.default_rng(6)
    lat = rng.lognormal(11, 1.0, 3000)
    st = LatencySketch.from_latencies(lat, CFG).stats
    ref = percentile_kernel(np, lat, (50.0, 95.0, 99.0))
    for got, want in zip((st.p50, st.p95, st.p99), ref):
        assert abs(got - want) / want <= CFG.rel_error
