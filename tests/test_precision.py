"""``core.precision``: float64 arithmetic on int64 bit patterns equals IEEE.

The virtual-time engines run on int64 bit patterns so that a TPU, which
only emulates float64, still produces the event engine's float64 times bit
for bit.  These tests hold ``add``/``sub``/``value`` and the sketch bucket
to numpy's IEEE results on the operand mixes the engines produce and on
the encoding's edge cases, through numpy and through a jitted program.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import precision as P
from repro.fabric.metrics import SketchConfig, sketch_bucket

TINY = np.finfo(np.float64).tiny


def _operands(seed: int, n: int = 50_000):
    rng = np.random.default_rng(seed)
    arrivals = np.cumsum(rng.exponential(1e4, n))
    cycles = np.floor(rng.uniform(1, 5e4, n)) * 8.0
    wide = rng.uniform(0, 1, n) * 2.0 ** rng.integers(-60, 60, n)
    sub = np.ldexp(rng.integers(0, 2**52, n).astype(np.float64), -1074)
    near = arrivals * (1 + rng.integers(-3, 4, n) * 2.0**-52)
    edge = np.array([0.0, TINY, 2.0**-1074, 1.0, 2.0**53, 2.0**53 + 2, np.inf])
    a = np.concatenate([arrivals, arrivals, wide, sub, near, np.repeat(edge, 7)])
    b = np.concatenate([cycles, wide, wide[::-1], sub[::-1], arrivals, np.tile(edge, 7)])
    return a, b


def _run(engine, fn, *args):
    if engine == "numpy":
        return fn(np, *args)
    with P.x64():
        return np.asarray(jax.jit(lambda *x: fn(jnp, *x))(*args))


@pytest.mark.parametrize("engine", ["numpy", "jit"])
@pytest.mark.parametrize("seed", [0, 1])
def test_add_is_ieee(engine, seed):
    a, b = _operands(seed)
    got = P.from_bits(_run(engine, P.add, P.to_bits(a), P.to_bits(b)))
    np.testing.assert_array_equal(got, a + b)


@pytest.mark.parametrize("engine", ["numpy", "jit"])
@pytest.mark.parametrize("seed", [0, 1])
def test_sub_is_ieee(engine, seed):
    a, b = _operands(seed)
    hi, lo = np.maximum(a, b), np.minimum(a, b)
    keep = np.isfinite(lo)
    hi, lo = hi[keep], lo[keep]
    got = P.from_bits(_run(engine, P.sub, P.to_bits(hi), P.to_bits(lo)))
    np.testing.assert_array_equal(got, hi - lo)


@pytest.mark.parametrize("engine", ["numpy", "jit"])
def test_value_and_order(engine):
    """Bit patterns decode to their values (normal numbers: XLA's CPU
    backend flushes subnormals) and order as the values do, infinities
    included, so max/min/sort need no decoding."""
    a, _ = _operands(2)
    a = a[(a == 0) | (a >= TINY)]
    np.testing.assert_array_equal(_run(engine, P.value, P.to_bits(a)), a)
    bits = P.to_bits(np.concatenate([a, [-np.inf]]))
    np.testing.assert_array_equal(
        np.argsort(bits, kind="stable"), np.argsort(P.from_bits(bits), kind="stable")
    )
    assert P.inf(bits) == P.to_bits(np.array([np.inf]))[0]
    assert P.ninf(bits) == P.to_bits(np.array([-np.inf]))[0]


@pytest.mark.parametrize("cfg", [SketchConfig(), SketchConfig(bins_per_octave=8, min_exp=-3)])
def test_sketch_bucket_from_bits(cfg):
    """The integer bucket of a bit pattern equals the frexp bucket."""
    a, _ = _operands(3)
    a = a[np.isfinite(a)]
    np.testing.assert_array_equal(
        sketch_bucket(np, P.to_bits(a), cfg), sketch_bucket(np, a, cfg)
    )
