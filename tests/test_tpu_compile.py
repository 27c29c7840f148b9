"""The main path's device programs compile for a TPU v5e.

No chip is attached: the topology is described, and the TPU compiler
lowers each program for one of its chips at ResNet18's real widths.  That
catches what interpret mode and the CPU backend cannot (block layouts the
Mosaic tiling rule refuses, ops with no TPU lowering, programs that do
not fit) at no chip time.  Nothing here runs, so nothing here is a time.

All such compiles live in this one file, and the topology is described
inside a fixture only, never while a module is imported.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.cim import (
    DEFAULT_ARRAY,
    allocate,
    derive_profile,
    resnet18_imagenet,
    with_array,
)
from repro.core.cim.profile import ActivationCapture, LayerCapture
from repro.core.cim.simulate import CLOCK_HZ
from repro.core.precision import to_bits, x64


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # no compiler logs in /tmp
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # pragma: no cover — libtpu missing or locked
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _sds(x, sharding):
    return jax.ShapeDtypeStruct(np.shape(x), np.asarray(x).dtype, sharding=sharding)


def _synthetic_capture(spec, samples: int = 128) -> ActivationCapture:
    """A capture with ResNet18's real shapes and random contents: the
    compiled programs depend on shapes only, so the forward is skipped."""
    rng = np.random.default_rng(0)
    layers = tuple(
        LayerCapture(
            name=l.name,
            rowbits=rng.integers(0, 8 * l.patches_per_image, size=l.rows),
            sampled_q=rng.integers(
                0, 256, size=(min(samples, l.patches_per_image), l.rows), dtype=np.uint8
            ),
            n_patches=l.patches_per_image,
            patches_per_image=l.patches_per_image,
        )
        for l in spec.layers
    )
    return ActivationCapture(spec.name, 1, samples, 0, layers)


@pytest.mark.parametrize("rows,blocks", [(128, 36), (256, 18)])
def test_bitplane_kernel_compiles(one_chip, rows, blocks):
    """The widest ResNet18 layer's word-line blocks at 128 and 256 rows."""
    from repro.kernels.bitplane_profile import bitplane_block_profile

    q = jax.ShapeDtypeStruct((blocks, 128, rows), jnp.int32, sharding=one_chip)
    compiled = jax.jit(
        lambda q: bitplane_block_profile(q, interpret=False)
    ).lower(q).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("fam", ["L", "B"])
def test_fused_chunk_program_compiles(one_chip, monkeypatch, fam):
    """One fused-DSE chunk (scatter + vmapped eval, float64) for ResNet18
    at the default 32768-config chunk, both replica families, as the
    sweep runs it: the packed block out, no replica tensor."""
    from repro.dse import fused

    spec = resnet18_imagenet()
    monkeypatch.setattr(fused, "get_captured", lambda *a, **k: _synthetic_capture(spec))
    pipe = fused.FusedPipeline("resnet18", DEFAULT_ARRAY, (2, 4, 8))
    fn = pipe._fn(fam, 64, CLOCK_HZ, need_dups=False)
    c = 32768
    width = pipe.L if fam == "L" else pipe.N
    with x64():
        args = (
            tuple(_sds(s, one_chip) for s in fn.args[0]),
            jax.ShapeDtypeStruct((c,), jnp.int32, sharding=one_chip),
            jax.ShapeDtypeStruct((c,), jnp.bool_, sharding=one_chip),
            jax.ShapeDtypeStruct((c, width), jnp.float64, sharding=one_chip),
        )
        compiled = fn.func.lower(*args).compile()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 16e9


def test_vtime_runner_compiles(one_chip):
    """The virtual-time replay scan (``run_batch`` engine ``"jax"``) for a
    block-wise ResNet18 allocation, times as int64 float64 bit patterns."""
    from repro.fabric.vtime import VirtualTimeFabric

    spec = with_array(resnet18_imagenet(), DEFAULT_ARRAY)
    prof = derive_profile(_synthetic_capture(spec), spec)
    alloc = allocate(spec, prof, "blockwise", 2 * spec.min_pes())
    vt = VirtualTimeFabric(spec, prof)
    (g,) = vt._groups([alloc])
    n = 16
    fn = vt._jax_runner(g, None, n)
    with x64():
        args = (
            tuple(_sds(to_bits(f), one_chip) for f in g.frees),
            None,
            jax.ShapeDtypeStruct((1, n), jnp.int64, sharding=one_chip),
            tuple(
                jax.ShapeDtypeStruct((n, l.patches_per_image), jnp.int64, sharding=one_chip)
                for l in spec.layers
            ),
        )
        compiled = fn.lower(*args).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 16e9


def test_fleet_stream_runner_compiles(one_chip):
    """The streaming fleet replay (``run_stream``): hashed service draws and
    the in-carry latency sketch, with no float64 bitcast (which the TPU
    cannot lower) anywhere in it.  Window 1 keeps the compile short; a
    wider window unrolls the same step."""
    from repro.fabric.fleet import _init_stream_state, _stream_dims_salts, _stream_runner
    from repro.fabric.metrics import SketchConfig
    from repro.fabric.vtime import VirtualTimeFabric

    spec = with_array(resnet18_imagenet(), DEFAULT_ARRAY)
    prof = derive_profile(_synthetic_capture(spec), spec)
    alloc = allocate(spec, prof, "blockwise", 2 * spec.min_pes())
    vt = VirtualTimeFabric(spec, prof)
    (g,) = vt._groups([alloc])
    cfg, n = SketchConfig(), 16
    dims, salts = _stream_dims_salts(vt, 0)
    plans = tuple((1, 0) for _ in dims)
    fn = _stream_runner(vt, g, None, n, 1, cfg, plans, dims, salts, 0, False)
    frees, ring, sk, hor = _init_stream_state(g, None, cfg)
    sk = tuple(to_bits(a) if i in (2, 3) else a for i, a in enumerate(sk))
    with x64():
        args = (
            tuple(_sds(to_bits(f), one_chip) for f in frees),
            None,
            jax.ShapeDtypeStruct((1, n), jnp.int64, sharding=one_chip),
            _sds(to_bits(ring), one_chip),
            tuple(_sds(a, one_chip) for a in sk),
            _sds(to_bits(hor), one_chip),
            0,
            n,
        )
        compiled = fn.lower(*args).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 16e9
