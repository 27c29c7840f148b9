"""The batched profiling engine is provably behavior-preserving.

Two invariants, deliberately held to different strengths:

  * **Cross-engine bit-identity** (the real contract): reference,
    vectorized and Pallas (interpret) derivations from ONE shared
    activation capture must agree bit for bit — densities, cycle samples,
    digests.  Any divergence is an engine bug, never environment noise.
  * **Engine vs committed golden** (environment-gated): the pinned
    tests/golden/<net>_profile.json fixtures carry the generating
    container's ``env`` stamp (jax/jaxlib/numpy/python/platform/backend).
    When the running environment MATCHES the stamp, the comparison is
    bit-exact — float lists, sample sums, sha256 cycle digests — because
    no legitimate source of drift exists there.  When it differs,
    XLA-version-sensitive matmul ulps through the deep resnet18 BN stacks
    shift a handful of quantized bit counts (observed density drift
    <= 1.2e-4 across containers), so the comparison holds structure
    exactly (names, shapes, baseline cycles) but numerics to a documented
    tolerance: density atol 1e-2, cycle statistics rtol 2e-2.

A geometry VIEW derived from the capture must also equal a from-scratch
``profile_network`` at the same geometry.
"""

import json
import pathlib

import numpy as np
import pytest

from repro.core.cim import (
    DEFAULT_ARRAY,
    PROFILE_ENGINES,
    capture_activations,
    derive_profile,
    profile_network,
    resnet18_imagenet,
    vgg11_cifar10,
    with_array,
)

GOLDEN = pathlib.Path(__file__).parent / "golden"
_SPEC_FNS = {"resnet18": resnet18_imagenet, "vgg11": vgg11_cifar10}


def _digest(cycles_sample: np.ndarray) -> str:
    import hashlib

    return hashlib.sha256(
        np.ascontiguousarray(cycles_sample.astype("<i8")).tobytes()
    ).hexdigest()


@pytest.fixture(scope="module", params=["vgg11", "resnet18"])
def pinned_capture(request):
    g = json.loads((GOLDEN / f"{request.param}_profile.json").read_text())
    spec = _SPEC_FNS[request.param]()
    cap = capture_activations(
        spec,
        n_images=g["profile_params"]["n_images"],
        sample_patches=g["profile_params"]["sample_patches"],
    )
    return spec, cap, g


def test_engines_bit_identical_from_shared_capture(pinned_capture):
    """reference == vectorized == pallas, BIT for bit, from one capture.

    This is the contract the golden fixtures used to carry; it lives
    in-session now so environment ulp drift cannot mask an engine bug."""
    spec, cap, _ = pinned_capture
    ref = derive_profile(cap, spec, engine="reference")
    for engine in ("vectorized", "pallas"):
        prof = derive_profile(cap, spec, engine=engine)
        for a, b in zip(ref.layers, prof.layers):
            assert a.name == b.name
            np.testing.assert_array_equal(a.block_density, b.block_density)
            np.testing.assert_array_equal(a.mean_cycles, b.mean_cycles)
            np.testing.assert_array_equal(a.cycles_sample, b.cycles_sample)
            np.testing.assert_array_equal(
                a.baseline_block_cycles, b.baseline_block_cycles
            )
            assert _digest(a.cycles_sample) == _digest(b.cycles_sample)


def _env_matches_fixture(g) -> bool:
    """True iff the running environment equals the fixture's generating
    container stamp — the gate between bit-exact and tolerant compare."""
    import sys

    sys.path.insert(0, str(GOLDEN))
    try:
        from regen import environment_stamp
    finally:
        sys.path.remove(str(GOLDEN))
    return g.get("env") == environment_stamp()


@pytest.mark.parametrize("engine", PROFILE_ENGINES)
def test_engines_match_profile_golden(pinned_capture, engine):
    """Engine vs committed fixture: bit-exact when the running environment
    matches the fixture's ``env`` stamp, structure-exact + documented
    numeric tolerance otherwise (see module docstring)."""
    spec, cap, g = pinned_capture
    exact = _env_matches_fixture(g)
    prof = derive_profile(cap, spec, engine=engine)
    assert len(prof.layers) == len(g["layers"])
    for lp, rec in zip(prof.layers, g["layers"]):
        assert lp.name == rec["name"]
        assert lp.patches_per_image == rec["patches_per_image"]
        # structure and geometry-derived integers are environment-free
        assert (
            lp.baseline_block_cycles.tolist() == rec["baseline_block_cycles"]
        ), (engine, lp.name)
        assert list(lp.cycles_sample.shape) == rec["cycles_sample_shape"]
        if exact:
            # same container as the fixture: any divergence is a real bug,
            # so hold the full bit-exact contract including the digest
            assert lp.block_density.tolist() == rec["block_density"], (
                engine, lp.name, "block_density",
            )
            assert lp.mean_cycles.tolist() == rec["mean_cycles"], (
                engine, lp.name, "mean_cycles",
            )
            assert int(lp.cycles_sample.sum()) == rec["cycles_sample_sum"]
            assert _digest(lp.cycles_sample) == rec["cycles_sample_sha256"], (
                engine, lp.name, "cycles_sample_sha256",
            )
            continue
        # numerics: XLA matmul ulps through deep BN stacks perturb a few
        # quantized bit counts per container — compare distributionally
        np.testing.assert_allclose(
            lp.block_density, rec["block_density"], atol=1e-2, rtol=0,
            err_msg=f"{engine}/{lp.name} block_density",
        )
        np.testing.assert_allclose(
            lp.mean_cycles, rec["mean_cycles"], rtol=2e-2,
            err_msg=f"{engine}/{lp.name} mean_cycles",
        )
        np.testing.assert_allclose(
            float(lp.cycles_sample.sum()), float(rec["cycles_sample_sum"]),
            rtol=2e-2, err_msg=f"{engine}/{lp.name} cycles_sample_sum",
        )


def test_profile_network_is_capture_plus_derive(pinned_capture):
    """The one-shot API equals the two-phase API bit for bit."""
    spec, cap, g = pinned_capture
    one_shot = profile_network(spec, **g["profile_params"])
    derived = derive_profile(cap, spec)
    for a, b in zip(one_shot.layers, derived.layers):
        np.testing.assert_array_equal(a.block_density, b.block_density)
        np.testing.assert_array_equal(a.cycles_sample, b.cycles_sample)
        np.testing.assert_array_equal(a.mean_cycles, b.mean_cycles)
        np.testing.assert_array_equal(a.baseline_block_cycles, b.baseline_block_cycles)


@pytest.fixture(scope="module")
def vgg_capture():
    return capture_activations(vgg11_cifar10(), n_images=1, sample_patches=64)


@pytest.mark.parametrize(
    "variant",
    [dict(rows=256, cols=256), dict(adc_bits=2), dict(adc_bits=5, rows=64, cols=64)],
)
def test_geometry_view_equals_fresh_profile(vgg_capture, variant):
    """A derived view for a swept geometry == re-profiling from scratch at
    that geometry — the forward really is geometry-independent."""
    array = DEFAULT_ARRAY.variant(**variant)
    spec = vgg11_cifar10()
    cap = vgg_capture
    spec_g = with_array(spec, array)
    view = derive_profile(cap, spec_g, array=array)
    fresh = profile_network(spec_g, n_images=1, sample_patches=64)
    for a, b, layer in zip(view.layers, fresh.layers, spec_g.layers):
        assert a.cycles_sample.shape[1] == layer.n_blocks
        np.testing.assert_array_equal(a.block_density, b.block_density)
        np.testing.assert_array_equal(a.cycles_sample, b.cycles_sample)
        np.testing.assert_array_equal(a.baseline_block_cycles, b.baseline_block_cycles)


def test_adc_view_recosts_without_changing_block_shapes(vgg_capture):
    """Same row slicing, different ADC: densities identical, cycles differ."""
    spec = vgg11_cifar10()
    cap = vgg_capture
    base = derive_profile(cap, spec)
    lowadc = derive_profile(cap, spec, array=DEFAULT_ARRAY.variant(adc_bits=2))
    for a, b in zip(base.layers, lowadc.layers):
        np.testing.assert_array_equal(a.block_density, b.block_density)
        assert a.cycles_sample.shape == b.cycles_sample.shape
        # 2-bit ADC reads 4 rows per cycle group instead of 8: never cheaper
        assert (b.cycles_sample >= a.cycles_sample).all()


def test_streaming_batches_cover_every_sample():
    """Streamed capture (batch_images < n_images) fills the full sample and
    accumulates rowbits over all patches — checked for CONTENT against an
    independent reassembly that gathers EVERY quantized patch of each batch
    from the same jit forward and applies the sample selection on the host,
    so an ownership-mask or rowbits-accumulation bug cannot hide."""
    import jax
    import jax.numpy as jnp
    from repro.core.precision import x64

    from repro.core.cim import profile as P

    spec = vgg11_cifar10()
    n, spp, batch = 4, 48, 2
    cap = capture_activations(spec, n_images=n, sample_patches=spp, batch_images=batch)

    key = jax.random.PRNGKey(0)
    kimg, kw = jax.random.split(key)
    keys = jax.random.split(kw, len(spec.layers))
    weights = tuple(
        P._kaiming(keys[i], l.rows, l.cout) for i, l in enumerate(spec.layers)
    )
    x = P.synthetic_images(n, 32, kimg)
    rng = np.random.default_rng(0)
    sel = [
        rng.choice(n * l.patches_per_image, size=min(spp, n * l.patches_per_image), replace=False)
        for l in spec.layers
    ]
    rowbits = [np.zeros(l.rows, np.int64) for l in spec.layers]
    sampled = [np.zeros((len(s), l.rows), np.uint8) for s, l in zip(sel, spec.layers)]
    for i0 in range(0, n, batch):
        sel_full = tuple(
            jnp.arange(batch * l.patches_per_image, dtype=jnp.int32)
            for l in spec.layers
        )
        with x64():
            rb, q_full = P._capture_jit(spec, weights, sel_full, x[i0 : i0 + batch])
        for li, layer in enumerate(spec.layers):
            rowbits[li] += np.asarray(rb[li])
            loc = sel[li] - i0 * layer.patches_per_image
            m = (loc >= 0) & (loc < batch * layer.patches_per_image)
            sampled[li][m] = np.asarray(q_full[li])[loc[m]]
    for lc, rb, qs, layer in zip(cap.layers, rowbits, sampled, spec.layers):
        assert lc.n_patches == n * layer.patches_per_image
        np.testing.assert_array_equal(lc.rowbits, rb)
        np.testing.assert_array_equal(lc.sampled_q, qs)


def test_derive_validates_engine_and_network():
    spec = vgg11_cifar10()
    cap = capture_activations(spec, n_images=1, sample_patches=8)
    with pytest.raises(ValueError, match="engine"):
        derive_profile(cap, spec, engine="gpu")
    with pytest.raises(ValueError, match="capture is for"):
        derive_profile(cap, resnet18_imagenet())


def test_capture_cache_split_shares_forward_across_geometries():
    """dse.get_profiled derives geometry views from ONE cached capture."""
    from repro.dse import clear_caches, get_captured, get_profiled
    from repro.dse.sweep import _CAPTURE_CACHE

    clear_caches()
    kw = dict(profile_images=1, sample_patches=32, seed=0)
    arrays = (DEFAULT_ARRAY, DEFAULT_ARRAY.variant(adc_bits=2),
              DEFAULT_ARRAY.variant(rows=256, cols=256))
    profs = [get_profiled("vgg11", a, **kw) for a in arrays]
    assert len(_CAPTURE_CACHE) == 1  # one forward for three geometries
    cap = get_captured("vgg11", **kw)
    for (spec, prof), arr in zip(profs, arrays):
        ref = derive_profile(cap, spec, array=arr)
        for a, b in zip(prof.layers, ref.layers):
            np.testing.assert_array_equal(a.cycles_sample, b.cycles_sample)
    with pytest.raises(ValueError, match="unknown network"):
        get_captured("alexnet")
    clear_caches()
