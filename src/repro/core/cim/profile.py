"""Input-statistics profiling (Section III-A, "profile the distribution of
'1's in the activations gathered from a large set of examples run on a GPU").

The profiler is split into two phases so that a geometry x ADC design sweep
pays the expensive part exactly once:

  * **capture** — one jit-compiled quantized forward per network
    (``capture_activations``).  The whole conv stack, including the in-graph
    uint8 quantization of every crossbar word-line input, runs as a single
    XLA computation per calibration batch: no per-layer host syncs, no
    geometry dependence.  Per layer we keep two geometry-independent
    sufficient statistics: the total '1'-bit count per lowered-matrix row
    over ALL patches and bit-planes (``rowbits``, drives exact per-block
    densities for any row slicing), and a fixed random sample of quantized
    patch rows (``sampled_q``, drives the per-(patch, block) cycle samples).
    Calibration images stream through in fixed-size batches at constant
    memory; quantization scales and BN statistics are per-batch under
    streaming (identical to the single-tensor path when ``n_images <=
    batch_images``).

  * **derive** — ``derive_profile`` turns one capture into a
    ``NetworkProfile`` for ANY ``ArrayConfig`` (block row-slicing, ADC
    precision, read width) without re-running the network.  Three engines
    produce bit-identical integer statistics: ``"reference"`` (the original
    per-block numpy loop, kept as the pinned-golden source), ``"vectorized"``
    (cumulative bit-plane sums, the CPU default), and ``"pallas"`` (the
    ``kernels.bitplane_profile`` popcount kernel; interpret-mode on CPU).

Inputs are synthetic-but-structured images (low-frequency random fields +
noise) — the distributional knobs the paper relies on (ReLU sparsity, per-
layer density spread) emerge from the network itself, not the dataset.  The
measured speedups are reported against our own profile in EXPERIMENTS.md.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from .cost import (
    ArrayConfig,
    DEFAULT_ARRAY,
    baseline_cycles,
    zskip_cycles,
    zskip_cycles_from_ones,
)
from .network import NetworkSpec, LayerSpec, with_array

__all__ = [
    "LayerProfile",
    "NetworkProfile",
    "LayerCapture",
    "ActivationCapture",
    "PROFILE_ENGINES",
    "capture_activations",
    "derive_profile",
    "profile_network",
    "synthetic_images",
]

PROFILE_ENGINES = ("reference", "vectorized", "pallas")
_FORWARD_PLANS = ("resnet18", "vgg11")


@dataclass(frozen=True)
class LayerProfile:
    name: str
    block_density: np.ndarray  # (B,) mean '1'-bit density per block
    mean_cycles: np.ndarray  # (B,) E[zskip cycles] per block per patch
    cycles_sample: np.ndarray  # (S, B) sampled per-patch per-block cycles
    baseline_block_cycles: np.ndarray  # (B,) constant cycles without zskip
    patches_per_image: int

    @property
    def density(self) -> float:
        return float(self.block_density.mean())


@dataclass(frozen=True)
class NetworkProfile:
    network: str
    layers: tuple[LayerProfile, ...]


@dataclass(frozen=True)
class LayerCapture:
    """Geometry-independent word-line input statistics for one layer."""

    name: str
    rowbits: np.ndarray  # (rows,) int64 — '1' bits per matrix row, all patches x planes
    sampled_q: np.ndarray  # (take, rows) uint8 — rng-sampled quantized patches
    n_patches: int  # P: total patches the rowbits cover
    patches_per_image: int


@dataclass(frozen=True)
class ActivationCapture:
    """One quantized forward's worth of profiling state.  Derives a
    ``NetworkProfile`` for any array geometry via ``derive_profile``."""

    network: str
    n_images: int
    sample_patches: int
    seed: int
    layers: tuple[LayerCapture, ...]


def synthetic_images(n: int, hw: int, key: jax.Array, channels: int = 3) -> jax.Array:
    """Low-frequency random fields + noise, normalized to [0, 1]."""
    k1, k2 = jax.random.split(key)
    coarse = jax.random.uniform(k1, (n, 8, 8, channels))
    smooth = jax.image.resize(coarse, (n, hw, hw, channels), method="cubic")
    noisy = smooth + 0.08 * jax.random.normal(k2, (n, hw, hw, channels))
    lo = noisy.min(axis=(1, 2, 3), keepdims=True)
    hi = noisy.max(axis=(1, 2, 3), keepdims=True)
    return (noisy - lo) / (hi - lo + 1e-9)


def _im2col(x: jax.Array, layer: LayerSpec) -> jax.Array:
    """(N,H,W,C) -> (P, rows) patch matrix for this conv layer.  Full
    float32 precision: at the TPU's default the patch extraction (a
    convolution) would round every activation to bfloat16."""
    pad = "SAME" if layer.kernel > 1 else "VALID"
    patches = jax.lax.conv_general_dilated_patches(
        x,
        (layer.kernel, layer.kernel),
        (layer.stride, layer.stride),
        pad,
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=jax.lax.Precision.HIGHEST,
    )  # (N, H', W', C*k*k)
    rows = patches.shape[-1]
    assert rows == layer.rows, (rows, layer.rows, layer.name)
    return patches.reshape(-1, rows)


def _kaiming(key: jax.Array, rows: int, cout: int) -> jax.Array:
    return jax.random.normal(key, (rows, cout)) * np.sqrt(2.0 / rows)


def _bn_relu(y: jax.Array) -> jax.Array:
    mu = y.mean(axis=tuple(range(y.ndim - 1)), keepdims=True)
    sd = y.std(axis=tuple(range(y.ndim - 1)), keepdims=True) + 1e-5
    return jax.nn.relu((y - mu) / sd)


class _CaptureTracer:
    """Plays a conv stack inside one jit trace, recording crossbar input
    statistics at every layer.  ``sel`` holds per-layer patch indices (already
    batch-local and clipped) whose quantized rows are gathered for the cycle
    sample."""

    def __init__(
        self,
        spec: NetworkSpec,
        weights: tuple[jax.Array, ...],
        sel: tuple[jax.Array, ...],
    ):
        self.spec = spec
        self.weights = weights
        self.sel = sel
        self.rowbits: list = [None] * len(spec.layers)
        self.sampled: list = [None] * len(spec.layers)

    def conv(self, idx: int, x: jax.Array) -> jax.Array:
        """Quantize in-graph -> record stats -> matmul -> (N,H',W',Cout)."""
        layer = self.spec.layers[idx]
        pat = jax.nn.relu(_im2col(x, layer))  # (P, rows) float32, >= 0
        # per-tensor uint8 quantization: the scale is computed in float64
        # (this traces under precision.x64) and applied in float32 — the same
        # arithmetic the host-side `float(jnp.max(x))` path performed
        scale = jnp.max(pat).astype(jnp.float64) / 255.0 + 1e-12
        s32 = scale.astype(jnp.float32)
        q = jnp.clip(jnp.round(pat / s32), 0, 255).astype(jnp.uint8)
        # per-row popcount over all patches and planes, one plane at a time
        # (a fori_loop keeps the graph small — 8 unrolled reductions per
        # layer dominate XLA compile time — and each (P, rows) bit
        # extraction fuses into its reduction, so the (P, rows, 8) bit
        # tensor never materializes; integer sums are order-independent)
        self.rowbits[idx] = jax.lax.fori_loop(
            0,
            8,
            lambda p, rb: rb + jnp.sum((q >> (7 - p)) & 1, axis=0, dtype=jnp.int64),
            jnp.zeros((layer.rows,), jnp.int64),
        )
        self.sampled[idx] = jnp.take(q, self.sel[idx], axis=0)
        y = jnp.matmul(
            q.astype(jnp.float32) * s32, self.weights[idx],
            precision=jax.lax.Precision.HIGHEST,
        )
        n = x.shape[0]
        return y.reshape(n, layer.out_hw, layer.out_hw, layer.cout)


def _forward_resnet18(p, x: jax.Array) -> jax.Array:
    """ResNet18 topology over the 20-layer spec (residuals included)."""
    x = _bn_relu(p.conv(0, x))  # conv1
    # maxpool 3x3 s2 -> 56x56
    x = jax.lax.reduce_window(
        x, -jnp.inf, jax.lax.max, (1, 3, 3, 1), (1, 2, 2, 1), "SAME"
    )

    def basic(x, i, down_idx=None):
        h = _bn_relu(p.conv(i, x))
        h = p.conv(i + 1, h)
        sc = p.conv(down_idx, x) if down_idx is not None else x
        return jax.nn.relu(_bn_relu(h) + sc)

    # layer1: idx 1..4
    x = basic(x, 1)
    x = basic(x, 3)
    # layer2: 5,6 + down 7; then 8,9
    x = basic(x, 5, down_idx=7)
    x = basic(x, 8)
    # layer3: 10,11 + 12; 13,14
    x = basic(x, 10, down_idx=12)
    x = basic(x, 13)
    # layer4: 15,16 + 17; 18,19
    x = basic(x, 15, down_idx=17)
    x = basic(x, 18)
    return x


def _forward_vgg11(p, x: jax.Array) -> jax.Array:
    pool_after = {0, 1, 3, 5, 7}
    for i in range(len(p.spec.layers)):
        x = _bn_relu(p.conv(i, x))
        if i in pool_after:
            x = jax.lax.reduce_window(
                x, -jnp.inf, jax.lax.max, (1, 2, 2, 1), (1, 2, 2, 1), "VALID"
            )
    return x


def _run_capture(spec, weights, sel, x):
    tr = _CaptureTracer(spec, weights, sel)
    if spec.name == "resnet18":
        _forward_resnet18(tr, x)
    elif spec.name == "vgg11":
        _forward_vgg11(tr, x)
    else:  # pragma: no cover — capture_activations validates upfront
        raise ValueError(f"no forward plan for {spec.name}")
    return tuple(tr.rowbits), tuple(tr.sampled)


_capture_jit = jax.jit(_run_capture, static_argnums=0)


def capture_activations(
    spec: NetworkSpec,
    n_images: int = 2,
    image_hw: int | None = None,
    sample_patches: int = 256,
    seed: int = 0,
    batch_images: int | None = 8,
) -> ActivationCapture:
    """Run the quantized calibration forward once; keep geometry-independent
    statistics.  ``batch_images`` bounds device memory: images stream through
    the jit forward in fixed-size slices (``None`` = one batch)."""
    if spec.name not in _FORWARD_PLANS:
        raise ValueError(f"no forward plan for {spec.name}")
    # the forward never reads the array geometry (layer rows/strides/channels
    # only), but ``spec`` is the jit static argument — canonicalize it so
    # every ArrayConfig variant of a network shares one compiled forward
    spec = with_array(spec, DEFAULT_ARRAY)
    if image_hw is None:
        image_hw = 224 if spec.name == "resnet18" else 32
    # the seeded weights and images are made on the host CPU, so that every
    # backend profiles the same network on the same inputs (an accelerator's
    # own random-normal and resize kernels round differently)
    with jax.default_device(jax.devices("cpu")[0]):
        key = jax.random.PRNGKey(seed)
        kimg, kw = jax.random.split(key)
        keys = jax.random.split(kw, len(spec.layers))
        weights = tuple(
            np.asarray(_kaiming(keys[i], l.rows, l.cout))
            for i, l in enumerate(spec.layers)
        )
        x = np.asarray(synthetic_images(n_images, image_hw, kimg))

    # sample patch indices over the FULL calibration run, one rng stream in
    # layer order (the legacy profiler's exact draw sequence)
    rng = np.random.default_rng(0)
    sel_global, takes = [], []
    for layer in spec.layers:
        P = n_images * layer.patches_per_image
        take = min(sample_patches, P)
        sel_global.append(rng.choice(P, size=take, replace=False))
        takes.append(take)

    L = len(spec.layers)
    rowbits = [np.zeros(l.rows, dtype=np.int64) for l in spec.layers]
    sampled = [
        np.zeros((t, l.rows), dtype=np.uint8) for t, l in zip(takes, spec.layers)
    ]
    batch = n_images if batch_images is None else max(1, min(batch_images, n_images))
    from ..precision import x64

    for i0 in range(0, n_images, batch):
        i1 = min(i0 + batch, n_images)
        nb = i1 - i0
        sel_local, owned = [], []
        for layer, sg in zip(spec.layers, sel_global):
            off = i0 * layer.patches_per_image
            pb = nb * layer.patches_per_image
            loc = sg - off
            owned.append((loc >= 0) & (loc < pb))
            sel_local.append(jnp.asarray(np.clip(loc, 0, pb - 1).astype(np.int32)))
        with x64():
            rb, qs = _capture_jit(spec, weights, tuple(sel_local), x[i0:i1])
        for li in range(L):
            rowbits[li] += np.asarray(rb[li])
            m = owned[li]
            if m.any():
                sampled[li][m] = np.asarray(qs[li])[m]

    layers = tuple(
        LayerCapture(
            name=l.name,
            rowbits=rowbits[i],
            sampled_q=sampled[i],
            n_patches=n_images * l.patches_per_image,
            patches_per_image=l.patches_per_image,
        )
        for i, l in enumerate(spec.layers)
    )
    return ActivationCapture(spec.name, n_images, sample_patches, seed, layers)


def _resolve_array(spec: NetworkSpec, array: ArrayConfig | None) -> ArrayConfig:
    if array is not None:
        return array
    # derive from the spec so swept geometries (dse.with_array) profile
    # with the array they will run on, not the default
    configs = {l.array for l in spec.layers}
    if len(configs) != 1:
        raise ValueError(
            f"{spec.name} mixes {len(configs)} array configs; pass array= explicitly"
        )
    (array,) = configs
    return array


def _slice_bounds(layer: LayerSpec) -> tuple[np.ndarray, np.ndarray]:
    slices = layer.block_row_slices()
    starts = np.asarray([sl.start for sl in slices])
    stops = np.asarray([sl.stop for sl in slices])
    return starts, stops


def _block_density(cap: LayerCapture, starts, stops) -> np.ndarray:
    """Exact per-block mean '1'-bit density over ALL captured patches —
    integer bit counts divided by exact float64 counts, so it reproduces
    ``np.unpackbits(...).mean()`` over the full patch matrix bit for bit."""
    rbz = np.concatenate([[0], np.cumsum(cap.rowbits)])
    counts = cap.n_patches * (stops - starts) * 8.0
    return (rbz[stops] - rbz[starts]) / counts


def _derive_layer_reference(
    cap: LayerCapture, layer: LayerSpec, array: ArrayConfig
) -> LayerProfile:
    """The original scalar numpy derivation, one python-loop pass per block
    slice — the math the golden profile fixtures pin."""
    dens, cyc_cols, base = [], [], []
    for sl in layer.block_row_slices():
        rows_here = sl.stop - sl.start
        dens.append(int(cap.rowbits[sl].sum()) / (cap.n_patches * rows_here * 8))
        cyc_cols.append(zskip_cycles(cap.sampled_q[:, sl], array))
        base.append(baseline_cycles(rows_here, array))
    cyc = np.stack(cyc_cols, axis=-1)  # (S, B)
    return LayerProfile(
        name=layer.name,
        block_density=np.asarray(dens),
        mean_cycles=cyc.mean(axis=0),
        cycles_sample=cyc,
        baseline_block_cycles=np.asarray(base, dtype=np.int64),
        patches_per_image=layer.patches_per_image,
    )


def _derive_layer_vectorized(
    cap: LayerCapture, layer: LayerSpec, array: ArrayConfig
) -> LayerProfile:
    """One segmented-reduction pass over the sampled bit-planes; every
    geometry's per-block '1' counts are row-range sums of the same bits.
    ``block_row_slices`` tiles [0, rows) contiguously, so the block starts
    are exactly ``np.add.reduceat`` boundaries."""
    starts, stops = _slice_bounds(layer)
    bits = np.unpackbits(cap.sampled_q[..., None], axis=-1)  # (S, rows, 8)
    ones = np.add.reduceat(bits.astype(np.int32), starts, axis=1)  # (S, B, 8)
    cyc = zskip_cycles_from_ones(ones.astype(np.int64), array)  # (S, B) int64
    return LayerProfile(
        name=layer.name,
        block_density=_block_density(cap, starts, stops),
        mean_cycles=cyc.mean(axis=0),
        cycles_sample=cyc,
        baseline_block_cycles=baseline_cycles(stops - starts, array).astype(np.int64),
        patches_per_image=layer.patches_per_image,
    )


def _derive_layer_pallas(
    cap: LayerCapture, layer: LayerSpec, array: ArrayConfig
) -> LayerProfile:
    """Cycle samples via the Pallas bit-plane popcount kernel
    (``kernels.bitplane_profile``; interpret-mode off-TPU)."""
    from ...kernels.bitplane_profile import bitplane_profile
    from ..device import interpret_mode

    starts, stops = _slice_bounds(layer)
    _, cyc = bitplane_profile(
        cap.sampled_q,
        block_rows=layer.array.rows,
        rows_per_read=array.rows_per_read,
        cycles_per_read=array.cycles_per_read,
        interpret=interpret_mode(),
    )
    cyc = np.asarray(cyc).astype(np.int64)
    return LayerProfile(
        name=layer.name,
        block_density=_block_density(cap, starts, stops),
        mean_cycles=cyc.mean(axis=0),
        cycles_sample=cyc,
        baseline_block_cycles=baseline_cycles(stops - starts, array).astype(np.int64),
        patches_per_image=layer.patches_per_image,
    )


_DERIVE = {
    "reference": _derive_layer_reference,
    "vectorized": _derive_layer_vectorized,
    "pallas": _derive_layer_pallas,
}


def derive_profile(
    capture: ActivationCapture,
    spec: NetworkSpec,
    array: ArrayConfig | None = None,
    engine: str = "vectorized",
) -> NetworkProfile:
    """A ``NetworkProfile`` for ``spec``'s geometry from one capture — the
    cheap phase of a geometry x ADC sweep.  All engines are bit-identical."""
    if engine not in PROFILE_ENGINES:
        raise ValueError(f"engine must be one of {PROFILE_ENGINES}, got {engine!r}")
    if spec.name != capture.network:
        raise ValueError(
            f"capture is for {capture.network!r}, spec is {spec.name!r}"
        )
    array = _resolve_array(spec, array)
    derive = _DERIVE[engine]
    layers = tuple(
        derive(cap, layer, array) for cap, layer in zip(capture.layers, spec.layers)
    )
    return NetworkProfile(spec.name, layers)


def profile_network(
    spec: NetworkSpec,
    n_images: int = 2,
    image_hw: int | None = None,
    sample_patches: int = 256,
    seed: int = 0,
    array: ArrayConfig | None = None,
    engine: str = "vectorized",
    batch_images: int | None = 8,
) -> NetworkProfile:
    """One-shot capture + derive.  For many geometries over one network, use
    ``capture_activations`` once and ``derive_profile`` per geometry (what
    ``dse.get_profiled`` does behind its split cache)."""
    array = _resolve_array(spec, array)
    cap = capture_activations(
        spec,
        n_images=n_images,
        image_hw=image_hw,
        sample_patches=sample_patches,
        seed=seed,
        batch_images=batch_images,
    )
    return derive_profile(cap, spec, array=array, engine=engine)
