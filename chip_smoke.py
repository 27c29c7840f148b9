#!/usr/bin/env python3
"""Chip smoke: drive the CIM simulator's main path once on a TPU.

    python chip_smoke.py              # one chip: capture, fused DSE, replay, fleet
    python chip_smoke.py --chips 4    # the sharded fused DSE sweep on 4 chips

Everything runs in this one process at the full width of ResNet18/ImageNet
(20 layers, 5472 arrays, 247 blocks), through the library entry points a
user calls:

  * capture: ``capture_activations`` over 64 calibration images, plus the
    pinned capture of ``tests/golden/resnet18_profile.json`` derived with
    the Pallas bit-plane kernel, checked against the numpy derivation (bit
    for bit) and against the golden: structure exact, block densities and
    cycle-sample sums within the tolerance of
    ``tests/test_profile_engines.py``.  Per-block mean cycles are reported
    against the golden, not gated: the random calibration network is
    chaotic past its first layers, and even a host CPU other than the one
    that made the golden misses that tolerance there;
  * fused: ``run_fused_sweep`` over a 102,400-config (geometry x ADC x
    policy x PE budget) grid, 64 sampled configs checked against the host
    scalar ``allocate`` + ``simulate`` (discrete columns exact, floats to
    rtol 1e-12);
  * replay: ``VirtualTimeFabric.run_batch`` for the four Figure-8 policies
    under Poisson traffic at 0.7x each design's analytic capacity, every
    completion checked bit for bit against the ``FabricSim`` event engine;
  * fleet: ``run_stream`` (window 8) over a diurnal trace, sketch
    percentiles within the sketch's bound of the exact ones and a prefix
    bit-identical to ``FabricSim(service_sampling="hash")``.

Each phase prints one JSON line: cold seconds (compiles included), warm
seconds (the same work again; every result is fetched to host numpy, so
the device has finished) and the checks that passed.  The last line is
``{"ok": true, "device": {...}}``.  Without a TPU, outside the repository,
or when any check fails, the script exits non-zero and prints no result.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

REPO = Path(__file__).resolve().parent
NET = "resnet18"
POLICIES = ("baseline", "weight_based", "perf_layerwise", "blockwise")
LOAD = 0.7
# the 16 geometry x ADC variants of the dse_fused bench
ROWS = (128, 256)
ADC_BITS = (1, 2, 3, 4, 5, 6, 7, 8)
# golden tolerance of tests/test_profile_engines.py (cross-environment)
GOLDEN_DENSITY_ATOL = 1e-2
GOLDEN_CYCLES_RTOL = 2e-2
FUSED_RTOL = 1e-12

# sizes of the one-chip run.  Replay and fleet are cut far below the
# 10^5 / 10^6 requests a replay user sends: one scan step per patch job
# (30,233 per ResNet18 request) costs ~17 us on a v5e, and the event-engine
# reference costs ~0.2 s per request on the host (PERF.md, section 5).
FULL = dict(
    capture_images=64,
    capture_batch=8,
    n_budgets=1600,
    n_check=64,
    replay_requests=48,
    fleet_requests=128,
    fleet_prefix=32,
)


def _timed(fn):
    t0 = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0


def _report(phase: str, cold: float, warm: float, checks: list, **extra) -> dict:
    line = {"phase": phase, "cold_s": cold, "warm_s": warm, "checks": checks}
    line.update(extra)
    print(json.dumps(line), flush=True)
    return line


def _check(cond, what: str, checks: list) -> None:
    if not cond:
        raise AssertionError(what)
    checks.append(what)


# ------------------------------------------------------------------ capture
def phase_capture(net: str = NET, n_images: int = 64, batch_images: int = 8) -> dict:
    """The streamed calibration forward at full width."""
    from repro.core.cim import capture_activations
    from repro.dse.sweep import _SPEC_FNS

    spec = _SPEC_FNS[net]()

    def run():
        return capture_activations(spec, n_images=n_images, batch_images=batch_images)

    cap, cold = _timed(run)
    cap, warm = _timed(run)
    checks: list = []
    _check(len(cap.layers) == len(spec.layers), "layer_count", checks)
    ok_shape = all(
        lc.rowbits.shape == (l.rows,)
        and lc.sampled_q.shape == (min(256, n_images * l.patches_per_image), l.rows)
        for lc, l in zip(cap.layers, spec.layers)
    )
    _check(ok_shape, "shapes", checks)
    bounded = all(
        (lc.rowbits >= 0).all() and (lc.rowbits <= lc.n_patches * 8).all()
        for lc in cap.layers
    )
    _check(bounded, "rowbits_in_0_8P", checks)
    dens = [lc.rowbits.sum() / (lc.n_patches * lc.rowbits.size * 8) for lc in cap.layers]
    _check(all(0.0 < d < 1.0 for d in dens), "density_in_0_1", checks)
    return _report(
        "capture", cold, warm, checks, images=n_images, batch_images=batch_images,
        layers=len(spec.layers),
    )


def phase_golden(net: str = NET) -> dict:
    """Pinned capture -> Pallas and numpy derivations -> golden fixture."""
    from repro.core.cim import capture_activations, derive_profile
    from repro.dse.sweep import _SPEC_FNS

    g = json.loads((REPO / "tests" / "golden" / f"{net}_profile.json").read_text())
    spec = _SPEC_FNS[net]()
    kw = g["profile_params"]

    def run():
        cap = capture_activations(
            spec, n_images=kw["n_images"], sample_patches=kw["sample_patches"]
        )
        return cap, derive_profile(cap, spec, engine="pallas")

    (cap, pallas), cold = _timed(run)
    (cap, pallas), warm = _timed(run)
    checks: list = []
    ref = derive_profile(cap, spec, engine="vectorized")
    same = all(
        np.array_equal(a.cycles_sample, b.cycles_sample)
        and np.array_equal(a.block_density, b.block_density)
        and np.array_equal(a.mean_cycles, b.mean_cycles)
        for a, b in zip(pallas.layers, ref.layers)
    )
    _check(same, "pallas_eq_numpy_bitwise", checks)
    _check(len(pallas.layers) == len(g["layers"]), "golden_layer_count", checks)
    structure = all(
        lp.name == rec["name"]
        and lp.baseline_block_cycles.tolist() == rec["baseline_block_cycles"]
        and list(lp.cycles_sample.shape) == rec["cycles_sample_shape"]
        for lp, rec in zip(pallas.layers, g["layers"])
    )
    _check(structure, "golden_structure_exact", checks)
    dens_err, cyc_err, sum_err = (
        [
            float(np.max(np.abs(err(lp, rec))))
            for lp, rec in zip(pallas.layers, g["layers"])
        ]
        for err in (
            lambda lp, rec: lp.block_density - np.asarray(rec["block_density"]),
            lambda lp, rec: lp.mean_cycles / np.asarray(rec["mean_cycles"]) - 1.0,
            lambda lp, rec: float(lp.cycles_sample.sum()) / rec["cycles_sample_sum"] - 1.0,
        )
    )
    _check(max(dens_err) <= GOLDEN_DENSITY_ATOL, "golden_density_atol_1e-2", checks)
    _check(max(sum_err) <= GOLDEN_CYCLES_RTOL, "golden_sample_sum_rtol_2e-2", checks)
    return _report(
        "golden", cold, warm, checks, density_max_abs_err=max(dens_err),
        sample_sum_max_rel_err=max(sum_err),
        mean_cycles_rel_err_by_layer=[round(e, 5) for e in cyc_err],
    )


# -------------------------------------------------------------------- fused
def fused_grid(net: str = NET, n_budgets: int = 1600, rows=ROWS, adc_bits=ADC_BITS):
    from repro.core.cim import DEFAULT_ARRAY
    from repro.dse import design_grid

    arrays = tuple(
        DEFAULT_ARRAY.variant(rows=r, cols=r, adc_bits=a) for r in rows for a in adc_bits
    )
    return design_grid(
        networks=(net,), policies=POLICIES,
        pe_multipliers=tuple(np.linspace(1.0, 2.5, n_budgets)), arrays=arrays,
    )


def phase_fused(net: str = NET, n_budgets: int = 1600, n_check: int = 64, **grid) -> dict:
    """The fused DSE sweep against the host scalar oracle."""
    from repro.core.cim import allocate, simulate
    from repro.dse import run_fused_sweep
    from repro.dse.sweep import get_profiled

    pts = fused_grid(net, n_budgets, **grid)
    res, cold = _timed(lambda: run_fused_sweep(pts))
    res, warm = _timed(lambda: run_fused_sweep(pts))
    checks: list = []
    cols = (res.total_cycles, res.images_per_sec, res.mean_utilization)
    _check(all(np.isfinite(c).all() and (c > 0).all() for c in cols), "finite_positive", checks)
    pick = np.random.default_rng(0).choice(len(pts), size=min(n_check, len(pts)), replace=False)
    worst = 0.0
    for i in pick:
        p = pts[i]
        spec, prof = get_profiled(p.network, p.array)
        a = allocate(spec, prof, p.policy, p.n_pes)
        s = simulate(spec, prof, a, n_images=64)
        if a.arrays_used != res.arrays_used[i] or a.arrays_total != res.arrays_total[i]:
            raise AssertionError(f"discrete mismatch at config {i}: {p}")
        for got, want in (
            (res.total_cycles[i], s.total_cycles),
            (res.images_per_sec[i], s.images_per_sec),
            (res.mean_utilization[i], s.mean_utilization),
        ):
            worst = max(worst, abs(got / want - 1.0))
    _check(True, f"scalar_oracle_discrete_exact_{len(pick)}", checks)
    _check(worst <= FUSED_RTOL, "scalar_oracle_float_rtol_1e-12", checks)
    return _report(
        "fused", cold, warm, checks, configs=len(pts),
        configs_per_s_warm=len(pts) / warm, oracle_max_rel_err=worst,
    )


def phase_sharded(net: str = NET, n_budgets: int = 1600, **grid) -> dict:
    """The same grid sharded over every local device and on one device."""
    import jax

    from repro.dse import run_fused_sweep

    pts = fused_grid(net, n_budgets, **grid)
    one, one_s = _timed(lambda: run_fused_sweep(pts))
    many, cold = _timed(lambda: run_fused_sweep(pts, shard_devices=True))
    many, warm = _timed(lambda: run_fused_sweep(pts, shard_devices=True))
    checks: list = []
    same = all(
        np.array_equal(getattr(one, c), getattr(many, c))
        for c in (
            "total_cycles", "images_per_sec", "mean_utilization",
            "arrays_used", "arrays_total",
        )
    )
    _check(same, "sharded_eq_one_device_elementwise", checks)
    return _report(
        "sharded_fused", cold, warm, checks, configs=len(pts),
        one_device_s=one_s, devices=len(jax.devices()),
    )


# ------------------------------------------------------------------- replay
def _designs(net: str):
    from repro.core.cim import allocate, simulate
    from repro.dse.sweep import get_profiled

    spec, prof = get_profiled(net)
    n_pes = 2 * spec.min_pes()
    allocs = [allocate(spec, prof, p, n_pes) for p in POLICIES]
    caps = [simulate(spec, prof, a, n_images=64).images_per_sec for a in allocs]
    return spec, prof, allocs, caps


def phase_replay(net: str = NET, n_requests: int = 48, seed: int = 3) -> dict:
    """Batched virtual-time replay vs the event engine."""
    from repro.core.cim.simulate import CLOCK_HZ
    from repro.fabric import FabricSim, PoissonOpen, VirtualTimeFabric

    spec, prof, allocs, caps = _designs(net)
    procs = [PoissonOpen(n_requests, LOAD * c / CLOCK_HZ, seed=seed) for c in caps]
    vt = VirtualTimeFabric(spec, prof)
    res, cold = _timed(lambda: vt.run_batch(allocs, procs, seed=seed))
    res, warm = _timed(lambda: vt.run_batch(allocs, procs, seed=seed))
    checks: list = []
    lat = res.latencies
    _check(np.isfinite(lat).all() and (lat > 0).all(), "latency_finite_positive", checks)
    _check(bool((np.diff(res.completions, axis=1) >= 0).all()), "non_overtaking", checks)
    for k, (a, p) in enumerate(zip(allocs, procs)):
        ref = FabricSim(spec, prof, a, seed=seed).run(p)
        if not np.array_equal(ref.completions, res.completions[k]):
            raise AssertionError(f"{POLICIES[k]}: vtime != FabricSim on {n_requests} requests")
    _check(True, f"fabricsim_bit_identical_{n_requests}req_x{len(allocs)}", checks)
    return _report(
        "replay", cold, warm, checks, configs=len(allocs), requests=n_requests,
        requests_per_s_warm=len(allocs) * n_requests / warm,
        p99_cycles=[float(x) for x in res.p99],
    )


def phase_fleet(net: str = NET, n_requests: int = 128, n_prefix: int = 32, seed: int = 7) -> dict:
    """Streaming fleet replay of the diurnal trace: sketch vs exact, and a
    prefix vs the event engine on the same hashed service draws."""
    from repro.core.cim.simulate import CLOCK_HZ
    from repro.fabric import (
        FabricSim, SinusoidalPoisson, TraceReplay, VirtualTimeFabric, arrival_times,
        run_stream,
    )

    spec, prof, allocs, caps = _designs(net)
    allocs = allocs[2:]  # perf_layerwise and blockwise: both dataflows
    rate = 0.6 * caps[3] / CLOCK_HZ
    # the fabric_fleet bench's trace: two diurnal cycles across its span
    times = arrival_times(
        SinusoidalPoisson(n_requests, base_rate=rate, period=n_requests / rate / 2.0,
                          amplitude=0.5, seed=0)
    )
    vt = VirtualTimeFabric(spec, prof)

    def run():
        return run_stream(vt, allocs, TraceReplay(times), seed=seed, window=8, materialize=True)

    res, cold = _timed(run)
    res, warm = _timed(run)
    checks: list = []
    exact = res.exact_percentiles
    err = float(np.max(np.abs(res.percentiles - exact) / exact))
    bound = res.sketches[0].config.rel_error
    _check(err <= bound, "sketch_within_bound", checks)
    _check(all(s.n == n_requests for s in res.sketches), "sketch_counts_all", checks)
    for k, a in enumerate(allocs):
        ref = FabricSim(spec, prof, a, seed=seed, service_sampling="hash").run(
            TraceReplay(times[:n_prefix])
        )
        if not np.array_equal(ref.completions, res.completions[k, :n_prefix]):
            raise AssertionError(f"fleet prefix != FabricSim(hash) for config {k}")
    _check(True, f"fabricsim_hash_prefix_bit_identical_{n_prefix}req", checks)
    return _report(
        "fleet", cold, warm, checks, configs=len(allocs), requests=n_requests,
        window=8, requests_per_s_warm=len(allocs) * n_requests / warm,
        sketch_max_rel_err=err, sketch_bound=bound,
    )


# --------------------------------------------------------------------- main
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded fused sweep across 4 chips")
    args = ap.parse_args(argv)

    if not (REPO / "src" / "repro").is_dir():
        print(f"chip_smoke: no repository next to {Path(__file__).name} "
              f"(expected {REPO / 'src' / 'repro'})", file=sys.stderr)
        return 2
    sys.path.insert(0, str(REPO / "src"))

    import jax

    backend = jax.default_backend()
    if backend != "tpu":
        print(f"chip_smoke: no TPU found (jax backend is {backend!r}); "
              f"this script runs only on a TPU", file=sys.stderr)
        return 1
    devices = jax.devices()
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU devices, "
              f"found {len(devices)}", file=sys.stderr)
        return 1

    from repro.core.device import enable_compile_cache

    cache = enable_compile_cache()
    print(json.dumps({"compile_cache": cache, "jax": jax.__version__}), flush=True)
    try:
        if args.chips == 4:
            line = phase_sharded(n_budgets=FULL["n_budgets"])
            if line["devices"] != 4:
                raise AssertionError(f"sharded over {line['devices']} devices, not 4")
        else:
            phase_capture(n_images=FULL["capture_images"], batch_images=FULL["capture_batch"])
            phase_golden()
            phase_fused(n_budgets=FULL["n_budgets"], n_check=FULL["n_check"])
            phase_replay(n_requests=FULL["replay_requests"])
            phase_fleet(n_requests=FULL["fleet_requests"], n_prefix=FULL["fleet_prefix"])
    except AssertionError as e:
        print(f"chip_smoke: check failed: {e}", file=sys.stderr)
        return 1
    d = jax.devices()
    print(json.dumps({
        "ok": True,
        "device": {"platform": d[0].platform, "kind": d[0].device_kind, "count": len(d)},
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
