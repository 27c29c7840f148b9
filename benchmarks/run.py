"""Benchmark harness — one function per paper table/figure plus the
TPU-analogue and fabric-runtime benches.  Prints ``name,us_per_call,derived``
CSV rows; ``--json`` additionally writes one ``BENCH_<mode>.json`` per bench
mode at the repo root (schema: mode, config, wall_clock_s, rows, details) so
the perf trajectory is tracked across PRs — CI uploads them as artifacts
from the nightly job.

  PYTHONPATH=src python -m benchmarks.run                    # everything
  PYTHONPATH=src python -m benchmarks.run fig8 fig9          # subset
  PYTHONPATH=src python -m benchmarks.run --json fabric_tail dse
"""

from __future__ import annotations

import pathlib
import sys
import time

import numpy as np

REPO_ROOT = pathlib.Path(__file__).resolve().parent.parent

_JSON_ROWS: list[dict] = []
_JSON_DETAILS: list[list] = []


def write_bench_json(mode: str, payload: dict) -> pathlib.Path:
    """Serialize one bench mode's payload to ``BENCH_<mode>.json`` at the
    repo root — the single write path every mode shares (schema: mode,
    config, wall_clock_s, rows, details).  ``benchmarks/check_drift.py``
    and the nightly CI artifact upload both consume exactly this layout."""
    import json

    path = REPO_ROOT / f"BENCH_{mode}.json"
    with open(path, "w") as f:
        json.dump({"mode": mode, **payload}, f, indent=2)
    print(f"# wrote {path}", file=sys.stderr)
    return path


def _bench_config() -> dict:
    import platform

    cfg = {
        "python": platform.python_version(),
        "platform": platform.platform(),
        "numpy": np.__version__,
        "argv": sys.argv[1:],
    }
    try:
        import jax

        cfg["jax"] = jax.__version__
    except Exception:
        cfg["jax"] = None
    return cfg


class _Timing(float):
    """Steady-state us-per-call that also carries the first-call time (which
    pays jit compile / tracing / cache warmup) — the compile-vs-run split."""

    first_us: float | None = None


def _timeit(fn, repeats=3):
    t0 = time.perf_counter()
    fn()  # warm — the first call pays compile/trace/cache fill
    first = (time.perf_counter() - t0) * 1e6
    t0 = time.perf_counter()
    for _ in range(repeats):
        fn()
    out = _Timing((time.perf_counter() - t0) / repeats * 1e6)
    out.first_us = first
    return out


def _row(name, us, derived):
    print(f"{name},{us:.1f},{derived}")
    row = {"name": name, "us_per_call": round(us, 1), "derived": derived}
    first = getattr(us, "first_us", None)
    if first is not None:  # compile-vs-run breakdown from _timeit
        row["first_call_us"] = round(first, 1)
    _JSON_ROWS.append(row)


def _detail(*fields):
    print("#" + ",".join(str(f) for f in fields))
    _JSON_DETAILS.append(list(fields))


# --------------------------------------------------------------------- paper
_PROFILES = {}


def _profile(netname):
    if netname not in _PROFILES:
        from repro.core.cim import profile_network, resnet18_imagenet, vgg11_cifar10

        spec = resnet18_imagenet() if netname == "resnet18" else vgg11_cifar10()
        _PROFILES[netname] = (spec, profile_network(spec, n_images=2))
    return _PROFILES[netname]


def fig4():
    """Cycles per array vs '1'-bit density (ResNet18 layers) — paper Fig 4."""
    from repro.core.cim import expected_cycles_from_density

    spec, prof = _profile("resnet18")
    dens = np.array([lp.density for lp in prof.layers])
    cyc = np.array([lp.mean_cycles.mean() for lp in prof.layers])
    # linearity: correlation between density and measured mean cycles
    r = np.corrcoef(dens, cyc)[0, 1]
    us = _timeit(lambda: expected_cycles_from_density(dens, 128))
    _row("fig4_cycles_vs_density", us, f"pearson_r={r:.3f}")
    for lp in prof.layers:
        _detail("fig4", lp.name, f"{lp.density:.4f}", f"{lp.mean_cycles.mean():.1f}")


def fig6():
    """Per-block cycle skew for ResNet18 layers 10 and 15 — paper Fig 6."""
    spec, prof = _profile("resnet18")
    rows = []
    for idx, label in ((6, "layer10"), (13, "layer15")):
        lp = prof.layers[idx]
        spread = lp.mean_cycles.max() / lp.mean_cycles.min() - 1
        rows.append((label, lp.mean_cycles, spread))
        for b, (d, c) in enumerate(zip(lp.block_density, lp.mean_cycles)):
            _detail("fig6", label, f"block{b}", f"{d:.4f}", f"{c:.1f}")
    _row(
        "fig6_block_skew",
        0.0,
        ";".join(f"{l}_spread={s*100:.0f}%" for l, _, s in rows),
    )


def fig8():
    """Throughput vs design size, 4 policies x 2 networks — paper Fig 8."""
    from repro.core.cim import run_policy

    for netname in ("resnet18", "vgg11"):
        spec, prof = _profile(netname)
        base_pes = spec.min_pes()
        # the paper's sweep: half-powers of 2 up to ~5.7x the minimum design
        sizes = [
            base_pes,
            int(base_pes * 1.41),
            base_pes * 2,
            int(base_pes * 2.83),
            base_pes * 4,
            int(base_pes * 5.66),
        ]
        results = {}
        t0 = time.perf_counter()
        for pol in ("baseline", "weight_based", "perf_layerwise", "blockwise"):
            results[pol] = [run_policy(spec, prof, pol, n).images_per_sec for n in sizes]
        us = (time.perf_counter() - t0) * 1e6
        bw, wb = results["blockwise"][-1], results["weight_based"][-1]
        bl, pl = results["baseline"][-1], results["perf_layerwise"][-1]
        _row(
            f"fig8_{netname}",
            us,
            f"blockwise_vs_weight={bw/wb:.2f}x;vs_baseline={bw/bl:.2f}x;vs_perf_layerwise={bw/pl:.2f}x",
        )
        for pol, vals in results.items():
            for n, v in zip(sizes, vals):
                _detail("fig8", netname, pol, n, f"{v:.1f}")


def ablation():
    """Separate the paper's two contributions: block-wise DATAFLOW alone
    (weight-based allocation) vs allocation+dataflow together."""
    from repro.core.cim import run_policy

    spec, prof = _profile("resnet18")
    pes = spec.min_pes() * 4
    import time as _t

    t0 = _t.perf_counter()
    wb = run_policy(spec, prof, "weight_based", pes).images_per_sec
    flow = run_policy(spec, prof, "weight_blockflow", pes).images_per_sec
    full = run_policy(spec, prof, "blockwise", pes).images_per_sec
    us = (_t.perf_counter() - t0) * 1e6
    _row(
        "ablation_dataflow_vs_allocation",
        us,
        f"dataflow_only={flow/wb:.2f}x;dataflow+alloc={full/wb:.2f}x "
        f"(of the {full/wb:.2f}x total, {flow/wb:.2f}x comes from the dataflow alone)",
    )


def fig9():
    """Array utilization per layer, ResNet18 — paper Fig 9."""
    from repro.core.cim import run_policy

    spec, prof = _profile("resnet18")
    pes = spec.min_pes() * 2
    t0 = time.perf_counter()
    utils = {
        pol: run_policy(spec, prof, pol, pes).layer_utilization
        for pol in ("weight_based", "perf_layerwise", "blockwise")
    }
    us = (time.perf_counter() - t0) * 1e6
    _row(
        "fig9_utilization",
        us,
        ";".join(f"{p}={u.mean():.3f}" for p, u in utils.items()),
    )
    for pol, u in utils.items():
        for i, v in enumerate(u):
            _detail("fig9", pol, f"layer{i}", f"{v:.3f}")


# ------------------------------------------------------------- TPU analogues
def expert_replication():
    """Paper technique at the MoE level: max-load + drop-rate relief."""
    from repro.core.alloc.expert import (
        drop_rate,
        expected_max_load,
        plan_replication,
    )

    rng = np.random.default_rng(0)
    hist = rng.pareto(1.1, size=160) + 0.05
    hist = hist / hist.sum()
    t0 = time.perf_counter()
    plan = plan_replication(hist, slot_budget=256, pad_to=256)
    us = (time.perf_counter() - t0) * 1e6
    base_max = expected_max_load(hist, n_tokens=65536, top_k=6)
    repl_max = expected_max_load(plan, n_tokens=65536, top_k=6)
    base_drop = drop_rate(hist, 65536, 6, 1.25)
    repl_drop = drop_rate(plan, 65536, 6, 1.25)
    _row(
        "expert_replication_160to256",
        us,
        f"max_load {base_max:.0f}->{repl_max:.0f} ({base_max/repl_max:.2f}x);"
        f"drop {base_drop*100:.1f}%->{repl_drop*100:.2f}%;balance={plan.balance:.3f}",
    )


def stage_balance():
    """Perf-based pipeline partitioning vs equal-count (paper Sec III-A)."""
    from repro.core.alloc.pipeline_stages import bottleneck, partition_stages

    rng = np.random.default_rng(1)
    costs = np.exp(rng.normal(0, 0.8, size=64))  # skewed per-layer costs
    P = 8
    t0 = time.perf_counter()
    smart = partition_stages(costs, P)
    us = (time.perf_counter() - t0) * 1e6
    step = -(-64 // P)
    naive = [(i * step, min((i + 1) * step, 64)) for i in range(P)]
    _row(
        "stage_balance_64L_8P",
        us,
        f"bottleneck {bottleneck(costs, naive):.2f}->{bottleneck(costs, smart):.2f} "
        f"({bottleneck(costs, naive)/bottleneck(costs, smart):.2f}x)",
    )


def kernels():
    """Pallas kernel interpret-mode sanity timings vs jnp references."""
    import jax
    from repro.kernels import ops, ref

    import jax.numpy as jnp

    key = jax.random.PRNGKey(0)
    # structured activation sparsity: half the tiles all-zero (the paper's
    # zero-skipping input regime at tile granularity)
    a = jax.nn.relu(jax.random.normal(key, (256, 256)))
    keep = jnp.kron(jnp.array([[1, 0], [0, 1]], jnp.float32), jnp.ones((128, 128)))
    a = a * keep
    b = jax.random.normal(key, (256, 256))
    us = _timeit(lambda: jax.block_until_ready(ops.zskip_matmul_op(a, b)))
    nz = float((ref.block_mask_ref(a, 128, 128) == 0).mean())
    _row("kernel_zskip_matmul_256", us, f"zero_tile_frac={nz:.2f}")

    q = jax.random.normal(key, (2, 128, 4, 64))
    us = _timeit(lambda: jax.block_until_ready(ops.flash_attention_op(q, q, q)))
    _row("kernel_flash_attention_128", us, "interpret=True")


def continuous_batching():
    """The paper's block-wise dataflow at the request level: static vs
    continuous batching under a log-normal generation-length workload."""
    from repro.serve.scheduler import (
        WorkloadConfig,
        sample_lengths,
        simulate_continuous,
        simulate_static,
    )
    import time as _t

    lens = sample_lengths(WorkloadConfig(n_requests=1024, mean_len=128, sigma=1.0))
    t0 = _t.perf_counter()
    st = simulate_static(lens, n_slots=32)
    ct = simulate_continuous(lens, n_slots=32)
    us = (_t.perf_counter() - t0) * 1e6
    _row(
        "continuous_batching_1024req_32slots",
        us,
        f"util {st.utilization:.2f}->{ct.utilization:.2f};"
        f"steps {st.total_steps}->{ct.total_steps} ({st.total_steps/ct.total_steps:.2f}x);"
        f"mean_latency {st.mean_latency:.0f}->{ct.mean_latency:.0f}",
    )


def roofline_table():
    """Re-emit the dry-run roofline table from results/ (no recompiles)."""
    import glob
    import json

    recs = []
    for f in sorted(glob.glob("results/dr_*.json")):
        recs.extend(json.load(open(f)))
    n_ok = sum(r["status"] == "ok" for r in recs)
    _row("roofline_table", 0.0, f"cells_ok={n_ok};cells_total={len(recs)}")
    for r in recs:
        if r["status"] != "ok":
            _detail("roofline", r["arch"], r["shape"], f"mp={int(r['multi_pod'])}", r["status"])
            continue
        ro = r["roofline"]
        _detail(
            "roofline", r["arch"], r["shape"], f"mp={int(r['multi_pod'])}",
            f"{ro['compute_s']:.3f}", f"{ro['memory_s']:.3f}",
            f"{ro['collective_s']:.3f}", ro["bottleneck"],
            f"{ro['roofline_fraction']:.4f}",
        )


# ------------------------------------------------------------ fabric runtime
def fabric_tail():
    """Tail latency across a (policy x load) grid on one fabric design:
    the scalar event engine vs ONE batched virtual-time evaluation of all
    (allocation, arrival-trace) pairs — the engine behind latency-aware
    provisioning.  Asserts bit-identical per-request completion times and
    reports the batch speedup (acceptance: >= 20x)."""
    from repro.core.cim import allocate, simulate
    from repro.core.cim.simulate import CLOCK_HZ
    from repro.fabric import (
        FabricSim,
        PoissonOpen,
        VirtualTimeFabric,
        provision_latency_aware,
    )

    spec, prof = _profile("vgg11")
    pes = spec.min_pes() * 2
    wb = allocate(spec, prof, "weight_based", pes)
    bw = allocate(spec, prof, "blockwise", pes)
    cap = simulate(spec, prof, bw, n_images=64).images_per_sec
    loads = (0.3, 0.5, 0.6, 0.7, 0.85)
    n_req = 400
    allocs, procs, labels = [], [], []
    vt_prov = VirtualTimeFabric(spec, prof, lane_quantum=8)  # shared warm cache
    for f in loads:
        la = provision_latency_aware(
            spec, prof, pes, offered_ips=f * cap, calib_requests=150, grants=0,
            vt=vt_prov,
        )
        proc = PoissonOpen(n_requests=n_req, rate_per_cycle=f * cap / CLOCK_HZ, seed=5)
        for pol, a in (("weight_based", wb), ("blockwise", bw), ("latency_aware", la)):
            allocs.append(a)
            procs.append(proc)
            labels.append((pol, f))

    t0 = time.perf_counter()
    scalar = [
        FabricSim(spec, prof, a, seed=3).run(p) for a, p in zip(allocs, procs)
    ]
    t_scalar = time.perf_counter() - t0

    vt = VirtualTimeFabric(spec, prof)
    t0 = time.perf_counter()
    vt.run_batch(allocs, procs, seed=3)
    t_cold = time.perf_counter() - t0
    t0 = time.perf_counter()
    res = vt.run_batch(allocs, procs, seed=3)
    t_warm = time.perf_counter() - t0

    bitident = all(
        np.array_equal(res.completions[i], r.completions)
        and np.array_equal(res.arrivals[i], r.arrivals)
        for i, r in enumerate(scalar)
    )
    # hard acceptance: the batched kernel must BE the event engine
    assert bitident, "virtual-time batch diverged from the scalar event engine"
    ms = 1e3 / CLOCK_HZ
    p99 = {lab: res.latency(i).p99 * ms for i, lab in enumerate(labels)}
    f0 = 0.7
    _row(
        f"fabric_tail_vgg11_{len(allocs)}cfg",
        t_warm * 1e6,
        f"speedup={t_scalar / t_warm:.1f}x;scalar_s={t_scalar:.2f};"
        f"batch_cold_s={t_cold:.2f};bitident={bitident};"
        f"p99@70% wb={p99[('weight_based', f0)]:.3f}ms "
        f"bw={p99[('blockwise', f0)]:.3f}ms "
        f"la={p99[('latency_aware', f0)]:.3f}ms",
    )
    for i, (pol, f) in enumerate(labels):
        st = res.latency(i)
        _detail(
            "fabric_tail", pol, f, f"{st.p50 * ms:.4f}", f"{st.p95 * ms:.4f}",
            f"{st.p99 * ms:.4f}", f"{st.mean * ms:.4f}",
        )


def fabric_drift():
    """Distribution shift mid-serve: stale allocation vs EWMA-triggered
    online re-allocation (warm-started greedy) vs clairvoyant oracle."""
    from repro.core.cim import allocate
    from repro.core.cim.simulate import ARRAYS_PER_PE
    from repro.fabric import (
        ClosedLoop,
        DriftConfig,
        FabricSim,
        OnlineReallocator,
        shift_profile,
    )

    spec, prof = _profile("vgg11")
    pes = spec.min_pes() * 2
    free = pes * ARRAYS_PER_PE - spec.n_arrays
    reserve = 0.4
    alloc0 = allocate(spec, prof, "blockwise", pes, free_budget=free * (1 - reserve))
    shifted = shift_profile(prof, {4: 1.8, 5: 1.8, 6: 1.8})
    cl = ClosedLoop(n_requests=120, concurrency=24)
    t0 = time.perf_counter()
    stale = FabricSim(spec, prof, alloc0, seed=2, live_prof=shifted).run(cl)
    rl = OnlineReallocator(spec, prof, reserve_arrays=free * reserve, cfg=DriftConfig())
    online = FabricSim(spec, prof, alloc0, seed=2, live_prof=shifted, reallocator=rl).run(cl)
    oracle = FabricSim(spec, shifted, allocate(spec, shifted, "blockwise", pes), seed=2).run(cl)
    us = (time.perf_counter() - t0) * 1e6
    ts, to, torc = stale.images_per_sec, online.images_per_sec, oracle.images_per_sec
    rec = (to - ts) / (torc - ts)
    if online.reallocations:
        ev = online.reallocations[0]
        realloc = f"stall={ev.stall_cycles:.0f}cyc;arrays_added={ev.arrays_added}"
    else:
        realloc = "realloc=never_tripped"
    _row(
        "fabric_drift_vgg11_shift1.8x",
        us,
        f"stale={ts:.0f};online={to:.0f};oracle={torc:.0f};recovery={rec:.2f};{realloc}",
    )
    _detail("fabric_drift", "stale", f"{ts:.1f}")
    _detail("fabric_drift", "online", f"{to:.1f}")
    _detail("fabric_drift", "oracle", f"{torc:.1f}")


def fabric_multitenant():
    """ResNet18 + VGG11 sharing one fabric, weighted-fair allocation."""
    from repro.core.cim.simulate import ARRAYS_PER_PE
    from repro.fabric import ClosedLoop, Tenant, allocate_shared, fairness_report, run_tenants

    rspec, rprof = _profile("resnet18")
    vspec, vprof = _profile("vgg11")
    tenants = [
        Tenant("resnet18", rspec, rprof, weight=2.0),
        Tenant("vgg11", vspec, vprof, weight=1.0),
    ]
    base = rspec.n_arrays + vspec.n_arrays
    n_pes = -(-base // ARRAYS_PER_PE) * 2
    t0 = time.perf_counter()
    shared = allocate_shared(tenants, n_pes=n_pes)
    results = run_tenants(shared, [ClosedLoop(60, 40), ClosedLoop(60, 16)], seed=0)
    us = (time.perf_counter() - t0) * 1e6
    rep = fairness_report(shared, results)
    _row(
        "fabric_multitenant_r18+vgg11",
        us,
        ";".join(
            f"{n}:ips={d['images_per_sec']:.0f},p99={d['latency_ms_p99']:.2f}ms,arrays={d['arrays']}"
            for n, d in rep["tenants"].items()
        )
        + f";balance={rep['weighted_rate_balance']:.2f}",
    )
    for n, d in rep["tenants"].items():
        _detail(
            "fabric_multitenant", n, d["weight"], d["arrays"],
            f"{d['images_per_sec']:.1f}", f"{d['latency_ms_p99']:.3f}",
            f"{d['mean_utilization']:.3f}",
        )


# ----------------------------------------------------------------- profile
class _LegacyProfiler:
    """The pre-batched-engine scalar profiler, kept verbatim as the bench
    baseline: per-layer host round-trips (``float(jnp.max)`` sync, numpy
    matmul), and a full ``np.unpackbits`` + python block loop per layer —
    re-run from scratch for EVERY array geometry."""

    def __init__(self, spec, key, sample_patches, array):
        import jax
        from repro.core.cim.profile import _kaiming

        self.spec = spec
        self.array = array
        self.sample = sample_patches
        self.records = {}
        keys = jax.random.split(key, len(spec.layers))
        self.weights = {
            i: _kaiming(keys[i], l.rows, l.cout) for i, l in enumerate(spec.layers)
        }
        self.rng = np.random.default_rng(0)

    def conv(self, idx, x):
        import jax
        import jax.numpy as jnp
        from repro.core.cim.profile import _im2col

        layer = self.spec.layers[idx]
        pat = _im2col(x, layer)
        relu = jax.nn.relu(pat)
        scale = float(jnp.max(relu)) / 255.0 + 1e-12  # host sync per layer
        q = np.asarray(jnp.clip(jnp.round(relu / scale), 0, 255), dtype=np.uint8)
        self._record(idx, layer, q)
        y = (q.astype(np.float32) * scale) @ np.asarray(self.weights[idx])
        n = x.shape[0]
        return jnp.asarray(y).reshape(n, layer.out_hw, layer.out_hw, layer.cout)

    def _record(self, idx, layer, q):
        from repro.core.cim.cost import baseline_cycles, zskip_cycles
        from repro.core.cim.profile import LayerProfile

        P = q.shape[0]
        take = min(self.sample, P)
        sel = self.rng.choice(P, size=take, replace=False)
        qs = q[sel]
        dens, cyc_cols, base = [], [], []
        bits_full = np.unpackbits(q[..., None], axis=-1)  # (P, rows, 8)
        for sl in layer.block_row_slices():
            rows_here = sl.stop - sl.start
            dens.append(bits_full[:, sl, :].mean())
            cyc_cols.append(zskip_cycles(qs[:, sl], self.array))
            base.append(baseline_cycles(rows_here, self.array))
        cyc = np.stack(cyc_cols, axis=-1)
        self.records[idx] = LayerProfile(
            name=layer.name,
            block_density=np.asarray(dens),
            mean_cycles=cyc.mean(axis=0),
            cycles_sample=cyc,
            baseline_block_cycles=np.asarray(base, dtype=np.int64),
            patches_per_image=layer.patches_per_image,
        )


def _legacy_profile_network(spec, n_images, sample_patches):
    import jax
    from repro.core.cim.profile import (
        NetworkProfile,
        _forward_resnet18,
        _forward_vgg11,
        _resolve_array,
        synthetic_images,
    )

    key = jax.random.PRNGKey(0)
    kimg, kw = jax.random.split(key)
    hw = 224 if spec.name == "resnet18" else 32
    x = synthetic_images(n_images, hw, kimg)
    p = _LegacyProfiler(spec, kw, sample_patches, array=_resolve_array(spec, None))
    (_forward_resnet18 if spec.name == "resnet18" else _forward_vgg11)(p, x)
    return NetworkProfile(
        spec.name, tuple(p.records[i] for i in range(len(spec.layers)))
    )


def profile():
    """The batched bit-plane profiling engine vs the pre-PR scalar profiler
    on a geometry x ADC sweep (ResNet18, the paper's workload).  The scalar
    path re-runs the quantized forward + full unpackbits per geometry; the
    engine captures activations ONCE (jit forward, in-graph popcount) and
    derives every geometry as a cheap bit-plane view.  Cold times include
    each path's own compile/warmup.  Acceptance: >=10x cold on the
    12-geometry sweep, engines bit-identical."""
    from repro.core.cim import DEFAULT_ARRAY, resnet18_imagenet
    from repro.core.cim.network import with_array
    from repro.core.cim.profile import capture_activations, derive_profile

    n_img, s_patches = 16, 128
    spec = resnet18_imagenet()
    geos = [
        DEFAULT_ARRAY.variant(rows=r, cols=r, adc_bits=a)
        for r in (64, 128, 256)
        for a in (2, 3, 4, 5)
    ]

    legacy_t = []
    legacy_first = None
    for g in geos:
        t0 = time.perf_counter()
        lp = _legacy_profile_network(with_array(spec, g), n_img, s_patches)
        legacy_t.append(time.perf_counter() - t0)
        legacy_first = legacy_first or lp

    t0 = time.perf_counter()
    cap = capture_activations(spec, n_images=n_img, sample_patches=s_patches)
    views = [derive_profile(cap, with_array(spec, g), array=g) for g in geos]
    t_cold = time.perf_counter() - t0
    t_cap0 = time.perf_counter()
    cap2 = capture_activations(spec, n_images=n_img, sample_patches=s_patches)
    t_cap_warm = time.perf_counter() - t_cap0
    t_derive = []
    for g in geos:
        t0 = time.perf_counter()
        derive_profile(cap2, with_array(spec, g), array=g)
        t_derive.append(time.perf_counter() - t0)
    t_warm = t_cap_warm + sum(t_derive)

    # the engine IS the scalar derivation, bit for bit (the golden suite
    # pins this per engine; re-checked here on the bench capture)
    ref = derive_profile(cap, with_array(spec, geos[0]), array=geos[0], engine="reference")
    bitident = all(
        np.array_equal(a.cycles_sample, b.cycles_sample)
        and np.array_equal(a.block_density, b.block_density)
        for a, b in zip(ref.layers, views[0].layers)
    )
    assert bitident, "profile engines diverged"
    # the legacy baseline measures the same statistics: geometry-derived
    # baselines bit-equal, densities within the XLA-vs-BLAS forward drift
    for a, b in zip(legacy_first.layers, views[0].layers):
        assert np.array_equal(a.baseline_block_cycles, b.baseline_block_cycles)
        assert a.cycles_sample.shape == b.cycles_sample.shape
        assert np.allclose(a.block_density, b.block_density, atol=0.05)

    # derives are pure numpy (no compile), so a K-geometry cold time is the
    # measured 12-geometry cold run minus the warm derive cost of the rest
    sp_1 = legacy_t[0] / (t_cold - sum(t_derive[1:]))
    sp_8 = sum(legacy_t[:8]) / (t_cold - sum(t_derive[8:]))
    sp_12 = sum(legacy_t) / t_cold
    _row(
        f"profile_resnet18_{len(geos)}geo_{n_img}img",
        t_cold * 1e6,
        f"speedup_12geo={sp_12:.1f}x;speedup_8geo={sp_8:.1f}x;"
        f"speedup_1geo={sp_1:.1f}x;legacy_12geo_s={sum(legacy_t):.1f};"
        f"engine_cold_s={t_cold:.2f};engine_warm_s={t_warm:.2f};"
        f"bitident={bitident}",
    )
    for g, lt, dt in zip(geos, legacy_t, t_derive):
        _detail(
            "profile", f"{g.rows}x{g.cols}", f"adc{g.adc_bits}",
            f"legacy_s={lt:.2f}", f"derive_s={dt:.4f}",
        )


# ------------------------------------------------------------------- dse
def dse():
    """Vectorized design-space sweep vs the scalar loop: >=1000 (policy,
    PE-count, array-geometry) configs, element-wise equivalence + speedup."""
    import numpy as np

    from repro.core.cim import DEFAULT_ARRAY
    from repro.dse import design_grid, pareto_frontier, run_sweep

    arrays = (
        DEFAULT_ARRAY,
        DEFAULT_ARRAY.variant(adc_bits=2),
        DEFAULT_ARRAY.variant(rows=256, cols=256),
    )
    points = design_grid(
        networks=("vgg11",),
        pe_multipliers=tuple(np.linspace(1.0, 6.0, 67)),
        arrays=arrays,
    )
    kw = dict(profile_images=1, sample_patches=64)
    cold = run_sweep(points, **kw)  # includes jit compile
    warm = run_sweep(points, **kw)
    scalar = run_sweep(points, engine="scalar", **kw)
    err = max(
        np.abs((warm.total_cycles - scalar.total_cycles) / scalar.total_cycles).max(),
        np.abs((warm.images_per_sec - scalar.images_per_sec) / scalar.images_per_sec).max(),
        np.abs(
            (warm.mean_utilization - scalar.mean_utilization) / scalar.mean_utilization
        ).max(),
    )
    alloc_equal = bool((warm.arrays_used == scalar.arrays_used).all())
    frontier = pareto_frontier(warm)
    _row(
        f"dse_sweep_vgg11_{len(points)}cfg",
        warm.elapsed_s * 1e6,
        f"speedup={scalar.elapsed_s / warm.elapsed_s:.1f}x;"
        f"scalar_s={scalar.elapsed_s:.2f};batch_cold_s={cold.elapsed_s:.2f};"
        f"max_rel_err={err:.1e};alloc_equal={alloc_equal};"
        f"pareto_points={len(frontier)}",
    )
    for i in frontier[:: max(1, len(frontier) // 20)]:
        p = warm.points[i]
        _detail(
            "dse_pareto", p.network, p.policy, p.n_pes,
            f"{p.array.rows}x{p.array.cols}", f"adc{p.array.adc_bits}",
            int(warm.arrays_total[i]), f"{warm.images_per_sec[i]:.1f}",
            f"{warm.mean_utilization[i]:.3f}",
        )


def fabric_multichip():
    """Equal-silicon scale-out: one fabric budget tiled over 1..8 chips at
    several link bandwidths, placed by the communication-aware allocator and
    measured on the batched virtual-time engine WITH inter-chip transfer
    delays.  The headline is the chip-scaling curve: throughput retention
    and p99 inflation vs the single-chip design at each link speed."""
    from repro.dse import (
        MULTICHIP_OBJECTIVES,
        chip_grid,
        pareto_frontier,
        run_multichip_sweep,
    )

    chips = (1, 2, 4, 8)
    links = (16.0, 64.0, 256.0)
    pts = chip_grid(
        networks=("vgg11",), chips=chips, link_gbps=links, pe_multiplier=2.0
    )
    t0 = time.perf_counter()
    res = run_multichip_sweep(
        pts, n_requests=200, closed_requests=60, concurrency=24,
        sample_patches=64, seed=0,
    )
    us = (time.perf_counter() - t0) * 1e6
    rows = {(p.n_chips, p.link_gbps): i for i, p in enumerate(res.points)}
    ret = {
        g: res.images_per_sec[rows[(8, g)]] / res.images_per_sec[rows[(1, g)]]
        for g in links
    }
    p99x = {
        g: res.p99_cycles[rows[(8, g)]] / res.p99_cycles[rows[(1, g)]]
        for g in links
    }
    frontier = pareto_frontier(res, MULTICHIP_OBJECTIVES)
    _row(
        f"fabric_multichip_vgg11_{len(pts)}cfg",
        us,
        ";".join(f"retention8chip@{g:.0f}gbps={ret[g]:.2f}x" for g in links)
        + ";"
        + ";".join(f"p99_8chip@{g:.0f}gbps={p99x[g]:.2f}x" for g in links)
        + f";pareto_points={len(frontier)}",
    )
    for r in res.rows():
        _detail(
            "fabric_multichip", r["network"], r["n_chips"],
            f"{r['link_gbps']:.0f}", f"{r['images_per_sec']:.1f}",
            f"{r['p50_ms']:.4f}", f"{r['p95_ms']:.4f}", f"{r['p99_ms']:.4f}",
            f"{r['max_stage_transfer_cycles']:.0f}", r["n_crossings"],
        )


def dse_fused():
    """The one-jit fused DSE pipeline (shared per-ADC bank stacks, event-
    schedule allocation replay, chunk-streamed scatter+eval dispatches) vs
    the staged path (host profile derive per (geometry, ADC) +
    allocate_batch + BatchSimulator per group), plus the lifted
    placement x load axis vs running the staged multichip sweep once per
    load.  The headline grid is 10^6 analytic configs streamed through the
    chunked driver; a density sub-table re-times the VGG11 analytic grid at
    several budgets-per-variant densities (the regime axis where the
    pre-shared-bank fused path used to LOSE — 0.69x at 6,400 pv).  Both
    paths share one warm activation capture; each analytic pass is timed on
    its second (compile-warm) invocation, with the staged pass re-paying
    the host profile derivation every run (that derivation is part of what
    the fusion moved in-graph).  Per-stage wall times and peak RSS land in
    the BENCH json as telemetry gauges.  Acceptance: every integer-cycle
    analytic column bit-equal (utilization at ULP tolerance), the 0.7-load
    chip column bit-equal, and the committed headlines
    ``end_to_end_speedup`` AND ``analytic_speedup`` present
    (benchmarks/check_drift.py errors out if either goes missing)."""
    import resource

    from repro.core.cim import DEFAULT_ARRAY
    from repro.dse import (
        chip_grid,
        design_grid,
        run_fused_multichip_sweep,
        run_fused_sweep,
        run_sweep,
    )
    from repro.dse.sweep import _PROFILE_CACHE, get_captured, run_multichip_sweep
    from repro.fabric.telemetry import get_telemetry

    arrays = tuple(
        DEFAULT_ARRAY.variant(rows=r, cols=r, adc_bits=a)
        for r in (128, 256)
        for a in (1, 2, 3, 4, 5, 6, 7, 8)
    )
    pols = ("baseline", "weight_based", "perf_layerwise", "blockwise")

    def vgg_grid(n_budgets):
        return design_grid(
            networks=("vgg11",), policies=pols,
            pe_multipliers=tuple(np.linspace(1.0, 6.0, n_budgets)),
            arrays=arrays,
        )

    # 64 (geometry, ADC, policy) variants x 11,250 + 4,400 budgets = the
    # 10^6-config headline grid the chunked fused driver streams through
    pts = vgg_grid(11250) + design_grid(
        networks=("resnet18",), policies=pols,
        pe_multipliers=tuple(np.linspace(1.0, 2.5, 4400)), arrays=arrays,
    )
    for net in ("vgg11", "resnet18"):
        get_captured(net)  # shared capture, warmed outside both timings

    def staged_pass(p):
        _PROFILE_CACHE.clear()  # staged honestly re-pays per-variant derive
        return run_sweep(p, engine="batch")

    staged_pass(pts)  # warm compiles (BatchSimulator per geometry)
    t0 = time.perf_counter()
    staged = staged_pass(pts)
    t_staged = time.perf_counter() - t0

    t0 = time.perf_counter()
    run_fused_sweep(pts)
    t_fused_cold = time.perf_counter() - t0
    t0 = time.perf_counter()
    fused = run_fused_sweep(pts)
    t_fused = time.perf_counter() - t0

    # discrete columns exactly equal; float columns at ULP tolerance —
    # staged and fused are different XLA programs and cross-compilation
    # op-fusion wobbles the last ULP (contract documented in dse/fused.py)
    equiv = np.array_equal(staged.arrays_used, fused.arrays_used) and all(
        np.allclose(getattr(staged, c), getattr(fused, c), rtol=1e-12, atol=0)
        for c in ("total_cycles", "images_per_sec", "mean_utilization")
    )
    assert equiv, "fused sweep diverged from the staged path"
    del staged, fused  # the 10^6-row columns: release before the density runs

    # density-vs-speedup table: same VGG11 variant set, budgets-per-variant
    # swept across the regimes EXPERIMENTS.md discusses (80 pv is the
    # variant-dense regime, 6,400 pv the config-dense one that measured
    # 0.69x before the shared-bank + event-schedule rework)
    density_keys = []
    for pv in (80, 400, 1200, 6400):
        dpts = vgg_grid(pv)
        staged_pass(dpts)  # warm this C's program shapes
        run_fused_sweep(dpts)
        t0 = time.perf_counter()
        staged_pass(dpts)
        ts = time.perf_counter() - t0
        t0 = time.perf_counter()
        run_fused_sweep(dpts)
        tf = time.perf_counter() - t0
        density_keys.append(f"analytic_speedup_{pv}pv={ts / tf:.2f}x")
        _detail(
            "dse_fused", "density", pv, len(dpts), f"{ts:.3f}", f"{tf:.3f}"
        )

    # placement x load surface: staged = one full multichip sweep PER load
    # (closed-loop re-measured and kernels re-built each time); fused = one
    # closed-loop call + one batched open-loop call over the whole surface
    cpts = chip_grid(networks=("vgg11",), chips=(1, 2, 4), link_gbps=(16.0, 64.0))
    loads = (0.3, 0.4, 0.5, 0.6, 0.7, 0.8)
    ckw = dict(n_requests=120, closed_requests=40, concurrency=24, seed=0)
    t0 = time.perf_counter()
    staged_chip = {
        lf: run_multichip_sweep(cpts, load_frac=lf, **ckw) for lf in loads
    }
    t_chip_staged = time.perf_counter() - t0
    t0 = time.perf_counter()
    fused_chip = run_fused_multichip_sweep(cpts, load_fracs=loads, **ckw)
    t_chip_fused = time.perf_counter() - t0
    s07 = staged_chip[0.7]
    k07 = loads.index(0.7)
    chip_equiv = np.allclose(
        np.stack([s07.p50_cycles, s07.p95_cycles, s07.p99_cycles], axis=1),
        fused_chip.pcts[:, k07, :], rtol=1e-12, atol=0,
    ) and np.allclose(
        s07.images_per_sec, fused_chip.images_per_sec, rtol=1e-12, atol=0
    )
    assert chip_equiv, "fused multichip surface diverged at load 0.7"

    n_cfg = len(pts) + fused_chip.n_evaluations
    e2e = (t_staged + t_chip_staged) / (t_fused + t_chip_fused)
    # stable row name + a configs= field: check_drift compares speedups
    # like-for-like and skips (with a WARN) when the grid size changes
    _row(
        "dse_fused",
        t_fused * 1e6,
        f"end_to_end_speedup={e2e:.2f}x;"
        f"analytic_speedup={t_staged / t_fused:.2f}x;"
        f"load_surface_ratio={t_chip_staged / t_chip_fused:.2f}x;"
        f"staged_s={t_staged + t_chip_staged:.2f};"
        f"fused_s={t_fused + t_chip_fused:.2f};"
        f"fused_cold_s={t_fused_cold:.2f};configs={n_cfg};"
        f"equiv={equiv and chip_equiv}",
    )
    # the density keys are self-labeled (fixed pv each), so they live on a
    # configs=-free row and stay drift-comparable across headline resizes
    _row("dse_fused_density", 0.0, ";".join(density_keys))
    # per-stage wall time + peak RSS ride the telemetry session into the
    # BENCH json (nightly uploads it with the artifact)
    tel = get_telemetry()
    tel.gauge("dse.fused.bench.analytic_staged_s", round(t_staged, 3))
    tel.gauge("dse.fused.bench.analytic_fused_s", round(t_fused, 3))
    tel.gauge("dse.fused.bench.analytic_fused_cold_s", round(t_fused_cold, 3))
    tel.gauge("dse.fused.bench.chip_staged_s", round(t_chip_staged, 3))
    tel.gauge("dse.fused.bench.chip_fused_s", round(t_chip_fused, 3))
    tel.gauge(
        "dse.fused.bench.peak_rss_mb",
        round(resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024.0, 1),
    )
    _detail("dse_fused", "analytic_configs", len(pts), f"{t_staged:.2f}", f"{t_fused:.2f}")
    _detail(
        "dse_fused", "chip_surface", fused_chip.n_evaluations,
        f"{t_chip_staged:.2f}", f"{t_chip_fused:.2f}",
    )
    for r in fused_chip.rows():
        if r["load_frac"] in (0.3, 0.7):
            _detail(
                "dse_fused", r["n_chips"], f"{r['link_gbps']:.0f}",
                r["load_frac"], f"{r['images_per_sec']:.1f}", f"{r['p99_ms']:.4f}",
            )


# ----------------------------------------------------------- fleet replay
def fabric_fleet():
    """Fleet-scale trace replay: a >= 10^6-request diurnal trace against a
    C=2 allocation batch, segmented at two control boundaries with
    warm-start re-allocation.

    baseline = the W=1 materializing path (exact per-request latencies,
    O(C x N) memory — what replaying a day of traffic used to cost);
    fleet    = blocked scan (window=8) + in-carry latency sketch + macro-job
    coarsening (tail_lanes=2) + segmented warm-start replay.

    Acceptance: replay_speedup >= 3x at bounded memory (peak-RSS gauges in
    the JSON), sketch percentiles within SketchConfig.rel_error of the
    baseline's exact ones (same hashed service draws), zero growth rejected
    nowhere — plus a W-sweep detail table isolating the blocked-scan term.
    """
    import os
    import resource

    from repro.core.cim import allocate, simulate
    from repro.core.cim.simulate import CLOCK_HZ
    from repro.fabric import (
        CoarsenConfig,
        SinusoidalPoisson,
        TraceReplay,
        VirtualTimeFabric,
        arrival_times,
        get_telemetry,
        run_stream,
        run_trace_segments,
        segment_growth_plan,
    )

    tel = get_telemetry()
    rss_mb = lambda: resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024.0

    spec, prof = _profile("vgg11")
    bw = allocate(spec, prof, "blockwise", spec.min_pes() * 2)
    cap = simulate(spec, prof, bw, n_images=64).images_per_sec
    vt = VirtualTimeFabric(spec, prof)
    plan = segment_growth_plan(spec, prof, bw, budgets=[64, 128])

    # overridable for smoke runs; the committed BENCH json uses the default
    n = int(os.environ.get("FLEET_BENCH_REQUESTS", 1_000_000))
    rate = 0.6 * cap / CLOCK_HZ
    # two diurnal cycles across the trace span
    trace = SinusoidalPoisson(
        n, base_rate=rate, period=n / rate / 2.0, amplitude=0.5, seed=0
    )
    times = arrival_times(trace)
    # C=2 candidates: hold the starting allocation vs grow at each boundary
    segs = [[bw, plan[0]], [bw, plan[1]], [bw, plan[2]]]
    bounds = [float(times[n // 3]), float(times[2 * n // 3])]
    coarsen = CoarsenConfig(tail_lanes=2)

    # ---- W-sweep (exact kernel, small slice): the blocked-scan term alone
    n_sweep = min(20_000, n)
    tr_sweep = TraceReplay(times[:n_sweep])
    for w in (1, 2, 4, 8, 16):
        run_stream(vt, [bw, plan[0]], tr_sweep, seed=7, window=w)  # warm
        t0 = time.perf_counter()
        run_stream(vt, [bw, plan[0]], tr_sweep, seed=7, window=w)
        _detail(
            "fabric_fleet_wsweep", w,
            f"{(time.perf_counter() - t0) / n_sweep * 1e6:.1f}",
        )

    # ---- baseline: W=1, materialized (C, N) latencies, exact percentiles
    t0 = time.perf_counter()
    base = run_stream(
        vt, [bw, plan[0]], TraceReplay(times), seed=7, window=1,
        materialize=True,
    )
    t_base = time.perf_counter() - t0
    tel.gauge("fabric.fleet.bench.baseline_s", round(t_base, 1))
    tel.gauge_max("fabric.fleet.bench.baseline_peak_rss_mb", round(rss_mb(), 1))
    exact = base.exact_percentiles  # (2, 3) exact np.percentile reference
    sk_err = float(
        np.max(np.abs(base.percentiles - exact) / exact)
    )  # same run, same draws: pure bucketization error
    bound = base.sketches[0].config.rel_error
    assert sk_err <= bound, f"sketch error {sk_err:.4f} exceeds bound {bound}"

    # ---- fleet: blocked scan + sketch + coarsening + segmented warm-start
    # (compile cost stays inside t_fleet, mirroring the baseline's own
    # first-run compile — both sides pay their cold start once)
    t0 = time.perf_counter()
    fleet = run_trace_segments(
        vt, segs, times, bounds, seed=7, window=8, coarsen=coarsen,
    )
    t_fleet = time.perf_counter() - t0
    tel.gauge("fabric.fleet.bench.fleet_s", round(t_fleet, 1))
    tel.gauge_max("fabric.fleet.bench.peak_rss_mb", round(rss_mb(), 1))
    speedup = t_base / t_fleet
    stall = fleet.total_stall_cycles
    rps = fleet.n_requests / float(fleet.makespan.max()) * CLOCK_HZ

    _row(
        "fabric_fleet",
        t_fleet * 1e6,
        f"replay_speedup={speedup:.2f}x;configs=2;requests={n};"
        f"baseline_s={t_base:.1f};fleet_s={t_fleet:.1f};"
        f"sketch_rel_err={sk_err:.4f};sketch_bound={bound:.4f};"
        f"requests_per_sec={rps:.1f}",
    )
    ms = 1e3 / CLOCK_HZ
    for k, name in enumerate(("hold", "grow")):
        p = fleet.percentiles[k]
        _detail(
            "fabric_fleet", name, f"{p[0] * ms:.3f}", f"{p[1] * ms:.3f}",
            f"{p[2] * ms:.3f}", f"{stall[k]:.0f}",
        )
    for s in fleet.segments:
        _detail(
            "fabric_fleet_segment", f"{s.start:.0f}", s.n_requests,
            f"{s.arrays_added[1]:.0f}", f"{s.stall_cycles[1]:.0f}",
        )


def fabric_faults():
    """Fault-tolerant fabric: spare-fraction x failure-rate sweep on VGG11.

    Every point holds back part of its free-array budget as hot spares,
    generates one seeded failure trace (per-array exponential hazards),
    compiles it to a ``DegradePlan`` (spares re-place lost replicas,
    reprogramming charges drift stalls), and replays Poisson traffic on the
    segmented vtime engine.  Headline: ``availability`` (serviceable-
    capacity fraction, REQUIRED by check_drift) at the stress corner —
    max spare fraction under the max failure rate — plus the full
    (spare, rate) -> (availability, p99) table in the details.

    A second table ablates the event-engine ``RetryPolicy`` on a
    zero-survivor outage (one block dead for a third of the trace):
    infinite patience stalls requests until the repair seam, finite
    timeouts shed them — served/shed counts and the served-p99 quantify
    the trade.
    """
    import os

    from repro.core.cim import allocate, simulate
    from repro.core.cim.simulate import CLOCK_HZ, split_block_dups
    from repro.dse import FAULT_OBJECTIVES, fault_grid, pareto_frontier, run_fault_sweep
    from repro.fabric import (
        FabricSim,
        RetryPolicy,
        TraceReplay,
        degrade_plan_from_allocs,
        get_telemetry,
    )
    from repro.fabric.dispatch import Allocation

    tel = get_telemetry()
    # overridable for smoke runs; the committed BENCH json uses the default
    n_req = int(os.environ.get("FAULT_BENCH_REQUESTS", 600))

    spares = (0.0, 0.1, 0.25)
    rates = (1e-9, 1e-8)
    points = fault_grid(
        networks=("vgg11",), spare_fractions=spares, rates=rates
    )
    t0 = time.perf_counter()
    res = run_fault_sweep(points, n_requests=n_req, seed=0)
    t_sweep = time.perf_counter() - t0
    tel.gauge("fabric.faults.bench.sweep_s", round(t_sweep, 1))

    # headlines = the two stress corners at max failure rate: full spares
    # (the availability the spares buy — the acceptance claim) and zero
    # spares (the undefended floor, the more regression-sensitive number);
    # both keys contain "availability" so check_drift guards both
    stress = max(
        range(len(points)),
        key=lambda i: (points[i].spare_fraction, points[i].rate_per_array),
    )
    floor = max(
        range(len(points)),
        key=lambda i: (-points[i].spare_fraction, points[i].rate_per_array),
    )
    frontier = pareto_frontier(res, FAULT_OBJECTIVES)
    _row(
        "fabric_faults",
        t_sweep * 1e6,
        f"availability={res.availability[stress]:.4f}x;"
        f"availability_nospare={res.availability[floor]:.4f}x;"
        f"configs={len(points)};requests={n_req};"
        f"p99_under_failure_ms={res.p99_cycles[stress] / CLOCK_HZ * 1e3:.3f};"
        f"frontier_points={len(frontier)}",
    )
    for r in res.rows():
        _detail(
            "fabric_faults", f"{r['spare_fraction']:.2f}",
            f"{r['rate_per_array']:.0e}", r["spare_arrays"],
            f"{r['availability']:.4f}", f"{r['p50_ms']:.3f}",
            f"{r['p99_ms']:.3f}", r["n_killed"],
            f"{r['total_stall_cycles']:.0f}",
        )

    # ---- RetryPolicy ablation: one block loses ALL replicas for the middle
    # third of the trace (zero survivors), then revives at the repair seam
    spec, prof = _profile("vgg11")
    bw = allocate(spec, prof, "blockwise", spec.min_pes() * 2)
    cap = simulate(spec, prof, bw, n_images=64).images_per_sec
    times = np.cumsum(
        np.random.default_rng(0).exponential(1.0, size=n_req)
    ) / (0.6 * cap / CLOCK_HZ)
    flat = np.concatenate(bw.block_dups)
    dead = flat.copy()
    dead[0] = 0  # first block of the first layer: total outage
    dead_alloc = Allocation(
        bw.policy, None, split_block_dups(spec, dead),
        bw.arrays_used, bw.arrays_total,
    )
    bounds = [float(times[n_req // 3]), float(times[2 * n_req // 3])]
    plan = degrade_plan_from_allocs(
        spec, [bw, dead_alloc, bw], bounds, horizon=float(times[-1])
    )
    for name, policy in (
        ("stall_forever", RetryPolicy()),
        ("timeout_median", RetryPolicy(timeout_cycles=(bounds[1] - bounds[0]) / 2)),
        ("timeout_zero", RetryPolicy(timeout_cycles=0.0)),
    ):
        sim = FabricSim(spec, prof, bw, seed=0, failures=plan, retry=policy)
        out = sim.run(TraceReplay(times))
        comp = np.asarray(out.completions)
        served = comp[~np.isnan(comp)]
        lat = served - times[~np.isnan(comp)]
        _detail(
            "fabric_faults_retry", name, int(served.size),
            int(comp.size - served.size),
            f"{np.percentile(lat, 99) / CLOCK_HZ * 1e3:.3f}",
        )
        tel.count(f"fabric.faults.bench.shed_{name}", comp.size - served.size)


# ------------------------------------------------------------- telemetry
def telemetry():
    """Recorder overhead on the fabric_tail workload: the event engine and
    the jit virtual-time kernel run with stats ON vs OFF on the same
    (allocation, trace) pairs.  OFF is the compiled-out configuration — the
    instrumented branches never execute, so its cost must be the baseline's
    (~0% overhead, measured as the ratio of two OFF runs); ON must stay
    within 5% (acceptance).  Both modes are asserted bit-identical, and the
    vtime accumulators are asserted to reconcile with the event engine's
    counters at rtol 1e-9."""
    from repro.core.cim import allocate, simulate
    from repro.core.cim.simulate import CLOCK_HZ
    from repro.fabric import FabricSim, PoissonOpen, VirtualTimeFabric

    spec, prof = _profile("vgg11")
    pes = spec.min_pes() * 2
    wb = allocate(spec, prof, "weight_based", pes)
    bw = allocate(spec, prof, "blockwise", pes)
    cap = simulate(spec, prof, bw, n_images=64).images_per_sec
    n_req = 400
    allocs, procs = [], []
    for f in (0.5, 0.7):
        proc = PoissonOpen(n_requests=n_req, rate_per_cycle=f * cap / CLOCK_HZ, seed=5)
        for a in (wb, bw):
            allocs.append(a)
            procs.append(proc)

    def run_event(stats):
        return [
            FabricSim(spec, prof, a, seed=3, stats=stats).run(p)
            for a, p in zip(allocs, procs)
        ]

    # Overhead ratios use CPU time (process_time) and per-config minima over
    # 8 interleaved rounds: CPU time rejects wall-clock stalls from co-tenant
    # load, and taking the min per (config, mode) at sub-pass granularity
    # gives every sample many chances to land in a quiet window — the summed
    # minima then estimate the true quiet-machine times for each mode.
    run_event(False)  # warm numpy/python caches
    ev = {False: [1e30] * len(allocs), True: [1e30] * len(allocs)}
    ev2 = {False: [1e30] * len(allocs), True: [1e30] * len(allocs)}
    off, on = [None] * len(allocs), [None] * len(allocs)
    import gc

    gc.disable()  # GC pauses would land on whichever mode triggers them
    try:
        for _ in range(8):
            for i, (a, p) in enumerate(zip(allocs, procs)):
                for st in (False, True):
                    t0 = time.process_time()
                    res = FabricSim(spec, prof, a, seed=3, stats=st).run(p)
                    dt = time.process_time() - t0
                    if dt < ev[st][i]:
                        ev2[st][i] = ev[st][i]
                        ev[st][i] = dt
                    elif dt < ev2[st][i]:
                        ev2[st][i] = dt
                    (on if st else off)[i] = res
            gc.collect()
    finally:
        gc.enable()
    assert all(
        np.array_equal(a.completions, b.completions) for a, b in zip(off, on)
    ), "event engine stats=True changed completion times"
    t_on, ev_base = sum(ev[True]), sum(ev[False])
    ev_over = t_on / ev_base
    # spread between best and second-best UNinstrumented samples = the noise
    # floor the "on" overhead must be read against ("~0% compiled out")
    ev_noise = sum(ev2[False]) / ev_base

    vt = VirtualTimeFabric(spec, prof)
    vt.run_batch(allocs, procs, seed=3)  # compile both kernel variants
    vt.run_batch(allocs, procs, seed=3, collect_stats=True)
    vtm = {False: [], True: []}
    voff = von = None
    for _ in range(8):
        for st in (False, True):
            t0 = time.process_time()
            for _rep in range(3):  # ~1s samples: single batches are too short
                res = vt.run_batch(allocs, procs, seed=3, collect_stats=st)
            vtm[st].append(time.process_time() - t0)
            von, voff = (res, voff) if st else (von, res)
    assert np.array_equal(
        voff.completions, von.completions
    ), "vtime collect_stats=True changed completion times"
    tv_on, vt_base = min(vtm[True]) / 3, min(vtm[False]) / 3
    vt_over = tv_on / vt_base
    vt_noise = sorted(vtm[False])[1] / min(vtm[False])

    # event counters and in-kernel accumulators describe the same cycles
    recon = 0.0
    for i, r in enumerate(on):
        recon = max(
            recon,
            float(
                np.abs(r.stats.layer_service - von.layer_busy[i]).max()
                / max(von.layer_busy[i].max(), 1.0)
            ),
        )
    assert recon < 1e-9, f"event/vtime busy-cycle reconciliation off by {recon}"

    _row(
        f"telemetry_vgg11_{len(allocs)}cfg",
        t_on * 1e6,
        f"overhead_event_on={ev_over:.2f}x;"
        f"overhead_event_off={ev_noise:.2f}x;"
        f"overhead_vtime_on={vt_over:.2f}x;"
        f"overhead_vtime_off={vt_noise:.2f}x;"
        f"recon_rel_err={recon:.1e};bitident=True",
    )
    _detail("telemetry", "event_off_s", f"{ev_base:.3f}")
    _detail("telemetry", "event_on_s", f"{t_on:.3f}")
    _detail("telemetry", "vtime_off_s", f"{vt_base:.3f}")
    _detail("telemetry", "vtime_on_s", f"{tv_on:.3f}")


ALL = {
    "fig4": fig4,
    "fig6": fig6,
    "fig8": fig8,
    "fig9": fig9,
    "ablation": ablation,
    "expert_replication": expert_replication,
    "stage_balance": stage_balance,
    "continuous_batching": continuous_batching,
    "kernels": kernels,
    "roofline_table": roofline_table,
    "fabric_tail": fabric_tail,
    "fabric_drift": fabric_drift,
    "fabric_multitenant": fabric_multitenant,
    "fabric_multichip": fabric_multichip,
    "profile": profile,
    "dse": dse,
    "dse_fused": dse_fused,
    "fabric_fleet": fabric_fleet,
    "fabric_faults": fabric_faults,
    "telemetry": telemetry,
}


def main() -> None:
    args = sys.argv[1:]
    write_json = "--json" in args
    if write_json:
        args = [a for a in args if a != "--json"]
    names = args or list(ALL)
    unknown = [n for n in names if n not in ALL]
    if unknown:
        raise SystemExit(f"unknown bench(es) {unknown}; choose from {list(ALL)}")
    from repro.core.device import enable_compile_cache
    from repro.fabric.telemetry import telemetry_session

    enable_compile_cache()
    print("name,us_per_call,derived")
    config = _bench_config()

    for n in names:
        r0, d0 = len(_JSON_ROWS), len(_JSON_DETAILS)
        t0 = time.perf_counter()
        # a scoped recorder per bench: anything instrumented underneath (DSE
        # cache hit/miss counters, profile timers) lands in this bench's JSON
        with telemetry_session() as tel:
            ALL[n]()
            snap = tel.snapshot()
        wall = time.perf_counter() - t0
        if write_json:
            payload = {
                "config": config,
                "wall_clock_s": round(wall, 3),
                "rows": _JSON_ROWS[r0:],
                "details": _JSON_DETAILS[d0:],
            }
            if snap["counters"] or snap["gauges"] or snap["histograms"]:
                payload["telemetry"] = {
                    "counters": snap["counters"],
                    "gauges": snap["gauges"],
                    "histograms": snap["histograms"],
                }
            write_bench_json(n, payload)


if __name__ == "__main__":
    main()
