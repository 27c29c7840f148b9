"""Telemetry layer: recorder semantics, zero-overhead-off guarantees, and
the hard correctness bar from the issue — instrumented runs are bit-identical
to uninstrumented ones (pinned by the golden fabric fixtures) and the jit
virtual-time accumulators reconcile with the event engine's counters."""

import json
import pathlib

import numpy as np
import pytest

from repro.core.cim import FabricTopology, allocate, allocate_placed
from repro.core.cim.simulate import CLOCK_HZ
from repro.fabric import (
    NULL_TELEMETRY,
    FabricSim,
    PoissonOpen,
    Telemetry,
    VirtualTimeFabric,
    get_telemetry,
    set_telemetry,
    telemetry_session,
)
from repro.obs import utilization_report

GOLDEN = pathlib.Path(__file__).parent / "golden"
RTOL = 1e-9


# ------------------------------------------------------------ recorder unit
def test_counters_gauges_histograms():
    t = Telemetry()
    t.count("jobs")
    t.count("jobs", 4)
    t.gauge("depth", 3.0)
    t.gauge("depth", 7.0)  # last write wins
    for v in (1.0, 2.0, 3.0, 4.0):
        t.observe("lat", v)
    snap = t.snapshot()
    assert snap["counters"]["jobs"] == 5
    assert snap["gauges"]["depth"] == 7.0
    h = snap["histograms"]["lat"]
    assert h["count"] == 4 and h["min"] == 1.0 and h["max"] == 4.0
    assert h["mean"] == pytest.approx(2.5)


def test_spans_and_timed():
    t = Telemetry()
    t.span("load", 1.0, 3.0, layer=2)
    with t.timed("work", tag="x"):
        pass
    snap = t.snapshot()
    names = [s["name"] for s in snap["spans"]]
    assert names == ["load", "work"]
    assert snap["spans"][0]["layer"] == 2
    work = snap["spans"][1]  # timed() records the span alone, no histogram twin
    assert work["tag"] == "x" and work["end"] >= work["start"] and work["parent"] is None
    assert "work.s" not in snap["histograms"]
    t.reset()
    assert t.snapshot() == {
        "counters": {},
        "gauges": {},
        "histograms": {},
        "spans": [],
    }


def test_null_telemetry_records_nothing():
    n = NULL_TELEMETRY
    n.count("x")
    n.gauge("x", 1.0)
    n.observe("x", 1.0)
    n.span("x", 0.0, 1.0)
    with n.timed("x"):
        pass
    assert not n.enabled
    assert n.snapshot() == {
        "counters": {},
        "gauges": {},
        "histograms": {},
        "spans": [],
    }


def test_session_installs_and_restores_global():
    assert get_telemetry() is NULL_TELEMETRY
    with telemetry_session() as t:
        assert get_telemetry() is t
        t.count("inside")
        with telemetry_session() as inner:  # nests; inner shadows outer
            assert get_telemetry() is inner
        assert get_telemetry() is t
    assert get_telemetry() is NULL_TELEMETRY
    assert t.snapshot()["counters"] == {"inside": 1}


def test_set_telemetry_none_resets_to_null():
    t = Telemetry()
    assert set_telemetry(t) is t
    try:
        assert get_telemetry() is t
    finally:
        set_telemetry(None)
    assert get_telemetry() is NULL_TELEMETRY


# ------------------------------------------- golden bit-identity (stats on)
@pytest.fixture(scope="module")
def vgg_golden(profiled):
    g = json.loads((GOLDEN / "vgg11_fabric_scalar.json").read_text())
    spec, prof = profiled("vgg11", **g["profile_params"])
    return spec, prof, g


def test_stats_on_matches_golden_bit_for_bit(vgg_golden):
    """stats=True must not perturb a single float: percentiles and
    completion times still equal the pre-telemetry pinned fixture exactly."""
    spec, prof, g = vgg_golden
    topo = FabricTopology.single_chip(g["results"][0]["n_pes"])
    for rec in g["results"]:
        kw = (
            {"offered_ips": rec["offered_ips"]}
            if rec["policy"] == "latency_aware"
            else {}
        )
        placed = allocate_placed(spec, prof, rec["policy"], topo, **kw)
        proc = PoissonOpen(
            g["n_requests"], rec["offered_ips"] / CLOCK_HZ, seed=g["arrival_seed"]
        )
        r = FabricSim(
            spec, prof, placed.allocation, seed=g["service_seed"],
            placement=placed.placement, stats=True,
        ).run(proc)
        pct = np.percentile(r.latencies, [50.0, 95.0, 99.0])
        assert pct.tolist() == rec["percentiles"], rec["policy"]
        assert float(r.completions.sum()) == rec["completions_sum"]
        assert r.completions[:5].tolist() == rec["completions_head"]
        assert r.completions[-5:].tolist() == rec["completions_tail"]
        assert r.stats is not None


# ----------------------------------------- event <-> vtime reconciliation
def _reconcile(spec, prof, policies, pes, n_req=80, load=0.7):
    from repro.core.cim import simulate

    allocs = [allocate(spec, prof, p, pes) for p in policies]
    cap = simulate(spec, prof, allocs[-1], n_images=64).images_per_sec
    proc = PoissonOpen(n_requests=n_req, rate_per_cycle=load * cap / CLOCK_HZ, seed=5)
    ev = [FabricSim(spec, prof, a, seed=3, stats=True).run(proc) for a in allocs]
    vt = VirtualTimeFabric(spec, prof)
    von = vt.run_batch(allocs, proc, seed=3, collect_stats=True)
    voff = vt.run_batch(allocs, proc, seed=3)
    # collect_stats must not change the kernel's answers...
    np.testing.assert_array_equal(voff.completions, von.completions)
    for i, r in enumerate(ev):
        # ...the engines stay bit-identical with telemetry on...
        np.testing.assert_array_equal(r.completions, von.completions[i])
        # ...and the in-kernel accumulators equal the event counters (fp
        # tolerance: scalar += vs vectorized sums accumulate in different
        # orders — documented in ISSUE acceptance)
        np.testing.assert_allclose(
            r.stats.layer_service, von.layer_busy[i], rtol=RTOL
        )
        np.testing.assert_allclose(
            r.stats.layer_queue_wait, von.layer_wait[i], rtol=RTOL, atol=1e-6
        )
    return ev


def test_vtime_accumulators_reconcile_vgg11(profiled):
    spec, prof = profiled("vgg11", n_images=1, sample_patches=64)
    _reconcile(spec, prof, ["weight_based", "blockwise"], spec.min_pes() * 2)


@pytest.mark.slow
def test_vtime_accumulators_reconcile_resnet18(profiled):
    spec, prof = profiled("resnet18", n_images=1, sample_patches=64)
    _reconcile(spec, prof, ["weight_based", "blockwise"], spec.min_pes() * 2)


# ----------------------------------------------------- stats semantics
def test_fabric_stats_invariants(profiled):
    spec, prof = profiled("vgg11", n_images=1, sample_patches=64)
    alloc = allocate(spec, prof, "blockwise", spec.min_pes() * 2)
    proc = PoissonOpen(n_requests=60, rate_per_cycle=2000.0 / CLOCK_HZ, seed=5)
    r = FabricSim(spec, prof, alloc, seed=3, stats=True).run(proc)
    st = r.stats
    L = len(spec.layers)
    assert st.layer_service.shape == (L,)
    assert st.layer_jobs.sum() > 0
    assert np.all(st.layer_queue_wait >= -1e-6)
    # replica lanes partition the pool's service cycles
    for li in range(L):
        lanes = np.concatenate([np.asarray(b) for b in st.replica_busy[li]])
        assert lanes.sum() == pytest.approx(st.layer_service[li], rel=1e-9)
    imb = st.replica_imbalance()
    assert imb.shape == (L,) and np.all(imb >= 1.0 - 1e-12)
    # requests traverse stages in order
    assert np.all(st.stage_exit >= st.stage_entry)
    assert np.all(np.diff(st.stage_entry, axis=1) >= 0)


def test_utilization_report_partitions_capacity(profiled):
    spec, prof = profiled("vgg11", n_images=1, sample_patches=64)
    alloc = allocate(spec, prof, "weight_based", spec.min_pes() * 2)
    proc = PoissonOpen(n_requests=60, rate_per_cycle=2000.0 / CLOCK_HZ, seed=5)
    r = FabricSim(spec, prof, alloc, seed=3, stats=True).run(proc)
    rep = utilization_report(r)
    total = rep.duty_cycle + rep.barrier_frac + rep.reprogram_frac + rep.starved_frac
    np.testing.assert_allclose(total, 1.0, atol=1e-9)
    assert np.all(rep.duty_cycle >= 0) and np.all(rep.duty_cycle <= 1 + 1e-12)
    assert 0.0 < rep.mean_duty_cycle <= 1.0
    txt = rep.format()
    assert "duty" in txt and str(len(spec.layers) - 1) in txt
    js = json.loads(json.dumps(rep.to_json()))  # round-trips through JSON
    assert js["n_requests"] == 60


def test_utilization_report_requires_stats(profiled):
    spec, prof = profiled("vgg11", n_images=1, sample_patches=64)
    alloc = allocate(spec, prof, "weight_based", spec.min_pes() * 2)
    proc = PoissonOpen(n_requests=10, rate_per_cycle=2000.0 / CLOCK_HZ, seed=5)
    r = FabricSim(spec, prof, alloc, seed=3).run(proc)
    with pytest.raises(ValueError, match="stats"):
        utilization_report(r)


# ------------------------------------------------------- allocation audit
def test_allocation_audit_traces_greedy_grants(profiled):
    from repro.obs import AllocationAudit

    spec, prof = profiled("vgg11", n_images=1, sample_patches=64)
    pes = spec.min_pes() * 2
    audit = AllocationAudit()
    a = allocate(spec, prof, "perf_layerwise", pes, audit=audit)
    plain = allocate(spec, prof, "perf_layerwise", pes)
    # auditing must not steer the allocator
    np.testing.assert_array_equal(a.layer_dups, plain.layer_dups)
    assert len(audit.grants) > 0
    assert audit.stop_reason == "budget"
    for e in audit.grants:
        assert e.latency_after < e.latency_before  # each grant helps its unit
        assert e.remaining >= 0
    # grants per unit reconcile with the final replica counts (the first
    # replica per layer is seeded before the greedy loop)
    per_unit = audit.summary()["grants_per_unit"]
    for li, d in enumerate(a.layer_dups.tolist()):
        assert per_unit.get(li, 0) == d - 1
    js = json.loads(json.dumps(audit.to_json()))
    assert len(js) == len(audit.entries)
