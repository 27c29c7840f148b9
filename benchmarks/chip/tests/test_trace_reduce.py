"""The trace reduction on a synthetic trace in the profiler's own schema."""

from __future__ import annotations

import pytest

import trace_reduce

MS = 10**9  # picoseconds in a millisecond


def _line(lid, name, events):
    ev = "".join(
        f"events {{ metadata_id: {m} offset_ps: {s * MS} duration_ps: {d * MS} }} "
        for m, s, d in events
    )
    return f'lines {{ id: {lid} name: "{name}" timestamp_ns: 0 {ev}}} '


def _meta(names):
    return "".join(
        f'event_metadata {{ key: {k} value {{ id: {k} name: "{n}" }} }} ' for k, n in names.items()
    )


OPS = [(1, 20, 20), (2, 30, 20), (1, 60, 10), (2, 90, 10)]


def _space(ops=OPS):
    host = (
        'planes { id: 1 name: "/host:CPU" '
        + _line(1, "python3", [(1, 10, 100), (2, 10, 5), (3, 15, 60), (2, 75, 5), (3, 80, 30),
                               (4, 72, 20), (6, 71, 12)])
        + _line(2, "pjrt-tasks", [(5, 50, 60)])
        + _meta({1: "bench.traced", 2: "bench.gen", 3: "bench.job", 4: "np.asarray(jax.Array)",
                 5: "Transpose", 6: "vt.fetch"})
        + "} "
    )
    # modules: 20-40, 30-50 (overlap), 60-70, 90-100; ops inside them
    dev = (
        'planes { id: 2 name: "/device:TPU:0" '
        + _line(1, "XLA Ops", ops)
        + _line(2, "XLA Modules", [(3, 20, 20), (3, 30, 20), (3, 60, 10), (4, 90, 10)])
        + _meta({1: "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p)", 2: "%copy.2 = f32[8]{0} copy(%x)",
                 3: "jit_fused(7)", 4: "jit_one(9)"})
        + "} "
    )
    return host + dev


@pytest.fixture(scope="module")
def summary():
    from jax.profiler import ProfileData

    return trace_reduce.reduce_profile(ProfileData.from_text_proto(_space()))


def test_window_and_busy(summary):
    assert summary.window == pytest.approx((0.010, 0.110))
    assert summary.devices == 1
    # union: 20-50, 60-70, 90-100 -> 50 ms
    assert summary.busy_s == pytest.approx(0.050)


def test_modules_and_ops(summary):
    assert summary.modules == pytest.approx({"jit_fused": 0.050, "jit_one": 0.010})
    assert summary.ops == pytest.approx({"%fusion.1": 0.030, "%copy.2": 0.030})


def test_gaps_are_named_by_the_host(summary):
    gaps = [(round(s * 1e3), round(e * 1e3), n) for s, e, n in summary.gaps]
    # the program's fetch span covers more than half of 70-90, jax's own
    # fetch event more of it; jax's events and other threads' name nothing
    assert gaps == [(10, 20, "bench.gen"), (50, 60, "bench.job"), (70, 90, "vt.fetch"),
                    (100, 110, "bench.job")]
    b = trace_reduce.breakdown(summary)
    assert b["idle_gaps"][0] == ["vt.fetch", pytest.approx(0.020)]
    assert sorted(n for n, _ in b["device_ops"]) == ["%copy.2", "%fusion.1"]


@pytest.mark.parametrize("ops", [[], OPS[:1]], ids=["none-kept", "most-dropped"])
def test_dropped_operations_leave_busy_and_modules_alone(ops):
    """A long scan has more operations than the device's trace buffer keeps:
    busy time and module times come from the programs alone."""
    from jax.profiler import ProfileData

    s = trace_reduce.reduce_profile(ProfileData.from_text_proto(_space(ops)))
    assert s.busy_s == pytest.approx(0.050)
    assert s.modules == pytest.approx({"jit_fused": 0.050, "jit_one": 0.010})


def test_a_trace_without_the_window_is_refused():
    from jax.profiler import ProfileData

    with pytest.raises(ValueError):
        trace_reduce.reduce_profile(ProfileData.from_text_proto(_space().replace("bench.traced", "x")))


# host notes (start, end, name) of one sweep job and the next job's inputs:
# the harness's job around the program's sweep, its grouping, allocation
# and a fetch
JOB = [(0, 100, "bench.job"), (2, 98, "dse.fused.sweep"), (4, 10, "dse.fused.group"),
       (10, 60, "dse.fused.allocate"), (60, 90, "dse.fused.fetch"), (100, 101, "bench.gen")]


@pytest.mark.parametrize("gap, name", [
    ((20, 50), "dse.fused.allocate"),  # nested notes: the innermost of those open
    ((64, 86), "dse.fused.fetch"),
    ((40, 65), "dse.fused.allocate"),  # straddles two spans: the one over half of it
    ((52, 68), "dse.fused.sweep"),  # straddles two spans evenly: their parent
    ((97, 99), "bench.job"),  # no program span open over half of it
    ((96, 106), "bench.job"),  # no note over half of it: the one that covers most
    ((100.25, 100.75), "bench.gen"),
    ((200, 210), "host: none"),
], ids=["nested", "nested-fetch", "straddle", "straddle-even", "no-program-span", "none-over-half",
        "gen", "nothing"])
def test_a_gap_is_named_by_the_innermost_note_over_half_of_it(gap, name):
    assert trace_reduce._doing(JOB, *gap) == name
