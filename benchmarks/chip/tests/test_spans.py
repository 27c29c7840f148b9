"""Reading the program's spans: self time per root call, the span readers'
median over calls, and the device's idle time split by the innermost
program span on a synthetic trace in the profiler's own schema."""

from __future__ import annotations

from types import SimpleNamespace

import pytest
from test_trace_reduce import _line, _meta

import bench
import spans
import trace_reduce

SWEEP_READERS = {
    "sweep.group_host_ms_per_Mconfig": "dse.fused.group",
    "sweep.alloc_host_ms_per_Mconfig": "dse.fused.allocate",
    "sweep.dispatch_host_ms_per_Mconfig": "dse.fused.dispatch",
    "sweep.fetch_host_ms_per_Mconfig": "dse.fused.fetch",
}


def _call(out: list, t0: float, root: str, attrs: dict, kids) -> float:
    """Append one root call whose children run back to back, with 1 ms of
    the root's own time before and after them; returns the call's end."""
    i = len(out)
    out.append({"name": root, "start": t0, "end": None, "parent": None, **attrs})
    t = t0 + 0.001
    for name, secs in kids:
        out.append({"name": name, "start": t, "end": t + secs, "parent": i})
        t += secs
    out[i]["end"] = t + 0.001
    return t + 0.002


def _sweep_snapshot(scales=(100.0, 1.0, 1.5, 1.2)):
    """Set-up spans of the kind every program version records, then one
    sweep job of 5,000 configs a scale: a slow warm-up first."""
    out = [{"name": "dse.capture", "start": 0.0, "end": 2.0, "parent": None, "network": "x"}]
    t = 3.0
    for scale in scales:
        kids = [("dse.fused.group", 0.02 * scale), ("dse.fused.allocate", 0.01 * scale),
                ("dse.fused.dispatch", 0.002 * scale), ("dse.fused.fetch", 0.004 * scale)]
        t = _call(out, t, "dse.fused.sweep", {"configs": 5000}, kids)
    return {"spans": out}


def _steady(snap, k):
    """Span name -> seconds of the ``k``-th sweep job (0: the warm-up)."""
    roots = [i for i, s in enumerate(snap["spans"]) if s["name"] == "dse.fused.sweep"]
    return {s["name"]: s["end"] - s["start"] for s in snap["spans"][roots[k]:roots[k] + 5]}


def _replay_spans(scales):
    out = []
    t = 0.0
    for scale in scales:
        kids = [("vt.arrivals", 0.001 * scale), ("vt.draws", 0.03 * scale),
                ("vt.pack", 0.02 * scale), ("vt.dispatch", 0.5 * scale), ("vt.fetch", 4.0),
                ("vt.percentiles", 0.004 * scale)]
        t = _call(out, t, "vt.batch", {"designs": 4}, kids)
    return {"spans": out}


def test_self_time_leaves_out_what_children_cover():
    snap = {"spans": []}
    _call(snap["spans"], 0.0, "vt.batch", {"designs": 4}, [("vt.pack", 0.5), ("vt.fetch", 0.25)])
    # a grandchild: the pack's self time loses it, the root's does not
    snap["spans"].append({"name": "vt.draws", "start": 0.101, "end": 0.201, "parent": 1})
    ((attrs, seconds),) = spans.per_call(snap, "vt.batch")
    assert attrs == {"designs": 4}
    assert seconds == pytest.approx({"vt.batch": 0.002, "vt.pack": 0.4, "vt.draws": 0.1,
                                     "vt.fetch": 0.25})


@pytest.mark.parametrize("metric,span", sorted(SWEEP_READERS.items()))
def test_sweep_readers_take_the_median_job(metric, span):
    snap = _sweep_snapshot()
    got = bench.reader(metric)(SimpleNamespace(telemetry=snap))
    # the slow warm-up is left out: the median of the later jobs is the one at 1.2x
    assert got == pytest.approx(1e9 * _steady(snap, 3)[span] / 5000)


@pytest.mark.parametrize("metric,span", sorted(SWEEP_READERS.items()))
def test_sweep_readers_leave_out_the_warm_up_of_two_calls(metric, span):
    """A traced replay holds the warm-up and one traced job: the median of
    the two would be their mean."""
    snap = _sweep_snapshot((100.0, 1.0))
    got = bench.reader(metric)(SimpleNamespace(telemetry=snap))
    assert got == pytest.approx(1e9 * _steady(snap, 1)[span] / 5000)


def test_replay_reader_sums_the_host_spans_of_the_median_batch():
    snap = _replay_spans((50.0, 1.0, 1.2, 1.1))
    got = bench.reader("replay.host_ms_per_batch")(SimpleNamespace(telemetry=snap))
    assert got == pytest.approx(1e3 * 0.055 * 1.1)


def test_replay_reader_leaves_out_the_warm_up_of_two_calls():
    snap = _replay_spans((50.0, 1.0))
    got = bench.reader("replay.host_ms_per_batch")(SimpleNamespace(telemetry=snap))
    assert got == pytest.approx(1e3 * 0.055)


@pytest.mark.parametrize("traced", [1, 2])
def test_replay_idle_reader_takes_dispatch_and_fetch_less_the_busy_time(traced):
    """Warm-up (its dispatch compiles), then traced batches, then batches
    after the trace stopped: only the traced ones are read."""
    scales = (50.0, 1.0, 1.2, 9.0)
    snap = _replay_spans(scales)
    busy = 3.9 * traced  # the scan: inside each batch's fetch
    ctx = SimpleNamespace(telemetry=snap, traced_jobs=traced, trace=SimpleNamespace(busy_s=busy))
    got = bench.reader("replay.dispatch_fetch_idle_ms_per_batch")(ctx)
    held = sum(0.5 * x + 4.0 for x in scales[1:1 + traced])
    assert got == pytest.approx(1e3 * (held - busy) / traced)


def test_replay_idle_reader_needs_the_traced_batches():
    ctx = SimpleNamespace(telemetry=_replay_spans((50.0, 1.0)), traced_jobs=2,
                          trace=SimpleNamespace(busy_s=1.0))
    assert bench.reader("replay.dispatch_fetch_idle_ms_per_batch")(ctx) is None


@pytest.mark.parametrize(
    "metric",
    [*SWEEP_READERS, "replay.host_ms_per_batch", "replay.dispatch_fetch_idle_ms_per_batch"],
)
def test_span_readers_read_nothing_from_a_program_without_the_spans(metric):
    """The spans the parent program records: set-up only, and no parents."""
    snap = {"spans": [{"name": "dse.capture", "start": 0.0, "end": 2.0, "network": "x"},
                      {"name": "dse.profile", "start": 2.0, "end": 2.5, "network": "x"}]}
    ctx = SimpleNamespace(telemetry=snap, traced_jobs=1, trace=SimpleNamespace(busy_s=4.1))
    assert bench.reader(metric)(ctx) is None


# ------------------------------------------------ idle time by program span
PROGRAM_EVENTS = [(4, 10, 80), (5, 10, 20), (6, 30, 20), (7, 60, 10)]


def _space(program=True):
    """Window 0-100 ms; device busy 50-60 and 70-80.  On the harness's line
    the sweep (10-90) holds grouping (10-30), allocation (30-50) and a
    fetch (60-70); 0-10 and 90-100 have no program span open.  Another
    thread's program-named event is not the harness's and counts for
    nothing."""
    harness = [(1, 0, 100), (2, 0, 5), (3, 5, 90)] + (PROGRAM_EVENTS if program else [])
    host = (
        'planes { id: 1 name: "/host:CPU" '
        + _line(1, "python3", harness)
        + _line(2, "pjrt-tasks", [(5, 80, 20)])
        + _meta({1: "bench.traced", 2: "bench.gen", 3: "bench.job", 4: "dse.fused.sweep",
                 5: "dse.fused.group", 6: "dse.fused.allocate", 7: "dse.fused.fetch"})
        + "} "
    )
    dev = (
        'planes { id: 2 name: "/device:TPU:0" '
        + _line(1, "XLA Modules", [(1, 50, 10), (1, 70, 10)])
        + _meta({1: "jit_fused(3)"})
        + "} "
    )
    return host + dev


def _trace(tmp_path, program=True):
    from jax.profiler import ProfileData

    text = _space(program)
    path = tmp_path / "plugins" / "profile" / "t" / "host.xplane.pb"
    path.parent.mkdir(parents=True)
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(text))
    return trace_reduce.reduce_profile(ProfileData.from_text_proto(text)), path


def test_idle_time_is_split_by_the_innermost_program_span(tmp_path):
    summary, path = _trace(tmp_path)
    assert summary.window_s == pytest.approx(0.1) and summary.busy_s == pytest.approx(0.02)
    idle = spans.idle_by_span(summary, path)
    assert idle == pytest.approx({
        spans.UNATTRIBUTED: 0.020,  # 0-10 and 90-100
        "dse.fused.group": 0.020,
        "dse.fused.allocate": 0.020,
        "dse.fused.fetch": 0.010,
        "dse.fused.sweep": 0.010,  # 80-90: the sweep's own time
    })
    assert sum(idle.values()) == pytest.approx(summary.window_s - summary.busy_s)


def test_unattributed_share_reader(tmp_path, monkeypatch):
    summary, _ = _trace(tmp_path)
    monkeypatch.setattr(spans, "TRACE_DIR", tmp_path)
    got = bench.reader("sweep.idle_unattributed_share")(SimpleNamespace(trace=summary))
    assert got == pytest.approx(20.0)


def test_a_trace_without_program_spans_reads_nothing(tmp_path, monkeypatch):
    summary, path = _trace(tmp_path, program=False)
    assert spans.idle_by_span(summary, path) is None
    monkeypatch.setattr(spans, "TRACE_DIR", tmp_path)
    assert bench.reader("sweep.idle_unattributed_share")(SimpleNamespace(trace=summary)) is None
    monkeypatch.setattr(spans, "TRACE_DIR", tmp_path / "none")
    assert bench.reader("sweep.idle_unattributed_share")(SimpleNamespace(trace=summary)) is None
