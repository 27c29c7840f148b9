"""The program's own spans: the recorder's parent tree and its profiler
annotations, the spans the fused sweep and the replay batch record at their
layer boundaries, and the XLA module names the chip benchmark's readers
match.  Spans never move a simulated number."""

import re
import time
from pathlib import Path

import numpy as np

from repro.core.cim import allocate
from repro.core.cim.cost import DEFAULT_ARRAY
from repro.core.cim.simulate import CLOCK_HZ
from repro.core.precision import to_bits, x64
from repro.dse import design_grid, get_fused_pipeline, run_fused_sweep
from repro.dse.sweep import get_profiled
from repro.fabric import (
    NULL_TELEMETRY,
    PoissonOpen,
    Telemetry,
    VirtualTimeFabric,
    telemetry_session,
)

METRICS = Path(__file__).resolve().parents[1] / "benchmarks" / "chip" / "metrics"
SWEEP_SPANS = {
    "dse.fused.sweep", "dse.fused.group", "dse.fused.allocate",
    "dse.fused.dispatch", "dse.fused.fetch",
}
VT_SPANS = {
    "vt.batch", "vt.arrivals", "vt.draws", "vt.pack",
    "vt.dispatch", "vt.fetch", "vt.percentiles",
}
POLS = ("baseline", "weight_based", "perf_layerwise", "blockwise")


def _self_s(spans, i):
    kids = [s for s in spans if s["parent"] == i]
    return spans[i]["end"] - spans[i]["start"] - sum(k["end"] - k["start"] for k in kids)


# ------------------------------------------------------------ the recorder
def test_parents_form_the_tree_and_self_time_excludes_children():
    t = Telemetry()
    with t.timed("root", configs=3):
        time.sleep(0.02)
        with t.timed("a"):
            with t.timed("a1"):
                time.sleep(0.005)
        with t.timed("b", late=7):
            time.sleep(0.005)
    t.span("after", 0.0, 1.0)
    spans = t.snapshot()["spans"]
    assert [s["name"] for s in spans] == ["root", "a", "a1", "b", "after"]
    assert [s["parent"] for s in spans] == [None, 0, 1, 0, None]
    assert spans[0]["configs"] == 3 and spans[3]["late"] == 7
    for s in spans[1:4]:
        p = spans[s["parent"]]
        assert p["start"] <= s["start"] <= s["end"] <= p["end"]
    # self time = duration less the children's cover: the root's own sleep
    assert 0.02 <= _self_s(spans, 0) < spans[0]["end"] - spans[0]["start"] - 0.01
    assert _self_s(spans, 1) < 0.005 <= _self_s(spans, 2)


def test_timed_annotates_the_trace_only_when_enabled(monkeypatch):
    import jax.profiler

    entered = []

    class Recording:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            entered.append(self.name)

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Recording)
    with NULL_TELEMETRY.timed("dse.fused.group"):
        pass
    assert entered == []
    t = Telemetry()
    with t.timed("dse.fused.group", configs=1):
        with t.timed("dse.fused.allocate"):
            pass
    assert entered == ["dse.fused.group", "dse.fused.allocate"]
    assert t.snapshot()["histograms"] == {}  # no <name>.s twin


def test_timed_span_lands_on_a_host_line_of_the_profiler_trace(tmp_path):
    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData

    t = Telemetry()
    with jax.profiler.trace(str(tmp_path)):
        with t.timed("dse.fused.group"):
            jnp.arange(4).sum().block_until_ready()
        with NULL_TELEMETRY.timed("dse.fused.fetch"):
            pass
    (path,) = tmp_path.rglob("*.xplane.pb")
    names = {
        ev.name
        for plane in ProfileData.from_file(str(path)).planes
        if plane.name.startswith("/host:")
        for line in plane.lines
        for ev in line.events
    }
    assert "dse.fused.group" in names
    assert "dse.fused.fetch" not in names


# --------------------------------------------------- spans where work happens
def _sweep_points():
    arrays = (
        DEFAULT_ARRAY,
        DEFAULT_ARRAY.variant(adc_bits=5),
        DEFAULT_ARRAY.variant(rows=256, cols=256),
    )
    return design_grid(
        networks=("vgg11",), policies=POLS, pe_multipliers=(1.0, 2.0), arrays=arrays
    )


def test_fused_sweep_records_its_layer_spans_and_outputs_do_not_move():
    pts = _sweep_points()
    C = len(pts)
    plain = run_fused_sweep(pts, chunk=5)  # NULL recorder; warms every cache
    with telemetry_session() as tel:
        traced = run_fused_sweep(pts, chunk=5)
        spans = tel.snapshot()["spans"]
    for col in ("total_cycles", "images_per_sec", "mean_utilization",
                "arrays_used", "arrays_total"):
        np.testing.assert_array_equal(getattr(plain, col), getattr(traced, col))

    assert {s["name"] for s in spans} == SWEEP_SPANS
    (root,) = [i for i, s in enumerate(spans) if s["name"] == "dse.fused.sweep"]
    assert root == 0 and spans[0]["parent"] is None and spans[0]["configs"] == C
    assert all(s["parent"] == root for s in spans[1:])

    def configs(name):
        return [s["configs"] for s in spans if s["name"] == name]

    # the grouping loop over every point, then one span per geometry group
    loop, *groups = configs("dse.fused.group")
    assert loop == C and len(groups) == 2 and sum(groups) == C
    assert sum(s["name"] == "dse.fused.allocate" for s in spans) == len(groups)
    # one dispatch and one fetch per chunk, padding rows not counted
    assert configs("dse.fused.dispatch") == configs("dse.fused.fetch")
    assert sum(configs("dse.fused.dispatch")) == C
    assert max(configs("dse.fused.dispatch")) == 5


def test_run_batch_records_its_layer_spans_and_outputs_do_not_move():
    spec, prof = get_profiled("vgg11")
    n_pes = 2 * spec.min_pes()
    allocs = [allocate(spec, prof, p, n_pes) for p in POLS]
    vt = VirtualTimeFabric(spec, prof)
    proc = PoissonOpen(6, 1e-6, seed=2)
    plain = vt.run_batch(allocs, proc, seed=3)
    with telemetry_session() as tel:
        traced = vt.run_batch(allocs, proc, seed=3)
        spans = tel.snapshot()["spans"]
    np.testing.assert_array_equal(plain.completions, traced.completions)
    np.testing.assert_array_equal(plain.percentiles, traced.percentiles)

    assert {s["name"] for s in spans} == VT_SPANS
    root = spans[0]
    assert root["name"] == "vt.batch" and root["parent"] is None
    assert root["designs"] == 4
    assert all(s["parent"] == 0 for s in spans[1:])
    count = {n: sum(s["name"] == n for s in spans) for n in VT_SPANS}
    groups = len(vt._groups(allocs))
    assert count == {"vt.batch": 1, "vt.arrivals": 1, "vt.draws": 1, "vt.pack": 1,
                     "vt.dispatch": groups, "vt.fetch": groups, "vt.percentiles": groups}


# ------------------------------------------ module names the readers match
def _reader_module(name):
    text = (METRICS / f"{name}.py").read_text()
    return re.search(r'^MODULE = "(\w+)"$', text, re.M).group(1)


def _module_name(lowered):
    return re.search(r"module @(\w+)", lowered.as_text()).group(1)


def test_xla_module_names_are_those_the_benchmark_readers_match():
    pipe = get_fused_pipeline("vgg11", DEFAULT_ARRAY, (3,))
    fn = pipe._fn("L", 64, CLOCK_HZ)
    c = 4
    with x64():
        lowered = fn.func.lower(
            *fn.args, np.zeros(c, np.int32), np.zeros(c, bool), np.ones((c, pipe.L))
        )
    for reader in ("sweep.eval_device_ms_per_Mconfig", "sweep.eval_roofline"):
        assert _module_name(lowered) == _reader_module(reader)

    spec, prof = get_profiled("vgg11")
    vt = VirtualTimeFabric(spec, prof)
    (g,) = vt._groups([allocate(spec, prof, "blockwise", 2 * spec.min_pes())])
    n = 2
    fn = vt._jax_runner(g, None, n)
    idx = tuple(np.zeros((n, l.patches_per_image), np.int64) for l in spec.layers)
    with x64():
        lowered = fn.lower(
            tuple(to_bits(f) for f in g.frees), None, to_bits(np.zeros((1, n))), idx
        )
    assert _module_name(lowered) == _reader_module("replay.scan_ns_per_job")

