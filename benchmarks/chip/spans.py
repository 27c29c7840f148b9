"""Read the program's own spans: its telemetry snapshot and its trace.

The program records a span at each layer boundary (``Telemetry.timed``):
a name, start and end on ``time.perf_counter``, the index of its
``parent`` span and attributes such as ``configs``.  Each is also a
``jax.profiler.TraceAnnotation`` of the same name, so under a trace it lies
on the harness thread's host line, on the clock of the device events.

* ``per_call``: the self time of every span name under each call of a root
  span (a sweep job's ``dse.fused.sweep``, a replay job's ``vt.batch``);
* ``median_per_call``: the median over root calls, the warm-up left out,
  of some names' self seconds over the call's work, what most span readers
  report;
* ``idle_by_span``: the device's idle time split by the innermost program
  span open on the host at each instant.

A program without these spans gives nothing to read: each function then
returns ``None`` or an empty list, and a reader reports no number.
"""

from __future__ import annotations

import statistics
from pathlib import Path

import trace_reduce

UNATTRIBUTED = "unattributed"
TRACE_DIR = Path(__file__).resolve().parents[2] / ".bench_trace"


def _cover(intervals, lo, hi) -> float:
    return sum(e - s for s, e in trace_reduce._union(intervals, lo, hi))


def per_call(snapshot: dict, root: str) -> list[tuple[dict, dict]]:
    """For each span named ``root``: its attributes, and the self seconds
    (duration less the part its child spans cover) of each span name in its
    subtree, the root's own included."""
    spans = snapshot["spans"]
    kids: dict[int, list[int]] = {}
    for i, s in enumerate(spans):
        if s.get("parent") is not None:
            kids.setdefault(s["parent"], []).append(i)

    def self_s(i):
        s = spans[i]
        inner = [(spans[k]["start"], spans[k]["end"]) for k in kids.get(i, ())]
        return s["end"] - s["start"] - _cover(inner, s["start"], s["end"])

    calls = []
    for i, s in enumerate(spans):
        if s["name"] != root:
            continue
        seconds: dict[str, float] = {}
        todo = [i]
        while todo:
            j = todo.pop()
            name = spans[j]["name"]
            seconds[name] = seconds.get(name, 0.0) + self_s(j)
            todo.extend(kids.get(j, ()))
        attrs = {k: v for k, v in s.items() if k not in ("name", "start", "end", "parent")}
        calls.append((attrs, seconds))
    return calls


def median_per_call(snapshot: dict, root: str, names, per) -> float | None:
    """Median over the calls of ``root`` after the first of the summed self
    seconds of ``names`` over ``per(attrs)``, the call's work.  The first
    call is the warm-up job, which the job kind's constructor always runs
    first and which compiles: it is left out by its place, and the median
    leaves out the few calls slowed by the profiler.  ``None`` where no
    later call holds any of ``names``."""
    calls = per_call(snapshot, root)[1:]
    if not any(n in seconds for _, seconds in calls for n in names):
        return None
    return statistics.median(
        sum(seconds.get(n, 0.0) for n in names) / per(attrs) for attrs, seconds in calls
    )


def _harness_line(pd):
    """The events of the host line that holds the harness's ``bench.*``
    annotations, as (name, start_s, end_s)."""
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            events = list(trace_reduce._events(line))
            if any(name.startswith(trace_reduce.PREFIX) for name, _, _ in events):
                return events
    return []


def idle_by_span(summary, xplane_path) -> dict[str, float] | None:
    """Device 0's idle seconds in the traced window (``summary.gaps``) by
    the innermost program span open at each instant on the harness's host
    line, read again from the trace at ``xplane_path``; ``UNATTRIBUTED``
    where none is open.  ``None`` where the window holds no program span
    (a program that does not annotate its spans)."""
    from jax.profiler import ProfileData

    lo, hi = summary.window
    spans = [
        (name, s, e)
        for name, s, e in _harness_line(ProfileData.from_file(str(xplane_path)))
        if name.startswith(trace_reduce.PROGRAM) and e > lo and s < hi
    ]
    if not spans:
        return None
    out: dict[str, float] = {}
    for g in summary.gaps:
        lo, hi = g[0], g[1]
        inside = [sp for sp in spans if sp[2] > lo and sp[1] < hi]
        cuts = sorted({lo, hi, *(t for _, s, e in inside for t in (s, e) if lo < t < hi)})
        for a, b in zip(cuts, cuts[1:]):
            mid = 0.5 * (a + b)
            open_ = [sp for sp in inside if sp[1] <= mid < sp[2]]
            # nested spans of one thread: the innermost started last
            name = max(open_, key=lambda sp: (sp[1], -sp[2]))[0] if open_ else UNATTRIBUTED
            out[name] = out.get(name, 0.0) + (b - a)
    return out


def trace_file() -> Path | None:
    """The ``.xplane.pb`` the harness's traced run wrote."""
    return next(TRACE_DIR.rglob("*.xplane.pb"), None)
