"""Reduce a profiler trace (``.xplane.pb``) to what the per-layer metrics read.

* device busy time: the union of the intervals in which a program ran on
  each device (its ``XLA Modules`` line), averaged over the devices, inside
  the traced window.  Programs and not their operations: a replay's scan
  runs millions of tiny operations, more than the device's trace buffer
  keeps, while its programs are a handful of events;
* device time per XLA module and per operation (``XLA Ops``, where the
  trace holds them), operations named by their HLO instruction;
* the events of the host thread that ran the harness: its annotations
  (``jax.profiler.TraceAnnotation``, named ``bench.*``) and the program's
  spans inside them (named ``dse.*`` or ``vt.*``), which name what the host
  was doing in each idle gap of the device.  What jax records there
  (``PjitFunction(...)`` dispatches, ``np.asarray(jax.Array)`` fetches)
  names no gap.

The traced window is the span of the ``bench.traced`` annotation.  Device
and host events share the profiler's clock.
"""

from __future__ import annotations

from dataclasses import dataclass, field

WINDOW = "bench.traced"
PREFIX = "bench."
PROGRAM = ("dse.", "vt.")  # names of the program's spans


@dataclass
class Summary:
    window: tuple[float, float]  # seconds
    devices: int
    busy_s: float  # mean over devices of the union of program intervals
    gaps: list  # (start_s, end_s, host annotation) idle gaps, device 0
    modules: dict = field(default_factory=dict)  # module name -> seconds, all devices
    ops: dict = field(default_factory=dict)  # op name -> seconds, all devices

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]


def _union(intervals, lo, hi):
    """Merged [start, end) intervals clipped to [lo, hi]."""
    out = []
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _events(line):
    for ev in line.events:
        yield ev.name, ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9


def _base_name(name: str) -> str:
    """``jit_fused(123)`` -> ``jit_fused``: module events carry a run id."""
    return name.split("(", 1)[0]


def reduce_profile(pd) -> Summary:
    """``pd``: a ``jax.profiler.ProfileData``."""
    notes, window = [], None
    devices = []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                events = list(_events(line))
                if not any(name.startswith(PREFIX) for name, _, _ in events):
                    continue
                for name, s, e in events:
                    if name == WINDOW:
                        window = (s, e)
                    elif name.startswith((PREFIX, *PROGRAM)):
                        notes.append((s, e, name))
        elif plane.name.startswith("/device:") and "TPU" in plane.name and "Core" not in plane.name:
            devices.append(plane)
    if window is None:
        raise ValueError(f"no {WINDOW!r} annotation in the trace")
    if not devices:
        raise ValueError("no device plane in the trace")
    lo, hi = window
    busy, modules, ops, gaps = [], {}, {}, []
    for k, plane in enumerate(devices):
        intervals = []
        for line in plane.lines:
            if line.name == "XLA Ops":
                for name, s, e in _events(line):
                    if e > lo and s < hi:
                        n = name.split(" = ", 1)[0]
                        ops[n] = ops.get(n, 0.0) + min(e, hi) - max(s, lo)
            elif line.name == "XLA Modules":
                for name, s, e in _events(line):
                    if e > lo and s < hi:
                        intervals.append((s, e))
                        n = _base_name(name)
                        modules[n] = modules.get(n, 0.0) + min(e, hi) - max(s, lo)
        merged = _union(intervals, lo, hi)
        busy.append(sum(e - s for s, e in merged))
        if k == 0:
            edges = [lo] + [x for iv in merged for x in iv] + [hi]
            for s, e in zip(edges[::2], edges[1::2]):
                if e > s:
                    gaps.append((s, e, _doing(notes, s, e)))
    return Summary(window, len(devices), sum(busy) / len(busy), gaps, modules, ops)


def _doing(notes, s, e) -> str:
    """What the host was doing in the gap [s, e]: the shortest (innermost)
    of the notes (harness annotations and program spans) that cover more
    than half of it, so that a program span names the gap before the
    harness's ``bench.job`` around it; where none covers half, the one that
    covers most."""
    covers = [(min(ne, e) - max(ns, s), ne - ns, name) for ns, ne, name in notes]
    covers = [c for c in covers if c[0] > 0]
    if not covers:
        return "host: none"
    over_half = [c for c in covers if c[0] > 0.5 * (e - s)]
    if over_half:
        return min(over_half, key=lambda c: c[1])[2]
    return max(covers, key=lambda c: (c[0], -c[1]))[2]


def load(path) -> Summary:
    from jax.profiler import ProfileData

    return reduce_profile(ProfileData.from_file(str(path)))


def breakdown(summary: Summary, top: int = 10) -> dict:
    ops = sorted(summary.ops.items(), key=lambda kv: -kv[1])[:top]
    gaps = sorted(summary.gaps, key=lambda g: -(g[1] - g[0]))[:top]
    return {
        "device_ops": [[name, s] for name, s in ops],
        "idle_gaps": [[name, e - s] for s, e, name in gaps],
    }
