#!/usr/bin/env python3
"""Run one cell of the chip benchmark once.

    python3 benchmarks/chip/bench.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``BENCHMARK.json``'s ``workloads``; its
configuration (``configs/<name>.json``), its traffic mix
(``traffic/<name>.json``) and each per-layer metric (``metrics/<name>.py``)
are files of their own, found by name.  The run sets up the program and
warms up every shape of the cell with one job, then runs jobs back to back
until ``--seconds`` have passed, fetching each job's results to host numpy.
After the window it compares the set-up capture and a sample of the
window's outputs with the plain reference that the configuration names
(``reference.py`` where it names none; ``traffic.reference_of``) and
prints, as the last line of standard output, one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and with ``--trace 1``
the per-layer metrics and a ``breakdown`` of the traced window), then
``checks``, each compared number beside its limit.  The same numbers close
standard error.

It runs only on a TPU: with no accelerator, or fewer chips than the cell
asks for, or without the program beside it, it exits non-zero and prints no
result.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]  # the checkout: BENCHMARK.json and src/
TRACE_DIR = ROOT / ".bench_trace"
CACHE_DIR = ROOT / ".jax_cache"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"


class Refused(Exception):
    """The run cannot produce a result here; the message says why."""


# ------------------------------------------------------------------ finding
def benchmark() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def data(kind: str, name: str) -> dict:
    path = HERE / kind / f"{name}.json"
    if not path.is_file():
        raise Refused(f"no {kind} file {path.relative_to(HERE)}")
    return json.loads(path.read_text())


def reader(name: str):
    """The ``read(ctx)`` function of ``metrics/<name>.py``."""
    path = HERE / "metrics" / f"{name}.py"
    if not path.is_file():
        raise Refused(f"no reader metrics/{name}.py")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def cell_plan(bench: dict, name: str) -> SimpleNamespace:
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise Refused(f"no workload {name!r} in BENCHMARK.json; known: {sorted(cells)}")
    w = cells[name]
    return SimpleNamespace(
        cell=w,
        config=data("configs", w["config"]),
        mix=data("traffic", w["traffic"]),
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, name)],
    )


# ------------------------------------------------------------------ device
def own_directories() -> None:
    """Before jax is imported: its compile cache goes to a fixed directory in
    the checkout, which the program's ``enable_compile_cache`` takes from
    ``JAX_COMPILATION_CACHE_DIR``, and libtpu writes no logs of its own."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")


def require_device(chips: int) -> None:
    import jax

    backend = jax.default_backend()
    if backend != "tpu":
        raise Refused(f"no TPU: jax's backend is {backend!r}; this benchmark runs only on a TPU")
    n = len(jax.devices())
    if n < chips:
        raise Refused(f"the cell needs {chips} TPU chips, jax finds {n}")


def device_info() -> dict:
    import jax

    devs = jax.devices()
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


# --------------------------------------------------------------------- run
def set_up(name: str) -> SimpleNamespace:
    """The cell's plan, the program on the path, the chip found and the
    compile cache on: what every process that drives the cell does first."""
    plan = cell_plan(benchmark(), name)
    src = ROOT / "src"
    if not (src / "repro").is_dir():
        raise Refused(f"the program is not here: no {src / 'repro'}")
    sys.path.insert(0, str(src))
    sys.path.insert(0, str(HERE))
    own_directories()

    import jax

    require_device(int(plan.cell["chips"]))
    from repro.core.device import enable_compile_cache

    enable_compile_cache()
    # every program of the cell goes to the cache, however fast it compiles
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return plan


def run(name: str, seed: int, seconds: float, trace: bool) -> tuple[dict, dict]:
    """One run of one cell: the result object, and what the window did
    (its seconds, jobs, compiles, and the outputs the check compared)."""
    plan = set_up(name)

    import jax

    from repro.fabric.telemetry import Telemetry, set_telemetry

    import counts
    import traffic
    import trace_reduce

    compiles, misses = [], []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, secs, **kw: compiles.append((time.perf_counter(), secs))
        if event == COMPILE_EVENT else None
    )
    jax.monitoring.register_event_listener(
        lambda event, **kw: misses.append(time.perf_counter()) if event == CACHE_MISS_EVENT else None
    )
    tel = set_telemetry(Telemetry())

    job = traffic.make(plan.config, plan.mix, seed)
    trace_jobs = int(plan.mix["trace_jobs"]) if trace else 0
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
        traced = jax.profiler.TraceAnnotation(trace_reduce.WINDOW)
        traced.__enter__()

    t_start = time.perf_counter()
    setup_s = t_start - T0
    kept, work, j, job_s = [], 0, 0, []
    while True:
        t_job = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.gen"):
            inp = job.inputs(j)
        with jax.profiler.TraceAnnotation("bench.job"):
            out = job.run(inp)
        job_s.append(time.perf_counter() - t_job)
        kept.append((j, inp, out))
        work += job.work(out)
        j += 1
        if j == trace_jobs:
            traced.__exit__(None, None, None)
            jax.profiler.stop_trace()
        if time.perf_counter() - t_start >= seconds and j >= trace_jobs:
            break
    window_s = time.perf_counter() - t_start
    setup_compiles = [s for t, s in compiles if t < t_start]
    window_compiles = sum(1 for t, _ in compiles if t >= t_start)
    device = device_info()

    unsound = sum(job.sound(o) for _, _, o in kept)
    numbers, limits = traffic.compare(job, kept)
    checks = {k: {"value": numbers[k], "limit": lim} for k, lim in limits.items()}
    correct = unsound == 0 and all(c["value"] <= c["limit"] for c in checks.values())

    result = {"correct": bool(correct), "attempted": work, "failed": unsound + numbers["failed"]}
    if trace:
        summary = trace_reduce.load(next(TRACE_DIR.rglob("*.xplane.pb")))
        ctx = SimpleNamespace(
            trace=summary, telemetry=tel.snapshot(), setup_compile_s=sum(setup_compiles),
            job=job, traced_jobs=trace_jobs, peaks=counts.peaks(device["kind"]),
            config=plan.config, mix=plan.mix,
        )
        metrics = {}
        for m in plan.per_layer:
            v = reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        device.update(busy_s=summary.busy_s, window_s=summary.window_s)
        result.update(metrics=metrics, device=device, breakdown=trace_reduce.breakdown(summary))
    else:
        have = {"setup_s": setup_s, job.rate_metric: work / window_s}
        metrics = {}
        for m in plan.end_to_end:
            if m["name"] not in have:
                raise Refused(f"the harness does not measure {m['name']!r} for {name}")
            metrics[m["name"]] = {"value": have[m["name"]], "unit": m["unit"]}
        result.update(metrics=metrics, device=device)
    result["checks"] = checks
    window = {"seconds": window_s, "jobs": j, "job_s": job_s, "compiles": window_compiles,
              "setup_compiles": len(setup_compiles),
              "setup_cache_misses": sum(1 for t in misses if t < t_start),
              "checked": numbers["checked"]}
    return result, window


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result, window = run(args.workload, args.seed, args.seconds, bool(args.trace))
    except Refused as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    print(f"window: {json.dumps(window)}", file=sys.stderr)
    for k, c in result["checks"].items():
        print(f"check {k}: {c['value']!r} (limit {c['limit']!r})", file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
