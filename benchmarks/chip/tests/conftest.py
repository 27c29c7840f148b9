"""Helpers for the benchmark's self-tests: a throwaway checkout that holds a
copy of the benchmark, extra files dropped into it, and the program."""

from __future__ import annotations

import importlib.util
import itertools
import json
import shutil
import sys
from pathlib import Path

import pytest

CHIP = Path(__file__).resolve().parents[1]
REPO = CHIP.parents[1]
sys.path.insert(0, str(CHIP))

# small enough for the CPU: 2 variants x 4 policies x 5 PE counts (VGG11's
# 71 to 75) a job
TINY_SWEEP = {
    "job": "sweep", "about": "test size", "rows": [128], "adc_bits": [3, 4],
    "policies": ["baseline", "weight_based", "perf_layerwise", "blockwise"],
    "pe_multiplier": [1.0, 1.05],
    "check_configs_per_variant_policy": 2, "trace_jobs": 1,
}
# 2 requests a job on VGG11 (1,448 patch jobs each)
TINY_REPLAY = {
    "job": "replay", "about": "test size",
    "policies": ["baseline", "weight_based", "perf_layerwise", "blockwise"],
    "pe_multiplier": 2.0, "load": 0.7, "patch_jobs_per_job": 2896,
    "check_jobs": 1, "trace_jobs": 1,
}
_ids = itertools.count()


def _metric(name, moves, cell):
    return {"name": name, "unit": "s", "better": "lower", "source": "program_span",
            "layer": "test", "moves": moves, "workloads": [cell]}


def make_checkout(tmp: Path, mixes: dict, metrics: dict | None = None, with_program=True,
                  config: str = "vgg11", configs: dict | None = None, files: dict | None = None):
    """A checkout with the benchmark, the given traffic mixes dropped in as
    ``traffic/<name>.json`` (one cell each, ``<config>.<name>`` on the
    configuration ``config``), configurations ``configs/<name>.json``,
    metric readers ``metrics/<name>.py``, other ``files`` by their path in
    ``benchmarks/chip`` and, unless told otherwise, the program.  Returns
    the checkout's own ``bench`` module."""
    chip = tmp / "benchmarks" / "chip"
    shutil.copytree(CHIP, chip, ignore=shutil.ignore_patterns("__pycache__", "tests"))
    for name, mix in mixes.items():
        (chip / "traffic" / f"{name}.json").write_text(json.dumps(mix))
    for name, body in (configs or {}).items():
        (chip / "configs" / f"{name}.json").write_text(json.dumps(body))
    for name, body in (metrics or {}).items():
        (chip / "metrics" / f"{name}.py").write_text(body)
    for name, body in (files or {}).items():
        (chip / name).write_text(body)
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    for name in configs or {}:
        bench["configs"].append({"name": name, "source": "test", "reduced": [], "why": "test",
                                 "file": f"benchmarks/chip/configs/{name}.json"})
    for name, mix in mixes.items():
        cell = f"{config}.{name}"
        rate = "dse_configs_per_s" if mix["job"] == "sweep" else "replay_requests_per_s"
        bench["workloads"].append({"name": cell, "config": config, "traffic": name,
                                   "chips": 1, "why": "test"})
        for m in bench["end_to_end"]:
            if m["name"] == rate:
                m["workloads"].append(cell)
        for m in (metrics or {}):
            bench["per_layer"].append(_metric(m, "setup_s", cell))
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    if with_program:
        (tmp / "src").symlink_to(REPO / "src", target_is_directory=True)
    spec = importlib.util.spec_from_file_location(f"bench_checkout_{next(_ids)}", chip / "bench.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# the harness's modules, imported by plain name from the directory that a
# run puts first on ``sys.path``, and the files that ``traffic`` loads
HARNESS = {"bench", "control", "counts", "reference", "spans", "trace_reduce", "traffic"}


def _harness_modules() -> set[str]:
    return {k for k in sys.modules if k in HARNESS or k.startswith("bench_")}


@pytest.fixture
def checkout(tmp_path, monkeypatch):
    """``checkout(mixes, metrics, **kw)`` -> the checkout's ``bench``
    module, with its look for a TPU skipped so that the rest of a run drives
    the CPU.  The run imports the checkout's own harness modules, and what
    it imported and put on ``sys.path`` is undone after the test."""

    # no compile cache: the checkout's would be the repository's own
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jax_cache"))
    monkeypatch.setattr(sys, "path", list(sys.path))
    saved = {k: sys.modules.pop(k) for k in _harness_modules()}

    def make(mixes, metrics=None, **kw):
        mod = make_checkout(tmp_path, mixes, metrics, **kw)
        monkeypatch.setattr(mod, "require_device", lambda chips: None)
        return mod

    yield make
    for k in _harness_modules():
        del sys.modules[k]
    sys.modules.update(saved)
