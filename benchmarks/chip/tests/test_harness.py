"""The harness finds its parts by name, prints its result line, and
refuses to run without a TPU or without the program."""

from __future__ import annotations

import dataclasses
import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest
from conftest import CHIP, REPO, TINY_REPLAY, TINY_SWEEP, make_checkout

import bench
import traffic

SPEC = json.loads((REPO / "BENCHMARK.json").read_text())
# what a reference module gives each job kind: reference.py's docstring
REFERENCE_API = {
    "sweep": ["first_layer_samples", "Array", "min_pes", "n_blocks", "Network", "allocate", "evaluate"],
}
REFERENCE_API["replay"] = REFERENCE_API["sweep"] + ["service_indices", "replay"]


def test_every_cell_finds_its_files():
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    for c in spec["configs"]:
        assert (REPO / c["file"]).is_file()
        assert json.loads((REPO / c["file"]).read_text())["name"] == c["name"]
    for w in spec["workloads"]:
        plan = bench.cell_plan(spec, w["name"])
        assert plan.config["name"] == w["config"]
        assert plan.mix["job"] in ("sweep", "replay")
        ref = traffic.reference_of(plan.config)
        assert all(hasattr(ref, name) for name in REFERENCE_API[plan.mix["job"]])
        assert [m["name"] for m in plan.end_to_end if m["name"] != "setup_s"]
        assert plan.per_layer
        for m in plan.per_layer:
            assert callable(bench.reader(m["name"]))


def test_dropped_in_files_are_picked_up(tmp_path):
    body = '"""Jobs in the traced window."""\n\n\ndef read(ctx):\n    return ctx.traced_jobs\n'
    mod = make_checkout(tmp_path, {"sweep_tiny": TINY_SWEEP}, {"test.traced_jobs": body})
    plan = mod.cell_plan(mod.benchmark(), "vgg11.sweep_tiny")
    assert plan.mix == TINY_SWEEP
    assert [m["name"] for m in plan.per_layer] == ["test.traced_jobs"]
    assert mod.reader("test.traced_jobs")(type("Ctx", (), {"traced_jobs": 3})) == 3


def test_result_line_has_only_its_keys(checkout):
    mod = checkout({"sweep_tiny": TINY_SWEEP})
    result, window = mod.run("vgg11.sweep_tiny", 2**31 + 12345, 0.0, False)
    assert list(result) == ["correct", "attempted", "failed", "metrics", "device", "checks"]
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] == 2 * 4 * 5 * window["jobs"]
    assert set(result["metrics"]) == {"dse_configs_per_s", "setup_s"}
    assert set(result["device"]) == {"platform", "kind", "count", "memory_peak_bytes"}
    assert set(result["checks"]) == {"discrete_mismatch", "float_rel_err", "capture_conv1_mismatch"}
    assert window["compiles"] == 0


def test_replay_run_is_correct(checkout):
    mod = checkout({"replay_tiny": TINY_REPLAY})
    result, window = mod.run("vgg11.replay_tiny", 7, 0.0, False)
    assert result["correct"] is True
    assert result["attempted"] == 4 * 2 * window["jobs"]
    assert set(result["metrics"]) == {"replay_requests_per_s", "setup_s"}


def _cli(root: Path, env_extra: dict) -> subprocess.CompletedProcess:
    env = {**os.environ, **env_extra}
    cell = json.loads((root / "BENCHMARK.json").read_text())["workloads"][0]["name"]
    return subprocess.run(
        [sys.executable, str(root / "benchmarks" / "chip" / "bench.py"), "--workload",
         cell, "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=root, env=env, capture_output=True, text=True, timeout=300,
    )


def test_without_a_tpu_it_exits_nonzero_and_prints_nothing():
    p = _cli(REPO, {"JAX_PLATFORMS": "cpu"})
    assert p.returncode != 0
    assert p.stdout == ""
    assert "TPU" in p.stderr


def test_without_the_program_it_exits_nonzero_and_prints_nothing(tmp_path):
    shutil.copy(REPO / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(CHIP, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _cli(tmp_path, {"JAX_PLATFORMS": "cpu"})
    assert p.returncode != 0
    assert p.stdout == ""
    assert "program" in p.stderr


NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def _line(text):
    return isinstance(text, str) and 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_benchmark_json_keeps_its_shape():
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    assert list(spec) == ["command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"]
    assert 1 <= spec["run_seconds"] <= 51 and isinstance(spec["run_seconds"], int)
    assert all(_line(w) for w in spec["command"]) and len(spec["command"]) <= 32
    cells = {w["name"]: w for w in spec["workloads"]}
    configs = {c["name"] for c in spec["configs"]}
    for c in spec["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and _line(c["why"]) and _line(c["source"])
        assert any(c["file"].startswith(p + "/") for p in spec["paths"])
    for w in spec["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"]) and _line(w["why"])
        assert w["config"] in configs and w["chips"] in (1, 4)
    assert {w["config"] for w in spec["workloads"]} == configs
    e2e = {m["name"]: m for m in spec["end_to_end"]}
    for m in spec["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in spec["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}
        assert m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
        assert _line(m["layer"]) and m["moves"] in e2e
        for cell in m.get("workloads", cells):
            assert cell in cells
            assert bench._applies(e2e[m["moves"]], cell)
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for name in cells:
        plan = bench.cell_plan(spec, name)
        reported = {m["name"] for m in plan.end_to_end}
        assert "setup_s" in reported and len(reported) >= 2 and plan.per_layer
    assert len(json.dumps(spec)) < 64 * 1024


@pytest.mark.parametrize("cell", [w["name"] for w in SPEC["workloads"]])
def test_every_cell_gets_the_per_layer_split_of_its_job_kind(cell):
    """Each per-layer metric that moves the cell's rate, or its set-up,
    names the cell or applies to every cell: a cell added later reads the
    same split as the cells of its job kind before it."""
    plan = bench.cell_plan(SPEC, cell)
    moved = {traffic.load_kind(plan.mix["job"]).Job.rate_metric, "setup_s"}
    for m in SPEC["per_layer"]:
        if m["moves"] in moved:
            assert bench._applies(m, cell), f"{m['name']} leaves out {cell}"


@pytest.mark.parametrize("config", SPEC["configs"], ids=lambda c: c["name"])
def test_every_configuration_is_the_network_the_program_runs(config):
    """Layer for layer, with the arrays, blocks and least PEs the
    configuration expects, on the configuration's own array."""
    from repro.core import cim

    c = json.loads((REPO / config["file"]).read_text())
    arr = cim.DEFAULT_ARRAY.variant(**c["array"])
    spec = cim.with_array(getattr(cim, c["spec"])(), arr)
    traffic.check_spec(c, spec)
    assert spec.min_pes(c["arrays_per_pe"]) == c["expect"]["min_pes"]


@dataclasses.dataclass(frozen=True)
class _GroupedLayer:
    """A layer type with one field beyond the conv configurations' six."""

    name: str
    kernel: int
    cin: int
    cout: int
    out_hw: int
    stride: int
    groups: int


@pytest.mark.parametrize("groups, refused", [(4, False), (2, True)], ids=["same", "extra-column-differs"])
def test_check_spec_reads_the_configurations_own_columns(groups, refused):
    cols = ["name", "kernel", "cin", "cout", "out_hw", "stride", "groups"]
    config = {"network": "grouped", "layer_columns": cols, "layers": [["g1", 3, 64, 64, 8, 1, 4]],
              "expect": {"n_arrays": 5, "n_blocks": 5}}
    spec = SimpleNamespace(layers=[_GroupedLayer("g1", 3, 64, 64, 8, 1, groups)], n_arrays=5, n_blocks=5)
    if refused:
        with pytest.raises(SystemExit, match="layers differ"):
            traffic.check_spec(config, spec)
    else:
        traffic.check_spec(config, spec)


@pytest.mark.parametrize("extra", [{}, {"calib_tokens": 512, "batch": 4}], ids=["none", "two"])
def test_every_other_profile_key_reaches_the_capture(monkeypatch, extra):
    import repro.dse.sweep as program_sweep

    seen = {}

    def stub(network, **kw):
        seen.update(network=network, **kw)
        return SimpleNamespace(layers=[SimpleNamespace(sampled_q=np.zeros((2, 3), np.uint8))])

    monkeypatch.setattr(program_sweep, "get_captured", stub)
    vgg11 = json.loads((CHIP / "configs" / "vgg11.json").read_text())
    config = {**vgg11, "profile": {**vgg11["profile"], **extra}}
    q = traffic.capture_samples(config)
    assert seen == {"network": "vgg11", "profile_images": 1, "sample_patches": 128, "seed": 0, **extra}
    assert [a.shape for a in q] == [(2, 3)]
