"""The CIM path loads no module of the language-model stack.

The models, configs, launch, training, optimizer, data, checkpoint,
runtime and serving-engine packages, and the attention, state-space and
zero-skip matmul kernels, serve no CIM path.  Each case imports one CIM
entry point in a fresh interpreter and lists what landed in
``sys.modules``, so an import added anywhere on the path that reaches the
stack fails here.
"""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

SRC = Path(__file__).resolve().parents[1] / "src"

LM_MODULES = (
    "repro.models",
    "repro.configs",
    "repro.launch",
    "repro.train",
    "repro.optim",
    "repro.data",
    "repro.checkpoint",
    "repro.runtime",
    "repro.serve.engine",
    "repro.kernels.flash_attention",
    "repro.kernels.ssd_scan",
    "repro.kernels.zskip_matmul",
    "repro.kernels.ops",
)


def _loaded(entry: str) -> list[str]:
    """Module names in ``sys.modules`` after importing ``entry`` alone."""
    code = (
        "import importlib, json, sys\n"
        f"importlib.import_module({entry!r})\n"
        "print(json.dumps(sorted(sys.modules)))\n"
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (str(SRC), env.get("PYTHONPATH", "")) if p
    )
    out = subprocess.run(
        [sys.executable, "-c", code],
        env=env,
        capture_output=True,
        text=True,
        timeout=300,
        check=True,
    )
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize(
    "entry",
    [
        "repro.dse",
        "repro.fabric",
        "repro.kernels.bitplane_profile",
        "repro.distrib.sharding",
    ],
)
def test_cim_path_loads_no_lm_module(entry):
    loaded = _loaded(entry)
    assert entry in loaded
    stack = [
        m for m in loaded
        if any(m == p or m.startswith(p + ".") for p in LM_MODULES)
    ]
    assert stack == [], f"{entry} loads {stack}"
