"""The one traffic generator: reads a mix's data file and drives the program.

A mix (``traffic/<name>.json``) names its ``job`` kind and holds every
parameter of it.  The kind is code of its own, ``jobs/<kind>.py``, whose
``Job`` makes a job's inputs from ``(seed, job index)`` alone, hands the
program nothing but those inputs, counts the job's work, keeps what the
check needs, and compares a sample of the window's outputs with
the configuration's reference once the window has closed.  A ``Job`` has:

* ``rate_metric``: the end-to-end metric its work per second is reported as;
* ``limits``: each number the comparison gives, with its limit;
* ``inputs(j)``, ``run(inputs)``, ``work(outputs)``, ``sound(outputs)``
  (outputs that are not finite or not possible) and ``compare(kept,
  control=False)``.

A configuration names its plain reference, ``"reference": "<module>"``
for ``benchmarks/chip/<module>.py`` (``reference.py`` where it names
none); ``reference_of`` loads it, and the capture check and every
comparison of every kind use that module, never a fixed one.  ``compare``
here adds to a job's numbers the check of the set-up capture against it.
"""

from __future__ import annotations

import importlib.util
import sys
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent
JOBS = HERE / "jobs"


def rng(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) % 2**63, *stream])


# stream numbers: job inputs use the job index (>= 0)
WARMUP = 2**32
SAMPLE = 2**32 + 1


def reference_of(config: dict):
    """The configuration's plain reference module, loaded by file."""
    name = config.get("reference", "reference")
    known = sorted(p.stem for p in HERE.glob("reference*.py"))
    if name not in known:
        raise SystemExit(f"unknown reference {name!r}; known: {known}")
    return _load(f"bench_reference_{name}", HERE / f"{name}.py")


def profile_args(config: dict) -> dict:
    """The capture's arguments: the ``profile`` block, ``images`` passed as
    ``profile_images`` and every other key as it is named."""
    p = dict(config["profile"])
    return {"profile_images": p.pop("images"), **p}


def capture_samples(config: dict) -> list[np.ndarray]:
    """The set-up capture's quantized activation samples: the data every
    profile, and the reference, is computed from."""
    from repro.dse.sweep import get_captured

    cap = get_captured(config["network"], **profile_args(config))
    return [np.asarray(layer.sampled_q) for layer in cap.layers]


# readings behind the limit: PERF.md, section 2
CAPTURE_LIMITS = {"capture_conv1_mismatch": 0}


def check_capture(config: dict, control: bool = False) -> dict:
    """Entries of the capture's first crossbar layer's samples that differ
    from the configuration's reference's own (``capture_conv1_mismatch``,
    named for the conv networks that first used it).  With ``control`` the
    reference's samples quantized in bfloat16 stand in the program's
    place."""
    ref = reference_of(config)
    want = ref.first_layer_samples(config)
    if control:
        import ml_dtypes

        got = ref.first_layer_samples(config, ml_dtypes.bfloat16)
    else:
        got = capture_samples(config)[0]
    return {"capture_conv1_mismatch": int(want.size) if got.shape != want.shape
            else int((got != want).sum())}


def compare(job, kept: list, control: bool = False) -> tuple[dict, dict]:
    """Every number the run compares, and its limit: the job's numbers for
    the window's outputs ``kept``, and the capture's."""
    numbers = {**job.compare(kept, control), **check_capture(job.config, control)}
    return numbers, {**job.limits, **CAPTURE_LIMITS}


def check_spec(config: dict, spec) -> None:
    """The program runs the configuration's network, layer for layer, each
    layer read by the configuration's own ``layer_columns``."""
    cols = config["layer_columns"]
    want = [tuple(row) for row in config["layers"]]
    got = [tuple(getattr(layer, c) for c in cols) for layer in spec.layers]
    if got != want:
        raise SystemExit(f"the program's {config['network']} layers differ from the configuration")
    e = config["expect"]
    if (spec.n_arrays, spec.n_blocks) != (e["n_arrays"], e["n_blocks"]):
        raise SystemExit(f"{config['network']}: {spec.n_arrays} arrays in {spec.n_blocks} blocks, "
                         f"expected {e['n_arrays']} in {e['n_blocks']}")


def make(config: dict, mix: dict, seed: int):
    """The job of ``mix``'s kind: ``jobs/<kind>.py``'s ``Job``, set up."""
    path = JOBS / f"{mix['job']}.py"
    if not path.is_file():
        known = sorted(p.stem for p in JOBS.glob("*.py"))
        raise SystemExit(f"unknown job kind {mix['job']!r}; known: {known}")
    return load_kind(mix["job"]).Job(config, mix, seed)


def load_kind(kind: str):
    return _load(f"bench_job_{kind}", JOBS / f"{kind}.py")


def _load(name: str, path: Path):
    """The module of the file ``path``, loaded once a process as ``name``."""
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[name] = mod
        spec.loader.exec_module(mod)
    return sys.modules[name]
