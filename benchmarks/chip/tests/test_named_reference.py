"""A configuration that names its own reference module is checked against
that module, for the capture, the sweep and the replay, with no harness file
edited: a copy of ``reference.py`` passes, and a fault planted in the copy
alone makes ``correct`` false."""

from __future__ import annotations

import json
from pathlib import Path

import pytest
from conftest import CHIP, TINY_REPLAY, TINY_SWEEP

import traffic

VGG11 = json.loads((CHIP / "configs" / "vgg11.json").read_text())
SOURCE = (CHIP / "reference.py").read_text()
NAMED = {**VGG11, "name": "vgg11_named", "reference": "reference_copy"}


def _planted(old: str, new: str) -> str:
    assert SOURCE.count(old) == 1, old
    return SOURCE.replace(old, new)


# each fault changes one answer of the copy, where the reference makes it
FIRST_LAYER = _planted("    return q[sel]\n", "    q = q[sel]\n    q[0, 0] ^= 1\n    return q\n")
EVALUATE = _planted('"images_per_sec": n / (f(T) / f(net.clock_hz)),',
                    '"images_per_sec": n / (f(T) / f(net.clock_hz)) * f(1 + 1e-6),')
REPLAY = _planted("        out[:, r] = t\n", "        out[:, r] = t + 1\n")


def _run(checkout, mix, body):
    mod = checkout({"tiny": mix}, config="vgg11_named", configs={"vgg11_named": NAMED},
                   files={"reference_copy.py": body})
    result, _ = mod.run("vgg11_named.tiny", 2**31 + 4321, 0.0, False)
    return result


@pytest.mark.parametrize("mix", [TINY_SWEEP, TINY_REPLAY], ids=["sweep", "replay"])
def test_a_copied_reference_named_by_the_configuration_is_correct(checkout, mix):
    result = _run(checkout, mix, SOURCE)
    assert result["correct"] is True, result["checks"]
    assert result["checks"]["capture_conv1_mismatch"]["value"] == 0


@pytest.mark.parametrize(
    "mix, body",
    [(TINY_SWEEP, FIRST_LAYER), (TINY_REPLAY, FIRST_LAYER), (TINY_SWEEP, EVALUATE),
     (TINY_REPLAY, REPLAY)],
    ids=["sweep-first-layer", "replay-first-layer", "sweep-evaluate", "replay-replay"],
)
def test_a_fault_in_the_named_reference_alone_is_not_correct(checkout, mix, body):
    result = _run(checkout, mix, body)
    assert result["correct"] is False, result["checks"]


def test_a_configuration_without_a_name_takes_reference_py():
    ref = traffic.reference_of(VGG11)
    assert Path(ref.__file__) == CHIP / "reference.py"
    assert traffic.reference_of({**VGG11, "reference": "reference"}) is ref


@pytest.mark.parametrize("name", ["reference_nope", "bench"])
def test_an_unknown_reference_is_refused_with_the_known_ones(name):
    with pytest.raises(SystemExit, match=rf"unknown reference '{name}'; known: \['reference'\]"):
        traffic.reference_of({**VGG11, "reference": name})
