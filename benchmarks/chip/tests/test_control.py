"""The control, the reference computed in the precision below the
configuration's in the program's place, has to come out as not correct; the
program, at the same size, as correct."""

from __future__ import annotations

import json

import pytest

from conftest import CHIP, TINY_REPLAY, TINY_SWEEP

import traffic

VGG11 = json.loads((CHIP / "configs" / "vgg11.json").read_text())


@pytest.mark.parametrize("mix", [TINY_SWEEP, TINY_REPLAY], ids=["sweep", "replay"])
def test_float32_control_fails_and_the_program_passes(mix):
    job = traffic.make(VGG11, mix, 2**31 + 99)
    inp = job.inputs(0)
    kept = [(0, inp, job.run(inp))]
    sound, limits = traffic.compare(job, kept)
    control, _ = traffic.compare(job, kept, control=True)
    assert all(sound[k] <= lim for k, lim in limits.items()), sound
    assert any(control[k] > lim for k, lim in limits.items()), control
