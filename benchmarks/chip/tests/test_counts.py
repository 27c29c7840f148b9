"""The peaks table and the eval's least bytes."""

from __future__ import annotations

import pytest

import counts


def test_v5e_peaks():
    p = counts.peaks("TPU v5 lite")
    assert p["hbm_bytes_per_s"] == 819e9
    assert p["bf16_flops_per_s"] == 197e12
    assert p["int8_ops_per_s"] == 393e12
    assert p["hbm_bytes"] == 16e9


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        counts.peaks("cpu")


def test_eval_least_bytes():
    # ResNet18, a layer-wise design: 20 replica counts in, 1 + 2 * 20 out
    assert counts.eval_least_bytes(1, 20, 20) == 8 * 61
    # block-wise: 247 block replica counts in
    assert counts.eval_least_bytes(1000, 247, 20) == 1000 * 8 * (247 + 41)
