"""Peaks of the chip and the least work of a kernel, kept with the benchmark."""

from __future__ import annotations

import json
from pathlib import Path

PEAKS = Path(__file__).with_name("peaks.json")


def peaks(device_kind: str) -> dict:
    """Published peaks of one chip.  A device that is not in the table is an
    error, never a default."""
    table = json.loads(PEAKS.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {PEAKS.name}")
    return table[device_kind]


def eval_least_bytes(configs: int, replica_len: int, layers: int) -> int:
    """HBM bytes that evaluating ``configs`` designs must move at the least:
    each design's replica vector read (``replica_len`` counts: one per layer,
    or one per block for block-wise designs, float64 as the eval takes them)
    and its outputs written (total cycles, then cycles and utilization per
    layer: 1 + 2L float64).  The shared statistic tables are read once and
    left out."""
    return configs * 8 * (replica_len + 1 + 2 * layers)
