"""Batched (allocate, simulate) evaluation — the DSE inner loop.

``allocate_batch`` mirrors ``core.cim.simulate.allocate`` policy-for-policy
but runs every config of a sweep at once: the proportional policies reuse the
scalar largest-remainder routine (cheap, exact), while the greedy policies —
the paper's actual algorithm and the sweep hot path — answer every budget
from one ``greedy_event_schedule`` per family, the same batched greedy the
fused pipeline uses.  Replica vectors are element-wise identical to the
scalar allocator; the golden-equivalence suite pins this.

``run_batch`` chains it into ``BatchSimulator`` (vmapped float64 kernel) so a
(policy, PE-count) sweep over one profiled network is two jit calls.
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np

from ..core.alloc.greedy import greedy_event_schedule, proportional_allocate_batch
from ..core.cim.network import NetworkSpec
from ..core.cim.profile import NetworkProfile
from ..core.cim.simulate import (
    ALL_POLICIES,
    ARRAYS_PER_PE,
    CLOCK_HZ,
    Allocation,
    BatchSimResult,
    BatchSimulator,
    _layer_patch_cycles,
    allocate,
    blockwise_units,
)

__all__ = [
    "AllocationBatch",
    "allocate_batch",
    "run_batch",
    "to_allocation",
]

_PROPORTIONAL = ("baseline", "weight_based", "weight_blockflow")
_LAYERWISE_FLOW = ("baseline", "weight_based", "perf_layerwise")


@dataclass(frozen=True)
class AllocationBatch:
    """Structure-of-arrays ``Allocation`` for C configs on one network."""

    policies: np.ndarray  # (C,) str
    n_pes: np.ndarray  # (C,)
    dups_lb: np.ndarray  # (C, L, Bmax) float replicas (padded blocks = 1)
    layerwise: np.ndarray  # (C,) bool — barrier dataflow
    zskip: np.ndarray  # (C,) bool
    arrays_used: np.ndarray  # (C,) int64
    arrays_total: np.ndarray  # (C,) int64

    def __len__(self) -> int:
        return self.policies.shape[0]


def allocate_batch(
    spec: NetworkSpec,
    prof: NetworkProfile,
    policies,
    n_pes,
    arrays_per_pe: int = ARRAYS_PER_PE,
    latency_load_frac: float = 0.7,
) -> AllocationBatch:
    """Batched ``allocate``: one call for a whole (policy, PE-count) sweep.

    ``latency_aware`` points are supported but allocate through the scalar
    path per config (the queueing greedy is load-dependent, so no shared
    grant table answers it); their offered load is ``latency_load_frac``
    times the scalar blockwise throughput at the same budget, matching the
    scalar ``allocate`` default."""
    policies = np.atleast_1d(np.asarray(policies, dtype=object))
    n_pes = np.atleast_1d(np.asarray(n_pes, dtype=np.int64))
    policies, n_pes = np.broadcast_arrays(policies, n_pes)
    unknown = sorted({p for p in policies if p not in ALL_POLICIES})
    if unknown:
        raise ValueError(f"unknown policies {unknown}; choose from {ALL_POLICIES}")
    C = policies.shape[0]
    total = n_pes * arrays_per_pe
    base_arrays = spec.n_arrays
    if np.any(total < base_arrays):
        worst = int(total.min())
        raise ValueError(f"{worst} arrays < minimum {base_arrays} for {spec.name}")
    free = (total - base_arrays).astype(np.float64)

    L = len(spec.layers)
    B = max(l.n_blocks for l in spec.layers)
    layer_arrays = np.array([l.n_arrays for l in spec.layers], dtype=np.float64)
    ppi = np.array([l.patches_per_image for l in spec.layers], dtype=np.float64)
    cyc = _layer_patch_cycles(prof, True)

    dups_lb = np.ones((C, L, B))
    used = np.zeros(C, dtype=np.int64)

    prop = np.isin(policies, _PROPORTIONAL)
    if prop.any():
        macs = np.array([l.macs_per_image for l in spec.layers], dtype=np.float64)
        res = proportional_allocate_batch(macs, layer_arrays, free[prop])
        dups_lb[prop] = res.replicas[:, :, None].astype(np.float64)
        used[prop] = base_arrays + ((res.replicas - 1) @ layer_arrays).astype(np.int64)

    perf = policies == "perf_layerwise"
    if perf.any():
        exp_lat = np.array([cyc[i].max(axis=1).mean() * ppi[i] for i in range(L)])
        # array costs and budgets are integers: the schedule is exact
        res = greedy_event_schedule(
            exp_lat, layer_arrays, float(free[perf].max())
        ).replicas_at(free[perf])
        dups_lb[perf] = res.replicas[:, :, None].astype(np.float64)
        used[perf] = base_arrays + res.spent.astype(np.int64)

    block = policies == "blockwise"
    if block.any():
        base_lat, cost = blockwise_units(spec, [cyc[i].mean(axis=0) for i in range(L)])
        res = greedy_event_schedule(
            base_lat, cost, float(free[block].max())
        ).replicas_at(free[block])
        table = spec.block_table()  # (n_blocks, 3): layer, block-in-layer, width
        rows = np.flatnonzero(block)
        dups_lb[rows[:, None], table[None, :, 0], table[None, :, 1]] = res.replicas
        used[block] = base_arrays + res.spent.astype(np.int64)

    for i in np.flatnonzero(policies == "latency_aware"):
        a = allocate(
            spec, prof, "latency_aware", int(n_pes[i]), arrays_per_pe,
            load_frac=latency_load_frac,
        )
        for li, d in enumerate(a.block_dups):
            dups_lb[i, li, : d.size] = d.astype(np.float64)
        used[i] = a.arrays_used

    return AllocationBatch(
        policies=policies.astype(str),
        n_pes=n_pes.copy(),
        dups_lb=dups_lb,
        layerwise=np.isin(policies, _LAYERWISE_FLOW),
        zskip=policies != "baseline",
        arrays_used=used,
        arrays_total=total,
    )


def to_allocation(batch: AllocationBatch, i: int, spec: NetworkSpec) -> Allocation:
    """Extract config ``i`` as a scalar ``Allocation`` (fabric-runtime handoff)."""
    policy = str(batch.policies[i])
    used = int(batch.arrays_used[i])
    total = int(batch.arrays_total[i])
    if policy in _LAYERWISE_FLOW:
        dups = batch.dups_lb[i, :, 0].astype(np.int64)
        return Allocation(policy, dups, None, used, total)
    block_dups = [
        batch.dups_lb[i, li, : l.n_blocks].astype(np.int64)
        for li, l in enumerate(spec.layers)
    ]
    return Allocation(policy, None, block_dups, used, total)


def run_batch(
    spec: NetworkSpec,
    prof: NetworkProfile,
    policies,
    n_pes,
    *,
    n_images: int = 64,
    clock_hz: float = CLOCK_HZ,
    arrays_per_pe: int = ARRAYS_PER_PE,
    simulator: BatchSimulator | None = None,
    latency_load_frac: float = 0.7,
) -> tuple[AllocationBatch, BatchSimResult]:
    """allocate_batch + BatchSimulator in one call."""
    alloc = allocate_batch(
        spec, prof, policies, n_pes, arrays_per_pe, latency_load_frac
    )
    sim = simulator if simulator is not None else BatchSimulator(spec, prof)
    res = sim(alloc.dups_lb, alloc.layerwise, alloc.zskip, n_images, clock_hz)
    return alloc, res
