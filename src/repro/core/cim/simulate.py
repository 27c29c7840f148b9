"""Allocation policies + pipelined-throughput simulator (Sections III & V).

Four policies, matching the paper's Figure 8:

  * ``baseline``        — zero-skipping OFF, arrays allocated by MACs
                          (deterministic arrays: the pre-zero-skip world).
  * ``weight_based``    — zero-skipping ON, arrays still allocated by MACs,
                          layer-wise dataflow (the naive policy that the
                          paper's 7.47x is measured against).
  * ``perf_layerwise``  — zero-skipping ON, arrays allocated greedily by
                          expected layer latency, layer-wise dataflow.
  * ``blockwise``       — zero-skipping ON, arrays allocated greedily by
                          expected *block* latency, block-wise dataflow
                          (the paper's contribution).

Dataflow model (steady-state pipelined throughput):

  Layer-wise: a duplicate is a full copy of the layer's block grid; all
  blocks of a duplicate synchronize per patch (gather/accumulate barrier), so
  a patch costs max_b cycles[p, b] and layer latency for N images is
      T_l = max( sum_p max_b c[p,b] / d_l ,  max_p max_b c[p,b] ).

  Block-wise: each block is an independent server pool with d_b replicas and
  no intra-layer barrier:
      T_l = max_b max( sum_p c[p,b] / d_b ,  max_p c[p,b] ).

  Layer pipelining makes throughput the bottleneck layer's:  T = max_l T_l.

Per-patch cycles come from the profiled sample (see profile.py); sums over
all patches are scaled from the sample mean.  Utilization = busy array-cycles
/ (arrays alive x T), per layer — the paper's Figure 9.

Array-kernel core
-----------------
The simulator is implemented as a pure array kernel over a *packed* profile
(``pack_profile`` -> ``SimTensors``): per-layer (S, B) cycle samples are
padded to a dense (L, S, Bmax) tensor with validity masks, reduced once to
sufficient statistics, and evaluated by ``_eval_kernel`` — plain array
algebra parameterized on the array module ``xp``.  The scalar ``simulate()``
runs it with ``xp=numpy`` (float64, drop-in API for the fabric runtime);
``BatchSimulator`` runs the same kernel with ``xp=jax.numpy`` under
``vmap``+``jit`` (x64) over a batch of allocations — the engine behind
``repro.dse`` design-space sweeps.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Literal

import numpy as np

from ..alloc.greedy import greedy_allocate, proportional_allocate, queueing_allocate
from .network import NetworkSpec
from .profile import NetworkProfile

__all__ = [
    "Policy",
    "POLICIES",
    "ALL_POLICIES",
    "Allocation",
    "SimResult",
    "SimTensors",
    "BatchSimResult",
    "BatchSimulator",
    "allocate",
    "pack_profile",
    "simulate",
    "run_policy",
    "blockwise_units",
    "split_block_dups",
]

Policy = Literal[
    "baseline",
    "weight_based",
    "perf_layerwise",
    "blockwise",
    # ablation: weight-based ALLOCATION but block-wise DATAFLOW — separates
    # the paper's two contributions (the paper reports them fused)
    "weight_blockflow",
    # serving extension: replicas by marginal queueing-delay reduction at a
    # target offered load (block-wise dataflow; see alloc.greedy
    # .queueing_allocate and fabric.vtime.refine_latency_aware)
    "latency_aware",
]
# the paper's Figure-8 policies — sweeps default to these; "latency_aware"
# additionally needs an offered load, so it joins sweeps explicitly
POLICIES: tuple[Policy, ...] = (
    "baseline",
    "weight_based",
    "perf_layerwise",
    "blockwise",
    "weight_blockflow",
)
ALL_POLICIES: tuple[Policy, ...] = POLICIES + ("latency_aware",)
ARRAYS_PER_PE = 64
CLOCK_HZ = 100e6


@dataclass(frozen=True)
class Allocation:
    policy: Policy
    layer_dups: np.ndarray | None  # (L,) for layer-wise policies
    block_dups: list[np.ndarray] | None  # per-layer (B_l,) for blockwise
    arrays_used: int
    arrays_total: int


@dataclass(frozen=True)
class SimResult:
    policy: Policy
    total_cycles: float
    images_per_sec: float
    layer_cycles: np.ndarray  # (L,) per-layer makespan for the batch
    layer_utilization: np.ndarray  # (L,) busy / (arrays x T)
    arrays_used: int

    @property
    def mean_utilization(self) -> float:
        return float(self.layer_utilization.mean())


def _layer_patch_cycles(prof: NetworkProfile, zskip: bool) -> list[np.ndarray]:
    """Per-layer (S, B) per-patch per-block cycle samples."""
    out = []
    for lp in prof.layers:
        if zskip:
            out.append(lp.cycles_sample.astype(np.float64))
        else:
            s = lp.cycles_sample.shape[0]
            out.append(np.broadcast_to(lp.baseline_block_cycles.astype(np.float64), (s, lp.baseline_block_cycles.size)).copy())
    return out


def blockwise_units(
    spec: NetworkSpec, block_mean_cycles: list[np.ndarray]
) -> tuple[np.ndarray, np.ndarray]:
    """Flattened per-block (base_latency, replica_cost) for greedy allocation.

    ``block_mean_cycles``: per-layer (B_l,) expected cycles per patch — from
    the profile, or from runtime-observed EWMA means (drift re-allocation).
    """
    base_lat, cost = [], []
    for i, layer in enumerate(spec.layers):
        mean_b = np.asarray(block_mean_cycles[i], dtype=np.float64)
        ppi = float(layer.patches_per_image)
        for b in range(layer.n_blocks):
            base_lat.append(mean_b[b] * ppi)
            cost.append(layer.arrays_per_block)
    return np.asarray(base_lat), np.asarray(cost, dtype=np.float64)


def split_block_dups(spec: NetworkSpec, replicas: np.ndarray) -> list[np.ndarray]:
    """Inverse of ``blockwise_units``'s flattening: per-layer (B_l,) replica
    arrays from the flat per-block vector (layers in order, blocks within)."""
    out, k = [], 0
    for layer in spec.layers:
        out.append(np.asarray(replicas[k : k + layer.n_blocks]).copy())
        k += layer.n_blocks
    return out


def allocate(
    spec: NetworkSpec,
    prof: NetworkProfile,
    policy: Policy,
    n_pes: int,
    arrays_per_pe: int = ARRAYS_PER_PE,
    free_budget: float | None = None,
    offered_ips: float | None = None,
    load_frac: float = 0.7,
    audit=None,
) -> Allocation:
    """Pick replica counts.  ``free_budget`` caps the arrays spent on extra
    replicas below the physical ``total - base`` (used to hold back a reserve
    pool for online re-allocation).

    The ``latency_aware`` policy additionally needs a target offered load:
    ``offered_ips`` (images/sec), or — when omitted — ``load_frac`` times
    the analytic throughput of the ``blockwise`` allocation at the same
    budget (the natural "provision for X% of peak" operating point).

    ``audit`` (a ``repro.obs.AllocationAudit``) records the greedy policies'
    per-grant decision log (``perf_layerwise`` / ``blockwise``); other
    policies do not route through the greedy loop and leave it empty."""
    total = n_pes * arrays_per_pe
    base_arrays = spec.n_arrays
    if total < base_arrays:
        raise ValueError(f"{total} arrays < minimum {base_arrays} for {spec.name}")
    free = total - base_arrays
    if free_budget is not None:
        if not 0 <= free_budget <= free:
            raise ValueError(
                f"free_budget {free_budget} outside [0, {free}] free arrays"
            )
        free = float(free_budget)
    L = len(spec.layers)
    layer_arrays = np.array([l.n_arrays for l in spec.layers], dtype=np.float64)
    zskip = policy != "baseline"
    cyc = _layer_patch_cycles(prof, zskip)
    ppi = np.array([l.patches_per_image for l in spec.layers], dtype=np.float64)

    if policy in ("baseline", "weight_based", "weight_blockflow"):
        macs = np.array([l.macs_per_image for l in spec.layers], dtype=np.float64)
        res = proportional_allocate(macs, layer_arrays, free)
        dups = res.replicas
        used = int(base_arrays + (res.replicas - 1) @ layer_arrays)
        if policy == "weight_blockflow":
            # same replica budget per layer, but blocks dispatch independently
            block_dups = [
                np.full(l.n_blocks, dups[i], dtype=np.int64)
                for i, l in enumerate(spec.layers)
            ]
            return Allocation(policy, None, block_dups, used, total)
        return Allocation(policy, dups, None, used, total)

    if policy == "perf_layerwise":
        # expected per-layer latency with one duplicate: patches x E[max_b c]
        exp_lat = np.array([cyc[i].max(axis=1).mean() * ppi[i] for i in range(L)])
        res = greedy_allocate(exp_lat, layer_arrays, free, audit=audit)
        used = int(base_arrays + (res.replicas - 1) @ layer_arrays)
        return Allocation(policy, res.replicas, None, used, total)

    if policy == "blockwise":
        # one unit per block across the whole network
        base_lat, cost = blockwise_units(spec, [cyc[i].mean(axis=0) for i in range(L)])
        res = greedy_allocate(base_lat, cost, free, audit=audit)
        block_dups = split_block_dups(spec, res.replicas)
        used = int(base_arrays + ((res.replicas - 1) * cost).sum())
        return Allocation(policy, None, block_dups, used, total)

    if policy == "latency_aware":
        if offered_ips is None:
            bw = allocate(spec, prof, "blockwise", n_pes, arrays_per_pe, free_budget)
            offered_ips = load_frac * simulate(spec, prof, bw).images_per_sec
        if offered_ips <= 0:
            raise ValueError(f"offered_ips must be positive, got {offered_ips}")
        r_cyc = float(offered_ips) / CLOCK_HZ  # images per fabric cycle
        job_rate, mean, scv, cost, batch, group = _queueing_inputs(spec, cyc, r_cyc)
        res = queueing_allocate(
            job_rate, mean, scv, cost, free, batch_size=batch, group=group
        )
        block_dups = split_block_dups(spec, res.replicas)
        used = int(base_arrays + ((res.replicas - 1) * cost).sum())
        return Allocation(policy, None, block_dups, used, total)

    raise ValueError(policy)


def _queueing_inputs(spec: NetworkSpec, cyc, r_cyc: float):
    """Per-block queueing-model inputs for the ``latency_aware`` policy.

    Per-block FIFO pools: every patch of layer ``l`` brings one job to each
    of its blocks, so the pool's job rate is ``r * patches/image``, arriving
    in request-batches of ``patches_per_image``; a layer (= one pipeline
    stage) is a group — its latency is its slowest pool's.  Shared between
    the flat ``allocate`` and the placed ``topology.allocate_placed`` so
    their scoring inputs cannot drift apart (the single-chip bit-identity
    guarantee hangs on it).  Returns flat (job_rate, mean, scv, cost,
    batch, group) arrays over all blocks.
    """
    mean, scv, job_rate, cost, batch, group = [], [], [], [], [], []
    for i, layer in enumerate(spec.layers):
        m = cyc[i].mean(axis=0)
        v = cyc[i].var(axis=0)
        mean.append(m)
        scv.append(v / np.maximum(m, 1e-300) ** 2)
        job_rate.append(np.full(layer.n_blocks, r_cyc * layer.patches_per_image))
        cost.append(np.full(layer.n_blocks, float(layer.arrays_per_block)))
        batch.append(np.full(layer.n_blocks, float(layer.patches_per_image)))
        group.append(np.full(layer.n_blocks, i, dtype=np.int64))
    return (
        np.concatenate(job_rate),
        np.concatenate(mean),
        np.concatenate(scv),
        np.concatenate(cost),
        np.concatenate(batch),
        np.concatenate(group),
    )


# ------------------------------------------------------- array-kernel core
@dataclass(frozen=True)
class SimTensors:
    """Packed (NetworkSpec, NetworkProfile) pair: padded cycle tensors plus
    the sufficient statistics the dataflow model needs.

    Leading axis 2 on the per-variant arrays selects zero-skipping:
    index 0 = baseline (deterministic cycles), 1 = zero-skipping.
    """

    cycles: np.ndarray  # (2, L, S, B) per-patch per-block cycles, 0-padded
    s_mask: np.ndarray  # (L, S) valid patch samples
    b_mask: np.ndarray  # (L, B) valid blocks
    ppi: np.ndarray  # (L,) patches per image
    width: np.ndarray  # (L,) arrays per block
    layer_arrays: np.ndarray  # (L,) arrays in one copy of the layer
    n_blocks: np.ndarray  # (L,) valid block count
    # derived statistics (2, ...):
    mean_b: np.ndarray  # (2, L, B) E_S[c]
    max_b: np.ndarray  # (2, L, B) max_S c
    pm_mean: np.ndarray  # (2, L) E_S[max_B c]  (layer-wise barrier)
    pm_max: np.ndarray  # (2, L) max_S max_B c
    busy_sum: np.ndarray  # (2, L) sum_B E_S[c]  (busy cycles per patch)

    @property
    def L(self) -> int:
        return self.b_mask.shape[0]

    @property
    def B(self) -> int:
        return self.b_mask.shape[1]


# keyed on object identity (the frozen dataclasses hold numpy arrays, so
# they are not hashable); weakref finalizers evict entries before an id can
# be reused, keeping repeated scalar simulate() calls from re-packing
_PACK_CACHE: dict[tuple[int, int], SimTensors] = {}


def pack_profile(spec: NetworkSpec, prof: NetworkProfile) -> SimTensors:
    """Pad per-layer (S, B) cycle samples into dense tensors + statistics.

    Cached per (spec, profile) object pair — the tensors are pure functions
    of the inputs and every ``simulate()`` call needs them."""
    import weakref

    key = (id(spec), id(prof))
    hit = _PACK_CACHE.get(key)
    if hit is not None:
        return hit
    st = _pack_profile(spec, prof)
    _PACK_CACHE[key] = st
    weakref.finalize(spec, _PACK_CACHE.pop, key, None)
    weakref.finalize(prof, _PACK_CACHE.pop, key, None)
    return st


def _pack_profile(spec: NetworkSpec, prof: NetworkProfile) -> SimTensors:
    L = len(spec.layers)
    variants = [_layer_patch_cycles(prof, False), _layer_patch_cycles(prof, True)]
    S = max(c.shape[0] for c in variants[1])
    B = max(l.n_blocks for l in spec.layers)
    cycles = np.zeros((2, L, S, B))
    s_mask = np.zeros((L, S), dtype=bool)
    b_mask = np.zeros((L, B), dtype=bool)
    for v, cyc in enumerate(variants):
        for i, c in enumerate(cyc):
            s, b = c.shape
            cycles[v, i, :s, :b] = c
            s_mask[i, :s] = True
            b_mask[i, :b] = True
    s_count = s_mask.sum(axis=1)  # (L,)
    mean_b = cycles.sum(axis=2) / s_count[None, :, None]
    max_b = cycles.max(axis=2)  # padded entries are 0 <= any real cycle count
    patch_max = np.where(b_mask[None, :, None, :], cycles, -np.inf).max(axis=3)
    pm_mean = np.where(s_mask, patch_max, 0.0).sum(axis=2) / s_count[None, :]
    pm_max = np.where(s_mask, patch_max, -np.inf).max(axis=2)
    busy_sum = np.where(b_mask, mean_b, 0.0).sum(axis=2)
    return SimTensors(
        cycles=cycles,
        s_mask=s_mask,
        b_mask=b_mask,
        ppi=np.array([l.patches_per_image for l in spec.layers], dtype=np.float64),
        width=np.array([l.arrays_per_block for l in spec.layers], dtype=np.float64),
        layer_arrays=np.array([l.n_arrays for l in spec.layers], dtype=np.float64),
        n_blocks=np.array([l.n_blocks for l in spec.layers], dtype=np.int64),
        mean_b=mean_b,
        max_b=max_b,
        pm_mean=pm_mean,
        pm_max=pm_max,
        busy_sum=busy_sum,
    )


def _eval_kernel(
    xp,
    mean_b,  # (L, B) — zskip variant already selected; (V, L, B) with ``sel``
    max_b,  # (L, B)
    pm_mean,  # (L,)
    pm_max,  # (L,)
    busy_sum,  # (L,)
    b_mask,  # (L, B)
    ppi,  # (L,)
    width,  # (L,)
    layer_arrays,  # (L,)
    dups_lb,  # (L, B) float replicas (layer-wise: broadcast along B)
    layerwise,  # scalar bool: barrier (layer-wise) vs independent blocks
    n_images,
    clock_hz,
    *,
    sel=None,  # scalar variant index into a leading stack axis, or None
):
    """One allocation -> (T, img/s, per-layer makespan, per-layer util).

    Pure array algebra: runs identically with ``xp=numpy`` (scalar float64
    path) and ``xp=jax.numpy`` (vmapped batch path).

    With ``sel`` the five statistic tensors carry a leading variant axis
    (e.g. the fused pipeline's (2A, L, B) baseline+zskip per-ADC stacks)
    and the kernel gathers its variant FIRST, inside the kernel body.
    Under ``vmap`` (banks unbatched, ``sel`` batched) this is a per-config
    scalar-indexed gather that XLA fuses into the eval loop — the bank
    stack stays shared across the whole batch instead of being
    materialized per config (the 0.69x dense-grid regression the shared
    bank layout removes).  Selecting an element is not arithmetic, so
    results are identical to pre-gathered inputs.
    """
    if sel is not None:
        mean_b = mean_b[sel]
        max_b = max_b[sel]
        pm_mean = pm_mean[sel]
        pm_max = pm_max[sel]
        busy_sum = busy_sum[sel]
    P = ppi * n_images  # (L,) patches in the batch
    d_layer = dups_lb[:, 0]
    # layer-wise: patches synchronize on the slowest block (barrier)
    t_lw = xp.maximum(pm_mean * P / d_layer, pm_max)
    # block-wise: every block is an independent replicated server pool
    per_block = xp.maximum(mean_b * P[:, None] / dups_lb, max_b)
    t_bw = xp.where(b_mask, per_block, -xp.inf).max(axis=-1)
    layer_T = xp.where(layerwise, t_lw, t_bw)
    alive = xp.where(
        layerwise,
        layer_arrays * d_layer,
        xp.where(b_mask, dups_lb * width[:, None], 0.0).sum(axis=-1),
    )
    # busy cycles are allocation-independent: every (patch, block) job runs
    # exactly once on `width` arrays.
    busy = busy_sum * P * width
    T = layer_T.max()
    util = busy / (alive * T)
    return T, images_per_sec(T, n_images, clock_hz), layer_T, util


def images_per_sec(T, n_images, clock_hz):
    """Throughput from total cycles.  Jitted callers apply this on the host
    to the device's ``T``: XLA rewrites ``a / (b / c)`` into ``(a * c) / b``,
    which moves the last bit away from the numpy reference."""
    return n_images / (T / clock_hz)


def _alloc_to_dups(st: SimTensors, alloc: Allocation) -> tuple[np.ndarray, bool]:
    """Allocation -> dense (L, B) replica matrix + layer-wise dataflow flag."""
    dups = np.ones((st.L, st.B))
    if alloc.layer_dups is not None:
        dups *= np.asarray(alloc.layer_dups, dtype=np.float64)[:, None]
        return dups, True
    for i, d in enumerate(alloc.block_dups):
        dups[i, : len(d)] = np.asarray(d, dtype=np.float64)
    return dups, False


def simulate(
    spec: NetworkSpec,
    prof: NetworkProfile,
    alloc: Allocation,
    n_images: int = 64,
    clock_hz: float = CLOCK_HZ,
) -> SimResult:
    st = pack_profile(spec, prof)
    z = int(alloc.policy != "baseline")
    dups_lb, layerwise = _alloc_to_dups(st, alloc)
    T, ips, layer_T, util = _eval_kernel(
        np,
        st.mean_b[z],
        st.max_b[z],
        st.pm_mean[z],
        st.pm_max[z],
        st.busy_sum[z],
        st.b_mask,
        st.ppi,
        st.width,
        st.layer_arrays,
        dups_lb,
        layerwise,
        n_images,
        clock_hz,
    )
    return SimResult(alloc.policy, float(T), float(ips), layer_T, util, alloc.arrays_used)


# ----------------------------------------------------------- batched engine
@dataclass(frozen=True)
class BatchSimResult:
    """Structure-of-arrays ``SimResult`` for a batch of C allocations."""

    total_cycles: np.ndarray  # (C,)
    images_per_sec: np.ndarray  # (C,)
    layer_cycles: np.ndarray  # (C, L)
    layer_utilization: np.ndarray  # (C, L)

    @property
    def mean_utilization(self) -> np.ndarray:  # (C,)
        return self.layer_utilization.mean(axis=1)

    def __len__(self) -> int:
        return self.total_cycles.shape[0]


class BatchSimulator:
    """jit + vmap of ``_eval_kernel`` over a batch of allocations.

    One instance per (spec, profile); the packed tensors are baked into the
    compiled kernel as constants.  Runs in float64 (``core.precision
    .x64``) so batch results match the scalar ``simulate()`` to
    roundoff — the golden-equivalence suite pins this at 1e-9.

    ``shard=True`` shard_maps the vmapped kernel over the host's local
    devices (``repro.distrib.sharding.shard_map_batch``): the batch is split
    device-wise, so sweep throughput scales with the accelerators present.
    Rows are evaluated independently either way — results are identical to
    the unsharded path (the suite asserts it).
    """

    def __init__(self, spec: NetworkSpec, prof: NetworkProfile, *, shard: bool = False):
        self.spec = spec
        self.tensors = pack_profile(spec, prof)
        self.shard = bool(shard)
        self._compiled: dict[tuple, object] = {}

    def _fn(self, n_images: int, clock_hz: float):
        key = (n_images, clock_hz)
        if key not in self._compiled:
            import jax
            import jax.numpy as jnp

            st = self.tensors

            def one(dups_lb, layerwise, zskip):
                pick = lambda a: jnp.where(zskip, a[1], a[0])  # noqa: E731
                T, _, layer_T, util = _eval_kernel(
                    jnp,
                    pick(st.mean_b),
                    pick(st.max_b),
                    pick(st.pm_mean),
                    pick(st.pm_max),
                    pick(st.busy_sum),
                    st.b_mask,
                    st.ppi,
                    st.width,
                    st.layer_arrays,
                    dups_lb,
                    layerwise,
                    n_images,
                    clock_hz,
                )
                return T, layer_T, util

            if self.shard:
                from ...distrib.sharding import shard_map_batch

                self._compiled[key] = shard_map_batch(jax.vmap(one))
            else:
                self._compiled[key] = jax.jit(jax.vmap(one))
        return self._compiled[key]

    def __call__(
        self,
        dups_lb: np.ndarray,  # (C, L, B) float replicas
        layerwise: np.ndarray,  # (C,) bool
        zskip: np.ndarray,  # (C,) bool
        n_images: int = 64,
        clock_hz: float = CLOCK_HZ,
    ) -> BatchSimResult:
        from ..precision import x64

        dups_lb = np.asarray(dups_lb, dtype=np.float64)
        if dups_lb.ndim != 3 or dups_lb.shape[1:] != (self.tensors.L, self.tensors.B):
            raise ValueError(
                f"dups_lb {dups_lb.shape} != (C, {self.tensors.L}, {self.tensors.B})"
            )
        with x64():
            T, layer_T, util = self._fn(int(n_images), float(clock_hz))(
                dups_lb, np.asarray(layerwise, bool), np.asarray(zskip, bool)
            )
        T = np.asarray(T)
        return BatchSimResult(
            T, images_per_sec(T, n_images, clock_hz), np.asarray(layer_T),
            np.asarray(util),
        )


def run_policy(
    spec: NetworkSpec,
    prof: NetworkProfile,
    policy: Policy,
    n_pes: int,
    n_images: int = 64,
) -> SimResult:
    return simulate(spec, prof, allocate(spec, prof, policy, n_pes), n_images)
