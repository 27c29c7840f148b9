"""One-jit fused DSE pipeline: profile-derive -> allocate -> evaluate.

The staged sweep (``run_sweep``) dispatches three separately-jitted stages
per (network, array) group — host-side ``derive_profile`` views per ADC
variant, the batched allocators, and the vmapped throughput kernel — with
host round-trips (and profile-cache traffic) between every pair.  This
module collapses them around ONE derive per (network, rows-geometry)
group: the per-ADC bit-plane cycle banks come from the shared
``capture_activations`` capture *in-graph*
(``kernels.bitplane_profile.bitplane_cycle_bank``: shift-and-mask popcount
+ multi-ADC zero-skip re-costing), stacked once and kept device-resident
across every chunk of every call.  Allocation exploits the same sharing:
each greedy family's base latencies are per-ADC-variant constants, so the
whole greedy is replayed from ONE sorted grant-event table per variant
(``core.alloc.greedy.greedy_event_schedule`` — exact, heap-order
tie-for-tie) at a ``searchsorted`` per config.  The per-chunk traced
program is then pure scatter + vmapped ``_eval_kernel``, with each
config gathering its variant's banks by one scalar ``sel`` INSIDE the
kernel — so nothing (C, L, B)-shaped exists besides the replica tensor
and a whole (ADC x policy x PE-budget) config tensor streams through with
no host round-trips between the stages.  Configs partition by replica
FAMILY (per-layer vectors: proportional + perf_layerwise; per-block-unit
vectors: blockwise) — one compiled program per family, spanning every ADC
variant per dispatch instead of one dispatch per (geometry, ADC, family).

Equivalence contract (pinned by tests/test_fused_dse.py): every DISCRETE
column — replica tensors, arrays used/total, chip crossings — is exactly
equal to the staged path, and every float-derived column (total cycles,
throughput, utilization, latency percentiles) agrees to <= 1e-12 relative,
with the observed wobble at the last ULP (~2e-16).  Why not full
bit-identity:

  * cycle samples are integer-valued float64, so any summation order gives
    the exact integer sum (all partials < 2^53), and each per-block mean is
    that exact sum divided once by the patch count — bit-equal to
    ``_pack_profile``'s.  Both paths then answer the greedy from the very
    same event schedule on those bit-equal inputs, which is why the
    replica tensors are EXACTLY equal, not merely close;
  * but the staged and fused evaluators are *different XLA programs*, and
    op-fusion choices between two compilations can shift the last ULP of
    the rounded mean->multiply->divide chains (observed: 1 config in 24 on
    a ResNet18 grid, 1.9e-16 relative in total cycles).  ``busy_sum``
    additionally sums the rounded per-block means in whatever reduction
    order each backend picks.  Float columns are therefore compared at
    rtol 1e-12 — four orders looser than the ULP wobble, tight enough that
    any real formula drift fails;
  * the proportional policies read NO profile data (MACs only), so their
    replica vectors are precomputed host-side with the same
    largest-remainder routine the staged path uses (this also sidesteps
    argsort tie-order differences between numpy and XLA) and enter the
    graph as config constants;
  * ``latency_aware`` is load-coupled and scalar by construction — it stays
    on the staged path and is rejected here.

``FusedPipeline.fabric_percentiles`` extends the fusion to the serving
side: the per-ADC cycle banks feed the ``lax.scan`` virtual-time kernel
through per-config (ADC, zskip, dataflow) gathers, so one vmapped fabric
call spans sub-batches that the staged ``VirtualTimeFabric`` would split
per (network, array) group.  ``run_fused_multichip_sweep`` lifts
``run_multichip_sweep``'s per-placement Python loop into a batchable
placement x load axis over the same kernel.

Scale-out: ``shard=True`` routes the fused program through
``distrib.sharding.shard_map_batch`` — the config axis splits across the
host's local devices, results identical to the unsharded path.
"""

from __future__ import annotations

import time
from dataclasses import dataclass

import numpy as np

from ..core.alloc.greedy import greedy_event_schedule, proportional_allocate_batch
from ..core.cim.cost import ArrayConfig, DEFAULT_ARRAY, baseline_cycles
from ..core.cim.network import NetworkSpec
from ..core.cim.profile import ActivationCapture
from ..core.cim.simulate import (
    ARRAYS_PER_PE,
    CLOCK_HZ,
    _eval_kernel,
    images_per_sec,
)
from ..core.cim.topology import allocate_placed, stage_transfer_matrix
from ..fabric.telemetry import get_telemetry
from .sweep import (
    ChipSweepPoint,
    FabricEval,
    SweepPoint,
    SweepResult,
    _spec_for,
    get_captured,
    get_profiled,
)

__all__ = [
    "FusedPipeline",
    "FusedChipSweepResult",
    "get_fused_pipeline",
    "clear_fused_caches",
    "run_fused_sweep",
    "run_fused_multichip_sweep",
]

_PROPORTIONAL = ("baseline", "weight_based", "weight_blockflow")
_LAYERWISE_FLOW = ("baseline", "weight_based", "perf_layerwise")
_FUSED_POLICIES = _PROPORTIONAL + ("perf_layerwise", "blockwise")
_KIND = {p: 0 for p in _PROPORTIONAL}
_KIND["perf_layerwise"] = 1
_KIND["blockwise"] = 2

_PIPELINE_CACHE: dict[tuple, "FusedPipeline"] = {}


def _canonical(array: ArrayConfig) -> ArrayConfig:
    """The rows-geometry key: ADC precision is a config axis INSIDE a fused
    group (it never changes block shapes), so strip it for grouping."""
    return array.variant(adc_bits=DEFAULT_ARRAY.adc_bits)


def _group_points(points: list[SweepPoint]):
    """Group sweep points by (network, rows-geometry) in one columnar pass.

    Each distinct (network, array) pair, keyed by value, gets a small
    integer code and is canonicalised once; pairs with equal canonical keys
    share a group.  Returns ``(groups, a_idx, pols, pes, distinct)``:
    ``groups`` lists ``(network, canonical array, adcs, rows)`` in order of
    first appearance, ``adcs`` the sorted ADC widths of the group and
    ``rows`` its point indices in ascending order; ``a_idx`` (each point's
    index into its group's ``adcs``), ``pols`` and ``pes`` are per-point
    columns; ``distinct`` counts the pairs canonicalised."""
    C = len(points)
    code_of: dict[tuple, int] = {}
    codes = np.fromiter(
        (code_of.setdefault((p.network, p.array), len(code_of)) for p in points),
        np.int64,
        C,
    )
    pols = np.array([p.policy for p in points], dtype=object)
    pes = np.array([p.n_pes for p in points], dtype=np.int64)

    pairs = list(code_of)
    group_ids: dict[tuple, int] = {}
    group_of = [
        group_ids.setdefault((net, _canonical(arr)), len(group_ids))
        for net, arr in pairs
    ]
    adc_sets: list[set] = [set() for _ in group_ids]
    for (_, arr), g in zip(pairs, group_of):
        adc_sets[g].add(arr.adc_bits)
    adcs = [tuple(sorted(s)) for s in adc_sets]
    a_of_code = np.array(
        [adcs[g].index(arr.adc_bits) for (_, arr), g in zip(pairs, group_of)],
        dtype=np.int32,
    )
    point_group = np.array(group_of, dtype=np.int64)[codes]
    order = np.argsort(point_group, kind="stable")
    counts = np.bincount(point_group, minlength=len(group_ids))
    ends = np.cumsum(counts)
    groups = [
        (net, arr, adcs[g], order[ends[g] - counts[g] : ends[g]])
        for (net, arr), g in group_ids.items()
    ]
    return groups, a_of_code[codes], pols, pes, len(pairs)


class FusedPipeline:
    """Fused derive->allocate->eval for one (network, rows-geometry) group.

    ``adc_bits`` is the group's ADC axis: per-config ``a_idx`` selects a
    variant in-graph.  All other ``ArrayConfig`` fields come from
    ``base_array`` and are part of the group identity (they change block
    shapes)."""

    def __init__(
        self,
        network: str,
        base_array: ArrayConfig,
        adc_bits: tuple[int, ...],
        *,
        profile_images: int = 1,
        sample_patches: int = 128,
        seed: int = 0,
        arrays_per_pe: int = ARRAYS_PER_PE,
        shard: bool = False,
    ):
        self.network = network
        self.adc_bits = tuple(int(a) for a in adc_bits)
        if len(set(self.adc_bits)) != len(self.adc_bits):
            raise ValueError(f"duplicate adc_bits {adc_bits}")
        self.base_array = _canonical(base_array)
        self.variants = tuple(
            self.base_array.variant(adc_bits=a) for a in self.adc_bits
        )
        self.arrays_per_pe = int(arrays_per_pe)
        self.shard = bool(shard)
        self.spec: NetworkSpec = _spec_for(network, self.base_array)
        self.capture: ActivationCapture = get_captured(
            network,
            profile_images=profile_images,
            sample_patches=sample_patches,
            seed=seed,
        )
        self._prof_kw = dict(
            profile_images=profile_images,
            sample_patches=sample_patches,
            seed=seed,
        )
        self._build_static()
        self._compiled: dict[tuple, object] = {}
        self._fabric_compiled: dict[tuple, object] = {}

    # ------------------------------------------------------------ host prep
    def _build_static(self) -> None:
        spec, cap = self.spec, self.capture
        L = len(spec.layers)
        B = max(l.n_blocks for l in spec.layers)
        R = self.base_array.rows
        self.S_l = [c.sampled_q.shape[0] for c in cap.layers]
        S = max(self.S_l)
        self.L, self.B, self.S = L, B, S
        # zero-padded (L, B, S, R) uint8 block tensor: padded rows/blocks/
        # samples contribute no '1' bits and are masked out after costing
        Q = np.zeros((L, B, S, R), dtype=np.uint8)
        s_mask = np.zeros((L, S), dtype=bool)
        b_mask = np.zeros((L, B), dtype=bool)
        for li, (layer, c) in enumerate(zip(spec.layers, cap.layers)):
            s = c.sampled_q.shape[0]
            s_mask[li, :s] = True
            b_mask[li, : layer.n_blocks] = True
            for bi, sl in enumerate(layer.block_row_slices()):
                Q[li, bi, :s, : sl.stop - sl.start] = c.sampled_q[:, sl]
        self.Q = Q
        self.s_mask = s_mask
        self.b_mask = b_mask
        self.s_count = s_mask.sum(axis=1).astype(np.float64)
        self.ppi = np.array(
            [l.patches_per_image for l in spec.layers], dtype=np.float64
        )
        self.width = np.array(
            [l.arrays_per_block for l in spec.layers], dtype=np.float64
        )
        self.layer_arrays = np.array(
            [l.n_arrays for l in spec.layers], dtype=np.float64
        )
        self.macs = np.array(
            [l.macs_per_image for l in spec.layers], dtype=np.float64
        )
        self.base_arrays = spec.n_arrays
        table = spec.block_table()  # (N, 3): layer, block-in-layer, width
        self.l_idx = table[:, 0].copy()
        self.blk_idx = table[:, 1].copy()
        self.cost_blk = table[:, 2].astype(np.float64)
        self.N = table.shape[0]
        # baseline (zskip OFF) statistics are capture-independent geometry
        # constants; computed with the exact ops _pack_profile applies to
        # its variant-0 slice so they are bit-equal to the staged banks
        A = len(self.variants)
        cyc0 = np.zeros((A, L, S, B))
        self.baseline_lb = np.zeros((A, L, B))
        for ai, v in enumerate(self.variants):
            for li, layer in enumerate(spec.layers):
                sl = layer.block_row_slices()
                base = baseline_cycles(
                    np.asarray([s.stop - s.start for s in sl]), v
                ).astype(np.float64)
                self.baseline_lb[ai, li, : layer.n_blocks] = base
                cyc0[ai, li, : self.S_l[li], : layer.n_blocks] = base
        self.mean0 = cyc0.sum(axis=2) / self.s_count[None, :, None]
        self.max0 = cyc0.max(axis=2)
        pmax0 = np.where(b_mask[None, :, None, :], cyc0, -np.inf).max(axis=3)
        self.pm_mean0 = (
            np.where(s_mask, pmax0, 0.0).sum(axis=2) / self.s_count[None, :]
        )
        self.pm_max0 = np.where(s_mask, pmax0, -np.inf).max(axis=2)
        self.busy0 = np.where(b_mask[None], self.mean0, 0.0).sum(axis=2)

    # --------------------------------------------- stage 1: shared bank stacks
    def _stats(self, return_bank: bool = False):
        """Per-group SHARED statistic stacks, derived ONCE and kept
        device-resident across every chunk of every call.

        Returns ``(mean_s, max_s (2A, L, B), pmn_s, pmx_s, busy_s (2A, L),
        exp_lat (A, L), base_blk (A, N))``: the baseline (zskip OFF)
        variants occupy stack slots [0, A) and the zero-skip derivations
        slots [A, 2A), so a per-config scalar ``sel = a_idx + A*zskip``
        picks a variant *inside* ``_eval_kernel`` — no per-config (L, B)
        bank is ever materialized.  The device runs the popcount, the
        multi-ADC re-costing and the sums and maxima over samples, all in
        int32 (exact on every backend); the few divisions by the sample
        count then run on the host, with the very ops ``_pack_profile``
        applies, so the stacks are bit-equal to the staged statistics.
        (A compiled division by a constant is not: XLA turns it into a
        multiply by the reciprocal.)"""
        key = bool(return_bank)
        cached = getattr(self, "_stats_cache", {})
        if key in cached:
            return cached[key]
        import jax
        import jax.numpy as jnp

        from ..core.precision import x64
        from ..kernels.bitplane_profile import bitplane_cycle_bank

        rows_per_read = tuple(v.rows_per_read for v in self.variants)
        cpr = self.base_array.cycles_per_read
        s_mask, b_mask, s_count, ppi = (
            self.s_mask, self.b_mask, self.s_count, self.ppi,
        )

        def derive(Q):
            bank = bitplane_cycle_bank(
                Q, rows_per_read, cycles_per_read=cpr
            )  # (A, L, B, S) int32
            valid = s_mask[None, :, None, :] & b_mask[None, :, :, None]
            cyc = jnp.swapaxes(jnp.where(valid, bank, 0), 2, 3)  # (A, L, S, B)
            # padded samples and blocks hold 0, below every real cycle count
            pmax = cyc.max(axis=3)  # (A, L, S) slowest block per patch
            sums = (
                cyc.sum(axis=2, dtype=jnp.int32),
                cyc.max(axis=2),
                pmax.sum(axis=2, dtype=jnp.int32),
                pmax.max(axis=2),
            )
            return sums + (cyc,) if return_bank else sums

        with x64():
            out = [np.asarray(o) for o in jax.jit(derive)(jnp.asarray(self.Q))]
        sum_b1, max_b1, pm_sum1, pm_max1 = (o.astype(np.float64) for o in out[:4])
        mean_b1 = sum_b1 / s_count[None, :, None]  # (A, L, B)
        pm_mean1 = pm_sum1 / s_count[None, :]
        busy1 = np.where(b_mask[None], mean_b1, 0.0).sum(axis=2)
        # baseline stacked under zskip: slot v, slot A+v per ADC index v
        with x64():
            stats = tuple(
                jnp.asarray(np.concatenate([s0, s1]))
                for s0, s1 in (
                    (self.mean0, mean_b1),
                    (self.max0, max_b1),
                    (self.pm_mean0, pm_mean1),
                    (self.pm_max0, pm_max1),
                    (self.busy0, busy1),
                )
            )
        stats += (
            pm_mean1 * ppi[None, :],  # per-ADC perf_layerwise bases
            (mean_b1 * ppi[None, :, None])[:, self.l_idx, self.blk_idx],  # blockwise
        )
        if return_bank:
            stats += (out[4].astype(np.float64),)
        cached[key] = stats
        self._stats_cache = cached
        return stats

    # ------------------------------------------- stage 2: schedule lookups
    def _schedule(self, kind: int, a: int, max_budget: float):
        """Cached ``GreedyEventSchedule`` for one (family, ADC variant).

        The greedy families' base latencies are per-variant constants
        (derived once by ``_stats``), so the entire greedy collapses
        into ONE sorted grant-event table per variant that
        answers every PE budget with a ``searchsorted`` — exactly (the
        schedule replays the heap order, tie-for-tie; see
        ``core.alloc.greedy.GreedyEventSchedule``).  Rebuilt only when a
        call's budget range outgrows the cached coverage."""
        cache = getattr(self, "_sched_cache", None)
        if cache is None:
            cache = self._sched_cache = {}
        sched = cache.get((kind, a))
        if sched is not None and sched.max_budget >= max_budget:
            return sched
        stats = self._stats()
        if kind == 1:
            base = np.asarray(stats[5])[a]  # (L,) expected layer latency
            cost = self.layer_arrays
        else:
            base = np.asarray(stats[6])[a]  # (N,) per-block-unit latency
            cost = self.cost_blk
        sched = greedy_event_schedule(base, cost, max_budget)
        cache[(kind, a)] = sched
        return sched

    # --------------------------------------------------------- traced program
    def _fn(self, fam: str, n_images: int, clock_hz: float):
        """Per-chunk program for one replica FAMILY: ``"L"`` (per-layer
        replica vectors — the proportional and perf_layerwise kinds) or
        ``"B"`` (per-block-unit vectors — blockwise).  With allocation
        answered by the shared event schedules, the traced program is pure
        scatter + vmapped eval; the bank stacks ride in as unbatched
        closures and each config gathers its variant by one scalar ``sel``
        inside ``_eval_kernel``."""
        key = (fam, n_images, clock_hz)
        if key in self._compiled:
            return self._compiled[key]
        import functools

        import jax
        import jax.numpy as jnp

        b_mask, ppi = self.b_mask, self.ppi
        width, layer_arrays = self.width, self.layer_arrays
        l_idx, blk_idx = self.l_idx, self.blk_idx
        L, B = self.L, self.B

        def fused(stats, sel, layerwise, r):
            mean_s, max_s, pmn_s, pmx_s, busy_s = stats
            C = sel.shape[0]
            if fam == "B":
                dups_lb = jnp.ones((C, L, B)).at[:, l_idx, blk_idx].set(r)
            else:
                dups_lb = jnp.broadcast_to(r[:, :, None], (C, L, B))
            eval_one = functools.partial(
                _eval_kernel,
                jnp,
                b_mask=jnp.asarray(b_mask),
                ppi=jnp.asarray(ppi),
                width=jnp.asarray(width),
                layer_arrays=jnp.asarray(layer_arrays),
                n_images=n_images,
                clock_hz=clock_hz,
            )
            T, _, layer_T, util = jax.vmap(
                lambda s, d, lw: eval_one(
                    mean_s, max_s, pmn_s, pmx_s, busy_s,
                    dups_lb=d, layerwise=lw, sel=s,
                )
            )(sel, dups_lb, layerwise)
            return T, layer_T, util, dups_lb

        stats = self._stats()[:5]
        if self.shard:
            # shard_map_batch splits every positional arg along the config
            # axis, so the bank stacks ride along as closed-over replicated
            # constants
            from ..distrib.sharding import shard_map_batch

            self._compiled[key] = shard_map_batch(
                functools.partial(fused, stats)
            )
        else:
            # donate the (C, L) replica operand where an output of the same
            # shape exists (layer_T / util): the chunked driver streams
            # fresh chunks through one program, so XLA reuses the buffer
            # instead of growing the live set per dispatch
            donate = (3,) if fam == "L" else ()
            self._compiled[key] = functools.partial(
                jax.jit(fused, donate_argnums=donate), stats
            )
        return self._compiled[key]

    def _validate(self, policies, n_pes):
        policies = np.atleast_1d(np.asarray(policies, dtype=object))
        n_pes = np.atleast_1d(np.asarray(n_pes, dtype=np.int64))
        policies, n_pes = np.broadcast_arrays(policies, n_pes)
        unknown = sorted({p for p in policies if p not in _FUSED_POLICIES})
        if unknown:
            raise ValueError(
                f"unsupported policies {unknown} for the fused pipeline; "
                f"choose from {_FUSED_POLICIES} ('latency_aware' is "
                f"load-coupled — use the staged run_sweep)"
            )
        total = n_pes * self.arrays_per_pe
        if np.any(total < self.base_arrays):
            raise ValueError(
                f"{int(total.min())} arrays < minimum {self.base_arrays} "
                f"for {self.spec.name}"
            )
        return policies, n_pes, total

    def __call__(
        self,
        a_idx,  # (C,) index into self.adc_bits
        policies,  # (C,) policy names
        n_pes,  # (C,) PE budgets
        *,
        n_images: int = 64,
        clock_hz: float = CLOCK_HZ,
        chunk: int = 32768,
        return_bank: bool = False,
        need_dups: bool = True,
    ):
        """Evaluate C packed configs in one fused dispatch per chunk.

        Returns a dict of numpy columns (total_cycles, images_per_sec,
        layer_cycles, layer_utilization, dups_lb, layerwise, zskip,
        arrays_used, arrays_total) plus ``bank`` (A, L, S, B) float64 when
        ``return_bank`` — element-wise identical to the staged
        ``allocate_batch`` + ``BatchSimulator`` outputs.

        ``chunk`` tiles the config axis: each tile is one fused dispatch,
        so peak memory is bounded by the tile, not by C — the knob that
        lets a 10^6-config sweep stream through a fixed device footprint.
        ``need_dups=False`` drops the (C, L, B) replica tensor from the
        host outputs (the analytic columns never read it back): at 10^6
        configs that single column is gigabytes, and skipping its
        device->host fetch is what keeps the host side flat too.
        """
        from ..core.precision import x64

        tel = get_telemetry()
        with tel.timed("dse.fused.allocate"):
            policies, n_pes, total = self._validate(policies, n_pes)
            a_idx = np.broadcast_to(
                np.atleast_1d(np.asarray(a_idx, dtype=np.int32)), policies.shape
            ).copy()
            if a_idx.size and (a_idx.min() < 0 or a_idx.max() >= len(self.adc_bits)):
                raise ValueError(
                    f"a_idx out of range for {len(self.adc_bits)} ADC variants"
                )
            C = policies.shape[0]
            budgets = (total - self.base_arrays).astype(np.float64)
            kind = np.array([_KIND[p] for p in policies], dtype=np.int32)
            zskip = policies != "baseline"
            layerwise = np.isin(policies, _LAYERWISE_FLOW)
            A = len(self.variants)
            sel = (a_idx + np.where(zskip, A, 0)).astype(np.int32)

            # ---- stage 2, host side: every replica vector from shared
            # tables, each distinct answer computed once and gathered to
            # its rows.  Proportional replicas are MACs-only constants of
            # the budget (the staged largest-remainder routine, exact); the
            # greedy families replay the per-variant event schedules —
            # element-wise identical to the scalar heap, at a searchsorted
            # per config.  Arrays used come from each table's own
            # ``spent`` (exact integers, as the replicas).
            r_layer = np.ones((C, self.L))  # rows of family "L" only
            used_f = np.zeros(C)
            distinct = 0
            prop = np.nonzero(kind == 0)[0]
            if prop.size:
                ub, inv = np.unique(budgets[prop], return_inverse=True)
                res = proportional_allocate_batch(
                    self.macs, self.layer_arrays, ub
                )
                r_layer[prop] = res.replicas[inv]
                used_f[prop] = res.spent[inv]
                distinct += ub.size
            rows_B = np.nonzero(kind == 2)[0]
            r_blk = np.ones((rows_B.size, self.N))  # family "B", rows_B order
            for k, rows_k in ((1, np.nonzero(kind == 1)[0]), (2, rows_B)):
                if rows_k.size == 0:
                    continue
                bmax = float(budgets[rows_k].max())
                for a in np.unique(a_idx[rows_k]):
                    rk = a_idx[rows_k] == a
                    rows = rows_k[rk]
                    got = self._schedule(k, int(a), bmax).replicas_at(
                        budgets[rows]
                    )
                    if k == 1:
                        r_layer[rows] = got.replicas
                    else:
                        r_blk[rk] = got.replicas
                    used_f[rows] = got.spent
                    # one snapshot per distinct stop, and stops differ in spent
                    distinct += np.unique(got.spent).size
            rows_L = np.nonzero(kind != 2)[0]
        tel.gauge("dse.fused.alloc_distinct", distinct)

        outs = {
            "total_cycles": np.zeros(C),
            "layer_cycles": np.zeros((C, self.L)),
            "layer_utilization": np.zeros((C, self.L)),
        }
        if need_dups:
            outs["dups_lb"] = np.zeros((C, self.L, self.B))
        csize_max = n_chunks = 0
        with x64():
            for fam, rows, r_fam in (("L", rows_L, r_layer), ("B", rows_B, r_blk)):
                if rows.size == 0:
                    continue
                fn = self._fn(fam, int(n_images), float(clock_hz))
                csize = min(int(chunk), rows.size)
                csize_max = max(csize_max, csize)
                for j0 in range(0, rows.size, csize):
                    part = rows[j0 : j0 + csize]
                    with tel.timed("dse.fused.dispatch", configs=part.size):
                        pad = csize - part.size
                        take = (
                            part
                            if pad == 0
                            else np.concatenate([part, np.repeat(part[:1], pad)])
                        )  # pad repeating row 0: one compilation per partition
                        # family "L" replicas index by global row; family "B"
                        # by position (r_blk rows are laid out in rows_B order)
                        if fam == "L":
                            r_take = r_fam[take]
                        else:
                            r_take = r_fam[j0 : j0 + csize]
                            if pad:
                                r_take = np.concatenate(
                                    [r_take, np.repeat(r_take[:1], pad, axis=0)]
                                )
                        T, layer_T, util, dups = fn(
                            sel[take], layerwise[take], r_take
                        )
                    with tel.timed("dse.fused.fetch", configs=part.size):
                        n = part.size
                        outs["total_cycles"][part] = np.asarray(T)[:n]
                        outs["layer_cycles"][part] = np.asarray(layer_T)[:n]
                        outs["layer_utilization"][part] = np.asarray(util)[:n]
                        if need_dups:
                            outs["dups_lb"][part] = np.asarray(dups)[:n]
                    n_chunks += 1
        outs["images_per_sec"] = images_per_sec(
            outs["total_cycles"], n_images, clock_hz
        )
        outs["arrays_used"] = self.base_arrays + used_f.astype(np.int64)
        # chunking telemetry: the live device set per dispatch is one tile —
        # the (csize, L, B) replica tensor dominates — never the full C
        # (the peak-memory smoke in tests/test_fused_dse.py reads these)
        tel.gauge("dse.fused.chunk_configs", csize_max)
        tel.gauge(
            "dse.fused.chunk_device_bytes",
            csize_max * (2 * self.L * self.B + self.N + 2 * self.L + 3) * 8,
        )
        tel.gauge(
            "dse.fused.host_out_bytes", sum(a.nbytes for a in outs.values())
        )
        tel.count("dse.fused.chunks", n_chunks)
        outs["arrays_total"] = total
        outs["layerwise"] = layerwise
        outs["zskip"] = zskip
        if return_bank:
            outs["bank"] = np.asarray(self._stats(return_bank=True)[-1])
        return outs

    # ----------------------------------------------------- fused fabric stage
    def _fabric_fn(self, n, D_by_layer, has_xfer, window):
        """Cached jit(vmap) of the virtual-time kernel over configs; times
        are int64 bit patterns (``core.precision``), exact on any backend."""
        key = (n, tuple(D_by_layer), has_xfer, window)
        if key in self._fabric_compiled:
            return self._fabric_compiled[key]
        import functools

        import jax
        import jax.numpy as jnp

        from ..core.precision import to_bits
        from ..fabric.vtime import run_fabric_kernel

        cyc_banks = [to_bits(c) for c in self._cyc_banks]  # per layer (A, S_l, B_l)
        base_banks = [
            to_bits(self.baseline_lb[:, li, : layer.n_blocks])
            for li, layer in enumerate(self.spec.layers)
        ]  # per layer (A, B_l)
        job_scan = functools.partial(jax.lax.scan, unroll=1)

        def one(frees, xfer, arrivals, a, z, lw, idx):
            stages = []
            for li in range(self.L):
                c1 = jnp.asarray(cyc_banks[li])[a]  # (S_l, B_l)
                c0 = jnp.broadcast_to(
                    jnp.asarray(base_banks[li])[a][None, :], c1.shape
                )
                c = jnp.where(z, c1, c0)
                b = c.shape[1]
                onehot0 = jnp.arange(b) == 0
                # layer-wise dataflow: the barrier collapses each patch to
                # its slowest block, dispatched on pool 0 (identical to the
                # staged per-group (S, 1) packing — max commutes with the
                # service-index gather)
                c_lw = jnp.where(
                    onehot0[None, :], c.max(axis=1, keepdims=True), 0
                )
                stages.append(
                    (
                        jnp.where(lw, c_lw, c),
                        jnp.where(lw, onehot0, jnp.ones(b, dtype=bool)),
                    )
                )
            return run_fabric_kernel(
                jnp,
                jax.lax.scan,
                tuple(stages),
                frees,
                arrivals,
                idx,
                None,
                job_scan=job_scan,
                xfer=xfer,
                window=window,
            )

        self._fabric_compiled[key] = jax.jit(
            jax.vmap(
                one,
                in_axes=(0, 0 if has_xfer else None, 0, 0, 0, 0, None),
            )
        )
        return self._fabric_compiled[key]

    @property
    def _cyc_banks(self):
        banks = getattr(self, "_cyc_banks_cache", None)
        if banks is None:
            # the shared derive already produced the full (A, L, S, B) bank
            full = np.asarray(self._stats(return_bank=True)[-1])
            banks = [
                np.ascontiguousarray(
                    full[:, li, : self.S_l[li], : layer.n_blocks]
                )
                for li, layer in enumerate(self.spec.layers)
            ]
            self._cyc_banks_cache = banks
        return banks

    def fabric_percentiles(
        self,
        a_idx: np.ndarray,  # (C,)
        dups_lb: np.ndarray,  # (C, L, B) from the analytic stage
        layerwise: np.ndarray,  # (C,) bool
        zskip: np.ndarray,  # (C,) bool
        arrival_times: np.ndarray,  # (C, n) cycles
        *,
        seed: int = 0,
        qs: tuple = (50.0, 95.0, 99.0),
        xfer: np.ndarray | None = None,  # (C, L) stage entry transfers
        lane_quantum: int = 1,
        window: int = 8,
    ) -> np.ndarray:
        """(C, len(qs)) latency percentiles through the fused virtual-time
        kernel: per-config (ADC, zskip, dataflow) gathers against the
        in-graph-derived cycle banks, one vmapped ``lax.scan`` call per
        lane-homogeneous sub-batch.  Bit-identical to routing each config
        through the staged ``VirtualTimeFabric``.

        ``window`` dispatches that many requests per ``lax.scan`` step (the
        blocked scan; non-overtaking makes any window bit-identical to
        ``window=1``, so this is purely a host-overhead knob)."""
        from ..core.precision import from_bits, to_bits, x64
        from ..fabric.vtime import sample_service_indices

        C, n = arrival_times.shape
        a_idx = np.asarray(a_idx, dtype=np.int32)
        lw = np.asarray(layerwise, dtype=bool)
        z = np.asarray(zskip, dtype=bool)
        dims = [(self.S_l[li], l.patches_per_image) for li, l in enumerate(self.spec.layers)]
        idx = sample_service_indices(np.random.default_rng(seed), dims, n)
        # effective lanes per (config, layer, pool): layer-wise configs pool
        # everything on block 0
        d_eff = []
        for li, layer in enumerate(self.spec.layers):
            b = layer.n_blocks
            d = np.asarray(dups_lb[:, li, :b], dtype=np.int64)
            d = np.where(
                lw[:, None],
                np.where(np.arange(b) == 0, dups_lb[:, li, :1].astype(np.int64), 0),
                d,
            )
            d_eff.append(d)  # (C, B_l)
        # bound lane padding: chain configs by their own scan cost, cutting
        # when one exceeds 1.5x its sub-batch's first (the staged policy)
        cost = np.zeros(C)
        for li, layer in enumerate(self.spec.layers):
            cost += layer.patches_per_image * layer.n_blocks * d_eff[li].max(axis=1)
        order = np.argsort(cost, kind="stable")
        subs: list[list[int]] = []
        for j in order:
            if subs and cost[j] <= 1.5 * max(cost[subs[-1][0]], 1.0):
                subs[-1].append(int(j))
            else:
                subs.append([int(j)])
        q = max(1, int(lane_quantum))
        pcts = np.zeros((C, len(qs)))
        with x64():
            for rows in subs:
                r = np.asarray(rows)
                frees = []
                for li in range(self.L):
                    d = d_eff[li][r]
                    D = -(-max(int(d.max()), 1) // q) * q
                    frees.append(
                        np.where(np.arange(D) < d[:, :, None], 0.0, np.inf)
                    )
                fn = self._fabric_fn(
                    n, [f.shape[2] for f in frees], xfer is not None, int(window)
                )
                out = fn(
                    tuple(to_bits(f) for f in frees),
                    None if xfer is None else to_bits(xfer[r]),
                    to_bits(arrival_times[r]),
                    a_idx[r],
                    z[r],
                    lw[r],
                    tuple(idx),
                )
                t_arr, comp = from_bits(out[0]), from_bits(out[1])
                # percentiles recomputed host-side from the bit-exact
                # latencies, matching the staged sweep columns exactly
                pcts[r] = np.percentile(comp - t_arr, qs, axis=1).T
        return pcts


def get_fused_pipeline(
    network: str,
    base_array: ArrayConfig,
    adc_bits: tuple[int, ...],
    *,
    profile_images: int = 1,
    sample_patches: int = 128,
    seed: int = 0,
    arrays_per_pe: int = ARRAYS_PER_PE,
    shard: bool = False,
) -> FusedPipeline:
    """Cached ``FusedPipeline`` — compiled programs survive across sweeps."""
    key = (
        network,
        _canonical(base_array),
        tuple(int(a) for a in adc_bits),
        profile_images,
        sample_patches,
        seed,
        arrays_per_pe,
        shard,
    )
    if key not in _PIPELINE_CACHE:
        _PIPELINE_CACHE[key] = FusedPipeline(
            network,
            base_array,
            adc_bits,
            profile_images=profile_images,
            sample_patches=sample_patches,
            seed=seed,
            arrays_per_pe=arrays_per_pe,
            shard=shard,
        )
    return _PIPELINE_CACHE[key]


def clear_fused_caches() -> None:
    _PIPELINE_CACHE.clear()


def run_fused_sweep(
    points: list[SweepPoint],
    *,
    n_images: int = 64,
    profile_images: int = 1,
    sample_patches: int = 128,
    seed: int = 0,
    arrays_per_pe: int = ARRAYS_PER_PE,
    fabric: FabricEval | None = None,
    shard_devices: bool = False,
    chunk: int = 32768,
) -> SweepResult:
    """Drop-in fused counterpart of ``run_sweep(engine="batch")``.

    Groups points by (network, rows-geometry); each group derives its
    shared per-ADC bank stacks once, then streams the whole (ADC x policy
    x PE-budget) config tensor through ONE fused allocate+eval dispatch
    per chunk — no host round-trips, peak memory bounded by the chunk
    (tilings are element-wise identical, pinned by tests/test_fused_dse.py)
    — optionally followed by the fused virtual-time stage for the latency
    columns.  Without a
    fabric stage the per-config replica tensors are never fetched to the
    host (``need_dups=False`` inside), so a 10^6-config analytic sweep
    holds only (C,)/(C, L) columns.  Results are element-wise identical
    to the staged path.  ``latency_aware`` points are rejected — that
    policy is load-coupled and stays staged."""
    C = len(points)
    out = {
        name: np.zeros(C)
        for name in ("total_cycles", "images_per_sec", "mean_utilization")
    }
    used = np.zeros(C, dtype=np.int64)
    total = np.zeros(C, dtype=np.int64)
    pcts = np.full((C, 3), np.nan) if fabric is not None else None

    tel = get_telemetry()
    with tel.timed("dse.fused.sweep", configs=C):
        with tel.timed("dse.fused.group", configs=C):
            groups, a_all, pols_all, pes_all, distinct = _group_points(points)
            tel.gauge("dse.fused.distinct_arrays", distinct)

        elapsed = 0.0
        for net, arr, adcs, idx in groups:
            with tel.timed("dse.fused.group", configs=len(idx)):
                a_idx, pols, pes = a_all[idx], pols_all[idx], pes_all[idx]
            pipe = get_fused_pipeline(
                net,
                arr,
                adcs,
                profile_images=profile_images,
                sample_patches=sample_patches,
                seed=seed,
                arrays_per_pe=arrays_per_pe,
                shard=shard_devices,
            )
            t0 = time.perf_counter()
            res = pipe(
                a_idx, pols, pes, n_images=n_images, chunk=chunk,
                need_dups=fabric is not None,
            )
            out["total_cycles"][idx] = res["total_cycles"]
            out["images_per_sec"][idx] = res["images_per_sec"]
            out["mean_utilization"][idx] = res["layer_utilization"].mean(axis=1)
            used[idx] = res["arrays_used"]
            total[idx] = res["arrays_total"]
            if fabric is not None:
                gaps = np.random.default_rng(fabric.seed).exponential(
                    1.0, size=fabric.n_requests
                )
                rates = fabric.load_frac * res["images_per_sec"] / CLOCK_HZ
                times = np.cumsum(gaps)[None, :] / rates[:, None]
                pcts[idx] = pipe.fabric_percentiles(
                    a_idx,
                    res["dups_lb"],
                    res["layerwise"],
                    res["zskip"],
                    times,
                    seed=fabric.seed,
                )
            elapsed += time.perf_counter() - t0

    return SweepResult(
        points=list(points),
        total_cycles=out["total_cycles"],
        images_per_sec=out["images_per_sec"],
        mean_utilization=out["mean_utilization"],
        arrays_used=used,
        arrays_total=total,
        elapsed_s=elapsed,
        engine="fused",
        p50_cycles=pcts[:, 0] if fabric is not None else None,
        p95_cycles=pcts[:, 1] if fabric is not None else None,
        p99_cycles=pcts[:, 2] if fabric is not None else None,
        fabric=fabric,
    )


# --------------------------------------------------- fused multi-chip sweep
@dataclass
class FusedChipSweepResult:
    """Multi-chip outcome with a batched LOAD axis: row i of ``pcts`` holds
    the (len(load_fracs), 3) p50/p95/p99 surface of ``points[i]`` —
    placement x load evaluated in one batched virtual-time call per group."""

    points: list[ChipSweepPoint]
    load_fracs: tuple
    images_per_sec: np.ndarray  # (C,)
    pcts: np.ndarray  # (C, K, 3) latency percentiles, cycles
    max_stage_transfer: np.ndarray
    n_crossings: np.ndarray
    arrays_used: np.ndarray
    arrays_total: np.ndarray
    elapsed_s: float

    def __len__(self) -> int:
        return len(self.points)

    @property
    def n_evaluations(self) -> int:
        return len(self.points) * len(self.load_fracs)

    def rows(self) -> list[dict]:
        out = []
        for i, p in enumerate(self.points):
            for k, lf in enumerate(self.load_fracs):
                out.append(
                    {
                        "network": p.network,
                        "policy": p.policy,
                        "n_chips": p.n_chips,
                        "link_gbps": p.link_gbps,
                        "load_frac": float(lf),
                        "images_per_sec": float(self.images_per_sec[i]),
                        "p50_ms": float(self.pcts[i, k, 0] / CLOCK_HZ * 1e3),
                        "p95_ms": float(self.pcts[i, k, 1] / CLOCK_HZ * 1e3),
                        "p99_ms": float(self.pcts[i, k, 2] / CLOCK_HZ * 1e3),
                        "max_stage_transfer_cycles": float(
                            self.max_stage_transfer[i]
                        ),
                        "n_crossings": int(self.n_crossings[i]),
                        "arrays_used": int(self.arrays_used[i]),
                        "arrays_total": int(self.arrays_total[i]),
                    }
                )
        return out


def run_fused_multichip_sweep(
    points: list[ChipSweepPoint],
    *,
    load_fracs: tuple = (0.7,),
    n_requests: int = 200,
    closed_requests: int = 80,
    concurrency: int = 32,
    seed: int = 0,
    profile_images: int = 1,
    sample_patches: int = 128,
    arrays_per_pe: int = ARRAYS_PER_PE,
    latency_load_frac: float = 0.7,
) -> FusedChipSweepResult:
    """``run_multichip_sweep`` with the placement loop lifted into a
    batchable placement x load axis.

    The staged sweep evaluates one load point per run and walks placements
    in Python; here every group's (unique placement) x (load_frac) cross
    product goes through ONE batched open-loop virtual-time call (the
    placements' per-stage transfer vectors packed by
    ``topology.stage_transfer_matrix``), after one batched closed-loop call
    for throughput.  At ``load_fracs=(0.7,)`` the outcome is element-wise
    identical to ``run_multichip_sweep`` (pinned by the equivalence suite).
    """
    from ..fabric.arrivals import ClosedLoop, TraceReplay
    from ..fabric.vtime import VirtualTimeFabric

    K = len(load_fracs)
    C = len(points)
    ips = np.zeros(C)
    pcts = np.zeros((C, K, 3))
    xfer_max = np.zeros(C)
    crossings = np.zeros(C, dtype=np.int64)
    used = np.zeros(C, dtype=np.int64)
    total = np.zeros(C, dtype=np.int64)

    groups: dict[tuple, list[int]] = {}
    for i, p in enumerate(points):
        groups.setdefault((p.network, p.array), []).append(i)
    prof_kw = dict(
        profile_images=profile_images, sample_patches=sample_patches, seed=seed
    )
    for net, arr in groups:
        get_profiled(net, arr, **prof_kw)

    elapsed = 0.0
    qs = (50.0, 95.0, 99.0)
    for (net, arr), rows in groups.items():
        spec, prof = get_profiled(net, arr, **prof_kw)
        alias: dict[int, int] = {}
        canon: dict[tuple, int] = {}
        uniq: list[int] = []
        for i in rows:
            p = points[i]
            key = (
                p.policy, p.n_pes_total, p.n_chips,
                p.link_gbps if p.n_chips > 1 else None,
            )
            if key not in canon:
                canon[key] = i
                uniq.append(i)
            alias[i] = canon[key]
        placed = []
        for i in uniq:
            p = points[i]
            pa = allocate_placed(
                spec, prof, p.policy, p.topology(arrays_per_pe),
                load_frac=latency_load_frac,
            )
            placed.append(pa)
            xfer_max[i] = pa.placement.max_stage_transfer
            crossings[i] = pa.placement.n_crossings
            used[i] = pa.allocation.arrays_used
            total[i] = pa.allocation.arrays_total
        allocs = [pa.allocation for pa in placed]
        places = [pa.placement for pa in placed]
        stage_transfer_matrix(places)  # validate the packable axis up front
        t0 = time.perf_counter()
        vt = VirtualTimeFabric(spec, prof, lane_quantum=8)
        cl = vt.run_batch(
            allocs, ClosedLoop(closed_requests, concurrency),
            seed=seed, percentiles=qs, placements=places,
        )
        ips[uniq] = cl.images_per_sec
        # the lifted axis: (placement x load) pairs share one normalized
        # gap sequence and evaluate in ONE batched open-loop call
        gaps = np.random.default_rng(seed).exponential(1.0, size=n_requests)
        cum = np.cumsum(gaps)
        U = len(uniq)
        allocs_x = [allocs[u] for u in range(U) for _ in range(K)]
        places_x = [places[u] for u in range(U) for _ in range(K)]
        procs = [
            TraceReplay(cum / (lf * ips[uniq[u]] / CLOCK_HZ))
            for u in range(U)
            for lf in load_fracs
        ]
        op = vt.run_batch(
            allocs_x, procs, seed=seed, percentiles=qs, placements=places_x
        )
        lat = op.latencies.reshape(U, K, -1)
        for k in range(K):
            pcts[np.asarray(uniq), k] = np.percentile(lat[:, k], qs, axis=1).T
        for i in rows:
            j = alias[i]
            if j != i:
                ips[i] = ips[j]
                pcts[i] = pcts[j]
                xfer_max[i] = xfer_max[j]
                crossings[i] = crossings[j]
                used[i] = used[j]
                total[i] = total[j]
        elapsed += time.perf_counter() - t0

    return FusedChipSweepResult(
        points=list(points),
        load_fracs=tuple(load_fracs),
        images_per_sec=ips,
        pcts=pcts,
        max_stage_transfer=xfer_max,
        n_crossings=crossings,
        arrays_used=used,
        arrays_total=total,
        elapsed_s=elapsed,
    )
