"""Fleet-scale trace replay: streaming sketches + segmented re-allocation.

``vtime.py`` proves the fabric collapses to a scan over requests; this
module makes that scan usable as a *what-if oracle over millions of
requests* — the ROADMAP's online-serving control plane needs to replay a
day of traffic against a batch of candidate allocations in seconds, not
keep a (configs, requests) latency matrix alive to do it.

Three pieces, composable and individually pinned:

  * ``run_stream``: the virtual-time kernel with O(lanes + sketch) carry —
    service indices come from an in-kernel counter hash
    (``hash_service_indices``; presampling is tens of GB at 10^6 requests),
    per-request latencies fold into a ``fabric.metrics`` log-bucket sketch
    plus exact min/max and Welford moments, and the request scan is blocked
    ``window`` at a time.  Bucket counts, min/max and makespan are pinned
    bit-identical against ``FabricSim(service_sampling="hash")`` and
    against the numpy replay of the same kernel.
  * ``run_trace_segments``: splits a long trace at control-interval
    boundaries, carries free-lane state across segments, and applies a
    per-segment allocation (growth or shrink), charging the event engine's
    reprogramming semantics at each boundary: every lane of a reshaped
    config freezes until ``boundary + DriftConfig.stall(arrays_added)``
    (net-new replicas only) and the new lanes come online then — exactly
    ``FabricSim.apply_growth``; shrunk lanes go to ``+inf`` (absent), which
    is how seeded failure traces replay on this engine.  With no allocation
    change and zero stall the segmented replay is bit-identical to the
    unsegmented run (pinned in tests).
  * ``segment_growth_plan``: builds such a trajectory from per-boundary
    array budgets (negative = degraded capacity, via ``greedy_release``)
    through ``greedy_allocate(initial_replicas=...)`` — the warm-start hook
    the autoscaling controller drives.
  * ``run_trace_failures``: the fault-tolerance entry — compiles a seeded
    ``fabric.failures.FailureTrace`` into a ``DegradePlan`` and replays it
    here, bit-identical to ``FabricSim(failures=plan)`` (the cross-engine
    contract pinned in tests/test_failures.py).

``CoarsenConfig`` (from ``vtime``) optionally trades ~0.3-2% pessimistic
tail bias for the 2.7-3.2x macro-job speedup on top; every default is the
exact kernel.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import numpy as np

from ..core.cim.network import NetworkSpec
from ..core.cim.profile import NetworkProfile
from ..core.cim.simulate import (
    Allocation,
    CLOCK_HZ,
    _layer_patch_cycles,
    blockwise_units,
    split_block_dups,
)
from ..core.precision import add, from_bits, sub, to_bits, x64
from .arrivals import ArrivalProcess, ClosedLoop, arrival_times
from .drift import DriftConfig
from .metrics import (
    LatencySketch,
    LatencyStats,
    SketchConfig,
    sketch_init,
    sketch_update,
)
from .vtime import (
    CoarsenConfig,
    VirtualTimeFabric,
    _GroupPack,
    _chunk_services,
    _hash_salt,
    _np_scan,
    _pack_group,
    _scan_windowed,
    chunk_plan,
    hash_service_indices,
    pool_dispatch_stream,
    run_fabric_kernel,
    sample_service_indices,
)

__all__ = [
    "FleetResult",
    "SegmentReport",
    "SegmentedReplayResult",
    "run_stream",
    "run_trace_failures",
    "run_trace_segments",
    "segment_growth_plan",
]


# ------------------------------------------------------------ stream kernel
def _tree_where(xp, pred, new, old):
    """Select whole carry trees by a scalar predicate — how padded requests
    (``i >= n_valid``) leave the fabric state untouched bit-for-bit."""
    if isinstance(new, tuple):
        return tuple(_tree_where(xp, pred, a, b) for a, b in zip(new, old))
    return xp.where(pred, new, old)


def _stream_request_step(
    xp, job_scan, stages, xfer, concurrency, salts, dims, plans, cfg,
    r0, n_valid, emit, carry, inp,
):
    """``vtime._request_step`` with O(1)-per-request carry: hash-derived
    service indices, carry-max stage completions, in-carry sketch + horizon
    instead of per-request ys.  ``r0`` offsets the local scan index to the
    global request id (segment continuation + hash identity); requests at
    ``i >= n_valid`` are padding and leave the carry unchanged.  ``emit``
    additionally materializes per-request ``(t_arrival, t_done)`` — the
    O(N)-memory baseline the sketch replaces (kept for validation and the
    fleet bench's exact-percentile reference)."""
    frees, ring, sk, horizon = carry
    i, t_arr = inp
    r = r0 + i
    if concurrency is None:
        t = t_arr
    else:
        pos = r % concurrency
        t = ring[pos]
    t0 = t
    new_frees = []
    for li, ((cycles, b_mask), free) in enumerate(zip(stages, frees)):
        if xfer is not None:
            t = add(xp, t, xfer[li])
        n_samples, ppi = dims[li]
        ix = hash_service_indices(xp, salts[li], r, ppi, n_samples)
        svc = _chunk_services(xp, cycles[ix], plans[li])
        free, t = pool_dispatch_stream(xp, job_scan, free, t, svc, b_mask)
        new_frees.append(free)
    if concurrency is not None:
        ring = xp.where(xp.arange(ring.shape[0]) == pos, t, ring)
    new = (
        tuple(new_frees),
        ring,
        sketch_update(xp, sk, sub(xp, t, t0), cfg),
        xp.maximum(horizon, t),
    )
    return _tree_where(xp, i < n_valid, new, carry), ((t0, t) if emit else None)


def _run_stream_kernel(
    xp, scan, stages, frees, arrivals, concurrency, cfg, salts, dims, plans,
    sk0, hor0, ring0, job_scan=None, xfer=None, window=1, r0=0, n_valid=None,
    emit=False,
):
    """One config/segment of the streaming replay; returns the final carry
    (frees, ring, sketch state, horizon) and — only with ``emit`` — the
    per-request ``(arrivals, completions)`` ys."""
    n = arrivals.shape[0]
    body = partial(
        _stream_request_step, xp, job_scan or scan, stages, xfer, concurrency,
        salts, dims, plans, cfg, r0, n_valid, emit,
    )
    if concurrency is not None:
        window = min(int(window), int(concurrency))
    carry0 = (frees, ring0, sk0, hor0)
    carry, ys = _scan_windowed(
        xp, scan, body, carry0, (xp.arange(n), arrivals), n, window
    )
    return (carry, ys) if emit else carry


def _stream_dims_salts(vt: VirtualTimeFabric, seed: int):
    dims = tuple(
        (int(vt._cyc[True][i].shape[0]), int(l.patches_per_image))
        for i, l in enumerate(vt.spec.layers)
    )
    salts = tuple(_hash_salt(seed, li) for li in range(len(dims)))
    return dims, salts


def _stream_runner(
    vt, g: _GroupPack, concurrency, n_pad, window, cfg, plans, dims, salts,
    seed, has_xfer, emit=False,
):
    """Cached jit(vmap) of the streaming kernel for one group structure.
    Lane state / ring / sketch state / r0 / n_valid are traced arguments, so
    segmented replay reuses ONE compiled kernel for every same-length
    (padded) segment.  Times are int64 bit patterns (``core.precision``)."""
    key = (
        "fleet", g.layerwise, g.zskip, concurrency, n_pad, window, cfg, plans,
        tuple(f.shape[1:] for f in g.frees), seed, has_xfer, emit,
    )
    if key not in vt._compiled:
        import functools

        import jax
        import jax.numpy as jnp

        np_stages = tuple((to_bits(c), m) for c, m in g.stages)
        job_scan = functools.partial(jax.lax.scan, unroll=1)

        def one(frees, xfer, arrivals, ring, sk, hor, r0, n_valid):
            # cycle constants converted INSIDE the trace: x64 survival,
            # same rationale as VirtualTimeFabric._jax_runner
            stages = tuple((jnp.asarray(c), jnp.asarray(m)) for c, m in np_stages)
            return _run_stream_kernel(
                jnp, jax.lax.scan, stages, frees, arrivals, concurrency, cfg,
                salts, dims, plans, sk, hor, ring0=ring, job_scan=job_scan,
                xfer=xfer, window=window, r0=r0, n_valid=n_valid, emit=emit,
            )

        vt._compiled[key] = jax.jit(
            jax.vmap(one, in_axes=(0, 0 if has_xfer else None, 0, 0, 0, 0, None, None))
        )
    return vt._compiled[key]


def _init_stream_state(g: _GroupPack, concurrency, cfg: SketchConfig):
    c = len(g.rows)
    ring = np.zeros((c, concurrency if concurrency is not None else 1))
    sk = tuple(
        np.zeros((c,) + np.shape(a), dtype=np.float64) + np.asarray(a)
        for a in sketch_init(np, cfg)
    )
    return (tuple(np.array(f) for f in g.frees), ring, sk, np.zeros(c))


def _stream_group_call(
    vt, g: _GroupPack, times, concurrency, seed, window, cfg, coarsen, engine,
    pad_to, state, r0, emit=False,
):
    """Advance one group's streaming state over ``times`` ((C, n) arrivals).
    Pads the segment to a multiple of ``pad_to`` with carry-masked requests
    so varying segment lengths share compiled kernels.  With ``emit`` also
    returns the materialized (C, n) completions (padding sliced off)."""
    c, n = times.shape
    if state is None:
        state = _init_stream_state(g, concurrency, cfg)
    if n == 0:
        return (state, (np.zeros((c, 0)), np.zeros((c, 0)))) if emit else state
    dims, salts = _stream_dims_salts(vt, seed)
    plans = tuple(
        chunk_plan(dims[li][1], g.frees[li].shape[-1], coarsen)
        for li in range(len(dims))
    )
    q = max(1, int(pad_to))
    n_pad = -(-n // q) * q
    if n_pad > n:
        times = np.concatenate(
            [times, np.broadcast_to(times[:, -1:], (c, n_pad - n))], axis=1
        )
    frees, ring, sk, hor = state
    if engine == "jax":
        fn = _stream_runner(
            vt, g, concurrency, n_pad, window, cfg, plans, dims, salts, seed,
            g.xfer is not None, emit,
        )

        def sk_times(sk, conv):  # the sketch's min and max are times
            return tuple(conv(a) if i in (2, 3) else np.asarray(a) for i, a in enumerate(sk))

        with x64():
            out = fn(
                tuple(to_bits(f) for f in frees),
                None if g.xfer is None else to_bits(g.xfer),
                to_bits(times), to_bits(ring), sk_times(sk, to_bits),
                to_bits(hor), r0, n,
            )
        if emit:
            out, ys = out
            comp = (from_bits(ys[0])[:, :n], from_bits(ys[1])[:, :n])
        frees = tuple(from_bits(f) for f in out[0])
        ring = from_bits(out[1])
        sk = sk_times(out[2], from_bits)
        hor = from_bits(out[3])
        state = (frees, ring, sk, hor)
        return (state, comp) if emit else state
    new_frees = [np.empty_like(f) for f in frees]
    ring = ring.copy()
    sk = tuple(a.copy() for a in sk)
    hor = hor.copy()
    comp = (np.zeros((c, n)), np.zeros((c, n))) if emit else None
    for k in range(c):
        carry = _run_stream_kernel(
            np, _np_scan, g.stages, tuple(f[k] for f in frees), times[k],
            concurrency, cfg, salts, dims, plans,
            tuple(a[k] for a in sk), hor[k], ring0=ring[k],
            xfer=None if g.xfer is None else g.xfer[k],
            window=window, r0=r0, n_valid=n, emit=emit,
        )
        if emit:
            carry, ys = carry
            comp[0][k] = np.asarray(ys[0])[:n]
            comp[1][k] = np.asarray(ys[1])[:n]
        for li, f in enumerate(carry[0]):
            new_frees[li][k] = f
        ring[k] = carry[1]
        for a, v in zip(sk, carry[2]):
            a[k] = v
        hor[k] = carry[3]
    state = (tuple(new_frees), ring, sk, hor)
    return (state, comp) if emit else state


# ----------------------------------------------------------------- results
@dataclass(frozen=True)
class FleetResult:
    """Streaming replay outcome: per-config sketches instead of (C, N)
    latency matrices — memory O(C x buckets) at any trace length."""

    sketches: tuple  # (C,) LatencySketch
    percentile_qs: tuple
    makespan: np.ndarray  # (C,) cycles (max completion)
    n_requests: int
    clock_hz: float = CLOCK_HZ
    window: int = 1
    arrivals: np.ndarray | None = None  # (C, N) materialize=True only
    completions: np.ndarray | None = None  # (C, N) materialize=True only

    def __len__(self) -> int:
        return len(self.sketches)

    @property
    def percentiles(self) -> np.ndarray:  # (C, Q) sketch-estimated, cycles
        return np.stack(
            [s.percentiles(self.percentile_qs) for s in self.sketches]
        )

    def percentile(self, q: float) -> np.ndarray:  # (C,)
        return self.percentiles[:, self.percentile_qs.index(q)]

    @property
    def p99(self) -> np.ndarray:
        return self.percentile(99.0)

    def latency(self, i: int) -> LatencyStats:
        return self.sketches[i].stats

    @property
    def exact_percentiles(self) -> np.ndarray:  # (C, Q), materialize=True only
        """Exact ``np.percentile`` over materialized latencies — the
        reference the sketch percentiles are pinned against."""
        if self.completions is None:
            raise ValueError("exact percentiles need run_stream(materialize=True)")
        lat = self.completions - self.arrivals
        return np.percentile(lat, self.percentile_qs, axis=1).T

    @property
    def requests_per_sec(self) -> np.ndarray:  # (C,) simulated service rate
        span = np.maximum(self.makespan, 1e-300)
        return np.where(
            self.makespan > 0, self.n_requests / span * self.clock_hz, 0.0
        )


@dataclass(frozen=True)
class SegmentReport:
    """One control interval: the re-allocation charged on entry + volume."""

    start: float  # cycles (0.0 for the first segment)
    n_requests: int
    arrays_added: np.ndarray  # (C,) eNVM arrays reprogrammed at entry
    stall_cycles: np.ndarray  # (C,) fabric freeze charged at entry


@dataclass(frozen=True)
class SegmentedReplayResult:
    """Whole-trace outcome of ``run_trace_segments``.

    ``sketches`` accumulate IN-KERNEL across segments (the sketch state is
    scan carry, handed from segment to segment), so they equal the
    unsegmented streaming sketches bit-for-bit when no allocation changes.
    Materializing mode (``stream=False``) also fills ``arrivals`` /
    ``completions`` for exact-percentile validation at test scale."""

    sketches: tuple  # (C,) LatencySketch over the whole trace
    percentile_qs: tuple
    segments: tuple  # (S,) SegmentReport
    makespan: np.ndarray  # (C,)
    n_requests: int
    clock_hz: float = CLOCK_HZ
    arrivals: np.ndarray | None = None  # (C, N) stream=False only
    completions: np.ndarray | None = None  # (C, N) stream=False only

    @property
    def percentiles(self) -> np.ndarray:  # (C, Q)
        return np.stack(
            [s.percentiles(self.percentile_qs) for s in self.sketches]
        )

    def percentile(self, q: float) -> np.ndarray:
        return self.percentiles[:, self.percentile_qs.index(q)]

    @property
    def p99(self) -> np.ndarray:
        return self.percentile(99.0)

    def latency(self, i: int) -> LatencyStats:
        return self.sketches[i].stats

    @property
    def total_stall_cycles(self) -> np.ndarray:  # (C,)
        return np.sum([s.stall_cycles for s in self.segments], axis=0)


# -------------------------------------------------------------- run_stream
def run_stream(
    vt: VirtualTimeFabric,
    allocs,
    proc: ArrivalProcess | list,
    *,
    seed: int = 0,
    engine: str = "jax",
    window: int = 8,
    percentiles: tuple = (50.0, 95.0, 99.0),
    sketch: SketchConfig = SketchConfig(),
    coarsen: CoarsenConfig | None = None,
    placements: list | None = None,
    pad_to: int = 1,
    materialize: bool = False,
) -> FleetResult:
    """Streaming batched replay: ``VirtualTimeFabric.run_batch`` semantics
    with O(lanes + sketch) memory per config and hash-derived service times.

    Service indices come from ``hash_service_indices(seed, layer, request,
    patch)`` rather than the presampled tensors, so results are a different
    (equally valid) draw than ``run_batch(seed=...)`` — the cross-engine pin
    is ``FabricSim(service_sampling="hash")``, which consumes the identical
    hash.  ``window`` blocks the request scan (bit-identical per the vtime
    proof); ``coarsen`` opts into macro-job chunking (documented pessimistic
    bias); percentiles come from the sketch within ``sketch.rel_error``.

    ``materialize`` additionally keeps the full (C, N) arrival/completion
    matrices — the exact-percentile baseline path (O(C x N) memory, what
    the sketch exists to avoid at fleet scale; same hashed service draws).
    """
    if engine not in ("jax", "numpy"):
        raise ValueError(f"engine must be 'jax' or 'numpy', got {engine!r}")
    allocs = list(allocs)
    if not allocs:
        raise ValueError("need at least one allocation")
    if placements is not None and len(placements) != len(allocs):
        raise ValueError(f"{len(placements)} placements for {len(allocs)} allocations")
    procs = proc if isinstance(proc, list) else [proc] * len(allocs)
    if len(procs) != len(allocs):
        raise ValueError(f"{len(procs)} arrival processes for {len(allocs)} allocations")
    closed = isinstance(procs[0], ClosedLoop)
    if any(isinstance(p, ClosedLoop) != closed for p in procs):
        raise ValueError("cannot mix closed- and open-loop processes in one batch")
    if closed:
        concurrency = procs[0].concurrency
        if any(
            p.concurrency != concurrency or p.n_requests != procs[0].n_requests
            for p in procs
        ):
            raise ValueError("closed-loop batch needs identical (n_requests, concurrency)")
        n = procs[0].n_requests
        times = np.zeros((len(allocs), n))
    else:
        concurrency = None
        tlist = [arrival_times(p) for p in procs]
        n = tlist[0].size
        if any(t.size != n for t in tlist):
            raise ValueError("all arrival traces in a batch need the same length")
        times = np.stack(tlist).astype(np.float64) if n else np.zeros((len(allocs), 0))

    c_total = len(allocs)
    sketches: list = [LatencySketch.from_latencies([], sketch)] * c_total
    makespan = np.zeros(c_total)
    arr = comp = None
    if materialize:
        arr, comp = np.zeros((c_total, n)), np.zeros((c_total, n))
    if n:
        for g in vt._groups(allocs, placements):
            state = _stream_group_call(
                vt, g, times[g.rows], concurrency, seed, window, sketch,
                coarsen, engine, pad_to, state=None, r0=0, emit=materialize,
            )
            if materialize:
                state, (t0s, ts) = state
                arr[g.rows], comp[g.rows] = t0s, ts
            _, _, sk, hor = state
            for k, row in enumerate(g.rows):
                sketches[row] = LatencySketch.from_state(
                    sketch, tuple(a[k] for a in sk)
                )
                makespan[row] = hor[k]
    return FleetResult(
        tuple(sketches), tuple(percentiles), makespan, int(n), vt.clock_hz,
        int(window), arrivals=arr, completions=comp,
    )


# ------------------------------------------------------- segmented replay
def segment_growth_plan(
    spec: NetworkSpec,
    prof: NetworkProfile,
    alloc: Allocation,
    budgets,
    *,
    zskip: bool | None = None,
) -> list[Allocation]:
    """Allocation trajectory for ``run_trace_segments``: at each control
    boundary grant ``budgets[s]`` additional arrays to the blocks with the
    highest expected drain time, warm-started from the previous segment's
    replicas via ``greedy_allocate(initial_replicas=...)`` — the controller
    hook named in the ROADMAP.  A NEGATIVE budget shrinks instead (degraded
    capacity after a failure): ``greedy_release`` frees at least ``-b``
    arrays from the blocks whose latency suffers least, the exact inverse
    of the grant rule.  Returns ``len(budgets) + 1`` allocations (the input
    first)."""
    from ..core.alloc.greedy import greedy_allocate, greedy_release

    if alloc.block_dups is None:
        raise ValueError("segment_growth_plan requires a block-wise allocation")
    if zskip is None:
        zskip = alloc.policy != "baseline"
    cyc = _layer_patch_cycles(prof, zskip)
    base_lat, cost = blockwise_units(spec, [c.mean(axis=0) for c in cyc])
    cur = np.concatenate(
        [np.asarray(d, dtype=np.int64) for d in alloc.block_dups]
    )
    used, total = int(alloc.arrays_used), int(alloc.arrays_total)
    out = [alloc]
    for b in budgets:
        if float(b) < 0:
            res = greedy_release(base_lat, cost, -float(b), replicas=cur)
        else:
            res = greedy_allocate(base_lat, cost, float(b), initial_replicas=cur)
        cur = res.replicas
        used += int(round(res.spent))
        out.append(
            Allocation(
                alloc.policy, None, split_block_dups(spec, cur), used,
                max(total, used),
            )
        )
    return out


def _segment_pack(vt: VirtualTimeFabric, segs):
    """One group for ALL segments: stages from the profile, lane count per
    layer = max over segments (lane_quantum-rounded) so every segment shares
    one compiled kernel shape.  Returns (group for segment 0, per-segment
    per-layer (C, B) dup arrays)."""
    zskip = segs[0][0].policy != "baseline"
    stages, _ = _pack_group(
        vt.spec, vt._cyc[zskip], False, segs[0], lane_quantum=vt.lane_quantum
    )
    n_layers = len(vt.spec.layers)
    dups = [
        [
            np.stack([np.asarray(a.block_dups[li], dtype=np.int64) for a in seg])
            for li in range(n_layers)
        ]
        for seg in segs
    ]  # (S)(L)(C, B)
    q = max(1, int(vt.lane_quantum))
    frees0 = []
    for li in range(n_layers):
        d_max = max(int(d[li].max()) for d in dups)
        d_lanes = -(-d_max // q) * q
        frees0.append(
            np.where(np.arange(d_lanes) < dups[0][li][:, :, None], 0.0, np.inf)
        )
    g = _GroupPack(
        np.arange(len(segs[0])), False, zskip, stages, tuple(frees0), None
    )
    return g, dups


def _apply_boundary(frees, dups_old, dups_new, arrays_added, t_free):
    """Event-engine seam semantics on packed lanes: for configs that
    reprogram (``arrays_added > 0``, positive dup diffs only) every existing
    lane freezes until ``t_free`` (= boundary + stall) and the grown lanes
    come online at ``t_free`` — exactly ``FabricSim.apply_growth``.  Blocks
    that SHRINK (failures: survivors < previous replicas) lose their
    latest-free lanes — sorted positions ``[dups_new, dups_old)`` hold the
    largest finite free-times, and setting them to ``+inf`` is the existing
    absent-server convention; ``ServerPool.kill`` removes the same multiset
    on the event side.  Unchanged configs pass through untouched (a
    zero-change boundary is a no-op)."""
    hit = arrays_added > 0
    out = []
    for li, f in enumerate(frees):
        lanes = np.array(f)  # (C, B, D) sorted ascending, inf = absent
        clamp = hit[:, None, None] & np.isfinite(lanes)
        lanes = np.where(clamp, np.maximum(lanes, t_free[:, None, None]), lanes)
        d = np.arange(lanes.shape[-1])
        grow = (d >= dups_old[li][:, :, None]) & (d < dups_new[li][:, :, None])
        lanes = np.where(grow, t_free[:, None, None], lanes)
        dead = (d >= dups_new[li][:, :, None]) & (d < dups_old[li][:, :, None])
        lanes = np.where(dead, np.inf, lanes)
        out.append(np.sort(lanes, axis=-1))
    return tuple(out)


def run_trace_segments(
    vt: VirtualTimeFabric,
    allocs_by_segment,
    proc: ArrivalProcess | np.ndarray,
    boundaries,
    *,
    drift: DriftConfig = DriftConfig(),
    seed: int = 0,
    engine: str = "jax",
    window: int = 8,
    percentiles: tuple = (50.0, 95.0, 99.0),
    sketch: SketchConfig = SketchConfig(),
    coarsen: CoarsenConfig | None = None,
    stream: bool = True,
    pad_to: int = 4096,
) -> SegmentedReplayResult:
    """Segmented warm-start replay of one long open-loop trace.

    The trace is split at ``boundaries`` (cycles, nondecreasing); segment
    ``s`` runs under ``allocs_by_segment[s]`` (one ``Allocation`` or a
    C-list per segment), with free-lane state carried across boundaries and
    each config's reprogramming stall — ``drift.stall(arrays_added)``, from
    net-NEW replicas only — charged to every lane at entry.  Allocations may
    grow or shrink at a seam: shrinking a block kills its latest-free lanes
    (``+inf``, the absent-server convention), which is how seeded failure
    traces replay here (``fabric.failures.degrade_plan`` /
    ``run_trace_failures``); a shrink-to-identical plan stays bit-identical
    to the unsegmented replay.

    ``stream=True`` (default) keeps sketch + lane state in-carry and pads
    segments to ``pad_to`` requests so all segments share compiled kernels;
    with identical allocations and zero stalls it is bit-identical to the
    unsegmented ``run_stream``.  ``stream=False`` materializes per-request
    completions (presampled service draws, exactly ``run_batch``'s) for
    validation at test scale — identical allocations reproduce
    ``run_batch`` completions bit-for-bit.
    """
    if engine not in ("jax", "numpy"):
        raise ValueError(f"engine must be 'jax' or 'numpy', got {engine!r}")
    if isinstance(proc, ClosedLoop):
        raise ValueError("segmented replay is open-loop only (trace/Poisson arrivals)")
    times = (
        np.asarray(proc, dtype=np.float64)
        if isinstance(proc, np.ndarray)
        else arrival_times(proc)
    )
    bounds = np.asarray(boundaries, dtype=np.float64)
    if bounds.ndim != 1:
        raise ValueError("boundaries must be a 1-D sequence of cycle times")
    if bounds.size and np.any(np.diff(bounds) < 0):
        raise ValueError("boundaries must be nondecreasing")
    segs = [
        list(seg) if isinstance(seg, (list, tuple)) else [seg]
        for seg in allocs_by_segment
    ]
    n_seg = len(segs)
    if n_seg != bounds.size + 1:
        raise ValueError(
            f"{n_seg} segment allocations need {n_seg - 1} boundaries, got {bounds.size}"
        )
    c_total = len(segs[0])
    if any(len(seg) != c_total for seg in segs):
        raise ValueError("every segment needs the same number of allocations")
    zskip = segs[0][0].policy != "baseline"
    for seg in segs:
        for a in seg:
            if a.block_dups is None:
                raise ValueError("segmented replay requires block-wise allocations")
            if (a.policy != "baseline") != zskip:
                raise ValueError("all segment allocations must share zero-skipping")

    g, dups = _segment_pack(vt, segs)
    n_layers = len(vt.spec.layers)
    widths = np.asarray(
        [vt.spec.layers[li].arrays_per_block for li in range(n_layers)],
        dtype=np.int64,
    )
    added = np.zeros((n_seg, c_total), dtype=np.int64)
    for s in range(1, n_seg):
        for li in range(n_layers):
            diff = dups[s][li] - dups[s - 1][li]  # (C, B)
            # positive diffs only: shrunk lanes (failures) lose their
            # replica without reprogramming anything, so only net-new
            # replicas charge the drift stall
            added[s] += np.maximum(diff, 0).sum(axis=1) * widths[li]
    stalls = np.zeros((n_seg, c_total))
    for s in range(1, n_seg):
        stalls[s] = [
            drift.stall(int(a)) if a > 0 else 0.0 for a in added[s]
        ]

    n = times.size
    cuts = np.searchsorted(times, bounds, side="left")
    starts = np.concatenate([[0], cuts]).astype(np.int64)
    ends = np.concatenate([cuts, [n]]).astype(np.int64)
    reports = tuple(
        SegmentReport(
            0.0 if s == 0 else float(bounds[s - 1]),
            int(ends[s] - starts[s]),
            added[s].astype(np.float64),
            stalls[s].copy(),
        )
        for s in range(n_seg)
    )

    if stream:
        state = _init_stream_state(g, None, sketch)
        for s in range(n_seg):
            if s:
                frees = _apply_boundary(
                    state[0], dups[s - 1], dups[s], added[s],
                    bounds[s - 1] + stalls[s],
                )
                state = (frees,) + state[1:]
            lo, hi = int(starts[s]), int(ends[s])
            if hi > lo:
                seg_times = np.broadcast_to(times[lo:hi], (c_total, hi - lo))
                state = _stream_group_call(
                    vt, g, seg_times, None, seed, window, sketch, coarsen,
                    engine, pad_to, state=state, r0=lo,
                )
        _, _, sk, hor = state
        sketches = tuple(
            LatencySketch.from_state(sketch, tuple(a[k] for a in sk))
            for k in range(c_total)
        )
        return SegmentedReplayResult(
            sketches, tuple(percentiles), reports, np.asarray(hor), int(n),
            vt.clock_hz,
        )

    # materializing mode: presampled draws (= run_batch's), exact outputs
    dims = [
        (vt._cyc[True][i].shape[0], l.patches_per_image)
        for i, l in enumerate(vt.spec.layers)
    ]
    idx = sample_service_indices(np.random.default_rng(seed), dims, n)
    frees = tuple(np.array(f) for f in g.frees)
    completions = np.zeros((c_total, n))
    for s in range(n_seg):
        if s:
            frees = _apply_boundary(
                frees, dups[s - 1], dups[s], added[s], bounds[s - 1] + stalls[s]
            )
        lo, hi = int(starts[s]), int(ends[s])
        if hi == lo:
            continue
        idx_s = tuple(ix[lo:hi] for ix in idx)
        times_s = times[lo:hi]
        if engine == "jax":
            fn = vt._jax_runner(g, None, hi - lo, window=window, return_state=True)
            with x64():
                out = fn(
                    tuple(to_bits(f) for f in frees), None,
                    to_bits(np.broadcast_to(times_s, (c_total, hi - lo))), idx_s,
                )
            completions[:, lo:hi] = from_bits(out[1])
            frees = tuple(from_bits(f) for f in out[2])
        else:
            new_frees = [np.empty_like(f) for f in frees]
            for k in range(c_total):
                out = run_fabric_kernel(
                    np, _np_scan, g.stages, tuple(f[k] for f in frees),
                    times_s, idx_s, None, window=window, return_state=True,
                )
                completions[k, lo:hi] = out[1]
                for li, f in enumerate(out[2]):
                    new_frees[li][k] = f
            frees = tuple(new_frees)
    arrivals = np.broadcast_to(times, (c_total, n)).copy()
    sketches = tuple(
        LatencySketch.from_latencies(completions[k] - times, sketch)
        for k in range(c_total)
    )
    makespan = completions.max(axis=1) if n else np.zeros(c_total)
    return SegmentedReplayResult(
        sketches, tuple(percentiles), reports, makespan, int(n), vt.clock_hz,
        arrivals=arrivals, completions=completions,
    )


def run_trace_failures(
    vt: VirtualTimeFabric,
    prof: NetworkProfile,
    alloc: Allocation,
    proc: ArrivalProcess | np.ndarray,
    failures,
    *,
    spare_arrays: float = 0.0,
    drift: DriftConfig = DriftConfig(),
    min_survivors: int = 1,
    **kwargs,
) -> SegmentedReplayResult:
    """Replay one trace under a seeded failure trace on the vtime engine.

    ``failures`` is a ``fabric.failures.FailureTrace`` (compiled to a
    ``DegradePlan`` here) or an already-built ``DegradePlan``.  Thin sugar
    over ``degrade_plan`` + ``run_trace_segments``: every failure/repair
    time becomes a segment seam, survivors are re-placed from the
    ``spare_arrays`` hot pool via warm-started greedy, and reprogramming
    stalls are charged in-kernel.  ``FabricSim(failures=plan)`` replays the
    same plan bit-identically (the cross-engine contract)."""
    from .failures import FailureTrace, degrade_plan

    if isinstance(failures, FailureTrace):
        plan = degrade_plan(
            vt.spec, prof, alloc, failures,
            spare_arrays=spare_arrays, drift=drift, min_survivors=min_survivors,
        )
    else:
        plan = failures
    return run_trace_segments(
        vt, list(plan.allocs), proc, plan.boundaries, drift=plan.drift, **kwargs
    )
