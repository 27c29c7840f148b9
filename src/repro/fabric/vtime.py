"""Packed virtual-time fabric kernel: the event engine as array algebra.

``events.py``/``dispatch.py`` simulate the fabric with an explicit event
calendar; this module evaluates the *same* model as a dense virtual-time
recurrence that runs identically in numpy and under ``jit``+``vmap``.

Why that is exact and not an approximation:

  * Pools are work-conserving FIFO and a request's patch jobs enqueue the
    moment it enters a stage, so a later request's jobs always sit behind an
    earlier request's jobs in every pool — *requests cannot overtake each
    other*.  The calendar's time-ordered pops therefore process each stage's
    dispatches in request-index order, and the whole simulation collapses to
    a scan over requests: request r runs through all L stages against pool
    state left by requests 0..r-1.
  * Closed-loop admission keeps the same shape: completions happen in index
    order, so request k arrives exactly when request ``k - concurrency``
    completes — a ring buffer in the scan carry.

Pool state is packed into dense per-layer ``(B, D)`` free-time tensors kept
sorted ascending (``+inf`` marks servers that do not exist): the sorted
lanes ARE the multiset of server free-times, which is all the FIFO
recurrence can observe, so one FIFO job is "pop lane 0, elementwise
sorted-insert of the end time" — pure array algebra with no reductions or
scatters, shared verbatim between the scalar numpy path and the batched jax
path (``lax.scan`` over jobs and requests, ``vmap`` over (allocation,
arrival-trace) pairs, jitted on times held as int64 float64 bit patterns,
``core.precision``, because a TPU only emulates float64).  Both paths
perform bit-for-bit the same IEEE operations as the ``ServerPool`` event
engine, so per-request completion times agree exactly (pinned in
tests/test_fabric_vtime.py).

Service times are presampled request-major (``sample_service_indices``) from
the profiled per-(patch, block) cycle sample; ``FabricSim`` consumes the
same helper in the same order, which is what makes the three paths
bit-identical rather than merely statistically equivalent.
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np

from ..core.cim.network import NetworkSpec
from ..core.cim.profile import NetworkProfile
from ..core.cim.simulate import Allocation, CLOCK_HZ, _layer_patch_cycles
from ..core.precision import add, from_bits, inf, ninf, sub, to_bits, value
from .arrivals import ArrivalProcess, ClosedLoop, PoissonOpen, arrival_times
from .metrics import LatencyStats, latency_stats, percentile_kernel, steady_throughput
from .telemetry import get_telemetry

__all__ = [
    "CoarsenConfig",
    "chunk_plan",
    "dispatch_step",
    "hash_service_indices",
    "pool_dispatch",
    "pool_dispatch_stream",
    "sample_service_indices",
    "VTResult",
    "VirtualTimeFabric",
    "provision_latency_aware",
    "refine_latency_aware",
]


# ------------------------------------------------------------ shared kernel
def dispatch_step(xp, free, svc):
    """One FIFO job per pool onto its earliest-free server.

    ``free``: (..., D) server free-times kept SORTED ascending (``+inf`` =
    absent server); ``svc``: (...,) the job's service time.  Because the
    lanes hold the sorted *multiset* of free-times — which is all the FIFO
    recurrence can observe — the earliest-free server is lane 0, and the
    update is an elementwise sorted-insert of the job's end time:

        r_i = min(max(u_{i-1}, v), u_i),   u = remaining lanes (+/-inf edges)

    No reductions, no scatter: the step is pure elementwise algebra, and it
    performs bit-for-bit the same IEEE add (start + svc) as the event
    engine's ``ServerPool``, whose completion times depend only on the same
    multiset.  Times are float64 or their int64 bit patterns
    (``core.precision``).  Returns (free', end).
    """
    end = add(xp, free[..., 0], svc)
    up = xp.concatenate([free[..., 1:], xp.full_like(free[..., :1], inf(free))], axis=-1)
    free = xp.minimum(xp.maximum(free, end[..., None]), up)
    return free, end


def pool_dispatch(xp, scan, free, t_ready, svc, b_mask, collect=False):
    """FIFO-dispatch a batch of jobs, all ready at ``t_ready``.

    ``free``: (B, D) per-pool server free-times; ``svc``: (P, B) one job per
    pool per row; ``b_mask``: (B,) valid pools.  Returns (free', done) with
    ``done`` = completion of the batch (max end over valid pools, at least
    ``t_ready``) — exactly ``ServerPool.dispatch`` batched over pools.

    Clamping every server to ``t_ready`` up front is equivalent to the event
    engine's per-job ``max(avail, t)``: dispatch times per pool are
    nondecreasing, so a stored pre-clamp value below ``t_ready`` can never
    matter again, and the sorted multiset of free-times (which is all the
    FIFO recurrence sees) evolves identically.

    ``collect=True`` additionally returns (busy, wait) for this batch: busy
    = total service cycles dispatched, wait = total queue-wait (job start -
    ``t_ready``).  A job's start is read off lane 0 AFTER the clamp and
    BEFORE the sorted-insert — the same quantity the event engine's
    ``max(avail_i, t_ready)`` yields — so the telemetry path performs the
    identical IEEE ops on ``free``/``done`` and cannot perturb results.
    """
    free = xp.maximum(free, t_ready)
    if not collect:

        def job(free, svc_p):
            return dispatch_step(xp, free, svc_p)

        free, ends = scan(job, free, svc)  # (P, B) per-job completion times
        done = xp.maximum(xp.where(b_mask, ends, ninf(ends)).max(), t_ready)
        return free, done

    def job(state, svc_p):
        free, acc = state
        start = free[..., 0]  # earliest-free lane = this job's start time
        free, end = dispatch_step(xp, free, svc_p)
        # accumulate queue wait in the carry (a 0-d scalar) rather than
        # emitting a second (B,) scan output: the collect kernel then adds
        # one fused reduction per job instead of doubling the ys traffic
        acc = acc + xp.where(b_mask, value(xp, sub(xp, start, t_ready)), 0.0).sum()
        return (free, acc), end

    (free, wait), ends = scan(job, (free, xp.zeros(())), svc)
    done = xp.maximum(xp.where(b_mask, ends, ninf(ends)).max(), t_ready)
    busy = xp.where(b_mask, value(xp, svc), 0.0).sum()
    return free, done, busy, wait


def pool_dispatch_stream(xp, scan, free, t_ready, svc, b_mask):
    """Carry-max variant of ``pool_dispatch``: accumulate the batch's
    completion as a running max in the scan carry instead of emitting a
    (P, B) per-job end matrix.  Float max is associative and commutative
    (no NaNs here), so folding the ends one job at a time — seeded with
    ``t_ready`` — produces bit-for-bit the same ``done`` as the
    materializing reduction; the lane updates are untouched.  This is what
    lets the fleet streaming kernel keep O(lanes) state per scan step
    regardless of trace length."""
    free = xp.maximum(free, t_ready)

    def job(state, svc_p):
        f, acc = state
        f, end = dispatch_step(xp, f, svc_p)
        acc = xp.maximum(acc, xp.where(b_mask, end, ninf(end)).max())
        return (f, acc), None

    (free, done), _ = scan(job, (free, t_ready), svc)
    return free, done


# ---------------------------------------------------- macro-job coarsening
@dataclass(frozen=True)
class CoarsenConfig:
    """Opt-in approximation: aggregate a stage's bulk patch jobs into
    macro-jobs of K patches (service times summed per pool), keeping the
    last ``tail_lanes * D`` jobs exact per-patch so end-of-stage lane
    balancing — which sets the next stage's start — is preserved.

    The kernel is work-bound at one scan step per job, so chunking the bulk
    is the honest wall-time lever: measured on VGG11 (single core),
    ``granularity=1, tail_lanes=3`` is 2.7x with ~0.3% positive (pessimistic)
    p50/p95/p99 bias and ``tail_lanes=2`` is 3.2x at ~2%.  Default off —
    every exactness-pinned path passes ``coarsen=None``.
    """

    granularity: float = 1.0  # target macro-jobs per lane in the bulk
    tail_lanes: int = 3  # exact per-patch jobs kept at stage end, x lanes
    k_max: int = 32  # macro-job size ceiling


def chunk_plan(n_patches: int, n_lanes: int, cfg: CoarsenConfig | None) -> tuple:
    """Static (K, n_bulk) macro-job plan for one stage; (1, 0) means exact.

    K is chosen so the bulk leaves ~``granularity * n_lanes`` macro-jobs
    (enough to keep every lane fed), capped at ``k_max``; the plan degrades
    to exact whenever the stage is too small to leave >= 2 bulk chunks."""
    if cfg is None:
        return (1, 0)
    target = max(1, int(round(cfg.granularity * n_lanes)))
    k = max(1, min(int(cfg.k_max), int(n_patches) // target))
    tail = min(int(n_patches), int(cfg.tail_lanes) * int(n_lanes))
    nb = max(0, (int(n_patches) - tail) // k)
    if k == 1 or nb < 2:
        return (1, 0)
    return (k, nb)


def _chunk_services(xp, svc, plan):
    """Aggregate (P, B) per-patch services into the planned macro-jobs.

    The K-way sum is an explicit left fold so numpy and jit accumulate in
    the identical order (library ``sum`` reduction trees differ)."""
    k, nb = plan
    if nb == 0:
        return svc
    head = svc[: nb * k].reshape((nb, k) + svc.shape[1:])
    acc = head[:, 0]
    for j in range(1, k):
        acc = add(xp, acc, head[:, j])
    return xp.concatenate([acc, svc[nb * k :]], axis=0)


def _request_step(xp, job_scan, stages, xfer, concurrency, collect, carry, inp):
    """Run one request through every stage against the carried pool state.

    ``stages``: sequence of (cycles (S, B), b_mask (B,)) per layer;
    ``xfer``: (L,) per-stage entry transfer delay (multi-chip placement), or
    None for the flat fabric — when present, the request's clock advances by
    ``xfer[l]`` before stage ``l`` dispatches, the identical IEEE add the
    event engine performs in ``FabricSim._dispatch_stage``;
    ``carry``: (per-layer free tensors, completion ring buffer);
    ``inp``: (request index, open-loop arrival time, per-layer (P,) sample
    indices).  Closed loop (``concurrency`` not None) reads the arrival from
    the ring: request r enters when request r - concurrency completed (slots
    before the first wrap hold the 0.0 init = the initial admissions).

    ``collect=True`` carries two extra per-layer tuples of 0-d accumulators
    (busy, wait) through the scan — the jit path's utilization/duty-cycle
    telemetry, emitted by the same single jit call as the completions.
    """
    if collect:
        frees, ring, busy, wait = carry
    else:
        frees, ring = carry
    r, t_arr, idx = inp
    if concurrency is None:
        t = t_arr
    else:
        pos = r % concurrency
        t = ring[pos]
    t0 = t
    new_frees = []
    for li, ((cycles, b_mask), free, ix) in enumerate(zip(stages, frees, idx)):
        if xfer is not None:
            t = add(xp, t, xfer[li])
        svc = cycles[ix]  # (P, B) this request's sampled per-block cycles
        if collect:
            free, t, b_l, w_l = pool_dispatch(
                xp, job_scan, free, t, svc, b_mask, collect=True
            )
            busy = busy[:li] + (busy[li] + b_l,) + busy[li + 1 :]
            wait = wait[:li] + (wait[li] + w_l,) + wait[li + 1 :]
        else:
            free, t = pool_dispatch(xp, job_scan, free, t, svc, b_mask)
        new_frees.append(free)
    if concurrency is not None:
        ring = xp.where(xp.arange(ring.shape[0]) == pos, t, ring)
    if collect:
        return (tuple(new_frees), ring, busy, wait), (t0, t)
    return (tuple(new_frees), ring), (t0, t)


def _tree_blocks(xs, nb, w):
    """Reshape each leaf (N, ...) -> (nb, w, ...) over the first nb*w rows."""
    if isinstance(xs, tuple):
        return tuple(_tree_blocks(x, nb, w) for x in xs)
    return xs[: nb * w].reshape((nb, w) + xs.shape[1:])


def _tree_tail(xs, lo):
    if isinstance(xs, tuple):
        return tuple(_tree_tail(x, lo) for x in xs)
    return xs[lo:]


def _scan_windowed(xp, scan, body, carry, xs, n, window):
    """Blocked request scan: ``window`` sequential ``body`` steps per scan
    step, cutting the scan length N -> N/W (+ a W=1 epilogue for the
    remainder).  The block body unrolls the SAME per-request step in the
    same order — only the loop-carried structure changes — so results are
    bit-identical to the W=1 scan for every W (pinned in tests).  Handles
    bodies that emit no ys (the streaming fleet kernel)."""
    w = max(1, min(int(window), n if n else 1))
    nb = n // w if w > 1 else 0
    parts = []
    if nb > 0:

        def block(c, blk):
            ys = []
            for j in range(w):
                c, y = body(c, _tree_index(blk, j))
                ys.append(y)
            if ys[0] is None:
                return c, None
            return c, tuple(
                xp.stack([y[k] for y in ys]) for k in range(len(ys[0]))
            )

        carry, ys = scan(block, carry, _tree_blocks(xs, nb, w))
        if ys is not None:
            # (nb, w, ...) -> (nb * w, ...) restores request-major order
            parts.append(tuple(y.reshape((nb * w,) + y.shape[2:]) for y in ys))
        done = nb * w
    else:
        done = 0
    if done < n:
        carry, ys = scan(body, carry, _tree_tail(xs, done))
        if ys is not None:
            parts.append(ys)
    if not parts:
        return carry, None
    if len(parts) == 1:
        return carry, parts[0]
    return carry, tuple(
        xp.concatenate([p[k] for p in parts]) for k in range(len(parts[0]))
    )


def run_fabric_kernel(
    xp, scan, stages, frees, arrivals, idx, concurrency,
    job_scan=None, xfer=None, collect_stats=False, window=1, return_state=False,
):
    """Whole-run recurrence: scan ``_request_step`` over requests and
    return per-request (arrival, completion) times — one fused computation
    in the jax path, a plain loop in the numpy path.  Latency percentiles
    are taken from them on the host (``VirtualTimeFabric.run_batch``), in
    float64 numpy, on every engine.  ``job_scan`` (defaults to
    ``scan``) drives the inner per-job loop; ``xfer`` is this config's (L,)
    stage transfer vector (or None for the flat fabric).

    ``window`` processes W requests per scan step (``_scan_windowed``),
    exploiting the non-overtaking property to shorten the scan N -> N/W
    bit-identically; the window auto-clamps to the closed-loop concurrency,
    where admission forces request k to wait on request k - concurrency and
    a wider block buys nothing.

    ``collect_stats=True`` returns two extra (L,) vectors — total busy
    (service) cycles and queue-wait cycles per layer, accumulated through
    the scan carry.  They reconcile with the event engine's ``PoolStats``
    counters to float64 summation-order tolerance (scalar ``+=`` there vs.
    ``xp.sum`` here); completions are bit-identical either way.

    ``return_state=True`` appends the final (frees, ring) carry to the
    outputs — the hook segmented replay uses to hand lane state across
    control-interval boundaries.
    """
    n = arrivals.shape[0]
    ring = xp.zeros(concurrency if concurrency is not None else 1, dtype=arrivals.dtype)
    from functools import partial

    body = partial(
        _request_step, xp, job_scan or scan, stages, xfer, concurrency, collect_stats
    )
    if concurrency is not None:
        window = min(int(window), int(concurrency))
    if collect_stats:
        zeros = tuple(xp.zeros(()) for _ in stages)
        carry0 = (frees, ring, zeros, zeros)
    else:
        carry0 = (frees, ring)
    carry, (t_arr, comp) = _scan_windowed(
        xp, scan, body, carry0, (xp.arange(n), arrivals, idx), n, window
    )
    out = (t_arr, comp)
    if collect_stats:
        out = out + (xp.stack(carry[2]), xp.stack(carry[3]))
    if return_state:
        out = out + (carry[0], carry[1])
    return out


def _tree_index(xs, j):
    if isinstance(xs, tuple):
        return tuple(_tree_index(x, j) for x in xs)
    return xs[j]


def _tree_len(xs):
    while isinstance(xs, tuple):
        xs = xs[0]
    return len(xs)


def _np_scan(f, init, xs):
    """``lax.scan`` semantics for numpy: xs is a (possibly nested) tuple of
    arrays sliced along axis 0; ys stacked (or None)."""
    n = _tree_len(xs)
    carry = init
    ys = []
    for j in range(n):
        carry, y = f(carry, _tree_index(xs, j))
        if y is not None:
            ys.append(y)
    if not ys:
        return carry, None
    if isinstance(ys[0], tuple):
        return carry, tuple(np.stack([y[k] for y in ys]) for k in range(len(ys[0])))
    return carry, np.stack(ys)


# --------------------------------------------------------------- packing
def sample_service_indices(rng: np.random.Generator, dims, n_requests: int):
    """Per-layer (N, ppi) sample-row indices, drawn layer-major.

    ``dims`` = [(S_l, ppi_l)] per stage.  Both ``FabricSim`` and the
    virtual-time paths draw through this helper with the same generator
    state, so all engines see identical service times per (request, patch).
    """
    return [
        rng.integers(0, s, size=(int(n_requests), int(ppi))) for s, ppi in dims
    ]


def _hash_salt(seed: int, layer: int) -> int:
    """Per-(seed, layer) salt for ``hash_service_indices`` — plain python
    int, mixed host-side so the kernel hashes only (request, patch)."""
    return (int(seed) * 0x9E3779B9 + (int(layer) + 1) * 0xC2B2AE35) & 0xFFFFFFFF


def hash_service_indices(xp, salt, r, n_patches, n_samples):
    """Counter-based service-sample indices: a splitmix-style uint32 hash of
    (salt, request, patch), evaluated in-kernel.

    Presampling (``sample_service_indices``) materializes per-layer (N, ppi)
    int64 tensors — tens of GB at fleet scale (10^6 requests x ~1.5k patches)
    — so the streaming replay derives each request's indices on the fly
    instead.  Pure uint32 array arithmetic (multiply/xor/shift wrap
    identically under numpy and jit), so every engine sees the same indices:
    ``r`` may be a traced scalar (one request inside the scan) or an (N,)
    vector (``FabricSim``'s vectorized draw); the result broadcasts to
    ``r.shape + (n_patches,)``.  The final modulo is bias-free whenever
    ``n_samples`` is a power of two (the profiler's sample counts are) and
    biased by < n_samples/2^32 otherwise.
    """
    u = xp.uint32
    r32 = xp.asarray(r).astype(u)[..., None]
    p = xp.arange(n_patches, dtype=u)
    h = (p + u(1)) * u(0x9E3779B9)
    h = h + (r32 + u(1)) * u(0x85EBCA6B) + u(salt)
    h = h ^ (h >> 16)
    h = h * u(0x7FEB352D)
    h = h ^ (h >> 15)
    h = h * u(0x846CA68B)
    h = h ^ (h >> 16)
    return (h % u(n_samples)).astype(xp.int32)


@dataclass(frozen=True)
class _GroupPack:
    """One homogeneous (dataflow, zskip) sub-batch of allocations."""

    rows: np.ndarray  # (C,) indices into the caller's allocation list
    layerwise: bool
    zskip: bool
    stages: tuple  # per layer (cycles (S, B) float64, b_mask (B,) bool)
    frees: tuple  # per layer (C, B, D) float64 initial free-times
    xfer: np.ndarray | None = None  # (C, L) per-stage entry transfers


def _pack_group(
    spec: NetworkSpec, cyc, layerwise: bool, allocs, lane_quantum: int = 1
) -> tuple:
    """Dense per-layer (cycles, b_mask) + per-config (C, B, D) free tensors.

    ``lane_quantum`` rounds each layer's lane count D up to a multiple, so
    callers that re-pack slowly-growing allocations (the oracle refinement
    loop) keep stable shapes and reuse compiled kernels."""
    stages, frees = [], []
    for i, layer in enumerate(spec.layers):
        if layerwise:
            cycles = cyc[i].max(axis=1, keepdims=True)  # (S, 1) barrier
            b_mask = np.ones(1, dtype=bool)
            dups = np.asarray(
                [int(a.layer_dups[i]) for a in allocs], dtype=np.int64
            )[:, None]  # (C, 1)
        else:
            cycles = cyc[i]  # (S, B)
            b_mask = np.ones(layer.n_blocks, dtype=bool)
            dups = np.stack(
                [np.asarray(a.block_dups[i], dtype=np.int64) for a in allocs]
            )  # (C, B)
        q = max(1, int(lane_quantum))
        D = -(-int(dups.max()) // q) * q
        free = np.where(
            np.arange(D) < dups[:, :, None], 0.0, np.inf
        )  # (C, B, D)
        stages.append((np.ascontiguousarray(cycles, dtype=np.float64), b_mask))
        frees.append(free)
    return tuple(stages), tuple(frees)


def _split_by_padded_cost(spec, allocs, rows, layerwise) -> list[list[int]]:
    """Partition same-shape configs so lane padding stays bounded.

    The dense (C, B, D) free tensors pad every config to the sub-batch max
    lanes per layer, so one heavily-replicated allocation (a low-load
    latency-aware reshape, say) would inflate the scan cost of the whole
    batch.  Greedily chain configs in order of their own padded cost and cut
    a new sub-group when a config is more than 1.5x the sub-group's first —
    bounding the padding waste at ~1.5x for a few extra jit calls.
    """

    def padded_cost(a):
        # per-job scan work: patches (scan steps) x lanes touched per step
        if layerwise:
            return float(
                sum(
                    l.patches_per_image * int(a.layer_dups[i])
                    for i, l in enumerate(spec.layers)
                )
            )
        return float(
            sum(
                l.patches_per_image * l.n_blocks * int(np.max(a.block_dups[i]))
                for i, l in enumerate(spec.layers)
            )
        )

    costs = {j: padded_cost(allocs[j]) for j in rows}
    order = sorted(rows, key=lambda j: costs[j])
    subs: list[list[int]] = []
    for j in order:
        if subs and costs[j] <= 1.5 * max(costs[subs[-1][0]], 1.0):
            subs[-1].append(j)
        else:
            subs.append([j])
    return subs


# ----------------------------------------------------------------- results
@dataclass(frozen=True)
class VTResult:
    """Structure-of-arrays fabric outcome for C (allocation, trace) pairs."""

    arrivals: np.ndarray  # (C, N) cycles
    completions: np.ndarray  # (C, N) cycles
    percentiles: np.ndarray  # (C, P) latency percentiles, cycles
    percentile_qs: tuple  # the P percentile levels
    clock_hz: float = CLOCK_HZ
    # telemetry (run_batch(collect_stats=True) only): per-layer service and
    # queue-wait job-cycles accumulated inside the kernel's scan carry —
    # reconcile with FabricSim(stats=True)'s PoolStats at rtol 1e-9
    layer_busy: np.ndarray | None = None  # (C, L)
    layer_wait: np.ndarray | None = None  # (C, L)

    def __len__(self) -> int:
        return self.completions.shape[0]

    @property
    def latencies(self) -> np.ndarray:  # (C, N)
        return self.completions - self.arrivals

    def percentile(self, q: float) -> np.ndarray:  # (C,)
        return self.percentiles[:, self.percentile_qs.index(q)]

    @property
    def p99(self) -> np.ndarray:
        return self.percentile(99.0)

    def latency(self, i: int) -> LatencyStats:
        return latency_stats(self.latencies[i])

    def latency_ms(self, i: int) -> LatencyStats:
        return self.latency(i).scaled(1e3 / self.clock_hz)

    @property
    def images_per_sec(self) -> np.ndarray:  # (C,)
        return np.asarray(
            [steady_throughput(c, clock_hz=self.clock_hz) for c in self.completions]
        )


class VirtualTimeFabric:
    """Batched fabric evaluation: one jit call per homogeneous sub-batch
    evaluates per-request completion times and latency percentiles for a
    whole batch of (allocation, arrival-trace) pairs.

    Allocations may mix dataflows/policies; they are grouped internally by
    (layerwise, zero-skipping) since those change the packed tensor shapes.
    ``engine="numpy"`` runs the identical kernel functions with ``xp=numpy``
    (the scalar reference path used by the equivalence suite).
    """

    def __init__(
        self,
        spec: NetworkSpec,
        prof: NetworkProfile,
        *,
        live_prof: NetworkProfile | None = None,
        clock_hz: float = CLOCK_HZ,
        lane_quantum: int = 1,
    ):
        self.spec = spec
        self.prof = prof
        self.live_prof = live_prof
        self.clock_hz = clock_hz
        self.lane_quantum = int(lane_quantum)
        self._cyc = {
            z: _layer_patch_cycles(live_prof or prof, z) for z in (False, True)
        }
        self._compiled: dict[tuple, object] = {}

    # ------------------------------------------------------------- internals
    def _groups(self, allocs, placements=None) -> list[_GroupPack]:
        keys: dict[tuple, list[int]] = {}
        for j, a in enumerate(allocs):
            keys.setdefault((a.layer_dups is not None, a.policy != "baseline"), []).append(j)
        out = []
        for (layerwise, zskip), rows in keys.items():
            for sub in _split_by_padded_cost(self.spec, allocs, rows, layerwise):
                stages, frees = _pack_group(
                    self.spec, self._cyc[zskip], layerwise,
                    [allocs[j] for j in sub],
                    lane_quantum=self.lane_quantum,
                )
                xfer = (
                    None
                    if placements is None
                    else np.ascontiguousarray(
                        np.stack(
                            [
                                np.asarray(
                                    placements[j].stage_transfer, dtype=np.float64
                                )
                                for j in sub
                            ]
                        )
                    )
                )
                out.append(
                    _GroupPack(np.asarray(sub), layerwise, zskip, stages, frees, xfer)
                )
        return out

    def _jax_runner(
        self, g: _GroupPack, concurrency, n, collect=False, window=1,
        return_state=False,
    ):
        """Cached jit(vmap) of the shared kernel for one group structure.
        Times go in and come out as int64 bit patterns (``core.precision``):
        exact float64 arithmetic on every backend, the TPU included."""
        has_xfer = g.xfer is not None
        key = (
            g.layerwise,
            g.zskip,
            concurrency,
            n,
            tuple(f.shape[1:] for f in g.frees),
            has_xfer,
            collect,  # stats-on kernels compile separately (extra outputs)
            window,
            return_state,
        )
        if key not in self._compiled:
            import functools

            import jax
            import jax.numpy as jnp

            np_stages = tuple((to_bits(c), m) for c, m in g.stages)
            job_scan = functools.partial(jax.lax.scan, unroll=1)

            def one(frees, xfer, arrivals, idx):
                # convert the cycle constants INSIDE the traced function:
                # tracing happens under precision.x64(), so the 64-bit values
                # survive (a module-level jnp.asarray would downcast to 32
                # bits and quietly break bit-identity)
                stages = tuple(
                    (jnp.asarray(c), jnp.asarray(m)) for c, m in np_stages
                )
                return run_fabric_kernel(
                    jnp, jax.lax.scan, stages, frees, arrivals, idx,
                    concurrency, job_scan=job_scan, xfer=xfer,
                    collect_stats=collect, window=window,
                    return_state=return_state,
                )

            self._compiled[key] = jax.jit(
                jax.vmap(one, in_axes=(0, 0 if has_xfer else None, 0, None))
            )
        return self._compiled[key]

    # ------------------------------------------------------------------ run
    def run_batch(
        self,
        allocs,
        proc: ArrivalProcess | list,
        *,
        seed: int = 0,
        engine: str = "jax",
        percentiles: tuple = (50.0, 95.0, 99.0),
        placements: list | None = None,
        collect_stats: bool = False,
        window: int = 1,
    ) -> VTResult:
        """Evaluate C allocations against one shared arrival process (or a
        per-allocation list of same-kind processes).  Service times are
        sampled once with ``default_rng(seed)`` — the same draws every
        ``FabricSim(spec, prof, alloc, seed=seed)`` would consume.

        ``placements`` (one ``core.cim.topology.Placement`` per allocation,
        or None for the flat fabric) adds each config's per-stage entry
        transfer delays to the kernel — the multi-chip path, bit-identical
        to ``FabricSim(placement=...)``.

        ``collect_stats=True`` additionally populates ``VTResult.layer_busy``
        / ``layer_wait`` (C, L) from in-kernel accumulators; completion times
        and percentiles are bit-identical with the flag on or off.

        ``window`` blocks the request scan W-at-a-time (bit-identical for
        every W; auto-clamped to the closed-loop concurrency) — the
        fleet-replay scan-length lever, safe to raise on long traces."""
        if engine not in ("jax", "numpy"):
            raise ValueError(f"engine must be 'jax' or 'numpy', got {engine!r}")
        allocs = list(allocs)
        tel = get_telemetry()
        with tel.timed("vt.batch", designs=len(allocs)):
            if not allocs:
                raise ValueError("need at least one allocation")
            if placements is not None and len(placements) != len(allocs):
                raise ValueError(
                    f"{len(placements)} placements for {len(allocs)} allocations"
                )
            procs = proc if isinstance(proc, list) else [proc] * len(allocs)
            if len(procs) != len(allocs):
                raise ValueError(f"{len(procs)} arrival processes for {len(allocs)} allocations")
            closed = isinstance(procs[0], ClosedLoop)
            if any(isinstance(p, ClosedLoop) != closed for p in procs):
                raise ValueError("cannot mix closed- and open-loop processes in one batch")
            with tel.timed("vt.arrivals"):
                if closed:
                    concurrency = procs[0].concurrency
                    if any(p.concurrency != concurrency or p.n_requests != procs[0].n_requests for p in procs):
                        raise ValueError("closed-loop batch needs identical (n_requests, concurrency)")
                    n = procs[0].n_requests
                    times = np.zeros((len(allocs), n))
                else:
                    concurrency = None
                    tlist = [arrival_times(p) for p in procs]
                    n = tlist[0].size
                    if any(t.size != n for t in tlist):
                        raise ValueError("all arrival traces in a batch need the same length")
                    times = np.stack(tlist).astype(np.float64)

            # one draw shared by every group: sampling dims depend only on the
            # profile (S_l, ppi_l), not on dataflow or zero-skipping
            dims = [
                (self._cyc[True][i].shape[0], l.patches_per_image)
                for i, l in enumerate(self.spec.layers)
            ]
            with tel.timed("vt.draws"):
                idx = sample_service_indices(np.random.default_rng(seed), dims, n)

            C = len(allocs)
            L = len(self.spec.layers)
            arrivals = np.zeros((C, n))
            completions = np.zeros((C, n))
            pcts = np.zeros((C, len(percentiles)))
            busy = np.zeros((C, L)) if collect_stats else None
            wait = np.zeros((C, L)) if collect_stats else None
            if n == 0:
                return VTResult(
                    arrivals, completions, pcts, tuple(percentiles), self.clock_hz,
                    layer_busy=busy, layer_wait=wait,
                )
            with tel.timed("vt.pack"):
                groups = self._groups(allocs, placements)
            for g in groups:
                if engine == "jax":
                    from ..core.precision import x64

                    with tel.timed("vt.dispatch"):
                        fn = self._jax_runner(
                            g, concurrency, n, collect=collect_stats, window=window,
                        )
                        with x64():
                            out = fn(
                                tuple(to_bits(f) for f in g.frees),
                                None if g.xfer is None else to_bits(g.xfer),
                                to_bits(times[g.rows]),
                                tuple(idx),
                            )
                    with tel.timed("vt.fetch"):
                        t_arr, comp = from_bits(out[0]), from_bits(out[1])
                        if collect_stats:
                            busy[g.rows] = np.asarray(out[2])
                            wait[g.rows] = np.asarray(out[3])
                else:
                    t_arr = np.zeros((len(g.rows), n))
                    comp = np.zeros((len(g.rows), n))
                    with tel.timed("vt.dispatch"):
                        for k, row in enumerate(g.rows):
                            frees = tuple(f[k].copy() for f in g.frees)
                            out = run_fabric_kernel(
                                np, _np_scan, g.stages, frees, times[row],
                                tuple(idx), concurrency,
                                xfer=None if g.xfer is None else g.xfer[k],
                                collect_stats=collect_stats, window=window,
                            )
                            t_arr[k], comp[k] = out[:2]
                            if collect_stats:
                                busy[row] = np.asarray(out[2])
                                wait[row] = np.asarray(out[3])
                arrivals[g.rows] = t_arr
                completions[g.rows] = comp
                with tel.timed("vt.percentiles"):
                    pcts[g.rows] = [
                        percentile_kernel(np, c - t, percentiles) for t, c in zip(t_arr, comp)
                    ]
            return VTResult(
                arrivals, completions, pcts, tuple(percentiles), self.clock_hz,
                layer_busy=busy, layer_wait=wait,
            )


# ------------------------------------------------- fabric-oracle refinement
def provision_latency_aware(
    spec: NetworkSpec,
    prof: NetworkProfile,
    n_pes: int,
    *,
    offered_ips: float | None = None,
    load_frac: float = 0.7,
    arrays_per_pe: int | None = None,
    proc: ArrivalProcess | list | None = None,
    calib_requests: int = 250,
    calib_seeds: tuple = (101, 211),
    margin: float = 0.02,
    grants: int = 8,
    seed: int = 0,
    percentile: float = 99.0,
    engine: str = "jax",
    vt: "VirtualTimeFabric | None" = None,
) -> Allocation:
    """Serving-oriented allocation: provision a fabric for traffic, not peak.

    The full latency-aware flow the analytic pieces plug into:

      1. build the paper's throughput allocation (``blockwise``) and the
         tail-weighted analytic allocation (``latency_aware`` =
         ``queueing_allocate``) at the same PE budget;
      2. measure both on a calibration workload with ONE batched
         virtual-time call per trace (``proc``, defaulting to open-loop
         Poisson traces at the offered load) and keep the measured-p99
         winner — the analytic model reshapes the fabric only where the
         measurement agrees it pays by more than ``margin`` (typically at
         low load, where bottleneck headroom the traffic does not need can
         buy a shorter request path; near saturation the paper's
         utilization-equalizing shape is already tail-near-optimal and
         wins the calibration);
      3. spend any arrays the winner's greedy left stranded with the
         fabric-oracle (``refine_latency_aware``).

    Returns a block-wise ``Allocation`` with policy ``latency_aware``.
    """
    from ..core.cim.simulate import ARRAYS_PER_PE, allocate, simulate

    app = ARRAYS_PER_PE if arrays_per_pe is None else arrays_per_pe
    bw = allocate(spec, prof, "blockwise", n_pes, app)
    if offered_ips is None:
        offered_ips = load_frac * simulate(spec, prof, bw).images_per_sec
    la = allocate(
        spec, prof, "latency_aware", n_pes, app, offered_ips=offered_ips
    )
    if proc is None:
        rate = float(offered_ips) / CLOCK_HZ
        procs = [
            PoissonOpen(int(calib_requests), rate, seed=s) for s in calib_seeds
        ]
    else:
        procs = proc if isinstance(proc, list) else [proc]
    if vt is None:
        vt = VirtualTimeFabric(spec, prof, lane_quantum=8)
    cands = [
        Allocation("latency_aware", None, bw.block_dups, bw.arrays_used, bw.arrays_total),
        la,
    ]
    p = np.zeros(len(cands))
    for k, pr in enumerate(procs):
        res = vt.run_batch(cands, pr, seed=seed + k, engine=engine, percentiles=(percentile,))
        p += res.percentiles[:, 0]
    # deviate from the throughput shape only on a decisive calibration win
    best = la if p[1] < p[0] * (1.0 - margin) else cands[0]
    if grants > 0 and best.arrays_total - best.arrays_used > 0:
        best = refine_latency_aware(
            spec, prof, best, procs, grants=grants, seed=seed,
            percentile=percentile, engine=engine, vt=vt,
        )
    return best


def refine_latency_aware(
    spec: NetworkSpec,
    prof: NetworkProfile,
    alloc: Allocation,
    proc: ArrivalProcess,
    *,
    grants: int = 16,
    candidates: int = 24,
    seed: int = 0,
    percentile: float = 99.0,
    engine: str = "jax",
    vt: "VirtualTimeFabric | None" = None,
) -> Allocation:
    """Greedy fabric-oracle refinement of a block-wise allocation.

    Each round evaluates, in ONE batched virtual-time call, the current
    allocation plus the ``candidates`` most promising affordable +1-replica
    moves (shortlisted by analytic marginal drain reduction per array), and
    grants the block with the best *measured* p``percentile`` reduction per
    array on the calibration workload ``proc``.  Stops after ``grants``
    rounds, when nothing is affordable, or when no candidate improves the
    tail.  This is the exact, expensive counterpart of the analytic
    queueing score inside the ``latency_aware`` allocator
    (``core.alloc.greedy.queueing_allocate``): the analytic path provisions
    the bulk, the oracle spends the last few replicas on the measured tail.
    """
    if alloc.block_dups is None:
        raise ValueError("fabric-oracle refinement requires a block-wise allocation")
    procs = proc if isinstance(proc, list) else [proc]
    # lane_quantum keeps packed shapes stable while replica counts creep up,
    # so the refinement loop reuses one compiled kernel per boundary; a
    # caller that already holds a warm VirtualTimeFabric passes it in
    if vt is None:
        vt = VirtualTimeFabric(spec, prof, lane_quantum=8)
    table = spec.block_table()  # (n_blocks, 3): layer, block-in-layer, width
    cost = table[:, 2].astype(np.int64)
    cyc = _layer_patch_cycles(prof, alloc.policy != "baseline")
    base_lat = np.concatenate(
        [c.mean(axis=0) * l.patches_per_image for c, l in zip(cyc, spec.layers)]
    )
    dups = [np.asarray(d, dtype=np.int64).copy() for d in alloc.block_dups]
    used, total = int(alloc.arrays_used), int(alloc.arrays_total)

    def mk(d, arrays_used):
        return Allocation(alloc.policy, None, [x.copy() for x in d], arrays_used, total)

    pq = (percentile,)
    for _ in range(int(grants)):
        budget = total - used
        flat = np.concatenate(dups).astype(np.float64)
        afford = np.flatnonzero(cost <= budget)
        if afford.size == 0:
            break
        # shortlist by analytic marginal drain reduction per array
        marg = (base_lat[afford] / flat[afford] - base_lat[afford] / (flat[afford] + 1)) / cost[afford]
        cand = afford[np.argsort(-marg, kind="stable")[: int(candidates)]]
        batch = [mk(dups, used)]
        for j in cand:
            li, bi = int(table[j, 0]), int(table[j, 1])
            d = [x.copy() for x in dups]
            d[li][bi] += 1
            batch.append(mk(d, used + int(cost[j])))
        # average the measured tail over the calibration traces (a list of
        # procs reduces single-trace overfit); one batched call per trace
        p = np.zeros(len(batch))
        for k, pr in enumerate(procs):
            res = vt.run_batch(batch, pr, seed=seed + k, engine=engine, percentiles=pq)
            p += res.percentiles[:, 0]
        p /= len(procs)
        gain = (p[0] - p[1:]) / cost[cand]
        best = int(np.argmax(gain))
        if gain[best] <= 0:
            break
        j = cand[best]
        li, bi = int(table[j, 0]), int(table[j, 1])
        dups[li][bi] += 1
        used += int(cost[j])
    return mk(dups, used)
