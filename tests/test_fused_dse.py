"""Fused-vs-staged DSE equivalence: the one-jit pipeline (in-graph profile
derivation -> allocation -> evaluation, ``dse/fused.py``) against the
staged path on pinned ResNet18 + VGG11 grids.

The contract (documented in ``dse/fused.py``): DISCRETE columns — replica
tensors, arrays used/total, chip crossings — are EXACTLY equal (both paths
answer the greedy from the same event schedule on bit-equal integer-cycle
inputs).
Float-derived columns — total cycles, throughput, utilization, latency
percentiles — are compared at rtol 1e-12: the staged and fused evaluators
are different XLA programs, and cross-compilation op-fusion can wobble the
last ULP of the rounded mean->multiply->divide chains (observed: 1 config
in 24, ~2e-16 relative; ``busy_sum`` additionally sums rounded means in
backend-chosen order).  1e-12 is four orders looser than that wobble and
tight enough that any real formula drift fails.

Also pinned here: the fused columns against the scalar ``allocate`` +
``simulate`` per config, sharded (``shard_map_batch``) vs plain fused
identity, and the fused pipeline's declared limits (latency_aware rejected,
infeasible budgets rejected).
"""

import functools

import numpy as np
import pytest

from repro.core.cim.cost import DEFAULT_ARRAY, ArrayConfig
from repro.dse import (
    FabricEval,
    allocate_batch,
    chip_grid,
    design_grid,
    get_fused_pipeline,
    run_fused_multichip_sweep,
    run_fused_sweep,
    run_sweep,
)
from repro.dse import fused
from repro.dse.sweep import SweepPoint, get_profiled, run_multichip_sweep

ARRAYS = (DEFAULT_ARRAY, DEFAULT_ARRAY.variant(adc_bits=5))
POLS = ("baseline", "weight_based", "perf_layerwise", "blockwise")
EXACT_COLS = ("arrays_used", "arrays_total")
FLOAT_COLS = ("total_cycles", "images_per_sec", "mean_utilization")
ULP_RTOL = 1e-12


def _assert_equiv(a, b, exact_cols, float_cols, msg=""):
    for col in exact_cols:
        np.testing.assert_array_equal(
            getattr(a, col), getattr(b, col), err_msg=f"{msg}{col}"
        )
    for col in float_cols:
        np.testing.assert_allclose(
            getattr(a, col), getattr(b, col), rtol=ULP_RTOL, atol=0,
            err_msg=f"{msg}{col}",
        )


def _grid(net):
    return design_grid(
        networks=(net,), policies=POLS, pe_multipliers=(1.0, 2.0, 3.5), arrays=ARRAYS
    )


@pytest.fixture(
    scope="module",
    params=["vgg11", pytest.param("resnet18", marks=pytest.mark.slow)],
)
def pair(request):
    """(staged, fused) SweepResult pair on the pinned grid, fabric attached.

    VGG11 runs in the fast tier on every PR; the ResNet18 grid (the one
    that exposed the cross-compilation ULP wobble) rides the nightly slow
    tier with the multichip surface and the sharded-identity check."""
    pts = _grid(request.param)
    fab = FabricEval(load_frac=0.7, n_requests=30, seed=0)
    staged = run_sweep(pts, engine="batch", fabric=fab)
    fused = run_fused_sweep(pts, fabric=fab)
    return staged, fused


def test_analytic_columns_equivalent(pair):
    staged, fused = pair
    _assert_equiv(staged, fused, EXACT_COLS, FLOAT_COLS)


def test_latency_percentiles_equivalent(pair):
    """The fused fabric stage (per-config ADC/zskip/dataflow gathers over
    the in-graph cycle banks) reproduces the staged VirtualTimeFabric's
    percentile columns — same service draws, same arrivals, same scan
    recurrence (ULP tolerance only, see module docstring)."""
    staged, fused = pair
    _assert_equiv(
        staged, fused, (), ("p50_cycles", "p95_cycles", "p99_cycles")
    )


def test_replica_tensors_bit_equal():
    """dups_lb out of the in-graph allocators == allocate_batch's, for every
    policy family (proportional constants, layer greedy, block greedy)."""
    net = "vgg11"
    pts = _grid(net)
    by_arr = {}
    for i, p in enumerate(pts):
        by_arr.setdefault(p.array, []).append(i)
    adcs = tuple(sorted({p.array.adc_bits for p in pts}))
    pipe = get_fused_pipeline(net, DEFAULT_ARRAY, adcs)
    res = pipe(
        np.array([adcs.index(p.array.adc_bits) for p in pts], dtype=np.int32),
        [p.policy for p in pts],
        [p.n_pes for p in pts],
    )
    for arr, rows in by_arr.items():
        spec, prof = get_profiled(net, arr)
        batch = allocate_batch(
            spec, prof, [pts[i].policy for i in rows], [pts[i].n_pes for i in rows]
        )
        fused_dups = res["dups_lb"][rows][:, :, : batch.dups_lb.shape[2]]
        np.testing.assert_array_equal(fused_dups, batch.dups_lb)
        np.testing.assert_array_equal(res["arrays_used"][rows], batch.arrays_used)


@pytest.mark.slow
def test_multichip_load_surface_matches_staged():
    """run_fused_multichip_sweep at K loads matches K staged sweeps column
    for column — the lifted placement x load axis changes the batching,
    not the numbers (discrete columns exact, float columns at ULP rtol)."""
    pts = chip_grid(networks=("vgg11",), chips=(1, 2), link_gbps=(16.0, 64.0))
    loads = (0.5, 0.7)
    kw = dict(n_requests=30, closed_requests=20, concurrency=8, seed=0)
    fused = run_fused_multichip_sweep(pts, load_fracs=loads, **kw)
    assert fused.pcts.shape == (len(pts), len(loads), 3)
    assert fused.n_evaluations == len(pts) * len(loads)
    for k, lf in enumerate(loads):
        staged = run_multichip_sweep(pts, load_frac=lf, **kw)
        np.testing.assert_allclose(
            staged.images_per_sec, fused.images_per_sec, rtol=ULP_RTOL, atol=0
        )
        np.testing.assert_allclose(
            np.stack(
                [staged.p50_cycles, staged.p95_cycles, staged.p99_cycles], axis=1
            ),
            fused.pcts[:, k, :],
            rtol=ULP_RTOL,
            atol=0,
        )
        np.testing.assert_array_equal(staged.n_crossings, fused.n_crossings)
        np.testing.assert_array_equal(
            staged.max_stage_transfer, fused.max_stage_transfer
        )
    rows = fused.rows()
    assert len(rows) == fused.n_evaluations
    assert {r["load_frac"] for r in rows} == set(loads)


@pytest.mark.slow
def test_sharded_fused_identical_to_plain():
    """shard_map_batch routing (padded config axis over local devices) must
    match the unsharded fused pipeline under the same contract."""
    pts = _grid("vgg11")[:11]  # odd count exercises the pad-to-devices path
    plain = run_fused_sweep(pts)
    shard = run_fused_sweep(pts, shard_devices=True)
    _assert_equiv(plain, shard, EXACT_COLS, FLOAT_COLS)


def _packed_grid(net="vgg11", pols=POLS, pes=(300, 557, 800)):
    """(a_idx, policies, n_pes) columns spanning both ADC variants."""
    P, A, N = [], [], []
    for p in pols:
        for a in (0, 1):
            for n in pes:
                P.append(p)
                A.append(a)
                N.append(n)
    return (
        np.array(A, dtype=np.int32),
        np.array(P, dtype=object),
        np.array(N, dtype=np.int64),
    )


# nine budgets: 72 family-"L" rows and 18 family-"B" rows, so chunks of 8,
# 21 and 64 each leave a ragged last chunk in at least one family
_ORACLE_PES = (300, 357, 420, 557, 640, 800, 901, 1024, 1200)


@functools.lru_cache(maxsize=1)
def _scalar_oracle(adcs):
    """Scalar ``allocate`` + ``simulate`` for every row of the oracle grid."""
    from repro.core.cim import allocate, simulate

    a_idx, pols, pes = _packed_grid(
        pols=POLS + ("weight_blockflow",), pes=_ORACLE_PES
    )
    rows = []
    for a, pol, n in zip(a_idx, pols, pes):
        spec, prof = get_profiled(
            "vgg11", DEFAULT_ARRAY.variant(adc_bits=adcs[a])
        )
        alloc = allocate(spec, prof, pol, int(n))
        rows.append((spec, alloc, simulate(spec, prof, alloc, n_images=64)))
    return (a_idx, pols, pes), rows


@pytest.mark.parametrize("chunk", [8, 21, 64])
def test_fused_columns_match_scalar_simulate(chunk):
    """The fused program (the one the chip runs) against the scalar
    ``allocate`` + ``simulate`` per config, through ragged last chunks:
    replicas and arrays used exactly equal, floats within rtol 1e-12."""
    adcs = (6, 8)
    pipe = get_fused_pipeline("vgg11", DEFAULT_ARRAY, adcs)
    (a_idx, pols, pes), rows = _scalar_oracle(adcs)
    got = pipe(a_idx, pols, pes, chunk=chunk, need_dups=True)
    for c, (spec, alloc, sim) in enumerate(rows):
        msg = f"chunk={chunk} row {c} {pols[c]}"
        for li, layer in enumerate(spec.layers):
            want = (
                np.full(layer.n_blocks, alloc.layer_dups[li])
                if alloc.layer_dups is not None
                else alloc.block_dups[li]
            )
            np.testing.assert_array_equal(
                got["dups_lb"][c, li, : layer.n_blocks], want, err_msg=msg
            )
        assert got["arrays_used"][c] == alloc.arrays_used, msg
        assert got["arrays_total"][c] == alloc.arrays_total, msg
        for k, want in (
            ("total_cycles", sim.total_cycles),
            ("images_per_sec", sim.images_per_sec),
            ("layer_utilization", sim.layer_utilization),
        ):
            np.testing.assert_allclose(
                got[k][c], want, rtol=ULP_RTOL, atol=0, err_msg=f"{msg} {k}"
            )


@pytest.mark.parametrize("chunk", [1, 5, 10**6])
def test_chunk_tilings_identical(chunk):
    """chunk=1 (one dispatch per config), a non-divisor tile (pad-repeat
    path), and chunk >= C (single dispatch) must all be element-wise
    IDENTICAL: chunking changes dispatch boundaries, never values."""
    pipe = get_fused_pipeline("vgg11", DEFAULT_ARRAY, (6, 8))
    a_idx, pols, pes = _packed_grid()
    ref = pipe(a_idx, pols, pes, need_dups=True)
    got = pipe(a_idx, pols, pes, need_dups=True, chunk=chunk)
    assert set(ref) == set(got)
    for k in ref:
        np.testing.assert_array_equal(ref[k], got[k], err_msg=f"chunk={chunk} {k}")


def test_chunking_bounds_device_footprint():
    """The peak-memory contract of the streamed sweep: the per-dispatch
    device footprint scales with the TILE, not with C — read back from the
    pipeline's telemetry gauges."""
    from repro.fabric.telemetry import telemetry_session

    pipe = get_fused_pipeline("vgg11", DEFAULT_ARRAY, (6, 8))
    a_idx, pols, pes = _packed_grid()
    C = len(pols)
    n_L = int(np.sum(pols != "blockwise"))
    n_B = C - n_L
    # need_dups=False: one (L, B) replica tensor, the replica operand (at
    # most N), the packed 1+L outputs, sel and layerwise
    per_config = (pipe.L * pipe.B + pipe.N + pipe.L + 3) * 8
    with telemetry_session() as tel:
        pipe(a_idx, pols, pes, chunk=4, need_dups=False)
        snap = tel.snapshot()
    assert snap["gauges"]["dse.fused.chunk_configs"] == 4
    assert snap["gauges"]["dse.fused.chunk_device_bytes"] == 4 * per_config
    assert snap["counters"]["dse.fused.chunks"] == -(-n_L // 4) - (-n_B // 4)
    assert snap["gauges"]["dse.fused.host_out_bytes"] > 0
    with telemetry_session() as tel:
        pipe(a_idx, pols, pes, need_dups=False)  # chunk >= C: one tile/family
        snap_full = tel.snapshot()
    assert snap_full["gauges"]["dse.fused.chunk_configs"] == max(n_L, n_B)
    assert (
        snap_full["gauges"]["dse.fused.chunk_device_bytes"]
        == max(n_L, n_B) * per_config
    )
    assert snap_full["counters"]["dse.fused.chunks"] == 2  # one per family


@pytest.mark.parametrize(
    "chunk, fabric",
    [
        (5, None),
        (10**6, None),
        (32768, FabricEval(load_frac=0.7, n_requests=30, seed=0)),
    ],
    ids=["ragged", "single", "fabric"],
)
def test_sweep_columns_equal_a_full_fetch(chunk, fabric):
    """``run_fused_sweep`` fetches no replicas without a fabric stage; its
    columns still equal, bit for bit, those of a direct call that fetches
    every column, and a fabric stage still gets the replicas its
    percentiles need."""
    from repro.core.cim.simulate import CLOCK_HZ

    pts = _grid("vgg11")
    got = run_fused_sweep(pts, chunk=chunk, fabric=fabric)
    adcs = tuple(sorted({p.array.adc_bits for p in pts}))
    pipe = get_fused_pipeline("vgg11", DEFAULT_ARRAY, adcs)
    a_idx = np.array([adcs.index(p.array.adc_bits) for p in pts], dtype=np.int32)
    ref = pipe(a_idx, [p.policy for p in pts], [p.n_pes for p in pts], need_dups=True)
    want = {k: ref[k] for k in EXACT_COLS + ("total_cycles", "images_per_sec")}
    want["mean_utilization"] = ref["layer_utilization"].mean(axis=1)
    for k, v in want.items():
        np.testing.assert_array_equal(getattr(got, k), v, err_msg=k)
    if fabric is None:
        assert got.p50_cycles is None
        return
    gaps = np.random.default_rng(fabric.seed).exponential(1.0, size=fabric.n_requests)
    rates = fabric.load_frac * ref["images_per_sec"] / CLOCK_HZ
    pcts = pipe.fabric_percentiles(
        a_idx, ref["dups_lb"], ref["layerwise"], ref["zskip"],
        np.cumsum(gaps)[None, :] / rates[:, None], seed=fabric.seed,
    )
    np.testing.assert_array_equal(
        np.stack([got.p50_cycles, got.p95_cycles, got.p99_cycles], axis=1), pcts
    )


def test_need_dups_drops_only_the_replicas():
    """Without ``need_dups`` the replica tensor is absent from the dict
    (never zeros), and every other column equals, element for element, a
    call that fetches it."""
    pipe = get_fused_pipeline("vgg11", DEFAULT_ARRAY, (6, 8))
    a_idx, pols, pes = _packed_grid()
    ref = pipe(a_idx, pols, pes, need_dups=True)
    got = pipe(a_idx, pols, pes, need_dups=False)
    assert set(ref) - set(got) == {"dups_lb"}
    for k in got:
        np.testing.assert_array_equal(got[k], ref[k], err_msg=k)


@pytest.mark.parametrize("need_dups", [False, True])
@pytest.mark.parametrize("fam", ["L", "B"])
def test_chunk_program_packs_its_columns(fam, need_dups):
    """A chunk program returns one (C, 1+L) block of total cycles then
    per-layer utilization, so the host fetches it in one copy, and the
    (C, L, B) replica tensor as a second output only with ``need_dups``."""
    from repro.core.cim.simulate import CLOCK_HZ
    from repro.core.precision import x64

    pipe = get_fused_pipeline("vgg11", DEFAULT_ARRAY, (6, 8))
    fn = pipe._fn(fam, 64, CLOCK_HZ, need_dups)
    c = 3
    width = pipe.L if fam == "L" else pipe.N
    with x64():
        got = fn(np.zeros(c, np.int32), np.zeros(c, bool), np.ones((c, width)))
        assert pipe._fn(fam, 64, CLOCK_HZ, need_dups) is fn
    assert [g.shape for g in got] == [(c, 1 + pipe.L)] + (
        [(c, pipe.L, pipe.B)] if need_dups else []
    )
    assert got[0].dtype == np.float64


def _alloc_call(pes=(557, 300, 800, 300, 1024)):
    """A call mixing every fused policy, both ADC variants and repeated PE
    budgets, with the replica tensor fetched."""
    from repro.fabric.telemetry import telemetry_session

    pipe = get_fused_pipeline("vgg11", DEFAULT_ARRAY, (6, 8))
    a_idx, pols, pes = _packed_grid(pols=POLS + ("weight_blockflow",), pes=pes)
    with telemetry_session() as tel:
        out = pipe(a_idx, pols, pes, need_dups=True)
        snap = tel.snapshot()
    budgets = (pes * pipe.arrays_per_pe - pipe.base_arrays).astype(np.float64)
    return pipe, a_idx, pols, budgets, out, snap


def test_fused_allocation_rows_and_arrays_used():
    """Proportional rows answered once per distinct budget equal
    ``proportional_allocate_batch`` run row by row, and ``arrays_used``
    (from each table's ``spent``) equals the replica vector's own cost."""
    from repro.core.alloc.greedy import proportional_allocate_batch

    pipe, _, pols, budgets, out, _ = _alloc_call()
    dups = out["dups_lb"]
    blockwise = pols == "blockwise"
    for c, pol in enumerate(pols):
        if blockwise[c]:
            r, cost = dups[c, pipe.l_idx, pipe.blk_idx], pipe.cost_blk
        else:
            r, cost = dups[c, :, 0], pipe.layer_arrays
        if fused._KIND[pol] == 0:
            want = proportional_allocate_batch(
                pipe.macs, pipe.layer_arrays, budgets[c : c + 1]
            )
            np.testing.assert_array_equal(r, want.replicas[0], err_msg=f"row {c}")
        assert out["arrays_used"][c] == pipe.base_arrays + ((r - 1) * cost).sum()


def test_alloc_distinct_gauge_counts_each_answer_once():
    """``dse.fused.alloc_distinct`` is the distinct proportional budgets
    plus, per greedy family and ADC variant, the distinct stops."""
    pipe, a_idx, pols, budgets, _, snap = _alloc_call()
    kind = np.array([fused._KIND[p] for p in pols])
    want = np.unique(budgets[kind == 0]).size
    for k in (1, 2):
        rows = kind == k
        for a in np.unique(a_idx[rows]):
            sched = pipe._schedule(k, int(a), float(budgets[rows].max()))
            b = budgets[rows & (a_idx == a)]
            want += np.unique(np.searchsorted(sched.cum_cost, b, "right")).size
    assert want < len(pols)
    assert snap["gauges"]["dse.fused.alloc_distinct"] == want


def _per_point_groups(points):
    """The grouping as a per-point loop: ``_canonical`` on every point, then
    each group's columns by list comprehension.  The reference for
    ``fused._group_points``."""
    groups: dict[tuple, list[int]] = {}
    for i, p in enumerate(points):
        groups.setdefault((p.network, fused._canonical(p.array)), []).append(i)
    out = []
    for (net, arr), rows in groups.items():
        adcs = tuple(sorted({points[i].array.adc_bits for i in rows}))
        a_idx = np.array(
            [adcs.index(points[i].array.adc_bits) for i in rows], dtype=np.int32
        )
        pols = np.array([points[i].policy for i in rows], dtype=object)
        pes = np.array([points[i].n_pes for i in rows], dtype=np.int64)
        out.append((net, arr, adcs, np.asarray(rows), a_idx, pols, pes))
    return out


def _mixed_points(seed=0, pes=(300, 557, 800, 1024)):
    """Two networks; equal arrays built as different objects; arrays that
    differ only in ``adc_bits`` (one group) or in ``noc_hop_cycles`` or
    geometry (groups of their own); in a seeded order."""
    arrays = (
        DEFAULT_ARRAY,
        ArrayConfig(),
        DEFAULT_ARRAY.variant(adc_bits=5),
        ArrayConfig(adc_bits=5),
        ArrayConfig(adc_bits=1),
        DEFAULT_ARRAY.variant(noc_hop_cycles=4),
        DEFAULT_ARRAY.variant(noc_hop_cycles=4, adc_bits=6),
        DEFAULT_ARRAY.variant(rows=256, cols=256, adc_bits=2),
    )
    pts = [
        SweepPoint(net, pol, n, arr)
        for net in ("vgg11", "resnet18")
        for arr in arrays
        for pol in POLS
        for n in pes
    ]
    return [pts[i] for i in np.random.default_rng(seed).permutation(len(pts))]


@pytest.mark.parametrize("seed", [0, 1])
def test_columnar_grouping_matches_per_point_loop(seed):
    """Same groups in the same order, rows ascending, and bit-identical
    ``adcs``/``a_idx``/``pols``/``pes`` for every group."""
    points = _mixed_points(seed)
    want = _per_point_groups(points)
    groups, a_idx, pols, pes, distinct = fused._group_points(points)
    assert distinct == len({(p.network, p.array) for p in points}) == 12
    assert [g[:3] for g in groups] == [w[:3] for w in want]
    assert len(want) == 6  # {vgg11, resnet18} x {default, noc 4, 256 rows}
    for (_, _, _, rows), (net, arr, _, *cols) in zip(groups, want):
        got = (rows, a_idx[rows], pols[rows], pes[rows])
        for g, w in zip(got, cols):
            assert g.dtype == w.dtype, (net, arr)
            np.testing.assert_array_equal(g, w, err_msg=f"{net} {arr}")


def test_canonical_once_per_distinct_array(monkeypatch):
    """``_canonical`` runs once per distinct (network, array) pair of a call,
    the same count in two calls on the same points (no state carried
    across calls), and the ``dse.fused.distinct_arrays`` gauge reads it."""
    from repro.fabric.telemetry import telemetry_session

    calls = []
    canonical = fused._canonical
    monkeypatch.setattr(
        fused, "_canonical", lambda a: calls.append(a) or canonical(a)
    )

    def fake_pipeline(net, arr, adcs, **kw):
        def run(a_idx, pols, pes, **kw):
            n = len(pols)
            return {
                "total_cycles": np.ones(n),
                "images_per_sec": np.ones(n),
                "layer_utilization": np.ones((n, 2)),
                "arrays_used": np.ones(n, np.int64),
                "arrays_total": np.ones(n, np.int64),
            }

        return run

    monkeypatch.setattr(fused, "get_fused_pipeline", fake_pipeline)
    points = _mixed_points(pes=tuple(range(300, 340)))
    distinct = len({(p.network, p.array) for p in points})
    counts, gauges = [], []
    for _ in range(2):
        calls.clear()
        with telemetry_session() as tel:
            fused.run_fused_sweep(points)
            gauges.append(tel.snapshot()["gauges"]["dse.fused.distinct_arrays"])
        counts.append(len(calls))
    assert counts == [distinct, distinct] and distinct < len(points) // 100
    assert gauges == [distinct, distinct]


def test_latency_aware_is_rejected():
    pts = design_grid(
        networks=("vgg11",), policies=("latency_aware",), pe_multipliers=(2.0,)
    )
    with pytest.raises(ValueError, match="latency_aware"):
        run_fused_sweep(pts)


def test_infeasible_budget_is_rejected():
    pipe = get_fused_pipeline("vgg11", DEFAULT_ARRAY, (3,))
    with pytest.raises(ValueError, match="arrays"):
        pipe(np.zeros(1, np.int32), ["blockwise"], [1])


def test_bad_adc_index_is_rejected():
    pipe = get_fused_pipeline("vgg11", DEFAULT_ARRAY, (3,))
    pes = pipe.spec.min_pes()
    with pytest.raises(ValueError, match="a_idx"):
        pipe(np.array([1], np.int32), ["blockwise"], [pes * 2])
