"""Fused greedy allocate + throughput eval — one Pallas grid step per
config block.

The fused DSE pipeline's dense-grid regime evaluates millions of (ADC,
policy, PE-budget) configs against bank statistics that are shared per
variant.  This kernel fuses the whole per-config pipeline — lock-step
greedy water-fill + residual loop, replica scatter, and the throughput/
utilization eval — into a single ``pallas_call``: the grid walks blocks of
configs while the (V, L, B) statistic stacks, the per-variant allocation
bases, and the one-hot unit map stay resident in VMEM across the block
(their ``BlockSpec`` index maps pin them to slot 0), so a block's entire
allocate->eval chain runs without touching HBM between the stages.

Exactness: the allocation phase CALLS ``core.alloc.greedy.
greedy_batch_kernel`` inside the kernel body — plain ``jax.lax`` control
flow, legal in Pallas — so replica counts are bit-identical to the batched
greedy by construction, not by re-derivation (the interpret-mode property
suite pins this against ``greedy_allocate_batch``, warm starts and ties
included).  The eval phase applies the same formulas as
``core.cim.simulate._eval_kernel`` batched over the block; float outputs
agree with the staged path at the fused pipeline's rtol 1e-12 contract.

Both greedy FAMILIES flatten onto one unit axis: perf_layerwise passes
units = layers (the unit map broadcasts a layer's replicas across its
blocks), blockwise passes units = per-block flat units (the map scatters
each unit to its (layer, block) cell); proportional configs ride along
with ``budget = 0`` and their host-precomputed replicas as the warm start
— budget 0 makes the greedy a no-op, so one kernel serves every family.

The kernel runs in interpret mode only (float64, off-TPU).  It does not
compile for a TPU: Mosaic has no 64-bit types, so float64 cannot lower,
and in float32 the in-kernel gathers (``base[aidx]``, ``[sel]``) are
refused — and float32 would break the 1e-12 contract anyway.  On a TPU
``fused_alloc_eval`` raises ``PALLAS_ON_TPU`` instead; the XLA path
(``dse.fused`` ``engine="xla"``) is the one that runs there.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..core.alloc.greedy import greedy_batch_kernel
from ..core.device import interpret_mode

__all__ = ["PALLAS_ON_TPU", "fused_alloc_eval", "fused_alloc_eval_kernel"]

PALLAS_ON_TPU = (
    "engine='pallas' (kernels.fused_alloc_eval) cannot run on a TPU: the "
    "kernel's float64 contract does not lower to Mosaic, which has no 64-bit "
    "types, and in float32 its in-kernel gathers are refused; use "
    "engine='xla', which runs the same allocate+eval on the TPU in float64"
)


def fused_alloc_eval_kernel(
    base_ref,  # (A, N)  per-ADC-variant unit base latencies
    cost_ref,  # (1, N)  cost per extra replica of each unit
    umap_ref,  # (N, L*B) one-hot unit -> (layer, block) replica map
    mean_ref,  # (V, L, B) bank stacks (V = baseline + zskip slots)
    max_ref,  # (V, L, B)
    pmn_ref,  # (V, L)
    pmx_ref,  # (V, L)
    busy_ref,  # (V, L)
    bmask_ref,  # (L, B) bool
    ppi_ref,  # (1, L)
    width_ref,  # (1, L)
    larr_ref,  # (1, L)
    budget_ref,  # (Cb,)  per-config replica budget (0 = warm start is final)
    aidx_ref,  # (Cb,) int32 — variant for the ALLOCATION bases
    sel_ref,  # (Cb,) int32 — bank stack slot for the EVAL
    lw_ref,  # (Cb,) bool — layer-wise barrier dataflow
    r0_ref,  # (Cb, N) warm-start replicas
    t_ref,  # out (Cb,) total cycles
    ips_ref,  # out (Cb,) images/sec
    layer_t_ref,  # out (Cb, L)
    util_ref,  # out (Cb, L)
    r_ref,  # out (Cb, N) replicas
    rem_ref,  # out (Cb,) leftover budget
    *,
    n_images: int,
    clock_hz: float,
):
    base = base_ref[...]
    cost = cost_ref[0]
    r0 = r0_ref[...]
    budget = budget_ref[...]
    cb, n = r0.shape

    # ---- allocate: the batched greedy, verbatim (bit-identical replicas)
    r, rem = greedy_batch_kernel(
        base[aidx_ref[...]], jnp.broadcast_to(cost, (cb, n)), budget, r0
    )

    # ---- scatter: one-hot matmul is exact (one nonzero * 1.0 per cell)
    l, b = bmask_ref.shape
    dups = (1.0 + (r - 1.0) @ umap_ref[...]).reshape(cb, l, b)

    # ---- eval: _eval_kernel's formulas, batched over the config block
    sel = sel_ref[...]
    mean_b = mean_ref[...][sel]
    max_b = max_ref[...][sel]
    pmn = pmn_ref[...][sel]
    pmx = pmx_ref[...][sel]
    busy = busy_ref[...][sel]
    bmask = bmask_ref[...]
    lw = lw_ref[...]
    p = ppi_ref[0] * n_images
    width = width_ref[0]
    larr = larr_ref[0]
    d_layer = dups[:, :, 0]
    t_lw = jnp.maximum(pmn * p[None, :] / d_layer, pmx)
    per_block = jnp.maximum(mean_b * p[None, :, None] / dups, max_b)
    t_bw = jnp.where(bmask[None], per_block, -jnp.inf).max(axis=-1)
    layer_t = jnp.where(lw[:, None], t_lw, t_bw)
    alive = jnp.where(
        lw[:, None],
        larr[None, :] * d_layer,
        jnp.where(bmask[None], dups * width[None, :, None], 0.0).sum(axis=-1),
    )
    busy_c = busy * p[None, :] * width[None, :]
    t = layer_t.max(axis=-1)
    t_ref[...] = t
    ips_ref[...] = n_images / (t / clock_hz)
    layer_t_ref[...] = layer_t
    util_ref[...] = busy_c / (alive * t[:, None])
    r_ref[...] = r
    rem_ref[...] = rem


def fused_alloc_eval(
    base: jax.Array,  # (A, N)
    cost: jax.Array,  # (N,)
    unit_map: jax.Array,  # (N, L, B) one-hot
    banks: tuple,  # (mean (V,L,B), max (V,L,B), pm_mean (V,L), pm_max (V,L), busy (V,L))
    b_mask: jax.Array,  # (L, B) bool
    ppi: jax.Array,  # (L,)
    width: jax.Array,  # (L,)
    layer_arrays: jax.Array,  # (L,)
    budgets: jax.Array,  # (C,)
    a_idx: jax.Array,  # (C,) int32
    sel: jax.Array,  # (C,) int32
    layerwise: jax.Array,  # (C,) bool
    r0: jax.Array,  # (C, N)
    *,
    n_images: int = 64,
    clock_hz: float = 1e9,
    block_configs: int = 128,
):
    """Run C configs through the fused allocate+eval kernel.

    Returns ``(T, ips, layer_T, util, r, rem)`` with shapes ``(C,)/(C,)/
    (C, L)/(C, L)/(C, N)/(C,)``.  The config axis is padded to a multiple
    of ``block_configs`` by repeating config 0 (one compiled program per
    shape) and truncated on return.  Runs in interpret mode; on a TPU it
    raises ``PALLAS_ON_TPU``.
    """
    if not interpret_mode():
        raise NotImplementedError(PALLAS_ON_TPU)
    mean_b, max_b, pm_mean, pm_max, busy = (jnp.asarray(x) for x in banks)
    base = jnp.asarray(base)
    cost = jnp.atleast_2d(jnp.asarray(cost))  # (1, N)
    v, l, b = mean_b.shape
    a, n = base.shape
    umap = jnp.asarray(unit_map).reshape(n, l * b)
    budgets = jnp.atleast_1d(jnp.asarray(budgets))
    c = budgets.shape[0]
    cb = min(int(block_configs), c)
    pad = (-c) % cb
    fullc = c + pad

    def padded(x):
        x = jnp.atleast_1d(jnp.asarray(x))
        if pad == 0:
            return x
        return jnp.concatenate([x, jnp.broadcast_to(x[:1], (pad,) + x.shape[1:])])

    budgets_p = padded(budgets)
    aidx_p = padded(a_idx).astype(jnp.int32)
    sel_p = padded(sel).astype(jnp.int32)
    lw_p = padded(layerwise).astype(bool)
    r0_p = padded(jnp.broadcast_to(jnp.asarray(r0), (c, n)))
    f = budgets_p.dtype
    ppi2 = jnp.asarray(ppi, f).reshape(1, l)
    width2 = jnp.asarray(width, f).reshape(1, l)
    larr2 = jnp.asarray(layer_arrays, f).reshape(1, l)

    fixed = lambda shape: pl.BlockSpec(shape, lambda i: (0,) * len(shape))
    kernel = functools.partial(
        fused_alloc_eval_kernel, n_images=int(n_images), clock_hz=float(clock_hz)
    )
    outs = pl.pallas_call(
        kernel,
        grid=(fullc // cb,),
        in_specs=[
            fixed((a, n)),
            fixed((1, n)),
            fixed((n, l * b)),
            fixed((v, l, b)),
            fixed((v, l, b)),
            fixed((v, l)),
            fixed((v, l)),
            fixed((v, l)),
            fixed((l, b)),
            fixed((1, l)),
            fixed((1, l)),
            fixed((1, l)),
            pl.BlockSpec((cb,), lambda i: (i,)),
            pl.BlockSpec((cb,), lambda i: (i,)),
            pl.BlockSpec((cb,), lambda i: (i,)),
            pl.BlockSpec((cb,), lambda i: (i,)),
            pl.BlockSpec((cb, n), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((cb,), lambda i: (i,)),
            pl.BlockSpec((cb,), lambda i: (i,)),
            pl.BlockSpec((cb, l), lambda i: (i, 0)),
            pl.BlockSpec((cb, l), lambda i: (i, 0)),
            pl.BlockSpec((cb, n), lambda i: (i, 0)),
            pl.BlockSpec((cb,), lambda i: (i,)),
        ],
        out_shape=(
            jax.ShapeDtypeStruct((fullc,), f),
            jax.ShapeDtypeStruct((fullc,), f),
            jax.ShapeDtypeStruct((fullc, l), f),
            jax.ShapeDtypeStruct((fullc, l), f),
            jax.ShapeDtypeStruct((fullc, n), f),
            jax.ShapeDtypeStruct((fullc,), f),
        ),
        interpret=True,
    )(
        base.astype(f),
        cost.astype(f),
        umap.astype(f),
        mean_b.astype(f),
        max_b.astype(f),
        pm_mean.astype(f),
        pm_max.astype(f),
        busy.astype(f),
        jnp.asarray(b_mask, bool),
        ppi2,
        width2,
        larr2,
        budgets_p,
        aidx_p,
        sel_p,
        lw_p,
        r0_p,
    )
    return tuple(o[:c] for o in outs)
