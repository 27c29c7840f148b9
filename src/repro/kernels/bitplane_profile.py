"""Bit-plane popcount + zero-skip block costing — the profiler's hot loop
as a Pallas kernel.

The CIM profiler (core/cim/profile.py) needs, for every sampled patch and
every crossbar block (a contiguous row slice of the lowered matrix), the
number of '1' bits per input bit-plane and the resulting zero-skip cycle
count ``cycles_per_read * sum_p max(1, ceil(ones_p / rows_per_read))``.
One grid step handles one block: it extracts the 8 bit-planes of a
(S, block_rows) int32 tile with shift-and-mask, reduces each plane over the
row axis (VPU-friendly: the reduced axis is the 128-wide lane dimension for
the default 128-row block), and folds the ceil-div read count on the fly.

Outputs are laid out block-major — ``ones`` as (B, planes, S) and ``cycles``
as (B, 1, S), last dimension S — so writes stay lane-contiguous and every
block's last two dimensions span the whole array, as the TPU's (8, 128)
tiling rule asks; the host-side wrapper transposes back to the profiler's
(S, B) convention.  Like
``zskip_matmul``, the kernel runs under ``interpret=True`` off-TPU (CI
exercises exactly that path).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

__all__ = [
    "bitplane_profile_kernel",
    "bitplane_block_profile",
    "bitplane_profile",
    "bitplane_cycle_bank",
]


def bitplane_profile_kernel(
    q_ref, ones_ref, cyc_ref, *, input_bits: int, rows_per_read: int, cycles_per_read: int
):
    """One block: (1, S, r) int32 quantized patches -> per-plane popcounts
    (1, planes, S) and zskip cycles (1, 1, S)."""
    q = q_ref[0]  # (S, r)
    total = jnp.zeros((q.shape[0],), jnp.int32)
    for p in range(input_bits):
        # plane 0 = MSB, matching np.unpackbits
        ones = jnp.sum((q >> (input_bits - 1 - p)) & 1, axis=1, dtype=jnp.int32)
        ones_ref[0, p, :] = ones
        total += jnp.maximum(1, (ones + rows_per_read - 1) // rows_per_read)
    cyc_ref[0, 0, :] = cycles_per_read * total


@functools.partial(
    jax.jit,
    static_argnames=("input_bits", "rows_per_read", "cycles_per_read", "interpret"),
)
def bitplane_block_profile(
    q_blocks: jax.Array,  # (B, S, r) integer quantized patch rows, one block per slot
    *,
    input_bits: int = 8,
    rows_per_read: int = 8,
    cycles_per_read: int = 8,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Raw kernel entry: returns (ones (B, planes, S) int32, cycles (B, S)
    int32).  Rows beyond a block's true extent must be zero-padded — zero
    rows contribute no '1' bits, exactly like the profiler's short last
    block."""
    assert q_blocks.ndim == 3, q_blocks.shape
    b, s, r = q_blocks.shape
    q_blocks = q_blocks.astype(jnp.int32)
    kernel = functools.partial(
        bitplane_profile_kernel,
        input_bits=input_bits,
        rows_per_read=rows_per_read,
        cycles_per_read=cycles_per_read,
    )
    ones, cyc = pl.pallas_call(
        kernel,
        grid=(b,),
        in_specs=[pl.BlockSpec((1, s, r), lambda i: (i, 0, 0))],
        out_specs=[
            pl.BlockSpec((1, input_bits, s), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, 1, s), lambda i: (i, 0, 0)),
        ],
        out_shape=(
            jax.ShapeDtypeStruct((b, input_bits, s), jnp.int32),
            jax.ShapeDtypeStruct((b, 1, s), jnp.int32),
        ),
        interpret=interpret,
    )(q_blocks)
    return ones, cyc[:, 0, :]


def bitplane_cycle_bank(
    q_blocks: jax.Array,  # (..., S, r) uint8/int blocks, zero-padded rows
    rows_per_read: tuple[int, ...],
    *,
    input_bits: int = 8,
    cycles_per_read: int = 8,
) -> jax.Array:
    """TRACEABLE multi-ADC zero-skip costing: one popcount, A re-costings.

    The fused DSE pipeline's in-graph derivation step: counts '1' bits per
    bit-plane ONCE (shift-and-mask, the same integers as ``np.unpackbits``
    or the Pallas kernel) and re-costs them for every ADC precision in
    ``rows_per_read`` — the whole ADC axis of a sweep from a single shared
    capture, with no host round-trip.  Returns int32 cycles shaped
    ``(A, ..., S)``; padded (all-zero) blocks cost the 1-read floor per
    plane and must be masked by the caller, exactly like the profiler's
    short last block.
    """
    q = q_blocks.astype(jnp.int32)
    ones = jnp.stack(
        [
            ((q >> (input_bits - 1 - p)) & 1).sum(axis=-1, dtype=jnp.int32)
            for p in range(input_bits)
        ],
        axis=-1,
    )  # (..., S, planes), plane 0 = MSB
    banks = [
        cycles_per_read
        * jnp.maximum(1, (ones + rpr - 1) // rpr).sum(axis=-1, dtype=jnp.int32)
        for rpr in rows_per_read
    ]
    return jnp.stack(banks, axis=0)  # (A, ..., S)


def bitplane_profile(
    patches_u8: np.ndarray,  # (S, rows) uint8 quantized word-line inputs
    *,
    block_rows: int,
    rows_per_read: int = 8,
    cycles_per_read: int = 8,
    interpret: bool = False,
) -> tuple[np.ndarray, np.ndarray]:
    """Profiler-facing wrapper: slice a (S, rows) patch matrix into
    ``ceil(rows / block_rows)`` word-line blocks (zero-padding the last) and
    run the kernel.  Returns (ones (S, B, planes) int64, cycles (S, B)
    int64) — bit-identical to ``np.unpackbits`` + ``zskip_cycles`` per row
    slice."""
    patches_u8 = np.asarray(patches_u8)
    if patches_u8.dtype != np.uint8:
        raise TypeError(f"expected uint8, got {patches_u8.dtype}")
    if patches_u8.ndim != 2:
        raise ValueError(f"expected (S, rows), got shape {patches_u8.shape}")
    s, rows = patches_u8.shape
    n_blocks = -(-rows // block_rows)
    padded = np.zeros((s, n_blocks * block_rows), np.uint8)
    padded[:, :rows] = patches_u8
    blocks = np.ascontiguousarray(
        padded.reshape(s, n_blocks, block_rows).transpose(1, 0, 2)
    )
    ones, cyc = bitplane_block_profile(
        jnp.asarray(blocks.astype(np.int32)),
        rows_per_read=rows_per_read,
        cycles_per_read=cycles_per_read,
        interpret=interpret,
    )
    ones = np.asarray(ones).transpose(2, 0, 1).astype(np.int64)  # (S, B, planes)
    cyc = np.asarray(cyc).T.astype(np.int64)  # (S, B)
    return ones, cyc
