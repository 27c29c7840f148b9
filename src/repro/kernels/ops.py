"""Jit'd public wrappers around the Pallas kernels.

Interpret mode follows the backend (``core.device.interpret_mode``): on
when no TPU backs jax, off on a TPU.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from ..core.device import interpret_mode
from .flash_attention import flash_attention as _flash
from .ssd_scan import ssd_chunk as _ssd_chunk
from .zskip_matmul import zskip_matmul as _zskip
from .ref import block_mask_ref

__all__ = ["zskip_matmul_op", "flash_attention_op", "ssd_chunk_op"]


@partial(jax.jit, static_argnames=("bm", "bn", "bk"))
def zskip_matmul_op(a, b, *, bm=128, bn=128, bk=128):
    """Zero-skipping matmul: builds the activation block mask then runs the
    kernel.  The mask build is one cheap reduction over A."""
    mask = block_mask_ref(a, bm, bk)
    return _zskip(a, b, mask, bm=bm, bn=bn, bk=bk, interpret=interpret_mode())


@partial(jax.jit, static_argnames=("causal",))
def flash_attention_op(q, k, v, *, causal=True):
    """q/k/v: (b, s, h, hd) -> (b, s, h, hd); h folded into the grid."""
    b, sq, h, hd = q.shape
    sk = k.shape[1]
    qf = q.transpose(0, 2, 1, 3).reshape(b * h, sq, hd)
    kf = k.transpose(0, 2, 1, 3).reshape(b * h, sk, hd)
    vf = v.transpose(0, 2, 1, 3).reshape(b * h, sk, hd)
    bq = min(128, sq)
    bk = min(128, sk)
    o = _flash(qf, kf, vf, causal=causal, bq=bq, bk=bk, interpret=interpret_mode())
    return o.reshape(b, h, sq, hd).transpose(0, 2, 1, 3)


@partial(jax.jit, static_argnames=("head_block",))
def ssd_chunk_op(cum, xdt, B, C, *, head_block=4):
    return _ssd_chunk(cum, xdt, B, C, head_block=head_block, interpret=interpret_mode())
