"""Pallas TPU kernel: batched 2x32-bit key hashing (keyhash2x32).

TPU adaptation (DESIGN.md §4): VPU lanes are 32-bit, so the 64-bit key hash
is carried as (hi, lo) uint32 lanes and mixed with murmur3 fmix32 finalizers
— pure element-wise VPU work, tiled over VMEM blocks.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .ref import _C1, _C2, _GOLD, _MIX5, _MIXC, U32


def _fmix32(x):
    x = x ^ (x >> 16)
    x = x * _C1
    x = x ^ (x >> 13)
    x = x * _C2
    x = x ^ (x >> 16)
    return x


def _keyhash_kernel(hi_ref, lo_ref, out_hi_ref, out_lo_ref):
    hi = hi_ref[...].astype(U32)
    lo = lo_ref[...].astype(U32)
    h1 = _fmix32(lo + _GOLD)
    h2 = _fmix32(hi ^ h1)
    h3 = _fmix32(h1 + h2 * _MIX5 + _MIXC)
    out_hi_ref[...] = h2
    out_lo_ref[...] = h3


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def keyhash2x32_pallas(
    hi: jnp.ndarray, lo: jnp.ndarray, *, block: int = 1024,
    interpret: bool,
):
    """[N]-shaped (hi, lo) -> mixed (hi', lo').  N must be a multiple of
    ``block``; callers pad (ops.py handles it)."""
    (n,) = hi.shape
    assert n % block == 0, (n, block)
    grid = (n // block,)
    spec = pl.BlockSpec((block,), lambda i: (i,))
    out_hi, out_lo = pl.pallas_call(
        _keyhash_kernel,
        grid=grid,
        in_specs=[spec, spec],
        out_specs=[spec, spec],
        out_shape=[
            jax.ShapeDtypeStruct((n,), U32),
            jax.ShapeDtypeStruct((n,), U32),
        ],
        interpret=interpret,
    )(hi.astype(U32), lo.astype(U32))
    return out_hi, out_lo
