"""Pallas TPU kernel: master-side commutativity check (§4.3).

conflicts[b] = any_u( valid[u] & window[u] == query[b] & classes conflict )
— a broadcast compare-reduce between the B incoming keyhashes and the
U-entry unsynced window.  Tiled as a (B-tile x U-tile) grid: the query tile
stays resident in VMEM while window tiles stream through; partial ORs
accumulate into the output block across the U-axis of the grid
(accumulate-on-revisit pattern).

Merge-lattice widening (CRDT-CURP): ``w_valid`` packs the window entry's op
class (0 = invalid, else 1 + class; legacy 0/1 callers get class SET, which
conflicts with everything), and each query carries its own class lane.  The
in-kernel decision is the same one-bit matrix test as the witness record
kernels (ref.matrix_rows), so a same-key INCR over an unsynced INCR is NOT
a conflict — the §4.3 check admits exactly what the widened witness admits.

Tile sizes default to (256, 512): the [Bt, Ut] compare cube is 256x512x4 B
= 512 KiB of VMEM intermediates, well within budget, and the minor dimension
is a multiple of 128 lanes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .ref import U32, matrix_rows


def _conflict_kernel(whi_ref, wlo_ref, wval_ref, qhi_ref, qlo_ref, qcls_ref,
                     out_ref):
    u = pl.program_id(1)

    @pl.when(u == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    qhi = qhi_ref[...]                     # [Bt]
    qlo = qlo_ref[...]
    whi = whi_ref[...]                     # [Ut]
    wlo = wlo_ref[...]
    wval = wval_ref[...]
    mrow = matrix_rows(qcls_ref[...])      # [Bt] matrix rows
    wcls = jnp.maximum(wval - 1, 0)
    eq = (
        (whi[None, :] == qhi[:, None])
        & (wlo[None, :] == qlo[:, None])
        & (wval[None, :] > 0)
        & (((mrow[:, None] >> wcls[None, :]) & 1) == 1)
    )
    hit = jnp.any(eq, axis=1).astype(jnp.int32)   # [Bt]
    out_ref[...] = jnp.maximum(out_ref[...], hit)  # OR across window tiles


@functools.partial(
    jax.jit, static_argnames=("block_b", "block_u", "interpret")
)
def conflict_scan_pallas(
    w_hi: jnp.ndarray, w_lo: jnp.ndarray, w_valid: jnp.ndarray,
    q_hi: jnp.ndarray, q_lo: jnp.ndarray, q_cls: jnp.ndarray,
    *, block_b: int = 256, block_u: int = 512, interpret: bool,
):
    (U,) = w_hi.shape
    (B,) = q_hi.shape
    assert B % block_b == 0 and U % block_u == 0, (B, U, block_b, block_u)
    grid = (B // block_b, U // block_u)
    wspec = pl.BlockSpec((block_u,), lambda b, u: (u,))
    qspec = pl.BlockSpec((block_b,), lambda b, u: (b,))
    out = pl.pallas_call(
        _conflict_kernel,
        grid=grid,
        in_specs=[wspec, wspec, wspec, qspec, qspec, qspec],
        out_specs=pl.BlockSpec((block_b,), lambda b, u: (b,)),
        out_shape=jax.ShapeDtypeStruct((B,), jnp.int32),
        interpret=interpret,
    )(w_hi.astype(U32), w_lo.astype(U32), w_valid.astype(jnp.int32),
      q_hi.astype(U32), q_lo.astype(U32), q_cls.astype(jnp.int32))
    return out
