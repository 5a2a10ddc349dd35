"""Pallas TPU kernel: stepped SYRK (paper §3.3, adapted to the MXU).

Computes the lower block triangle of ``F = Yᵀ Y`` for a stepped Y. TPU
adaptation (DESIGN.md §2):

  * The *output splitting* becomes the first two Pallas **grid** axes over
    (bm × bm) output tiles; upper-triangle tiles write zeros (the same
    schedule a causal-attention kernel uses to skip fully-masked blocks).
  * The *k-dimension reduction* is the third grid axis over (bs, bm) row
    blocks of the two Y stripes, accumulated in an f32 VMEM scratch. Tile
    (I, J≤I) accumulates only from row blocks at or below the pivot of
    column stripe I (``start_block[I]``): steps above it re-point their
    blocks at the start row, so the zero region above the pivots is never
    read, and upper-triangle tiles keep the previous step's blocks, so
    they fetch nothing.
  * Accumulation is in fp32 (MXU native) regardless of the storage dtype.

ops.py mirrors the strict lower blocks to the upper triangle afterwards;
the dense F̃ᵢ is consumed by symmetric GEMV in the PCPG solve phase.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import (
    acc_dtype,
    check_dtype,
    compiler_params,
    dot_tn,
    i32,
)

__all__ = ["stepped_syrk_pallas"]


def _syrk_kernel(starts_ref, yi_ref, yj_ref, out_ref, acc_ref):
    i = pl.program_id(0)
    j = pl.program_id(1)
    k = pl.program_id(2)
    acc_t = acc_dtype(out_ref.dtype)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # pivots sorted => tile (i, j<=i) starts at stripe i's pivot
    @pl.when(jnp.logical_and(j <= i, k >= starts_ref[i]))
    def _accumulate():
        acc_ref[...] += dot_tn(yi_ref[...], yj_ref[...], acc_t)

    @pl.when(k == pl.num_programs(2) - 1)
    def _store():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bs", "bm", "interpret"))
def stepped_syrk_pallas(
    Y: jax.Array,  # (n, m) stepped TRSM solution (padded to block multiples)
    start_block: jax.Array,  # (m // bm,) int32 first contributing row block
    bs: int,
    bm: int,
    interpret: bool = False,
) -> jax.Array:
    n, m = Y.shape
    if n % bs or m % bm:
        raise ValueError("inputs must be padded to block multiples (see ops.py)")
    nb, nc = n // bs, m // bm
    if start_block.shape != (nc,):
        raise ValueError(f"start_block shape {start_block.shape} != {(nc,)}")
    check_dtype(Y.dtype, interpret)

    def row(i, j, k, st):
        # lower tiles: k past the start (clamped for all-zero stripes);
        # upper tiles: the last row, i.e. the previous step's block
        lower = jnp.minimum(jnp.maximum(k, st[i]), nb - 1)
        return jax.lax.select(j <= i, lower, i32(nb - 1))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,  # start_block
        grid=(nc, nc, nb),
        in_specs=[
            pl.BlockSpec((bs, bm), lambda i, j, k, st: (row(i, j, k, st), i)),
            pl.BlockSpec((bs, bm), lambda i, j, k, st: (
                row(i, j, k, st), jnp.minimum(i, j))),
        ],
        out_specs=pl.BlockSpec((bm, bm), lambda i, j, k, st: (i, j)),
        scratch_shapes=[pltpu.VMEM((bm, bm), acc_dtype(Y.dtype))],
    )
    return pl.pallas_call(
        _syrk_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, m), Y.dtype),
        compiler_params=compiler_params("parallel", "parallel", "arbitrary"),
        interpret=interpret,
    )(start_block.astype(jnp.int32), Y, Y)
