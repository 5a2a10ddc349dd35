"""Pallas TPU kernel: stepped TRSM (paper §3.2, adapted to the MXU).

Solves ``L Y = B`` where B is in stepped shape. TPU adaptation of the
paper's CUDA kernels (DESIGN.md §2):

  * The *RHS splitting* becomes the outer Pallas **grid** axis: one column
    stripe per program row, each starting its forward substitution at its
    own ``start_block`` (the stripe's highest column pivot, floored to the
    block grid) — the zero region above the pivots is never touched.
  * The inner grid axis walks the factor: step ``(c, k)`` reads the
    ``(bs, n)`` row panel ``L[k]`` from HBM (dense), or step ``(c, t)`` the
    ``t``-th stored block of the packed value stack (packed), while the
    ``(n, bm)`` solution stripe stays resident in VMEM across the whole
    axis. Steps above the stripe's start re-point their blocks at the
    start row, so the pipeline fetches nothing for them.
  * The per-block triangular solve is a **multiply with the pre-inverted
    diagonal block** (``Linv[k] @ acc``): row-serial forward substitution
    is VPU-hostile, while small pre-inverted blocks turn the whole kernel
    into dense MXU matmuls.
  * The factor-split GEMM update is the loop over the factor tiles of row
    ``k`` with a dynamic lower bound — tiles left of ``start_block`` are
    skipped, the paper's zero-block pruning at tile level.

VMEM holds one row panel or block (double-buffered) and one solution
stripe, so the working set grows with ``n`` linearly, not quadratically
(:func:`repro.kernels.common.vmem_bytes`).
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
    dot,
    i32,
)

__all__ = ["stepped_trsm_pallas", "stepped_trsm_packed_pallas"]


def _row(c, k, starts, nb):
    """Factor block row fetched at grid step (c, k): k itself once the
    stripe's substitution has started, the start row before (no refetch),
    clamped for all-zero stripes (start == nb)."""
    return jnp.minimum(jnp.maximum(k, starts[c]), nb - 1)


def _trsm_kernel(starts_ref, linv_ref, l_ref, b_ref, out_ref, *, bs: int):
    c = pl.program_id(0)
    k = pl.program_id(1)
    start = starts_ref[c]
    acc_t = acc_dtype(out_ref.dtype)

    @pl.when(k == 0)
    def _zero():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(k >= start)
    def _solve():
        def inner(j, acc):
            col = pl.multiple_of(j * bs, bs)
            lkj = l_ref[:, pl.ds(col, bs)]
            return acc - dot(lkj, out_ref[pl.ds(col, bs), :], acc_t)

        acc = jax.lax.fori_loop(start, k, inner,
                                b_ref[...].astype(acc_t))
        yk = dot(linv_ref[0], acc, acc_t)
        out_ref[pl.ds(pl.multiple_of(k * bs, bs), bs), :] = \
            yk.astype(out_ref.dtype)


def check_shapes(n, m, bs, bm, Linv_diag, start_block):
    if n % bs or m % bm:
        raise ValueError("inputs must be padded to block multiples (see ops.py)")
    nb, nc = n // bs, m // bm
    if Linv_diag.shape != (nb, bs, bs):
        raise ValueError(f"Linv_diag shape {Linv_diag.shape} != {(nb, bs, bs)}")
    if start_block.shape != (nc,):
        raise ValueError(f"start_block shape {start_block.shape} != {(nc,)}")
    return nb, nc


@functools.partial(jax.jit, static_argnames=("bs", "bm", "interpret"))
def stepped_trsm_pallas(
    Linv_diag: jax.Array,  # (nb, bs, bs) pre-inverted diagonal blocks
    L: jax.Array,  # (n, n) lower factor (padded to bs multiples)
    B: jax.Array,  # (n, m) stepped RHS (padded to bm multiples)
    start_block: jax.Array,  # (m // bm,) int32: first factor block per stripe
    bs: int,
    bm: int,
    interpret: bool = False,
) -> jax.Array:
    n, m = B.shape
    nb, nc = check_shapes(n, m, bs, bm, Linv_diag, start_block)
    check_dtype(B.dtype, interpret)

    def row(c, k, st):
        return _row(c, k, st, nb)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,  # start_block
        grid=(nc, nb),
        in_specs=[
            pl.BlockSpec((1, bs, bs),
                         lambda c, k, st: (row(c, k, st), i32(0), i32(0))),
            pl.BlockSpec((bs, n), lambda c, k, st: (row(c, k, st), i32(0))),
            pl.BlockSpec((bs, bm), lambda c, k, st: (row(c, k, st), c)),
        ],
        out_specs=pl.BlockSpec((n, bm), lambda c, k, st: (i32(0), c)),
    )
    return pl.pallas_call(
        functools.partial(_trsm_kernel, bs=bs),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n, m), B.dtype),
        compiler_params=compiler_params("parallel", "arbitrary"),
        interpret=interpret,
    )(start_block.astype(jnp.int32), Linv_diag, L, B)


def packed_slot(c, t, starts, rowptr, n_slots: int):
    """Packed-factor slot whose blocks grid step (c, t) fetches: t itself
    from the first slot of stripe c's start row on, that first slot before
    it (so the pipeline fetches nothing there), and the last slot past the
    end (all-zero stripes; the fused kernel's SYRK steps)."""
    first = rowptr[starts[c]]  # starts <= nb and rowptr[nb] == n_slots
    return jnp.minimum(jnp.maximum(t, first), n_slots - 1)


def packed_slot_specs(bs: int, bm: int, n_slots: int):
    """BlockSpecs of the slot-stepped packed kernels: the pre-inverted
    diagonal block of the slot's row, the slot's factor block and the
    slot's row block of stripe c of B."""

    def slot(c, t, st, rp, rows, cols):
        return packed_slot(c, t, st, rp, n_slots)

    def row(c, t, st, rp, rows, cols):
        return rows[slot(c, t, st, rp, rows, cols)]

    return [
        pl.BlockSpec((1, bs, bs), lambda c, t, *p: (row(c, t, *p), i32(0),
                                                    i32(0))),
        pl.BlockSpec((1, bs, bs), lambda c, t, *p: (slot(c, t, *p), i32(0),
                                                    i32(0))),
        pl.BlockSpec((bs, bm), lambda c, t, *p: (row(c, t, *p), c)),
    ]


def packed_slot_step(t, c, starts_ref, rowptr_ref, rows_ref, cols_ref,
                     linv_ref, vals_ref, b_ref, acc_ref, y_ref, *, bs: int,
                     y_lead: tuple = ()):
    """One stored factor block of the packed forward substitution into the
    (n, bm) solution stripe ``y_ref``. Slots run row-major with the
    diagonal last in each row, so row k's first slot loads ``acc`` with
    B[k], each strictly-subdiagonal slot (k, j) subtracts L[k, j] Y[j], and
    the diagonal slot writes Y[k] = Linv[k] acc. Rows above the stripe's
    start are skipped; stored blocks left of the start meet zero Y rows.
    ``y_lead`` indexes the stripe within ``y_ref`` (the fused kernel's
    panel); the unfused kernel's ``y_ref`` is the stripe itself."""
    k = rows_ref[t]
    j = cols_ref[t]
    active = k >= starts_ref[c]
    acc_t = acc_ref.dtype

    @pl.when(jnp.logical_and(active, t == rowptr_ref[k]))
    def _load():
        acc_ref[...] = b_ref[...].astype(acc_t)

    @pl.when(jnp.logical_and(active, j < k))
    def _update():
        rows = pl.ds(pl.multiple_of(j * bs, bs), bs)
        acc_ref[...] -= dot(vals_ref[0], y_ref[(*y_lead, rows)], acc_t)

    @pl.when(jnp.logical_and(active, j == k))
    def _solve():
        yk = dot(linv_ref[0], acc_ref[...], acc_t)
        rows = pl.ds(pl.multiple_of(k * bs, bs), bs)
        y_ref[(*y_lead, rows)] = yk.astype(y_ref.dtype)


def _trsm_packed_kernel(starts_ref, rowptr_ref, rows_ref, cols_ref,
                        linv_ref, vals_ref, b_ref, out_ref, acc_ref,
                        *, bs: int):
    """Packed-factor stepped TRSM: the grid walks the STORED factor blocks
    (slots) of the packed (n_blocks, bs, bs) value stack, one per step, so
    the paper's zero-block pruning is structural: absent blocks are never
    addressed, and VMEM holds one factor block at a time."""
    c = pl.program_id(0)
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _zero():
        out_ref[...] = jnp.zeros_like(out_ref)

    packed_slot_step(t, c, starts_ref, rowptr_ref, rows_ref, cols_ref,
                     linv_ref, vals_ref, b_ref, acc_ref, out_ref, bs=bs)


def check_packed(values, rowptr, rows, cols, bs: int, nb: int) -> int:
    n_slots = values.shape[0]
    if values.shape != (n_slots, bs, bs):
        raise ValueError(f"values shape {values.shape} != {(n_slots, bs, bs)}")
    if rowptr.shape != (nb + 1,) or rows.shape != (n_slots,) \
            or cols.shape != (n_slots,):
        raise ValueError("rowptr/rows/cols shapes do not match the block "
                         "index")
    return n_slots


@functools.partial(jax.jit, static_argnames=("bs", "bm", "interpret"))
def stepped_trsm_packed_pallas(
    Linv_diag: jax.Array,  # (nb, bs, bs) pre-inverted diagonal blocks
    values: jax.Array,  # (n_blocks, bs, bs) packed factor blocks
    rowptr: jax.Array,  # (nb + 1,) int32 CSR row pointers (diag last in row)
    rows: jax.Array,  # (n_blocks,) int32 block-row of each slot
    cols: jax.Array,  # (n_blocks,) int32 block-column of each slot
    B: jax.Array,  # (n, m) stepped RHS (padded to block multiples)
    start_block: jax.Array,  # (m // bm,) int32: first factor block per stripe
    bs: int,
    bm: int,
    interpret: bool = False,
) -> jax.Array:
    """Packed variant of :func:`stepped_trsm_pallas`: the factor never
    leaves HBM whole — the grid streams its stored blocks one at a time."""
    n, m = B.shape
    nb, nc = check_shapes(n, m, bs, bm, Linv_diag, start_block)
    check_dtype(B.dtype, interpret)
    n_slots = check_packed(values, rowptr, rows, cols, bs, nb)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,  # start_block, rowptr, rows, cols
        grid=(nc, n_slots),
        in_specs=packed_slot_specs(bs, bm, n_slots),
        out_specs=pl.BlockSpec((n, bm), lambda c, t, *p: (i32(0), c)),
        scratch_shapes=[pltpu.VMEM((bs, bm), acc_dtype(B.dtype))],
    )
    return pl.pallas_call(
        functools.partial(_trsm_packed_kernel, bs=bs),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n, m), B.dtype),
        compiler_params=compiler_params("parallel", "arbitrary"),
        interpret=interpret,
    )(start_block.astype(jnp.int32), rowptr.astype(jnp.int32),
      rows.astype(jnp.int32), cols.astype(jnp.int32), Linv_diag, values, B)
