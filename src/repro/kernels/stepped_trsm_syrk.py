"""Pallas TPU megakernel: fused stepped TRSM→SYRK (stage-graph tentpole).

Computes the lower block triangle of ``F = Yᵀ Y`` with ``L Y = B`` solved
*inside the same kernel*: the TRSM solution panel never round-trips HBM
between the two stages. The unfused pipeline writes Y once and re-reads it
``nc`` times (once per SYRK output-tile row); here Y lives in a VMEM
scratch that persists across grid iterations, so HBM traffic drops to
factor + B + F.

Schedule (DESIGN.md §2, fused): grid ``(nc, nb + nc)`` over (stripe c,
step s), executed **sequentially** on a core — the ordering guarantee the
fusion rides on.

  * Steps ``s < nb`` forward-substitute block row ``k = s`` of stripe c
    into the persistent Y scratch, streaming the ``(bs, n)`` factor row
    panel from HBM (dense) or DMAing the row's stored blocks (packed). The
    stepped ``start_block`` skip applies exactly as in stepped_trsm: rows
    above the start fetch nothing and compute nothing.
  * Steps ``s = nb + j`` contract stripes c and j ≤ c straight out of VMEM
    into output tile (c, j); stripe j < c was solved in an earlier row of
    the grid. Upper-triangle tiles (j > c) write zeros; ops.py mirrors the
    strict lower triangle, identical to the unfused stepped_syrk.
  * The k reduction of tile (c, j ≤ c) starts at ``start_block[c]``
    (pivots sorted ⇒ stripe c's pivot dominates), so the zero region above
    the steps is neither solved nor contracted.

VMEM holds the (nc, n, bm) solution panel plus one factor row panel
(:func:`repro.kernels.common.vmem_bytes`); the planner offers the kernel
only where that fits.
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
    dot_tn,
    i32,
)
from repro.kernels.stepped_trsm import (
    check_packed,
    check_shapes,
    packed_slot_specs,
    packed_slot_step,
)

__all__ = ["stepped_trsm_syrk_pallas", "stepped_trsm_syrk_packed_pallas"]


def _syrk_tile(c, j, y_ref, start, out_ref, *, bs: int, nb: int):
    """Contract Y stripes c and j (both already in the VMEM scratch) into
    the (bm, bm) output tile — the SYRK half shared by both variants."""
    acc_t = acc_dtype(out_ref.dtype)

    def body(k, acc):
        rk = pl.ds(pl.multiple_of(k * bs, bs), bs)
        return acc + dot_tn(y_ref[c, rk, :], y_ref[j, rk, :], acc_t)

    bm = out_ref.shape[-1]
    acc = jax.lax.fori_loop(start, i32(nb), body,
                            jnp.zeros((bm, bm), acc_t))
    out_ref[...] = acc.astype(out_ref.dtype)


def _syrk_steps(c, j, y_ref, start, out_ref, *, bs: int, nb: int):
    """Grid steps past the TRSM ones: output tile (c, j) for j >= 0 —
    contracted from the panel for j <= c, zero above the diagonal."""

    @pl.when(jnp.logical_and(j >= 0, j <= c))
    def _syrk():
        _syrk_tile(c, j, y_ref, start, out_ref, bs=bs, nb=nb)

    @pl.when(j > c)
    def _upper():
        out_ref[...] = jnp.zeros_like(out_ref)


def _fused_kernel(starts_ref, linv_ref, l_ref, b_ref, out_ref, y_ref,
                  *, bs: int, nb: int):
    c = pl.program_id(0)
    s = pl.program_id(1)
    start = starts_ref[c]
    acc_t = acc_dtype(out_ref.dtype)

    @pl.when(s == 0)
    def _zero():
        y_ref[c] = jnp.zeros_like(y_ref[c])

    @pl.when(jnp.logical_and(s >= start, s < nb))
    def _trsm():  # block row k = s of stripe c, factor row panel streamed
        def inner(jj, acc):
            col = pl.multiple_of(jj * bs, bs)
            return acc - dot(l_ref[:, pl.ds(col, bs)],
                             y_ref[c, pl.ds(col, bs), :], acc_t)

        acc = jax.lax.fori_loop(start, s, inner, b_ref[...].astype(acc_t))
        yk = dot(linv_ref[0], acc, acc_t)
        y_ref[c, pl.ds(pl.multiple_of(s * bs, bs), bs), :] = \
            yk.astype(y_ref.dtype)

    _syrk_steps(c, s - nb, y_ref, start, out_ref, bs=bs, nb=nb)


def _fused_packed_kernel(starts_ref, rowptr_ref, rows_ref, cols_ref,
                         linv_ref, vals_ref, b_ref, out_ref, y_ref, acc_ref,
                         *, bs: int, nb: int, n_slots: int):
    """Packed variant: the TRSM steps walk the factor's STORED blocks
    (slots), one per grid step (stepped_trsm.packed_slot_step), then the
    SYRK steps contract out of the VMEM panel as in the dense kernel."""
    c = pl.program_id(0)
    s = pl.program_id(1)

    @pl.when(s == 0)
    def _zero():
        y_ref[c] = jnp.zeros_like(y_ref[c])

    @pl.when(s < n_slots)
    def _trsm():
        packed_slot_step(s, c, starts_ref, rowptr_ref, rows_ref, cols_ref,
                         linv_ref, vals_ref, b_ref, acc_ref, y_ref, bs=bs,
                         y_lead=(c,))

    _syrk_steps(c, s - n_slots, y_ref, starts_ref[c], out_ref, bs=bs, nb=nb)


def _out_map(n_steps: int, nc: int):
    """Output tile of grid step (c, s): (c, j) during the SYRK steps
    s = n_steps + j, and (c, 0) before them — one residency."""
    return lambda c, s, *p: (c, jnp.minimum(jnp.maximum(s - n_steps, 0),
                                            nc - 1))


@functools.partial(jax.jit, static_argnames=("bs", "bm", "interpret"))
def stepped_trsm_syrk_pallas(
    Linv_diag: jax.Array,  # (nb, bs, bs) pre-inverted diagonal blocks
    L: jax.Array,  # (n, n) lower factor (padded to bs multiples)
    B: jax.Array,  # (n, m) stepped RHS (padded to bm multiples)
    start_block: jax.Array,  # (m // bm,) int32: first factor block per stripe
    bs: int,
    bm: int,
    interpret: bool = False,
) -> jax.Array:
    """Fused stepped TRSM→SYRK: lower block triangle of (L⁻¹B)ᵀ(L⁻¹B)."""
    n, m = B.shape
    nb, nc = check_shapes(n, m, bs, bm, Linv_diag, start_block)
    check_dtype(B.dtype, interpret)

    def row(c, s, st):
        # the TRSM row, clamped to the stripe's start and, once the SYRK
        # steps begin, to the last row: neither fetches anything new
        return jnp.minimum(jnp.maximum(s, st[c]), nb - 1)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,  # start_block
        grid=(nc, nb + nc),
        in_specs=[
            pl.BlockSpec((1, bs, bs),
                         lambda c, s, st: (row(c, s, st), i32(0), i32(0))),
            pl.BlockSpec((bs, n), lambda c, s, st: (row(c, s, st), i32(0))),
            pl.BlockSpec((bs, bm), lambda c, s, st: (row(c, s, st), c)),
        ],
        out_specs=pl.BlockSpec((bm, bm), _out_map(nb, nc)),
        scratch_shapes=[pltpu.VMEM((nc, n, bm), B.dtype)],  # persistent Y
    )
    return pl.pallas_call(
        functools.partial(_fused_kernel, bs=bs, nb=nb),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, m), B.dtype),
        # the fusion depends on sequential grid execution along BOTH axes
        compiler_params=compiler_params("arbitrary", "arbitrary"),
        interpret=interpret,
    )(start_block.astype(jnp.int32), Linv_diag, L, B)


@functools.partial(jax.jit, static_argnames=("bs", "bm", "interpret"))
def stepped_trsm_syrk_packed_pallas(
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
    """Packed-factor fused TRSM→SYRK: the TRSM steps stream the packed
    value stack's stored blocks one per grid step; VMEM holds one factor
    block plus the persistent Y panel."""
    n, m = B.shape
    nb, nc = check_shapes(n, m, bs, bm, Linv_diag, start_block)
    check_dtype(B.dtype, interpret)
    n_slots = check_packed(values, rowptr, rows, cols, bs, nb)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,  # start_block, rowptr, rows, cols
        grid=(nc, n_slots + nc),
        in_specs=packed_slot_specs(bs, bm, n_slots),
        out_specs=pl.BlockSpec((bm, bm), _out_map(n_slots, nc)),
        scratch_shapes=[pltpu.VMEM((nc, n, bm), B.dtype),  # persistent Y
                        pltpu.VMEM((bs, bm), acc_dtype(B.dtype))],
    )
    return pl.pallas_call(
        functools.partial(_fused_packed_kernel, bs=bs, nb=nb,
                          n_slots=n_slots),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, m), B.dtype),
        compiler_params=compiler_params("arbitrary", "arbitrary"),
        interpret=interpret,
    )(start_block.astype(jnp.int32), rowptr.astype(jnp.int32),
      rows.astype(jnp.int32), cols.astype(jnp.int32), Linv_diag, values, B)
