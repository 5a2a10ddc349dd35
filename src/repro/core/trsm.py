"""Sparsity-utilizing TRSM variants (paper §3.2).

Solves ``L Y = B`` for a lower-triangular factor ``L`` and a *stepped* RHS
``B`` (columns permuted so pivots are non-decreasing, see stepped.py).

Variants:
  * ``trsm_dense``         — the baseline of [Homola et al. 2502.08382]: one
                             library TRSM on the full matrices (paper §3.1).
  * ``trsm_rhs_split``     — RHS column-block splitting (paper Fig. 3a): each
                             column block only needs the trailing subfactor
                             starting at its highest column pivot.
  * ``trsm_factor_split``  — factor blocking (paper Fig. 3b): per diagonal
                             block, a small TRSM restricted to the columns
                             that are nonzero so far, then a GEMM update of
                             the rows below. With a block fill mask this also
                             *prunes* structurally-zero factor blocks from the
                             update (paper's "pruning", CHOLMOD-supernodal
                             style — on TPU, zero *blocks* rather than zero
                             rows, since the MXU wants dense 128-ish tiles).

All loops below are Python loops over compile-time-constant block indices:
the stepped metadata is fixed per decomposition (symbolic/numeric split), so
XLA sees a fully static program and each (pattern, config) compiles once.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.precision import mm
from repro.core.stepped import SteppedMeta

__all__ = [
    "trsm_dense",
    "trsm_rhs_split",
    "trsm_factor_split",
    "trsm_factor_split_packed",
]


def _solve_lower(L: jax.Array, B: jax.Array) -> jax.Array:
    return jax.lax.linalg.triangular_solve(
        L, B, left_side=True, lower=True, transpose_a=False, unit_diagonal=False
    )


def trsm_dense(L: jax.Array, B: jax.Array) -> jax.Array:
    """Baseline: full dense TRSM, no sparsity utilization (paper §3.1)."""
    return _solve_lower(L, B)


def trsm_rhs_split(L: jax.Array, B: jax.Array, meta: SteppedMeta) -> jax.Array:
    """RHS splitting (paper Fig. 3a).

    For each RHS column block the rows above its smallest column pivot are
    zero and — because forward substitution only propagates *downward* —
    remain zero in the solution. So block ``c`` is solved against only the
    trailing subfactor ``L[s_c:, s_c:]``.
    """
    if B.shape != (meta.n, meta.m):
        raise ValueError(f"B shape {B.shape} != meta ({meta.n},{meta.m})")
    Y = jnp.zeros_like(B)
    for c in range(meta.num_col_blocks):
        c0, c1 = meta.col_block(c)
        s = int(meta.col_starts[c])
        if s >= meta.n:  # all-zero column block: solution stays zero
            continue
        sol = _solve_lower(L[s:, s:], B[s:, c0:c1])
        Y = Y.at[s:, c0:c1].set(sol)
    return Y


def trsm_factor_split(
    L: jax.Array,
    B: jax.Array,
    meta: SteppedMeta,
    block_mask: Optional[np.ndarray] = None,
) -> jax.Array:
    """Factor splitting with optional pruning (paper Fig. 3b).

    Blocked forward substitution. At factor block-row ``k`` only the leading
    ``widths[k]`` RHS columns can be nonzero; the diagonal TRSM and the GEMM
    update of the rows below are restricted to them. If ``block_mask`` (the
    lower-triangular block fill pattern of ``L``) is given, GEMM updates for
    structurally-zero factor blocks are skipped entirely — the TPU-native
    form of the paper's row pruning.
    """
    if B.shape != (meta.n, meta.m):
        raise ValueError(f"B shape {B.shape} != meta ({meta.n},{meta.m})")
    nb = meta.num_row_blocks
    if block_mask is not None:
        block_mask = np.asarray(block_mask)
        if block_mask.shape != (nb, nb):
            raise ValueError(f"block_mask shape {block_mask.shape} != ({nb},{nb})")
    Y = B
    n = meta.n
    for k in range(nb):
        r0, r1 = meta.row_block(k)
        w = int(meta.widths[k])
        if w == 0:
            continue
        Yk = _solve_lower(L[r0:r1, r0:r1], Y[r0:r1, :w])
        Y = Y.at[r0:r1, :w].set(Yk)
        if r1 >= n:
            continue
        if block_mask is None:
            Y = Y.at[r1:, :w].add(-mm(L[r1:, r0:r1], Yk))
        else:
            # Pruning: touch only structurally nonzero subdiagonal blocks.
            for i in range(k + 1, nb):
                if not block_mask[i, k]:
                    continue
                i0, i1 = meta.row_block(i)
                Y = Y.at[i0:i1, :w].add(-mm(L[i0:i1, r0:r1], Yk))
    return Y


def trsm_factor_split_packed(L, B: jax.Array, meta: SteppedMeta) -> jax.Array:
    """Factor splitting on a PACKED factor (repro.sparse.packed).

    Same blocked forward substitution as :func:`trsm_factor_split`, but the
    factor blocks are gathered from the packed value stack instead of sliced
    out of a dense (n, n) array — pruning is inherent: blocks absent from
    the packed layout simply do not exist. Ragged last blocks are handled by
    static slicing of the (identity-padded) stored tiles, so results match
    the dense-masked path bit-for-bit.
    """
    from repro.sparse.packed import PackedBlocks

    if not isinstance(L, PackedBlocks):
        raise TypeError("trsm_factor_split_packed expects a PackedBlocks "
                        f"factor, got {type(L).__name__}")
    index = L.index
    vals = L.values
    if B.shape != (meta.n, meta.m):
        raise ValueError(f"B shape {B.shape} != meta ({meta.n},{meta.m})")
    if (index.bs, index.n) != (meta.block_size, meta.n):
        raise ValueError(
            f"packed index (n={index.n}, bs={index.bs}) does not match "
            f"stepped meta (n={meta.n}, bs={meta.block_size})")
    nb = meta.num_row_blocks
    Y = B
    n = meta.n
    for k in range(nb):
        r0, r1 = meta.row_block(k)
        b = r1 - r0
        w = int(meta.widths[k])
        if w == 0:
            continue
        Lkk = vals[index.slot(k, k)][:b, :b]
        Yk = _solve_lower(Lkk, Y[r0:r1, :w])
        Y = Y.at[r0:r1, :w].set(Yk)
        if r1 >= n:
            continue
        for i, s in index.col_slots(k):
            i0, i1 = meta.row_block(i)
            Y = Y.at[i0:i1, :w].add(-mm(vals[s][: i1 - i0, :b], Yk))
    return Y
