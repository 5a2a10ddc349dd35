"""Sparsity-utilizing SYRK variants (paper §3.3).

Computes ``F = Yᵀ Y`` for the stepped matrix ``Y`` produced by TRSM (zeros
above the column pivots are preserved by forward substitution, so Y carries
the same stepped envelope as B̃ᵀ).

Variants:
  * ``syrk_dense``        — baseline full SYRK (paper §3.1).
  * ``syrk_input_split``  — split Y into row blocks (paper Fig. 4a): row
                            block k is nonzero only in its leading
                            ``widths[k]`` columns, so each partial SYRK
                            updates only the top-left ``w×w`` principal
                            submatrix of the output.
  * ``syrk_output_split`` — tile the output (paper Fig. 4b): output block
                            row I needs input rows starting only at the
                            pivot of column block I (k-dimension reduction);
                            the diagonal block is a small SYRK, the blocks
                            to its left are GEMMs.

The result is returned as the full symmetric matrix (both triangles filled):
the dense F̃ᵢ is consumed by GEMV in every PCPG iteration, and on TPU a full
symmetric GEMV is preferable to a triangular-packed one. FLOP accounting in
stepped.SteppedMeta counts lower-triangle work only, matching the paper.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.precision import mm
from repro.core.stepped import SteppedMeta

__all__ = ["syrk_dense", "syrk_input_split", "syrk_output_split"]


def syrk_dense(Y: jax.Array) -> jax.Array:
    """Baseline: full dense SYRK F = YᵀY."""
    return mm(Y.T, Y)


def syrk_input_split(Y: jax.Array, meta: SteppedMeta) -> jax.Array:
    """Input (row-block) splitting, paper Fig. 4a."""
    if Y.shape != (meta.n, meta.m):
        raise ValueError(f"Y shape {Y.shape} != meta ({meta.n},{meta.m})")
    F = jnp.zeros((meta.m, meta.m), dtype=Y.dtype)
    for k in range(meta.num_row_blocks):
        r0, r1 = meta.row_block(k)
        w = int(meta.widths[k])
        if w == 0:
            continue
        Yk = Y[r0:r1, :w]
        F = F.at[:w, :w].add(mm(Yk.T, Yk))
    return F


def syrk_output_split(Y: jax.Array, meta: SteppedMeta) -> jax.Array:
    """Output (block-row of F) splitting, paper Fig. 4b.

    For output block row I (columns of F up to block I), contributions from
    input rows above ``col_starts[I]`` vanish because every column in block
    I has its pivot at or below that row. The diagonal block is an inner
    SYRK; the off-diagonal strip ``F[I, :I]`` is one GEMM. Both triangles of
    F are written (the strip is mirrored).
    """
    if Y.shape != (meta.n, meta.m):
        raise ValueError(f"Y shape {Y.shape} != meta ({meta.n},{meta.m})")
    F = jnp.zeros((meta.m, meta.m), dtype=Y.dtype)
    for i in range(meta.num_col_blocks):
        i0, i1 = meta.col_block(i)
        s = int(meta.col_starts[i])
        if s >= meta.n:  # structurally zero columns -> zero row/col of F
            continue
        Ci = Y[s:, i0:i1]
        F = F.at[i0:i1, i0:i1].set(mm(Ci.T, Ci))
        if i0 > 0:
            strip = mm(Ci.T, Y[s:, :i0])
            F = F.at[i0:i1, :i0].set(strip)
            F = F.at[:i0, i0:i1].set(strip.T)
    return F
