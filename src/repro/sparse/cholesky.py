"""Blocked numerical Cholesky in JAX (paper §2.2's numerical stage).

Right-looking block Cholesky over a uniform block grid. With a block fill
mask from the symbolic stage, structurally-zero blocks are skipped — the
TPU-native analogue of sparse supernodal factorization: every surviving
block is a dense MXU-aligned tile.

Block loops are Python loops over compile-time-constant indices (the mask
is static per decomposition), so XLA sees a static program; multi-step
simulations with fixed sparsity recompile zero times, matching the paper's
symbolic/numeric split.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.precision import mm

__all__ = ["block_cholesky", "block_cholesky_flops"]


def _solve_lower_right(Lkk: jax.Array, W: jax.Array) -> jax.Array:
    """Solve X Lkkᵀ = W for X (i.e. X = W Lkk⁻ᵀ)."""
    return jax.lax.linalg.triangular_solve(
        Lkk, W, left_side=False, lower=True, transpose_a=True
    )


def block_cholesky(
    K: jax.Array,
    block_size: int,
    mask: Optional[np.ndarray] = None,
) -> jax.Array:
    """Cholesky factor L (lower, dense storage) of SPD K.

    Args:
      K: (n, n) SPD matrix.
      block_size: tile size (128-aligned on real TPU; small in tests).
      mask: optional (nb, nb) lower-triangular block fill mask from
        :func:`repro.sparse.symbolic.block_symbolic_cholesky`. Blocks
        outside the mask are skipped entirely (their result is zero).
    """
    n = K.shape[0]
    nb = -(-n // block_size)

    def blk(k):
        return k * block_size, min((k + 1) * block_size, n)

    if mask is not None:
        mask = np.asarray(mask)
        if mask.shape != (nb, nb):
            raise ValueError(f"mask shape {mask.shape} != ({nb},{nb})")

    W = K
    L = jnp.zeros_like(K)
    for k in range(nb):
        k0, k1 = blk(k)
        Lkk = jnp.linalg.cholesky(W[k0:k1, k0:k1])
        L = L.at[k0:k1, k0:k1].set(Lkk)
        if k1 >= n:
            break
        if mask is None:
            panel = _solve_lower_right(Lkk, W[k1:, k0:k1])
            L = L.at[k1:, k0:k1].set(panel)
            W = W.at[k1:, k1:].add(-mm(panel, panel.T))
        else:
            below = [i for i in range(k + 1, nb) if mask[i, k]]
            panels = {}
            for i in below:
                i0, i1 = blk(i)
                Lik = _solve_lower_right(Lkk, W[i0:i1, k0:k1])
                L = L.at[i0:i1, k0:k1].set(Lik)
                panels[i] = (i0, i1, Lik)
            for i in below:
                i0, i1, Lik = panels[i]
                for j in below:
                    if j > i:
                        break
                    j0, j1, Ljk = panels[j]
                    W = W.at[i0:i1, j0:j1].add(-mm(Lik, Ljk.T))
    return L


def block_cholesky_flops(n: int, block_size: int,
                         mask: Optional[np.ndarray] = None) -> int:
    """FLOP model of the blocked factorization (MAC = 2 flops)."""
    nb = -(-n // block_size)

    def bsz(k):
        return min((k + 1) * block_size, n) - k * block_size

    total = 0
    for k in range(nb):
        b = bsz(k)
        total += b * b * b // 3  # dense Cholesky of the diagonal block
        below = (
            [i for i in range(k + 1, nb) if mask[i, k]]
            if mask is not None
            else list(range(k + 1, nb))
        )
        for i in below:
            total += bsz(i) * b * b  # panel triangular solve
        for ii, i in enumerate(below):
            for j in below[: ii + 1]:
                total += 2 * bsz(i) * bsz(j) * b  # trailing GEMM update
    return total
