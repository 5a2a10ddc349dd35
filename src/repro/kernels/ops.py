"""jit'd wrappers around the Pallas stepped kernels.

Handles everything the kernels require to stay simple and MXU-aligned:
padding to block multiples (identity-padded factor diagonal), per-stripe
start-block metadata derived from the stepped pivots, pre-inversion of the
factor's diagonal blocks, and the mirror of SYRK's lower block triangle.

API mirrors the pure-jnp variants in repro.core so SchurAssemblyConfig can
dispatch between backends transparently.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.stepped import SteppedMeta
from repro.kernels.stepped_syrk import stepped_syrk_pallas
from repro.kernels.stepped_trsm import (
    stepped_trsm_packed_pallas,
    stepped_trsm_pallas,
)
from repro.kernels.stepped_trsm_syrk import (
    stepped_trsm_syrk_packed_pallas,
    stepped_trsm_syrk_pallas,
)

__all__ = [
    "stepped_trsm",
    "stepped_trsm_packed",
    "stepped_syrk",
    "stepped_trsm_syrk",
    "invert_diag_blocks",
]


def _pad_to(x: jax.Array, rows: int, cols: int) -> jax.Array:
    pr, pc = rows - x.shape[0], cols - x.shape[1]
    return jnp.pad(x, ((0, pr), (0, pc)))


def invert_diag_blocks(L: jax.Array, bs: int) -> jax.Array:
    """(nb, bs, bs) inverses of the factor's diagonal blocks (batched).

    Small-block inversion via triangular solve against the identity; cost
    nb·bs³ — negligible next to the TRSM itself, and it converts the whole
    kernel into MXU matmuls (see stepped_trsm.py docstring).
    """
    n = L.shape[0]
    nb = n // bs
    blocks = L.reshape(nb, bs, nb, bs)
    diag = jnp.stack([blocks[k, :, k, :] for k in range(nb)])
    eye = jnp.broadcast_to(jnp.eye(bs, dtype=L.dtype), (nb, bs, bs))
    return jax.lax.linalg.triangular_solve(
        diag, eye, left_side=True, lower=True
    )


def _start_blocks(meta: SteppedMeta, bm: int, bs: int, m_pad: int,
                  n_pad: int) -> np.ndarray:
    """First factor block each padded column stripe contributes from."""
    nb = n_pad // bs
    nc = m_pad // bm
    starts = np.full((nc,), nb, dtype=np.int32)
    for c in range(nc):
        c0 = c * bm
        if c0 < meta.m:
            piv = int(meta.pivots[c0])
            starts[c] = min(piv // bs, nb)
    return starts


def stepped_trsm(L: jax.Array, B: jax.Array, meta: SteppedMeta,
                 interpret: bool = False) -> jax.Array:
    """Pallas stepped TRSM with the same signature semantics as
    :func:`repro.core.trsm.trsm_rhs_split` (B already in stepped order)."""
    bs, bm = meta.block_size, meta.rhs_block_size
    n, m = meta.n, meta.m
    n_pad = -(-n // bs) * bs
    m_pad = -(-m // bm) * bm
    Lp = _pad_to(L, n_pad, n_pad)
    if n_pad > n:  # identity on the padded diagonal keeps blocks invertible
        idx = jnp.arange(n, n_pad)
        Lp = Lp.at[idx, idx].set(1.0)
    Bp = _pad_to(B, n_pad, m_pad)
    starts = jnp.asarray(_start_blocks(meta, bm, bs, m_pad, n_pad))
    Linv = invert_diag_blocks(Lp, bs)
    with jax.named_scope("pallas:stepped_trsm"):
        Y = stepped_trsm_pallas(Linv, Lp, Bp, starts, bs=bs, bm=bm,
                                interpret=interpret)
    return Y[:n, :m]


def stepped_trsm_packed(L, B: jax.Array, meta: SteppedMeta,
                        interpret: bool = False) -> jax.Array:
    """Pallas stepped TRSM against a PACKED factor (repro.sparse.packed).

    ``L`` is a :class:`~repro.sparse.packed.PackedBlocks` whose index was
    built at the same block size as ``meta``; only the stored factor blocks
    are shipped to the kernel (plus the CSR block index in SMEM), so VMEM
    holds O(nnz_blocks·bs²) instead of the padded dense factor.
    """
    from repro.sparse.packed import PackedBlocks

    if not isinstance(L, PackedBlocks):
        raise TypeError("stepped_trsm_packed expects a PackedBlocks factor, "
                        f"got {type(L).__name__}")
    index = L.index
    bs, bm = meta.block_size, meta.rhs_block_size
    n, m = meta.n, meta.m
    if (index.bs, index.n) != (bs, n):
        raise ValueError(
            f"packed index (n={index.n}, bs={index.bs}) does not match "
            f"stepped meta (n={n}, bs={bs})")
    n_pad = index.n_pad
    m_pad = -(-m // bm) * bm
    Bp = _pad_to(B, n_pad, m_pad)
    starts = jnp.asarray(_start_blocks(meta, bm, bs, m_pad, n_pad))
    # diagonal blocks are identity-padded by construction (pack_factor /
    # block_cholesky_packed), so they are always triangular-invertible
    diag = L.values[index.diag_slots]
    eye = jnp.broadcast_to(jnp.eye(bs, dtype=diag.dtype),
                           (index.nb, bs, bs))
    Linv = jax.lax.linalg.triangular_solve(diag, eye, left_side=True,
                                           lower=True)
    with jax.named_scope("pallas:stepped_trsm_packed"):
        Y = stepped_trsm_packed_pallas(
            Linv, L.values,
            jnp.asarray(index.rowptr), jnp.asarray(index.rows),
            jnp.asarray(index.cols), Bp, starts, bs=bs, bm=bm,
            interpret=interpret)
    return Y[:n, :m]


def _mirror_lower(Fl: jax.Array, bm: int, m_pad: int, m: int) -> jax.Array:
    """Mirror the strictly-lower block triangle (diagonal tiles are full)."""
    nc = m_pad // bm
    tile_row = jnp.repeat(jnp.arange(nc), bm)
    strict = tile_row[:, None] > tile_row[None, :]
    F = Fl + jnp.where(strict, Fl, 0).T
    return F[:m, :m]


def stepped_trsm_syrk(L, B: jax.Array, meta: SteppedMeta,
                      interpret: bool = False) -> jax.Array:
    """Fused Pallas TRSM→SYRK: F = (L⁻¹B)ᵀ(L⁻¹B) in ONE kernel, the
    solution panel staying in VMEM across the stage boundary
    (stepped_trsm_syrk.py). ``L`` is a dense factor or a
    :class:`~repro.sparse.packed.PackedBlocks`; dispatches accordingly."""
    from repro.sparse.packed import PackedBlocks

    bs, bm = meta.block_size, meta.rhs_block_size
    n, m = meta.n, meta.m
    m_pad = -(-m // bm) * bm
    if isinstance(L, PackedBlocks):
        index = L.index
        if (index.bs, index.n) != (bs, n):
            raise ValueError(
                f"packed index (n={index.n}, bs={index.bs}) does not match "
                f"stepped meta (n={n}, bs={bs})")
        n_pad = index.n_pad
        Bp = _pad_to(B, n_pad, m_pad)
        starts = jnp.asarray(_start_blocks(meta, bm, bs, m_pad, n_pad))
        diag = L.values[index.diag_slots]
        eye = jnp.broadcast_to(jnp.eye(bs, dtype=diag.dtype),
                               (index.nb, bs, bs))
        Linv = jax.lax.linalg.triangular_solve(diag, eye, left_side=True,
                                               lower=True)
        with jax.named_scope("pallas:stepped_trsm_syrk_packed"):
            Fl = stepped_trsm_syrk_packed_pallas(
                Linv, L.values,
                jnp.asarray(index.rowptr), jnp.asarray(index.rows),
                jnp.asarray(index.cols), Bp, starts, bs=bs, bm=bm,
                interpret=interpret)
    else:
        n_pad = -(-n // bs) * bs
        Lp = _pad_to(L, n_pad, n_pad)
        if n_pad > n:
            idx = jnp.arange(n, n_pad)
            Lp = Lp.at[idx, idx].set(1.0)
        Bp = _pad_to(B, n_pad, m_pad)
        starts = jnp.asarray(_start_blocks(meta, bm, bs, m_pad, n_pad))
        Linv = invert_diag_blocks(Lp, bs)
        with jax.named_scope("pallas:stepped_trsm_syrk"):
            Fl = stepped_trsm_syrk_pallas(Linv, Lp, Bp, starts, bs=bs,
                                          bm=bm, interpret=interpret)
    return _mirror_lower(Fl, bm, m_pad, m)


def stepped_syrk(Y: jax.Array, meta: SteppedMeta,
                 interpret: bool = False) -> jax.Array:
    """Pallas stepped SYRK: full symmetric F = YᵀY (lower computed by the
    kernel, strict-lower blocks mirrored here)."""
    bs, bm = meta.block_size, meta.rhs_block_size
    n, m = meta.n, meta.m
    n_pad = -(-n // bs) * bs
    m_pad = -(-m // bm) * bm
    Yp = _pad_to(Y, n_pad, m_pad)
    starts = jnp.asarray(_start_blocks(meta, bm, bs, m_pad, n_pad))
    with jax.named_scope("pallas:stepped_syrk"):
        Fl = stepped_syrk_pallas(Yp, starts, bs=bs, bm=bm,
                                 interpret=interpret)
    return _mirror_lower(Fl, bm, m_pad, m)
