"""Packed block-sparse factor storage: the symbolic fill mask AS the layout.

The symbolic stage (symbolic.py) produces the block fill mask of the
Cholesky factor. Everywhere else in the pipeline that mask used to be a
*FLOP filter* — structurally-zero blocks were skipped, but every factor was
still materialized as a dense ``(n, n)`` device array. This module makes
the mask the *storage layout*: the factor lives as a stacked
``(n_blocks, bs, bs)`` value array plus a static host-side block index, so
device memory drops from O(n²) to O(nnz_blocks · bs²) per subdomain — the
lever that bounds subdomain size on real accelerators (cf. Cheik Ahamed &
Magoulès, arXiv:2108.13162: storage, not FLOPs, limits GPU sub-structuring).

Layout invariants (relied on by the Pallas packed TRSM kernel):

  * blocks are lower-triangular (``col <= row``) on a uniform ``bs`` grid
    padded to ``nb = ceil(n / bs)`` blocks per side;
  * slots are sorted by ``(row, col)`` — row-major CSR-like order — so the
    **diagonal block is the last slot of its row** and ``rowptr`` gives each
    row's contiguous slot range;
  * padded rows/columns beyond ``n`` carry an identity diagonal (factors)
    or zeros (general matrices), so every stored value is exact: packing
    then unpacking reproduces the dense array bit-for-bit.

All index arrays are host-side numpy (compile-time constants inside jit —
the symbolic/numeric split of paper §2.2); only ``values`` lives on device.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.precision import einsum, mm

__all__ = [
    "PackedBlockIndex",
    "PackedBlocks",
    "pack_factor",
    "block_cholesky_packed",
    "packed_tri_solve",
    "packed_symm_matvec",
    "packed_block_index_for",
]


class PackedBlockIndex:
    """Static block index of a packed lower-triangular block layout.

    Attributes:
      n: unpadded matrix dimension.
      bs: uniform block size.
      nb: blocks per side (``ceil(n / bs)``).
      rows / cols: (n_blocks,) block coordinates, sorted by (row, col).
      rowptr: (nb + 1,) CSR-style row pointers into the slot axis.
      slot_table: (nb, nb) slot of block (i, j), -1 where absent.
    """

    def __init__(self, mask: np.ndarray, n: int, bs: int):
        mask = np.asarray(mask, dtype=bool)
        nb = -(-n // bs)
        if mask.shape != (nb, nb):
            raise ValueError(f"mask shape {mask.shape} != ({nb},{nb})")
        mask = np.tril(mask).copy()
        # diagonal blocks must always exist (factorization pivots / padding)
        np.fill_diagonal(mask, True)
        rows, cols = np.nonzero(mask)  # np.nonzero is row-major == (row, col)
        self.n = int(n)
        self.bs = int(bs)
        self.nb = int(nb)
        self.rows = rows.astype(np.int32)
        self.cols = cols.astype(np.int32)
        self.rowptr = np.concatenate(
            [[0], np.cumsum(np.bincount(rows, minlength=nb))]
        ).astype(np.int32)
        table = np.full((nb, nb), -1, dtype=np.int32)
        table[rows, cols] = np.arange(len(rows), dtype=np.int32)
        self.slot_table = table
        self.mask = mask
        self._digest = (self.n, self.bs, self.rows.tobytes(),
                        self.cols.tobytes())

    # -- constructors ------------------------------------------------------

    @classmethod
    def from_mask(cls, mask: np.ndarray, n: int, bs: int) -> "PackedBlockIndex":
        """Index from a symbolic block fill mask (block_symbolic_cholesky)."""
        return cls(mask, n, bs)

    @classmethod
    def full(cls, n: int, bs: int) -> "PackedBlockIndex":
        """All lower-triangular blocks present (no sparsity information)."""
        nb = -(-n // bs)
        return cls(np.tril(np.ones((nb, nb), dtype=bool)), n, bs)

    # -- basic accessors ---------------------------------------------------

    @property
    def n_blocks(self) -> int:
        return len(self.rows)

    @property
    def n_pad(self) -> int:
        return self.nb * self.bs

    @property
    def diag_slots(self) -> np.ndarray:
        """(nb,) slot of each diagonal block (last slot of its row)."""
        return self.rowptr[1:] - 1

    def slot(self, i: int, j: int) -> int:
        """Slot of block (i, j); raises KeyError when structurally absent."""
        s = int(self.slot_table[i, j])
        if s < 0:
            raise KeyError(f"block ({i},{j}) not in packed layout")
        return s

    def row_slots(self, k: int) -> list[tuple[int, int]]:
        """[(j, slot)] of the strictly-subdiagonal blocks in row k (j < k)."""
        lo, hi = int(self.rowptr[k]), int(self.rowptr[k + 1]) - 1
        return [(int(self.cols[t]), t) for t in range(lo, hi)]

    def col_slots(self, k: int) -> list[tuple[int, int]]:
        """[(i, slot)] of the strictly-subdiagonal blocks in column k (i > k)."""
        col = self.slot_table[k + 1:, k]
        return [(k + 1 + i, int(s)) for i, s in enumerate(col) if s >= 0]

    # -- memory accounting -------------------------------------------------

    def packed_nbytes(self, dtype_bytes: int = 8) -> int:
        """Device bytes of ONE packed matrix's value array."""
        return self.n_blocks * self.bs * self.bs * dtype_bytes

    def dense_nbytes(self, dtype_bytes: int = 8) -> int:
        """Device bytes of the dense (n, n) array this layout replaces."""
        return self.n * self.n * dtype_bytes

    # -- pack / unpack (jit-friendly; arbitrary leading batch dims) --------

    def pack(self, A: jax.Array, diag_identity_pad: bool = False) -> jax.Array:
        """Gather the stored blocks of dense ``A`` (..., n, n) into
        (..., n_blocks, bs, bs) values.

        ``diag_identity_pad`` puts 1s on the padded tail of the diagonal
        (keeps factor diagonal blocks triangular-invertible and SPD inputs
        factorizable); the off-diagonal padding is always zero.
        """
        lead = A.shape[:-2]
        if A.shape[-2:] != (self.n, self.n):
            raise ValueError(f"expected (..., {self.n}, {self.n}), "
                             f"got {A.shape}")
        pad = self.n_pad - self.n
        if pad:
            A = jnp.pad(A, [(0, 0)] * len(lead) + [(0, pad), (0, pad)])
            if diag_identity_pad:
                idx = jnp.arange(self.n, self.n_pad)
                A = A.at[..., idx, idx].set(1.0)
        blocks = A.reshape(*lead, self.nb, self.bs, self.nb, self.bs)
        blocks = jnp.swapaxes(blocks, -3, -2)  # (..., nb, nb, bs, bs)
        return blocks[..., self.rows, self.cols, :, :]

    def pack_host(self, A: np.ndarray, dtype=None,
                  diag_identity_pad: bool = False,
                  perm: Optional[np.ndarray] = None) -> np.ndarray:
        """Host (numpy) twin of :meth:`pack` for ONE dense ``(n, n)``
        matrix — of ``A[perm][:, perm]`` when ``perm`` is given. The stored
        blocks' entries are gathered straight from ``A`` (padding reads as
        zero), so neither the permuted nor the padded matrix is ever
        formed: setup data is packed on the host one subdomain at a time,
        and no dense stack is built for it or shipped to a device."""
        A = np.asarray(A)
        if A.shape != (self.n, self.n):
            raise ValueError(f"expected ({self.n}, {self.n}), got {A.shape}")
        pos = np.arange(self.n_pad)
        src = pos if perm is None else np.concatenate(
            [np.asarray(perm), pos[self.n:]])
        valid = src < self.n
        src = np.where(valid, src, 0)
        local = np.arange(self.bs)
        r = self.rows[:, None] * self.bs + local  # (n_blocks, bs) positions
        c = self.cols[:, None] * self.bs + local
        vals = A[src[r][:, :, None], src[c][:, None, :]]
        vals = np.where(valid[r][:, :, None] & valid[c][:, None, :], vals, 0)
        vals = vals.astype(dtype or A.dtype)
        if diag_identity_pad and self.n_pad > self.n:
            tail = local[self.n - (self.nb - 1) * self.bs:]
            vals[self.diag_slots[-1], tail, tail] = 1.0
        return vals

    def unpack(self, values: jax.Array) -> jax.Array:
        """Scatter (..., n_blocks, bs, bs) values back to dense (..., n, n).

        Unstored blocks come back as exact zeros; the padded tail (including
        any identity diagonal padding) is trimmed away.
        """
        lead = values.shape[:-3]
        if values.shape[-3:] != (self.n_blocks, self.bs, self.bs):
            raise ValueError(
                f"expected (..., {self.n_blocks}, {self.bs}, {self.bs}), "
                f"got {values.shape}")
        grid = jnp.zeros(lead + (self.nb, self.nb, self.bs, self.bs),
                         values.dtype)
        grid = grid.at[..., self.rows, self.cols, :, :].set(values)
        dense = grid.swapaxes(-3, -2).reshape(
            *lead, self.n_pad, self.n_pad)
        return dense[..., : self.n, : self.n]

    def unpack_symmetric(self, values: jax.Array) -> jax.Array:
        """Dense (..., n, n) symmetric matrix whose lower block triangle is
        stored in ``values`` (diagonal blocks whole): the strictly lower
        blocks are mirrored into the upper triangle."""
        lead = values.shape[:-3]
        strict = np.flatnonzero(self.rows != self.cols)
        grid = jnp.zeros(lead + (self.nb, self.nb, self.bs, self.bs),
                         values.dtype)
        grid = grid.at[..., self.rows, self.cols, :, :].set(values)
        grid = grid.at[..., self.cols[strict], self.rows[strict], :, :].set(
            jnp.swapaxes(values[..., strict, :, :], -1, -2))
        dense = grid.swapaxes(-3, -2).reshape(
            *lead, self.n_pad, self.n_pad)
        return dense[..., : self.n, : self.n]

    def validate(self, values) -> None:
        """Shape-check a value array (batched or not) against this index."""
        shape = jnp.shape(values)
        if len(shape) < 3 or shape[-3:] != (self.n_blocks, self.bs, self.bs):
            raise ValueError(
                f"values shape {shape} does not end in "
                f"({self.n_blocks}, {self.bs}, {self.bs})")

    # -- identity (static-arg hashability for jit) -------------------------

    def __hash__(self):
        return hash(self._digest)

    def __eq__(self, other):
        return (isinstance(other, PackedBlockIndex)
                and self._digest == other._digest)

    def __repr__(self):
        dense_blocks = self.nb * (self.nb + 1) // 2
        return (f"PackedBlockIndex(n={self.n}, bs={self.bs}, nb={self.nb}, "
                f"n_blocks={self.n_blocks}/{dense_blocks})")


@dataclasses.dataclass
class PackedBlocks:
    """A packed block-sparse matrix (or a stack of them): device values +
    static index. Registered as a pytree with the index as static aux data,
    so it flows through jit / vmap / shard_map like a plain array (the
    leading batch axis, if any, lives on ``values``)."""

    values: jax.Array  # (..., n_blocks, bs, bs)
    index: PackedBlockIndex

    @property
    def nbytes(self) -> int:
        return int(np.prod(jnp.shape(self.values))
                   * jnp.result_type(self.values).itemsize)

    @property
    def batch_shape(self) -> tuple:
        return jnp.shape(self.values)[:-3]

    def unpack(self) -> jax.Array:
        return self.index.unpack(self.values)

    def tree_flatten(self):
        return (self.values,), self.index

    @classmethod
    def tree_unflatten(cls, index, children):
        return cls(children[0], index)


jax.tree_util.register_pytree_node(
    PackedBlocks,
    lambda pb: pb.tree_flatten(),
    PackedBlocks.tree_unflatten,
)


def pack_factor(L: jax.Array, index: PackedBlockIndex) -> PackedBlocks:
    """Pack a dense lower-triangular factor (..., n, n) into the layout,
    identity-padding the diagonal tail so every diagonal block stays
    triangular-invertible."""
    return PackedBlocks(index.pack(L, diag_identity_pad=True), index)


def _solve_lower_right(Lkk: jax.Array, W: jax.Array) -> jax.Array:
    """Solve X Lkkᵀ = W for X (i.e. X = W Lkk⁻ᵀ)."""
    return jax.lax.linalg.triangular_solve(
        Lkk, W, left_side=False, lower=True, transpose_a=True
    )


def block_cholesky_packed(K, index: PackedBlockIndex) -> PackedBlocks:
    """Cholesky factor of SPD ``K`` computed AND stored in packed form.

    ``K`` is the dense (n, n) matrix, or its lower block triangle already
    packed in this layout (a :class:`PackedBlocks` whose diagonal tail is
    identity-padded, e.g. from :meth:`PackedBlockIndex.pack_host`) — then
    no dense matrix exists at any point.

    The numerical twin of :func:`repro.sparse.cholesky.block_cholesky` with
    ``mask=index.mask``: the diagonal/panel/update loops walk the static
    block list instead of slicing a dense working matrix, so no (n, n)
    factor is ever materialized. Per-block operations are identical to the
    dense-masked path (padding contributes exact zeros / an exact identity),
    so the stored blocks match it bit-for-bit.
    """
    if isinstance(K, PackedBlocks):
        vals = K.values
    else:
        vals = index.pack(K, diag_identity_pad=True)
    nb = index.nb
    for k in range(nb):
        dk = index.slot(k, k)
        Lkk = jnp.linalg.cholesky(vals[dk])
        vals = vals.at[dk].set(Lkk)
        below = index.col_slots(k)
        panels = {}
        for i, s in below:
            Lik = _solve_lower_right(Lkk, vals[s])
            vals = vals.at[s].set(Lik)
            panels[i] = Lik
        for i, _ in below:
            for j, _ in below:
                if j > i:
                    break
                # symbolic fill guarantees (i, j) is stored: i, j share
                # column k, so eliminating k fills their pairing
                vals = vals.at[index.slot(i, j)].add(
                    -mm(panels[i], panels[j].T))
    return PackedBlocks(vals, index)


def packed_tri_solve(pb: PackedBlocks, b: jax.Array,
                     transpose: bool = False) -> jax.Array:
    """Solve ``L x = b`` (or ``Lᵀ x = b``) with a packed factor, one (n,)
    right-hand side. Block forward/backward substitution over the static
    slot lists; batch with ``jax.vmap`` (see feti.operator.solve_with_factor).
    """
    index = pb.index
    vals = pb.values
    n, bs, nb = index.n, index.bs, index.nb
    pad = index.n_pad - n
    if pad:
        b = jnp.concatenate([b, jnp.zeros((pad,), b.dtype)])
    x = b.reshape(nb, bs)
    if not transpose:
        # forward: x_k = L_kk^{-1} (b_k - sum_{j<k} L_kj x_j)
        for k in range(nb):
            acc = x[k]
            for j, s in index.row_slots(k):
                acc = acc - mm(vals[s], x[j])
            xk = jax.lax.linalg.triangular_solve(
                vals[index.slot(k, k)], acc[:, None],
                left_side=True, lower=True)[:, 0]
            x = x.at[k].set(xk)
    else:
        # backward: x_k = L_kk^{-T} (b_k - sum_{i>k} L_ik^T x_i)
        for k in range(nb - 1, -1, -1):
            acc = x[k]
            for i, s in index.col_slots(k):
                acc = acc - mm(vals[s].T, x[i])
            xk = jax.lax.linalg.triangular_solve(
                vals[index.slot(k, k)], acc[:, None],
                left_side=True, lower=True, transpose_a=True)[:, 0]
            x = x.at[k].set(xk)
    return x.reshape(-1)[:n]


def packed_symm_matvec(pb: PackedBlocks, v: jax.Array) -> jax.Array:
    """``A @ v`` for a symmetric matrix stored as its packed lower triangle.

    Fully vectorized: one batched GEMV over all stored blocks scattered into
    the block rows, plus the transposed contribution of the strictly-lower
    blocks scattered into the block columns.
    """
    index = pb.index
    vals = pb.values
    n, bs, nb = index.n, index.bs, index.nb
    pad = index.n_pad - n
    if pad:
        v = jnp.concatenate([v, jnp.zeros((pad,), v.dtype)])
    vb = v.reshape(nb, bs)
    out = jnp.zeros((nb, bs), v.dtype)
    out = out.at[index.rows].add(
        einsum("bij,bj->bi", vals, vb[index.cols]))
    strict = np.flatnonzero(index.rows != index.cols)
    if strict.size:
        out = out.at[index.cols[strict]].add(
            einsum("bji,bj->bi", vals[strict], vb[index.rows[strict]]))
    return out.reshape(-1)[:n]


def packed_block_index_for(mask: Optional[np.ndarray], n: int, bs: int
                           ) -> PackedBlockIndex:
    """Index from a fill mask, or the full lower triangle when no symbolic
    information is available (packed storage then still works — it is just
    not smaller than dense)."""
    if mask is None:
        return PackedBlockIndex.full(n, bs)
    return PackedBlockIndex.from_mask(mask, n, bs)
