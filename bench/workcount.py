"""Operations and bytes of the explicit dual-operator assembly
F̃ᵢ = B̃ᵢ Kᵢ⁻¹ B̃ᵢᵀ, counted from the problem alone.

The count is the stepped algorithm's at scalar granularity, so it reads
the same work whatever block size, variant, ordering or kernel the
program picks:

  * the factor Lᵢ at its scalar fill under a fixed nested-dissection
    ordering of the subdomain's nodes (this module's own copy of the
    geometric dissection);
  * the TRSM Y = L⁻¹ B̃ᵀ with every column of B̃ᵀ dense from its first
    nonzero down: row i of column j costs one division and two flops per
    stored entry below the diagonal of column i of L;
  * the SYRK F̃ = Yᵀ Y on its lower triangle: entry (j, k) costs two flops
    per row at or below both columns' first nonzeros;
  * bytes: the factor's stored values read once, B̃ᵀ (dense from each
    column's first nonzero) read once and F̃ written once, at the storage
    dtype.
"""
from __future__ import annotations

import numpy as np

from reference import Layout, node_grid, simplices


def nested_dissection(node_shape, leaf: int = 4) -> np.ndarray:
    """Geometric nested dissection of a node grid (first axis fastest):
    ``perm[k]`` is the node eliminated k-th. Boxes with every side at
    most ``leaf`` nodes are kept whole; otherwise the longest axis is cut
    at its middle plane, the halves ordered first and the plane last."""
    strides = np.cumprod([1] + list(node_shape[:-1]))
    out = []

    def emit(box):
        grids = np.meshgrid(*[np.arange(lo, hi) for lo, hi in box],
                            indexing="ij")
        out.append(np.sort(sum(g.ravel() * s for g, s in zip(grids,
                                                              strides))))

    def dissect(box):
        sizes = [hi - lo for lo, hi in box]
        if max(sizes) <= leaf:
            emit(box)
            return
        ax = int(np.argmax(sizes))
        lo, hi = box[ax]
        mid = (lo + hi) // 2
        for part in ((lo, mid), (mid + 1, hi)):
            if part[0] < part[1]:
                dissect(box[:ax] + [part] + box[ax + 1:])
        emit(box[:ax] + [(mid, mid + 1)] + box[ax + 1:])

    dissect([(0, s) for s in node_shape])
    return np.concatenate(out).astype(np.int64)


def local_pattern(layout: Layout):
    """Strictly-lower adjacency of one subdomain's stiffness matrix in DOF
    numbering: ``adj[j]`` holds the DOFs i > j that share an element."""
    d = layout.ndpn
    elems = simplices(layout.elems_per_sub)
    dofs = (elems[:, :, None] * d + np.arange(d)).reshape(len(elems), -1)
    rows = np.repeat(dofs, dofs.shape[1], axis=1).reshape(-1)
    cols = np.tile(dofs, (1, dofs.shape[1])).reshape(-1)
    return rows, cols


def factor_column_counts(n: int, rows: np.ndarray, cols: np.ndarray,
                         perm: np.ndarray) -> np.ndarray:
    """Entries strictly below the diagonal in each column of the Cholesky
    factor of the pattern (rows, cols) ordered by ``perm``: symbolic
    elimination along the elimination tree."""
    pos = np.empty(n, np.int64)
    pos[perm] = np.arange(n)
    r, c = pos[rows], pos[cols]
    keep = r > c
    order = np.lexsort((r[keep], c[keep]))
    rc, cc = r[keep][order], c[keep][order]
    starts = np.searchsorted(cc, np.arange(n + 1))
    children = [[] for _ in range(n)]
    struct = [None] * n
    counts = np.zeros(n, np.int64)
    for j in range(n):
        s = set(rc[starts[j]:starts[j + 1]].tolist())
        for ch in children[j]:
            s |= struct[ch]
            struct[ch] = None
        s.discard(j)
        counts[j] = len(s)
        if s:
            children[min(s)].append(j)
        struct[j] = s
    return counts


def multiplier_dofs(layout: Layout) -> list:
    """Local DOF of every multiplier column of each subdomain's B̃ᵀ: a node
    on the x = 0 face is pinned once in every subdomain holding it; any
    other node shared by c subdomains is glued in a chain, so its first
    and last copies carry one multiplier and the copies between two; one
    column per component."""
    d = layout.ndpn
    nodes = layout.dof_gids[:, ::d] // d  # (S, n_nodes_local)
    S = nodes.shape[0]
    copies = np.bincount(nodes.reshape(-1), minlength=layout.n_nodes)
    rank = np.zeros_like(nodes)
    seen = np.zeros(layout.n_nodes, np.int64)
    for s in range(S):
        rank[s] = seen[nodes[s]]
        seen[nodes[s]] += 1
    on_face = node_grid(layout.shape)[:, 0] == 0
    out = []
    for s in range(S):
        c, t = copies[nodes[s]], rank[s]
        per_node = np.where(on_face[nodes[s]], 1,
                            (t > 0).astype(int) + (t < c - 1).astype(int))
        local = np.repeat(np.arange(nodes.shape[1]), per_node)
        out.append((local[:, None] * d + np.arange(d)).reshape(-1))
    return out


def assembly_work(cfg: dict, itemsize: int) -> dict:
    """Flops and bytes of assembling every subdomain's F̃ once."""
    layout = Layout(cfg)
    d, n = layout.ndpn, layout.n_local
    node_perm = nested_dissection(tuple(e + 1 for e in layout.elems_per_sub))
    perm = (node_perm[:, None] * d + np.arange(d)).reshape(-1)
    pos = np.empty(n, np.int64)
    pos[perm] = np.arange(n)
    counts = factor_column_counts(n, *local_pattern(layout), perm)
    # trsm_tail[p]: flops of one column solved from row p to the end
    trsm_tail = np.concatenate(
        [np.cumsum((1 + 2 * counts)[::-1])[::-1], [0]])
    flops = trsm = syrk = 0
    nbytes = 0
    for dofs in multiplier_dofs(layout):
        piv = np.sort(pos[dofs])
        m = len(piv)
        t = int(trsm_tail[piv].sum())
        k = int((2 * (n - piv) * np.arange(1, m + 1)).sum())
        trsm += t
        syrk += k
        nbytes += itemsize * (int(counts.sum()) + n + int((n - piv).sum())
                              + m * m)
    flops = trsm + syrk
    return dict(flops=flops, trsm_flops=trsm, syrk_flops=syrk,
                bytes=nbytes, factor_nnz=int(counts.sum()) + n)
