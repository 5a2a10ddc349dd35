"""P1 finite-element assembly for the scalar heat-transfer (Laplace)
problem and vector-valued linear elasticity.

Element stiffness and scatter-assembly are host-side numpy (vectorized
over elements): they build setup data, which never belongs on an
accelerator. A scipy CSR path serves as the reference oracle for
validating the FETI solve against an undecomposed global solve.

Vector problems use node-blocked DOF numbering: DOF ``node * d + c`` is
component ``c`` of ``node`` (d = 2 or 3 components per node). The scatter
assemblers are index-generic, so both problems share them through
:func:`element_dofs`.
"""
from __future__ import annotations

import math

import numpy as np
import scipy.sparse as sps

__all__ = [
    "p1_element_stiffness",
    "p1_elasticity_stiffness",
    "elasticity_matrix",
    "element_dofs",
    "load_vector",
    "elasticity_load_vector",
    "assemble_dense",
    "assemble_scipy_csr",
]


def _p1_gradients(coords, elems, dtype=np.float64):
    """Barycentric shape-function gradients and volumes, per element.

    For a simplex with vertices p0..pd, ``g_j = rows of inv(D)`` for j>=1
    (``D[:, j-1] = p_j - p_0``) and ``g_0 = -sum_j g_j``.

    Returns ``(G, vol)`` with G: (n_elems, d+1, d) and vol: (n_elems,).
    """
    coords = np.asarray(coords, dtype=dtype)
    elems = np.asarray(elems)
    d = coords.shape[1]
    p = coords[elems]  # (ne, d+1, d)
    D = np.swapaxes(p[:, 1:, :] - p[:, :1, :], 1, 2)  # (ne, d, d)
    vol = np.abs(np.linalg.det(D)) / math.factorial(d)
    g_rest = np.linalg.inv(D)  # (ne, d, d); rows are g_1..g_d
    g0 = -np.sum(g_rest, axis=1, keepdims=True)  # (ne, 1, d)
    G = np.concatenate([g0, g_rest], axis=1)  # (ne, d+1, d)
    return G, vol


def p1_element_stiffness(coords, elems, kappa: float = 1.0, dtype=np.float64):
    """Per-element P1 heat stiffness ``Ke = kappa * vol * G Gᵀ``,
    vectorized over elements. Returns (n_elems, d+1, d+1)."""
    G, vol = _p1_gradients(coords, elems, dtype=dtype)
    return kappa * vol[:, None, None] * np.einsum("eid,ejd->eij", G, G)


def elasticity_matrix(dim: int, lam: float = 1.0, mu: float = 1.0,
                      dtype=np.float64):
    """Isotropic elasticity matrix C in Voigt notation (Lamé parameters).

    2D is plane strain (3 strain components: εxx, εyy, γxy); 3D has the
    full 6 (εxx, εyy, εzz, γxy, γyz, γxz). Shear rows use engineering
    strain, so the shear diagonal is μ.
    """
    if dim == 2:
        C = [[lam + 2 * mu, lam, 0.0],
             [lam, lam + 2 * mu, 0.0],
             [0.0, 0.0, mu]]
    elif dim == 3:
        C = [[lam + 2 * mu, lam, lam, 0, 0, 0],
             [lam, lam + 2 * mu, lam, 0, 0, 0],
             [lam, lam, lam + 2 * mu, 0, 0, 0],
             [0, 0, 0, mu, 0, 0],
             [0, 0, 0, 0, mu, 0],
             [0, 0, 0, 0, 0, mu]]
    else:
        raise ValueError("elasticity supports dim 2 or 3")
    return np.asarray(C, dtype=dtype)


def _strain_displacement(G):
    """Element strain-displacement matrices B: (ne, n_strain, (d+1)*d).

    Node-blocked column order (node-major, component-minor), matching
    :func:`element_dofs`. Constant per element for P1.
    """
    ne, d1, d = G.shape
    if d == 2:
        # rows: εxx, εyy, γxy
        B = np.zeros((ne, 3, d1 * 2), G.dtype)
        for a in range(d1):
            gx, gy = G[:, a, 0], G[:, a, 1]
            B[:, 0, 2 * a + 0] = gx
            B[:, 1, 2 * a + 1] = gy
            B[:, 2, 2 * a + 0] = gy
            B[:, 2, 2 * a + 1] = gx
    else:
        # rows: εxx, εyy, εzz, γxy, γyz, γxz
        B = np.zeros((ne, 6, d1 * 3), G.dtype)
        for a in range(d1):
            gx, gy, gz = G[:, a, 0], G[:, a, 1], G[:, a, 2]
            B[:, 0, 3 * a + 0] = gx
            B[:, 1, 3 * a + 1] = gy
            B[:, 2, 3 * a + 2] = gz
            B[:, 3, 3 * a + 0] = gy
            B[:, 3, 3 * a + 1] = gx
            B[:, 4, 3 * a + 1] = gz
            B[:, 4, 3 * a + 2] = gy
            B[:, 5, 3 * a + 0] = gz
            B[:, 5, 3 * a + 2] = gx
    return B


def p1_elasticity_stiffness(coords, elems, lam: float = 1.0, mu: float = 1.0,
                            dtype=np.float64):
    """Per-element P1 linear-elasticity stiffness ``Ke = vol * Bᵀ C B``.

    Returns (n_elems, (d+1)*d, (d+1)*d) in node-blocked DOF order; scatter
    with ``element_dofs(elems, d)`` through the same assemblers as heat.
    """
    G, vol = _p1_gradients(coords, elems, dtype=dtype)
    d = G.shape[2]
    C = elasticity_matrix(d, lam, mu, dtype=G.dtype)
    B = _strain_displacement(G)
    return vol[:, None, None] * np.einsum("esi,st,etj->eij", B, C, B)


def element_dofs(elems, ndof_per_node: int) -> np.ndarray:
    """Expand node connectivity (ne, d+1) to DOF connectivity
    (ne, (d+1)*ndpn) in node-blocked order (DOF = node*ndpn + c)."""
    elems = np.asarray(elems)
    if ndof_per_node == 1:
        return elems
    return (elems[:, :, None] * ndof_per_node
            + np.arange(ndof_per_node)).reshape(elems.shape[0], -1)


def load_vector(coords, elems, n_nodes: int, source: float = 1.0,
                dtype=np.float64):
    """Consistent P1 load vector for a constant source term."""
    coords = np.asarray(coords, dtype=dtype)
    elems_j = np.asarray(elems)
    d = coords.shape[1]
    p = coords[elems_j]
    D = np.swapaxes(p[:, 1:, :] - p[:, :1, :], 1, 2)
    vol = np.abs(np.linalg.det(D)) / math.factorial(d)
    contrib = (source / (d + 1)) * vol  # per vertex of each element
    f = np.zeros((n_nodes,), dtype=dtype)
    for v in range(d + 1):
        np.add.at(f, elems_j[:, v], contrib)
    return f


def elasticity_load_vector(coords, elems, n_nodes: int, body_force,
                           dtype=np.float64):
    """Consistent P1 load for a constant body force (d components).

    Returns the (n_nodes * d,) node-blocked DOF load vector.
    """
    body_force = np.asarray(body_force, dtype=dtype)
    d = len(body_force)
    comps = [load_vector(coords, elems, n_nodes, source=float(body_force[c]),
                         dtype=dtype) for c in range(d)]
    return np.stack(comps, axis=1).reshape(n_nodes * d)


def assemble_dense(n_dofs: int, elems, Ke, dtype=None) -> np.ndarray:
    """Scatter per-element stiffness into a dense (n, n) numpy matrix.

    ``elems`` is any per-element index array (node connectivity for scalar
    problems, :func:`element_dofs` output for vector problems).
    """
    Ke = np.asarray(Ke)
    return assemble_scipy_csr(n_dofs, elems, Ke).toarray().astype(
        dtype or Ke.dtype)


def assemble_scipy_csr(n_dofs: int, elems, Ke) -> sps.csr_matrix:
    """CSR assembly (duplicates summed): the reference oracle's global
    matrix, and the source of :func:`assemble_dense`."""
    elems = np.asarray(elems)
    Ke = np.asarray(Ke)
    d1 = elems.shape[1]
    rows = np.repeat(elems, d1, axis=1).reshape(-1)
    cols = np.tile(elems, (1, d1)).reshape(-1)
    K = sps.coo_matrix((Ke.reshape(-1), (rows, cols)), shape=(n_dofs, n_dofs))
    return K.tocsr()
