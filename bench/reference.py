"""The plain reference: the undecomposed problem, assembled and solved with
scipy, and the map from the solver's per-subdomain arrays to global DOFs.

Written from the problem statement alone (P1 simplices on the unit box, a
constant source or body force, homogeneous Dirichlet conditions on the
x = 0 face, subdomains a regular grid of equal boxes) and imports nothing
of the program under test. The numbering follows the layout the solver's
public arrays use:

  * nodes of a box with ``e`` elements per axis are numbered with the
    first axis fastest (``i + j (e0 + 1) + ...``), on the global mesh and
    on every subdomain alike;
  * subdomains are numbered with the LAST grid axis fastest;
  * vector problems number DOFs node-major (``node * d + component``).
"""
from __future__ import annotations

import itertools
import math

import numpy as np
import scipy.sparse as sps
import scipy.sparse.linalg as spla


def node_grid(shape) -> np.ndarray:
    """(n_nodes, dim) integer node coordinates, first axis fastest."""
    axes = [np.arange(e + 1) for e in shape]
    grids = np.meshgrid(*axes, indexing="ij")
    return np.stack([g.ravel(order="F") for g in grids], axis=1)


def node_ids(shape, idx: np.ndarray) -> np.ndarray:
    """Node number of integer node coordinates ``idx`` (…, dim)."""
    strides = np.cumprod([1] + [e + 1 for e in shape[:-1]])
    return (np.asarray(idx) * strides).sum(axis=-1)


def simplices(shape) -> np.ndarray:
    """Element connectivity of the box: each square cut along its
    (0,0)-(1,1) diagonal into two triangles; each cube into the six Kuhn
    tetrahedra that share its (0,0,0)-(1,1,1) diagonal."""
    dim = len(shape)
    cells = np.stack([g.ravel() for g in np.meshgrid(
        *[np.arange(e) for e in shape], indexing="ij")], axis=1)
    unit = np.eye(dim, dtype=np.int64)
    out = []
    for order in itertools.permutations(range(dim)):
        corners = [np.zeros(dim, np.int64)]
        for ax in order:
            corners.append(corners[-1] + unit[ax])
        out.append(np.stack([node_ids(shape, cells + c) for c in corners],
                            axis=1))
    return np.concatenate(out, axis=0)


def _gradients(coords: np.ndarray, elems: np.ndarray):
    """Shape-function gradients (ne, d+1, d) and volumes (ne,)."""
    p = coords[elems]
    D = np.swapaxes(p[:, 1:] - p[:, :1], 1, 2)
    vol = np.abs(np.linalg.det(D)) / math.factorial(coords.shape[1])
    rest = np.linalg.inv(D)
    return np.concatenate([-rest.sum(axis=1, keepdims=True), rest], 1), vol


def _strain(G: np.ndarray) -> np.ndarray:
    """Engineering strain-displacement matrices, node-major columns."""
    ne, nv, d = G.shape
    pairs = [(a, b) for a in range(d) for b in range(a + 1, d)]
    B = np.zeros((ne, d + len(pairs), nv * d))
    for v in range(nv):
        for a in range(d):
            B[:, a, v * d + a] = G[:, v, a]
        for r, (a, b) in enumerate(pairs, start=d):
            B[:, r, v * d + a] = G[:, v, b]
            B[:, r, v * d + b] = G[:, v, a]
    return B


def _lame(d: int, lam: float, mu: float) -> np.ndarray:
    """Isotropic elasticity matrix (plane strain in 2D)."""
    n_shear = d * (d - 1) // 2
    C = np.zeros((d + n_shear, d + n_shear))
    C[:d, :d] = lam
    C[range(d), range(d)] += 2 * mu
    C[range(d, d + n_shear), range(d, d + n_shear)] = mu
    return C


def element_system(problem: str, coords, elems, params: dict):
    """Element stiffness (ne, k, k), element load (ne, k) and element DOF
    ids (ne, k) of the P1 heat or linear-elasticity problem."""
    G, vol = _gradients(coords, elems)
    d = coords.shape[1]
    nv = d + 1
    if problem == "heat":
        Ke = params["kappa"] * vol[:, None, None] * np.einsum(
            "eid,ejd->eij", G, G)
        fe = np.repeat((params["source"] * vol / nv)[:, None], nv, axis=1)
        return Ke, fe, elems
    B = _strain(G)
    Ke = vol[:, None, None] * np.einsum(
        "esi,st,etj->eij", B, _lame(d, params["lam"], params["mu"]), B)
    force = np.asarray(params["body_force"], float)
    fe = (vol / nv)[:, None, None] * force[None, None, :]
    fe = np.broadcast_to(fe, (len(elems), nv, d)).reshape(len(elems), -1)
    dofs = (elems[:, :, None] * d + np.arange(d)).reshape(len(elems), -1)
    return Ke, fe, dofs


class Layout:
    """The decomposition's geometry: global mesh, subdomain boxes, and the
    global DOF of every (subdomain, local DOF)."""

    def __init__(self, cfg: dict):
        self.problem = cfg["problem"]
        self.sub_grid = tuple(cfg["sub_grid"])
        self.elems_per_sub = tuple(cfg["elems_per_sub"])
        self.dim = len(self.sub_grid)
        self.ndpn = 1 if self.problem == "heat" else self.dim
        self.shape = tuple(s * e for s, e in
                           zip(self.sub_grid, self.elems_per_sub))
        self.n_nodes = int(np.prod([e + 1 for e in self.shape]))
        self.n_dofs = self.n_nodes * self.ndpn
        local = node_grid(self.elems_per_sub)
        gids = []
        for box in itertools.product(*[range(s) for s in self.sub_grid]):
            offset = np.asarray(box) * np.asarray(self.elems_per_sub)
            nodes = node_ids(self.shape, local + offset)
            gids.append((nodes[:, None] * self.ndpn
                         + np.arange(self.ndpn)).reshape(-1))
        self.dof_gids = np.stack(gids)  # (S, n)

    @property
    def n_subdomains(self) -> int:
        return self.dof_gids.shape[0]

    @property
    def n_local(self) -> int:
        return self.dof_gids.shape[1]

    def global_load(self, loads: np.ndarray) -> np.ndarray:
        """(..., S, n) per-subdomain loads -> (..., n_dofs): interface
        copies add up, as the subdomain elements partition the mesh."""
        lead = loads.shape[:-2]
        flat = loads.reshape(lead + (-1,))
        out = np.zeros(lead + (self.n_dofs,))
        idx = self.dof_gids.reshape(-1)
        for k in np.ndindex(*lead):
            out[k] = np.bincount(idx, weights=flat[k],
                                 minlength=self.n_dofs)
        return out

    def base_load_scale(self, params: dict) -> float:
        """max |f| of the per-subdomain body loads: the scale random load
        cases are drawn at."""
        shape = self.elems_per_sub
        coords = node_grid(shape) / np.asarray(self.shape, float)
        _, fe, dofs = element_system(self.problem, coords, simplices(shape),
                                     params)
        f = np.bincount(dofs.reshape(-1), weights=fe.reshape(-1),
                        minlength=self.n_local)
        return float(np.abs(f).max())


class Reference:
    """The undecomposed system with its Dirichlet face removed, factorized
    once; :meth:`solve` answers any number of per-subdomain load cases."""

    def __init__(self, cfg: dict):
        self.layout = lay = Layout(cfg)
        coords = node_grid(lay.shape) / np.asarray(lay.shape, float)
        Ke, _, dofs = element_system(lay.problem, coords,
                                     simplices(lay.shape), cfg["params"])
        k = dofs.shape[1]
        K = sps.csc_matrix((Ke.reshape(-1), (np.repeat(dofs, k, axis=1)
                                             .reshape(-1),
                                             np.tile(dofs, (1, k))
                                             .reshape(-1))),
                           shape=(lay.n_dofs, lay.n_dofs))
        fixed = np.zeros(lay.n_dofs, bool)
        face = np.flatnonzero(node_grid(lay.shape)[:, 0] == 0)
        fixed[(face[:, None] * lay.ndpn + np.arange(lay.ndpn)).reshape(-1)] \
            = True
        self.free = np.flatnonzero(~fixed)
        self._lu = spla.splu(K[self.free][:, self.free].tocsc())

    def solve(self, loads: np.ndarray) -> np.ndarray:
        """(r, S, n) load cases -> (r, n_dofs) global solutions."""
        f = self.layout.global_load(loads)[:, self.free]
        u = np.zeros(f.shape[:1] + (self.layout.n_dofs,))
        u[:, self.free] = self._lu.solve(np.ascontiguousarray(f.T)).T
        return u


def rel_err(u: np.ndarray, u_ref: np.ndarray) -> float:
    """max |u - u_ref| / max |u_ref|: the accuracy the configuration
    states for every answer."""
    return float(np.max(np.abs(u - u_ref)) / np.max(np.abs(u_ref)))
