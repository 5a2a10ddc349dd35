"""Dirichlet preconditioner: the *primal* boundary/interior Schur pipeline.

The FETI Dirichlet preconditioner

    M⁻¹ = Σᵢ B̃ᵢ S_b,i B̃ᵢᵀ,   S_b = K_bb − K_bi K_ii⁻¹ K_ib

is a second family of Schur complements, assembled per subdomain onto the
*boundary* DOFs (the rows B̃ᵀ touches) instead of onto the multipliers
(ESPRESO lineage: Homola et al., "Assembly of the FETI dual operator using
CUDA", arXiv:2502.08382). With L_ii the Cholesky factor of K_ii,

    K_bi K_ii⁻¹ K_ib = (L_ii⁻¹ K_ib)ᵀ (L_ii⁻¹ K_ib)

is exactly the TRSM+SYRK product the dual-operator assembly computes
(paper eq. 14) with K_ib as the sparse right-hand side — so this module
*reuses* :func:`repro.core.schur.make_assembler` verbatim: the interior
gets its own fill-reducing ordering and symbolic block fill mask, K_ib gets
its own stepped column metadata, and the whole dense/packed × TRSM/SYRK ×
block-size × Pallas design space (and the autotuner that searches it)
applies to the preconditioner stage unchanged.

Everything here is host-side symbolic analysis plus jit-friendly builders;
:func:`repro.feti.assembly.preprocess_cluster` threads them into the
batched (and optionally ``shard_map``-sharded) preprocessing program, and
:func:`repro.feti.operator.dirichlet_preconditioner` applies the stored
S_b stack inside PCPG. See docs/preconditioners.md for the cost model and
when the extra assembly amortizes.

Conventions:

* **Boundary** = every DOF carrying a B̃ᵀ row in *any* subdomain of the
  cluster (all subdomains share one local topology, so the split is shared
  and the cluster batches through one compiled program). Gluing is
  per-node-copy, so for vector problems the split is node-blocked: all
  ``ndof_per_node`` components of a node land on the same side.
* **Interior** DOFs are ordered by the restriction of the subdomain's
  fill-reducing node ordering (:mod:`repro.sparse.ordering`); boundary
  DOFs keep their original (node-blocked) order, so ``B̃ᵀ[boundary]``
  needs no column bookkeeping beyond the row restriction.
* A subdomain at the cluster's outer surface has faces the union classes
  as boundary but that carry none of ITS multipliers. The true Dirichlet
  preconditioner eliminates those too, so after the shared sparse
  assembly a per-subdomain **own-boundary restriction** (Schur complements
  compose) eliminates each subdomain's spurious boundary DOFs as a dense
  batched epilogue — the per-subdomain variation lives in a 0/1 *value*
  mask, never in the compiled structure
  (:func:`restrict_own_boundary`). Measured on the elasticity oracle
  cases this is what pushes the Dirichlet iteration counts strictly below
  lumped's (docs/preconditioners.md §Own-boundary).
* S_b is assembled from the **unregularized** K — K_ii is SPD outright
  (a rigid mode vanishing on the whole boundary is zero), and the
  fixing-DOF regularization would perturb S_b by ρ on boundary diagonal
  entries (elasticity places its fixing DOFs on corner nodes), measurably
  degrading the preconditioner. Assembling from a regularized K remains
  supported for the SPD-variant tests.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.precision import mm
from repro.core import SchurAssemblyConfig, build_stepped_meta, make_assembler
from repro.core.stepped import SteppedMeta, column_pivots
from repro.fem.decomposition import FetiProblem
from repro.fem.meshgen import structured_mesh
from repro.sparse import (
    block_pattern,
    block_symbolic_cholesky,
    matrix_pattern_from_elems,
    node_ordering,
)
from repro.sparse.cholesky import block_cholesky
from repro.sparse.packed import PackedBlockIndex, block_cholesky_packed

__all__ = [
    "BoundaryInteriorSplit",
    "boundary_interior_split",
    "dirichlet_symbolic",
    "make_dirichlet_assembler",
    "own_boundary_masks",
    "restrict_own_boundary",
    "assemble_dirichlet_schur",
    "dirichlet_fingerprint",
]


@dataclasses.dataclass(frozen=True)
class BoundaryInteriorSplit:
    """The shared boundary/interior partition of one cluster's local DOFs.

    ``interior`` is already in the interior fill-reducing elimination
    order; ``boundary`` is in ascending original (node-blocked) DOF order.
    ``dperm = [interior; boundary]`` is the row/column permutation that
    brings every subdomain's K into the 2x2 primal Schur layout.
    """

    n: int  # local DOFs per subdomain
    interior: np.ndarray  # (n_i,) original DOF ids, fill-reducing order
    boundary: np.ndarray  # (n_b,) original DOF ids, ascending

    @property
    def n_i(self) -> int:
        return len(self.interior)

    @property
    def n_b(self) -> int:
        return len(self.boundary)

    @property
    def dperm(self) -> np.ndarray:
        return np.concatenate([self.interior, self.boundary])

    def validate_partition(self) -> None:
        """boundary ∪ interior = all DOFs, disjoint (tested property)."""
        both = np.concatenate([self.interior, self.boundary])
        if len(both) != self.n or len(np.unique(both)) != self.n:
            raise ValueError("boundary/interior do not partition the DOFs")


def boundary_interior_split(
    problem: FetiProblem,
    ordering: str = "nd",
    dof_perm: Optional[np.ndarray] = None,
) -> BoundaryInteriorSplit:
    """Classify the cluster's local DOFs as boundary (any B̃ᵀ row across
    the cluster's subdomains) vs interior, node-blocked for vector DOFs.

    Using the *union* over subdomains keeps the split (and with it the
    symbolic products and the compiled program) shared: a superset of one
    subdomain's true boundary only grows its S_b — applying B̃ S_b B̃ᵀ
    still reads exactly the rows that subdomain's B̃ᵀ touches.

    ``dof_perm`` is the expanded fill-reducing DOF permutation. The
    cluster preprocessor passes the ONE it already computed (the stage
    graph computes each symbolic product exactly once — this function used
    to silently rebuild ``node_ordering`` + ``expand_node_perm``, a
    duplication that could drift); ``dof_perm=None`` rebuilds it from
    ``ordering`` for standalone use and must produce the identical order.
    """
    subs = problem.subdomains
    n = subs[0].n
    ndpn = problem.ndof_per_node
    bmask = np.zeros(n, dtype=bool)
    for sd in subs:
        bmask[sd.b_rows[: sd.m]] = True
    if ndpn > 1:
        # node-blocked closure (gluing/pinning is per node copy, so this is
        # a no-op on well-formed decompositions — but it guarantees the
        # packed layout's node blocks never straddle the split)
        node_b = bmask.reshape(-1, ndpn).any(axis=1)
        bmask = np.repeat(node_b, ndpn)
    if not bmask.any():
        raise ValueError("no boundary DOFs: the decomposition has no "
                         "multipliers, so there is nothing to precondition")

    if dof_perm is None:
        node_shape = tuple(e + 1 for e in problem.elems_per_sub)
        nperm = node_ordering(node_shape, ordering)
        from repro.feti.assembly import expand_node_perm

        dof_perm = expand_node_perm(nperm, ndpn)
    elif len(dof_perm) != n:
        raise ValueError(f"dof_perm has {len(dof_perm)} entries for {n} "
                         "local DOFs")
    # restriction of the fill-reducing order to the interior subgraph:
    # interior nodes keep their relative elimination order, which preserves
    # the separator structure (and hence the low fill) on the sub-box
    interior = dof_perm[~bmask[dof_perm]]
    boundary = np.flatnonzero(bmask).astype(np.int64)
    split = BoundaryInteriorSplit(n=n, interior=interior, boundary=boundary)
    split.validate_partition()
    return split


def _local_dof_pattern(problem: FetiProblem) -> np.ndarray:
    """Dense boolean pattern of one subdomain's K in original DOF order."""
    from repro.feti.assembly import expand_node_pattern

    ndpn = problem.ndof_per_node
    lmesh = structured_mesh(problem.elems_per_sub)
    npat = matrix_pattern_from_elems(lmesh.n_nodes, lmesh.elems)
    return expand_node_pattern(npat, ndpn)


def dirichlet_symbolic(
    problem: FetiProblem,
    split: BoundaryInteriorSplit,
    block_size: int,
    rhs_block_size: Optional[int] = None,
    kpat: Optional[np.ndarray] = None,
) -> Tuple[SteppedMeta, np.ndarray]:
    """Symbolic products of the primal Schur stage, shared by the cluster.

    Returns ``(meta_ib, mask_ii)``: the stepped column metadata of the
    (n_i, n_b) right-hand side K_ib — its columns are boundary DOFs whose
    pivot is their first interior neighbour in elimination order — and the
    interior factor's block fill mask. Both feed
    :func:`repro.core.schur.make_assembler` exactly like the dual stage's
    B̃ᵀ metadata and K fill mask do.
    """
    if kpat is None:
        kpat = _local_dof_pattern(problem)
    P, B = split.interior, split.boundary
    pat_ii = kpat[P][:, P]
    pat_ib = kpat[P][:, B]
    mask_ii = block_symbolic_cholesky(block_pattern(pat_ii, block_size))
    meta_ib = build_stepped_meta(
        pat_ib, block_size=block_size,
        rhs_block_size=rhs_block_size or block_size)
    return meta_ib, mask_ii


def dirichlet_fingerprint(problem: FetiProblem,
                          split: BoundaryInteriorSplit,
                          kpat: Optional[np.ndarray] = None) -> str:
    """Content hash of the dirichlet stage's sparsity inputs, for the plan
    cache. Distinct from the dual stage's fingerprint by construction (the
    K_ib pivots are interior row indices), and the cache key additionally
    carries ``stage="dirichlet"`` (:func:`repro.core.autotune.
    plan_from_builder`). Pass the original-order DOF pattern ``kpat`` when
    the caller already holds it (the cluster preprocessor does)."""
    from repro.core.autotune import pattern_fingerprint

    if kpat is None:
        kpat = _local_dof_pattern(problem)
    pat_ib = kpat[split.interior][:, split.boundary]
    row_deg = kpat[split.interior][:, split.interior].sum(axis=1)
    return pattern_fingerprint(
        column_pivots(pat_ib), split.n_i, split.n_b,
        extra=[row_deg.astype(np.int64), split.interior])


def own_boundary_masks(problem: FetiProblem,
                       split: BoundaryInteriorSplit) -> np.ndarray:
    """(S, n_b) float mask, 1.0 where the shared boundary DOF carries NONE
    of that subdomain's multipliers (its "spurious" boundary — faces on
    the cluster's outer surface). These are the DOFs
    :func:`restrict_own_boundary` eliminates per subdomain; interior
    subdomains of large grids get an all-zero row (no correction)."""
    ndpn = problem.ndof_per_node
    Z = np.zeros((len(problem.subdomains), split.n_b))
    for i, sd in enumerate(problem.subdomains):
        own = np.zeros(sd.n, dtype=bool)
        own[sd.b_rows[: sd.m]] = True
        if ndpn > 1:
            own = np.repeat(own.reshape(-1, ndpn).any(axis=1), ndpn)
        Z[i] = (~own[split.boundary]).astype(np.float64)
    return Z


def restrict_own_boundary(Sb: jax.Array, z: jax.Array) -> jax.Array:
    """Eliminate one subdomain's spurious boundary DOFs from the shared
    union Schur complement — Schur complements compose, so

        S_own = S − (Z S)ᵀ E⁻¹ (Z S),   E = Z S Z + diag(1 − z),

    with Z = diag(z) selecting the spurious set, equals the Schur
    complement of K onto exactly this subdomain's glued DOFs, embedded in
    the shared (n_b, n_b) frame with exact zero spurious rows/columns
    (S_ss − S_ss S_ss⁻¹ S_ss ≡ 0). Everything is dense and shape-uniform:
    the per-subdomain variation enters through the VALUES of ``z``, so the
    correction batches under vmap and shards under shard_map like any
    other stack. ``z`` all-zero (nothing spurious) gives E = I and an
    exact no-op.
    """
    E = Sb * z[:, None] * z[None, :] + jnp.diag(1.0 - z)
    C = jnp.linalg.cholesky(E)
    ZS = z[:, None] * Sb
    Y = jax.scipy.linalg.cho_solve((C, True), ZS)
    return Sb - mm(ZS.T, Y)


def make_dirichlet_assembler(
    split: BoundaryInteriorSplit,
    meta_ib: SteppedMeta,
    mask_ii: np.ndarray,
    cfg: SchurAssemblyConfig,
    index_ii: Optional[PackedBlockIndex] = None,
    shared: bool = False,
) -> Callable[..., jax.Array]:
    """Build the per-subdomain S_b assembler (jit/vmap/shard_map friendly).

    Returns ``assemble(Kd) -> S_b`` where ``Kd`` is one subdomain's
    (regularized) K permuted into ``split.dperm`` order and ``S_b`` is the
    dense (n_b, n_b) boundary Schur complement. Factorization storage and
    the TRSM/SYRK schedule follow ``cfg`` — the same knobs as the dual
    assembly, including packed interior factors.

    ``shared=True`` is the stage-graph factor dedup: the interior
    factorization is ELIDED and the assembler becomes
    ``assemble(L_ii, Kib, Kbb) -> S_b``, taking the leading (n_i, n_i)
    principal block of the DUAL stage's factor (valid whenever the dual
    rows are ordered ``split.dperm`` and the regularization only touches
    boundary DOFs — then L[:n_i, :n_i] IS the Cholesky factor of the
    unregularized K_ii). ``L_ii`` arrives dense; a packed ``cfg`` repacks
    it inside the compiled program via the assembler's storage coercion.
    """
    ni = split.n_i
    if ni == 0:
        # degenerate split (every DOF glued): S_b = K_bb, nothing to solve
        if shared:
            return lambda L_ii, Kib, Kbb: Kbb
        return lambda Kd: Kd

    packed = cfg.storage == "packed"
    if packed and index_ii is None:
        index_ii = PackedBlockIndex.from_mask(mask_ii, ni, cfg.block_size)
    assembler = make_assembler(meta_ib, cfg, mask_ii)

    if shared:

        def assemble_shared(L_ii: jax.Array, Kib: jax.Array,
                            Kbb: jax.Array) -> jax.Array:
            return Kbb - assembler(L_ii, Kib)

        return assemble_shared

    def assemble(Kd: jax.Array) -> jax.Array:
        Kii = Kd[:ni, :ni]
        Kib = Kd[:ni, ni:]
        Kbb = Kd[ni:, ni:]
        if packed:
            L = block_cholesky_packed(Kii, index_ii)
        else:
            L = block_cholesky(Kii, cfg.block_size, mask=mask_ii)
        return Kbb - assembler(L, Kib)

    return assemble


def assemble_dirichlet_schur(
    problem: FetiProblem,
    cfg=None,
    ordering: Optional[str] = None,
    dtype=None,
    regularized: bool = False,
    restrict: bool = True,
) -> Tuple[jax.Array, jax.Array, BoundaryInteriorSplit]:
    """One-shot convenience: (S_b stack, boundary B̃ᵀ stack, split).

    The standalone (non-batched-preprocessing) entry point used by tests
    and benchmarks; :func:`repro.feti.assembly.preprocess_cluster` inlines
    the same pieces into its compiled program instead.

    ``cfg`` may be a :class:`~repro.core.SchurAssemblyConfig`, a full
    :class:`~repro.feti.config.FetiConfig` (its assembly config, ordering
    AND dtype are honored — the bug this replaced hardcoded
    ``dtype=jnp.float64``, silently diverging from the threaded
    ``preprocess_cluster`` path under any non-f64 config), or ``None``.
    Explicit ``ordering``/``dtype`` arguments override the config;
    unspecified they default to "nd" / f64. bf16 storage assembles at its
    f32 compute dtype and rounds the outputs, like the compiled prep.
    ``regularized`` assembles from the fixing-DOF-regularized K (S_b is
    then SPD instead of SPSD); ``restrict=False`` skips the per-subdomain
    own-boundary restriction and returns the shared union Schur
    complement.
    """
    from repro.core.precision import canonical_dtype, compute_dtype
    from repro.fem.regularization import fixing_dofs_regularization
    from repro.feti.config import FetiConfig

    if isinstance(cfg, FetiConfig):
        fc = cfg
        cfg = fc.schur if isinstance(fc.schur, SchurAssemblyConfig) \
            else SchurAssemblyConfig()
        ordering = ordering if ordering is not None else fc.ordering
        dtype = dtype if dtype is not None else fc.dtype
    cfg = cfg or SchurAssemblyConfig()
    ordering = ordering if ordering is not None else "nd"
    sdt = canonical_dtype(jnp.float64 if dtype is None else dtype)
    cdt = np.dtype(compute_dtype(sdt))

    split = boundary_interior_split(problem, ordering=ordering)
    meta_ib, mask_ii = dirichlet_symbolic(
        problem, split, cfg.block_size, cfg.rhs_bs)
    assemble = make_dirichlet_assembler(split, meta_ib, mask_ii, cfg)
    dperm = split.dperm
    Kd = np.stack([
        (fixing_dofs_regularization(sd.K, sd.fixing_dofs)
         if regularized else sd.K)[dperm][:, dperm]
        for sd in problem.subdomains
    ])
    # storage-dtype rounding first (what a reduced-precision pipeline
    # holds), then the compute dtype for the factorization/TRSM/SYRK
    Kd_dev = jnp.asarray(Kd, dtype=sdt).astype(cdt)
    Sb = jax.jit(jax.vmap(assemble))(Kd_dev)
    if restrict:
        Z = jnp.asarray(own_boundary_masks(problem, split), dtype=cdt)
        Sb = jax.jit(jax.vmap(restrict_own_boundary))(Sb, Z)
    Sb = Sb.astype(sdt)
    Btb = jnp.asarray(
        np.stack([sd.Bt[split.boundary] for sd in problem.subdomains]),
        dtype=sdt)
    return Sb, Btb, split
