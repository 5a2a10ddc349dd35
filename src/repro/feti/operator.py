"""The FETI dual operator F = B K⁺ Bᵀ and friends, batched over subdomains.

Implicit application (paper eq. 11): SPMV + two TRSV + SPMV per subdomain.
Explicit application (paper eq. 12): one dense GEMV per subdomain against
the preassembled SC — the thing the whole paper exists to make cheap.

The gather (λ → local) / scatter-add (local → λ) pair is the algebraic form
of the paper's MPI neighbour exchange. These batched implementations are
also the per-shard bodies of the distributed deployment: under shard_map
the scatter lands in a device-local partial and becomes a psum over the
subdomain-sharded axis (see :mod:`repro.feti.sharded`).

Factor stacks may be dense ``(S, n, n)`` arrays or packed block-sparse
:class:`~repro.sparse.packed.PackedBlocks` stacks (``storage="packed"`` in
:class:`~repro.core.SchurAssemblyConfig`); :func:`solve_with_factor`
dispatches per representation so every operator below is storage-agnostic.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.precision import einsum
from repro.sparse.packed import (
    PackedBlocks,
    packed_symm_matvec,
    packed_tri_solve,
)

__all__ = [
    "gather_local",
    "scatter_dual",
    "local_dual_apply",
    "explicit_dual_apply",
    "explicit_dual_apply_many",
    "implicit_dual_apply",
    "implicit_dual_apply_many",
    "lumped_preconditioner",
    "lumped_preconditioner_many",
    "dirichlet_preconditioner",
    "dirichlet_preconditioner_many",
    "dual_rhs",
    "dual_rhs_many",
    "dual_rhs_refined",
    "dual_rhs_refined_many",
    "solve_with_factor",
    "solve_with_factor_many",
    "solve_with_factor_refined",
    "solve_with_factor_refined_many",
    "implicit_dual_apply_refined",
    "implicit_dual_apply_refined_many",
    "apply_stiffness",
    "apply_stiffness_many",
]


def gather_local(lam: jax.Array, lambda_ids: jax.Array) -> jax.Array:
    """(n_lambda,) dual vector -> (S, m_max) local blocks (pad id reads 0).

    Rank-generic: an (n_lambda, n_rhs) multiplier stack gathers to
    (S, m_max, n_rhs) — the same one-hot exchange applied per column.
    """
    lam_ext = jnp.concatenate(
        [lam, jnp.zeros((1,) + lam.shape[1:], lam.dtype)])
    return lam_ext[lambda_ids]


def scatter_dual(vals: jax.Array, lambda_ids: jax.Array, n_lambda: int) -> jax.Array:
    """(S, m_max) local blocks -> (n_lambda,) additive dual assembly.

    Rank-generic like :func:`gather_local`: (S, m_max, n_rhs) local column
    stacks scatter-add to (n_lambda, n_rhs).
    """
    out = jnp.zeros((n_lambda + 1,) + vals.shape[2:], vals.dtype)
    return out.at[lambda_ids].add(vals)[:-1]


def local_dual_apply(apply_local, lambda_ids: jax.Array, n_lambda: int,
                     lam: jax.Array) -> jax.Array:
    """The λ-space sandwich every dual-side operator shares:
    gather(λ) → per-subdomain local apply → scatter-add back into λ space.

    ``apply_local`` maps the (S, m_max) gathered local multiplier blocks to
    (S, m_max) results; the gather/scatter pair around it is the algebraic
    form of the paper's MPI neighbour exchange. The explicit dual operator
    and both preconditioners are instances — only the per-subdomain GEMV
    stack in the middle differs.
    """
    return scatter_dual(apply_local(gather_local(lam, lambda_ids)),
                        lambda_ids, n_lambda)


def explicit_dual_apply(F: jax.Array, lambda_ids: jax.Array, n_lambda: int,
                        lam: jax.Array) -> jax.Array:
    """q = Σᵢ B̃ᵢᵀ-scatter( F̃ᵢ · gather(λ) )   (paper eq. 12)."""
    return local_dual_apply(
        lambda p: einsum("sab,sb->sa", F, p), lambda_ids, n_lambda, lam)


def _tri_solve(L, b, transpose):
    return jax.lax.linalg.triangular_solve(
        L, b[..., None], left_side=True, lower=True, transpose_a=transpose
    )[..., 0]


def solve_with_factor(L, b: jax.Array) -> jax.Array:
    """Apply (L Lᵀ)⁻¹ to a subdomain-stacked (S, n) right-hand side.

    The one forward/backward triangular-solve pair every consumer of the
    factor shares (implicit dual operator, dual RHS, solution recovery).
    ``L`` is either a dense (S, n, n) stack or a packed
    :class:`~repro.sparse.packed.PackedBlocks` stack — same semantics.
    """
    if isinstance(L, PackedBlocks):
        fwd = jax.vmap(packed_tri_solve, in_axes=(0, 0, None))
        return fwd(L, fwd(L, b, False), True)
    t = jax.vmap(_tri_solve, in_axes=(0, 0, None))(L, b, False)
    return jax.vmap(_tri_solve, in_axes=(0, 0, None))(L, t, True)


def apply_stiffness(K, v: jax.Array) -> jax.Array:
    """Batched ``Kᵢ vᵢ`` for a stiffness stack stored dense or packed
    (packed = the symmetric lower block triangle in fill-mask layout)."""
    if isinstance(K, PackedBlocks):
        return jax.vmap(packed_symm_matvec)(K, v)
    return einsum("snk,sk->sn", K, v)


def implicit_dual_apply(L, Btp: jax.Array, lambda_ids: jax.Array,
                        n_lambda: int, lam: jax.Array) -> jax.Array:
    """q = Σᵢ scatter( B̃ᵢ L⁻ᵀL⁻¹ B̃ᵢᵀ gather(λ) )  (paper eq. 11)."""
    p_loc = gather_local(lam, lambda_ids)
    v = einsum("snm,sm->sn", Btp, p_loc)
    t = solve_with_factor(L, v)
    q_loc = einsum("snm,sn->sm", Btp, t)
    return scatter_dual(q_loc, lambda_ids, n_lambda)


def lumped_preconditioner(K, Bt: jax.Array, lambda_ids: jax.Array,
                          n_lambda: int, w: jax.Array) -> jax.Array:
    """Lumped FETI preconditioner: M⁻¹ ≈ Σᵢ B̃ᵢ Kᵢ B̃ᵢᵀ.

    The cheap special case of the Dirichlet sandwich below with the FULL
    stiffness K standing in for the boundary Schur complement S_b (lumping
    the interior contribution instead of eliminating it — zero extra
    preprocessing, weaker spectral equivalence; docs/preconditioners.md).

    ``K`` is the unregularized stiffness stack — dense, or packed in the
    factor's block layout (the form :func:`repro.feti.assembly.
    preprocess_cluster` stores: no dense (S, n, n) K survives preprocessing).
    ``Bt`` must share K's row order (the factor order when K is packed).
    """

    def apply_local(p):
        v = einsum("snm,sm->sn", Bt, p)
        v = apply_stiffness(K, v)
        return einsum("snm,sn->sm", Bt, v)

    return local_dual_apply(apply_local, lambda_ids, n_lambda, w)


def dirichlet_preconditioner(Sb: jax.Array, Btb: jax.Array,
                             lambda_ids: jax.Array, n_lambda: int,
                             w: jax.Array) -> jax.Array:
    """Dirichlet FETI preconditioner: M⁻¹ = Σᵢ B̃ᵢ S_b,i B̃ᵢᵀ with the
    *primal* boundary Schur complement S_b = K_bb − K_bi K_ii⁻¹ K_ib
    assembled per subdomain by :mod:`repro.feti.dirichlet`.

    ``Sb`` is the dense (S, n_b, n_b) stack; ``Btb`` is the boundary-row
    slice of B̃ᵀ, (S, n_b, m_max) — B̃ᵀ has no interior rows by
    construction of the split, so the restriction loses nothing. The apply
    is gather → restrict to boundary → dense GEMV against S_b → expand →
    scatter, the preconditioner mirror of :func:`explicit_dual_apply`.
    """

    def apply_local(p):
        v = einsum("sbm,sm->sb", Btb, p)
        v = einsum("sab,sb->sa", Sb, v)
        return einsum("sbm,sb->sm", Btb, v)

    return local_dual_apply(apply_local, lambda_ids, n_lambda, w)


def dual_rhs(L, Btp: jax.Array, fp: jax.Array,
             lambda_ids: jax.Array, n_lambda: int, c: jax.Array) -> jax.Array:
    """d = B K⁺ f − c (paper §2.1)."""
    t = solve_with_factor(L, fp)
    q_loc = einsum("snm,sn->sm", Btp, t)
    return scatter_dual(q_loc, lambda_ids, n_lambda) - c


# --------------------------------------------------------------------------
# iterative refinement around reduced-precision factors (ISSUE 9)
# --------------------------------------------------------------------------
#
# The mixed-precision pipeline keeps the factor stacks (and the explicit SC)
# at the reduced storage dtype but needs f64-accurate interior solves for
# the accuracy contract. Classic fixed-precision iterative refinement does
# it: solve in the factor dtype, compute the true residual against the f64
# regularized stiffness Kreg (the matrix the factor approximates), correct.
# Each step contracts the error by ~kappa(K_reg)·eps_f32, so 2 steps reach
# f64 for the well-conditioned subdomain operators this pipeline
# factorizes (docs/mixed_precision.md). ``steps`` is a static python int
# (unrolled under jit; 2 by default via FetiConfig.resolved_refine()).

def _factor_dtype(L):
    return L.values.dtype if isinstance(L, PackedBlocks) else L.dtype


def solve_with_factor_refined(L, Kreg, b: jax.Array,
                              steps: int) -> jax.Array:
    """f64-accurate (K_reg)⁻¹ b through a reduced-precision factor ``L``
    of K_reg, via ``steps`` rounds of iterative refinement. ``Kreg`` is
    the f64 regularized stiffness stack (factor row order, dense or
    packed); ``b`` carries the solve dtype (f64)."""
    fd = _factor_dtype(L)
    x = solve_with_factor(L, b.astype(fd)).astype(b.dtype)
    for _ in range(steps):
        r = b - apply_stiffness(Kreg, x)
        x = x + solve_with_factor(L, r.astype(fd)).astype(b.dtype)
    return x


def implicit_dual_apply_refined(L, Kreg, Btp: jax.Array,
                                lambda_ids: jax.Array, n_lambda: int,
                                steps: int, lam: jax.Array) -> jax.Array:
    """Eq. 11 with a refined interior solve: f64-accurate F application
    through a reduced-precision factor. ``Btp`` holds exact ±1/0 entries,
    so its (promoting) einsums against the f64 vectors are exact."""
    p_loc = gather_local(lam, lambda_ids)
    v = einsum("snm,sm->sn", Btp, p_loc)
    t = solve_with_factor_refined(L, Kreg, v, steps)
    q_loc = einsum("snm,sn->sm", Btp, t)
    return scatter_dual(q_loc, lambda_ids, n_lambda)


def dual_rhs_refined(L, Kreg, Btp: jax.Array, fp: jax.Array,
                     lambda_ids: jax.Array, n_lambda: int,
                     steps: int, c: jax.Array) -> jax.Array:
    """d = B K⁺ f − c with the refined (f64-accurate) interior solve."""
    t = solve_with_factor_refined(L, Kreg, fp, steps)
    q_loc = einsum("snm,sn->sm", Btp, t)
    return scatter_dual(q_loc, lambda_ids, n_lambda) - c


# --------------------------------------------------------------------------
# multi-RHS column-stacked variants (ISSUE 6)
# --------------------------------------------------------------------------
#
# Same operators on (.., n_rhs) column stacks: multiplier stacks are
# (n_lambda, n_rhs), subdomain-local stacks (S, n, n_rhs). Kept as separate
# functions (not a rank-polymorphic rewrite of the single-RHS ones) so the
# single-column programs — whose iteration counts several tests pin — stay
# byte-identical; gather/scatter are shared because indexing is naturally
# rank-generic. The per-subdomain GEMV of the single-RHS path widens to a
# GEMM, which is exactly the amortization story: the SC / factor /
# preconditioner stacks are read from memory once per *block* application
# and reused across all columns.

def local_dual_apply_many(apply_local, lambda_ids: jax.Array, n_lambda: int,
                          Lam: jax.Array) -> jax.Array:
    """Gather → local apply → scatter for an (n_lambda, n_rhs) stack.

    ``apply_local`` maps (S, m_max, n_rhs) gathered column stacks to
    (S, m_max, n_rhs) results.
    """
    return scatter_dual(apply_local(gather_local(Lam, lambda_ids)),
                        lambda_ids, n_lambda)


def explicit_dual_apply_many(F: jax.Array, lambda_ids: jax.Array,
                             n_lambda: int, Lam: jax.Array) -> jax.Array:
    """Eq. 12 on a column stack: one (m×m)·(m×r) GEMM per subdomain."""
    return local_dual_apply_many(
        lambda p: einsum("sab,sbr->sar", F, p), lambda_ids, n_lambda, Lam)


def solve_with_factor_many(L, B: jax.Array) -> jax.Array:
    """(L Lᵀ)⁻¹ applied to a subdomain-stacked (S, n, n_rhs) column block.

    Dense factors use the batched multi-RHS triangular solve directly;
    packed factors vmap :func:`~repro.sparse.packed.packed_tri_solve` over
    the trailing column axis (the packed kernel is single-RHS by design —
    its block loop is structure-driven, not RHS-driven).
    """
    if isinstance(L, PackedBlocks):
        cols = jax.vmap(packed_tri_solve, in_axes=(None, 1, None), out_axes=1)
        fwd = jax.vmap(cols, in_axes=(0, 0, None))
        return fwd(L, fwd(L, B, False), True)

    def tri(L_, B_, transpose):
        return jax.lax.linalg.triangular_solve(
            L_, B_, left_side=True, lower=True, transpose_a=transpose)

    t = jax.vmap(tri, in_axes=(0, 0, None))(L, B, False)
    return jax.vmap(tri, in_axes=(0, 0, None))(L, t, True)


def apply_stiffness_many(K, V: jax.Array) -> jax.Array:
    """Batched ``Kᵢ Vᵢ`` for an (S, n, n_rhs) column block (dense/packed)."""
    if isinstance(K, PackedBlocks):
        cols = jax.vmap(packed_symm_matvec, in_axes=(None, 1), out_axes=1)
        return jax.vmap(cols)(K, V)
    return einsum("snk,skr->snr", K, V)


def implicit_dual_apply_many(L, Btp: jax.Array, lambda_ids: jax.Array,
                             n_lambda: int, Lam: jax.Array) -> jax.Array:
    """Eq. 11 on a column stack: SPMM + multi-RHS TRSM + SPMM."""
    p_loc = gather_local(Lam, lambda_ids)  # (S, m_max, n_rhs)
    v = einsum("snm,smr->snr", Btp, p_loc)
    t = solve_with_factor_many(L, v)
    q_loc = einsum("snm,snr->smr", Btp, t)
    return scatter_dual(q_loc, lambda_ids, n_lambda)


def lumped_preconditioner_many(K, Bt: jax.Array, lambda_ids: jax.Array,
                               n_lambda: int, W: jax.Array) -> jax.Array:
    """Lumped preconditioner on an (n_lambda, n_rhs) residual stack."""

    def apply_local(p):
        v = einsum("snm,smr->snr", Bt, p)
        v = apply_stiffness_many(K, v)
        return einsum("snm,snr->smr", Bt, v)

    return local_dual_apply_many(apply_local, lambda_ids, n_lambda, W)


def dirichlet_preconditioner_many(Sb: jax.Array, Btb: jax.Array,
                                  lambda_ids: jax.Array, n_lambda: int,
                                  W: jax.Array) -> jax.Array:
    """Dirichlet preconditioner on an (n_lambda, n_rhs) residual stack."""

    def apply_local(p):
        v = einsum("sbm,smr->sbr", Btb, p)
        v = einsum("sab,sbr->sar", Sb, v)
        return einsum("sbm,sbr->smr", Btb, v)

    return local_dual_apply_many(apply_local, lambda_ids, n_lambda, W)


def dual_rhs_many(L, Btp: jax.Array, Fp: jax.Array, lambda_ids: jax.Array,
                  n_lambda: int, c: jax.Array) -> jax.Array:
    """D = B K⁺ F − c1ᵀ for an (S, n, n_rhs) load-case stack ``Fp``
    (factor row order); ``c`` broadcasts over the column axis."""
    t = solve_with_factor_many(L, Fp)
    q_loc = einsum("snm,snr->smr", Btp, t)
    return scatter_dual(q_loc, lambda_ids, n_lambda) - c[:, None]


def solve_with_factor_refined_many(L, Kreg, B: jax.Array,
                                   steps: int) -> jax.Array:
    """Column-stacked :func:`solve_with_factor_refined` on (S, n, n_rhs)."""
    fd = _factor_dtype(L)
    x = solve_with_factor_many(L, B.astype(fd)).astype(B.dtype)
    for _ in range(steps):
        r = B - apply_stiffness_many(Kreg, x)
        x = x + solve_with_factor_many(L, r.astype(fd)).astype(B.dtype)
    return x


def implicit_dual_apply_refined_many(L, Kreg, Btp: jax.Array,
                                     lambda_ids: jax.Array, n_lambda: int,
                                     steps: int, Lam: jax.Array) -> jax.Array:
    """Eq. 11 on a column stack with refined interior solves."""
    p_loc = gather_local(Lam, lambda_ids)
    v = einsum("snm,smr->snr", Btp, p_loc)
    t = solve_with_factor_refined_many(L, Kreg, v, steps)
    q_loc = einsum("snm,snr->smr", Btp, t)
    return scatter_dual(q_loc, lambda_ids, n_lambda)


def dual_rhs_refined_many(L, Kreg, Btp: jax.Array, Fp: jax.Array,
                          lambda_ids: jax.Array, n_lambda: int,
                          steps: int, c: jax.Array) -> jax.Array:
    """D = B K⁺ F − c1ᵀ with refined (f64-accurate) interior solves."""
    t = solve_with_factor_refined_many(L, Kreg, Fp, steps)
    q_loc = einsum("snm,snr->smr", Btp, t)
    return scatter_dual(q_loc, lambda_ids, n_lambda) - c[:, None]
