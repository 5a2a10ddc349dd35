"""Natural coarse space of FETI: G = BR, the projector
P = I − G(GᵀG)⁻¹Gᵀ, and the α recovery (paper §2.1, eqs. 4–7).

``R`` is the subdomain-stacked kernel basis (S, n, k): k = 1 for scalar
heat (the normalized constant), k = 3/6 for 2D/3D elasticity (rigid-body
modes). Each subdomain contributes k columns to G, so G is
(n_lambda, S·k), GᵀG is the (S·k, S·k) block Gram matrix, and α is the
flattened (S·k,) vector of kernel coefficients.

The triangular coarse factor comes from a **QR of G** (R from ``qr(G)``
IS the Cholesky factor of GᵀG up to row signs), not from forming GᵀG and
factorizing it: squaring the condition number plus the stabilizing jitter
the squared form needed put an ≈1e-10 relative floor under the attainable
PCPG residual — exactly the elasticity convergence floor PR 4 pinned its
test grids around. With the QR factor the floor drops by orders of
magnitude and tight (1e-10) dual tolerances become reachable on larger
elasticity problems (see docs/preconditioners.md §Floor).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.precision import einsum, mm

__all__ = ["CoarseProblem", "build_coarse_problem", "coarse_g_e",
           "coarse_e", "coarse_e_many", "coarse_factor", "interface_kernels"]


def interface_kernels(subdomains) -> np.ndarray:
    """(S, m_max, k) stack of B̃ᵢRᵢ: each subdomain's kernel basis at its
    local multipliers, from the compact gluing record. Column j of B̃ᵢᵀ
    holds ``b_vals[j]`` (±1; 0 for a padding column) at row ``b_rows[j]``
    alone, so every entry is one exact product — the dense (S, n, m_max)
    B̃ᵀ is never needed to build G."""
    return np.stack([sd.b_vals[:, None] * sd.R[sd.b_rows]
                     for sd in subdomains])


def coarse_g_e(BR: jax.Array, f: jax.Array, R: jax.Array,
               lambda_ids: jax.Array, n_lambda: int):
    """G = BR columns and e = Rᵀf for a stack of subdomains.

    ``BR`` is the (S, m_max, k) :func:`interface_kernels` stack and ``R``
    the (S, n, k) kernel bases; subdomain i contributes the k columns
    scatter(lambda_ids_i, B̃ᵢ R_i), laid out subdomain-major in the
    (n_lambda, S·k) result; ``e`` is the matching (S·k,) flat Rᵀf.
    The shared body of the single-device construction below and of the
    per-shard body in :mod:`repro.feti.sharded` (where the stacks are that
    device's slice of subdomains)."""
    S, _, k = R.shape
    s_idx = jnp.broadcast_to(jnp.arange(S)[:, None], lambda_ids.shape)
    G = jnp.zeros((n_lambda + 1, S, k), BR.dtype)
    G = G.at[lambda_ids, s_idx].add(BR)[:-1].reshape(n_lambda, S * k)
    e = einsum("sn,snk->sk", f, R).reshape(S * k)
    return G, e


def coarse_e(f: jax.Array, R: jax.Array) -> jax.Array:
    """e = Rᵀf for one (S, n) load stack: the load-dependent half of
    :func:`coarse_g_e`, split out so a solver can stream new load cases
    through a cached coarse problem (G and its factor are load-free).
    Same einsum as :func:`coarse_g_e`, so the result is bit-identical."""
    S, _, k = R.shape
    return einsum("sn,snk->sk", f, R).reshape(S * k)


def coarse_e_many(F: jax.Array, R: jax.Array) -> jax.Array:
    """e = RᵀF for an (S, n, n_rhs) load-case stack → (S·k, n_rhs),
    subdomain-major rows matching G's column order."""
    S, _, k = R.shape
    return einsum("snr,snk->skr", F, R).reshape(S * k, F.shape[2])


def coarse_factor(G: jax.Array) -> jax.Array:
    """Lower-triangular factor L with L Lᵀ = GᵀG, computed as Rᵀ from the
    QR of G (never forming GᵀG — no condition-number squaring, no jitter).

    Row signs are normalized so the diagonal is positive (the genuine
    Cholesky factor). Rank safety, replacing what the old GᵀG jitter
    bought without its accuracy cost: structurally-zero columns of G (the
    inert padding subdomains of the sharded deployment) give exact zero R
    diagonals that are replaced by 1, so their α components come out
    exactly zero through both triangular solves; *numerically* dependent
    columns (a rank-deficient coarse problem) give ~eps-sized diagonals
    that are clamped to a dtype-aware floor — max(1e-12, (1e3·eps)²) of
    the mean column norm, so rank detection survives f32 — keeping the
    solve bounded like the old jittered Gram factor did. Fewer rows than
    columns (more kernel columns than multipliers — degenerate but legal)
    is handled by zero-row padding, which leaves GᵀG unchanged and lets
    the clamp absorb the missing rank.
    """
    n_rows, ncols = G.shape
    if n_rows < ncols:
        G = jnp.concatenate(
            [G, jnp.zeros((ncols - n_rows, ncols), G.dtype)])
    Rq = jnp.linalg.qr(G, mode="r")
    diag = jnp.diagonal(Rq)
    # rank guard with the old jitter's floor, applied ONLY to degenerate
    # pivots: healthy ones pass through bit-unchanged (so the old
    # jitter's ≈1e-10 residual floor stays gone), while zero/eps-sized
    # ones get the sqrt(floor_fac·trace(GᵀG)/ncols) pivot the jittered
    # Gram factor would have had — rank-deficient coarse solves stay bounded,
    # and trailing zero (padding) columns still yield exactly-zero α
    # (their R rows/columns are exact zeros for any pivot value).
    # the floor factor must sit ABOVE the dtype's squared rank-detection
    # scale: 1e-12 is fine for f64 (eps²·1e6 ≈ 4.9e-26) but far below f32
    # eps, where QR noise on dependent columns lands near eps·‖G‖ — so
    # take max(1e-12, (1e3·eps)²). For f64 the max picks 1e-12 and the
    # f64 program stays bit-identical; for f32 it is ≈1.4e-8.
    floor_fac = max(1e-12, float(jnp.finfo(G.dtype).eps * 1e3) ** 2)
    floor2 = floor_fac * jnp.sum(G * G) / ncols
    floor2 = jnp.where(floor2 == 0.0, 1.0, floor2)
    safe = jnp.where(
        diag * diag < floor2,
        jnp.sqrt(floor2) * jnp.where(diag < 0, -1.0, 1.0), diag)
    idx = jnp.arange(ncols)
    Rq = Rq.at[idx, idx].set(safe)
    sign = jnp.sign(jnp.diagonal(Rq))
    return (Rq * sign[:, None]).T


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class CoarseProblem:
    G: jax.Array  # (n_lambda, S·k)
    GtG_chol: jax.Array  # (S·k, S·k) lower factor of GᵀG (QR-derived)
    e: jax.Array  # (S·k,) = Rᵀf, subdomain-major

    def solve_coarse(self, b: jax.Array) -> jax.Array:
        """(GᵀG)⁻¹ b via the cached Cholesky factor."""
        t = jax.scipy.linalg.solve_triangular(self.GtG_chol, b, lower=True)
        return jax.scipy.linalg.solve_triangular(
            self.GtG_chol.T, t, lower=False
        )

    # Every method below is rank-generic over trailing column axes: the
    # matmuls / triangular solves broadcast an (n_lambda, n_rhs) multiplier
    # stack or an (S·k, n_rhs) e-stack unchanged — this is PR 4's
    # matrix-valued α machinery, now load-bearing for the multi-RHS path.

    def project(self, x: jax.Array) -> jax.Array:
        """P x = x − G (GᵀG)⁻¹ Gᵀ x."""
        return x - mm(self.G, self.solve_coarse(mm(self.G.T, x)))

    def lambda0(self, e: jax.Array = None) -> jax.Array:
        """Feasible start: λ⁰ = G(GᵀG)⁻¹e satisfies Gᵀλ⁰ = e.

        ``e`` overrides the cached load moment — a (S·k,) vector or an
        (S·k, n_rhs) stack of them for new load cases (see
        :func:`coarse_e` / :func:`coarse_e_many`)."""
        return mm(self.G, self.solve_coarse(self.e if e is None else e))

    def alpha(self, Flam_minus_d: jax.Array) -> jax.Array:
        """α = (GᵀG)⁻¹Gᵀ(Fλ − d): (S·k,), reshape to (S, k) per subdomain."""
        return self.solve_coarse(mm(self.G.T, Flam_minus_d))


def build_coarse_problem(BR: jax.Array, f: jax.Array, R: jax.Array,
                         lambda_ids: jax.Array, n_lambda: int) -> CoarseProblem:
    """Assemble G = BR (R = stacked kernel bases) and e = Rᵀf.

    ``BR`` is the (S, m_max, k) :func:`interface_kernels` stack; ``f`` and
    ``R`` must share a row (DOF) order — we pass the original-order load
    and kernel stacks.
    """
    G, e = coarse_g_e(BR, f, R, lambda_ids, n_lambda)
    return CoarseProblem(G=G, GtG_chol=coarse_factor(G), e=e)
