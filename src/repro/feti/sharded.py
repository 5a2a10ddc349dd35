"""Distributed FETI: the subdomain axis sharded over a ``("data",)`` mesh.

The single-device pipeline batches all subdomains of a cluster through one
compiled program with a leading subdomain axis (feti/assembly.py). This
module is the multi-node story that docstring promises: the same stacks,
placed with ``NamedSharding(P("data"))`` so each device owns a contiguous
slice of subdomains, and the solution-phase operators moved under
``shard_map`` where the per-subdomain scatter into multiplier (λ) space
becomes a ``psum`` over the subdomain-sharded axis — the JAX analogue of
the MPI neighbour exchange in the paper's CUDA predecessor (Homola et al.,
arXiv:2502.08382) and of classic GPU-cluster sub-structuring (Cheik Ahamed
& Magoulès, arXiv:2108.13162).

Design notes:

* **Relabeled multipliers.** Under sharding the per-subdomain stepped
  *column* permutations of B̃ᵀ would be batched runtime gathers, which
  GSPMD can only partition by replicating the gather operand. The local
  multiplier order is arbitrary, so preprocessing relabels columns
  host-side once (B̃ᵀ, ``lambda_ids`` and the explicit SC all move to
  stepped order together) and the assembler runs its ``col_perm=None``
  fast path — zero runtime permutes, perfectly partitionable. λ-space
  results are unchanged because gather/scatter use the relabeled ids.
* **Padding.** The subdomain count is padded up to a multiple of the mesh
  size with identity-stiffness / zero-gluing dummies whose multiplier ids
  all point at the scatter's dummy slot: they factorize to identity,
  assemble to zero, and contribute exactly nothing to any psum.
* **Replicated λ.** Dual vectors (length ``n_lambda``) stay replicated on
  every device; only the subdomain-stacked arrays are sharded. PCPG is
  unchanged — it sees the same functional operator signatures.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core.precision import einsum, mm
from repro.feti import operator as op
from repro.feti import projector as proj
from repro.feti.projector import CoarseProblem, coarse_factor, coarse_g_e

shard_map = jax.shard_map

__all__ = [
    "AXIS",
    "ShardedCoarseProblem",
    "build_coarse_problem",
    "coarse_e",
    "coarse_e_many",
    "data_sharding",
    "dirichlet_preconditioner",
    "dirichlet_preconditioner_many",
    "dual_rhs",
    "dual_rhs_many",
    "dual_rhs_refined",
    "dual_rhs_refined_many",
    "explicit_dual_apply",
    "explicit_dual_apply_many",
    "implicit_dual_apply",
    "implicit_dual_apply_many",
    "implicit_dual_apply_refined",
    "implicit_dual_apply_refined_many",
    "lumped_preconditioner",
    "lumped_preconditioner_many",
    "mesh_size",
    "pad_stack",
    "padded_count",
    "relabel_columns",
    "replicated_sharding",
    "shard_stack",
]

AXIS = "data"  # the one mesh axis FETI shards over (see launch/mesh.py)


# --------------------------------------------------------------------------
# placement helpers
# --------------------------------------------------------------------------

def mesh_size(mesh: Mesh) -> int:
    """Number of devices along the FETI ``data`` axis."""
    if AXIS not in mesh.axis_names:
        raise ValueError(
            f"FETI sharding needs a {AXIS!r} mesh axis, got {mesh.axis_names}"
        )
    return mesh.shape[AXIS]


def data_sharding(mesh: Mesh) -> NamedSharding:
    """Shard the leading (subdomain) axis; replicate the rest."""
    return NamedSharding(mesh, P(AXIS))


def replicated_sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def padded_count(S: int, mesh: Mesh) -> int:
    """Subdomain count padded up to a multiple of the mesh size."""
    D = mesh_size(mesh)
    return -(-S // D) * D


def pad_stack(x: np.ndarray, S_pad: int, identity: bool = False) -> np.ndarray:
    """Pad a (S, ...) stack to (S_pad, ...) subdomains.

    ``identity=True`` pads square-matrix stacks with identity matrices so
    dummy subdomains stay factorizable; the default zero padding is right
    for gluing/load/SC stacks (dummies then contribute nothing).
    """
    S = x.shape[0]
    if S_pad < S:
        raise ValueError(f"cannot pad {S} subdomains down to {S_pad}")
    if S_pad == S:
        return x
    if identity:
        n = x.shape[1]
        pad = np.broadcast_to(np.eye(n, dtype=x.dtype), (S_pad - S, n, n))
    else:
        pad = np.zeros((S_pad - S,) + x.shape[1:], dtype=x.dtype)
    return np.concatenate([x, pad], axis=0)


def shard_stack(mesh: Mesh, x) -> jax.Array:
    """Place a stack on the mesh, subdomain axis sharded over AXIS. A host
    array goes straight to its shards: each device receives only its own
    slice of subdomains."""
    return jax.device_put(x, data_sharding(mesh))


def relabel_columns(stack: np.ndarray, col_perm: np.ndarray) -> np.ndarray:
    """Apply each subdomain's stepped column permutation host-side.

    ``stack`` is (S, ..., m_max) with multiplier columns last; ``col_perm``
    is (S, m_max). Returns ``out[s, ..., j] = stack[s, ..., col_perm[s, j]]``
    — the once-per-pattern relabeling that lets the runtime assembler and
    dual operator skip per-subdomain permutes entirely.
    """
    idx = col_perm.reshape(
        (col_perm.shape[0],) + (1,) * (stack.ndim - 2) + (col_perm.shape[1],)
    )
    return np.take_along_axis(stack, idx, axis=-1)


# --------------------------------------------------------------------------
# the dual operator & friends under shard_map
# --------------------------------------------------------------------------
#
# Each wrapper reuses the batched single-device implementation from
# feti/operator.py as the *per-shard* body: inside shard_map the scatter
# lands in a device-local (n_lambda,) buffer holding this shard's partial
# subdomain sums, and the trailing psum over AXIS completes the additive
# dual assembly. λ inputs/outputs are replicated.

def explicit_dual_apply(
    mesh: Mesh,
    F: jax.Array,
    lambda_ids: jax.Array,
    n_lambda: int,
    lam: jax.Array,
) -> jax.Array:
    """q = Σᵢ scatter(F̃ᵢ gather(λ)) with the Σ as a psum (paper eq. 12)."""

    def body(F_l, ids_l, lam_r):
        q = op.explicit_dual_apply(F_l, ids_l, n_lambda, lam_r)
        return jax.lax.psum(q, AXIS)

    return shard_map(
        body, mesh=mesh, in_specs=(P(AXIS), P(AXIS), P()), out_specs=P()
    )(F, lambda_ids, lam)


def implicit_dual_apply(
    mesh: Mesh,
    L: jax.Array,
    Btp: jax.Array,
    lambda_ids: jax.Array,
    n_lambda: int,
    lam: jax.Array,
) -> jax.Array:
    """q = Σᵢ scatter(B̃ᵢ L⁻ᵀL⁻¹ B̃ᵢᵀ gather(λ)), Σ as psum (paper eq. 11)."""

    def body(L_l, B_l, ids_l, lam_r):
        q = op.implicit_dual_apply(L_l, B_l, ids_l, n_lambda, lam_r)
        return jax.lax.psum(q, AXIS)

    return shard_map(
        body,
        mesh=mesh,
        in_specs=(P(AXIS), P(AXIS), P(AXIS), P()),
        out_specs=P(),
    )(L, Btp, lambda_ids, lam)


def lumped_preconditioner(
    mesh: Mesh,
    K: jax.Array,
    Bt: jax.Array,
    lambda_ids: jax.Array,
    n_lambda: int,
    w: jax.Array,
) -> jax.Array:
    """Lumped FETI preconditioner M⁻¹ ≈ Σᵢ B̃ᵢ Kᵢ B̃ᵢᵀ, Σ as psum."""

    def body(K_l, B_l, ids_l, w_r):
        q = op.lumped_preconditioner(K_l, B_l, ids_l, n_lambda, w_r)
        return jax.lax.psum(q, AXIS)

    return shard_map(
        body,
        mesh=mesh,
        in_specs=(P(AXIS), P(AXIS), P(AXIS), P()),
        out_specs=P(),
    )(K, Bt, lambda_ids, w)


def dirichlet_preconditioner(
    mesh: Mesh,
    Sb: jax.Array,
    Btb: jax.Array,
    lambda_ids: jax.Array,
    n_lambda: int,
    w: jax.Array,
) -> jax.Array:
    """Dirichlet preconditioner M⁻¹ = Σᵢ B̃ᵢ S_b,i B̃ᵢᵀ, Σ as psum.

    ``Sb`` (the per-subdomain primal boundary Schur complements) and the
    boundary-row B̃ᵀ slice ``Btb`` are carried under the same ``P(AXIS)``
    specs as the explicit SC stack — padded dummy subdomains have zero
    ``Btb``, so whatever their (identity-padded) S_b is, they contribute
    exactly nothing to the psum.
    """

    def body(Sb_l, Bb_l, ids_l, w_r):
        q = op.dirichlet_preconditioner(Sb_l, Bb_l, ids_l, n_lambda, w_r)
        return jax.lax.psum(q, AXIS)

    return shard_map(
        body,
        mesh=mesh,
        in_specs=(P(AXIS), P(AXIS), P(AXIS), P()),
        out_specs=P(),
    )(Sb, Btb, lambda_ids, w)


def dual_rhs(
    mesh: Mesh,
    L: jax.Array,
    Btp: jax.Array,
    fp: jax.Array,
    lambda_ids: jax.Array,
    n_lambda: int,
    c: jax.Array,
) -> jax.Array:
    """d = B K⁺ f − c; the B-scatter is psum'd, c subtracted once outside."""

    def body(L_l, B_l, f_l, ids_l):
        zero_c = jnp.zeros((n_lambda,), B_l.dtype)
        q = op.dual_rhs(L_l, B_l, f_l, ids_l, n_lambda, zero_c)
        return jax.lax.psum(q, AXIS)

    out = shard_map(
        body, mesh=mesh, in_specs=(P(AXIS),) * 4, out_specs=P()
    )(L, Btp, fp, lambda_ids)
    return out - c


# --------------------------------------------------------------------------
# refined (mixed-precision) operators — ISSUE 9
# --------------------------------------------------------------------------
#
# The iterative-refinement bodies of feti/operator.py deploy exactly like
# their plain counterparts: the f64 packed K_reg stack is one more
# P(AXIS)-sharded operand (PackedBlocks is a registered pytree, same as
# the lumped K above), and ``steps`` is a static python int closed over by
# the shard_map body.

def implicit_dual_apply_refined(
    mesh: Mesh,
    L: jax.Array,
    Kreg,
    Btp: jax.Array,
    lambda_ids: jax.Array,
    n_lambda: int,
    steps: int,
    lam: jax.Array,
) -> jax.Array:
    """Eq. 11 with f64-refined interior solves, Σ as psum."""

    def body(L_l, Kr_l, B_l, ids_l, lam_r):
        q = op.implicit_dual_apply_refined(
            L_l, Kr_l, B_l, ids_l, n_lambda, steps, lam_r)
        return jax.lax.psum(q, AXIS)

    return shard_map(
        body,
        mesh=mesh,
        in_specs=(P(AXIS), P(AXIS), P(AXIS), P(AXIS), P()),
        out_specs=P(),
    )(L, Kreg, Btp, lambda_ids, lam)


def implicit_dual_apply_refined_many(
    mesh: Mesh,
    L: jax.Array,
    Kreg,
    Btp: jax.Array,
    lambda_ids: jax.Array,
    n_lambda: int,
    steps: int,
    Lam: jax.Array,
) -> jax.Array:
    """Eq. 11 on a column stack with f64-refined interior solves."""

    def body(L_l, Kr_l, B_l, ids_l, Lam_r):
        q = op.implicit_dual_apply_refined_many(
            L_l, Kr_l, B_l, ids_l, n_lambda, steps, Lam_r)
        return jax.lax.psum(q, AXIS)

    return shard_map(
        body,
        mesh=mesh,
        in_specs=(P(AXIS), P(AXIS), P(AXIS), P(AXIS), P()),
        out_specs=P(),
    )(L, Kreg, Btp, lambda_ids, Lam)


def dual_rhs_refined(
    mesh: Mesh,
    L: jax.Array,
    Kreg,
    Btp: jax.Array,
    fp: jax.Array,
    lambda_ids: jax.Array,
    n_lambda: int,
    steps: int,
    c: jax.Array,
) -> jax.Array:
    """d = B K⁺ f − c with f64-refined interior solves."""

    def body(L_l, Kr_l, B_l, f_l, ids_l):
        t = op.solve_with_factor_refined(L_l, Kr_l, f_l, steps)
        q_loc = einsum("snm,sn->sm", B_l, t)
        q = op.scatter_dual(q_loc, ids_l, n_lambda)
        return jax.lax.psum(q, AXIS)

    out = shard_map(
        body, mesh=mesh, in_specs=(P(AXIS),) * 5, out_specs=P()
    )(L, Kreg, Btp, fp, lambda_ids)
    return out - c


def dual_rhs_refined_many(
    mesh: Mesh,
    L: jax.Array,
    Kreg,
    Btp: jax.Array,
    Fp: jax.Array,
    lambda_ids: jax.Array,
    n_lambda: int,
    steps: int,
    c: jax.Array,
) -> jax.Array:
    """D = B K⁺ F − c1ᵀ with f64-refined interior solves."""

    def body(L_l, Kr_l, B_l, F_l, ids_l):
        t = op.solve_with_factor_refined_many(L_l, Kr_l, F_l, steps)
        q_loc = einsum("snm,snr->smr", B_l, t)
        q = op.scatter_dual(q_loc, ids_l, n_lambda)
        return jax.lax.psum(q, AXIS)

    out = shard_map(
        body, mesh=mesh, in_specs=(P(AXIS),) * 5, out_specs=P()
    )(L, Kreg, Btp, Fp, lambda_ids)
    return out - c[:, None]


# --------------------------------------------------------------------------
# multi-RHS column-stacked operators (ISSUE 6)
# --------------------------------------------------------------------------
#
# Same deployment as the single-RHS wrappers above — subdomain stacks
# sharded P(AXIS), multiplier stacks replicated P() — with the batched
# `_many` bodies of feti/operator.py per shard. A replicated rank-2
# (n_lambda, n_rhs) stack and an extra trailing column axis on the sharded
# (S, n, n_rhs) load stacks need no new specs: P(AXIS)/P() shard the
# leading axis and replicate everything else, whatever the rank.

def explicit_dual_apply_many(
    mesh: Mesh,
    F: jax.Array,
    lambda_ids: jax.Array,
    n_lambda: int,
    Lam: jax.Array,
) -> jax.Array:
    """Eq. 12 on an (n_lambda, n_rhs) stack, the Σ over subdomains psum'd."""

    def body(F_l, ids_l, Lam_r):
        q = op.explicit_dual_apply_many(F_l, ids_l, n_lambda, Lam_r)
        return jax.lax.psum(q, AXIS)

    return shard_map(
        body, mesh=mesh, in_specs=(P(AXIS), P(AXIS), P()), out_specs=P()
    )(F, lambda_ids, Lam)


def implicit_dual_apply_many(
    mesh: Mesh,
    L: jax.Array,
    Btp: jax.Array,
    lambda_ids: jax.Array,
    n_lambda: int,
    Lam: jax.Array,
) -> jax.Array:
    """Eq. 11 on an (n_lambda, n_rhs) stack, the Σ over subdomains psum'd."""

    def body(L_l, B_l, ids_l, Lam_r):
        q = op.implicit_dual_apply_many(L_l, B_l, ids_l, n_lambda, Lam_r)
        return jax.lax.psum(q, AXIS)

    return shard_map(
        body,
        mesh=mesh,
        in_specs=(P(AXIS), P(AXIS), P(AXIS), P()),
        out_specs=P(),
    )(L, Btp, lambda_ids, Lam)


def lumped_preconditioner_many(
    mesh: Mesh,
    K: jax.Array,
    Bt: jax.Array,
    lambda_ids: jax.Array,
    n_lambda: int,
    W: jax.Array,
) -> jax.Array:
    """Lumped preconditioner on an (n_lambda, n_rhs) residual stack."""

    def body(K_l, B_l, ids_l, W_r):
        q = op.lumped_preconditioner_many(K_l, B_l, ids_l, n_lambda, W_r)
        return jax.lax.psum(q, AXIS)

    return shard_map(
        body,
        mesh=mesh,
        in_specs=(P(AXIS), P(AXIS), P(AXIS), P()),
        out_specs=P(),
    )(K, Bt, lambda_ids, W)


def dirichlet_preconditioner_many(
    mesh: Mesh,
    Sb: jax.Array,
    Btb: jax.Array,
    lambda_ids: jax.Array,
    n_lambda: int,
    W: jax.Array,
) -> jax.Array:
    """Dirichlet preconditioner on an (n_lambda, n_rhs) residual stack."""

    def body(Sb_l, Bb_l, ids_l, W_r):
        q = op.dirichlet_preconditioner_many(Sb_l, Bb_l, ids_l, n_lambda, W_r)
        return jax.lax.psum(q, AXIS)

    return shard_map(
        body,
        mesh=mesh,
        in_specs=(P(AXIS), P(AXIS), P(AXIS), P()),
        out_specs=P(),
    )(Sb, Btb, lambda_ids, W)


def dual_rhs_many(
    mesh: Mesh,
    L: jax.Array,
    Btp: jax.Array,
    Fp: jax.Array,
    lambda_ids: jax.Array,
    n_lambda: int,
    c: jax.Array,
) -> jax.Array:
    """D = B K⁺ F − c1ᵀ for a sharded (S_pad, n, n_rhs) load-case stack;
    the B-scatter is psum'd, c broadcast-subtracted once outside."""

    def body(L_l, B_l, F_l, ids_l):
        t = op.solve_with_factor_many(L_l, F_l)
        q_loc = einsum("snm,snr->smr", B_l, t)
        q = op.scatter_dual(q_loc, ids_l, n_lambda)
        return jax.lax.psum(q, AXIS)

    out = shard_map(
        body, mesh=mesh, in_specs=(P(AXIS),) * 4, out_specs=P()
    )(L, Btp, Fp, lambda_ids)
    return out - c[:, None]


def coarse_e(mesh: Mesh, f: jax.Array, R: jax.Array) -> jax.Array:
    """e = Rᵀf from sharded (padded) stacks → replicated (S_pad·k,).

    The load-dependent half of the coarse problem for streamed load
    cases; padded subdomains have zero R, so their entries are zero."""
    out = shard_map(
        proj.coarse_e,
        mesh=mesh,
        in_specs=(P(AXIS), P(AXIS)),
        out_specs=P(AXIS),
    )(f, R)
    return jax.device_put(out, replicated_sharding(mesh))


def coarse_e_many(mesh: Mesh, F: jax.Array, R: jax.Array) -> jax.Array:
    """e = RᵀF for a sharded (S_pad, n, n_rhs) load-case stack →
    replicated (S_pad·k, n_rhs), subdomain-major like G's columns."""
    out = shard_map(
        proj.coarse_e_many,
        mesh=mesh,
        in_specs=(P(AXIS), P(AXIS)),
        out_specs=P(AXIS),
    )(F, R)
    return jax.device_put(out, replicated_sharding(mesh))


# --------------------------------------------------------------------------
# coarse problem with column-sharded G
# --------------------------------------------------------------------------

@jax.tree_util.register_dataclass
@dataclasses.dataclass
class ShardedCoarseProblem(CoarseProblem):
    """Natural coarse space with G = BR column-sharded over subdomains.

    ``G`` keeps each (padded) subdomain's k kernel columns on that
    subdomain's device — shape (n_lambda, S_pad·k), columns sharded over
    AXIS in subdomain-major order; the tiny (S_pad·k, S_pad·k) Gram
    Cholesky factor and e = Rᵀf are replicated
    (``solve_coarse`` is inherited unchanged). The projector applications
    split into a communication-free local Gᵀx (columns are disjoint) and a
    psum'd G·t — the same exchange pattern as the dual operator.
    """

    mesh: Mesh = dataclasses.field(metadata=dict(static=True))

    def _gt_x(self, x: jax.Array) -> jax.Array:
        """Gᵀ x: per-shard local matvec, no exchange (disjoint columns)."""
        return shard_map(
            lambda G_l, x_r: mm(G_l.T, x_r),
            mesh=self.mesh,
            in_specs=(P(None, AXIS), P()),
            out_specs=P(AXIS),
        )(self.G, x)

    def _g_t(self, t: jax.Array) -> jax.Array:
        """G t: per-shard partial sums completed by a psum over AXIS."""
        return shard_map(
            lambda G_l, t_l: jax.lax.psum(mm(G_l, t_l), AXIS),
            mesh=self.mesh,
            in_specs=(P(None, AXIS), P(AXIS)),
            out_specs=P(),
        )(self.G, t)

    def project(self, x: jax.Array) -> jax.Array:
        """P x = x − G (GᵀG)⁻¹ Gᵀ x."""
        return x - self._g_t(self.solve_coarse(self._gt_x(x)))

    def lambda0(self, e: jax.Array = None) -> jax.Array:
        """Feasible start: λ⁰ = G(GᵀG)⁻¹e satisfies Gᵀλ⁰ = e.

        ``e`` overrides the cached load moment — a replicated (S_pad·k,)
        vector or (S_pad·k, n_rhs) stack (see :func:`coarse_e` /
        :func:`coarse_e_many`); ``_g_t`` broadcasts the extra column axis
        through its per-shard partial sums unchanged."""
        return self._g_t(self.solve_coarse(self.e if e is None else e))

    def alpha(self, Flam_minus_d: jax.Array) -> jax.Array:
        """α = (GᵀG)⁻¹Gᵀ(Fλ − d); padded entries come out exactly zero."""
        return self.solve_coarse(self._gt_x(Flam_minus_d))


def build_coarse_problem(
    mesh: Mesh,
    BR: jax.Array,
    f: jax.Array,
    R: jax.Array,
    lambda_ids: jax.Array,
    n_lambda: int,
    S_real: int,
) -> ShardedCoarseProblem:
    """Assemble G = BR and e = Rᵀf from subdomain-sharded (padded) stacks.

    ``BR`` is the (S_pad, m_max, k)
    :func:`repro.feti.projector.interface_kernels` stack, multiplier
    columns relabeled like the state's; ``R`` is the (S_pad, n, k)
    kernel-basis stack (zero for padding).
    Padded subdomains have zero B̃R and zero load, so their G columns and
    e entries are exactly zero; the QR-derived coarse factor
    (:func:`repro.feti.projector.coarse_factor`, computed once here —
    GSPMD gathers the sharded columns for the setup-only QR) gives those
    zero columns a unit pivot, so the padded α components stay exactly
    zero through both triangular solves, and the leading block of the
    factor is bit-identical to the unpadded single-device one (Householder
    QR processes columns left to right; the trailing zero columns touch
    nothing before them).
    """

    def body(BR_l, f_l, R_l, ids_l):
        return coarse_g_e(BR_l, f_l, R_l, ids_l, n_lambda)

    G, e = shard_map(
        body,
        mesh=mesh,
        in_specs=(P(AXIS),) * 4,
        out_specs=(P(None, AXIS), P(AXIS)),
    )(BR, f, R, lambda_ids)

    chol = jax.device_put(coarse_factor(G), replicated_sharding(mesh))
    e = jax.device_put(e, replicated_sharding(mesh))
    return ShardedCoarseProblem(mesh=mesh, G=G, GtG_chol=chol, e=e)
