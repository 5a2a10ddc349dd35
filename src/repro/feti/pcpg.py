"""Preconditioned Conjugate Projected Gradient (paper §2.1, [10]).

Jittable lax.while_loop implementation; the dual operator F, the projector
P and the preconditioner M⁻¹ are injected as closures, so the same loop
serves implicit/explicit operators, single-host batched or mesh-sharded
deployments. Each closure runs under a named scope of its own
(``feti:dual_apply``, ``feti:precond``, ``feti:project``), so a device
trace attributes every operation of the loop to the operator it belongs
to, whichever deployment built it.
"""
from __future__ import annotations

import dataclasses
import warnings
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from repro.core.precision import dtype_name, tol_floor
from repro.obs import metrics

__all__ = ["PCPGResult", "PCPGManyResult", "pcpg", "pcpg_many",
           "TolClampState", "reset_tol_clamp_warnings", "scoped",
           "DUAL_APPLY_SCOPE", "PRECOND_SCOPE", "PROJECT_SCOPE"]

DUAL_APPLY_SCOPE = "feti:dual_apply"
PRECOND_SCOPE = "feti:precond"
PROJECT_SCOPE = "feti:project"


def scoped(name: str, fn: Callable) -> Callable:
    """``fn`` called under the named scope ``name`` (metadata only: the
    operations and their fusion are unchanged)."""

    def call(*xs):
        with jax.named_scope(name):
            return fn(*xs)

    return call


class TolClampState:
    """Warn-once dedup state for the tolerance clamp, one instance per
    call site (ISSUE 10 satellite): :func:`pcpg` and :func:`pcpg_many`
    each own one, so whether a given solve warns no longer depends on
    which OTHER call site ran first — warning counts are deterministic
    across test orderings. :func:`reset_tol_clamp_warnings` rearms both.
    """

    def __init__(self):
        self.warned: set = set()

    def reset(self) -> None:
        self.warned.clear()


_CLAMP_STATE_PCPG = TolClampState()
_CLAMP_STATE_PCPG_MANY = TolClampState()


def reset_tol_clamp_warnings() -> None:
    """Rearm the one-per-dtype tolerance-clamp ``RuntimeWarning`` at every
    call site (tests pinning warning behavior call this first)."""
    _CLAMP_STATE_PCPG.reset()
    _CLAMP_STATE_PCPG_MANY.reset()


def _clamp_tol(tol: float, dtype, state: TolClampState) -> float:
    """Clamp a requested relative tolerance to what ``dtype`` residual
    arithmetic can attain (:func:`repro.core.precision.tol_floor`).

    CG's recursive residual stagnates at a modest multiple of eps; asking
    an f32 operator for ``tol=1e-10`` would burn the full ``max_iter``
    budget on rounding noise and report non-convergence. Warns once per
    dtype per ``state`` when the clamp engages; f64 requests above
    ~1.1e-14 pass through untouched (bit-identical f64 behavior).

    Every engagement increments the ``pcpg.tol_clamp`` telemetry counter
    (exact — independent of the warn-once dedup). This function runs at
    trace time, so the counter counts clamped *compilations/call sites*,
    not clamped while-loop trips."""
    floor = tol_floor(dtype)
    if tol >= floor:
        return tol
    name = dtype_name(dtype)
    metrics.inc("pcpg.tol_clamp", dtype=name)
    if name not in state.warned:
        state.warned.add(name)
        warnings.warn(
            f"PCPG tol={tol:g} is below the attainable floor "
            f"{floor:g} for {name} residual arithmetic; clamping. "
            f"Use refinement (FetiConfig.refine) for f64 accuracy on "
            f"reduced-precision operators.",
            RuntimeWarning, stacklevel=3)
    return floor


def _safe_denom(x: jax.Array) -> jax.Array:
    """Sign-preserving denominator guard: |x| floored at the dtype's
    smallest normal. Bit-identical for every normal value (the f64 path
    never sees subnormal curvatures), but keeps f32 columns from
    dividing by a flushed-to-zero p·Fp or ζ."""
    tiny = jnp.finfo(x.dtype).tiny
    return jnp.where(jnp.abs(x) < tiny,
                     jnp.where(x < 0, -tiny, tiny), x)


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class PCPGResult:
    lam: jax.Array
    iterations: jax.Array  # int32 scalar
    residual: jax.Array  # final ||P r||
    converged: jax.Array  # bool scalar
    # (max_iter,) per-iteration ||P r|| when history was requested (entries
    # past ``iterations`` are zero — trim host-side), else None
    residual_history: Optional[jax.Array] = None


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class PCPGManyResult:
    lam: jax.Array  # (n_lambda, n_rhs) multiplier stack
    iterations: jax.Array  # (n_rhs,) int32 per-column iteration counts
    residual: jax.Array  # (n_rhs,) final per-column ||P r||
    converged: jax.Array  # (n_rhs,) bool
    block_iterations: jax.Array  # int32 scalar: loop trips executed
    # (max_iter, n_rhs) per-block-iteration ||P r|| per column when history
    # was requested (frozen columns repeat their converged value; rows past
    # ``block_iterations`` are zero — trim host-side), else None
    residual_history: Optional[jax.Array] = None


def _identity(x: jax.Array) -> jax.Array:
    return x


def _scoped_operators(apply_F, project, precondition):
    """The injected operators, each under its named scope."""
    return (scoped(DUAL_APPLY_SCOPE, apply_F),
            scoped(PROJECT_SCOPE, project),
            _identity if precondition is None
            else scoped(PRECOND_SCOPE, precondition))


def pcpg(
    apply_F: Callable[[jax.Array], jax.Array],
    project: Callable[[jax.Array], jax.Array],
    d: jax.Array,
    lam0: jax.Array,
    precondition: Optional[Callable[[jax.Array], jax.Array]] = None,
    tol: float = 1e-9,
    max_iter: int = 500,
    mesh=None,
    history: bool = False,
    atol=0.0,
) -> PCPGResult:
    """Solve P F λ = P d on the affine space λ⁰ + Ker(Gᵀ).

    Iterates:  w = P r;  z = P M⁻¹ w;  standard CG update with (z·w) inner
    products. Without a preconditioner z = w (M = I).

    ``mesh`` (optional, the subdomain-sharded deployment of
    :mod:`repro.feti.sharded`) pins the CG carries to replicated layout so
    GSPMD never round-trips the dual vectors through a sharded
    representation between the shard_map'd operator applications; with
    ``mesh=None`` the loop is exactly the single-device program.

    ``history=True`` carries a fixed ``(max_iter,)`` buffer through the
    while-loop recording ``‖P r‖`` after every iteration (so
    ``residual_history[iterations - 1] == residual`` on exit; trim the
    zero tail host-side). The buffer is write-only with respect to the CG
    recurrence — the λ/r/p updates are the same operations — so the
    returned ``lam`` is bit-identical to the ``history=False`` program;
    ``history=False`` carries nothing extra at all.

    ``atol`` (a traced scalar is fine) floors the stopping threshold:
    the loop ends once ``‖P r‖ <= max(tol·‖P r⁰‖, atol)``. A
    defect-correction solve passes the outer loop's target here, so it
    stops once the correction is good enough instead of chasing a
    relative reduction its operator's rounding cannot reach.
    """
    apply_F, project, precondition = _scoped_operators(
        apply_F, project, precondition)
    if mesh is None:
        constrain = _identity
    else:
        from jax.sharding import NamedSharding, PartitionSpec

        replicated = NamedSharding(mesh, PartitionSpec())

        def constrain(x):
            return jax.lax.with_sharding_constraint(x, replicated)

    tol = _clamp_tol(tol, d.dtype, _CLAMP_STATE_PCPG)

    r0 = d - apply_F(lam0)
    w0 = project(r0)
    z0 = project(precondition(w0))
    zeta0 = jnp.vdot(z0, w0)
    norm_w0 = jnp.linalg.norm(w0)
    atol = jnp.maximum(tol * jnp.maximum(norm_w0, 1e-30), atol)

    def cond(carry):
        w_norm, k = carry[4], carry[5]
        return jnp.logical_and(k < max_iter, w_norm > atol)

    def body(carry):
        lam, r, p, zeta, _, k = carry[:6]
        Fp = apply_F(p)
        gamma = zeta / _safe_denom(jnp.vdot(p, Fp))
        lam = constrain(lam + gamma * p)
        r = constrain(r - gamma * Fp)
        w = project(r)
        z = project(precondition(w))
        zeta_new = jnp.vdot(z, w)
        beta = zeta_new / _safe_denom(zeta)
        p = constrain(z + beta * p)
        w_norm = jnp.linalg.norm(w)
        out = (lam, r, p, zeta_new, w_norm, k + 1)
        if history:  # write-only buffer: iteration k records its ||P r||
            out = out + (carry[6].at[k].set(w_norm),)
        return out

    init = (lam0, r0, z0, zeta0, norm_w0, jnp.asarray(0, jnp.int32))
    if history:
        init = init + (jnp.zeros((max_iter,), dtype=norm_w0.dtype),)
    final = jax.lax.while_loop(cond, body, init)
    lam, w_norm, k = final[0], final[4], final[5]
    return PCPGResult(
        lam=lam, iterations=k, residual=w_norm, converged=w_norm <= atol,
        residual_history=final[6] if history else None,
    )


def pcpg_many(
    apply_F: Callable[[jax.Array], jax.Array],
    project: Callable[[jax.Array], jax.Array],
    D: jax.Array,
    Lam0: jax.Array,
    precondition: Optional[Callable[[jax.Array], jax.Array]] = None,
    tol: float = 1e-9,
    max_iter: int = 500,
    mesh=None,
    history: bool = False,
    atol=0.0,
) -> PCPGManyResult:
    """Block-batched PCPG over an (n_lambda, n_rhs) multiplier stack with
    per-column stopping.

    Each column j runs the SAME iteration as :func:`pcpg` on its own
    (d_j, λ⁰_j) — inner products, step lengths and stopping tests are all
    per-column (reductions over the λ axis only), so the trajectory of a
    column is independent of what its neighbours carry. The win over
    ``vmap(pcpg)`` is shared operator traffic: ``apply_F``/``project``/
    ``precondition`` see the whole (n_lambda, n_rhs) stack at once, so the
    explicit SC stack (and the preconditioner stacks) stream from memory
    once per *block* iteration instead of once per column — the multi-RHS
    amortization the paper's explicit assembly exists for.

    Per-column stopping freezes converged columns in place: their λ/r/p
    carries stop updating (``jnp.where`` masks with safe denominators, so
    no NaNs leak from frozen columns), their recorded residual/iteration
    count stays at the converged value, and the loop exits when every
    column is frozen or ``max_iter`` block iterations have run. A frozen
    column still rides through the operator applications (its flops are
    spent regardless — the block shape is static), which keeps the loop a
    single ``lax.while_loop`` with one compiled program per (n_lambda,
    n_rhs) shape; see docs/multirhs.md for the tradeoff discussion.

    ``mesh`` has the same meaning as in :func:`pcpg`: carries pinned to
    replicated layout between the shard_map'd operator applications.

    ``history=True`` carries a fixed ``(max_iter, n_rhs)`` buffer through
    the while-loop recording every column's ``‖P r‖`` after every block
    iteration (frozen columns repeat their converged value, so column j's
    curve is ``residual_history[:iterations[j], j]`` and
    ``residual_history[iterations[j] - 1, j] == residual[j]``; rows past
    ``block_iterations`` are zero — trim host-side). The buffer is
    write-only with respect to the CG recurrences, so ``lam`` is
    bit-identical to the ``history=False`` program.

    ``atol`` (scalar or per column) floors each column's stopping
    threshold, as in :func:`pcpg`.
    """
    apply_F, project, precondition = _scoped_operators(
        apply_F, project, precondition)
    if mesh is None:
        constrain = _identity
    else:
        from jax.sharding import NamedSharding, PartitionSpec

        replicated = NamedSharding(mesh, PartitionSpec())

        def constrain(x):
            return jax.lax.with_sharding_constraint(x, replicated)

    def col_dot(a, b):
        return jnp.sum(a * b, axis=0)  # (n_rhs,) per-column inner products

    def col_norm(a):
        return jnp.sqrt(jnp.sum(a * a, axis=0))

    tol = _clamp_tol(tol, D.dtype, _CLAMP_STATE_PCPG_MANY)

    R0 = D - apply_F(Lam0)
    W0 = project(R0)
    Z0 = project(precondition(W0))
    zeta0 = col_dot(Z0, W0)
    norm_w0 = col_norm(W0)
    atol = jnp.maximum(tol * jnp.maximum(norm_w0, 1e-30), atol)  # (n_rhs,)
    active0 = norm_w0 > atol  # already-converged (e.g. zero-load padding)
    #                           columns never enter the loop: 0 iterations

    def cond(carry):
        active, k = carry[5], carry[7]
        return jnp.logical_and(k < max_iter, jnp.any(active))

    def body(carry):
        Lam, R, Pm, zeta, w_norm, active, iters, k = carry[:8]
        FP = apply_F(Pm)
        pFp = col_dot(Pm, FP)
        gamma = jnp.where(
            active,
            zeta / _safe_denom(jnp.where(active, pFp, 1.0)), 0.0)
        Lam = constrain(Lam + gamma * Pm)
        R = constrain(R - gamma * FP)
        # frozen columns have unchanged R, hence unchanged W/Z — cheap to
        # recompute (block ops), and their w_norm/zeta stay at the frozen
        # values without extra masking
        W = project(R)
        Z = project(precondition(W))
        zeta_new = col_dot(Z, W)
        beta = jnp.where(
            active,
            zeta_new / _safe_denom(jnp.where(active, zeta, 1.0)), 0.0)
        Pm = constrain(jnp.where(active, Z + beta * Pm, Pm))
        zeta = jnp.where(active, zeta_new, zeta)
        w_norm = jnp.where(active, col_norm(W), w_norm)
        iters = iters + active.astype(jnp.int32)
        active = jnp.logical_and(active, w_norm > atol)
        out = (Lam, R, Pm, zeta, w_norm, active, iters, k + 1)
        if history:  # write-only buffer: block trip k records every column
            out = out + (carry[8].at[k].set(w_norm),)
        return out

    n_rhs = D.shape[1]
    init = (
        Lam0, R0, Z0, zeta0, norm_w0, active0,
        jnp.zeros((n_rhs,), jnp.int32), jnp.asarray(0, jnp.int32),
    )
    if history:
        init = init + (jnp.zeros((max_iter, n_rhs), dtype=norm_w0.dtype),)
    final = jax.lax.while_loop(cond, body, init)
    Lam, w_norm, iters, k = final[0], final[4], final[6], final[7]
    return PCPGManyResult(
        lam=Lam, iterations=iters, residual=w_norm,
        converged=w_norm <= atol, block_iterations=k,
        residual_history=final[8] if history else None,
    )
