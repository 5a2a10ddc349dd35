"""End-to-end FETI solver (paper §2 + §5).

Stages exactly as the paper defines them:
  initialization —  symbolic factorization & persistent structures
                    (inside :func:`repro.feti.assembly.preprocess_cluster`),
  preprocessing  —  numerical factorization + explicit SC assembly,
  solution       —  PCPG iterations applying the dual operator.

``FetiSolver(mode=...)`` selects the implicit (eq. 11) or explicit (eq. 12)
dual operator; ``amortization_report`` computes the iteration count at which
the explicit approach pays off — the paper's central figure of merit
(Fig. 10: ≈10 iterations with the sparsity-utilizing assembly).
"""
from __future__ import annotations

import dataclasses
import time
from functools import partial
from types import SimpleNamespace
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.tree_util import Partial

from repro.core import SchurAssemblyConfig, assembly_flops
from repro.core.precision import einsum, tol_floor
from repro.feti.assembly import ClusterState, preprocess_cluster
from repro.feti.config import FetiConfig, _coerce_config
from repro.feti import operator as oplib
from repro.feti import projector
from repro.feti.operator import (
    gather_local,
    solve_with_factor,
    solve_with_factor_many,
    solve_with_factor_refined,
    solve_with_factor_refined_many,
)
from repro.feti.pcpg import (
    DUAL_APPLY_SCOPE,
    PCPGManyResult,
    PCPGResult,
    pcpg,
    pcpg_many,
)
from repro.fem.decomposition import FetiProblem
from repro.obs import Telemetry, metrics
from repro.obs.trace import use_tracer

__all__ = ["FetiSolver", "FetiSolution", "FetiManySolution",
           "PRECONDITIONERS", "solve_many"]

PRECONDITIONERS = ("lumped", "dirichlet", "none")

# defect-correction outer iterations (explicit mode on a reduced-precision
# explicit operator): each outer solves P F δ = P r to the storage dtype's
# floor and contracts the f64 residual by ~that floor, so a handful always
# suffices — the cap is a stagnation guard, not a tuning knob
_MAX_OUTER = 8
# a correction solve stops once its residual is this fraction of the
# outer target: the outer's f64 residual then meets the target unless the
# storage-precision operator's error exceeds the rest
_CORRECTION_ATOL = 0.5


# the operator functions of both deployments, by name: repro.feti.operator
# (and projector) for one device, repro.feti.sharded (mesh first) for a mesh
_OPS = ("explicit_dual_apply", "explicit_dual_apply_many",
        "implicit_dual_apply", "implicit_dual_apply_many",
        "implicit_dual_apply_refined", "implicit_dual_apply_refined_many",
        "lumped_preconditioner", "lumped_preconditioner_many",
        "dirichlet_preconditioner", "dirichlet_preconditioner_many",
        "dual_rhs", "dual_rhs_many", "dual_rhs_refined",
        "dual_rhs_refined_many", "coarse_e", "coarse_e_many")


@dataclasses.dataclass(frozen=True)
class _Call:
    """``fn(*static, *args)``, compared and hashed by value: the static
    data of an operator pytree. A program that takes the operator as an
    argument then serves every solver that builds an equal one, where a
    lambda or ``functools.partial`` (compared by identity) would make
    each new solver trace the program again."""

    fn: Callable
    static: tuple = ()

    def __call__(self, *args):
        return self.fn(*self.static, *args)


def _deployment_ops(mesh) -> SimpleNamespace:
    """:data:`_OPS` of the deployment ``mesh`` selects, with one call
    signature: the sharded functions get ``mesh`` bound."""
    if mesh is None:
        return SimpleNamespace(**{
            n: getattr(oplib, n, None) or getattr(projector, n)
            for n in _OPS})
    from repro.feti import sharded as shlib

    return SimpleNamespace(**{n: _Call(getattr(shlib, n), (mesh,))
                              for n in _OPS})


def _with_arrays(fn: Callable, static: tuple, arrays: tuple, x):
    return fn(*arrays, *static, x)


def _bound(fn: Callable, arrays: tuple, *static) -> Partial:
    """The operator ``x -> fn(*arrays, *static, x)`` as a pytree whose
    leaves are ``arrays``: a jitted function that takes it as an argument
    receives the cluster state's arrays as program arguments, where a
    jitted closure over them would bake them into the program as
    constants (gigabytes at full size). ``fn`` and ``static`` are its
    static data, compared by value (:class:`_Call`)."""
    return Partial(_Call(_with_arrays, (fn, static)), tuple(arrays))


def _at_dtype(dt, out_dt, op: Partial, x):
    """``op`` applied at the storage dtype ``dt`` to a solve-dtype vector."""
    return op(x.astype(dt)).astype(out_dt)


def _rhs(dual_rhs: Callable, n_lambda: int, L, Btp, ids, c, fp):
    return dual_rhs(L, Btp, fp, ids, n_lambda, c)


def _rhs_refined(dual_rhs_refined: Callable, n_lambda: int, refine: int,
                 L, Kreg, Btp, ids, c, fp):
    return dual_rhs_refined(L, Kreg, Btp, fp, ids, n_lambda, refine, c)


def _factor_solve_refined(solve: Callable, refine: int, L, Kreg, b):
    return solve(L, Kreg, b, refine)


def _solver_programs() -> SimpleNamespace:
    """The solver's programs of one structure, defined afresh for each
    (:meth:`repro.feti.programs.StructurePrograms.solver`), so that a
    forgotten structure's compiled code goes with its functions. They take
    the cluster state's arrays as arguments and their operators as static
    data compared by value, so every solver of the structure and shapes
    runs the programs the first one traced and loaded."""
    # op(*xs) as one compiled program per operator: op by op, the
    # factor-backed operators' unrolled block loops would dispatch (and on
    # an accelerator, compile) hundreds of small operations per call
    compiled = jax.jit(lambda op, *xs: op(*xs))

    @jax.jit
    def dual_apply(op, x):
        """The solver's own dual-operator applications (refinement
        residuals, recovery), under the scope PCPG gives its own."""
        with jax.named_scope(DUAL_APPLY_SCOPE):
            return op(x)

    @partial(jax.jit, static_argnums=(0, 1, 2, 3, 4))
    def pcpg_run(loop, tol, max_iter, history, mesh, apply_F, coarse,
                 precond, d, lam0, atol):
        """Single-RHS PCPG: ``loop`` is :func:`repro.feti.pcpg.pcpg`,
        static like the operators' functions, so the program is keyed by
        every function it runs.

        The program is named: the device trace's scope paths and the
        ``jit:*`` spans then say which program ran, and the name is part
        of the persistent compilation cache's key, which leaves out debug
        info and with it the named scopes — a program of the same
        operations under other scopes is another entry."""
        return loop(apply_F, coarse.project, d, lam0, precondition=precond,
                    tol=tol, max_iter=max_iter, mesh=mesh, history=history,
                    atol=atol)

    @partial(jax.jit, static_argnums=(0, 1, 2, 3, 4))
    def pcpg_many_run(loop, tol, max_iter, history, mesh, apply_F, coarse,
                      precond, D, Lam0, atol):
        """Block PCPG (``loop`` is :func:`repro.feti.pcpg.pcpg_many`),
        named and keyed like ``pcpg_run``."""
        return loop(apply_F, coarse.project, D, Lam0, precondition=precond,
                    tol=tol, max_iter=max_iter, mesh=mesh, history=history,
                    atol=atol)

    return SimpleNamespace(compiled=compiled, dual_apply=dual_apply,
                           pcpg_run=pcpg_run, pcpg_many_run=pcpg_many_run)


@dataclasses.dataclass
class FetiSolution:
    u: np.ndarray  # (S, n) subdomain solutions, original DOF order
    u_global: np.ndarray  # (n_global_dofs,) averaged onto the global mesh
    lam: np.ndarray
    alpha: np.ndarray  # (S, k) kernel coefficients per subdomain
    iterations: int
    residual: float
    converged: bool
    timings: dict
    refine_outer: int = 0  # defect-correction outer iterations performed
    residual_history: Optional[np.ndarray] = None  # (iterations,) ‖P r‖
    #   per PCPG iteration, concatenated across defect-correction outers;
    #   populated only by solve(history=True)


@dataclasses.dataclass
class FetiManySolution:
    """A batch of load-case solutions from :meth:`FetiSolver.solve_many`.

    All arrays carry the load-case index first; padding columns (when
    ``rhs_unit`` rounded the batch up) are already stripped."""

    u: np.ndarray  # (n_rhs, S, n) subdomain solutions, original DOF order
    u_global: np.ndarray  # (n_rhs, n_global_dofs)
    lam: np.ndarray  # (n_rhs, n_lambda)
    alpha: np.ndarray  # (n_rhs, S, k)
    iterations: np.ndarray  # (n_rhs,) per-column PCPG iteration counts
    residuals: np.ndarray  # (n_rhs,) per-column final ||P r||
    converged: np.ndarray  # (n_rhs,) bool
    block_iterations: int  # block-PCPG loop trips (= max of iterations)
    n_rhs: int  # requested load cases
    n_rhs_padded: int  # columns actually solved (rhs_unit padding)
    timings: dict
    refine_outer: int = 0  # defect-correction outer iterations performed
    residual_history: Optional[np.ndarray] = None  # (n_rhs, block_iters)
    #   per-block-iteration ‖P r‖ per column (converged columns repeat
    #   their frozen value); populated only by solve_many(history=True)


@dataclasses.dataclass
class _SolutionOps:
    """Load-independent solution-phase machinery, built once per cluster
    state and reused across :meth:`FetiSolver.solve` /
    :meth:`FetiSolver.solve_many` calls — the server-style reuse pattern:
    everything here depends only on the preprocessed cluster, so streaming
    a new load case costs one RHS build plus PCPG iterations."""

    coarse: object  # CoarseProblem / ShardedCoarseProblem
    apply_F: Callable  # (n_lambda,) -> (n_lambda,); the FAST operator the
    #   PCPG inner loop runs (reduced-precision GEMV under mixed precision)
    apply_F_many: Callable  # (n_lambda, r) -> (n_lambda, r)
    apply_F_exact: Callable  # f64-accurate application (refined implicit)
    #   for outer residuals / α recovery; == apply_F when not refining
    apply_F_exact_many: Callable
    precond: Optional[Callable]
    precond_many: Optional[Callable]
    dual_rhs_vec: Callable  # fp (S, n) -> d (n_lambda,)
    dual_rhs_cols: Callable  # Fp (S, n, r) -> D (n_lambda, r)
    coarse_e_vec: Callable  # f (S, n) -> e (S·k,)
    coarse_e_cols: Callable  # F (S, n, r) -> E (S·k, r)
    factor_solve: Callable  # b (S, n) -> K_reg⁻¹ b, refined when refining
    factor_solve_many: Callable  # B (S, n, r) -> K_reg⁻¹ B
    programs: SimpleNamespace  # the structure's (_solver_programs)


class FetiSolver:
    """Drives preprocess + PCPG for one cluster (batched subdomains)."""

    def __init__(self, problem: FetiProblem, config=None, **deprecated):
        """``config`` is a :class:`~repro.feti.config.FetiConfig` or one of
        its shorthand forms: ``None`` (defaults), a bare
        ``SchurAssemblyConfig``, or the string ``"auto"`` (the stage graph
        plans every assembly stage jointly during :meth:`preprocess`;
        ``self.cfg``/``self.plan`` carry the resolved dual-stage config and
        its cost report afterwards, ``self.state.graph_plan`` the joint
        result). The pre-FetiConfig keyword arguments (``cfg=``, ``mode=``,
        ``preconditioner=``, ``ordering=``, ``dtype=``, ``measure=``,
        ``plan_cache=``, ``mesh=``, ``storage=``) still work via
        ``**deprecated`` but emit a ``DeprecationWarning`` — see README
        §Migrating to FetiConfig.

        ``FetiConfig.mesh`` (a ``("data",)`` device mesh, see
        :func:`repro.launch.mesh.make_feti_mesh`) shards the subdomain
        axis over devices: preprocessing partitions per-device and the
        PCPG operators run under shard_map with psum exchange
        (:mod:`repro.feti.sharded`). ``mesh=None`` keeps the single-device
        batched behavior bit-for-bit."""
        fc = _coerce_config(config, deprecated, "FetiSolver")
        self.problem = problem
        self.config = fc
        # resolved views, kept as public attributes for existing callers;
        # cfg/plan are overwritten with the planner's choice on preprocess
        self.cfg = fc.schur if fc.schur is not None else SchurAssemblyConfig()
        self.plan = None
        self.mode = fc.mode
        self.preconditioner = fc.preconditioner
        self.ordering = fc.ordering
        self.dtype = fc.dtype
        # storage dtype vs solve dtype: reduced-precision stacks with
        # refinement run the PCPG vectors (and recover accuracy) in f64
        self.solve_dtype = fc.solve_dtype
        self.refine = fc.resolved_refine()
        self.measure = fc.measure
        self.plan_cache = fc.plan_cache
        self.mesh = fc.mesh
        self.storage = fc.storage
        self.state: Optional[ClusterState] = None
        # structured telemetry (spans + metrics): enabled by default —
        # the tracer's per-span cost is two clock reads and a sync the
        # surrounding code needed anyway; telemetry.disable() turns the
        # spans into no-ops for overhead-critical paths
        self.telemetry = Telemetry()
        self.timings: dict = {}  # deprecated flat view; prefer report()
        self._ops: Optional[_SolutionOps] = None

    # ---- preprocessing (paper §2.2) ----
    def preprocess(self) -> ClusterState:
        tr = self.telemetry.tracer
        t0 = time.perf_counter()
        with use_tracer(tr), tr.span("preprocess") as sp:
            self.state = preprocess_cluster(self.problem, self.config)
            # explicit syncs (not just the span's): the timings entry
            # below must stay honest when telemetry is disabled
            jax.block_until_ready(self.state.L)
            if self.state.F is not None:
                jax.block_until_ready(self.state.F)
            if self.state.Sb is not None:
                jax.block_until_ready(self.state.Sb)
            sp.set(mode=self.mode, S=int(self.state.S))
        self.cfg = self.state.cfg  # resolved when "auto" was passed
        self.plan = self.state.plan
        self._ops = None  # operators close over state arrays
        self.timings["preprocess_s"] = time.perf_counter() - t0
        return self.state

    # ---- solution-phase machinery, load-independent ----
    def _solution_ops(self) -> _SolutionOps:
        """Coarse problem + operator closures, built once per state (in
        the span ``solution_ops``, its coarse problem synced) and cached:
        the pieces of the solution phase that do NOT depend on the load,
        so streamed load cases reuse them."""
        if self._ops is None:
            with self.telemetry.tracer.span("solution_ops") as sp:
                self._ops = self._build_solution_ops()
                sp.sync(self._ops.coarse)
        return self._ops

    def _build_solution_ops(self) -> _SolutionOps:
        st = self.state
        prob = self.problem
        nl = prob.n_lambda
        sdt = self.solve_dtype  # f64 when refining, else the storage dtype
        refine = st.refine_steps
        c = jnp.asarray(prob.c, dtype=sdt)
        BR = projector.interface_kernels(prob.subdomains)  # (S, m_max, k)
        k = _deployment_ops(st.mesh)
        progs = st.programs.solver(_solver_programs)

        if st.mesh is None:
            coarse = projector.build_coarse_problem(
                jnp.asarray(BR, dtype=sdt), st.f, st.R, st.lambda_ids, nl)
        else:
            from repro.feti import sharded as shlib

            # match the state's relabeled multiplier columns, pad the
            # dummy subdomains (zero gluing), and shard like the stacks
            BR_rel = shlib.relabel_columns(
                BR.swapaxes(1, 2), np.asarray(st.col_perm)).swapaxes(1, 2)
            coarse = shlib.build_coarse_problem(
                st.mesh, shlib.shard_stack(
                    st.mesh, np.asarray(shlib.pad_stack(BR_rel, st.S),
                                        dtype=sdt)),
                st.f, st.R, st.lambda_ids, nl, S_real=st.S_real,
            )

        if refine > 0:
            # implicit + refined: the operator itself is f64-accurate
            # (reduced-precision triangular solves wrapped in f64 residual
            # corrections), so plain PCPG reaches f64 tols
            refined = (st.L, st.Kreg, st.Btp, st.lambda_ids)
            exact = _bound(k.implicit_dual_apply_refined, refined, nl,
                           refine)
            exact_many = _bound(k.implicit_dual_apply_refined_many,
                                refined, nl, refine)
        if self.mode == "explicit":
            apply_F = _bound(k.explicit_dual_apply, (st.F, st.lambda_ids),
                             nl)
            apply_F_many = _bound(k.explicit_dual_apply_many,
                                  (st.F, st.lambda_ids), nl)
        elif refine > 0:
            apply_F, apply_F_many = exact, exact_many
        else:
            apply_F = _bound(k.implicit_dual_apply,
                             (st.L, st.Btp, st.lambda_ids), nl)
            apply_F_many = _bound(k.implicit_dual_apply_many,
                                  (st.L, st.Btp, st.lambda_ids), nl)
        if refine > 0:
            # compiled: the defect-correction outer loop calls these
            # eagerly several times per solve
            apply_F_exact = partial(progs.dual_apply, exact)
            apply_F_exact_many = partial(progs.dual_apply, exact_many)
        else:
            # applied op by op: a named scope does not reach the programs
            # an eager call dispatches
            apply_F_exact = apply_F
            apply_F_exact_many = apply_F_many

        if self.preconditioner == "lumped":
            # K is packed in factor row order, so it pairs with Btp (the
            # product B̃ K B̃ᵀ is invariant to the shared row permutation)
            args = (st.K, st.Btp, st.lambda_ids)
            precond = _bound(k.lumped_preconditioner, args, nl)
            precond_many = _bound(k.lumped_preconditioner_many, args, nl)
        elif self.preconditioner == "dirichlet":
            if st.Sb is None:
                raise ValueError(
                    "state was preprocessed without the dirichlet stage; "
                    "construct the solver with preconditioner='dirichlet' "
                    "before preprocess()")
            args = (st.Sb, st.Btb, st.lambda_ids)
            precond = _bound(k.dirichlet_preconditioner, args, nl)
            precond_many = _bound(k.dirichlet_preconditioner_many, args, nl)
        elif self.preconditioner == "none":
            precond = None
            precond_many = None
        else:
            raise ValueError(f"unknown preconditioner {self.preconditioner!r}")

        stor_dt = self.config.dtype_np
        if np.dtype(sdt) != stor_dt:
            # mixed precision: the PCPG vectors live at the solve dtype
            # (f64) but the device stacks at the storage dtype. Cast
            # explicitly around the stored-operator applications so the
            # GEMVs run at storage precision — jnp promotion would
            # silently upcast the whole stack to f64 compute instead.
            # (apply_F_exact and the refined implicit apply stay unwrapped:
            # they are f64-accurate by construction.)
            fast = _Call(_at_dtype, (stor_dt, sdt))
            if self.mode == "explicit":
                apply_F = Partial(fast, apply_F)
                apply_F_many = Partial(fast, apply_F_many)
            if precond is not None:
                precond = Partial(fast, precond)
                precond_many = Partial(fast, precond_many)

        if refine > 0:
            dual_rhs_vec = Partial(
                _Call(_rhs_refined, (k.dual_rhs_refined, nl, refine)),
                *refined, c)
            dual_rhs_cols = Partial(
                _Call(_rhs_refined, (k.dual_rhs_refined_many, nl, refine)),
                *refined, c)
            factor_solve = Partial(
                _Call(_factor_solve_refined,
                      (solve_with_factor_refined, refine)), st.L, st.Kreg)
            factor_solve_many = Partial(
                _Call(_factor_solve_refined,
                      (solve_with_factor_refined_many, refine)),
                st.L, st.Kreg)
        else:
            dual_rhs_vec = Partial(_Call(_rhs, (k.dual_rhs, nl)),
                                   st.L, st.Btp, st.lambda_ids, c)
            dual_rhs_cols = Partial(_Call(_rhs, (k.dual_rhs_many, nl)),
                                    st.L, st.Btp, st.lambda_ids, c)
            factor_solve = Partial(solve_with_factor, st.L)
            factor_solve_many = Partial(solve_with_factor_many, st.L)

        return _SolutionOps(
            coarse=coarse, apply_F=apply_F, apply_F_many=apply_F_many,
            apply_F_exact=apply_F_exact,
            apply_F_exact_many=apply_F_exact_many,
            precond=precond, precond_many=precond_many,
            dual_rhs_vec=partial(progs.compiled, dual_rhs_vec),
            dual_rhs_cols=partial(progs.compiled, dual_rhs_cols),
            coarse_e_vec=lambda f: k.coarse_e(f, st.R),
            coarse_e_cols=lambda F: k.coarse_e_many(F, st.R),
            factor_solve=partial(progs.compiled, factor_solve),
            factor_solve_many=partial(progs.compiled, factor_solve_many),
            programs=progs,
        )

    def _load_stacks(self, loads: np.ndarray):
        """Host (S_real, n, ...) load stack -> device (f, fp) arrays in
        original and factor row order, padded + sharded when meshed."""
        st = self.state
        f_host = np.asarray(loads, dtype=self.solve_dtype)
        fp_host = f_host[:, np.asarray(st.node_perm)]
        if st.mesh is None:
            return jnp.asarray(f_host), jnp.asarray(fp_host)
        from repro.feti import sharded as shlib

        return (
            shlib.shard_stack(st.mesh, shlib.pad_stack(f_host, st.S)),
            shlib.shard_stack(st.mesh, shlib.pad_stack(fp_host, st.S)),
        )

    def _recover_u(self, up, alpha_flat, n_cols: Optional[int]):
        """Shared recovery tail: factor-order K⁺(f − Bᵀλ) + kernel
        correction, back-permuted to original DOF order and averaged onto
        the global mesh. ``n_cols=None`` recovers one solution ((S, n) /
        (n_global,)); an int recovers that many stacked columns with the
        load-case axis leading."""
        st = self.state
        prob = self.problem
        k = st.R.shape[2]
        inv_perm = np.argsort(st.node_perm)
        up_h = np.asarray(up)[: st.S_real]
        R_h = np.asarray(st.R)[: st.S_real]
        if n_cols is None:
            alpha = np.asarray(alpha_flat).reshape(st.S, k)[: st.S_real]
            u = up_h[:, inv_perm] + np.einsum("snk,sk->sn", R_h, alpha)
        else:
            alpha = np.asarray(alpha_flat).reshape(
                st.S, k, n_cols)[: st.S_real]
            u = (up_h[:, inv_perm]
                 + np.einsum("snk,skr->snr", R_h, alpha))
            u = np.moveaxis(u, -1, 0)  # (n_rhs, S, n)
            alpha = np.moveaxis(alpha, -1, 0)  # (n_rhs, S, k)

        # average duplicated interface copies onto the global mesh (DOFs)
        nn = prob.n_global_dofs
        lead = () if n_cols is None else (n_cols,)
        acc = np.zeros(lead + (nn,))
        cnt = np.zeros(nn)
        for i, sd in enumerate(prob.subdomains):
            np.add.at(acc, (..., sd.dof_gids), u[..., i, :])
            np.add.at(cnt, sd.dof_gids, 1.0)
        u_global = acc / np.maximum(cnt, 1.0)
        return u, alpha, u_global

    # ---- solution (paper §2.2) ----
    def solve(self, tol: float = 1e-9, max_iter: int = 2000,
              loads: Optional[np.ndarray] = None,
              history: bool = False) -> FetiSolution:
        """One PCPG solve. ``loads`` (optional, host (S_real, n) stack in
        original DOF order) overrides the problem's own load vectors —
        the single-case form of the :meth:`solve_many` streaming path.

        ``history=True`` additionally records the per-iteration ``‖P r‖``
        trace on ``FetiSolution.residual_history`` — trimmed to the
        iterations actually run and concatenated across defect-correction
        outers. The returned ``lam`` is bit-identical to ``history=False``
        (the buffer is write-only w.r.t. the CG recurrence)."""
        if self.state is None:
            self.preprocess()
        st = self.state
        tr = self.telemetry.tracer

        with use_tracer(tr), tr.span("solve", mode=self.mode) as sp_solve:
            ops = self._solution_ops()
            st.programs.trim()  # a sweep's compiled variants stay bounded
            coarse = ops.coarse
            t0 = time.perf_counter()
            with tr.span("rhs_setup"):
                if loads is None:
                    fp_dev = st.fp
                    lam0 = coarse.lambda0()
                else:
                    f_dev, fp_dev = self._load_stacks(loads)
                    lam0 = coarse.lambda0(ops.coarse_e_vec(f_dev))
                d = ops.dual_rhs_vec(fp_dev)
                jax.block_until_ready(d)
            self.timings["rhs_setup_s"] = time.perf_counter() - t0

            # mixed precision, explicit mode: the inner PCPG runs the FAST
            # reduced-precision explicit operator down to that dtype's
            # floor, then defect-correction outers recover f64 accuracy —
            # each outer measures the true residual with the refined
            # implicit apply and solves P F δ = P r for a correction that
            # stays in Ker(Gᵀ)
            mixed = (st.refine_steps > 0 and self.mode == "explicit")
            inner_tol = max(tol, tol_floor(self.dtype)) if mixed else tol

            t0 = time.perf_counter()
            run = self._run(inner_tol, max_iter, history)
            with tr.span("pcpg", tol=float(inner_tol)) as sp:
                res: PCPGResult = run(d, lam0, 0.0)
                jax.block_until_ready(res.lam)
                sp.set(iterations=int(res.iterations),
                       residual=float(res.residual))

            lam = res.lam
            iterations = int(res.iterations)
            residual = float(res.residual)
            converged = bool(res.converged)
            hist_parts = []
            if history:
                hist_parts.append(
                    np.asarray(res.residual_history)[:iterations])
            n_outer = 0
            if mixed:
                project = coarse.project
                # target scale matches pcpg's: tol · ‖P(d − F λ0)‖ (the
                # fast operator is accurate enough to set a scale)
                w0n = float(jnp.linalg.norm(project(d - ops.apply_F(lam0))))
                target = tol * max(w0n, 1e-300)
                r = d - ops.apply_F_exact(lam)
                wnorm = float(jnp.linalg.norm(project(r)))
                prev = float("inf")
                while (wnorm > target and n_outer < _MAX_OUTER
                       and wnorm < 0.5 * prev):
                    prev = wnorm
                    with tr.span("refine_outer", outer=n_outer) as sp:
                        # the correction needs to reach the target only:
                        # past it, its f32 operator's rounding can stall the
                        # relative test (2000 iterations at heat2d size)
                        cres: PCPGResult = run(r, jnp.zeros_like(lam),
                                               _CORRECTION_ATOL * target)
                        lam = lam + cres.lam
                        jax.block_until_ready(lam)
                        sp.set(iterations=int(cres.iterations))
                    if history:
                        hist_parts.append(
                            np.asarray(cres.residual_history)
                            [:int(cres.iterations)])
                    iterations += int(cres.iterations)
                    n_outer += 1
                    r = d - ops.apply_F_exact(lam)
                    wnorm = float(jnp.linalg.norm(project(r)))
                residual = wnorm
                converged = wnorm <= target
            self.timings["solve_s"] = time.perf_counter() - t0
            metrics.inc("pcpg.solves")
            metrics.inc("pcpg.iterations", iterations)
            if n_outer:
                metrics.inc("pcpg.refine_outer", n_outer)

            # ---- recover α and u (paper eqs. 5, 7) ----
            t0 = time.perf_counter()
            with tr.span("recover"):
                Flam = ops.apply_F_exact(lam)
                alpha_flat = coarse.alpha(Flam - d)  # (S·k,), sd-major
                lam_loc = gather_local(lam, st.lambda_ids)
                rhs = fp_dev - einsum("snm,sm->sn", st.Btp, lam_loc)
                up = ops.factor_solve(rhs)
                # force the device work BEFORE the clock is read: the
                # triangular solves dispatch asynchronously and numpy's
                # implicit transfer in _recover_u would otherwise charge
                # them to whatever timer runs next
                jax.block_until_ready(up)
                # back to original DOF order + kernel (rigid-body)
                # correction u_i = K⁺(f − Bᵀλ)_i + R_i α_i; drop any inert
                # mesh-padding subdomains (S_real == S unsharded)
                u, alpha, u_global = self._recover_u(up, alpha_flat, None)
            self.timings["recover_s"] = time.perf_counter() - t0

            residual_history = (
                np.concatenate(hist_parts) if history else None)
            if history:
                sp_solve.set(
                    iterations=iterations,
                    residual_history=[float(x) for x in residual_history])

        return FetiSolution(
            u=u,
            u_global=u_global,
            lam=np.asarray(lam),
            alpha=alpha,
            iterations=iterations,
            residual=residual,
            converged=converged,
            timings=dict(self.timings),
            refine_outer=n_outer,
            residual_history=residual_history,
        )

    def _run(self, tol: float, max_iter: int, history: bool = False):
        """The single-RHS PCPG program (the structure's ``pcpg_run``,
        :func:`_solver_programs`) with this solver's operators and static
        arguments bound, called as ``run(d, lam0, atol)``; ``atol`` floors
        the stopping threshold (:func:`repro.feti.pcpg.pcpg`). A stream of
        single load cases (``solve(loads=...)`` or 1-column
        :meth:`solve_many` batches) traces and compiles once per tolerance
        and shapes, for every solver of the structure."""
        ops = self._solution_ops()
        return partial(ops.programs.pcpg_run, pcpg, float(tol),
                       int(max_iter), bool(history), self.state.mesh,
                       ops.apply_F, ops.coarse, ops.precond)

    def _many_run(self, tol: float, max_iter: int, history: bool = False):
        """The block-PCPG program (the structure's ``pcpg_many_run``),
        bound like :meth:`_run` (jax.jit handles distinct (n_lambda,
        n_rhs) shapes within one program)."""
        ops = self._solution_ops()
        return partial(ops.programs.pcpg_many_run, pcpg_many, float(tol),
                       int(max_iter), bool(history), self.state.mesh,
                       ops.apply_F_many, ops.coarse, ops.precond_many)

    def solve_many(self, loads, tol: float = 1e-9, max_iter: int = 2000,
                   rhs_unit: int = 1,
                   history: bool = False) -> FetiManySolution:
        """Solve a batch of load cases against the cached cluster state.

        This is the server-style entry point the amortization story asks
        for: :meth:`preprocess` is paid once (factorization, explicit SC
        assembly, autotuned plans, Dirichlet S_b), then an arbitrary
        sequence of ``solve_many`` calls streams load-case batches through
        one block-PCPG (:func:`repro.feti.pcpg.pcpg_many`) whose operator
        applications touch the cached stacks once per block iteration for
        ALL columns. Per-column stopping freezes converged columns, so a
        mixed batch costs max-over-columns iterations, not the sum.

        ``loads``: (n_rhs, S_real, n) host stack of per-subdomain load
        vectors in original DOF order (a single (S_real, n) case is
        promoted to a 1-batch). ``rhs_unit`` > 1 pads the batch with
        zero-load dummy columns up to a multiple of that unit — zero
        columns converge at iteration 0, so padding costs only the block
        width — keeping compiled-shape reuse under control for ragged
        request streams; the padding is stripped from the result.

        A 1-column batch dispatches through the exact single-RHS
        :meth:`solve` program, so its result is bit-identical to
        ``solve(loads=...)``.

        ``history=True`` records the per-block-iteration ``‖P r‖`` of
        every column on ``FetiManySolution.residual_history`` (shape
        ``(n_rhs, block_iterations)``, converged columns repeating their
        frozen value) without perturbing ``lam``.
        """
        if self.state is None:
            self.preprocess()
        st = self.state
        prob = self.problem
        loads = np.asarray(loads)
        if loads.ndim == 2:
            loads = loads[None]
        S_real, n = st.S_real, prob.subdomains[0].n
        if loads.ndim != 3 or loads.shape[1:] != (S_real, n):
            raise ValueError(
                f"loads must be (n_rhs, {S_real}, {n}) "
                f"(or one (S_real, n) case), got {loads.shape}")
        if rhs_unit < 1:
            raise ValueError(f"rhs_unit must be >= 1, got {rhs_unit}")
        n_rhs = loads.shape[0]
        r_pad = -(-n_rhs // rhs_unit) * rhs_unit

        if r_pad == 1:
            sol = self.solve(tol=tol, max_iter=max_iter, loads=loads[0],
                             history=history)
            self.timings["solve_many_s"] = self.timings["solve_s"]
            self.timings["per_solve_s"] = self.timings["solve_s"]
            return FetiManySolution(
                u=sol.u[None], u_global=sol.u_global[None],
                lam=sol.lam[None], alpha=sol.alpha[None],
                iterations=np.asarray([sol.iterations]),
                residuals=np.asarray([sol.residual]),
                converged=np.asarray([sol.converged]),
                block_iterations=sol.iterations,
                n_rhs=1, n_rhs_padded=1, timings=dict(self.timings),
                refine_outer=sol.refine_outer,
                residual_history=(None if sol.residual_history is None
                                  else sol.residual_history[None]),
            )

        tr = self.telemetry.tracer
        with use_tracer(tr), tr.span("solve", mode=self.mode,
                                     n_rhs=int(n_rhs)) as sp_solve:
            ops = self._solution_ops()
            st.programs.trim()
            coarse = ops.coarse
            t0 = time.perf_counter()
            with tr.span("rhs_setup"):
                if r_pad > n_rhs:
                    loads = np.concatenate(
                        [loads,
                         np.zeros((r_pad - n_rhs, S_real, n), loads.dtype)])
                # column-stacked device layout: (S, n, n_rhs), case last
                F_dev, Fp_dev = self._load_stacks(loads.transpose(1, 2, 0))
                D = ops.dual_rhs_cols(Fp_dev)
                Lam0 = coarse.lambda0(ops.coarse_e_cols(F_dev))
                jax.block_until_ready(D)
            self.timings["rhs_setup_s"] = time.perf_counter() - t0

            mixed = (st.refine_steps > 0 and self.mode == "explicit")
            inner_tol = max(tol, tol_floor(self.dtype)) if mixed else tol

            t0 = time.perf_counter()
            run = self._many_run(inner_tol, max_iter, history)
            with tr.span("pcpg", tol=float(inner_tol)) as sp:
                res: PCPGManyResult = run(D, Lam0, np.zeros(r_pad))
                jax.block_until_ready(res.lam)
                sp.set(block_iterations=int(res.block_iterations))

            Lam = res.lam
            iters = np.asarray(res.iterations).astype(np.int64)
            residuals = np.asarray(res.residual)
            converged = np.asarray(res.converged)
            block_iters = int(res.block_iterations)
            hist_rows = []
            if history:
                hist_rows.append(
                    np.asarray(res.residual_history)[:block_iters])
            n_outer = 0
            if mixed:
                # block defect correction (see solve()): converged columns
                # of the correction solve freeze at iteration 0, so the
                # block outer costs max-over-unconverged-columns iterations
                project = coarse.project
                cn = lambda W: np.asarray(  # noqa: E731
                    jnp.linalg.norm(W, axis=0))
                W0n = cn(project(D - ops.apply_F_many(Lam0)))
                targets = tol * np.maximum(W0n, 1e-300)
                R = D - ops.apply_F_exact_many(Lam)
                Wn = cn(project(R))
                prev = np.full_like(Wn, np.inf)
                while (np.any(Wn > targets) and n_outer < _MAX_OUTER
                       and np.all(Wn <= np.maximum(0.5 * prev, targets))):
                    prev = Wn
                    with tr.span("refine_outer", outer=n_outer) as sp:
                        cres: PCPGManyResult = run(
                            R, jnp.zeros_like(Lam),
                            _CORRECTION_ATOL * targets)
                        Lam = Lam + cres.lam
                        jax.block_until_ready(Lam)
                        sp.set(block_iterations=int(cres.block_iterations))
                    if history:
                        hist_rows.append(
                            np.asarray(cres.residual_history)
                            [:int(cres.block_iterations)])
                    iters += np.asarray(cres.iterations)
                    block_iters += int(cres.block_iterations)
                    n_outer += 1
                    R = D - ops.apply_F_exact_many(Lam)
                    Wn = cn(project(R))
                residuals = Wn
                converged = Wn <= targets
            t_solve = time.perf_counter() - t0
            self.timings["solve_many_s"] = t_solve
            self.timings["per_solve_s"] = t_solve / n_rhs
            metrics.inc("pcpg.solves", n_rhs)
            metrics.inc("pcpg.iterations", int(iters.sum()))
            if n_outer:
                metrics.inc("pcpg.refine_outer", n_outer)

            # ---- recover α and u per column (paper eqs. 5, 7) ----
            t0 = time.perf_counter()
            with tr.span("recover"):
                Flam = ops.apply_F_exact_many(Lam)
                alpha_flat = coarse.alpha(Flam - D)  # (S·k, r), sd-major
                lam_loc = gather_local(Lam, st.lambda_ids)  # (S, m_max, r)
                rhs = Fp_dev - einsum("snm,smr->snr", st.Btp, lam_loc)
                up = ops.factor_solve_many(rhs)
                # force the device work BEFORE the clock is read: the
                # triangular solves dispatch asynchronously and numpy's
                # implicit transfer in _recover_u would otherwise charge
                # them to whatever timer runs next (this timing was
                # dispatch-only before the explicit sync landed)
                jax.block_until_ready(up)
                u, alpha, u_global = self._recover_u(up, alpha_flat, r_pad)
            self.timings["recover_s"] = time.perf_counter() - t0

            keep = slice(0, n_rhs)  # strip rhs_unit padding columns
            residual_history = None
            if history:
                H = np.concatenate(hist_rows, axis=0)  # (blk_iters, r_pad)
                residual_history = np.ascontiguousarray(H.T[keep])
                sp_solve.set(block_iterations=block_iters)

        return FetiManySolution(
            u=u[keep], u_global=u_global[keep],
            lam=np.asarray(Lam).T[keep],
            alpha=alpha[keep],
            iterations=iters[keep],
            residuals=residuals[keep],
            converged=converged[keep],
            block_iterations=block_iters,
            n_rhs=n_rhs, n_rhs_padded=r_pad,
            timings=dict(self.timings),
            refine_outer=n_outer,
            residual_history=residual_history,
        )

    # ---- telemetry surfacing ----
    def report(self) -> dict:
        """Structured telemetry report for this solver: the nested span
        tree (device-synchronized wall times for every pipeline phase),
        the metrics snapshot (plan-cache hits/misses, PCPG iterations,
        tolerance clamps, device bytes), and the per-stack device bytes.
        ``timings`` is kept as a deprecated flat view — prefer the spans,
        which attribute time instead of overwriting it per call."""
        rep = {
            "schema_version": 1,
            "spans": self.telemetry.tracer.tree(),
            "metrics": metrics.snapshot(),
            "timings": dict(self.timings),  # deprecated: use spans
        }
        if self.state is not None:
            rep["device_bytes"] = self.state.device_bytes()
        return rep

    # ---- amortization (paper §5, Fig. 10) ----
    def amortization_report(self,
                            t_assembly_s: Optional[float] = None,
                            t_implicit_iter_s: Optional[float] = None,
                            t_explicit_iter_s: Optional[float] = None,
                            t_dirichlet_s: Optional[float] = None,
                            n_rhs: int = 1,
                            iters_per_solve: Optional[float] = None) -> dict:
        """Iterations needed before the explicit approach wins (paper §1).

        Every timing argument is optional: when omitted it is filled from
        measured telemetry spans — ``t_assembly_s`` from the last
        ``stage:dual`` span less the program builds (``jit:*`` spans)
        inside it, which are no part of the assembly (falling back to the
        autotuner's measured per-subdomain micro-run scaled by S),
        ``t_dirichlet_s`` likewise from the last ``stage:dirichlet`` span
        (else 0), and the CURRENT mode's
        per-iteration time from the last ``pcpg`` span divided by its
        iteration count. The counterpart mode's per-iteration time cannot
        be inferred from this solver's own spans and must be passed; a
        ``ValueError`` names whatever is still missing. The returned dict
        records the provenance of inferred numbers under
        ``"measured_from"``.

        ``t_dirichlet_s`` is the extra preprocessing spent assembling the
        Dirichlet preconditioner's boundary Schur complements (zero when
        preconditioner != "dirichlet"); it goes into the numerator — the
        stage pays for itself through *fewer* iterations, but its wall
        time still delays the break-even point of the explicit operator.

        Multi-RHS extension (ISSUE 6): with ``n_rhs`` > 1 the iteration
        times are understood as *block* iteration times on an
        (n_lambda, n_rhs) stack, so ``amortization_iterations`` stays the
        block-iteration break-even. Passing ``iters_per_solve`` (the
        typical PCPG iteration count of one load case) additionally
        reports ``amortization_solves`` — the number of *load cases*
        after which explicit assembly has paid for itself: each batch of
        ``n_rhs`` cases costs ~``iters_per_solve`` block iterations, so
        break-even solves = break-even iterations / iters_per_solve ·
        n_rhs. The analytic per-iteration cost model
        (:func:`repro.launch.analytic.feti_solve_iter_counts`, shared
        with the dry-run cells) is attached per n_rhs.
        """
        tr = self.telemetry.tracer
        measured_from = {}
        if t_assembly_s is None:
            sp = tr.last("stage:dual")
            if sp is not None:
                t_assembly_s = tr.net_of_builds(sp)
                measured_from["assembly_s"] = "span:stage:dual - jit:*"
            elif (self.plan is not None
                  and getattr(self.plan, "measured_s", None) is not None
                  and self.state is not None):
                t_assembly_s = self.plan.measured_s * self.state.S_real
                measured_from["assembly_s"] = "plan.measured_s * S_real"
        if t_dirichlet_s is None:
            sp = tr.last("stage:dirichlet")
            if sp is not None:
                t_dirichlet_s = tr.net_of_builds(sp)
                measured_from["dirichlet_s"] = "span:stage:dirichlet - jit:*"
            else:
                t_dirichlet_s = 0.0
        if t_implicit_iter_s is None or t_explicit_iter_s is None:
            sp = tr.last("pcpg")
            iters = None
            if sp is not None:
                iters = sp.attrs.get("iterations",
                                     sp.attrs.get("block_iterations"))
            if iters:
                per_iter = sp.duration / iters
                if self.mode == "implicit" and t_implicit_iter_s is None:
                    t_implicit_iter_s = per_iter
                    measured_from["implicit_iter_s"] = "span:pcpg"
                elif self.mode == "explicit" and t_explicit_iter_s is None:
                    t_explicit_iter_s = per_iter
                    measured_from["explicit_iter_s"] = "span:pcpg"
        missing = [nm for nm, v in (("t_assembly_s", t_assembly_s),
                                    ("t_implicit_iter_s", t_implicit_iter_s),
                                    ("t_explicit_iter_s", t_explicit_iter_s))
                   if v is None]
        if missing:
            raise ValueError(
                "amortization_report could not infer "
                + ", ".join(missing) + " from telemetry spans; pass "
                "explicitly (the counterpart mode's per-iteration time "
                "always must be — this solver only ever measures its own "
                "mode)")
        gain = t_implicit_iter_s - t_explicit_iter_s
        overhead = t_assembly_s + t_dirichlet_s
        point = float("inf") if gain <= 0 else overhead / gain
        amort_solves = None
        if iters_per_solve is not None and iters_per_solve > 0:
            amort_solves = point / iters_per_solve * n_rhs
        iter_counts = None
        if self.state is not None:
            from repro.launch.analytic import feti_solve_iter_counts

            iter_counts = feti_solve_iter_counts(
                self.state.S_real, self.problem.m_max, n_rhs=n_rhs,
                fb=self.config.dtype_np.itemsize)
        flops = assembly_flops(self.state.env, self.cfg) if self.state else None
        d_flops = None
        st = self.state
        if st is not None and st.dirichlet_env is not None:
            from repro.sparse.cholesky import block_cholesky_flops

            d_flops = assembly_flops(st.dirichlet_env, st.dirichlet_cfg)
            d_flops = dict(d_flops)
            chol_ii = block_cholesky_flops(
                st.split.n_i, st.dirichlet_cfg.block_size, st.dirichlet_mask)
            # the stage-graph factor dedup elides the interior
            # factorization — the dual factor already holds it
            d_flops["cholesky_ii"] = 0.0 if st.shared_factor else chol_ii
            d_flops["cholesky_ii_saved_by_sharing"] = (
                chol_ii if st.shared_factor else 0.0)
            d_flops["total"] += d_flops["cholesky_ii"]
        return {
            "amortization_iterations": point,
            "amortization_solves": amort_solves,
            "n_rhs": int(n_rhs),
            "assembly_s": t_assembly_s,
            "dirichlet_s": t_dirichlet_s,
            "implicit_iter_s": t_implicit_iter_s,
            "explicit_iter_s": t_explicit_iter_s,
            "assembly_flops_per_subdomain": flops,
            "dirichlet_flops_per_subdomain": d_flops,
            "solve_iter_counts": iter_counts,
            "measured_from": measured_from,
        }


def solve_many(problem: FetiProblem, loads, config=None, *,
               tol: float = 1e-9, max_iter: int = 2000,
               rhs_unit: int = 1) -> FetiManySolution:
    """One-shot multi-load solve: preprocess once, block-PCPG the batch.

    The functional front door for the server-style workload when no solver
    object needs to outlive the call: ``solve_many(problem, loads,
    FetiConfig(...))`` is exactly ``FetiSolver(problem, config)
    .solve_many(loads, ...)``. Callers streaming many batches against one
    preprocessing should hold a :class:`FetiSolver` instead.
    """
    return FetiSolver(problem, config).solve_many(
        loads, tol=tol, max_iter=max_iter, rhs_unit=rhs_unit)
