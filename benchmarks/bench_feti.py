"""Paper Figs. 9 & 10: FETI preprocessing across dual-operator approaches
and the amortization points.

Approaches benchmarked (paper Table 2, mapped to this framework):
  impl            — numerical factorization only (implicit dual op)
  expl_dense      — factorization + dense §3.1 SC assembly   (= expl_cuda)
  expl_opt        — factorization + sparsity-utilizing SC    (= expl_gpu_opt)
  expl_dirichlet  — expl_opt + the dirichlet preconditioner's primal
                    boundary Schur stage (docs/preconditioners.md)

The lumped-vs-dirichlet rows report PCPG iterations, preconditioner
apply time, the dirichlet stage's preprocessing overhead, and the
amortization point WITH that overhead in the numerator
(``FetiSolver.amortization_report(t_dirichlet_s=...)``).

Amortization point = preprocessing overhead / per-iteration saving
(implicit TRSV pair vs explicit GEMV), reported per subdomain size — the
paper's headline claim is ≈10 iterations, flat across sizes.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import SchurAssemblyConfig
from repro.fem import decompose_problem
from repro.feti import FetiConfig, FetiSolver
from repro.feti.assembly import preprocess_cluster
from repro.feti.operator import (
    dirichlet_preconditioner,
    explicit_dual_apply,
    implicit_dual_apply,
    lumped_preconditioner,
)
from benchmarks.common import emit, fmt_bytes, time_fn


def _write_convergence(records, path=None):
    """Write the lumped-vs-dirichlet convergence fixture: one JSONL record
    per (case, preconditioner) carrying the full per-iteration ``‖P r‖``
    history from ``solve(history=True)`` — the raw material for the
    preconditioner-comparison convergence plots (docs/observability.md).
    Validated in CI by ``python -m repro.obs.validate``."""
    import json
    from pathlib import Path

    if path is None:
        path = (Path(__file__).resolve().parents[1]
                / "results" / "convergence_feti.jsonl")
    path.parent.mkdir(parents=True, exist_ok=True)
    with open(path, "w") as f:
        for rec in records:
            f.write(json.dumps(rec) + "\n")
    return path


def run(cases=(("heat", 2, (2, 2), (8, 8)), ("heat", 2, (2, 2), (16, 16)),
               ("heat", 3, (2, 2, 1), (4, 4, 4)),
               ("heat", 3, (2, 2, 1), (6, 6, 6)),
               # elasticity: 2-3 DOFs/node, kernel dim 3/6 — heat-vs-
               # elasticity preprocessing cost at comparable DOF counts
               ("elasticity", 2, (2, 2), (8, 8)),
               ("elasticity", 3, (2, 2, 1), (3, 3, 3))),
        bs: int = 16, reps: int = 3,
        n_rhs_list=(1, 4, 16, 64)) -> list[tuple]:
    rows = []
    convergence = []  # (case, preconditioner) residual histories
    for problem, dim, grid, eps in cases:
        prob = decompose_problem(problem, dim, grid, eps)
        n = prob.subdomains[0].n
        tag = f"{dim}d/n{n}" if problem == "heat" else f"{dim}d-ela/n{n}"
        # storage pinned to dense: these are the dense-stored references
        # the preproc_expl_packed row compares against (REPRO_STORAGE must
        # not flip them under the CI packed lane)
        cfg_opt = SchurAssemblyConfig(block_size=bs, rhs_block_size=bs,
                                      storage="dense")
        cfg_dense = SchurAssemblyConfig(trsm_variant="dense",
                                        syrk_variant="dense",
                                        block_size=bs, rhs_block_size=bs,
                                        prune=False, storage="dense")

        import numpy as np

        from repro.feti.assembly import host_stacks, make_cluster_preprocessor

        def preprocess_time(cfg, explicit, dirichlet=False,
                            share_factor="auto"):
            """Time the COMPILED preprocessing (pattern fixed, values new —
            the paper's multi-step regime)."""
            fc = FetiConfig(
                schur=cfg,
                mode="explicit" if explicit else "implicit",
                preconditioner="dirichlet" if dirichlet else "lumped",
                share_factor=share_factor)
            static, prep = make_cluster_preprocessor(prob, fc)
            st = host_stacks(prob, static, fc)
            args = [jnp.asarray(st["Kp"]), jnp.asarray(st["Btp"])]
            if dirichlet:
                args += [jnp.asarray(st["Kd"]), jnp.asarray(st["Zb"])]
            idx = 2 if dirichlet else (1 if explicit else 0)
            us = time_fn(lambda *a: prep(*a)[idx], *args, reps=reps)
            st = preprocess_cluster(prob, fc)
            return st, us

        import dataclasses

        cfg_packed = dataclasses.replace(cfg_opt, storage="packed")

        st_impl, t_impl = preprocess_time(cfg_opt, explicit=False)
        _, t_expl_dense = preprocess_time(cfg_dense, explicit=True)
        st_expl, t_expl_opt = preprocess_time(cfg_opt, explicit=True)
        st_pack, t_expl_packed = preprocess_time(cfg_packed, explicit=True)
        rows.append((f"feti/{tag}/preproc_impl", t_impl, fmt_bytes(st_impl)))
        rows.append((f"feti/{tag}/preproc_expl_dense", t_expl_dense,
                     f"slowdown_vs_impl={t_expl_dense / t_impl:.2f}"))
        rows.append((f"feti/{tag}/preproc_expl_opt", t_expl_opt,
                     f"slowdown_vs_impl={t_expl_opt / t_impl:.2f};"
                     + fmt_bytes(st_expl)))
        rows.append((f"feti/{tag}/preproc_expl_packed", t_expl_packed,
                     f"slowdown_vs_impl={t_expl_packed / t_impl:.2f};"
                     + fmt_bytes(st_pack)))

        # per-iteration dual operator application
        nl = prob.n_lambda
        lam = jnp.zeros((nl,))
        imp = jax.jit(lambda p: implicit_dual_apply(
            st_impl.L, st_impl.Btp, st_impl.lambda_ids, nl, p))
        exp = jax.jit(lambda p: explicit_dual_apply(
            st_expl.F, st_expl.lambda_ids, nl, p))
        t_it_imp = time_fn(imp, lam, reps=reps)
        t_it_exp = time_fn(exp, lam, reps=reps)
        overhead = t_expl_opt - t_impl
        gain = t_it_imp - t_it_exp
        amort = overhead / gain if gain > 0 else float("inf")
        rows.append((f"feti/{tag}/iter_implicit", t_it_imp, ""))
        rows.append((f"feti/{tag}/iter_explicit", t_it_exp,
                     f"amortization_iters={amort:.1f}"))

        # end-to-end sanity: solve and report iterations (history feeds
        # the lumped-vs-dirichlet convergence fixture in results/)
        solver = FetiSolver(prob, cfg_opt)
        sol = solver.solve(tol=1e-8, max_iter=500, history=True)
        rows.append((f"feti/{tag}/pcpg_iterations", float(sol.iterations),
                     f"converged={sol.converged}"))
        convergence.append({
            "schema_version": 1, "case": tag, "preconditioner": "lumped",
            "tol": 1e-8, "iterations": int(sol.iterations),
            "converged": bool(sol.converged),
            "residual_history": [float(x) for x in sol.residual_history],
        })

        # ---- multi-RHS block solve service (ISSUE 6) ----
        # The primary number is the warm END-TO-END wall time per
        # delivered solution (RHS setup + block PCPG + α/u recovery,
        # preprocessing excluded): the per-batch fixed costs amortize
        # over the columns and the (S, m, m) operator stack streams once
        # per *block* iteration whatever the column count, so cost per
        # solve collapses as n_rhs grows. Rows reuse the SAME solver
        # (the server pattern of docs/multirhs.md: preprocess once,
        # stream batches); break-even is reported in *solves* via
        # amortization_report(n_rhs=..., iters_per_solve=...).
        from repro.feti.operator import (
            explicit_dual_apply_many,
            implicit_dual_apply_many,
        )
        from repro.obs.timing import time_call

        for r in n_rhs_list:
            loads = prob.load_cases(r, kind="random", seed=0)
            t_best, solm = time_call(
                lambda: solver.solve_many(loads, tol=1e-8, max_iter=500),
                reps=reps, warmup=1)
            t_many = t_best * 1e6
            Lam = jnp.zeros((nl, r))
            imp_m = jax.jit(lambda p: implicit_dual_apply_many(
                st_impl.L, st_impl.Btp, st_impl.lambda_ids, nl, p))
            exp_m = jax.jit(lambda p: explicit_dual_apply_many(
                st_expl.F, st_expl.lambda_ids, nl, p))
            t_blk_imp = time_fn(imp_m, Lam, reps=reps)
            t_blk_exp = time_fn(exp_m, Lam, reps=reps)
            rep_m = solver.amortization_report(
                t_assembly_s=(t_expl_opt - t_impl) * 1e-6,
                t_implicit_iter_s=t_blk_imp * 1e-6,
                t_explicit_iter_s=t_blk_exp * 1e-6,
                n_rhs=r,
                iters_per_solve=float(np.mean(np.asarray(solm.iterations))),
            )
            ai = rep_m["solve_iter_counts"]["arithmetic_intensity"]
            rows.append((
                f"feti/{tag}/solve_many_r{r}",
                t_many / r,  # warm end-to-end wall time per solve, us
                f"total_us={t_many:.0f};"
                f"pcpg_us={solm.timings['solve_many_s'] * 1e6:.0f};"
                f"rhs_setup_us={solm.timings['rhs_setup_s'] * 1e6:.0f};"
                f"recover_us={solm.timings['recover_s'] * 1e6:.0f};"
                f"block_iters={int(solm.block_iterations)};"
                f"blockiter_expl_us={t_blk_exp:.1f};"
                f"blockiter_impl_us={t_blk_imp:.1f};"
                f"amort_solves={rep_m['amortization_solves']:.1f};"
                f"analytic_ai={ai:.2f}"))

        # ---- lumped vs dirichlet preconditioner (ISSUE 5) ----
        st_dir, t_expl_dir = preprocess_time(cfg_opt, explicit=True,
                                             dirichlet=True)
        t_dir_stage = t_expl_dir - t_expl_opt  # the stage's extra cost
        apply_l = jax.jit(lambda w: lumped_preconditioner(
            st_expl.K, st_expl.Btp, st_expl.lambda_ids, nl, w))
        apply_d = jax.jit(lambda w: dirichlet_preconditioner(
            st_dir.Sb, st_dir.Btb, st_dir.lambda_ids, nl, w))
        t_ap_l = time_fn(apply_l, lam, reps=reps)
        t_ap_d = time_fn(apply_d, lam, reps=reps)
        solver_dir = FetiSolver(prob, FetiConfig(
            schur=cfg_opt, preconditioner="dirichlet"))
        sol_dir = solver_dir.solve(tol=1e-8, max_iter=500, history=True)
        convergence.append({
            "schema_version": 1, "case": tag,
            "preconditioner": "dirichlet",
            "tol": 1e-8, "iterations": int(sol_dir.iterations),
            "converged": bool(sol_dir.converged),
            "residual_history":
                [float(x) for x in sol_dir.residual_history],
        })
        rep = solver_dir.amortization_report(
            t_assembly_s=(t_expl_opt - t_impl) * 1e-6,
            t_implicit_iter_s=t_it_imp * 1e-6,
            t_explicit_iter_s=t_it_exp * 1e-6,
            t_dirichlet_s=t_dir_stage * 1e-6,
        )
        rows.append((f"feti/{tag}/precond_lumped", t_ap_l,
                     f"pcpg_iters={sol.iterations}"))
        rows.append((
            f"feti/{tag}/precond_dirichlet", t_ap_d,
            f"pcpg_iters={sol_dir.iterations};"
            f"iter_saving_vs_lumped={sol.iterations - sol_dir.iterations};"
            f"dirichlet_stage_us={t_dir_stage:.1f};"
            f"amort_iters_with_dirichlet="
            f"{rep['amortization_iterations']:.1f};"
            f"Sb_bytes={st_dir.device_bytes()['Sb']}"))
    path = _write_convergence(convergence)
    rows.append(("feti/convergence_fixture", float(len(convergence)),
                 f"path={path}"))
    rows += run_precision(reps=reps)
    return rows


def run_precision(cases=(("heat", 2, (2, 2), (16, 16)),
                         ("elasticity", 2, (2, 2), (8, 8))),
                  bs: int = 16, reps: int = 3) -> list[tuple]:
    """ISSUE 9: the f32-storage solver against the f64 reference — warm
    end-to-end solve time, persistent device bytes (the f32 stacks halve
    every per-iteration stream; Kreg adds one f64 packed stack for the
    refinement loop), PCPG iterations and the defect-correction outers
    that recover the f64-level residual (docs/mixed_precision.md)."""
    import numpy as np

    from repro.obs.timing import time_call

    rows = []
    cfg = SchurAssemblyConfig(block_size=bs, rhs_block_size=bs,
                              storage="dense")
    for problem, dim, grid, eps in cases:
        prob = decompose_problem(problem, dim, grid, eps)
        n = prob.subdomains[0].n
        tag = f"{dim}d/n{n}" if problem == "heat" else f"{dim}d-ela/n{n}"
        out = {}
        for dt in ("f64", "f32"):
            solver = FetiSolver(prob, FetiConfig(schur=cfg, dtype=dt))
            solver.solve(tol=1e-8, max_iter=500)  # preprocess + compile
            best_s, sol = time_call(
                lambda: solver.solve(tol=1e-8, max_iter=500),
                reps=reps, warmup=0)
            best = best_s * 1e6
            by = solver.state.device_bytes()
            err = np.abs(sol.u_global - prob.reference_solution()).max()
            out[dt] = (best, sol, by, err)
        (t64, s64, b64, e64), (t32, s32, b32, e32) = out["f64"], out["f32"]
        rows.append((
            f"feti/{tag}/solve_f32_vs_f64", t32,
            f"f64_us={t64:.0f};time_ratio={t64 / t32:.2f};"
            f"iters={s32.iterations}(f64={s64.iterations});"
            f"refine_outer={s32.refine_outer};"
            f"stack_bytes_f32={b32['total']};stack_bytes_f64={b64['total']};"
            f"stream_bytes_f32={b32['total'] - b32['Kreg']};"
            f"byte_ratio={(b32['total'] - b32['Kreg']) / b64['total']:.2f};"
            f"max_err_f32={e32:.1e};max_err_f64={e64:.1e}"))
    return rows


def main():
    emit(run())


if __name__ == "__main__":
    main()
