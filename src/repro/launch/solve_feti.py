"""FETI solve launcher (the paper's 'serving' equivalent):
``python -m repro.launch.solve_feti --arch feti-heat-2d --smoke``.

Runs preprocess (factorization + sparsity-utilizing SC assembly) and the
PCPG solve for a registered FETI architecture, reports stage timings,
iteration counts and the amortization point, and validates against the
undecomposed global solve.

``--problem {heat,elasticity}`` overrides the architecture's workload:
``elasticity`` solves vector-valued P1 linear elasticity (node-blocked
2-3 DOFs per node) with rigid-body-mode kernels of dimension 3 (2D) / 6
(3D) — the paper's target engineering setting (docs/elasticity.md).
Dedicated ``feti-elasticity-{2d,3d}`` architectures default to it.

``--autotune`` replaces the architecture's hand-picked assembly config with
the planner of :mod:`repro.core.autotune` (the paper's Table-1 choice made
automatically), prints the selected plan with predicted-vs-measured cost,
and cross-checks the autotuned SCs against the dense baseline of [9].

``--devices N`` shards the subdomain axis over an N-device ``("data",)``
mesh (:mod:`repro.feti.sharded`). On a CPU host the flag forces N
host-platform devices via XLA's ``--xla_force_host_platform_device_count``,
so the distributed pipeline runs end to end without accelerators; on an
accelerator host with fewer than N devices it is an error. Combined with
``--validate`` the sharded solution is additionally checked against a
fresh single-device solve.

The persistent compilation cache follows ``JAX_COMPILATION_CACHE_DIR`` when
it is set, and otherwise lives in the checkout's ``.jax_cache``
(:mod:`repro.launch.compile_cache`).
"""
from __future__ import annotations

import argparse
import sys


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--arch", default="feti-heat-2d")
    p.add_argument("--smoke", action="store_true")
    p.add_argument("--problem", choices=("heat", "elasticity"), default=None,
                   help="workload override: scalar heat (1 DOF/node, "
                        "kernel dim 1) or vector linear elasticity "
                        "(2-3 DOFs/node, rigid-body kernel dim 3/6); "
                        "default: the architecture's own problem")
    p.add_argument("--mode", choices=("explicit", "implicit"),
                   default="explicit")
    p.add_argument("--precond", choices=("lumped", "dirichlet", "none"),
                   default="lumped",
                   help="PCPG preconditioner: lumped (B K Bᵀ, free), "
                        "dirichlet (B S_b Bᵀ with the primal boundary "
                        "Schur complement assembled on-device through the "
                        "same sparsity-utilizing pipeline; "
                        "docs/preconditioners.md), or none")
    p.add_argument("--tol", type=float, default=1e-9)
    p.add_argument("--validate", action="store_true",
                   help="compare against the global sparse solve (and, "
                        "with --devices, against a single-device solve)")
    p.add_argument("--autotune", action="store_true",
                   help="let the stage graph's joint planner pick every "
                        "assembly stage's config (docs/stage_graph.md)")
    p.add_argument("--fused", action="store_true",
                   help="use the fused TRSM→SYRK Pallas megakernel "
                        "(stepped_trsm_syrk) instead of the architecture's "
                        "two-kernel schedule; ignored with --autotune "
                        "(the planner already enumerates fused=True)")
    p.add_argument("--storage", choices=("dense", "packed"), default=None,
                   help="factor storage layout: dense (S,n,n) stacks or "
                        "packed block-sparse stacks in the symbolic "
                        "fill-mask layout (docs/packed_storage.md); "
                        "default: the config's choice, or the autotuner's "
                        "with --autotune")
    p.add_argument("--dtype", choices=("f64", "f32", "bf16"), default="f64",
                   help="assembly/storage dtype of the device stacks "
                        "(docs/mixed_precision.md): f64 (reference), f32 "
                        "(half the bytes; f64 accuracy recovered via "
                        "iterative refinement + defect-correction outers), "
                        "bf16 (experimental, storage-only)")
    p.add_argument("--refine", type=int, default=None, metavar="STEPS",
                   help="interior-solve refinement steps (default: 0 for "
                        "f64, 2 for reduced dtypes; 0 disables refinement "
                        "and solves entirely at the storage dtype)")
    p.add_argument("--no-plan-cache", action="store_true",
                   help="ignore + don't write the on-disk plan cache")
    p.add_argument("--devices", type=int, default=0, metavar="N",
                   help="shard subdomains over an N-device ('data',) mesh "
                        "(forces N host devices on CPU-only hosts)")
    p.add_argument("--n-rhs", type=int, default=0, metavar="R",
                   help="solve R stacked load cases through the multi-RHS "
                        "block-PCPG service (solve_many: preprocess once, "
                        "stream the batch; docs/multirhs.md) instead of "
                        "the single-load solve; with --validate each "
                        "column is checked against its own global solve")
    p.add_argument("--trace", default=None, metavar="OUT.json",
                   help="export the run's telemetry as a Chrome-trace JSON "
                        "(chrome://tracing / Perfetto): nested spans for "
                        "every pipeline phase plus the metrics snapshot; "
                        "implies per-iteration residual history "
                        "(docs/observability.md)")
    p.add_argument("--report", action="store_true",
                   help="print the structured telemetry report "
                        "(FetiSolver.report(): span tree, metrics, device "
                        "bytes) as JSON after the solve")
    args = p.parse_args(argv)

    if args.devices:
        # must precede jax backend init — which is why all jax work
        # happens inside main
        from repro.launch.mesh import force_host_device_count

        force_host_device_count(args.devices)

    import jax

    from repro.launch.compile_cache import enable_compile_cache

    jax.config.update("jax_enable_x64", True)
    enable_compile_cache()

    import numpy as np

    from repro.configs import FetiArchConfig, get_config, get_smoke_config
    from repro.core import SchurAssemblyConfig
    from repro.fem import decompose_problem
    from repro.feti import FetiConfig, FetiSolver
    from repro.launch.mesh import make_feti_mesh

    mesh = None
    if args.devices:
        # make_feti_mesh raises when fewer devices exist than asked for
        mesh = make_feti_mesh(args.devices)
        print(f"[feti] mesh: {mesh.shape['data']} device(s) on axis 'data'")

    fc = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    if not isinstance(fc, FetiArchConfig):
        raise SystemExit(f"{args.arch} is not a FETI architecture")

    problem = args.problem or fc.problem
    prob = decompose_problem(problem, fc.dim, fc.sub_grid, fc.elems_per_sub)
    print(f"[feti] {fc.name}: problem={problem} "
          f"({prob.ndof_per_node} DOF/node, kernel dim {prob.kernel_dim}), "
          f"{prob.n_subdomains} subdomains x {prob.subdomains[0].n} DOFs, "
          f"{prob.n_lambda} multipliers")

    if args.autotune:
        cfg = "auto"
    elif args.fused:
        # the fused megakernel needs Pallas; interpret off-TPU so the
        # smoke lane exercises the exact kernel logic on CPU
        cfg = SchurAssemblyConfig(
            block_size=fc.block_size, rhs_block_size=fc.rhs_block_size,
            use_pallas=True, fused=True,
            interpret=jax.devices()[0].platform != "tpu",
        )
    else:
        cfg = SchurAssemblyConfig(
            trsm_variant=fc.trsm_variant, syrk_variant=fc.syrk_variant,
            block_size=fc.block_size, rhs_block_size=fc.rhs_block_size,
        )
    config = FetiConfig(schur=cfg, mode=args.mode,
                        preconditioner=args.precond,
                        plan_cache=not args.no_plan_cache, mesh=mesh,
                        storage=args.storage, dtype=args.dtype,
                        refine=args.refine)
    solver = FetiSolver(prob, config)
    if config.reduced:
        print(f"[feti] dtype={config.dtype_name} "
              f"refine={config.resolved_refine()} "
              f"solve_dtype={np.dtype(config.solve_dtype).name}")
    history = bool(args.trace)  # the trace embeds the convergence curve
    if args.n_rhs > 0:
        # multi-RHS service: preprocess once, stream a load-case batch
        loads = prob.load_cases(args.n_rhs, kind="sweep")
        sol = solver.solve_many(loads, tol=args.tol, history=history)
    else:
        sol = solver.solve(tol=args.tol, history=history)

    if args.trace or args.report:
        import json

        rep = solver.report()
        if args.trace:
            solver.telemetry.tracer.to_chrome_trace(
                args.trace, metrics=rep["metrics"])
            print(f"[feti] telemetry trace -> {args.trace}")
        if args.report:
            print(json.dumps(rep, indent=1, default=float))

    st = solver.state
    if st is not None:
        by = st.device_bytes()
        print(f"[feti] storage={st.storage} device bytes: "
              f"L={by['L']:,} K={by['K']:,} Btp={by['Btp']:,} "
              f"F={by['F']:,} (dense L would be {by['dense_L']:,})")
        if st.Sb is not None:
            sp = st.split
            shared = " (shared interior factor)" if st.shared_factor else ""
            print(f"[feti] precond=dirichlet: boundary/interior split "
                  f"{sp.n_b}/{sp.n_i} of {sp.n} DOFs, "
                  f"Sb={by['Sb']:,} Btb={by['Btb']:,} bytes{shared}")
            if st.dirichlet_plan is not None:
                for line in st.dirichlet_plan.summary().splitlines():
                    print(f"[autotune:dirichlet] {line}")

    if args.autotune and solver.plan is not None:
        for line in solver.plan.summary().splitlines():
            print(f"[autotune] {line}")
        if solver.state is not None and solver.state.F is not None:
            import jax.numpy as jnp

            from repro.core import schur_dense_baseline
            from repro.sparse import PackedBlocks

            st = solver.state
            L_ref = st.L.unpack() if isinstance(st.L, PackedBlocks) else st.L
            F_ref = jax.vmap(schur_dense_baseline)(L_ref, st.Btp)
            err = float(jnp.max(jnp.abs(st.F - F_ref)))
            print(f"[autotune] max |F_auto - F_dense_baseline| = {err:.2e}")
            if err > 1e-8:
                print("[autotune] FAIL: autotuned assembly disagrees with "
                      "the dense baseline")
                return 1
    if args.n_rhs > 0:
        converged = bool(sol.converged.all())
        iters = " ".join(str(int(i)) for i in sol.iterations)
        print(f"[feti] mode={args.mode} n_rhs={sol.n_rhs} "
              f"(padded {sol.n_rhs_padded}) iters=[{iters}] "
              f"block_iters={sol.block_iterations} converged={converged}")
        print(f"[feti] preprocess={sol.timings['preprocess_s']:.2f}s "
              f"solve_many={sol.timings['solve_many_s']:.2f}s "
              f"per_solve={sol.timings['per_solve_s'] * 1e3:.1f}ms")
        if args.validate:
            refs = prob.reference_solutions(loads)
            scale = np.abs(refs).max()
            err = np.max(np.abs(sol.u_global - refs)) / scale
            print(f"[feti] max per-column rel err vs global solves: "
                  f"{err:.2e}")
            if err > 1e-6:
                return 1
            if mesh is not None:
                ref = FetiSolver(prob, config.replace(mesh=None)
                                 ).solve_many(loads, tol=args.tol)
                du = np.max(np.abs(sol.u_global - ref.u_global))
                print(f"[feti] sharded vs single-device solve_many: "
                      f"max|Δu|={du:.2e}")
                if du > 1e-9:
                    print("[feti] FAIL: sharded solve_many diverged from "
                          "the single-device one")
                    return 1
        return 0 if converged else 1

    outer = f" refine_outer={sol.refine_outer}" if config.reduced else ""
    print(f"[feti] mode={args.mode} iters={sol.iterations} "
          f"residual={sol.residual:.2e} converged={sol.converged}{outer}")
    print(f"[feti] preprocess={sol.timings['preprocess_s']:.2f}s "
          f"solve={sol.timings['solve_s']:.2f}s")

    if args.validate:
        u_ref = prob.reference_solution()
        err = np.max(np.abs(sol.u_global - u_ref)) / np.abs(u_ref).max()
        print(f"[feti] rel err vs global solve: {err:.2e}")
        if err > 1e-6:
            return 1
        if mesh is not None:
            # the distributed run must reproduce the single-device one.
            # With --precond dirichlet the S_b stacks come from a
            # differently-scheduled compiled program under shard_map and
            # agree only to machine epsilon, so the PCPG stopping test can
            # flip by one iteration near the threshold — allow that single
            # flip there; the solution agreement stays strict either way.
            ref = FetiSolver(prob, config.replace(mesh=None)
                             ).solve(tol=args.tol)
            du = np.max(np.abs(sol.u_global - ref.u_global))
            print(f"[feti] sharded vs single-device: max|Δu|={du:.2e} "
                  f"iters {sol.iterations} vs {ref.iterations}")
            iter_slack = 1 if args.precond == "dirichlet" else 0
            if du > 1e-9 or abs(sol.iterations - ref.iterations) > iter_slack:
                print("[feti] FAIL: sharded solve diverged from the "
                      "single-device solve")
                return 1
    return 0 if sol.converged else 1


if __name__ == "__main__":
    sys.exit(main())
