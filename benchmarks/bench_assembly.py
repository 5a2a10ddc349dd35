"""Paper Fig. 8: whole explicit SC assembly — separated (factor given) and
mixed (numerical factorization + assembly together) configurations,
optimized pipeline vs the dense §3.1 baseline, plus the packed-vs-dense
factor-storage comparison (time AND device bytes: the packed layout keeps
only the fill mask's blocks on device, docs/packed_storage.md).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.core import (
    SchurAssemblyConfig,
    assembly_flops,
    make_assembler,
    schur_dense_baseline,
)
from repro.sparse import block_cholesky_packed, pack_factor
from repro.sparse.cholesky import block_cholesky, block_cholesky_flops
from benchmarks.common import device_bytes, emit, subdomain_problem, time_fn


def run(sizes_2d=(16, 24), sizes_3d=(6, 9), ela_2d=(12, 16), ela_3d=(4, 6),
        bs: int = 32, reps: int = 3,
        stage_graph_cases=((2, (2, 2), (8, 8)), (2, (2, 2), (20, 20)),
                           (3, (2, 1, 1), (3, 3, 3)))) -> list[tuple]:
    rows = []
    cases = [("heat", 2, sizes_2d), ("heat", 3, sizes_3d),
             # elasticity: same node grids are 2-3x the DOFs (node-blocked),
             # so the block-size ↔ DOFs-per-node interplay shows up in the
             # same table at comparable n
             ("elasticity", 2, ela_2d), ("elasticity", 3, ela_3d)]
    for problem, dim, sizes in cases:
        for e in sizes:
            prob = subdomain_problem(dim, e, bs, problem=problem)
            K = jnp.asarray(prob["K"])
            L = jnp.asarray(prob["L"])
            Bt = jnp.asarray(prob["Bt"])
            meta, mask = prob["meta"], prob["mask"]
            n = prob["n"]
            tag = (f"{dim}d/n{n}" if problem == "heat"
                   else f"{dim}d-ela/n{n}")
            # storage pinned: these rows ARE the dense-stored reference the
            # packed rows below compare against (REPRO_STORAGE must not
            # flip them under the CI packed lane)
            cfg = SchurAssemblyConfig(block_size=bs, rhs_block_size=bs,
                                      storage="dense")

            opt = jax.jit(make_assembler(meta, cfg, mask))
            t_sep_opt = time_fn(opt, L, Bt, reps=reps)
            t_sep_dense = time_fn(jax.jit(schur_dense_baseline), L, Bt,
                                  reps=reps)
            rows.append((f"assembly/{tag}/sep_opt", t_sep_opt,
                         f"speedup={t_sep_dense / t_sep_opt:.2f}"))

            def mixed_opt(Kx, Bx):
                Lx = block_cholesky(Kx, bs, mask=mask)
                return make_assembler(meta, cfg, mask)(Lx, Bx)

            def mixed_dense(Kx, Bx):
                Lx = block_cholesky(Kx, bs)
                return schur_dense_baseline(Lx, Bx)

            t_mix_opt = time_fn(jax.jit(mixed_opt), K, Bt, reps=reps)
            t_mix_dense = time_fn(jax.jit(mixed_dense), K, Bt, reps=reps)
            fl = (assembly_flops(meta, cfg)["total"]
                  + block_cholesky_flops(n, bs, mask))
            rows.append((f"assembly/{tag}/mix_opt", t_mix_opt,
                         f"speedup={t_mix_dense / t_mix_opt:.2f};flops={fl}"))

            # packed factor storage: same assembly, factor lives as the
            # fill-mask block stack — report time AND device bytes
            index = prob["index"]
            cfg_p = dataclasses.replace(cfg, storage="packed")
            Lp = jax.block_until_ready(pack_factor(L, index))
            packed = jax.jit(make_assembler(meta, cfg_p, mask))
            t_sep_packed = time_fn(packed, Lp, Bt, reps=reps)
            b_packed, b_dense = device_bytes(Lp), device_bytes(L)
            rows.append((
                f"assembly/{tag}/sep_packed", t_sep_packed,
                f"speedup={t_sep_dense / t_sep_packed:.2f};"
                f"L_bytes={b_packed};dense_L_bytes={b_dense};"
                f"mem_ratio={b_packed / b_dense:.2f}"))

            def mixed_packed(Kx, Bx):
                Lx = block_cholesky_packed(Kx, index)
                return make_assembler(meta, cfg_p, mask)(Lx, Bx)

            t_mix_packed = time_fn(jax.jit(mixed_packed), K, Bt, reps=reps)
            rows.append((
                f"assembly/{tag}/mix_packed", t_mix_packed,
                f"speedup={t_mix_dense / t_mix_packed:.2f};"
                f"mem_ratio={b_packed / b_dense:.2f}"))
    rows += run_stage_graph(cases=stage_graph_cases, reps=max(reps, 3))
    rows += run_precision(reps=max(reps, 3))
    return rows


def run_precision(cases=((2, 16), (2, 24), (3, 9)), bs: int = 32,
                  reps: int = 3) -> list[tuple]:
    """ISSUE 9: the same separated assembly at f32 vs f64 storage — the
    measured time ratio and the exact device-byte halving behind the
    EXPERIMENTS.md mixed-precision table. On this CPU container the time
    ratio reflects halved memory traffic only; the per-dtype FLOP-peak
    ratio of the GPU/TPU targets (2-16x, DEVICE_MODELS) rides on top."""
    rows = []
    for dim, e in cases:
        prob = subdomain_problem(dim, e, bs)
        meta, mask, n = prob["meta"], prob["mask"], prob["n"]
        cfg = SchurAssemblyConfig(block_size=bs, rhs_block_size=bs,
                                  storage="dense")
        asm = jax.jit(make_assembler(meta, cfg, mask))
        out = {}
        for dt in (jnp.float64, jnp.float32):
            L = jnp.asarray(prob["L"], dtype=dt)
            Bt = jnp.asarray(prob["Bt"], dtype=dt)
            out[dt] = (time_fn(asm, L, Bt, reps=reps),
                       device_bytes(L) + device_bytes(Bt))
        (t64, b64), (t32, b32) = out[jnp.float64], out[jnp.float32]
        rows.append((
            f"assembly/{dim}d/n{n}/sep_f32_vs_f64", t32,
            f"f64_us={t64:.1f};speedup={t64 / t32:.2f};"
            f"bytes_f32={b32};bytes_f64={b64};"
            f"byte_ratio={b32 / b64:.2f}"))
    return rows


def run_stage_graph(cases, bs: int = 32, reps: int = 5) -> list[tuple]:
    """ISSUE 7: mixed preprocessing (factorization + BOTH Schur stages)
    through the stage graph with the shared interior factor, against the
    PR-5 two-pipeline baseline (``share_factor=False``: the Dirichlet
    stage refactorizes K_ii). Same compiled-prep timing protocol as
    ``bench_feti`` — pattern fixed, values streamed. The win scales with
    the interior fraction (the saved work is the Dirichlet stage's own
    K_ii factorization plus streaming K_bb instead of the full permuted
    K): ~1.3x on the (2,2)x(20,20) 2D case, nil on small-interior 3D
    boxes."""
    from repro.fem.decomposition import decompose_elasticity_problem
    from repro.feti import FetiConfig
    from repro.feti.assembly import host_stacks, make_cluster_preprocessor

    rows = []
    for dim, grid, eps in cases:
        prob = decompose_elasticity_problem(dim, grid, eps)
        n = prob.subdomains[0].n
        tag = f"{dim}d-ela/n{n}"
        cfg = SchurAssemblyConfig(block_size=bs, rhs_block_size=bs,
                                  storage="dense")

        def prep_time(share):
            fc = FetiConfig(schur=cfg, preconditioner="dirichlet",
                            share_factor=share)
            static, prep = make_cluster_preprocessor(prob, fc)
            st = host_stacks(prob, static, fc)
            args = [jnp.asarray(st[k]) for k in ("Kp", "Btp", "Kd", "Zb")]

            def both_stages(*a):
                _, F, Sb = prep(*a)
                return F, Sb

            return time_fn(both_stages, *args, reps=reps), static["share"]

        t_base, shared0 = prep_time(False)
        t_shared, shared1 = prep_time(True)
        assert not shared0 and shared1
        rows.append((f"assembly/{tag}/mix_two_pipelines", t_base, "baseline"))
        rows.append((f"assembly/{tag}/mix_shared_factor", t_shared,
                     f"speedup={t_base / t_shared:.2f}"))
    return rows


def main():
    emit(run())


if __name__ == "__main__":
    main()
