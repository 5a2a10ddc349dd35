#!/usr/bin/env python3
"""Chip smoke test: drive the FETI solver once on a TPU at full size.

    python chip_smoke.py             # one chip: heat2d, pallas, f64 phases
    python chip_smoke.py --chips 4   # the sharded path on 4 chips vs 1

Everything runs in this one process, through the entry points a user calls
(``decompose_problem``, ``FetiConfig``, ``FetiSolver``), at the registry's
full sizes, and every result is checked against the scipy oracle of the
undecomposed problem (``FetiProblem.reference_solution``). Phases, one
chip:

  * heat2d — ``feti-heat-2d`` (8x8 subdomains of 64x64 elements), explicit
    dual operator, lumped preconditioner, f32 stacks in packed storage with
    the default refinement; relative error <= 1e-8.
  * pallas — on all 64 heat2d factors, between heat2d's preprocessing and
    its solve, the compiled stepped TRSM->SYRK Pallas kernels (dense and
    packed storage, unfused and fused), :data:`PALLAS_CHUNK` subdomains per
    call, each F compared with ``schur_dense_baseline`` within
    :data:`PALLAS_REL_BOUND`.
  * f64 — ``feti-elasticity-2d`` at the default f64 with the Dirichlet
    preconditioner, packed storage as in heat2d; relative error <= 1e-8.

``--chips 4`` runs only the sharded path: heat2d on a 4-chip ``("data",)``
mesh against the same solve on one chip; both meet the oracle bar, their
iteration counts differ by at most one, and the stacks must sit on all
four devices.

Each phase prints one ``[phase] {json}`` line, with the process's peak
host memory (``host_max_rss_gib``) and device memory so far. The last line
of standard output is ``{"ok": true, "device": {...}}``. Any failed check
raises, so the script exits non-zero without that line; so it does when
JAX finds no TPU, when the repository's ``src/`` is not next to this file,
and when the run passes :data:`DEADLINE_S` (all threads' stacks go to
standard error first, as they do on SIGTERM). The persistent compilation
cache follows ``JAX_COMPILATION_CACHE_DIR``, else the checkout's
``.jax_cache``.
"""
from __future__ import annotations

import argparse
import faulthandler
import json
import os
import resource
import signal
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(HERE, "src")

ORACLE_BOUND = 1e-8  # max|u - u_ref| / max|u_ref|, every solve phase
# F from the f32 Pallas kernels vs the f32 dense baseline, relative to
# max|F|: both are f32 TRSM+SYRK pipelines at HIGHEST precision that differ
# only in operation order, so they agree to a few hundred f32 ulps
PALLAS_REL_BOUND = 1e-4
PALLAS_CHUNK = 16  # subdomains per kernel call: bounds the dense factors
DEADLINE_S = 1140  # the whole run, compilation included


def check(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"check failed: {what}")


def emit(phase: str, **fields) -> None:
    print(f"[{phase}] {json.dumps(fields, default=float)}", flush=True)


def peak_bytes(device) -> int:
    return int(device.memory_stats()["peak_bytes_in_use"])


def host_max_rss_gib() -> float:
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 2**20


def arch_schur(fc, **overrides):
    """The registered architecture's hand-picked assembly config."""
    from repro.core import SchurAssemblyConfig

    kw = dict(trsm_variant=fc.trsm_variant, syrk_variant=fc.syrk_variant,
              block_size=fc.block_size, rhs_block_size=fc.rhs_block_size)
    kw.update(overrides)
    return SchurAssemblyConfig(**kw)


def decompose(arch: str):
    from repro.configs import get_config
    from repro.fem import decompose_problem

    fc = get_config(arch)
    t0 = time.perf_counter()
    prob = decompose_problem(fc.problem, fc.dim, fc.sub_grid,
                             fc.elems_per_sub)
    t1 = time.perf_counter()
    u_ref = prob.reference_solution()
    emit("decompose", arch=arch, decompose_s=t1 - t0,
         oracle_s=time.perf_counter() - t1,
         host_max_rss_gib=host_max_rss_gib())
    return fc, prob, u_ref


def run_solve(prob, config, u_ref, tol: float = 1e-9, after_prep=None):
    """Preprocess + solve through FetiSolver; returns (solver, solution,
    fields to report). Fails unless the solve converged to the oracle.
    ``after_prep(solver)`` runs between preprocessing and the solve."""
    import numpy as np

    from repro.feti import FetiSolver

    solver = FetiSolver(prob, config)
    t0 = time.perf_counter()
    solver.preprocess()
    pre_s = time.perf_counter() - t0
    prep_rss = host_max_rss_gib()
    if after_prep is not None:
        after_prep(solver)
    t0 = time.perf_counter()
    sol = solver.solve(tol=tol)
    solve_s = time.perf_counter() - t0
    err = float(np.max(np.abs(sol.u_global - u_ref)) / np.abs(u_ref).max())
    by = solver.state.device_bytes()
    fields = dict(
        S=prob.n_subdomains, n=prob.subdomains[0].n, n_lambda=prob.n_lambda,
        storage=solver.state.storage, dtype=config.dtype_name,
        preconditioner=config.preconditioner, mode=config.mode,
        preprocess_s=pre_s, host_max_rss_after_prep_gib=prep_rss,
        solve_s=solve_s, iterations=sol.iterations,
        refine_outer=sol.refine_outer, converged=bool(sol.converged),
        rel_err=err, device_bytes={k: v for k, v in by.items()
                                   if k != "per_stage"})
    check(bool(sol.converged), f"solve converged ({fields})")
    check(err <= ORACLE_BOUND, f"rel err {err:.3e} <= {ORACLE_BOUND}")
    return solver, sol, fields


def heat2d_config(mesh=None):
    from repro.configs import get_config
    from repro.feti import FetiConfig

    fc = get_config("feti-heat-2d")
    return FetiConfig(schur=arch_schur(fc, storage="packed"),
                      mode="explicit", preconditioner="lumped", dtype="f32",
                      storage="packed", plan_cache=False, mesh=mesh)


def phase_heat2d(device):
    """heat2d, with the pallas phase run on its factors between its
    preprocessing and its solve."""
    fc, prob, u_ref = decompose("feti-heat-2d")
    _, _, fields = run_solve(prob, heat2d_config(), u_ref,
                             after_prep=phase_pallas)
    emit("heat2d", arch=fc.name, peak_device_bytes=peak_bytes(device),
         host_max_rss_gib=host_max_rss_gib(), **fields)


def phase_pallas(solver):
    """The compiled stepped Pallas kernels on every heat2d factor, through
    the production assembler (:func:`repro.feti.assembly.batched_assemble`),
    :data:`PALLAS_CHUNK` subdomains per call."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from repro.core import schur_dense_baseline
    from repro.feti.assembly import batched_assemble
    from repro.sparse import PackedBlocks

    st = solver.state
    unpack = jax.jit(st.index.unpack)
    baseline = jax.jit(jax.vmap(schur_dense_baseline))
    base = dataclasses.replace(st.cfg, use_pallas=True, prune=False,
                               interpret=False)
    variants = {
        "dense": dataclasses.replace(base, trsm_variant="rhs_split",
                                     syrk_variant="output_split",
                                     storage="dense"),
        "packed": dataclasses.replace(base, trsm_variant="factor_split",
                                      syrk_variant="output_split",
                                      storage="packed"),
        "fused_dense": dataclasses.replace(
            base, trsm_variant="rhs_split", syrk_variant="output_split",
            fused=True, storage="dense"),
        "fused_packed": dataclasses.replace(
            base, trsm_variant="factor_split", syrk_variant="output_split",
            fused=True, storage="packed"),
    }
    runs = {name: jax.jit(lambda L, B, cp, icp, cfg=cfg: batched_assemble(
        L, B, cp, icp, st.env, cfg, st.block_mask))
        for name, cfg in variants.items()}
    errs = dict.fromkeys(runs, 0.0)
    secs = dict.fromkeys(runs, 0.0)
    scale = 0.0
    t_all = time.perf_counter()
    for s0 in range(0, st.S, PALLAS_CHUNK):
        part = slice(s0, s0 + PALLAS_CHUNK)
        L_packed = PackedBlocks(st.L.values[part], st.index)
        L_dense = unpack(L_packed.values)
        Btp, cp, icp = st.Btp[part], st.col_perm[part], st.inv_col_perm[part]
        F_ref = baseline(L_dense, Btp)
        ref_max = float(jnp.max(jnp.abs(F_ref)))
        scale = max(scale, ref_max)
        for name, run in runs.items():
            L = L_dense if name.endswith("dense") else L_packed
            t0 = time.perf_counter()
            F = jax.block_until_ready(run(L, Btp, cp, icp))
            secs[name] += time.perf_counter() - t0
            check(bool(jnp.all(jnp.isfinite(F))),
                  f"{name}: finite F (subdomains {s0}+)")
            errs[name] = max(errs[name], float(
                jnp.max(jnp.abs(F - F_ref))) / ref_max)
        del L_dense, F_ref
    emit("pallas", subdomains=st.S, chunk=PALLAS_CHUNK, interpret=False,
         dtype=str(st.Btp.dtype), max_abs_F=scale, rel_err=errs,
         bound=PALLAS_REL_BOUND, seconds=secs,
         wall_s=time.perf_counter() - t_all)
    for name, e in errs.items():
        check(e <= PALLAS_REL_BOUND,
              f"pallas {name}: rel err {e:.3e} <= {PALLAS_REL_BOUND}")


def phase_f64(device):
    from repro.feti import FetiConfig

    fc, prob, u_ref = decompose("feti-elasticity-2d")
    config = FetiConfig(schur=arch_schur(fc, storage="packed"),
                        preconditioner="dirichlet", storage="packed",
                        plan_cache=False)
    _, _, fields = run_solve(prob, config, u_ref)
    emit("f64", arch=fc.name, peak_device_bytes=peak_bytes(device),
         host_max_rss_gib=host_max_rss_gib(), **fields)


def phase_sharded(n_chips: int):
    """heat2d on an ``n_chips`` mesh against the same solve on one chip."""
    import jax

    from repro.launch.mesh import make_feti_mesh

    _, prob, u_ref = decompose("feti-heat-2d")
    config = heat2d_config(make_feti_mesh(n_chips))
    solver, sol, fields = run_solve(prob, config, u_ref)
    st = solver.state
    placed = {}
    for name, arr in (("L", st.L.values), ("F", st.F), ("Btp", st.Btp)):
        shards = arr.addressable_shards
        placed[name] = sorted((str(s.device), s.data.shape[0])
                              for s in shards)
        devices = {s.device for s in shards}
        check(len(devices) == n_chips,
              f"{name} spread over {n_chips} devices, got {len(devices)}")
        check(all(s.data.shape[0] == st.S // n_chips for s in shards),
              f"{name}: {st.S // n_chips} subdomains per device")
    del solver, st
    _, ref, ref_fields = run_solve(prob, config.replace(mesh=None), u_ref)
    d_iters = abs(sol.iterations - ref.iterations)
    emit("sharded", chips=n_chips, placements=placed,
         devices=[str(d) for d in jax.devices()],
         single_chip=ref_fields, **fields)
    check(d_iters <= 1, f"iterations {sol.iterations} vs 1-chip "
          f"{ref.iterations} differ by <= 1")


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--chips", type=int, choices=(1, 4), default=1,
                   help="4: run only the sharded path, on four chips")
    args = p.parse_args(argv)

    if not os.path.isdir(os.path.join(SRC, "repro")):
        print(f"chip_smoke: no repository sources at {SRC}",
              file=sys.stderr)
        return 2
    sys.path.insert(0, SRC)

    import jax

    from repro.launch.compile_cache import enable_compile_cache

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {platform!r} devices",
              file=sys.stderr)
        return 3
    jax.config.update("jax_enable_x64", True)
    cache = enable_compile_cache()
    dev = devices[0]
    emit("device", platform=platform, kind=dev.device_kind,
         count=len(devices), compile_cache=cache,
         host_mem_gib=os.sysconf("SC_PAGE_SIZE")
         * os.sysconf("SC_PHYS_PAGES") / 2**30)

    faulthandler.register(signal.SIGTERM, chain=True)
    faulthandler.dump_traceback_later(DEADLINE_S, exit=True)
    t0 = time.perf_counter()
    if args.chips == 4:
        phase_sharded(4)
    else:
        phase_heat2d(dev)
        phase_f64(dev)
    faulthandler.cancel_dump_traceback_later()
    emit("done", wall_s=time.perf_counter() - t0)
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
