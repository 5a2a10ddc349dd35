"""Distributed FETI scaling: assembly + per-iteration time vs device count.

Shards the subdomain axis of one cluster over ``("data",)`` meshes of
1, 2, 4, ... devices (:mod:`repro.feti.sharded`) and measures

  * ``preproc``  — compiled numerical factorization + explicit SC assembly
    (the paper's preprocessing stage, now partitioned per-device), and
  * ``iter_explicit`` / ``iter_implicit`` — one dual-operator application
    under shard_map (a device-local GEMV/TRSV batch + one λ-sized psum).

On this CPU container the devices are XLA host-platform devices forced via
``--xla_force_host_platform_device_count`` (set REPRO_BENCH_DEVICES before
running to change the pool, default 8), so the numbers measure *scaling
shape* and exchange overhead, not real accelerator throughput.
"""
from __future__ import annotations

import os
import sys

from repro.launch.mesh import force_host_device_count

# must be set before the jax backend initializes (import side effect)
_N_DEV = int(os.environ.get("REPRO_BENCH_DEVICES", "8"))
force_host_device_count(_N_DEV)

import jax
import jax.numpy as jnp

from benchmarks.common import emit, fmt_bytes, time_fn
from repro.core import SchurAssemblyConfig
from repro.fem import decompose_heat_problem
from repro.feti import FetiConfig
from repro.feti import sharded as shlib
from repro.feti.assembly import preprocess_cluster
from repro.launch.mesh import make_feti_mesh
from repro.sparse import PackedBlocks


def run(dim: int = 2, sub_grid=(4, 4), elems_per_sub=(16, 16),
        bs: int = 16, reps: int = 3) -> list[tuple]:
    if len(jax.devices()) < _N_DEV:
        # e.g. under `python -m benchmarks.run`, where an earlier bench
        # module already initialized the backend at its device count
        print(
            f"[bench_sharded] backend has {len(jax.devices())} device(s), "
            f"wanted {_N_DEV} — jax initialized before this module? "
            f"(run with `--only sharded` for the full scaling curve)",
            file=sys.stderr,
        )
    prob = decompose_heat_problem(dim, sub_grid, elems_per_sub)
    cfg = SchurAssemblyConfig(block_size=bs, rhs_block_size=bs)
    nl = prob.n_lambda
    S = prob.n_subdomains
    n = prob.subdomains[0].n
    tag = f"{dim}d/S{S}/n{n}"

    counts = []
    d = 1
    while d <= len(jax.devices()):
        counts.append(d)
        d *= 2

    rows = []
    base_preproc = base_expl = base_impl = None
    for nd in counts:
        mesh = make_feti_mesh(nd)
        st = preprocess_cluster(prob, FetiConfig(schur=cfg, mesh=mesh))

        # preprocessing: re-run the compiled factorize+assemble the state
        # carries on already-placed stacks (multi-step regime, fixed pattern)
        L_d = st.L.unpack() if isinstance(st.L, PackedBlocks) else st.L
        # any SPD stack, packed as prep takes it and placed right
        Kp = st.index.pack(L_d @ jnp.swapaxes(L_d, -1, -2),
                           diag_identity_pad=True)
        t_pre = time_fn(lambda a, b: st.prep(a, b)[1], Kp, st.Btp, reps=reps)

        lam = jax.device_put(jnp.zeros((nl,)), shlib.replicated_sharding(mesh))
        expl = jax.jit(lambda p, st=st, mesh=mesh: shlib.explicit_dual_apply(
            mesh, st.F, st.lambda_ids, nl, p))
        impl = jax.jit(lambda p, st=st, mesh=mesh: shlib.implicit_dual_apply(
            mesh, st.L, st.Btp, st.lambda_ids, nl, p))
        t_expl = time_fn(expl, lam, reps=reps)
        t_impl = time_fn(impl, lam, reps=reps)

        if nd == 1:
            base_preproc, base_expl, base_impl = t_pre, t_expl, t_impl
        rows.append((f"feti_sharded/{tag}/d{nd}/preproc", t_pre,
                     f"speedup_vs_1dev={base_preproc / t_pre:.2f};"
                     + fmt_bytes(st)))
        rows.append((f"feti_sharded/{tag}/d{nd}/iter_explicit", t_expl,
                     f"speedup_vs_1dev={base_expl / t_expl:.2f}"))
        rows.append((f"feti_sharded/{tag}/d{nd}/iter_implicit", t_impl,
                     f"speedup_vs_1dev={base_impl / t_impl:.2f}"))
    return rows


def main():
    emit(run())


if __name__ == "__main__":
    main()
