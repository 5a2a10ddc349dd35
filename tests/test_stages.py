"""Stage-graph tests (ISSUE 7): the fused TRSM→SYRK megakernel against the
two-kernel schedule, the shared-interior-factor dedup against the
two-pipeline baseline, joint plan-cache behavior, and the 3D-elasticity
regression for the single-computation dof_perm threading."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import SchurAssemblyConfig, StageGraph, StageSpec
from repro.core.stages import _store_graph  # noqa: F401  (cache dir reuse)
from repro.fem.decomposition import (
    decompose_elasticity_problem,
    decompose_heat_problem,
)
from repro.fem.regularization import fixing_dofs_regularization
from repro.feti import FetiConfig, preprocess_cluster
from repro.feti import dirichlet as dirlib
from repro.feti.assembly import batched_assemble
from repro.sparse.cholesky import block_cholesky

pytestmark = pytest.mark.stages


# ------------------------------------------------- fused megakernel ----

@pytest.mark.parametrize("ordering", ["nd", "rcm"])
@pytest.mark.parametrize("storage", ["dense", "packed"])
@pytest.mark.parametrize("bs", [8, 16])
def test_fused_matches_unfused(ordering, storage, bs):
    """The fused megakernel (one Pallas program keeping the TRSM panels in
    VMEM) agrees with the separately-scheduled TRSM + SYRK pipeline to
    1e-12 across storage layouts, orderings and block sizes (interpret
    mode exercises the exact kernel logic on CPU)."""
    prob = decompose_heat_problem(2, (2, 2), (4, 4))
    base = SchurAssemblyConfig(block_size=bs, storage=storage)
    fused = SchurAssemblyConfig(block_size=bs, storage=storage,
                                use_pallas=True, fused=True, interpret=True)
    st0 = preprocess_cluster(prob, FetiConfig(schur=base, ordering=ordering))
    st1 = preprocess_cluster(prob, FetiConfig(schur=fused, ordering=ordering))
    assert st1.cfg.fused
    err = np.max(np.abs(np.asarray(st0.F) - np.asarray(st1.F)))
    assert err <= 1e-12, err


def test_fused_requires_pallas():
    with pytest.raises(ValueError, match="fused"):
        SchurAssemblyConfig(fused=True, use_pallas=False)


def test_fused_smoke_solve():
    """Tier-1 smoke: a full PCPG solve through the fused megakernel."""
    from repro.feti import FetiSolver

    prob = decompose_heat_problem(2, (2, 2), (3, 3))
    cfg = SchurAssemblyConfig(block_size=8, use_pallas=True, fused=True,
                              interpret=True)
    sol = FetiSolver(prob, FetiConfig(schur=cfg)).solve()
    assert sol.converged
    ref = prob.reference_solution()
    err = np.max(np.abs(sol.u_global - ref)) / np.abs(ref).max()
    assert err < 1e-8, err


# --------------------------------------- shared-interior-factor dedup ----

def _elasticity_problem():
    # corner fixing nodes lie on the union boundary -> sharing is valid
    return decompose_elasticity_problem(2, (2, 2), (3, 3))


def test_shared_factor_bit_identical_to_two_pipelines():
    """With the dual rows in split.dperm order and a block size dividing
    n_i, the stage graph's shared path produces BIT-identical F and S_b to
    independently-run dual + Dirichlet pipelines in the same ordering:
    sharing changes where the interior factor comes from, not one bit of
    what is computed."""
    prob = _elasticity_problem()
    cfg = SchurAssemblyConfig(block_size=4)  # divides n_i = 8
    st = preprocess_cluster(
        prob, FetiConfig(schur=cfg, preconditioner="dirichlet"))
    assert st.shared_factor
    split = st.split
    assert split.n_i % cfg.block_size == 0
    dperm = split.dperm
    assert np.array_equal(st.node_perm, dperm)

    # Both reference pipelines run compiled (jax.jit), like the prep they
    # are compared with: XLA compiles a whole program with other roundings
    # than op-by-op dispatch (on jax 0.9.0 the eager assembly differs from
    # the jitted one by one ulp), so only compiled-vs-compiled can be
    # compared bit for bit.

    # pipeline 1 (dual): factorize regularized K in the same dperm order,
    # assemble F with the same metadata — the pre-graph computation
    Kreg = np.stack([fixing_dofs_regularization(sd.K, sd.fixing_dofs)
                     for sd in prob.subdomains])
    Kp = jnp.asarray(Kreg[:, dperm][:, :, dperm])
    Btp = jnp.asarray(np.stack([sd.Bt[dperm] for sd in prob.subdomains],
                               dtype=np.float64))

    @jax.jit
    def dual(Kp, Btp):
        L = jax.vmap(lambda A: block_cholesky(A, cfg.block_size,
                                              mask=st.block_mask))(Kp)
        return batched_assemble(L, Btp, st.col_perm, st.inv_col_perm,
                                st.env, cfg, st.block_mask)

    F_ref = dual(Kp, Btp)

    # pipeline 2 (dirichlet): its OWN interior factorization of the
    # unregularized K_ii (shared=False assembler), same symbolic products
    d_assemble = dirlib.make_dirichlet_assembler(
        split, st.dirichlet_env, st.dirichlet_mask, st.dirichlet_cfg)
    Kd = jnp.asarray(np.stack(
        [sd.K[dperm][:, dperm] for sd in prob.subdomains]))
    Zb = jnp.asarray(dirlib.own_boundary_masks(prob, split))
    Sb_ref = jax.jit(lambda Kd, Zb: jax.vmap(dirlib.restrict_own_boundary)(
        jax.vmap(d_assemble)(Kd), Zb))(Kd, Zb)

    assert np.array_equal(np.asarray(st.F), np.asarray(F_ref))
    assert np.array_equal(np.asarray(st.Sb), np.asarray(Sb_ref))


@pytest.mark.parametrize("storage", ["dense", "packed"])
def test_shared_vs_unshared_agree(storage):
    """share_factor=False keeps the two independent pipelines (the dual in
    plain fill-reducing order); outputs agree with the shared path to
    1e-12 — the orderings differ, so only numerically."""
    prob = _elasticity_problem()
    fc = FetiConfig(preconditioner="dirichlet", storage=storage)
    st1 = preprocess_cluster(prob, fc)
    st0 = preprocess_cluster(prob, fc.replace(share_factor=False))
    assert st1.shared_factor and not st0.shared_factor
    assert np.max(np.abs(np.asarray(st1.Sb) - np.asarray(st0.Sb))) <= 1e-12
    assert np.max(np.abs(np.asarray(st1.F) - np.asarray(st0.F))) <= 1e-12


def test_share_factor_auto_disables_on_interior_fixing_dofs():
    """The heat workload fixes the subdomain CENTER node — interior — so
    the regularization would perturb the shared factor: 'auto' must fall
    back to the two-pipeline form, and share_factor=True must refuse."""
    prob = decompose_heat_problem(2, (2, 2), (3, 3))
    st = preprocess_cluster(prob, FetiConfig(preconditioner="dirichlet"))
    assert not st.shared_factor
    with pytest.raises(ValueError, match="share_factor"):
        preprocess_cluster(
            prob, FetiConfig(preconditioner="dirichlet", share_factor=True))


def test_state_stage_views():
    """ClusterState exposes the graph view: outputs keyed by stage name,
    per-stage device-byte attribution, resolved stages."""
    prob = _elasticity_problem()
    st = preprocess_cluster(prob, FetiConfig(preconditioner="dirichlet"))
    out = st.outputs()
    assert set(out) == {"dual", "dirichlet"}
    assert out["dual"] is st.F and out["dirichlet"] is st.Sb
    assert set(st.stages) == {"dual", "dirichlet"}
    assert st.stages["dirichlet"].spec.share_factor_of == "dual"
    by = st.device_bytes()["per_stage"]
    assert set(by) == {"dual", "dirichlet"}
    assert by["dual"] > 0 and by["dirichlet"] > 0


# ------------------------------------------------- joint plan cache ----

def test_joint_plan_cache_hit_miss(tmp_path, monkeypatch):
    """One graph cache entry covers ALL stages: second identical build
    hits; changing any stage's sparsity fingerprint misses."""
    monkeypatch.setenv("REPRO_PLAN_CACHE_DIR", str(tmp_path))
    prob = _elasticity_problem()
    fc = FetiConfig(schur="auto", preconditioner="dirichlet",
                    measure="never")
    st1 = preprocess_cluster(prob, fc)
    assert st1.graph_plan is not None and not st1.graph_plan.from_cache
    assert set(st1.graph_plan.plans) == {"dual", "dirichlet"}
    st2 = preprocess_cluster(prob, fc)
    assert st2.graph_plan.from_cache
    assert st2.graph_plan.key == st1.graph_plan.key
    assert np.array_equal(np.asarray(st1.F), np.asarray(st2.F))
    assert np.array_equal(np.asarray(st1.Sb), np.asarray(st2.Sb))
    # a different decomposition (different sparsity) -> different key
    st3 = preprocess_cluster(decompose_elasticity_problem(2, (2, 2), (4, 4)),
                             fc)
    assert st3.graph_plan.key != st1.graph_plan.key
    assert not st3.graph_plan.from_cache


def test_stage_graph_validates_wiring():
    def builder(bs, rbs):  # pragma: no cover - never called
        raise AssertionError

    a = StageSpec(name="a", builder=builder, fingerprint="fa", n=8)
    with pytest.raises(ValueError, match="duplicate"):
        StageGraph([a, StageSpec(name="a", builder=builder,
                                 fingerprint="fb", n=8)])
    with pytest.raises(ValueError, match="earlier stage"):
        StageGraph([StageSpec(name="b", builder=builder, fingerprint="fb",
                              n=8, share_factor_of="zzz")])


# --------------------------------- dof_perm threading (3D regression) ----

def test_split_threading_3d_elasticity():
    """The preprocessor computes the fill-reducing DOF permutation ONCE
    and threads it into boundary_interior_split (which used to silently
    rebuild it — a drift hazard this 3D vector-DOF case would catch):
    the threaded split must equal the standalone rebuild, and the full
    shared-factor Dirichlet pipeline must match the one-shot oracle."""
    prob = decompose_elasticity_problem(3, (2, 1, 1), (2, 2, 2))
    st = preprocess_cluster(prob, FetiConfig(preconditioner="dirichlet"))
    ref = dirlib.boundary_interior_split(prob, ordering="nd")
    assert np.array_equal(st.split.interior, ref.interior)
    assert np.array_equal(st.split.boundary, ref.boundary)
    assert st.shared_factor  # 3D corner fixing nodes are boundary
    Sb_ref, _, _ = dirlib.assemble_dirichlet_schur(prob)
    err = np.max(np.abs(np.asarray(st.Sb) - np.asarray(Sb_ref)))
    assert err <= 1e-12, err
