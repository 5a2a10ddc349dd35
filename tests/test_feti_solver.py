"""End-to-end FETI validation: the decomposed PCPG solve must reproduce the
undecomposed global sparse solve, for 2D and 3D, implicit and explicit dual
operators, every SC assembly variant, and both workloads (scalar heat with
kernel dim 1, vector elasticity with rigid-body kernel dim 3/6)."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import SchurAssemblyConfig
from repro.fem import decompose_problem
from repro.feti import FetiConfig, FetiSolver
from repro.feti.assembly import preprocess_cluster
from repro.feti.operator import explicit_dual_apply, implicit_dual_apply
from repro.feti.projector import interface_kernels


@pytest.fixture(scope="module", params=["heat", "elasticity"])
def prob2d(request):
    return decompose_problem(request.param, 2, (2, 2), (4, 4))


@pytest.fixture(scope="module", params=["heat", "elasticity"])
def prob3d(request):
    return decompose_problem(request.param, 3, (2, 2, 1), (2, 2, 2))


def _check_against_reference(prob, sol, rtol=1e-6):
    u_ref = prob.reference_solution()
    scale = np.abs(u_ref).max()
    np.testing.assert_allclose(sol.u_global, u_ref, atol=rtol * scale)
    # interface copies agree across subdomains
    nn = prob.n_global_dofs
    vals = [[] for _ in range(nn)]
    for i, sd in enumerate(prob.subdomains):
        for lid, g in enumerate(sd.dof_gids):
            vals[g].append(sol.u[i, lid])
    for g, vs in enumerate(vals):
        if len(vs) > 1:
            assert np.ptp(vs) < rtol * scale * 10, f"interface jump at DOF {g}"


@pytest.mark.parametrize("mode", ["explicit", "implicit"])
def test_feti_2d_matches_global_solve(prob2d, mode):
    solver = FetiSolver(prob2d, FetiConfig(
        schur=SchurAssemblyConfig(block_size=8, rhs_block_size=8),
        mode=mode))
    sol = solver.solve(tol=1e-10)
    assert sol.converged
    _check_against_reference(prob2d, sol)


@pytest.mark.parametrize("mode", ["explicit", "implicit"])
def test_feti_3d_matches_global_solve(prob3d, mode):
    solver = FetiSolver(prob3d, FetiConfig(
        schur=SchurAssemblyConfig(block_size=8, rhs_block_size=8),
        mode=mode))
    sol = solver.solve(tol=1e-10)
    assert sol.converged
    _check_against_reference(prob3d, sol)


def test_interface_kernels_match_the_dense_contraction(prob2d):
    """B̃ᵢRᵢ from the compact gluing record is the dense B̃ᵢᵀ contraction
    bit for bit, for k = 1 (heat) and k = 3 (elasticity), padding columns
    (zero) included."""
    subs = prob2d.subdomains
    Bt = np.stack([sd.Bt for sd in subs])
    R = np.stack([sd.R for sd in subs])
    assert any((sd.b_vals == 0).any() for sd in subs)  # padding present
    BR = interface_kernels(subs)
    assert BR.shape == (len(subs), prob2d.m_max, R.shape[-1])
    np.testing.assert_array_equal(BR, np.einsum("snm,snk->smk", Bt, R))
    for sd, br in zip(subs, BR):
        assert not br[sd.b_vals == 0].any()


def test_explicit_equals_implicit_operator(prob2d):
    """F applied explicitly (preassembled SC) == implicitly (eq. 11 vs 12)."""
    cfg = SchurAssemblyConfig(block_size=8, rhs_block_size=8)
    st = preprocess_cluster(prob2d, cfg)
    nl = prob2d.n_lambda
    rng = np.random.default_rng(0)
    lam = jnp.asarray(rng.standard_normal(nl))
    q_exp = explicit_dual_apply(st.F, st.lambda_ids, nl, lam)
    q_imp = implicit_dual_apply(st.L, st.Btp, st.lambda_ids, nl, lam)
    np.testing.assert_allclose(np.asarray(q_exp), np.asarray(q_imp),
                               rtol=1e-8, atol=1e-8)


@pytest.mark.parametrize("trsm_variant,syrk_variant", [
    ("dense", "dense"),
    ("rhs_split", "input_split"),
    ("factor_split", "output_split"),
])
def test_feti_all_assembly_variants(prob2d, trsm_variant, syrk_variant):
    cfg = SchurAssemblyConfig(trsm_variant=trsm_variant, syrk_variant=syrk_variant,
                              block_size=8, rhs_block_size=8)
    sol = FetiSolver(prob2d, cfg).solve(tol=1e-10)
    assert sol.converged
    _check_against_reference(prob2d, sol)


@pytest.mark.parametrize("ordering", ["nd", "rcm", "natural"])
def test_feti_orderings(prob2d, ordering):
    cfg = SchurAssemblyConfig(block_size=8, rhs_block_size=8)
    sol = FetiSolver(prob2d, FetiConfig(
        schur=cfg, ordering=ordering)).solve(tol=1e-10)
    assert sol.converged
    _check_against_reference(prob2d, sol)


def test_feti_unpreconditioned_converges(prob2d):
    cfg = SchurAssemblyConfig(block_size=8, rhs_block_size=8)
    sol = FetiSolver(prob2d, FetiConfig(
        schur=cfg, preconditioner="none")).solve(tol=1e-10)
    assert sol.converged
    _check_against_reference(prob2d, sol)


def test_lumped_preconditioner_stays_correct_and_bounded():
    """On tiny well-conditioned heat problems the lumped preconditioner need
    not win (its payoff is on large/ill-conditioned systems), but it must
    stay correct and not blow up the iteration count."""
    prob = decompose_problem("heat", 2, (3, 3), (4, 4))
    cfg = SchurAssemblyConfig(block_size=8, rhs_block_size=8)
    sol_pre = FetiSolver(prob, cfg).solve(tol=1e-9)
    sol_no = FetiSolver(prob, FetiConfig(
        schur=cfg, preconditioner="none")).solve(tol=1e-9)
    assert sol_pre.converged and sol_no.converged
    _check_against_reference(prob, sol_pre)
    assert sol_pre.iterations <= 3 * sol_no.iterations


def test_amortization_report():
    prob = decompose_problem("heat", 2, (2, 2), (4, 4))
    solver = FetiSolver(prob, SchurAssemblyConfig(block_size=8, rhs_block_size=8))
    solver.preprocess()
    rep = solver.amortization_report(
        t_assembly_s=1.0, t_implicit_iter_s=0.15, t_explicit_iter_s=0.05
    )
    assert rep["amortization_iterations"] == pytest.approx(10.0)
    assert rep["assembly_flops_per_subdomain"]["total"] > 0


# --------------------------------------- host-side preprocessing inputs ----

def test_host_stacks_are_packed_per_subdomain():
    """The prep's inputs are built on the host one subdomain at a time:
    every K stack is packed, and the packed regularized K is exactly the
    regularized, permuted dense K."""
    from repro.fem.regularization import fixing_dofs_regularization
    from repro.feti.assembly import host_stacks, make_cluster_preprocessor

    prob = decompose_problem("heat", 2, (2, 2), (4, 4))
    fc = FetiConfig(schur=SchurAssemblyConfig(block_size=8), dtype="f32")
    static, _ = make_cluster_preprocessor(prob, fc)
    st = host_stacks(prob, static, fc)
    index, perm = static["index"], static["node_perm"]
    S = prob.n_subdomains
    shape = (S, index.n_blocks, index.bs, index.bs)
    assert st["Kp"].shape == st["K"].shape == shape
    assert st["Kp"].dtype == st["K"].dtype == np.float32
    assert st["Kreg"].dtype == np.float64  # refinement's f64 matrix
    for i, sd in enumerate(prob.subdomains):
        Kreg = fixing_dofs_regularization(sd.K, sd.fixing_dofs)
        np.testing.assert_array_equal(
            st["Kreg"][i], index.pack_host(Kreg[perm][:, perm]))
        np.testing.assert_array_equal(
            st["K"][i], index.pack_host(sd.K[perm][:, perm], np.float32))
    assert host_stacks(prob, static, fc.replace(dtype="f64"))["Kreg"] is None


def test_prep_in_subdomain_chunks_matches_batched(monkeypatch):
    """f64 stacks on a TPU preprocess one subdomain at a time (XLA:TPU's
    emulated f64 products need temporaries that grow with the batch); the
    chunked program computes what the batched one does."""
    import repro.feti.assembly as asm

    prob = decompose_problem("elasticity", 2, (2, 2), (3, 3))
    fc = FetiConfig(schur=SchurAssemblyConfig(block_size=4),
                    preconditioner="dirichlet")
    assert asm.subdomain_chunk(fc, 4) == 4  # batched here (not a TPU)
    ref = asm.preprocess_cluster(prob, fc)
    monkeypatch.setattr(asm, "subdomain_chunk", lambda fc, S: 1)
    got = asm.preprocess_cluster(prob, fc)
    for a, b in ((got.L, ref.L), (got.F, ref.F), (got.Sb, ref.Sb)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=0, atol=1e-12)
