"""Packed block-sparse factor storage (repro.sparse.packed).

The acceptance bar of the storage refactor: packed and dense paths produce
identical (<=1e-12) factors, TRSM results, dual-operator applications and
PCPG iterates across orderings and block sizes, while the packed L+K
footprint is strictly below dense for every non-trivial fill mask. The
``multidevice``-marked test runs the sharded packed solve against the
single-device one (CI multidevice lane).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import (
    SchurAssemblyConfig,
    build_stepped_meta,
    make_assembler,
    schur_dense_baseline,
    trsm_factor_split,
    trsm_factor_split_packed,
)
from repro.fem import decompose_problem
from repro.feti import FetiConfig, FetiSolver
from repro.feti.assembly import preprocess_cluster
from repro.feti.operator import (
    dual_rhs,
    implicit_dual_apply,
    lumped_preconditioner,
    solve_with_factor,
)
from repro.sparse import (
    PackedBlockIndex,
    PackedBlocks,
    block_cholesky,
    block_cholesky_packed,
    block_pattern,
    block_symbolic_cholesky,
    matrix_pattern_from_elems,
    nested_dissection_order,
    pack_factor,
    packed_symm_matvec,
    packed_tri_solve,
)
from repro.testing import (
    random_banded_spd,
    random_feti_like_bt,
    random_lower_banded,
)

multidevice = pytest.mark.multidevice

CFG_P = SchurAssemblyConfig(block_size=8, rhs_block_size=8, storage="packed")
CFG_D = SchurAssemblyConfig(block_size=8, rhs_block_size=8, storage="dense")


# --------------------------------------------------------------------------
# the container: pack / unpack / index invariants
# --------------------------------------------------------------------------


@settings(max_examples=15, deadline=None)
@given(n=st.integers(8, 48), bs=st.integers(2, 16),
       seed=st.integers(0, 2**31 - 1))
def test_property_pack_unpack_roundtrip(n, bs, seed):
    """Pack -> unpack reproduces any matrix covered by the index exactly."""
    rng = np.random.default_rng(seed)
    L = random_lower_banded(n, min(n - 1, 6), rng)
    pat = (np.abs(L) + np.abs(L.T)) > 0
    idx = PackedBlockIndex.from_mask(
        block_symbolic_cholesky(block_pattern(pat, bs)), n, bs)
    pb = pack_factor(jnp.asarray(L), idx)
    np.testing.assert_array_equal(np.asarray(pb.unpack()), L)
    # the layout invariant the Pallas kernel relies on: slots are (row,
    # col)-sorted, so each row's diagonal block is its last slot
    assert np.array_equal(idx.cols[idx.diag_slots], np.arange(idx.nb))
    lex = np.lexsort((idx.cols, idx.rows))
    assert np.array_equal(lex, np.arange(idx.n_blocks))


def test_index_rejects_bad_shapes_and_missing_blocks():
    idx = PackedBlockIndex.full(10, 4)
    with pytest.raises(ValueError):
        idx.unpack(jnp.zeros((idx.n_blocks + 1, 4, 4)))
    with pytest.raises(ValueError):
        idx.pack(jnp.zeros((11, 11)))
    sparse_idx = PackedBlockIndex.from_mask(
        np.eye(3, dtype=bool), n=12, bs=4)
    with pytest.raises(KeyError):
        sparse_idx.slot(2, 0)


# --------------------------------------------------------------------------
# packed numerical Cholesky == dense masked path, across orderings/sizes
# --------------------------------------------------------------------------


def _subdomain(ordering: str, shape=(7, 7)):
    from repro.fem import assemble_dense, p1_element_stiffness, structured_mesh
    from repro.fem.regularization import fixing_node_regularization
    from repro.sparse import rcm_order

    mesh = structured_mesh(tuple(s - 1 for s in shape))
    Ke = p1_element_stiffness(mesh.coords, mesh.elems)
    K = np.asarray(assemble_dense(mesh.n_nodes, mesh.elems, Ke))
    K = fixing_node_regularization(K, fixing_node=0)
    n = K.shape[0]
    if ordering == "nd":
        perm = nested_dissection_order(shape)
    elif ordering == "rcm":
        perm = rcm_order(shape)
    else:
        perm = np.arange(n)
    Kp = K[perm][:, perm]
    pat = matrix_pattern_from_elems(n, mesh.elems)[perm][:, perm]
    return Kp, pat


@pytest.mark.parametrize("ordering", ["nd", "rcm", "natural"])
@pytest.mark.parametrize("bs", [4, 8, 16])
def test_packed_cholesky_matches_dense_masked(ordering, bs):
    Kp, pat = _subdomain(ordering)
    mask = block_symbolic_cholesky(block_pattern(pat, bs))
    idx = PackedBlockIndex.from_mask(mask, Kp.shape[0], bs)
    Ld = np.asarray(block_cholesky(jnp.asarray(Kp), bs, mask=mask))
    Lp = np.asarray(block_cholesky_packed(jnp.asarray(Kp), idx).unpack())
    np.testing.assert_allclose(Lp, Ld, rtol=0, atol=1e-12)
    np.testing.assert_allclose(Lp, np.linalg.cholesky(Kp), rtol=1e-8,
                               atol=1e-8)


@settings(max_examples=10, deadline=None)
@given(n=st.integers(12, 40), bs=st.integers(3, 12),
       seed=st.integers(0, 2**31 - 1))
def test_property_packed_cholesky_random_spd(n, bs, seed):
    rng = np.random.default_rng(seed)
    K = random_banded_spd(n, min(n - 1, 7), rng)
    mask = block_symbolic_cholesky(block_pattern(np.abs(K) > 0, bs))
    idx = PackedBlockIndex.from_mask(mask, n, bs)
    pb = block_cholesky_packed(jnp.asarray(K), idx)
    L = np.asarray(pb.unpack())
    np.testing.assert_allclose(L @ L.T, K, rtol=1e-8, atol=1e-8)
    assert np.allclose(L, np.tril(L))


# --------------------------------------------------------------------------
# packed solves / matvec / TRSM
# --------------------------------------------------------------------------


@settings(max_examples=10, deadline=None)
@given(n=st.integers(12, 40), bs=st.integers(3, 12),
       seed=st.integers(0, 2**31 - 1))
def test_property_packed_tri_solve_and_matvec(n, bs, seed):
    rng = np.random.default_rng(seed)
    K = random_banded_spd(n, min(n - 1, 7), rng)
    mask = block_symbolic_cholesky(block_pattern(np.abs(K) > 0, bs))
    idx = PackedBlockIndex.from_mask(mask, n, bs)
    pb = block_cholesky_packed(jnp.asarray(K), idx)
    L = np.asarray(pb.unpack())
    b = rng.standard_normal(n)
    np.testing.assert_allclose(
        np.asarray(packed_tri_solve(pb, jnp.asarray(b))),
        np.linalg.solve(L, b), rtol=1e-9, atol=1e-9)
    np.testing.assert_allclose(
        np.asarray(packed_tri_solve(pb, jnp.asarray(b), transpose=True)),
        np.linalg.solve(L.T, b), rtol=1e-9, atol=1e-9)
    # symmetric matvec on packed K (lower blocks only; diagonal blocks
    # store their full symmetric tile)
    pk = PackedBlocks(idx.pack(jnp.asarray(K)), idx)
    np.testing.assert_allclose(
        np.asarray(packed_symm_matvec(pk, jnp.asarray(b))),
        K @ b, rtol=1e-10, atol=1e-10)


@settings(max_examples=12, deadline=None)
@given(n=st.integers(16, 48), m=st.integers(4, 20), bs=st.integers(4, 16),
       seed=st.integers(0, 2**31 - 1))
def test_property_packed_trsm_matches_dense(n, m, bs, seed):
    """trsm_factor_split_packed == the dense pruned factor_split path."""
    rng = np.random.default_rng(seed)
    L = random_lower_banded(n, min(10, n - 1), rng)
    Bt = random_feti_like_bt(n, m, rng)
    meta = build_stepped_meta(Bt != 0, block_size=bs, rhs_block_size=bs)
    mask = block_symbolic_cholesky(
        block_pattern((np.abs(L) + np.abs(L.T)) > 0, bs))
    idx = PackedBlockIndex.from_mask(mask, n, bs)
    pb = pack_factor(jnp.asarray(L), idx)
    Bp = jnp.asarray(Bt)[:, meta.perm]
    Yd = np.asarray(trsm_factor_split(jnp.asarray(L), Bp, meta,
                                      block_mask=mask))
    Yp = np.asarray(trsm_factor_split_packed(pb, Bp, meta))
    np.testing.assert_allclose(Yp, Yd, rtol=0, atol=1e-12)


def test_packed_pallas_trsm_matches_reference_interpret():
    rng = np.random.default_rng(7)
    n, m, bs = 48, 18, 8
    L = random_lower_banded(n, 10, rng)
    Bt = random_feti_like_bt(n, m, rng)
    meta = build_stepped_meta(Bt != 0, block_size=bs, rhs_block_size=bs)
    mask = block_symbolic_cholesky(
        block_pattern((np.abs(L) + np.abs(L.T)) > 0, bs))
    idx = PackedBlockIndex.from_mask(mask, n, bs)
    pb = pack_factor(jnp.asarray(L), idx)
    Bp = jnp.asarray(Bt)[:, meta.perm]
    from repro.kernels.ops import stepped_trsm_packed

    Y = np.asarray(stepped_trsm_packed(pb, Bp, meta, interpret=True))
    ref = np.asarray(jax.lax.linalg.triangular_solve(
        jnp.asarray(L), Bp, left_side=True, lower=True))
    np.testing.assert_allclose(Y, ref, rtol=1e-10, atol=1e-10)


@pytest.mark.parametrize("use_pallas", [False, True])
def test_packed_assembler_matches_dense_baseline(use_pallas):
    rng = np.random.default_rng(3)
    n, m, bs = 40, 16, 8
    L = random_lower_banded(n, 9, rng)
    Bt = random_feti_like_bt(n, m, rng)
    meta = build_stepped_meta(Bt != 0, block_size=bs, rhs_block_size=bs)
    mask = block_symbolic_cholesky(
        block_pattern((np.abs(L) + np.abs(L.T)) > 0, bs))
    idx = PackedBlockIndex.from_mask(mask, n, bs)
    pb = pack_factor(jnp.asarray(L), idx)
    cfg = SchurAssemblyConfig(
        trsm_variant="factor_split", syrk_variant="input_split",
        block_size=bs, rhs_block_size=bs, storage="packed",
        use_pallas=use_pallas, interpret=use_pallas)
    F = np.asarray(make_assembler(meta, cfg, mask)(pb, jnp.asarray(Bt)))
    F_ref = np.asarray(schur_dense_baseline(jnp.asarray(L), jnp.asarray(Bt)))
    np.testing.assert_allclose(F, F_ref, rtol=1e-9, atol=1e-9)


# --------------------------------------------------------------------------
# the FETI pipeline: packed == dense end-to-end (the acceptance criterion)
# --------------------------------------------------------------------------


# both workloads (heat kernel dim 1, elasticity node-blocked vector DOFs
# kernel dim 3) × both PCPG preconditioners — packed storage must be
# numerically invisible in every combination. PR 4 pinned the lumped
# elasticity grid at 4x4 elements because the f64 dual residual floored
# above the tight 1e-10 tolerance on larger grids; the QR-derived coarse
# factor removed that floor and the dirichlet preconditioner converges in
# strictly fewer iterations, so its case runs the full 8x8 grid (162
# DOFs) the lumped case had to give up.
PRECOND_CASES = [
    ("heat", "lumped", (8, 8)),
    ("elasticity", "lumped", (4, 4)),
    ("elasticity", "dirichlet", (8, 8)),
]


@pytest.fixture(scope="module", params=PRECOND_CASES,
                ids=[f"{p}-{pc}" for p, pc, _ in PRECOND_CASES])
def case2d(request):
    problem, precond, eps = request.param
    return decompose_problem(problem, 2, (2, 2), eps), precond


@pytest.fixture(scope="module")
def prob2d(case2d):
    return case2d[0]


@pytest.fixture(scope="module")
def states(case2d):
    prob, precond = case2d
    dirichlet = precond == "dirichlet"
    pre = "dirichlet" if dirichlet else "lumped"
    return (preprocess_cluster(prob, FetiConfig(schur=CFG_D,
                                                preconditioner=pre)),
            preprocess_cluster(prob, FetiConfig(schur=CFG_P,
                                                preconditioner=pre)))


def test_packed_state_layout_and_footprint(states):
    """Packed L is a PackedBlocks stack; K is packed in BOTH modes; the
    packed L+K footprint is strictly below dense for this non-trivial
    fill mask."""
    st_d, st_p = states
    assert st_d.storage == "dense" and st_p.storage == "packed"
    assert isinstance(st_p.L, PackedBlocks)
    assert isinstance(st_d.K, PackedBlocks)  # no dense K in either mode
    assert isinstance(st_p.K, PackedBlocks)
    bd, bp = st_d.device_bytes(), st_p.device_bytes()
    # non-trivial mask: fewer stored blocks than the full lower triangle
    nb = st_p.index.nb
    assert st_p.index.n_blocks < nb * (nb + 1) // 2
    assert bp["L"] < bd["L"]
    assert bp["L"] + bp["K"] < bd["dense_L"] + bd["dense_K"]


def test_packed_factor_and_sc_match_dense(states):
    st_d, st_p = states
    np.testing.assert_allclose(
        np.asarray(st_p.L.unpack()), np.asarray(st_d.L),
        rtol=0, atol=1e-12)
    np.testing.assert_allclose(
        np.asarray(st_p.F), np.asarray(st_d.F), rtol=0, atol=1e-12)
    if st_d.Sb is not None:
        # the dirichlet stage's primal Schur complements: packed interior
        # factors must reproduce the dense ones through TRSM+SYRK too
        scale = np.abs(np.asarray(st_d.Sb)).max()
        np.testing.assert_allclose(
            np.asarray(st_p.Sb), np.asarray(st_d.Sb),
            rtol=0, atol=1e-12 * max(scale, 1.0))


def test_packed_operators_match_dense(states, prob2d):
    st_d, st_p = states
    nl = prob2d.n_lambda
    rng = np.random.default_rng(0)
    lam = jnp.asarray(rng.standard_normal(nl))
    qi_d = implicit_dual_apply(st_d.L, st_d.Btp, st_d.lambda_ids, nl, lam)
    qi_p = implicit_dual_apply(st_p.L, st_p.Btp, st_p.lambda_ids, nl, lam)
    np.testing.assert_allclose(np.asarray(qi_p), np.asarray(qi_d),
                               rtol=0, atol=1e-12)
    w_d = lumped_preconditioner(st_d.K, st_d.Btp, st_d.lambda_ids, nl, lam)
    w_p = lumped_preconditioner(st_p.K, st_p.Btp, st_p.lambda_ids, nl, lam)
    np.testing.assert_allclose(np.asarray(w_p), np.asarray(w_d),
                               rtol=0, atol=1e-12)
    if st_d.Sb is not None:
        from repro.feti.operator import dirichlet_preconditioner

        v_d = dirichlet_preconditioner(st_d.Sb, st_d.Btb, st_d.lambda_ids,
                                       nl, lam)
        v_p = dirichlet_preconditioner(st_p.Sb, st_p.Btb, st_p.lambda_ids,
                                       nl, lam)
        np.testing.assert_allclose(np.asarray(v_p), np.asarray(v_d),
                                   rtol=0, atol=1e-11)
    c = jnp.zeros((nl,))
    d_d = dual_rhs(st_d.L, st_d.Btp, st_d.fp, st_d.lambda_ids, nl, c)
    d_p = dual_rhs(st_p.L, st_p.Btp, st_p.fp, st_p.lambda_ids, nl, c)
    np.testing.assert_allclose(np.asarray(d_p), np.asarray(d_d),
                               rtol=0, atol=1e-12)
    # solve_with_factor: the shared fwd/bwd pair, dense vs packed
    rhs = jnp.asarray(rng.standard_normal(st_d.fp.shape))
    np.testing.assert_allclose(
        np.asarray(solve_with_factor(st_p.L, rhs)),
        np.asarray(solve_with_factor(st_d.L, rhs)), rtol=0, atol=1e-11)


@pytest.mark.parametrize("ordering", ["nd", "rcm", "natural"])
@pytest.mark.parametrize("mode", ["explicit", "implicit"])
def test_packed_solve_matches_dense_iterates(case2d, ordering, mode):
    """Same PCPG iterate count, same multipliers, same solution — packed
    storage is numerically invisible (for the lumped AND the dirichlet
    preconditioner; the dirichlet case runs the 8x8 elasticity grid the
    old floor forced the lumped case to pin at 4x4)."""
    prob, precond = case2d
    fc = FetiConfig(mode=mode, preconditioner=precond, ordering=ordering)
    sol_d = FetiSolver(prob, fc.replace(schur=CFG_D)).solve(tol=1e-10)
    sol_p = FetiSolver(prob, fc.replace(schur=CFG_P)).solve(tol=1e-10)
    assert sol_d.converged and sol_p.converged
    if precond == "lumped":
        assert sol_d.iterations == sol_p.iterations
        np.testing.assert_allclose(sol_p.lam, sol_d.lam, rtol=0, atol=5e-12)
        np.testing.assert_allclose(sol_p.u_global, sol_d.u_global,
                                   rtol=0, atol=5e-12)
    else:
        # the dirichlet S_b agrees across storages only to ~1e-15·‖S‖
        # (the packed TRSM schedules the same flops through K_ii⁻¹ in a
        # different order); near the stopping threshold that can shift
        # convergence by one iteration, so equality is on the solution
        assert abs(sol_d.iterations - sol_p.iterations) <= 1
        np.testing.assert_allclose(sol_p.u_global, sol_d.u_global,
                                   rtol=0, atol=1e-9)
    u_ref = prob.reference_solution()
    np.testing.assert_allclose(sol_p.u_global, u_ref,
                               atol=1e-6 * np.abs(u_ref).max())


@pytest.mark.parametrize("bs", [4, 8, 16])
def test_packed_solve_across_block_sizes(case2d, bs):
    prob, precond = case2d
    cfg_d = SchurAssemblyConfig(block_size=bs, rhs_block_size=bs,
                                storage="dense")
    cfg_p = SchurAssemblyConfig(block_size=bs, rhs_block_size=bs,
                                storage="packed")
    sol_d = FetiSolver(prob, FetiConfig(
        schur=cfg_d, preconditioner=precond)).solve(tol=1e-10)
    sol_p = FetiSolver(prob, FetiConfig(
        schur=cfg_p, preconditioner=precond)).solve(tol=1e-10)
    if precond == "lumped":
        assert sol_d.iterations == sol_p.iterations
        np.testing.assert_allclose(sol_p.u_global, sol_d.u_global,
                                   rtol=0, atol=5e-12)
    else:  # see test_packed_solve_matches_dense_iterates
        assert abs(sol_d.iterations - sol_p.iterations) <= 1
        np.testing.assert_allclose(sol_p.u_global, sol_d.u_global,
                                   rtol=0, atol=1e-9)


def test_storage_override_knob(prob2d):
    """The storage= knob on preprocess_cluster/FetiSolver overrides the
    config's layout without touching anything else."""
    st = preprocess_cluster(prob2d, FetiConfig(schur=CFG_D,
                                               storage="packed"))
    assert st.storage == "packed" and st.cfg.storage == "packed"
    solver = FetiSolver(prob2d, FetiConfig(schur=CFG_P, storage="dense"))
    solver.preprocess()
    assert solver.state.storage == "dense"


def test_implicit_mode_keeps_packed_factor(prob2d):
    st = preprocess_cluster(prob2d, FetiConfig(schur=CFG_P,
                                               mode="implicit"))
    assert st.F is None
    assert isinstance(st.L, PackedBlocks)


# --------------------------------------------------------------------------
# sharded packed pipeline (CI multidevice lane)
# --------------------------------------------------------------------------


@multidevice
@pytest.mark.parametrize("mode", ["explicit", "implicit"])
def test_sharded_packed_solve_matches_single_device(case2d, mode):
    from repro.launch.mesh import make_feti_mesh

    prob, precond = case2d
    mesh = make_feti_mesh()
    fc = FetiConfig(schur=CFG_P, mode=mode, preconditioner=precond)
    sol_sh = FetiSolver(prob, fc.replace(mesh=mesh)).solve(tol=1e-10)
    sol1 = FetiSolver(prob, fc).solve(tol=1e-10)
    assert sol_sh.converged and sol1.converged
    # dirichlet: the shard_map-compiled S_b matches single-device only to
    # machine epsilon, which can flip the stopping test by one iteration
    slack = 0 if precond == "lumped" else 1
    assert abs(sol_sh.iterations - sol1.iterations) <= slack
    assert np.max(np.abs(sol_sh.u_global - sol1.u_global)) < 1e-9


@multidevice
def test_sharded_packed_state_is_packed(prob2d):
    from repro.feti import sharded as shlib
    from repro.launch.mesh import make_feti_mesh

    mesh = make_feti_mesh()
    st = preprocess_cluster(prob2d, FetiConfig(schur=CFG_P, mesh=mesh))
    assert isinstance(st.L, PackedBlocks)
    assert st.S % shlib.mesh_size(mesh) == 0
    # dummy padding subdomains factorize to identity in packed form too
    L_dense = np.asarray(st.L.unpack())
    for s in range(st.S_real, st.S):
        np.testing.assert_allclose(L_dense[s], np.eye(L_dense.shape[1]),
                                   rtol=0, atol=1e-12)


# --------------------------------------------------------------------------
# host-side packing (setup data never densified as a stack, nor on device)
# --------------------------------------------------------------------------


@pytest.mark.parametrize("n,bs", [(37, 8), (40, 8), (25, 16)])
def test_pack_host_matches_device_pack(n, bs):
    """pack_host (numpy, one matrix) stores exactly what the jnp pack
    stores, identity-padded diagonal tail included; unpack_symmetric
    recovers the whole symmetric matrix from the lower block triangle."""
    A = random_banded_spd(n, 5, np.random.default_rng(n))
    mask = block_symbolic_cholesky(block_pattern(np.abs(A) > 0, bs))
    index = PackedBlockIndex.from_mask(mask, n, bs)
    for pad in (False, True):
        host = index.pack_host(A, np.float64, diag_identity_pad=pad)
        dev = np.asarray(index.pack(jnp.asarray(A), diag_identity_pad=pad))
        assert isinstance(host, np.ndarray)
        np.testing.assert_array_equal(host, dev)
    np.testing.assert_array_equal(
        np.asarray(index.unpack_symmetric(jnp.asarray(
            index.pack_host(A)))), A)
    f32 = index.pack_host(A, np.float32)
    assert f32.dtype == np.float32
    np.testing.assert_array_equal(f32, index.pack_host(A).astype(np.float32))
    # perm packs A[perm][:, perm] without forming it
    perm = np.random.default_rng(n + 1).permutation(n)
    np.testing.assert_array_equal(
        index.pack_host(A, perm=perm, diag_identity_pad=True),
        index.pack_host(A[perm][:, perm], diag_identity_pad=True))


def test_packed_cholesky_from_packed_input():
    """block_cholesky_packed on an already-packed matrix (the form the
    preprocessor now streams) equals factorizing the dense matrix."""
    n, bs = 40, 8
    A = random_banded_spd(n, 6, np.random.default_rng(3))
    mask = block_symbolic_cholesky(block_pattern(np.abs(A) > 0, bs))
    index = PackedBlockIndex.from_mask(mask, n, bs)
    ref = block_cholesky_packed(jnp.asarray(A), index)
    vals = jnp.asarray(index.pack_host(A, diag_identity_pad=True))
    got = block_cholesky_packed(PackedBlocks(vals, index), index)
    np.testing.assert_array_equal(np.asarray(got.values),
                                  np.asarray(ref.values))
