"""Distributed FETI (repro.feti.sharded) and the relabeled-multiplier path.

Single-device tests cover the host-side placement helpers and the
``col_perm=None`` assembler equivalence — the property the sharded
deployment is built on: relabeling the local multiplier columns host-side
commutes with the whole assembly, for dense and sparse variants alike.

Tests marked ``multidevice`` compare the sharded pipeline (assembly, dual
operators, coarse problem, full PCPG solve) against the single-device one.
They auto-skip unless the backend has >=2 devices (tests/conftest.py); the
CI ``multidevice`` lane forces 8 host devices via
``XLA_FLAGS=--xla_force_host_platform_device_count=8``.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import SchurAssemblyConfig, build_stepped_meta, shared_envelope
from repro.fem import decompose_problem
from repro.feti import FetiConfig, FetiSolver
from repro.feti import sharded as shlib
from repro.feti.assembly import batched_assemble, preprocess_cluster
from repro.feti.operator import (
    explicit_dual_apply,
    implicit_dual_apply,
    lumped_preconditioner,
)
from repro.launch.mesh import make_feti_mesh
from repro.testing import random_feti_like_bt, random_lower_banded

CFG = SchurAssemblyConfig(block_size=8, rhs_block_size=8)

multidevice = pytest.mark.multidevice


# both workloads: the sharded pipeline must reproduce the single-device
# one with kernel dimension 1 (heat) AND > 1 (elasticity rigid bodies,
# k = 3 → the coarse G carries 3 columns per subdomain shard)
@pytest.fixture(scope="module", params=["heat", "elasticity"])
def prob(request):
    return decompose_problem(request.param, 2, (2, 2), (4, 4))


@pytest.fixture(scope="module")
def single(prob):
    return preprocess_cluster(prob, CFG)


@pytest.fixture(scope="module")
def mesh():
    return make_feti_mesh()


@pytest.fixture(scope="module")
def sharded_state(prob, mesh):
    return preprocess_cluster(prob, FetiConfig(schur=CFG, mesh=mesh))


def _br_stack(prob):
    from repro.feti.projector import interface_kernels

    return interface_kernels(prob.subdomains)


def _relabeled_padded_br(prob, st1, st_sh, mesh):
    """The (S, m_max, k) B̃R stack in the sharded layout (multiplier
    columns relabeled, dummy subdomains padded)."""
    BR_rel = shlib.relabel_columns(
        _br_stack(prob).swapaxes(1, 2), np.asarray(st1.col_perm)
    ).swapaxes(1, 2)
    return shlib.shard_stack(mesh, shlib.pad_stack(BR_rel, st_sh.S))


# --------------------------------------------------------------------------
# host-side helpers (single device)
# --------------------------------------------------------------------------


def test_pad_stack_zero_and_identity():
    x = np.arange(12.0).reshape(2, 3, 2)
    padded = shlib.pad_stack(x, 4)
    assert padded.shape == (4, 3, 2)
    np.testing.assert_array_equal(padded[:2], x)
    np.testing.assert_array_equal(padded[2:], 0.0)
    sq = np.ones((2, 3, 3))
    eye = shlib.pad_stack(sq, 3, identity=True)
    np.testing.assert_array_equal(eye[:2], sq)
    np.testing.assert_array_equal(eye[2], np.eye(3))
    assert shlib.pad_stack(x, 2) is x
    with pytest.raises(ValueError):
        shlib.pad_stack(x, 1)


def test_relabel_columns_is_the_column_permutation():
    rng = np.random.default_rng(0)
    stack = rng.standard_normal((3, 5, 4))
    perm = np.stack([rng.permutation(4) for _ in range(3)])
    out = shlib.relabel_columns(stack, perm)
    for s in range(3):
        np.testing.assert_array_equal(out[s], stack[s][:, perm[s]])
    # 2-d stacks (lambda_ids) relabel identically
    ids = rng.integers(0, 9, size=(3, 4))
    out2 = shlib.relabel_columns(ids, perm)
    for s in range(3):
        np.testing.assert_array_equal(out2[s], ids[s][perm[s]])


def test_mesh_size_requires_data_axis():
    bad = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("model",))
    with pytest.raises(ValueError):
        shlib.mesh_size(bad)


def test_padded_count_single_device_is_identity():
    mesh = make_feti_mesh(1)
    assert shlib.padded_count(5, mesh) == 5


# --------------------------------------------------------------------------
# the relabeled (col_perm=None) assembler path == the permuted path
# --------------------------------------------------------------------------


@settings(max_examples=12, deadline=None)
@given(
    trsm=st.sampled_from(["dense", "rhs_split", "factor_split"]),
    syrk=st.sampled_from(["dense", "input_split", "output_split"]),
    n=st.integers(16, 48),
    m=st.integers(4, 20),
    bs=st.integers(4, 16),
    seed=st.integers(0, 2**31 - 1),
)
def test_property_relabeled_path_matches_permuted_path(trsm, syrk, n, m, bs, seed):
    """Property: for ANY random cluster and ANY dense/sparse variant combo,
    ``batched_assemble(col_perm=None)`` on host-relabeled B̃ᵀ equals the
    runtime-permuted path up to the relabeling permutation, and both equal
    B̃ K⁻¹ B̃ᵀ."""
    rng = np.random.default_rng(seed)
    S = 3
    L = np.stack([random_lower_banded(n, min(8, n - 1), rng) for _ in range(S)])
    Bt = np.stack([random_feti_like_bt(n, m, rng) for _ in range(S)])
    metas = [build_stepped_meta(b != 0, block_size=bs, rhs_block_size=bs) for b in Bt]
    env = shared_envelope(metas)
    cp = np.stack([me.perm for me in metas])
    icp = np.stack([me.inv_perm for me in metas])
    cfg = SchurAssemblyConfig(
        trsm_variant=trsm,
        syrk_variant=syrk,
        block_size=bs,
        rhs_block_size=bs,
    )

    F_perm = np.asarray(
        batched_assemble(
            jnp.asarray(L),
            jnp.asarray(Bt),
            jnp.asarray(cp),
            jnp.asarray(icp),
            env,
            cfg,
            None,
        )
    )
    Bt_rel = shlib.relabel_columns(Bt, cp)
    F_rel = np.asarray(
        batched_assemble(
            jnp.asarray(L),
            jnp.asarray(Bt_rel),
            None,
            None,
            env,
            cfg,
            None,
        )
    )
    for s in range(S):
        np.testing.assert_allclose(
            F_rel[s],
            F_perm[s][cp[s]][:, cp[s]],
            rtol=1e-10,
            atol=1e-10,
        )
        K = L[s] @ L[s].T
        want = Bt[s].T @ np.linalg.solve(K, Bt[s])
        np.testing.assert_allclose(F_perm[s], want, rtol=1e-7, atol=1e-7)


@pytest.mark.parametrize(
    "trsm,syrk",
    [
        ("dense", "dense"),
        ("rhs_split", "input_split"),
        ("factor_split", "output_split"),
    ],
)
def test_cluster_relabeled_assembly_matches_state(prob, trsm, syrk):
    """Same equivalence on a REAL cluster state: the relabeled assembler
    reproduces ``ClusterState.F`` (which used the permuted path) up to each
    subdomain's stepped relabeling."""
    cfg = SchurAssemblyConfig(
        trsm_variant=trsm,
        syrk_variant=syrk,
        block_size=8,
        rhs_block_size=8,
    )
    st1 = preprocess_cluster(prob, cfg)
    cp = np.asarray(st1.col_perm)
    Btp_rel = shlib.relabel_columns(np.asarray(st1.Btp), cp)
    F_rel = np.asarray(
        batched_assemble(
            st1.L,
            jnp.asarray(Btp_rel),
            None,
            None,
            st1.env,
            cfg,
            st1.block_mask,
        )
    )
    F = np.asarray(st1.F)
    for s in range(F.shape[0]):
        np.testing.assert_allclose(
            F_rel[s],
            F[s][cp[s]][:, cp[s]],
            rtol=1e-10,
            atol=1e-10,
        )


# --------------------------------------------------------------------------
# sharded pipeline == single-device pipeline (the CI multidevice lane)
# --------------------------------------------------------------------------


@multidevice
def test_padded_count_rounds_up_to_mesh_multiple(mesh):
    D = shlib.mesh_size(mesh)
    assert D >= 2
    assert shlib.padded_count(1, mesh) == D
    assert shlib.padded_count(D, mesh) == D
    assert shlib.padded_count(D + 1, mesh) == 2 * D


@multidevice
def test_sharded_assembly_matches_batched(prob, mesh, single, sharded_state):
    """The sharded assembler's F equals the single-device batched_assemble
    result (up to the relabeling); padded dummy subdomains assemble to 0."""
    st1, st_sh = single, sharded_state
    S_real = st_sh.S_real
    assert st_sh.S % shlib.mesh_size(mesh) == 0
    assert S_real == len(prob.subdomains)
    cp = np.asarray(st1.col_perm)
    F1 = np.asarray(st1.F)
    F_sh = np.asarray(st_sh.F)
    for s in range(S_real):
        np.testing.assert_allclose(
            F_sh[s],
            F1[s][cp[s]][:, cp[s]],
            rtol=1e-10,
            atol=1e-10,
        )
    np.testing.assert_array_equal(F_sh[S_real:], 0.0)
    # factors of the real subdomains are untouched by sharding
    np.testing.assert_allclose(
        np.asarray(st_sh.L)[:S_real],
        np.asarray(st1.L),
        rtol=1e-12,
        atol=1e-12,
    )


@multidevice
def test_sharded_dual_operators_match(prob, mesh, single, sharded_state):
    st1, st_sh = single, sharded_state
    nl = prob.n_lambda
    rng = np.random.default_rng(3)
    lam = jnp.asarray(rng.standard_normal(nl))

    q1 = explicit_dual_apply(st1.F, st1.lambda_ids, nl, lam)
    q_sh = shlib.explicit_dual_apply(mesh, st_sh.F, st_sh.lambda_ids, nl, lam)
    np.testing.assert_allclose(np.asarray(q_sh), np.asarray(q1), rtol=1e-10, atol=1e-10)

    qi1 = implicit_dual_apply(st1.L, st1.Btp, st1.lambda_ids, nl, lam)
    qi_sh = shlib.implicit_dual_apply(
        mesh,
        st_sh.L,
        st_sh.Btp,
        st_sh.lambda_ids,
        nl,
        lam,
    )
    np.testing.assert_allclose(
        np.asarray(qi_sh),
        np.asarray(qi1),
        rtol=1e-10,
        atol=1e-10,
    )

    # K is packed in factor row order and pairs with Btp (feti.assembly)
    w1 = lumped_preconditioner(st1.K, st1.Btp, st1.lambda_ids, nl, lam)
    w_sh = shlib.lumped_preconditioner(
        mesh,
        st_sh.K,
        st_sh.Btp,
        st_sh.lambda_ids,
        nl,
        lam,
    )
    np.testing.assert_allclose(np.asarray(w_sh), np.asarray(w1), rtol=1e-10, atol=1e-10)


@multidevice
def test_sharded_coarse_problem_matches(prob, mesh, single, sharded_state):
    from repro.feti.projector import build_coarse_problem as build_single

    st1, st_sh = single, sharded_state
    nl = prob.n_lambda
    c1 = build_single(
        jnp.asarray(_br_stack(prob)),
        st1.f,
        st1.R,
        st1.lambda_ids,
        nl,
    )
    c_sh = shlib.build_coarse_problem(
        mesh,
        _relabeled_padded_br(prob, st1, st_sh, mesh),
        st_sh.f,
        st_sh.R,
        st_sh.lambda_ids,
        nl,
        S_real=st_sh.S_real,
    )
    np.testing.assert_allclose(
        np.asarray(c_sh.lambda0()),
        np.asarray(c1.lambda0()),
        rtol=1e-9,
        atol=1e-12,
    )
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.standard_normal(nl))
    np.testing.assert_allclose(
        np.asarray(c_sh.project(x)),
        np.asarray(c1.project(x)),
        rtol=1e-9,
        atol=1e-12,
    )


@multidevice
@pytest.mark.parametrize("mode", ["explicit", "implicit"])
def test_sharded_solve_matches_single_device(prob, mesh, mode):
    """The acceptance bar: same u_global (to 1e-9) and same iteration count
    as the single-device solve, and both match the undecomposed solve."""
    fc = FetiConfig(schur=CFG, mode=mode)
    sol_sh = FetiSolver(prob, fc.replace(mesh=mesh)).solve(tol=1e-10)
    sol1 = FetiSolver(prob, fc).solve(tol=1e-10)
    assert sol_sh.converged and sol1.converged
    assert sol_sh.iterations == sol1.iterations
    assert np.max(np.abs(sol_sh.u_global - sol1.u_global)) < 1e-9
    u_ref = prob.reference_solution()
    scale = np.abs(u_ref).max()
    np.testing.assert_allclose(sol_sh.u_global, u_ref, atol=1e-6 * scale)


@multidevice
def test_sharded_coarse_problem_carries_kernel_columns(prob, sharded_state):
    """G has k columns per (padded) subdomain — kernel dim > 1 for the
    elasticity parametrization."""
    st_sh = sharded_state
    k = st_sh.R.shape[2]
    assert k == (1 if prob.problem == "heat" else 3)


@multidevice
def test_sharded_solve_across_mesh_sizes(prob):
    """Mesh sizes that do and don't divide the subdomain count (padding)."""
    sol1 = FetiSolver(prob, CFG).solve(tol=1e-10)
    n_dev = len(jax.devices())
    for nd in sorted({2, 3, n_dev}):
        if nd > n_dev:
            continue
        sol = FetiSolver(prob, FetiConfig(
            schur=CFG, mesh=make_feti_mesh(nd))).solve(tol=1e-10)
        assert sol.iterations == sol1.iterations
        assert np.max(np.abs(sol.u_global - sol1.u_global)) < 1e-9
