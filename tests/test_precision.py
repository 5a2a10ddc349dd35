"""Mixed-precision assembly + refined-accuracy tier (ISSUE 9).

Covers the precision axis end to end: the dtype vocabulary of
:mod:`repro.core.precision`, dtype propagation through the compiled
preprocessing (no silent f64 upcasts), the f64-refined accuracy contract
(f32 storage must still match the scipy oracle to 1e-8), strict
bit-identity of the f64 default path, and regression tests for the three
dtype-blind bugs this issue fixed:

  * ``assemble_dirichlet_schur`` hardcoded ``dtype=jnp.float64``,
  * the coarse-factor pivot floor sat below f32 eps (rank detection
    broke for f32 coarse problems),
  * PCPG accepted tolerances no reduced-precision operator can attain
    and divided by denominators that underflow in f32.
"""
import warnings

import jax.numpy as jnp
import numpy as np
import pytest

import repro.core.precision as prec
from repro.core import SchurAssemblyConfig
from repro.fem import decompose_problem
from repro.feti import FetiConfig, FetiSolver
from repro.feti.assembly import preprocess_cluster
from repro.feti.dirichlet import assemble_dirichlet_schur
from repro.feti.projector import coarse_factor
from repro.sparse import PackedBlocks

pytestmark = pytest.mark.precision

CFG = SchurAssemblyConfig(block_size=8, rhs_block_size=8)

# the accuracy contract: reduced-precision storage with refinement must
# match the undecomposed scipy solve as tightly as the f64 pipeline does
ORACLE_RTOL = 1e-8


@pytest.fixture(scope="module", params=["heat", "elasticity"])
def prob2d(request):
    return decompose_problem(request.param, 2, (2, 2), (3, 3))


def _values(x):
    return np.asarray(x.values if isinstance(x, PackedBlocks) else x)


def _dtype_of(x):
    return (x.values if isinstance(x, PackedBlocks) else x).dtype


# --------------------------------------------------------------------------
# the dtype vocabulary
# --------------------------------------------------------------------------


def test_canonical_dtype_spellings():
    assert prec.canonical_dtype("f64") == np.dtype(np.float64)
    assert prec.canonical_dtype("f32") == np.dtype(np.float32)
    assert prec.canonical_dtype(np.float32) == np.dtype(np.float32)
    assert prec.canonical_dtype(float) == np.dtype(np.float64)
    assert prec.dtype_name(np.float64) == "f64"
    with pytest.raises(ValueError, match="unsupported"):
        prec.canonical_dtype(np.int32)
    with pytest.raises(ValueError, match="unsupported"):
        prec.canonical_dtype("f16")


def test_compute_and_solve_dtype_rules():
    assert prec.compute_dtype("f64") == np.dtype(np.float64)
    assert prec.compute_dtype("f32") == np.dtype(np.float32)
    if "bf16" in prec.SUPPORTED_DTYPES:
        # bf16 factorizes/accumulates in f32 — the only rule hardware has
        assert prec.compute_dtype("bf16") == np.dtype(np.float32)
    assert prec.solve_dtype("f32", 2) == np.dtype(np.float64)
    assert prec.solve_dtype("f32", 0) == np.dtype(np.float32)
    assert prec.solve_dtype("f64", 0) == np.dtype(np.float64)
    assert prec.itemsize("f32") == 4
    assert prec.tol_floor("f32") == 50 * np.finfo(np.float32).eps
    assert prec.default_refine_steps("f64") == 0
    assert prec.default_refine_steps("f32") > 0


def test_feticonfig_precision_members():
    fc = FetiConfig(dtype="f32")
    assert fc.reduced and fc.dtype_name == "f32"
    assert fc.resolved_refine() == prec.default_refine_steps("f32")
    assert np.dtype(fc.solve_dtype) == np.dtype(np.float64)
    fc0 = FetiConfig(dtype="f32", refine=0)
    assert np.dtype(fc0.solve_dtype) == np.dtype(np.float32)
    f64 = FetiConfig()
    assert not f64.reduced and f64.resolved_refine() == 0
    with pytest.raises(ValueError, match="unsupported"):
        FetiConfig(dtype="f16")
    with pytest.raises(ValueError, match="refine"):
        FetiConfig(refine=-1)


def test_device_model_per_dtype_peaks():
    from repro.launch.roofline import DEVICE_MODELS

    for name in ("TPU v5 lite", "NVIDIA A100-SXM4-80GB"):
        dev = DEVICE_MODELS[name]
        assert dev.peak("f32") > dev.peak("f64")
        assert dev.peak("bf16") >= dev.peak("f32")
        # pricing follows the peak: same flops, cheaper in f32
        assert dev.time_s(1e12, 0, dtype="f32") < dev.time_s(
            1e12, 0, dtype="f64")


# --------------------------------------------------------------------------
# dtype propagation through preprocessing (no silent f64 upcasts)
# --------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", ["f64", "f32"])
def test_cluster_state_dtypes(prob2d, dtype):
    fc = FetiConfig(schur=CFG, mode="explicit", preconditioner="dirichlet",
                    dtype=dtype)
    st = preprocess_cluster(prob2d, fc)
    sdt = prec.canonical_dtype(dtype)
    # every assembled/factorized stack carries the storage dtype — the
    # compiled prep must not leak f64 intermediates
    for name in ("L", "K", "F", "Sb", "Btp", "Btb"):
        arr = getattr(st, name)
        assert _dtype_of(arr) == sdt, f"{name} is {_dtype_of(arr)}"
    # the PCPG outer vectors carry the SOLVE dtype
    expect_solve = np.dtype(fc.solve_dtype)
    for name in ("f", "fp", "R"):
        assert np.asarray(getattr(st, name)).dtype == expect_solve, name
    if fc.resolved_refine() > 0:
        assert st.Kreg is not None
        assert _dtype_of(st.Kreg) == np.dtype(np.float64)
        assert st.refine_steps == fc.resolved_refine()
    else:
        assert st.Kreg is None and st.refine_steps == 0


def test_f32_halves_device_bytes(prob2d):
    st64 = preprocess_cluster(prob2d, FetiConfig(schur=CFG))
    st32 = preprocess_cluster(
        prob2d, FetiConfig(schur=CFG, dtype="f32", refine=0))
    b64, b32 = st64.device_bytes(), st32.device_bytes()
    for name in ("L", "K", "Btp", "F"):
        assert b32[name] * 2 == b64[name], name


# --------------------------------------------------------------------------
# satellite regression: dirichlet assembly honors the requested dtype
# --------------------------------------------------------------------------


def test_dirichlet_schur_dtype_honored(prob2d):
    Sb64, _, _ = assemble_dirichlet_schur(prob2d, CFG)
    assert np.asarray(Sb64).dtype == np.float64  # default unchanged
    Sb32, Btb32, _ = assemble_dirichlet_schur(prob2d, CFG, dtype="f32")
    assert np.asarray(Sb32).dtype == np.float32
    assert np.asarray(Btb32).dtype == np.float32
    np.testing.assert_allclose(
        np.asarray(Sb32), np.asarray(Sb64), rtol=0, atol=1e-3)
    # a FetiConfig carries its own dtype through
    SbC, _, _ = assemble_dirichlet_schur(
        prob2d, FetiConfig(schur=CFG, dtype="f32"))
    assert np.asarray(SbC).dtype == np.float32


# --------------------------------------------------------------------------
# satellite regression: coarse-factor rank floor scales with eps
# --------------------------------------------------------------------------


def test_coarse_factor_rank_floor_is_dtype_aware():
    rng = np.random.default_rng(0)
    A = rng.standard_normal((40, 3))
    G = np.concatenate([A, A[:, :1]], axis=1)  # exactly dependent col
    for dt, lo in ((np.float64, 1e-10), (np.float32, 1e-6)):
        Gd = np.asarray(G, dtype=dt)
        L = np.asarray(coarse_factor(Gd))
        diag = np.abs(np.diag(L))
        col_scale = np.sqrt((Gd * Gd).sum() / Gd.shape[1])
        # the dependent column's pivot is floored well above dtype noise
        # (the old hardcoded 1e-12 floor sat below f32 QR noise, so f32
        # rank detection silently failed)
        floor = np.sqrt(max(1e-12, float(np.finfo(dt).eps * 1e3) ** 2))
        assert diag.min() >= 0.99 * floor * col_scale
        # healthy pivots pass through untouched: solving with the factor
        # reproduces GᵀG on the independent block
        GtG = (Gd.astype(np.float64).T @ Gd.astype(np.float64))[:3, :3]
        np.testing.assert_allclose(
            (L @ L.T)[:3, :3], GtG, rtol=0, atol=lo * col_scale**2)


def test_coarse_factor_f64_path_bit_identical():
    # the dtype-aware floor must not perturb f64: max(1e-12, (1e3·eps)²)
    # picks the historical 1e-12 exactly
    assert max(1e-12, float(np.finfo(np.float64).eps * 1e3) ** 2) == 1e-12


# --------------------------------------------------------------------------
# satellite regression: PCPG dtype-aware tolerances + safe denominators
# --------------------------------------------------------------------------


def test_pcpg_clamps_unattainable_tol(prob2d):
    import sys

    # repro.feti re-exports the pcpg *function* under the submodule's
    # name, so `import repro.feti.pcpg` resolves to the function — go
    # through sys.modules for the module object
    pcpg_mod = sys.modules["repro.feti.pcpg"]
    pcpg_mod.reset_tol_clamp_warnings()
    solver = FetiSolver(
        prob2d, FetiConfig(schur=CFG, mode="explicit", dtype="f32",
                           refine=0))
    with pytest.warns(RuntimeWarning, match="attainable floor"):
        sol = solver.solve(tol=1e-13)
    # the clamped solve stops at the f32 floor instead of burning
    # max_iter on rounding noise — and still converges (to that floor)
    assert sol.converged
    assert sol.iterations < 2000


def test_pcpg_f64_tol_passes_unclamped():
    from repro.feti.pcpg import TolClampState, _clamp_tol

    with warnings.catch_warnings():
        warnings.simplefilter("error")
        assert _clamp_tol(1e-9, np.dtype(np.float64),
                          TolClampState()) == 1e-9


def test_safe_denom_no_underflow():
    import jax.numpy as jnp

    from repro.feti.pcpg import _safe_denom

    tiny = np.float32(1e-42)  # subnormal in f32
    x = jnp.asarray([tiny, -tiny, np.float32(0.0), np.float32(2.0)])
    d = np.asarray(_safe_denom(x))
    assert np.all(np.abs(d) >= np.finfo(np.float32).tiny)
    assert d[3] == np.float32(2.0)  # normal values bit-unchanged
    assert np.all(np.isfinite(1.0 / d))


# --------------------------------------------------------------------------
# the accuracy contract: f32-refined solves match the scipy oracle
# --------------------------------------------------------------------------


@pytest.mark.parametrize("mode", ["explicit", "implicit"])
def test_f32_refined_matches_oracle(prob2d, mode):
    solver = FetiSolver(
        prob2d, FetiConfig(schur=CFG, mode=mode, dtype="f32"))
    sol = solver.solve(tol=1e-10)
    assert sol.converged
    u_ref = prob2d.reference_solution()
    scale = np.abs(u_ref).max()
    np.testing.assert_allclose(
        sol.u_global, u_ref, rtol=0, atol=ORACLE_RTOL * scale)
    if mode == "explicit":
        assert sol.refine_outer >= 1  # the outer loop actually engaged


@pytest.mark.dirichlet
def test_f32_refined_dirichlet_matches_oracle(prob2d):
    solver = FetiSolver(
        prob2d, FetiConfig(schur=CFG, mode="explicit",
                           preconditioner="dirichlet", dtype="f32"))
    sol = solver.solve(tol=1e-10)
    assert sol.converged
    u_ref = prob2d.reference_solution()
    scale = np.abs(u_ref).max()
    np.testing.assert_allclose(
        sol.u_global, u_ref, rtol=0, atol=ORACLE_RTOL * scale)


@pytest.mark.multirhs
def test_f32_refined_solve_many_matches_oracle(prob2d):
    cases = prob2d.load_cases(3, kind="mixed", seed=0)
    solver = FetiSolver(
        prob2d, FetiConfig(schur=CFG, mode="explicit", dtype="f32"))
    solm = solver.solve_many(cases, tol=1e-10)
    assert bool(solm.converged.all())
    refs = prob2d.reference_solutions(cases)
    scale = np.abs(refs).max()
    np.testing.assert_allclose(
        solm.u_global, refs, rtol=0, atol=ORACLE_RTOL * scale)


def test_f64_config_bit_identical(prob2d):
    """dtype="f64" must run the exact pre-ISSUE-9 program: same iteration
    count, bit-identical multipliers and solution as the default config."""
    a = FetiSolver(prob2d, FetiConfig(schur=CFG, mode="explicit"))
    b = FetiSolver(
        prob2d, FetiConfig(schur=CFG, mode="explicit", dtype="f64",
                           refine=0))
    sa, sb = a.solve(tol=1e-10), b.solve(tol=1e-10)
    assert sa.iterations == sb.iterations
    assert sa.refine_outer == sb.refine_outer == 0
    np.testing.assert_array_equal(sa.lam, sb.lam)
    np.testing.assert_array_equal(sa.u_global, sb.u_global)
    assert a.state.Kreg is None and b.state.Kreg is None


@pytest.mark.skipif("bf16" not in prec.SUPPORTED_DTYPES,
                    reason="ml_dtypes missing")
def test_bf16_smoke():
    """bf16 storage is experimental: the solve must run end to end and
    land in the right neighborhood (the refined operators are only as
    accurate as κ·eps_bf16 lets them be — no 1e-8 contract here)."""
    prob = decompose_problem("heat", 2, (2, 2), (3, 3))
    solver = FetiSolver(prob, FetiConfig(schur=CFG, mode="explicit",
                                         dtype="bf16"))
    sol = solver.solve(tol=1e-6, max_iter=500)
    u_ref = prob.reference_solution()
    scale = np.abs(u_ref).max()
    assert np.all(np.isfinite(sol.u_global))
    assert np.abs(sol.u_global - u_ref).max() <= 1e-2 * scale


# --------------------------------------------------------------------------
# the planner: dtype in the cost model, the cache key, and the plan
# --------------------------------------------------------------------------


def test_autotune_cache_key_includes_dtype():
    from repro.core.autotune import _cache_key
    from repro.launch.roofline import DEVICE_MODELS

    dev = DEVICE_MODELS["cpu"]
    k64 = _cache_key("deadbeef", dev, (8, 16), False, dtype="f64")
    k32 = _cache_key("deadbeef", dev, (8, 16), False, dtype="f32")
    assert k64 != k32


def test_stage_graph_key_and_spec_carry_dtype():
    from repro.core import StageGraph, StageSpec
    from repro.launch.roofline import DEVICE_MODELS

    def builder(bs, rbs):  # never called by joint_key
        raise AssertionError

    def graph(dt):
        return StageGraph([StageSpec(
            name="dual", builder=builder, fingerprint="feedface", n=64,
            dtype=dt, block_sizes=(16,))])

    dev = DEVICE_MODELS["cpu"]
    assert graph("f64").joint_key(dev, False) \
        != graph("f32").joint_key(dev, False)
    spec = graph("f32").stages[0]
    assert spec.dtype_bytes == 4  # derived from the dtype name
    assert StageSpec.__dataclass_fields__["dtype"].default == "f64"


def test_planned_stage_dtype_matches_config(prob2d):
    """schur="auto" under a reduced config: the planner prices, probes
    and records the stage at the config's dtype."""
    fc = FetiConfig(schur="auto", dtype="f32", measure="never",
                    plan_cache=False)
    st = preprocess_cluster(prob2d, fc)
    assert st.graph_plan is not None
    p = st.graph_plan["dual"]
    assert p.dtype == "f32"
    assert "dtype=f32" in p.summary()
    assert _dtype_of(st.F) == np.float32


def test_assembly_cost_prices_dtype():
    """Same stage, f32 dtype: half the bytes and a wider FLOP peak, so
    the predicted cost must strictly drop on GPU-like devices."""
    from repro.core import assembly_cost, build_stepped_meta
    from repro.launch.roofline import DEVICE_MODELS
    from repro.testing import random_feti_like_bt

    rng = np.random.default_rng(0)
    pat = random_feti_like_bt(96, 40, rng) != 0
    meta = build_stepped_meta(pat, block_size=16)
    cfg = SchurAssemblyConfig(block_size=16)
    dev = DEVICE_MODELS["NVIDIA A100-SXM4-80GB"]
    c64 = assembly_cost(meta, cfg, dev, dtype="f64")
    c32 = assembly_cost(meta, cfg, dev, dtype="f32")
    assert c32["total_s"] < c64["total_s"]
    assert c32["bytes"] * 2 == c64["bytes"]


# ------------------------------------- f64 contractions on a TPU ----------

@pytest.mark.parametrize("subscripts,shapes", [
    ("snm,sm->sn", [(3, 7, 5), (3, 5)]),
    ("snm,sn->sm", [(3, 7, 5), (3, 7)]),
    ("snm,snk->smk", [(3, 7, 5), (3, 7, 2)]),
    ("snm,smr->snr", [(3, 7, 5), (3, 5, 4)]),
    ("bji,bj->bi", [(6, 4, 4), (6, 4)]),
    ("sab,sb->sa", [(3, 5, 5), (3, 5)]),
])
def test_f64_contractions_on_tpu_route(subscripts, shapes, monkeypatch):
    """On a TPU, f64 matrix-vector contractions run as an elementwise
    product + sum (XLA:TPU's emulated f64 dot needs temporaries several
    times its operands); the route computes the same contraction."""
    from repro.core import precision

    rng = np.random.default_rng(len(subscripts))
    a, b = (jnp.asarray(rng.normal(size=s)) for s in shapes)
    ref = jnp.einsum(subscripts, a, b)
    assert precision.einsum(subscripts, a, b).dtype == ref.dtype
    monkeypatch.setattr(precision, "_f64_on_tpu",
                        lambda *xs: jnp.result_type(*xs) == jnp.float64)
    got = precision.einsum(subscripts, a, b)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-14, atol=1e-14)
    # matmul-shaped contractions (index space >> operands) keep the dot
    assert precision._elementwise_einsum(
        "ij,jk->ik", jnp.ones((64, 64)), jnp.ones((64, 64))) is None
    G = jnp.asarray(rng.normal(size=(9, 4)))
    x = jnp.asarray(rng.normal(size=(4,)))
    np.testing.assert_allclose(np.asarray(precision.mm(G, x)),
                               np.asarray(G @ x), rtol=1e-14, atol=1e-14)
