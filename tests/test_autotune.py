"""Tests for the assembly autotuner + plan cache (repro.core.autotune).

Covers the ISSUE-1 acceptance set: plan-cache hit determinism, agreement of
``cfg="auto"`` with the best-scoring explicit config on a fixed pattern,
and numerical agreement of autotuned assembly with the dense baseline.
"""
import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    SchurAssemblyConfig,
    assembly_cost,
    build_stepped_meta,
    enumerate_space,
    make_assembler,
    plan,
    plan_assembly,
    schur_dense_baseline,
)
from repro.core.autotune import (
    assembly_bytes,
    clear_plan_cache,
    default_block_sizes,
    pattern_fingerprint,
    plan_cache_dir,
)
from repro.launch.roofline import DEVICE_MODELS, detect_device
from repro.testing import random_feti_like_bt


@pytest.fixture()
def tmp_cache(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_PLAN_CACHE", str(tmp_path / "plans"))
    return tmp_path / "plans"


def test_plan_cache_dir_env_routing(tmp_path, monkeypatch):
    """$REPRO_PLAN_CACHE_DIR (the canonical, CI-facing spelling) wins over
    the legacy $REPRO_PLAN_CACHE, which wins over the home default —
    re-read at every access, not captured at import."""
    monkeypatch.delenv("REPRO_PLAN_CACHE_DIR", raising=False)
    monkeypatch.delenv("REPRO_PLAN_CACHE", raising=False)
    assert plan_cache_dir().endswith(os.path.join("repro", "plans"))
    monkeypatch.setenv("REPRO_PLAN_CACHE", str(tmp_path / "legacy"))
    assert plan_cache_dir() == str(tmp_path / "legacy")
    monkeypatch.setenv("REPRO_PLAN_CACHE_DIR", str(tmp_path / "canonical"))
    assert plan_cache_dir() == str(tmp_path / "canonical")


def _pattern(n=96, m=40, seed=0):
    rng = np.random.default_rng(seed)
    return random_feti_like_bt(n, m, rng) != 0


# ---------------------------------------------------------------- space ----

def test_enumerate_space_canonical():
    space = enumerate_space([16, 32])
    # no structural duplicates
    assert len(space) == len(set(space))
    # prune only toggles for factor_split, pallas never pairs dense/dense,
    # and packed storage only appears where it is native (factor_split)
    for cfg in space:
        if cfg.trsm_variant != "factor_split":
            assert not cfg.prune
        if cfg.use_pallas:
            assert not (cfg.trsm_variant == "dense"
                        and cfg.syrk_variant == "dense")
        if cfg.storage == "packed":
            assert cfg.trsm_variant == "factor_split"
        if cfg.fused:
            assert cfg.use_pallas
    # per block size: 12 dense non-pallas (9 combos + 3 extra prunes)
    # + 8 dense pallas + 3 packed factor_split + 3 packed pallas
    # + 2 fused megakernel (1 dense + 1 packed)
    assert len(space) == 2 * (12 + 8 + 3 + 3 + 2)
    assert sum(c.fused for c in space) == 4
    # every variant pair is represented
    pairs = {(c.trsm_variant, c.syrk_variant) for c in space}
    assert len(pairs) == 9
    # storage restriction prunes the space to one layout
    assert all(c.storage == "packed" for c in
               enumerate_space([16], storage="packed"))
    assert all(c.storage == "dense" for c in
               enumerate_space([16], storage="dense"))


def test_default_block_sizes_clip_to_problem():
    assert default_block_sizes(25) == (8, 16)
    assert max(default_block_sizes(5000)) == 256
    assert default_block_sizes(4) == (4,)


# ----------------------------------------------------------- cost model ----

def test_cost_model_positive_and_dense_single_op():
    pat = _pattern()
    meta = build_stepped_meta(pat, block_size=16)
    # storage is pinned: this asserts the DENSE baseline's launch count
    # (under the packed-default CI lane the env would flip it otherwise)
    dense = SchurAssemblyConfig("dense", "dense", 16, prune=False,
                                storage="dense")
    by = assembly_bytes(meta, dense)
    assert by["ops"] == 2  # one TRSM + one SYRK launch
    assert by["total"] > 0
    dev = DEVICE_MODELS["cpu"]
    for cfg in enumerate_space([16]):
        cost = assembly_cost(meta, cfg, dev)
        assert cost["total_s"] > 0
        assert cost["flops"] > 0


def test_pallas_never_wins_off_tpu():
    pat = _pattern()
    meta = build_stepped_meta(pat, block_size=16)
    dev = DEVICE_MODELS["cpu"]
    costs = {cfg: assembly_cost(meta, cfg, dev)["total_s"]
             for cfg in enumerate_space([16])}
    best = min(costs, key=costs.get)
    assert not best.use_pallas


# ----------------------------------------------------------- plan cache ----

def test_plan_cache_hit_determinism(tmp_cache):
    pat = _pattern()
    p1 = plan_assembly(pat, measure="never")
    assert not p1.from_cache
    p2 = plan_assembly(pat, measure="never")
    assert p2.from_cache
    assert p2.cfg == p1.cfg
    assert p2.key == p1.key
    assert p2.predicted_s == p1.predicted_s
    # same *pattern content* in a fresh array object also hits
    p3 = plan_assembly(pat.copy(), measure="never")
    assert p3.from_cache and p3.cfg == p1.cfg


def test_plan_cache_respects_pattern_and_device(tmp_cache):
    pat = _pattern(seed=1)
    p1 = plan_assembly(pat, measure="never")
    other = plan_assembly(_pattern(seed=2), measure="never")
    assert other.key != p1.key
    gpu = plan_assembly(pat, measure="never",
                        device=DEVICE_MODELS["NVIDIA A100-SXM4-80GB"])
    assert gpu.key != p1.key
    assert not gpu.from_cache


def test_cache_can_be_disabled_and_cleared(tmp_cache):
    pat = _pattern(seed=3)
    plan_assembly(pat, measure="never")
    assert clear_plan_cache() >= 1
    p = plan_assembly(pat, measure="never", cache=False)
    assert not p.from_cache
    assert clear_plan_cache() == 0  # cache=False wrote nothing


def test_fingerprint_is_content_addressed():
    piv = np.array([0, 3, 5, 9])
    a = pattern_fingerprint(piv, 12, 4)
    assert a == pattern_fingerprint(piv.copy(), 12, 4)
    assert a != pattern_fingerprint(piv + 1, 12, 4)
    assert a != pattern_fingerprint(piv, 13, 4)


# ------------------------------------------------------ plan selection -----

def test_auto_equals_best_scoring_explicit_config(tmp_cache):
    """measure='never' planning must return exactly the roofline argmin."""
    pat = _pattern(n=128, m=48, seed=4)
    p = plan_assembly(pat, measure="never", block_sizes=(16, 32))
    dev = detect_device()
    best_cfg, best_s = None, float("inf")
    for cfg in enumerate_space((16, 32), interpret=dev.kind != "tpu"):
        meta = build_stepped_meta(pat, block_size=cfg.block_size,
                                  rhs_block_size=cfg.rhs_bs)
        s = assembly_cost(meta, cfg, dev)["total_s"]
        if s < best_s:
            best_cfg, best_s = cfg, s
    assert p.cfg == best_cfg
    assert p.predicted_s == pytest.approx(best_s)


def test_plan_summary_mentions_choice(tmp_cache):
    p = plan_assembly(_pattern(seed=5), measure="never")
    s = p.summary()
    assert p.cfg.trsm_variant in s and p.cfg.syrk_variant in s
    assert "predicted" in s


# ------------------------------------------------- numerical agreement -----

def test_autotuned_assembly_matches_dense_baseline(tmp_cache):
    rng = np.random.default_rng(6)
    n, m = 96, 40
    Bt = random_feti_like_bt(n, m, rng)
    p = plan_assembly(Bt != 0, measure="never")
    meta = build_stepped_meta(Bt != 0, block_size=p.cfg.block_size,
                              rhs_block_size=p.cfg.rhs_bs)
    L = np.tril(rng.standard_normal((n, n))) * 0.1
    np.fill_diagonal(L, 1.0 + rng.random(n))
    Lj, Btj = jnp.asarray(L), jnp.asarray(Bt)
    F_auto = make_assembler(meta, p.cfg)(Lj, Btj)
    F_ref = schur_dense_baseline(Lj, Btj)
    assert float(jnp.max(jnp.abs(F_auto - F_ref))) < 1e-8


def test_preprocess_cluster_auto_end_to_end(tmp_cache):
    """cfg='auto' flows through the cluster path; SCs match the baseline."""
    from repro.fem import decompose_heat_problem
    from repro.feti import FetiConfig, preprocess_cluster

    prob = decompose_heat_problem(2, (2, 2), (4, 4))
    st = preprocess_cluster(prob, FetiConfig(schur="auto",
                                             measure="never"))
    assert isinstance(st.cfg, SchurAssemblyConfig)
    assert st.plan is not None
    assert st.plan.cfg == st.cfg
    F_ref = jax.vmap(schur_dense_baseline)(st.L, st.Btp)
    assert float(jnp.max(jnp.abs(st.F - F_ref))) < 1e-8
    # second preprocess is a cache hit with the same plan
    st2 = preprocess_cluster(prob, FetiConfig(schur="auto",
                                              measure="never"))
    assert st2.plan.from_cache
    assert st2.cfg == st.cfg


def test_solver_accepts_auto(tmp_cache):
    from repro.fem import decompose_heat_problem
    from repro.feti import FetiConfig, FetiSolver

    prob = decompose_heat_problem(2, (2, 2), (4, 4))
    solver = FetiSolver(prob, FetiConfig(schur="auto", measure="never"))
    sol = solver.solve(tol=1e-9)
    assert sol.converged
    assert isinstance(solver.cfg, SchurAssemblyConfig)
    assert solver.plan is not None
    # agrees with the hand-picked default config's solution
    ref = FetiSolver(prob, SchurAssemblyConfig(block_size=8)).solve(tol=1e-9)
    assert np.allclose(sol.u_global, ref.u_global, atol=1e-8)


def test_plan_facade_exported():
    assert plan is plan_assembly


def test_plan_json_roundtrip(tmp_cache):
    from repro.core.autotune import Plan

    p = plan_assembly(_pattern(seed=7), measure="never")
    q = Plan.from_json(p.to_json())
    assert q.cfg == p.cfg and q.from_cache
    assert dataclasses.asdict(q.cfg) == dataclasses.asdict(p.cfg)


# ------------------------------------------ device model + TPU gating ----

def test_detect_device_keys_on_device_kind():
    """Device models are keyed by jax's device_kind; the CPU this suite
    runs on resolves to the CPU model, and a v5e to the TPU one."""
    assert detect_device().name == jax.devices()[0].device_kind
    assert detect_device("cpu").kind == "cpu"
    tpu = detect_device("TPU v5 lite")
    assert tpu.kind == "tpu" and tpu is DEVICE_MODELS["TPU v5 lite"]


@pytest.mark.parametrize("kind", ["TPU v4", "TPU v6 lite", "NVIDIA H100",
                                  "tpu", "gpu"])
def test_detect_device_rejects_unknown_accelerators(kind):
    """An accelerator with no model is an error — never another device's
    numbers (the old fallback ranked plans with the CPU model)."""
    with pytest.raises(ValueError, match="no device model"):
        detect_device(kind)


def _tpu_candidates(pat, dtype, block_sizes=(128, 256)):
    tpu = DEVICE_MODELS["TPU v5 lite"]
    p = plan_assembly(pat, measure="never", block_sizes=block_sizes,
                      device=tpu, cache=False, dtype=dtype)
    from repro.core.autotune import offered_on

    offered = []
    for cfg in enumerate_space(block_sizes, interpret=False):
        meta = build_stepped_meta(pat, block_size=cfg.block_size,
                                  rhs_block_size=cfg.rhs_bs)
        if offered_on(tpu, cfg, meta, None, dtype):
            offered.append(cfg)
    return p, offered


def test_tpu_planner_offers_no_pallas_for_f64():
    """Mosaic has no f64: on a TPU, f64 stages get no Pallas candidate, so
    the plan is never a Pallas one; f32/bf16 stages get them all."""
    pat = _pattern(n=512, m=160, seed=4)
    p64, offered64 = _tpu_candidates(pat, "f64")
    assert offered64 and not any(c.use_pallas for c in offered64)
    assert not p64.cfg.use_pallas
    n_all = len(enumerate_space((128, 256), interpret=False))
    assert p64.candidates == len(offered64) < n_all
    for dtype in ("f32", "bf16"):
        _, offered = _tpu_candidates(pat, dtype)
        assert len(offered) == n_all
        assert any(c.use_pallas and c.fused for c in offered)


def test_tpu_planner_offers_pallas_only_at_lane_multiples():
    """Mosaic refuses block shapes whose lane dimension is not a multiple
    of 128 (and the dense kernels' lane slices at bs = 64): on a TPU,
    Pallas candidates at smaller blocks are not offered, the jnp ones
    still are."""
    pat = _pattern(n=512, m=160, seed=4)
    _, offered = _tpu_candidates(pat, "f32", block_sizes=(64, 128))
    assert any(c.use_pallas and c.block_size == 128 for c in offered)
    assert not any(c.use_pallas and c.block_size == 64 for c in offered)
    assert any(not c.use_pallas and c.block_size == 64 for c in offered)


def test_tpu_planner_drops_candidates_over_vmem(monkeypatch):
    """A Pallas candidate whose modeled VMEM working set exceeds the limit
    the kernels ask for is not offered; the unfused kernels, whose working
    set is one stripe, still are when the fused panel is what is too big."""
    from repro.core import autotune
    from repro.kernels import common

    pat = _pattern(n=512, m=256, seed=5)
    meta = build_stepped_meta(pat, block_size=128, rhs_block_size=128)
    fused = SchurAssemblyConfig(block_size=128, use_pallas=True, fused=True,
                                trsm_variant="rhs_split",
                                syrk_variant="output_split", storage="dense")
    unfused = dataclasses.replace(fused, fused=False)
    need_fused = autotune.pallas_vmem_bytes(meta, fused, "f32")
    need_unfused = autotune.pallas_vmem_bytes(meta, unfused, "f32")
    assert need_unfused < need_fused
    tpu = DEVICE_MODELS["TPU v5 lite"]
    assert autotune.offered_on(tpu, fused, meta, None, "f32")
    monkeypatch.setattr(common, "VMEM_LIMIT_BYTES", need_fused - 1)
    assert not autotune.offered_on(tpu, fused, meta, None, "f32")
    assert autotune.offered_on(tpu, unfused, meta, None, "f32")
    monkeypatch.setattr(common, "VMEM_LIMIT_BYTES", need_unfused - 1)
    assert not autotune.offered_on(tpu, unfused, meta, None, "f32")
    # off-TPU nothing is gated: Pallas runs interpreted, penalized
    assert autotune.offered_on(DEVICE_MODELS["cpu"], fused, meta, None,
                               "f64")


def test_vmem_model_heat2d_full_size_fits():
    """At feti-heat-2d's real shapes every kernel's working set fits: the
    factor streams from HBM instead of sitting whole in VMEM (a padded f32
    factor alone is 4352² · 4 B ≈ 76 MB)."""
    from repro.kernels.common import VMEM_LIMIT_BYTES, vmem_bytes

    for kernel in ("trsm", "trsm_packed", "syrk", "fused", "fused_packed"):
        need = vmem_bytes(kernel, 4352, 384, 128, 128, 4)
        assert need <= VMEM_LIMIT_BYTES, (kernel, need)
    assert vmem_bytes("fused", 4352, 384, 128, 128, 4) > 4352 * 384 * 4
