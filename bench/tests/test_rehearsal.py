"""A rehearsal of the harness on the CPU at the registry's smoke sizes:
the traffic is the seed's alone, the reference agrees with the program's
own oracle, a run reaches ``correct`` true, the control and each planted
fault read ``correct`` false, and ``run.py`` refuses to run without a TPU
or without the program.

Run by path: ``JAX_PLATFORMS=cpu python -m pytest bench/tests``.
"""
import copy
import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path[:0] = [str(BENCH), str(ROOT / "src")]

import harness  # noqa: E402
import traffic  # noqa: E402
from reference import Reference, rel_err  # noqa: E402

SEED = 2**31 + 12345  # the driver's seeds pass 32 signed bits
CELLS = [w["name"] for w in harness.manifest()["workloads"]]


def config_file(cell: str) -> Path:
    man = harness.manifest()
    w = next(w for w in man["workloads"] if w["name"] == cell)
    return ROOT / next(c for c in man["configs"]
                       if c["name"] == w["config"])["file"]


# not a cell (too small to stand for a deployment): the elasticity
# reference and the f64 path, rehearsed at smoke size
ELASTICITY = BENCH / "tests" / "data" / "feti-elasticity-2d.json"
CONFIGS = [config_file("heat2d-assemble"), ELASTICITY]


def smoke(cell_or_file) -> dict:
    """A configuration at the registry's smoke size: 2x2 subdomains of
    4x4 elements, blocks of 8."""
    path = (cell_or_file if isinstance(cell_or_file, Path)
            else config_file(cell_or_file))
    cfg = copy.deepcopy(harness.read_json(path))
    cfg["sub_grid"] = [2] * len(cfg["sub_grid"])
    cfg["elems_per_sub"] = [4] * len(cfg["elems_per_sub"])
    cfg["feti"]["schur"].update(block_size=8, rhs_block_size=8)
    return cfg


@pytest.fixture(autouse=True)
def _cache(tmp_path, monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))


def run(cell, cfg=None, **kw):
    return harness.run_cell(cell, SEED, 0.2, False, time.perf_counter(),
                            cfg=cfg or smoke(cell), require_tpu=False, **kw)


@pytest.mark.parametrize("seed", [SEED, 0])
def test_traffic_is_the_seeds_alone(seed):
    ids = list(range(40))
    a = traffic.load_cases(seed, ids, 4, 25, 1.5)
    assert np.array_equal(a, traffic.load_cases(seed, ids, 4, 25, 1.5))
    assert not np.array_equal(a, traffic.load_cases(seed + 1, ids, 4, 25,
                                                    1.5))
    # a case is the same whichever others are drawn beside it
    assert np.array_equal(a[7], traffic.load_cases(seed, [7], 4, 25, 1.5)[0])
    # no case of a run repeats another
    assert len({a[k].tobytes() for k in ids}) == len(ids)


@pytest.mark.parametrize("path", CONFIGS, ids=lambda p: p.stem)
def test_traffic_copies_the_programs_random_load_cases(path):
    cfg = smoke(path)
    prob = harness.decompose(cfg)
    from reference import Layout

    lay = Layout(cfg)
    for case in (0, 5):
        got = traffic.load_cases(7, [case], lay.n_subdomains, lay.n_local,
                                 lay.base_load_scale(cfg["params"]))
        np.testing.assert_array_equal(
            got, prob.load_cases(1, "random", [7, case]))


@pytest.mark.parametrize("path", CONFIGS, ids=lambda p: p.stem)
def test_reference_agrees_with_the_programs_oracle(path):
    cfg = smoke(path)
    prob = harness.decompose(cfg)
    cases = prob.load_cases(3, "random", 11)
    got = Reference(cfg).solve(cases)
    for k in range(3):
        assert rel_err(got[k], prob.reference_solution(cases[k])) < 1e-12


@pytest.mark.parametrize("cell", CELLS)
def test_a_cpu_run_is_correct_and_reports_its_metrics(cell):
    out = run(cell)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] >= 1
    man = harness.manifest()
    want = {m["name"] for m in harness.cell_metrics(man, cell, "end_to_end")}
    # no device memory is read off the chip
    assert set(out["metrics"]) == want - {"peak_hbm_bytes"}
    assert list(out)[-1] == "checks"
    json.dumps(out)


def test_a_new_mix_is_data_alone(tmp_path):
    """A cell on a new mix needs only a mix file and manifest entries."""
    man = harness.manifest()
    man["workloads"].append({"name": "heat2d-replay", "config": "feti-heat-2d",
                             "traffic": "replay", "chips": 1, "why": "test"})
    for m in man["end_to_end"]:
        if "heat2d-stream" in m.get("workloads", []):
            m["workloads"].append("heat2d-replay")
    (tmp_path / "bench" / "traffic").mkdir(parents=True)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(man))
    (tmp_path / "bench" / "traffic" / "replay.json").write_text(json.dumps(
        {"cluster": "once", "load": "random"}))
    out = harness.run_cell("heat2d-replay", SEED, 0.2, False,
                           time.perf_counter(), root=tmp_path,
                           cfg=smoke("heat2d-stream"), require_tpu=False)
    assert out["correct"], out["checks"]
    assert out["attempted"] >= 1 and out["metrics"]["solve_s"]["value"]


def _elasticity_cell(tmp_path) -> Path:
    """A checkout whose manifest adds an assemble cell on the elasticity
    configuration: a configuration is a file and manifest entries."""
    man = harness.manifest()
    man["configs"].append({"name": "feti-elasticity-2d", "source": "test",
                           "file": str(ELASTICITY.relative_to(ROOT)),
                           "reduced": [], "why": "test"})
    man["workloads"].append({"name": "elast2d-assemble",
                             "config": "feti-elasticity-2d",
                             "traffic": "assemble", "chips": 1,
                             "why": "test"})
    for m in man["end_to_end"] + man["per_layer"]:
        if "heat2d-assemble" in m.get("workloads", []):
            m["workloads"].append("elast2d-assemble")
    (tmp_path / "bench").symlink_to(BENCH)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(man))
    return tmp_path


@pytest.mark.parametrize("kind", ["sound", "control"])
def test_an_elasticity_cell_is_data_alone(kind, tmp_path):
    """The f64 elasticity path reads correct; its control (f32, no
    refinement) does not."""
    cfg = smoke(ELASTICITY)
    over = cfg["control"]["feti"] if kind == "control" else None
    out = harness.run_cell("elast2d-assemble", SEED, 0.2, False,
                           time.perf_counter(), root=_elasticity_cell(tmp_path),
                           cfg=cfg, feti_overrides=over, require_tpu=False)
    assert out["correct"] == (kind == "sound"), out["checks"]
    assert out["metrics"]["solution_s"]["value"] > 0


def test_a_traced_cpu_run_reads_its_program_spans():
    out = harness.run_cell("heat2d-stream", SEED, 0.2, True,
                           time.perf_counter(), cfg=smoke("heat2d-stream"),
                           require_tpu=False)
    assert out["correct"]
    for name in ("pcpg_iterations.stream", "pcpg_iter_ms.stream",
                 "solve_host_s.stream"):
        assert out["metrics"][name]["value"] > 0
    assert "busy_s" in out["device"] and "breakdown" in out


@pytest.mark.parametrize("cell", CELLS)
def test_the_control_is_not_correct(cell):
    cfg = smoke(cell)
    out = run(cell, cfg, feti_overrides=cfg["control"]["feti"])
    assert not out["correct"], out["checks"]


def _alter_answer(monkeypatch):
    from repro.feti import solver as sollib

    orig = sollib.FetiSolver.solve

    def solve(self, *a, **kw):
        sol = orig(self, *a, **kw)
        sol.u_global = sol.u_global.copy()
        sol.u_global[len(sol.u_global) // 2] += 1e-6 * np.abs(
            sol.u_global).max()
        return sol

    monkeypatch.setattr(sollib.FetiSolver, "solve", solve)


def _state_unchanged(monkeypatch):
    """PCPG returns the state it was given, as if no step ran."""
    from repro.feti import solver as sollib

    orig = sollib.pcpg

    def pcpg(apply_F, project, d, lam0, *a, **kw):
        res = orig(apply_F, project, d, lam0, *a, **kw)
        return res.__class__(lam=lam0, iterations=res.iterations,
                             residual=res.residual, converged=res.converged,
                             residual_history=res.residual_history)

    monkeypatch.setattr(sollib, "pcpg", pcpg)


def _half_the_batch(monkeypatch):
    """The dual operator sums half of the subdomains' F̃ᵢ, scaled by two."""
    from repro.feti import operator as oplib

    orig = oplib.explicit_dual_apply

    def apply(F, lambda_ids, n_lambda, lam):
        keep = (np.arange(F.shape[0]) % 2 == 0).astype(F.dtype) * 2
        return orig(F * keep[:, None, None], lambda_ids, n_lambda, lam)

    monkeypatch.setattr(oplib, "explicit_dual_apply", apply)


@pytest.mark.parametrize("fault", [_alter_answer, _state_unchanged,
                                   _half_the_batch],
                         ids=["answer_altered", "state_unchanged",
                              "half_the_batch"])
@pytest.mark.parametrize("cell", CELLS)
def test_a_planted_fault_is_not_correct(cell, fault, monkeypatch):
    fault(monkeypatch)
    out = run(cell)
    assert not out["correct"], out["checks"]


def test_run_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, str(BENCH / "run.py"), "--workload",
                        CELLS[0], "--seed", str(SEED), "--seconds", "1",
                        "--trace", "0"], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


def test_run_refuses_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        CELLS[0], "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=tmp_path,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
