"""Programs are built once per structure, not once per solver: a new
cluster with the same sparsity, static configuration, shapes and dtypes
runs the prep and solve programs an earlier one built (no ``jit:*``
span), while every number (stacks, factors, F̃, coarse problem, loads) is
computed afresh; another structure builds its own programs, a dropped
solver leaves no stack alive, and a sweep over more structures and
tolerances than are kept holds no more programs than the bounds allow."""
import gc

import jax
import numpy as np
import pytest

from repro.core import SchurAssemblyConfig
from repro.fem import decompose_problem
from repro.feti import FetiConfig, FetiSolver
from repro.feti import programs as proglib
from repro.feti.programs import forget
from repro.obs import metrics
from repro.obs.trace import BUILD_PREFIX

DTYPES = ("f64", "f32")  # f32 stacks are refined to f64 by default
TOL = 1e-10


def _problem(kappa=1.0, elems=(3, 3)):
    return decompose_problem("heat", 2, (2, 2), elems, kappa=kappa)


def _solver(prob, dtype, block_size=8):
    return FetiSolver(prob, FetiConfig(
        schur=SchurAssemblyConfig(block_size=block_size,
                                  rhs_block_size=block_size),
        dtype=dtype))


def _loads(prob, seed, n=1):
    return prob.load_cases(n, kind="random", seed=seed)


def _builds(solver):
    return [(sp.name, sp.attrs["fun"]) for sp in solver.telemetry.tracer.spans
            if sp.name.startswith(BUILD_PREFIX)]


def _rel_err(u, ref):
    return float(np.max(np.abs(u - ref)) / np.max(np.abs(ref)))


@pytest.mark.parametrize("dtype", DTYPES)
def test_a_second_cluster_of_the_structure_builds_nothing(dtype):
    forget()
    metrics.reset()
    probs = (_problem(kappa=1.0), _problem(kappa=3.0))
    solvers = []
    for seed, prob in enumerate(probs):
        loads = _loads(prob, seed)[0]
        solver = _solver(prob, dtype)
        sol = solver.solve(tol=TOL, max_iter=500, loads=loads)
        assert _rel_err(sol.u_global, prob.reference_solution(loads)) <= 1e-8
        solvers.append(solver)
    first, second = solvers
    assert ("jit:trace", "prep") in _builds(first)
    assert _builds(second) == []
    assert second.telemetry.tracer.last("init").attrs == {
        "prep_program": "hit"}
    assert metrics.get("programs.prep_hit") == 1
    assert metrics.get("programs.prep_miss") == 1
    # the numbers are the second cluster's own (κ = 3, not 1)
    assert not np.allclose(np.asarray(first.state.F),
                           np.asarray(second.state.F))


@pytest.mark.parametrize("change", ["elems_per_sub", "block_size"])
def test_another_structure_builds_its_own_programs(change):
    forget()
    metrics.reset()
    _solver(_problem(), "f64").solve(tol=TOL, max_iter=500)
    prob = _problem(elems=(4, 4)) if change == "elems_per_sub" else _problem()
    other = _solver(prob, "f64", block_size=4 if change == "block_size"
                    else 8)
    sol = other.solve(tol=TOL, max_iter=500)
    assert _rel_err(sol.u_global, prob.reference_solution()) <= 1e-8
    assert ("jit:trace", "prep") in _builds(other)
    assert other.telemetry.tracer.last("init").attrs == {
        "prep_program": "miss"}
    assert metrics.get("programs.prep_miss") == 2
    assert metrics.get("programs.prep_hit") == 0


def _float_arrays_by_shape(shapes, arrays) -> dict:
    out = dict.fromkeys(shapes, 0)
    for x in arrays:
        if np.issubdtype(x.dtype, np.floating) and x.shape in out:
            out[x.shape] += 1
    return out


@pytest.mark.parametrize("dtype", DTYPES)
def test_a_dropped_solver_leaves_no_stack_alive(dtype):
    gc.collect()
    before = list(jax.live_arrays())
    first = _solver(_problem(), dtype)
    first.solve(tol=TOL, max_iter=500)
    st = first.state
    stacks = [st.L, st.F, st.Btp, st.K.values, st.f, st.fp, st.R,
              first._ops.coarse.G, first._ops.coarse.GtG_chol]
    if st.Kreg is not None:
        stacks.append(st.Kreg.values)
    shapes = {x.shape for x in stacks}
    base = _float_arrays_by_shape(shapes, before)
    del before, first, st, stacks
    gc.collect()
    # the next cluster of the structure still runs the kept programs ...
    second = _solver(_problem(kappa=2.0), dtype)
    second.solve(tol=TOL, max_iter=500)
    assert _builds(second) == []
    del second
    gc.collect()
    # ... and no array of either solver outlives it
    assert _float_arrays_by_shape(shapes, jax.live_arrays()) == base


@pytest.mark.parametrize("dtype", DTYPES)
def test_solve_many_on_a_second_cluster_builds_nothing(dtype):
    forget()
    solvers = []
    for seed, prob in enumerate((_problem(kappa=1.0), _problem(kappa=3.0))):
        cases = _loads(prob, 10 + seed, n=3)
        solver = _solver(prob, dtype)
        many = solver.solve_many(cases, tol=TOL, max_iter=500)
        for u, ref in zip(many.u_global, prob.reference_solutions(cases)):
            assert _rel_err(u, ref) <= 1e-8
        solvers.append(solver)
    first, second = solvers
    assert ("jit:trace", "pcpg_many_run") in _builds(first)
    assert _builds(second) == []


def _n_float_arrays() -> int:
    return sum(np.issubdtype(x.dtype, np.floating)
               for x in jax.live_arrays())


# more structures than the process keeps: sizes and a block size
SWEEP = [((2, 2), 8), ((3, 3), 8), ((4, 4), 8), ((5, 5), 8), ((6, 6), 8),
         ((3, 3), 4)]
TOLS = (1e-8, 1e-9, 1e-10, 1e-11)


def test_a_sweep_keeps_its_programs_and_arrays_bounded(monkeypatch):
    """Structure after structure, several tolerances each, nothing
    forgotten by hand: the process keeps the last KEPT structures, a
    forgotten one holds no compiled variant, a kept program is cleared
    once a solve finds it past VARIANTS, and no float array of a dropped
    solver stays alive."""
    monkeypatch.setattr(proglib, "VARIANTS", 2)
    forget()
    gc.collect()
    base = _n_float_arrays()
    met = []
    for elems, block_size in SWEEP:
        prob = _problem(elems=elems)
        solver = _solver(prob, "f64", block_size=block_size)
        for tol in TOLS:
            sol = solver.solve(tol=tol, max_iter=500)
        assert _rel_err(sol.u_global, prob.reference_solution()) <= 1e-8
        # one pcpg_run variant a tolerance: the fourth solve found three,
        # cleared them and built its own
        assert solver._ops.programs.pcpg_run._cache_size() == 1
        met.append(solver.state.programs)
        del solver, sol
    assert len(SWEEP) > proglib.KEPT
    assert proglib.kept() == met[-proglib.KEPT:]
    assert [entry.variants() for entry in met[:-proglib.KEPT]] == [0, 0]
    assert all(0 < entry.variants() for entry in proglib.kept())
    gc.collect()
    assert _n_float_arrays() == base
