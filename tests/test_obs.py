"""Telemetry tier: the span tracer (nesting, monotonicity,
disabled-path overhead, profiler annotations), the program-build spans
(``jit:*``), the PCPG operators' named scopes, the metrics registry, the
trace/JSONL exports and their schema validator, plan-cache hit/miss
counters, the PCPG residual history (trim semantics + bit-identity of
``lam`` with history off), the recover-span sync regression, and the exact
tolerance-clamp counter."""
import json
import re
import sys
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import SchurAssemblyConfig
from repro.core.precision import dtype_name
from repro.fem import decompose_problem
from repro.feti import FetiConfig, FetiSolver, preprocess_cluster
from repro.feti.pcpg import (
    TolClampState,
    _clamp_tol,
    reset_tol_clamp_warnings,
)
from repro.feti.programs import forget
from repro.obs import Telemetry, Tracer, metrics
from repro.obs.trace import (
    _NULL_SPAN,
    BUILD_PREFIX,
    current_tracer,
    use_tracer,
)
from repro.obs.validate import (
    main as validate_main,
    validate_chrome_trace,
    validate_jsonl,
)

pytestmark = pytest.mark.obs


@pytest.fixture(scope="module")
def prob():
    return decompose_problem("heat", 2, (2, 2), (3, 3))


def _solver(prob):
    return FetiSolver(prob, FetiConfig(
        schur=SchurAssemblyConfig(block_size=8, rhs_block_size=8)))


@pytest.fixture(scope="module")
def first_solve(prob):
    """The first solver of its structure in the process (the programs
    built so far forgotten) after its first solve, with that solve's wall
    time (preprocessing included)."""
    forget()
    solver = _solver(prob)
    t0 = time.perf_counter()
    solver.solve(tol=1e-8, max_iter=200)
    return solver, time.perf_counter() - t0


def _builds(spans):
    return [sp for sp in spans if sp.name.startswith(BUILD_PREFIX)]


# ----------------------------------------------------- span tracer ----

def test_span_nesting_and_monotonicity():
    tr = Tracer()
    with tr.span("outer"):
        time.sleep(0.002)
        with tr.span("inner", k=1) as sp_i:
            time.sleep(0.002)
            sp_i.set(extra=2)
        with tr.span("inner2"):
            pass
    outer, inner, inner2 = tr.spans
    assert [s.name for s in tr.spans] == ["outer", "inner", "inner2"]
    assert (outer.depth, inner.depth, inner2.depth) == (0, 1, 1)
    assert inner.parent == outer.index and inner2.parent == outer.index
    assert outer.parent is None
    # monotone timeline: children open after the parent, close before it
    assert outer.t_start <= inner.t_start <= inner.t_end <= outer.t_end
    assert inner.t_end <= inner2.t_start <= inner2.t_end <= outer.t_end
    assert outer.duration >= inner.duration + inner2.duration
    assert inner.attrs == {"k": 1, "extra": 2}
    # queries
    assert tr.last("inner") is inner
    assert tr.last_duration("nope") is None
    (root,) = tr.tree()
    assert root["name"] == "outer"
    assert [c["name"] for c in root["children"]] == ["inner", "inner2"]


def test_disabled_tracer_is_noop_and_cheap():
    tr = Tracer(enabled=False)
    sp = tr.span("anything", k=1)
    assert sp is _NULL_SPAN
    with sp as s:
        s.sync(object()).set(a=1)
    assert tr.spans == [] and sp.duration == 0.0
    # overhead smoke: the disabled path must stay allocation/clock free —
    # 100k no-op spans in well under a second even on a loaded CI box
    t0 = time.perf_counter()
    for _ in range(100_000):
        with tr.span("x"):
            pass
    assert time.perf_counter() - t0 < 1.0


def test_tracer_propagation_stack():
    assert not current_tracer().enabled  # no tracer installed -> disabled
    tr = Tracer()
    with use_tracer(tr):
        assert current_tracer() is tr
        with tr.span("downstream"):
            pass
    assert not current_tracer().enabled
    assert tr.spans[0].name == "downstream"


def test_enabled_span_is_a_profiler_annotation(monkeypatch):
    events = []

    class Annotation:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            events.append(("enter", self.name))

        def __exit__(self, *exc):
            events.append(("exit", self.name))

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Annotation)
    tr = Tracer()
    with tr.span("outer"):
        with tr.span("inner") as sp:
            sp.sync(jnp.ones(2))
    assert events == [("enter", "outer"), ("enter", "inner"),
                      ("exit", "inner"), ("exit", "outer")]
    events.clear()
    off = Tracer(enabled=False)
    with off.span("outer"):
        pass
    assert events == []


def test_telemetry_enable_disable():
    tel = Telemetry()
    assert tel.enabled
    tel.disable()
    assert tel.tracer.span("x") is _NULL_SPAN
    tel.enable()
    with tel.tracer.span("y"):
        pass
    assert tel.tracer.last("y") is not None


# ------------------------------------------------ program builds ----

def test_first_solve_records_program_builds_and_the_second_none(
        first_solve):
    solver, wall = first_solve
    tr = solver.telemetry.tracer
    builds = _builds(tr.spans)
    names = {sp.name for sp in builds}
    assert "jit:trace" in names and names & {"jit:compile", "jit:load"}
    for sp in builds:
        assert sp.attrs["fun"]
        # a leaf inside a phase span
        assert sp.parent is not None
        assert not tr.spans[sp.parent].name.startswith(BUILD_PREFIX)
    # the solver's own programs are traced inside preprocess and solve,
    # under their names
    assert {tr.spans[sp.parent].name for sp in builds} >= {
        "stage:dual", "pcpg"}
    assert {sp.attrs["fun"] for sp in builds} >= {"prep", "pcpg_run"}
    assert sum(sp.duration for sp in builds) <= wall
    # a second solver of the structure builds nothing: it runs the
    # programs the first one built
    second = _solver(solver.problem)
    second.solve(tol=1e-8, max_iter=200)
    assert _builds(second.telemetry.tracer.spans) == []
    assert second.telemetry.tracer.last("init").attrs == {
        "prep_program": "hit"}
    # the steady state rebuilds no program
    n = len(tr.spans)
    solver.solve(tol=1e-8, max_iter=200)
    assert [sp.name for sp in tr.spans[n:]] == [
        "solve", "rhs_setup", "pcpg", "recover"]


def test_a_nested_trace_is_part_of_the_outer_one():
    def inner(x):
        return x * 2.0 + 1.0

    inner_jit = jax.jit(inner)

    def outer(x):
        return inner_jit(x) - 3.0

    x = jnp.arange(3.0)
    tr = Tracer()
    with use_tracer(tr), tr.span("request") as req:
        jax.jit(outer)(x).block_until_ready()
    traces = [sp for sp in tr.spans if sp.name == "jit:trace"]
    assert [sp.attrs["fun"] for sp in traces] == ["outer"]
    assert sum(sp.duration for sp in _builds(tr.spans)) <= req.duration


def test_a_compile_is_a_load_when_the_cache_served_it():
    tr = Tracer()
    event = "/jax/core/compile/backend_compile_duration"
    with use_tracer(tr), tr.span("request"):
        jax.monitoring.record_event_duration_secs(event, 0.01,
                                                  fun_name="f")
        jax.monitoring.record_event("/jax/compilation_cache/cache_hits")
        jax.monitoring.record_event_duration_secs(event, 0.01,
                                                  fun_name="g")
    with use_tracer(Tracer(enabled=False)):
        jax.monitoring.record_event_duration_secs(event, 0.01,
                                                  fun_name="h")
    assert [(sp.name, sp.attrs["fun"]) for sp in _builds(tr.spans)] == [
        ("jit:compile", "f"), ("jit:load", "g")]


# ------------------------------------------------- operator scopes ----

def _strip_metadata(hlo: str) -> str:
    """Compiled HLO text without its metadata and source-frame tables."""
    return re.sub(r", metadata=\{[^}]*\}", "", hlo[hlo.index("\n%"):])


def _runner_lowered(solver):
    run = solver._run(1e-8, 200)
    ops = solver._solution_ops()
    d = ops.dual_rhs_vec(solver.state.fp)
    return run.func.lower(*run.args, d, ops.coarse.lambda0(), 0.0)


def test_pcpg_runner_names_its_operator_scopes(first_solve, prob,
                                               monkeypatch):
    solver, _ = first_solve
    lowered = _runner_lowered(solver)
    text = lowered.as_text(debug_info=True)
    for scope in ("feti:dual_apply", "feti:precond", "feti:project"):
        assert scope in text
    # metadata only: without the scopes the compiled program is the same
    scoped_hlo = _strip_metadata(lowered.compile().as_text())
    monkeypatch.setattr(sys.modules["repro.feti.pcpg"], "scoped",
                        lambda name, fn: fn)
    # the structure's programs are kept: trace them again unscoped, and
    # forget the unscoped ones after
    forget()
    try:
        plain = _solver(prob)
        plain.preprocess()
        plain_lowered = _runner_lowered(plain)
        assert "feti:project" not in plain_lowered.as_text(debug_info=True)
        assert (_strip_metadata(plain_lowered.compile().as_text())
                == scoped_hlo)
    finally:
        forget()


# ------------------------------------------------ exports + schema ----

def _sample_tracer():
    tr = Tracer()
    with tr.span("solve"):
        with tr.span("pcpg", iterations=3):
            pass
    return tr


def test_jsonl_export_validates(tmp_path):
    tr = _sample_tracer()
    path = tmp_path / "spans.jsonl"
    tr.to_jsonl(str(path))
    assert validate_jsonl(str(path)) == []
    recs = [json.loads(ln) for ln in path.read_text().splitlines()]
    assert [r["name"] for r in recs] == ["solve", "pcpg"]
    assert all(r["schema_version"] == 1 for r in recs)
    assert recs[1]["parent"] == 0 and recs[1]["depth"] == 1


def test_chrome_trace_schema(tmp_path):
    tr = _sample_tracer()
    path = tmp_path / "trace.json"
    tr.to_chrome_trace(str(path), metrics={"counters": {"a": 1}})
    assert validate_chrome_trace(str(path)) == []
    doc = json.loads(path.read_text())
    assert doc["schema_version"] == 1
    assert doc["metrics"] == {"counters": {"a": 1}}
    evs = doc["traceEvents"]
    assert [e["name"] for e in evs] == ["solve", "pcpg"]
    assert all(e["ph"] == "X" and e["dur"] >= 0 for e in evs)
    # nesting is encoded by containment: parent ts <= child ts and the
    # child ends no later than the parent
    assert evs[0]["ts"] <= evs[1]["ts"]
    assert evs[1]["ts"] + evs[1]["dur"] <= evs[0]["ts"] + evs[0]["dur"]


def test_validator_rejects_corrupt_artifacts(tmp_path):
    bad_trace = tmp_path / "bad.json"
    bad_trace.write_text(json.dumps(
        {"traceEvents": [{"name": "x", "ph": "B", "ts": -1}]}))
    errors = validate_chrome_trace(str(bad_trace))
    assert any("schema_version" in e for e in errors)
    assert any("metrics" in e for e in errors)
    assert any("missing" in e for e in errors)

    bad_jsonl = tmp_path / "bad.jsonl"
    bad_jsonl.write_text('{"ok": 1}\nnot json\n')
    errors = validate_jsonl(str(bad_jsonl))
    assert any("schema_version" in e for e in errors)
    assert any("invalid JSON" in e for e in errors)

    good = tmp_path / "good.jsonl"
    good.write_text('{"schema_version": 1}\n')
    assert validate_main([str(good)]) == 0
    assert validate_main([str(bad_jsonl)]) == 1
    assert validate_main([]) == 2


def test_committed_artifacts_validate():
    """The committed result artifacts carry schema_version (CI runs the
    same validator against them)."""
    from pathlib import Path

    results = Path(__file__).resolve().parents[1] / "results"
    assert validate_jsonl(str(results / "dryrun.jsonl")) == []
    conv = results / "convergence_feti.jsonl"
    assert validate_jsonl(str(conv)) == []
    recs = [json.loads(ln) for ln in
            open(conv).read().splitlines() if ln.strip()]
    assert {r["preconditioner"] for r in recs} == {"lumped", "dirichlet"}
    for r in recs:
        assert len(r["residual_history"]) == r["iterations"]


# -------------------------------------------------- metrics registry ----

def test_registry_counters_gauges_labels():
    reg = metrics.Registry()
    reg.inc("hits", stage="dual", dtype="f64")
    reg.inc("hits", 2, dtype="f64", stage="dual")  # label order-insensitive
    reg.inc("hits", stage="dirichlet", dtype="f64")
    reg.gauge("bytes", 100, stack="L")
    reg.gauge("bytes", 200, stack="L")  # last value wins
    assert reg.get("hits", stage="dual", dtype="f64") == 3
    assert reg.get("hits", stage="dirichlet", dtype="f64") == 1
    assert reg.get("hits") == 0  # unlabeled key is distinct
    assert reg.get("bytes", stack="L") == 200
    assert reg.get_matching("hits") == {
        "hits{dtype=f64,stage=dual}": 3,
        "hits{dtype=f64,stage=dirichlet}": 1,
    }
    snap = reg.snapshot()
    assert snap["counters"]["hits{dtype=f64,stage=dual}"] == 3
    assert snap["gauges"]["bytes{stack=L}"] == 200
    reg.reset()
    assert reg.snapshot() == {"counters": {}, "gauges": {}}


def test_module_level_helpers_hit_default_registry():
    metrics.reset()
    metrics.inc("t.c", 5)
    metrics.gauge("t.g", 7, k="v")
    assert metrics.get("t.c") == 5
    assert metrics.snapshot()["gauges"]["t.g{k=v}"] == 7
    assert metrics.get_matching("t.") == {"t.c": 5}
    metrics.reset()
    assert metrics.get("t.c") == 0


# ----------------------------------------------- plan-cache counters ----

def test_plan_cache_cold_miss_then_warm_hit(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_PLAN_CACHE_DIR", str(tmp_path))
    prob = decompose_problem("heat", 2, (2, 2), (3, 3))
    fc = FetiConfig(schur="auto", measure="never")
    metrics.reset()
    preprocess_cluster(prob, fc)
    assert sum(metrics.get_matching("plan_cache.graph.miss").values()) == 1
    assert sum(metrics.get_matching("plan_cache.graph.hit").values()) == 0
    # the cold miss ran the per-stage planner (its own hit-or-miss counter)
    assert sum(metrics.get_matching("plan_cache.stage.").values()) >= 1
    preprocess_cluster(prob, fc)
    assert sum(metrics.get_matching("plan_cache.graph.miss").values()) == 1
    assert sum(metrics.get_matching("plan_cache.graph.hit").values()) == 1


# --------------------------------------------------- residual history ----

def test_residual_history_single_rhs(prob):
    solver = _solver(prob)
    sol_h = solver.solve(tol=1e-10, max_iter=200, history=True)
    sol = solver.solve(tol=1e-10, max_iter=200, history=False)
    assert sol.residual_history is None
    h = sol_h.residual_history
    assert h is not None and h.shape == (sol_h.iterations,)
    # trimmed exactly: the last recorded ||P r|| IS the final residual
    assert h[-1] == sol_h.residual
    assert np.all(h > 0)
    # the history buffer is write-only w.r.t. the CG recurrence: lam is
    # BIT-identical with history on and off
    assert np.array_equal(sol_h.lam, sol.lam)
    assert sol_h.iterations == sol.iterations


@pytest.mark.multirhs
def test_residual_history_many_rhs(prob):
    solver = _solver(prob)
    loads = prob.load_cases(3, kind="random", seed=0)
    sm_h = solver.solve_many(loads, tol=1e-10, max_iter=200, history=True)
    sm = solver.solve_many(loads, tol=1e-10, max_iter=200, history=False)
    assert sm.residual_history is None
    H = sm_h.residual_history
    assert H is not None and H.shape == (3, sm_h.block_iterations)
    # frozen columns repeat their converged value: column j's curve ends
    # at its own final residual at iterations[j] - 1
    for j in range(3):
        it = int(sm_h.iterations[j])
        assert H[j, it - 1] == sm_h.residuals[j]
        if it < sm_h.block_iterations:  # frozen tail repeats
            assert np.all(H[j, it - 1:] == sm_h.residuals[j])
    assert np.array_equal(sm_h.lam, sm.lam)
    # 1-column batches dispatch through solve(): history rides along
    sm1 = solver.solve_many(loads[:1], tol=1e-10, max_iter=200,
                            history=True)
    assert sm1.residual_history.shape == (1, int(sm1.iterations[0]))


# ------------------------------------------- recover-span regression ----

def test_recover_span_dominates_trivial_solve(prob):
    """Regression for the dispatch-only ``timings["recover_s"]`` bug: the
    recovery phase launches triangular solves asynchronously, so without
    an explicit sync the timer recorded dispatch (microseconds) and the
    device work leaked into the next phase. On a trivially-converged
    solve (loose tol -> PCPG exits almost immediately) the recovery IS
    the bulk of the device work, so its span must carry real time."""
    solver = _solver(prob)
    solver.solve(tol=0.5, max_iter=50)  # warm: compile everything
    tr = solver.telemetry.tracer
    tr.clear()
    sol = solver.solve(tol=0.5, max_iter=50)
    assert sol.iterations <= 5
    rec = tr.last("recover")
    pcpg_sp = tr.last("pcpg")
    assert rec is not None and pcpg_sp is not None
    assert rec.duration > 0
    assert sol.timings["recover_s"] > 0
    # recover must be a material fraction of the measured solve phases,
    # not a dispatch-only blip
    total = (sol.timings["rhs_setup_s"] + sol.timings["solve_s"]
             + sol.timings["recover_s"])
    assert sol.timings["recover_s"] >= 0.05 * total
    # the nested spans and the deprecated flat view agree on what they
    # both measure
    assert rec.duration == pytest.approx(sol.timings["recover_s"],
                                         rel=0.5, abs=5e-3)


# -------------------------------------------------- tolerance clamp ----

def test_tol_clamp_counter_exact_and_resettable():
    metrics.reset()
    reset_tol_clamp_warnings()
    state = TolClampState()
    name = dtype_name(jnp.float32)
    with pytest.warns(RuntimeWarning, match="clamping"):
        _clamp_tol(1e-12, jnp.float32, state)
    # warn-once per dtype per state; the counter counts EVERY engagement
    import warnings as _warnings

    with _warnings.catch_warnings():
        _warnings.simplefilter("error")
        _clamp_tol(1e-12, jnp.float32, state)
        _clamp_tol(1e-13, jnp.float32, state)
        # f64 above the floor: no clamp, no counter, no warning
        assert _clamp_tol(1e-9, jnp.float64, state) == 1e-9
    assert metrics.get("pcpg.tol_clamp", dtype=name) == 3
    assert metrics.get_matching("pcpg.tol_clamp") == {
        f"pcpg.tol_clamp{{dtype={name}}}": 3.0}
    # reset rearms the warning without touching the exact counter
    state.reset()
    with pytest.warns(RuntimeWarning, match="clamping"):
        _clamp_tol(1e-12, jnp.float32, state)
    assert metrics.get("pcpg.tol_clamp", dtype=name) == 4
    # the global call-site states are independently rearmed
    reset_tol_clamp_warnings()
    from repro.feti.pcpg import _CLAMP_STATE_PCPG

    with pytest.warns(RuntimeWarning):
        _clamp_tol(1e-12, jnp.float32, _CLAMP_STATE_PCPG)
    metrics.reset()


# ------------------------------------------------------ report() ----

def test_solver_report_structure(prob):
    metrics.reset()
    solver = _solver(prob)
    sol = solver.solve(tol=1e-8, max_iter=200, history=True)
    rep = solver.report()
    assert rep["schema_version"] == 1
    names = {s["name"] for s in rep["spans"]}
    assert {"preprocess", "solve"} <= names
    solve_span = [s for s in rep["spans"] if s["name"] == "solve"][-1]
    child_names = [c["name"] for c in solve_span["children"]
                   if not c["name"].startswith(BUILD_PREFIX)]
    assert child_names[:3] == ["solution_ops", "rhs_setup", "pcpg"]
    assert child_names[-1] == "recover"
    hist = solve_span["attrs"]["residual_history"]
    assert len(hist) == sol.iterations
    assert rep["metrics"]["counters"]["pcpg.solves"] >= 1
    assert rep["metrics"]["counters"]["pcpg.iterations"] >= sol.iterations
    # the persistent stacks' device bytes, per stack
    by = rep["device_bytes"]
    for stack in ("L", "K", "Btp", "F"):
        assert by[stack] > 0
    assert by["total"] >= by["L"] + by["K"] + by["Btp"] + by["F"]
    # deprecated flat view still present
    assert set(rep["timings"]) >= {"preprocess_s", "solve_s", "recover_s"}
    # the report round-trips through the chrome-trace exporter
    assert "traceEvents" in solver.telemetry.tracer.chrome_trace(
        rep["metrics"])


# ------------------------------------------------ amortization ----

def test_amortization_assembly_time_is_net_of_program_builds(first_solve):
    solver, _ = first_solve
    tr = solver.telemetry.tracer
    dual = tr.last("stage:dual")
    builds = [sp for sp in tr.within(dual)
              if sp.name.startswith(BUILD_PREFIX)]
    assert builds  # the prep program was built inside the span
    rep = solver.amortization_report(t_implicit_iter_s=0.15,
                                     t_explicit_iter_s=0.05)
    assert rep["measured_from"]["assembly_s"] == "span:stage:dual - jit:*"
    assert rep["assembly_s"] == pytest.approx(
        dual.duration - sum(sp.duration for sp in builds))
    assert 0 <= rep["assembly_s"] < dual.duration
