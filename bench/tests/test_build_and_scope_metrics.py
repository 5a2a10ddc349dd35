"""The readers of the program-build spans (``jit_*``) and of the PCPG
operators' named scopes (``*_device_ms.stream``): on hand-built runs and
reductions, on the scope paths the compiler writes into the solve
program's operations, and in traced CPU runs of both cells.

Run by path: ``JAX_PLATFORMS=cpu python -m pytest bench/tests``.
"""
import copy
import re
import sys
import time
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path[:0] = [str(BENCH), str(ROOT / "src")]

import harness  # noqa: E402
import tracereduce  # noqa: E402
import traffic  # noqa: E402

SEED = 2**31 + 4242
BUILD = ("jit_trace_s", "jit_load_s", "jit_programs")
SCOPES = {"dual_apply_device_ms.stream": "feti:dual_apply",
          "precond_device_ms.stream": "feti:precond",
          "project_device_ms.stream": "feti:project"}


def read(name, run):
    return harness.reader(name)(run)


def mix(cluster):
    return traffic.Mix(name=cluster, cluster=cluster, load="random")


def request(index, spans):
    return traffic.Request(index, 1.0, None, 10, True, spans)


def a_run(cluster, requests, trace=None):
    return harness.Run(cfg={}, mix=mix(cluster), setup_s=1.0, window_s=2.0,
                       requests=requests, peak_bytes=0, device_kind="cpu",
                       trace=trace)


# spans as a request keeps them: (name, t_start, t_end, depth)
CLUSTER_A = [("preprocess", 0.0, 10.0, 0), ("init", 0.0, 1.0, 1),
             ("jit:trace", 0.1, 0.4, 2), ("stage:dual", 2.0, 6.0, 2),
             ("jit:trace", 2.0, 3.0, 3), ("jit:lower", 3.0, 3.5, 3),
             ("jit:load", 3.5, 5.0, 3), ("solve", 10.0, 14.0, 0),
             ("jit:trace", 10.0, 10.5, 1), ("jit:compile", 10.5, 11.0, 1)]
CLUSTER_B = [("preprocess", 20.0, 25.0, 0), ("jit:trace", 20.0, 21.0, 1),
             ("jit:load", 21.0, 22.0, 1)]


def test_build_readers_sum_every_span_at_every_depth():
    run = a_run("per_request", [request(1, CLUSTER_A), request(2, CLUSTER_B)])
    # trace + lower: A 0.3 + 1.0 + 0.5 + 0.5, B 1.0; over two clusters
    assert read("jit_trace_s", run) == pytest.approx(3.3 / 2)
    # load + compile: A 1.5 + 0.5, B 1.0
    assert read("jit_load_s", run) == pytest.approx(3.0 / 2)
    assert read("jit_programs", run) == pytest.approx(4 / 2)
    # Run.span_total keeps the shallowest depth of a name alone
    assert run.span_total("jit:trace") == pytest.approx(0.5 + 1.0)


@pytest.mark.parametrize("name", BUILD)
def test_build_readers_read_nothing_where_nothing_is(name):
    bare = [("preprocess", 0.0, 1.0, 0), ("solve", 1.0, 2.0, 0)]
    assert read(name, a_run("per_request", [request(1, bare)])) is None
    assert read(name, a_run("per_request", [])) is None
    # a stream window builds no program and is not this metric's cell
    assert read(name, a_run("once", [request(1, CLUSTER_A)])) is None


def reduction(ops, requests=2):
    H = tracereduce.HloOp
    return tracereduce.Reduction(
        (0.0, 10.0), {0: [(0.0, 9.0)]},
        [H("fusion", path, t) for path, t in ops], host_spans=[],
        requests=requests)


def test_scope_readers_per_case_in_ms():
    ops = [("jit(<lambda>)/while/body/feti:dual_apply/dot_general", 0.2),
           ("jit(<lambda>)/while/body/feti:precond/dot_general", 0.1),
           ("jit(<lambda>)/while/body/feti:project/jit(solve_triangular)/"
            "triangular_solve", 0.3),
           ("jit(<lambda>)/feti:project/feti:projectx/mul", 0.05),
           ("jit(<lambda>)/feti:dual_apply/jit(_einsum)/dot_general", 0.4),
           ("jit(<lambda>)/while/body/add", 0.5)]
    run = a_run("once", [request(1, [])], reduction(ops))
    assert read("dual_apply_device_ms.stream", run) == pytest.approx(300.0)
    assert read("precond_device_ms.stream", run) == pytest.approx(50.0)
    assert read("project_device_ms.stream", run) == pytest.approx(175.0)


@pytest.mark.parametrize("name", SCOPES)
def test_scope_readers_read_nothing_where_nothing_is(name):
    other = reduction([("jit(prep)/stage:dual/dot_general", 1.0)])
    assert read(name, a_run("once", [request(1, [])], other)) is None
    assert read(name, a_run("once", [request(1, [])], None)) is None
    scoped = reduction([(f"jit(f)/{SCOPES[name]}/dot", 1.0)])
    assert read(name, a_run("per_request", [request(1, [])], scoped)) is None


def smoke(cell) -> dict:
    """The cell's configuration at the registry's smoke size."""
    man = harness.manifest()
    w = next(w for w in man["workloads"] if w["name"] == cell)
    conf = next(c for c in man["configs"] if c["name"] == w["config"])
    cfg = copy.deepcopy(harness.read_json(ROOT / conf["file"]))
    cfg["sub_grid"] = [2] * len(cfg["sub_grid"])
    cfg["elems_per_sub"] = [4] * len(cfg["elems_per_sub"])
    cfg["feti"]["schur"].update(block_size=8, rhs_block_size=8)
    return cfg


@pytest.fixture(autouse=True)
def _cache(tmp_path, monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))


def test_the_compiled_solve_program_carries_the_scopes():
    """Each scope reaches the operations of the compiled PCPG program (the
    scope path a device trace reports for them), and a reduction of those
    paths reads every scope metric, their sum within the whole."""
    harness.setup_jax(ROOT)
    cfg = smoke("heat2d-stream")
    from repro.feti import FetiSolver

    solver = FetiSolver(harness.decompose(cfg), harness.feti_config(cfg))
    solver.solve(tol=cfg["solve"]["tol"], max_iter=cfg["solve"]["max_iter"])
    run = solver._run(cfg["solve"]["tol"], cfg["solve"]["max_iter"])
    ops = solver._solution_ops()
    d = ops.dual_rhs_vec(solver.state.fp)
    hlo = run.func.lower(*run.args, d, ops.coarse.lambda0(),
                         0.0).compile().as_text()
    paths = re.findall(r'op_name="([^"]+)"', hlo)
    red = reduction([(p, 1e-3) for p in paths], requests=1)
    got = {n: read(n, a_run("once", [request(1, [])], red)) for n in SCOPES}
    assert all(v is not None and v > 0 for v in got.values()), got
    assert sum(got.values()) <= 1e3 * red.scope_time(
        {p for path in paths for p in path.split("/")})


def traced(cell, monkeypatch):
    """A traced CPU run of the cell at smoke size, and its requests."""
    seen = []
    measure = harness.measure

    def keep(*a, **kw):
        out = measure(*a, **kw)
        seen.extend(out[0])
        return out

    monkeypatch.setattr(harness, "measure", keep)
    out = harness.run_cell(cell, SEED, 0.2, True, time.perf_counter(),
                           cfg=smoke(cell), require_tpu=False)
    assert out["correct"], out["checks"]
    return out, seen


def test_a_traced_stream_window_builds_no_program(monkeypatch):
    out, requests = traced("heat2d-stream", monkeypatch)
    assert requests
    for q in requests:
        assert not [s for s in q.spans if s[0].startswith("jit:")], q.spans
    # a CPU trace holds no device operations: the scope metrics are left
    # out, not misread (the chip's trace reads them)
    assert not set(SCOPES) & set(out["metrics"])
    assert out["metrics"]["pcpg_iter_ms.stream"]["value"] > 0


def test_a_traced_assemble_run_reads_its_program_builds(monkeypatch):
    out, requests = traced("heat2d-assemble", monkeypatch)
    for name in BUILD:
        assert out["metrics"][name]["value"] > 0, name
    # every cluster traces its solver's own programs afresh
    assert out["metrics"]["jit_programs"]["value"] >= 3
    for q in requests:
        build = sum(e - s for n, s, e, _ in q.spans if n.startswith("jit:"))
        assert build <= q.latency_s
