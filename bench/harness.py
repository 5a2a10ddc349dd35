"""One run of one benchmark cell: set-up, the measured window, the
correctness check and the metrics, all found by name from the manifest.

Everything that belongs to one configuration, traffic mix or metric lives
in a file of its own, found by the name ``BENCHMARK.json`` gives it:

  * ``bench/configs/<config>.json``: the deployment and its FetiConfig;
  * ``bench/traffic/<mix>.json``: the mix's parameters (see traffic.py);
  * ``bench/metrics/<metric>.py``: a reader ``read(run)`` that returns the
    metric's value, or None where the run holds nothing to read.

From the program the harness takes only its public entry points
(``decompose_problem``, ``FetiConfig``, ``FetiSolver``), the spans of each
solver's tracer and the kernel scope names in the device trace.
"""
from __future__ import annotations

import dataclasses
import functools
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path
from typing import Optional

import numpy as np

import traffic as trafficlib
from reference import Layout, Reference, rel_err

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


class NoDevice(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def manifest(root: Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def read_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def cell_metrics(man: dict, cell: str, kind: str) -> list:
    """The ``end_to_end`` or ``per_layer`` entries a cell reports: those
    that list it, and those without a ``workloads`` key."""
    return [m for m in man[kind]
            if cell in m.get("workloads", [cell])]


@functools.lru_cache(maxsize=None)
def reader(name: str):
    """``read`` of ``bench/metrics/<name>.py``."""
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def feti_config(cfg: dict, overrides: Optional[dict] = None):
    """The FetiConfig a configuration file states, with ``overrides``
    (the control's fields) applied on top."""
    from repro.core import SchurAssemblyConfig
    from repro.feti import FetiConfig

    kw = dict(cfg["feti"])
    kw.update(overrides or {})
    if isinstance(kw.get("schur"), dict):
        kw["schur"] = SchurAssemblyConfig(**kw["schur"])
    return FetiConfig(**kw)


def decompose(cfg: dict):
    from repro.fem import decompose_problem

    params = dict(cfg["params"])
    if "body_force" in params:
        params["body_force"] = tuple(params["body_force"])
    return decompose_problem(cfg["problem"], len(cfg["sub_grid"]),
                             tuple(cfg["sub_grid"]),
                             tuple(cfg["elems_per_sub"]), **params)


def device_check(chips: int):
    """The devices of the run: a TPU with at least ``chips`` chips."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoDevice(f"needs a TPU, JAX found {devices[0].platform!r} "
                       "devices")
    if len(devices) < chips:
        raise NoDevice(f"the cell asks for {chips} chips, JAX found "
                       f"{len(devices)}")
    return devices


def setup_jax(root: Path) -> str:
    """x64 (the solver's f64 paths need it) and the persistent compilation
    cache: ``JAX_COMPILATION_CACHE_DIR`` where set, else the checkout's
    fixed ``.jax_cache``. Every program is cached, however short its
    compile: each new FetiSolver traces its programs afresh, and the
    window must find them all in the cache."""
    import jax

    jax.config.update("jax_enable_x64", True)
    cache = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return cache


class CompileCounter:
    """While on, counts XLA compilations (persistent-cache misses) and the
    programs loaded from the cache, with the seconds spent loading."""

    def __init__(self):
        import jax

        self.on = False
        self.compiles = self.loads = 0
        self.load_s = 0.0
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._dur)

    def _event(self, event: str, **_):
        if self.on and event == "/jax/compilation_cache/cache_misses":
            self.compiles += 1
        elif self.on and event == "/jax/compilation_cache/cache_hits":
            self.loads += 1

    def _dur(self, event: str, secs: float, **_):
        if self.on and event == "/jax/compilation_cache/cache_retrieval_time_sec":
            self.load_s += secs


@dataclasses.dataclass
class Run:
    """What the metric readers read."""

    cfg: dict
    mix: trafficlib.Mix
    setup_s: float
    window_s: float
    requests: list
    peak_bytes: int
    device_kind: str
    trace: object = None  # tracereduce.Reduction in a --trace 1 run

    @property
    def cases(self) -> int:
        """Load cases solved in the window: one a request."""
        return len(self.requests)

    def span_total(self, *names) -> float:
        """Seconds the window's requests spent in the program's spans of
        these names (nested spans of one name are not double counted:
        only the outermost of each name is summed)."""
        total = 0.0
        for r in self.requests:
            for name in names:
                depth = min((s[3] for s in r.spans if s[0] == name),
                            default=None)
                total += sum(s[2] - s[1] for s in r.spans
                             if s[0] == name and s[3] == depth)
        return total

    @functools.cached_property
    def peaks(self) -> dict:
        table = read_json(BENCH / "peaks.json")["devices"]
        if self.device_kind not in table:
            raise KeyError(f"no peaks for device kind {self.device_kind!r} "
                           "in bench/peaks.json")
        return table[self.device_kind]

    @property
    def storage_itemsize(self) -> int:
        """Bytes of one stored value (FetiConfig's dtype, f64 unset)."""
        return {"f64": 8, "f32": 4, "bf16": 2}[
            self.cfg["feti"].get("dtype", "f64")]


def _note(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def run_cell(cell_name: str, seed: int, seconds: float, trace: bool,
             t_start: float, root: Path = ROOT, cfg: Optional[dict] = None,
             feti_overrides: Optional[dict] = None,
             require_tpu: bool = True) -> dict:
    """Set up, measure ``seconds``, check and reduce one run; returns the
    result line. ``cfg`` replaces the cell's configuration file (smaller
    sizes in tests); ``feti_overrides`` change its FetiConfig fields (the
    control); ``require_tpu=False`` skips the device check (tests)."""
    man = manifest(root)
    cell = next(w for w in man["workloads"] if w["name"] == cell_name)
    conf = next(c for c in man["configs"] if c["name"] == cell["config"])
    cfg = cfg or read_json(root / conf["file"])
    mix = trafficlib.Mix.from_dict(
        cell["traffic"],
        read_json(root / "bench" / "traffic" / f"{cell['traffic']}.json"))

    import jax

    devices = device_check(cell["chips"]) if require_tpu else jax.devices()
    setup_jax(root)
    from repro.feti import FetiSolver

    counter = CompileCounter()
    problem = decompose(cfg)
    layout = Layout(cfg)
    draw = functools.partial(
        trafficlib.load_cases, seed, n_subdomains=layout.n_subdomains,
        n_local=layout.n_local, scale=layout.base_load_scale(cfg["params"]))
    config = feti_config(cfg, feti_overrides)
    driver = trafficlib.Driver(
        mix, lambda: FetiSolver(problem, config), draw,
        dict(tol=cfg["solve"]["tol"], max_iter=cfg["solve"]["max_iter"]))
    warm = driver.request(0)
    setup_s = time.perf_counter() - t_start
    _note(f"[setup] {setup_s:.3f} s, warm-up request {warm.latency_s:.3f} s")

    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    counter.on = True
    requests, window_s, t_traced = measure(driver, seconds, trace_dir)
    counter.on = False
    _note(f"[window] {window_s:.3f} s, {len(requests)} requests; "
          f"{counter.compiles} compiles, {counter.loads} programs loaded "
          f"from the compilation cache in {counter.load_s:.3f} s")
    peak = max(int(d.memory_stats()["peak_bytes_in_use"]) for d in devices
               ) if require_tpu else 0
    driver.close()
    del driver, problem

    run = Run(cfg=cfg, mix=mix, setup_s=setup_s,
              window_s=window_s, requests=requests, peak_bytes=peak,
              device_kind=devices[0].device_kind)
    if trace:
        import tracereduce

        t_read = time.perf_counter()
        run.trace = tracereduce.reduce(trace_dir, t_traced, requests[:1])
        shutil.rmtree(trace_dir, ignore_errors=True)
        _note(f"[trace] read in {time.perf_counter() - t_read:.3f} s")

    checks = check(run, cfg, draw)
    kind = "per_layer" if trace else "end_to_end"
    metrics = {}
    for m in cell_metrics(man, cell_name, kind):
        value = reader(m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": peak}
    out = {"correct": all(c["value"] <= c["limit"] for c in checks.values()),
           "attempted": run.cases,
           "failed": sum(not q.converged for q in requests),
           "metrics": metrics, "device": device}
    if trace:
        device.update(busy_s=run.trace.busy_s, window_s=run.trace.window_s)
        out["breakdown"] = run.trace.breakdown()
    for name, c in checks.items():
        _note(f"[check] {name} = {c['value']!r} (limit {c['limit']!r})")
    out["checks"] = checks
    return out


def start_trace(trace_dir: str) -> None:
    """The profiler, without its Python tracer: every Python call of a
    request would swamp the trace."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(trace_dir, profiler_options=opts)


def measure(driver, seconds: float, trace_dir: Optional[str]):
    """The window: requests sent one after another while fewer than
    ``seconds`` have passed since it opened. With ``trace_dir``, the
    profiler records the first request alone, inside a ``bench.window``
    annotation: a whole step of the assemble mix, one load case of the
    stream mix. (A full-size step runs millions of device operations, and
    the profiler takes a minute or more to collect them.) Returns the
    requests, the window's length, and the perf_counter time at which the
    annotation opened, or None."""
    import jax

    requests, t_traced = [], None
    t0 = time.perf_counter()
    if trace_dir:
        start_trace(trace_dir)
        with jax.profiler.TraceAnnotation("bench.window"):
            t_traced = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.request"):
                requests.append(driver.request(1))
        jax.profiler.stop_trace()
    while time.perf_counter() - t0 < seconds:
        with jax.profiler.TraceAnnotation("bench.request"):
            requests.append(driver.request(len(requests) + 1))
    return requests, time.perf_counter() - t0, t_traced


def check(run: Run, cfg: dict, draw) -> dict:
    """Every answer of the window against the reference: the largest
    relative error of any, beside the configuration's limit. ``draw``
    gives the load cases of the ids the window offered."""
    t0 = time.perf_counter()
    ref = Reference(cfg)
    u_ref = ref.solve(draw([q.index for q in run.requests]))
    worst = 0.0
    for q, want in zip(run.requests, u_ref):
        # a non-finite answer reads as the largest float: JSON has no inf
        e = (rel_err(q.u_global, want) if np.all(np.isfinite(q.u_global))
             else float(np.finfo(float).max))
        worst = max(worst, e)
    _note(f"[check] {run.cases} answers against the reference in "
          f"{time.perf_counter() - t0:.3f} s")
    return {"rel_err": {"value": worst, "limit": cfg["accuracy"]["rel_err"]}}
