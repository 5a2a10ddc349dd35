"""The one traffic generator: load cases drawn from the seed, and the
closed loop that offers them to the solver.

A mix (``bench/traffic/<mix>.json``) sets:

  * ``cluster``: ``"per_request"`` builds and preprocesses a new cluster
    for every request, ``"once"`` preprocesses one cluster in set-up and
    streams every request against it;
  * ``load``: ``"random"``, i.i.d. standard normal per-DOF loads scaled to
    the base body load's largest entry (the arithmetic of the program's
    ``FetiProblem.load_cases(kind="random")``).

Request r solves load case r with ``FetiSolver.solve``. The case is drawn
when it is offered, from its own generator ``default_rng([seed, r])``, so
no case of a run repeats one that the process has already solved, and
the reference solves exactly the cases that were offered.

The loop is closed with one caller: a request is sent when the previous
one has returned. Requests start while the window is open; the one that is
running when it closes runs to its end, and the window's time includes it.
"""
from __future__ import annotations

import dataclasses
import gc
import time
from typing import Callable

import numpy as np

CLUSTERS = ("per_request", "once")


@dataclasses.dataclass(frozen=True)
class Mix:
    name: str
    cluster: str
    load: str

    @classmethod
    def from_dict(cls, name: str, d: dict) -> "Mix":
        mix = cls(name=name, cluster=d["cluster"], load=d["load"])
        if mix.cluster not in CLUSTERS:
            raise ValueError(f"mix {name}: cluster must be one of "
                             f"{CLUSTERS}, got {mix.cluster!r}")
        if mix.load != "random":
            raise ValueError(f"mix {name}: unknown load {mix.load!r}")
        return mix


def load_cases(seed: int, ids, n_subdomains: int, n_local: int,
               scale: float) -> np.ndarray:
    """(len(ids), S, n) load cases: case c from ``default_rng([seed, c])``;
    the same seed and ids give the same cases."""
    return np.stack([
        np.random.default_rng([seed % 2**64, int(c)]).standard_normal(
            (n_subdomains, n_local)) * scale for c in ids])


@dataclasses.dataclass
class Request:
    """One answered request of the window."""

    index: int  # the request's number, and its load case's
    latency_s: float
    u_global: np.ndarray  # (n_dofs,)
    iterations: int
    converged: bool
    spans: list  # (name, t_start, t_end, depth) of the program's tracer


def _spans(solver) -> list:
    return [(s.name, s.t_start, s.t_end, s.depth)
            for s in solver.telemetry.tracer.spans if s.t_end is not None]


class Driver:
    """Offers a mix's requests to the solver built by ``make_solver``."""

    def __init__(self, mix: Mix, make_solver: Callable, draw: Callable,
                 solve_kw: dict):
        self.mix = mix
        self.make_solver = make_solver
        self.draw = draw  # case ids -> (cases, S, n) loads
        self.solve_kw = solve_kw
        self.solver = None
        if mix.cluster == "once":
            self.solver = make_solver()
            self.solver.preprocess()

    def request(self, r: int) -> Request:
        loads = self.draw([r])[0]
        t0 = time.perf_counter()
        solver = self.solver
        if solver is None:
            solver = self.make_solver()
            solver.preprocess()
        else:
            solver.telemetry.tracer.clear()
        sol = solver.solve(loads=loads, **self.solve_kw)
        u, iters, conv = sol.u_global, int(sol.iterations), bool(sol.converged)
        spans = _spans(solver)
        if self.solver is None:
            # one cluster's memory at a time: the next request's cluster
            # is built only once this one is gone
            del solver, sol
            gc.collect()
        latency = time.perf_counter() - t0
        return Request(r, latency, u, iters, conv, spans)

    def close(self) -> None:
        self.solver = None
        gc.collect()
