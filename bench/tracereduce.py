"""Reduction of a profiler trace to the benchmark's device numbers.

From the ``.xplane.pb`` that ``jax.profiler`` writes:

  * busy time: the union of the intervals of the events on each TPU
    plane's ``XLA Ops`` line (read with JAX's ``ProfileData``) inside the
    ``bench.window`` annotation, averaged over the chips that ran any; the
    idle time is the rest of the window;
  * the device time of each HLO operation with the named-scope path of the
    program op it came from (xprof's ``hlo_stats`` table: self time, so a
    loop's body is not counted twice), for the time under given scopes and
    the table of the operations that took most time;
  * the idle gaps, each put down to the innermost host span it falls in:
    the program's own spans (``repro.obs`` tracer, perf_counter clock) and
    the harness's ``bench.*`` annotations, aligned to the trace's clock by
    the ``bench.window`` annotation.

The traced stretch is all the trace holds of the device: tracing starts
just before the annotation opens and stops just after it closes, with no
device work between.
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import json
import os
import tempfile
from typing import Iterable

import numpy as np

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
WINDOW = "bench.window"


def union_length(intervals: Iterable) -> float:
    """Total length covered by (start, end) intervals."""
    total, end = 0.0, -np.inf
    for s, e in sorted(intervals):
        if e <= end:
            continue
        total += e - max(s, end)
        end = e
    return total


def gaps(intervals: list, lo: float, hi: float) -> list:
    """The (start, end) stretches of [lo, hi] no interval covers."""
    out, cur = [], lo
    for s, e in sorted(intervals):
        if s > cur:
            out.append((cur, min(s, hi)))
        cur = max(cur, e)
        if cur >= hi:
            break
    if cur < hi:
        out.append((cur, hi))
    return [(s, e) for s, e in out if e > s]


@dataclasses.dataclass
class HloOp:
    category: str  # xprof's category ("custom-call", "loop fusion", ...)
    scope: str  # named-scope path, "jit(prep)/factorize/jit(cholesky)/..."
    self_s: float  # device seconds, summed over the trace and its chips


@dataclasses.dataclass
class Reduction:
    window: tuple  # (start, end) on the trace's clock, seconds
    busy: dict  # chip -> busy intervals inside the window
    hlo_ops: list  # HloOp
    host_spans: list  # (name, start, end, depth) on the trace's clock
    requests: int  # requests inside the traced stretch

    @property
    def chips(self) -> int:
        return len(self.busy)

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    @property
    def busy_s(self) -> float:
        if not self.busy:
            return 0.0
        return sum(map(union_length, self.busy.values())) / self.chips

    def scope_time(self, include=(), exclude=()) -> float:
        """Device seconds, averaged over chips, of the operations whose
        scope path has a component in ``include`` and none in
        ``exclude`` (components matched whole)."""
        inc, exc = set(include), set(exclude)
        t = sum(op.self_s for op in self.hlo_ops
                if (parts := set(op.scope.split("/"))) & inc
                and not parts & exc)
        return t / max(self.chips, 1)

    def breakdown(self, top: int = 10) -> dict:
        by_op = collections.Counter()
        for op in self.hlo_ops:
            by_op[f"{op.category}: {op.scope}"[:160]] += (
                op.self_s / max(self.chips, 1))
        idle = collections.Counter()
        if self.busy:
            for s, e in gaps(self.busy[min(self.busy)], *self.window):
                idle[self._host_at((s + e) / 2)] += e - s
        return {"device_ops": [[k, v] for k, v in by_op.most_common(top)],
                "idle_gaps": [[k, v] for k, v in idle.most_common(top)]}

    def _host_at(self, t: float) -> str:
        inner = None
        for name, s, e, depth in self.host_spans:
            if s <= t < e and (inner is None or depth > inner[1]):
                inner = (name, depth)
        return inner[0] if inner else "outside any span"


def hlo_ops(path: str) -> list:
    """Per-HLO-operation device self time with its scope path, from
    xprof's ``hlo_stats`` tool."""
    from xprof.convert import _pywrap_profiler_plugin as xprof

    # xprof keeps what it computes in a file beside the trace it reads and
    # reuses it later: read through a link in a directory of its own
    with tempfile.TemporaryDirectory() as tmp:
        link = os.path.join(tmp, os.path.basename(path))
        os.symlink(os.path.abspath(path), link)
        data, ok = xprof.xspace_to_tools_data(
            [link], "hlo_stats", {"use_saved_result": False})
    if not ok:
        raise RuntimeError(f"xprof could not read {path}")
    table = json.loads(data)
    cols = [c["id"] for c in table["cols"]]
    out = []
    for row in table["rows"]:
        r = dict(zip(cols, (c.get("v") for c in row["c"])))
        out.append(HloOp(r["category"], r["tf_op_name"] or "",
                         r["total_self_time"] * 1e-6))  # us -> s
    return out


def reduce(trace_dir: str, t0: float, requests: list) -> Reduction:
    """Reduce the trace in ``trace_dir``: the traced stretch of the window
    is the ``bench.window`` annotation, which opened at ``t0`` on the
    host's perf_counter clock; ``requests`` are the requests inside it,
    with the program's spans."""
    from jax.profiler import ProfileData

    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise RuntimeError(f"expected one trace file, found {files}")
    pd = ProfileData.from_file(files[0])
    host, busy, win = [], {}, None
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            chip = int(plane.name[len(DEVICE_PREFIX):])
            for line in plane.lines:
                if line.name == OPS_LINE:
                    busy[chip] = [(ev.start_ns * 1e-9,
                                   (ev.start_ns + ev.duration_ns) * 1e-9)
                                  for ev in line.events]
        elif plane.name.startswith("/host"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("bench."):
                        s = ev.start_ns * 1e-9
                        host.append((ev.name, s, s + ev.duration_ns * 1e-9))
                        if ev.name == WINDOW:
                            win = host[-1][1:]
    if win is None:
        raise RuntimeError(f"no {WINDOW} annotation in the trace")
    lo, hi = win
    busy = {c: [(max(s, lo), min(e, hi)) for s, e in iv if e > lo and s < hi]
            for c, iv in busy.items()}
    busy = {c: iv for c, iv in busy.items() if iv}
    shift = lo - t0  # perf_counter seconds -> trace seconds
    spans = [(n, s, e, 0 if n == WINDOW else 1) for n, s, e in host]
    for q in requests:
        spans += [(n, s + shift, e + shift, 2 + d) for n, s, e, d in q.spans]
    ops = hlo_ops(files[0]) if busy else []
    return Reduction(window=win, busy=busy, hlo_ops=ops, host_spans=spans,
                     requests=len(requests))
