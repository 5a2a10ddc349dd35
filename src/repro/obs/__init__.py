"""Structured telemetry for the FETI pipeline (spans, metrics, timing).

The observability layer the break-even story runs on: the paper's central
claim is an amortization argument, and arguing it honestly needs measured,
synchronized, attributable time — not an ad-hoc timings dict.

  * :mod:`repro.obs.trace` — nested span tracer (context-manager API,
    ``block_until_ready`` at span close, Chrome-trace/JSONL export,
    cross-module propagation via :func:`use_tracer`/:func:`current_tracer`,
    every span a profiler annotation, ``jit:*`` spans for program builds)
  * :mod:`repro.obs.metrics` — process-global counters/gauges (plan-cache
    hit/miss, PCPG iterations, tolerance clamps)
  * :mod:`repro.obs.timing` — THE synchronized timing helper shared by the
    autotuner's measured refinement and the benchmark harness
  * :mod:`repro.obs.validate` — schema validation for the exported
    artifacts (``python -m repro.obs.validate out.json``)

See docs/observability.md for the span model and the metrics reference.
"""
from __future__ import annotations

from repro.obs import metrics
from repro.obs.timing import min_time, time_call, timed_reps
from repro.obs.trace import (
    TRACE_SCHEMA_VERSION,
    Span,
    Tracer,
    current_tracer,
    use_tracer,
)

__all__ = [
    "TRACE_SCHEMA_VERSION",
    "Span",
    "Tracer",
    "Telemetry",
    "current_tracer",
    "use_tracer",
    "metrics",
    "timed_reps",
    "min_time",
    "time_call",
]


class Telemetry:
    """One solver's telemetry bundle: a span tracer plus the process-global
    metrics registry. Held by :class:`repro.feti.solver.FetiSolver` as
    ``solver.telemetry``; ``disable()`` turns the spans into no-ops (the
    near-zero-overhead path) without touching the counters."""

    def __init__(self, enabled: bool = True):
        self.tracer = Tracer(enabled=enabled)
        self.metrics = metrics.REGISTRY

    @property
    def enabled(self) -> bool:
        return self.tracer.enabled

    def enable(self) -> None:
        self.tracer.enabled = True

    def disable(self) -> None:
        self.tracer.enabled = False
