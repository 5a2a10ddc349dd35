"""Nested span tracer for the FETI pipeline (observability layer).

A :class:`Tracer` records a flat list of :class:`Span` records with
parent/depth links — the context-manager API keeps nesting implicit:

    tr = Tracer()
    with tr.span("solve"):
        with tr.span("pcpg", tol=1e-9) as sp:
            res = run(d, lam0)
            sp.sync(res.lam)          # block_until_ready at span close
            sp.set(iterations=int(res.iterations))

JAX dispatch is asynchronous, so a span that launches device work but
does not wait for it measures *dispatch*, not compute. ``sp.sync(x, ...)``
registers arrays (or pytrees) to ``jax.block_until_ready`` at span close,
so the recorded end time covers the device work the span claims.

Disabled tracers (``Tracer(enabled=False)``) hand out a shared no-op span
— no allocation, no clock reads, no sync — so instrumented code paths cost
nothing when telemetry is off.

Spans export as JSONL (one span per line, ``schema_version`` on every
record) and as the Chrome trace event format readable by
``chrome://tracing`` / Perfetto (:meth:`Tracer.to_chrome_trace`).

Cross-module propagation uses a tracer stack: the solver installs its
tracer with :func:`use_tracer` and downstream layers (assembly, the stage
graph, the autotuner) pick it up via :func:`current_tracer` — no tracer
installed means every downstream span is a no-op.

Every enabled span is also a ``jax.profiler.TraceAnnotation`` of its own
name, so a profiler trace shows the phases on the profiler's clock next to
the device's operations; inside jitted code use ``jax.named_scope``.

Program builds are recorded by the program itself: one ``jax.monitoring``
listener, registered at import, records a closed leaf span under the
innermost open span of the installed tracer for every

  * ``jit:trace`` — a top-level jaxpr trace (a trace nested inside another
    is part of the outer one's time and gets no span of its own),
  * ``jit:lower`` — a jaxpr's lowering to an MLIR module,
  * ``jit:load`` — a backend compile served by the persistent compilation
    cache,
  * ``jit:compile`` — a backend compile that ran the compiler,

each with the built function's name as ``fun``. With no enabled tracer
installed the listener returns after one check.
"""
from __future__ import annotations

import contextlib
import dataclasses
import json
import time
from typing import Any, Optional

import jax
from jax._src import core as jax_core  # trace_state_clean: no public form

__all__ = [
    "TRACE_SCHEMA_VERSION",
    "BUILD_PREFIX",
    "Span",
    "Tracer",
    "current_tracer",
    "use_tracer",
]

TRACE_SCHEMA_VERSION = 1
BUILD_PREFIX = "jit:"  # the program-build spans: jit:trace, jit:lower, ...


@dataclasses.dataclass
class Span:
    """One closed (or still-open) region of the timeline."""

    name: str
    t_start: float  # perf_counter seconds (absolute)
    t_end: Optional[float] = None
    depth: int = 0
    parent: Optional[int] = None  # index into Tracer.spans
    index: int = -1
    attrs: dict = dataclasses.field(default_factory=dict)

    @property
    def duration(self) -> float:
        """Seconds between open and close (0.0 while still open)."""
        return 0.0 if self.t_end is None else self.t_end - self.t_start


class _NullSpan:
    """The disabled path: a shared do-nothing span/context manager."""

    __slots__ = ()
    duration = 0.0
    attrs: dict = {}

    def sync(self, *values):
        return self

    def set(self, **attrs):
        return self

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL_SPAN = _NullSpan()


class _SpanHandle:
    """Context manager for one open span on one tracer."""

    __slots__ = ("_tracer", "span", "_sync", "_annotation")

    def __init__(self, tracer: "Tracer", span: Span):
        self._tracer = tracer
        self.span = span
        self._sync: list = []
        self._annotation = jax.profiler.TraceAnnotation(span.name)

    def sync(self, *values):
        """Register arrays/pytrees to ``jax.block_until_ready`` at close."""
        self._sync.extend(v for v in values if v is not None)
        return self

    def set(self, **attrs):
        self.span.attrs.update(attrs)
        return self

    @property
    def duration(self) -> float:
        return self.span.duration

    def __enter__(self):
        self._annotation.__enter__()
        return self

    def __exit__(self, *exc):
        if self._sync:
            jax.block_until_ready(self._sync)
        self._annotation.__exit__(*exc)
        self._tracer._close(self.span)
        return False


class Tracer:
    """Collects nested spans; near-zero overhead when ``enabled=False``."""

    def __init__(self, enabled: bool = True):
        self.enabled = enabled
        self.spans: list[Span] = []
        self._stack: list[int] = []
        self._epoch = time.perf_counter()

    # -- recording ---------------------------------------------------------

    def span(self, name: str, **attrs):
        """Open a nested span; use as a context manager."""
        if not self.enabled:
            return _NULL_SPAN
        sp = self._add(name, time.perf_counter(), None, attrs)
        self._stack.append(sp.index)
        return _SpanHandle(self, sp)

    def record(self, name: str, t_start: float, t_end: float,
               **attrs) -> Optional[Span]:
        """Record an already-closed leaf span under the innermost open
        span (the program-build listener's entry point)."""
        if not self.enabled:
            return None
        return self._add(name, t_start, t_end, attrs)

    def _add(self, name: str, t_start: float, t_end: Optional[float],
             attrs: dict) -> Span:
        sp = Span(
            name=name,
            t_start=t_start,
            t_end=t_end,
            depth=len(self._stack),
            parent=self._stack[-1] if self._stack else None,
            index=len(self.spans),
            attrs=attrs,
        )
        self.spans.append(sp)
        return sp

    def _close(self, span: Span) -> None:
        span.t_end = time.perf_counter()
        if self._stack and self._stack[-1] == span.index:
            self._stack.pop()
        elif span.index in self._stack:  # defensive: out-of-order close
            self._stack.remove(span.index)

    def clear(self) -> None:
        self.spans = []
        self._stack = []
        self._epoch = time.perf_counter()

    # -- queries -----------------------------------------------------------

    def last(self, name: str) -> Optional[Span]:
        """Most recent CLOSED span with this name, or None."""
        for sp in reversed(self.spans):
            if sp.name == name and sp.t_end is not None:
                return sp
        return None

    def last_duration(self, name: str) -> Optional[float]:
        sp = self.last(name)
        return None if sp is None else sp.duration

    def within(self, span: Span) -> list:
        """The spans nested, at any depth, inside ``span``."""
        inside = {span.index}
        out = []
        for sp in self.spans[span.index + 1:]:
            if sp.parent in inside:
                inside.add(sp.index)
                out.append(sp)
        return out

    def net_of_builds(self, span: Span) -> float:
        """``span``'s seconds less those of the program-build spans
        (``jit:*``) inside it: the time of the work it ran."""
        return span.duration - sum(
            sp.duration for sp in self.within(span)
            if sp.name.startswith(BUILD_PREFIX))

    def tree(self) -> list:
        """Nested view: list of root span dicts with ``children`` lists."""
        nodes = [
            {
                "name": sp.name,
                "t_start_s": sp.t_start - self._epoch,
                "duration_s": sp.duration,
                "attrs": dict(sp.attrs),
                "children": [],
            }
            for sp in self.spans
        ]
        roots: list = []
        for sp, node in zip(self.spans, nodes):
            if sp.parent is None:
                roots.append(node)
            else:
                nodes[sp.parent]["children"].append(node)
        return roots

    # -- export ------------------------------------------------------------

    def _records(self) -> list:
        return [
            {
                "schema_version": TRACE_SCHEMA_VERSION,
                "name": sp.name,
                "ts_us": (sp.t_start - self._epoch) * 1e6,
                "dur_us": sp.duration * 1e6,
                "depth": sp.depth,
                "parent": sp.parent,
                "attrs": _jsonable(sp.attrs),
            }
            for sp in self.spans
        ]

    def to_jsonl(self, path: str) -> None:
        """One span per line; every record carries ``schema_version``."""
        with open(path, "w") as f:
            for rec in self._records():
                f.write(json.dumps(rec) + "\n")

    def chrome_trace(self, metrics: Optional[dict] = None) -> dict:
        """The Chrome trace event object (see to_chrome_trace)."""
        events = [
            {
                "name": rec["name"],
                "ph": "X",  # complete event: ts + dur
                "ts": rec["ts_us"],
                "dur": rec["dur_us"],
                "pid": 1,
                "tid": 1,
                "args": rec["attrs"],
            }
            for rec in self._records()
        ]
        return {
            "schema_version": TRACE_SCHEMA_VERSION,
            "displayTimeUnit": "ms",
            "traceEvents": events,
            "metrics": _jsonable(metrics or {}),
        }

    def to_chrome_trace(self, path: str,
                        metrics: Optional[dict] = None) -> None:
        """Write a ``chrome://tracing`` / Perfetto-loadable JSON file.

        ``metrics`` (e.g. a :func:`repro.obs.metrics.snapshot`) is embedded
        under the top-level ``metrics`` key so one artifact carries the
        timeline AND the counters (plan-cache hits, PCPG iterations, ...).
        """
        with open(path, "w") as f:
            json.dump(self.chrome_trace(metrics), f, indent=1)


def _jsonable(obj: Any):
    """Best-effort conversion of span attrs to JSON-safe values."""
    if isinstance(obj, dict):
        return {str(k): _jsonable(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [_jsonable(v) for v in obj]
    if isinstance(obj, (str, int, float, bool)) or obj is None:
        return obj
    if hasattr(obj, "item") and getattr(obj, "ndim", None) == 0:
        return obj.item()  # numpy / jax scalars
    if hasattr(obj, "tolist"):
        return obj.tolist()
    return str(obj)


# -- cross-module tracer propagation ---------------------------------------

_DISABLED = Tracer(enabled=False)
_ACTIVE: list[Tracer] = []


def current_tracer() -> Tracer:
    """The innermost tracer installed by :func:`use_tracer` (a disabled
    tracer when none is installed — downstream spans become no-ops)."""
    return _ACTIVE[-1] if _ACTIVE else _DISABLED


@contextlib.contextmanager
def use_tracer(tracer: Tracer):
    """Install ``tracer`` as the current tracer for the dynamic extent."""
    _ACTIVE.append(tracer)
    try:
        yield tracer
    finally:
        _ACTIVE.pop()


# -- program builds, recorded by the program --------------------------------

_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
_BUILD_SPANS = {
    _TRACE_EVENT: "jit:trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jit:lower",
}
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_last_cache_hit = float("-inf")  # perf_counter of the latest cache hit


def _on_event(event: str, **_) -> None:
    global _last_cache_hit
    if event == _CACHE_HIT_EVENT:
        _last_cache_hit = time.perf_counter()


def _on_duration(event: str, secs: float, fun_name: str = "?",
                 **_) -> None:
    if not (_ACTIVE and _ACTIVE[-1].enabled):
        return
    if event == _COMPILE_EVENT:
        name = None  # jit:load or jit:compile, by the time of the last hit
    elif event in _BUILD_SPANS:
        # a trace nested in another trace is part of the outer one's time
        if event == _TRACE_EVENT and not jax_core.trace_state_clean():
            return
        name = _BUILD_SPANS[event]
    else:
        return
    t_end = time.perf_counter()
    t_start = t_end - secs
    if name is None:
        # a compile served by the persistent cache reports its hit while
        # it runs
        name = "jit:load" if _last_cache_hit >= t_start else "jit:compile"
    _ACTIVE[-1].record(name, t_start, t_end, fun=fun_name)


jax.monitoring.register_event_listener(_on_event)
jax.monitoring.register_event_duration_secs_listener(_on_duration)
