"""Roofline analysis from compiled dry-run artifacts (deliverable g).

Three terms per (arch × shape × mesh) cell, from the SPMD-partitioned
module (which is per-device, so no further division by chip count):

    compute_s    = HLO_FLOPs_per_device    / peak_FLOPs      (197 TF bf16)
    memory_s     = HLO_bytes_per_device    / HBM_bw          (819 GB/s)
    collective_s = collective_bytes_per_device / link_bw     (~50 GB/s ICI;
                   'pod'-axis collectives ride DCI at ~25 GB/s)

HLO_FLOPs / HLO_bytes come from ``compiled.cost_analysis()``; collective
bytes are NOT in cost_analysis, so we parse the optimized HLO text and sum
result-tensor sizes of every all-gather / all-reduce / reduce-scatter /
all-to-all / collective-permute instruction.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Mapping, Optional

__all__ = ["HW", "CollectiveStats", "parse_collective_bytes",
           "roofline_terms", "Roofline", "DeviceModel", "DEVICE_MODELS",
           "detect_device"]

# TPU v5e hardware constants (per chip)
HW = {
    "peak_flops": 197e12,  # bf16
    "hbm_bw": 819e9,  # B/s
    "ici_bw": 50e9,  # B/s per link
    "dci_bw": 25e9,  # B/s cross-pod (approx; 'pod'-axis collectives)
    "hbm_bytes": 16 * 2**30,  # capacity, for fit checks
}


@dataclasses.dataclass(frozen=True)
class DeviceModel:
    """Per-device roofline constants for *ranking* kernel schedules.

    The autotuner (repro.core.autotune) feeds FLOP/byte models through
    :meth:`time_s` to order candidate assembly plans; absolute accuracy is
    irrelevant as long as relative costs rank correctly — measured refinement
    handles the rest. ``peak_flops``/``mem_bw`` are for the f64 regime the
    FETI substrate defaults to (NOT the bf16 LM numbers in ``HW``);
    ``peak_flops_by_dtype`` prices the reduced-precision regimes the
    mixed-precision stage graph plans over (ISSUE 9) — the paper's premise
    is exactly that the f32/f64 throughput ratio (2x on the A100's
    non-tensor-core units, ~16x with tf32 tensor cores) makes reduced
    precision worth the refinement machinery.

    Attributes:
      kind: jax platform string ("tpu" | "gpu" | "cpu").
      name: the ``device_kind`` it models (its key in DEVICE_MODELS).
      peak_flops: sustained f64 FLOP/s (the default dtype's regime).
      mem_bw: main-memory bandwidth, B/s.
      overhead_s: per-dispatched-op launch/dispatch overhead. This is the
        term that penalizes tiny block sizes (many small ops) and rewards
        fused/pallas single-launch schedules.
      peak_flops_by_dtype: sustained FLOP/s per stage dtype name ("f64" |
        "f32" | "bf16"); missing entries fall back to ``peak_flops``.
    """

    kind: str
    name: str
    peak_flops: float
    mem_bw: float
    overhead_s: float = 5e-6
    peak_flops_by_dtype: Optional[Mapping[str, float]] = None

    def peak(self, dtype: str = "f64") -> float:
        """Sustained FLOP/s for a stage dtype name; unknown names fall
        back to the f64 figure (conservative: never makes an unmodeled
        dtype look faster than the default regime)."""
        if self.peak_flops_by_dtype is None:
            return self.peak_flops
        return self.peak_flops_by_dtype.get(dtype, self.peak_flops)

    def time_s(self, flops: float, bytes_: float, n_ops: int = 1,
               dtype: str = "f64") -> float:
        """Roofline execution-time estimate: max(compute, memory) + launches."""
        return max(flops / self.peak(dtype), bytes_ / self.mem_bw) \
            + n_ops * self.overhead_s


def _freeze(d: dict) -> Mapping[str, float]:
    # dataclass is frozen; keep the mapping immutable too so models are
    # safely shareable across planner calls
    import types

    return types.MappingProxyType(dict(d))


# One model per device kind, keyed by ``jax.Device.device_kind``.
DEVICE_MODELS = {
    # TPU v5e (Google Cloud docs, "TPU v5e": 197 TFLOP/s bf16, 819 GB/s
    # HBM). f32 products run at Precision.HIGHEST (repro.core.precision),
    # which XLA:TPU executes as 6 bf16 MXU passes: 197/6 TFLOP/s. f64 has
    # no hardware path and is emulated in software; 1 TFLOP/s is an
    # unmeasured placeholder that ranks it far below f32.
    "TPU v5 lite": DeviceModel("tpu", "TPU v5 lite", peak_flops=1.0e12,
                               mem_bw=HW["hbm_bw"], overhead_s=2e-6,
                               peak_flops_by_dtype=_freeze(
                                   {"f64": 1.0e12,
                                    "f32": HW["peak_flops"] / 6,
                                    "bf16": HW["peak_flops"]})),
    # A100-class card (the paper's hardware; NVIDIA A100 datasheet): f64
    # non-tensor-core peak, f32 FMA peak (2x), bf16 tensor cores (16x).
    "NVIDIA A100-SXM4-80GB": DeviceModel(
        "gpu", "NVIDIA A100-SXM4-80GB", peak_flops=9.7e12, mem_bw=1.55e12,
        overhead_s=5e-6, peak_flops_by_dtype=_freeze(
            {"f64": 9.7e12, "f32": 19.5e12, "bf16": 156e12})),
    # container-grade CPU; XLA:CPU per-op dispatch is comparatively heavy.
    # f32 SIMD lanes are 2x f64; bf16 has no fast path (== f32 compute).
    "cpu": DeviceModel("cpu", "cpu", peak_flops=5.0e10,
                       mem_bw=2.0e10, overhead_s=10e-6,
                       peak_flops_by_dtype=_freeze(
                           {"f64": 5.0e10, "f32": 1.0e11,
                            "bf16": 1.0e11})),
}


def detect_device(kind: Optional[str] = None) -> DeviceModel:
    """The :class:`DeviceModel` of a ``device_kind`` (default: that of
    jax's first device). A device kind with no model is an error: ranking
    plans with another device's numbers would hide the device."""
    if kind is None:
        import jax  # local: roofline stays importable without a backend

        kind = jax.devices()[0].device_kind
    if kind not in DEVICE_MODELS:
        raise ValueError(
            f"no device model for device_kind {kind!r}; known: "
            f"{sorted(DEVICE_MODELS)} (add one to "
            "repro.launch.roofline.DEVICE_MODELS)")
    return DEVICE_MODELS[kind]

_DTYPE_BYTES = {
    "f64": 8, "s64": 8, "u64": 8, "c64": 8,
    "f32": 4, "s32": 4, "u32": 4,
    "bf16": 2, "f16": 2, "s16": 2, "u16": 2,
    "f8e4m3fn": 1, "f8e5m2": 1, "s8": 1, "u8": 1, "pred": 1,
}

_COLL_OPS = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
             "collective-permute")

# one tensor literal: dtype[d0,d1,...]{layout}   (layout optional)
_TENSOR_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_LINE_RE = re.compile(
    r"=\s*(\([^)]*\)|\w+\[[\d,]*\](?:\{[^}]*\})?)\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?\("
)


@dataclasses.dataclass
class CollectiveStats:
    bytes_by_op: dict
    count_by_op: dict

    @property
    def total_bytes(self) -> int:
        return sum(self.bytes_by_op.values())

    @property
    def total_count(self) -> int:
        return sum(self.count_by_op.values())


def _shape_bytes(tensors: str) -> int:
    total = 0
    for dtype, dims in _TENSOR_RE.findall(tensors):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def parse_collective_bytes(hlo_text: str) -> CollectiveStats:
    """Sum result-tensor bytes of every collective in the optimized HLO,
    counting each loop body ONCE (the raw structural schedule).

    ``-start``/``-done`` async pairs are counted once (on the start op —
    done ops repeat the shape and are skipped by the dedup below).
    """
    bytes_by_op = {op: 0 for op in _COLL_OPS}
    count_by_op = {op: 0 for op in _COLL_OPS}
    for line in hlo_text.splitlines():
        if "-done(" in line:
            continue  # async completion: payload counted at -start
        m = _LINE_RE.search(line)
        if not m:
            continue
        tensors, op = m.group(1), m.group(2)
        bytes_by_op[op] += _shape_bytes(tensors)
        count_by_op[op] += 1
    return CollectiveStats(bytes_by_op=bytes_by_op, count_by_op=count_by_op)


# ------------------------- trip-count-corrected collective accounting ------
# greedy param match: computation params nest tuples, e.g.
#   %body.1 (arg: (s32[], f32[64,64])) -> (s32[], f32[64,64]) {
_COMP_HEADER_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w\.\-]+)\s*\(.*\)\s*->")
_WHILE_RE = re.compile(
    r"while\(.*?\)(?:,|\s).*?condition=%?([\w\.\-]+),\s*body=%?([\w\.\-]+)"
)
_CONST_RE = re.compile(r"constant\((\d+)\)")


def _split_computations(hlo_text: str):
    comps: dict[str, list[str]] = {}
    entry = None
    current = None
    for line in hlo_text.splitlines():
        if line and not line[0].isspace():
            m = _COMP_HEADER_RE.match(line.strip())
            if m and "{" in line:
                current = m.group(1)
                comps[current] = []
                if line.strip().startswith("ENTRY"):
                    entry = current
                continue
        if current is not None:
            if line.strip() == "}":
                current = None
            else:
                comps[current].append(line)
    return comps, entry


def _trip_count(cond_lines: list[str]) -> int:
    """Trip count heuristic: scan loops compare an induction var against a
    constant bound; take the max integer constant in the condition."""
    best = 1
    for line in cond_lines:
        for c in _CONST_RE.findall(line):
            best = max(best, int(c))
    return best


def collective_stats_trip_corrected(hlo_text: str) -> CollectiveStats:
    """Like :func:`parse_collective_bytes`, but multiplies collectives
    inside while-loop bodies by the loop trip count (recursively) — XLA's
    own cost/byte counters count loop bodies once, which undercounts
    scanned programs by orders of magnitude."""
    comps, entry = _split_computations(hlo_text)

    def direct(comp_lines):
        b = {op: 0 for op in _COLL_OPS}
        c = {op: 0 for op in _COLL_OPS}
        whiles = []
        for line in comp_lines:
            w = _WHILE_RE.search(line)
            if w:
                whiles.append((w.group(1), w.group(2)))
                continue
            if "-done(" in line:
                continue
            m = _LINE_RE.search(line)
            if m:
                b[m.group(2)] += _shape_bytes(m.group(1))
                c[m.group(2)] += 1
        return b, c, whiles

    memo: dict[str, tuple] = {}

    def total(name: str) -> tuple:
        if name in memo:
            return memo[name]
        lines = comps.get(name, [])
        b, c, whiles = direct(lines)
        memo[name] = (b, c)  # break cycles defensively
        for cond, body in whiles:
            trips = _trip_count(comps.get(cond, []))
            bb, bc = total(body)
            for op in _COLL_OPS:
                b[op] += trips * bb[op]
                c[op] += trips * bc[op]
        memo[name] = (b, c)
        return b, c

    if entry is None:  # defensive: fall back to the flat count
        return parse_collective_bytes(hlo_text)
    b, c = total(entry)
    return CollectiveStats(bytes_by_op=dict(b), count_by_op=dict(c))


@dataclasses.dataclass
class Roofline:
    flops_per_dev: float
    bytes_per_dev: float
    coll_bytes_per_dev: float
    compute_s: float
    memory_s: float
    collective_s: float
    dominant: str
    model_flops: Optional[float] = None
    useful_ratio: Optional[float] = None  # MODEL_FLOPS / (HLO_FLOPs * chips)

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)


def roofline_terms(cost: dict, coll: CollectiveStats, chips: int,
                   model_flops: Optional[float] = None,
                   link_bw: float = HW["ici_bw"]) -> Roofline:
    flops = float(cost.get("flops", 0.0))
    bytes_ = float(cost.get("bytes accessed", 0.0))
    cb = float(coll.total_bytes)
    compute_s = flops / HW["peak_flops"]
    memory_s = bytes_ / HW["hbm_bw"]
    collective_s = cb / link_bw
    terms = {"compute": compute_s, "memory": memory_s,
             "collective": collective_s}
    dominant = max(terms, key=terms.get)
    useful = None
    if model_flops:
        useful = model_flops / max(flops * chips, 1.0)
    return Roofline(
        flops_per_dev=flops,
        bytes_per_dev=bytes_,
        coll_bytes_per_dev=cb,
        compute_s=compute_s,
        memory_s=memory_s,
        collective_s=collective_s,
        dominant=dominant,
        model_flops=model_flops,
        useful_ratio=useful,
    )
