"""Assembly autotuner: pick the SC-assembly plan the paper picks by hand.

The paper's central empirical result (Table 1, Figs. 5-6) is that the best
TRSM/SYRK splitting variant AND the best block size depend on the input
sparsity pattern — the authors choose them per machine and per mesh. This
module turns that manual choice into a planner:

  1. **Enumerate** the full ``SchurAssemblyConfig`` design space: 3 TRSM
     variants x 3 SYRK variants x candidate block sizes x pruning on/off x
     Pallas kernels on/off (structural duplicates are canonicalized away —
     e.g. ``prune`` only distinguishes ``factor_split`` TRSM).
  2. **Score** every candidate with the existing FLOP model
     (:func:`repro.core.schur.assembly_flops`) plus a byte-traffic and
     launch-count model (below), fed through the roofline cost model of
     :mod:`repro.launch.roofline` (``DeviceModel.time_s``).
  3. Optionally **measure** the top-k candidates (plus the dense baseline)
     with real timed micro-runs on synthetic data carrying the exact
     sparsity pattern (``measure="auto"``), and pick the fastest.
  4. **Cache** the winning plan in a content-addressed on-disk cache keyed
     by a fingerprint of the sparsity pattern + device kind, so multi-step
     simulations and repeat launches pay the search once.

See docs/autotuning.md for the cost model derivation, the cache-key
contents, and how to pin a plan for reproducibility.
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
import os
from typing import Callable, Optional, Sequence, Tuple

import numpy as np

from repro.core.schur import (
    SYRK_VARIANTS,
    TRSM_VARIANTS,
    SchurAssemblyConfig,
    assembly_flops,
    make_assembler,
    schur_dense_baseline,
)
from repro.core.precision import compute_dtype, itemsize
from repro.core.stepped import SteppedMeta, build_stepped_meta
from repro.launch.roofline import DeviceModel, detect_device

__all__ = [
    "Plan",
    "plan_assembly",
    "plan_from_builder",
    "enumerate_space",
    "assembly_cost",
    "assembly_bytes",
    "pattern_fingerprint",
    "default_block_sizes",
    "offered_on",
    "pallas_vmem_bytes",
    "plan_cache_dir",
    "clear_plan_cache",
]

# Bump when the candidate space or the cost model changes shape: stale
# cached plans from an older search must not be served for the new one.
# v2: packed factor storage joined the space (storage= on every config).
# v3: the assembly stage joined the cache key ("dual" | "dirichlet" —
#     the primal boundary Schur stage of the Dirichlet preconditioner is
#     planned and cached independently of the dual-operator stage).
# v4: the fused TRSM→SYRK megakernel joined the space (fused= on every
#     config), and multi-stage graphs are planned JOINTLY under one cache
#     key over all stages (repro.core.stages) instead of per-stage entries.
# v5: the stage dtype joined the cost model and the cache key (per-dtype
#     peak FLOPs in DeviceModel, itemsize-scaled byte model, dtype-cast
#     measurement probes) — an f32 plan must never be served to an f64
#     stage or vice versa.
# v6: device models are keyed by device_kind (which joined the key), and
#     on TPU Pallas candidates are offered only for f32/bf16 stages whose
#     kernels fit VMEM (offered_on).
SPACE_VERSION = 6

# Pallas kernels only run natively on TPU; elsewhere they fall back to
# interpret mode, which is orders of magnitude slower. The model multiplies
# pallas-candidate times by this on non-TPU devices so they are enumerated
# (full design space) but never win off-TPU.
_INTERPRET_PENALTY = 200.0

_F64 = 8  # assembly dtype bytes (the FETI substrate runs f64)


# --------------------------------------------------------------------------
# byte-traffic + launch-count model (complements SteppedMeta's FLOP model)
# --------------------------------------------------------------------------

def _packed_blocks(meta: SteppedMeta,
                   block_mask: Optional[np.ndarray]) -> int:
    """Stored factor blocks under packed storage: the fill mask's nnz, or
    the full lower triangle when no symbolic mask is available."""
    nb = meta.num_row_blocks
    if block_mask is None:
        return nb * (nb + 1) // 2
    return int(np.tril(np.asarray(block_mask)).sum())


def _trsm_bytes_ops(meta: SteppedMeta, cfg: SchurAssemblyConfig,
                    block_mask: Optional[np.ndarray], db: int
                    ) -> Tuple[float, int]:
    n, m = meta.n, meta.m
    packed = cfg.storage == "packed"
    if cfg.use_pallas and cfg.trsm_variant != "dense":
        # single fused launch; streams the factor (packed: only the stored
        # blocks + the SMEM block index), Linv and B/Y once
        bs = meta.block_size
        n_pad = meta.num_row_blocks * bs
        m_pad = meta.num_col_blocks * meta.rhs_block_size
        if packed:
            factor = _packed_blocks(meta, block_mask) * bs * bs
        else:
            factor = n_pad * n_pad / 2
        return db * (factor + n_pad * bs + 2 * n_pad * m_pad), 1
    if cfg.trsm_variant == "dense":
        extra = 0.0
        if packed:
            # transient densify of the packed factor before the library TRSM
            extra = _packed_blocks(meta, block_mask) * meta.block_size ** 2 \
                + n * n / 2
        return db * (n * n / 2 + 2 * n * m + extra), 1 + int(packed)
    if cfg.trsm_variant == "rhs_split":
        total, ops = 0.0, 0
        if packed:  # transient densify before the per-stripe solves
            total += db * (_packed_blocks(meta, block_mask)
                           * meta.block_size ** 2 + n * n / 2)
            ops += 1
        for c in range(meta.num_col_blocks):
            c0, c1 = meta.col_block(c)
            s = int(meta.col_starts[c])
            if s >= n:
                continue
            nn = n - s
            total += db * (nn * nn / 2 + 2 * nn * (c1 - c0))
            ops += 1
        return total, ops
    # factor_split: packed storage prunes structurally (absent blocks are
    # never addressed), so it always takes the masked accounting
    total, ops = 0.0, 0
    nb = meta.num_row_blocks
    mask = np.asarray(block_mask) \
        if ((cfg.prune or packed) and block_mask is not None) else None
    for k in range(nb):
        r0, r1 = meta.row_block(k)
        b = r1 - r0
        w = int(meta.widths[k])
        if w == 0:
            continue
        total += db * (b * b / 2 + 2 * b * w)  # diagonal TRSM
        ops += 1
        if r1 >= n:
            continue
        if mask is None:
            total += db * ((n - r1) * b + 2 * (n - r1) * w)
            ops += 1
        else:
            for i in range(k + 1, nb):
                if not mask[i, k]:
                    continue
                i0, i1 = meta.row_block(i)
                total += db * ((i1 - i0) * b + 2 * (i1 - i0) * w)
                ops += 1
    return total, ops


def _syrk_bytes_ops(meta: SteppedMeta, cfg: SchurAssemblyConfig,
                    db: int) -> Tuple[float, int]:
    n, m = meta.n, meta.m
    if cfg.use_pallas and cfg.syrk_variant != "dense":
        n_pad = meta.num_row_blocks * meta.block_size
        m_pad = meta.num_col_blocks * meta.rhs_block_size
        return db * (n_pad * m_pad + m_pad * m_pad), 1
    if cfg.syrk_variant == "dense":
        return db * (n * m + m * m), 1
    if cfg.syrk_variant == "input_split":
        total, ops = 0.0, 0
        for k in range(meta.num_row_blocks):
            r0, r1 = meta.row_block(k)
            w = int(meta.widths[k])
            if w == 0:
                continue
            # read the row block + read-modify-write the w x w accumulator:
            # this term is what penalizes small blocks for input_split
            total += db * ((r1 - r0) * w + 2 * w * w)
            ops += 1
        return total, ops
    # output_split
    total, ops = 0.0, 0
    for i in range(meta.num_col_blocks):
        i0, i1 = meta.col_block(i)
        s = int(meta.col_starts[i])
        if s >= n:
            continue
        ci = i1 - i0
        total += db * ((n - s) * ci + ci * ci)
        ops += 1
        if i0 > 0:
            total += db * ((n - s) * i0 + 2 * ci * i0)
            ops += 1
    return total, ops


def assembly_bytes(meta: SteppedMeta, cfg: SchurAssemblyConfig,
                   block_mask: Optional[np.ndarray] = None,
                   dtype_bytes: int = _F64) -> dict:
    """Estimated main-memory traffic (bytes) and dispatched-op counts."""
    if cfg.fused:
        # ONE megakernel launch: factor + Linv + B in, F out — the Y panel
        # lives in VMEM and never touches HBM (the whole point of fusing;
        # unfused pays ~2·n·m for the Y round-trip plus nc re-reads)
        db = dtype_bytes
        bs = meta.block_size
        n_pad = meta.num_row_blocks * bs
        m_pad = meta.num_col_blocks * meta.rhs_block_size
        if cfg.storage == "packed":
            factor = _packed_blocks(meta, block_mask) * bs * bs
        else:
            factor = n_pad * n_pad / 2
        total = db * (factor + n_pad * bs + n_pad * m_pad + m_pad * m_pad)
        # attribute it all to "trsm" so the roofline sums stay well-formed
        return {"trsm": total, "syrk": 0.0, "total": total,
                "trsm_ops": 1, "syrk_ops": 0, "ops": 1}
    tb, to = _trsm_bytes_ops(meta, cfg, block_mask, dtype_bytes)
    sb, so = _syrk_bytes_ops(meta, cfg, dtype_bytes)
    return {"trsm": tb, "syrk": sb, "total": tb + sb,
            "trsm_ops": to, "syrk_ops": so, "ops": to + so}


def assembly_cost(meta: SteppedMeta, cfg: SchurAssemblyConfig,
                  device: DeviceModel,
                  block_mask: Optional[np.ndarray] = None,
                  dtype: str = "f64") -> dict:
    """Roofline time estimate of one assembly under ``cfg`` on ``device``.

    FLOPs come from the paper-validated model (:func:`assembly_flops`);
    bytes and launch counts from :func:`assembly_bytes`; both are combined
    by ``DeviceModel.time_s``. ``dtype`` is the stage's storage dtype
    name ("f64" | "f32" | "bf16"): it scales the byte model by itemsize
    and selects the per-dtype FLOP peak — the precision axis of the
    design space. Pallas candidates off-TPU get the interpret penalty
    (they are enumerated, but cannot win).
    """
    db = itemsize(dtype)
    fl = assembly_flops(meta, cfg)
    by = assembly_bytes(meta, cfg, block_mask, db)
    trsm_s = device.time_s(fl["trsm"], by["trsm"], by["trsm_ops"],
                           dtype=dtype)
    syrk_s = device.time_s(fl["syrk"], by["syrk"], by["syrk_ops"],
                           dtype=dtype)
    total = trsm_s + syrk_s
    if cfg.use_pallas and device.kind != "tpu":
        total *= _INTERPRET_PENALTY
    return {"trsm_s": trsm_s, "syrk_s": syrk_s, "total_s": total,
            "flops": fl["total"], "bytes": by["total"], "ops": by["ops"]}


def pallas_vmem_bytes(meta: SteppedMeta, cfg: SchurAssemblyConfig,
                      dtype: str = "f32") -> int:
    """Modeled VMEM working set of the Pallas kernels ``cfg`` runs
    (:func:`repro.kernels.common.vmem_bytes`): the fused kernel, or the
    larger of the TRSM and SYRK kernels."""
    from repro.kernels.common import vmem_bytes

    bs, bm = meta.block_size, meta.rhs_block_size
    n_pad = meta.num_row_blocks * bs
    m_pad = meta.num_col_blocks * bm
    db = itemsize(compute_dtype(dtype))
    packed = cfg.storage == "packed"

    def need(kernel):
        return vmem_bytes(kernel, n_pad, m_pad, bs, bm, db)

    if cfg.fused:
        return need("fused_packed" if packed else "fused")
    out = 0
    if cfg.trsm_variant != "dense":
        out = need("trsm_packed" if packed else "trsm")
    if cfg.syrk_variant != "dense":
        out = max(out, need("syrk"))
    return out


def offered_on(device: DeviceModel, cfg: SchurAssemblyConfig,
               meta: SteppedMeta, block_mask: Optional[np.ndarray],
               dtype: str) -> bool:
    """Whether the planner may offer ``cfg`` on ``device``. On a TPU a
    Pallas candidate needs an f32/bf16 stage (Mosaic has no f64), factor
    and RHS blocks that are multiples of the 128-lane vreg width (Mosaic
    refuses narrower block shapes and the dense kernels' unaligned lane
    slices), and a kernel working set within the VMEM limit the kernels ask
    for; off-TPU every candidate is enumerated (Pallas ones run interpreted
    and carry the interpret penalty)."""
    if not (cfg.use_pallas and device.kind == "tpu"):
        return True
    if dtype not in ("f32", "bf16"):
        return False
    if meta.block_size % 128 or meta.rhs_block_size % 128:
        return False
    from repro.kernels.common import VMEM_LIMIT_BYTES

    return pallas_vmem_bytes(meta, cfg, dtype) <= VMEM_LIMIT_BYTES


# --------------------------------------------------------------------------
# candidate enumeration
# --------------------------------------------------------------------------

def default_block_sizes(n: int) -> Tuple[int, ...]:
    """Candidate factor block sizes for an n-row factor: powers of two in
    the paper's sweep range (Fig. 5 sweeps ~100..2000; MXU wants 128-ish),
    clipped to the problem size."""
    cands = [b for b in (8, 16, 32, 64, 128, 256) if b <= n]
    return tuple(cands) if cands else (max(1, n),)


def enumerate_space(block_sizes: Sequence[int],
                    interpret: bool = False,
                    storage: Optional[str] = None
                    ) -> list[SchurAssemblyConfig]:
    """The full Table-1 design space, canonicalized — now including the
    factor storage layout.

    3 TRSM x 3 SYRK x |block_sizes| x prune on/off x pallas on/off x
    storage, minus structural duplicates: ``prune`` only affects non-pallas
    ``factor_split`` TRSM, ``use_pallas`` is an identity when both variants
    are "dense" (the pallas kernels only cover split variants), and packed
    storage is only enumerated where it is native (``factor_split`` TRSM
    and the Pallas kernels — elsewhere it densifies transiently and can
    never beat its dense twin). ``storage`` restricts the space to one
    layout ("dense"/"packed"); ``None`` enumerates both.

    The fused TRSM→SYRK megakernel (SPACE_VERSION 4) adds one candidate
    per (block size, storage): its schedule is structurally rhs-split ×
    output-split, so the variant fields are pinned to that pair (dense
    storage) / factor-split × output-split (packed storage, where the
    factor arrives as the CSR block stack) and ``fused=True`` marks it as
    its own measured-refinement family.
    """
    if storage not in (None, "dense", "packed"):
        raise ValueError(f"storage must be None|dense|packed, got {storage!r}")
    want = ("dense", "packed") if storage is None else (storage,)
    out = []
    for bs in block_sizes:
        for tv in TRSM_VARIANTS:
            for sv in SYRK_VARIANTS:
                if "dense" in want:
                    prunes = (False, True) if tv == "factor_split" \
                        else (False,)
                    for prune in prunes:
                        out.append(SchurAssemblyConfig(
                            trsm_variant=tv, syrk_variant=sv, block_size=bs,
                            prune=prune, use_pallas=False, storage="dense"))
                if "packed" in want and tv == "factor_split":
                    out.append(SchurAssemblyConfig(
                        trsm_variant=tv, syrk_variant=sv, block_size=bs,
                        prune=True, use_pallas=False, storage="packed"))
                if tv == "dense" and sv == "dense":
                    continue
                if "dense" in want:
                    out.append(SchurAssemblyConfig(
                        trsm_variant=tv, syrk_variant=sv, block_size=bs,
                        prune=False, use_pallas=True, interpret=interpret,
                        storage="dense"))
                if "packed" in want and tv == "factor_split":
                    out.append(SchurAssemblyConfig(
                        trsm_variant=tv, syrk_variant=sv, block_size=bs,
                        prune=False, use_pallas=True, interpret=interpret,
                        storage="packed"))
        # the fused megakernel: one candidate per storage layout
        if "dense" in want:
            out.append(SchurAssemblyConfig(
                trsm_variant="rhs_split", syrk_variant="output_split",
                block_size=bs, prune=False, use_pallas=True, fused=True,
                interpret=interpret, storage="dense"))
        if "packed" in want:
            out.append(SchurAssemblyConfig(
                trsm_variant="factor_split", syrk_variant="output_split",
                block_size=bs, prune=False, use_pallas=True, fused=True,
                interpret=interpret, storage="packed"))
    if not out:
        # storage="packed" with no native candidate shape cannot happen
        # (factor_split is always enumerated), but guard anyway
        raise ValueError("empty candidate space")
    return out


# --------------------------------------------------------------------------
# content-addressed plan cache
# --------------------------------------------------------------------------

def plan_cache_dir() -> str:
    """Cache root: ``$REPRO_PLAN_CACHE_DIR`` (canonical; what CI sets for
    hermetic per-job caches), falling back to the legacy
    ``$REPRO_PLAN_CACHE`` spelling, then ``~/.cache/repro/plans``.

    Read at every cache access — not captured at import — so tests and CI
    can point the planner at a temp dir without reloading the module."""
    root = os.environ.get("REPRO_PLAN_CACHE_DIR") \
        or os.environ.get("REPRO_PLAN_CACHE")
    if not root:
        root = os.path.join(os.path.expanduser("~"), ".cache", "repro",
                            "plans")
    return root


def clear_plan_cache() -> int:
    """Delete every cached plan; returns the number removed."""
    root = plan_cache_dir()
    if not os.path.isdir(root):
        return 0
    removed = 0
    for fn in os.listdir(root):
        if fn.endswith(".json"):
            os.remove(os.path.join(root, fn))
            removed += 1
    return removed


def pattern_fingerprint(pivots: np.ndarray, n: int, m: int,
                        extra: Sequence[np.ndarray] = ()) -> str:
    """Content hash of what the cost model can see of a sparsity pattern.

    The stepped pipeline's cost is fully determined by the column pivots
    (plus factor structure, passed via ``extra`` when pruning matters) —
    two B-transpose patterns with identical pivots assemble identically, so
    they deliberately share a plan-cache entry.
    """
    h = hashlib.sha256()
    h.update(f"{n}:{m}:".encode())
    h.update(np.ascontiguousarray(pivots, dtype=np.int64).tobytes())
    for a in extra:
        h.update(b"|")
        h.update(np.ascontiguousarray(a).tobytes())
    return h.hexdigest()


def _cache_key(fingerprint: str, device: DeviceModel,
               block_sizes: Sequence[int], measured: bool,
               storage: Optional[str] = None,
               stage: str = "dual",
               dtype: str = "f64") -> str:
    # `measured` is part of the key: a model-only plan must never be served
    # to a measure="auto" caller (it would silently skip the measured
    # refinement and its never-slower-than-dense guarantee), nor vice versa.
    # `storage` restrictions likewise search a different space, `stage`
    # separates the dual-operator assembly from the Dirichlet primal Schur
    # assembly even if their pattern fingerprints ever collided, and
    # `dtype` keys the precision axis (the per-dtype FLOP peaks and the
    # itemsize-scaled byte model rank candidates differently).
    h = hashlib.sha256()
    h.update(f"v{SPACE_VERSION}:{device.name}:{stage}:{fingerprint}:"
             f"{int(measured)}:{storage or 'any'}:{dtype}:".encode())
    h.update(",".join(str(b) for b in sorted(block_sizes)).encode())
    return h.hexdigest()


# --------------------------------------------------------------------------
# the plan
# --------------------------------------------------------------------------

@dataclasses.dataclass
class Plan:
    """A chosen assembly configuration plus its cost accounting.

    ``predicted_s`` is the roofline-model estimate, ``measured_s`` the
    median timed micro-run (None when ``measure="never"`` or on cache
    hits from model-only searches). ``baseline_*`` are the same numbers
    for the dense baseline of [9] for speedup reporting.
    """

    cfg: SchurAssemblyConfig
    predicted_s: float
    measured_s: Optional[float]
    baseline_predicted_s: float
    baseline_measured_s: Optional[float]
    device: str
    key: str
    candidates: int
    dtype: str = "f64"
    from_cache: bool = False

    @property
    def predicted_speedup(self) -> float:
        return self.baseline_predicted_s / max(self.predicted_s, 1e-30)

    @property
    def measured_speedup(self) -> Optional[float]:
        if self.measured_s is None or self.baseline_measured_s is None:
            return None
        return self.baseline_measured_s / max(self.measured_s, 1e-30)

    def summary(self) -> str:
        c = self.cfg
        lines = [
            f"plan[{self.device}] trsm={c.trsm_variant} "
            f"syrk={c.syrk_variant} block={c.block_size} "
            f"rhs_block={c.rhs_bs} prune={c.prune} pallas={c.use_pallas} "
            f"storage={c.storage} dtype={self.dtype}"
            f"{' (cached)' if self.from_cache else ''}",
            f"  predicted {self.predicted_s * 1e6:9.1f}us  "
            f"(dense baseline {self.baseline_predicted_s * 1e6:.1f}us, "
            f"{self.predicted_speedup:.2f}x) over "
            f"{self.candidates} candidates",
        ]
        if self.measured_s is not None:
            base = ("" if self.baseline_measured_s is None else
                    f"  (dense baseline {self.baseline_measured_s * 1e6:.1f}"
                    f"us, {self.measured_speedup:.2f}x)")
            lines.append(
                f"  measured  {self.measured_s * 1e6:9.1f}us{base}")
        return "\n".join(lines)

    def to_json(self) -> dict:
        d = dataclasses.asdict(self)
        d["cfg"] = dataclasses.asdict(self.cfg)
        d.pop("from_cache")
        return d

    @classmethod
    def from_json(cls, d: dict) -> "Plan":
        d = dict(d)
        d["cfg"] = SchurAssemblyConfig(**d["cfg"])
        return cls(**d, from_cache=True)


def _load_cached(key: str) -> Optional[Plan]:
    path = os.path.join(plan_cache_dir(), key + ".json")
    try:
        with open(path) as f:
            return Plan.from_json(json.load(f))
    except (OSError, ValueError, TypeError, KeyError):
        return None


def _store(plan: Plan) -> None:
    root = plan_cache_dir()
    try:
        os.makedirs(root, exist_ok=True)
        tmp = os.path.join(root, f".{plan.key}.tmp.{os.getpid()}")
        with open(tmp, "w") as f:
            json.dump(plan.to_json(), f, indent=1)
        os.replace(tmp, os.path.join(root, plan.key + ".json"))
    except OSError:
        pass  # cache is best-effort; planning correctness never depends on it


# --------------------------------------------------------------------------
# timed micro-runs
# --------------------------------------------------------------------------

def _synthesize_inputs(meta: SteppedMeta, seed: int = 0):
    """Timing probes with the exact sparsity pattern; values are never
    consumed numerically, only their shapes/pattern drive the schedule."""
    rng = np.random.default_rng(seed)
    n, m = meta.n, meta.m
    L = np.tril(rng.standard_normal((n, n))) * 0.05
    np.fill_diagonal(L, 1.0 + rng.random(n))
    piv_orig = meta.pivots[meta.inv_perm]
    Bt = np.zeros((n, m))
    cols = np.flatnonzero(piv_orig < n)
    Bt[piv_orig[cols], cols] = rng.choice([-1.0, 1.0], size=len(cols))
    return L, Bt


def _time_best(fn, *args, reps: int = 5, warmup: int = 2) -> float:
    """Min-of-reps wall time, delegating to the repo's ONE synchronized
    timing helper (:func:`repro.obs.timing.min_time`) so the measured
    refinement and the benchmark harness share a discipline."""
    from repro.obs.timing import min_time

    return min_time(fn, *args, reps=reps, warmup=warmup)


# --------------------------------------------------------------------------
# the planner
# --------------------------------------------------------------------------

MetaBuilder = Callable[
    [int, int], Tuple[SteppedMeta, Optional[np.ndarray]]
]  # (block_size, rhs_block_size) -> (meta, block_mask)


def plan_from_builder(
    meta_builder: MetaBuilder,
    fingerprint: str,
    *,
    block_sizes: Optional[Sequence[int]] = None,
    n_hint: Optional[int] = None,
    measure: str = "auto",
    top_k: int = 8,
    device: Optional[DeviceModel] = None,
    cache: bool = True,
    reps: int = 5,
    storage: Optional[str] = None,
    stage: str = "dual",
    dtype: str = "f64",
) -> Plan:
    """Core search: builder-parameterized so the cluster path can score the
    true *envelope* metadata it will execute with (see feti.assembly).

    ``measure``: "auto" refines the model's top-k with timed micro-runs
    ("never"/"model" skips them — pure roofline ranking). Pallas candidates
    are measured only on TPU (interpret timing is meaningless).

    ``storage`` restricts the search to one factor layout ("dense" |
    "packed"); ``None`` searches both and the winning plan's
    ``cfg.storage`` records the choice.

    ``stage`` names which assembly the plan is for — "dual" (the B̃ᵀ-RHS
    dual-operator SC) or "dirichlet" (the K_ib-RHS primal boundary Schur
    of :mod:`repro.feti.dirichlet`). It only enters the cache key: the
    candidate space and cost model are shared, the sparsity inputs differ.

    ``dtype`` is the stage's storage dtype ("f64" | "f32" | "bf16"): it
    prices candidates against the per-dtype FLOP peak and itemsize-scaled
    bytes, casts the measurement probes to the stage's *compute* dtype
    (bf16 stacks run their level-3 math in f32), and joins the cache key.
    """
    if measure not in ("auto", "never", "model"):
        raise ValueError(f"measure must be auto|never|model, got {measure!r}")
    device = device or detect_device()

    probe_meta, _ = meta_builder(8, 8) if n_hint is None else (None, None)
    n = n_hint if n_hint is not None else probe_meta.n
    if block_sizes is None:
        block_sizes = default_block_sizes(n)

    from repro.obs import metrics
    from repro.obs.trace import current_tracer

    key = _cache_key(fingerprint, device, block_sizes,
                     measured=(measure == "auto"), storage=storage,
                     stage=stage, dtype=dtype)
    if cache:
        hit = _load_cached(key)
        if hit is not None:
            metrics.inc("plan_cache.stage.hit", stage=stage, dtype=dtype)
            return hit
    # counted also when the graph planner bypasses the per-stage cache
    # (cache=False): a "miss" is a search actually performed
    metrics.inc("plan_cache.stage.miss", stage=stage, dtype=dtype)
    tr = current_tracer()

    interpret = device.kind != "tpu"
    candidates = enumerate_space(block_sizes, interpret=interpret,
                                 storage=storage)

    # score every candidate with the roofline model; metas/masks are shared
    # per (block_size, rhs_block_size) so the builder runs once per size
    built: dict[tuple, tuple] = {}
    scored = []
    with tr.span("plan:score", stage=stage, dtype=dtype) as sp:
        for cfg in candidates:
            bk = (cfg.block_size, cfg.rhs_bs)
            if bk not in built:
                built[bk] = meta_builder(*bk)
            meta, mask = built[bk]
            if not offered_on(device, cfg, meta, mask, dtype):
                continue
            cost = assembly_cost(meta, cfg, device, block_mask=mask,
                                 dtype=dtype)
            scored.append((cost["total_s"], cfg, meta, mask))
        scored.sort(key=lambda t: t[0])
        sp.set(candidates=len(scored))

    dense_cfg = SchurAssemblyConfig(
        trsm_variant="dense", syrk_variant="dense",
        block_size=min(block_sizes), prune=False, storage="dense")
    bk = (dense_cfg.block_size, dense_cfg.rhs_bs)
    if bk not in built:
        built[bk] = meta_builder(*bk)
    dense_meta, dense_mask = built[bk]
    baseline_pred = assembly_cost(
        dense_meta, dense_cfg, device, block_mask=dense_mask,
        dtype=dtype)["total_s"]

    best_s, best_cfg, best_meta, best_mask = scored[0]
    measured_s = baseline_meas = None

    if measure == "auto":
        import jax
        import jax.numpy as jnp

        sp_meas = tr.span("plan:measure", stage=stage, dtype=dtype)
        sp_meas.__enter__()
        # probes run at the stage's compute dtype so measured times price
        # the precision the real prep will execute with (bf16 -> f32)
        cd = compute_dtype(dtype)
        Lh, Bth = _synthesize_inputs(dense_meta)
        L = jnp.asarray(Lh, dtype=cd)
        Bt = jnp.asarray(Bth, dtype=cd)
        # throwaway run first: spins up BLAS threads / clock governors so
        # whichever candidate happens to be timed first isn't penalized
        jax.block_until_ready(schur_dense_baseline(L, Bt))
        baseline_meas = _time_best(
            jax.jit(schur_dense_baseline), L, Bt, reps=reps)

        def _measure(t):
            _, cfg, meta, mask = t
            if cfg.is_dense_baseline and cfg.storage == "dense":
                # byte-identical program to schur_dense_baseline (the
                # permutation-skip fast path) — reuse its timing
                return baseline_meas
            Lrun = L
            if cfg.storage == "packed":
                # packing happens once in preprocessing, so it is kept out
                # of the timed region — the assembler sees the packed stack
                from repro.sparse.packed import (
                    pack_factor,
                    packed_block_index_for,
                )

                index = packed_block_index_for(mask, meta.n, cfg.block_size)
                Lrun = jax.block_until_ready(pack_factor(L, index))
            assembler = jax.jit(make_assembler(meta, cfg, mask))
            return _time_best(assembler, Lrun, Bt, reps=reps)

        # Two-stage measured refinement. The roofline model is only trusted
        # to rank candidates WITHIN a variant family (it can misjudge a
        # whole family's library/backend constant), so:
        #   stage 1 — time the model-best candidate of every (trsm, syrk)
        #             pair; dense/dense is one of them, so the chosen plan
        #             can never be slower than the baseline it reports;
        #   stage 2 — sweep the winning pair across its remaining block
        #             sizes / prune toggles (the Fig. 5 axis), bounded by
        #             top_k.
        # family key: the fused megakernel is its own family, so whenever
        # pallas candidates are runnable (on TPU) fused is always timed
        # against unfused — "never slower than unfused" holds by
        # construction of this refinement, not by trusting the model
        def _family(cfg):
            return (cfg.trsm_variant, cfg.syrk_variant, cfg.storage,
                    cfg.fused)

        runnable = [t for t in scored
                    if not (t[1].use_pallas and device.kind != "tpu")]
        stage1: dict = {}
        for t in runnable:  # runnable is model-score sorted
            stage1.setdefault(_family(t[1]), t)
        results = [(_measure(t), t) for t in stage1.values()]
        _, win = min(results, key=lambda r: r[0])
        win_pair = _family(win[1])
        stage2 = [t for t in runnable
                  if _family(t[1]) == win_pair
                  and t is not stage1[win_pair]][:top_k]
        results += [(_measure(t), t) for t in stage2]

        best_meas, (best_s, best_cfg, best_meta, best_mask) = \
            min(results, key=lambda r: r[0])
        measured_s = best_meas
        if baseline_meas < best_meas and storage != "packed":
            # noise guard: never ship a plan measured slower than dense
            # (unless the caller pinned packed storage — then the layout
            # is a requirement, not a candidate)
            best_s, best_cfg = baseline_pred, dense_cfg
            measured_s = baseline_meas
        sp_meas.set(timed=len(results))
        sp_meas.__exit__(None, None, None)

    plan = Plan(
        cfg=best_cfg,
        predicted_s=float(best_s),
        measured_s=measured_s,
        baseline_predicted_s=float(baseline_pred),
        baseline_measured_s=baseline_meas,
        device=device.kind,
        key=key,
        candidates=len(scored),
        dtype=dtype,
    )
    if cache:
        _store(plan)
    return plan


def plan_assembly(
    pattern: np.ndarray,
    *,
    factor_pattern: Optional[np.ndarray] = None,
    block_sizes: Optional[Sequence[int]] = None,
    measure: str = "auto",
    top_k: int = 8,
    device: Optional[DeviceModel] = None,
    cache: bool = True,
    storage: Optional[str] = None,
    dtype: str = "f64",
) -> Plan:
    """Plan the SC assembly for one B-transpose sparsity ``pattern``.

    Args:
      pattern: (n, m) boolean-ish sparsity pattern of B-transpose in factor
        row order / original column order (what :func:`build_stepped_meta`
        takes).
      factor_pattern: optional (n, n) sparsity pattern of the (permuted)
        stiffness matrix; enables scoring of the pruning toggle via the
        symbolic block fill mask at each candidate block size.
      block_sizes / measure / top_k / device / cache / dtype: see
        :func:`plan_from_builder`.
    """
    pattern = np.asarray(pattern) != 0
    n, m = pattern.shape

    def builder(bs: int, rbs: int):
        meta = build_stepped_meta(pattern, block_size=bs, rhs_block_size=rbs)
        mask = None
        if factor_pattern is not None:
            from repro.sparse import block_pattern, block_symbolic_cholesky

            mask = block_symbolic_cholesky(
                block_pattern(factor_pattern, bs))
        return meta, mask

    from repro.core.stepped import column_pivots

    extra = []
    if factor_pattern is not None:
        # cheap factor-structure summary: per-row nonzero counts
        extra.append(np.asarray(factor_pattern != 0).sum(axis=1)
                     .astype(np.int64))
    fp = pattern_fingerprint(column_pivots(pattern), n, m, extra=extra)
    return plan_from_builder(
        builder, fp, block_sizes=block_sizes, n_hint=n, measure=measure,
        top_k=top_k, device=device, cache=cache, storage=storage,
        dtype=dtype)
