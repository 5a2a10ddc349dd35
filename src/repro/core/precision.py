"""The precision axis of the assembly pipeline (ISSUE 9).

The paper's GPU speedups presume hardware where f32/tf32 level-3
throughput is 2-16x f64, so the stage graph treats the assembly dtype as
a plannable dimension like the TRSM/SYRK variant or the block size:
every :class:`~repro.core.stages.StageSpec` carries a dtype name, the
roofline model prices FLOPs per dtype
(:class:`~repro.launch.roofline.DeviceModel.peak`), and the byte model
scales with the itemsize. f64 *accuracy* is recovered around the
reduced-precision stacks by iterative refinement + defect-correction
outer iterations (docs/mixed_precision.md), so the contract is:

  * stacks/kernels run at the **storage dtype** (what this module
    canonicalizes),
  * factorization/TRSM/SYRK run at the **compute dtype** (same, except
    bf16 accumulates in f32 — the only accumulate rule hardware
    supports),
  * PCPG outer vectors run at the **solve dtype** (f64 whenever
    refinement is on, else the storage dtype).

Dtype spellings accepted everywhere: the short names ``"f64"``/
``"f32"``/``"bf16"``, numpy/jax dtypes, and python ``float``.
"""
from __future__ import annotations

from typing import Any

import jax.numpy as jnp
import ml_dtypes
import numpy as np

__all__ = [
    "SUPPORTED_DTYPES",
    "canonical_dtype",
    "dtype_name",
    "compute_dtype",
    "solve_dtype",
    "eps",
    "itemsize",
    "tol_floor",
    "default_refine_steps",
    "HIGHEST",
    "einsum",
    "mm",
]

# name <-> dtype tables. bf16 comes from ml_dtypes (a jax dependency), so
# this module stays importable without initializing a jax backend.
_BF16 = np.dtype(ml_dtypes.bfloat16)
_NAME_TO_DTYPE = {"f64": np.dtype(np.float64), "f32": np.dtype(np.float32),
                  "bf16": _BF16}
_DTYPE_TO_NAME = {v: k for k, v in _NAME_TO_DTYPE.items()}

SUPPORTED_DTYPES = tuple(_NAME_TO_DTYPE)


def canonical_dtype(dtype: Any) -> np.dtype:
    """Normalize any accepted dtype spelling to its numpy dtype.

    Accepts the short names ("f64" | "f32" | "bf16"), numpy/jax dtypes
    and python ``float``; anything outside :data:`SUPPORTED_DTYPES`
    raises (integer or complex stacks are never meaningful here).
    """
    if isinstance(dtype, str) and dtype in _NAME_TO_DTYPE:
        return _NAME_TO_DTYPE[dtype]
    dt = np.dtype(dtype)
    if dt not in _DTYPE_TO_NAME:
        raise ValueError(
            f"unsupported assembly dtype {dtype!r}; supported: "
            f"{SUPPORTED_DTYPES}")
    return dt


def dtype_name(dtype: Any) -> str:
    """Short stage-graph name of a dtype: "f64" | "f32" | "bf16"."""
    return _DTYPE_TO_NAME[canonical_dtype(dtype)]


def compute_dtype(dtype: Any) -> np.dtype:
    """The dtype the factorization/TRSM/SYRK math runs in: the storage
    dtype itself, except bf16 which accumulates in f32 (bf16 has no
    library Cholesky anywhere, and every Pallas kernel here already
    accumulates sub-f32 inputs in f32 — "bf16-accumulate-f32")."""
    dt = canonical_dtype(dtype)
    if dt == _BF16:
        return np.dtype(np.float32)
    return dt


def solve_dtype(dtype: Any, refine_steps: int) -> np.dtype:
    """The dtype of the PCPG outer vectors: f64 whenever refinement is
    on (the refined operators deliver f64-accurate applications around
    the reduced-precision stacks), else the storage dtype itself."""
    dt = canonical_dtype(dtype)
    if refine_steps > 0 and dt != np.dtype(np.float64):
        return np.dtype(np.float64)
    return dt


def eps(dtype: Any) -> float:
    """Machine epsilon of a (canonicalized) dtype as a python float."""
    # ml_dtypes.finfo covers bfloat16, which np.finfo does not know
    return float(ml_dtypes.finfo(canonical_dtype(dtype)).eps)


def itemsize(dtype: Any) -> int:
    return canonical_dtype(dtype).itemsize


def tol_floor(dtype: Any, factor: float = 50.0) -> float:
    """The smallest relative PCPG tolerance worth asking of an operator
    in ``dtype``: ``factor * eps``. CG residual stagnation sets in at a
    modest multiple of eps (rounding of the recursive residual); 50x is
    conservative enough to stop before ``max_iter`` burns on noise while
    staying far below anything a preconditioned solve actually needs
    (f64 floor ~1.1e-14, f32 ~6e-6, bf16 ~0.4)."""
    return factor * eps(dtype)


def default_refine_steps(dtype: Any) -> int:
    """Refinement steps when ``FetiConfig.refine`` is None: f64 stacks
    need none (0 keeps the f64 program byte-identical to the pre-ISSUE-9
    pipeline); reduced-precision stacks get 2 interior-solve refinement
    steps, which recovers f64 interior solves for the well-conditioned
    subdomain operators this pipeline factorizes (docs/mixed_precision.md
    derives the kappa * eps bound)."""
    return 0 if canonical_dtype(dtype) == np.dtype(np.float64) else 2


# Matmul precision of every product over the stored stacks (factorization,
# assembly, operators, preconditioners, the Pallas kernels). On a TPU an
# f32 contraction at the default precision runs as a single bf16 MXU pass
# (~3 significant digits); the f32 path's refinement and its 1e-8 oracle
# contract assume true f32 products. XLA:CPU computes at full precision
# whatever is asked, so CPU results do not change.
HIGHEST = "highest"

# An f64 contraction on a TPU (no f64 units) is emulated; for matrix-vector
# shapes XLA:TPU's emulated dot holds temporaries several times its
# operands (7.4 GB for the 1.2 GB f64 packed-K matvec of feti-heat-2d's
# refinement, against 16 GB of HBM). An elementwise product and a sum need
# no more than the product itself, so contractions whose full index space is
# at most this many times their largest operand take that route there.
_ELEMENTWISE_SPAN = 2


def _f64_on_tpu(*xs) -> bool:
    import jax

    return (jnp.result_type(*xs) == np.float64
            and jax.default_backend() == "tpu")


def _elementwise_einsum(subscripts: str, a, b):
    """``einsum`` of two operands as one broadcast product and a sum (no
    repeated indices within an operand), or None where the product's index
    space exceeds :data:`_ELEMENTWISE_SPAN` times the largest operand."""
    ins, out = subscripts.replace(" ", "").split("->")
    sa, sb = ins.split(",")
    union = list(dict.fromkeys(sa + sb))
    size = dict(zip(sa, a.shape)) | dict(zip(sb, b.shape))
    if int(np.prod([size[c] for c in union])) > _ELEMENTWISE_SPAN * max(
            int(np.prod(a.shape)), int(np.prod(b.shape))):
        return None

    def expand(x, sub):
        order = [c for c in union if c in sub]
        x = jnp.transpose(x, [sub.index(c) for c in order])
        return x.reshape([size[c] if c in sub else 1 for c in union])

    r = jnp.sum(expand(a, sa) * expand(b, sb),
                axis=tuple(i for i, c in enumerate(union) if c not in out))
    kept = [c for c in union if c in out]
    return jnp.transpose(r, [kept.index(c) for c in out])


def einsum(subscripts: str, a, b):
    """Two-operand ``jnp.einsum`` at :data:`HIGHEST` precision; on a TPU,
    f64 contractions of matrix-vector shape run elementwise (see
    :data:`_ELEMENTWISE_SPAN`)."""
    if _f64_on_tpu(a, b):
        r = _elementwise_einsum(subscripts, a, b)
        if r is not None:
            return r
    return jnp.einsum(subscripts, a, b, precision=HIGHEST)


def mm(a, b):
    """``a @ b`` at :data:`HIGHEST` precision (f64 matrix-vector products
    on a TPU elementwise, as in :func:`einsum`)."""
    if _f64_on_tpu(a, b) and a.ndim == 2 and b.ndim in (1, 2):
        r = _elementwise_einsum("ij,j->i" if b.ndim == 1 else "ij,jk->ik",
                                a, b)
        if r is not None:
            return r
    return jnp.matmul(a, b, precision=HIGHEST)
