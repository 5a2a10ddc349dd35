"""What every stepped Pallas kernel shares: dtype rules, the matmul
precision, the x64-safe index helpers and the VMEM working-set model.

Mosaic (the TPU kernel compiler) has no f64, so the compiled kernels take
f32/bf16 only; f64 runs exist solely in interpret mode, where the
interpreter evaluates the same kernel body with XLA ops. Every in-kernel
matmul pins ``Precision.HIGHEST``: an f32 contraction at the default
precision runs as one bf16 pass on the MXU, which the f32-refined solve
path cannot recover from.

The repository runs with ``jax_enable_x64`` on, so a bare Python int in an
index map or loop bound traces as i64, which Mosaic refuses. Index maps and
loop bounds therefore go through :func:`i32`.

VMEM model: every kernel streams the factor from HBM in ``(bs, n)`` row
panels (dense) or one stored block per grid step (packed) and keeps at most
one ``(n, bm)`` solution stripe resident, plus the fused kernel's
``(nc, n, bm)`` solution panel. :func:`vmem_bytes` prices that working set
(double-buffered pipeline blocks included); the planner offers a kernel
only where it fits :data:`VMEM_LIMIT_BYTES`, and each kernel asks Mosaic
for that same limit.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.pallas import tpu as pltpu

from repro.core.precision import HIGHEST

__all__ = ["HIGHEST", "VMEM_LIMIT_BYTES", "acc_dtype", "check_dtype", "i32",
           "dot", "dot_tn", "compiler_params", "vmem_bytes"]

# scoped VMEM each kernel may use: half of a v5e core's 128 MiB, so the
# compiler keeps room for its own internal scratch
VMEM_LIMIT_BYTES = 64 * 2**20

_KERNEL_DTYPES = (np.dtype(np.float32), np.dtype(jnp.bfloat16))


def acc_dtype(dtype):
    """Accumulation dtype: f32 for f32/bf16 inputs; the input dtype itself
    otherwise (f64, interpret mode only)."""
    return jnp.float32 if np.dtype(dtype) in _KERNEL_DTYPES else dtype


def check_dtype(dtype, interpret: bool) -> None:
    """Compiled kernels take f32/bf16; f64 is accepted in interpret mode."""
    if not interpret and np.dtype(dtype) not in _KERNEL_DTYPES:
        raise TypeError(
            f"compiled Pallas kernels take float32/bfloat16 inputs, got "
            f"{np.dtype(dtype).name} (Mosaic has no f64); run f64 through "
            "the jnp variants or interpret=True")


def i32(x):
    """An int32 scalar, whatever ``jax_enable_x64`` says."""
    return jnp.asarray(x, jnp.int32)


def dot(a, b, acc_t):
    """``a @ b`` at full precision, accumulated in ``acc_t``."""
    return jnp.dot(a, b, preferred_element_type=acc_t, precision=HIGHEST)


def dot_tn(a, b, acc_t):
    """``a.T @ b`` at full precision, accumulated in ``acc_t``."""
    return jax.lax.dot_general(
        a, b, (((0,), (0,)), ((), ())), preferred_element_type=acc_t,
        precision=HIGHEST)


def compiler_params(*semantics: str) -> pltpu.CompilerParams:
    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=VMEM_LIMIT_BYTES)


def vmem_bytes(kernel: str, n_pad: int, m_pad: int, bs: int, bm: int,
               itemsize: int) -> int:
    """Modeled VMEM working set of one kernel call, in bytes.

    ``kernel`` is "trsm" | "trsm_packed" | "syrk" | "fused" |
    "fused_packed". Pipelined blocks count twice (double buffering),
    scratch once; accumulators are f32.
    """
    nc = m_pad // bm
    tile = bs * bs * itemsize
    acc = bs * bm * 4
    small = 2 * (tile + bs * bm * itemsize)  # Linv block + B block
    if kernel == "syrk":
        return 2 * 2 * bs * bm * itemsize + 2 * bm * bm * itemsize \
            + bm * bm * 4
    if kernel in ("trsm_packed", "fused_packed"):
        factor = 2 * tile + acc  # one stored block per grid step
    else:
        factor = 2 * bs * n_pad * itemsize  # (bs, n) row panel
    if kernel in ("trsm", "trsm_packed"):
        return factor + small + 2 * n_pad * bm * itemsize
    if kernel in ("fused", "fused_packed"):
        return factor + small + nc * n_pad * bm * itemsize \
            + 2 * bm * bm * itemsize
    raise ValueError(f"unknown kernel {kernel!r}")
