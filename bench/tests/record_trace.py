#!/usr/bin/env python3
"""Record the small TPU profiler trace that test_tracereduce.py reads.

    python bench/tests/record_trace.py <out_dir>    # on a TPU

A jitted program with the prep program's scope names (``factorize``,
``stage:dual``) runs twice inside a ``bench.window`` annotation, with a
host-only pause between the runs under a ``bench.pause`` annotation,
traced as the harness traces. The trace's ``.xplane.pb`` and the window's
perf_counter start go to ``out_dir``.
"""
import glob
import json
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path

import jax
import jax.numpy as jnp

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import harness  # noqa: E402


@jax.jit
def prep(K, B):
    with jax.named_scope("factorize"):
        L = jnp.linalg.cholesky(K)
    with jax.named_scope("stage:dual"):
        Y = jax.scipy.linalg.solve_triangular(L, B, lower=True)
        F = Y.T @ Y
    return L, F


def main(out_dir: str) -> int:
    if jax.devices()[0].platform != "tpu":
        print("record_trace: needs a TPU", file=sys.stderr)
        return 3
    n, m = 8192, 2048  # tens of ms of device work a run
    A = jax.random.normal(jax.random.key(0), (n, n), jnp.float32)
    K = A @ A.T + n * jnp.eye(n, dtype=jnp.float32)
    B = jax.random.normal(jax.random.key(1), (n, m), jnp.float32)
    jax.block_until_ready(prep(K, B))
    tmp = tempfile.mkdtemp()
    harness.start_trace(tmp)
    with jax.profiler.TraceAnnotation("bench.window"):
        t0 = time.perf_counter()
        jax.block_until_ready(prep(K, B))
        with jax.profiler.TraceAnnotation("bench.pause"):
            time.sleep(0.05)
        jax.block_until_ready(prep(K, B))
        t1 = time.perf_counter()
    jax.profiler.stop_trace()
    (f,) = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"), recursive=True)
    os.makedirs(out_dir, exist_ok=True)
    shutil.copy(f, os.path.join(out_dir, "tiny.xplane.pb"))
    with open(os.path.join(out_dir, "tiny.json"), "w") as fh:
        json.dump({"t0": t0, "t1": t1, "pause_s": 0.05,
                   "device_kind": jax.devices()[0].device_kind}, fh)
    shutil.rmtree(tmp)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
