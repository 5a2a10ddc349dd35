"""Share (%) of the roofline reached by the dual-operator assembly (the
stepped TRSM and SYRK that build every F̃ᵢ): the least time the chip
needs for the counted work, max(flops / peak FLOP/s, bytes / peak
bandwidth), over the device time of the operations under ``stage:dual``
and not under ``factorize``. The work is counted from the problem alone
(bench/workcount.py); the peaks are bench/peaks.json's."""
import sys

import workcount


def read(run):
    if run.trace is None or run.mix.cluster != "per_request":
        return None
    t = run.trace.scope_time(include=("stage:dual",), exclude=("factorize",))
    if t <= 0:
        return None
    work = workcount.assembly_work(run.cfg, run.storage_itemsize)
    compute = work["flops"] / run.peaks["flops_per_s"]
    memory = work["bytes"] / run.peaks["hbm_bytes_per_s"]
    print(f"[assembly_roofline] {work['flops']} flops, {work['bytes']} "
          f"bytes per cluster: {'compute' if compute >= memory else 'memory'}"
          f" bound ({compute:.3e} s vs {memory:.3e} s), "
          f"{t / run.trace.requests:.6f} device s per cluster",
          file=sys.stderr)
    return 100.0 * max(compute, memory) * run.trace.requests / t
