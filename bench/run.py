#!/usr/bin/env python3
"""Run one cell of the benchmark once, on the TPU this process finds.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, its traffic mix and its metrics are named in
``BENCHMARK.json`` at the checkout's root and found under ``bench/``. The
program under test is imported from the checkout's ``src/``. The last line
of standard output is one JSON object (``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, with ``--trace 1`` also
``breakdown``, and last ``checks``: each number compared beside its
limit); the last lines of standard error repeat the checks. Without a TPU
(or with fewer chips than the cell asks for), or without the program's
sources, it exits non-zero and prints no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)

    src = ROOT / "src"
    if not (src / "repro").is_dir():
        print(f"bench: no program sources at {src}", file=sys.stderr)
        return 2
    sys.path[:0] = [str(BENCH), str(src)]
    import harness

    names = [w["name"] for w in harness.manifest(ROOT)["workloads"]]
    if args.workload not in names:
        print(f"bench: unknown workload {args.workload!r}; cells: {names}",
              file=sys.stderr)
        return 2
    try:
        out = harness.run_cell(args.workload, args.seed, args.seconds,
                               bool(args.trace), T_START, root=ROOT)
    except harness.NoDevice as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
