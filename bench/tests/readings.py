#!/usr/bin/env python3
"""Readings that set a cell's limits, taken on the chip at the cell's own
size: the program's compared numbers on some seeds, and the control's
(the configuration's ``control`` FetiConfig fields: the nearest precision
below the stated one) on the same seeds. One process, so the decomposition
is made once per configuration.

    python bench/tests/readings.py --workload <cell> [--workload ...] \
        --seeds <n> <n> ... --seconds <s> [--control-only]

Prints one JSON line per run: cell, seed, sound or control, and the
checks. The benchmark's own runs never run the control.
"""
import argparse
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

import harness  # noqa: E402


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", action="append", required=True)
    p.add_argument("--seeds", type=int, nargs="+", required=True)
    p.add_argument("--seconds", type=float, default=5.0)
    p.add_argument("--control-only", action="store_true")
    args = p.parse_args(argv)

    problems = {}
    decompose = harness.decompose

    def cached(cfg):
        key = json.dumps(cfg, sort_keys=True)
        if key not in problems:
            problems[key] = decompose(cfg)
        return problems[key]

    harness.decompose = cached
    man = harness.manifest()
    for cell in args.workload:
        w = next(w for w in man["workloads"] if w["name"] == cell)
        conf = next(c for c in man["configs"] if c["name"] == w["config"])
        cfg = harness.read_json(harness.ROOT / conf["file"])
        kinds = [("control", cfg["control"]["feti"])]
        if not args.control_only:
            kinds.insert(0, ("sound", None))
        for seed in args.seeds:
            for kind, overrides in kinds:
                out = harness.run_cell(cell, seed, args.seconds, False,
                                       time.perf_counter(),
                                       feti_overrides=overrides)
                print(json.dumps({"cell": cell, "seed": seed, "kind": kind,
                                  "correct": out["correct"],
                                  "attempted": out["attempted"],
                                  "checks": out["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
