"""BENCHMARK.json against the files the harness finds by name, and the
manifest's own rules.

Run by path: ``python -m pytest bench/tests``.
"""
import json
import re
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
MAN = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
METRICS = MAN["end_to_end"] + MAN["per_layer"]


@pytest.mark.parametrize("m", METRICS, ids=lambda m: m["name"])
def test_every_metric_has_its_reader(m):
    assert NAME.match(m["name"]) and UNIT.match(m["unit"])
    assert (BENCH / "metrics" / f"{m['name']}.py").is_file()
    assert m["better"] in ("lower", "higher")


@pytest.mark.parametrize("w", MAN["workloads"], ids=lambda w: w["name"])
def test_every_cell_names_its_files_and_reports_enough(w):
    assert NAME.match(w["name"]) and 1 <= len(w["why"]) <= 200
    conf = next(c for c in MAN["configs"] if c["name"] == w["config"])
    assert (BENCH.parent / conf["file"]).is_file()
    assert (BENCH / "traffic" / f"{w['traffic']}.json").is_file()
    e2e = [m["name"] for m in MAN["end_to_end"]
           if w["name"] in m.get("workloads", [w["name"]])]
    layer = [m for m in MAN["per_layer"]
             if w["name"] in m.get("workloads", [w["name"]])]
    assert "setup_s" in e2e and len(e2e) >= 2 and layer
    assert all(m["moves"] in e2e for m in layer)


def test_names_are_unique_and_bounds_in_range():
    for group in ("configs", "workloads"):
        names = [x["name"] for x in MAN[group]]
        assert len(names) == len(set(names))
    names = [m["name"] for m in METRICS]
    assert len(names) == len(set(names))
    for m in MAN["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    assert 1 <= MAN["run_seconds"] <= 51
