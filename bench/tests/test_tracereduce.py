"""The trace reduction: interval arithmetic on made-up intervals, and the
whole reduction on a small trace recorded on a TPU v5e by
``record_trace.py`` (kept in ``data/``).

Run by path: ``python -m pytest bench/tests``.
"""
import json
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import tracereduce  # noqa: E402

DATA = Path(__file__).resolve().parent / "data"
SKEW_S = 2e-3


def test_union_merges_overlaps_and_skips_nested():
    iv = [(0, 2), (1, 3), (5, 6), (5.2, 5.5), (7, 7)]
    assert tracereduce.union_length(iv) == pytest.approx(4.0)


def test_gaps_are_the_complement_inside_the_window():
    iv = [(1, 2), (1.5, 3), (5, 6), (8, 12)]
    assert tracereduce.gaps(iv, 0, 10) == [(0, 1), (3, 5), (6, 8)]
    assert tracereduce.gaps([], 0, 1) == [(0, 1)]


def test_scope_time_matches_whole_components_and_averages_chips():
    H = tracereduce.HloOp
    ops = [H("custom-call", "jit(prep)/factorize/cholesky", 2.0),
           H("fusion", "jit(prep)/stage:dual/dot", 4.0),
           H("fusion", "jit(prep)/stage:dual/factorize/x", 2.0),
           H("fusion", "jit(prep)/stage:dualx/dot", 2.0)]
    busy = {0: [(0, 1), (1, 3), (3, 4), (4, 5)], 1: [(0, 2)]}
    r = tracereduce.Reduction((0, 10), busy, ops, host_spans=[], requests=1)
    assert r.scope_time(("factorize",)) == pytest.approx(2.0)
    assert r.scope_time(("stage:dual",), ("factorize",)) == pytest.approx(2.0)
    assert r.busy_s == pytest.approx((5 + 2) / 2)


def test_idle_gaps_go_to_the_innermost_host_span():
    spans = [("bench.window", 0, 6, 0), ("solve", 0.5, 6, 2),
             ("recover", 1, 3.5, 3)]
    r = tracereduce.Reduction((0, 6), {0: [(0, 1), (4, 5)]}, [],
                              host_spans=spans, requests=1)
    idle = dict(r.breakdown()["idle_gaps"])
    assert idle == {"recover": pytest.approx(3.0), "solve": pytest.approx(1.0)}


def test_a_recorded_tpu_trace():
    meta = json.loads((DATA / "tiny.json").read_text())
    r = tracereduce.reduce(str(DATA), meta["t0"], [])
    assert r.chips == 1 and r.requests == 0
    # the annotation spans the two runs and the pause between them
    assert r.window_s >= meta["pause_s"]
    assert r.window_s == pytest.approx(meta["t1"] - meta["t0"], abs=2e-3)
    fac = r.scope_time(("factorize",))
    dual = r.scope_time(("stage:dual",), ("factorize",))
    assert fac > 0 and dual > 0
    # the device's clock and the host's agree to about a millisecond, so
    # the window's edges may cut that much of the device's work
    assert fac + dual <= r.busy_s + SKEW_S and r.busy_s <= r.window_s
    assert r.busy_s >= 0.5 * (fac + dual)
    # the pause is host-only: the device idles through it
    idle = dict(r.breakdown()["idle_gaps"])
    assert idle["bench.pause"] >= 0.9 * meta["pause_s"]
    assert r.window_s - r.busy_s >= 0.9 * meta["pause_s"]
