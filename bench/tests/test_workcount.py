"""The assembly work count against a brute-force count on tiny factors.

Run by path: ``python -m pytest bench/tests``.
"""
import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import workcount  # noqa: E402
from reference import Layout  # noqa: E402


def brute_force(cfg: dict, itemsize: int) -> dict:
    """Dense symbolic elimination, then every flop of the forward solves
    and of the lower-triangle SYRK counted one by one."""
    lay = Layout(cfg)
    n, d = lay.n_local, lay.ndpn
    rows, cols = workcount.local_pattern(lay)
    node_perm = workcount.nested_dissection(
        tuple(e + 1 for e in lay.elems_per_sub))
    perm = (node_perm[:, None] * d + np.arange(d)).reshape(-1)
    pos = np.empty(n, int)
    pos[perm] = np.arange(n)
    L = np.zeros((n, n), bool)
    L[pos[rows], pos[cols]] = True
    L = np.tril(L | L.T)
    for k in range(n):
        below = np.flatnonzero(L[k + 1:, k]) + k + 1
        L[np.ix_(below, below)] = True
        L = np.tril(L)
    flops = nbytes = 0
    for dofs in workcount.multiplier_dofs(lay):
        piv = pos[dofs]
        for p in piv:
            for i in range(p, n):
                flops += 1 + 2 * int(L[i + 1:, i].sum())
        for j in range(len(piv)):
            for k in range(j, len(piv)):
                flops += sum(2 for i in range(n)
                             if i >= piv[j] and i >= piv[k])
        nbytes += itemsize * (int(L.sum()) + int((n - piv).sum())
                              + len(piv) ** 2)
    return dict(flops=flops, bytes=nbytes, factor_nnz=int(L.sum()))


@pytest.mark.parametrize("cfg", [
    dict(problem="heat", sub_grid=(2, 3), elems_per_sub=(5, 4)),
    dict(problem="heat", sub_grid=(2, 2), elems_per_sub=(8, 8)),
    dict(problem="elasticity", sub_grid=(3, 2), elems_per_sub=(3, 4)),
    dict(problem="heat", sub_grid=(2, 1, 2), elems_per_sub=(2, 3, 2)),
], ids=lambda c: f"{c['problem']}-{'x'.join(map(str, c['elems_per_sub']))}")
def test_count_matches_brute_force(cfg):
    got = workcount.assembly_work(cfg, itemsize=4)
    want = brute_force(cfg, itemsize=4)
    assert got["factor_nnz"] == want["factor_nnz"]
    assert got["flops"] == want["flops"]
    assert got["bytes"] == want["bytes"]


def test_nested_dissection_is_a_permutation():
    perm = workcount.nested_dissection((9, 7))
    assert sorted(perm.tolist()) == list(range(63))


def test_multipliers_follow_the_gluing_rule():
    """2x2 subdomains of 2x2 elements: the centre node is glued in a chain
    of four copies; x = 0 face nodes are pinned once per copy."""
    lay = Layout(dict(problem="heat", sub_grid=(2, 2), elems_per_sub=(2, 2)))
    md = workcount.multiplier_dofs(lay)
    # subdomain 0 (x, y in [0, 2]): face nodes 0, 3, 6 pinned; nodes 2, 5
    # glued to subdomain 2 along x = 2 (first copies); 6, 7 to subdomain 1
    # along y = 2; node 8 is the first of four copies
    assert sorted(md[0].tolist()) == [0, 2, 3, 5, 6, 7, 8]
    assert [len(m) for m in md] == [7, 8, 6, 5]
