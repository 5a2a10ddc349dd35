"""The compiled programs of the last cluster structures the process met.

A cluster's programs bake in its structure (the column permutations, the
stepped envelope and fill masks, the packed index, the resolved configs,
the dtypes, the mesh) and take every number as an argument. So a new
cluster of a structure met before runs the programs an earlier one built:
it traces, lowers and loads nothing. Only programs are kept here: no host
stack, factor, F̃, coarse problem or load outlives the solver that made it.

This module is their one owner. A structure gets one
:class:`StructurePrograms` entry, keyed by the digest of what its prep
program bakes in (:func:`digest`, by value, never by the problem object):
the prep program, and the solver's programs, made by the first solver of
the structure that asks. An executable stays resident on the device while
its program is kept (on a v5e, ≈490 MB of generated code a feti-heat-2d
structure), so the process keeps the last :data:`KEPT` structures, and a
program of a kept structure at most :data:`VARIANTS` compiled variants
(tolerances, batch widths). A structure or program past these bounds has
its caches cleared, which frees its executables; a solver that still
holds it builds them again on its next call.
"""
from __future__ import annotations

import collections
import dataclasses
import hashlib
from types import SimpleNamespace
from typing import Callable, Optional

import jax
import numpy as np

from repro.obs import metrics

__all__ = ["KEPT", "VARIANTS", "StructurePrograms", "digest", "forget",
           "for_structure", "kept"]

# structures kept: a nonlinear or parameter-study run alternates between
# one or two; four at feti-heat-2d hold ≈2 GB of a v5e's 16 GB
KEPT = 4
# compiled variants a program of a kept structure holds before it is
# cleared: a solve needs one or two of each, a sweep of tolerances or
# batch widths more
VARIANTS = 8


def _feed(h, x) -> None:
    """Feed ``x`` to the hash ``h`` by value: arrays by dtype, shape and
    bytes; containers, dataclasses and plain objects part by part; any
    other value by its type and repr."""
    if isinstance(x, (np.ndarray, jax.Array)):
        a = np.ascontiguousarray(x)
        h.update(f"array {a.dtype.str} {a.shape}".encode())
        h.update(a.tobytes())
    elif isinstance(x, (list, tuple)):
        h.update(f"{type(x).__name__} {len(x)}".encode())
        for v in x:
            _feed(h, v)
    elif isinstance(x, dict):
        h.update(f"dict {len(x)}".encode())
        for k in sorted(x):
            _feed(h, k)
            _feed(h, x[k])
    elif isinstance(x, jax.sharding.Mesh):
        _feed(h, (x.axis_names, x.device_ids))
    elif dataclasses.is_dataclass(x):
        h.update(type(x).__qualname__.encode())
        for f in dataclasses.fields(x):
            _feed(h, getattr(x, f.name))
    elif hasattr(x, "__dict__"):
        h.update(type(x).__qualname__.encode())
        _feed(h, vars(x))
    else:
        h.update(f"{type(x).__name__} {x!r}".encode())


def digest(*parts) -> str:
    """The digest of ``parts``, by value (:func:`_feed`)."""
    h = hashlib.blake2b(digest_size=20)
    for x in parts:
        _feed(h, x)
    return h.hexdigest()


class StructurePrograms:
    """The programs of one structure: ``prep`` (jitted here) and the
    solver's programs (:meth:`solver`)."""

    def __init__(self, prep: Callable):
        self.prep = jax.jit(prep)
        self._solver: Optional[SimpleNamespace] = None

    def solver(self, make: Callable[[], SimpleNamespace]) -> SimpleNamespace:
        """The solver's programs: ``make()``'s namespace of jitted
        functions, made by the first solver of the structure that asks.
        ``make`` defines them afresh, so a forgotten structure's compiled
        code goes with its functions."""
        if self._solver is None:
            self._solver = make()
        return self._solver

    def programs(self) -> list:
        return [self.prep, *vars(self._solver or SimpleNamespace()).values()]

    def variants(self) -> int:
        """Compiled variants held, over every program of the structure."""
        return sum(p._cache_size() for p in self.programs())

    def trim(self) -> None:
        """Clear each program that holds more than :data:`VARIANTS`
        compiled variants."""
        for program in self.programs():
            if program._cache_size() > VARIANTS:
                program.clear_cache()

    def clear(self) -> None:
        """Clear every program's caches: its traces and executables."""
        for program in self.programs():
            program.clear_cache()


_KEPT: collections.OrderedDict = collections.OrderedDict()


def for_structure(key: str, prep: Callable) -> tuple[StructurePrograms, bool]:
    """The programs of the structure ``key``: the kept entry, if there is
    one (a hit), else a new one over ``prep``, which forgets the structure
    met least recently once more than :data:`KEPT` are kept. Counted by
    ``programs.prep_hit`` / ``programs.prep_miss``."""
    entry = _KEPT.get(key)
    hit = entry is not None
    if hit:
        _KEPT.move_to_end(key)
    else:
        entry = _KEPT[key] = StructurePrograms(prep)
        if len(_KEPT) > KEPT:
            _KEPT.popitem(last=False)[1].clear()
    metrics.inc("programs.prep_hit" if hit else "programs.prep_miss")
    return entry, hit


def kept() -> list[StructurePrograms]:
    """The kept structures' programs, least recently met first."""
    return list(_KEPT.values())


def forget() -> None:
    """Forget every kept structure and clear its programs."""
    while _KEPT:
        _KEPT.popitem()[1].clear()
