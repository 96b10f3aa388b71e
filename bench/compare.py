"""The numbers that decide ``correct``, and the check against limits."""
from __future__ import annotations

import sys

import numpy as np

#: A leaf whose reference gradient is below this share of the median
#: leaf's is nought to rounding, and its change is not compared.
FLAT_LEAF = 1e-3


def rel_gap(got: float, want: float) -> float:
    return abs(got - want) / max(abs(want), np.finfo(np.float64).tiny)


def rel_err(got, want) -> float:
    """Relative Frobenius error ``|got - want| / |want|``."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want)
                 / max(np.linalg.norm(want), np.finfo(np.float64).tiny))


def max_gap(got, want) -> float:
    """The widest single gap over the largest reference magnitude."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    return float(np.max(np.abs(got - want))
                 / max(np.max(np.abs(want)), np.finfo(np.float64).tiny))


def worst_leaf_norm_gap(got: list, want: list, keep=None) -> float:
    """The widest gap between a leaf's norm in ``got`` and in ``want``,
    over the larger of that leaf's reference norm and the median leaf's;
    ``keep`` (a bool per leaf) leaves some leaves out."""
    g = [float(np.linalg.norm(np.asarray(x, np.float64))) for x in got]
    w = [float(np.linalg.norm(np.asarray(x, np.float64))) for x in want]
    med = float(np.median(w))
    idx = [i for i in range(len(w)) if keep is None or keep[i]]
    return max(abs(g[i] - w[i]) / max(w[i], med, np.finfo(np.float64).tiny)
               for i in idx)


def moving_leaves(ref_grads: list) -> list:
    """The leaves whose reference gradient is not nought to rounding."""
    norms = [float(np.linalg.norm(np.asarray(g, np.float64)))
             for g in ref_grads]
    med = float(np.median(norms))
    return [n >= FLAT_LEAF * med for n in norms]


def checks(numbers: dict, limits: dict) -> list:
    """``[name, value, limit]`` for every number that has a limit; a
    number without a limit is an error in the cell's files."""
    missing = sorted(set(numbers) - set(limits))
    if missing:
        raise KeyError(f"no limit for {missing}")
    return [[k, float(numbers[k]), float(limits[k])] for k in sorted(numbers)]


def passed(rows: list) -> bool:
    return all(np.isfinite(v) and v <= lim for _, v, lim in rows)


def print_rows(rows: list) -> None:
    """Each number beside its limit, as the last lines on stderr."""
    for name, value, limit in rows:
        ok = "ok" if np.isfinite(value) and value <= limit else "FAIL"
        print(f"check {name} = {value!r} limit {limit!r} {ok}",
              file=sys.stderr, flush=True)
