"""The benchmark's own graphs and matrices, generated in numpy from a seed.

Every matrix is returned as plain CSR arrays ``(n, indptr, indices, data)``
(int32 pointers and column ids, float32 values).  The reference reads these
arrays directly; the program gets them wrapped in its ``CSR`` container by
the runner.  Nothing here imports the program.
"""
from __future__ import annotations

import numpy as np


def csr_from_coo(n: int, rows: np.ndarray, cols: np.ndarray,
                 vals: np.ndarray) -> tuple:
    """Square CSR arrays from COO triples; duplicate entries are summed."""
    key = rows.astype(np.int64) * n + cols.astype(np.int64)
    uniq, inv = np.unique(key, return_inverse=True)
    data = np.bincount(inv, weights=vals, minlength=uniq.shape[0])
    urows = uniq // n
    indptr = np.zeros(n + 1, np.int64)
    np.cumsum(np.bincount(urows, minlength=n), out=indptr[1:])
    return (n, indptr.astype(np.int32), (uniq % n).astype(np.int32),
            data.astype(np.float32))


def powerlaw(n: int, edges: int, alpha: float, seed: int) -> tuple:
    """Chung-Lu power-law graph: ``edges`` endpoint pairs drawn with
    probability proportional to ``i**(-1/(alpha-1))``, self pairs dropped,
    symmetrized, duplicates merged, plus a self loop on every node."""
    rng = np.random.default_rng(seed)
    w = np.arange(1, n + 1, dtype=np.float64) ** (-1.0 / (alpha - 1.0))
    p = w / w.sum()
    src = rng.choice(n, size=edges, p=p)
    dst = rng.choice(n, size=edges, p=p)
    keep = src != dst
    src, dst = src[keep], dst[keep]
    loops = np.arange(n)
    rows = np.concatenate([src, dst, loops])
    cols = np.concatenate([dst, src, loops])
    return csr_from_coo(n, rows, cols, np.ones(rows.shape[0]))


def grid5_spd(ny: int, nx: int, rows: int, seed: int) -> tuple:
    """The 5-point stencil of an ``ny`` x ``nx`` grid in natural (row-major)
    order, cut to its leading ``rows`` x ``rows`` block: node ``i`` couples
    to ``i +- 1`` within a grid row and to ``i +- nx``.  Each grid edge
    gets a conductance drawn from U[0.5, 1.5); the off-diagonal entries are
    their negatives, and the diagonal is each node's summed conductance
    plus 1e-3 (a ground), so the matrix is symmetric and positive definite,
    as ecology2's landscape-circuit matrix is.  The whole matrix is then
    divided by its largest absolute row sum, so its spectral radius is at
    most 1 and repeated products stay bounded."""
    rng = np.random.default_rng(seed)
    node = np.arange(ny * nx, dtype=np.int64).reshape(ny, nx)
    pairs = [(node[:, :-1].ravel(), node[:, 1:].ravel()),
             (node[:-1, :].ravel(), node[1:, :].ravel())]
    src = np.concatenate([p[0] for p in pairs])
    dst = np.concatenate([p[1] for p in pairs])
    keep = (src < rows) & (dst < rows)
    src, dst = src[keep], dst[keep]
    g = rng.uniform(0.5, 1.5, src.shape[0])
    diag = np.full(rows, 1e-3)
    np.add.at(diag, src, g)
    np.add.at(diag, dst, g)
    loops = np.arange(rows)
    n_, indptr, indices, data = csr_from_coo(
        rows, np.concatenate([src, dst, loops]),
        np.concatenate([dst, src, loops]), np.concatenate([-g, -g, diag]))
    row_sum = np.add.reduceat(np.abs(data.astype(np.float64)), indptr[:-1])
    return n_, indptr, indices, (data / row_sum.max()).astype(np.float32)


def row_ids(indptr: np.ndarray) -> np.ndarray:
    """The row index of every stored entry."""
    return np.repeat(np.arange(indptr.shape[0] - 1, dtype=np.int32),
                     np.diff(indptr))


def gcn_normalize(g: tuple) -> tuple:
    """``D^-1/2 A D^-1/2`` with the row degree (entry count) on both sides,
    in float64 and rounded to float32 once."""
    n, indptr, indices, data = g
    deg = np.maximum(np.diff(indptr), 1).astype(np.float64)
    dinv = 1.0 / np.sqrt(deg)
    val = data.astype(np.float64) * dinv[row_ids(indptr)] * dinv[indices]
    return n, indptr, indices, val.astype(np.float32)
