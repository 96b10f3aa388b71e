"""JAX executors for the fused schedule + the paper's baselines.

``fused_gemm_spmm`` / ``fused_spmm_spmm`` are the jit-compilable fused codes
(Listing 1 / Listing 3 of the paper, vmapped over tiles instead of OpenMP).
``unfused_*`` are the two-call baselines.
"""
from __future__ import annotations

import functools
import time
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ...trace import scope, span
from ..sparse.formats import (CSR, HybridELL, csr_content_digest,
                              ell_slot_coords)
from .schedule import DeviceSchedule


def _ell_rows(cols, vals, table, name="ell_body"):
    """rows[j] = Σ_w vals[j, w] · table[cols[j, w]] — scanned over w so the
    gather never materializes the (…, w, c_col) tensor (VMEM/cache friendly,
    mirrors the kernel's one-hot accumulation loop).  The scan runs under
    the scope ``name``.

    Slot 0 seeds the carry instead of a zeros array: the carry then has
    the body's exact type, including the manual mesh axes the operands
    vary over under ``shard_map(check_vma=True)``, which a fresh zeros
    array would not carry."""
    def term(cw, vw):
        return vw[..., None] * table[cw]

    if cols.shape[-1] == 0:
        return jnp.zeros(cols.shape[:-1] + (table.shape[-1],), table.dtype)

    def body(acc, wv):
        return acc + term(*wv), None

    with scope(name):
        acc, _ = jax.lax.scan(body, term(cols[..., 0], vals[..., 0]),
                              (jnp.moveaxis(cols[..., 1:], -1, 0),
                               jnp.moveaxis(vals[..., 1:], -1, 0)))
    return acc


def _spill_add(d, spill_rows, spill_cols, spill_vals, table):
    """Scatter-add COO spill lanes: d[r] += v * table[c] for each lane.

    The tile executors' hybrid-ELL tail pass (their lanes are addressed by
    tile-padded position; the full-matrix SpMM folds per row instead,
    ``spmm_hybrid``): called after the body's ``.set`` scatter so a
    capped row's total is body + tail.  Zero lanes are a no-op (traced
    statically — callers may skip the call entirely when size is 0)."""
    with scope("spill"):
        return d.at[spill_rows].add(
            spill_vals.astype(table.dtype)[:, None] * table[spill_cols])


# --------------------------------------------------------------------------
# Fused executors (tile fusion)
# --------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("t_pad", "n_i", "n_j"))
def _fused_gemm_spmm_impl(b_pad, c, i_starts, j_rows0, cols0, vals0,
                          j_rows1, cols1, vals1, srows1, scols1, svals1,
                          *, t_pad, n_i, n_j):
    c_col = c.shape[1]

    # ---- wavefront 0: one vmapped step per fused tile ----
    def tile_fn(i_start, j_rows, cols, vals):
        b_t = jax.lax.dynamic_slice(b_pad, (i_start, 0), (t_pad, b_pad.shape[1]))
        with scope("gemm"):
            d1_t = b_t @ c                           # GeMM rows of the tile
        rows = _ell_rows(cols, vals, d1_t)               # fused SpMM rows
        return d1_t, rows

    with scope("wf0"):
        d1_tiles, rows0 = jax.vmap(tile_fn)(i_starts, j_rows0, cols0, vals0)

        # stitch D1 (disjoint contiguous ranges; padded rows dropped)
        row_idx = (i_starts[:, None] + jnp.arange(t_pad)[None, :]).reshape(-1)
        row_idx = jnp.where(row_idx < n_i, row_idx, n_i)  # pad rows -> drop
        d1 = jnp.zeros((n_i, c_col), c.dtype).at[row_idx].set(
            d1_tiles.reshape(-1, c_col), mode="drop")
        d = jnp.zeros((n_j, c_col), c.dtype).at[j_rows0.reshape(-1)].set(
            rows0.reshape(-1, c_col), mode="drop")

    # ---- barrier; wavefront 1: global gather over D1 (body, then spill) ----
    with scope("wf1"):
        if j_rows1.shape[0]:
            rows1 = _ell_rows(cols1, vals1, d1)          # (T1, j1_max, c_col)
            d = d.at[j_rows1.reshape(-1)].set(
                rows1.reshape(-1, c_col), mode="drop")
        if srows1.shape[0]:
            d = _spill_add(d, srows1, scols1, svals1, d1)
    return d


@functools.partial(jax.jit, static_argnames=("t", "n_i", "n_j"))
def _fused_gemm_spmm_uniform(b_pad, c, j_rows0, cols0, vals0,
                             j_rows1, cols1, vals1, srows1, scols1, svals1,
                             *, t, n_i, n_j):
    """Uniform-tile fast path: one batched matmul, no dynamic slices, no
    padding waste — the executor twin of the Pallas kernel's grid."""
    c_col = c.shape[1]
    n_t = b_pad.shape[0] // t
    with scope("wf0"):
        with scope("gemm"):
            d1_tiles = jnp.einsum("tkb,bc->tkc", b_pad.reshape(n_t, t, -1), c)
        rows0 = jax.vmap(_ell_rows)(cols0, vals0, d1_tiles)
        d1 = d1_tiles.reshape(n_t * t, c_col)
        d = jnp.zeros((n_j, c_col), c.dtype).at[j_rows0.reshape(-1)].set(
            rows0.reshape(-1, c_col), mode="drop")
    with scope("wf1"):
        if j_rows1.shape[0]:
            rows1 = _ell_rows(cols1, vals1, d1[:n_i])
            d = d.at[j_rows1.reshape(-1)].set(rows1.reshape(-1, c_col),
                                              mode="drop")
        if srows1.shape[0]:
            d = _spill_add(d, srows1, scols1, svals1, d1[:n_i])
    return d


def _is_uniform(dsched: DeviceSchedule) -> bool:
    """True when wavefront-0 tiles form one uniform grid of stride t_pad
    (the layout the batched-matmul fast path and the Pallas kernel need).
    An empty schedule is trivially uniform."""
    t = dsched.t_pad
    st = np.asarray(dsched.i_starts)
    ln = np.asarray(dsched.i_lens)
    if st.size == 0:
        return True
    return bool((st == np.arange(st.shape[0]) * t).all()
                and (ln[:-1] == t).all())


def _schedule_args(dsched: DeviceSchedule, dtype) -> tuple:
    """The schedule's wavefront arrays as device arrays (values in
    ``dtype``): wf0 rows, cols, vals; wf1 rows, cols, vals; spill rows,
    cols, vals.  The host-to-device copy is the ``repro.upload`` span."""
    with span("upload"):
        return (jnp.asarray(dsched.j_rows0), jnp.asarray(dsched.ell_cols0),
                jnp.asarray(dsched.ell_vals0, dtype),
                jnp.asarray(dsched.j_rows1), jnp.asarray(dsched.ell_cols1),
                jnp.asarray(dsched.ell_vals1, dtype),
                jnp.asarray(dsched.spill_rows1),
                jnp.asarray(dsched.spill_cols1),
                jnp.asarray(dsched.spill_vals1, dtype))


def fused_gemm_spmm(dsched: DeviceSchedule, b: jax.Array, c: jax.Array) -> jax.Array:
    args = _schedule_args(dsched, c.dtype)
    if _is_uniform(dsched):
        t = dsched.t_pad
        n_t = dsched.n_tiles0
        b_pad = jnp.pad(b, ((0, n_t * t - b.shape[0]), (0, 0)))
        return _fused_gemm_spmm_uniform(
            b_pad, c, *args, t=t, n_i=dsched.n_i, n_j=dsched.n_j)
    b_pad = jnp.pad(b, ((0, dsched.t_pad), (0, 0)))
    with span("upload"):
        i_starts = jnp.asarray(dsched.i_starts)
    return _fused_gemm_spmm_impl(
        b_pad, c, i_starts, *args,
        t_pad=dsched.t_pad, n_i=dsched.n_i, n_j=dsched.n_j)


@functools.partial(jax.jit, static_argnames=("t_pad", "n_i", "n_j"))
def _fused_spmm_spmm_impl(c, i_starts, op1_cols, op1_vals, d1_spill,
                          j_rows0, cols0, vals0, j_rows1, cols1, vals1,
                          srows1, scols1, svals1, *, t_pad, n_i, n_j):
    c_col = c.shape[1]

    def tile_fn(i_start, o_cols, o_vals, d1_sp, j_rows, cols, vals):
        # op1 SpMM rows of the tile: hybrid ELL body over global C, plus the
        # tile's precomputed spill delta (hub-row tails past the width cap)
        d1_t = _ell_rows(o_cols, o_vals, c) + d1_sp
        rows = _ell_rows(cols, vals, d1_t)               # in-tile gather
        return d1_t, rows

    with scope("wf0"):
        d1_tiles, rows0 = jax.vmap(tile_fn)(
            i_starts, op1_cols, op1_vals, d1_spill, j_rows0, cols0, vals0)

        row_idx = (i_starts[:, None] + jnp.arange(t_pad)[None, :]).reshape(-1)
        row_idx = jnp.where(row_idx < n_i, row_idx, n_i)
        d1 = jnp.zeros((n_i, c_col), c.dtype).at[row_idx].set(
            d1_tiles.reshape(-1, c_col), mode="drop")
        d = jnp.zeros((n_j, c_col), c.dtype).at[j_rows0.reshape(-1)].set(
            rows0.reshape(-1, c_col), mode="drop")

    with scope("wf1"):
        if j_rows1.shape[0]:
            rows1 = _ell_rows(cols1, vals1, d1)
            d = d.at[j_rows1.reshape(-1)].set(rows1.reshape(-1, c_col),
                                              mode="drop")
        if srows1.shape[0]:
            d = _spill_add(d, srows1, scols1, svals1, d1)
    return d


def _op1_ell(a1: CSR, dsched: DeviceSchedule, width_cap: int | None = None):
    """Per-tile hybrid ELL of the op-1 rows (global columns into C).

    Routes through the shared ``HybridELL`` packer (one packer for every
    ELL in the system): the tiles' contiguous row ranges are concatenated
    into one packed row set, the body comes back reshaped to
    ``(T0, t_pad, w)``, and entries past ``width_cap`` come back as flat
    spill lanes addressed by *tile-padded* D1 position
    (``tile * t_pad + in_tile_slot``) so executors can scatter-add them
    onto the flattened D1 tiles before the in-tile gather runs.

    Memoized on the (cached) DeviceSchedule per op-1 content: the O(nnz)
    host repack runs once per (schedule, a1, cap), not once per executor
    call — the same amortization contract as the schedule cache itself."""
    memo_key = (csr_content_digest(a1),
                None if width_cap is None else int(width_cap))
    memo = getattr(dsched, "_op1_pack_memo", None)
    if memo is not None and memo[0] == memo_key:
        return memo[1]
    t0 = time.perf_counter()
    with span("pack"):
        packed = _op1_ell_build(a1, dsched, width_cap)
    from . import api   # api imports this module: bind at call time
    api.count_pack(time.perf_counter() - t0)
    object.__setattr__(dsched, "_op1_pack_memo", (memo_key, packed))
    return packed


def _op1_ell_build(a1: CSR, dsched: DeviceSchedule, width_cap: int | None):
    t_pad = dsched.t_pad
    n_t = dsched.n_tiles0
    i_lens = np.asarray(dsched.i_lens, dtype=np.int64)
    w_cap = int(width_cap) if width_cap is not None else None
    if not int(i_lens.sum()):
        w = 1 if w_cap is None else max(min(w_cap, 1), 1)
        return (np.zeros((n_t, t_pad, w), np.int32),
                np.zeros((n_t, t_pad, w), np.float32),
                np.zeros(0, np.int64), np.zeros(0, np.int32),
                np.zeros(0, np.float32))
    tile_of, k_of = ell_slot_coords(i_lens)         # ranges concatenated
    rows = np.asarray(dsched.i_starts, np.int64)[tile_of] + k_of
    hell = HybridELL.from_csr_rows(
        a1, rows, cap=w_cap if w_cap is not None else a1.n_cols)
    w = hell.width
    cols = np.zeros((n_t, t_pad, w), np.int32)
    vals = np.zeros((n_t, t_pad, w), np.float32)
    cols[tile_of, k_of] = hell.cols
    vals[tile_of, k_of] = hell.vals.astype(np.float32)
    sr = hell.spill_rows.astype(np.int64)           # packed-row index
    spill_flat = tile_of[sr] * np.int64(t_pad) + k_of[sr]
    return (cols, vals, spill_flat, hell.spill_cols,
            hell.spill_vals.astype(np.float32))


def fused_spmm_spmm(dsched: DeviceSchedule, a1: CSR, c: jax.Array) -> jax.Array:
    cols, vals, spill_flat, spill_cols, spill_vals = _op1_ell(
        a1, dsched, width_cap=dsched.width_cap)
    n_t, t_pad = dsched.n_tiles0, dsched.t_pad
    c_col = c.shape[1]
    args = _schedule_args(dsched, c.dtype)
    with span("upload"):
        i_starts = jnp.asarray(dsched.i_starts)
        op1 = (jnp.asarray(cols), jnp.asarray(vals, c.dtype))
        spill = (jnp.asarray(spill_flat), jnp.asarray(spill_cols),
                 jnp.asarray(spill_vals, c.dtype))
    # spill delta on the flattened padded D1 tiles, zero when nothing spills
    d1_spill = jnp.zeros((n_t * t_pad, c_col), c.dtype)
    if spill_flat.size:
        d1_spill = _spill_add(d1_spill, *spill, c)
    return _fused_spmm_spmm_impl(
        c, i_starts, *op1, d1_spill.reshape(n_t, t_pad, c_col), *args,
        t_pad=dsched.t_pad, n_i=dsched.n_i, n_j=dsched.n_j)


# --------------------------------------------------------------------------
# Unfused baselines (two separate routines, D1 round-trips memory)
# --------------------------------------------------------------------------
class FoldedELL(NamedTuple):
    """Full-matrix hybrid ELL on the device: the capped body
    (``cols``/``vals``) and its spill lanes folded per row
    (``formats.SpillFold``: virtual rows and the row each adds to).
    Without spill lanes the fold's arrays are empty."""

    cols: jax.Array
    vals: jax.Array
    vcols: jax.Array
    vvals: jax.Array
    vrows: jax.Array


def csr_to_ell(a: CSR, width_cap: int | None = None) -> FoldedELL:
    """Full-matrix hybrid ELL (the unfused executor's format) as device
    arrays; with ``width_cap=None`` the body is pad-to-max and nothing
    spills (the pre-hybrid layout)."""
    hell = HybridELL.from_csr_rows(
        a, np.arange(a.n_rows),
        cap=width_cap if width_cap is not None else max(a.n_cols, 1))
    fold = hell.spill_fold()
    return FoldedELL(
        jnp.asarray(hell.cols), jnp.asarray(hell.vals, jnp.float32),
        jnp.asarray(fold.vcols), jnp.asarray(fold.vvals, jnp.float32),
        jnp.asarray(fold.rows))


@jax.jit
def spmm_ell(cols: jax.Array, vals: jax.Array, x: jax.Array) -> jax.Array:
    """Row-ELL SpMM: D[i] = sum_w vals[i,w] * X[cols[i,w]]."""
    return _ell_rows(cols, vals.astype(x.dtype), x)


def _spill_fold(d, ell: FoldedELL, x):
    """``d + spill``, one update per virtual row: the virtual rows' SpMM
    (the body's scan), then a sorted segment sum of its rows into ``d``.
    Every entry's product is the per-lane pass's; only the order in which
    a row's terms are added differs."""
    with scope("spill"):
        vvals = ell.vvals.astype(x.dtype)
    part = _ell_rows(ell.vcols, vvals, x, name="spill")
    with scope("spill"):
        return d.at[ell.vrows].add(part, indices_are_sorted=True)


@jax.jit
def spmm_hybrid(ell: FoldedELL, x):
    """Hybrid-ELL SpMM: capped body pass, then the spill fold where the
    pack has spill lanes (with none, the body pass alone)."""
    d = _ell_rows(ell.cols, ell.vals.astype(x.dtype), x)
    if ell.vcols.shape[0]:
        d = _spill_fold(d, ell, x)
    return d


@jax.jit
def unfused_gemm_spmm(ell: FoldedELL, b, c):
    with scope("gemm"):
        d1 = b @ c
    return spmm_hybrid(ell, d1)


@jax.jit
def unfused_spmm_spmm(ell_a: FoldedELL, ell_a1: FoldedELL, c):
    return spmm_hybrid(ell_a, spmm_hybrid(ell_a1, c))

