"""Pallas TPU kernel for wavefront-0 fused tiles of GeMM-SpMM.

TPU adaptation of the paper's fused code (Listing 1).  One grid step = one
fused tile (the paper's OpenMP-parallel tile loop becomes the Pallas grid;
grid steps are independent — exactly the wavefront-0 guarantee).

Per tile ``v`` covering rows ``[v*t, (v+1)*t)``:

  1. GeMM:  ``D1_t = B_t @ C``      — MXU matmul, ``B_t`` staged to VMEM by
     BlockSpec, ``D1_t`` *never leaves VMEM* before its consumers run.
  2. Fused SpMM: the tile-local rows of ``A`` are densified on the fly from
     ELL into a ``(j0_max, t)`` matrix ``W`` via one-hot accumulation, and the
     fused rows are ``W @ D1_t`` — a second MXU matmul.  This replaces the
     CPU scalar gather: on TPU, gather-by-matmul is the idiomatic way to keep
     the systolic array busy (DESIGN.md §2).

The tile size ``t`` is the TPU analogue of the paper's step-2 splitting: VMEM
working set is ``t*(bCol+cCol) + j0_max*(t+cCol)`` elements, uniform across
tiles, so step 2 reduces to choosing the largest 128-aligned ``t`` under the
VMEM budget (``vmem_bytes`` below; see ``ops.choose_kernel_tile``).

Wavefront 1 (the post-barrier tiles) runs as a second kernel (``spmm.py``)
reading the now-complete ``D1`` — the ``pallas_call`` boundary *is* the
paper's single synchronization barrier.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .config import compiler_params, resolve_interpret, vmem_buffer_bytes


def densify_ell(cols, vals, n_cols: int):
    """Dense ``(rows, n_cols)`` matrix of an ELL block by one-hot
    accumulation, one slot at a time.  The slot loop is unrolled at trace
    time (the width is static and small) so every slot read is a static
    lane slice, which Mosaic lowers; a loop-counter index would be a
    dynamic lane slice, which it does not."""
    iota = jax.lax.broadcasted_iota(jnp.int32, (1, n_cols), 1)
    acc = jnp.zeros((cols.shape[0], n_cols), vals.dtype)
    for w in range(cols.shape[1]):
        onehot = (cols[:, w:w + 1] == iota).astype(vals.dtype)
        acc = acc + vals[:, w:w + 1] * onehot
    return acc


def _kernel(cols_ref, vals_ref, b_ref, c_ref, d1_ref, rows_ref):
    # ---- GeMM part: D1 tile, stays in VMEM ----
    d1_t = jnp.dot(b_ref[...], c_ref[...],
                   preferred_element_type=jnp.float32)          # (t, cCol)
    d1_ref[...] = d1_t.astype(d1_ref.dtype)

    # ---- fused SpMM part: densify tile-local A rows, multiply on MXU ----
    w_mat = densify_ell(cols_ref[0], vals_ref[0], d1_t.shape[0])  # (j0, t)
    rows = jnp.dot(w_mat, d1_t, preferred_element_type=jnp.float32)
    rows_ref[0] = rows.astype(rows_ref.dtype)


def vmem_bytes(j0_max: int, w: int, t: int, b_col: int, c_col: int,
               itemsize: int) -> int:
    """Scoped-VMEM working set of one grid step: every blocked operand
    double-buffered by the pipeline, plus the f32 D1 tile, the densified
    A tile with its one-hot, and the f32 fused rows."""
    blocks = (vmem_buffer_bytes((j0_max, w), 4)
              + vmem_buffer_bytes((j0_max, w), itemsize)
              + vmem_buffer_bytes((t, b_col), itemsize)
              + vmem_buffer_bytes((b_col, c_col), itemsize)
              + vmem_buffer_bytes((t, c_col), itemsize)
              + vmem_buffer_bytes((j0_max, c_col), itemsize))
    temps = (vmem_buffer_bytes((t, c_col), 4)
             + 2 * vmem_buffer_bytes((j0_max, t), itemsize)
             + vmem_buffer_bytes((j0_max, c_col), 4))
    return 2 * blocks + temps


def tile_fused_gemm_spmm_wf0(cols0: jax.Array, vals0: jax.Array,
                             b: jax.Array, c: jax.Array,
                             *, t: int, interpret: bool | None = None):
    """Run wavefront 0.

    Args:
      cols0: (T0, j0_max, w) int32 tile-local ELL columns of fused A rows.
      vals0: (T0, j0_max, w) values.
      b: (T0*t, bCol) dense B (padded to a multiple of t).
      c: (bCol, cCol) dense C.
      t: uniform kernel tile size (rows of B / D1 per tile).
    Returns:
      d1: (T0*t, cCol) intermediate, rows0: (T0, j0_max, cCol) fused rows
      (caller scatters rows0 to D via the schedule's j_rows0).
    """
    return _tile_fused_gemm_spmm_wf0(cols0, vals0, b, c, t=t,
                                     interpret=resolve_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("t", "interpret"))
def _tile_fused_gemm_spmm_wf0(cols0, vals0, b, c, *, t: int, interpret: bool):
    n_tiles, j0_max, w = cols0.shape
    b_col, c_col = c.shape
    assert b.shape[0] == n_tiles * t, (b.shape, n_tiles, t)
    out_shape = (
        jax.ShapeDtypeStruct((n_tiles * t, c_col), b.dtype),
        jax.ShapeDtypeStruct((n_tiles, j0_max, c_col), b.dtype),
    )
    grid = (n_tiles,)
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, j0_max, w), lambda v: (v, 0, 0)),
            pl.BlockSpec((1, j0_max, w), lambda v: (v, 0, 0)),
            pl.BlockSpec((t, b_col), lambda v: (v, 0)),
            pl.BlockSpec((b_col, c_col), lambda v: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((t, c_col), lambda v: (v, 0)),
            pl.BlockSpec((1, j0_max, c_col), lambda v: (v, 0, 0)),
        ],
        out_shape=out_shape,
        compiler_params=compiler_params(),
        interpret=interpret,
        name="tile_fused_gemm_spmm",
    )(cols0, vals0, b, c)
