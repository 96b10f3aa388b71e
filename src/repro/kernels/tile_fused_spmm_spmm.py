"""Pallas TPU kernel for wavefront-0 fused tiles of SpMM-SpMM.

TPU adaptation of the paper's fused sparse-sparse code (Listing 3): one grid
step = one fused tile, grid steps independent — the wavefront-0 guarantee.
This is the sparse-op-1 twin of ``tile_fused_gemm_spmm.py``; the GeMM stage
is replaced by a *sparse gather* of the tile's op-1 rows:

Per tile ``v`` covering D1 rows ``[v*t, (v+1)*t)``:

  1. op-1 SpMM: the tile's op-1 rows arrive as hybrid-ELL body
     ``(t, w1)`` with *global* columns into ``C``; they are densified on the
     fly into a ``(t, n)`` one-hot matrix and multiplied against ``C`` on
     the MXU — the TPU form of the row gather (no efficient VMEM
     row-gather exists; gather-by-matmul keeps the systolic array busy).
     Hub-row tails past the hybrid width cap are *pre-accumulated* by the
     caller into ``d1_spill`` (a ``(t, cCol)`` delta per tile, zeros when
     nothing spills) and added here, so ``D1_t`` is exact while the ELL
     body stays cap-bounded — one pathological row no longer dictates the
     kernel's static width.
  2. Fused SpMM: identical to the GeMM-SpMM kernel — tile-local fused A
     rows densify from ELL into ``(j0_max, t)`` and multiply ``D1_t``.

``D1_t`` never leaves VMEM between the two stages; the ``pallas_call``
boundary is the paper's single synchronization barrier, after which
wavefront 1 runs over the spilled ``D1`` (``spmm.py``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .config import compiler_params, resolve_interpret, vmem_buffer_bytes
from .tile_fused_gemm_spmm import densify_ell


def _kernel(op1_cols_ref, op1_vals_ref, spill_ref, cols_ref, vals_ref,
            c_ref, d1_ref, rows_ref, *, n_c_rows: int):
    # ---- op-1 SpMM part: densify the tile's op-1 ELL body, gather C ----
    w1_mat = densify_ell(op1_cols_ref[0], op1_vals_ref[0], n_c_rows)  # (t, n)
    d1_t = jnp.dot(w1_mat, c_ref[...], preferred_element_type=jnp.float32)
    d1_t = d1_t + spill_ref[...]             # hub-row tails past the cap
    d1_ref[...] = d1_t.astype(d1_ref.dtype)

    # ---- fused SpMM part: tile-local A rows, multiply on MXU ----
    w0_mat = densify_ell(cols_ref[0], vals_ref[0], d1_t.shape[0])  # (j0, t)
    rows = jnp.dot(w0_mat, d1_t, preferred_element_type=jnp.float32)
    rows_ref[0] = rows.astype(rows_ref.dtype)


def vmem_bytes(t: int, w1: int, j0_max: int, w0: int, n: int, c_col: int,
               itemsize: int) -> int:
    """Scoped-VMEM working set of one grid step: every blocked operand
    (all of ``C`` included) double-buffered by the pipeline, plus the
    ``(t, n)`` densified op-1 rows and the ``(j0_max, t)`` densified A
    tile (each with its one-hot), the f32 D1 tile and fused rows.  Grows
    with ``n``, not with nnz."""
    blocks = (vmem_buffer_bytes((t, w1), 4)
              + vmem_buffer_bytes((t, w1), itemsize)
              + vmem_buffer_bytes((t, c_col), itemsize)
              + vmem_buffer_bytes((j0_max, w0), 4)
              + vmem_buffer_bytes((j0_max, w0), itemsize)
              + vmem_buffer_bytes((n, c_col), itemsize)
              + vmem_buffer_bytes((t, c_col), itemsize)
              + vmem_buffer_bytes((j0_max, c_col), itemsize))
    temps = (2 * vmem_buffer_bytes((t, n), itemsize)
             + 2 * vmem_buffer_bytes((j0_max, t), itemsize)
             + vmem_buffer_bytes((t, c_col), 4)
             + vmem_buffer_bytes((j0_max, c_col), 4))
    return 2 * blocks + temps


def tile_fused_spmm_spmm_wf0(op1_cols: jax.Array, op1_vals: jax.Array,
                             d1_spill: jax.Array,
                             cols0: jax.Array, vals0: jax.Array,
                             c: jax.Array, *, t: int,
                             interpret: bool | None = None):
    """Run wavefront 0 of SpMM-SpMM.

    Args:
      op1_cols: (T0, t, w1) int32 hybrid-ELL body columns of the op-1 rows,
        *global* into C (pad col 0 / val 0).
      op1_vals: (T0, t, w1) values.
      d1_spill: (T0*t, cCol) pre-accumulated spill delta — contributions of
        op-1 entries past the hybrid width cap (zeros when none spill).
      cols0: (T0, j0_max, w0) int32 tile-local ELL columns of fused A rows.
      vals0: (T0, j0_max, w0) values.
      c: (n, cCol) dense C, staged to VMEM in full per grid step.
      t: uniform kernel tile size (rows of D1 per tile).
    Returns:
      d1: (T0*t, cCol) intermediate, rows0: (T0, j0_max, cCol) fused rows
      (caller scatters rows0 to D via the schedule's j_rows0).
    """
    return _tile_fused_spmm_spmm_wf0(op1_cols, op1_vals, d1_spill, cols0,
                                     vals0, c, t=t,
                                     interpret=resolve_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("t", "interpret"))
def _tile_fused_spmm_spmm_wf0(op1_cols, op1_vals, d1_spill, cols0, vals0, c,
                              *, t: int, interpret: bool):
    n_tiles, t_in, w1 = op1_cols.shape
    assert t_in == t, (op1_cols.shape, t)
    _, j0_max, w0 = cols0.shape
    n, c_col = c.shape
    assert d1_spill.shape == (n_tiles * t, c_col), (d1_spill.shape, n_tiles, t)
    out_shape = (
        jax.ShapeDtypeStruct((n_tiles * t, c_col), c.dtype),
        jax.ShapeDtypeStruct((n_tiles, j0_max, c_col), c.dtype),
    )
    grid = (n_tiles,)
    return pl.pallas_call(
        functools.partial(_kernel, n_c_rows=n),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, t, w1), lambda v: (v, 0, 0)),
            pl.BlockSpec((1, t, w1), lambda v: (v, 0, 0)),
            pl.BlockSpec((t, c_col), lambda v: (v, 0)),
            pl.BlockSpec((1, j0_max, w0), lambda v: (v, 0, 0)),
            pl.BlockSpec((1, j0_max, w0), lambda v: (v, 0, 0)),
            pl.BlockSpec((n, c_col), lambda v: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((t, c_col), lambda v: (v, 0)),
            pl.BlockSpec((1, j0_max, c_col), lambda v: (v, 0, 0)),
        ],
        out_shape=out_shape,
        compiler_params=compiler_params(),
        interpret=interpret,
        name="tile_fused_spmm_spmm",
    )(op1_cols, op1_vals, d1_spill, cols0, vals0, c)
