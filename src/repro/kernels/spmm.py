"""Pallas ELL SpMM kernel: D[i] = sum_w vals[i, w] * X[cols[i, w]].

Used standalone (unfused baseline, wavefront-1 tiles) and as the second-op
code version inside the fused pipeline.  Rows are blocked over the grid; the
dense operand ``X`` is staged to VMEM in full (valid for the sizes this
framework feeds it: X = D1 tile or cCol-wide activations; auto dispatch
takes the XLA executor when ``vmem_bytes`` exceeds the VMEM budget).

The gather is expressed as a one-hot matmul over *column blocks* of X so the
MXU does the work (TPU has no efficient VMEM row-gather; DESIGN.md §2).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .config import compiler_params, resolve_interpret, vmem_buffer_bytes
from .tile_fused_gemm_spmm import densify_ell

#: Rows of the ELL per grid step.
BLOCK_ROWS = 256


def _kernel(cols_ref, vals_ref, x_ref, out_ref, *, n_rows_x: int):
    w_mat = densify_ell(cols_ref[...], vals_ref[...], n_rows_x)   # (bm, n)
    out_ref[...] = jnp.dot(w_mat, x_ref[...],
                           preferred_element_type=jnp.float32
                           ).astype(out_ref.dtype)


def vmem_bytes(block_rows: int, w: int, n: int, c: int,
               itemsize: int) -> int:
    """Scoped-VMEM working set of one grid step: every blocked operand
    (all of ``X`` included) double-buffered by the pipeline, plus the
    ``(block_rows, n)`` densified rows with their one-hot and the f32
    output block.  Grows with ``n``, not with nnz."""
    blocks = (vmem_buffer_bytes((block_rows, w), 4)
              + vmem_buffer_bytes((block_rows, w), itemsize)
              + vmem_buffer_bytes((n, c), itemsize)
              + vmem_buffer_bytes((block_rows, c), itemsize))
    temps = (2 * vmem_buffer_bytes((block_rows, n), itemsize)
             + vmem_buffer_bytes((block_rows, c), 4))
    return 2 * blocks + temps


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def _spmm_ell(cols: jax.Array, vals: jax.Array, x: jax.Array,
              *, block_rows: int, interpret: bool) -> jax.Array:
    n_rows, w = cols.shape
    n, c = x.shape
    # rows that don't fill the last block are padded with col=0/val=0 slots
    # (contribute nothing) and sliced off the output
    pad = -n_rows % block_rows
    if pad:
        cols = jnp.pad(cols, ((0, pad), (0, 0)))
        vals = jnp.pad(vals, ((0, pad), (0, 0)))
    grid = ((n_rows + pad) // block_rows,)
    out = pl.pallas_call(
        functools.partial(_kernel, n_rows_x=n),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_rows, w), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, w), lambda i: (i, 0)),
            pl.BlockSpec((n, c), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_rows, c), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_rows + pad, c), x.dtype),
        compiler_params=compiler_params(),
        interpret=interpret,
        name="spmm",
    )(cols, vals, x)
    return out[:n_rows] if pad else out


def spmm_ell(cols: jax.Array, vals: jax.Array, x: jax.Array,
             *, block_rows: int = BLOCK_ROWS,
             interpret: bool | None = None) -> jax.Array:
    """ELL SpMM.  cols/vals: (n_rows, w); x: (n, c).  Any n_rows (padded to a
    block_rows multiple internally)."""
    return _spmm_ell(cols, vals, x, block_rows=block_rows,
                     interpret=resolve_interpret(interpret))
