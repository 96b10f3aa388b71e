"""Public jit'd entry points for the Pallas kernels.

Dispatch policy: on TPU the kernels run compiled (interpret=False); on the
CPU backend they run in interpret mode (kernel body executed as XLA ops) —
same numerics, same blocking.  The choice is made once, in
``config.default_interpret`` (``PALLAS_INTERPRET`` can force either).
Each op also exposes an ``impl="xla"`` escape hatch used by the dry-run
(representative HLO without a TPU custom-call).  ``VMEM_BUDGET``
(``config``) is the scoped-VMEM limit the tile-fusion kernels compile under.
"""
from __future__ import annotations

from . import ref
from .config import VMEM_BUDGET
from .config import default_interpret as _interpret
from .fused_ffn import fused_ffn as _fused_ffn_pallas
from .flash_attention import flash_attention as _flash_pallas
from .moe import fused_moe_ffn as _moe_pallas
from .spmm import BLOCK_ROWS
from .spmm import spmm_ell as _spmm_pallas
from .tile_fused_gemm_spmm import tile_fused_gemm_spmm_wf0 as _tf_pallas
from .tile_fused_gemm_spmm import vmem_bytes as _tf_vmem_bytes
from .tile_fused_spmm_spmm import tile_fused_spmm_spmm_wf0 as _tfss_pallas


def choose_kernel_tile(b_col: int, c_col: int, j0_max: int, w: int,
                       dtype_bytes: int = 4,
                       budget: int = VMEM_BUDGET) -> int:
    """TPU form of the paper's step-2 splitting: the largest 128-aligned
    uniform tile size t whose VMEM working set
    (``tile_fused_gemm_spmm.vmem_bytes``) fits the budget."""
    t = best = 128
    while t <= 8192 and _tf_vmem_bytes(j0_max, w, t, b_col, c_col,
                                       dtype_bytes) <= budget:
        best = t
        t *= 2
    return best


def tile_fused_gemm_spmm_wf0(cols0, vals0, b, c, *, t: int,
                             impl: str = "pallas"):
    if impl == "xla":
        return ref.tile_fused_gemm_spmm_wf0(cols0, vals0, b, c, t=t)
    return _tf_pallas(cols0, vals0, b, c, t=t, interpret=_interpret())


def tile_fused_spmm_spmm_wf0(op1_cols, op1_vals, d1_spill, cols0, vals0, c,
                             *, t: int, impl: str = "pallas"):
    if impl == "xla":
        return ref.tile_fused_spmm_spmm_wf0(op1_cols, op1_vals, d1_spill,
                                            cols0, vals0, c, t=t)
    return _tfss_pallas(op1_cols, op1_vals, d1_spill, cols0, vals0, c, t=t,
                        interpret=_interpret())


def spmm_ell(cols, vals, x, *, block_rows: int = BLOCK_ROWS,
             impl: str = "pallas"):
    if impl == "xla":
        return ref.spmm_ell(cols, vals, x)
    return _spmm_pallas(cols, vals, x, block_rows=block_rows,
                        interpret=_interpret())


def fused_ffn(x, w1, w2, *, block_m: int = 256, block_f: int = 512,
              act: str = "gelu", impl: str = "pallas"):
    m, _ = x.shape
    f = w1.shape[1]
    if impl == "xla" or m % block_m or f % block_f:
        return ref.ffn(x, w1, w2, act=act)
    return _fused_ffn_pallas(x, w1, w2, block_m=block_m, block_f=block_f,
                             act=act, interpret=_interpret())


def fused_moe_ffn(x, w1, w2, *, block_c: int = 128, block_f: int = 512,
                  act: str = "silu", impl: str = "pallas"):
    _, cap, _ = x.shape
    f = w1.shape[2]
    if impl == "xla" or cap % block_c or f % block_f:
        return ref.moe_ffn(x, w1, w2, act=act)
    return _moe_pallas(x, w1, w2, block_c=block_c, block_f=block_f,
                       act=act, interpret=_interpret())


def flash_attention(q, k, v, *, block_q: int = 128, block_k: int = 128,
                    causal: bool = True, window: int = 0,
                    sm_scale: float | None = None, impl: str = "pallas"):
    sq, sk = q.shape[2], k.shape[2]
    if impl == "xla" or sq % block_q or sk % block_k:
        return ref.attention(q, k, v, causal=causal, window=window,
                             sm_scale=sm_scale)
    return _flash_pallas(q, k, v, block_q=block_q, block_k=block_k,
                         causal=causal, window=window, sm_scale=sm_scale,
                         interpret=_interpret())
