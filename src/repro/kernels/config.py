"""Single place the Pallas kernels resolve their ``interpret`` default.

Compiled Pallas lowering exists for TPU (Mosaic); on the CPU backend the
kernels run in interpret mode (kernel body executed as XLA ops — same
numerics, same blocking).  Kernels take ``interpret=None`` and resolve it
here so a real backend never silently falls into interpret mode.

``PALLAS_INTERPRET=0/1`` force-overrides in either direction (used by the
kernel tests to pin a mode regardless of backend).

The scoped-VMEM budget lives here too: the tile-fusion kernels hand it to
Mosaic as ``vmem_limit_bytes`` and dispatch checks each kernel's working
set against it, so the limit a kernel compiles under and the limit auto
dispatch admits it by can never drift apart.
"""
from __future__ import annotations

import os

import jax

#: Scoped-VMEM limit of the tile-fusion kernels: half of v5e's 128 MiB per
#: TensorCore (Mosaic's default scoped limit is 16 MiB).
VMEM_BUDGET = 64 * 1024 * 1024


def compiler_params():
    """Mosaic compiler parameters shared by the tile-fusion kernels."""
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.CompilerParams(vmem_limit_bytes=VMEM_BUDGET)


def vmem_buffer_bytes(shape, itemsize: int) -> int:
    """Bytes one VMEM buffer of ``shape`` occupies: the trailing two dims
    pad to the (sublane, 128-lane) tile, 8 sublanes of 32-bit words."""
    *lead, rows, lanes = (1, 1) + tuple(int(d) for d in shape)
    sub = 8 * max(4 // int(itemsize), 1)
    n = -(-rows // sub) * sub * (-(-lanes // 128) * 128)
    for d in lead:
        n *= d
    return n * int(itemsize)


def default_interpret() -> bool:
    env = os.environ.get("PALLAS_INTERPRET")
    if env is not None:
        return env == "1"
    # only TPU has a compiled (Mosaic) lowering for these kernels; CPU *and*
    # GPU interpret (the kernels use pltpu scratch shapes — no Triton path)
    return jax.default_backend() != "tpu"


def resolve_interpret(interpret: bool | None) -> bool:
    return default_interpret() if interpret is None else bool(interpret)


def compiled_or_forced() -> bool:
    """Capability gate for dispatching *to* the Pallas kernels: a compiled
    (Mosaic) lowering exists, or interpret mode was explicitly forced via
    ``PALLAS_INTERPRET=1`` (CI parity runs).  Interpret mode is never a
    perf win, so plain CPU/GPU — where ``default_interpret`` silently
    interprets — does not qualify; it must be opted into.  Owned here so
    the dispatch gate can never drift from how the kernels themselves
    resolve their mode."""
    if os.environ.get("PALLAS_INTERPRET") == "1":
        return True
    return jax.default_backend() == "tpu"
