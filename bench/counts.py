"""Operations and bytes a call needs, from the problem's shapes alone.

The counts never read a schedule, a backend choice or the program's cost
model, so they are the same whatever ``auto`` resolves to.  FLOPs: 2·nnz·w
for a sparse product of width w, 2·m·k·n for a dense one.  Bytes: the
fused ideal, in which each distinct input of a product is read once, its
output written once, and no intermediate is counted.  Elementwise work
(activations, the loss, the optimizer update) is left out.  Both counts are
lower bounds on what any implementation moves, so a share of the roofline
above 100% means a count or a time is wrong.
"""
from __future__ import annotations

VALUE_BYTES = 4   # float32
INDEX_BYTES = 4   # int32 column ids and row pointers


def sparse_bytes(n_rows: int, nnz: int) -> int:
    """A CSR operand: a value and a column id per entry, and row pointers."""
    return nnz * (VALUE_BYTES + INDEX_BYTES) + (n_rows + 1) * INDEX_BYTES


def dense_bytes(*shape: int) -> int:
    out = VALUE_BYTES
    for s in shape:
        out *= s
    return out


def gcn_forward(n: int, nnz: int, dims: list) -> dict:
    """``H_l = A·(H_{l-1}·W_l)`` for each layer: one GeMM-SpMM pair each."""
    flops = byts = 0
    a = sparse_bytes(n, nnz)
    for k, m in zip(dims[:-1], dims[1:]):
        flops += 2 * n * k * m + 2 * nnz * m
        byts += a + dense_bytes(n, k) + dense_bytes(k, m) + dense_bytes(n, m)
    return {"flops": flops, "bytes": byts}


def gcn_train_step(n: int, nnz: int, dims: list) -> dict:
    """Forward, plus for each layer ``dW_l = H_{l-1}ᵀ·(Aᵀ·G_l)`` and, above
    the first layer, ``dH_{l-1} = Aᵀ·(G_l·W_lᵀ)``: each a fused pair."""
    out = gcn_forward(n, nnz, dims)
    a = sparse_bytes(n, nnz)
    for layer, (k, m) in enumerate(zip(dims[:-1], dims[1:])):
        # dW: reads A, G (n, m) and H (n, k); writes dW (k, m)
        out["flops"] += 2 * nnz * m + 2 * n * k * m
        out["bytes"] += (a + dense_bytes(n, m) + dense_bytes(n, k)
                         + dense_bytes(k, m))
        if layer > 0:
            # dH: reads A, G (n, m) and W (k, m); writes dH (n, k)
            out["flops"] += 2 * n * m * k + 2 * nnz * k
            out["bytes"] += (a + dense_bytes(n, m) + dense_bytes(k, m)
                             + dense_bytes(n, k))
    return out


def spmm_spmm(n: int, nnz: int, width: int) -> dict:
    """``D = A·(A·C)`` with one square A: two sparse products of ``width``;
    A is read once, C read once, D written once."""
    return {"flops": 2 * 2 * nnz * width,
            "bytes": sparse_bytes(n, nnz) + 2 * dense_bytes(n, width)}


def least_time_s(counts: dict, peak: dict) -> tuple:
    """The roofline's least time for ``counts`` on a chip with ``peak``,
    and which term binds (``"flops"`` or ``"bytes"``)."""
    t_f = counts["flops"] / peak["flops_per_s"]
    t_b = counts["bytes"] / peak["bytes_per_s"]
    return (t_f, "flops") if t_f >= t_b else (t_b, "bytes")
