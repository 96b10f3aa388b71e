"""Plain references the program's results are compared with.

Written from the layer equations in ``jax.numpy``, in float32, with no
kernel, schedule or cache, and nothing imported from the program.  A
sparse product is a gather, a multiply and a scatter-add over blocks of
entries, so it fits the chip at any size.  Two precisions:

- ``"highest"``, the reference: dense products at
  ``Precision.HIGHEST`` and exact float32 multiplies in sparse products;
- ``"high"``, the control: what a three-pass bfloat16 product gives
  (``Precision.HIGH``), taken explicitly: each operand is split into a
  bfloat16 high part and a bfloat16 low part, and the low-by-low term is
  dropped, in dense and sparse products alike.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

MODES = ("highest", "high")
#: Entries per block of a sparse product: at 256 columns a block's gather
#: is 256 MiB.
CHUNK = 1 << 18


def _check_mode(mode: str) -> str:
    if mode not in MODES:
        raise ValueError(f"precision mode {mode!r}; expected one of {MODES}")
    return mode


def _round_bf16(x):
    """``x`` rounded to the nearest bfloat16 (ties to even), kept in
    float32.  Done on the bits, since a compiler may drop a float32 to
    bfloat16 to float32 round trip as excess precision."""
    u = lax.bitcast_convert_type(x, jnp.uint32)
    u = (u + jnp.uint32(0x7FFF) + ((u >> 16) & jnp.uint32(1))) \
        & jnp.uint32(0xFFFF0000)
    return lax.bitcast_convert_type(u, jnp.float32)


def _split(x):
    hi = _round_bf16(x)
    return hi, _round_bf16(x - hi)


def _mul(v, x, mode: str):
    if _check_mode(mode) == "highest":
        return v * x
    vh, vl = _split(v)
    xh, xl = _split(x)
    return vh * xh + (vh * xl + vl * xh)


def matmul(a, b, mode: str):
    """A dense product; ``"high"`` takes the three bfloat16 passes
    explicitly, so it reads the same on every platform."""
    hi = lax.Precision.HIGHEST
    if _check_mode(mode) == "highest":
        return jnp.matmul(a, b, precision=hi)
    ah, al = _split(a)
    bh, bl = _split(b)
    return (jnp.matmul(ah, bh, precision=hi)
            + (jnp.matmul(ah, bl, precision=hi)
               + jnp.matmul(al, bh, precision=hi)))


@functools.partial(jax.jit, static_argnames=("n_out", "mode"))
def _spmm(rows, cols, vals, x, *, n_out: int, mode: str):
    def body(i, acc):
        r = lax.dynamic_slice(rows, (i * CHUNK,), (CHUNK,))
        c = lax.dynamic_slice(cols, (i * CHUNK,), (CHUNK,))
        v = lax.dynamic_slice(vals, (i * CHUNK,), (CHUNK,))
        return acc.at[r].add(_mul(v[:, None], x[c], mode), mode="drop")
    acc = jnp.zeros((n_out, x.shape[1]), jnp.float32)
    return lax.fori_loop(0, rows.shape[0] // CHUNK, body, acc)


class Sparse:
    """A square sparse matrix on the device as padded COO blocks, for
    ``A·x`` and ``Aᵀ·x``."""

    def __init__(self, g: tuple):
        n, indptr, indices, data = g
        nnz = indices.shape[0]
        pad = -nnz % CHUNK
        rows = np.repeat(np.arange(n, dtype=np.int32), np.diff(indptr))
        # padding entries scatter to row n, which the product drops
        self.n = n
        self.rows = jnp.asarray(np.concatenate([rows, np.full(pad, n,
                                                             np.int32)]))
        self.cols = jnp.asarray(np.concatenate([indices.astype(np.int32),
                                                np.zeros(pad, np.int32)]))
        self.vals = jnp.asarray(np.concatenate([data.astype(np.float32),
                                                np.zeros(pad, np.float32)]))
        # for the transpose the padding must land out of range the other way
        self.cols_t = self.cols.at[nnz:].set(n)
        self.rows_t = self.rows.at[nnz:].set(0)

    def matmul(self, x, mode: str):
        """``A·x``."""
        return _spmm(self.rows, self.cols, self.vals, x, n_out=self.n,
                     mode=mode)

    def rmatmul(self, x, mode: str):
        """``Aᵀ·x``."""
        return _spmm(self.cols_t, self.rows_t, self.vals, x, n_out=self.n,
                     mode=mode)


def gcn_forward(a: Sparse, x, params, mode: str):
    """Logits and the pre-activations ``Z_l = A·(H_{l-1}·W_l)``;
    ``H_l = relu(Z_l)`` below the last layer."""
    zs, h = [], x
    for i, w in enumerate(params):
        z = a.matmul(matmul(h, w, mode), mode)
        zs.append(z)
        h = jax.nn.relu(z) if i < len(params) - 1 else z
    return h, zs


def gcn_loss_and_grads(a: Sparse, x, y, params, mode: str):
    """Mean cross-entropy and its gradient for each weight, by the chain
    rule written out: ``G_L = (softmax(Z_L) - onehot(y)) / n``,
    ``dW_l = H_{l-1}ᵀ·(Aᵀ·G_l)`` and
    ``G_{l-1} = ((Aᵀ·G_l)·W_lᵀ) ⊙ [Z_{l-1} > 0]``."""
    logits, zs = gcn_forward(a, x, params, mode)
    logp = jax.nn.log_softmax(logits, axis=-1)
    n = logits.shape[0]
    loss = -jnp.mean(jnp.take_along_axis(logp, y[:, None], 1))
    g = (jnp.exp(logp) - jax.nn.one_hot(y, logits.shape[1],
                                        dtype=jnp.float32)) / n
    grads = [None] * len(params)
    for i in range(len(params) - 1, -1, -1):
        s = a.rmatmul(g, mode)
        h_in = x if i == 0 else jax.nn.relu(zs[i - 1])
        grads[i] = matmul(h_in.T, s, mode)
        if i:
            g = matmul(s, params[i].T, mode) * (zs[i - 1] > 0)
    return loss, grads


def gcn_sgd(a: Sparse, x, y, params, lr: float, steps: int, mode: str):
    """``steps`` SGD steps from ``params``: the losses, the first step's
    gradients, and the parameters after each step (host arrays)."""
    losses, params_after, first = [], [], None
    params = [jnp.asarray(p) for p in params]
    for _ in range(steps):
        loss, grads = gcn_loss_and_grads(a, x, y, params, mode)
        if first is None:
            first = [np.asarray(g) for g in grads]
        params = [p - lr * g for p, g in zip(params, grads)]
        losses.append(float(loss))
        params_after.append([np.asarray(p) for p in params])
    return {"losses": losses, "grads": first, "params": params_after}


class Diagonals:
    """A square matrix stored as its few nonzero diagonals, for ``A·x``: a
    sum of shifted rows, with no scatter, so a chain of products at a
    million rows stays short.  Fits a banded or a stencil matrix, whose
    entries lie on a handful of offsets from the main diagonal."""

    def __init__(self, g: tuple):
        n, indptr, indices, data = g
        rows = np.repeat(np.arange(n), np.diff(indptr))
        off = indices.astype(np.int64) - rows
        self.offsets, which = np.unique(off, return_inverse=True)
        diags = np.zeros((self.offsets.shape[0], n), np.float32)
        diags[which, rows] = data
        self.n = n
        self.diags = jnp.asarray(diags)

    def matmul(self, x, mode: str):
        """``A·x``."""
        return _diagonals(self.diags, x,
                          offsets=tuple(int(o) for o in self.offsets),
                          mode=mode)


@functools.partial(jax.jit, static_argnames=("offsets", "mode"))
def _diagonals(diags, x, *, offsets: tuple, mode: str):
    n = x.shape[0]
    b = max(abs(o) for o in offsets)
    xp = jnp.pad(x, ((b, b), (0, 0)))
    out = jnp.zeros_like(x)
    for k, d in enumerate(offsets):
        out = out + _mul(diags[k][:, None], xp[b + d:b + d + n], mode)
    return out


def chain_product(a, c, mode: str):
    """``A·(A·C)`` for a ``Sparse`` or ``Diagonals`` A."""
    return a.matmul(a.matmul(c, mode), mode)
