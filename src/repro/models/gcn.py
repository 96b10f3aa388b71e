"""GCN — the paper's native application, built on tile fusion.

One GCN layer is ``H' = σ(Â (H W))`` — exactly the paper's GeMM-SpMM with
``A = Â`` (normalized adjacency), ``B = H``, ``C = W``.  Every layer routes
through ``core.tilefusion.api.tile_fused_matmul``: the schedule is inspected
once per (graph, layer shape) and served from the content-keyed cache for
every subsequent layer and training step (paper §4.2.3 amortization).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..core.sparse.formats import CSR
from ..core.tilefusion import api
from ..trace import scope


def normalize_adjacency(a: CSR) -> CSR:
    """Â = D^{-1/2} (A) D^{-1/2} (self-loops assumed already present).

    The degree arithmetic runs in float64 for accuracy, but the result is
    cast back to ``a.data``'s dtype: a float32 (or bf16) adjacency must
    not silently become a float64 one, which would hash, pack, and price
    every downstream schedule at the wrong itemsize.

    Square adjacencies use the row degree on both sides (the classic
    symmetric normalization).  Rectangular ones — hetero-graph relations
    are ``(n_dst, n_src)`` — scale each side by its own axis degree:
    rows by out-neighbour count, columns by in-neighbour count."""
    deg = np.maximum(np.diff(a.indptr), 1).astype(np.float64)
    dinv = 1.0 / np.sqrt(deg)
    rows = np.repeat(np.arange(a.n_rows), np.diff(a.indptr))
    if a.n_rows == a.n_cols:
        cinv = dinv
    else:
        col_deg = np.maximum(
            np.bincount(a.indices, minlength=a.n_cols), 1).astype(
                np.float64)
        cinv = 1.0 / np.sqrt(col_deg)
    data = (a.data * dinv[rows] * cinv[a.indices]).astype(
        a.data.dtype, copy=False)
    return CSR(a.n_rows, a.n_cols, a.indptr, a.indices, data)


class GCN:
    """Tile-fused GCN on the unified dispatch API."""

    def __init__(self, cfg, adj: CSR, *, p: int = 8,
                 cache_size: float = 600_000.0, ct_size: int = 2048,
                 spec: api.FusionSpec | None = None):
        self.cfg = cfg
        self.adj = normalize_adjacency(adj)
        # one FusionSpec drives every layer's inspection and dispatch; the
        # scalar ctor knobs survive as sugar for the common case
        self.spec = spec if spec is not None else api.FusionSpec(
            p=p, cache_size=cache_size, ct_size=ct_size)
        self.p = self.spec.p
        self.cache_size = self.spec.cache_size
        self.ct_size = self.spec.ct_size
        # warm the inspector cache for every layer shape once per graph;
        # forward() then hits it for every layer and step
        dims = ([cfg.in_dim] + [cfg.hidden_dim] * (cfg.n_layers - 1)
                + [cfg.out_dim])
        self.dims = dims
        self.entries = [
            api.get_schedule(self.adj, b_col=dims[i], c_col=dims[i + 1],
                             spec=self.spec)
            for i in range(cfg.n_layers)]
        self.entry = self.entries[0]   # back-compat alias (layer 0)

    @property
    def sched(self):
        return self.entry.sched

    @property
    def dsched(self):
        return self.entry.dsched

    def layer_traffic_models(self) -> list:
        """Per-layer Eq-3 traffic models from the warmed entries — one dict
        per layer, not just layer 0 (the layers have different ``b_col`` /
        ``c_col`` and hence different fused savings)."""
        return [e.traffic_model for e in self.entries]

    def train_step_traffic_models(self) -> list:
        """Per-layer forward+backward traffic (``cost_model
        .train_step_traffic``): the forward entry's Eq-3 model, plus the
        backward's one ``Âᵀ·Ḋ`` SpMM and the two GEMMs that derive the
        cotangents from it."""
        from ..core.tilefusion import cost_model
        return [cost_model.train_step_traffic(
                    e.traffic_model, nnz=self.adj.nnz, n_i=self.adj.n_cols,
                    n_j=self.adj.n_rows, b_col=e.b_col, c_col=e.c_col,
                    dtype_bytes=e.dtype_bytes)
                for e in self.entries]

    def init_params(self, key):
        cfg = self.cfg
        dims = ([cfg.in_dim] + [cfg.hidden_dim] * (cfg.n_layers - 1)
                + [cfg.out_dim])
        ks = jax.random.split(key, cfg.n_layers)
        return [
            jax.random.normal(ks[i], (dims[i], dims[i + 1]), jnp.float32)
            / (dims[i] ** 0.5)
            for i in range(cfg.n_layers)
        ]

    def forward(self, params, x, *, fused: bool = True, impl: str = None,
                backend: str = None, mesh=None):
        """``backend=`` overrides directly; otherwise the legacy
        (fused, impl) pair maps onto the API's explicit backends.
        Differentiable end to end: under ``jax.grad`` each layer's
        backward runs the fused transposed products (api custom_vjp),
        including under a non-trivial ``mesh=``."""
        import dataclasses
        be = backend or ("unfused" if not fused
                         else "pallas" if impl == "pallas" else "xla")
        spec = (dataclasses.replace(self.spec, mesh=mesh)
                if mesh is not None else self.spec)
        for i, w in enumerate(params):
            with scope(f"gcn.layer{i}"):
                h = api.tile_fused_matmul(self.adj, x, w, backend=be,
                                          spec=spec)
                x = jax.nn.relu(h) if i < len(params) - 1 else h
        return x

    def loss(self, params, x, labels, *, fused: bool = True,
             backend: str = None, mesh=None):
        logits = self.forward(params, x, fused=fused, backend=backend,
                              mesh=mesh)
        with scope("gcn.loss"):
            logp = jax.nn.log_softmax(logits, axis=-1)
            return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], 1))
