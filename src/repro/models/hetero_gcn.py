"""Hetero-GCN — RGCN-style relational convolution on the fused stack.

One layer computes, per destination node type ``dt``,

    ``out[dt] = σ( Σ_{r : dst(r) = dt}  Â_r · (X[src(r)] · W_r) )``

— one normalized-adjacency GeMM-SpMM per relation, summed over the
relations that share a destination type.  The whole bundle of
per-relation products runs as ONE ``hetero_fused_matmul`` dispatch
(block-diagonal stack, single Algorithm-1 inspection, single kernel
launch) instead of the N small SpMMs an HGT/RGCN loop would issue; the
per-relation outputs come back un-stacked and are summed per type.

The layer is functional in its parameters (a dict of per-relation
weight matrices) so ``jax.grad`` flows through the fused custom_vjp.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from ..core.tilefusion import api, hetero
from .gcn import normalize_adjacency


@dataclasses.dataclass(frozen=True)
class HeteroGraph:
    """A typed multi-relation graph.

    ``relations`` maps ``(src_type, name, dst_type)`` to the relation's
    adjacency (``(n_dst, n_src)`` CSR); ``node_counts`` gives each node
    type's cardinality.  Relation order is the sorted key order — the
    deterministic stacking order of the fused dispatch."""

    node_counts: dict
    relations: dict

    def __post_init__(self):
        for (src, _, dst), a in self.relations.items():
            if a.n_rows != self.node_counts[dst]:
                raise ValueError(f"adjacency of {(src, _, dst)} has "
                                 f"{a.n_rows} rows; dst type {dst!r} has "
                                 f"{self.node_counts[dst]} nodes")
            if a.n_cols != self.node_counts[src]:
                raise ValueError(f"adjacency of {(src, _, dst)} has "
                                 f"{a.n_cols} cols; src type {src!r} has "
                                 f"{self.node_counts[src]} nodes")

    @property
    def rel_keys(self):
        return sorted(self.relations)


class HeteroGCNLayer:
    """One relational convolution layer on the fused hetero dispatch."""

    def __init__(self, graph: HeteroGraph, in_dims: dict, out_dim: int, *,
                 spec: api.FusionSpec | None = None, backend: str = "auto",
                 activation=jax.nn.relu):
        self.graph = graph
        self.in_dims = dict(in_dims)
        self.out_dim = int(out_dim)
        self.spec = spec if spec is not None else api.FusionSpec()
        self.backend = backend
        self.activation = activation
        # symmetric-normalized adjacencies, fixed stacking order
        self.rel_keys = graph.rel_keys
        self.adjs = {k: normalize_adjacency(graph.relations[k])
                     for k in self.rel_keys}
        # warm the one stacked schedule (and its cache entry) up front —
        # the hetero analogue of GCN.__init__'s per-layer warmup
        stack = hetero.stack_adjacencies([self.adjs[k]
                                          for k in self.rel_keys])
        b_col = sum(self.in_dims[k[0]] for k in self.rel_keys)
        self.entry = api.get_schedule(stack.a, b_col=b_col,
                                      c_col=self.out_dim, spec=self.spec)

    def init_params(self, rng: np.random.Generator) -> dict:
        """Glorot-ish per-relation weights ``W_r`` of shape
        ``(in_dims[src(r)], out_dim)``."""
        params = {}
        for key in self.rel_keys:
            fan_in = self.in_dims[key[0]]
            scale = float(np.sqrt(2.0 / (fan_in + self.out_dim)))
            params[key] = jnp.asarray(
                rng.standard_normal((fan_in, self.out_dim)) * scale,
                jnp.float32)
        return params

    def __call__(self, params: dict, feats: dict) -> dict:
        """``feats`` maps node type -> ``(n_type, in_dims[type])`` array;
        returns per-destination-type activations."""
        relations = [(self.adjs[k], feats[k[0]], params[k])
                     for k in self.rel_keys]
        outs = hetero.hetero_fused_matmul(relations, backend=self.backend,
                                          spec=self.spec)
        by_dst: dict = {}
        for key, d_r in zip(self.rel_keys, outs):
            dst = key[2]
            by_dst[dst] = d_r if dst not in by_dst else by_dst[dst] + d_r
        if self.activation is not None:
            by_dst = {t: self.activation(v) for t, v in by_dst.items()}
        return by_dst

    def reference(self, params: dict, feats: dict) -> dict:
        """The per-relation loop oracle (unfused dispatch per relation) —
        what the fused layer must reproduce exactly."""
        by_dst: dict = {}
        for key in self.rel_keys:
            d_r = api.tile_fused_matmul(self.adjs[key], feats[key[0]],
                                        params[key], backend="unfused",
                                        spec=self.spec)
            dst = key[2]
            by_dst[dst] = d_r if dst not in by_dst else by_dst[dst] + d_r
        if self.activation is not None:
            by_dst = {t: self.activation(v) for t, v in by_dst.items()}
        return by_dst
