"""Activation/param sharding rules threaded through the models.

Models are pure functions; distribution is expressed as optional
``PartitionSpec`` constraints applied at the few points where GSPMD
propagation needs an anchor.  ``rules=None`` (smoke tests, single device)
makes every constraint a no-op.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Optional

import jax
from jax.sharding import NamedSharding, PartitionSpec as P


def mesh_row_repl_axes(mesh, layout: str = "1d") -> tuple:
    """Split a mesh's axis names into (row_axes, repl_axes, depth_axes)
    for the sharded tile-fusion executors.

    ``"1d"`` flattens every axis into the row-block dimension (repl and
    depth empty — the pre-2-D behavior for any mesh rank); ``"1.5d"``
    keeps the leading axis for row blocks and hands the trailing axes to
    the dense operand's column replicas; ``"2.5d"`` additionally peels the
    axes past the second into a depth dimension that replicates the
    wavefront-0 compute and splits wavefront-1 halo work.  Halo
    all-gathers run over ``row_axes`` only; depth layers combine their
    partial outputs with a psum over ``depth_axes``; the column-replica
    groups never exchange bytes — their column slices are independent by
    construction.  The split is derived from
    ``scheduler.resolve_mesh_layout`` — the one place the layout rule
    lives — so the executor's axis use can never disagree with the
    partitioner's shard counts; a 1-D mesh has nothing to replicate over,
    so every layout degenerates to (all axes, (), ())."""
    import numpy as np

    from ..core.tilefusion.scheduler import resolve_mesh_layout

    names = tuple(str(n) for n in mesh.axis_names)
    _, n_repl, n_depth = resolve_mesh_layout(np.shape(mesh.devices), layout)
    if n_depth > 1:
        return names[:1], names[1:2], names[2:]
    if n_repl > 1:
        return names[:1], names[1:], ()
    return names, (), ()


@dataclasses.dataclass(frozen=True)
class ShardingRules:
    """Logical-axis assignment.  ``batch_axes`` composes ("pod","data")."""
    batch_axes: tuple = ("data",)
    model_axis: str = "model"
    # whether attention heads divide the model axis (else heads replicate)
    shard_heads: bool = True
    # mesh handle for shard_map'd layers (MoE dispatch); None = single-device
    mesh: object = None

    @property
    def act_btd(self) -> P:   # (batch, seq, d_model)
        return P(self.batch_axes, None, None)

    @property
    def act_btf(self) -> P:   # (batch, seq, d_ff) — ffn hidden
        return P(self.batch_axes, None, self.model_axis)

    @property
    def act_bhtd(self) -> P:  # (batch, heads, seq, head_dim)
        # §Perf iteration 2: head_dim-sharding for non-divisible head counts
        # was REFUTED — it triggers SPMD involuntary full rematerialization
        # in the GQA QK dot (resharding storms).  Replicated-head attention
        # costs duplicate attention FLOPs on the model axis but removes the
        # TB-scale resharding collectives.
        if self.shard_heads:
            return P(self.batch_axes, self.model_axis, None, None)
        return P(self.batch_axes, None, None, None)

    @property
    def logits(self) -> P:    # (batch, seq, vocab)
        return P(self.batch_axes, None, self.model_axis)


def shard(x: jax.Array, spec: Optional[P]) -> jax.Array:
    if spec is None:
        return x
    return jax.lax.with_sharding_constraint(x, spec)


# --------------------------------------------------------------------------
# Parameter shardings: path-pattern -> PartitionSpec.  Matmul weights shard
# their contraction-free big axis over "model"; everything else replicates.
# Leading scan (layer-stack) axes are unsharded.
# --------------------------------------------------------------------------
_PARAM_RULES = [
    (r"embed", lambda nd: P(*([None] * (nd - 2) + ["model", None]))),   # (vocab, d)
    (r"(lm_head|w_out_proj)", lambda nd: P(*([None] * (nd - 2) + [None, "model"]))),
    # NOTE: sLSTM's w_rec is deliberately NOT here — it contracts inside the
    # per-timestep scan; sharding it would emit one all-reduce per timestep.
    (r"(wq|wk|wv|w_up|w_gate|w_in|w1|w3)$",
     lambda nd: P(*([None] * (nd - 2) + [None, "model"]))),
    (r"(wo|w_down|w2)$", lambda nd: P(*([None] * (nd - 2) + ["model", None]))),
    (r"(router|w_dkv|w_uk|w_uv|w_dq|w_uq)$", lambda nd: P()),
]


def param_spec(path: str, ndim: int) -> P:
    for pat, fn in _PARAM_RULES:
        if re.search(pat, path):
            if ndim >= 2:
                return fn(ndim)
            return P()
    return P()


def param_shardings(params, mesh) -> object:
    """Pytree of NamedSharding matching ``params`` (works on shape trees)."""
    def one(path, leaf):
        name = "/".join(str(getattr(k, "key", k)) for k in path)
        nd = len(leaf.shape)
        spec = param_spec(name, nd)
        # guard divisibility: replicate anything that doesn't divide
        axis_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        dims = list(spec)
        ok = True
        for d, ax in enumerate(dims):
            if ax is None:
                continue
            sz = axis_sizes.get(ax, 1)
            if d < nd and leaf.shape[d] % sz != 0:
                ok = False
        if not ok:
            spec = P()
        return NamedSharding(mesh, spec)
    return jax.tree_util.tree_map_with_path(one, params)
