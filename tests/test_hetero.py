"""Heterogeneous multi-relation fusion — the block-diagonal stack contract.

``hetero_fused_matmul`` must be indistinguishable from the per-relation
loop it replaces: same outputs (mixed rectangular relations, both op
pairs, every backend), one Algorithm-1 inspection per relation *set*
(not per call), gradients through the stacked custom_vjp, and
composition with ``spec.reorder``.  Plus the formats satellites the
stack leans on: ``block_diag_csr`` geometry and the dtype-correctness
fixes in ``from_coo`` / ``from_dense`` / ``csr_content_digest``.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.sparse.formats import (CSR, block_diag_csr,
                                       csr_content_digest)
from repro.core.tilefusion import api, hetero

SPEC = api.FusionSpec(p=2, cache_size=30_000.0, ct_size=32)


def _rect_csr(n_rows, n_cols, seed, density=0.15):
    rng = np.random.default_rng(seed)
    dense = ((rng.random((n_rows, n_cols)) < density)
             * rng.standard_normal((n_rows, n_cols)))
    return CSR.from_dense(dense)


def _mixed_relations(c_col=6, sparse_op1=False, seed=0):
    """Four relations with distinct rectangular shapes — the shapes a
    typed hetero graph actually produces."""
    rng = np.random.default_rng(seed)
    shapes = [(40, 36), (30, 30), (24, 32), (18, 18)]
    rels = []
    for i, (nj, ni) in enumerate(shapes):
        a = _rect_csr(nj, ni, seed=seed + i)
        if sparse_op1:
            nk = 20 + 4 * i
            a1 = _rect_csr(ni, nk, seed=seed + 10 + i, density=0.2)
            c = jnp.asarray(rng.standard_normal((nk, c_col)), jnp.float32)
            rels.append((a, a1, c))
        else:
            b_col = 4 + 2 * i
            b = jnp.asarray(rng.standard_normal((ni, b_col)), jnp.float32)
            c = jnp.asarray(rng.standard_normal((b_col, c_col)),
                            jnp.float32)
            rels.append((a, b, c))
    return rels


def _loop_oracle(rels):
    outs = []
    for a, op1, c in rels:
        mid = (np.asarray(op1.to_dense()) if isinstance(op1, CSR)
               else np.asarray(op1, np.float64))
        outs.append(a.to_dense() @ (mid @ np.asarray(c, np.float64)))
    return outs


@pytest.mark.parametrize("sparse_op1", [False, True],
                         ids=["gemm_spmm", "spmm_spmm"])
@pytest.mark.parametrize("backend", ["auto", "xla", "unfused"])
def test_hetero_fused_matches_loop(backend, sparse_op1):
    rels = _mixed_relations(sparse_op1=sparse_op1)
    got = hetero.hetero_fused_matmul(rels, backend=backend, spec=SPEC)
    want = _loop_oracle(rels)
    loop = hetero.hetero_loop_matmul(rels, backend=backend, spec=SPEC)
    assert len(got) == len(rels)
    for g, l, w, (a, _, _) in zip(got, loop, want, rels):
        assert g.shape == (a.n_rows, w.shape[1])
        np.testing.assert_allclose(np.asarray(g), w, rtol=2e-3, atol=2e-3)
        np.testing.assert_allclose(np.asarray(l), w, rtol=2e-3, atol=2e-3)


def test_hetero_single_inspection_per_relation_set():
    """The stack is the cache citizen: N relations cost ONE schedule
    entry, and repeat calls (fresh dense operands) re-stack and
    re-inspect nothing."""
    api.clear_schedule_cache()
    hetero.clear_stack_cache()
    rels = _mixed_relations()
    hetero.hetero_fused_matmul(rels, backend="xla", spec=SPEC)
    st = api.schedule_cache_stats()
    assert st["misses"] == 1
    rng = np.random.default_rng(99)
    rels2 = [(a, b, jnp.asarray(rng.standard_normal(c.shape), jnp.float32))
             for a, b, c in rels]
    hetero.hetero_fused_matmul(rels2, backend="xla", spec=SPEC)
    after = api.schedule_cache_stats()
    assert after["misses"] == 1 and after["hits"] >= st["hits"] + 1


def test_hetero_grad_matches_loop_reference():
    rels = _mixed_relations()
    adjs = [r[0] for r in rels]
    bs = [r[1] for r in rels]
    cs = [r[2] for r in rels]

    def fused_loss(bs_, cs_):
        outs = hetero.hetero_fused_matmul(
            list(zip(adjs, bs_, cs_)), backend="xla", spec=SPEC)
        return sum(jnp.sum(d ** 2) for d in outs)

    def loop_loss(bs_, cs_):
        outs = [api.tile_fused_matmul(a, b, c, backend="unfused", spec=SPEC)
                for a, b, c in zip(adjs, bs_, cs_)]
        return sum(jnp.sum(d ** 2) for d in outs)

    g_fused = jax.grad(fused_loss, argnums=(0, 1))(bs, cs)
    g_loop = jax.grad(loop_loss, argnums=(0, 1))(bs, cs)
    for got_set, want_set in zip(g_fused, g_loop):
        for g, w in zip(got_set, want_set):
            np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                       rtol=2e-3, atol=2e-3)


def test_hetero_composes_with_reorder():
    """``spec.reorder`` applies to the stacked square pattern like any
    other — outputs still match the loop oracle."""
    import dataclasses
    rels = _mixed_relations(sparse_op1=True, seed=3)
    spec = dataclasses.replace(SPEC, reorder="rcm")
    got = hetero.hetero_fused_matmul(rels, backend="xla", spec=spec)
    for g, w in zip(got, _loop_oracle(rels)):
        np.testing.assert_allclose(np.asarray(g), w, rtol=2e-3, atol=2e-3)


def test_hetero_input_validation():
    rels = _mixed_relations()
    with pytest.raises(ValueError, match="at least one"):
        hetero.hetero_fused_matmul([])
    with pytest.raises(ValueError, match="triple"):
        hetero.hetero_fused_matmul([rels[0][:2]])
    sparse = _mixed_relations(sparse_op1=True)
    with pytest.raises(ValueError, match="mix dense and sparse"):
        hetero.hetero_fused_matmul([rels[0], sparse[1]])
    a, b, c = rels[0]
    with pytest.raises(ValueError, match="c_col"):
        hetero.hetero_fused_matmul([rels[0], (rels[1][0], rels[1][1],
                                              rels[1][2][:, :3])])
    with pytest.raises(ValueError, match="rows"):
        hetero.hetero_fused_matmul([(a, b[:-1], c)])


def test_hetero_gcn_layer_matches_reference():
    from repro.models.hetero_gcn import HeteroGCNLayer, HeteroGraph
    counts = {"user": 30, "item": 24, "tag": 12}
    graph = HeteroGraph(
        node_counts=counts,
        relations={
            ("user", "buys", "item"): _rect_csr(24, 30, seed=1),
            ("item", "bought_by", "user"): _rect_csr(30, 24, seed=2),
            ("tag", "tags", "item"): _rect_csr(24, 12, seed=3),
            ("user", "follows", "user"): _rect_csr(30, 30, seed=4),
        })
    in_dims = {"user": 8, "item": 6, "tag": 4}
    layer = HeteroGCNLayer(graph, in_dims, out_dim=5, spec=SPEC,
                           backend="xla")
    rng = np.random.default_rng(0)
    params = layer.init_params(rng)
    feats = {t: jnp.asarray(rng.standard_normal((n, in_dims[t])),
                            jnp.float32) for t, n in counts.items()}
    got = layer(params, feats)
    want = layer.reference(params, feats)
    assert sorted(got) == sorted(want)
    for t in want:
        np.testing.assert_allclose(np.asarray(got[t]), np.asarray(want[t]),
                                   rtol=2e-3, atol=2e-3)
    # and it trains: grads through the fused layer match the loop oracle
    def loss(fn, p):
        return sum(jnp.sum(v ** 2) for v in fn(p, feats).values())
    g_fused = jax.grad(lambda p: loss(layer, p))(params)
    g_ref = jax.grad(lambda p: loss(layer.reference, p))(params)
    for k in params:
        np.testing.assert_allclose(np.asarray(g_fused[k]),
                                   np.asarray(g_ref[k]),
                                   rtol=2e-3, atol=2e-3, err_msg=str(k))


def test_block_diag_csr_geometry():
    a = _rect_csr(4, 3, seed=0, density=0.6)
    b = _rect_csr(2, 5, seed=1, density=0.6)
    out = block_diag_csr([a, b])
    want = np.zeros((6, 8))
    want[:4, :3] = a.to_dense()
    want[4:, 3:] = b.to_dense()
    np.testing.assert_array_equal(out.to_dense(), want)
    # padded placement: blocks sit at their offsets, pad rows/cols empty
    out = block_diag_csr([a, b], row_sizes=[5, 4], col_sizes=[5, 6])
    want = np.zeros((9, 11))
    want[:4, :5][:, :3] = a.to_dense()
    want[5:7, 5:] [:, :5] = b.to_dense()
    np.testing.assert_array_equal(out.to_dense(), want)
    with pytest.raises(ValueError):
        block_diag_csr([a, b], row_sizes=[3, 2])


def test_from_empty_inputs_preserve_dtype():
    """Satellite: an all-zero f32 dense (or an empty COO triplet) used to
    come back float64 — poisoning dtype-keyed caches downstream."""
    empty32 = CSR.from_dense(np.zeros((3, 4), np.float32))
    assert empty32.data.dtype == np.float32
    coo32 = CSR.from_coo(3, 4, [], [], [], dtype=np.float32)
    assert coo32.data.dtype == np.float32
    # list inputs coerce, and explicit dtype= wins over the values' type
    coo = CSR.from_coo(2, 2, [0, 1], [1, 0], [1.0, 2.0], dtype=np.float32)
    assert coo.data.dtype == np.float32


def test_content_digest_distinguishes_dtype():
    """Satellite: f32 and f64 matrices with identical values used to hash
    identically (values are digested as f64) — a bf16 and an f32 serving
    stream could alias one schedule entry."""
    a32 = CSR.from_dense(np.eye(4, dtype=np.float32))
    a64 = CSR.from_dense(np.eye(4, dtype=np.float64))
    assert csr_content_digest(a32) != csr_content_digest(a64)
    # same content, same dtype -> same digest (fresh instances)
    assert (csr_content_digest(CSR.from_dense(np.eye(4, dtype=np.float32)))
            == csr_content_digest(a32))
