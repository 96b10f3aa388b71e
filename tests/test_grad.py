"""Gradient parity through the fused path — the custom_vjp contract.

``jax.grad`` through ``tile_fused_matmul`` must match the gradient of the
dense reference product on every backend × op-pair cell: the backward is
not XLA autodiff through the executors but the api's ``custom_vjp``.  The
GeMM-SpMM backward runs one plain transposed SpMM ``G = Aᵀ·Ḋ`` and derives
``dB = G·Cᵀ`` and ``dC = Bᵀ·G`` with dense GEMMs; the SpMM-SpMM backward
dispatches its transposed product back through the same seam (off schedule
entries cached with ``transpose=True``).  Alongside parity, the suite pins
the amortization contract — forward+backward of an N-layer GCN inspects
no backward schedule and re-inspects nothing across training steps, eager
and jitted — and the dtype-pricing satellite (bf16 operands price Eq-3
value traffic at 2 bytes, never 4).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from repro.configs.gcn import GCNConfig
from repro.core.sparse.formats import CSR
from repro.core.sparse.random import banded_spd, hub_powerlaw
from repro.core.tilefusion import api, cost_model
from repro.launch.steps import make_gcn_train_step
from repro.models.gcn import GCN

KNOBS = dict(p=2, cache_size=30_000.0, ct_size=32)

#: per-dtype allclose tolerance: f32 roundoff vs bf16's ~8-bit mantissa
#: (both sides of the comparison accumulate in the operand dtype)
DTYPES = {"f32": (jnp.float32, 2e-3), "bf16": (jnp.bfloat16, 1e-1)}

BACKENDS = ("pallas", "xla", "unfused", "sharded")


def _host_mesh() -> Mesh:
    """All devices on one 1-D axis (8 on the CI multi-device leg, 1 on a
    plain run — the trivial-mesh fallback)."""
    return Mesh(np.array(jax.devices()), ("shards",))


def _empty_rows(n: int, seed: int) -> CSR:
    dense = banded_spd(n, 3, seed=seed).to_dense()
    dense[::2, :] = 0.0
    return CSR.from_dense(dense)


PATTERNS = {
    "banded": lambda n, seed: banded_spd(n, 4, seed=seed),
    "powerlaw-hub": lambda n, seed: hub_powerlaw(n, 4, seed=seed),
    "empty-rows": _empty_rows,
}


def _grad_cell(a: CSR, op_pair: str, backend: str, dtype) -> tuple:
    """One grad-parity cell: (fused grads, dense-reference grads)."""
    rng = np.random.default_rng(7)
    n = a.n_rows
    ad = jnp.asarray(a.to_dense(), dtype)
    kwargs = dict(KNOBS)
    if backend == "sharded":
        kwargs["mesh"] = _host_mesh()
    # a fixed random cotangent (sum(w * D)) exercises the full backward
    # without the squared-loss magnitude blowup bf16 can't resolve
    if op_pair == "spmm":
        c = jnp.asarray(rng.standard_normal((n, 6)), dtype)
        w = jnp.asarray(rng.standard_normal((n, 6)), dtype)
        got = jax.grad(lambda c_: jnp.sum(
            w * api.tile_fused_matmul(a, a, c_, backend=backend,
                                      **kwargs)))(c)
        want = jax.grad(lambda c_: jnp.sum(w * (ad @ (ad @ c_))))(c)
        return (np.asarray(got, np.float32),), (np.asarray(want,
                                                           np.float32),)
    b = jnp.asarray(rng.standard_normal((n, 8)), dtype)
    c = jnp.asarray(rng.standard_normal((8, 6)), dtype)
    w = jnp.asarray(rng.standard_normal((n, 6)), dtype)
    got = jax.grad(lambda b_, c_: jnp.sum(
        w * api.tile_fused_matmul(a, b_, c_, backend=backend, **kwargs)),
        argnums=(0, 1))(b, c)
    want = jax.grad(lambda b_, c_: jnp.sum(w * (ad @ (b_ @ c_))),
                    argnums=(0, 1))(b, c)
    return (tuple(np.asarray(g, np.float32) for g in got),
            tuple(np.asarray(g, np.float32) for g in want))


@pytest.mark.parametrize("dtype_name", sorted(DTYPES))
@pytest.mark.parametrize("pattern", sorted(PATTERNS))
@pytest.mark.parametrize("op_pair", ["gemm", "spmm"])
def test_grad_parity_cell(op_pair, pattern, dtype_name):
    dtype, tol = DTYPES[dtype_name]
    a = PATTERNS[pattern](64, 3)
    for backend in BACKENDS:
        got, want = _grad_cell(a, op_pair, backend, dtype)
        for g, r in zip(got, want):
            np.testing.assert_allclose(
                g, r, rtol=tol, atol=tol,
                err_msg=f"{op_pair}/{backend}/{pattern}/{dtype_name}")


def test_backward_served_by_cached_transpose_schedule():
    """The GeMM-SpMM backward inspects no schedule: a grad call mints no
    transpose entry, and repeat calls add no schedule miss and no ELL
    miss (the transposed ELL is a cache citizen like the forward's)."""
    api.clear_schedule_cache()
    a = banded_spd(64, 4, seed=0)
    b = jnp.ones((64, 8), jnp.float32)
    c = jnp.ones((8, 4), jnp.float32)

    def loss(b_, c_):
        return jnp.sum(api.tile_fused_matmul(a, b_, c_, backend="xla",
                                             **KNOBS) ** 2)

    jax.grad(loss, argnums=(0, 1))(b, c)
    stats = api.schedule_cache_stats()
    assert stats["transpose_entries"] == 0
    jax.grad(loss, argnums=(0, 1))(b, c)
    after = api.schedule_cache_stats()
    assert after["misses"] == stats["misses"]
    assert after["ell_misses"] == stats["ell_misses"]
    assert after["transpose_entries"] == 0


@pytest.mark.parametrize("jit", [False, True])
def test_gcn_train_one_transpose_inspection_per_shape(jit):
    """Forward+backward of an N-layer GCN inspects no backward schedule —
    no transpose entry for any layer shape — and further training steps
    add no schedule miss and no ELL miss, eager and jitted alike."""
    api.clear_schedule_cache()
    cfg = GCNConfig(n_nodes=96, in_dim=16, hidden_dim=16, out_dim=8,
                    n_layers=3)
    adj = banded_spd(cfg.n_nodes, 4, seed=1)
    model = GCN(cfg, adj, **{k: v for k, v in KNOBS.items()})
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((cfg.n_nodes, cfg.in_dim)),
                    jnp.float32)
    y = jnp.asarray(rng.integers(0, cfg.out_dim, cfg.n_nodes))
    params = model.init_params(jax.random.PRNGKey(0))

    step = make_gcn_train_step(model, lr=0.1, jit=jit)
    params, loss0 = step(params, x, y)
    stats = api.schedule_cache_stats()
    assert stats["transpose_entries"] == 0
    for _ in range(3):
        params, loss = step(params, x, y)
    after = api.schedule_cache_stats()
    assert after["misses"] == stats["misses"], "training steps re-inspected"
    assert after["ell_misses"] == stats["ell_misses"], "steps repacked"
    assert after["transpose_entries"] == 0
    assert float(loss) < float(loss0), "SGD on fused grads went uphill"


@pytest.mark.parametrize("backend", ["xla", "unfused"])
def test_gemm_spmm_backward_runs_one_transposed_spmm(backend, monkeypatch):
    """One GeMM-SpMM grad runs the transposed SpMM exactly once and never
    re-enters ``tile_fused_matmul``: both cotangents come from the one
    ``Aᵀ·Ḋ``, and still match the dense reference."""
    a = hub_powerlaw(64, 4, seed=5)
    ad = jnp.asarray(a.to_dense(), jnp.float32)
    rng = np.random.default_rng(3)
    b = jnp.asarray(rng.standard_normal((64, 8)), jnp.float32)
    c = jnp.asarray(rng.standard_normal((8, 6)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((64, 6)), jnp.float32)
    fused, spmm = api.tile_fused_matmul, api._transpose_spmm
    calls = {"tile_fused_matmul": 0, "_transpose_spmm": 0}

    def counted(name, fn):
        def spy(*args, **kwargs):
            calls[name] += 1
            return fn(*args, **kwargs)
        return spy

    monkeypatch.setattr(api, "tile_fused_matmul",
                        counted("tile_fused_matmul", fused))
    monkeypatch.setattr(api, "_transpose_spmm",
                        counted("_transpose_spmm", spmm))
    got = jax.grad(lambda b_, c_: jnp.sum(
        w * fused(a, b_, c_, backend=backend, **KNOBS)),
        argnums=(0, 1))(b, c)
    assert calls == {"tile_fused_matmul": 0, "_transpose_spmm": 1}
    want = jax.grad(lambda b_, c_: jnp.sum(w * (ad @ (b_ @ c_))),
                    argnums=(0, 1))(b, c)
    for g, r in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                   rtol=2e-3, atol=2e-3)


def test_train_step_traffic_prices_one_spmm_and_two_gemms():
    """The backward's bytes are one ``Aᵀ·Ḋ`` SpMM at ``c_col`` plus the
    GEMMs ``G·Cᵀ`` and ``Bᵀ·G``, each reading its operands once and
    writing its output once — and pricing a GCN's step inspects nothing."""
    tm = cost_model.train_step_traffic(
        {"fused_bytes": 100.0}, nnz=10, n_i=4, n_j=3, b_col=2, c_col=5)
    # SpMM: (Ḋ 3×5 in + G 4×5 out + 10 values) × 4 B + 10 indices × 4 B
    assert tm["backward_spmm_bytes"] == 220.0
    # GEMMs: 2 × (G 4×5 + B or dB 4×2 + C or dC 2×5) × 4 B
    assert tm["backward_gemm_bytes"] == 304.0
    assert tm["backward_bytes"] == 524.0
    assert tm["train_step_bytes"] == 624.0

    api.clear_schedule_cache()
    cfg = GCNConfig(n_nodes=64, in_dim=8, hidden_dim=8, out_dim=4,
                    n_layers=2)
    model = GCN(cfg, banded_spd(cfg.n_nodes, 4, seed=4), **KNOBS)
    misses = api.schedule_cache_stats()["misses"]
    got = model.train_step_traffic_models()
    assert api.schedule_cache_stats()["misses"] == misses
    assert api.schedule_cache_stats()["transpose_entries"] == 0
    for e, t in zip(model.entries, got):
        assert t["forward_bytes"] == e.traffic_model["fused_bytes"]
        assert t["backward_spmm_bytes"] == cost_model.spmm_bytes(
            model.adj.nnz, 64, 64, e.c_col)


@pytest.mark.parametrize("dtype", [np.float32, np.float64])
def test_normalize_adjacency_preserves_dtype(dtype):
    """``normalize_adjacency`` must not silently upcast the adjacency to
    float64 (the degree arithmetic runs in f64): a float32 graph stays
    float32 all the way into the schedule cache, so nothing downstream
    hashes/packs a wide matrix that gets downcast per call."""
    from repro.models.gcn import normalize_adjacency
    a = banded_spd(32, 3, seed=0)
    a = CSR(a.n_rows, a.n_cols, a.indptr, a.indices,
            a.data.astype(dtype))
    out = normalize_adjacency(a)
    assert out.data.dtype == np.dtype(dtype)
    # and the normalization itself is right in either dtype
    deg = np.maximum(np.diff(a.indptr), 1).astype(np.float64)
    dinv = 1.0 / np.sqrt(deg)
    rows = np.repeat(np.arange(a.n_rows), np.diff(a.indptr))
    want = a.data.astype(np.float64) * dinv[rows] * dinv[a.indices]
    np.testing.assert_allclose(out.data.astype(np.float64), want,
                               rtol=1e-6)


def test_operand_dtype_bytes():
    assert cost_model.operand_dtype_bytes(jnp.ones((2,), jnp.float32)) == 4
    assert cost_model.operand_dtype_bytes(jnp.ones((2,), jnp.bfloat16)) == 2
    assert cost_model.operand_dtype_bytes(None, jnp.ones((2,),
                                                         jnp.float16)) == 2
    assert cost_model.operand_dtype_bytes() == 4


def test_dtype_pricing_splits_value_and_index_traffic():
    """bf16 entries price value traffic at 2 bytes while index traffic
    stays at 4 — so the bf16 fused-bytes prediction sits strictly between
    half the f32 one (all-value) and the f32 one (all-index)."""
    api.clear_schedule_cache()
    a = banded_spd(96, 4, seed=2)
    e32 = api.get_schedule(a, b_col=8, c_col=8, dtype_bytes=4, **KNOBS)
    e16 = api.get_schedule(a, b_col=8, c_col=8, dtype_bytes=2, **KNOBS)
    f32b, f16b = (e32.traffic_model["fused_bytes"],
                  e16.traffic_model["fused_bytes"])
    assert 0.5 * f32b < f16b < f32b
    assert e32.dtype_bytes == 4 and e16.dtype_bytes == 2
    # distinct cache entries: the second inspection was a miss, not a hit
    assert api.schedule_cache_stats()["misses"] >= 2
    # and the dispatch derives the key from the operands: a bf16 forward
    # hits the dtype_bytes=2 entry instead of minting a third
    misses = api.schedule_cache_stats()["misses"]
    api.tile_fused_matmul(a, jnp.ones((96, 8), jnp.bfloat16),
                          jnp.ones((8, 8), jnp.bfloat16), backend="xla",
                          **KNOBS)
    assert api.schedule_cache_stats()["misses"] == misses


def test_grad_under_mesh_trains():
    """The GCN training loop differentiates under a non-trivial ``mesh=``:
    the backward dispatches through the sharded executors (or their
    trivial-mesh fallback on a 1-device run) and still matches the dense
    reference."""
    cfg = GCNConfig(n_nodes=64, in_dim=8, hidden_dim=8, out_dim=4,
                    n_layers=2)
    adj = banded_spd(cfg.n_nodes, 4, seed=3)
    model = GCN(cfg, adj, **{k: v for k, v in KNOBS.items()})
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal((cfg.n_nodes, cfg.in_dim)),
                    jnp.float32)
    y = jnp.asarray(rng.integers(0, cfg.out_dim, cfg.n_nodes))
    params = model.init_params(jax.random.PRNGKey(1))
    mesh = _host_mesh()
    g_mesh = jax.grad(lambda p: model.loss(p, x, y, backend="sharded",
                                           mesh=mesh))(params)
    g_ref = jax.grad(lambda p: model.loss(p, x, y, backend="xla"))(params)
    for gm, gr in zip(g_mesh, g_ref):
        np.testing.assert_allclose(np.asarray(gm), np.asarray(gr),
                                   rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("reorder", ["rcm", "similarity", "auto"])
@pytest.mark.parametrize("op_pair", ["gemm", "spmm"])
def test_grad_parity_through_reordered_schedule(op_pair, reorder):
    """``jax.grad`` through a ``spec.reorder`` schedule matches the dense
    reference: the in/out permutations are linear (``jnp.take``), so the
    custom_vjp backward — served from the transpose-keyed entry, itself
    built under the same reorder knob — needs no special-casing."""
    from repro.core.sparse.random import powerlaw_graph
    a = powerlaw_graph(64, 5, seed=9)
    spec = api.FusionSpec(**KNOBS, reorder=reorder)
    rng = np.random.default_rng(11)
    ad = jnp.asarray(a.to_dense(), jnp.float32)
    w = jnp.asarray(rng.standard_normal((64, 6)), jnp.float32)
    for backend in ("xla", "unfused", "auto"):
        if op_pair == "spmm":
            c = jnp.asarray(rng.standard_normal((64, 6)), jnp.float32)
            got = jax.grad(lambda c_: jnp.sum(
                w * api.tile_fused_matmul(a, a, c_, backend=backend,
                                          spec=spec)))(c)
            want = jax.grad(lambda c_: jnp.sum(w * (ad @ (ad @ c_))))(c)
            pairs = [(got, want)]
        else:
            b = jnp.asarray(rng.standard_normal((64, 8)), jnp.float32)
            c = jnp.asarray(rng.standard_normal((8, 6)), jnp.float32)
            got = jax.grad(lambda b_, c_: jnp.sum(
                w * api.tile_fused_matmul(a, b_, c_, backend=backend,
                                          spec=spec)),
                argnums=(0, 1))(b, c)
            want = jax.grad(lambda b_, c_: jnp.sum(w * (ad @ (b_ @ c_))),
                            argnums=(0, 1))(b, c)
            pairs = list(zip(got, want))
        for g, r in pairs:
            np.testing.assert_allclose(
                np.asarray(g), np.asarray(r), rtol=2e-3, atol=2e-3,
                err_msg=f"{op_pair}/{backend}/reorder={reorder}")
