"""Training-path benchmark: fused vs unfused GCN train steps, the
transpose-schedule cache, and a backward-parity gate.

Rows:

* ``train/gcn/{fused,unfused}`` — median wall time of one jitted
  train step (fwd + custom_vjp bwd + SGD update); derived
  ``train_step_ms`` is the headline column, plus the post-run loss.
* ``train/transpose_cache`` — an *eager* training loop so every layer's
  backward actually performs its transpose-schedule lookup (a jitted loop
  looks it up once at trace time); derived ``hit_rate`` is the fraction of
  those lookups served from cache and ``entries`` the live transpose
  entries (one per layer shape when amortization holds).
* ``train/grad_parity`` — max abs error of ``jax.grad`` through
  ``tile_fused_matmul`` vs the dense-reference gradient; threshold-gated
  in benchmarks/thresholds.json (smoke: the backward must stay correct,
  not just fast).
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import util
from repro.configs.gcn import GCNConfig
from repro.core.sparse.random import powerlaw_graph
from repro.core.tilefusion import api
from repro.launch.steps import make_gcn_train_step
from repro.models.gcn import GCN


def _setup(n: int):
    cfg = GCNConfig(n_nodes=n, in_dim=64, hidden_dim=64, out_dim=16,
                    n_layers=2)
    adj = powerlaw_graph(cfg.n_nodes, cfg.avg_degree, seed=0)
    model = GCN(cfg, adj, cache_size=300_000.0, ct_size=256)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((cfg.n_nodes, cfg.in_dim)),
                    jnp.float32)
    y = jnp.asarray(rng.integers(0, cfg.out_dim, cfg.n_nodes))
    params = model.init_params(jax.random.PRNGKey(0))
    return cfg, model, x, y, params


def run():
    n = util.bench_n(2048, smoke_n=256)
    cfg, model, x, y, params = _setup(n)

    # -- fused vs unfused step time --------------------------------------
    for fused in (True, False):
        step = make_gcn_train_step(model, lr=0.1, fused=fused)
        p, loss = step(params, x, y)            # compile + warm caches
        us = util.time_fn(lambda: step(p, x, y)[1])
        name = f"train/gcn/{'fused' if fused else 'unfused'}"
        yield (name, us,
               f"train_step_ms={us / 1e3:.3f};nodes={n};"
               f"loss={float(loss):.4f}")

    # -- transpose-cache hit rate (eager: each step really looks up) -----
    api.clear_schedule_cache()
    model = GCN(cfg, model.adj, cache_size=300_000.0, ct_size=256)
    step = make_gcn_train_step(model, lr=0.1, jit=False)
    steps = 2 if util.smoke() else 10
    p, _ = step(params, x, y)       # warming step (GeMM-SpMM mints none)
    tr0 = api.schedule_cache_stats()["transpose_entries"]
    t0 = time.perf_counter()
    for _ in range(steps):
        p, _ = step(p, x, y)
    us = (time.perf_counter() - t0) / steps * 1e6
    st = api.schedule_cache_stats()
    lookups = steps * cfg.n_layers
    misses = st["transpose_entries"] - tr0
    yield ("train/transpose_cache", us,
           f"hit_rate={1.0 - misses / lookups:.3f};"
           f"entries={st['transpose_entries']};lookups={lookups}")

    # -- backward parity gate --------------------------------------------
    a = model.adj
    rng = np.random.default_rng(1)
    b = jnp.asarray(rng.standard_normal((a.n_cols, 32)), jnp.float32)
    c = jnp.asarray(rng.standard_normal((32, 16)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((a.n_rows, 16)), jnp.float32)
    ad = jnp.asarray(a.to_dense(), jnp.float32)
    t0 = time.perf_counter()
    gb, gc = jax.grad(lambda b_, c_: jnp.sum(
        w * api.tile_fused_matmul(
            a, b_, c_, backend="xla",
            spec=api.FusionSpec(cache_size=300_000.0, ct_size=256))),
        argnums=(0, 1))(b, c)
    us = (time.perf_counter() - t0) * 1e6
    rb, rc = jax.grad(lambda b_, c_: jnp.sum(w * (ad @ (b_ @ c_))),
                      argnums=(0, 1))(b, c)
    err = max(float(jnp.abs(gb - rb).max()), float(jnp.abs(gc - rc).max()))
    yield ("train/grad_parity", us, f"max_err={err:.2e};nodes={n}")
