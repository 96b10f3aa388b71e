"""Quickstart: the paper's technique in 60 seconds.

  PYTHONPATH=src python examples/quickstart.py

Inspects a tile-fusion schedule for a graph matrix through the unified
dispatch API, validates the fused GeMM-SpMM against the unfused oracle,
prints schedule quality metrics, shows the inspector cache amortizing, and
trains a 2-layer GCN (the paper's native workload) for a few steps.
"""
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import gcn as gcn_cfg
from repro.core.sparse.random import banded_spd, powerlaw_graph
from repro.core.tilefusion import api, fused_ref
from repro.models.gcn import GCN

# ---- 1. inspect a GeMM-SpMM schedule: D = A (B C) ----
# banded SPD = the paper's scientific-computing matrix group (group I);
# swap in powerlaw_graph(...) for the graph group (lower fused ratio)
n, bcol, ccol = 2048, 64, 64
a = banded_spd(n, bandwidth=8, seed=0)
spec = api.FusionSpec(p=8, cache_size=300_000.0, ct_size=512)
entry = api.get_schedule(a, b_col=bcol, c_col=ccol, spec=spec)
sched = entry.sched
print(f"matrix: {n}x{n}, nnz={a.nnz}")
print(f"schedule: {len(sched.wavefronts[0])} fused tiles + "
      f"{len(sched.wavefronts[1])} wavefront-1 tiles, t={sched.t}, "
      f"fused_ratio={sched.fused_ratio:.2f} (1 barrier, 0 atomics)")

tm = entry.traffic_model
print(f"traffic model: fused moves {tm['fused_bytes']/1e6:.1f}MB vs "
      f"unfused {tm['unfused_bytes']/1e6:.1f}MB "
      f"({100*tm['traffic_saving']:.0f}% saved, "
      f"{tm['d1_spill_rows']}/{n} D1 rows spill past the barrier)")

# ---- 2. correctness vs oracle, dispatch + inspector amortization ----
rng = np.random.default_rng(0)
b = rng.standard_normal((n, bcol))
c = rng.standard_normal((bcol, ccol))
d_ref = fused_ref.unfused_gemm_spmm(a, b, c)
d = api.tile_fused_matmul(a, jnp.asarray(b, jnp.float32),
                          jnp.asarray(c, jnp.float32), spec=spec)
err = float(np.abs(np.asarray(d) - d_ref).max() / np.abs(d_ref).max())
print(f"fused (backend=auto -> {api.select_backend(entry)}) "
      f"vs oracle rel err: {err:.2e}")
stats = api.schedule_cache_stats()
print(f"inspector: {stats['inspect_s']*1e3:.1f}ms once, then cached — "
      f"stats {stats}")

# ---- 3. GCN training on the fused path ----
cfg = gcn_cfg.REDUCED
model = GCN(cfg, powerlaw_graph(cfg.n_nodes, cfg.avg_degree, seed=1))
params = model.init_params(jax.random.PRNGKey(0))
x = jnp.asarray(rng.standard_normal((cfg.n_nodes, cfg.in_dim)), jnp.float32)
y = jnp.asarray(rng.integers(0, cfg.out_dim, cfg.n_nodes))
loss_grad = jax.jit(jax.value_and_grad(
    lambda p: model.loss(p, x, y, fused=True)))
t0 = time.time()
for step in range(10):
    loss, grads = loss_grad(params)
    params = jax.tree.map(lambda p, g: p - 0.5 * g, params, grads)
    if step % 3 == 0:
        print(f"gcn step {step}: loss {float(loss):.4f}")
print(f"10 GCN steps in {time.time()-t0:.1f}s — schedule inspected once, "
      f"served from cache every step (paper §4.2.3)")
