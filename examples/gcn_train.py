"""End-to-end GCN training — the paper's native application at full size.

  PYTHONPATH=src python examples/gcn_train.py [--nodes 4096] [--steps 100]

GCN layer = D = Â(XW) = GeMM-SpMM; every layer and every step runs through
``tile_fused_matmul`` (schedule inspected once per graph, then served from
the content-keyed cache).  The backward is the api's custom_vjp: one
transposed SpMM ``Âᵀ·Ḋ`` per layer, from which two dense GEMMs derive both
gradients.  Reports fused vs unfused wall time, per-layer traffic models,
and the train-step (fwd+bwd) traffic.
"""
import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.gcn import GCNConfig
from repro.core.sparse.random import powerlaw_graph
from repro.core.tilefusion import api
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.steps import make_gcn_train_step
from repro.models.gcn import GCN


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--nodes", type=int, default=4096)
    ap.add_argument("--hidden", type=int, default=128)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--lr", type=float, default=0.3)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = GCNConfig(n_nodes=args.nodes, in_dim=args.hidden,
                    hidden_dim=args.hidden, out_dim=32, n_layers=2)
    adj = powerlaw_graph(cfg.n_nodes, cfg.avg_degree, seed=0)
    t0 = time.time()
    model = GCN(cfg, adj, spec=api.FusionSpec(cache_size=300_000.0))
    print(f"schedule inspect: {time.time()-t0:.2f}s (cached for every "
          f"layer/step), fused_ratio={model.sched.fused_ratio:.2f}, "
          f"tiles={len(model.sched.wavefronts[0])}+"
          f"{len(model.sched.wavefronts[1])}")
    for i, tm in enumerate(model.layer_traffic_models()):
        print(f"layer {i} ({model.dims[i]}->{model.dims[i+1]}): traffic "
              f"saving (kernel path) {100*tm['traffic_saving']:.0f}%")
    for i, tm in enumerate(model.train_step_traffic_models()):
        print(f"layer {i} train step: fwd {tm['forward_bytes']/1e6:.1f} MB "
              f"+ bwd {tm['backward_bytes']/1e6:.1f} MB "
              f"(SpMM {tm['backward_spmm_bytes']/1e6:.1f} MB, "
              f"GEMMs {tm['backward_gemm_bytes']/1e6:.1f} MB)")

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((cfg.n_nodes, cfg.in_dim)),
                    jnp.float32)
    y = jnp.asarray(rng.integers(0, cfg.out_dim, cfg.n_nodes))
    params = model.init_params(jax.random.PRNGKey(0))

    for fused in (True, False):
        p = params
        # the fused leg runs backend="auto": Eq-3 picks the executor per
        # entry, falling back to the plain hybrid SpMM when the modeled
        # saving can't cover the tile loop's fixed costs — "fused" here
        # means "through the dispatch", never slower than the baseline
        be = "auto" if fused else "unfused"
        picks = ",".join(sorted({api.select_backend(e)
                                 for e in model.entries})) if fused else be
        step_fn = make_gcn_train_step(model, lr=args.lr, backend=be)
        jax.block_until_ready(step_fn(p, x, y))  # compile
        misses0 = api.schedule_cache_stats()["misses"]
        t0 = time.time()
        for _ in range(args.steps):
            p, loss = step_fn(p, x, y)
        jax.block_until_ready(loss)   # async dispatch would under-report
        dt = time.time() - t0
        # the printed loss is evaluated at the *post-loop* params — the
        # in-loop value lags one update behind the weights it's reported for
        final_loss = float(model.loss(
            p, x, y, backend="xla" if fused else "unfused"))
        stats = api.schedule_cache_stats()
        print(f"{f'fused[{picks}]' if fused else 'unfused'}: "
              f"{args.steps} steps "
              f"in {dt:.2f}s ({dt/args.steps*1e3:.1f} ms/step), "
              f"final loss {final_loss:.4f}, "
              f"re-inspections during loop: "
              f"{stats['misses'] - misses0}")


if __name__ == "__main__":
    main()
