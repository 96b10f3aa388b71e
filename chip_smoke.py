"""Drive the library's main path once on a TPU and check what comes out.

  PYTHONPATH=src python chip_smoke.py               # one chip
  PYTHONPATH=src python chip_smoke.py --four-chips  # four chips

One chip runs two phases, both in this one process:

  1. GCN training at ``configs/gcn.py`` widths through ``GCN`` and
     ``make_gcn_train_step(..., backend="auto")`` on a power-law graph with
     ogbn-arxiv's node count and average degree, generated from ``--seed``.
     The forward must match the unfused backend, the loss must be finite
     and fall, and the step loop must re-inspect nothing.
  2. The same step on a banded graph at the largest row count whose Pallas
     kernels pass the VMEM check.  ``auto`` must resolve to ``"pallas"``
     for every layer, the compiled forward must hold Mosaic kernels
     (``tpu_custom_call``), and forward and gradients must match the
     unfused path.  One SpMM-SpMM product that resolves to ``"pallas"`` is
     checked against the numpy oracle.

``--four-chips`` runs only the sharded path: both op pairs on a 2x2 mesh,
layouts ``1d`` and ``1.5d`` with halo overlap off and on, reduce-scatter
combine, each checked against a single-device XLA result and the numpy
oracle.

With no TPU, in Pallas interpret mode, or on any failed check the script
exits non-zero and prints no result line.  Otherwise the last line of its
output is ``{"ok": true, "device": {"platform": ..., "kind": ..., "count":
...}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import Mesh  # noqa: E402

from repro.configs.gcn import CONFIG  # noqa: E402
from repro.core.sparse.random import banded_spd, powerlaw_graph  # noqa: E402
from repro.core.tilefusion import FusionSpec, api, fused_ref  # noqa: E402
from repro.kernels.config import VMEM_BUDGET, default_interpret  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.steps import make_gcn_train_step  # noqa: E402
from repro.models.gcn import GCN  # noqa: E402

#: ogbn-arxiv: 169,343 nodes and 1,166,243 edges, average degree ~7.
ARXIV_NODES = 169_343
ARXIV_AVG_DEGREE = 7
#: Half-bandwidth of the phase-2 graph (the benchmark suite's banded_spd_b4).
BANDWIDTH = 4
#: Phase-2 row counts are searched in multiples of this, up to the maximum.
ROW_STEP = 1024
MAX_ROWS = 65_536
STEPS = 5
#: Largest relative (Frobenius) error admitted against a reference: a few
#: bf16 roundings (2^-9 each) of the matmul operands on the TPU's MXU.
REL_TOL = 1e-2


class SmokeFailure(Exception):
    """A check of the smoke run failed."""


def check(name: str, ok: bool, detail: str = "") -> None:
    print(f"  {'ok  ' if ok else 'FAIL'} {name} {detail}".rstrip(), flush=True)
    if not ok:
        raise SmokeFailure(f"{name} {detail}")


def rel_err(got, want) -> float:
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want)
                 / max(np.linalg.norm(want), np.finfo(np.float64).tiny))


def require_tpu(n_chips: int) -> dict:
    """The device record of the result line; fails unless JAX runs on at
    least ``n_chips`` TPU chips with compiled (not interpreted) kernels."""
    if "PALLAS_INTERPRET" in os.environ:
        raise SmokeFailure("PALLAS_INTERPRET is set; the smoke runs compiled "
                           "kernels only")
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SmokeFailure(f"no TPU: JAX runs on {devs[0].platform}")
    if len(devs) < n_chips:
        raise SmokeFailure(f"{n_chips} chips needed, JAX sees {len(devs)}")
    if default_interpret():
        raise SmokeFailure("Pallas kernels would run in interpret mode")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def arxiv_graph(seed: int):
    return powerlaw_graph(ARXIV_NODES, ARXIV_AVG_DEGREE, seed=seed)


def gcn_config(n_nodes: int, avg_degree: int):
    return dataclasses.replace(CONFIG, n_nodes=n_nodes, avg_degree=avg_degree)


def _largest_admitted(fits) -> int:
    """Largest multiple of ROW_STEP up to MAX_ROWS for which ``fits(n)``
    holds; working sets grow with n, so the search bisects."""
    lo, hi = 0, MAX_ROWS // ROW_STEP
    while lo < hi:
        mid = (lo + hi + 1) // 2
        if fits(mid * ROW_STEP):
            lo = mid
        else:
            hi = mid - 1
    if lo == 0:
        raise SmokeFailure(f"no banded row count >= {ROW_STEP} passes the "
                           f"VMEM check")
    return lo * ROW_STEP


def pallas_gcn_rows(seed: int) -> int:
    """Phase-2 row count: the largest banded GCN whose layer entries all
    pass ``api.pallas_fits_vmem``."""
    def fits(n):
        model = GCN(gcn_config(n, BANDWIDTH), banded_spd(n, BANDWIDTH, seed))
        return all(api.pallas_fits_vmem(e) for e in model.entries)
    return _largest_admitted(fits)


def pallas_spmm_spmm_rows(seed: int, c_col: int) -> int:
    """Largest banded A for which A·(A·C) passes the VMEM check."""
    def fits(n):
        return api.pallas_fits_vmem(api.get_schedule(
            banded_spd(n, BANDWIDTH, seed), b_col=c_col, c_col=c_col,
            b_is_sparse=True))
    return _largest_admitted(fits)


def gcn_inputs(model: GCN, seed: int):
    cfg = model.cfg
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal((cfg.n_nodes, cfg.in_dim)),
                    jnp.float32)
    y = jnp.asarray(rng.integers(0, cfg.out_dim, cfg.n_nodes))
    return model.init_params(jax.random.PRNGKey(seed)), x, y


def report_backends(model: GCN) -> list:
    """Print and return what ``auto`` resolves to for each layer's forward
    (the backward always runs one plain transposed SpMM)."""
    picks = []
    for i, e in enumerate(model.entries):
        vmem = {k: round(v / 2**20, 3)
                for k, v in api.pallas_vmem_bytes(e).items()}
        pick = api.select_backend(e)
        picks.append(pick)
        print(f"  layer {i} {e.b_col}->{e.c_col}: backend={pick} "
              f"fused_ratio={e.sched.fused_ratio:.4f} "
              f"traffic_saving={e.traffic_model['traffic_saving']:.4f} "
              f"t={e.dsched.t_pad} pallas_vmem_MiB={vmem} "
              f"budget_MiB={VMEM_BUDGET / 2**20:g}", flush=True)
    return picks


def forward_parity(model: GCN, params, x, *, want_kernel: bool) -> None:
    """First-step forward through ``auto`` against the unfused backend; with
    ``want_kernel`` the compiled forward must hold Mosaic kernels."""
    fwd = jax.jit(lambda p, h: model.forward(p, h, backend="auto"))
    compiled = fwd.lower(params, x).compile()
    if want_kernel:
        n_kernels = compiled.as_text().count("tpu_custom_call")
        check("compiled forward holds Mosaic kernels", n_kernels > 0,
              f"tpu_custom_call x{n_kernels}")
    got = compiled(params, x)
    want = jax.jit(lambda p, h: model.forward(p, h, backend="unfused"))(
        params, x)
    err = rel_err(got, want)
    check("forward auto vs unfused", err <= REL_TOL, f"rel_err={err:.3e}")


def grad_parity(model: GCN, params, x, y) -> None:
    grads = {be: jax.jit(jax.grad(
        lambda p, h, lab, be=be: model.loss(p, h, lab, backend=be)))(
            params, x, y) for be in ("auto", "unfused")}
    for i, (g, w) in enumerate(zip(grads["auto"], grads["unfused"])):
        err = rel_err(g, w)
        check(f"layer {i} gradient auto vs unfused", err <= REL_TOL,
              f"rel_err={err:.3e}")


def train_loop(model: GCN, params, x, y) -> None:
    """``STEPS`` jitted SGD steps through ``auto``: the first compiles,
    the rest must re-inspect nothing; the loss must stay finite and
    fall."""
    step = make_gcn_train_step(model, backend="auto")
    t0 = time.perf_counter()
    params, loss = step(params, x, y)
    losses = [float(loss)]
    print(f"  first step (compile + inspection): "
          f"{time.perf_counter() - t0:.2f}s host clock", flush=True)
    misses0 = api.schedule_cache_stats()["misses"]
    for _ in range(STEPS - 1):
        params, loss = step(params, x, y)
        losses.append(float(loss))
    misses = api.schedule_cache_stats()["misses"] - misses0
    print(f"  losses: {losses}", flush=True)
    check("loss finite", bool(np.isfinite(losses).all()))
    check("loss falls", losses[-1] < losses[0],
          f"{losses[0]:.6f} -> {losses[-1]:.6f}")
    check("no re-inspection inside the step loop", misses == 0,
          f"cache misses={misses}")


def phase_gcn_arxiv(seed: int) -> None:
    print(f"phase 1: GCN training at ogbn-arxiv shape ({ARXIV_NODES} nodes, "
          f"avg degree {ARXIV_AVG_DEGREE})", flush=True)
    t0 = time.perf_counter()
    adj = arxiv_graph(seed)
    model = GCN(gcn_config(ARXIV_NODES, ARXIV_AVG_DEGREE), adj)
    print(f"  graph nnz={adj.nnz}; generate + inspect "
          f"{time.perf_counter() - t0:.2f}s host clock", flush=True)
    report_backends(model)
    params, x, y = gcn_inputs(model, seed)
    forward_parity(model, params, x, want_kernel=False)
    train_loop(model, params, x, y)


def phase_pallas(seed: int) -> None:
    n = pallas_gcn_rows(seed)
    print(f"phase 2: Pallas kernels, banded GCN at {n} rows (the largest "
          f"multiple of {ROW_STEP} the VMEM check admits)", flush=True)
    api.clear_schedule_cache()
    model = GCN(gcn_config(n, BANDWIDTH), banded_spd(n, BANDWIDTH, seed))
    picks = report_backends(model)
    check("auto resolves to pallas for every layer",
          all(p == "pallas" for p in picks), f"{picks}")
    params, x, y = gcn_inputs(model, seed)
    forward_parity(model, params, x, want_kernel=True)
    grad_parity(model, params, x, y)
    train_loop(model, params, x, y)

    c_col = CONFIG.hidden_dim
    n_ss = pallas_spmm_spmm_rows(seed, c_col)
    a = banded_spd(n_ss, BANDWIDTH, seed)
    entry = api.get_schedule(a, b_col=c_col, c_col=c_col, b_is_sparse=True)
    pick = api.select_backend(entry)
    print(f"  SpMM-SpMM A·(A·C) at {n_ss} rows, C {c_col} wide: "
          f"backend={pick}", flush=True)
    check("SpMM-SpMM auto resolves to pallas", pick == "pallas", pick)
    c = np.random.default_rng(seed).standard_normal((n_ss, c_col))
    got = api.tile_fused_matmul(a, a, jnp.asarray(c, jnp.float32))
    err = rel_err(got, fused_ref.unfused_spmm_spmm(a, a, c))
    check("SpMM-SpMM pallas vs numpy oracle", err <= REL_TOL,
          f"rel_err={err:.3e}")


def phase_sharded(devices, a, *, width: int, seed: int) -> None:
    """Both op pairs on a 2x2 mesh of ``devices``: layouts 1d and 1.5d,
    overlap off and on, reduce-scatter combine; each result against a
    single-device XLA result and the numpy oracle."""
    mesh = Mesh(np.asarray(devices[:4]).reshape(2, 2), ("x", "y"))
    rng = np.random.default_rng(seed)
    n = a.n_rows
    b = rng.standard_normal((n, width)).astype(np.float32)
    c = rng.standard_normal((width, width)).astype(np.float32)
    c_s = rng.standard_normal((n, width)).astype(np.float32)
    pairs = (("GeMM-SpMM", (a, jnp.asarray(b), jnp.asarray(c)),
              lambda: fused_ref.unfused_gemm_spmm(a, b, c)),
             ("SpMM-SpMM", (a, a, jnp.asarray(c_s)),
              lambda: fused_ref.unfused_spmm_spmm(a, a, c_s)))
    for name, args, oracle in pairs:
        want = oracle()
        single = np.asarray(api.tile_fused_matmul(*args, backend="xla"))
        err = rel_err(single, want)
        check(f"{name} single-device xla vs oracle", err <= REL_TOL,
              f"rel_err={err:.3e}")
        for layout in ("1d", "1.5d"):
            for overlap in (False, True):
                spec = FusionSpec(mesh=mesh, shard_layout=layout,
                                  overlap=overlap,
                                  shard_combine="reduce_scatter")
                entry = api.get_schedule(
                    a, b_col=width, c_col=width,
                    b_is_sparse=name == "SpMM-SpMM", spec=spec)
                pick = api.select_backend(entry)
                sh = entry.shard
                tag = f"{name} {layout} overlap={overlap}"
                print(f"  {tag}: backend={pick} "
                      f"shards={getattr(sh, 'n_shards', None)}x"
                      f"{getattr(sh, 'n_repl', None)} "
                      f"combine={getattr(sh, 'combine', None)} "
                      f"halo_rows={getattr(sh, 'halo_size', None)}",
                      flush=True)
                check(f"{tag} resolves to sharded", pick == "sharded", pick)
                check(f"{tag} layout", sh.layout == layout
                      and sh.combine == "reduce_scatter"
                      and sh.overlap == overlap,
                      f"{sh.layout}/{sh.combine}/{sh.overlap}")
                got = np.asarray(api.tile_fused_matmul(*args, spec=spec))
                err_o, err_s = rel_err(got, want), rel_err(got, single)
                check(f"{tag} vs oracle and single-device",
                      max(err_o, err_s) <= REL_TOL,
                      f"rel_err oracle={err_o:.3e} single={err_s:.3e}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded path on a 2x2 mesh of four "
                         "chips")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the generated graphs, features and weights")
    args = ap.parse_args(argv)
    device = require_tpu(4 if args.four_chips else 1)
    print(f"device: {device}; compile cache: {enable_compile_cache()}",
          flush=True)
    t0 = time.perf_counter()
    if args.four_chips:
        print(f"sharded phase: 2x2 mesh, ogbn-arxiv-shaped power-law graph "
              f"({ARXIV_NODES} nodes), {CONFIG.in_dim} wide", flush=True)
        phase_sharded(jax.devices(), arxiv_graph(args.seed),
                      width=CONFIG.in_dim, seed=args.seed)
    else:
        phase_gcn_arxiv(args.seed)
        phase_pallas(args.seed)
    print(f"all phases passed in {time.perf_counter() - t0:.1f}s host clock",
          flush=True)
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
