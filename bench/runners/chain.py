"""A chain of SpMM-SpMM products ``C_{k+1} = A·(A·C_k)``, closed loop:
each product's output is the next one's input, with ``in_flight`` products
enqueued ahead of the one the host waits for.  A is scaled so that its spectral radius is at most
1, and the chain restarts from ``C_0`` every ``restart_every`` products, so
values neither overflow nor decay to nothing however long the window runs.

Set-up builds A from the configuration's ``matrix`` (fixed by its own
``graph_seed``), draws ``C_0`` from the seed on the device, and runs one
product, which inspects A and compiles.  After the window, the outputs of
``check_products`` products drawn from the seed, and of the last one, are
compared on the device with the reference's own chain from ``C_0``.

Traffic keys: ``restart_every``, ``in_flight``, ``check_products``,
``limits``.
"""
from __future__ import annotations

import collections
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import counts, graphs, reference, seeds, tracing


class Chain:
    def __init__(self, cfg: dict, traffic: dict, log):
        from repro.core.sparse.formats import CSR
        self.cfg, self.traffic, self.log = cfg, traffic, log
        m = cfg["matrix"]
        self.width = int(cfg["width"])
        self.period = int(traffic["restart_every"])
        self.in_flight = int(traffic["in_flight"])
        t0 = time.perf_counter()
        self.graph = graphs.grid5_spd(*m["grid"], m["rows"], m["graph_seed"])
        n, indptr, indices, data = self.graph
        self.a = CSR(n, n, indptr, indices, data)
        log(f"matrix: {n} rows, {indices.shape[0]} entries, generated in "
            f"{time.perf_counter() - t0:.2f}s")
        self.counts = counts.spmm_spmm(n, indices.shape[0], self.width)
        self.make_c = jax.jit(lambda key: jax.random.normal(
            key, (n, self.width), jnp.float32))
        self._ref_a = None

    def product(self, c):
        from repro.core.tilefusion.api import tile_fused_matmul
        return tile_fused_matmul(self.a, self.a, c)

    def backend(self) -> str:
        from repro.core.tilefusion import api
        entry = api.get_schedule(self.a, b_col=self.width, c_col=self.width,
                                 b_is_sparse=True,
                                 spec=api.FusionSpec(dtype_bytes=4))
        return api.select_backend(entry)

    def start(self, seed: int) -> None:
        """``C_0`` from the seed, and one product that inspects and
        compiles; its time sets how many products the window may hold."""
        self.seed = seed
        self.c0 = self.make_c(seeds.key(seed))
        t0 = time.perf_counter()
        self.product(self.c0).block_until_ready()
        self.log(f"first product (inspection, compilation): "
                 f"{time.perf_counter() - t0:.2f}s")
        t0 = time.perf_counter()
        self.product(self.c0).block_until_ready()
        self.warm_s = time.perf_counter() - t0

    def sample(self, seconds: float) -> set:
        """Products to check, drawn from the seed among those the window
        surely completes (half of what the warm product's time allows)."""
        sure = max(int(0.5 * seconds / max(self.warm_s, 1e-6)), 1)
        k = min(int(self.traffic["check_products"]), sure)
        rng = seeds.rng(self.seed, 1)
        return set(int(j) for j in rng.choice(sure, size=k, replace=False))

    def window(self, seconds: float, keep: set) -> tuple:
        """Products until ``seconds`` have passed; (products completed, wall
        seconds, {index: output} for the indices in ``keep`` and the last
        one enqueued).  The window closes as a product completes; those
        still in flight then complete in ``drain``, outside it."""
        c0, period, product = self.c0, self.period, self.product
        c, j, done, kept = c0, 0, 0, {}
        pending = collections.deque()
        t0 = time.perf_counter()
        while True:
            with tracing.span("product"):
                c = product(c0 if j % period == 0 else c)
            if j in keep:
                kept[j] = c
            pending.append(c)
            j += 1
            if len(pending) > self.in_flight:
                pending.popleft().block_until_ready()
                done += 1
                if time.perf_counter() - t0 >= seconds:
                    break
        wall = time.perf_counter() - t0
        kept[j - 1] = c
        return done, wall, kept

    @staticmethod
    def drain(kept: dict) -> None:
        jax.block_until_ready(list(kept.values()))

    def free_program(self) -> None:
        from repro.core.tilefusion import api
        del self.a
        api.clear_schedule_cache()
        jax.clear_caches()

    def reference_chain(self, mode: str, positions: set) -> dict:
        """The reference's outputs at cycle ``positions`` (0 is the first
        product after a restart), on the device."""
        if self._ref_a is None:
            self._ref_a = reference.Diagonals(self.graph)
        out, c = {}, self.c0
        for p in range(max(positions) + 1):
            c = reference.chain_product(self._ref_a, c, mode)
            if p in positions:
                out[p] = c
        return out

    def numbers(self, got: dict, want: dict) -> dict:
        """Widest relative error and widest single gap over the products
        in ``got`` ({index: output}) against ``want`` ({position: ...}),
        reduced on the device: half-GB outputs never cross to the host."""
        rel, gap = 0.0, 0.0
        for j, d in got.items():
            diff, ref, diff_max, ref_max = (
                float(v) for v in _gaps(d, want[j % self.period]))
            tiny = np.finfo(np.float32).tiny
            rel = max(rel, diff / max(ref, tiny))
            gap = max(gap, diff_max / max(ref_max, tiny))
        return {"product_rel_err": rel, "product_max_gap": gap}

    def check(self, kept: dict) -> dict:
        positions = {j % self.period for j in kept}
        return self.numbers(kept, self.reference_chain("highest", positions))

    def control(self, kept: dict) -> dict:
        """The reference at the precision below in the program's place."""
        positions = {j % self.period for j in kept}
        low = self.reference_chain("high", positions)
        return self.numbers(low, self.reference_chain("highest", positions))


@jax.jit
def _gaps(d, w):
    """``|d - w|``, ``|w|`` (Frobenius) and the largest ``|d - w|`` and
    ``|w|``: the parts of ``compare.rel_err`` and ``compare.max_gap``."""
    diff = d - w
    return (jnp.linalg.norm(diff), jnp.linalg.norm(w),
            jnp.max(jnp.abs(diff)), jnp.max(jnp.abs(w)))


make = Chain


def readings(ch: Chain, seed: int, seconds: float, control: bool) -> dict:
    """The numbers of a ``seconds`` window from ``seed`` and, with
    ``control``, of the control on the same products."""
    ch.start(seed)
    _, _, kept = ch.window(seconds, ch.sample(seconds))
    out = {"program": ch.check(kept)}
    if control:
        out["control"] = ch.control(kept)
    return out


def run(ctx) -> dict:
    ch = make(ctx.config, ctx.traffic, ctx.log)
    ch.start(ctx.seed)
    ctx.log(f"backend: A·(A·C) at {ch.a.n_rows} rows -> {ch.backend()}")
    keep = ch.sample(ctx.seconds)
    ctx.setup_done()
    with ctx.window():
        n, wall, kept = ch.window(ctx.seconds, keep)
    ch.drain(kept)
    ctx.log(f"window: {n} products in {wall:.3f}s; checking {sorted(kept)}")
    ctx.read_memory()
    ch.free_program()
    return {
        "metrics": {"chain_product_ms": 1e3 * wall / n},
        "attempted": n, "failed": 0,
        "numbers": ch.check(kept),
        "records": {"steps": n, "wall_s": wall, "counts": ch.counts},
    }
