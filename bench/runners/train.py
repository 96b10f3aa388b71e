"""Full-batch GCN training, closed loop: each step's parameters feed the
next, with ``in_flight`` steps enqueued ahead of the one the host waits
for, as an asynchronous training loop runs.

Set-up builds the graph (the configuration's own ``graph_seed``), the
model (which inspects it), the jitted step, and the seed's inputs; it then
drives that one step object through its first three steps, which compile
and which the check follows.  The window carries on from step 4.

Traffic keys: ``lr`` (SGD step size), ``in_flight``, ``check_steps``
(steps the reference follows), ``limits``.
"""
from __future__ import annotations

import collections
import time
import types

import jax
import jax.numpy as jnp
import numpy as np

from bench import compare, counts, graphs, reference, seeds, tracing


def dims(cfg: dict) -> list:
    return ([cfg["in_dim"]] + [cfg["hidden_dim"]] * (cfg["n_layers"] - 1)
            + [cfg["out_dim"]])


def build_graph(cfg: dict) -> tuple:
    g = cfg["graph"]
    return graphs.powerlaw(cfg["n_nodes"], g["edges"], g["alpha"],
                           g["graph_seed"])


class Train:
    def __init__(self, cfg: dict, traffic: dict, log):
        from repro.core.sparse.formats import CSR
        from repro.launch.steps import make_gcn_train_step
        from repro.models.gcn import GCN
        self.cfg, self.traffic, self.log = cfg, traffic, log
        self.dims = dims(cfg)
        self.lr = float(traffic["lr"])
        self.in_flight = int(traffic["in_flight"])
        t0 = time.perf_counter()
        self.graph = build_graph(cfg)
        n, indptr, indices, data = self.graph
        log(f"graph: {n} nodes, {indices.shape[0]} entries, generated in "
            f"{time.perf_counter() - t0:.2f}s")
        t0 = time.perf_counter()
        model_cfg = types.SimpleNamespace(
            n_nodes=n, in_dim=cfg["in_dim"], hidden_dim=cfg["hidden_dim"],
            out_dim=cfg["out_dim"], n_layers=cfg["n_layers"])
        self.model = GCN(model_cfg, CSR(n, n, indptr, indices, data))
        log(f"model built (forward inspection) in "
            f"{time.perf_counter() - t0:.2f}s")
        self.step = make_gcn_train_step(self.model, lr=self.lr,
                                        backend="auto")
        self.counts = counts.gcn_train_step(n, indices.shape[0], self.dims)
        shapes = list(zip(self.dims[:-1], self.dims[1:]))

        @jax.jit
        def make_inputs(key):
            kx, ky, *kw = jax.random.split(key, 2 + len(shapes))
            x = jax.random.normal(kx, (n, self.dims[0]), jnp.float32)
            y = jax.random.randint(ky, (n,), 0, self.dims[-1])
            ws = [jax.random.normal(k, s, jnp.float32) / np.sqrt(s[0])
                  for k, s in zip(kw, shapes)]
            return x, y, ws
        self.make_inputs = make_inputs
        self._ref_a = None

    def backends(self) -> list:
        """What ``auto`` resolves to for each layer, forward and transpose."""
        import dataclasses

        from repro.core.tilefusion import api
        out = []
        for i, e in enumerate(self.model.entries):
            et = api.get_schedule(
                self.model.adj, b_col=e.c_col, c_col=e.b_col,
                spec=dataclasses.replace(self.model.spec, transpose=True,
                                         dtype_bytes=e.dtype_bytes))
            out.append(f"layer{i}:{e.b_col}->{e.c_col}:forward="
                       f"{api.select_backend(e)},transpose="
                       f"{api.select_backend(et)}")
        return out

    def start(self, seed: int) -> None:
        """The seed's inputs and the first ``check_steps`` steps."""
        self.x, self.y, p = self.make_inputs(seeds.key(seed))
        self.params0 = [np.asarray(w) for w in p]
        self.losses, self.after = [], []
        for _ in range(int(self.traffic["check_steps"])):
            p, loss = self.step(p, self.x, self.y)
            self.losses.append(float(loss))
            self.after.append([np.asarray(w) for w in p])
        self.p = p
        self._ref = None

    def window(self, seconds: float) -> tuple:
        """Steps until ``seconds`` have passed; (steps completed, wall
        seconds).  The window closes as a step completes; the steps still
        in flight then complete in ``drain``, outside it."""
        p, x, y, step = self.p, self.x, self.y, self.step
        pending = collections.deque()
        done = 0
        t0 = time.perf_counter()
        while True:
            with tracing.span("step"):
                p, loss = step(p, x, y)
            pending.append(loss)
            if len(pending) > self.in_flight:
                pending.popleft().block_until_ready()
                done += 1
                if time.perf_counter() - t0 >= seconds:
                    break
        wall = time.perf_counter() - t0
        self.p = p
        return done, wall

    def drain(self) -> None:
        jax.block_until_ready(self.p)

    def free_program(self) -> None:
        from repro.core.tilefusion import api
        del self.model, self.step, self.p
        api.clear_schedule_cache()
        jax.clear_caches()

    def reference(self, mode: str) -> dict:
        """The reference's first steps from the seed's inputs."""
        if self._ref_a is None:
            self._ref_a = reference.Sparse(graphs.gcn_normalize(self.graph))
        return reference.gcn_sgd(self._ref_a, self.x, self.y, self.params0,
                                 self.lr, len(self.losses), mode)

    def numbers(self, got: dict, want: dict) -> dict:
        """Loss, first-gradient and change gaps of ``got`` against
        ``want`` (both as ``reference.gcn_sgd`` returns them)."""
        p0 = self.params0
        keep = compare.moving_leaves(want["grads"])
        return {
            "loss_gap": max(compare.rel_gap(a, b) for a, b in
                            zip(got["losses"], want["losses"])),
            "grad_norm_gap": compare.worst_leaf_norm_gap(
                got["grads"], want["grads"]),
            "change_norm_gap": compare.worst_leaf_norm_gap(
                [w - w0 for w, w0 in zip(got["params"][-1], p0)],
                [w - w0 for w, w0 in zip(want["params"][-1], p0)], keep),
        }

    def as_optimizer_sees(self, result: dict) -> dict:
        """``result`` with its first gradient read back from the
        parameters after one SGD step, as the optimizer got it."""
        grads = [(w0.astype(np.float64) - w1.astype(np.float64)) / self.lr
                 for w0, w1 in zip(self.params0, result["params"][0])]
        return dict(result, grads=grads)

    def want(self) -> dict:
        if self._ref is None:
            self._ref = self.reference("highest")
        return self._ref

    def check(self) -> dict:
        got = {"losses": self.losses, "params": self.after}
        return self.numbers(self.as_optimizer_sees(got), self.want())

    def control(self) -> dict:
        """The control: the reference at the precision below, in the
        program's place."""
        return self.numbers(self.as_optimizer_sees(self.reference("high")),
                            self.want())


make = Train


def readings(t: Train, seed: int, seconds: float, control: bool) -> dict:
    """The numbers of the program's first steps from ``seed`` and, with
    ``control``, of the control's (``seconds`` is not used: training's
    numbers need no window)."""
    t.start(seed)
    out = {"program": t.check()}
    if control:
        out["control"] = t.control()
    return out


def run(ctx) -> dict:
    t = make(ctx.config, ctx.traffic, ctx.log)
    t.start(ctx.seed)
    ctx.log("backends: " + " ".join(t.backends()))
    ctx.setup_done()
    with ctx.window():
        n_steps, wall = t.window(ctx.seconds)
    t.drain()
    ctx.log(f"window: {n_steps} steps in {wall:.3f}s")
    ctx.read_memory()
    t.free_program()
    return {
        "metrics": {"train_step_ms": 1e3 * wall / n_steps},
        "attempted": n_steps, "failed": 0,
        "numbers": t.check(),
        "records": {"steps": n_steps, "wall_s": wall, "counts": t.counts},
    }
