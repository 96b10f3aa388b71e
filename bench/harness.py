"""Runs one cell of ``BENCHMARK.json`` and prints the result line.

Everything that belongs to a configuration, a traffic mix or a per-layer
metric sits in a file of its own, found by the name in ``BENCHMARK.json``:

- ``bench/configs/<config>.json``: the configuration as it is run;
- ``bench/traffic/<traffic>.json``: the mix's parameters, the ``runner``
  that runs it, and the ``limits`` of the numbers that decide ``correct``;
- ``bench/runners/<runner>.py``: ``run(ctx)`` sets up, measures the
  window, checks the output and returns what it measured;
- ``bench/layer_metrics/<metric>.py``: ``read(records)`` returns the
  metric, or None where the run has nothing to read.

No list of configurations, mixes or metrics lives here.
"""
from __future__ import annotations

import argparse
import contextlib
import importlib.util
import json
import os
import sys
import time

from bench import compare, device, tracing

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
SPEC_FILE = os.path.join(ROOT, "BENCHMARK.json")


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(kind: str, name: str, bench_dir: str = BENCH_DIR):
    """``<bench_dir>/<kind>/<name>.py`` as a module."""
    path = os.path.join(bench_dir, kind, name + ".py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no {kind} named {name!r}: {path}")
    spec = importlib.util.spec_from_file_location(
        f"bench.{kind}.{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell(spec: dict, workload: str) -> tuple:
    """The workload entry, its end-to-end metrics and its per-layer ones."""
    found = [w for w in spec["workloads"] if w["name"] == workload]
    if not found:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    e2e = [m for m in spec["end_to_end"]
           if workload in m.get("workloads", [workload])]
    names = {m["name"] for m in e2e}
    layer = [m for m in spec["per_layer"]
             if (workload in m["workloads"] if "workloads" in m
                 else m["moves"] in names)]
    return found[0], e2e, layer


class Context:
    """What a runner gets: the cell's files, the seed and window, and the
    hooks that mark set-up, the window and the memory reading."""

    def __init__(self, args, t_start: float, config: dict, traffic: dict,
                 devices: list):
        self.workload = args.workload
        self.seed = args.seed
        self.seconds = float(args.seconds)
        self.trace = bool(args.trace)
        self.config, self.traffic = config, traffic
        self.devices = devices
        self.t_start = t_start
        self.setup_s = None
        self.memory_peak = None
        self.captured: dict = {}
        self.compiles = 0
        self.window_compiles = 0
        self.log = log

    def setup_done(self) -> None:
        self.setup_s = time.perf_counter() - self.t_start

    @contextlib.contextmanager
    def window(self):
        """The measured window: traced when the run traces, and counted
        for compilations (``ctx.window_compiles`` after it)."""
        c0 = self.compiles
        with tracing.capture(self.trace) as cap:
            with tracing.span("window"):
                yield
        self.captured = cap
        self.window_compiles = self.compiles - c0

    def read_memory(self) -> None:
        self.memory_peak = device.memory_peak_bytes(self.devices)


def _count_compiles(ctx: Context) -> None:
    import jax
    from jax._src import dispatch

    def listener(event: str, _secs: float, **_kw) -> None:
        if event == dispatch.BACKEND_COMPILE_EVENT:
            ctx.compiles += 1
    jax.monitoring.register_event_duration_secs_listener(listener)


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def resolve(workload: str, bench_dir: str = BENCH_DIR,
            spec_file: str = SPEC_FILE) -> dict:
    """The cell's entry, metrics, configuration, traffic and runner, found
    by name."""
    spec = load_json(spec_file)
    wl, e2e, layer = cell(spec, workload)
    traffic = load_json(os.path.join(bench_dir, "traffic",
                                     wl["traffic"] + ".json"))
    return {"workload": wl, "e2e": e2e, "layer": layer, "bench_dir": bench_dir,
            "config": load_json(os.path.join(bench_dir, "configs",
                                             wl["config"] + ".json")),
            "traffic": traffic,
            "runner": load_module("runners", traffic["runner"], bench_dir)}


def execute(c: dict, args, t_start: float, devices: list,
            peak: dict) -> dict:
    """Everything of a run after the look for the chip: the runner's set-up,
    window and check, and the result line as a dict."""
    import jax
    jax.config.update("jax_default_matmul_precision",
                      c["config"]["matmul_precision"])
    ctx = Context(args, t_start, c["config"], c["traffic"], devices)
    _count_compiles(ctx)
    out = c["runner"].run(ctx)
    rows = compare.checks(out["numbers"], c["traffic"]["limits"])
    correct = compare.passed(rows) and out["failed"] == 0

    dev = dict(device.record(devices), memory_peak_bytes=ctx.memory_peak)
    records = dict(out["records"], peak=peak,
                   compiles_in_window=ctx.window_compiles)
    result = {"correct": bool(correct), "attempted": int(out["attempted"]),
              "failed": int(out["failed"])}
    if args.trace:
        try:
            reduced = tracing.reduce(tracing.load(ctx.captured["path"]))
        finally:
            tracing.discard(ctx.captured)
        records["trace"] = reduced
        dev.update(busy_s=reduced["busy_s"], window_s=reduced["window_s"])
        metrics = {}
        for m in c["layer"]:
            value = load_module("layer_metrics", m["name"],
                                c["bench_dir"]).read(records)
            if value is not None:
                metrics[m["name"]] = {"value": float(value),
                                      "unit": m["unit"]}
    else:
        measured = dict(out["metrics"], setup_s=ctx.setup_s)
        metrics = {m["name"]: {"value": float(measured[m["name"]]),
                               "unit": m["unit"]} for m in c["e2e"]}
    log(f"setup_s={ctx.setup_s!r} compiles_in_window={ctx.window_compiles}")
    result.update(metrics=metrics, device=dev)
    if args.trace:
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    result["checks"] = {name: {"value": v, "limit": lim}
                        for name, v, lim in rows}
    compare.print_rows(rows)
    return result


def hold_chip(c: dict) -> list:
    """The chips the cell asks for, with the persistent compilation cache
    on; raises ``device.NoChip`` where there are none."""
    import jax
    from repro.kernels.config import default_interpret
    from repro.launch.compile_cache import enable_compile_cache
    devices = device.require_tpu(int(c["workload"]["chips"]),
                                 default_interpret())
    log(f"device: {device.record(devices)}; "
        f"compile cache: {enable_compile_cache()}")
    # every program the cell runs is cached, however quickly it compiled
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_default_matmul_precision",
                      c["config"]["matmul_precision"])
    return devices


def main(argv=None, t_start: float | None = None) -> int:
    t_start = time.perf_counter() if t_start is None else t_start
    args = parse(argv)
    c = resolve(args.workload)
    try:
        devices = hold_chip(c)
    except device.NoChip as e:
        log(f"cannot measure: {e}")
        return 3
    result = execute(c, args, t_start, devices,
                     device.peaks(devices[0].device_kind))
    print(json.dumps(result), flush=True)
    return 0
