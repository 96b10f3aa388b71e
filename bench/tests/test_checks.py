"""The check that decides ``correct``, driven through the harness on the
CPU at small sizes with the cells' own limits: a sound run passes, and
each fault a cell can have, planted underneath the harness, and the
chain's control (the reference at the precision below, in the program's
place) come out as not correct.  The training cell has no control here:
at its own size no number of a training step tells the three-pass control
from the program (PERF.md, section 6)."""
import json
import os

import jax
import pytest

from bench import faults, harness

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: Small sizes of each cell's configuration and traffic; the limits are the
#: cell's own.
SMALL = {
    "gcn-arxiv.train": ({"n_nodes": 2048, "in_dim": 16, "hidden_dim": 32,
                         "out_dim": 8, "graph": {"edges": 8000}}, {}),
    "spd-chain.n1m": ({"width": 16, "matrix": {"grid": [64, 64],
                                               "rows": 4095}}, {}),
}
PLANTED = {
    "gcn-arxiv.train": ["unchanged", "half_batch", "altered"],
    "spd-chain.n1m": ["unchanged", "half_batch", "altered", "control"],
}


def cells():
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        return [w["name"] for w in json.load(f)["workloads"]]


def merge(base: dict, over: dict) -> dict:
    out = dict(base)
    for k, v in over.items():
        out[k] = merge(base[k], v) if isinstance(v, dict) else v
    return out


def run_small(workload: str, plant: str | None) -> dict:
    c = harness.resolve(workload)
    cfg_over, traffic_over = SMALL[workload]
    c["config"] = merge(c["config"], cfg_over)
    c["traffic"] = merge(c["traffic"], traffic_over)
    if plant is not None:
        make = c["runner"].make
        fault = faults.control if plant == "control" else \
            faults.FAULTS[plant]

        def broken(*a, **kw):
            obj = make(*a, **kw)
            fault(obj)
            return obj
        c["runner"].make = broken
    args = harness.parse(["--workload", workload, "--seed", str(2**35 + 17),
                          "--seconds", "0.5"])
    return harness.execute(c, args, 0.0, jax.devices()[:1],
                           {"flops_per_s": 197e12, "bytes_per_s": 819e9})


@pytest.mark.parametrize("workload", cells())
def test_sound_run_is_correct(workload):
    result = run_small(workload, None)
    assert result["correct"] is True, result["checks"]


@pytest.mark.parametrize("workload, plant", [
    (w, p) for w in cells() for p in PLANTED[w]])
def test_control_and_faults_are_not_correct(workload, plant):
    result = run_small(workload, plant)
    assert result["correct"] is False, result["checks"]
