"""Readings that the limits of a cell are set from, in one process.

  python3 bench/calibrate.py --workload <name> --seeds 1,2,... \
      [--control-seeds ...] [--faults unchanged,half_batch,altered] \
      [--fault-seeds ...] [--seconds 1]

For each seed: the numbers that decide ``correct`` for the program (set up
once, the seed's inputs, the first steps or a short window at the cell's
load), and for the control (the reference at the precision below, in the
program's place).  Then each fault, planted in the program
(``bench/faults.py``), on its seeds.  One JSON line per reading, and a
summary: the largest program reading and the smallest control and fault
readings of each number.  The benchmark's own runs never run this.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [_ROOT, os.path.join(_ROOT, "src")]

from bench import device, faults, harness  # noqa: E402


def _seeds(text: str) -> list:
    return [int(s) for s in text.split(",") if s]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=_seeds, required=True)
    ap.add_argument("--control-seeds", type=_seeds, default=[])
    ap.add_argument("--faults", default="")
    ap.add_argument("--fault-seeds", type=_seeds, default=[])
    ap.add_argument("--seconds", type=float, default=1.0)
    args = ap.parse_args(argv)
    c = harness.resolve(args.workload)

    try:
        devices = harness.hold_chip(c)
    except device.NoChip as e:
        harness.log(f"cannot measure: {e}")
        return 3
    runner = c["runner"]
    obj = runner.make(c["config"], c["traffic"], harness.log)
    harness.log(f"set up in {time.perf_counter() - T_START:.1f}s on "
                f"{device.record(devices)}")
    rows = []

    def emit(kind: str, seed: int, numbers: dict) -> None:
        row = {"kind": kind, "seed": seed, "numbers": numbers}
        rows.append(row)
        print(json.dumps(row), flush=True)

    for s in args.seeds:
        out = runner.readings(obj, s, args.seconds, s in args.control_seeds)
        emit("program", s, out["program"])
        if "control" in out:
            emit("control", s, out["control"])
    saved = faults.snapshot(obj)
    for name in [f for f in args.faults.split(",") if f]:
        faults.FAULTS[name](obj)
        for s in args.fault_seeds:
            emit(f"fault:{name}", s,
                 runner.readings(obj, s, args.seconds, False)["program"])
        faults.restore(obj, saved)

    summary: dict = {}
    for row in rows:
        for k, v in row["numbers"].items():
            d = summary.setdefault(k, {})
            if row["kind"] == "program":
                d["lower"] = max(d.get("lower", 0.0), v)
            else:
                d[row["kind"]] = min(d.get(row["kind"], float("inf")), v)
    print(json.dumps({"summary": summary, "workload": args.workload,
                      "seconds_total": time.perf_counter() - T_START}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
