# One function per paper table/figure. Prints ``name,us_per_call,derived``
# CSV and (with --json) writes a machine-readable BENCH_<date>.json for
# trend tracking; --check compares rows against benchmarks/thresholds.json
# and exits non-zero on a regression.
#
# ``--smoke`` runs every driver at one tiny problem size (sets
# REPRO_BENCH_SMOKE=1 before the drivers import; see benchmarks/util.py) —
# a bit-rot check, not a measurement.  The tier-1 suite invokes it via
# tests/test_bench_smoke.py.  Thresholds not marked ``"smoke": true`` are
# skipped under --smoke (tiny-size timings are meaningless).
from __future__ import annotations

import argparse
import json
import os
import sys
import time

from repro.launch.compile_cache import enable_compile_cache

MODULES = [
    "fig1_fused_ratio_census",
    "fig4_ratio_vs_tilesize",
    "table2_gemm_spmm",
    "table3_spmm_spmm",
    "fig6_fused_baselines",
    "fig9_step_ablation",
    "fig10_amortization",
    "inspector_bench",
    "reorder_ablation",
    "hetero_bench",
    "kernels_bench",
    "sharded_scaling",
    "serving_bench",
    "train_bench",
]

THRESHOLDS_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                               "thresholds.json")


def parse_derived(s: str) -> dict:
    """``"k=v;k2=v2"`` -> dict, floats where possible (``39.5x`` -> 39.5)."""
    out: dict = {}
    for kv in s.split(";"):
        if "=" not in kv:
            continue
        k, v = kv.split("=", 1)
        try:
            out[k] = float(v.rstrip("x"))
        except ValueError:
            out[k] = v
    return out


def emit_json(path: str, rows: list, meta: dict) -> None:
    """Write the collected rows as a trend-trackable JSON document."""
    doc = {"meta": meta,
           "rows": [{"name": n, "us": us, "derived": parse_derived(d),
                     "derived_raw": d} for n, us, d in rows]}
    with open(path, "w") as f:
        json.dump(doc, f, indent=2, sort_keys=True)
        f.write("\n")


def check_thresholds(rows: list, thresholds: list, smoke: bool) -> list:
    """Threshold violations (empty list = pass).

    Each threshold is ``{"row": <name prefix>, "key": "us"|<derived key>,
    "min"/"max": float, "smoke": bool}``; a threshold with no matching row
    is itself a violation (a renamed bench must not silently drop its
    regression gate)."""
    violations = []
    for th in thresholds:
        if smoke and not th.get("smoke", False):
            continue
        matches = [r for r in rows if r[0].startswith(th["row"])]
        if not matches:
            violations.append(f"threshold {th['row']}: no matching rows")
            continue
        for name, us, derived in matches:
            val = us if th["key"] == "us" else parse_derived(derived).get(
                th["key"])
            if not isinstance(val, float):
                violations.append(
                    f"{name}: key {th['key']!r} missing or non-numeric")
                continue
            if "min" in th and val < th["min"]:
                violations.append(
                    f"{name}: {th['key']}={val:g} < min {th['min']:g}")
            if "max" in th and val > th["max"]:
                violations.append(
                    f"{name}: {th['key']}={val:g} > max {th['max']:g}")
    return violations


def main(argv=None) -> None:
    import importlib
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("modules", nargs="*",
                    help="run only these drivers (default: all)")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny problem sizes, 1 rep (bit-rot check)")
    ap.add_argument("--json", nargs="?", const="__default__", default=None,
                    metavar="PATH",
                    help="also write rows to PATH "
                         "(default BENCH_<yyyymmdd>.json)")
    ap.add_argument("--check", action="store_true",
                    help="compare rows against benchmarks/thresholds.json; "
                         "exit 1 on a regression")
    args = ap.parse_args(argv)
    enable_compile_cache()
    if args.smoke:
        os.environ["REPRO_BENCH_SMOKE"] = "1"
    only = set(args.modules) or None
    rows: list = []
    print("name,us_per_call,derived")
    ran = []
    for mod_name in MODULES:
        if only and mod_name not in only:
            continue
        t0 = time.time()
        mod = importlib.import_module(f"benchmarks.{mod_name}")
        for name, us, derived in mod.run():
            print(f"{name},{us:.1f},{derived}", flush=True)
            rows.append((name, float(us), derived))
        ran.append(mod_name)
        print(f"# {mod_name} done in {time.time()-t0:.0f}s", flush=True)
    if args.json is not None:
        path = (f"BENCH_{time.strftime('%Y%m%d')}.json"
                if args.json == "__default__" else args.json)
        emit_json(path, rows, meta={
            "date": time.strftime("%Y-%m-%dT%H:%M:%S"),
            "smoke": bool(args.smoke), "modules": ran})
        print(f"# wrote {path}", flush=True)
    if args.check:
        with open(THRESHOLDS_PATH) as f:
            thresholds = json.load(f)
        if only:   # partial runs only gate the thresholds they can see
            thresholds = [t for t in thresholds
                          if any(r[0].startswith(t["row"]) for r in rows)]
        violations = check_thresholds(rows, thresholds, bool(args.smoke))
        for v in violations:
            print(f"THRESHOLD VIOLATION: {v}", file=sys.stderr)
        if violations:
            sys.exit(1)
        print("# thresholds ok", flush=True)


if __name__ == "__main__":
    main()
