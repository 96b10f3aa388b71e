import os
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = " ".join(
    [os.environ.get("XLA_FLAGS", ""),
     "--xla_force_host_platform_device_count=512"]).strip()
"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

The lines above MUST stay first: jax locks the platform and the device
count on first init.  The dry-run compiles on the host CPU alone (pinned,
never an attached accelerator), and the production meshes need 512
placeholder host devices; the flag is appended so flags the caller set
survive.

Usage:
  python -m repro.launch.dryrun --arch qwen2.5-3b --shape train_4k
  python -m repro.launch.dryrun --all --both-meshes [--skip-existing]

Per cell:
  1. the FULL-DEPTH scan-over-layers step is lowered with sharded
     ShapeDtypeStruct inputs and compiled — the large-scale runnability
     proof and the memory_analysis source (no arrays are ever allocated);
  2. (single-pod roofline cells) two SHALLOW fully-unrolled variants are
     compiled and the per-layer FLOPs / bytes / collective-bytes rates are
     extrapolated to full depth.  This sidesteps a known XLA artifact: HLO
     cost_analysis counts a while-loop body ONCE regardless of trip count,
     so the scanned step under-reports per-step cost by ~n_layers.
"""
import argparse
import json
import sys
import time
import traceback

import jax

from ..configs import cells, get_config, get_shape
from ..optim import OptConfig, adamw
from ..roofline import collective_bytes, model_flops, roofline
from . import partitioning, steps
from .mesh import make_production_mesh


def _compile(arch: str, shape_name: str, mesh, *, unroll: bool,
             cfg_replace: dict | None = None, override_rules=None):
    """Lower + compile one variant; return raw analysis artifacts."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    import jax.numpy as jnp

    pl_ = partitioning.plan(arch, shape_name, mesh, unroll=unroll,
                            cfg_replace=cfg_replace)
    cfg, shape = pl_["cfg"], pl_["shape"]
    rules = override_rules if override_rules is not None else pl_["rules"]
    t0 = time.time()
    with mesh:
        if shape.kind == "train":
            step = steps.make_train_step(cfg, OptConfig(), rules)
            opt_abs = jax.eval_shape(adamw.init, pl_["params"])
            # ZeRO-1 (§Perf iter 5): moments shard over data axes too
            moment_shard = partitioning.opt_shardings(
                pl_["param_shardings"], pl_["params"], mesh)
            opt_shard = type(opt_abs)(
                step=NamedSharding(mesh, P()),
                mu=moment_shard, nu=moment_shard)
            lowered = jax.jit(step, in_shardings=(
                pl_["param_shardings"], opt_shard, pl_["batch_shardings"]),
            ).lower(pl_["params"], opt_abs, pl_["batch"])
        elif shape.kind == "prefill":
            step = steps.make_prefill_step(cfg, rules)
            lowered = jax.jit(step, in_shardings=(
                pl_["param_shardings"], pl_["batch_shardings"]),
            ).lower(pl_["params"], pl_["batch"])
        else:
            step = steps.make_serve_step(cfg, rules)
            lowered = jax.jit(step, in_shardings=(
                pl_["param_shardings"], pl_["batch_shardings"],
                pl_["cache_shardings"], NamedSharding(mesh, P())),
            ).lower(pl_["params"], pl_["batch"], pl_["cache"],
                    jax.ShapeDtypeStruct((), jnp.int32))
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
    cost = compiled.cost_analysis() or {}
    mem = compiled.memory_analysis()
    coll = collective_bytes(compiled.as_text())
    return {
        "cfg": cfg, "shape": shape,
        "cost": cost, "mem": mem, "coll": coll,
        "lower_s": t_lower, "compile_s": t_compile,
    }


def _peak_bytes(mem):
    """Per-device peak memory.  ``CompiledMemoryStats.peak_memory_in_bytes``
    only exists on newer jaxlib / TPU runtimes; the CPU/host backend exposes
    just the component sizes, so derive the peak from those instead of
    silently reporting None."""
    if mem is None:
        return None
    peak = getattr(mem, "peak_memory_in_bytes", None)
    if peak:
        return int(peak)
    parts = ("argument_size_in_bytes", "output_size_in_bytes",
             "temp_size_in_bytes")
    total = sum(int(getattr(mem, k, 0) or 0) for k in parts)
    total -= int(getattr(mem, "alias_size_in_bytes", 0) or 0)
    return max(total, 0)


def _depth_points(cfg):
    """Two shallow depths for the affine-in-depth extrapolation."""
    if cfg.block_pattern == "mlstm7+slstm":
        return 8, 16
    return 2, 4


def run_cell(arch: str, shape_name: str, *, multi_pod: bool = False,
             roofline_terms: bool = True, override_rules=None,
             extra_tag: str = "", cfg_replace: dict | None = None,
             verbose: bool = True) -> dict:
    mesh = make_production_mesh(multi_pod=multi_pod)
    cfg = get_config(arch)
    shape = get_shape(shape_name)
    n_dev = mesh.devices.size

    # ---- 1) full-depth scan compile: runnability proof + memory ----
    full = _compile(arch, shape_name, mesh, unroll=False,
                    cfg_replace=cfg_replace, override_rules=override_rules)
    mem = full["mem"]
    result = {
        "arch": arch, "shape": shape_name,
        "mesh": "x".join(map(str, mesh.devices.shape)),
        "multi_pod": multi_pod, "tag": extra_tag, "n_devices": n_dev,
        "lower_s": round(full["lower_s"], 1),
        "compile_s": round(full["compile_s"], 1),
        "memory_analysis": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "peak_bytes": _peak_bytes(mem),
        },
    }

    # ---- 2) depth-extrapolated roofline terms (single-pod cells) ----
    if roofline_terms:
        k1, k2 = _depth_points(cfg)
        enc_scale = cfg.encoder_layers / max(cfg.n_layers, 1)
        reps = []
        for k in (k1, k2):
            rep = dict(cfg_replace or {})
            rep.update(n_layers=k,
                       encoder_layers=int(round(k * enc_scale)))
            reps.append(_compile(arch, shape_name, mesh, unroll=True,
                                 cfg_replace=rep,
                                 override_rules=override_rules))

        def affine(get):
            y1, y2 = (float(get(r) or 0.0) for r in reps)
            slope = (y2 - y1) / (k2 - k1)
            eff_cfg = cfg_replace or {}
            depth = eff_cfg.get("n_layers", cfg.n_layers)
            return y2 + slope * (depth - k2)

        flops = affine(lambda r: r["cost"].get("flops"))
        bytes_acc = affine(lambda r: r["cost"].get("bytes accessed"))
        coll_total = affine(lambda r: r["coll"]["total_bytes"])
        coll_kinds = {
            kind: affine(lambda r, k_=kind: r["coll"]["bytes"][k_])
            for kind in reps[0]["coll"]["bytes"]
        }
        rl = roofline({"flops": flops, "bytes accessed": bytes_acc},
                      {"total_bytes": coll_total},
                      model_flops_global=model_flops(cfg, shape),
                      n_devices=n_dev)
        result["cost_analysis"] = {"flops": flops,
                                   "bytes accessed": bytes_acc}
        result["collectives"] = {"bytes": coll_kinds,
                                 "total_bytes": coll_total}
        result["roofline"] = rl.to_dict()
        result["extrapolation"] = {"depths": [k1, k2]}
    if verbose:
        print(json.dumps(result, indent=1, default=str))
    return result


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args(argv)

    todo = cells() if args.all else [(args.arch, args.shape)]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    os.makedirs(args.out, exist_ok=True)
    failures = []
    for arch, shape_name in todo:
        for mp in meshes:
            tag = f"{arch}_{shape_name}_{'512' if mp else '256'}"
            path = os.path.join(args.out, tag + ".json")
            if args.skip_existing and os.path.exists(path):
                print(f"[skip] {tag}", flush=True)
                continue
            print(f"[dryrun] {tag} ...", flush=True)
            try:
                # roofline terms only for the single-pod table (§Roofline)
                res = run_cell(arch, shape_name, multi_pod=mp,
                               roofline_terms=not mp, verbose=False)
                with open(path, "w") as f:
                    json.dump(res, f, indent=1, default=str)
                if "roofline" in res:
                    r = res["roofline"]
                    print(f"[ok] {tag}: bottleneck={r['bottleneck']} "
                          f"compute={r['compute_s']:.2e}s "
                          f"memory={r['memory_s']:.2e}s "
                          f"coll={r['collective_s']:.2e}s "
                          f"useful={r['useful_ratio']:.2f} "
                          f"(compile {res['compile_s']}s)", flush=True)
                else:
                    print(f"[ok] {tag}: compiled "
                          f"(compile {res['compile_s']}s, peak "
                          f"{res['memory_analysis']['peak_bytes']})",
                          flush=True)
            except Exception as e:
                failures.append((tag, repr(e)))
                print(f"[FAIL] {tag}: {e!r}", flush=True)
                traceback.print_exc()
    if failures:
        print(f"\n{len(failures)} FAILURES:")
        for t, e in failures:
            print(" ", t, e)
        sys.exit(1)
    print("\nall dry-run cells passed")


if __name__ == "__main__":
    main()
