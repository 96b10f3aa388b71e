"""Device time put down to the program's scopes, idle gaps to its spans.

The program names its layers with ``repro.trace``: a ``repro.<name>`` scope
lands in the HLO ``op_name`` of every operation traced inside it, and a
``repro.<name>`` host span lands on the profiler's clock beside the
benchmark's ``bench.`` spans. A profile's device events carry an
instruction's HLO text but not its ``op_name``, so the link goes through
the optimized HLO that XLA dumps: (module, instruction, result shape) ->
``op_name`` -> the ``repro.`` scopes in it, wrappers such as
``transpose(jvp(...))`` seen through.

- ``read_dump`` / ``parse_hlo``: the optimized HLO text of every module;
- ``load``: a profile's device operations with their module, and its
  ``bench.`` and ``repro.`` host spans;
- ``reduce``: ``tracing.reduce`` over those events (so busy, window,
  device operations and idle gaps are computed as the benchmark does, the
  gaps now named by the innermost span of either kind), plus each scope's
  device seconds (the union of its operations' intervals, clipped to the
  window, so a ``while`` and its body count once), the unscoped seconds,
  the top innermost scope paths, and each program span's total;
- ``layer_numbers``: the per-layer readings taken from a reduction.

Run one cell as ``bench/run.py --trace 1`` does, with XLA's HLO dump on
and the persistent compilation cache off for the process (a cache hit
compiles nothing, so dumps nothing):

    python3 bench/scopes.py --workload <cell> --seed <n> --seconds <s>

It prints one JSON line: the reduction, the layer readings, and whether the
run's outputs passed the cell's checks.
"""
from __future__ import annotations

import collections
import glob
import os
import re
import sys
import time

T_START = time.perf_counter()

if __package__ in (None, ""):
    _ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path[:0] = [_ROOT, os.path.join(_ROOT, "src")]

from bench import tracing  # noqa: E402

SCOPE_PREFIX = "repro."
SPAN_PREFIXES = (tracing.SPAN_PREFIX, SCOPE_PREFIX)
MODULES_LINE = "XLA Modules"
UNSCOPED = ()
TOP = tracing.TOP

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([^\s=(){}]+) = (.*)$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_MODULE = re.compile(r"^HloModule ([^\s,]+)")
_SCOPE = re.compile(re.escape(SCOPE_PREFIX) + r"[\w.\-]+")


def dump_flags(directory: str) -> str:
    """The ``XLA_FLAGS`` words that dump every module's HLO as text."""
    return f"--xla_dump_to={directory} --xla_dump_hlo_as_text"


def split_type(rest: str) -> tuple:
    """``"<type> <opcode>(...)..."`` -> (type, the rest); a tuple type is
    read to its closing parenthesis."""
    if not rest.startswith("("):
        head, _, tail = rest.partition(" ")
        return head, tail
    depth = 0
    for i, ch in enumerate(rest):
        depth += ch == "("
        depth -= ch == ")"
        if depth == 0:
            return rest[:i + 1], rest[i + 1:]
    return rest, ""


def shape_of(type_text: str) -> str:
    """A result type without layouts, comments or spaces:
    ``f32[8,128]{1,0:T(8,128)}`` -> ``f32[8,128]``."""
    t = re.sub(r"/\*.*?\*/", "", type_text)
    t = re.sub(r"\{[^{}]*\}", "", t)
    return t.replace(" ", "")


def scope_path(op_name: str) -> tuple:
    """The ``repro.`` scopes of an ``op_name``, outermost first:
    ``jit(step)/transpose(jvp(repro.gcn.layer0))/repro.backward/mul`` ->
    ``("repro.gcn.layer0", "repro.backward")``."""
    return tuple(_SCOPE.findall(op_name))


def parse_hlo(text: str) -> tuple:
    """One module's HLO text -> (module name, {instruction: (shape,
    scope path)}).  Instruction names are unique within a module."""
    m = _MODULE.search(text)
    name = m.group(1) if m else ""
    instrs = {}
    for line in text.splitlines():
        im = _INSTR.match(line)
        if not im:
            continue
        typ, tail = split_type(im.group(2))
        om = _OP_NAME.search(tail)
        instrs[im.group(1)] = (shape_of(typ),
                               scope_path(om.group(1)) if om else UNSCOPED)
    return name, instrs


def read_dump(directory: str) -> dict:
    """Every optimized module XLA dumped into ``directory``:
    {module name: [instruction map, ...]} (one name may be compiled more
    than once)."""
    out: dict = collections.defaultdict(list)
    for path in sorted(glob.glob(os.path.join(directory,
                                              "*after_optimizations.txt"))):
        with open(path) as f:
            name, instrs = parse_hlo(f.read())
        out[name].append(instrs)
    return dict(out)


def parse_event(text: str) -> tuple:
    """A device event's name (its HLO text) -> (instruction, shape)."""
    im = _INSTR.match(text)
    if not im:
        return text, ""
    return im.group(1), shape_of(split_type(im.group(2))[0])


def _module_of(modules: list, t: float):
    """The name of the module event (sorted by start) that covers ``t``."""
    lo, hi = 0, len(modules)
    while lo < hi:
        mid = (lo + hi) // 2
        if modules[mid][1] <= t:
            lo = mid + 1
        else:
            hi = mid
    if lo and modules[lo - 1][1] <= t <= modules[lo - 1][2]:
        return modules[lo - 1][0]
    return None


def load(path: str) -> dict:
    """Events of a profile: ``devices`` maps each chip's plane to its
    operations as ``(name, module, instruction, shape, start_ns, end_ns)``
    (``name`` as ``tracing.load`` gives it, ``module`` the executable's
    name as the trace gives it, fingerprint included); ``spans`` lists the
    host's ``bench.`` and ``repro.`` spans as ``(name, start_ns,
    end_ns)``."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices, spans = {}, []
    for plane in pd.planes:
        lines = list(plane.lines)
        if tracing.CHIP_PLANE.match(plane.name):
            modules = sorted(
                ((ev.name, float(ev.start_ns),
                  float(ev.start_ns) + float(ev.duration_ns))
                 for ln in lines if ln.name == MODULES_LINE
                 for ev in ln.events), key=lambda m: m[1])
            ops = ([ln for ln in lines if ln.name == tracing.OPS_LINE]
                   or lines)
            evs = []
            for ln in ops:
                for ev in ln.events:
                    s = float(ev.start_ns)
                    instr, shape = parse_event(ev.name)
                    evs.append((ev.name.split("{")[0],
                                _module_of(modules, s), instr, shape, s,
                                s + float(ev.duration_ns)))
            devices[plane.name] = evs
        else:
            spans += [(ev.name, float(ev.start_ns),
                       float(ev.start_ns) + float(ev.duration_ns))
                      for ln in lines for ev in ln.events
                      if ev.name.startswith(SPAN_PREFIXES)]
    return {"devices": devices, "spans": spans}


def _pick_module(candidates: list, seen: set) -> dict:
    """Of the dumped modules that share a name, the one that holds most
    of the (instruction, shape) pairs the trace ran under it."""
    def hits(instrs):
        return sum(1 for i, s in seen if instrs.get(i, ("",))[0] == s)
    return max(candidates, key=hits)


def resolve(devices: dict, hlo: dict) -> dict:
    """{(module, instruction): scope path} for every operation the trace
    ran; an operation whose module or instruction the dump lacks, or whose
    shape differs from the dump's, is unscoped."""
    seen = collections.defaultdict(set)
    for evs in devices.values():
        for _, module, instr, shape, _, _ in evs:
            seen[module].add((instr, shape))
    out = {}
    for module, pairs in seen.items():
        candidates = hlo.get(module.split("(")[0]) if module else None
        if not candidates:
            continue
        instrs = _pick_module(candidates, pairs)
        for instr, shape in pairs:
            got = instrs.get(instr)
            if got is not None and got[0] == shape:
                out[(module, instr)] = got[1]
    return out


def _length(intervals) -> float:
    return sum(e - s for s, e in tracing.merge(intervals))


def reduce(events: dict, hlo: dict) -> dict:
    """``tracing.reduce`` of the profile, with the gaps named by the
    innermost span of either kind, plus:

    - ``scope_s``: each scope's device seconds (union over its operations,
      clipped to the window, averaged over chips);
    - ``unscoped_s``: busy seconds under no scope;
    - ``scopes``: the ``TOP`` innermost scope paths by device seconds;
    - ``spans``: each program span's count and seconds in the window."""
    plain = {p: [(n, s, e) for n, _, _, _, s, e in evs]
             for p, evs in events["devices"].items()}
    out = tracing.reduce({"devices": plain, "spans": events["spans"]})
    windows = [(s, e) for n, s, e in events["spans"]
               if n == tracing.WINDOW_SPAN]
    all_ops = [(s, e) for evs in plain.values() for _, s, e in evs]
    lo = min(s for s, _ in (windows or all_ops))
    hi = max(e for _, e in (windows or all_ops))
    paths = resolve(events["devices"], hlo)
    n_dev = len(plain)
    scope_ns: dict = collections.Counter()
    path_ns: dict = collections.Counter()
    scoped_ns = 0.0
    for evs in events["devices"].values():
        by_path = collections.defaultdict(list)
        for _, module, instr, _, s, e in evs:
            if e > lo and s < hi:
                by_path[paths.get((module, instr), UNSCOPED)].append(
                    (max(s, lo), min(e, hi)))
        by_scope = collections.defaultdict(list)
        for path, ivs in by_path.items():
            path_ns["/".join(path)] += _length(ivs)
            for name in set(path):
                by_scope[name] += ivs
        for name, ivs in by_scope.items():
            scope_ns[name] += _length(ivs)
        scoped_ns += _length([iv for p, ivs in by_path.items() if p
                              for iv in ivs])
    path_ns.pop("", None)
    span_stats: dict = {}
    for name, s, e in events["spans"]:
        if name.startswith(SCOPE_PREFIX) and e > lo and s < hi:
            count, ns = span_stats.get(name, (0, 0.0))
            span_stats[name] = (count + 1, ns + min(e, hi) - max(s, lo))
    out.update(
        scope_s={k: v / n_dev / 1e9 for k, v in sorted(scope_ns.items())},
        unscoped_s=max(out["busy_s"] - scoped_ns / n_dev / 1e9, 0.0),
        scopes=[[p, v / n_dev / 1e9] for p, v in sorted(
            path_ns.items(), key=lambda kv: -kv[1])[:TOP]],
        spans={k: {"count": c, "s": ns / 1e9}
               for k, (c, ns) in sorted(span_stats.items())})
    return out


def share(reduced: dict, scope: str):
    """``scope``'s device seconds over busy seconds, in %; None where the
    profile has no device time under it."""
    s = reduced["scope_s"].get(SCOPE_PREFIX + scope)
    if not s or reduced["busy_s"] <= 0:
        return None
    return 100.0 * s / reduced["busy_s"]


def mean_span_ms(reduced: dict, name: str):
    """The mean length of a program span in the window, in ms."""
    st = reduced["spans"].get(SCOPE_PREFIX + name)
    if not st:
        return None
    return 1e3 * st["s"] / st["count"]


def host_setup_s(stats: dict | None):
    """Inspection and ELL packing seconds, from ``schedule_cache_stats()``
    read at set-up's end."""
    if not stats or "inspect_s" not in stats:
        return None
    return float(stats["inspect_s"]) + float(stats["pack_s"])


def layer_numbers(reduced: dict, stats: dict | None) -> dict:
    """The per-layer readings of a traced run."""
    busy = reduced["busy_s"]
    return {
        "spill_share": share(reduced, "spill"),
        "backward_share": share(reduced, "backward"),
        "ell_share": share(reduced, "ell_body"),
        "dispatch_ms": mean_span_ms(reduced, "dispatch"),
        "inspect_s": host_setup_s(stats),
        "unscoped_share": (100.0 * reduced["unscoped_s"] / busy
                           if busy > 0 else None),
    }


def main(argv=None) -> int:
    import json
    import shutil
    import tempfile

    from bench import compare, device, harness

    args = harness.parse(argv)
    args.trace = 1
    dump = tempfile.mkdtemp(prefix="bench-hlo-")
    # read when the backend starts, which is below
    os.environ["XLA_FLAGS"] = " ".join(
        filter(None, [os.environ.get("XLA_FLAGS"), dump_flags(dump)]))
    c = harness.resolve(args.workload)
    import jax

    from repro.core.tilefusion import api
    from repro.kernels.config import default_interpret
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        devices = device.require_tpu(int(c["workload"]["chips"]),
                                     default_interpret())
    except device.NoChip as e:
        harness.log(f"cannot measure: {e}")
        return 3
    jax.config.update("jax_default_matmul_precision",
                      c["config"]["matmul_precision"])

    class Context(harness.Context):
        def setup_done(self):
            super().setup_done()
            self.stats = api.schedule_cache_stats()

    ctx = Context(args, T_START, c["config"], c["traffic"], devices)
    out = c["runner"].run(ctx)
    rows = compare.checks(out["numbers"], c["traffic"]["limits"])
    try:
        reduced = reduce(load(ctx.captured["path"]), read_dump(dump))
    finally:
        tracing.discard(ctx.captured)
        shutil.rmtree(dump, ignore_errors=True)
    print(json.dumps({
        "workload": args.workload, "seed": args.seed,
        "correct": compare.passed(rows) and out["failed"] == 0,
        "units": out["attempted"], "wall_s": out["records"]["wall_s"],
        "setup_s": ctx.setup_s, "stats_at_setup": ctx.stats,
        "numbers": layer_numbers(reduced, ctx.stats), "trace": reduced,
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
