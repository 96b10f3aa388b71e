"""Profiler capture and the reduction from trace to device metrics.

The run records its window with JAX's profiler (Python tracer off, so the
host pays little) and writes its own host spans with
``jax.profiler.TraceAnnotation``; every span name starts with ``bench.``.
The reduction reads the ``.xplane.pb`` with ``jax.profiler.ProfileData``:

- device busy: the union of the intervals in which an operation ran on a
  device plane, clipped to the ``bench.window`` span, averaged over chips;
- idle share: 1 - busy / window;
- breakdown: the device operations with the most time, and the longest
  idle gaps, each named by the innermost ``bench.`` span that covers it on
  the host.
"""
from __future__ import annotations

import contextlib
import glob
import os
import re
import shutil
import tempfile

WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
#: One plane per chip; the runtime adds other ``/device:`` planes (such as
#: ``/device:CUSTOM:Megascale Trace``) that are not chips.
CHIP_PLANE = re.compile(r"^/device:TPU:\d+$")
#: The device line that holds one event per executed operation; planes
#: that lack it contribute all their lines.
OPS_LINE = "XLA Ops"
TOP = 10


def span(name: str):
    """A host span on the profiler's clock (a no-op when not tracing)."""
    import jax
    return jax.profiler.TraceAnnotation(SPAN_PREFIX + name)


@contextlib.contextmanager
def capture(enabled: bool):
    """Trace the body when ``enabled``; yields a dict that holds, after the
    body, the path of the ``.xplane.pb`` (key ``path``) and the directory
    to delete (key ``dir``)."""
    out: dict = {}
    if not enabled:
        yield out
        return
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    d = tempfile.mkdtemp(prefix="bench-trace-")
    jax.profiler.start_trace(d, profiler_options=opts)
    try:
        yield out
    finally:
        jax.profiler.stop_trace()
        found = glob.glob(os.path.join(d, "**", "*.xplane.pb"),
                          recursive=True)
        out.update(dir=d, path=found[0] if found else None)


def discard(captured: dict) -> None:
    if captured.get("dir"):
        shutil.rmtree(captured["dir"], ignore_errors=True)


def load(path: str) -> dict:
    """Events of a profile as plain tuples ``(name, start_ns, end_ns)``:
    ``devices`` maps each device plane to its operation events, ``spans``
    lists the host's ``bench.`` spans."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices, spans = {}, []
    for plane in pd.planes:
        lines = list(plane.lines)
        if CHIP_PLANE.match(plane.name):
            ops = [ln for ln in lines if ln.name == OPS_LINE] or lines
            # an op's name is its HLO text; keep the name and result type
            devices[plane.name] = [
                (ev.name.split("{")[0], float(ev.start_ns),
                 float(ev.start_ns) + float(ev.duration_ns))
                for ln in ops for ev in ln.events]
        else:
            spans += [(ev.name, float(ev.start_ns),
                       float(ev.start_ns) + float(ev.duration_ns))
                      for ln in lines for ev in ln.events
                      if ev.name.startswith(SPAN_PREFIX)]
    return {"devices": devices, "spans": spans}


def merge(intervals) -> list:
    """The union of ``(start, end)`` intervals as sorted disjoint ones."""
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: float, hi: float) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def gaps(busy: list, lo: float, hi: float) -> list:
    """Idle ``(start, end)`` stretches of ``[lo, hi]`` between the disjoint
    sorted ``busy`` intervals."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def covering_span(spans, t: float) -> str:
    """The innermost (shortest) span that covers time ``t``."""
    best = None
    for name, s, e in spans:
        if s <= t <= e and (best is None or e - s < best[1]):
            best = (name, e - s)
    return best[0] if best else "outside bench spans"


def reduce(events: dict) -> dict:
    """Device busy and window seconds, the top operations and the longest
    idle gaps of a loaded profile (see ``load``)."""
    spans = events["spans"]
    windows = [(s, e) for name, s, e in spans if name == WINDOW_SPAN]
    per_dev = events["devices"]
    if not per_dev or not any(per_dev.values()):
        raise ValueError("the trace holds no device operation")
    if windows:
        lo, hi = min(s for s, _ in windows), max(e for _, e in windows)
    else:
        lo = min(s for evs in per_dev.values() for _, s, _ in evs)
        hi = max(e for evs in per_dev.values() for _, _, e in evs)
    busy_ns, op_ns, gap_list = [], {}, []
    for evs in per_dev.values():
        ivs = merge(clip([(s, e) for _, s, e in evs], lo, hi))
        busy_ns.append(sum(e - s for s, e in ivs))
        for name, s, e in evs:
            if e > lo and s < hi:
                op_ns[name] = op_ns.get(name, 0.0) + min(e, hi) - max(s, lo)
        gap_list += gaps(ivs, lo, hi)
    gap_list.sort(key=lambda g: g[0] - g[1])
    return {
        "busy_s": sum(busy_ns) / len(busy_ns) / 1e9,
        "window_s": (hi - lo) / 1e9,
        "device_ops": [[n, v / 1e9] for n, v in sorted(
            op_ns.items(), key=lambda kv: -kv[1])[:TOP]],
        "idle_gaps": [[covering_span(spans, (s + e) / 2), (e - s) / 1e9]
                      for s, e in gap_list[:TOP]],
    }
