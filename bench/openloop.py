"""Open-loop load: requests put on a queue at their due times by a
generator thread, served in order, and each timed from its due time.

A server that stalls shows as latency of every request behind the stall,
never as a lower offered rate.  Nothing here imports the program.
"""
from __future__ import annotations

import queue
import threading
import time

import numpy as np

from bench import seeds


def arrivals(seed: int, rate: float, seconds: float,
             graph_seed: int) -> np.ndarray:
    """Due times (seconds from the window's start) of ``rate * seconds``
    requests: exponential gaps drawn once from the configuration's
    ``graph_seed`` and scaled to fill the window, in an order drawn from
    ``seed``.  Every seed offers the same requests and the same gaps, in
    another order."""
    n = int(rate * seconds)
    gaps = seeds.rng(graph_seed, 3).exponential(1.0 / rate, size=n + 1)
    gaps *= seconds / gaps.sum()
    return np.cumsum(seeds.rng(seed, 3).permutation(gaps))[:n]


def serve(due: np.ndarray, handle, drain_s: float) -> dict:
    """Serve every request due (``due``, seconds from the start) with
    ``handle(i)``, which returns the request's result once it is ready.

    Returns each request's latency from its due time (inf where it failed
    or never came), how late the generator sent it, the results, and the
    count of failed requests.  Requests are waited for until ``drain_s``
    after the last due time; a request that raises counts as failed."""
    q: queue.Queue = queue.Queue()
    t0 = time.perf_counter() + 0.01
    send = np.full(len(due), np.nan)

    def generate():
        for i, d in enumerate(due):
            wait = t0 + d - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            send[i] = time.perf_counter() - t0
            q.put(i)
    gen = threading.Thread(target=generate, daemon=True)
    gen.start()
    lat = np.full(len(due), np.inf)
    outs, failed = {}, 0
    deadline = t0 + (float(due[-1]) if len(due) else 0.0) + drain_s
    for i in range(len(due)):
        try:
            q.get(timeout=max(deadline - time.perf_counter(), 0.0))
        except queue.Empty:
            failed += len(due) - i
            break
        try:
            outs[i] = handle(i)
        except Exception:   # a failed request is counted, not fatal
            failed += 1
            continue
        lat[i] = time.perf_counter() - t0 - due[i]
    gen.join(timeout=drain_s)
    return {"latency_s": lat, "send_lag_s": send - due, "outs": outs,
            "failed": failed}
