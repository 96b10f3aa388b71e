"""Shares of the chip's peak, read from a run's records (see
``harness``): the counted work's least time over the measured time."""
from __future__ import annotations

import sys

from bench import counts


def least_time_per_unit(records: dict) -> float:
    t, binds = counts.least_time_s(records["counts"], records["peak"])
    print(f"roofline: least time {t!r}s per unit, bound by {binds}",
          file=sys.stderr, flush=True)
    return t


def mfu(records: dict) -> float:
    """Least time over the wall time per unit (step or product), in %."""
    return 100.0 * least_time_per_unit(records) * records["steps"] \
        / records["wall_s"]


def kernel_roofline(records: dict) -> float | None:
    """Least time over the device's busy time per unit, in %."""
    tr = records.get("trace")
    if not tr or tr["busy_s"] <= 0:
        return None
    return 100.0 * least_time_per_unit(records) * records["steps"] \
        / tr["busy_s"]


def idle_share(records: dict) -> float | None:
    tr = records.get("trace")
    if not tr:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
