"""The trace reduction: busy is a union, overlaps count once, idle gaps are
found and named by the host span that covers them."""
import os

import pytest

from bench import tracing

TPU0, TPU1 = "/device:TPU:0", "/device:TPU:1"
RECORDED = os.path.join(os.path.dirname(__file__), "data",
                        "small_tpu.xplane.pb")


def events(devices, spans=()):
    return {"devices": devices, "spans": list(spans)}


def test_merge_unions_nested_overlapping_and_touching():
    assert tracing.merge([(5, 7), (0, 4), (1, 2), (4, 5), (9, 10)]) == [
        (0, 7), (9, 10)]


def test_gaps_between_busy_intervals_and_at_the_ends():
    assert tracing.gaps([(2, 4), (6, 7)], 0, 10) == [(0, 2), (4, 6), (7, 10)]


def test_busy_is_the_union_clipped_to_the_window():
    ev = events({TPU0: [("a", 0, 10), ("b", 5, 15), ("c", 20, 30),
                        ("late", 35, 50)]},
                [("bench.window", 0, 40), ("bench.step", 12, 25)])
    r = tracing.reduce(ev)
    assert r["busy_s"] == pytest.approx(30e-9)     # 0-15, 20-30, 35-40
    assert r["window_s"] == pytest.approx(40e-9)
    # 15-20 lies in the step span; 30-35 only in the window span
    assert r["idle_gaps"] == [["bench.step", pytest.approx(5e-9)],
                              ["bench.window", pytest.approx(5e-9)]]
    ops = dict(r["device_ops"])
    assert ops["a"] == pytest.approx(10e-9) and ops["late"] == \
        pytest.approx(5e-9)


def test_busy_is_averaged_over_devices():
    ev = events({TPU0: [("a", 0, 10)], TPU1: [("a", 0, 30)]},
                [("bench.window", 0, 40)])
    r = tracing.reduce(ev)
    assert r["busy_s"] == pytest.approx(20e-9)


def test_a_trace_without_device_operations_is_refused():
    with pytest.raises(ValueError):
        tracing.reduce(events({}, [("bench.window", 0, 1)]))


def test_recorded_tpu_trace():
    """A small trace recorded on one v5e chip: three steps of two jitted
    programs with a host sleep between them, in a ``bench.window`` span."""
    ev = tracing.load(RECORDED)
    assert list(ev["devices"]) == [TPU0]
    assert {n for n, _, _ in ev["spans"]} >= {"bench.window", "bench.step"}
    r = tracing.reduce(ev)
    assert 0 < r["busy_s"] < r["window_s"]
    busy = sum(e - s for s, e in tracing.merge(
        [(s, e) for _, s, e in ev["devices"][TPU0]]))
    assert r["busy_s"] <= busy / 1e9 + 1e-12
    assert r["idle_gaps"] and r["device_ops"]
