"""The open-loop generator's due-time accounting: a stalled server shows up
as latency of the requests behind the stall, not as a lower rate."""
import time

import numpy as np
import pytest

from bench import openloop

RATE, SECONDS, STALL_S = 50.0, 1.0, 0.3


def run(stall_at: int | None) -> tuple:
    """Serve a second of requests with a handler that takes no time,
    except one request that stalls for ``STALL_S``."""
    due = openloop.arrivals(seed=2**40 + 3, rate=RATE, seconds=SECONDS,
                            graph_seed=0)

    def handle(i):
        if i == stall_at:
            time.sleep(STALL_S)
        return i
    return due, openloop.serve(due, handle, drain_s=5.0)


def test_arrivals_fill_the_window_with_the_same_gaps_for_every_seed():
    due = openloop.arrivals(seed=7, rate=200.0, seconds=5.0, graph_seed=0)
    assert len(due) == 1000
    assert np.all(np.diff(due) > 0) and due[-1] < 5.0
    np.testing.assert_array_equal(due, openloop.arrivals(7, 200.0, 5.0, 0))
    other = openloop.arrivals(2**33 + 8, 200.0, 5.0, 0)
    assert not np.array_equal(due, other)
    gaps = np.sort(np.diff(np.concatenate([[0.0], due])))
    other_gaps = np.sort(np.diff(np.concatenate([[0.0], other])))
    # the same multiset of gaps, but for the one each leaves past the end
    matched = sum(bool(np.isclose(other_gaps, g, rtol=0, atol=1e-9).any())
                  for g in gaps)
    assert matched >= 999
    # exponential: the coefficient of variation of the gaps is about 1
    assert np.std(gaps) / np.mean(gaps) == pytest.approx(1.0, abs=0.1)


def test_a_stall_is_latency_not_a_lower_rate():
    _, base = run(stall_at=None)
    due, stalled = run(stall_at=5)
    n = len(due)
    # every request due in the window is served, stall or not
    assert len(stalled["outs"]) == len(base["outs"]) == n
    assert stalled["failed"] == 0
    # the generator kept sending on time while the server stalled
    assert np.nanmax(stalled["send_lag_s"]) < 0.05
    # requests due during the stall wait for it: latency from their due time
    stall_start = due[5]
    behind = [i for i in range(6, n) if due[i] < stall_start + STALL_S]
    assert behind
    for i in behind:
        assert stalled["latency_s"][i] >= stall_start + STALL_S - due[i] - 0.02
    assert np.max(base["latency_s"]) < 0.05
    assert np.percentile(stalled["latency_s"], 95) > 0.1


def test_a_failed_request_is_counted():
    due = openloop.arrivals(seed=3, rate=RATE, seconds=0.2, graph_seed=0)

    def handle(i):
        if i == 2:
            raise RuntimeError("planted")
        return i
    out = openloop.serve(due, handle, drain_s=5.0)
    assert out["failed"] == 1 and 2 not in out["outs"]
    assert np.isinf(out["latency_s"][2])
