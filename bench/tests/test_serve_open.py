"""The open-loop generator: its schedule, its clock (latency from the
due time) and its report of how late it ran."""
import threading
import time

import numpy as np

import lib

serve_open = lib.load_module("traffic", "serve_open")


class _Future:
    def __init__(self, done_at):
        self.done_at = done_at

    def result(self, timeout=None):
        wait = self.done_at - time.perf_counter()
        if wait > timeout:
            raise TimeoutError("not answered in time")
        if wait > 0:
            time.sleep(wait)
        return [np.zeros(1)]


def test_same_seed_same_schedule():
    a = serve_open.schedule(7, 200.0, 2.0, 16)
    b = serve_open.schedule(7, 200.0, 2.0, 16)
    c = serve_open.schedule(8, 200.0, 2.0, 16)
    assert np.array_equal(a[0], b[0]) and np.array_equal(a[1], b[1])
    assert not np.array_equal(a[0][:10], c[0][:10])
    due, picks = a
    assert np.all(np.diff(due) > 0) and due[-1] < 2.0
    assert len(due) == len(picks) and picks.max() < 16
    # Poisson arrivals at 200/s over 2 s: 400 +- 5 sigma
    assert 300 < len(due) < 500


def test_latency_runs_from_the_due_time_and_lateness_is_reported():
    """The first submit stalls 60 ms. Every request answers 5 ms after
    its submit, so a clock started at the actual submit would read 5 ms
    everywhere; from the due time the stalled requests read the stall."""
    due = np.array([0.0, 0.010, 0.020, 0.200])
    calls = []

    def submit(pick):
        if not calls:
            time.sleep(0.060)
        calls.append(pick)
        return _Future(time.perf_counter() + 0.005)

    import contextlib

    t0 = time.perf_counter() + 0.01
    latency, late = serve_open.drive(
        submit, due, [3, 1, 2, 0], t0, 5.0,
        lambda name: contextlib.nullcontext())
    assert calls == [3, 1, 2, 0]
    assert latency[0] >= 0.065
    assert latency[1] >= 0.055 - 1e-3   # due at 10 ms, sent at >= 60 ms
    assert latency[2] >= 0.045 - 1e-3
    assert 0.005 <= latency[3] < 0.030  # the stall is over by then
    assert late[1] >= 0.049 and late[2] >= 0.039
    assert late[0] < 0.005 and late[3] < 0.005


def test_unanswered_and_refused_requests_are_misses():
    import contextlib

    def submit(pick):
        if pick == 1:
            raise RuntimeError("refused")
        return _Future(time.perf_counter() + (10.0 if pick == 2 else 0.001))

    latency, _ = serve_open.drive(
        submit, np.array([0.0, 0.001, 0.002]), [0, 1, 2],
        time.perf_counter(), 0.05, lambda name: contextlib.nullcontext())
    assert latency[0] is not None
    assert latency[1] is None and latency[2] is None
    assert threading.active_count() >= 1
