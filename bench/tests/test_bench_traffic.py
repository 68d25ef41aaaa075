"""The load generator: seeded schedules, timing from when a request was
due, and the latency arithmetic."""
import statistics
import time

import numpy as np
import pytest

from bench import traffic as T


def test_same_seed_same_schedule():
    a = T.open_schedule(2**31 + 12345, 3.0, 40.0, 1, 4)
    b = T.open_schedule(2**31 + 12345, 3.0, 40.0, 1, 4)
    assert a == b and len(a) > 100


def test_seeds_share_the_work_in_another_order():
    a = T.open_schedule(1, 3.0, 40.0, 1, 4)
    b = T.open_schedule(2, 3.0, 40.0, 1, 4)
    assert [r.due_s for r in a] != [r.due_s for r in b]
    assert sorted(r.samples for r in a) == sorted(r.samples for r in b)
    gaps_a = np.diff([r.due_s for r in a])
    gaps_b = np.diff([r.due_s for r in b])
    assert abs(gaps_a.mean() - 1 / 3.0) < 0.02 and abs(gaps_b.mean() - 1 / 3.0) < 0.02


def test_schedule_stays_in_the_window():
    sched = T.open_schedule(9, 5.0, 10.0, 1, 4)
    assert sched[0].due_s == 0.0
    assert all(0 <= r.due_s < 10.0 for r in sched)
    assert {r.samples for r in sched} == {1, 2, 3, 4}


class _Done:
    """A request the fake server answers the moment it is submitted."""

    status = "done"

    def __init__(self):
        self.t_done = time.perf_counter()


def test_injected_stall_shows_in_p95():
    """A stall in submit makes every later request late; timed from when it
    was due, the stall shows in the tail, as it would not from submit."""
    sched = T.open_schedule(3, 200.0, 0.2, 1, 1)
    assert len(sched) >= 30

    def run(stall_at):
        def submit(r):
            if r.index == stall_at:
                time.sleep(0.3)
            return _Done()
        outcomes, _t0 = T.drive_open(submit, sched)
        return ([(o.handle.t_done - o.due_abs) * 1e3 for o in outcomes],
                [(o.handle.t_done - o.submitted) * 1e3 for o in outcomes])

    calm, _ = run(stall_at=None)
    stalled, from_submit = run(stall_at=len(sched) // 4)
    assert T.quantile(calm, 0.95) < 50
    assert T.quantile(stalled, 0.95) > 150
    assert T.quantile(from_submit, 0.95) < 50


def test_closed_loop_keeps_clients_outstanding():
    outstanding, peak = [0], [0]

    class H:
        status = "done"

    def submit(r):
        outstanding[0] += 1
        peak[0] = max(peak[0], outstanding[0])
        return H()

    def wait(h):
        time.sleep(0.01)
        outstanding[0] -= 1
        h.t_done = time.perf_counter()

    outcomes, _ = T.drive_closed(submit, wait, clients=2, samples=16, seconds=0.1)
    assert peak[0] == 2
    assert all(o.request.samples == 16 for o in outcomes)
    assert len(outcomes) >= 5


@pytest.mark.parametrize("q", [0.0, 0.25, 0.5, 0.95, 1.0])
def test_quantile_is_numpys(q):
    v = list(np.random.default_rng(0).exponential(size=37))
    assert T.quantile(v, q) == pytest.approx(float(np.quantile(v, q)))


def test_spread_uses_statistics_quartiles():
    v = [10.0, 11.0, 12.0, 13.0, 30.0, 9.0]
    q1, q2, q3 = statistics.quantiles(v, n=4)
    assert T.spread(v) == pytest.approx((q3 - q1) / q2)


def test_rng_takes_large_seeds():
    a = T.rng_for(2**40 + 3, 2, 5).standard_normal(4)
    b = T.rng_for(2**40 + 3, 2, 5).standard_normal(4)
    assert np.array_equal(a, b)
