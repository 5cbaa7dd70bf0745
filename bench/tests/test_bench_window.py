"""The measured window of the batch cells: jobs dispatched ahead, every job
started is finished inside the window, and none starts after its time."""

from __future__ import annotations

import sys
import time
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import harness  # noqa: E402
from bench.jobs import BatchJobs  # noqa: E402


class Jobs:
    """Fake jobs that log when each starts and finishes."""

    def __init__(self, job_s: float):
        self.job_s = job_s
        self.started, self.finished, self.in_flight = [], [], []

    def start(self):
        n = len(self.started)
        self.started.append(time.perf_counter())
        self.in_flight.append(len(self.started) - len(self.finished))
        return n

    def finish(self, n):
        time.sleep(self.job_s)
        self.finished.append(time.perf_counter())
        return n


@pytest.mark.parametrize("ahead", [0, 1, 5])
def test_every_started_job_finishes_inside_the_window(ahead):
    jobs = Jobs(0.002)
    window_s, outs = harness.run_jobs(jobs.start, jobs.finish, 0.05, ahead)
    assert outs == list(range(len(jobs.started)))
    assert len(jobs.finished) == len(jobs.started)
    assert max(jobs.in_flight) == ahead + 1
    # the window's clock starts before the first job and stops after the last
    t0 = jobs.started[0]
    assert all(s - t0 < 0.05 for s in jobs.started)
    assert window_s >= 0.05
    assert jobs.finished[-1] - t0 <= window_s


def test_without_ahead_the_window_closes_on_the_first_job_past_its_time():
    jobs = Jobs(0.004)
    window_s, outs = harness.run_jobs(jobs.start, jobs.finish, 0.03)
    assert max(jobs.in_flight) == 1
    # every job but the last ended before the time was up
    t0 = jobs.started[0]
    assert all(f - t0 < 0.03 for f in jobs.finished[:-1])
    assert window_s >= 0.03


class Traffic(BatchJobs):
    def __init__(self, traffic: dict, warm_s: float):
        self.run = type("Run", (), {"traffic": traffic})()
        self.warm_s = warm_s


@pytest.mark.parametrize("traffic, warm_s, ahead", [
    ({}, 0.24, 0),
    ({"ahead_s": 6}, 0.24, 25),
    ({"ahead_s": 6}, 3.15, 2),
    ({"ahead_s": 6}, 20.0, 1),
])
def test_jobs_ahead_are_the_traffic_seconds_over_the_warm_job(
        traffic, warm_s, ahead):
    assert Traffic(traffic, warm_s).ahead() == ahead
