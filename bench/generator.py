"""The load generator's timing: schedules, admission and creation stamps.

The generator stands apart from the system under test.  It stamps every
snapshot when it is made (``time.time()``, the clock the program's sinks
stamp results with) and, for a scheduled traffic, records how late each
write ran against its due time, so a starved generator is not read as a
fast system.

Admission keeps the configuration's guarantee that every snapshot is
analysed.  Windows fire on a watermark that is the furthest any stream's
in-order commit frontier has reached, so a stream that lags by more than
the window's allowed lateness would have its records dropped as late.  The
generator therefore writes event time ``t`` only while ``t`` minus the
slowest stream's committed frontier stays under that lateness: end-to-end
``block`` backpressure.  A stall there is counted as generator lateness in
a scheduled traffic and as producer time in a free-running one.
"""
from __future__ import annotations

import time

from bench.stats import percentile


class Admission:
    """Blocks a write at event time ``t`` until every stream's committed
    frontier is within ``horizon`` of it.  ``streams``: the engine's stream
    keys; a stream with nothing committed yet counts from ``t_first``.

    Each look takes the plan's frontier lock, which every commit takes too,
    and copies every stream's frontier under it, so the poll is slow
    (``poll_s``): the horizon holds seconds of writes, and a write that
    waits a few milliseconds longer leaves the engine no less work."""

    def __init__(self, plan, streams: list[str], horizon: float,
                 t_first: float, poll_s: float = 0.005):
        self.plan = plan
        self.streams = list(streams)
        self.horizon = horizon
        self.t_first = t_first
        self.poll_s = poll_s
        self.waited_s = 0.0

    def slowest(self) -> float:
        fr = self.plan.frontier_snapshot()["streams"]
        floor = self.t_first - 1e-9
        return min(max(fr[k]["committed"], floor) if k in fr else floor
                   for k in self.streams)

    def wait(self, t: float, deadline: float | None = None) -> bool:
        """Wait until ``t`` may be written; False if ``deadline`` (wall
        time) passed first."""
        if t - self.slowest() < self.horizon:
            return True
        t0 = time.time()
        try:
            while t - self.slowest() >= self.horizon:
                if deadline is not None and time.time() >= deadline:
                    return False
                time.sleep(self.poll_s)
            return True
        finally:
            self.waited_s += time.time() - t0


class Schedule:
    """Due times of output steps: ``steps_per_s`` at a fixed rate (open
    loop), or, with ``None``, as soon as admitted.  Lateness is recorded
    per write."""

    def __init__(self, traffic: dict, t0: float):
        self.rate = traffic.get("steps_per_s")
        self.t0 = t0
        self.late: list[float] = []

    def due(self, i: int) -> float | None:
        """Wall time at which output step ``i`` (from 0) is due."""
        return None if self.rate is None else self.t0 + i / self.rate

    def wait(self, i: int) -> None:
        due = self.due(i)
        if due is None:
            return
        now = time.time()
        if now < due:
            time.sleep(due - now)
        self.late.append(max(0.0, time.time() - due))

    def report(self) -> str:
        if not self.late:
            return "generator unscheduled (writes as soon as admitted)"
        return (f"generator_late_ms p50={percentile(self.late, 50) * 1e3:.3f} "
                f"p95={percentile(self.late, 95) * 1e3:.3f} "
                f"max={max(self.late) * 1e3:.3f} over {len(self.late)} writes")
