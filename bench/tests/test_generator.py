"""The generator's schedule and admission."""
import threading
import time

import pytest

from bench.generator import Admission, Schedule


def test_fixed_rate_schedule():
    s = Schedule({"steps_per_s": 100.0}, t0=50.0)
    assert s.due(0) == 50.0
    assert s.due(250) == pytest.approx(52.5)
    assert Schedule({"steps_per_s": None}, t0=0.0).due(7) is None


def test_lateness_is_recorded_per_write():
    s = Schedule({"steps_per_s": 1000.0}, t0=time.time() - 1.0)
    s.wait(0)                                  # due a second ago
    s.wait(1100)                               # due 0.1 s from now
    assert len(s.late) == 2
    assert s.late[0] >= 1.0 and s.late[1] < 0.5
    assert "over 2 writes" in s.report()


class _Plan:
    def __init__(self):
        self.committed = {"a": 0.0}

    def frontier_snapshot(self):
        return {"streams": {k: {"committed": v} for k, v in self.committed.items()}}


def test_admission_waits_for_the_slowest_stream():
    plan = _Plan()
    adm = Admission(plan, ["a", "b"], horizon=10.0, t_first=0.0)
    assert adm.wait(9.0)                       # b counts from t_first
    assert not adm.wait(10.0, deadline=time.time() + 0.05)
    plan.committed["b"] = 5.0
    assert not adm.wait(10.0, deadline=time.time() + 0.05)   # a still at 0
    threading.Timer(0.05, lambda: plan.committed.update(a=3.0)).start()
    assert adm.wait(12.5, deadline=time.time() + 5.0)
    assert adm.waited_s > 0.03
