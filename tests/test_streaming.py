"""Streaming engine: micro-batching, work stealing, executor failure,
elastic scaling — the Spark-side semantics the paper leans on."""
import time

import numpy as np
import pytest

from repro.core.broker import Broker, BrokerConfig
from repro.core.grouping import GroupPlan
from repro.core.records import StreamRecord
from repro.runtime.clock import VirtualClock
from repro.streaming.endpoint import make_endpoints
from repro.streaming.engine import MicroBatch, StreamEngine

from one_stage import one_stage


def _push(broker, n_ranks=4, steps=5):
    for s in range(steps):
        for r in range(n_ranks):
            broker.write("f", r, s, np.full(8, float(s), np.float32))


def _mk_engine(n_eps=1, n_exec=2, analyze=None, trigger=0.05, n_ranks=4):
    eps = make_endpoints(n_eps)
    plan = GroupPlan(n_producers=n_ranks, n_groups=n_eps, executors_per_group=2)
    broker = Broker(plan, eps, BrokerConfig(compress="none"))
    analyze = analyze or (lambda key, recs: len(recs))
    eng = StreamEngine([e.handle for e in eps], one_stage(analyze).compile(),
                       n_exec, trigger_interval=trigger)
    return broker, eps, eng


def test_microbatches_and_collect():
    broker, eps, eng = _mk_engine()
    _push(broker, steps=6)
    broker.flush()
    eng.drain_and_stop()
    results = eng.collect()
    assert sum(r.n_records for r in results) == 24
    keys = {r.stream_key for r in results}
    assert len(keys) == 4                      # one stream per rank
    stats = eng.latency_stats()
    assert stats["n"] > 0 and stats["mean"] >= 0


def test_sticky_partition_assignment():
    broker, eps, eng = _mk_engine(n_exec=3)
    _push(broker, steps=10)
    broker.flush()
    eng.drain_and_stop()
    by_key = {}
    for r in eng.collect():
        by_key.setdefault(r.stream_key, set()).add(r.executor)
    # fixed subset mapping (allow steal-induced exceptions on at most 1 key)
    sticky = sum(1 for execs in by_key.values() if len(execs) == 1)
    assert sticky >= len(by_key) - 1


def test_work_stealing_absorbs_straggler():
    # manual triggering for determinism: the straggler's queue must be
    # visibly deep before the fast executor goes idle
    broker, eps, eng = _mk_engine(n_exec=2, trigger=30)
    straggler = eng.executors[0]
    straggler.slowdown = 0.3
    total = 0
    for wave in range(6):                      # many small micro-batches
        _push(broker, n_ranks=4, steps=1)
        broker.flush()
        total += eng.trigger_once()
        time.sleep(0.02)
    assert total > 0
    eng.drain_and_stop(timeout=30)
    stolen = sum(e.stolen for e in eng.executors)
    assert stolen > 0, "idle executor should have stolen work"
    assert sum(r.n_records for r in eng.collect()) == 24


def test_drain_waits_out_a_trigger_in_flight():
    """``drain_and_stop`` must not stop while a trigger holds records it
    has taken from the endpoints but not yet queued: the executor, busy
    meanwhile, would exit with them queued and no survivor to run them."""
    import threading
    busy = threading.Event()

    def analyze(key, recs):
        busy.wait(5.0)                     # the one executor stays busy
        return len(recs)

    broker, eps, eng = _mk_engine(n_exec=1, analyze=analyze, trigger=30,
                                  n_ranks=1)
    broker.write("f", 0, 0, np.zeros(8, np.float32))
    broker.flush()
    assert eng.trigger_once(force=True) == 1
    broker.write("f", 0, 1, np.ones(8, np.float32))
    broker.flush()
    # a trigger in flight: it has drained the endpoint and not yet queued
    picking, go = threading.Event(), threading.Event()
    pick = eng._pick_executor

    def slow_pick(*args, **kw):
        picking.set()
        go.wait(5.0)
        return pick(*args, **kw)

    eng._pick_executor = slow_pick
    trigger = threading.Thread(target=eng.trigger_once,
                               kwargs={"force": True})
    trigger.start()
    assert picking.wait(5.0)
    del eng._pick_executor
    drain = threading.Thread(target=eng.drain_and_stop)
    drain.start()
    time.sleep(0.2)                        # the drain looks meanwhile
    go.set()
    trigger.join(5.0)
    time.sleep(0.2)                        # the drain decides meanwhile
    busy.set()
    drain.join(30.0)
    assert not trigger.is_alive() and not drain.is_alive()
    assert sum(r.n_records for r in eng.collect()) == 2


def test_executor_failure_reassigns():
    broker, eps, eng = _mk_engine(n_exec=2, trigger=10)  # driver won't fire
    _push(broker, steps=4)
    broker.flush()
    n = eng.trigger_once()
    assert n > 0
    # kill the executor holding queued partitions
    victim = max(eng.executors, key=lambda e: e.q.qsize())
    eng.kill_executor(victim.idx)
    eng.drain_and_stop()
    assert sum(r.n_records for r in eng.collect()) == 16
    assert all(r.executor != victim.idx or True for r in eng.collect())


def test_per_stream_order_survives_stealing():
    """Regression for the steal ordering hazard: a stolen micro-batch must
    never be analyzed concurrently with — or ahead of — an earlier
    micro-batch of the same stream still on the sticky executor.  A slowed
    executor + single hot stream forces steals; per-stream sequence tickets
    must keep analysis order == dispatch order."""
    import threading
    order: dict[str, list[int]] = {}
    in_flight: dict[str, int] = {}
    overlap = []
    lock = threading.Lock()

    def analyze(key, recs):
        with lock:
            if in_flight.get(key):
                overlap.append(key)        # concurrent same-stream analysis
            in_flight[key] = in_flight.get(key, 0) + 1
        time.sleep(0.01)
        with lock:
            in_flight[key] -= 1
            order.setdefault(key, []).extend(r.step for r in recs)
        return len(recs)

    broker, eps, eng = _mk_engine(n_exec=2, analyze=analyze, trigger=30,
                                  n_ranks=1)
    eng.min_batch = 1
    eng.executors[0].slowdown = 0.05
    for step in range(30):                 # many 1-record batches, one stream
        broker.write("f", 0, step, np.full(8, float(step), np.float32))
        broker.flush()
        eng.trigger_once()
    eng.drain_and_stop(timeout=30)
    stolen = sum(e.stolen for e in eng.executors)
    assert stolen > 0, "scenario must actually exercise stealing"
    assert not overlap, f"concurrent same-stream analysis on {overlap}"
    for key, steps in order.items():
        assert steps == sorted(steps), f"stream {key} reordered: {steps}"
    assert sum(len(s) for s in order.values()) == 30
    assert eng.order_timeouts == 0


def test_rebalance_releases_only_idle_streams():
    """Scale events must not migrate a backlogged stream away from the
    executor still holding its dispatched batches (ordering would stall);
    only fully-drained streams are released for reassignment."""
    broker, eps, eng = _mk_engine(n_exec=2, trigger=30)
    for e in eng.executors:
        e.slowdown = 0.3               # keep dispatched batches unfinished
    _push(broker, steps=4)
    broker.flush()
    assert eng.trigger_once() > 0
    with eng._tlock:
        assigned_before = dict(eng._assign)
    assert assigned_before
    released = eng.rebalance()
    assert released == 0, "busy streams must keep their assignment"
    with eng._tlock:
        assert eng._assign == assigned_before
    for e in eng.executors:
        e.slowdown = 0.0
    eng.drain_and_stop(timeout=30)
    # exiting executors hand back their queues and drop their assignments;
    # with everything drained a rebalance has nothing left to hold
    with eng._tlock:
        assert eng._assign == {}
    assert eng.rebalance() == 0


def test_elastic_scale_up_down():
    broker, eps, eng = _mk_engine(n_exec=1, trigger=0.02)
    assert len([e for e in eng.executors if e.alive]) == 1
    eng.add_executor()
    eng.add_executor()
    assert len([e for e in eng.executors if e.alive]) == 3
    _push(broker, steps=6)
    broker.flush()
    removed = eng.remove_executor()
    assert removed is not None
    eng.drain_and_stop()
    assert sum(r.n_records for r in eng.collect()) == 24
    assert len([e for e in eng.executors if e.alive]) == 0  # stopped


def _returns(key, recs):
    return ("seen", key, len(recs))


def _returns_none(key, recs):
    return None


def _raises(key, recs):
    raise RuntimeError(f"bad batch on {key}")


@pytest.mark.parametrize("fn", [_returns, _returns_none, _raises],
                         ids=["value", "none", "raises"])
def test_one_stage_plan_result_value(fn):
    """A callback run as the one-stage plan gives each Result.value as the
    callback itself would: its return value, None, or the exception."""
    broker, eps, eng = _mk_engine(n_exec=2, analyze=fn)
    _push(broker, steps=3)
    broker.flush()
    eng.drain_and_stop()
    results = eng.collect()
    assert sum(r.n_records for r in results) == 12
    for r in results:
        try:
            want = fn(r.stream_key, [None] * r.n_records)
        except RuntimeError as e:
            assert type(r.value) is RuntimeError and r.value.args == e.args
        else:
            assert r.value == want


def test_slowdown_on_ordered_plan_sleeps_after_the_ticket():
    """On a plan with no order-insensitive prefix, a straggler sleeps once
    it holds its ordering turn, not while it waits for it: with every
    executor slowed by S, the second batch of a stream is analyzed S after
    the first (virtual time), not alongside it."""
    slow = 1.0
    clk = VirtualClock()
    clk.attach()
    analyzed: list[tuple[int, float]] = []

    def analyze(key, recs):
        analyzed.append((recs[0].step, clk.now()))
        return len(recs)

    eng = StreamEngine([], one_stage(analyze).compile(), 2,
                       trigger_interval=60.0, clock=clk)
    for ex in eng.executors:
        ex.slowdown = slow
    for seq, ex in enumerate(eng.executors):   # one stream, two executors
        rec = StreamRecord("f", 0, 0, seq, np.zeros(4, np.float32))
        ex.q.put(MicroBatch(stream_key="f/g0/r0", records=[rec], seq=seq))
    assert clk.wait(lambda: len(eng.collect()) == 2, timeout=10.0)
    eng.drain_and_stop()
    clk.detach()
    (s0, t0), (s1, t1) = analyzed
    assert (s0, s1) == (0, 1)
    assert t0 >= slow
    assert t1 - t0 >= slow, "the ticket wait overlapped the slowdown"


class _Feed:
    """An endpoint handle filled by hand: the engine's drain API only."""

    def __init__(self):
        self.buf: dict[str, list] = {}

    def put(self, key, step, t=0.0):
        self.buf.setdefault(key, []).append(
            StreamRecord("f", 0, 0, step, np.zeros(4, np.float32),
                         t_generated=t))

    def stream_keys(self):
        return list(self.buf)

    def drain(self, key):
        return self.buf.pop(key, [])

    def pending(self):
        return sum(len(v) for v in self.buf.values())


@pytest.mark.parametrize("clock,n_exec,n_eps,seconds",
                         [("wall", 128, 8, 1.0), ("virtual", 16, 1, 5.0)])
def test_idle_executors_block_instead_of_polling(clock, n_exec, n_eps,
                                                 seconds):
    """With no traffic an executor wakes only on its safety-net timeout:
    at most 2 wakeups per executor per second, on either clock, and no
    executor is woken to steal."""
    clk = VirtualClock() if clock == "virtual" else None
    if clk is not None:
        clk.attach()
    now = clk.now if clk is not None else time.monotonic
    t0 = now()
    eng = StreamEngine([_Feed() for _ in range(n_eps)],
                       one_stage(lambda k, recs: len(recs)).compile(),
                       n_exec, trigger_interval=0.25, clock=clk)
    if clk is not None:
        clk.sleep(seconds)
    else:
        time.sleep(seconds)
    m = eng.metrics()
    elapsed = now() - t0
    eng.drain_and_stop()
    if clk is not None:
        clk.detach()
    assert m["idle_wakeups"] <= 2 * n_exec * elapsed, m["idle_wakeups"]
    assert m["steal_wakes"] == 0
    assert not any(e.is_alive() for e in eng.executors)


def test_backlog_wakes_an_idle_executor_to_steal():
    """A sticky stream backs up behind a straggler's slow batch of another
    stream: the put that leaves the backlog wakes the idle executor, which
    takes the stream's run and analyses it in sequence order long before
    the straggler's batch ends (and before its own safety-net timeout)."""
    clk = VirtualClock()
    clk.attach()
    feed = _Feed()
    eng = StreamEngine([feed], one_stage(
        lambda k, recs: [r.step for r in recs]).compile(), 2,
        trigger_interval=60.0, min_batch=1, clock=clk)
    straggler, thief = eng.executors
    straggler.slowdown = 5.0
    feed.put("a", 0)
    eng.trigger_once()                  # both queues empty: the first wins
    clk.sleep(0.1)                      # the straggler starts its batch
    assert straggler.current_key == "a"
    t_dispatch = clk.now()
    for step in range(3):               # "b" sticks to the straggler too
        feed.put("b", step)
        eng.trigger_once()
        clk.sleep(0.01)
    assert clk.wait(lambda: len(eng.collect()) == 4, timeout=20.0)
    m = eng.metrics()
    eng.drain_and_stop()
    clk.detach()
    assert m["steal_wakes"] > 0 and thief.stolen > 0
    res = {r.stream_key: [] for r in eng.collect()}
    for r in sorted(eng.collect(), key=lambda r: r.t_analyzed):
        res[r.stream_key].append(r)
    (a,) = res["a"]
    assert [r.value for r in res["b"]] == [[0], [1], [2]]
    assert all(r.executor == thief.idx for r in res["b"])
    assert all(r.t_analyzed < a.t_analyzed for r in res["b"])
    assert res["b"][-1].t_analyzed - t_dispatch < 0.5


@pytest.mark.parametrize("how", ["drain_and_stop", "remove_executor",
                                 "replace_executor", "kill"])
def test_shutdown_reaches_blocked_executors(how):
    """Every way of stopping an executor wakes it from its blocking wait
    on an empty queue at once, well inside the engine's join timeouts and
    before the idle safety-net timeout would."""
    from repro.streaming.engine import _IDLE_WAIT_S
    clk = VirtualClock()
    clk.attach()
    eng = StreamEngine([_Feed()], one_stage(lambda k, recs: 0).compile(),
                       3, trigger_interval=60.0, clock=clk)
    clk.sleep(0.1)                      # every executor blocks, idle
    t0 = clk.now()
    if how == "remove_executor":
        stopped = [eng.executors[eng.remove_executor(1)]]
    elif how == "replace_executor":
        stopped = [eng.executors[0]]
        new = eng.replace_executor(0)
    else:
        stopped = list(eng.executors)
        getattr(eng, how)()
    assert all(clk.join(e, timeout=5.0) for e in stopped)
    took = clk.now() - t0
    if how == "replace_executor":
        assert new.is_alive()
    eng.drain_and_stop()
    clk.detach()
    assert took < _IDLE_WAIT_S / 2, took
    assert not any(e.is_alive() for e in eng.executors)


def test_wakes_and_steals_under_contention_keep_streams_exact():
    """Stress: more executor threads than cores and a short switch
    interval, sticky streams backing up behind two stragglers so that
    dispatches wake idle executors and steals migrate runs all the while.
    Every record is analysed exactly once, each stream in dispatch order
    and never twice at a time, and no ordering wait times out."""
    import os
    import sys
    import threading
    n_exec = 2 * (os.cpu_count() or 4) + 2
    n_streams, rounds = 32, 20
    lock = threading.Lock()
    order: dict[str, list[int]] = {}
    running: dict[str, int] = {}
    overlap = []

    def analyze(key, recs):
        with lock:
            if running.get(key):
                overlap.append(key)
            running[key] = running.get(key, 0) + 1
        time.sleep(0.001)
        with lock:
            running[key] -= 1
            order.setdefault(key, []).extend(r.step for r in recs)
        return len(recs)

    feed = _Feed()
    switch = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        eng = StreamEngine([feed], one_stage(analyze).compile(), n_exec,
                           trigger_interval=30.0, min_batch=1)
        eng.executors[0].slowdown = eng.executors[1].slowdown = 0.02
        for step in range(rounds):
            for s in range(n_streams):
                feed.put(f"s{s}", step)
            eng.trigger_once()
            time.sleep(0.005)
        eng.drain_and_stop(timeout=30)
    finally:
        sys.setswitchinterval(switch)
    m = eng.metrics()
    assert not overlap, f"concurrent same-stream analysis on {overlap}"
    assert sorted(order) == sorted(f"s{s}" for s in range(n_streams))
    for key, steps in order.items():
        assert steps == list(range(rounds)), f"stream {key}: {steps}"
    assert m["order_timeouts"] == 0
    assert m["steal_wakes"] > 0
    assert not any(e.is_alive() for e in eng.executors)
