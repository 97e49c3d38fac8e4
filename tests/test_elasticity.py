"""The closed elasticity loop: telemetry bus snapshots, race-free per-sender
broker stats, ElasticController policies (scale up/down, batch-cap
adaptation), Session-owned control-plane lifecycle, and detector-driven
endpoint failover.

Timing-sensitive tests run on a ``VirtualClock``: waits are condition polls
on simulated time (no real sleeping, no flake); where a real wall-clock
pipeline is the point, waits go through ``Clock.wait`` condition polling
instead of hand-rolled deadline/sleep loops."""
import threading

import numpy as np
import pytest

from repro.core.broker import Broker, BrokerConfig
from repro.core.grouping import GroupPlan
from repro.runtime.clock import VirtualClock, ensure_clock
from repro.runtime.controller import (Action, BatchCapPolicy,
                                      ElasticController, ElasticityConfig,
                                      LatencyScalePolicy, TrendScalePolicy)
from repro.runtime.fault import FailureDetector
from repro.runtime.telemetry import TelemetryBus, TelemetrySnapshot
from repro.streaming.endpoint import make_endpoints
from repro.streaming.engine import StreamEngine
from repro.workflow import Session, WorkflowConfig

from one_stage import one_stage


# ------------------------------------------------- race-free per-sender stats
def test_broker_stats_exact_under_concurrent_writers():
    """All counters must be exact when many producer threads hammer the same
    group sender (the seed's shared unlocked dataclass lost += updates)."""
    eps = make_endpoints(1)
    broker = Broker(GroupPlan(8, 1, 1), eps,
                    BrokerConfig(compress="none", backpressure="block",
                                 queue_capacity=4096))
    n_threads, per_thread = 8, 400
    payload = np.zeros(16, np.float32)

    def hammer(rank):
        for s in range(per_thread):
            broker.write("f", rank, s, payload)

    threads = [threading.Thread(target=hammer, args=(r,))
               for r in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    stats = broker.finalize()
    total = n_threads * per_thread
    assert stats.written == total
    assert stats.sent + stats.dropped == total
    assert stats.dropped == 0 and stats.sent == total
    assert eps[0].handle.records_in == total


def test_broker_group_telemetry_shape():
    eps = make_endpoints(2)
    broker = Broker(GroupPlan(4, 2, 1), eps, BrokerConfig(compress="none"))
    for r in range(4):
        broker.write("f", r, 0, np.zeros(4, np.float32))
    broker.flush()
    rows = broker.group_telemetry()
    assert [r["group"] for r in rows] == [0, 1]
    for row in rows:
        assert row["written"] == 2 and row["sent"] == 2
        assert row["batch_cap"] == broker.cfg.max_batch_records
        assert row["queue_depth"] == 0
    broker.finalize()


def test_broker_set_batch_cap_and_reroute():
    eps = make_endpoints(2)
    broker = Broker(GroupPlan(4, 2, 1), eps, BrokerConfig(compress="none"))
    broker.set_batch_cap(64)
    assert all(r["batch_cap"] == 64 for r in broker.group_telemetry())
    broker.set_batch_cap(4, group=1)
    assert [r["batch_cap"] for r in broker.group_telemetry()] == [64, 4]
    # proactive failover off a dead endpoint
    eps[0].handle.fail()
    moved = broker.reroute_from_endpoint(0)
    assert moved == 1                       # group 0's primary was endpoint 0
    assert all(s.primary == 1 for s in broker._senders.values())
    broker.finalize()


# ------------------------------------------------------------- telemetry bus
def _slow_analyzer(cost=0.005, clock=None):
    clk = ensure_clock(clock)

    def analyze(key, recs):
        if cost:
            clk.sleep(cost * len(recs))
        return len(recs)
    return analyze


def test_telemetry_snapshot_covers_all_layers():
    clk = VirtualClock()
    clk.attach()
    cfg = WorkflowConfig(n_producers=2, n_groups=1, executors_per_group=2,
                         compress="none", trigger_interval=0.05, min_batch=1)
    with Session(cfg, pipeline=one_stage(_slow_analyzer(0.0)),
                 clock=clk) as sess:
        h = sess.open_field("f", shape=(8,))
        bus = TelemetryBus(broker=sess.broker,
                           endpoints=[e.handle for e in sess.endpoints],
                           engine=sess.engine, clock=clk)
        for s in range(6):
            h.write_batch(s, [np.zeros(8, np.float32)] * 2, ranks=[0, 1])
        sess.flush()
        assert clk.wait(lambda: sess.engine.metrics()["n_results"] > 0,
                        timeout=5.0)
        snap = bus.sample()
    assert isinstance(snap, TelemetrySnapshot)
    assert len(snap.groups) == 1 and snap.groups[0].written == 12
    assert len(snap.endpoints) == 1 and snap.endpoints[0].records_in == 12
    assert snap.alive_executors == 2
    assert snap.latency_n > 0 and snap.latency_p99 >= 0
    assert bus.last() is snap and snap in bus.history


def test_telemetry_rates_from_sample_deltas():
    clk = VirtualClock()
    clk.attach()
    eps = make_endpoints(1, clock=clk)
    broker = Broker(GroupPlan(1, 1, 1), eps,
                    BrokerConfig(compress="none", queue_capacity=4,
                                 backpressure="drop_oldest"), clock=clk)
    bus = TelemetryBus(broker=broker, endpoints=[e.handle for e in eps],
                       clock=clk)
    bus.sample()
    eps[0].handle.fail()                    # queue fills -> drops accumulate
    for s in range(64):
        broker.write("f", 0, s, np.zeros(4, np.float32))
    clk.sleep(0.1)                          # a dt>0 between rate samples
    snap = bus.sample()
    assert snap.groups[0].dropped > 0
    assert snap.groups[0].drop_rate > 0
    eps[0].handle.recover()
    broker.finalize()
    clk.detach()


def test_endpoint_ingest_rate_counter():
    eps = make_endpoints(1)
    broker = Broker(GroupPlan(1, 1, 1), eps, BrokerConfig(compress="none"))
    for s in range(20):
        broker.write("f", 0, s, np.zeros(4, np.float32))
    broker.flush()
    h = eps[0].handle
    assert h.ingest_rate(window_s=10.0) > 0
    t = h.telemetry()
    assert t["records_in"] == 20 and t["healthy"] and t["pending"] == 20
    broker.finalize()


# ------------------------------------------------------- config block
def test_elasticity_config_validation():
    with pytest.raises(ValueError, match="min_executors"):
        ElasticityConfig(min_executors=5, max_executors=2).validate()
    with pytest.raises(ValueError, match="interval_s"):
        ElasticityConfig(interval_s=0).validate()
    with pytest.raises(ValueError, match="batch_cap"):
        ElasticityConfig(batch_cap_min=8, batch_cap_max=2).validate()
    with pytest.raises(ValueError, match="target_p99_s"):
        WorkflowConfig(elasticity=ElasticityConfig(target_p99_s=-1)).validate()


def test_workflow_config_roundtrip_with_elasticity():
    cfg = WorkflowConfig(
        n_producers=4, n_groups=2,
        elasticity=ElasticityConfig(enabled=True, target_p99_s=0.7,
                                    max_executors=9)).validate()
    d = cfg.to_dict()
    assert isinstance(d["elasticity"], dict)        # JSON-serializable
    back = WorkflowConfig.from_dict(d)
    assert back == cfg
    assert back.elasticity.max_executors == 9
    with pytest.raises(ValueError, match="unknown ElasticityConfig keys"):
        WorkflowConfig.from_dict(
            {"n_producers": 2, "elasticity": {"wat": 1}})


def test_workflow_config_clock_knob():
    cfg = WorkflowConfig(clock="virtual", clock_seed=7).validate()
    assert cfg.make_clock().virtual
    d = cfg.to_dict()
    assert d["clock"] == "virtual" and d["clock_seed"] == 7
    assert WorkflowConfig.from_dict(d) == cfg
    with pytest.raises(ValueError, match="clock"):
        WorkflowConfig(clock="sundial").validate()
    # virtual time now composes with the loopback transport (the frames go
    # through VirtualLoopbackTransport instead of real sockets)
    WorkflowConfig(clock="virtual", transport="loopback").validate()
    assert not WorkflowConfig().make_clock().virtual


# ------------------------------------------------------- controller policies
def _mk_loop(n_exec=1, cost=0.02, el=None, n_eps=1, clock=None):
    clk = ensure_clock(clock)
    eps = make_endpoints(n_eps, clock=clk)
    plan = GroupPlan(n_producers=2, n_groups=n_eps, executors_per_group=2)
    broker = Broker(plan, eps, BrokerConfig(compress="none",
                                            backpressure="block",
                                            queue_capacity=4096), clock=clk)
    eng = StreamEngine([e.handle for e in eps],
                       one_stage(_slow_analyzer(cost, clock=clk)).compile(),
                       n_exec, trigger_interval=0.02, min_batch=1, clock=clk)
    bus = TelemetryBus(broker=broker, endpoints=[e.handle for e in eps],
                       engine=eng, clock=clk)
    el = el or ElasticityConfig(enabled=True, interval_s=0.02,
                                target_p99_s=0.2, backlog_high=8,
                                min_executors=1, max_executors=4,
                                cooldown_s=0.0, idle_scale_down_s=0.05)
    ctl = ElasticController(bus, el, engine=eng, broker=broker, clock=clk)
    return broker, eps, eng, bus, ctl


def test_controller_scales_up_on_backlog_breach():
    clk = VirtualClock()
    clk.attach()
    broker, eps, eng, bus, ctl = _mk_loop(n_exec=1, cost=0.05, clock=clk)
    for s in range(40):
        broker.write("f", 0, s, np.zeros(8, np.float32))
    broker.flush()

    def pump():
        eng.trigger_once()
        ctl.tick()
        return eng.metrics()["alive_executors"] > 1

    assert clk.wait(pump, timeout=5.0, poll=0.02)
    kinds = [a.kind for _, a in ctl.actions_log]
    assert "scale_up" in kinds
    eng.drain_and_stop()
    broker.finalize()
    clk.detach()


def test_controller_scales_down_when_idle():
    clk = VirtualClock()
    clk.attach()
    broker, eps, eng, bus, ctl = _mk_loop(n_exec=3, cost=0.0, clock=clk)

    def pump():
        ctl.tick()
        return eng.metrics()["alive_executors"] <= 1

    assert clk.wait(pump, timeout=5.0, poll=0.03)
    assert eng.metrics()["alive_executors"] == 1      # min_executors floor
    assert [a.kind for _, a in ctl.actions_log].count("scale_down") == 2
    eng.drain_and_stop()
    broker.finalize()
    clk.detach()


def test_batch_cap_policy_follows_queue_depth():
    el = ElasticityConfig(enabled=True, batch_cap_min=1, batch_cap_max=128)
    policy = BatchCapPolicy(el, baseline=8)
    # a slow endpoint (token-bucket bandwidth model) makes the sender's
    # queue build up while everything still delivers eventually
    eps = make_endpoints(1, inbound_bw=20_000)
    broker = Broker(GroupPlan(1, 1, 1), eps,
                    BrokerConfig(compress="none", queue_capacity=2048,
                                 backpressure="block", max_batch_records=8))
    bus = TelemetryBus(broker=broker, endpoints=[e.handle for e in eps])
    for s in range(256):
        broker.write("f", 0, s, np.zeros(1024, np.float32))
    acts = policy.decide(bus.sample(), bus.history)
    assert acts and acts[0].kind == "set_batch_cap" and acts[0].value > 8
    broker.set_batch_cap(acts[0].value, group=acts[0].group)
    assert broker.group_telemetry()[0]["batch_cap"] == acts[0].value
    broker.flush(timeout=60)
    # queue drained: cap decays back toward the baseline
    acts = policy.decide(bus.sample(), bus.history)
    assert acts and acts[0].kind == "set_batch_cap"
    assert acts[0].value < broker.group_telemetry()[0]["batch_cap"]
    broker.finalize()


def test_latency_policy_cooldown_and_bounds():
    el = ElasticityConfig(enabled=True, target_p99_s=0.1, cooldown_s=3600,
                          max_executors=2)
    pol = LatencyScalePolicy(el)
    # small t (a virtual-time origin): the FIRST breach must scale even
    # though t < cooldown_s — cooldown only gates scale-to-scale gaps
    breach = TelemetrySnapshot(t=1.0, latency_p50=1.0,
                               latency_p99=1.0, latency_n=10,
                               alive_executors=1)
    acts = pol.decide(breach, [])
    assert len(acts) == 1 and acts[0].kind == "scale_up"
    # cooldown: immediate second breach does nothing
    assert pol.decide(breach, []) == []
    # at max_executors: no scale-up even on breach
    pol2 = LatencyScalePolicy(el)
    at_max = TelemetrySnapshot(t=1.0, latency_p99=1.0, latency_n=10,
                               alive_executors=2)
    assert pol2.decide(at_max, []) == []


def test_trend_policy_scales_before_the_breach():
    """Predictive scale-up: a rising-but-not-yet-breaching p99 series whose
    projection crosses the target within the horizon must trigger, while
    flat sub-target series must not."""
    el = ElasticityConfig(enabled=True, predictive=True, target_p99_s=1.0,
                          trend_window=6, trend_horizon_s=2.0,
                          cooldown_s=0.0, backlog_high=1_000_000)

    def snaps(vals, t0=0.0, dt=0.1):
        return [TelemetrySnapshot(t=t0 + i * dt, latency_p99=v,
                                  latency_n=10, alive_executors=1)
                for i, v in enumerate(vals)]

    pol = TrendScalePolicy(el)
    rising = snaps([0.3, 0.4, 0.5, 0.6, 0.7])      # slope 1.0/s, proj 2.7
    acts = pol.decide(rising[-1], rising)
    assert len(acts) == 1 and acts[0].kind == "scale_up"
    assert acts[0].reason.startswith("projected")
    assert rising[-1].latency_p99 < el.target_p99_s    # fired PRE-breach

    flat = snaps([0.5] * 5)
    assert TrendScalePolicy(el).decide(flat[-1], flat) == []
    falling = snaps([0.9, 0.8, 0.7, 0.6, 0.5])
    assert TrendScalePolicy(el).decide(falling[-1], falling) == []
    # too little history for a slope: no action
    short = snaps([0.3, 0.9])
    assert TrendScalePolicy(el).decide(short[-1], short) == []


def test_trend_policy_projects_backlog_growth():
    el = ElasticityConfig(enabled=True, predictive=True, backlog_high=64,
                          trend_window=5, trend_horizon_s=1.0, cooldown_s=10.0)
    grow = [TelemetrySnapshot(t=i * 0.1, alive_executors=1,
                              executors=(), held_records=i * 10)
            for i in range(5)]                      # backlog 0..40, +100/s
    pol = TrendScalePolicy(el)
    acts = pol.decide(grow[-1], grow)
    assert len(acts) == 1 and acts[0].kind == "scale_up"
    assert "backlog" in acts[0].reason
    # cooldown respected on the very next tick
    assert pol.decide(grow[-1], grow) == []


def test_predictive_plus_reactive_respect_max_executors():
    """Both scale policies deciding off the same stale snapshot must not
    double the step or push the fleet past max_executors: one scale-up per
    tick, and _apply clamps to the cap."""
    clk = VirtualClock()
    clk.attach()
    el = ElasticityConfig(enabled=True, interval_s=0.02, target_p99_s=0.01,
                          min_executors=1, max_executors=3, scale_up_step=2,
                          cooldown_s=0.0, predictive=True, trend_window=3,
                          trend_horizon_s=1.0, backlog_high=1)
    broker, eps, eng, bus, ctl = _mk_loop(n_exec=1, cost=0.05, el=el,
                                          clock=clk)
    assert len(ctl.policies) >= 3           # Trend + Latency + BatchCap
    for s in range(60):                     # saturate: p99 + backlog breach
        broker.write("f", 0, s, np.zeros(8, np.float32))
    broker.flush()
    for _ in range(30):
        eng.trigger_once()
        ctl.tick()
        assert eng.metrics()["alive_executors"] <= el.max_executors, \
            "scale-up overshot max_executors"
        clk.sleep(0.02)
    ups = [a for _, a in ctl.actions_log if a.kind == "scale_up"]
    assert ups, "saturated pipeline must scale up"
    assert eng.metrics()["alive_executors"] == el.max_executors
    eng.drain_and_stop()
    broker.finalize()
    clk.detach()


def test_trend_policy_validation():
    with pytest.raises(ValueError, match="trend_window"):
        ElasticityConfig(trend_window=2).validate()
    with pytest.raises(ValueError, match="trend_horizon_s"):
        ElasticityConfig(trend_horizon_s=0.0).validate()


def test_predictive_spike_scales_before_reactive_on_virtual_time():
    """The ROADMAP claim end-to-end: under a ramping load on virtual time,
    the predictive controller's first scale-up lands EARLIER than the
    reactive controller's, before the p99 target is breached."""
    from repro.sim.scenario import LoadPhase, Scenario, ScenarioRunner

    def run(predictive: bool):
        wf = WorkflowConfig(
            n_producers=4, n_groups=2, executors_per_group=2,
            compress="none", backpressure="block", queue_capacity=4096,
            trigger_interval=0.05, min_batch=4, n_executors=1,
            max_batch_records=8, clock="virtual",
            elasticity=ElasticityConfig(
                enabled=True, interval_s=0.1, target_p99_s=1.5,
                min_executors=1, max_executors=4, scale_up_step=2,
                backlog_high=24, idle_scale_down_s=2.0, cooldown_s=0.3,
                predictive=predictive, trend_window=5, trend_horizon_s=1.0))
        sc = Scenario(workflow=wf,
                      phases=(LoadPhase("low", 2.0, 5.0),
                              LoadPhase("ramp1", 1.5, 20.0),
                              LoadPhase("ramp2", 1.5, 40.0),
                              LoadPhase("spike", 3.0, 60.0),
                              LoadPhase("low", 2.0, 5.0)),
                      seed=0, analysis_cost_s=0.008, payload_elems=64)
        return ScenarioRunner(sc).run()

    reactive, predictive = run(False), run(True)
    def first_scale_up(trace):
        ts = [t for t, d in trace.events_of("action")
              if d["kind"] == "scale_up"]
        return min(ts) if ts else float("inf")

    t_pred, t_react = first_scale_up(predictive), first_scale_up(reactive)
    assert t_pred < float("inf"), "predictive run never scaled"
    assert t_pred < t_react, (
        f"predictive first scale-up at {t_pred}s not earlier than "
        f"reactive at {t_react}s")
    assert any(d["reason"].startswith("projected")
               for _t, d in predictive.events_of("action")
               if d["kind"] == "scale_up")
    # QoS: the predictive run must hold the target through the spike
    assert predictive.phase_p99("spike") <= 1.5


def test_slow_uniform_analysis_is_not_declared_dead():
    """A single analyze call longer than heartbeat_timeout_s must not get a
    healthy executor replaced: busy-mid-analysis is revived by the
    controller (up to stuck_analysis_s), and with uniformly slow peers the
    straggler median flags nobody.  Virtual time: the 4 "seconds" of slow
    uniform analysis cost milliseconds of wall time."""
    clk = VirtualClock()
    clk.attach()
    eps = make_endpoints(1, clock=clk)
    plan = GroupPlan(n_producers=2, n_groups=1, executors_per_group=1)
    broker = Broker(plan, eps, BrokerConfig(compress="none",
                                            backpressure="block",
                                            queue_capacity=4096), clock=clk)
    eng = StreamEngine([e.handle for e in eps],
                       one_stage(_slow_analyzer(0.4, clock=clk)).compile(),
                       n_executors=2, trigger_interval=0.03, min_batch=1,
                       clock=clk)
    bus = TelemetryBus(broker=broker, endpoints=[e.handle for e in eps],
                       engine=eng, clock=clk)
    el = ElasticityConfig(enabled=True, interval_s=0.05,
                          heartbeat_timeout_s=0.15, idle_scale_down_s=3600,
                          target_p99_s=3600, backlog_high=10_000)
    ctl = ElasticController(bus, el, engine=eng, broker=broker, clock=clk)
    deadline = clk.now() + 4.0
    step = 0
    while clk.now() < deadline:
        for r in range(2):
            broker.write("f", r, step, np.zeros(4, np.float32))
        step += 1
        ctl.tick()
        clk.sleep(0.05)
    assert not any(a.kind == "replace_executor"
                   for _, a in ctl.actions_log), \
        "healthy-but-slow executors must not be churned"
    assert all(e.alive for e in eng.executors)
    broker.flush()
    eng.drain_and_stop(timeout=30)
    broker.finalize()
    clk.detach()


# ------------------------------------------- Session-owned control plane
def test_session_owns_controller_lifecycle():
    cfg = WorkflowConfig(
        n_producers=2, n_groups=1, executors_per_group=1, compress="none",
        trigger_interval=0.05, min_batch=1,
        elasticity=ElasticityConfig(enabled=True, interval_s=0.05))
    sess = Session(cfg, pipeline=one_stage(_slow_analyzer(0.0)))
    assert sess.controller is not None and sess.controller.is_alive()
    assert sess.telemetry is not None and sess.detector is not None
    h = sess.open_field("f", shape=(4,))
    for s in range(4):
        h.write(s, np.zeros(4, np.float32), rank=s % 2)
    sess.flush()
    stats = sess.close()
    # ordered teardown: controller stopped first, then broker drained
    assert not sess.controller.is_alive()
    assert stats.sent == 4 and stats.dropped == 0
    assert sess.close().sent == 4           # idempotent
    # telemetry accumulated while running
    assert len(sess.telemetry.history) > 0


def test_session_without_elasticity_has_no_control_plane():
    cfg = WorkflowConfig(n_producers=1, n_groups=1, executors_per_group=1,
                         compress="none")
    with Session(cfg, pipeline=one_stage(_slow_analyzer(0.0))) as sess:
        assert sess.controller is None and sess.telemetry is None


def test_endpoint_failure_detected_and_recovered_no_drops():
    """Acceptance: a mid-run endpoint death is detected via missed
    heartbeats (not just send-path retries), the controller proactively
    re-routes the group, and nothing is dropped under block backpressure.
    Runs on virtual time via the config's clock knob — deterministic and
    milliseconds of wall clock."""
    cfg = WorkflowConfig(
        n_producers=4, n_groups=2, executors_per_group=1, compress="none",
        backpressure="block", queue_capacity=1024, trigger_interval=0.05,
        min_batch=1, clock="virtual",
        elasticity=ElasticityConfig(enabled=True, interval_s=0.05,
                                    heartbeat_timeout_s=0.3,
                                    idle_scale_down_s=3600))
    seen: dict[str, list[int]] = {}
    lock = threading.Lock()

    def analyze(key, records):
        with lock:
            seen.setdefault(key, []).extend(r.step for r in records)
        return len(records)

    sess = Session(cfg, pipeline=one_stage(analyze))
    clk = sess.clock
    h = sess.open_field("f", shape=(8,))
    n_steps = 30
    for s in range(n_steps):
        h.write_batch(s, [np.full(8, float(s), np.float32)] * 4,
                      ranks=[0, 1, 2, 3])
        if s == n_steps // 2:
            sess.endpoints[0].handle.fail()
        clk.sleep(0.02)

    # detector flags the dead endpoint; controller reroutes proactively
    def ep0_flagged():
        node = sess.detector.nodes.get("ep0")
        return node is not None and not node.alive

    assert clk.wait(ep0_flagged, timeout=5.0, poll=0.02)
    sess.flush()
    stats = sess.close()
    assert any(a.kind == "reroute_endpoint"
               for _, a in sess.controller.actions_log)
    assert stats.dropped == 0
    assert stats.sent == stats.written == 4 * n_steps
    for key, steps in seen.items():
        assert steps == sorted(steps), f"stream {key} reordered"
        assert len(steps) == n_steps
