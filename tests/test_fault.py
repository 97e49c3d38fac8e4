"""Fault-tolerance runtime: detection, stragglers, checkpoint-restart, and
the detector→controller→engine recovery loop.

Detector and recovery-loop tests run on a ``VirtualClock``: heartbeat gaps
and straggler cadences are exact simulated intervals instead of real
``time.sleep`` (deterministic, no flake, milliseconds of wall time)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.configs as C
from repro.checkpoint.ckpt import CheckpointManager
from repro.core.broker import Broker, BrokerConfig
from repro.core.grouping import GroupPlan
from repro.data.pipeline import TokenPipeline
from repro.models import transformer as T
from repro.models.modules import materialize
from repro.models.steps import make_train_step
from repro.optim import adamw
from repro.runtime.clock import VirtualClock
from repro.runtime.controller import ElasticController, ElasticityConfig
from repro.runtime.fault import FailureDetector, RestartPolicy
from repro.runtime.telemetry import TelemetryBus
from repro.streaming.endpoint import make_endpoints
from repro.streaming.engine import StreamEngine

from one_stage import one_stage


def test_heartbeat_failure_detection():
    clk = VirtualClock()
    det = FailureDetector(timeout_s=0.1, clock=clk)
    det.register("worker0", "producer")
    det.register("worker1", "producer")
    failed_names = []
    det.on_failure.append(lambda st: failed_names.append(st.name))
    for _ in range(3):
        det.beat("worker0")
        det.beat("worker1")
        clk.sleep(0.02)
    det.beat("worker0")
    clk.sleep(0.15)                  # worker1 misses exactly this window
    det.beat("worker0")
    failed = det.scan()
    assert [f.name for f in failed] == ["worker1"]
    assert failed_names == ["worker1"]
    assert det.nodes["worker0"].alive


def test_straggler_detection():
    clk = VirtualClock()
    det = FailureDetector(timeout_s=10, straggler_factor=3.0, clock=clk)
    flagged = []
    det.on_straggler.append(lambda st: flagged.append(st.name))
    for n in ["fast0", "fast1", "slow"]:
        det.register(n, "executor")
    for i in range(25):               # slow needs >=4 recorded intervals
        det.beat("fast0"); det.beat("fast1")
        clk.sleep(0.01)
        if i % 5 == 4:
            det.beat("slow")          # exactly 5x its peers' beat interval
    det.scan()
    assert "slow" in flagged


def test_straggler_callback_drives_executor_replacement():
    """End-to-end over the real callbacks: a slowed executor's sparse
    heartbeats trip FailureDetector.on_straggler, the ElasticController
    replaces it, the engine rebalances, and every record still lands —
    previously test-only callbacks now close a real loop.  The 25 virtual
    seconds this may need cost well under a second of wall time."""
    clk = VirtualClock()
    clk.attach()
    eps = make_endpoints(1, clock=clk)
    plan = GroupPlan(n_producers=4, n_groups=1, executors_per_group=1)
    broker = Broker(plan, eps, BrokerConfig(compress="none",
                                            backpressure="block",
                                            queue_capacity=4096), clock=clk)

    import threading
    seen: dict[str, list[int]] = {}
    seen_lock = threading.Lock()

    def analyze(key, recs):
        clk.sleep(0.01 * len(recs))
        with seen_lock:
            seen.setdefault(key, []).extend(r.step for r in recs)
        return len(recs)

    eng = StreamEngine([e.handle for e in eps], one_stage(analyze).compile(),
                       n_executors=3, trigger_interval=0.03, min_batch=1,
                       clock=clk)
    straggler = eng.executors[0]
    straggler.slowdown = 0.5               # ~10x its peers' service time
    bus = TelemetryBus(broker=broker, endpoints=[e.handle for e in eps],
                       engine=eng, clock=clk)
    el = ElasticityConfig(enabled=True, interval_s=0.05,
                          heartbeat_timeout_s=10.0, straggler_factor=2.5,
                          min_executors=1, max_executors=8,
                          idle_scale_down_s=3600, target_p99_s=3600)
    ctl = ElasticController(bus, el, engine=eng, broker=broker, clock=clk)
    deadline = clk.now() + 25.0
    written = 0
    while clk.now() < deadline:
        for r in range(4):                 # keep every executor fed
            broker.write("f", r, written, np.zeros(8, np.float32))
        written += 1
        ctl.tick()
        if any(a.kind == "replace_executor" for _, a in ctl.actions_log):
            break
        clk.sleep(0.02)
    assert any(a.kind == "replace_executor" for _, a in ctl.actions_log), \
        "controller never replaced the straggler"
    assert ctl.detector.nodes["executor-0"].marked_straggler
    assert not straggler.alive                 # retired
    assert sum(1 for e in eng.executors if e.alive) >= 3   # replacement up
    broker.flush()
    eng.drain_and_stop(timeout=30)
    broker.finalize()
    clk.detach()
    assert sum(r.n_records for r in eng.collect()) == 4 * written
    for key, steps in seen.items():
        assert steps == sorted(steps), f"{key} reordered across replacement"


def test_dead_executor_heartbeat_timeout_triggers_replacement():
    """An executor whose thread dies (hard kill) stops beating entirely;
    the detector times it out and the controller replaces it."""
    clk = VirtualClock()
    clk.attach()
    eps = make_endpoints(1, clock=clk)
    plan = GroupPlan(n_producers=1, n_groups=1, executors_per_group=2)
    broker = Broker(plan, eps, BrokerConfig(compress="none"), clock=clk)
    eng = StreamEngine([e.handle for e in eps],
                       one_stage(lambda k, recs: len(recs)).compile(),
                       n_executors=1,
                       trigger_interval=0.03, min_batch=1, clock=clk)
    bus = TelemetryBus(broker=broker, endpoints=[e.handle for e in eps],
                       engine=eng, clock=clk)
    el = ElasticityConfig(enabled=True, interval_s=0.05,
                          heartbeat_timeout_s=0.2, stuck_analysis_s=0.3,
                          idle_scale_down_s=3600, target_p99_s=3600)
    ctl = ElasticController(bus, el, engine=eng, broker=broker, clock=clk)
    ctl.tick()                                  # register + first beats
    # simulate a wedged (not cooperatively-killed) executor: alive flag on,
    # but it neither progresses nor empties its queue (the huge slowdown
    # parks it far beyond the test horizon on the virtual timeline)
    from repro.streaming.engine import MicroBatch
    victim = eng.executors[0]
    victim.slowdown = 1e9                       # never finishes anything
    victim.q.put(MicroBatch(stream_key="probe", records=[]))   # being "run"
    victim.q.put(MicroBatch(stream_key="probe", records=[]))   # stuck queued

    def pump():
        ctl.tick()
        return any(a.kind == "replace_executor"
                   for _, a in ctl.actions_log)

    assert clk.wait(pump, timeout=5.0, poll=0.05)
    eng.drain_and_stop(timeout=5)
    broker.finalize()
    clk.detach()


def test_restart_policy_resumes_training(tmp_path):
    """Simulated preemption mid-run; training completes with identical final
    loss to an uninterrupted run."""
    cfg = C.get("mamba2-2.7b").reduced()
    params0 = materialize(T.build_specs(cfg), jax.random.key(0), jnp.float32)
    opt_cfg = adamw.AdamWConfig()
    step_fn = jax.jit(make_train_step(cfg, opt_cfg, 1))
    pipe = TokenPipeline(cfg, batch=2, seq=32)
    total = 6

    def run_clean():
        p, o = params0, adamw.init_opt_state(opt_cfg, params0)
        for s in range(total):
            p, o, m, _ = step_fn(p, o, pipe.batch_at(s))
        return float(m["loss"])

    mgr = CheckpointManager(tmp_path)
    crashed = {"done": False}

    def train_fn(resume):
        if resume is None:
            p, o, s0 = params0, adamw.init_opt_state(opt_cfg, params0), 0
        else:
            tree, s0 = mgr.restore(
                {"params": params0,
                 "opt": adamw.init_opt_state(opt_cfg, params0)})
            p, o = tree["params"], tree["opt"]
        for s in range(s0, total):
            if s == 3 and not crashed["done"]:
                mgr.save(s, {"params": p, "opt": o}, blocking=True)
                crashed["done"] = True
                raise RuntimeError("simulated preemption")
            p, o, m, _ = step_fn(p, o, pipe.batch_at(s))
        train_fn.final_loss = float(m["loss"])
        return total

    policy = RestartPolicy()
    assert policy.run_with_restarts(train_fn, mgr) == total
    assert policy.restarts == 1
    assert train_fn.final_loss == pytest.approx(run_clean(), abs=1e-6)
