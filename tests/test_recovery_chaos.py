"""Exactly-once delivery under chaos: WAL-backed replay across endpoint
failover, broker restarts, and whole-session kill/restore — gated on the
two oracles the paper's realtime-insight story needs: the loss ledger
closes (nothing silently vanishes) and the sink contents are byte-identical
to a fault-free same-seed run (nothing is double-applied either)."""
import numpy as np
import pytest

from repro.checkpoint.session_store import SessionCheckpointStore
from repro.runtime.wal import WalStore
from repro.sim.scenario import (Fault, LoadPhase, Scenario, run_scenario,
                                sink_digest)
from repro.streaming.operators import OperatorPipeline
from repro.workflow import ElasticityConfig, WorkflowConfig
from repro.workflow.session import Session

from one_stage import one_stage

SEEDS = [0, 1, 2]


def _wf(elastic=False, **kw):
    el = ElasticityConfig(enabled=elastic, interval_s=0.1,
                          heartbeat_timeout_s=0.5, min_executors=1,
                          max_executors=4, cooldown_s=0.3)
    base = dict(n_producers=4, n_groups=2, executors_per_group=2,
                compress="none", backpressure="block", queue_capacity=4096,
                trigger_interval=0.05, min_batch=4, n_executors=2,
                max_batch_records=8, delivery="exactly-once",
                clock="virtual", flush_timeout_s=60.0, elasticity=el)
    base.update(kw)
    return WorkflowConfig(**base)


def _pipe():
    return (OperatorPipeline()
            .map("norm", lambda k, rec: (rec.step,
                 round(float(np.asarray(rec.payload,
                                        dtype=np.float64).sum()), 6)))
            .key_by("bygroup", lambda k, v: k.split("/")[1])
            .tumbling_window("win", 0.5, allowed_lateness_s=1.0)
            .aggregate("agg", lambda k, vals: sorted(vals))
            .sink("out"))


PHASES = (LoadPhase("steady", 2.0, 20.0), LoadPhase("drain", 2.5, 0.0))


def _assert_exact(trace):
    s = trace.summary
    assert s["analyzed"] == s["written"] - s["dropped_by_policy"] \
        - s["records_dropped_injected"]
    assert s["order_timeouts"] == 0
    assert s["windows"]["closed"]


def _baseline(seed):
    return run_scenario(Scenario(workflow=_wf(), phases=PHASES, seed=seed,
                                 operators=_pipe))


# ------------------------------------------------------------- config gates
def test_exactly_once_config_constraints():
    with pytest.raises(ValueError, match="backpressure"):
        WorkflowConfig(delivery="exactly-once",
                       backpressure="drop_oldest").validate()
    with pytest.raises(ValueError, match="delta_encode"):
        WorkflowConfig(delivery="exactly-once", backpressure="block",
                       delta_encode=True).validate()
    with pytest.raises(ValueError, match="delivery"):
        WorkflowConfig(delivery="at-least-once").validate()
    with pytest.raises(ValueError, match="wal_capacity_bytes"):
        WorkflowConfig(wal_capacity_bytes=16).validate()


def test_scenario_kill_faults_require_exactly_once():
    amo = _wf(delivery="at-most-once")
    with pytest.raises(ValueError, match="exactly-once"):
        Scenario(workflow=amo, faults=(Fault(t=1, kind="kill_broker"),),
                 operators=_pipe).validate()
    with pytest.raises(ValueError, match="exactly-once"):
        Scenario(workflow=amo, checkpoint_every_s=1.0,
                 operators=_pipe).validate()
    with pytest.raises(ValueError, match="operators"):
        Scenario(workflow=_wf(),
                 faults=(Fault(t=1, kind="kill_session"),)).validate()


def test_broker_wal_requires_exactly_once():
    from repro.core.broker import Broker, BrokerConfig
    from repro.core.grouping import GroupPlan
    from repro.streaming.endpoint import make_endpoints
    eps = make_endpoints(1)
    with pytest.raises(ValueError, match="exactly-once"):
        Broker(GroupPlan(n_producers=1, n_groups=1, executors_per_group=1),
               eps, BrokerConfig(), wal=WalStore())


# ---------------------------------------------- abandonment is never silent
def test_retry_exhaustion_warns_and_counts_frames_abandoned():
    """At-most-once keeps its drop semantics, but dropping a frame at retry
    exhaustion now raises a RuntimeWarning and bumps frames_abandoned."""
    cfg = _wf(delivery="at-most-once", flush_timeout_s=0.5, retry_limit=2)
    with pytest.warns(RuntimeWarning, match="abandon"):
        with Session(cfg, pipeline=one_stage(lambda k, r: None)) as sess:
            for ep in sess.endpoints:
                ep.handle.fail()
            h = sess.open_field("f", shape=(4,))
            h.write_batch(0, [np.zeros(4, dtype=np.float32)] * 4,
                          ranks=[0, 1, 2, 3])
    st = sess.stats
    assert st.frames_abandoned >= 1
    assert st.dropped >= 1                    # still counted as dropped


# -------------------------------------------------- virtual-time loopback
def test_virtual_clock_loopback_transport_validates_and_delivers():
    """PR-4's inprocess-only guard is gone: clock='virtual' now composes
    with transport='loopback' via VirtualLoopbackTransport."""
    cfg = _wf(transport="loopback")
    cfg.validate()                            # formerly raised ValueError
    seen = []
    with Session(cfg,
                 pipeline=one_stage(lambda k, r: seen.append(len(r)))) as sess:
        h = sess.open_field("f", shape=(4,))
        for s in range(12):
            h.write_batch(s, [np.full(4, s, dtype=np.float32)] * 4,
                          ranks=[0, 1, 2, 3])
        sess.flush(timeout=30.0)
    assert sum(seen) == 48
    assert sess.stats.sent == 48


def test_virtual_loopback_scenario_matches_inprocess_digest():
    t_in = _baseline(0)
    t_lb = run_scenario(Scenario(workflow=_wf(transport="loopback"),
                                 phases=PHASES, seed=0, operators=_pipe))
    _assert_exact(t_lb)
    assert t_lb.summary["sink_digest"] == t_in.summary["sink_digest"]


# --------------------------------------------------------- broker restart
@pytest.mark.parametrize("seed", SEEDS)
def test_kill_broker_replays_wal_tail(seed):
    trace = run_scenario(Scenario(
        workflow=_wf(), phases=PHASES, seed=seed, operators=_pipe,
        faults=(Fault(t=0.7, kind="kill_broker"),
                Fault(t=1.4, kind="kill_broker"))))
    _assert_exact(trace)
    s = trace.summary
    assert s["sink_digest"] == _baseline(seed).summary["sink_digest"]
    assert all(d["ok"] for _, d in trace.events_of("fault"))


# ------------------------------------------------- session kill + restore
@pytest.mark.parametrize("seed", SEEDS)
def test_kill_session_restores_from_checkpoint(seed):
    trace = run_scenario(Scenario(
        workflow=_wf(), phases=PHASES, seed=seed, operators=_pipe,
        checkpoint_every_s=0.6,
        faults=(Fault(t=1.5, kind="kill_session"),)))
    _assert_exact(trace)
    s = trace.summary
    assert s["recovery"]["session_restores"] == 1
    assert s["recovery"]["checkpoints"] >= 1
    assert s["sink_digest"] == _baseline(seed).summary["sink_digest"]


def test_kill_session_without_any_checkpoint_replays_everything():
    """Crash before the first checkpoint: restore starts from genesis and
    the whole WAL replays (retain='commit' holds even acked entries)."""
    trace = run_scenario(Scenario(
        workflow=_wf(), phases=PHASES, seed=0, operators=_pipe,
        faults=(Fault(t=0.4, kind="kill_session"),)))
    _assert_exact(trace)
    s = trace.summary
    assert s["recovery"]["session_restores"] == 1
    assert s["recovery"]["records_replayed"] > 0
    assert s["sink_digest"] == _baseline(0).summary["sink_digest"]


# --------------------------------------------------- the kill-anything gate
def _kill_anything(seed):
    return Scenario(
        workflow=_wf(elastic=True), phases=PHASES, seed=seed,
        operators=_pipe, checkpoint_every_s=0.6,
        faults=(Fault(t=0.45, kind="kill_executor", target=1),
                Fault(t=0.65, kind="kill_broker"),      # mid-window
                Fault(t=0.95, kind="fail_endpoint", target=0),
                Fault(t=1.25, kind="kill_session"),     # mid-checkpoint zone
                Fault(t=1.8, kind="kill_executor", target=0),
                Fault(t=2.1, kind="kill_broker")))


@pytest.mark.parametrize("seed", SEEDS)
def test_kill_anything_is_exactly_once(seed):
    """The PR gate: kill an executor, the broker (twice), an endpoint, and
    the whole session mid-run — the loss ledger still closes and the sink
    contents are byte-identical to the fault-free same-seed run."""
    trace = run_scenario(_kill_anything(seed))
    _assert_exact(trace)
    s = trace.summary
    assert s["dropped_by_policy"] == 0
    assert s["analyzed"] == s["written"]
    assert s["recovery"]["frames_abandoned"] == 0
    assert s["recovery"]["session_restores"] == 1
    assert s["sink_digest"] == _baseline(seed).summary["sink_digest"]


def test_kill_anything_replays_deterministically():
    a = run_scenario(_kill_anything(1))
    b = run_scenario(_kill_anything(1))
    assert a.digest() == b.digest()


# ----------------------------------- injected silent drops stay accounted
@pytest.mark.parametrize("seed", SEEDS)
def test_injected_drop_consumes_seqs_instead_of_resurrecting(seed):
    """drop_frames eats delivered frames AFTER the endpoint acked them.
    Exactly-once must not 'heal' that audited loss on a later replay — the
    endpoint marks the seqs consumed, so the ledger stays closed with the
    drop visible, and a broker restart does not resurrect the records."""
    trace = run_scenario(Scenario(
        workflow=_wf(), phases=PHASES, seed=seed, operators=_pipe,
        faults=(Fault(t=0.6, kind="drop_frames", target=0, value=2),
                Fault(t=0.61, kind="drop_frames", target=1, value=2),
                Fault(t=1.2, kind="kill_broker"))))
    _assert_exact(trace)
    s = trace.summary
    assert s["records_dropped_injected"] > 0
    assert s["analyzed"] == s["written"] - s["records_dropped_injected"]


# -------------------------------------------- direct Session-level restore
def test_session_checkpoint_restore_roundtrip(tmp_path):
    cfg = _wf()
    store = SessionCheckpointStore(tmp_path / "ckpts")
    wal = WalStore(capacity_bytes=cfg.wal_capacity_bytes,
                   queue_capacity=cfg.queue_capacity, retain="commit")

    def feed(sess, lo, hi):
        h = sess.open_field("f", shape=(8,))
        for s in range(lo, hi):
            h.write_batch(s, [np.full(8, s, dtype=np.float32)] * 4,
                          ranks=[0, 1, 2, 3], t=s * 0.05)
            sess.clock.sleep(0.05)

    sess = Session(cfg, pipeline=_pipe(), wal=wal, checkpoints=store)
    feed(sess, 0, 30)
    cid = sess.checkpoint(timeout=60.0)
    assert cid == 1
    feed(sess, 30, 45)
    sess.kill()                                # post-checkpoint tail in WAL

    sess2 = Session.restore(cfg, checkpoints=store, wal=wal,
                            pipeline=_pipe())
    feed(sess2, 45, 60)
    sess2.clock.sleep(2.0)                     # let trailing windows close
    sess2.flush(timeout=60.0)
    sess2.close()
    st = sess2.stats
    assert st.written == 240
    assert st.records_replayed > 0

    # oracle: one uninterrupted run over the same schedule
    ref = Session(cfg, pipeline=_pipe())
    feed(ref, 0, 60)
    ref.clock.sleep(2.0)
    ref.flush(timeout=60.0)
    ref.close()
    assert sink_digest(sess2.exec_plan) == sink_digest(ref.exec_plan)
    analyzed = sum(r.n_records for r in sess2.results())
    assert analyzed == 240


def test_restore_without_config_or_checkpoint_raises(tmp_path):
    store = SessionCheckpointStore(tmp_path / "empty")
    with pytest.raises(ValueError, match="no checkpoint and no config"):
        Session.restore(checkpoints=store, wal=WalStore(retain="commit"),
                        pipeline=_pipe())
