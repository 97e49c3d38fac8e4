"""repro.workflow: WorkflowConfig round-trip, Session lifecycle, the
OperatorPipeline builder, FieldHandle batching, the compat shim, and the
broker regressions
fixed alongside the redesign (flush early-return, silent plan shrink,
failover with batched frames in flight)."""
import itertools
import time
import warnings

import numpy as np
import pytest

from repro.core import api
from repro.core.api import (broker_connect, broker_finalize, broker_init,
                            broker_write)
from repro.core.broker import Broker, BrokerConfig
from repro.core.grouping import GroupPlan
from repro.core.taps import TapStreamer
from repro.streaming.endpoint import make_endpoints
from repro.streaming.operators import Map
from repro.workflow import (ExecutionPlan, FieldHandle, OperatorPipeline,
                            Session, WorkflowConfig)

from one_stage import one_stage


# ------------------------------------------------------------- WorkflowConfig
def test_config_roundtrip_grid():
    """from_dict(to_dict()) is the identity over a deterministic sweep (the
    hypothesis-driven version lives in test_workflow_prop.py)."""
    for n, groups, compress, bp, transport, delta in itertools.product(
            (1, 3, 64), (None, 1, 2), ("none", "int8+zstd"),
            ("block", "drop_oldest", "sample"), ("inprocess", "loopback"),
            (False, True)):
        if groups is not None and groups > n:
            groups = n
        cfg = WorkflowConfig(n_producers=n, n_groups=groups, compress=compress,
                             backpressure=bp, transport=transport,
                             delta_encode=delta, trigger_interval=0.7,
                             inbound_bw=None if delta else 1e6).validate()
        assert WorkflowConfig.from_dict(cfg.to_dict()) == cfg


def test_config_rejects_bad_values():
    with pytest.raises(ValueError, match="unknown WorkflowConfig keys"):
        WorkflowConfig.from_dict({"n_producers": 2, "wat": 1})
    with pytest.raises(ValueError, match="backpressure"):
        WorkflowConfig(backpressure="yolo").validate()
    with pytest.raises(ValueError, match="transport"):
        WorkflowConfig(transport="carrier-pigeon").validate()
    with pytest.raises(ValueError, match="n_groups"):
        WorkflowConfig(n_producers=2, n_groups=5).validate()
    with pytest.raises(ValueError, match="endpoints"):
        WorkflowConfig(n_producers=8, n_groups=4, n_endpoints=2).validate()
    with pytest.raises(ValueError, match="endpoints"):
        # auto-planned group count must respect a declared endpoint budget too
        WorkflowConfig(n_producers=64, n_endpoints=2).validate()
    with pytest.raises(ValueError, match="sample_keep"):
        WorkflowConfig(backpressure="sample", sample_keep=0).validate()


def test_config_derived_subconfigs():
    cfg = WorkflowConfig(n_producers=8, n_groups=2, executors_per_group=3,
                         compress="none", queue_capacity=17)
    plan = cfg.group_plan()
    assert (plan.n_producers, plan.n_groups, plan.n_executors) == (8, 2, 6)
    bcfg = cfg.broker_config()
    assert bcfg.compress == "none" and bcfg.queue_capacity == 17
    assert cfg.endpoint_count == 2
    # auto-planned group count comes from the bandwidth planner
    assert WorkflowConfig(n_producers=40).group_plan().n_groups >= 1


# ------------------------------------------------------------------ Session
def _count_analyzer():
    def analyze(key, records):
        return len(records)
    return analyze


def test_session_end_to_end_context_manager():
    cfg = WorkflowConfig(n_producers=4, n_groups=2, executors_per_group=2,
                         compress="none", trigger_interval=0.05)
    with Session(cfg, pipeline=one_stage(_count_analyzer())) as sess:
        h = sess.open_field("f", shape=(8,))
        assert sess.open_field("f", shape=(8,)) is h      # cached handle
        for s in range(6):
            for r in range(4):
                assert h.write(s, np.full(8, float(s), np.float32), rank=r)
        sess.flush()
    results = sess.results()
    assert sum(r.n_records for r in results) == 24
    assert {r.stream_key for r in results} == {
        f"f/g{r % 2}/r{r}" for r in range(4)}
    assert sess.stats.sent == 24 and sess.stats.dropped == 0
    assert sess.latency_stats()["n"] > 0
    # idempotent close
    assert sess.close().sent == 24


def test_session_field_handle_typing():
    with Session(WorkflowConfig(n_producers=1, n_groups=1, compress="none",
                                executors_per_group=1)) as sess:
        h = sess.open_field("typed", shape=(4,), dtype="float32")
        with pytest.raises(ValueError, match="declared shape"):
            h.write(0, np.zeros(5, np.float32))
        assert h.write(0, [1, 2, 3, 4])                 # coerced to float32
        loose = sess.open_field("loose")                # shape=(): unchecked
        assert loose.write(0, np.zeros(17))
    assert sess.stats.sent == 2


def test_session_attach_pipeline_swaps_engine_plan():
    cfg = WorkflowConfig(n_producers=1, n_groups=1, executors_per_group=1,
                         compress="none", trigger_interval=0.05)
    sess = Session(cfg, pipeline=one_stage(_count_analyzer()))
    engine = sess.engine
    plan = sess.attach_pipeline(one_stage(lambda k, recs: "swapped"))
    assert sess.engine is engine                      # same engine, new plan
    assert engine.plan is plan is sess.exec_plan
    h = sess.open_field("f")
    h.write(0, np.zeros(4, np.float32))
    sess.flush()
    sess.close()
    assert [r.value for r in sess.results()] == ["swapped"]


def test_session_init_failure_does_not_leak_threads():
    """A bad pipeline must not leak sender threads / loopback sockets from
    the already-constructed broker and endpoints."""
    import threading
    before = set(threading.enumerate())
    with pytest.raises(ValueError, match="empty pipeline"):
        Session(WorkflowConfig(n_producers=2, n_groups=1,
                               executors_per_group=1, compress="none",
                               transport="loopback"),
                pipeline=OperatorPipeline())
    deadline = time.time() + 2.0
    while time.time() < deadline:
        leaked = [t for t in threading.enumerate() if t not in before
                  and (t.name.startswith("broker-g")
                       or t.name.startswith("loopback-"))]
        if not leaked:
            break
        time.sleep(0.02)
    assert not leaked, f"leaked threads: {[t.name for t in leaked]}"


# ------------------------------------------------------------ OperatorPipeline
def test_pipeline_builder_topology():
    pipe = (OperatorPipeline(granularity="batch")
            .map("dmd", lambda k, recs: len(recs))
            .map("stability", lambda k, v: v * 2)
            .map("trend", lambda k, v: -v, after="dmd")
            .at("stability").map("alert", lambda k, v: v if v > 2 else None))
    assert set(pipe.edges()) == {("dmd", "stability"), ("dmd", "trend"),
                                 ("stability", "alert")}
    plan = pipe.compile()
    assert isinstance(plan, ExecutionPlan)
    assert plan.source == "dmd"
    assert sorted(plan.down["dmd"]) == ["stability", "trend"]


def test_pipeline_branch_after_at_fans_out_from_new_cursor():
    """A fan-out after at() hangs off the operator named in after=, not
    the construction-order tail."""
    pipe = (OperatorPipeline()
            .map("src", lambda k, v: v)
            .map("a", lambda k, v: v)
            .map("deep", lambda k, v: v)
            .at("a").map("b", lambda k, v: v, after="src"))  # sibling of a
    assert set(pipe.edges()) == {("src", "a"), ("a", "deep"), ("src", "b")}


def test_pipeline_at_cannot_introduce_cycle():
    """The builder only ever attaches NEW nodes below existing ones, so a
    back-edge is unreachable: re-adding an ancestor after at() hits the
    duplicate check, and the compiled plan always validates acyclic."""
    pipe = (OperatorPipeline()
            .map("a", lambda k, v: v)
            .map("b", lambda k, v: v))
    with pytest.raises(ValueError, match="duplicate operator"):
        pipe.at("b").map("a", lambda k, v: v)         # would be the back-edge
    pipe.at("b").map("c", lambda k, v: v)
    pipe.compile()                                    # still acyclic

    # a hand-assembled cyclic graph is rejected by ExecutionPlan itself
    with pytest.raises(ValueError, match="cycle"):
        ExecutionPlan({"a": Map("a", None), "b": Map("b", None)},
                      {"a": ["b"], "b": ["a"]}, "a")


def test_pipeline_duplicate_names_rejected_across_all_verbs():
    with pytest.raises(ValueError, match="duplicate operator"):
        (OperatorPipeline().map("x", None).map("y", None)
         .map("y", None, after="x"))
    with pytest.raises(ValueError, match="duplicate operator"):
        OperatorPipeline().map("x", None).map("y", None).at("x").sink("y")
    with pytest.raises(ValueError, match="non-empty"):
        OperatorPipeline().map("", None)


def test_pipeline_runs_in_session():
    cfg = WorkflowConfig(n_producers=2, n_groups=1, executors_per_group=2,
                         compress="none", trigger_interval=0.05)
    pipe = (OperatorPipeline(granularity="batch")
            .map("count", lambda k, recs: len(recs))
            .map("double", lambda k, v: v * 2).sink("doubles")
            .map("flag", lambda k, v: "big" if v >= 3 else None,
                 after="count").sink("flags"))
    with Session(cfg, pipeline=pipe) as sess:
        h = sess.open_field("f")
        for s in range(3):
            h.write_batch(s, [np.zeros(4, np.float32)] * 2, ranks=[0, 1])
        sess.flush()
    doubles = sess.exec_plan.latest("doubles")
    assert set(doubles) == {"f/g0/r0", "f/g0/r1"}
    assert all(v % 2 == 0 for v in doubles.values())
    assert sess.results("doubles") == sess.exec_plan.results("doubles")
    # "flag" filtered: only micro-batches of >= 3 records sink
    assert all(v == "big" for _, v, _ in sess.results("flags"))


def test_engine_attach_plan_reroutes_microbatches():
    cfg = WorkflowConfig(n_producers=1, n_groups=1, executors_per_group=1,
                         compress="none", trigger_interval=0.05)
    sess = Session(cfg, pipeline=one_stage(_count_analyzer()))
    plan = one_stage(lambda k, recs: f"plan:{len(recs)}").compile()
    sess.engine.attach_plan(plan)
    h = sess.open_field("f")
    h.write(0, np.zeros(2, np.float32))
    sess.flush()
    sess.close()
    assert [r.value for r in sess.results()] == ["plan:1"]


# -------------------------------------------------- FieldHandle.write_batch
def test_write_batch_validates_alignment():
    with Session(WorkflowConfig(n_producers=2, n_groups=1, compress="none",
                                executors_per_group=1)) as sess:
        h = sess.open_field("f")
        with pytest.raises(ValueError, match="aligned"):
            h.write_batch([0, 1], [np.zeros(2)] * 3)
        assert h.write_batch(7, [np.zeros(2)] * 3, ranks=[0, 1, 0]) == 3


def test_tap_publish_is_one_frame_per_field():
    """F fields x R regions per publish must produce <= F wire frames."""
    F, R = 2, 4
    cfg = WorkflowConfig(n_producers=R, n_groups=1, executors_per_group=1,
                         compress="none")
    sess = Session(cfg)
    streamer = TapStreamer(sess, n_regions=R)
    taps = {"resid_norm": np.random.randn(3, 8).astype(np.float32),
            "snapshot": np.random.randn(3, 8, 16).astype(np.float32)}
    assert streamer.publish(0, taps) == F * R
    sess.flush()
    ep = sess.endpoints[0].handle
    assert ep.records_in == F * R
    assert ep.frames_in <= F, (
        f"publish of {F} fields x {R} regions took {ep.frames_in} frames")
    sess.close()


def test_tapstreamer_still_accepts_bare_broker():
    eps = make_endpoints(1)
    broker = Broker(GroupPlan(2, 1, 1), eps, BrokerConfig(compress="none"))
    streamer = TapStreamer(broker, n_regions=2)
    n = streamer.publish(0, {"resid_norm": np.ones((2, 4), np.float32),
                             "snapshot": np.ones((2, 4, 8), np.float32)})
    assert n == 4
    broker.finalize()
    assert eps[0].handle.records_in == 4


# ----------------------------------- backpressure accounting with batch items
def _parked_sender(**cfg_kw):
    """A _GroupSender that is never start()ed: queue state and eviction
    accounting are fully deterministic (same construction as
    test_hotpath_batch's coalescing test)."""
    from repro.core.broker import _GroupSender
    eps = make_endpoints(1)
    sender = _GroupSender(0, eps, 0, BrokerConfig(compress="none", **cfg_kw))
    return sender, eps


def _rec(step, rank=0):
    from repro.core.records import StreamRecord
    return StreamRecord("f", 0, rank, step, np.full(4, float(step), np.float32))


def test_drop_oldest_eviction_counts_batch_records():
    """Evicting a queued submit_batch list must count all its records, or
    written-sent-dropped accounting skews and flush() spins to timeout."""
    sender, eps = _parked_sender(queue_capacity=2, backpressure="drop_oldest",
                                 max_batch_records=8)
    st = sender.stats
    for s in range(2):                      # fills the 2-item queue
        assert sender.submit_batch([_rec(s), _rec(s, 1), _rec(s)]) == 3
    assert st.written == 6 and st.dropped == 0
    # single-record submit evicts the OLDEST item — a 3-record batch
    assert sender.submit(_rec(99))
    assert st.written == 7
    assert st.dropped == 3, "batch eviction must count all records in the item"
    # batch submit evicts the other 3-record batch
    assert sender.submit_batch([_rec(100), _rec(101)]) == 2
    assert st.written == 9 and st.dropped == 6
    # accounting identity holds once the sender drains the survivors
    sender.start()
    sender.stop(timeout=5.0)
    assert st.written == st.sent + st.dropped == 9
    assert st.sent == 3                     # rec 99 + batch [100, 101]


def test_sample_backpressure_keeps_fresh_batches():
    """submit_batch under 'sample' keeps 1 of sample_keep batches (evicting
    stale ones) instead of dropping every new batch whole."""
    sender, eps = _parked_sender(queue_capacity=2, backpressure="sample",
                                 sample_keep=2, max_batch_records=8)
    st = sender.stats
    for s in range(8):
        sender.submit_batch([_rec(s), _rec(s, 1)])
    queued = []
    while not sender.q.empty():
        item = sender.q.get_nowait()
        queued.extend(item if isinstance(item, list) else [item])
    assert queued, "sample policy must admit some batches under pressure"
    assert max(r.step for r in queued) >= 4, \
        "fresh batches should displace stale ones"
    assert st.written == 16
    assert st.dropped + len(queued) == st.written - st.sent


def test_paper_api_wire_behavior_matches_seed():
    """The shim must hand payloads to the codec in their input dtype, exactly
    like the seed broker_write (the wire itself is float32 by codec design:
    encode() does astype(float32) on the raw path).  Guard both halves: the
    compat FieldHandle doesn't pre-coerce, and the delivered values match the
    seed's float32 wire semantics."""
    eps = make_endpoints(1)
    broker = Broker(GroupPlan(1, 1, 1), eps, BrokerConfig(compress="none"))
    ctx = broker_init("counters", rank=0, broker=broker)
    assert ctx.handle.coerce_dtype is False
    assert ctx.handle._coerce(np.arange(3, dtype=np.int64)).dtype == np.int64
    data = np.array([1.5, -2.25, 1e7], dtype=np.float64)
    assert broker_write(ctx, 0, data)
    broker_finalize(ctx)
    [rec] = eps[0].handle.drain("counters/g0/r0")
    assert rec.payload.dtype == np.float32       # codec-defined, as in seed
    np.testing.assert_allclose(rec.payload, data.astype(np.float32))


# ----------------------------------------------------- flush() early return
def test_flush_waits_out_recovered_endpoint():
    """Errors from a past failure episode must not make flush() bail while
    records written after recovery are still in flight."""
    eps = make_endpoints(1, inbound_bw=50_000)       # slow drain post-recovery
    broker = Broker(GroupPlan(1, 1, 1), eps,
                    BrokerConfig(compress="none", backpressure="block",
                                 retry_limit=2, queue_capacity=512,
                                 max_batch_records=1, flush_timeout_s=30.0))
    eps[0].handle.fail()
    for s in range(5):
        broker.write("f", 0, s, np.zeros(1024, np.float32))
    deadline = time.time() + 5.0
    while time.time() < deadline and broker.stats.dropped < 5:
        time.sleep(0.01)
    assert broker.stats.dropped == 5                 # failure episode over
    assert broker.stats.send_errors >= 10            # its errors linger
    eps[0].handle.recover()
    for s in range(5, 45):
        broker.write("f", 0, s, np.zeros(1024, np.float32))
    broker.flush()
    # flush must have outlasted the bandwidth-paced drain of all 40 records
    assert broker.stats.sent == 40
    assert all(s.q.empty() for s in broker._senders.values())
    broker.finalize()


# ------------------------------------------------------- plan-shrink warning
def test_connect_shrink_warns_and_records_effective_plan():
    eps = make_endpoints(2)
    with pytest.warns(RuntimeWarning, match="shrinking to 2"):
        broker = broker_connect(eps, n_producers=8,
                                plan=GroupPlan(8, 4, 2))
    assert broker.plan.n_groups == 2
    assert broker.stats.planned_groups == 4
    assert broker.stats.effective_groups == 2
    broker.finalize()


def test_connect_exact_fit_does_not_warn():
    eps = make_endpoints(2)
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        broker = broker_connect(eps, n_producers=4, plan=GroupPlan(4, 2, 2))
    assert broker.stats.planned_groups == broker.stats.effective_groups == 2
    broker.finalize()


# --------------------------------------------- failover with frames in flight
def test_failover_midstream_batched_no_loss_ordered():
    """Kill the primary endpoint while batched frames are in flight: traffic
    reroutes, nothing is lost under backpressure="block", and the engine's
    per-stream record order survives the re-route."""
    cfg = WorkflowConfig(n_producers=4, n_groups=2, executors_per_group=2,
                         compress="none", backpressure="block",
                         queue_capacity=512, max_batch_records=8,
                         trigger_interval=0.05, n_executors=1)
    seen: dict[str, list[int]] = {}

    def analyze(key, records):
        seen.setdefault(key, []).extend(r.step for r in records)
        return len(records)

    sess = Session(cfg, pipeline=one_stage(analyze))
    h = sess.open_field("f")
    n_steps = 40
    for s in range(n_steps):
        h.write_batch(s, [np.full(16, float(s), np.float32)] * 4,
                      ranks=[0, 1, 2, 3])
        if s == n_steps // 2:
            sess.endpoints[0].handle.fail()      # kill primary mid-stream
        time.sleep(0.002)
    sess.flush()
    stats = sess.close()
    assert stats.rerouted > 0
    assert stats.dropped == 0
    assert stats.sent == stats.written == 4 * n_steps    # no record loss
    assert set(seen) == {f"f/g{r % 2}/r{r}" for r in range(4)}
    for key, steps in seen.items():
        assert steps == sorted(steps), f"stream {key} reordered: {steps}"
        assert len(steps) == n_steps


# ----------------------------------------------------------- compat shim
def test_paper_api_is_session_backed():
    eps = make_endpoints(2)
    broker = broker_connect(eps, n_producers=4)
    assert api._shared_session is not None
    assert api._shared_session.broker is broker
    ctx = broker_init("pressure", rank=1, shape=(16,))
    assert isinstance(ctx.handle, FieldHandle)
    assert broker_write(ctx, step=0, data=np.zeros(16, np.float32))
    stats = broker_finalize(ctx)            # closes the shared Session
    assert stats.sent == 1
    assert api._shared_session._closed


def test_broker_init_with_external_broker():
    eps = make_endpoints(1)
    broker = Broker(GroupPlan(2, 1, 1), eps, BrokerConfig(compress="none"))
    ctx = broker_init("f", rank=1, broker=broker)
    assert broker_write(ctx, 0, np.arange(4, dtype=np.float32))
    stats = broker_finalize(ctx)
    assert stats.sent == 1


# ------------------------------------------------------- loopback transport
def test_loopback_transport_survives_broker_suite_smoke():
    cfg = WorkflowConfig(n_producers=4, n_groups=2, executors_per_group=2,
                         compress="int8+zstd", transport="loopback",
                         trigger_interval=0.05)
    with Session(cfg, pipeline=one_stage(_count_analyzer())) as sess:
        h = sess.open_field("f", shape=(32,))
        for s in range(5):
            h.write_batch(s, [np.random.randn(32).astype(np.float32)] * 4,
                          ranks=[0, 1, 2, 3])
        sess.flush()
    assert sess.stats.sent == 20 and sess.stats.dropped == 0
    assert sum(r.n_records for r in sess.results()) == 20
