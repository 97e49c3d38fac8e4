"""Stream-operator API: typed operators, ordering contracts, event-time
windows with keyed state, plan-aware engine dispatch (intra-stream
parallelism), and snapshot/restore migration."""
import threading

import numpy as np
import pytest

from repro.core.records import StreamRecord
from repro.runtime.clock import VirtualClock
from repro.sim.scenario import LoadPhase, Scenario, ScenarioRunner
from repro.streaming.operators import (KEYED, ORDERED, UNORDERED, Aggregate,
                                       BatchAggregate, Element, ExecutionPlan,
                                       Filter, KeyBy, Map, OperatorPipeline,
                                       Sink, SlidingWindow, TumblingWindow,
                                       WindowPane)
from repro.workflow import Session, WorkflowConfig

from one_stage import one_stage


def _rec(step, t, rank=0, val=None, dim=4):
    payload = np.full(dim, float(step if val is None else val), np.float32)
    return StreamRecord("f", 0, rank, step, payload, t_generated=float(t))


# ------------------------------------------------------------------ builder
@pytest.mark.parametrize("build, match", [
    (lambda: OperatorPipeline().map("a", None).map("a", None),
     "duplicate operator"),
    (lambda: OperatorPipeline().map("a", None).at("zz"), "unknown operator"),
    (lambda: OperatorPipeline().map("a", None).map("b", None, after="zz"),
     "unknown operator"),
    (lambda: OperatorPipeline().map("a", None, after="x"), "no upstream"),
    (lambda: OperatorPipeline().compile(), "empty pipeline"),
    (lambda: Map("m", None, ordering="chaotic"), "ordering must be one of"),
    (lambda: Map("m", None, parallelism=0), "parallelism"),
    (lambda: TumblingWindow("w", 0.0), "size_s"),
    (lambda: SlidingWindow("w", 1.0, 2.0), "slide_s must be <= size_s"),
], ids=["duplicate", "at-unknown", "after-unknown", "source-after",
        "empty", "ordering", "parallelism", "window-size", "slide"])
def test_builder_validation(build, match):
    with pytest.raises(ValueError, match=match):
        build()


def test_plan_phase_split_and_contract():
    plan = (OperatorPipeline()
            .map("pre1", lambda k, v: v, ordering=UNORDERED)
            .key_by("kb", lambda k, v: k)
            .tumbling_window("win", 1.0)
            .aggregate("agg", lambda k, vals: len(vals))
            .map("post1", lambda k, v: v, ordering=ORDERED)
            .sink("out")
            .compile())
    assert plan.pre_stages == ["pre1", "kb", "win", "agg"]
    assert plan.post_stages == ["post1", "out"]
    assert plan.contract == ORDERED
    assert plan.parallel_dispatch

    unordered = (OperatorPipeline()
                 .map("a", lambda k, v: v, ordering=UNORDERED)
                 .sink("s")
                 .compile())
    assert unordered.contract == UNORDERED and not unordered.post_stages

    keyed = (OperatorPipeline()
             .key_by("kb", lambda k, v: k)
             .sink("s")
             .compile())
    assert keyed.contract == KEYED

    # an ordered ANCESTOR poisons the whole suffix even if later stages are
    # order-insensitive themselves
    poisoned = (OperatorPipeline()
                .map("o", lambda k, v: v, ordering=ORDERED)
                .map("u", lambda k, v: v, ordering=UNORDERED)
                .compile())
    assert poisoned.pre_stages == [] and not poisoned.parallel_dispatch


def test_plan_parallelism_hint_is_min_over_prefix():
    plan = (OperatorPipeline()
            .map("a", lambda k, v: v, ordering=UNORDERED, parallelism=8)
            .map("b", lambda k, v: v, ordering=UNORDERED, parallelism=2)
            .compile())
    assert plan.parallelism == 2
    nohint = (OperatorPipeline()
              .map("a", lambda k, v: v, ordering=UNORDERED)
              .compile())
    assert nohint.parallelism is None


def test_plan_rejects_cycles_and_unknown_stages():
    ops = {"a": Map("a", lambda k, v: v), "b": Map("b", lambda k, v: v)}
    with pytest.raises(ValueError, match="cycle"):
        ExecutionPlan(ops, {"a": ["b"], "b": ["a"]}, "a")
    with pytest.raises(ValueError, match="unknown downstream"):
        ExecutionPlan(ops, {"a": ["zz"], "b": []}, "a")
    with pytest.raises(ValueError, match="unreachable"):
        ExecutionPlan(ops, {"a": [], "b": []}, "a")


# ---------------------------------------------------- inline element semantics
def test_map_filter_keyby_sink_inline():
    plan = (OperatorPipeline()
            .map("double", lambda k, rec: rec.step * 2, ordering=UNORDERED)
            .filter("evens", lambda k, v: v % 4 == 0)
            .key_by("shard", lambda k, v: f"s{v % 8}")
            .sink("out")
            .compile())
    assert plan("f/g0/r0", [_rec(s, t=0.1 * s) for s in range(5)]) == 5
    out = plan.results("out")
    # steps 0..4 -> doubled 0,2,4,6,8 -> evens filter keeps 0,4,8
    assert [v for _k, v, _t in out] == [0, 4, 8]
    assert [k for k, _v, _t in out] == ["s0", "s4", "s0"]
    with pytest.raises(ValueError, match="not a Sink"):
        plan.results("double")
    assert plan.sinks() == ["out"]


def test_sink_passes_through_mid_chain():
    plan = (OperatorPipeline()
            .map("a", lambda k, rec: rec.step, ordering=UNORDERED)
            .sink("raw")
            .map("b", lambda k, v: v + 100, ordering=UNORDERED)
            .sink("shifted")
            .compile())
    plan("s", [_rec(1, 0.0), _rec(2, 0.0)])
    assert [v for _k, v, _t in plan.results("raw")] == [1, 2]
    assert [v for _k, v, _t in plan.results("shifted")] == [101, 102]


def test_aggregate_on_plain_iterable():
    agg = Aggregate("a", lambda k, vals: sum(vals))
    [out] = agg.process(Element("k", [1, 2, 3], 0.0))
    assert out.value == 6


# ----------------------------------------------------------- windows (event time)
def test_tumbling_window_event_time_and_flush():
    plan = (OperatorPipeline()
            .tumbling_window("win", 1.0)
            .aggregate("agg", lambda k, vals: sorted(r.step for r in vals))
            .sink("out")
            .compile())
    # t in [0,1) bucket: steps 0,1; watermark crossing 1.0 fires it
    plan("s", [_rec(0, 0.2), _rec(1, 0.8)])
    assert plan.results("out") == []                 # watermark still < 1.0
    plan("s", [_rec(2, 1.3)])
    out = plan.results("out")
    assert [v for _k, v, _t in out] == [[0, 1]]
    # the [1,2) pane is open until flush
    acct = plan.accounting()["windows"]["win"]
    assert acct["open_panes"] == 1 and acct["closed"]
    plan.flush()
    assert [v for _k, v, _t in plan.results("out")] == [[0, 1], [2]]
    acct = plan.accounting()["windows"]["win"]
    assert acct["records_in"] == 3 and acct["panes_fired"] == 2
    assert acct["open_panes"] == 0 and acct["closed"]


def test_tumbling_window_late_drop_accounting():
    plan = (OperatorPipeline()
            .tumbling_window("win", 1.0)
            .sink("out")
            .compile())
    late = []
    plan.on_event = lambda kind, **d: late.append(d) if kind == "late_drop" \
        else None
    plan("s", [_rec(0, 0.5), _rec(1, 2.5)])          # fires [0,1)
    plan("s", [_rec(2, 0.7)])                        # pane [0,1) already gone
    acct = plan.accounting()["windows"]["win"]
    assert acct["late_dropped"] == 1 and acct["closed"]
    assert late and late[0]["t_event"] == 0.7
    plan.flush()
    fired = [v for _k, v, _t in plan.results("out")]
    assert sum(p.n for p in fired) + acct["late_dropped"] == 3


def test_tumbling_window_allowed_lateness_accepts_stragglers():
    plan = (OperatorPipeline()
            .tumbling_window("win", 1.0, allowed_lateness_s=1.0)
            .sink("out")
            .compile())
    plan("s", [_rec(0, 0.5), _rec(1, 1.5)])          # [0,1) held open
    plan("s", [_rec(2, 0.9)])                        # late but within grace
    plan.flush()
    panes = {(p.start, p.end): p.n
             for _k, p, _t in plan.results("out")}
    assert panes[(0.0, 1.0)] == 2
    assert plan.accounting()["windows"]["win"]["late_dropped"] == 0


def test_sliding_window_overlapping_panes():
    plan = (OperatorPipeline()
            .sliding_window("win", 2.0, 1.0)
            .sink("out")
            .compile())
    plan("s", [_rec(0, 0.5)])      # joins [-1,1) and [0,2)
    plan.flush()
    panes = {(p.start, p.end): [r.step for r in p.values]
             for _k, p, _t in plan.results("out")}
    assert panes == {(-1.0, 1.0): [0], (0.0, 2.0): [0]}
    acct = plan.accounting()["windows"]["win"]
    assert acct["records_in"] == 1 and acct["assignments"] == 2
    assert acct["closed"]


def test_window_keyed_panes_shared_watermark():
    """Panes are per key; the watermark is per OPERATOR (Flink-style), so
    one key's progress releases every key's ripe panes."""
    plan = (OperatorPipeline()
            .key_by("by_rank", lambda k, rec: f"r{rec.rank}")
            .tumbling_window("win", 1.0)
            .aggregate("agg", lambda k, vals: len(vals))
            .sink("out")
            .compile())
    plan("f/g0/r0", [_rec(0, 0.1, rank=0), _rec(0, 0.2, rank=1)])
    assert plan.results("out") == []
    plan("f/g0/r0", [_rec(1, 1.5, rank=0)])   # watermark 1.5: both keys fire
    out = plan.results("out")
    assert sorted((k, v) for k, v, _t in out) == [("r0", 1), ("r1", 1)]
    plan.flush()                              # r0's open [1,2) pane remains
    assert sorted((k, v) for k, v, _t in plan.results("out")) \
        == [("r0", 1), ("r0", 1), ("r1", 1)]


def test_out_of_order_batches_do_not_late_drop():
    """The parallel-dispatch hazard: batch N+1 processed BEFORE batch N
    must not advance the watermark past N's still-uninserted records.  The
    frontier only commits contiguous seqs, so nothing here may late-drop."""
    plan = (OperatorPipeline()
            .tumbling_window("win", 0.5)
            .aggregate("agg", lambda k, vals: sorted(r.step for r in vals))
            .sink("out")
            .compile())
    # seq 1 (later event times) lands first — an executor raced ahead
    plan.run_pre("s", [_rec(2, 0.60), _rec(3, 0.75)], seq=1)
    assert plan.results("out") == []          # frontier stalls at seq 0
    plan.run_pre("s", [_rec(0, 0.40), _rec(1, 0.45)], seq=0)
    out = plan.results("out")                 # commit 0 then 1: fires [.5,1)?
    acct = plan.accounting()["windows"]["win"]
    assert acct["late_dropped"] == 0, "in-flight reorder must not drop"
    plan.flush()
    panes = [v for _k, v, _t in plan.results("out")]
    assert sorted(map(tuple, panes)) == [(0, 1), (2, 3)]
    assert plan.accounting()["closed"]
    assert out == [] or panes[0] == [0, 1]    # [0,.5) fired complete first


# ------------------------------------------------- snapshot / restore migration
def test_window_snapshot_restore_midwindow():
    def build():
        return (OperatorPipeline()
                .tumbling_window("win", 1.0)
                .aggregate("agg", lambda k, vals: sorted(r.step for r in vals))
                .sink("out")
                .compile())

    a = build()
    a("s", [_rec(0, 0.1), _rec(1, 0.4)])             # mid-window state
    snap = a.snapshot()
    b = build()
    b.restore(snap)
    b("s", [_rec(2, 0.7), _rec(3, 1.2)])             # fires [0,1) on b
    out = b.results("out")
    assert [v for _k, v, _t in out] == [[0, 1, 2]]
    acct = b.accounting()["windows"]["win"]
    assert acct["records_in"] == 4 and acct["closed"]
    # the donor's state is an independent deep copy: feeding it more records
    # must not affect b
    a("s", [_rec(9, 0.9)])
    assert [v for _k, v, _t in b.results("out")] == [[0, 1, 2]]
    with pytest.raises(ValueError, match="unknown operator"):
        b.restore({"nope": {}})


# -------------------------------------------- engine integration (virtual time)
def _virtual_session(pipe_or_plan, *, n_producers=1, n_executors=4, seed=0,
                     min_batch=4):
    clock = VirtualClock(seed=seed)
    clock.attach()
    cfg = WorkflowConfig(n_producers=n_producers, n_groups=1,
                         compress="none", backpressure="block",
                         queue_capacity=4096, trigger_interval=0.02,
                         min_batch=min_batch, n_executors=n_executors,
                         clock="virtual", clock_seed=seed)
    return Session(cfg, pipeline=pipe_or_plan, clock=clock), clock


def test_unordered_stage_runs_intra_stream_parallel():
    """The ROADMAP follow-up: order-insensitive stages bypass the ordering
    ticket and spread ONE stream's micro-batches across executors."""
    holder = {}

    def work(key, rec):
        holder["clock"].sleep(0.02)
        return rec.step

    pipe = (OperatorPipeline()
            .map("work", work, ordering=UNORDERED)
            .sink("out"))
    sess, clock = _virtual_session(pipe)
    holder["clock"] = sess.clock
    h = sess.open_field("f", shape=(4,))
    t0 = clock.now()
    for s in range(48):
        h.write(s, np.zeros(4, np.float32))
        clock.sleep(0.005)
    sess.flush(timeout=120.0)
    sess.close()
    dur = clock.now() - t0
    out = sess.exec_plan.results("out")
    assert sorted(v for _k, v, _t in out) == list(range(48))
    serial = 48 * 0.02
    assert dur < serial / 2, (
        f"virtual duration {dur:.3f}s is not >=2x faster than the "
        f"{serial:.3f}s serial floor — no intra-stream parallelism")
    assert any(e.processed > 0 for e in sess.engine.executors[1:]), \
        "work never spread beyond the first executor"


def test_ordered_stage_exact_sequence_under_stealing():
    """The flip side of the acceptance bar: an ordered stage keeps the
    exact per-stream dispatch sequence even with stragglers forcing
    steals."""
    holder = {}

    def work(key, rec):
        holder["clock"].sleep(0.01)
        return rec.step

    pipe = (OperatorPipeline()
            .map("work", work, ordering=ORDERED)
            .sink("out"))
    sess, clock = _virtual_session(pipe, n_producers=2, n_executors=3,
                                   min_batch=2)
    holder["clock"] = sess.clock
    sess.engine.executors[0].slowdown = 0.08     # straggler => steals
    h = sess.open_field("f", shape=(4,))
    for s in range(40):
        h.write_batch(s, [np.zeros(4, np.float32)] * 2, ranks=[0, 1])
        clock.sleep(0.004)
    sess.flush(timeout=120.0)
    sess.close()
    per_key: dict[str, list[int]] = {}
    for k, v, _t in sess.exec_plan.results("out"):
        per_key.setdefault(k, []).append(v)
    assert set(per_key) == {"f/g0/r0", "f/g0/r1"}
    for k, steps in per_key.items():
        assert steps == sorted(steps), f"stream {k} reordered: {steps}"
        assert len(steps) == 40
    assert sess.engine.metrics()["order_timeouts"] == 0


def test_prefix_exception_preserves_ordered_suffix_sequence():
    """A raising prefix batch must still take its ordering turn: the
    release is a max-jump, so an early out-of-sequence release would
    unblock every in-flight batch at once and scramble the ordered
    suffix."""
    holder = {}

    def work(key, rec):
        holder["clock"].sleep(0.01)
        if rec.step == 7:
            raise RuntimeError("poisoned batch")
        return rec.step

    pipe = (OperatorPipeline()
            .map("work", work, ordering=UNORDERED)
            .map("seq", lambda k, v: v, ordering=ORDERED)
            .sink("out"))
    sess, clock = _virtual_session(pipe, n_executors=4, min_batch=2)
    holder["clock"] = sess.clock
    h = sess.open_field("f", shape=(4,))
    for s in range(40):
        h.write(s, np.zeros(4, np.float32))
        clock.sleep(0.004)
    sess.flush(timeout=120.0)
    sess.close()
    steps = [v for _k, v, _t in sess.exec_plan.results("out")]
    assert steps == sorted(steps), f"ordered suffix scrambled: {steps}"
    assert 7 not in steps                 # the poisoned batch is dropped...
    assert len(steps) >= 40 - 4           # ...but ONLY that batch
    assert any(isinstance(r.value, RuntimeError) for r in sess.results())
    assert sess.engine.metrics()["order_timeouts"] == 0


def test_window_state_survives_replace_executor_midwindow():
    """Acceptance: keyed window state lives in the plan, not an executor —
    replacing an executor mid-window loses nothing and the loss ledger
    closes."""
    pipe = (OperatorPipeline()
            .key_by("by_rank", lambda k, rec: f"r{rec.rank}")
            .tumbling_window("win", 1.0)
            .aggregate("agg", lambda k, vals: len(vals))
            .sink("out"))
    sess, clock = _virtual_session(pipe, n_producers=2, n_executors=3,
                                   min_batch=2)
    h = sess.open_field("f", shape=(4,))
    n_steps = 30
    for s in range(n_steps):
        h.write_batch(s, [np.zeros(4, np.float32)] * 2, ranks=[0, 1])
        if s == n_steps // 2:
            sess.engine.replace_executor(0)      # mid-window remediation
        clock.sleep(0.05)
    sess.flush(timeout=120.0)
    sess.close()
    acct = sess.exec_plan.accounting()
    win = acct["windows"]["win"]
    assert win["records_in"] == 2 * n_steps, "records lost across replace"
    assert win["late_dropped"] == 0
    assert acct["closed"], f"loss ledger must close: {win}"
    # every record landed in exactly one fired pane
    assert win["fired_inserts"] == 2 * n_steps
    total = sum(v for _k, v, _t in sess.exec_plan.results("out"))
    assert total == 2 * n_steps


# ------------------------------------------------------ replacing the plan
def test_attach_pipeline_replaces_operator_plan():
    pipe = (OperatorPipeline()
            .map("m", lambda k, rec: rec.step, ordering=UNORDERED)
            .sink("out"))
    cfg = WorkflowConfig(n_producers=1, n_groups=1, executors_per_group=1,
                         compress="none", trigger_interval=0.05)
    sess = Session(cfg, pipeline=pipe)
    first = sess.engine.plan
    assert first is sess.exec_plan
    swapped = sess.attach_pipeline(one_stage(lambda k, recs: "swapped"))
    assert sess.engine.plan is swapped is sess.exec_plan
    h = sess.open_field("f")
    h.write(0, np.zeros(4, np.float32))
    sess.flush()
    sess.close()
    assert [r.value for r in sess.results()] == ["swapped"]
    assert first.results("out") == []         # the replaced plan saw nothing


# ------------------------------------------------------ scenario integration
def _op_scenario(seed=0):
    def factory():
        return (OperatorPipeline()
                .key_by("by_rank", lambda k, rec: f"r{rec.rank}")
                .tumbling_window("win", 0.5)
                .aggregate("agg", lambda k, vals: len(vals))
                .sink("out"))

    wf = WorkflowConfig(n_producers=2, n_groups=1, executors_per_group=2,
                        compress="none", backpressure="block",
                        queue_capacity=4096, trigger_interval=0.05,
                        min_batch=2, n_executors=2,
                        clock="virtual", clock_seed=seed)
    return Scenario(workflow=wf, phases=(LoadPhase("load", 2.0, 10.0),),
                    seed=seed, operators=factory)


def test_scenario_operator_trace_events_and_determinism():
    t1 = ScenarioRunner(_op_scenario(seed=3)).run()
    ops = t1.events_of("op")
    assert any(d["event"] == "window_fire" for _t, d in ops)
    assert any(d["event"] == "sink" for _t, d in ops)
    win = t1.summary["windows"]["windows"]["win"]
    assert win["records_in"] == t1.summary["endpoint_records_in"]
    assert t1.summary["windows"]["closed"]
    t2 = ScenarioRunner(_op_scenario(seed=3)).run()
    assert t1.digest() == t2.digest()


def test_scenario_record_latency_events():
    wf = WorkflowConfig(n_producers=2, n_groups=1, executors_per_group=2,
                        compress="none", trigger_interval=0.05, min_batch=2,
                        clock="virtual")
    sc = Scenario(workflow=wf, phases=(LoadPhase("load", 1.0, 10.0),),
                  seed=1, analysis_cost_s=0.002, record_latency=True)
    trace = ScenarioRunner(sc).run()
    curve = trace.latency_curve()
    assert len(curve) == trace.summary["analyzed"]
    assert all(lat >= 0.0 for _t, lat in curve)
    assert curve == sorted(curve)
    with pytest.raises(ValueError, match="factory"):
        Scenario(workflow=wf, operators=object()).validate()


def test_failed_pre_batch_still_commits_frontier():
    """A stage exception mid-prefix must not stall the stream's watermark:
    the seq commits anyway, so later batches keep firing windows."""
    def boom(key, rec):
        if rec.step == 2:
            raise RuntimeError("malformed record")
        return rec

    plan = (OperatorPipeline()
            .map("guard", boom, ordering=UNORDERED)
            .tumbling_window("win", 0.5)
            .sink("out")
            .compile())
    plan.run_pre("s", [_rec(0, 0.1), _rec(1, 0.2)], seq=0)
    with pytest.raises(RuntimeError):
        plan.run_pre("s", [_rec(2, 0.4)], seq=1)     # poisoned batch
    plan.run_pre("s", [_rec(3, 0.9), _rec(4, 1.4)], seq=2)
    # watermark reached 1.4 through the poisoned seq: ripe panes fired
    fired = [p for _k, p, _t in plan.results("out")]
    assert [(p.start, p.end) for p in fired] == [(0.0, 0.5), (0.5, 1.0)]
    assert sorted(r.step for r in fired[0].values) == [0, 1]
    assert [r.step for r in fired[1].values] == [3]


def test_attach_plan_midrun_seeds_frontier():
    """Rewiring a running Session onto an operator plan must align the
    plan's frontier with the engine's continuing seq counters — otherwise
    every post-attach batch pends and windows only fire at drain."""
    cfg = WorkflowConfig(n_producers=1, n_groups=1, executors_per_group=2,
                         compress="none", trigger_interval=0.05, min_batch=2,
                         clock="virtual")
    clock = VirtualClock(seed=0)
    clock.attach()
    sess = Session(cfg, pipeline=one_stage(lambda k, recs: len(recs)),
                   clock=clock)
    h = sess.open_field("f", shape=(4,))
    for s in range(10):                       # burn seqs on the callback path
        h.write(s, np.zeros(4, np.float32))
        clock.sleep(0.05)
    sess.flush()
    pipe = (OperatorPipeline()
            .tumbling_window("win", 0.2)
            .aggregate("agg", lambda k, vals: len(vals))
            .sink("out"))
    plan = sess.attach_pipeline(pipe)         # mid-run rewiring
    t0 = clock.now()
    for s in range(10, 40):
        h.write(s, np.zeros(4, np.float32))
        clock.sleep(0.05)
    # windows must fire DURING streaming (watermark advances), not at drain
    assert clock.wait(lambda: len(plan.results("out")) > 0, timeout=5.0), \
        "frontier misaligned: no pane fired while streaming"
    t_first_fire = clock.now() - t0
    sess.flush()
    sess.close()
    # >= 30: all post-attach records, plus any pre-attach batch still in
    # flight at the switch (re-routed through the plan, not lost)
    total = sum(v for _k, v, _t in plan.results("out"))
    assert 30 <= total <= 40
    assert plan.accounting()["closed"]
    assert t_first_fire < 2.0


def test_batch_granularity_unordered_source_keeps_primary():
    """Relaxing a batch source's contract must not change Result.value
    semantics: the source stage's output stays the primary value."""
    plan = (OperatorPipeline(granularity="batch")
            .map("count", lambda k, recs: sorted(r.step for r in recs),
                 ordering=UNORDERED)
            .sink("out")
            .compile())
    assert plan("s", [_rec(1, 0.1), _rec(0, 0.05)]) == [0, 1]
    pre = plan.run_pre("s", [_rec(2, 0.2)], seq=0)
    assert pre.primary == [2]


def test_scenario_rejects_callback_knobs_with_operators():
    wf = WorkflowConfig(n_producers=1, n_groups=1, compress="none",
                        clock="virtual")
    factory = OperatorPipeline                # any zero-arg callable
    with pytest.raises(ValueError, match="analysis_cost_s"):
        Scenario(workflow=wf, operators=factory,
                 analysis_cost_s=0.01).validate()
    with pytest.raises(ValueError, match="record_latency"):
        Scenario(workflow=wf, operators=factory,
                 record_latency=True).validate()


def test_windowpane_repr_fields():
    p = WindowPane("k", 0.0, 1.0, (1, 2, 3))
    assert p.n == 3
    assert isinstance(Filter("f", lambda k, v: True), Filter)
    assert KeyBy("kb", lambda k, v: k).ordering == KEYED
    assert Sink("s").ordering == UNORDERED


# -------------------------------------------------- lock striping / batching
def _keys_by_stripe(win):
    """Find (anchor, same-stripe, different-stripe) keys deterministically."""
    anchor = "k0"
    si = win._stripe_of(anchor)
    same = diff = None
    i = 1
    while same is None or diff is None:
        k = f"k{i}"
        if win._stripe_of(k) == si and same is None and k != anchor:
            same = k
        elif win._stripe_of(k) != si and diff is None:
            diff = k
        i += 1
    return anchor, same, diff


def test_window_lock_striping_contention():
    """Different-stripe keys ingest concurrently; only same-stripe keys
    serialize.  Holding one stripe's lock must not block the others."""
    win = TumblingWindow("w", 1.0, stripes=8)
    anchor, same, diff = _keys_by_stripe(win)
    done = []

    def ing(key):
        win.ingest(Element(key, 1.0, 0.2))
        done.append(key)

    with win._stripe_locks[win._stripe_of(anchor)]:
        t_diff = threading.Thread(target=ing, args=(diff,), daemon=True)
        t_diff.start()
        t_diff.join(timeout=5.0)
        assert done == [diff], "different stripe must not contend"
        t_same = threading.Thread(target=ing, args=(same,), daemon=True)
        t_same.start()
        t_same.join(timeout=0.2)
        assert same not in done, "same stripe must serialize on its lock"
    t_same.join(timeout=5.0)
    assert sorted(done) == sorted([diff, same])
    assert win.records_in == 2 and win.accounting()["closed"]


def test_window_striping_keyed_fire_parity():
    """A striped window fires the same panes in the same order as the
    single-lock semantics: (key, span) sorted emission, closed ledger."""
    def build(stripes):
        return (OperatorPipeline()
                .key_by("kb", lambda k, rec: f"r{rec.rank}")
                .tumbling_window("win", 1.0, stripes=stripes)
                .aggregate("agg", lambda k, vals: sorted(r.step for r in vals))
                .sink("out")
                .compile())

    outs = []
    for stripes in (1, 4, 16):
        plan = build(stripes)
        for seq, batch in enumerate(
                [[_rec(s, 0.3 * s + 0.1, rank=s % 3) for s in range(4)],
                 [_rec(s, 0.3 * s + 0.1, rank=s % 3) for s in range(4, 8)]]):
            plan.run_pre("f/g0/r0", batch, seq=seq)
        plan.flush()
        acct = plan.accounting()
        assert acct["closed"] and acct["windows"]["win"]["late_dropped"] == 0
        outs.append([(k, v) for k, v, _t in plan.results("out")])
    assert outs[0] == outs[1] == outs[2]


def test_tumbling_window_stripes_validation():
    with pytest.raises(ValueError, match="stripes"):
        TumblingWindow("w", 1.0, stripes=0)


def test_batch_aggregate_coalesces_cofired_panes():
    """Panes fired for many keys at one watermark advance reach the
    BatchAggregate in a single process_many call."""
    seen = []

    def fn(items):
        seen.append(len(items))
        return [sum(float(r.payload[0]) for r in vals) for _k, vals in items]

    plan = (OperatorPipeline()
            .key_by("kb", lambda k, rec: f"r{rec.rank}")
            .tumbling_window("win", 1.0)
            .batch_aggregate("agg", fn)
            .sink("out")
            .compile())
    plan.run_pre("f/g0/r0",
                 [_rec(1, 0.2, rank=r, val=r) for r in range(4)], seq=0)
    plan.run_pre("f/g0/r0", [_rec(2, 1.5, rank=0, val=9)], seq=1)
    out = plan.results("out")
    assert sorted((k, v) for k, v, _t in out) \
        == [("r0", 0.0), ("r1", 1.0), ("r2", 2.0), ("r3", 3.0)]
    assert max(seen) == 4, "all four co-fired panes must batch into one call"
    stats = plan.batch_stats()["agg"]
    assert stats["max_batch"] == 4 and stats["items"] == 4


def test_batch_aggregate_matches_plain_aggregate():
    def per_key(k, vals):
        return round(sum(r.step for r in vals), 6)

    def batched(items):
        return [round(sum(r.step for r in vals), 6) for _k, vals in items]

    def feed(plan):
        for seq, batch in enumerate(
                [[_rec(s, 0.4 * s, rank=s % 3) for s in range(6)],
                 [_rec(9, 3.0, rank=0)]]):
            plan.run_pre("f/g0/r0", batch, seq=seq)
        plan.flush()
        return sorted((k, v) for k, v, _t in plan.results("out"))

    base = (OperatorPipeline()
            .key_by("kb", lambda k, rec: f"r{rec.rank}")
            .tumbling_window("win", 1.0)
            .aggregate("agg", per_key)
            .sink("out").compile())
    fast = (OperatorPipeline()
            .key_by("kb", lambda k, rec: f"r{rec.rank}")
            .tumbling_window("win", 1.0)
            .batch_aggregate("agg", batched)
            .sink("out").compile())
    assert feed(base) == feed(fast)


def test_batch_aggregate_single_and_mismatch():
    agg = BatchAggregate("b", lambda items: [len(v) for _k, v in items])
    [out] = agg.process(Element("k", [1, 2, 3], 0.0))
    assert out.value == 3 and agg.batch_stats()["batches"] == 1
    bad = BatchAggregate("b", lambda items: [])
    with pytest.raises(ValueError, match="returned 0 results for 1"):
        bad.process(Element("k", [1], 0.0))


def test_batch_aggregate_e2e_virtual_clock_metrics():
    """Keyed end-to-end on VirtualClock: coalescing shows up in the engine's
    metrics() snapshot and the window ledger stays closed."""
    clock = VirtualClock(seed=0)
    clock.attach()
    cfg = WorkflowConfig(n_producers=2, n_groups=1, executors_per_group=2,
                         compress="none", trigger_interval=0.05, min_batch=2,
                         clock="virtual", clock_seed=0)
    pipeline = (OperatorPipeline()
                .key_by("kb", lambda k, rec: f"r{rec.rank}")
                .tumbling_window("win", 0.5, allowed_lateness_s=0.5)
                .batch_aggregate("agg", lambda items: [len(v)
                                                       for _k, v in items])
                .sink("out"))
    sess = Session(cfg, pipeline=pipeline, clock=clock)
    h = sess.open_field("f", shape=(4,))
    for step in range(60):
        for rank in range(2):
            h.write(step, np.full(4, float(step), np.float32), rank=rank)
        clock.sleep(0.05)
    sess.flush(timeout=60.0)
    m = sess.engine.metrics()
    stats = sess.exec_plan.batch_stats()["agg"]
    acct = sess.exec_plan.accounting()
    sess.close()
    assert m["batch_agg"]["agg"] == stats
    assert stats["items"] >= 4 and stats["max_batch"] >= 2
    assert acct["closed"]
    counted = sum(v for _k, v, _t in sess.exec_plan.results("out"))
    assert counted == 120
