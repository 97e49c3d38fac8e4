"""Distributed stream-processing engine — the Spark-Streaming stand-in.

Implements the paper's Cloud pipeline (Fig 2/3): endpoints feed per-stream
micro-batches (trigger-interval windows, like Spark DStreams); micro-batches
of one stream form partitions of an RDD-like unit of work; a fixed subset of
executors owns each endpoint's partitions (the paper's 16:1:16 mapping) and
pipes each partition exactly once (rdd.pipe) through the compiled operator
``ExecutionPlan`` (repro.streaming.operators) — the engine's only unit of
work; a per-batch callback is a one-stage, batch-granularity plan.  A
collector gathers results (rdd.collect) with generation->analysis latency.

Beyond the paper (Spark gave these for free; we implement them):
  * work stealing   — idle executors steal queued partitions (straggler
                      mitigation).  An idle executor blocks on its own
                      queue; a put that leaves a backlog wakes one of them
                      to steal.  Steals migrate the stream's sticky
                      assignment to the thief, and per-stream sequence
                      tickets guarantee a stolen micro-batch is never
                      analyzed concurrently with — or ahead of — an earlier
                      micro-batch of the same stream,
  * elastic scaling — add/remove/replace executors at runtime; every scale
                      event triggers ``rebalance()`` so stream→executor
                      stickiness is recomputed against the new fleet,
  * failure handling — a dead executor's queued partitions are reassigned,
  * observability   — ``metrics()`` returns a thread-safe control-plane
                      snapshot (per-executor queues, rolling latency
                      percentiles, executor-seconds) consumed by
                      ``repro.runtime.telemetry``,
  * plan-aware dispatch — the plan's order-insensitive prefix runs
                      before/without the per-stream ordering ticket with
                      partitions spread across executors (intra-stream
                      parallelism), while the ordered suffix keeps the
                      exact-sequence guarantee; ``drain_and_stop`` fires
                      still-open window panes once every partition has
                      completed.
"""
from __future__ import annotations

import queue
import threading
from collections import deque
from dataclasses import dataclass, field
from typing import Any

from repro.core.records import StreamRecord
from repro.runtime.clock import Clock, ensure_clock
from repro.runtime.telemetry import span

# A waiting executor proceeds out-of-order after this long rather than stall
# the pipeline if its stream's ticket chain broke (a dropped partition with
# no surviving executor); counted in metrics()["order_timeouts"].
_ORDER_WAIT_S = 5.0

# An idle executor blocks on its own queue until a put or a wake token
# lands there; this timeout is only the safety net against a lost wake.
_IDLE_WAIT_S = 1.0

# metrics() latency percentiles cover at most this much trailing wall time,
# so a past breach episode ages out of the QoS signal instead of pinning
# the controller's p99 reading high through a quiet period.
_LATENCY_WINDOW_S = 30.0


def percentile_sorted(sorted_vals: list, p: float) -> float:
    """Nearest-rank percentile over an ASCENDING-sorted list; NaN if empty.
    The one definition shared by latency_stats(), metrics(), and the
    elasticity benchmark, so the controller's QoS signal and the bench's
    pass/fail gate measure the same quantity."""
    if not sorted_vals:
        return float("nan")
    return sorted_vals[min(len(sorted_vals) - 1, int(len(sorted_vals) * p))]


@dataclass
class MicroBatch:
    stream_key: str
    records: list[StreamRecord]
    # 0.0, not wall time: the engine stamps this explicitly from its clock
    # at dispatch (trigger_once); a wall-epoch default would leak ~1.7e9s
    # timestamps into virtual-time runs from directly-constructed batches
    t_created: float = 0.0
    seq: int = 0                 # per-stream dispatch sequence (ordering)

    @property
    def steps(self) -> list[int]:
        return [r.step for r in self.records]


@dataclass
class Result:
    stream_key: str
    value: Any
    n_records: int
    t_generated_min: float
    t_analyzed: float
    executor: int
    # per-tenant share of this batch: tenant -> (n_records, min t_generated);
    # the QoS plane's per-tenant latency is t_analyzed - that tenant's min
    tenants: dict = field(default_factory=dict)

    @property
    def latency(self) -> float:
        """Paper §4.3 metric: data generated -> data analyzed."""
        return self.t_analyzed - self.t_generated_min

    def tenant_latency(self, name: str) -> float | None:
        ent = self.tenants.get(name)
        return None if ent is None else self.t_analyzed - ent[1]


class _Executor(threading.Thread):
    def __init__(self, idx: int, engine: "StreamEngine"):
        super().__init__(daemon=True, name=f"executor-{idx}")
        self.idx = idx
        self.engine = engine
        self.q: queue.Queue = queue.Queue()
        self.alive = True
        self.processed = 0
        self.stolen = 0
        self.idle_wakeups = 0          # wakeups that found nothing to do
        self.slowdown = 0.0            # straggler injection (tests/benches)
        self.current_key: str | None = None    # stream being analyzed now
        self.t_busy_since = 0.0        # when the current analysis started
        self.waiting = False           # blocked on an ordering ticket

    def run(self):
        eng = self.engine
        clock = eng.clock
        while self.alive:
            mb = self._take(eng, clock)
            if mb is None:
                continue
            if mb is _POISON:
                break
            self.current_key = mb.stream_key
            # (stream, seq) ties this batch's wait to the spans inside it
            with span("engine.run", stream=mb.stream_key, seq=mb.seq,
                      records=len(mb.records),
                      queued_us=int((clock.now() - mb.t_created) * 1e6)):
                self._process(mb, clock)
            self.processed += 1
            self.current_key = None
            eng._release_turn(mb)
        # hand back anything still queued: a partition can land here AFTER
        # _reassign drained this queue (e.g. this thread was mid-_steal when
        # it was replaced and put the stolen run into its own dead queue)
        eng._reassign(self)
        clock.detach()     # exit the schedule without a watchdog stall

    def _take(self, eng: "StreamEngine", clock):
        """The next micro-batch: this executor's own queue first.  Else it
        joins the engine's idle set, tries one steal and, finding nothing,
        blocks on its queue until a dispatch, a shutdown or a wake token
        lands there (``StreamEngine._wake_thief``: a backlog formed
        elsewhere), then steals once.  None when a wakeup found nothing
        to do."""
        try:
            mb = self.q.get_nowait()
        except queue.Empty:
            mb = None
        if mb is not None and mb is not _WAKE:
            return mb
        # idle before the scan: a backlog that forms after it wakes us
        eng._idle_enter(self)
        try:
            mb = eng._steal(self.idx)
            if mb is None:
                mb = clock.queue_get(self.q, timeout=_IDLE_WAIT_S)
                if mb is not None and mb is not _WAKE:
                    return mb              # own work, or _POISON
                if not self.alive:
                    return None
                mb = eng._steal(self.idx)
                if mb is None:
                    self.idle_wakeups += 1
                    return None
        finally:
            eng._idle_leave(self)
        self.stolen += 1
        return mb

    def _process(self, mb: MicroBatch, clock) -> None:
        """Analyse one micro-batch and hand its Result to the engine."""
        eng = self.engine
        value = self._run_plan(eng.plan, mb, clock)
        tmin = min((r.t_generated for r in mb.records), default=mb.t_created)
        by_tenant: dict[str, tuple[int, float]] = {}
        for r in mb.records:
            ent = by_tenant.get(r.tenant)
            by_tenant[r.tenant] = (1, r.t_generated) if ent is None else \
                (ent[0] + 1, min(ent[1], r.t_generated))
        eng._collect(Result(stream_key=mb.stream_key, value=value,
                            n_records=len(mb.records),
                            t_generated_min=tmin,
                            t_analyzed=clock.now(), executor=self.idx,
                            tenants=by_tenant))

    def _run_plan(self, plan, mb: MicroBatch, clock) -> Any:
        """Plan-aware execution: the order-insensitive prefix runs BEFORE
        (and without) the stream's ordering ticket — that's what lets
        micro-batches of ONE stream proceed concurrently on many executors —
        then the ordered suffix (if any) under the ticket, exactly
        sequenced.  A plan with no ordered stages never takes the ticket.
        A straggler's ``slowdown`` lands just before the first stage this
        executor runs: ahead of the prefix, or after the ticket when the
        plan is all suffix."""
        eng = self.engine
        self.t_busy_since = clock.now()
        pre_out = None
        if plan.pre_stages:
            if self.slowdown:
                clock.sleep(self.slowdown)
            try:
                # seq feeds the plan's in-order frontier: window watermarks
                # advance only over the contiguous per-stream prefix, so
                # concurrent out-of-order batches can't induce late drops
                pre_out = plan.run_pre(mb.stream_key, mb.records, seq=mb.seq)
            except Exception as e:     # analysis failure != engine failure
                if plan.post_stages:
                    # the failed batch must still take its ordering turn:
                    # the caller's _release_turn is a max-jump, so releasing
                    # out of sequence would unblock every in-flight earlier
                    # batch at once and break the ordered suffix's contract
                    self.waiting = True
                    eng._await_turn(mb)
                    self.waiting = False
                return e
            if not plan.post_stages:
                return pre_out.primary
        self.waiting = True
        eng._await_turn(mb)
        self.waiting = False
        self.t_busy_since = clock.now()
        if pre_out is None and self.slowdown:
            clock.sleep(self.slowdown)
        try:
            return plan.run_post(mb.stream_key, pre_out, mb.records)
        except Exception as e:
            return e

    def kill(self):
        """Simulated hard failure: drop the thread, orphan its queue."""
        self.alive = False


_POISON = MicroBatch(stream_key="__poison__", records=[])
# put in an idle executor's queue to make it run one steal; carries no work
_WAKE = MicroBatch(stream_key="__wake__", records=[])


class StreamEngine:
    def __init__(self, endpoints: list, plan, n_executors: int, *,
                 trigger_interval: float = 3.0,
                 min_batch: int = 2, clock: Clock | None = None,
                 order_wait_s: float = _ORDER_WAIT_S,
                 shuffle_partitions: int | None = None):
        """endpoints: Endpoint handles (drain API).  plan: the compiled
        operator ``ExecutionPlan`` every micro-batch runs through.

        ``min_batch``: a stream's drained records are held until at least
        this many accumulate (so the analyze path sees real micro-batches —
        one device call per batch, not per record) or until a trigger
        interval has passed since the first held record, whichever first;
        ``drain_and_stop`` force-flushes the remainder.

        ``clock``: every timestamp, sleep, and blocking wait goes through it
        (default wall time); a ``VirtualClock`` makes the whole engine —
        driver, executors, ordering waits, latency accounting — run on
        deterministic simulated time.

        ``shuffle_partitions``: when set and the attached plan compiles to
        a shuffle edge (source ``KeyBy`` at record granularity), dispatch
        re-partitions records ACROSS producer streams by the KeyBy's output
        key: micro-batches become key partitions (``part:NNNN``), sticky
        partition->executor ownership replaces producer-stream ownership,
        and ordering tickets are issued per partition."""
        self.endpoints = endpoints
        self.trigger_interval = trigger_interval
        self.min_batch = min_batch
        self.order_wait_s = order_wait_s
        self.shuffle_partitions = shuffle_partitions
        self.clock = ensure_clock(clock)
        self.results: list[Result] = []
        self._recent_lat: deque = deque(maxlen=512)  # rolling latency window
        # per-tenant rolling latency + analyzed totals (QoS plane rollups)
        self._tenant_lat: dict[str, deque] = {}
        self._tenant_analyzed: dict[str, int] = {}
        self._rlock = threading.Lock()
        self._elock = threading.Lock()
        # trigger_once reentrancy + hold/assign/seq state (RLock: _reassign
        # and _pick_executor may be reached both under it and bare)
        self._tlock = threading.RLock()
        self._hold: dict[str, list[StreamRecord]] = {}
        self._hold_t: dict[str, float] = {}    # first-held time per stream
        self.executors: list[_Executor] = []
        self._stop = threading.Event()
        self._assign: dict[str, int] = {}      # stream -> executor idx
        self._next_seq: dict[str, int] = {}    # stream -> next dispatch seq
        self._done_cv = threading.Condition()
        self._done_seq: dict[str, int] = {}    # stream -> completed prefix
        self.order_timeouts = 0                # broken-chain escapes (rare)
        self.rebalances = 0
        # executors blocked on an empty queue, longest idle first
        self._ilock = threading.Lock()
        self._idle: dict[int, _Executor] = {}
        self.steal_wakes = 0                   # idle executors woken to steal
        self.attach_plan(plan)
        # executor-seconds integral (elasticity cost accounting)
        self._exec_secs = 0.0
        self._exec_t = self.clock.now()
        for _ in range(n_executors):
            self._add_executor_locked()
        self._driver = threading.Thread(target=self._drive, daemon=True,
                                        name="stream-driver")
        self.clock.thread_started(self._driver)
        self._driver.start()

    @classmethod
    def from_config(cls, cfg, endpoints: list, plan, *, groups=None,
                    clock: Clock | None = None) -> "StreamEngine":
        """Build from a ``repro.workflow.WorkflowConfig`` (duck-typed here to
        keep streaming← workflow import-free).  ``n_executors=None`` falls
        back to the ``GroupPlan`` ``groups``' groups × executors_per_group —
        the paper's 16:1:16 operating point."""
        n_exec = cfg.n_executors
        if n_exec is None:
            n_exec = groups.n_executors if groups is not None \
                else max(1, len(endpoints)) * cfg.executors_per_group
        return cls(endpoints, plan, n_executors=n_exec,
                   trigger_interval=cfg.trigger_interval,
                   min_batch=cfg.min_batch, clock=clock,
                   order_wait_s=getattr(cfg, "order_wait_s", _ORDER_WAIT_S),
                   shuffle_partitions=getattr(cfg, "shuffle_partitions",
                                              None))

    def attach_plan(self, plan) -> None:
        """Route every micro-batch through a compiled operator
        ``ExecutionPlan`` (see ``repro.streaming.operators``).  Dispatch
        is plan-aware: plans with an order-insensitive prefix get their
        partitions spread across executors (intra-stream parallelism, capped
        by the plan's parallelism hint) instead of sticky-assigned, and the
        ordering ticket is only taken for the plan's ordered suffix.

        Attaching mid-run replaces the plan and aligns its watermark
        frontier with the engine's continuing per-stream seq counters — a
        fresh frontier expecting seq 0 would park every future batch as
        pending and stall window firing until drain.

        With ``shuffle_partitions`` configured, a plan that compiles to a
        shuffle edge (source KeyBy, record granularity) switches to keyed-
        shuffle dispatch; plans without one keep producer partitioning."""
        if self.shuffle_partitions is not None \
                and plan.shuffle_op is not None:
            plan.enable_shuffle(self.shuffle_partitions)
        with self._tlock:
            plan.seed_frontier(dict(self._next_seq))
        self.plan = plan

    # ---- per-stream ordering tickets ------------------------------------
    def _await_turn(self, mb: MicroBatch) -> bool:
        """Block until every earlier micro-batch of this stream has been
        analyzed.  Sequence tickets are issued at dispatch, so order holds
        across steals, reassignment, and rebalance.  Returns False on the
        (pathological) broken-chain timeout."""
        if self.clock.wait_cv(
                self._done_cv,
                lambda: self._done_seq.get(mb.stream_key, 0) >= mb.seq,
                timeout=self.order_wait_s):
            return True
        self.order_timeouts += 1
        return False

    def _release_turn(self, mb: MicroBatch) -> None:
        with self._done_cv:
            if mb.seq + 1 > self._done_seq.get(mb.stream_key, 0):
                self._done_seq[mb.stream_key] = mb.seq + 1
            self._done_cv.notify_all()

    # ---- executor lifecycle (elasticity + failure) ----------------------
    def _account_locked(self, now: float | None = None) -> None:
        """Advance the executor-seconds integral (call under _elock)."""
        now = self.clock.now() if now is None else now
        alive = sum(1 for e in self.executors if e.alive)
        self._exec_secs += alive * (now - self._exec_t)
        self._exec_t = now

    def executor_seconds(self) -> float:
        """∫ alive-executor-count dt since engine start — the provisioning
        cost the elasticity benchmark compares against static peak."""
        with self._elock:
            self._account_locked()
            return self._exec_secs

    def _add_executor_locked(self):
        self._account_locked()
        ex = _Executor(len(self.executors), self)
        self.executors.append(ex)
        self.clock.thread_started(ex)
        ex.start()
        return ex

    def add_executor(self):
        with self._elock:
            ex = self._add_executor_locked()
        self.rebalance()
        return ex

    def remove_executor(self, idx: int | None = None):
        """Graceful scale-in.  ``idx=None`` retires the newest alive
        executor; an explicit ``idx`` retires that one (the cloud capacity
        plane drains a *specific* node's executors before poweroff).
        Queued partitions are reassigned to survivors either way."""
        with self._elock:
            removed = None
            cands = (reversed(self.executors) if idx is None
                     else [self.executors[idx]])
            for ex in cands:
                if ex.alive:
                    self._account_locked()
                    ex.alive = False
                    self._reassign(ex)
                    ex.q.put(_POISON)      # after: _reassign drops poison
                    removed = ex.idx
                    break
        if removed is not None:
            self.rebalance()
        return removed

    def attach_endpoint(self, handle) -> None:
        """Start draining a freshly provisioned endpoint's streams (cloud
        capacity plane: list append is atomic, pollers see it next cycle)."""
        self.endpoints.append(handle)

    def kill_executor(self, idx: int):
        """Hard failure; queued partitions are reassigned to survivors."""
        ex = self.executors[idx]
        with self._elock:
            self._account_locked()
            ex.kill()
            self._reassign(ex)
            ex.q.put(_POISON)          # a blocked executor exits now
        self.rebalance()

    def replace_executor(self, idx: int):
        """Straggler/failure remediation: retire executor ``idx`` (its queue
        is reassigned) and bring up a fresh one.  Returns the replacement."""
        ex = self.executors[idx]
        with self._elock:
            self._account_locked()
            was_alive, ex.alive = ex.alive, False
            self._reassign(ex)
            if was_alive:
                ex.q.put(_POISON)      # after: _reassign drops poison
            new = self._add_executor_locked()
        self.rebalance()
        return new

    def rebalance(self) -> int:
        """Recompute stream→executor stickiness against the current fleet
        (called on every scale/failure event).  Only streams with NO
        dispatched-but-unfinished micro-batches are released — a backlogged
        stream must keep its assignment so new dispatches queue behind the
        backlog in order; *stealing* is what migrates a backlog to a new
        executor (oldest batch first, assignment moved with it).  Returns
        the number of stream assignments released."""
        with self._done_cv:
            done = dict(self._done_seq)
        n = 0
        with self._tlock:
            for key in list(self._assign):
                if done.get(key, 0) >= self._next_seq.get(key, 0):
                    del self._assign[key]
                    n += 1
        self.rebalances += 1
        return n

    def _enqueue_in_seq_order(self, tgt: _Executor, mb: MicroBatch) -> None:
        """Insert a reassigned partition BEFORE any later-seq partition of
        the same stream already queued on the target (the driver may have
        dispatched newer batches to the new sticky executor while the dead
        one's queue was still being drained); plain append would make the
        target block on its own queue and then analyze out of order."""
        self._idle_leave(tgt)
        with tgt.q.mutex:
            dq = tgt.q.queue
            pos = next((i for i, x in enumerate(dq)
                        if isinstance(x, MicroBatch) and x is not _POISON
                        and x.stream_key == mb.stream_key
                        and x.seq > mb.seq), None)
            if pos is None:
                dq.append(mb)
            else:
                dq.insert(pos, mb)
            tgt.q.not_empty.notify()
        self._wake_thief(tgt)

    def _reassign(self, dead: _Executor):
        moved = 0
        while True:
            try:
                mb = dead.q.get_nowait()
            except queue.Empty:
                break
            if mb is _POISON or mb is _WAKE:
                continue
            tgt = self._pick_executor(mb.stream_key, exclude=dead.idx)
            if tgt is not None:
                self._enqueue_in_seq_order(tgt, mb)
                moved += 1
            else:
                # no survivor: release the ticket so later batches of this
                # stream (none can exist yet without executors, but a scale-up
                # may follow) don't wait on a batch nobody holds
                self._release_turn(mb)
        with self._tlock:
            for k, v in list(self._assign.items()):
                if v == dead.idx:
                    del self._assign[k]
        return moved

    def _alive(self) -> list[_Executor]:
        return [e for e in self.executors if e.alive]

    def _pick_executor(self, stream_key: str, exclude: int | None = None):
        alive = [e for e in self._alive() if e.idx != exclude]
        if not alive:
            return None
        with self._tlock:
            if stream_key in self._assign:
                idx = self._assign[stream_key]
                for e in alive:
                    if e.idx == idx:
                        return e
            # sticky partition->executor mapping (paper: fixed subset per
            # stream), least-loaded at (re)assignment time
            e = min(alive, key=lambda e: e.q.qsize())
            self._assign[stream_key] = e.idx
            return e

    def _pick_parallel(self):
        """Plan-aware dispatch for order-insensitive work: NO stickiness —
        each partition goes to the least-loaded alive executor, so one
        stream's micro-batches spread across the fleet.  A parallelism hint
        caps the *candidates per dispatch* to the hint least-loaded
        executors (not a fixed low-index subset — scale-ups must stay
        usable).  Per-stream queues stay seq-ascending because dispatch
        itself is in seq order."""
        alive = self._alive()
        if not alive:
            return None
        hint = self.plan.parallelism
        if hint is not None and hint < len(alive):
            alive = sorted(alive, key=lambda e: e.q.qsize())[:hint]
        return min(alive, key=lambda e: e.q.qsize())

    # ---- idle executors and wakes -----------------------------------------
    def _idle_enter(self, ex: _Executor) -> None:
        with self._ilock:
            self._idle[ex.idx] = ex

    def _idle_leave(self, ex: _Executor) -> None:
        with self._ilock:
            self._idle.pop(ex.idx, None)

    def _put(self, ex: _Executor, mb: MicroBatch) -> int:
        """Queue ``mb`` on ``ex`` (the put itself wakes it, so it leaves the
        idle set) and wake a thief if that left a backlog; returns the
        number of executors woken to steal (0 or 1)."""
        self._idle_leave(ex)
        ex.q.put(mb)
        return self._wake_thief(ex)

    def _wake_thief(self, busy: _Executor) -> int:
        """A put left ``busy``'s queue holding more than one item: wake the
        longest-idle executor with a token so it runs one steal.  Returns
        the number woken (0 or 1)."""
        if busy.q.qsize() < 2:
            return 0
        with self._ilock:
            thief = next((e for e in self._idle.values()
                          if e is not busy and e.alive), None)
            if thief is None:
                return 0
            del self._idle[thief.idx]
            self.steal_wakes += 1
        thief.q.put(_WAKE)
        return 1

    # ---- work stealing ---------------------------------------------------
    @staticmethod
    def _peek_key(ex: _Executor) -> str | None:
        with ex.q.mutex:
            head = ex.q.queue[0] if ex.q.queue else None
        return head.stream_key if isinstance(head, MicroBatch) else None

    def _steal(self, thief_idx: int):
        """Steal the oldest queued partition from the deepest victim — and
        migrate the WHOLE stream with it: every later queued partition of
        that stream moves to the thief (in order) and the sticky assignment
        follows, so the thief owns the stream's run end-to-end instead of
        blocking on ordering tickets behind the victim's queue.  Prefer
        victims whose head partition is NOT the stream the victim is
        analyzing right now (that ticket would make the thief wait out the
        victim's in-flight batch); tickets keep order correct either way."""
        victims = sorted(
            (e for e in self._alive()
             if e.idx != thief_idx and e.q.qsize() > 1),
            key=lambda e: e.q.qsize(), reverse=True)
        if not victims:
            return None
        preferred = [v for v in victims
                     if self._peek_key(v) != v.current_key] or victims
        for victim in preferred:
            try:
                mb = victim.q.get_nowait()
            except queue.Empty:
                continue
            if mb is _POISON:          # dying executor: hand it back
                victim.q.put(_POISON)
                continue
            if mb is _WAKE:            # the victim has other items queued
                continue
            if self.plan.parallel_dispatch and not self.plan.shuffled:
                # parallel-dispatch plans have no sticky run to migrate:
                # batches of one stream are already spread, so steal just
                # the head partition.  Shuffled plans DO have sticky runs
                # (partition ownership) and fall through to run migration.
                return mb
            key = mb.stream_key
            # extract the rest of this stream's queued run, preserving order
            with victim.q.mutex:
                rest = [x for x in victim.q.queue
                        if isinstance(x, MicroBatch) and x is not _POISON
                        and x.stream_key == key]
                for x in rest:
                    victim.q.queue.remove(x)
            with self._tlock:
                if self._assign.get(key) == victim.idx:
                    self._assign[key] = thief_idx
            thief = self.executors[thief_idx]
            for x in rest:
                thief.q.put(x)
            self._wake_thief(thief)
            return mb
        return None

    # ---- driver: trigger-interval micro-batching -------------------------
    def _drive(self):
        while not self._stop.is_set():
            t0 = self.clock.now()
            self.trigger_once()
            dt = self.clock.now() - t0
            self.clock.wait_event(self._stop,
                                  timeout=max(0.0, self.trigger_interval - dt))
        self.clock.detach()    # exit the schedule without a watchdog stall

    def trigger_once(self, force: bool = False) -> int:
        """Drain endpoints into hold buffers and dispatch every buffer that
        is ripe: >= min_batch records held, the first held record is older
        than one trigger interval, or ``force``.

        Hold buffers are per producer stream by default.  Under keyed
        shuffle (``plan.shuffled``) they are per key **partition**: each
        drained record is routed to ``part:NNNN`` by the plan's shuffle
        edge, pooling co-keyed records from many streams into one partition
        and spreading one hot stream's keys over all partitions.  Shuffled
        partitions dispatch with sticky partition->executor ownership (the
        partition, not the producer stream, is the unit the fleet owns),
        and seq tickets are issued per partition."""
        n = woken = 0
        now = self.clock.now()
        plan = self.plan
        shuffled = plan.shuffled
        with span("engine.trigger") as sp, self._tlock:
            for ep in self.endpoints:
                for key in ep.stream_keys():
                    recs = ep.drain(key)
                    if not recs:
                        continue
                    if shuffled:
                        for r in recs:
                            pkey = f"part:{plan.shuffle_partition(r):04d}"
                            self._hold.setdefault(pkey, []).append(r)
                            self._hold_t.setdefault(pkey, now)
                    else:
                        self._hold.setdefault(key, []).extend(recs)
                        self._hold_t.setdefault(key, now)
            for key in list(self._hold):
                held = self._hold[key]
                ripe = (force or len(held) >= self.min_batch
                        or now - self._hold_t[key] >= self.trigger_interval)
                if not ripe:
                    continue
                parallel = not shuffled and plan.parallel_dispatch
                ex = self._pick_parallel() if parallel \
                    else self._pick_executor(key)
                if ex is None:
                    continue
                seq = self._next_seq.get(key, 0)
                self._next_seq[key] = seq + 1
                woken += self._put(ex, MicroBatch(
                    stream_key=key, records=held, seq=seq, t_created=now))
                del self._hold[key], self._hold_t[key]
                n += 1
            sp.set_metadata(dispatched=n, held=len(self._hold), woken=woken)
        return n

    def held(self) -> int:
        with self._tlock:
            return sum(len(v) for v in self._hold.values())

    def _collect(self, r: Result):
        with self._rlock:
            self.results.append(r)
            self._recent_lat.append((r.t_analyzed, r.latency))
            for name, (n, tmin) in r.tenants.items():
                self._tenant_analyzed[name] = \
                    self._tenant_analyzed.get(name, 0) + n
                self._tenant_lat.setdefault(name, deque(maxlen=512)).append(
                    (r.t_analyzed, r.t_analyzed - tmin))

    # ---- public ----------------------------------------------------------
    def collect(self, clear: bool = False) -> list[Result]:
        with self._rlock:
            out = list(self.results)
            if clear:
                self.results.clear()
            return out

    def latency_stats(self) -> dict:
        lats = [r.latency for r in self.collect()]
        if not lats:
            return {"n": 0}
        lats.sort()
        return {"n": len(lats),
                "mean": sum(lats) / len(lats),
                "p50": percentile_sorted(lats, 0.50),
                "p99": percentile_sorted(lats, 0.99),
                "max": lats[-1]}

    def metrics(self) -> dict:
        """Thread-safe control-plane snapshot: per-executor queue depth /
        steal counts, hold-buffer backlog, rolling (windowed) latency
        percentiles, the executor-seconds integral, and how often idle
        executors woke: ``idle_wakeups`` (found nothing to do) and
        ``steal_wakes`` (woken because a backlog formed).  This is the
        engine's feed into ``runtime.telemetry.TelemetryBus``."""
        def _qrecords(ex: _Executor) -> int:
            with ex.q.mutex:
                return sum(len(x.records) for x in ex.q.queue
                           if isinstance(x, MicroBatch))
        with self._elock:
            self._account_locked()
            execs = [{"idx": e.idx, "alive": e.alive,
                      "queue_depth": e.q.qsize(),
                      "queued_records": _qrecords(e),
                      "processed": e.processed,
                      "stolen": e.stolen, "current_key": e.current_key,
                      "waiting": e.waiting}
                     for e in self.executors]
            exec_secs = self._exec_secs
        with self._tlock:
            held = sum(len(v) for v in self._hold.values())
            n_streams = len(self._next_seq)
        cut = self.clock.now() - _LATENCY_WINDOW_S
        with self._rlock:
            lats = sorted(lat for t, lat in self._recent_lat if t >= cut)
            n_results = len(self.results)
            tenants = {}
            for name, analyzed in self._tenant_analyzed.items():
                tl = sorted(lat for t, lat in self._tenant_lat.get(name, ())
                            if t >= cut)
                tenants[name] = {
                    "analyzed": analyzed,
                    "latency_window_n": len(tl),
                    "latency_p50": percentile_sorted(tl, 0.50),
                    "latency_p99": percentile_sorted(tl, 0.99)}
        batch_agg = self.plan.batch_stats()
        shuffle_n = self.plan.shuffle_partitions   # None unless shuffled
        return {"executors": execs,
                "tenants": tenants,
                "shuffle_partitions": shuffle_n,
                "alive_executors": sum(1 for e in execs if e["alive"]),
                "batch_agg": batch_agg,
                "queued": sum(e["queue_depth"] for e in execs if e["alive"]),
                "queued_records": sum(e["queued_records"] for e in execs),
                "held_records": held,
                "n_streams": n_streams,
                "n_results": n_results,
                "latency_window_n": len(lats),
                "latency_p50": percentile_sorted(lats, 0.50),
                "latency_p99": percentile_sorted(lats, 0.99),
                "executor_seconds": exec_secs,
                "order_timeouts": self.order_timeouts,
                "rebalances": self.rebalances,
                "idle_wakeups": sum(e.idle_wakeups for e in self.executors),
                "steal_wakes": self.steal_wakes}

    def drain_and_stop(self, timeout: float = 30.0):
        deadline = self.clock.now() + timeout
        while self.clock.now() < deadline:
            # partitions stranded on dead executors (dispatch/steal raced a
            # kill) go back to survivors before we test for emptiness
            for e in self.executors:
                if not e.alive and e.q.qsize() and self._alive():
                    self._reassign(e)
            # under the trigger lock: a trigger in flight holds records it
            # took from the endpoints and has not queued yet
            with self._tlock:
                pending = sum(ep.pending() for ep in self.endpoints)
                queued = sum(e.q.qsize() for e in self._alive())
                stranded = sum(e.q.qsize() for e in self.executors
                               if not e.alive)
                held = self.held()
            if pending == 0 and queued == 0 and held == 0 \
                    and (stranded == 0 or not self._alive()):
                break
            self.trigger_once(force=True)
            self.clock.sleep(0.05)
        self._stop.set()
        with self._elock:
            self._account_locked()
        survivors = self._alive()
        for e in survivors:
            e.alive = False
            e.q.put(_POISON)
        for e in survivors:          # results must be collected before return
            self.clock.join(e, timeout=5.0)
        # every partition is done: fire still-open window panes through
        # the rest of the graph (single-threaded, deterministic order)
        self.plan.flush()

    # ---- exactly-once recovery -------------------------------------------
    def kill(self) -> None:
        """Simulated hard crash: driver and executors stop immediately,
        queued and held micro-batches are discarded (the replacement
        session replays them from the broker WAL).  Contrast
        :meth:`drain_and_stop`, which completes all in-flight work."""
        self._stop.set()
        with self._elock:
            self._account_locked()
            for e in self.executors:
                e.alive = False
                with e.q.mutex:
                    e.q.queue.clear()
                    e.q.not_empty.notify_all()
                e.q.put(_POISON)
        with self._tlock:
            self._hold.clear()
            self._hold_t.clear()
        with self._done_cv:
            self._done_cv.notify_all()
        self.clock.join(self._driver, timeout=5.0)
        for e in self.executors:
            self.clock.join(e, timeout=5.0)

    def state_snapshot(self) -> dict:
        """Dispatch/ordering counters plus collected results — the engine's
        share of a Session checkpoint.  Callers quiesce the pipeline first
        (``Session.checkpoint`` does), so the snapshot is a consistent cut."""
        with self._tlock:
            next_seq = dict(self._next_seq)
        with self._done_cv:
            done_seq = dict(self._done_seq)
        with self._rlock:
            results = list(self.results)
        return {"next_seq": next_seq, "done_seq": done_seq,
                "results": results}

    def restore_state(self, state: dict) -> None:
        """Install a checkpointed :meth:`state_snapshot` into a fresh
        engine: per-stream seq counters resume where the dead engine
        stopped (keeping the plan's commit frontier consistent) and
        pre-crash results survive."""
        with self._tlock:
            self._next_seq = dict(state["next_seq"])
        with self._done_cv:
            self._done_seq = dict(state["done_seq"])
            self._done_cv.notify_all()
        with self._rlock:
            self.results = list(state["results"])
            # rebuild per-tenant analyzed totals from the restored results so
            # QoS rollups stay exact across a session restore (the rolling
            # latency windows restart — they are time-local by design)
            self._tenant_analyzed = {}
            for r in self.results:
                for name, (n, _) in getattr(r, "tenants", {}).items():
                    self._tenant_analyzed[name] = \
                        self._tenant_analyzed.get(name, 0) + n
