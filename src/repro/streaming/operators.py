"""Typed stream operators — the Cloud analysis layer as a real dataflow API.

The paper's Cloud side is a distributed stream-processing service (§4: Flink
jobs over broker streams).  This module is its analysis layer: a typed
operator model in the spirit of openPMD/ADIOS2 streaming pipelines and
Wilkins-style declarative in-situ graphs, and the only way to attach
analysis to the ``StreamEngine``:

* :class:`Map` / :class:`Filter`   — per-element transforms,
* :class:`KeyBy`                   — re-key the stream (fan records of many
                                     producer streams into logical keys),
* :class:`TumblingWindow` / :class:`SlidingWindow`
                                   — event-time windows over
                                     ``StreamRecord.t_generated``, holding
                                     keyed state with snapshot/restore hooks,
* :class:`Aggregate`               — reduce a fired window pane to a value,
* :class:`Sink`                    — collect results (session-clock stamped).

Every operator declares an **ordering contract** — ``ordered`` (exact
per-stream arrival order), ``unordered`` (no cross-batch order), or ``keyed``
(per-key state consistency; event-time bucketing makes results insensitive
to processing order) — and a **parallelism hint**.  :meth:`OperatorPipeline.
compile` lowers the graph to an :class:`ExecutionPlan` the
``StreamEngine`` honors: the maximal order-insensitive prefix (every stage
``unordered``/``keyed`` with no ``ordered`` ancestor) runs *before and
without* the stream's ordering ticket, so micro-batches of ONE stream are
analyzed concurrently by many executors; the ordered suffix (if any) keeps
the exactly-sequenced guarantee.  A per-micro-batch callback ``fn(key,
records)`` is the one-stage plan
``OperatorPipeline(granularity="batch").map("analyze", fn,
ordering="ordered")``: the engine's ``Result.value`` is fn's return value
(``None`` when it returns None, the exception when it raises), in sticky,
ticketed per-stream order.

Window state lives in the plan (shared across executors, striped per-key
locks), NOT in any executor thread — so elasticity-driven steals,
``replace_executor``, and rebalances never drop a pane.  ``snapshot()`` /
``restore()`` serialize that state for migration across engines or
sessions, and ``accounting()`` closes the loss ledger:
``records_in == records into fired panes + records in open panes +
late_dropped`` for tumbling windows (per-pane identities for sliding).
"""
from __future__ import annotations

import copy
import threading
from dataclasses import dataclass
from typing import Any, Callable

from repro.core.grouping import partition_of
from repro.runtime.clock import Clock, ensure_clock
from repro.runtime.telemetry import span

ORDERED = "ordered"
UNORDERED = "unordered"
KEYED = "keyed"
_CONTRACTS = (ORDERED, UNORDERED, KEYED)


@dataclass(frozen=True)
class Element:
    """One item flowing through the graph: a key, a value, and its event
    time (``StreamRecord.t_generated`` at the source; pane end for windows)."""

    key: str
    value: Any
    t_event: float


@dataclass(frozen=True)
class WindowPane:
    """One fired window: ``[start, end)`` in event time, values in arrival
    order (sort by your own criterion in the downstream Aggregate if the
    reduction is order-sensitive)."""

    key: str
    start: float
    end: float
    values: tuple

    @property
    def n(self) -> int:
        return len(self.values)


class Operator:
    """One typed stage.  Subclasses implement :meth:`process`; stateful
    operators also implement ``flush``/``snapshot``/``restore``.

    ``ordering`` is the stage's contract (see module docstring);
    ``parallelism`` is a hint capping how many executors the engine spreads
    this stage's partitions over (``None`` = no cap).
    """

    stateful = False

    def __init__(self, name: str, *, ordering: str, parallelism: int | None = None):
        if not name:
            raise ValueError("operator name must be non-empty")
        if ordering not in _CONTRACTS:
            raise ValueError(f"ordering must be one of {_CONTRACTS}, "
                             f"got {ordering!r}")
        if parallelism is not None and parallelism < 1:
            raise ValueError(f"parallelism hint must be >= 1, got {parallelism}")
        self.name = name
        self.ordering = ordering
        self.parallelism = parallelism
        self._plan: "ExecutionPlan | None" = None

    # plan wiring (clock + event hook access)
    def open(self, plan: "ExecutionPlan") -> None:
        self._plan = plan

    @property
    def clock(self) -> Clock:
        return self._plan.clock if self._plan is not None else ensure_clock(None)

    def process(self, elem: Element) -> list[Element]:
        raise NotImplementedError

    def flush(self) -> list[Element]:
        """Emit whatever the operator is still holding (drain path)."""
        return []

    def snapshot(self):
        return None

    def restore(self, state) -> None:
        pass

    def __repr__(self):
        return (f"{type(self).__name__}({self.name!r}, "
                f"ordering={self.ordering!r})")


class Map(Operator):
    """``fn(key, value) -> value | None`` (None filters the element)."""

    def __init__(self, name: str, fn: Callable[[str, Any], Any], *,
                 ordering: str = ORDERED, parallelism: int | None = None):
        super().__init__(name, ordering=ordering, parallelism=parallelism)
        self.fn = fn

    def process(self, elem: Element) -> list[Element]:
        out = self.fn(elem.key, elem.value)
        if out is None:
            return []
        return [Element(elem.key, out, elem.t_event)]


class Filter(Operator):
    """Keep elements where ``predicate(key, value)`` is truthy.  Stateless,
    hence ``unordered`` by default."""

    def __init__(self, name: str, predicate: Callable[[str, Any], bool], *,
                 ordering: str = UNORDERED, parallelism: int | None = None):
        super().__init__(name, ordering=ordering, parallelism=parallelism)
        self.predicate = predicate

    def process(self, elem: Element) -> list[Element]:
        return [elem] if self.predicate(elem.key, elem.value) else []


class KeyBy(Operator):
    """Re-key the stream: ``key_fn(key, value) -> new_key``.  Downstream
    keyed state (windows) buckets by the new key, so many producer streams
    can pool into one logical key (e.g. all ranks of a field)."""

    def __init__(self, name: str, key_fn: Callable[[str, Any], str], *,
                 parallelism: int | None = None):
        super().__init__(name, ordering=KEYED, parallelism=parallelism)
        self.key_fn = key_fn

    def process(self, elem: Element) -> list[Element]:
        return [Element(str(self.key_fn(elem.key, elem.value)), elem.value,
                        elem.t_event)]


class Aggregate(Operator):
    """Reduce a fired :class:`WindowPane` (or any iterable value) with
    ``fn(key, values) -> value``."""

    def __init__(self, name: str, fn: Callable[[str, list], Any], *,
                 ordering: str = KEYED, parallelism: int | None = None):
        super().__init__(name, ordering=ordering, parallelism=parallelism)
        self.fn = fn

    def process(self, elem: Element) -> list[Element]:
        v = elem.value
        values = list(v.values) if isinstance(v, WindowPane) else list(v)
        out = self.fn(elem.key, values)
        if out is None:
            return []
        return [Element(elem.key, out, elem.t_event)]


class BatchAggregate(Operator):
    """An :class:`Aggregate` that consumes **co-emitted elements in one
    call**: ``fn(items) -> outputs`` where ``items`` is a list of
    ``(key, values)`` pairs and ``outputs`` the same-length list of results
    (None filters that slot).  When an upstream window fires panes for many
    keys at the same watermark advance, the plan hands all of them to
    :meth:`process_many` at once — which is what lets a batched solver
    (e.g. ``analysis.dmd.batched_window_dmd``) collapse k per-pane device
    dispatches into one vmapped call.  ``process`` (single element) simply
    delegates, so the operator composes anywhere an Aggregate does.

    ``batch_stats()`` reports how much coalescing actually happened:
    ``batches`` (calls), ``items`` (elements across calls), ``max_batch``.
    """

    def __init__(self, name: str, fn: Callable[[list], list], *,
                 ordering: str = KEYED, parallelism: int | None = None):
        super().__init__(name, ordering=ordering, parallelism=parallelism)
        self.fn = fn
        self._stats_lock = threading.Lock()
        self.batches = 0
        self.items = 0
        self.max_batch = 0

    def process(self, elem: Element) -> list[Element]:
        return self.process_many([elem])

    def process_many(self, elems: list[Element]) -> list[Element]:
        if not elems:
            return []
        items = []
        for e in elems:
            v = e.value
            values = list(v.values) if isinstance(v, WindowPane) else list(v)
            items.append((e.key, values))
        with span("operators.batch_aggregate", items=len(items)):
            outs = self.fn(items)
        if len(outs) != len(elems):
            raise ValueError(
                f"BatchAggregate {self.name!r}: fn returned {len(outs)} "
                f"results for {len(elems)} items")
        with self._stats_lock:
            self.batches += 1
            self.items += len(elems)
            self.max_batch = max(self.max_batch, len(elems))
        return [Element(e.key, o, e.t_event)
                for e, o in zip(elems, outs) if o is not None]

    def batch_stats(self) -> dict:
        with self._stats_lock:
            return {"batches": self.batches, "items": self.items,
                    "max_batch": self.max_batch}


class Sink(Operator):
    """Terminal collection point: appends ``(key, value, t)`` with the
    session clock's now() — never wall time — and passes the element through
    (sinks may sit mid-chain)."""

    def __init__(self, name: str, *, ordering: str = UNORDERED):
        super().__init__(name, ordering=ordering)
        self._results: list[tuple[str, Any, float]] = []
        self._lock = threading.Lock()

    def process(self, elem: Element) -> list[Element]:
        t = self.clock.now()
        with self._lock:
            self._results.append((elem.key, elem.value, t))
        if self._plan is not None:
            self._plan.emit_event("sink", op=self.name, key=elem.key)
        return [elem]

    def results(self) -> list[tuple[str, Any, float]]:
        with self._lock:
            return list(self._results)

    def latest(self) -> dict[str, Any]:
        out: dict[str, Any] = {}
        for key, value, _t in self.results():
            out[key] = value
        return out

    # Sinks are checkpointed with the plan (exactly-once recovery restores
    # collected results alongside window panes) even though ``stateful``
    # stays False — that flag feeds the ordering contract, and a sink does
    # not need keyed ordering.
    def snapshot(self) -> dict:
        with self._lock:
            return {"results": list(self._results)}

    def restore(self, state: dict) -> None:
        with self._lock:
            self._results = list(state["results"])


_COUNTER_NAMES = ("records_in", "late_dropped", "assigned", "assignments",
                  "panes_fired", "fired_inserts")


class _Window(Operator):
    """Shared machinery for event-time windows: per-key panes under
    **striped** per-key locks, an operator-level watermark, loss ledger,
    and snapshot/restore.

    The watermark does NOT follow raw processing order.  Under plan-aware
    parallel dispatch, micro-batches of one stream run concurrently on many
    executors, so batch N+1 can be *processed* before batch N; if its
    (later) event times advanced the watermark directly, batch N's records
    would read as late and drop nondeterministically.  Instead, insertion
    (:meth:`ingest`, commutative) is decoupled from firing
    (:meth:`advance_watermark`), and the ExecutionPlan only advances the
    watermark along the per-stream **in-order commit frontier** — batch N+1
    contributes only after batches 0..N have finished inserting.  Producer
    event times are monotone per stream, so a record can never be late with
    respect to its own stream's frontier; records pooled across *different*
    streams (KeyBy) can still race each other's frontiers, which is what
    ``allowed_lateness_s`` is for.

    Locking: keys hash (stable crc32) onto ``stripes`` locks, so parallel
    keyed dispatch of different keys no longer serializes on one operator
    mutex — only same-stripe keys contend.  ``advance_watermark`` publishes
    the new watermark under ``_wmlock`` *before* popping each stripe under
    its stripe lock; because every pop and every insert for a stripe is
    totally ordered by that stripe's lock, an ingest that runs after the
    pop observes the already-raised watermark and classifies its element
    against it — a popped pane can never be re-created ("reborn") behind
    the watermark, and no pane fires twice.  Lock order everywhere is
    ``_wmlock`` then stripes ascending (snapshot/flush/accounting take all
    of them; the hot paths take exactly one)."""

    stateful = True

    def __init__(self, name: str, *, allowed_lateness_s: float = 0.0,
                 parallelism: int | None = None, stripes: int = 16):
        super().__init__(name, ordering=KEYED, parallelism=parallelism)
        if allowed_lateness_s < 0:
            raise ValueError("allowed_lateness_s must be >= 0")
        if stripes < 1:
            raise ValueError("stripes must be >= 1")
        self.allowed_lateness_s = float(allowed_lateness_s)
        self.n_stripes = int(stripes)
        self._wmlock = threading.Lock()
        self._watermark = float("-inf")
        self._stripe_locks = [threading.Lock() for _ in range(self.n_stripes)]
        # stripe -> key -> {(start, end): [values]}
        self._stripe_panes: list[dict[str, dict[tuple[float, float], list]]] \
            = [{} for _ in range(self.n_stripes)]
        # loss ledger, sharded per stripe (see accounting()); the public
        # ``records_in`` etc. read as summing properties below
        self._counters = [dict.fromkeys(_COUNTER_NAMES, 0)
                          for _ in range(self.n_stripes)]

    def _stripe_of(self, key: str) -> int:
        """Stable key -> stripe hash (crc32, not PYTHONHASHSEED-dependent
        ``hash``) so stripe layout — and with it any contention pattern —
        is deterministic across runs.  Same hash family as the shuffle
        stage's routing (:func:`repro.core.grouping.partition_of`), so a
        key's window state and its shuffled records agree on ownership."""
        return partition_of(key, self.n_stripes)

    def _counter_sum(self, name: str) -> int:
        return sum(c[name] for c in self._counters)

    records_in = property(lambda self: self._counter_sum("records_in"))
    late_dropped = property(lambda self: self._counter_sum("late_dropped"))
    assigned = property(lambda self: self._counter_sum("assigned"))
    assignments = property(lambda self: self._counter_sum("assignments"))
    panes_fired = property(lambda self: self._counter_sum("panes_fired"))
    fired_inserts = property(lambda self: self._counter_sum("fired_inserts"))

    # subclass: event time -> [(start, end), ...] pane memberships
    def _assign(self, t: float) -> list[tuple[float, float]]:
        raise NotImplementedError

    def ingest(self, elem: Element) -> None:
        """Insert-only half: bucket the element into its live panes (order-
        insensitive, safe to call from any executor at any time).  Takes
        only the element's stripe lock."""
        si = self._stripe_of(elem.key)
        ctr = self._counters[si]
        with self._stripe_locks[si]:
            ctr["records_in"] += 1
            # a pane is live until the watermark passes end + lateness;
            # the stripe lock orders this read against the stripe's pops
            wm = self._watermark
            live = [(s, e) for s, e in self._assign(elem.t_event)
                    if e + self.allowed_lateness_s > wm]
            if not live:
                ctr["late_dropped"] += 1
                if self._plan is not None:
                    self._plan._count_late()
                    self._plan.emit_event("late_drop", op=self.name,
                                          key=elem.key, t_event=elem.t_event)
                return
            ctr["assigned"] += 1
            panes = self._stripe_panes[si].setdefault(elem.key, {})
            for span in live:
                panes.setdefault(span, []).append(elem.value)
                ctr["assignments"] += 1

    def _pop_fired(self, si: int, threshold: float | None,
                   fired: list) -> None:
        """Pop every pane of stripe ``si`` past ``threshold`` (None = all)
        into ``fired``.  Caller holds the stripe lock."""
        ctr = self._counters[si]
        stripe = self._stripe_panes[si]
        for key in list(stripe):
            panes = stripe[key]
            for span in sorted(panes):
                if threshold is None or span[1] + self.allowed_lateness_s \
                        <= threshold:
                    values = panes.pop(span)
                    ctr["panes_fired"] += 1
                    ctr["fired_inserts"] += len(values)
                    fired.append((key, span[0], span[1], tuple(values)))

    def advance_watermark(self, t: float) -> list[Element]:
        """Firing half: move the watermark forward (monotone) and pop every
        pane it passed, emitted in (key, span) sorted order for determinism.
        Called by the plan with in-order frontier times only."""
        with self._wmlock:
            if t <= self._watermark:
                return []
            # publish BEFORE popping: any ingest that loses a stripe-lock
            # race to a pop will already see the raised watermark
            self._watermark = t
        fired: list[tuple[str, float, float, tuple]] = []
        for si in range(self.n_stripes):
            with self._stripe_locks[si]:
                self._pop_fired(si, t, fired)
        fired.sort()
        return [self._emit(k, s, e, v) for k, s, e, v in fired]

    def process(self, elem: Element) -> list[Element]:
        """In-order context (ordered suffix under the ticket, inline plan
        calls, flush-fed elements): insert and advance directly."""
        self.ingest(elem)
        return self.advance_watermark(elem.t_event)

    def _emit(self, key: str, start: float, end: float, values: tuple) -> Element:
        if self._plan is not None:
            self._plan.emit_event("window_fire", op=self.name, key=key,
                                  start=start, end=end, n=len(values))
        return Element(key, WindowPane(key, start, end, values), end)

    def flush(self) -> list[Element]:
        """Fire every open pane (drain path) in (key, span) sorted order
        so flush emission is deterministic."""
        fired: list[tuple[str, float, float, tuple]] = []
        with self._wmlock:
            for si in range(self.n_stripes):
                with self._stripe_locks[si]:
                    self._pop_fired(si, None, fired)
        fired.sort()
        return [self._emit(k, s, e, v) for k, s, e, v in fired]

    def _merged_panes(self) -> dict:
        """key -> {span: values} across stripes (callers hold all locks)."""
        merged: dict[str, dict[tuple[float, float], list]] = {}
        for stripe in self._stripe_panes:
            for key, panes in stripe.items():
                if panes:
                    merged[key] = panes
        return merged

    # ---- keyed-state migration hooks ------------------------------------
    def snapshot(self) -> dict:
        """Deep-copied keyed state + ledger — enough to rebuild the operator
        mid-window on another engine/session (elasticity migration).  The
        format is stripe-agnostic (one merged panes dict), so snapshots
        move between operators with different stripe counts."""
        with self._wmlock:
            for lk in self._stripe_locks:
                lk.acquire()
            try:
                return copy.deepcopy({
                    "watermark": self._watermark,
                    "panes": self._merged_panes(),
                    "counters": {n: self._counter_sum(n)
                                 for n in _COUNTER_NAMES}})
            finally:
                for lk in reversed(self._stripe_locks):
                    lk.release()

    def restore(self, state: dict) -> None:
        with self._wmlock:
            for lk in self._stripe_locks:
                lk.acquire()
            try:
                snap = copy.deepcopy(state)
                self._watermark = snap["watermark"]
                self._stripe_panes = [{} for _ in range(self.n_stripes)]
                for key, panes in snap["panes"].items():
                    self._stripe_panes[self._stripe_of(key)][key] = panes
                # ledger totals land on stripe 0 (only sums are observable)
                self._counters = [dict.fromkeys(_COUNTER_NAMES, 0)
                                  for _ in range(self.n_stripes)]
                self._counters[0].update(snap["counters"])
            finally:
                for lk in reversed(self._stripe_locks):
                    lk.release()

    def accounting(self) -> dict:
        """The loss ledger.  ``closed`` is the record-conservation identity:
        every record that entered either joined >= 1 pane or was counted as
        a late drop, and every pane insertion is either fired or still open."""
        with self._wmlock:
            for lk in self._stripe_locks:
                lk.acquire()
            try:
                open_inserts = sum(
                    len(v) for stripe in self._stripe_panes
                    for panes in stripe.values() for v in panes.values())
                open_panes = sum(len(panes) for stripe in self._stripe_panes
                                 for panes in stripe.values())
                c = {n: self._counter_sum(n) for n in _COUNTER_NAMES}
            finally:
                for lk in reversed(self._stripe_locks):
                    lk.release()
        return {**c,
                "open_inserts": open_inserts,
                "open_panes": open_panes,
                "closed": (c["records_in"]
                           == c["assigned"] + c["late_dropped"]
                           and c["assignments"]
                           == c["fired_inserts"] + open_inserts)}


class TumblingWindow(_Window):
    """Fixed event-time buckets of ``size_s``: record at t falls in exactly
    ``[floor(t/size)*size, +size)``."""

    def __init__(self, name: str, size_s: float, **kw):
        if size_s <= 0:
            raise ValueError("size_s must be > 0")
        super().__init__(name, **kw)
        self.size_s = float(size_s)

    def _assign(self, t: float) -> list[tuple[float, float]]:
        b = int(t // self.size_s)
        return [(b * self.size_s, (b + 1) * self.size_s)]


class SlidingWindow(_Window):
    """Overlapping panes of ``size_s`` every ``slide_s``: record at t joins
    every pane ``[k*slide, k*slide + size)`` containing t."""

    def __init__(self, name: str, size_s: float, slide_s: float, **kw):
        if size_s <= 0 or slide_s <= 0:
            raise ValueError("size_s and slide_s must be > 0")
        if slide_s > size_s:
            raise ValueError("slide_s must be <= size_s (gaps would drop "
                             "records; use a TumblingWindow instead)")
        super().__init__(name, **kw)
        self.size_s = float(size_s)
        self.slide_s = float(slide_s)

    def _assign(self, t: float) -> list[tuple[float, float]]:
        k_max = int(t // self.slide_s)
        k_min = int((t - self.size_s) // self.slide_s) + 1
        return [(k * self.slide_s, k * self.slide_s + self.size_s)
                for k in range(k_min, k_max + 1)]


# ---------------------------------------------------------------------------
# The compiled plan
# ---------------------------------------------------------------------------

class _PreOut:
    """Result of the order-insensitive prefix: elements parked at the
    pre/post phase boundary, plus the partition's primary value."""

    __slots__ = ("boundary", "primary")

    def __init__(self, boundary: list, primary):
        self.boundary = boundary
        self.primary = primary


class ExecutionPlan:
    """An operator graph lowered for the ``StreamEngine``.

    The compiler splits stages into two phases:

    * **pre**  — the maximal prefix where every stage is order-insensitive
      (``unordered``/``keyed``) and has no ``ordered`` ancestor.  The engine
      runs this *without* the per-stream ordering ticket, so micro-batches
      of one stream proceed concurrently on many executors.
    * **post** — everything from the first ``ordered`` stage on, run under
      the ticket in exact per-stream dispatch order.

    ``contract`` summarizes the plan ("ordered" if any post stage exists,
    else "keyed" if any keyed/stateful stage, else "unordered");
    ``parallel_dispatch`` tells the engine to spread a stream's partitions
    over executors instead of sticky-assigning them; ``parallelism`` is the
    tightest pre-stage hint (None = no cap).

    ``granularity`` selects what a source element is: ``"record"`` explodes
    a micro-batch into one element per ``StreamRecord`` (event time =
    ``t_generated``); ``"batch"`` feeds the whole records list as one
    element, and the source stage's output becomes the partition's primary
    value (see :meth:`_run_batch_source`).
    """

    def __init__(self, ops: dict[str, Operator], downstream: dict[str, list[str]],
                 source: str, *, clock: Clock | None = None,
                 granularity: str = "record"):
        if source not in ops:
            raise ValueError(f"unknown source {source!r}")
        if granularity not in ("record", "batch"):
            raise ValueError(f"granularity must be 'record' or 'batch', "
                             f"got {granularity!r}")
        for name, downs in downstream.items():
            if name not in ops:
                raise ValueError(f"unknown stage {name!r} in downstream map")
            for d in downs:
                if d not in ops:
                    raise ValueError(f"unknown downstream stage {d!r}")
        self.ops = dict(ops)
        self.down = {n: list(downstream.get(n, [])) for n in ops}
        self.source = source
        self.clock = ensure_clock(clock)
        self.granularity = granularity
        self.on_event: Callable | None = None   # (kind, **detail) trace hook
        self._topo = self._toposort()
        self._pre, self._post = self._split_phases()
        # in-order commit frontier (see _Window docstring): per source
        # stream, batches contribute their max event time to the watermark
        # only once every earlier-seq batch of that stream has finished
        # inserting; the operator watermark is the max over stream frontiers
        self._flock = threading.Lock()
        self._frontier: dict[str, dict] = {}
        self._committed_max = float("-inf")
        # keyed shuffle (set by the engine via enable_shuffle()): when
        # active, micro-batches are key partitions, not producer streams,
        # and source elements carry each record's own stream key
        self._shuffle_n: int | None = None
        # late drops of the batch this thread inserts (run_pre's span)
        self._batch = threading.local()
        for op in self.ops.values():
            op.open(self)

    # ---- compilation ----------------------------------------------------
    def _toposort(self) -> list[str]:
        state: dict[str, int] = {}
        order: list[str] = []

        def visit(n: str, path: frozenset):
            if state.get(n) == 2:
                return
            if n in path:
                raise ValueError(f"cycle through {n!r}")
            for d in self.down[n]:
                visit(d, path | {n})
            state[n] = 2
            order.append(n)

        visit(self.source, frozenset())
        unreachable = set(self.ops) - set(order)
        if unreachable:
            raise ValueError(
                f"stages unreachable from source {self.source!r}: "
                f"{sorted(unreachable)}")
        order.reverse()
        return order

    def _split_phases(self) -> tuple[list[str], list[str]]:
        parents: dict[str, list[str]] = {n: [] for n in self.ops}
        for n, downs in self.down.items():
            for d in downs:
                parents[d].append(n)
        pre: list[str] = []
        pre_set: set[str] = set()
        for n in self._topo:                     # parents precede children
            op = self.ops[n]
            if op.ordering != ORDERED and all(p in pre_set for p in parents[n]):
                pre.append(n)
                pre_set.add(n)
        post = [n for n in self._topo if n not in pre_set]
        return pre, post

    @property
    def pre_stages(self) -> list[str]:
        return list(self._pre)

    @property
    def post_stages(self) -> list[str]:
        return list(self._post)

    @property
    def contract(self) -> str:
        if self._post:
            return ORDERED
        if any(op.stateful or op.ordering == KEYED for op in self.ops.values()):
            return KEYED
        return UNORDERED

    @property
    def parallel_dispatch(self) -> bool:
        """True when the engine should spread one stream's partitions across
        executors (there is order-insensitive work to parallelize)."""
        return bool(self._pre)

    @property
    def parallelism(self) -> int | None:
        hints = [self.ops[n].parallelism for n in self._pre
                 if self.ops[n].parallelism is not None]
        return min(hints) if hints else None

    @property
    def shuffle_op(self) -> "KeyBy | None":
        """The shuffle edge this plan compiles to: a record-granularity
        graph whose SOURCE is a :class:`KeyBy` re-partitions records across
        streams — the engine may dispatch by the KeyBy's output key instead
        of by producer stream.  None when the plan has no shuffle edge."""
        op = self.ops[self.source]
        if self.granularity == "record" and isinstance(op, KeyBy):
            return op
        return None

    @property
    def shuffled(self) -> bool:
        return self._shuffle_n is not None

    @property
    def shuffle_partitions(self) -> int | None:
        return self._shuffle_n

    def enable_shuffle(self, n_partitions: int) -> None:
        """Switch the plan to keyed-shuffle dispatch over ``n_partitions``
        partitions.  Engine-called at attach time; requires a shuffle edge."""
        if self.shuffle_op is None:
            raise ValueError(
                "plan has no shuffle edge (source must be a KeyBy on a "
                "record-granularity graph)")
        if n_partitions < 1:
            raise ValueError(f"need >= 1 partitions, got {n_partitions}")
        self._shuffle_n = int(n_partitions)

    def shuffle_partition(self, record) -> int:
        """Partition owning ``record`` under the shuffle edge: the KeyBy's
        output key hashed with the shared stable :func:`partition_of` —
        crc32, same family as the window stripe hash, so co-keyed records
        from different producer streams always land together."""
        kb = self.shuffle_op
        key = str(kb.key_fn(record.key(), record))
        return partition_of(key, self._shuffle_n)

    def bind_clock(self, clock: Clock | None) -> None:
        """Adopt the Session's clock (operators read it through the plan, so
        a rebind covers every sink/window timestamp)."""
        self.clock = ensure_clock(clock)

    def _count_late(self) -> None:
        self._batch.late = getattr(self._batch, "late", 0) + 1

    def emit_event(self, kind: str, **detail) -> None:
        cb = self.on_event
        if cb is not None:
            cb(kind, **detail)

    # ---- execution -------------------------------------------------------
    def _source_elements(self, key: str, records: list) -> list[Element]:
        if self.granularity == "batch":
            tmin = min((r.t_generated for r in records),
                       default=self.clock.now())
            return [Element(key, records, tmin)]
        if self._shuffle_n is not None:
            # shuffled micro-batches pool records of many producer streams
            # under one partition key; each element keeps its own record's
            # stream key so the source KeyBy re-keys exactly as it would
            # have under producer-partitioned dispatch
            return [Element(r.key(), r, r.t_generated) for r in records]
        return [Element(key, r, r.t_generated) for r in records]

    def _feed(self, name: str, elem: Element, allowed: set | None,
              boundary: list | None, defer_fire: bool = False) -> None:
        """DFS one element through the graph.  Stages outside ``allowed``
        park the element at the phase boundary instead of running.  With
        ``defer_fire``, windows only ingest — firing waits for the in-order
        frontier commit (:meth:`run_pre`)."""
        if allowed is not None and name not in allowed:
            boundary.append((name, elem))
            return
        op = self.ops[name]
        if defer_fire and isinstance(op, _Window):
            op.ingest(elem)
            return
        self._fan_out(name, op.process(elem), allowed, boundary, defer_fire)

    def _fan_out(self, name: str, outs: list, allowed: set | None,
                 boundary: list | None, defer_fire: bool = False) -> None:
        """Feed one stage's output elements downstream.  When a stage emits
        several elements at once (a window firing panes across keys) and a
        downstream stage is a :class:`BatchAggregate`, all of them go down
        in ONE ``process_many`` call — the multi-key coalescing hook.  For
        every other downstream, elements flow one at a time in emission
        order, exactly as the plain DFS did."""
        if not outs:
            return
        for d in self.down[name]:
            dop = self.ops[d]
            if (len(outs) > 1 and isinstance(dop, BatchAggregate)
                    and (allowed is None or d in allowed)):
                self._fan_out(d, dop.process_many(outs), allowed, boundary,
                              defer_fire)
            else:
                for out in outs:
                    self._feed(d, out, allowed, boundary, defer_fire)

    def _commit(self, stream: str, seq: int | None, batch_max: float) -> float:
        """Record one batch's max event time on its stream's frontier.
        ``seq=None`` (inline callers) commits immediately; otherwise the
        frontier only advances over the contiguous seq prefix, so an
        out-of-order-processed batch never pushes the watermark past a
        still-inserting earlier batch.  A seq below the frontier (dispatched
        before this plan was attached mid-run) folds in directly.  Returns
        the new global watermark."""
        with self._flock:
            st = self._frontier.setdefault(
                stream, {"next": 0, "pending": {},
                         "committed": float("-inf")})
            if seq is None or seq < st["next"]:
                st["committed"] = max(st["committed"], batch_max)
            else:
                st["pending"][seq] = batch_max
                while st["next"] in st["pending"]:
                    st["committed"] = max(st["committed"],
                                          st["pending"].pop(st["next"]))
                    st["next"] += 1
            if st["committed"] > self._committed_max:
                self._committed_max = st["committed"]
            return self._committed_max

    def seed_frontier(self, stream_next_seq: dict[str, int]) -> None:
        """Align the frontier with an engine whose per-stream seq counters
        are already past zero (a plan attached mid-run): the next expected
        seq per stream is the engine's, and anything older folds straight
        into the committed watermark (see :meth:`_commit`)."""
        with self._flock:
            for stream, nxt in stream_next_seq.items():
                self._frontier.setdefault(
                    stream, {"next": int(nxt), "pending": {},
                             "committed": float("-inf")})

    def run_pre(self, key: str, records: list,
                seq: int | None = None) -> _PreOut:
        """The order-insensitive prefix (call WITHOUT the ordering ticket).
        Window insertion happens inline; window *firing* happens here too,
        but only up to the in-order frontier watermark.  The seq is
        committed even when a stage raises — a poisoned batch must not
        stall its stream's watermark forever."""
        boundary: list = []
        allowed = set(self._pre)
        elems = self._source_elements(key, records)
        primary = self._primary(key, records)
        with span("operators.insert", records=len(records)) as sp:
            self._batch.late = 0
            try:
                for elem in elems:
                    if self.granularity == "batch" and self.source in allowed:
                        primary = self._run_batch_source(
                            elem, allowed, boundary, defer_fire=True)
                    else:
                        self._feed(self.source, elem, allowed, boundary,
                                   defer_fire=True)
            finally:
                w = self._commit(
                    key, seq,
                    max((e.t_event for e in elems), default=float("-inf")))
            sp.set_metadata(late=self._batch.late)
        with span("operators.fire") as sp:
            panes = 0
            for name in self._pre:
                op = self.ops[name]
                if isinstance(op, _Window):
                    fired = op.advance_watermark(w)
                    panes += len(fired)
                    self._fan_out(name, fired, allowed, boundary,
                                  defer_fire=True)
            sp.set_metadata(panes=panes)
        return _PreOut(boundary, primary)

    def run_post(self, key: str, pre_out: _PreOut | None, records: list):
        """The ordered suffix (call UNDER the ordering ticket).  With
        ``pre_out=None`` (no prefix ran) the whole graph runs here."""
        if pre_out is None:
            boundary = [(self.source, e)
                        for e in self._source_elements(key, records)]
        else:
            boundary = pre_out.boundary
        primary = self._primary(key, records)
        for name, elem in boundary:
            if self.granularity == "batch" and name == self.source:
                primary = self._run_batch_source(elem, None, None)
            else:
                self._feed(name, elem, None, None)
        return primary

    def _run_batch_source(self, elem: Element, allowed: set | None,
                          boundary: list | None, defer_fire: bool = False):
        """Batch-granularity source, capturing its first output's value as
        the primary value (None when it emits nothing) — in whichever phase
        the source landed."""
        op = self.ops[self.source]
        if defer_fire and isinstance(op, _Window):
            op.ingest(elem)
            return None              # a deferred window has no output yet
        outs = op.process(elem)
        for out in outs:
            for d in self.down[self.source]:
                self._feed(d, out, allowed, boundary, defer_fire)
        return outs[0].value if outs else None

    def _primary(self, key: str, records: list):
        """The engine ``Result.value`` for this partition: the record count
        (a batch-granularity source's output overrides it, see
        :meth:`_run_batch_source`)."""
        return len(records)

    def __call__(self, key: str, records: list):
        """Whole graph inline (both phases), as one executor would run it
        with no concurrency — for single-threaded callers and tests."""
        if self._pre:
            pre_out = self.run_pre(key, records)
            if not self._post:
                return pre_out.primary
            return self.run_post(key, pre_out, records)
        return self.run_post(key, None, records)

    def flush(self) -> None:
        """Drain path (single-threaded, after executors stop): fire every
        open window pane through the rest of the graph, topo order.  Like
        the watermark path, co-fired panes coalesce into a downstream
        :class:`BatchAggregate`."""
        for name in self._topo:
            self._fan_out(name, self.ops[name].flush(), None, None)

    # ---- observability / state migration --------------------------------
    def sinks(self) -> list[str]:
        return [n for n, op in self.ops.items() if isinstance(op, Sink)]

    def results(self, name: str) -> list[tuple[str, Any, float]]:
        op = self.ops.get(name)
        if not isinstance(op, Sink):
            raise ValueError(f"{name!r} is not a Sink (sinks: {self.sinks()})")
        return op.results()

    def latest(self, name: str) -> dict[str, Any]:
        op = self.ops.get(name)
        if not isinstance(op, Sink):
            raise ValueError(f"{name!r} is not a Sink (sinks: {self.sinks()})")
        return op.latest()

    def snapshot(self) -> dict:
        """Keyed state of every stateful operator (windows), deep-copied,
        plus every sink's collected results (so an exactly-once restore
        resumes with pre-crash outputs intact)."""
        return {n: op.snapshot() for n, op in self.ops.items()
                if op.stateful or isinstance(op, Sink)}

    def restore(self, state: dict) -> None:
        for n, s in state.items():
            if n not in self.ops:
                raise ValueError(f"snapshot has unknown operator {n!r}")
            self.ops[n].restore(s)

    def frontier_snapshot(self) -> dict:
        """The per-stream in-order commit frontier (see :meth:`_commit`) —
        captured by ``Session.checkpoint()`` so a restored run resumes
        firing windows from the same watermark instead of re-waiting for
        each stream's seq 0."""
        with self._flock:
            return {"streams": {k: {"next": st["next"],
                                    "pending": dict(st["pending"]),
                                    "committed": st["committed"]}
                                for k, st in self._frontier.items()},
                    "committed_max": self._committed_max}

    def restore_frontier(self, state: dict) -> None:
        with self._flock:
            self._frontier = {k: {"next": int(st["next"]),
                                  "pending": dict(st["pending"]),
                                  "committed": st["committed"]}
                              for k, st in state["streams"].items()}
            self._committed_max = state["committed_max"]

    def accounting(self) -> dict:
        """Per-window loss ledgers plus the global ``closed`` flag."""
        per_op = {n: op.accounting() for n, op in self.ops.items()
                  if isinstance(op, _Window)}
        return {"windows": per_op,
                "closed": all(a["closed"] for a in per_op.values())}

    def batch_stats(self) -> dict:
        """Coalescing scoreboard: per-BatchAggregate call/item/max-batch
        counts (how many device dispatches the multi-key fast path saved)."""
        return {n: op.batch_stats() for n, op in self.ops.items()
                if isinstance(op, BatchAggregate)}

    def __repr__(self):
        return (f"ExecutionPlan(contract={self.contract!r}, "
                f"pre={self._pre}, post={self._post}, "
                f"granularity={self.granularity!r})")


# ---------------------------------------------------------------------------
# Builder
# ---------------------------------------------------------------------------

class OperatorPipeline:
    """Fluent builder for operator graphs:

        pipe = (OperatorPipeline()
                .key_by("by_field", lambda k, r: k.split("/")[0])
                .tumbling_window("win", size_s=1.0)
                .aggregate("dmd", window_dmd)
                .map("alert", alert_fn, ordering="ordered")
                .sink("alerts"))

    Each verb appends downstream of the cursor and advances it; ``after=``
    attaches anywhere (fan-out), ``at()`` repositions the cursor.  The graph
    is acyclic by construction; ``compile()`` validates and returns the
    :class:`ExecutionPlan`.

    ``granularity="record"`` (default) feeds the source one element per
    ``StreamRecord``; ``"batch"`` feeds the whole micro-batch records list
    as one element — for stages that are inherently per-batch (e.g. a
    stateful StreamingDMD update).

    Note the compiled plan owns the *live* operator instances: compiling
    the same builder twice yields plans SHARING sink/window state.  Build a
    fresh pipeline per Session (scenario factories do exactly this).
    """

    def __init__(self, granularity: str = "record"):
        if granularity not in ("record", "batch"):
            raise ValueError(f"granularity must be 'record' or 'batch', "
                             f"got {granularity!r}")
        self.granularity = granularity
        self._ops: dict[str, Operator] = {}
        self._down: dict[str, list[str]] = {}
        self._source: str | None = None
        self._cursor: str | None = None

    def add(self, op: Operator, *, after: str | None = None) -> "OperatorPipeline":
        """Attach ``op`` downstream of ``after`` (default: the cursor) and
        move the cursor to it.  The first operator becomes the source."""
        if op.name in self._ops:
            raise ValueError(f"duplicate operator {op.name!r}")
        if self._source is None:
            if after is not None:
                raise ValueError("the first operator is the source; it has "
                                 "no upstream to attach after")
        else:
            parent = self._cursor if after is None else after
            if parent not in self._ops:
                raise ValueError(f"unknown operator {parent!r}")
            self._down[parent].append(op.name)
        self._ops[op.name] = op
        self._down[op.name] = []
        if self._source is None:
            self._source = op.name
        self._cursor = op.name
        return self

    def at(self, name: str) -> "OperatorPipeline":
        """Move the cursor to an existing operator (fan-out topologies)."""
        if name not in self._ops:
            raise ValueError(f"unknown operator {name!r}")
        self._cursor = name
        return self

    # ---- typed conveniences ---------------------------------------------
    def map(self, name: str, fn, *, ordering: str = ORDERED,
            parallelism: int | None = None, after: str | None = None):
        return self.add(Map(name, fn, ordering=ordering,
                            parallelism=parallelism), after=after)

    def filter(self, name: str, predicate, *, ordering: str = UNORDERED,
               parallelism: int | None = None, after: str | None = None):
        return self.add(Filter(name, predicate, ordering=ordering,
                               parallelism=parallelism), after=after)

    def key_by(self, name: str, key_fn, *, after: str | None = None):
        return self.add(KeyBy(name, key_fn), after=after)

    def tumbling_window(self, name: str, size_s: float, *,
                        allowed_lateness_s: float = 0.0, stripes: int = 16,
                        after: str | None = None):
        return self.add(TumblingWindow(name, size_s,
                                       allowed_lateness_s=allowed_lateness_s,
                                       stripes=stripes),
                        after=after)

    def sliding_window(self, name: str, size_s: float, slide_s: float, *,
                       allowed_lateness_s: float = 0.0, stripes: int = 16,
                       after: str | None = None):
        return self.add(SlidingWindow(name, size_s, slide_s,
                                      allowed_lateness_s=allowed_lateness_s,
                                      stripes=stripes),
                        after=after)

    def aggregate(self, name: str, fn, *, ordering: str = KEYED,
                  after: str | None = None):
        return self.add(Aggregate(name, fn, ordering=ordering), after=after)

    def batch_aggregate(self, name: str, fn, *, ordering: str = KEYED,
                        after: str | None = None):
        return self.add(BatchAggregate(name, fn, ordering=ordering),
                        after=after)

    def sink(self, name: str, *, ordering: str = UNORDERED,
             after: str | None = None):
        return self.add(Sink(name, ordering=ordering), after=after)

    # ---- introspection / compilation ------------------------------------
    def edges(self) -> list[tuple[str, str]]:
        return [(p, c) for p, downs in self._down.items() for c in downs]

    def compile(self, clock: Clock | None = None,
                granularity: str | None = None) -> ExecutionPlan:
        if self._source is None:
            raise ValueError("empty pipeline: add at least one operator")
        return ExecutionPlan(self._ops, self._down, self._source, clock=clock,
                             granularity=granularity or self.granularity)

