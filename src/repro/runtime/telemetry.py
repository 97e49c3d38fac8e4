"""Unified telemetry bus — the control plane's sensor layer.

Every layer of the HPC→Cloud pipeline already kept private counters (the
broker's per-sender stats, the endpoints' ingest totals, the engine's
results); :class:`TelemetryBus` samples them into one immutable
:class:`TelemetrySnapshot` per tick:

  * per-group broker state — live queue depth, drop/error *rates* (computed
    as deltas between consecutive samples), wire batch cap,
  * per-endpoint ingest rate and pending backlog,
  * per-executor queue depth / steal counts,
  * rolling p50/p99 generation→analysis latency (the paper's §4.3 QoS
    metric, over the engine's windowed recent results).

Snapshots fan out to subscribers (the :class:`repro.runtime.controller.
ElasticController` closes the loop on them) and accumulate in a bounded
history so policies can reason about trends, not just instants.  The bus
holds weak expectations of its sources — anything exposing
``group_telemetry()`` / ``telemetry()`` / ``metrics()`` works — so it stays
import-free of broker/engine internals.

Where the counters say how much work a layer did, :func:`span` says when:
each layer marks its units of work (a frame, a micro-batch, a window
solve; never a record) as ``repro.<layer>.<what>`` host spans of the JAX
profiler, with the unit's counts as the span's arguments, on the same clock
as the device's operations in a profiler trace.
"""
from __future__ import annotations

import math
import threading
from collections import deque
from dataclasses import dataclass

from repro.runtime.clock import Clock, ensure_clock


def span(name: str, **args):
    """A ``repro.<name>`` host span around one unit of work, carrying
    ``args`` (counts, identifiers) as its arguments; a context manager
    whose ``set_metadata(**args)`` adds arguments known only at its end.

    It records only while a ``jax.profiler`` trace runs, and costs about a
    microsecond otherwise.  It never waits for the device: where device
    work ends shows in the same trace."""
    # imported here so that the broker, endpoint and engine modules stay
    # importable without loading JAX
    from jax.profiler import TraceAnnotation
    return TraceAnnotation(f"repro.{name}", **args)


@dataclass(frozen=True)
class GroupTelemetry:
    """One broker group sender, sampled."""

    group: int
    queue_depth: int
    queue_capacity: int
    batch_cap: int
    primary: int
    written: int
    sent: int
    dropped: int
    send_errors: int
    drop_rate: float = 0.0        # records/s since previous sample
    error_rate: float = 0.0       # send errors/s since previous sample
    send_rate: float = 0.0        # delivered records/s since previous sample


@dataclass(frozen=True)
class ShardTelemetry:
    """One broker shard (group-owning slice of the sharded fan-in),
    sampled: how much backlog and traffic its groups carry together."""

    shard: int
    groups: int
    queue_depth: int
    written: int
    sent: int
    dropped: int
    send_errors: int
    rerouted: int
    endpoints: int
    send_rate: float = 0.0        # delivered records/s since previous sample


@dataclass(frozen=True)
class EndpointTelemetry:
    name: str
    healthy: bool
    pending: int                  # undrained records buffered
    records_in: int
    ingest_rate_rps: float


@dataclass(frozen=True)
class ExecutorTelemetry:
    idx: int
    alive: bool
    queue_depth: int              # micro-batches waiting
    queued_records: int           # records inside those micro-batches
    processed: int
    stolen: int


@dataclass(frozen=True)
class TenantTelemetry:
    """One tenant's QoS rollup: the registry's declared contract plus the
    broker's loss-ledger counters and the engine's per-tenant latency."""

    name: str
    priority: int = 0
    p99_target_s: float | None = None
    weight: float = 1.0
    admitted: int = 0
    sent: int = 0
    dropped: int = 0
    evicted: int = 0
    quota_rejected: int = 0
    backlog: int = 0              # queued + parked records in the broker
    parked: int = 0               # currently parked (subset of backlog)
    analyzed: int = 0
    latency_p50: float = math.nan
    latency_p99: float = math.nan
    latency_n: int = 0            # samples in the rolling window


@dataclass(frozen=True)
class TelemetrySnapshot:
    """One consistent-enough control-plane sample across all layers."""

    t: float
    groups: tuple[GroupTelemetry, ...] = ()
    shards: tuple[ShardTelemetry, ...] = ()
    endpoints: tuple[EndpointTelemetry, ...] = ()
    executors: tuple[ExecutorTelemetry, ...] = ()
    held_records: int = 0         # engine hold-buffer backlog
    alive_executors: int = 0
    queued_partitions: int = 0    # micro-batches waiting on executors
    latency_p50: float = math.nan
    latency_p99: float = math.nan
    latency_n: int = 0            # samples in the rolling window
    executor_seconds: float = 0.0
    tenants: tuple[TenantTelemetry, ...] = ()   # QoS plane rollups (by name)

    @property
    def backlog(self) -> int:
        """Total records not yet analyzed anywhere in the pipeline: broker
        queues + endpoint buffers + engine hold + records queued on
        executors — the load signal scale-up policies watch.  (Executor
        queues matter most: when analysis saturates, dispatch keeps up and
        the pile-up happens there.)"""
        return (sum(g.queue_depth for g in self.groups)
                + sum(e.pending for e in self.endpoints)
                + self.held_records
                + sum(x.queued_records for x in self.executors if x.alive))


@dataclass
class _GroupPrev:
    t: float = 0.0
    dropped: int = 0
    send_errors: int = 0
    sent: int = 0


@dataclass
class _ShardPrev:
    t: float = 0.0
    sent: int = 0


class TelemetryBus:
    """Samples broker + endpoints + engine into TelemetrySnapshots, keeps a
    bounded history, and fans snapshots out to subscribers.

    All sources are optional and attachable after construction (the Session
    creates its engine lazily): ``attach_engine`` late-binds the consumer
    side.  ``sample()`` is safe from any thread; subscriber callbacks run on
    the sampling thread and must not block.
    """

    def __init__(self, *, broker=None, endpoints=(), engine=None,
                 history: int = 256, clock: Clock | None = None,
                 tenants=None):
        self.broker = broker
        self.endpoints = list(endpoints)
        self.engine = engine
        self.tenants = tenants      # TenantRegistry (duck-typed), or None
        self.clock = ensure_clock(clock)
        self.history: deque[TelemetrySnapshot] = deque(maxlen=history)
        self._subs: list = []
        self._prev: dict[int, _GroupPrev] = {}
        self._shard_prev: dict[int, _ShardPrev] = {}
        self._lock = threading.Lock()

    def attach_engine(self, engine) -> None:
        self.engine = engine

    def subscribe(self, cb) -> None:
        """cb(snapshot) on every sample()."""
        self._subs.append(cb)

    def last(self) -> TelemetrySnapshot | None:
        with self._lock:
            return self.history[-1] if self.history else None

    # ---- sampling --------------------------------------------------------
    def _sample_groups(self, now: float) -> tuple[GroupTelemetry, ...]:
        if self.broker is None:
            return ()
        out = []
        for row in self.broker.group_telemetry():
            g = row["group"]
            prev = self._prev.get(g)
            dt = (now - prev.t) if prev else 0.0
            if prev and dt > 1e-6:
                drop_rate = (row["dropped"] - prev.dropped) / dt
                error_rate = (row["send_errors"] - prev.send_errors) / dt
                send_rate = (row["sent"] - prev.sent) / dt
            else:
                drop_rate = error_rate = send_rate = 0.0
            self._prev[g] = _GroupPrev(t=now, dropped=row["dropped"],
                                       send_errors=row["send_errors"],
                                       sent=row["sent"])
            out.append(GroupTelemetry(
                group=g, queue_depth=row["queue_depth"],
                queue_capacity=row["queue_capacity"],
                batch_cap=row["batch_cap"], primary=row["primary"],
                written=row["written"], sent=row["sent"],
                dropped=row["dropped"], send_errors=row["send_errors"],
                drop_rate=drop_rate, error_rate=error_rate,
                send_rate=send_rate))
        return tuple(out)

    def _sample_shards(self, now: float) -> tuple[ShardTelemetry, ...]:
        """Per-shard rollups from a sharded broker (``shard_telemetry()``);
        () for brokers without shards — policies treat that as 'no shard
        signal' and fall back to fleet-level thresholds."""
        shard_fn = getattr(self.broker, "shard_telemetry", None)
        if shard_fn is None:
            return ()
        out = []
        for row in shard_fn():
            sid = row["shard"]
            prev = self._shard_prev.get(sid)
            dt = (now - prev.t) if prev else 0.0
            send_rate = (row["sent"] - prev.sent) / dt \
                if prev and dt > 1e-6 else 0.0
            self._shard_prev[sid] = _ShardPrev(t=now, sent=row["sent"])
            out.append(ShardTelemetry(
                shard=sid, groups=row["groups"],
                queue_depth=row["queue_depth"], written=row["written"],
                sent=row["sent"], dropped=row["dropped"],
                send_errors=row["send_errors"], rerouted=row["rerouted"],
                endpoints=row["endpoints"], send_rate=send_rate))
        return tuple(out)

    def _sample_endpoints(self) -> tuple[EndpointTelemetry, ...]:
        out = []
        for ep in self.endpoints:
            t = ep.telemetry()
            out.append(EndpointTelemetry(
                name=t["name"], healthy=t["healthy"], pending=t["pending"],
                records_in=t["records_in"],
                ingest_rate_rps=t["ingest_rate_rps"]))
        return tuple(out)

    def _sample_tenants(self, engine_metrics: dict | None) \
            -> tuple[TenantTelemetry, ...]:
        """Join the broker's per-tenant loss ledger with the engine's
        per-tenant latency under the registry's declared contracts; ()
        without a registry (single-tenant deployments pay nothing)."""
        if self.tenants is None:
            return ()
        broker_rows = {}
        tenant_fn = getattr(self.broker, "tenant_telemetry", None)
        if tenant_fn is not None:
            broker_rows = tenant_fn()
        eng_rows = (engine_metrics or {}).get("tenants", {})
        out = []
        for name in self.tenants.names():
            spec = self.tenants.spec(name)
            b = broker_rows.get(name, {})
            e = eng_rows.get(name, {})
            out.append(TenantTelemetry(
                name=name, priority=spec.priority,
                p99_target_s=spec.p99_target_s, weight=spec.weight,
                admitted=b.get("admitted", 0), sent=b.get("sent", 0),
                dropped=b.get("dropped", 0), evicted=b.get("evicted", 0),
                quota_rejected=b.get("quota_rejected", 0),
                backlog=b.get("backlog", 0), parked=b.get("parked", 0),
                analyzed=e.get("analyzed", 0),
                latency_p50=e.get("latency_p50", math.nan),
                latency_p99=e.get("latency_p99", math.nan),
                latency_n=e.get("latency_window_n", 0)))
        return tuple(out)

    def sample(self) -> TelemetrySnapshot:
        now = self.clock.now()
        with self._lock:
            groups = self._sample_groups(now)
            shards = self._sample_shards(now)
        endpoints = self._sample_endpoints()
        executors: tuple[ExecutorTelemetry, ...] = ()
        held = queued = alive = lat_n = 0
        p50 = p99 = math.nan
        exec_secs = 0.0
        m = None
        if self.engine is not None:
            m = self.engine.metrics()
            executors = tuple(ExecutorTelemetry(
                idx=e["idx"], alive=e["alive"],
                queue_depth=e["queue_depth"],
                queued_records=e["queued_records"], processed=e["processed"],
                stolen=e["stolen"]) for e in m["executors"])
            held = m["held_records"]
            queued = m["queued"]
            alive = m["alive_executors"]
            p50, p99 = m["latency_p50"], m["latency_p99"]
            lat_n = m["latency_window_n"]
            exec_secs = m["executor_seconds"]
        snap = TelemetrySnapshot(
            t=now, groups=groups, shards=shards,
            endpoints=endpoints, executors=executors,
            held_records=held, queued_partitions=queued,
            alive_executors=alive, latency_p50=p50, latency_p99=p99,
            latency_n=lat_n, executor_seconds=exec_secs,
            tenants=self._sample_tenants(m))
        with self._lock:
            self.history.append(snap)
        for cb in list(self._subs):
            try:
                cb(snap)
            except Exception:       # a broken subscriber must not kill the bus
                pass
        return snap
