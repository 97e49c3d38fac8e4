"""The composable Session owning the whole HPC→Cloud pipeline.

One object replaces the seed's four hand-wired call sites:

    with Session(WorkflowConfig(n_producers=8, n_groups=2),
                 pipeline=my_pipeline) as sess:
        vel = sess.open_field("velocity", shape=(256,))
        for s in range(steps):
            vel.write(s, field, rank=r)            # or write_batch(...)
    panel = sess.results()                         # after ordered teardown

``Session`` owns endpoint creation (per the config's transport), broker
construction, engine + operator-plan lifecycle, and ordered teardown —
``broker.finalize()`` (drain producer queues onto the endpoints) then
``engine.drain_and_stop()`` (drain endpoints through the plan) then
transport close.  :class:`FieldHandle` is the typed producer-side handle the
paper's free-floating ``broker_ctx`` grew into: dtype-coercing,
shape-checking, and batch-aware (``write_batch`` ships all regions of a
field as one aggregated queue item per group ⇒ ≤ one wire frame per
(field, group)).
"""
from __future__ import annotations

import threading

import numpy as np

from repro.core.broker import _COUNTER_FIELDS, Broker, BrokerStats
from repro.core.records import FieldSchema
from repro.runtime.clock import Clock, ensure_clock
from repro.runtime.controller import ElasticController
from repro.runtime.fault import FailureDetector
from repro.runtime.recovery import RecoverySupervisor
from repro.runtime.telemetry import TelemetryBus
from repro.runtime.wal import FileWalStore, SeqLedger, WalStore
from repro.streaming.endpoint import make_endpoint, make_endpoints
from repro.streaming.engine import StreamEngine
from repro.streaming.operators import ExecutionPlan, OperatorPipeline
from repro.tenancy import merge_counts
from repro.workflow.config import WorkflowConfig


class RestoreTopologyError(ValueError):
    """A checkpoint's topology disagrees with the live ``WorkflowConfig``
    handed to :meth:`Session.restore`.

    Per-group WAL segments, the receive-side seq ledger, and the endpoint
    audit counters are all keyed by the checkpointed group/endpoint layout;
    silently rebuilding them under a different ``n_groups``/endpoint count
    would map replayed records to the wrong groups (or truncate the
    endpoint state zip) and corrupt the exactly-once guarantee.  Restore
    with a matching topology, or omit ``config`` to adopt the
    checkpointed one."""


def _check_restore_topology(ckpt_cfg: WorkflowConfig,
                            live_cfg: WorkflowConfig) -> None:
    """Raise :class:`RestoreTopologyError` on any mismatch that changes how
    checkpointed per-group/per-endpoint state maps onto the new session."""
    old_plan, new_plan = ckpt_cfg.group_plan(), live_cfg.group_plan()
    mismatches = []
    if old_plan.n_producers != new_plan.n_producers:
        mismatches.append(f"n_producers {old_plan.n_producers} -> "
                          f"{new_plan.n_producers}")
    if old_plan.n_groups != new_plan.n_groups:
        mismatches.append(f"n_groups {old_plan.n_groups} -> "
                          f"{new_plan.n_groups}")
    if ckpt_cfg.endpoint_count != live_cfg.endpoint_count:
        mismatches.append(f"endpoint_count {ckpt_cfg.endpoint_count} -> "
                          f"{live_cfg.endpoint_count}")
    if ckpt_cfg.delivery != live_cfg.delivery:
        mismatches.append(f"delivery {ckpt_cfg.delivery!r} -> "
                          f"{live_cfg.delivery!r}")
    if mismatches:
        raise RestoreTopologyError(
            "checkpointed topology does not match the live config "
            f"({'; '.join(mismatches)}): per-group WAL/ledger state cannot "
            "be adopted across a topology change — restore with the "
            "checkpointed topology (or pass config=None to adopt it)")


class FieldHandle:
    """Typed handle for one streamed field (all ranks of the job).

    ``shape=()`` means "unchecked" (the paper's ``void* data``); a concrete
    shape makes every write validate the payload's size.  Arrays are coerced
    to the declared dtype before they hit the wire — except with
    ``coerce_dtype=False`` (the paper-API compat path), where the declared
    dtype is schema metadata only and payloads keep their input dtype, as
    the original ``broker_write`` did.
    """

    def __init__(self, broker: Broker, name: str, shape=(),
                 dtype: str = "float32", rank: int = 0, *,
                 coerce_dtype: bool = True, tenant: str = "default"):
        self.broker = broker
        self.name = name
        self.shape = tuple(shape)
        self.dtype = dtype
        self.coerce_dtype = coerce_dtype
        self.rank = rank                    # default rank for write()
        self.tenant = tenant                # QoS identity stamped on writes
        for g in range(broker.plan.n_groups):
            broker.register(FieldSchema(field_name=name, shape=self.shape,
                                        dtype=dtype, group_id=g))

    def _coerce(self, arr) -> np.ndarray:
        out = np.asarray(arr, dtype=self.dtype if self.coerce_dtype else None)
        if self.shape and out.size != int(np.prod(self.shape)):
            raise ValueError(
                f"field {self.name!r} declared shape {self.shape} "
                f"({int(np.prod(self.shape))} elems) but payload has shape "
                f"{out.shape} ({out.size} elems)")
        return out

    def write(self, step: int, arr, *, rank: int | None = None,
              t: float | None = None) -> bool:
        """Enqueue one snapshot; returns False if backpressure dropped it.
        ``t``: explicit event timestamp (default: session clock's now)."""
        r = self.rank if rank is None else rank
        return self.broker.write(self.name, r, step, self._coerce(arr), t=t,
                                 tenant=self.tenant)

    def write_batch(self, steps, arrs, *, ranks=None,
                    t: float | None = None) -> int:
        """Enqueue many snapshots as one aggregated batch.

        ``steps`` is a scalar (broadcast) or a sequence aligned with
        ``arrs``; ``ranks`` likewise (default: the handle's rank).  Records
        are grouped by destination and each group receives ONE queue item,
        so the batch leaves as at most one wire frame per (field, group).
        Returns #records accepted.
        """
        arrs = [self._coerce(a) for a in arrs]
        n = len(arrs)
        if np.isscalar(steps):
            steps = [int(steps)] * n
        if ranks is None:
            ranks = [self.rank] * n
        elif np.isscalar(ranks):
            ranks = [int(ranks)] * n
        if not (len(steps) == len(ranks) == n):
            raise ValueError(
                f"write_batch needs aligned sequences: {len(steps)} steps, "
                f"{len(ranks)} ranks, {n} payloads")
        return self.broker.write_batch(self.name, list(ranks), list(steps),
                                       arrs, t=t, tenant=self.tenant)

    def __repr__(self):
        return (f"FieldHandle({self.name!r}, shape={self.shape}, "
                f"dtype={self.dtype!r})")


class Session:
    """Context manager owning broker → endpoint → engine → plan wiring."""

    def __init__(self, config: WorkflowConfig | None = None, *,
                 endpoints: list | None = None, pipeline=None,
                 clock: Clock | None = None, wal: WalStore | None = None,
                 checkpoints=None, ledger: SeqLedger | None = None,
                 _paused: bool = False):
        self.config = (config or WorkflowConfig()).validate()
        self.plan = self.config.group_plan()
        # one time source for every layer: an explicit ``clock`` wins,
        # otherwise the config's clock knob ("wall" | "virtual") decides
        self.clock = ensure_clock(clock) if clock is not None \
            else self.config.make_clock()
        self._attached_thread = None
        if self.clock.virtual:
            # the building thread is the schedule's driver: register it
            # before any component thread starts, so virtual time cannot
            # advance while construction is still in flight.  Remembered so
            # close() detaches THIS thread even when called from another —
            # detaching the closer would strand the builder in the
            # runnable set and freeze the schedule.
            self._attached_thread = threading.current_thread()
            self.clock.attach(self._attached_thread)
        # -- exactly-once durability (no-ops in at-most-once mode) --------
        self._ckpt_store = checkpoints
        exactly_once = self.config.delivery == "exactly-once"
        if exactly_once:
            if ledger is None:
                ledger = SeqLedger()
            if wal is None:
                retain = "commit" if checkpoints is not None else "ack"
                if self.config.wal_dir is not None:
                    # disk-backed: adopts whatever a previous run synced
                    # into the directory (torn tails discarded on load)
                    wal = FileWalStore(
                        self.config.wal_dir,
                        capacity_bytes=self.config.wal_capacity_bytes,
                        queue_capacity=self.config.queue_capacity,
                        retain=retain)
                else:
                    wal = WalStore(
                        capacity_bytes=self.config.wal_capacity_bytes,
                        queue_capacity=self.config.queue_capacity,
                        retain=retain)
        self._ledger = ledger
        self._wal = wal
        self._stats_base: dict[str, int] = {}
        self._tenants_base: dict[str, dict[str, int]] = {}
        # multi-tenant QoS: one registry for the whole wiring (broker
        # admission, telemetry rollups, debt-weighted scaling); None keeps
        # every layer on its single-tenant fast path
        self.tenants = self.config.tenant_registry()
        self.recovery: RecoverySupervisor | None = None
        if endpoints is not None:
            self.endpoints = list(endpoints)
            self._owns_endpoints = False
        else:
            # endpoint_count >= plan.n_groups is enforced by validate()
            self.endpoints = make_endpoints(
                self.config.endpoint_count,
                inbound_bw=self.config.inbound_bw,
                base_port=self.config.base_port,
                transport=self.config.transport,
                clock=self.clock, ledger=self._ledger)
            self._owns_endpoints = True
        self.broker = Broker(self.plan, self.endpoints,
                             self.config.broker_config(), clock=self.clock,
                             wal=self._wal, paused=_paused,
                             tenants=self.tenants)
        self.engine: StreamEngine | None = None
        self.exec_plan: ExecutionPlan | None = None   # compiled operator plan
        # control plane (built lazily with the engine when elasticity is on)
        self.telemetry: TelemetryBus | None = None
        self.detector: FailureDetector | None = None
        self.controller: ElasticController | None = None
        # cloud capacity plane (built with the control plane when
        # ``elasticity.provision``); _dynamic_eps tracks endpoints attached
        # to the live session so teardown closes them even when the base
        # fleet was caller-supplied
        self.provisioner = None
        self._dynamic_eps: list = []
        self._fields: dict[tuple, FieldHandle] = {}
        self._closed = False
        try:
            if pipeline is not None:
                self.attach_pipeline(pipeline)
        except Exception:   # don't leak sender threads / loopback sockets
            self.close()
            raise

    # ---- consumer-side wiring -------------------------------------------
    def _handles(self) -> list:
        return [e.handle for e in self.endpoints]

    def attach_endpoint(self) -> int:
        """Attach one more endpoint to the LIVE session (cloud capacity
        plane: a freshly booted node brings its endpoint up mid-run).

        The new endpoint shares the fleet's SeqLedger so exactly-once
        dedupe spans it, and is registered with the broker (routable on
        the next send/reroute), the engine (drained next trigger cycle)
        and the telemetry bus.  Returns the new fleet index."""
        i = len(self.endpoints)
        ledger = self._ledger
        if ledger is None and self.endpoints:
            ledger = getattr(self.endpoints[0].handle, "ledger", None)
        ep = make_endpoint(i, inbound_bw=self.config.inbound_bw,
                           base_port=self.config.base_port,
                           transport=self.config.transport,
                           clock=self.clock, ledger=ledger)
        self.endpoints.append(ep)
        self._dynamic_eps.append(ep)
        bidx = self.broker.attach_endpoint(ep)
        assert bidx == i, f"broker fleet index diverged: {bidx} != {i}"
        if self.engine is not None:
            self.engine.attach_endpoint(ep.handle)
        if self.telemetry is not None:
            self.telemetry.endpoints.append(ep.handle)
        return i

    def attach_pipeline(self, pipeline) -> ExecutionPlan:
        """Route every micro-batch through an operator pipeline: an
        :class:`OperatorPipeline` (compiled here against the Session clock)
        or a prebuilt :class:`ExecutionPlan`.  The first attach builds the
        engine; a later one replaces the plan on the live engine.  Returns
        the compiled plan."""
        if isinstance(pipeline, OperatorPipeline):
            plan = pipeline.compile(clock=self.clock)
        elif isinstance(pipeline, ExecutionPlan):
            plan = pipeline
            plan.bind_clock(self.clock)
        else:
            raise TypeError(f"attach_pipeline needs an OperatorPipeline or "
                            f"an ExecutionPlan, got {type(pipeline).__name__}")
        if self.engine is None:
            self.engine = StreamEngine.from_config(
                self.config, self._handles(), plan, groups=self.plan,
                clock=self.clock)
            self._start_control_plane()
        else:
            self.engine.attach_plan(plan)
        self.exec_plan = plan
        return plan

    def _start_control_plane(self) -> None:
        """With ``elasticity.enabled``, the Session owns the closed loop:
        a TelemetryBus over its broker/endpoints/engine, a FailureDetector,
        and the ElasticController thread (started here, stopped FIRST in
        :meth:`close` so no actuator races the ordered teardown)."""
        el = self.config.elasticity
        if not el.enabled or self.controller is not None \
                or self.engine is None:
            return
        self.telemetry = TelemetryBus(broker=self.broker,
                                      endpoints=self._handles(),
                                      engine=self.engine, clock=self.clock,
                                      tenants=self.tenants)
        self.detector = FailureDetector(
            timeout_s=el.heartbeat_timeout_s,
            straggler_factor=el.straggler_factor, clock=self.clock)
        if self.config.delivery == "exactly-once":
            # endpoint/executor death routes through the supervisor: the
            # same re-point, but with WAL replay behind it instead of loss
            self.recovery = RecoverySupervisor(broker=self.broker,
                                               engine=self.engine,
                                               clock=self.clock)
        if el.provision:
            from repro.cloud import (DEFAULT_CATALOG, CloudProvisioner,
                                     SessionFabric)
            if el.node_class not in DEFAULT_CATALOG:
                raise ValueError(
                    f"unknown elasticity.node_class {el.node_class!r}; "
                    f"catalog has {sorted(DEFAULT_CATALOG)}")
            self.provisioner = CloudProvisioner(
                SessionFabric(self), clock=self.clock,
                seed=self.config.clock_seed,
                retry_limit=el.provision_retry_limit,
                backoff_s=el.provision_backoff_s)
        self.controller = ElasticController(
            self.telemetry, el, engine=self.engine, broker=self.broker,
            detector=self.detector, clock=self.clock,
            recovery=self.recovery, provisioner=self.provisioner,
            tenants=self.tenants)
        self.controller.start()

    # ---- producer-side API ----------------------------------------------
    def open_field(self, name: str, shape=(), dtype: str = "float32",
                   tenant: str = "default") -> FieldHandle:
        """Register a field and return its (cached) typed handle.

        ``tenant`` stamps every write from the handle with that QoS
        identity (must be declared in ``config.tenants`` when a registry
        is active)."""
        key = (name, tuple(shape), dtype, tenant)
        if key not in self._fields:
            self._fields[key] = FieldHandle(self.broker, name, shape=shape,
                                            dtype=dtype, tenant=tenant)
        return self._fields[key]

    # ---- observability ---------------------------------------------------
    @property
    def stats(self) -> BrokerStats:
        """Broker counters, folded with whatever previous broker/session
        incarnations accumulated before a crash (exactly-once restarts)."""
        return self._merge_base(self.broker.stats)

    def _merge_base(self, st: BrokerStats) -> BrokerStats:
        for f, v in self._stats_base.items():
            setattr(st, f, getattr(st, f) + v)
        if self._tenants_base:
            merge_counts(st.tenants, self._tenants_base)
        return st

    def _absorb_stats(self, stats: BrokerStats) -> None:
        """Fold a dead incarnation's counters into the session base.

        In exactly-once mode ``written`` is excluded: it derives from the
        WAL segments the successor broker shares, so the live broker's
        count already covers the dead incarnation's writes.  Per-tenant
        counters fold additively — ``admitted`` is counted once at WAL
        append and never on replay, so the sum stays exact."""
        for f in _COUNTER_FIELDS:
            if f == "written" and self._wal is not None:
                continue
            self._stats_base[f] = self._stats_base.get(f, 0) \
                + getattr(stats, f)
        merge_counts(self._tenants_base, stats.tenants)

    def results(self, stage: str | None = None) -> list:
        """Engine results; with ``stage``, the attached operator plan's
        :class:`Sink` results."""
        if stage is not None:
            if self.exec_plan is None:
                raise ValueError("no pipeline attached; results(stage=...) "
                                 "needs attach_pipeline()")
            return self.exec_plan.results(stage)
        return self.engine.collect() if self.engine is not None else []

    def latency_stats(self) -> dict:
        return self.engine.latency_stats() if self.engine is not None else {"n": 0}

    def flush(self, timeout: float | None = None) -> None:
        self.broker.flush(timeout=timeout)

    # ---- exactly-once: checkpoint / crash / restore ----------------------
    def _quiesce_engine(self, timeout: float = 60.0) -> None:
        """Run the pipeline dry: force-trigger until nothing is pending on
        the endpoints, held in the engine, queued, or being analyzed.  A
        checkpoint taken here is a consistent cut — every record the broker
        acked has fully traversed the plan."""
        eng = self.engine

        def idle() -> bool:
            if self._closed:
                raise RuntimeError("session killed during checkpoint quiesce")
            eng.trigger_once(force=True)
            if any(h.pending() for h in self._handles()):
                return False
            m = eng.metrics()
            return (eng.held() == 0 and m["queued"] == 0
                    and all(e["current_key"] is None
                            for e in m["executors"]))

        if not self.clock.wait(idle, timeout=timeout, poll=0.01):
            raise TimeoutError(
                "pipeline did not quiesce within the checkpoint timeout")

    def checkpoint(self, timeout: float = 60.0) -> int:
        """Quiesce and capture a consistent cut of the whole run — plan
        state (window panes, watermarks, loss ledgers, sink results), the
        per-stream commit frontier, engine seq counters + results, broker
        counters and WAL trim points, the receive-side seq ledger, and the
        endpoints' audit counters — into the checkpoint store.  The WAL is
        marked committed through the cut only after the store commits, so a
        crash during save still restores from the previous checkpoint."""
        if self._ckpt_store is None:
            raise ValueError("no checkpoint store: pass "
                             "checkpoints=SessionCheckpointStore(dir)")
        if self._wal is None or self.exec_plan is None:
            raise ValueError("checkpoint() requires delivery='exactly-once' "
                             "and an attached operator pipeline")
        self.broker.flush(timeout=timeout)
        self._quiesce_engine(timeout=timeout)
        st = self.stats
        state = {
            "config": self.config.to_dict(),
            "plan": self.exec_plan.snapshot(),
            "frontier": self.exec_plan.frontier_snapshot(),
            "engine": self.engine.state_snapshot(),
            "stats": {f: getattr(st, f) for f in _COUNTER_FIELDS},
            "tenant_stats": {k: dict(v) for k, v in st.tenants.items()},
            "wal": self.broker.wal_points(),
            "ledger": self._ledger.snapshot(),
            "endpoints": [h.audit_snapshot() for h in self._handles()],
        }
        cid = self._ckpt_store.save(state)
        self.broker.commit_wal()
        if isinstance(self._wal, FileWalStore):
            # durable cut: the commit frontier (and the tail behind it)
            # reaches disk, so a *host* crash restores from this checkpoint
            self._wal.sync()
        return cid

    def kill(self) -> None:
        """Simulated whole-session crash: controller, broker senders, and
        engine threads stop immediately; queued work and in-memory state
        die.  The durable artifacts — the WalStore and checkpoint store
        passed to __init__ — survive for :meth:`restore`."""
        if self._closed:
            return
        self._closed = True
        if self.controller is not None:
            self.controller.stop()
        self.broker.kill()
        if self.engine is not None:
            self.engine.kill()
        closing = list(self.endpoints) if self._owns_endpoints \
            else list(self._dynamic_eps)
        for ep in closing:
            close = getattr(ep, "close", None)
            if close is not None:
                close()
        self.clock.detach(self._attached_thread)

    def restart_broker(self) -> Broker:
        """Crash-and-replace the broker in place (exactly-once only): the
        dead broker's senders stop without draining, a fresh Broker adopts
        the same WalStore, and each group's unacked tail replays through
        the endpoints (receive-side dedupe keeps delivery exact)."""
        if self._wal is None:
            raise ValueError("restart_broker() requires "
                             "delivery='exactly-once' (the WAL is what "
                             "makes a broker restart lossless)")
        old = self.broker
        self._absorb_stats(old.kill())
        replay = self._wal.unacked_records()
        self.broker = Broker(self.plan, self.endpoints,
                             self.config.broker_config(), clock=self.clock,
                             wal=self._wal)
        for schema in old.schemas.values():
            self.broker.register(schema)
        for h in self._fields.values():
            h.broker = self.broker
        if self.telemetry is not None:
            self.telemetry.broker = self.broker
        if self.controller is not None:
            self.controller.broker = self.broker
        if self.recovery is not None:
            self.recovery.broker = self.broker
            self.recovery.on_broker_restart(replay)
        return self.broker

    @classmethod
    def restore(cls, config: WorkflowConfig | None = None, *, checkpoints,
                wal: WalStore, pipeline, clock: Clock | None = None,
                endpoints: list | None = None) -> "Session":
        """Rebuild a crashed exactly-once run: load the latest committed
        checkpoint (if any), rewind the WAL's acked frontier to its commit
        frontier, and start a Session whose broker replays the uncommitted
        tail through a freshly-built pipeline restored to the checkpoint
        cut — windows resume mid-pane, sinks keep pre-crash results, and
        the loss ledger stays closed across the crash."""
        try:
            state, _cid = checkpoints.load()
        except FileNotFoundError:
            state = None                   # crash before the 1st checkpoint
        if config is None:
            if state is None:
                raise ValueError("no checkpoint and no config: cannot "
                                 "reconstruct the workflow")
            config = WorkflowConfig.from_dict(state["config"])
        elif state is not None:
            _check_restore_topology(
                WorkflowConfig.from_dict(state["config"]), config)
        ledger = SeqLedger()
        if state is not None:
            ledger.restore(state["ledger"])
        wal.reset_for_restore()            # tail past the commit replays
        sess = cls(config, pipeline=pipeline, clock=clock, wal=wal,
                   checkpoints=checkpoints, ledger=ledger,
                   endpoints=endpoints, _paused=True)
        try:
            if state is not None:
                sess.exec_plan.restore(state["plan"])
                sess.exec_plan.restore_frontier(state["frontier"])
                sess.engine.restore_state(state["engine"])
                for h, snap in zip(sess._handles(), state["endpoints"]):
                    h.restore_audit(snap)
                sess._stats_base = dict(state["stats"])
                sess._tenants_base = {
                    k: dict(v)
                    for k, v in state.get("tenant_stats", {}).items()}
            # ``written`` derives from the shared WAL segments (total ever
            # appended, across every incarnation), so the new broker already
            # reports the pre-crash writes — carrying the checkpoint's count
            # forward would double them
            sess._stats_base["written"] = 0
        except Exception:
            sess.kill()
            raise
        sess.broker.release()              # state is in place: replay
        return sess

    # ---- lifecycle --------------------------------------------------------
    def close(self) -> BrokerStats:
        """Ordered teardown: controller.stop() (quiesce the control plane so
        no scale/reroute action races the drain) → broker.finalize() →
        engine.drain_and_stop() → transport close.  Idempotent; returns the
        final broker stats."""
        if self._closed:
            return self._merge_base(self.broker.stats)
        self._closed = True
        if self.controller is not None:
            self.controller.stop()
        stats = self._merge_base(self.broker.finalize())
        if self.engine is not None:
            self.engine.drain_and_stop()
        if self.provisioner is not None:
            # close the capacity books: any node still booting/ready/
            # draining is powered off now, so the cost ledger ends closed
            self.provisioner.shutdown()
        if isinstance(self._wal, FileWalStore):
            self._wal.sync()
        closing = list(self.endpoints) if self._owns_endpoints \
            else list(self._dynamic_eps)
        for ep in closing:
            close = getattr(ep, "close", None)
            if close is not None:
                close()
        # leave the virtual schedule: every component thread is joined by
        # now.  Detach the thread __init__ attached (not necessarily the
        # closer) so a cross-thread close can't strand the builder as a
        # permanently-runnable participant.
        self.clock.detach(self._attached_thread)
        return stats

    def __enter__(self) -> "Session":
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        self.close()

    def __repr__(self):
        state = "closed" if self._closed else "open"
        return (f"Session({state}, plan={self.plan.n_producers}p/"
                f"{self.plan.n_groups}g, transport={self.config.transport!r}, "
                f"fields={sorted({k[0] for k in self._fields})})")
