"""Deterministic scenario runner — chaos and load studies on virtual time.

SIM-SITU-style faithful simulation of the in-situ pipeline: a
:class:`Scenario` composes a synthetic load profile (:class:`LoadPhase`
rates and spike schedules, per-record analysis cost) with a seeded fault
plan (:class:`Fault`: kill/revive executors and endpoints, inject
stragglers, silently drop transport frames at time T) and drives a real
:class:`repro.workflow.Session` — broker, endpoints, engine, telemetry,
controller, all of it — under a :class:`repro.runtime.clock.VirtualClock`.

Because the virtual clock serializes participants and advances only on
quiescence, a run is **deterministic**: same seed ⇒ byte-identical
:class:`ScenarioTrace` (verify with :meth:`ScenarioTrace.digest`), and a
"20 second" load-spike study finishes in well under a second of wall time.
That makes the PR-3 elasticity loop, the straggler scan, and the
steal/ordering machinery assertable in milliseconds and replayable from a
seed — see ``tests/test_scenario_chaos.py`` and
``benchmarks/elasticity.py`` (virtual mode).

The trace records every load step, fault injection, analysis call (with the
exact step sequence per stream — the ordering oracle), controller action,
and engine result, each stamped with virtual time, plus a summary of the
delivery/loss accounting across all layers.
"""
from __future__ import annotations

import hashlib
import json
import struct
import tempfile
import threading
from dataclasses import dataclass, field

import numpy as np

from repro.checkpoint.session_store import SessionCheckpointStore
from repro.cloud.nodes import READY
from repro.runtime.clock import VirtualClock
from repro.runtime.wal import WalStore
from repro.streaming.engine import percentile_sorted
from repro.streaming.operators import OperatorPipeline, WindowPane
from repro.tenancy import TENANT_COUNTERS, closure_errors
from repro.workflow.config import WorkflowConfig
from repro.workflow.session import Session


@dataclass(frozen=True)
class LoadPhase:
    """One segment of the load profile.  ``rate_hz`` is producer steps/s;
    each step writes one record per producer rank, so records/s =
    ``rate_hz * n_producers``.  ``rate_hz=0`` is an idle (drain) window."""

    name: str
    duration_s: float
    rate_hz: float


@dataclass(frozen=True)
class TenantTraffic:
    """One tenant's slice of the producer load: which ranks write under
    this QoS identity, thinned to every ``every``-th load step.  A scenario
    with ``tenant_traffic`` opens one FieldHandle per tenant, so records
    carry tenant identity end to end (broker admission → telemetry rollups
    → per-tenant trace events)."""

    tenant: str
    ranks: tuple = (0,)
    every: int = 1                     # write on steps where step % every == 0


@dataclass(frozen=True)
class Fault:
    """One scheduled fault, applied when virtual time reaches ``t``.

    kinds:
      ``kill_executor``      hard-kill executor ``target`` (queue reassigned)
      ``add_executor``       bring up a fresh executor
      ``inject_straggler``   slow executor ``target`` by ``value`` s/batch
      ``clear_straggler``    remove the slowdown from executor ``target``
      ``fail_endpoint``      endpoint ``target`` refuses pushes (retry path)
      ``recover_endpoint``   endpoint ``target`` accepts again
      ``drop_frames``        endpoint ``target`` silently discards the next
                             ``value`` accepted frames (acked, then lost —
                             invisible to the broker's retry logic)
      ``kill_broker``        crash the broker in place; a fresh one adopts
                             the same WAL and replays the unacked tail
                             (requires delivery="exactly-once")
      ``kill_session``       whole-session crash — broker, engine, endpoints
                             all die mid-flight — then ``Session.restore``
                             from the latest checkpoint + WAL tail replay
                             (requires delivery="exactly-once" and an
                             ``operators`` factory)
      ``provision_fail``     the next ``value`` CloudProvisioner power_on
                             attempts fail (retry/backoff/recover path;
                             requires elasticity.provision)
      ``boot_stall``         stretch cold starts by ``value`` s: nodes
                             currently booting are delayed, else the next
                             boot is (requires elasticity.provision)
      ``kill_node``          hard-fail the ``target``-th READY cloud node:
                             its endpoint and executors die atomically, its
                             cost-ledger record closes at death, and the
                             node lands in FAILED for provisioner.recover()
                             (requires elasticity.provision)
    """

    t: float
    kind: str
    target: int = 0
    value: float = 0.0


_FAULT_KINDS = ("kill_executor", "add_executor", "inject_straggler",
                "clear_straggler", "fail_endpoint", "recover_endpoint",
                "drop_frames", "kill_broker", "kill_session",
                "provision_fail", "boot_stall", "kill_node")
_KILL_KINDS = ("kill_broker", "kill_session")
_PROVISION_KINDS = ("provision_fail", "boot_stall", "kill_node")


@dataclass(frozen=True)
class Scenario:
    """A reproducible experiment: workflow wiring + load profile + fault
    plan + one seed controlling every source of scheduling randomness.

    ``operators``: optional factory returning a fresh
    :class:`repro.streaming.operators.OperatorPipeline` — the run then
    attaches it instead of the per-record analyze callback, and every
    operator-level event (window fires, late drops, sink emits) lands in
    the trace as an ``op`` event, with the plan's window loss ledger in
    ``summary["windows"]``.  A factory (not a prebuilt pipeline) so each
    run starts from empty keyed state.  Mutually exclusive with
    ``analysis_cost_s``/``record_latency`` (callback-path knobs — model
    cost inside the operator fns instead).

    ``record_latency``: emit one ``latency`` trace event PER RECORD at
    analysis time (callback scenarios) — the raw material for
    controller-policy regression curves, sweepable for free on virtual
    time."""

    workflow: WorkflowConfig
    phases: tuple = ()
    faults: tuple = ()
    seed: int = 0
    analysis_cost_s: float = 0.0       # simulated work per record
    payload_elems: int = 64
    field_name: str = "load"
    flush_timeout_s: float = 120.0     # virtual seconds, costs nothing real
    operators: object = None           # () -> OperatorPipeline factory
    record_latency: bool = False
    # take a Session.checkpoint() roughly every N virtual seconds of load
    # (0 = never).  Exactly-once only.  ``checkpoint_dir`` pins the store
    # on disk (CI artifact inspection); the default is a fresh temp dir per
    # run, which re-running the same Scenario requires — a reused dir would
    # make run #2 restore run #1's checkpoints.
    checkpoint_every_s: float = 0.0
    checkpoint_dir: str | None = None
    # multi-tenant load: per-tenant rank slices (requires workflow.tenants);
    # () keeps the single-handle load loop
    tenant_traffic: tuple = ()

    def validate(self) -> "Scenario":
        self.workflow.validate()
        for ph in self.phases:
            if ph.duration_s <= 0 or ph.rate_hz < 0:
                raise ValueError(f"bad phase {ph}")
        for f in self.faults:
            if f.kind not in _FAULT_KINDS:
                raise ValueError(f"unknown fault kind {f.kind!r} "
                                 f"(expected one of {_FAULT_KINDS})")
            if f.t < 0:
                raise ValueError(f"fault time must be >= 0, got {f.t}")
        if self.checkpoint_every_s < 0:
            raise ValueError("checkpoint_every_s must be >= 0")
        kinds = {f.kind for f in self.faults}
        if (kinds & set(_KILL_KINDS) or self.checkpoint_every_s) \
                and self.workflow.delivery != "exactly-once":
            raise ValueError(
                "kill_broker/kill_session faults and checkpoint_every_s "
                "require workflow.delivery='exactly-once' (there is nothing "
                "to replay from in at-most-once mode)")
        if kinds & set(_PROVISION_KINDS) \
                and not (self.workflow.elasticity.enabled
                         and self.workflow.elasticity.provision):
            raise ValueError(
                "provision_fail/boot_stall/kill_node faults require "
                "workflow.elasticity.enabled and .provision (there is no "
                "CloudProvisioner to fault otherwise)")
        if ("kill_session" in kinds or self.checkpoint_every_s) \
                and self.operators is None:
            raise ValueError(
                "kill_session and checkpoint_every_s require an operators "
                "factory: Session.restore/checkpoint rebuild plan state "
                "(window panes, sinks), which the callback path has none of")
        if self.tenant_traffic:
            reg = self.workflow.tenant_registry()
            if reg is None:
                raise ValueError("tenant_traffic requires workflow.tenants "
                                 "(records need a registry to be admitted "
                                 "under)")
            for tr in self.tenant_traffic:
                if tr.tenant not in reg:
                    raise ValueError(f"tenant_traffic names undeclared "
                                     f"tenant {tr.tenant!r}")
                if tr.every < 1:
                    raise ValueError("TenantTraffic.every must be >= 1")
                if not tr.ranks or any(
                        not (0 <= r < self.workflow.n_producers)
                        for r in tr.ranks):
                    raise ValueError(
                        f"TenantTraffic.ranks must be non-empty and within "
                        f"[0, n_producers={self.workflow.n_producers})")
        if self.operators is not None:
            if not callable(self.operators):
                raise ValueError("operators must be a zero-arg factory "
                                 "returning an OperatorPipeline (fresh state "
                                 "per run)")
            # these two live in the callback analyze path only; silently
            # ignoring them would skew any operator-vs-callback comparison
            if self.analysis_cost_s:
                raise ValueError(
                    "analysis_cost_s only applies to callback scenarios; "
                    "model cost inside the operator fns (clock.sleep)")
            if self.record_latency:
                raise ValueError(
                    "record_latency only applies to callback scenarios; "
                    "operator runs trace per-event 'op' records instead")
        return self


@dataclass
class ScenarioTrace:
    """The deterministic record of one run: events sorted by
    ``(t, kind, payload)`` so two same-seed runs serialize byte-for-byte."""

    seed: int
    events: list = field(default_factory=list)   # (t, kind, detail dict)
    summary: dict = field(default_factory=dict)
    phase_windows: list = field(default_factory=list)  # (name, t0, t1)

    def events_of(self, kind: str) -> list:
        return [(t, d) for t, k, d in self.events if k == kind]

    def per_stream_steps(self) -> dict[str, list[int]]:
        """Steps in ANALYSIS order per stream — the ordering oracle: any
        deviation from sorted order means a steal/reassign broke the
        per-stream sequence guarantee."""
        out: dict[str, list[int]] = {}
        for _, d in self.events_of("analyze"):
            out.setdefault(d["stream"], []).extend(d["steps"])
        return out

    def latency_curve(self) -> list[tuple[float, float]]:
        """Per-record ``(t_analyzed, latency)`` pairs in time order — the
        regression curve controller-policy sweeps compare (requires the
        scenario to have run with ``record_latency=True``)."""
        return sorted((t, d["latency"]) for t, d in self.events_of("latency"))

    def phase_p99(self, name: str, tenant: str | None = None) -> float:
        """p99 generation→analysis latency over results whose records were
        *generated* inside the named phase's window (paper §4.3 framing).

        With ``tenant``, only that tenant's slice of each result counts —
        its own oldest-record timestamp decides phase membership and its
        own latency feeds the percentile (multi-tenant scenarios emit a
        ``tenants`` map per result event)."""
        if tenant is None:
            lats = sorted(d["latency"] for _, d in self.events_of("result")
                          for (pn, a, b) in self.phase_windows
                          if pn == name and a <= d["t_generated"] < b)
        else:
            lats = sorted(d["tenants"][tenant][2]
                          for _, d in self.events_of("result")
                          if tenant in d.get("tenants", {})
                          for (pn, a, b) in self.phase_windows
                          if pn == name
                          and a <= d["tenants"][tenant][1] < b)
        return percentile_sorted(lats, 0.99)

    def to_jsonl(self) -> str:
        """Canonical serialization: one sorted-key JSON object per line.
        Byte-identical across same-seed runs (the CI determinism gate
        compares exactly this)."""
        lines = [json.dumps({"seed": self.seed, "summary": self.summary,
                             "phases": self.phase_windows}, sort_keys=True)]
        lines += [json.dumps({"t": t, "kind": k, **d}, sort_keys=True)
                  for t, k, d in self.events]
        return "\n".join(lines) + "\n"

    def digest(self) -> str:
        return hashlib.sha256(self.to_jsonl().encode()).hexdigest()


def _canon(v) -> bytes:
    """Canonical bytes of one sink value — type-tagged so e.g. 1 and 1.0
    and "1" cannot collide — for :func:`sink_digest`."""
    if isinstance(v, np.ndarray):
        return b"nd:" + str(v.dtype).encode() + str(v.shape).encode() \
            + v.tobytes()
    if isinstance(v, np.generic):
        v = v.item()
    if isinstance(v, bool):
        return b"b1" if v else b"b0"
    if isinstance(v, float):
        return b"f:" + struct.pack("!d", v)
    if isinstance(v, int):
        return b"i:" + str(v).encode()
    if isinstance(v, str):
        return b"s:" + v.encode()
    if isinstance(v, bytes):
        return b"y:" + v
    if isinstance(v, WindowPane):
        return b"w:" + struct.pack("!dd", v.start, v.end) \
            + _canon(v.key) + _canon(list(v.values))
    if isinstance(v, (tuple, list)):
        return b"l:" + b",".join(_canon(x) for x in v)
    if isinstance(v, dict):
        return b"d:" + b",".join(
            _canon(k) + b"=" + _canon(v[k]) for k in sorted(v, key=repr))
    return b"r:" + repr(v).encode()


def sink_digest(plan) -> str:
    """sha256 over every sink's per-key ordered value sequences, timestamps
    excluded — the "did the cloud see exactly the same analysis results"
    oracle.  A chaos run whose digest equals the fault-free same-seed run's
    delivered byte-identical results despite every injected death."""
    h = hashlib.sha256()
    for name in sorted(plan.sinks()):
        h.update(b"S:" + name.encode())
        per_key: dict[str, list] = {}
        for key, value, _t in plan.results(name):
            per_key.setdefault(key, []).append(value)
        for key in sorted(per_key):
            h.update(b"K:" + key.encode())
            for value in per_key[key]:
                h.update(_canon(value))
    return h.hexdigest()


class ScenarioRunner:
    """Drives one :class:`Scenario` to completion under a seeded
    ``VirtualClock`` and returns its :class:`ScenarioTrace`."""

    def __init__(self, scenario: Scenario):
        self.scenario = scenario.validate()

    # ---- fault application ----------------------------------------------
    @staticmethod
    def _apply_fault(sess: Session, f: Fault) -> None:
        eng = sess.engine
        if f.kind == "kill_executor":
            eng.kill_executor(f.target % len(eng.executors))
        elif f.kind == "add_executor":
            eng.add_executor()
        elif f.kind == "inject_straggler":
            eng.executors[f.target % len(eng.executors)].slowdown = float(f.value)
        elif f.kind == "clear_straggler":
            eng.executors[f.target % len(eng.executors)].slowdown = 0.0
        elif f.kind == "fail_endpoint":
            sess.endpoints[f.target % len(sess.endpoints)].handle.fail()
        elif f.kind == "recover_endpoint":
            sess.endpoints[f.target % len(sess.endpoints)].handle.recover()
        elif f.kind == "drop_frames":
            sess.endpoints[f.target % len(sess.endpoints)].handle \
                .drop_next_frames(int(f.value))
        elif f.kind == "provision_fail":
            sess.provisioner.inject_provision_failures(int(f.value))
        elif f.kind == "boot_stall":
            sess.provisioner.inject_boot_stall(float(f.value))
        elif f.kind == "kill_node":
            ready = sess.provisioner.nodes_in_state(READY)
            if not ready:
                raise LookupError("no READY cloud node to kill")
            sess.provisioner.fail_node(ready[f.target % len(ready)])

    # ---- the run ---------------------------------------------------------
    def run(self) -> ScenarioTrace:
        sc = self.scenario
        clock = VirtualClock(seed=sc.seed)
        clock.attach()                 # this thread drives the schedule
        trace = ScenarioTrace(seed=sc.seed)
        elock = threading.Lock()

        def emit(kind: str, **detail) -> None:
            with elock:
                trace.events.append((round(clock.now(), 9), kind, detail))

        def analyze(key, records):
            # simulated per-record cost on VIRTUAL time, plus the ordering
            # oracle: the exact step sequence each stream is analyzed in
            if sc.analysis_cost_s:
                clock.sleep(sc.analysis_cost_s * len(records))
            if sc.record_latency:
                now = clock.now()
                for r in records:
                    emit("latency", stream=key, step=r.step,
                         latency=round(now - r.t_generated, 9))
            emit("analyze", stream=key, steps=[r.step for r in records])
            return len(records)

        # ---- durable artifacts shared across session incarnations -------
        kinds = {f.kind for f in sc.faults}
        durable = sc.checkpoint_every_s > 0 or "kill_session" in kinds
        wal = ckpt_store = None
        if sc.workflow.delivery == "exactly-once":
            # retain="commit" keeps even acked entries until a checkpoint
            # commits them, so a whole-session crash can replay the tail
            wal = WalStore(capacity_bytes=sc.workflow.wal_capacity_bytes,
                           queue_capacity=sc.workflow.queue_capacity,
                           retain="commit" if durable else "ack")
            if durable:
                ckpt_store = SessionCheckpointStore(
                    sc.checkpoint_dir
                    or tempfile.mkdtemp(prefix="repro_scenario_ckpt_"))

        def op_emit(kind, **d):
            # operator-level trace events: window fires / late drops / sinks
            emit("op", event=kind, **d)

        pipeline = sc.operators() if sc.operators is not None else \
            OperatorPipeline(granularity="batch").map("analyze", analyze,
                                                      ordering="ordered")
        sess = Session(sc.workflow, pipeline=pipeline, clock=clock,
                       wal=wal, checkpoints=ckpt_store)
        sess.exec_plan.on_event = op_emit

        # every live reference routes through the box: kill_session swaps
        # the session (and its field handle) under the load loop's feet
        box = {"sess": sess, "handle": None, "actions": [],
               "recovery_counts": {}, "restores": 0, "prov_events": []}

        def absorb_dead(old: Session) -> None:
            # controller actions and recovery events die with a killed
            # incarnation — fold them out before the crash
            if old.controller is not None:
                box["actions"].extend(old.controller.actions_log)
            if old.recovery is not None:
                for k, v in old.recovery.summary().items():
                    box["recovery_counts"][k] = \
                        box["recovery_counts"].get(k, 0) + v
            if old.provisioner is not None:
                box["prov_events"].extend(old.provisioner.events)

        def open_handles(s: Session) -> None:
            box["handle"] = s.open_field(sc.field_name,
                                         shape=(sc.payload_elems,))
            box["handles"] = {
                tr.tenant: s.open_field(sc.field_name,
                                        shape=(sc.payload_elems,),
                                        tenant=tr.tenant)
                for tr in sc.tenant_traffic}

        def restore_session() -> None:
            old = box["sess"]
            absorb_dead(old)
            old.kill()
            new = Session.restore(sc.workflow, checkpoints=ckpt_store,
                                  wal=wal, pipeline=sc.operators(),
                                  clock=clock)
            new.exec_plan.on_event = op_emit
            box["sess"] = new
            open_handles(new)
            box["restores"] += 1

        try:
            open_handles(sess)
            n_ranks = sc.workflow.n_producers
            rng = np.random.RandomState(sc.seed)
            payloads = [rng.randn(sc.payload_elems).astype(np.float32)
                        for _ in range(n_ranks)]

            # fault plan runs on its own participant thread so injections
            # land at their exact virtual instants, independent of the
            # load loop's cadence
            faults = sorted(sc.faults, key=lambda f: (f.t, f.kind, f.target))

            def inject():
                for f in faults:
                    # sleep_until: the exact float deadline, so a fault at
                    # f.t ties (and tie-breaks deterministically) with any
                    # other waiter targeting the same instant
                    clock.sleep_until(f.t)
                    try:
                        if f.kind == "kill_broker":
                            box["sess"].restart_broker()
                        elif f.kind == "kill_session":
                            restore_session()
                        else:
                            self._apply_fault(box["sess"], f)
                        emit("fault", fault=f.kind, target=f.target,
                             value=f.value, ok=True)
                    except Exception as e:   # a mistargeted fault is a trace
                        emit("fault", fault=f.kind, target=f.target,
                             value=f.value, ok=False,
                             error=type(e).__name__)
                clock.detach()   # leave the schedule with no watchdog stall

            injector = threading.Thread(target=inject, daemon=True,
                                        name="fault-injector")
            clock.thread_started(injector)
            injector.start()

            next_ckpt = sc.checkpoint_every_s or None

            def maybe_checkpoint() -> None:
                nonlocal next_ckpt
                if next_ckpt is None or clock.now() < next_ckpt:
                    return
                try:
                    cid = box["sess"].checkpoint(timeout=sc.flush_timeout_s)
                    emit("checkpoint", ok=True, ckpt_id=cid)
                except Exception as e:
                    # a kill landing mid-quiesce aborts THIS checkpoint;
                    # the run continues from the previous committed one
                    emit("checkpoint", ok=False, error=type(e).__name__)
                next_ckpt = clock.now() + sc.checkpoint_every_s

            step = 0
            sched = 0.0   # nominal producer time: event timestamps follow
            #               the simulation schedule, not the (crash-delayed)
            #               virtual instant a write lands, so window
            #               membership is identical across recovery replays
            for ph in sc.phases:
                t0 = round(clock.now(), 9)
                emit("phase", name=ph.name, rate_hz=ph.rate_hz,
                     duration_s=ph.duration_s)
                n_steps = int(round(ph.duration_s * ph.rate_hz))
                if n_steps == 0:
                    sched += ph.duration_s
                    clock.sleep(ph.duration_s)
                else:
                    period = ph.duration_s / n_steps
                    for _ in range(n_steps):
                        if sc.tenant_traffic:
                            accepted = 0
                            for tr in sc.tenant_traffic:
                                if step % tr.every:
                                    continue
                                accepted += box["handles"][tr.tenant] \
                                    .write_batch(
                                        step,
                                        [payloads[r] for r in tr.ranks],
                                        ranks=list(tr.ranks),
                                        t=round(sched, 9))
                        else:
                            accepted = box["handle"].write_batch(
                                step, payloads, ranks=list(range(n_ranks)),
                                t=round(sched, 9))
                        emit("write", step=step, accepted=accepted)
                        step += 1
                        sched += period
                        clock.sleep(period)
                        maybe_checkpoint()
                trace.phase_windows.append((ph.name, t0,
                                            round(clock.now(), 9)))

            clock.join(injector)       # let trailing faults land
            box["sess"].flush(timeout=sc.flush_timeout_s)
        finally:
            box["sess"].close()
        sess = box["sess"]

        # post-run, single-threaded: merge the controller's action log (all
        # incarnations — killed sessions' logs were absorbed into the box)
        # and the engine's results into the trace at their virtual timestamps
        actions = list(box["actions"])
        if sess.controller is not None:
            actions.extend(sess.controller.actions_log)
        for t, a in actions:
            trace.events.append((round(t, 9), "action",
                                 {"kind": a.kind, "value": a.value,
                                  "group": a.group, "reason": a.reason}))
        prov_events = list(box["prov_events"])
        if sess.provisioner is not None:
            prov_events.extend(sess.provisioner.events)
        for t, d in prov_events:
            trace.events.append((round(t, 9), "provision", dict(d)))
        tenancy = bool(sc.workflow.tenants)
        for r in sess.results():
            detail = {"stream": r.stream_key,
                      "executor": r.executor,
                      "n_records": r.n_records,
                      "t_generated": round(r.t_generated_min, 9),
                      "latency": round(r.latency, 9)}
            rt = getattr(r, "tenants", None)
            if tenancy and rt:
                # per-tenant slice of the micro-batch: count, oldest
                # generation instant, and that slice's own latency
                detail["tenants"] = {
                    name: [n, round(tg, 9), round(r.t_analyzed - tg, 9)]
                    for name, (n, tg) in sorted(rt.items())}
            trace.events.append((round(r.t_analyzed, 9), "result", detail))
        trace.events.sort(key=lambda e: (e[0], e[1],
                                         json.dumps(e[2], sort_keys=True)))

        st = sess.stats
        eps = [e.handle.telemetry() for e in sess.endpoints]
        peak = max((s.alive_executors for s in sess.telemetry.history),
                   default=0) if sess.telemetry is not None else 0
        m = sess.engine.metrics() if sess.engine is not None else {}
        trace.summary = {
            "written": st.written, "sent": st.sent,
            "dropped_by_policy": st.dropped,
            "send_errors": st.send_errors, "rerouted": st.rerouted,
            "frames_sent": st.frames_sent,
            "endpoint_records_in": sum(e["records_in"] for e in eps),
            "frames_dropped_injected": sum(e["frames_dropped"] for e in eps),
            "records_dropped_injected": sum(e["records_dropped"] for e in eps),
            "analyzed": sum(d["n_records"]
                            for _, d in trace.events_of("result")),
            "executor_seconds": round(
                sess.engine.executor_seconds(), 9) if sess.engine else 0.0,
            "executors_peak": peak,
            "order_timeouts": m.get("order_timeouts", 0),
            "latency_p99": round(percentile_sorted(
                sorted(d["latency"]
                       for _, d in trace.events_of("result")), 0.99), 9),
            "virtual_duration_s": round(clock.now(), 9),
            "clock_wakeups": clock.wakeups,
        }
        if tenancy:
            # per-tenant QoS rollup + the loss-ledger closure verdict: after
            # an ordered close the broker backlog is empty, so every
            # admitted record must be accounted sent or evicted — per
            # tenant, in every scenario, chaos included
            by_tenant_lat: dict[str, list] = {}
            analyzed_by: dict[str, int] = {}
            for _, d in trace.events_of("result"):
                for name, (n, _tg, lat) in d.get("tenants", {}).items():
                    analyzed_by[name] = analyzed_by.get(name, 0) + n
                    by_tenant_lat.setdefault(name, []).append(lat)
            errs = closure_errors(st.tenants)
            rows = {}
            for name in sorted(set(st.tenants) | set(analyzed_by)):
                c = st.tenants.get(name, {})
                rows[name] = {k: c.get(k, 0) for k in TENANT_COUNTERS}
                rows[name]["analyzed"] = analyzed_by.get(name, 0)
                rows[name]["latency_p99"] = round(percentile_sorted(
                    sorted(by_tenant_lat.get(name, [])), 0.99), 9)
            trace.summary["tenants"] = rows
            trace.summary["tenant_ledger"] = {"closed": not errs,
                                              "errors": errs}
            if sess.provisioner is not None:
                trace.summary["cost_by_tenant"] = \
                    sess.provisioner.ledger.attribute(
                        {n: float(analyzed_by.get(n, 0)) for n in rows})
        if sess.controller is not None or actions:
            act_counts: dict[str, int] = {}
            for _, a in actions:
                act_counts[a.kind] = act_counts.get(a.kind, 0) + 1
            trace.summary["controller_actions"] = act_counts
        if sess.provisioner is not None:
            trace.summary["provisioning"] = sess.provisioner.summary()
        if sc.operators is not None:
            trace.summary["windows"] = sess.exec_plan.accounting()
            # content oracle: per-sink, per-key ordered values (no times)
            trace.summary["sink_digest"] = sink_digest(sess.exec_plan)
        if sc.workflow.delivery == "exactly-once":
            rec = dict(box["recovery_counts"])
            if sess.recovery is not None:
                for k, v in sess.recovery.summary().items():
                    rec[k] = rec.get(k, 0) + v
            trace.summary["recovery"] = {
                "frames_abandoned": st.frames_abandoned,
                "frames_replayed": st.frames_replayed,
                "records_replayed": st.records_replayed,
                "frames_deduped": sum(e["frames_deduped"] for e in eps),
                "records_deduped": sum(e["records_deduped"] for e in eps),
                "checkpoints": sum(1 for _, d in
                                   trace.events_of("checkpoint") if d["ok"]),
                "session_restores": box["restores"],
                "events": rec,
            }
        return trace


def run_scenario(scenario: Scenario) -> ScenarioTrace:
    return ScenarioRunner(scenario).run()
