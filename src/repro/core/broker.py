"""The ElasticBroker producer-side runtime.

Mirrors the paper's design (§3.1): producer ranks are partitioned into groups;
each group registers with one Cloud endpoint; ``write`` converts a field
snapshot into a stream record and hands it to an **asynchronous dispatcher**
(bounded queue + background sender thread per group) so the producer —
an OpenFOAM solver there, a JAX train/serve step here — never stalls on the
wide-area link.  That asynchrony is what produces the paper's Fig-6 result
(ElasticBroker ≈ simulation-only elapsed time, file-based I/O much slower).

Fault tolerance beyond the paper: bounded-queue backpressure policies
(block / drop_oldest / sample), endpoint failure detection and group
re-routing to surviving endpoints, and per-group delivery metrics.

Wire aggregation (the paper's "data aggregation" duty): each sender
wake-up coalesces all queued records — up to the sender's ``batch_cap``,
seeded from ``cfg.max_batch_records`` and adjustable at runtime
(``Broker.set_batch_cap``, driven by the elasticity controller from queue
depth) — into one batched frame (core/records.py ``encode_batch``), so
framing, compression, and the endpoint's bandwidth model are paid per batch
rather than per record.  ``stats.frames_sent`` vs ``stats.sent`` shows the
achieved aggregation ratio.

Stats accounting is race-free by construction: every ``_GroupSender`` owns a
lock-guarded :class:`_SenderStats` that only its producers/sender touch, and
``Broker.stats`` merges them into one :class:`BrokerStats` view on read —
counters stay exact under arbitrary producer/sender concurrency (the seed
shared one unlocked dataclass across all sender threads, so ``+=`` lost
updates under load).
"""
from __future__ import annotations

import queue
import threading
import warnings
from collections import deque
from dataclasses import dataclass, field

import numpy as np

from repro.core.grouping import GroupPlan
from repro.core.records import (FieldSchema, StreamRecord, decode, encode,
                                encode_batch, wrap_seq)
from repro.core.transport import Transport
from repro.runtime.clock import Clock, ensure_clock
from repro.runtime.telemetry import span
from repro.runtime.wal import WalSegment, WalStore
from repro.tenancy import TenantAdmission, TenantRegistry, merge_counts


@dataclass
class BrokerConfig:
    compress: str = "int8+zstd"       # none | zstd | int8 | int8+zstd
    queue_capacity: int = 256         # records per group queue
    backpressure: str = "drop_oldest" # block | drop_oldest | sample
    sample_keep: int = 2              # with `sample`: keep 1 of N on pressure
    flush_timeout_s: float = 10.0
    retry_limit: int = 3
    # Wire aggregation: each sender wake-up coalesces every record already
    # queued (up to this many) into one batched frame — one msgpack frame,
    # one zstd pass, one Endpoint.push per batch instead of per record.
    # 1 disables coalescing (seed per-record framing).  This seeds each
    # sender's mutable ``batch_cap``.
    max_batch_records: int = 32
    delta_encode: bool = False        # delta-vs-previous-step in batch frames
    # Delivery guarantee.  "exactly-once" logs every record to a per-group
    # write-ahead segment (runtime.wal) before it ships: the WAL replaces
    # the sender queue, endpoints dedupe on frame seq, and unacked tails
    # replay across endpoint failover and broker restarts.  Requires
    # backpressure="block" (a drop policy contradicts the guarantee).
    delivery: str = "at-most-once"    # at-most-once | exactly-once
    wal_capacity_bytes: int = 16 << 20  # per-group WAL byte bound
    # Sharded fan-in: the broker splits into this many group-owning shards
    # (group g lives on shard g % n_shards), each with its own endpoint
    # ring, WAL segments, and sender stats, behind a thin routing layer.
    # 1 keeps the paper's single fan-in.  Clamped to n_groups.
    n_shards: int = 1
    # ---- multi-tenant QoS admission ------------------------------------
    # Active only when the Broker is built with a TenantRegistry (and the
    # backpressure policy is not "block"); plain deployments are untouched.
    # Parking starts when a shard's queued records cross high_water_frac of
    # its aggregate queue capacity; parked traffic re-admits once the
    # sender's own queue falls to low_water_frac of its capacity.
    high_water_frac: float = 0.75
    low_water_frac: float = 0.25
    park_capacity: int | None = None  # parked records/sender (None: queue_capacity)


@dataclass
class BrokerStats:
    """Merged, read-only view over the per-sender counters (``Broker.stats``
    builds a fresh one per read)."""

    written: int = 0
    sent: int = 0                     # records delivered
    frames_sent: int = 0              # wire frames pushed (≤ sent)
    dropped: int = 0
    rerouted: int = 0
    bytes_sent: int = 0
    send_errors: int = 0
    # frames given up on (at-most-once retry exhaustion, or an exactly-once
    # drain whose endpoints were all dead past the flush timeout) — always
    # paired with a RuntimeWarning, never silent
    frames_abandoned: int = 0
    # exactly-once replay traffic: frames/records re-shipped from the WAL
    # after a failover or restart (also counted in frames_sent/sent)
    frames_replayed: int = 0
    records_replayed: int = 0
    # Effective deployment shape: a connect-time plan that asks for more
    # groups than there are endpoints is silently shrunk; these two fields
    # make that visible (planned != effective ⇒ mis-sized deployment).
    planned_groups: int = 0
    effective_groups: int = 0
    # per-tenant loss ledger (tenant -> counters, see repro.tenancy.ledger);
    # empty unless the broker was built with a TenantRegistry
    tenants: dict = field(default_factory=dict)


_COUNTER_FIELDS = ("written", "sent", "frames_sent", "dropped", "rerouted",
                   "bytes_sent", "send_errors", "frames_abandoned",
                   "frames_replayed", "records_replayed")


class _SenderStats:
    """Lock-guarded per-sender counters.  One instance per ``_GroupSender``;
    the producer threads (submit/submit_batch) and the sender thread mutate
    it under ``lock``, so reads via ``snapshot()`` are exact."""

    __slots__ = ("lock", "written", "sent", "frames_sent", "dropped",
                 "rerouted", "bytes_sent", "send_errors", "frames_abandoned",
                 "frames_replayed", "records_replayed", "tenants")

    def __init__(self):
        self.lock = threading.Lock()
        for f in _COUNTER_FIELDS:
            setattr(self, f, 0)
        # tenant -> counter dict (repro.tenancy.ledger.TENANT_COUNTERS);
        # stays empty unless the QoS plane is active
        self.tenants: dict[str, dict[str, int]] = {}

    def add(self, **deltas: int) -> None:
        with self.lock:
            for name, d in deltas.items():
                setattr(self, name, getattr(self, name) + d)

    def add_tenant(self, tenant: str, **deltas: int) -> None:
        with self.lock:
            c = self.tenants.setdefault(tenant, {})
            for name, d in deltas.items():
                c[name] = c.get(name, 0) + d

    def snapshot(self) -> dict:
        with self.lock:
            return {f: getattr(self, f) for f in _COUNTER_FIELDS}

    def tenant_snapshot(self) -> dict[str, dict[str, int]]:
        with self.lock:
            return {n: dict(c) for n, c in self.tenants.items()}


class _GroupSender(threading.Thread):
    """One background sender per producer group (paper: one TCP stream per
    group to its designated endpoint)."""

    def __init__(self, group_id: int, endpoints: list[Transport], primary: int,
                 cfg: BrokerConfig, clock: Clock | None = None, *,
                 wal: WalSegment | None = None,
                 go: threading.Event | None = None,
                 tenants: TenantRegistry | None = None):
        super().__init__(daemon=True, name=f"broker-g{group_id}")
        self.group_id = group_id
        self.endpoints = endpoints            # anything satisfying Transport
        self.primary = primary
        self.cfg = cfg
        self.clock = ensure_clock(clock)
        # QoS plane: with a registry, admission becomes priority-aware —
        # parkable tenants hold out of the shared queue under backlog
        # pressure and eviction sheds the lowest priority class first
        self.tenants = tenants
        self._shard: _BrokerShard | None = None   # set by the owning shard
        self._park: deque = deque()               # parked items, FIFO
        self._park_records = 0
        self._park_tenants: dict[str, int] = {}   # currently parked, per tenant
        self._q_tenants: dict[str, int] = {}      # currently queued, per tenant
        # each sender owns its counters; Broker.stats merges them on read
        self.stats = _SenderStats()
        # mutable wire-aggregation cap, adapted at runtime from queue depth
        # by the elasticity controller (seeded from the static config)
        self.batch_cap = max(1, cfg.max_batch_records)
        self.q: queue.Queue = queue.Queue(maxsize=cfg.queue_capacity)
        # record-accurate backlog: q.qsize() counts queue ITEMS, but a
        # submit_batch item is a whole record list — telemetry reading
        # qsize() under-reports by the batch width (a "depth 2" queue can
        # hide hundreds of records), which starves the controller's
        # backlog/shard signals.  This counter tracks records admitted and
        # not yet sent (including the chunk the sender is pacing out).
        self._q_records = 0
        self._q_lock = threading.Lock()
        # NB: must not be named `_stop` — that would shadow Thread._stop(),
        # which threading.join() calls on finished threads
        self._stop_evt = threading.Event()
        self._sample_lock = threading.Lock()
        self._sample_ctr = 0
        # -- exactly-once state ------------------------------------------
        # In exactly-once mode the WAL *is* the queue: producers append,
        # this thread ships through the segment's `shipped` pointer, so
        # wire order == seq order by construction.
        self.wal = wal
        self._killed = False                  # simulated crash (kill())
        # held shut while a restored Session rebuilds plan/ledger state;
        # Broker.release() opens it (normal construction pre-sets it)
        if go is None:                        # standalone sender: open gate
            go = threading.Event()
            go.set()
        self._go = go
        self._replay_horizon = 0
        if wal is not None:
            # entries adopted from a previous broker incarnation replay
            # first; count acks at-or-below this horizon as replay traffic
            self._replay_horizon = wal.last_seq
            wal.rewind_shipped()

    @property
    def _exactly_once(self) -> bool:
        return self.wal is not None

    def set_batch_cap(self, cap: int) -> int:
        self.batch_cap = max(1, int(cap))
        return self.batch_cap

    def _q_add(self, n: int, tenant: str | None = None) -> None:
        with self._q_lock:
            self._q_records += n
            if tenant is not None and self.tenants is not None:
                self._q_tenants[tenant] = self._q_tenants.get(tenant, 0) + n

    def _q_sub_chunk(self, recs: list[StreamRecord]) -> None:
        """Decrement the record backlog for a sent/abandoned chunk, split by
        tenant when the QoS plane is active (a coalesced chunk can mix
        tenants across queue items)."""
        if self.tenants is None:
            self._q_add(-len(recs))
            return
        counts: dict[str, int] = {}
        for r in recs:
            counts[r.tenant] = counts.get(r.tenant, 0) + 1
        with self._q_lock:
            self._q_records -= len(recs)
            for t, m in counts.items():
                self._q_tenants[t] = self._q_tenants.get(t, 0) - m

    def queued_records(self) -> int:
        """Records in the shared queue only (parked records excluded) —
        the high-water signal that drives parking."""
        with self._q_lock:
            return self._q_records

    def _count_chunk_tenants(self, recs: list[StreamRecord],
                             counter: str) -> None:
        """Per-tenant accounting for a whole outbound chunk (no-op without
        the QoS plane)."""
        if self.tenants is None:
            return
        counts: dict[str, int] = {}
        for r in recs:
            counts[r.tenant] = counts.get(r.tenant, 0) + 1
        for t, m in counts.items():
            self.stats.add_tenant(t, **{counter: m})

    def _sample_tick(self) -> bool:
        """1-of-N admission under `sample` pressure, race-free."""
        with self._sample_lock:
            self._sample_ctr += 1
            return self._sample_ctr % self.cfg.sample_keep == 0

    # ---- producer side ------------------------------------------------
    def _evict_one(self) -> bool:
        """Drop the oldest queue item, counting its records (items are single
        records or submit_batch lists)."""
        try:
            evicted = self.q.get_nowait()
        except queue.Empty:
            return False
        n = len(evicted) if isinstance(evicted, list) else 1
        self._q_add(-n)
        self.stats.add(dropped=n)
        return True

    # ---- QoS admission (active only with a TenantRegistry) -------------
    @staticmethod
    def _item_meta(item) -> tuple[int, str]:
        """(record count, tenant) of a queue item — items are single records
        or single-tenant submit_batch lists."""
        if isinstance(item, list):
            return len(item), item[0].tenant
        return 1, item.tenant

    def _over_high_water(self) -> bool:
        """Shard-level pressure signal: queued records (across the owning
        shard's senders) at or past high_water_frac of aggregate capacity."""
        if self._shard is not None:
            depth = self._shard.queue_records()
            n_senders = len(self._shard.senders)
        else:
            depth, n_senders = self.queued_records(), 1
        return depth >= self.cfg.high_water_frac * \
            self.cfg.queue_capacity * max(1, n_senders)

    def _evict_for(self, priority: int) -> bool:
        """Evict the oldest queue item of the LOWEST evictable priority class
        (<= the incoming record's class).  Never touches a higher-priority
        tenant: if only higher classes are queued, the caller's record is the
        one that gets dropped."""
        with self.q.mutex:
            best_i: int | None = None
            best_pr: int | None = None
            for i, it in enumerate(self.q.queue):
                _, t = self._item_meta(it)
                pr = self.tenants.priority(t)
                if pr <= priority and (best_pr is None or pr < best_pr):
                    best_i, best_pr = i, pr
            if best_i is None:
                return False
            victim = self.q.queue[best_i]
            del self.q.queue[best_i]
            self.q.not_full.notify()
        n, vt = self._item_meta(victim)
        self._q_add(-n, vt)
        self.stats.add(dropped=n)
        self.stats.add_tenant(vt, evicted=n)
        return True

    def _park_item(self, item, n: int, tenant: str) -> None:
        """Admit a parkable tenant's item into the bounded side-park instead
        of the shared queue.  Overflow evicts the oldest parked item —
        counted per tenant, never silent."""
        cap = self.cfg.park_capacity or self.cfg.queue_capacity
        evictions: list[tuple[str, int]] = []
        with self._q_lock:
            self._park.append(item)
            self._park_records += n
            self._park_tenants[tenant] = self._park_tenants.get(tenant, 0) + n
            while self._park_records > cap and len(self._park) > 1:
                old = self._park.popleft()
                m, ot = self._item_meta(old)
                self._park_records -= m
                self._park_tenants[ot] = self._park_tenants.get(ot, 0) - m
                evictions.append((ot, m))
        self.stats.add_tenant(tenant, admitted=n, parked_total=n)
        for ot, m in evictions:
            self.stats.add(dropped=m)
            self.stats.add_tenant(ot, evicted=m)

    def _maybe_unpark(self) -> None:
        """Re-admit parked items (oldest first) once the sender's own queue
        has fallen to the low-water mark — or unconditionally during a
        stop-drain, so parked records flush rather than strand."""
        if self._park_records == 0:
            return
        low = self.cfg.low_water_frac * self.cfg.queue_capacity
        draining = self._stop_evt.is_set()
        while True:
            with self._q_lock:
                if not self._park:
                    return
                if not draining and self._q_records > low:
                    return
                item = self._park[0]
                try:
                    self.q.put_nowait(item)
                except queue.Full:
                    return
                self._park.popleft()
                n, t = self._item_meta(item)
                self._park_records -= n
                self._park_tenants[t] = self._park_tenants.get(t, 0) - n
                self._q_records += n
                self._q_tenants[t] = self._q_tenants.get(t, 0) + n
            self.stats.add_tenant(t, unparked=n)

    def _submit_qos(self, item, n: int, tenant: str) -> int:
        """Priority-aware admission, replacing the anonymous drop policy when
        the QoS plane is active: parkable tenants side-park under shard
        backlog pressure, and on a full queue the lowest priority class at or
        below the incoming record's is evicted first."""
        st = self.stats
        if self.cfg.backpressure == "block":
            # block semantics keep their no-shed guarantee; only account
            self.clock.queue_put(self.q, item)
            self._q_add(n, tenant)
            st.add_tenant(tenant, admitted=n)
            return n
        if self.tenants.parks(tenant) and (
                self._park_tenants.get(tenant, 0) > 0
                or self._over_high_water()):
            # once a tenant has parked records, later ones park too —
            # re-admission is FIFO, so per-stream order is preserved
            self._park_item(item, n, tenant)
            return n
        try:
            self.q.put_nowait(item)
            self._q_add(n, tenant)
            st.add_tenant(tenant, admitted=n)
            return n
        except queue.Full:
            pass
        if self._evict_for(self.tenants.priority(tenant)):
            try:
                self.q.put_nowait(item)
                self._q_add(n, tenant)
                st.add_tenant(tenant, admitted=n)
                return n
            except queue.Full:
                pass
        st.add(dropped=n)
        st.add_tenant(tenant, dropped=n)
        return 0

    def _submit_eo(self, recs: list[StreamRecord]) -> int:
        """Exactly-once admission: log each record to the WAL before it can
        ship.  Blocks (bounded-WAL backpressure) until space frees.  A
        *killed* sender still appends — the WAL outlives this broker
        incarnation and its successor ships the record — but a gracefully
        stopped one refuses new records.  ``written`` is not counted here:
        in exactly-once mode it derives from the WAL itself (see
        :meth:`stats_snapshot`), the one ledger producers share across
        broker incarnations."""
        n = 0
        for rec in recs:
            blob = encode(rec, compress=self.cfg.compress)
            while True:
                if self._stop_evt.is_set() and not self._killed:
                    return n                  # graceful shutdown: refuse
                if self.wal.try_append(blob, rec) is not None:
                    break
                self.clock.sleep(0.005)       # WAL full: bounded backpressure
            if self.tenants is not None:
                # counted at append: try_append is atomic, so the per-tenant
                # admitted count is exact across broker incarnations
                self.stats.add_tenant(rec.tenant, admitted=1)
            n += 1
        return n

    def submit(self, rec: StreamRecord) -> bool:
        if self._exactly_once:
            return self._submit_eo([rec]) == 1
        self.stats.add(written=1)
        if self.tenants is not None:
            return self._submit_qos(rec, 1, rec.tenant) == 1
        if self.cfg.backpressure == "block":
            with span("broker.enqueue", records=1):
                self.clock.queue_put(self.q, rec)
            self._q_add(1)
            return True
        try:
            self.q.put_nowait(rec)
            self._q_add(1)
            return True
        except queue.Full:
            if self.cfg.backpressure == "drop_oldest":
                self._evict_one()
                try:
                    self.q.put_nowait(rec)
                    self._q_add(1)
                    return True
                except queue.Full:
                    self.stats.add(dropped=1)
                    return False
            # sample: keep 1 of N while under pressure
            if self._sample_tick():
                if self._evict_one():
                    try:
                        self.q.put_nowait(rec)
                        self._q_add(1)
                        return True
                    except queue.Full:
                        pass
            self.stats.add(dropped=1)
            return False

    def submit_batch(self, recs: list[StreamRecord]) -> int:
        """Enqueue a pre-batched record list as ONE queue item, so the whole
        batch leaves as (at most) one wire frame regardless of sender-thread
        timing — this is what gives ``FieldHandle.write_batch`` its ≤ one
        frame per (field, group) guarantee.  Returns #records accepted."""
        if not recs:
            return 0
        if self._exactly_once:
            return self._submit_eo(list(recs))
        self.stats.add(written=len(recs))
        with span("broker.enqueue", records=len(recs)):
            return self._admit_batch(list(recs))

    def _admit_batch(self, item: list[StreamRecord]) -> int:
        """Queue one batch under the backpressure policy (or the QoS
        plane's admission); returns #records accepted."""
        if self.tenants is not None:
            # queue items must be single-tenant for priority eviction and
            # park accounting; mixed batches split (rare — FieldHandle and
            # Broker.write_batch are single-tenant per call)
            if len({r.tenant for r in item}) == 1:
                return self._submit_qos(item, len(item), item[0].tenant)
            total = 0
            by_tenant: dict[str, list[StreamRecord]] = {}
            for r in item:
                by_tenant.setdefault(r.tenant, []).append(r)
            for tname, sub in by_tenant.items():
                total += self._submit_qos(sub, len(sub), tname)
            return total
        if self.cfg.backpressure == "block":
            self.clock.queue_put(self.q, item)
            self._q_add(len(item))
            return len(item)
        try:
            self.q.put_nowait(item)
            self._q_add(len(item))
            return len(item)
        except queue.Full:
            if self.cfg.backpressure == "drop_oldest":
                self._evict_one()
                try:
                    self.q.put_nowait(item)
                    self._q_add(len(item))
                    return len(item)
                except queue.Full:
                    pass
            elif self.cfg.backpressure == "sample":
                # same 1-of-N policy as submit(), at batch granularity
                if self._sample_tick() and self._evict_one():
                    try:
                        self.q.put_nowait(item)
                        self._q_add(len(item))
                        return len(item)
                    except queue.Full:
                        pass
            # overflow: the whole batch is one unit — drop it whole
            self.stats.add(dropped=len(item))
            return 0

    # ---- sender loop ---------------------------------------------------
    def run(self):
        try:
            if not self._go.is_set():
                self.clock.wait_event(self._go)
            if self._exactly_once:
                self._run_wal()
            else:
                self._run_queue()
        finally:
            # leave the clock's schedule on exit so a virtual schedule never
            # waits out the dead-participant watchdog for this thread
            self.clock.detach()

    def _run_queue(self):
        """At-most-once drain: each wake-up takes every queued record (up to
        ``batch_cap``, re-read per wake-up so the controller can retune it
        live) and ships them as one batched wire frame, so a burst of writes
        pays framing/compression/bandwidth-model cost once per batch, not
        once per record.  Queue items are single records (``submit``) or
        record lists (``submit_batch``); an oversized list is chunked at the
        cap."""
        while not self._killed \
                and (not self._stop_evt.is_set() or not self.q.empty()
                     or self._park_records > 0):
            if self.tenants is not None:
                self._maybe_unpark()
            cap = max(1, self.batch_cap)
            item = self.clock.queue_get(self.q, timeout=0.05)
            if item is None:
                continue
            recs = list(item) if isinstance(item, list) else [item]
            while len(recs) < cap:
                try:
                    nxt = self.q.get_nowait()
                except queue.Empty:
                    break
                recs.extend(nxt if isinstance(nxt, list) else [nxt])
            for i in range(0, len(recs), cap):
                chunk = recs[i:i + cap]
                with span("broker.encode", records=len(chunk)) as sp:
                    if len(chunk) == 1:
                        blob = encode(chunk[0], compress=self.cfg.compress)
                    else:
                        blob = encode_batch(chunk, compress=self.cfg.compress,
                                            delta=self.cfg.delta_encode)
                    sp.set_metadata(bytes=len(blob))
                sent = self._send(blob)
                # decremented only now: records stay on the backlog while
                # the sender paces the frame out through the endpoint's
                # bandwidth model — that wait IS the congestion the
                # controller's backlog signals are meant to see
                self._q_sub_chunk(chunk)
                if sent:
                    self.stats.add(sent=len(chunk), frames_sent=1,
                                   bytes_sent=len(blob))
                    self._count_chunk_tenants(chunk, "sent")
                else:
                    # retries exhausted: the frame is gone.  Loudly — silent
                    # loss is indistinguishable from a broken pipeline.
                    self.stats.add(dropped=len(chunk), frames_abandoned=1)
                    self._count_chunk_tenants(chunk, "evicted")
                    warnings.warn(
                        f"broker group {self.group_id}: abandoned a frame of "
                        f"{len(chunk)} record(s) after {self.cfg.retry_limit} "
                        "failed sends (at-most-once delivery: records are "
                        "lost; use delivery='exactly-once' for replay)",
                        RuntimeWarning, stacklevel=2)

    def _run_wal(self):
        """Exactly-once ship loop: fetch unshipped WAL entries in seq order,
        wrap them with their seq range, and retry each frame until an
        endpoint acks it — head-of-line blocking is intentional (acks are
        contiguous).  Entries adopted from a dead broker incarnation (seq <=
        the replay horizon) are replay traffic; the receive-side SeqLedger
        makes re-sends idempotent."""
        wal = self.wal
        while not self._killed:
            entries = wal.fetch_unshipped(max(1, self.batch_cap))
            if not entries:
                if self._stop_evt.is_set() and wal.unshipped_count() == 0:
                    return
                self.clock.sleep(0.02)
                continue
            if len(entries) == 1:
                blob = entries[0].blob        # reuse the logged encoding
                recs_n = 1
            else:
                recs = [e.rec if e.rec is not None else decode(e.blob)
                        for e in entries]
                with span("broker.encode", records=len(recs)) as sp:
                    blob = encode_batch(recs, compress=self.cfg.compress,
                                        delta=self.cfg.delta_encode)
                    sp.set_metadata(bytes=len(blob))
                recs_n = len(recs)
            wire = wrap_seq(entries[0].seq, recs_n, blob)
            if not self._ship(wire, entries):
                return                        # killed mid-retry

    def _ship(self, wire: bytes, entries) -> bool:
        """Retry one wrapped frame until acked (exactly-once never drops on
        its own).  During a stop-drain with every endpoint dead we abandon
        after the flush timeout — loudly — instead of hanging teardown."""
        last = entries[-1].seq
        n = len(entries)
        deadline = None
        while True:
            if self._send(wire):
                self.wal.ack(last)
                replayed = sum(1 for e in entries
                               if e.seq <= self._replay_horizon)
                extra = {"frames_replayed": 1, "records_replayed": replayed} \
                    if replayed else {}
                self.stats.add(sent=n, frames_sent=1, bytes_sent=len(wire),
                               **extra)
                if self.tenants is not None:
                    self._count_chunk_tenants(
                        [e.rec if e.rec is not None else decode(e.blob)
                         for e in entries], "sent")
                return True
            if self._killed:
                return False
            if self._stop_evt.is_set():
                if deadline is None:
                    deadline = self.clock.now() + self.cfg.flush_timeout_s
                elif self.clock.now() >= deadline:
                    self.wal.ack(last)        # consume so teardown can exit
                    self.stats.add(dropped=n, frames_abandoned=1)
                    if self.tenants is not None:
                        self._count_chunk_tenants(
                            [e.rec if e.rec is not None else decode(e.blob)
                             for e in entries], "evicted")
                    warnings.warn(
                        f"broker group {self.group_id}: abandoned a frame of "
                        f"{n} record(s) at shutdown — no endpoint recovered "
                        f"within flush_timeout_s={self.cfg.flush_timeout_s}",
                        RuntimeWarning, stacklevel=2)
                    return True
            self.clock.sleep(0.05)

    def _send(self, blob: bytes) -> bool:
        """Send to primary; on failure re-route to the next healthy endpoint
        (pure remapping — the paper's grouping makes failover trivial)."""
        n = len(self.endpoints)
        with span("broker.send", bytes=len(blob)):
            for attempt in range(self.cfg.retry_limit):
                ep = self.endpoints[(self.primary + attempt) % n]
                try:
                    if ep.healthy():
                        ep.push(self.group_id, blob)
                        if attempt > 0:
                            self.stats.add(rerouted=1)
                            self.primary = (self.primary + attempt) % n
                        return True
                except Exception:
                    pass
                self.stats.add(send_errors=1)
            return False

    @staticmethod
    def _endpoint_load(ep) -> float | None:
        """Routing-load estimate for reroute target selection: buffered
        backlog plus current ingest rate.  None when the binding exposes no
        telemetry (bare transports) — callers fall back to ring order."""
        handle = getattr(ep, "handle", None)
        if handle is None:
            return None
        try:
            return float(handle.pending()) + float(handle.ingest_rate())
        except Exception:
            return None

    def reroute(self) -> int | None:
        """Proactively move the primary off a known-dead endpoint (the
        controller's FailureDetector path) instead of waiting for the next
        send to burn retries.  Returns the new primary index, or None when no
        healthy endpoint exists.

        Target selection is least-loaded, not first-surviving: when an
        endpoint dies mid-spike, every orphaned group rerouting to the same
        "next" survivor would dogpile it while emptier endpoints idle.
        Candidates are ranked by pending+ingest telemetry; ties (and
        endpoints with no telemetry) resolve in ring order, which keeps the
        choice deterministic."""
        n = len(self.endpoints)
        candidates: list[tuple[int, float | None]] = []
        for shift in range(1, n + 1):
            idx = (self.primary + shift) % n
            try:
                if not self.endpoints[idx].healthy():
                    continue
            except Exception:
                continue
            candidates.append((idx, self._endpoint_load(self.endpoints[idx])))
        if not candidates:
            return None
        if any(load is None for _, load in candidates):
            best = candidates[0][0]       # no telemetry: legacy ring order
        else:
            best = min(candidates, key=lambda c: c[1])[0]
        if best != self.primary:
            self.primary = best
            self.stats.add(rerouted=1)
        return best

    def backlog(self) -> int:
        """Records admitted but not yet handed to the wire.  Counted in
        RECORDS, not queue items: ``q.qsize()`` would report a whole
        ``submit_batch`` list as depth 1, hiding the real backlog from the
        controller's ``backlog_high`` / ``shard_backlog_high`` signals."""
        if self._exactly_once:
            return self.wal.unshipped_count()
        with self._q_lock:
            # parked records are admitted-but-unsent: they belong on the
            # backlog (flush() must wait for the park to drain)
            return self._q_records + self._park_records

    def tenant_backlog(self) -> tuple[dict[str, int], dict[str, int]]:
        """(queued, parked) records per tenant — live gauges for telemetry
        and ledger-closure checks."""
        with self._q_lock:
            return dict(self._q_tenants), dict(self._park_tenants)

    def stats_snapshot(self) -> dict:
        snap = self.stats.snapshot()
        if self._exactly_once:
            # written derives from the WAL (total ever appended to this
            # group's segment): producers may append across broker
            # incarnations — racing a restart — and the segment is the one
            # ledger they all share, so it is the only exact count
            snap["written"] = self.wal.points()["last"]
        return snap

    def stop(self, timeout: float):
        self._stop_evt.set()
        self._go.set()                        # never strand a paused sender
        # clock-mediated join: under VirtualClock a native join would stall
        # the schedule (the joiner is runnable but blocked outside the clock)
        self.clock.join(self, timeout=timeout)

    def kill(self):
        """Simulated crash: stop immediately without draining.  In
        exactly-once mode unacked WAL entries survive in the (external)
        WalStore and replay in the next broker incarnation; in at-most-once
        mode queued records are lost, exactly as a real crash would lose
        them."""
        self._killed = True
        self._stop_evt.set()
        self._go.set()                        # never strand a paused sender
        self.clock.join(self, timeout=5.0)


class _BrokerShard:
    """One group-owning shard of the sharded fan-in.

    A shard runs the :class:`_GroupSender` threads for its groups against
    its OWN endpoint ring (a shard-local list: senders size their failover
    ring from it, and :meth:`attach_endpoint` grows it independently), and
    owns its groups' WAL segments and per-sender stats.  The :class:`Broker`
    above it is a thin routing layer — ``write``/``write_batch`` route by
    ``group % n_shards`` — so no producer ever funnels through a single
    fan-in lock or sender set."""

    def __init__(self, shard_id: int, groups: list[int],
                 endpoints: list[Transport], cfg: BrokerConfig,
                 clock: Clock, *, wal: WalStore | None,
                 go: threading.Event,
                 tenants: TenantRegistry | None = None):
        self.shard_id = shard_id
        self.cfg = cfg
        # shard-local ring: a copy, so each shard's failover surface and
        # dynamic attaches are its own (the router fans attaches out to
        # every shard in fleet order, keeping indices aligned)
        self.endpoints = list(endpoints)
        self.senders: dict[int, _GroupSender] = {}
        for g in groups:
            s = _GroupSender(g, self.endpoints, g % len(self.endpoints),
                             cfg, clock,
                             wal=wal.segment(g) if wal else None,
                             go=go, tenants=tenants)
            s._shard = self      # backref for the shard-level park signal
            clock.thread_started(s)
            s.start()
            self.senders[g] = s

    def attach_endpoint(self, ep: Transport) -> int:
        """Grow this shard's ring; returns the new shard-local index (equal
        to the fleet index when the router fans out in order)."""
        self.endpoints.append(ep)
        return len(self.endpoints) - 1

    def reroute_from_endpoint(self, endpoint_idx: int) -> int:
        """Re-point every one of this shard's groups whose primary is the
        dead endpoint.  Returns #groups rerouted."""
        n = 0
        for s in self.senders.values():
            if s.primary == endpoint_idx and s.reroute() is not None:
                n += 1
        return n

    def groups_on_endpoint(self, endpoint_idx: int) -> int:
        return sum(1 for s in self.senders.values()
                   if s.primary == endpoint_idx)

    def backlog(self) -> int:
        return sum(s.backlog() for s in self.senders.values())

    def queue_records(self) -> int:
        """Aggregate queued records across this shard's senders, excluding
        parks — the shard backlog that triggers QoS parking."""
        return sum(s.queued_records() for s in self.senders.values())

    def telemetry(self) -> dict:
        """Shard-level control-plane rollup — one row per shard in
        ``TelemetrySnapshot.shards``."""
        row = dict.fromkeys(_COUNTER_FIELDS, 0)
        depth = 0
        for s in self.senders.values():
            snap = s.stats_snapshot()
            for f in _COUNTER_FIELDS:
                row[f] += snap[f]
            depth += s.backlog()
        row.update(shard=self.shard_id, groups=len(self.senders),
                   queue_depth=depth, endpoints=len(self.endpoints))
        return row


class Broker:
    """Producer-side broker: one per job, shared by all local ranks.

    Internally sharded (``cfg.n_shards``): group-owning :class:`_BrokerShard`
    objects run the senders; this class is the routing layer that preserves
    the original single-broker surface (stats merge, group telemetry,
    flush/finalize/kill, WAL bookkeeping) on top of them."""

    def __init__(self, plan: GroupPlan, endpoints: list[Transport],
                 cfg: BrokerConfig | None = None, *,
                 clock: Clock | None = None, wal: WalStore | None = None,
                 paused: bool = False,
                 tenants: TenantRegistry | None = None):
        assert len(endpoints) >= plan.n_groups, (
            f"{plan.n_groups} groups need >= that many endpoints, "
            f"got {len(endpoints)}")
        self.plan = plan
        self.cfg = cfg or BrokerConfig()
        self.clock = ensure_clock(clock)
        self.endpoints = list(endpoints)
        self.planned_groups = plan.n_groups
        self.effective_groups = plan.n_groups
        self.schemas: dict[str, FieldSchema] = {}
        self.wal = wal
        # ---- multi-tenant QoS plane ------------------------------------
        self.tenants = tenants
        self._quota = TenantAdmission(tenants, self.clock) \
            if tenants is not None and tenants.has_quota else None
        self._quota_lock = threading.Lock()
        self._quota_rejected: dict[str, int] = {}
        if self.cfg.delivery == "exactly-once":
            if self.cfg.backpressure != "block":
                raise ValueError(
                    "delivery='exactly-once' requires backpressure='block' "
                    "(a drop policy contradicts the guarantee)")
            if self.wal is None:
                self.wal = WalStore(capacity_bytes=self.cfg.wal_capacity_bytes,
                                    queue_capacity=self.cfg.queue_capacity)
        elif self.wal is not None:
            raise ValueError("a WalStore requires delivery='exactly-once'")
        # `paused` holds the senders shut until release() — Session.restore
        # uses it so replay cannot race the plan/ledger state restore
        self._go = threading.Event()
        if not paused:
            self._go.set()
        self.n_shards = max(1, min(int(self.cfg.n_shards), plan.n_groups))
        self.shards: list[_BrokerShard] = []
        for sid in range(self.n_shards):
            groups = [g for g in range(plan.n_groups)
                      if g % self.n_shards == sid]
            self.shards.append(_BrokerShard(
                sid, groups, self.endpoints, self.cfg, self.clock,
                wal=self.wal, go=self._go, tenants=tenants))

    def shard_of(self, group: int) -> int:
        return group % self.n_shards

    def _sender(self, group: int) -> _GroupSender:
        return self.shards[group % self.n_shards].senders[group]

    @property
    def _senders(self) -> dict[int, _GroupSender]:
        """Merged group->sender view across shards (observability, tests,
        and whole-fleet operations; routing uses :meth:`_sender`)."""
        out: dict[int, _GroupSender] = {}
        for shard in self.shards:
            out.update(shard.senders)
        return out

    def release(self) -> None:
        """Open the sender gate of a ``paused=True`` broker (replay starts)."""
        self._go.set()

    # ---- observability --------------------------------------------------
    @property
    def stats(self) -> BrokerStats:
        """Exact merged view: per-sender counters aggregated on read."""
        out = BrokerStats(planned_groups=self.planned_groups,
                          effective_groups=self.effective_groups)
        for s in self._senders.values():
            snap = s.stats_snapshot()
            for f in _COUNTER_FIELDS:
                setattr(out, f, getattr(out, f) + snap[f])
            if self.tenants is not None:
                merge_counts(out.tenants, s.stats.tenant_snapshot())
        if self.tenants is not None:
            with self._quota_lock:
                rejected = dict(self._quota_rejected)
            merge_counts(out.tenants,
                         {t: {"quota_rejected": n}
                          for t, n in rejected.items()})
        return out

    def group_telemetry(self) -> list[dict]:
        """Per-group control-plane sample: live queue depth, batch cap,
        primary endpoint, and the sender's exact counters — the broker's
        contribution to ``runtime.telemetry.TelemetrySnapshot``."""
        rows = []
        for g, s in sorted(self._senders.items()):
            row = s.stats_snapshot()
            row.update(group=g, shard=self.shard_of(g),
                       queue_depth=s.backlog(),
                       queue_capacity=self.cfg.queue_capacity,
                       batch_cap=s.batch_cap, primary=s.primary)
            rows.append(row)
        return rows

    def shard_telemetry(self) -> list[dict]:
        """Per-shard control-plane rollup (one row per shard, ascending):
        queue depth, sender counters, ring size — the sharded fan-in's
        contribution to ``TelemetrySnapshot.shards``, which is what lets
        the controller see one hot shard inside an otherwise calm fleet."""
        return [shard.telemetry() for shard in self.shards]

    def tenant_telemetry(self) -> dict[str, dict]:
        """Per-tenant QoS rollup (counters + live queued/parked gauges) —
        the broker's contribution to ``TelemetrySnapshot.tenants``.  Empty
        without a TenantRegistry."""
        if self.tenants is None:
            return {}
        out: dict[str, dict] = {
            name: {"backlog": 0, "parked": 0} for name in self.tenants.names()}
        merged: dict[str, dict[str, int]] = {}
        for s in self._senders.values():
            merge_counts(merged, s.stats.tenant_snapshot())
            queued, parked = s.tenant_backlog()
            for t, m in queued.items():
                out.setdefault(t, {"backlog": 0, "parked": 0})["backlog"] += m
            for t, m in parked.items():
                row = out.setdefault(t, {"backlog": 0, "parked": 0})
                row["backlog"] += m
                row["parked"] += m
        with self._quota_lock:
            merge_counts(merged, {t: {"quota_rejected": n}
                                  for t, n in self._quota_rejected.items()})
        for t, counts in merged.items():
            out.setdefault(t, {"backlog": 0, "parked": 0}).update(counts)
        return out

    # ---- control-plane actuators ----------------------------------------
    def set_batch_cap(self, cap: int, group: int | None = None) -> None:
        """Retune wire aggregation at runtime (controller: deep queue ⇒
        bigger frames to amortize, shallow queue ⇒ small frames for
        latency).  ``group=None`` applies to every sender."""
        targets = self._senders.values() if group is None \
            else [self._sender(group)]
        for s in targets:
            s.set_batch_cap(cap)

    def reroute_group(self, group: int) -> int | None:
        """Move one group's primary to the next healthy endpoint."""
        return self._sender(group).reroute()

    def reroute_from_endpoint(self, endpoint_idx: int) -> int:
        """Detector-driven failover, fanned out shard by shard: every group
        whose primary is the dead endpoint is proactively re-pointed on its
        owning shard.  Returns #groups rerouted."""
        return sum(shard.reroute_from_endpoint(endpoint_idx)
                   for shard in self.shards)

    def groups_on_endpoint(self, endpoint_idx: int) -> int:
        """#groups whose primary currently targets this endpoint — the
        cloud capacity plane's drain gate (a node may only power off once
        this reaches zero and its endpoint queue is empty)."""
        return sum(shard.groups_on_endpoint(endpoint_idx)
                   for shard in self.shards)

    def attach_endpoint(self, ep: Transport) -> int:
        """Register a freshly provisioned endpoint fleet-wide: append to the
        router's list and fan out to every shard's ring in order, so the
        shard-local index equals the fleet index on all of them.  Senders
        size their failover ring from their shard's list per call, so the
        new slot becomes routable on the next send/reroute.  Returns the
        new endpoint's fleet index."""
        self.endpoints.append(ep)
        fleet_idx = len(self.endpoints) - 1
        for shard in self.shards:
            idx = shard.attach_endpoint(ep)
            assert idx == fleet_idx, (
                f"shard {shard.shard_id} ring diverged: local idx {idx} != "
                f"fleet idx {fleet_idx}")
        return fleet_idx

    # -- the paper's three-call API surface lives in core.api ------------
    def register(self, schema: FieldSchema) -> None:
        self.schemas[f"{schema.field_name}/g{schema.group_id}"] = schema

    def _check_tenant(self, tenant: str) -> str:
        if self.tenants is not None and tenant not in self.tenants:
            raise ValueError(f"unknown tenant {tenant!r}: declare it in the "
                             "TenantRegistry before writing")
        return tenant

    def _quota_take(self, tenant: str, n: int) -> int:
        """Front-door rate quota: grant up to n admission tokens; the
        rejected remainder is counted per tenant, never silent."""
        if self._quota is None:
            return n
        granted = self._quota.take(tenant, n)
        if granted < n:
            with self._quota_lock:
                self._quota_rejected[tenant] = \
                    self._quota_rejected.get(tenant, 0) + (n - granted)
        return granted

    def write(self, field_name: str, rank: int, step: int,
              payload: np.ndarray, *, t: float | None = None,
              tenant: str = "default") -> bool:
        """``t`` overrides the event timestamp (default: the clock's now).
        Producers that know their simulation time should pass it — event
        time then survives backpressure stalls and crash-recovery delays,
        keeping window membership identical across replays.  ``tenant``
        tags the record with its QoS class (repro.tenancy)."""
        self._check_tenant(tenant)
        if self._quota_take(tenant, 1) < 1:
            return False
        g = self.plan.group_of(rank)
        rec = StreamRecord(field_name=field_name, group_id=g, rank=rank,
                           step=step, payload=np.asarray(payload),
                           t_generated=self.clock.now() if t is None
                           else float(t), tenant=tenant)
        return self._sender(g).submit(rec)

    def write_batch(self, field_name: str, ranks, steps, payloads, *,
                    t: float | None = None, tenant: str = "default") -> int:
        """Submit many records at once, one aggregated queue item per group,
        so each group ships the batch as (at most) one wire frame.  ``ranks``,
        ``steps`` and ``payloads`` are aligned sequences; returns #records
        accepted (backpressure may drop whole per-group batches).  ``t``:
        explicit event timestamp, as in :meth:`write`.  ``tenant`` applies
        to every record in the call; the rate quota (if any) admits a prefix
        and counts the rejected remainder."""
        self._check_tenant(tenant)
        triplets = list(zip(ranks, steps, payloads))
        granted = self._quota_take(tenant, len(triplets))
        by_group: dict[int, list[StreamRecord]] = {}
        now = self.clock.now() if t is None else float(t)
        for rank, step, payload in triplets[:granted]:
            g = self.plan.group_of(rank)
            by_group.setdefault(g, []).append(
                StreamRecord(field_name=field_name, group_id=g, rank=rank,
                             step=step, payload=np.asarray(payload),
                             t_generated=now, tenant=tenant))
        return sum(self._sender(g).submit_batch(recs)
                   for g, recs in by_group.items())

    def flush(self, timeout: float | None = None) -> None:
        """Block until every written record is delivered (or dropped/errored
        out) — exact accounting, no queue-emptiness race.

        Gives up early only when *this* flush has watched a full retry budget
        burn with zero delivery progress.  The error window is measured as a
        delta from the start of the flush (and restarts whenever a record is
        delivered or dropped), so error counts accumulated during a past
        failure episode cannot trigger a return while records written after
        the endpoints recovered are still in flight."""
        deadline = self.clock.now() + (timeout or self.cfg.flush_timeout_s)
        if self.cfg.delivery == "exactly-once":
            # the WAL is the exact in-flight ledger: flushed means every
            # appended record is acked by an endpoint.  No early give-up —
            # an endpoint may come back, and giving up early would lie.
            while self.clock.now() < deadline:
                if self.wal.unacked_records() == 0:
                    return
                self.clock.sleep(0.01)
            return
        st = self.stats
        err_mark = st.send_errors
        progress_mark = st.sent + st.dropped
        while self.clock.now() < deadline:
            st = self.stats
            undelivered = st.written - st.sent - st.dropped
            if undelivered <= 0 \
                    and all(s.backlog() == 0 for s in self._senders.values()):
                return
            delivered = st.sent + st.dropped
            if delivered != progress_mark:     # progress: restart error window
                progress_mark = delivered
                err_mark = st.send_errors
            elif st.send_errors - err_mark >= \
                    self.cfg.retry_limit * max(undelivered, 1):
                return  # endpoints down and this flush's retries exhausted
            self.clock.sleep(0.01)

    def finalize(self) -> BrokerStats:
        self.flush()
        for s in self._senders.values():
            s.stop(timeout=self.cfg.flush_timeout_s)
        return self.stats

    # ---- exactly-once lifecycle -----------------------------------------
    def kill(self) -> BrokerStats:
        """Simulated hard crash: every sender stops without draining (see
        _GroupSender.kill).  Returns the final stats of this incarnation so
        a replacement broker can fold them into its accounting."""
        for s in self._senders.values():
            s.kill()
        return self.stats

    def commit_wal(self) -> dict[int, dict]:
        """Checkpoint hook: mark everything appended so far as committed
        (the caller guarantees the pipeline is quiescent, i.e. it is all
        acked and applied) and trim.  Returns post-commit trim points."""
        out = {}
        for g, s in self._senders.items():
            if s.wal is not None:
                s.wal.commit(s.wal.last_seq)
                out[g] = s.wal.points()
        return out

    def wal_points(self) -> dict[int, dict]:
        """Read-only per-group WAL trim points ({} in at-most-once mode)."""
        return self.wal.points() if self.wal is not None else {}

    def unacked_records(self) -> int:
        return self.wal.unacked_records() if self.wal is not None else 0
