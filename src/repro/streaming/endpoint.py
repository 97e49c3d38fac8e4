"""Cloud endpoints — the Redis-server stand-ins of the paper's Fig 2.

Each endpoint accepts framed stream records pushed by producer groups and
holds them in per-stream buffers (stream = one producer rank's trajectory,
exactly like the paper's per-MPI-process Redis streams).  Includes a simple
inbound-bandwidth model (for the Fig-7 throughput study), health/failure
injection (for failover tests), and drain APIs for the micro-batcher.
"""
from __future__ import annotations

import threading
from collections import defaultdict, deque

from repro.core.records import StreamRecord, decode_any, unwrap_seq
from repro.runtime.clock import Clock, ensure_clock
from repro.runtime.telemetry import span
from repro.runtime.wal import SeqLedger


class Endpoint:
    def __init__(self, name: str = "ep0", *, inbound_bw: float | None = None,
                 port: int = 6379, clock: Clock | None = None,
                 ledger: SeqLedger | None = None):
        self.name = name
        self.port = port
        self.inbound_bw = inbound_bw          # bytes/s, None = unmetered
        self.clock = ensure_clock(clock)
        self._streams: dict[str, deque] = defaultdict(deque)
        self._lock = threading.Lock()
        self._healthy = True
        # cloud lifecycle (repro.cloud): draining = unhealthy to *senders*
        # (nothing new is routed here) but still accepting in-flight frames
        # and still alive to the failure detector; retired = deliberately
        # powered off — skipped by heartbeat pumps entirely
        self._draining = False
        self._retired = False
        self.bytes_in = 0
        self.records_in = 0
        self.frames_in = 0            # wire frames (batched: frames < records)
        # exactly-once receive side: a SeqLedger (shared by the whole
        # endpoint fleet) dedupes replayed frames on their WAL seq range
        self.ledger = ledger
        self.frames_deduped = 0       # wholly-duplicate frames skipped
        self.records_deduped = 0      # leading duplicate records skipped
        # fault injection: silently discard the next N accepted frames (the
        # scenario runner's lossy-transport model); counters make the loss
        # auditable so chaos tests can assert "no loss beyond what was
        # injected + what the drop policy allows"
        self._drop_frames = 0
        self.frames_dropped = 0
        self.records_dropped = 0
        self._bw_debt = 0.0
        self._bw_t = self.clock.now()
        # rolling ingest window for the telemetry bus: (t, n_records) per
        # push, trimmed to the rate window on read
        self._ingest_win: deque = deque(maxlen=4096)

    # ---- producer side --------------------------------------------------
    def healthy(self) -> bool:
        return self._healthy and not self._draining

    def fail(self):
        self._healthy = False

    def recover(self):
        self._healthy = True

    # ---- cloud lifecycle (drain-before-poweroff) -------------------------
    @property
    def draining(self) -> bool:
        return self._draining

    @property
    def retired(self) -> bool:
        return self._retired

    def begin_drain(self) -> None:
        """Stop being a routing target while the buffered backlog empties.
        ``push`` still accepts frames already in flight — drain is not
        failure, so nothing is lost on a deliberate scale-in."""
        self._draining = True

    def end_drain(self) -> None:
        self._draining = False

    def retire(self) -> None:
        """Deliberate power-off: unhealthy AND excluded from heartbeats."""
        self._draining = False
        self._healthy = False
        self._retired = True

    def drop_next_frames(self, n: int) -> None:
        """Fault injection: the next ``n`` accepted frames vanish after the
        ack — the sender believes they were delivered (this is silent loss,
        unlike ``fail()`` which the broker's retry path observes)."""
        with self._lock:
            self._drop_frames += int(n)

    def push(self, group_id: int, blob: bytes) -> None:
        if not self._healthy:
            raise ConnectionError(f"endpoint {self.name} down")
        if self.inbound_bw:
            # token-bucket style pacing: model the shared inbound link
            now = self.clock.now()
            self._bw_debt = max(0.0, self._bw_debt - (now - self._bw_t) * self.inbound_bw)
            self._bw_t = now
            self._bw_debt += len(blob)
            lag = self._bw_debt / self.inbound_bw
            if lag > 1e-4:
                self.clock.sleep(min(lag, 0.05))
        with span("endpoint.decode") as sp:
            base, count, payload = unwrap_seq(blob)   # exactly-once seq header
            recs = decode_any(payload)    # single-record or aggregated frame
            sp.set_metadata(records=len(recs))
        with self._lock:
            if self._drop_frames > 0:
                self._drop_frames -= 1
                self.frames_dropped += 1
                self.records_dropped += len(recs)
                if base is not None and self.ledger is not None:
                    # the drop is silent: the frame acks upstream, so its
                    # seqs are consumed — replay must NOT resurrect injected
                    # loss, or it would stop being auditable as loss
                    self.ledger.mark_consumed(group_id, base, len(recs))
                return
            if base is not None and self.ledger is not None:
                skip = self.ledger.admit(group_id, base, len(recs))
                if skip:
                    if skip == len(recs):
                        self.frames_deduped += 1
                    self.records_deduped += skip
                    recs = recs[skip:]
                if not recs:
                    return            # whole frame was a replay duplicate
            for rec in recs:
                self._streams[rec.key()].append(rec)
            self.bytes_in += len(blob)
            self.records_in += len(recs)
            self.frames_in += 1
            self._ingest_win.append((self.clock.now(), len(recs)))

    # ---- consumer side (micro-batcher) -----------------------------------
    def stream_keys(self) -> list[str]:
        with self._lock:
            return list(self._streams.keys())

    def drain(self, key: str, max_records: int | None = None) -> list[StreamRecord]:
        with self._lock:
            dq = self._streams.get(key)
            if not dq:
                return []
            n = len(dq) if max_records is None else min(len(dq), max_records)
            return [dq.popleft() for _ in range(n)]

    def pending(self) -> int:
        with self._lock:
            return sum(len(d) for d in self._streams.values())

    # ---- telemetry -------------------------------------------------------
    def ingest_rate(self, window_s: float = 2.0) -> float:
        """Records/s over the trailing window (telemetry-bus feed)."""
        now = self.clock.now()
        with self._lock:
            while self._ingest_win and now - self._ingest_win[0][0] > window_s:
                self._ingest_win.popleft()
            return sum(n for _, n in self._ingest_win) / max(window_s, 1e-9)

    def telemetry(self) -> dict:
        """One control-plane sample: ingest rate, pending backlog, totals."""
        return {"name": self.name, "healthy": self.healthy(),
                "draining": self._draining,
                "pending": self.pending(), "records_in": self.records_in,
                "bytes_in": self.bytes_in, "frames_in": self.frames_in,
                "frames_dropped": self.frames_dropped,
                "records_dropped": self.records_dropped,
                "frames_deduped": self.frames_deduped,
                "records_deduped": self.records_deduped,
                "ingest_rate_rps": self.ingest_rate()}

    # ---- exactly-once checkpointing --------------------------------------
    _AUDIT_FIELDS = ("bytes_in", "records_in", "frames_in", "frames_dropped",
                     "records_dropped", "frames_deduped", "records_deduped")

    def audit_snapshot(self) -> dict:
        """The delivery-audit counters a Session checkpoint carries, so a
        restored run's loss accounting stays closed across the crash."""
        with self._lock:
            return {f: getattr(self, f) for f in self._AUDIT_FIELDS}

    def restore_audit(self, state: dict) -> None:
        with self._lock:
            for f in self._AUDIT_FIELDS:
                setattr(self, f, int(state.get(f, 0)))


def make_endpoint(i: int, *, inbound_bw: float | None = None,
                  base_port: int = 6379, transport: str = "inprocess",
                  clock: Clock | None = None,
                  ledger: SeqLedger | None = None):
    """One CloudEndpoint at fleet slot ``i``.

    Split out of :func:`make_endpoints` so the cloud capacity plane
    (repro.cloud) can attach endpoints to a *live* Session one at a time;
    pass the fleet's shared ``ledger`` so exactly-once dedupe spans
    dynamically provisioned endpoints too."""
    from repro.core.transport import (CloudEndpoint, LoopbackTransport,
                                      VirtualLoopbackTransport)
    clock = ensure_clock(clock)
    if ledger is None:
        ledger = SeqLedger()
    h = Endpoint(name=f"ep{i}", inbound_bw=inbound_bw, port=base_port,
                 clock=clock, ledger=ledger)
    if transport == "inprocess":
        return CloudEndpoint(service_ip=f"10.0.0.{i+1}",
                             service_port=base_port, handle=h)
    elif transport == "loopback":
        if clock.virtual:
            t = VirtualLoopbackTransport(h, clock=clock)
        else:
            t = LoopbackTransport(h)
        return CloudEndpoint(service_ip="127.0.0.1",
                             service_port=t.port, handle=h, transport=t)
    raise ValueError(f"unknown transport {transport!r} "
                     "(expected 'inprocess' or 'loopback')")


def make_endpoints(n: int, *, inbound_bw: float | None = None,
                   base_port: int = 6379, transport: str = "inprocess",
                   clock: Clock | None = None,
                   ledger: SeqLedger | None = None) -> list:
    """The paper's `struct CloudEndpoint endpoints[NUM_GROUPS]`.

    ``transport="inprocess"`` binds each CloudEndpoint straight to its
    Endpoint handle; ``"loopback"`` routes frames through a real localhost
    TCP socket (same semantics, proves the Transport seam).  Under a
    virtual ``clock`` the loopback flavor swaps in
    ``VirtualLoopbackTransport`` — the same frame protocol executed
    synchronously on simulated time, so chaos/replay scenarios also cover
    the TCP framing path.

    All endpoints of one fleet share one ``SeqLedger`` (created here when
    not supplied): exactly-once dedupe must recognize a frame replayed onto
    a *different* endpoint after failover."""
    clock = ensure_clock(clock)
    if ledger is None:
        ledger = SeqLedger()
    return [make_endpoint(i, inbound_bw=inbound_bw, base_port=base_port,
                          transport=transport, clock=clock, ledger=ledger)
            for i in range(n)]
