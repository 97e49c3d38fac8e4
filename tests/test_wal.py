"""WAL durability properties: append/ack/trim invariants under random
operation interleavings (property-style, seeded), torn-tail recovery of the
durable serialization, and the receive-side SeqLedger dedupe contract."""
import random
import zlib

import pytest

from repro.runtime.wal import FileWalStore, SeqLedger, WalSegment, WalStore


def _pointers_ordered(seg: WalSegment) -> None:
    p = seg.points()
    assert 0 <= p["base"] <= p["acked"] <= p["shipped"] <= p["last"]
    assert p["committed"] <= p["last"]
    # base never trims past the retention point
    point = p["acked"] if seg.retain == "ack" \
        else min(p["acked"], p["committed"])
    assert p["base"] <= point or p["last"] == 0


# --------------------------------------------------------------- basic cycle
def test_append_fetch_ack_roundtrip():
    seg = WalSegment(0, capacity_bytes=1 << 16, max_pending=64)
    blobs = [bytes([i]) * (i + 1) for i in range(10)]
    seqs = [seg.try_append(b) for b in blobs]
    assert seqs == list(range(1, 11))          # seqs start at 1, contiguous
    got = seg.fetch_unshipped(4)
    assert [e.seq for e in got] == [1, 2, 3, 4]
    assert [e.blob for e in got] == blobs[:4]
    assert seg.unshipped_count() == 6
    seg.ack(4)
    assert seg.unacked_count() == 6
    assert seg.points()["base"] == 4           # retain="ack": acked trimmed
    rest = seg.fetch_unshipped(100)
    assert [e.seq for e in rest] == [5, 6, 7, 8, 9, 10]
    seg.ack(10)
    assert seg.bytes_used() == 0 and seg.unacked_count() == 0
    _pointers_ordered(seg)


def test_capacity_and_pending_bounds_refuse_appends():
    seg = WalSegment(0, capacity_bytes=64, max_pending=4)
    assert seg.try_append(b"x" * 60) is not None
    assert seg.try_append(b"y" * 10) is None       # over byte capacity
    seg.ack(1)                                     # trim frees the bytes
    for i in range(4):
        assert seg.try_append(b"a") is not None
    assert seg.try_append(b"b") is None            # max_pending unshipped
    seg.fetch_unshipped(4)
    assert seg.try_append(b"b") is not None        # shipping frees the slot


def test_oversized_single_record_is_always_accepted():
    # a record larger than capacity must not wedge the log forever: the
    # bound applies to the *backlog*, a lone append always fits
    seg = WalSegment(0, capacity_bytes=16, max_pending=8)
    assert seg.try_append(b"z" * 100) is not None


def test_rewind_shipped_replays_unacked_tail():
    seg = WalSegment(0)
    for i in range(6):
        seg.try_append(bytes([i]))
    seg.fetch_unshipped(6)
    seg.ack(2)
    assert seg.rewind_shipped() == 4
    assert [e.seq for e in seg.fetch_unshipped(10)] == [3, 4, 5, 6]


def test_commit_retention_keeps_acked_tail_until_commit():
    seg = WalSegment(0, retain="commit")
    for i in range(8):
        seg.try_append(bytes([i]))
    seg.fetch_unshipped(8)
    seg.ack(8)
    assert seg.points()["base"] == 0               # acked but NOT committed
    assert seg.reset_acked_to_commit() == 8        # a restore replays all 8
    assert [e.seq for e in seg.fetch_unshipped(10)] == list(range(1, 9))
    seg.ack(8)
    seg.commit(5)
    assert seg.points()["base"] == 5               # min(acked, committed)
    _pointers_ordered(seg)


# ----------------------------------------------------- seeded property sweep
@pytest.mark.parametrize("seed", range(8))
def test_random_interleavings_preserve_invariants(seed):
    """Random append/fetch/ack/commit/rewind sequences: pointers stay
    ordered, fetched seqs are exactly the gap-free unshipped range, and
    every appended blob is either still retained or was acked past."""
    rng = random.Random(seed)
    retain = rng.choice(("ack", "commit"))
    seg = WalSegment(0, capacity_bytes=1 << 12, max_pending=32,
                     retain=retain)
    appended: dict[int, bytes] = {}
    shipped: list[int] = []
    for _ in range(400):
        op = rng.randrange(6)
        if op <= 1:
            blob = bytes(rng.randrange(256) for _ in range(rng.randrange(1, 24)))
            seq = seg.try_append(blob)
            if seq is not None:
                assert seq == max(appended, default=0) + 1   # monotonic
                appended[seq] = blob
        elif op == 2:
            before = seg.points()
            got = seg.fetch_unshipped(rng.randrange(1, 8))
            for e in got:
                assert appended[e.seq] == e.blob             # no corruption
            seqs = [e.seq for e in got]
            # a fetch hands out exactly the gap-free range above the
            # shipped pointer — never trimmed entries, never a skip
            want = min(len(seqs), before["last"] - before["shipped"])
            assert seqs == list(range(before["shipped"] + 1,
                                      before["shipped"] + 1 + want))
            assert all(s > before["base"] for s in seqs)
            shipped.extend(seqs)
        elif op == 3 and shipped:
            seg.ack(rng.choice(shipped))
        elif op == 4 and shipped:
            seg.commit(rng.choice(shipped))
        elif op == 5:
            seg.rewind_shipped()
        _pointers_ordered(seg)
    p = seg.points()
    # everything not yet trimmed must still be retrievable, in order
    seg.rewind_shipped()
    tail = seg.fetch_unshipped(10_000)
    assert [e.seq for e in tail] == list(range(p["acked"] + 1, p["last"] + 1))
    for e in tail:
        assert appended[e.seq] == e.blob


# -------------------------------------------------------- durable round-trip
def _filled_segment(retain="commit"):
    seg = WalSegment(3, retain=retain)
    for i in range(12):
        seg.try_append(bytes([i]) * (i + 3))
    seg.fetch_unshipped(12)
    seg.ack(7)
    seg.commit(4)
    return seg


def test_serialization_roundtrip_preserves_entries_and_pointers():
    seg = _filled_segment()
    back = WalSegment.from_bytes(seg.to_bytes(), retain="commit")
    assert back.points() == {**seg.points(), "shipped": seg.points()["acked"]}
    back.rewind_shipped()
    a = [(e.seq, e.blob) for e in back.fetch_unshipped(100)]
    seg.rewind_shipped()
    b = [(e.seq, e.blob) for e in seg.fetch_unshipped(100)]
    assert a == b


@pytest.mark.parametrize("seed", range(6))
def test_truncated_tail_recovers_prefix_not_garbage(seed):
    """Cut the serialized log at a random byte (a crash mid-write): recovery
    must yield a clean contiguous prefix — never an exception, never a
    record whose bytes differ from what was appended."""
    seg = _filled_segment()
    data = seg.to_bytes()
    rng = random.Random(seed)
    cut = rng.randrange(len(b"WALSEG1\n") + 28, len(data))
    back = WalSegment.from_bytes(data[:cut], retain="commit")
    p = seg.points()
    q = back.points()
    assert q["base"] == p["base"]
    assert q["last"] <= p["last"]                  # only the tail is lost
    assert q["acked"] <= p["acked"] and q["committed"] <= p["committed"]
    back.rewind_shipped()
    for e in back.fetch_unshipped(100):
        assert e.blob == bytes([e.seq - 1]) * (e.seq + 2)   # intact bytes


def test_corrupt_tail_crc_discards_only_the_bad_suffix():
    seg = _filled_segment()
    data = bytearray(seg.to_bytes())
    data[-1] ^= 0xFF                               # flip a payload byte
    back = WalSegment.from_bytes(bytes(data), retain="commit")
    assert back.points()["last"] == seg.points()["last"] - 1
    # CRC actually protects the payload, not just the length
    assert zlib.crc32 is not None


def test_from_bytes_rejects_bad_magic():
    with pytest.raises(ValueError, match="magic"):
        WalSegment.from_bytes(b"NOTAWAL\n" + b"\x00" * 64)


# ----------------------------------------------------------------- the store
def test_store_segments_share_limits_and_survive_reset():
    store = WalStore(capacity_bytes=1 << 12, queue_capacity=8,
                     retain="commit")
    a, b = store.segment(0), store.segment(1)
    assert store.segment(0) is a                   # create-on-demand, cached
    for i in range(5):
        a.try_append(b"a")
        b.try_append(b"b")
    a.fetch_unshipped(5)
    a.ack(5)
    b.fetch_unshipped(3)
    b.ack(3)
    assert store.unacked_records() == 2
    a.commit(2)
    assert store.reset_for_restore() == 3 + 5      # a: 5-2 committed, b: 5-0
    assert store.unacked_records() == 8
    assert sorted(store.points()) == [0, 1]


def test_store_rejects_bad_retain():
    with pytest.raises(ValueError, match="retain"):
        WalStore(retain="forever")


# --------------------------------------------------------- disk-backed store
def test_file_store_sync_then_adopt(tmp_path):
    store = FileWalStore(tmp_path, capacity_bytes=1 << 12, queue_capacity=8,
                         retain="commit")
    a, b = store.segment(0), store.segment(2)
    for i in range(5):
        a.try_append(bytes([i]) * 3)
        b.try_append(bytes([i + 16]))
    a.fetch_unshipped(5)
    a.ack(3)
    a.commit(2)
    assert store.sync() > 0
    assert not list(tmp_path.glob("*.tmp"))          # atomic: no temp debris
    assert sorted(p.name for p in tmp_path.glob("group-*.wal")) == \
        ["group-00000.wal", "group-00002.wal"]

    adopted = FileWalStore(tmp_path, capacity_bytes=1 << 12,
                           queue_capacity=8, retain="commit")
    assert adopted.groups() == [0, 2]
    pa = adopted.segment(0).points()
    # retain="commit" kept entries past commit=2; acked pointer survived,
    # shipped rewound to acked so the unacked tail is fetchable again
    assert (pa["base"], pa["acked"], pa["committed"], pa["last"]) == (2, 3, 2, 5)
    assert [e.blob for e in adopted.segment(0).fetch_unshipped(10)] == \
        [bytes([3]) * 3, bytes([4]) * 3]
    assert adopted.reset_for_restore() == 3 + 5      # acked rewinds to commit
    assert [e.seq for e in adopted.segment(0).fetch_unshipped(10)] == [3, 4, 5]
    assert [e.blob for e in adopted.segment(2).fetch_unshipped(10)] == \
        [bytes([i + 16]) for i in range(5)]


def test_file_store_torn_tail_recovers_prefix(tmp_path):
    store = FileWalStore(tmp_path)
    seg = store.segment(1)
    for i in range(8):
        seg.try_append(bytes([i]) * 50)
    store.sync()
    path = tmp_path / "group-00001.wal"
    data = path.read_bytes()
    path.write_bytes(data[:-20])                     # crash mid final record
    adopted = FileWalStore(tmp_path)
    recovered = adopted.segment(1)
    assert recovered.points()["last"] == 7           # prefix intact, tail gone
    assert [e.blob for e in recovered.fetch_unshipped(10)] == \
        [bytes([i]) * 50 for i in range(7)]


def test_file_store_skips_unreadable_and_alien_files(tmp_path):
    (tmp_path / "group-00004.wal").write_bytes(b"not a wal segment")
    (tmp_path / "group-bogus.wal").write_bytes(b"xx")
    (tmp_path / "notes.txt").write_text("ignore me")
    store = FileWalStore(tmp_path)
    assert store.groups() == []                      # fresh logs, no crash
    seg = store.segment(4)
    seg.try_append(b"clean")
    store.sync()
    assert FileWalStore(tmp_path).segment(4).points()["last"] == 1


def test_wal_dir_requires_exactly_once():
    from repro.workflow import WorkflowConfig
    with pytest.raises(ValueError, match="wal_dir"):
        WorkflowConfig(wal_dir="/tmp/x").validate()


def test_session_wal_dir_persists_log_across_sessions(tmp_path):
    """An exactly-once Session with wal_dir syncs its WAL at close; a new
    Session over the same directory adopts the surviving segments."""
    import numpy as np

    from one_stage import one_stage
    from repro.workflow import Session, WorkflowConfig
    cfg = WorkflowConfig(n_producers=2, n_groups=1, executors_per_group=1,
                         compress="none", backpressure="block",
                         trigger_interval=0.05, delivery="exactly-once",
                         wal_dir=str(tmp_path / "wal"))
    with Session(cfg, pipeline=one_stage(lambda k, recs: len(recs))) as sess:
        h = sess.open_field("f", shape=(4,))
        for s in range(5):
            for r in range(2):
                assert h.write(s, np.zeros(4, np.float32), rank=r)
        sess.flush()
    assert sum(r.n_records for r in sess.results()) == 10
    wal_files = list((tmp_path / "wal").glob("group-*.wal"))
    assert wal_files, "close() never synced the WAL to disk"
    adopted = FileWalStore(tmp_path / "wal")
    assert adopted.groups() == [0]
    # everything shipped and acked before close: nothing left to replay
    assert adopted.segment(0).points()["last"] == 10
    assert adopted.unacked_records() == 0


# ---------------------------------------------------------------- the ledger
def test_seq_ledger_dedupes_replayed_prefixes():
    led = SeqLedger()
    assert led.admit(0, 1, 4) == 0                 # fresh frame: apply all
    assert led.applied(0) == 4
    assert led.admit(0, 1, 4) == 4                 # exact replay: whole dup
    assert led.admit(0, 3, 4) == 2                 # overlap: skip 3,4
    assert led.applied(0) == 6
    assert led.admit(1, 1, 2) == 0                 # groups are independent
    snap = led.snapshot()
    led2 = SeqLedger()
    led2.restore(snap)
    assert led2.applied(0) == 6 and led2.applied(1) == 2


def test_seq_ledger_mark_consumed_blocks_resurrection():
    led = SeqLedger()
    led.mark_consumed(0, 1, 3)                     # injected drop ate 1..3
    assert led.admit(0, 1, 3) == 3                 # replay must NOT re-apply
    assert led.admit(0, 4, 2) == 0
