"""Batched hot path: fused gram-pair kernel vs oracle, device-resident
StreamingDMD batch updates vs sequential, aggregated wire frames round-trip,
broker coalescing, and StreamEngine min_batch semantics."""
import time

import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.dmd import StreamingDMD, gram_pair_update
from repro.core.broker import Broker, BrokerConfig, _GroupSender
from repro.core.grouping import GroupPlan
from repro.core.records import (StreamRecord, decode_any, decode_batch,
                                encode, encode_batch)
from repro.kernels import ops, ref
from repro.streaming.endpoint import make_endpoints
from repro.streaming.engine import StreamEngine

from one_stage import one_stage


# ------------------------------------------------------------ fused kernel
@pytest.mark.parametrize("n,d", [(64, 64), (300, 200), (5, 96), (1, 32),
                                 (130, 130)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_gram_pair_vs_ref(rng, n, d, dtype):
    x = jnp.asarray(rng.randn(n, d), dtype)
    y = jnp.asarray(rng.randn(n, d), dtype)
    g = jnp.asarray(rng.randn(d, d), jnp.float32)
    a = jnp.asarray(rng.randn(d, d), jnp.float32)
    got_g, got_a = ops.gram_pair_accumulate(x, y, g, a)
    want_g, want_a = ref.gram_pair_ref(x.astype(jnp.float32),
                                       y.astype(jnp.float32), g, a)
    tol = 0.5 if dtype == jnp.bfloat16 else 1e-2
    np.testing.assert_allclose(np.asarray(got_g), np.asarray(want_g),
                               atol=tol, rtol=tol)
    np.testing.assert_allclose(np.asarray(got_a), np.asarray(want_a),
                               atol=tol, rtol=tol)


def test_gram_pair_matches_single_gram_and_jnp_path(rng):
    """Fused kernel == the standalone gram kernel for G, and == the portable
    jnp path that StreamingDMD uses off-TPU."""
    n, d = 96, 64
    x = jnp.asarray(rng.randn(n, d), jnp.float32)
    y = jnp.asarray(rng.randn(n, d), jnp.float32)
    g = jnp.zeros((d, d), jnp.float32)
    a = jnp.zeros((d, d), jnp.float32)
    fg, fa = ops.gram_pair_accumulate(x, y, g, a)
    sg = ops.gram_accumulate(x, g)
    jg, ja = gram_pair_update(g, a, x, y)
    np.testing.assert_allclose(np.asarray(fg), np.asarray(sg), atol=1e-2,
                               rtol=1e-3)
    np.testing.assert_allclose(np.asarray(fg), np.asarray(jg), atol=1e-2,
                               rtol=1e-3)
    np.testing.assert_allclose(np.asarray(fa), np.asarray(ja), atol=1e-2,
                               rtol=1e-3)


# ------------------------------------------------------- batched streaming
@pytest.mark.parametrize("use_kernel", [False, True])
def test_update_batch_matches_sequential(rng, use_kernel):
    snaps = rng.randn(50, 32).astype(np.float32)
    sd_seq = StreamingDMD(n_features=32, window=8, rank=4)
    for s in snaps:
        sd_seq.update(s)
    sd_bat = StreamingDMD(n_features=32, window=8, rank=4,
                          use_kernel=use_kernel)
    for i in range(0, len(snaps), 7):       # uneven batches on purpose
        sd_bat.update_batch(snaps[i: i + 7])
    assert sd_bat.n_seen == sd_seq.n_seen == 50
    np.testing.assert_allclose(np.asarray(sd_seq._G), np.asarray(sd_bat._G),
                               atol=1e-2, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(sd_seq._A), np.asarray(sd_bat._A),
                               atol=1e-2, rtol=1e-4)
    e_seq, e_bat = sd_seq.eigenvalues(), sd_bat.eigenvalues()
    e_seq = np.sort_complex(e_seq[np.isfinite(e_seq)])
    e_bat = np.sort_complex(e_bat[np.isfinite(e_bat)])
    np.testing.assert_allclose(e_seq, e_bat, atol=1e-4)
    # the point of batching: far fewer device round-trips
    assert sd_bat.device_calls < sd_seq.device_calls / 5
    assert sd_bat.h2d_transfers < sd_seq.h2d_transfers / 5


def test_update_batch_short_and_padded_payloads(rng):
    sd = StreamingDMD(n_features=16, window=8, rank=2)
    sd.update_batch([rng.randn(30), rng.randn(5), rng.randn(16)])  # trim/pad
    assert sd.n_seen == 3
    assert all(b.shape == (16,) for b in sd._buf)
    sd.update_batch([])                      # no-op, no state touched
    assert sd.n_seen == 3


def test_update_batch_window_trim(rng):
    sd = StreamingDMD(n_features=8, window=4, rank=2)
    sd.update_batch(rng.randn(11, 8).astype(np.float32))
    assert len(sd._buf) == 4 and sd.n_seen == 11


# ------------------------------------------------------------- wire frames
@pytest.mark.parametrize("compress", ["none", "zstd", "int8", "int8+zstd"])
@pytest.mark.parametrize("delta", [False, True])
def test_batch_codec_roundtrip(rng, compress, delta):
    base = rng.randn(40).astype(np.float32)
    recs = [StreamRecord("vel", 0, 1, s,
                         base + 0.01 * s + 0.001 * rng.randn(40).astype(
                             np.float32))
            for s in range(9)]
    out = decode_batch(encode_batch(recs, compress=compress, delta=delta))
    assert len(out) == len(recs)
    for a, b in zip(recs, out):
        assert (a.field_name, a.group_id, a.rank, a.step) == \
               (b.field_name, b.group_id, b.rank, b.step)
        assert a.t_generated == pytest.approx(b.t_generated)
        assert b.payload.shape == a.payload.shape
        if compress.startswith("int8"):
            # int8 error accumulates along a delta chain (documented)
            np.testing.assert_allclose(a.payload, b.payload,
                                       atol=0.15 if delta else 0.05)
        elif delta:   # float delta chains reconstruct to roundoff, not bitwise
            np.testing.assert_allclose(a.payload, b.payload, atol=1e-5)
        else:
            np.testing.assert_array_equal(a.payload, b.payload)


def test_int8_delta_chain_error_does_not_accumulate(rng):
    """Per-stream scales + closed-loop deltas: along a 64-record delta chain
    every record's error stays bounded by its OWN quantization step instead
    of summing the chain's.  (The seed codec accumulated error record over
    record — tail error grew with chain length.)"""
    base = rng.randn(1000).astype(np.float32)
    recs, p = [], base.copy()
    for s in range(64):
        p = p + 0.01 * rng.randn(1000).astype(np.float32)
        recs.append(StreamRecord("vel", 0, 1, s, p.copy()))
    out = decode_batch(encode_batch(recs, compress="int8", delta=True))
    errs = [np.abs(a.payload - b.payload).max() for a, b in zip(recs, out)]
    # every record within the classic single-record int8 bound...
    bound = max(np.abs(r.payload).max() for r in recs) / 100
    assert max(errs) <= bound
    # ...and the chain tail is no worse than the chain head: deltas are tiny
    # relative to the base record, so closed-loop errors should be far
    # SMALLER downstream, not accumulating
    assert max(errs[32:]) <= errs[0]
    assert max(errs[1:]) < bound / 5


def test_legacy_int8_batch_frames_still_decode(rng):
    """Pre-per-stream-scale frames (enc tag 'int8', one blockwise pass over
    the concatenated buffer) must keep decoding."""
    import msgpack
    from repro.core.records import quantize_int8
    recs = [StreamRecord("f", 0, 0, s, rng.randn(40).astype(np.float32))
            for s in range(5)]
    buf = np.concatenate([r.payload.reshape(-1) for r in recs])
    msg = {"n": len(recs), "f": "f", "g": 0, "r": 0,
           "s": [r.step for r in recs], "t": [r.t_generated for r in recs],
           "e": "int8", "d": 0,
           "sh": [list(r.payload.shape) for r in recs],
           "p": quantize_int8(buf)}
    blob = b"B" + msgpack.packb(msg, use_bin_type=True)
    out = decode_batch(blob)
    assert len(out) == 5
    for a, b in zip(recs, out):
        np.testing.assert_allclose(a.payload, b.payload, atol=0.05)
        assert a.step == b.step


def test_batch_codec_mixed_streams_and_shapes(rng):
    """Delta chains must reset across stream/shape changes; identity columns
    expand back per record."""
    recs = [StreamRecord("a", 0, 0, 0, rng.randn(8).astype(np.float32)),
            StreamRecord("b", 1, 2, 0, rng.randn(3, 4).astype(np.float32)),
            StreamRecord("b", 1, 2, 1, rng.randn(3, 4).astype(np.float32)),
            StreamRecord("a", 0, 0, 1, rng.randn(8).astype(np.float32)),
            StreamRecord("a", 0, 0, 2, rng.randn(2).astype(np.float32))]
    out = decode_batch(encode_batch(recs, compress="none", delta=True))
    for a, b in zip(recs, out):
        assert (a.field_name, a.group_id, a.rank, a.step) == \
               (b.field_name, b.group_id, b.rank, b.step)
        assert b.payload.shape == a.payload.shape
        np.testing.assert_allclose(np.asarray(a.payload, np.float32),
                                   b.payload, atol=1e-5)


@pytest.mark.parametrize("compress", ["none", "zstd", "int8", "int8+zstd"])
def test_batch_codec_frame_tags(rng, compress):
    """*zstd modes always ship the compressed batch tag, the others plain
    framing — zstandard is a hard dependency, so there is no silent
    uncompressed fallback."""
    recs = [StreamRecord("f", 0, 0, s, rng.randn(16).astype(np.float32))
            for s in range(4)]
    blob = encode_batch(recs, compress=compress)
    assert blob[:1] == (b"C" if compress.endswith("zstd") else b"B")
    out = decode_batch(blob)
    tol = 0.05 if compress.startswith("int8") else 0
    for a, b in zip(recs, out):
        np.testing.assert_allclose(a.payload, b.payload, atol=tol)


def test_decode_any_dispatch(rng):
    rec = StreamRecord("f", 0, 0, 7, rng.randn(8).astype(np.float32))
    assert len(decode_any(encode(rec, compress="none"))) == 1
    assert len(decode_any(encode_batch([rec, rec], compress="none"))) == 2


def test_encode_batch_empty_raises():
    with pytest.raises(ValueError):
        encode_batch([])


def test_batch_frame_smaller_than_single_frames(rng):
    recs = [StreamRecord("vel", 0, 1, s, rng.randn(256).astype(np.float32))
            for s in range(32)]
    batch = len(encode_batch(recs, compress="int8"))
    singles = sum(len(encode(r, compress="int8")) for r in recs)
    assert batch < singles


# -------------------------------------------------------- broker coalescing
def test_sender_coalesces_queued_records(rng):
    """Records queued before the sender starts must leave as ≤ ceil(n/cap)
    aggregated frames, all decodable on the endpoint side."""
    eps = make_endpoints(1)
    s = _GroupSender(0, eps, 0,
                     BrokerConfig(compress="none", max_batch_records=8,
                                  queue_capacity=64))
    for i in range(32):
        s.submit(StreamRecord("f", 0, 0, i, np.arange(4, dtype=np.float32)))
    s.start()
    s.stop(timeout=5.0)
    h = eps[0].handle
    assert h.records_in == 32
    assert s.stats.sent == 32
    assert h.frames_in == s.stats.frames_sent == 4   # 32 / cap(8)
    assert sorted(r.step for r in h.drain("f/g0/r0")) == list(range(32))


def test_broker_end_to_end_with_batching(rng):
    eps = make_endpoints(1)
    plan = GroupPlan(n_producers=4, n_groups=1, executors_per_group=2)
    broker = Broker(plan, eps, BrokerConfig(compress="int8+zstd",
                                            max_batch_records=16,
                                            delta_encode=True))
    for st in range(8):
        for r in range(4):
            broker.write("f", r, st, np.full(32, float(st), np.float32))
    broker.flush()
    stats = broker.finalize()
    h = eps[0].handle
    assert stats.sent == h.records_in == 32
    assert h.frames_in == stats.frames_sent <= 32


# ----------------------------------------------------------- engine batching
def test_engine_min_batch_holds_until_threshold():
    eps = make_endpoints(1)
    plan = GroupPlan(n_producers=1, n_groups=1, executors_per_group=1)
    broker = Broker(plan, eps, BrokerConfig(compress="none",
                                            max_batch_records=1))
    eng = StreamEngine([e.handle for e in eps],
                       one_stage(lambda k, r: len(r)).compile(), 1,
                       trigger_interval=60.0, min_batch=4)
    try:
        for st in range(2):
            broker.write("f", 0, st, np.arange(4, dtype=np.float32))
        broker.flush()
        assert eng.trigger_once() == 0          # 2 < min_batch: held
        assert eng.held() == 2
        for st in range(2, 4):
            broker.write("f", 0, st, np.arange(4, dtype=np.float32))
        broker.flush()
        assert eng.trigger_once() == 1          # threshold reached
        assert eng.held() == 0
    finally:
        broker.finalize()
        eng.drain_and_stop(timeout=10)
    results = eng.collect()
    assert [r.n_records for r in results] == [4]    # one real micro-batch


def test_engine_min_batch_age_release():
    """A stale sub-threshold hold is released after one trigger interval."""
    eps = make_endpoints(1)
    plan = GroupPlan(n_producers=1, n_groups=1, executors_per_group=1)
    broker = Broker(plan, eps, BrokerConfig(compress="none",
                                            max_batch_records=1))
    eng = StreamEngine([e.handle for e in eps],
                       one_stage(lambda k, r: len(r)).compile(), 1,
                       trigger_interval=0.1, min_batch=100)
    try:
        for st in range(3):
            broker.write("f", 0, st, np.arange(4, dtype=np.float32))
        broker.flush()
        deadline = time.time() + 5.0
        while time.time() < deadline and not eng.collect():
            time.sleep(0.02)
        results = eng.collect()
        assert results and results[0].n_records == 3
    finally:
        broker.finalize()
        eng.drain_and_stop(timeout=10)


def test_engine_drain_flushes_held_records():
    eps = make_endpoints(1)
    plan = GroupPlan(n_producers=1, n_groups=1, executors_per_group=1)
    broker = Broker(plan, eps, BrokerConfig(compress="none",
                                            max_batch_records=1))
    eng = StreamEngine([e.handle for e in eps],
                       one_stage(lambda k, r: len(r)).compile(), 1,
                       trigger_interval=60.0, min_batch=100)
    broker.write("f", 0, 0, np.arange(4, dtype=np.float32))
    broker.flush()
    assert eng.trigger_once() == 0              # held below threshold
    broker.finalize()
    eng.drain_and_stop(timeout=10)              # force-flushes the hold
    assert sum(r.n_records for r in eng.collect()) == 1


def test_zstd_frames_are_thread_safe(rng):
    """Broker senders and endpoints each run on their own thread; frames
    encoded and decoded concurrently must all round-trip (a zstd context
    shared across threads corrupts frames)."""
    import threading
    recs = [StreamRecord("v", 0, r, 0, rng.randn(2304).astype(np.float32))
            for r in range(32)]
    want = decode_batch(encode_batch(recs, compress="zstd"))
    errors = []

    def work():
        try:
            for _ in range(10):
                out = decode_batch(encode_batch(recs, compress="zstd"))
                assert all(np.array_equal(a.payload, b.payload)
                           for a, b in zip(out, want))
        except Exception as e:            # collected for the main thread
            errors.append(e)

    threads = [threading.Thread(target=work) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors, errors[:2]
