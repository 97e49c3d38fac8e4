"""Property tests (hypothesis): VirtualClock scheduling invariants under
randomized sleep plans, and an ``encode_batch``/``decode_batch`` round-trip
property across codec × delta × dtype.  Deterministic spot-check versions
of the clock invariants live in ``tests/test_clock.py``."""
import threading

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.records import StreamRecord, decode_batch, encode_batch
from repro.runtime.clock import VirtualClock

# ---------------------------------------------------------------------------
# VirtualClock scheduling invariants
# ---------------------------------------------------------------------------

durations = st.lists(
    st.floats(min_value=0.0, max_value=10.0,
              allow_nan=False, allow_infinity=False),
    min_size=1, max_size=20)


@settings(max_examples=30, deadline=None)
@given(plan=durations)
def test_now_monotonic_under_any_sleep_plan(plan):
    clk = VirtualClock()
    seen = []
    for d in plan:
        clk.sleep(d)
        seen.append(clk.now())
    assert all(b >= a for a, b in zip(seen, seen[1:]))
    assert seen[-1] == pytest.approx(sum(plan))


@settings(max_examples=20, deadline=None)
@given(plans=st.lists(durations, min_size=2, max_size=6),
       seed=st.one_of(st.none(), st.integers(0, 2**31)))
def test_no_lost_wakeups_under_concurrent_sleepers(plans, seed):
    """Every sleeper completes its full randomized plan regardless of how
    many peers are interleaved or how ties are broken."""
    clk = VirtualClock(seed=seed)
    clk.attach()
    done, lock = [], threading.Lock()

    def sleeper(i, plan):
        for d in plan:
            clk.sleep(d)
        with lock:
            done.append((i, clk.now()))   # finish instant, pre-join

    threads = [threading.Thread(target=sleeper, args=(i, p), daemon=True)
               for i, p in enumerate(plans)]
    for t in threads:
        clk.thread_started(t)
        t.start()
    clk.detach()
    for t in threads:
        assert clk.join(t, timeout=None)
    assert sorted(i for i, _ in done) == list(range(len(plans)))
    # each sleeper finishes exactly at its own cumulative deadline: the
    # schedule neither stalls a waiter nor overshoots it (join() itself
    # polls on virtual time, so clk.now() afterwards may sit a few poll
    # quanta past the last finish — measure inside the sleepers instead)
    finish = max(t for _, t in done)
    assert finish == pytest.approx(max(sum(p) for p in plans))
    assert clk.now() >= finish


@settings(max_examples=20, deadline=None)
@given(n=st.integers(min_value=2, max_value=8),
       target=st.floats(min_value=1.0, max_value=100.0,
                        allow_nan=False, allow_infinity=False))
def test_fifo_wakeup_among_equal_deadlines(n, target):
    """Park order (forced deterministic by serialized staggered sleeps) is
    wake order when deadlines tie exactly and no seed is set."""
    clk = VirtualClock()
    clk.attach()
    order, lock = [], threading.Lock()

    def sleeper(i):
        clk.sleep(0.001 * i)       # serialized: fixes park order = i order
        clk.sleep_until(target)    # identical absolute deadline for all
        with lock:
            order.append(i)

    threads = [threading.Thread(target=sleeper, args=(i,), daemon=True)
               for i in range(n)]
    for t in threads:
        clk.thread_started(t)
        t.start()
    clk.detach()
    for t in threads:
        assert clk.join(t, timeout=None)
    assert order == list(range(n))


@settings(max_examples=30, deadline=None)
@given(timeout=st.floats(min_value=0.01, max_value=50.0,
                         allow_nan=False, allow_infinity=False))
def test_wait_timeout_is_exact_in_virtual_time(timeout):
    clk = VirtualClock()
    t0 = clk.now()
    assert clk.wait(lambda: False, timeout=timeout) is False
    assert clk.now() - t0 == pytest.approx(timeout)


# ---------------------------------------------------------------------------
# Wire-codec round-trip property: codec × delta × dtype
# ---------------------------------------------------------------------------

_DTYPES = (np.float32, np.float64, np.float16, np.int32)


@st.composite
def record_batches(draw):
    n = draw(st.integers(min_value=1, max_value=12))
    dtype = draw(st.sampled_from(_DTYPES))
    size = draw(st.integers(min_value=1, max_value=64))
    n_streams = draw(st.integers(min_value=1, max_value=3))
    rng = np.random.RandomState(draw(st.integers(0, 2**31)))
    scale = draw(st.floats(min_value=1e-3, max_value=1e3))
    recs = []
    for i in range(n):
        rank = i % n_streams
        payload = (rng.randn(size) * scale).astype(dtype)
        recs.append(StreamRecord("f", 0, rank, i // n_streams, payload))
    return recs


@settings(max_examples=40, deadline=None)
@given(recs=record_batches(),
       compress=st.sampled_from(["none", "zstd", "int8", "int8+zstd"]),
       delta=st.booleans())
def test_encode_decode_batch_roundtrip(recs, compress, delta):
    out = decode_batch(encode_batch(recs, compress=compress, delta=delta))
    assert len(out) == len(recs)
    for i, (a, b) in enumerate(zip(recs, out)):
        assert (a.field_name, a.group_id, a.rank, a.step) == \
               (b.field_name, b.group_id, b.rank, b.step)
        assert b.payload.shape == np.asarray(a.payload).shape
        ref = np.asarray(a.payload, np.float32)   # wire format is f32
        if compress.startswith("int8"):
            # closed-loop per-stream quantization: error bounded by the
            # quant step of what was quantized (max|src|/254), never by
            # chain position.  A chained record quantizes its delta against
            # the previous record's reconstruction, which decode returns.
            chained = (delta and i > 0 and recs[i - 1].key() == a.key()
                       and np.shape(recs[i - 1].payload) == ref.shape)
            src = ref - out[i - 1].payload if chained else ref
            amax = float(np.abs(src).max()) if src.size else 0.0
            atol = max(amax / 254.0 * 1.5, 1e-6)
            np.testing.assert_allclose(ref, b.payload, atol=atol)
        elif delta:
            # float delta chains reconstruct to roundoff, not bitwise
            atol = 1e-5 * max(1.0, float(np.abs(ref).max() or 1.0))
            np.testing.assert_allclose(ref, b.payload, atol=atol)
        else:
            np.testing.assert_array_equal(ref, b.payload)
