"""Device-resident analysis fast path: batched multi-key windowed DMD
(bucketed padding, bounded jit cache), donation + eigenvalue caching in
StreamingDMD, the kernel block-config registry/autotune hooks, and the
Pallas int8 codec backend's byte parity with the numpy wire codec."""
import numpy as np
import pytest

from repro.analysis import dmd
from repro.analysis.dmd import StreamingDMD, batched_window_dmd, window_dmd
from repro.analysis.metrics import unit_circle_distance
from repro.core.records import (StreamRecord, decode_batch, encode_batch,
                                get_quant_backend, set_quant_backend)
from repro.kernels import ops, ref


@pytest.fixture
def rng():
    return np.random.RandomState(0)


def _linear_panes(rng, d, lengths, eigs=(0.95, 0.7, -0.5)):
    """Panes driven by a known linear map: diag(eigs) in a random basis."""
    r = len(eigs)
    basis = np.linalg.qr(rng.randn(d, r))[0]
    A = basis @ np.diag(eigs) @ basis.T
    panes = []
    for m in lengths:
        x = basis @ rng.randn(r)
        rows = []
        for _ in range(m):
            rows.append(x.astype(np.float32))
            x = A @ x
        panes.append(rows)
    return panes


# ------------------------------------------------------- masked window solve
def test_window_dmd_recovers_known_eigenvalues(rng):
    [pane] = _linear_panes(rng, 24, [14])
    eigs = window_dmd(pane, rank=4, n_features=24)
    finite = np.sort(np.abs(eigs[np.isfinite(eigs)]))[::-1]
    assert np.allclose(finite[:3], [0.95, 0.7, 0.5], atol=1e-3)


def test_masked_solve_matches_svd_oracle(rng):
    """The device-resident masked Gram-route solve agrees with the host
    SVD-route oracle (ref.window_eigs_ref) on zero-padded panes.  The
    dynamics are a rotation pair + a decaying mode — well-separated
    eigenvalues keep the pane's Vandermonde conditioning benign (the Gram
    route squares singular values, so near-degenerate spectra push real
    modes under the rank tolerance by design)."""
    c, s = 0.97 * np.cos(0.7), 0.97 * np.sin(0.7)
    D = np.array([[c, -s, 0.0], [s, c, 0.0], [0.0, 0.0, 0.9]])
    basis = np.linalg.qr(rng.randn(32, 3))[0]
    A = basis @ D @ basis.T
    for n_valid, m in ((8, 8), (9, 16), (16, 16)):
        x = basis @ rng.randn(3)
        snaps = np.zeros((32, m), np.float32)
        for j in range(n_valid):
            snaps[:, j] = x
            x = A @ x
        # the operator takes the pane as (m, d) rows, the oracle as columns
        got = dmd._small_eigs(*dmd._window_operator(snaps.T, n_valid,
                                                    rank=4))
        want = np.asarray(ref.window_eigs_ref(snaps, n_valid, 4))
        k = int(np.isfinite(got).sum())
        assert k >= 3
        assert np.allclose(np.sort_complex(got[:3]),
                           np.sort_complex(want[:3]), atol=1e-3)


def test_batched_matches_per_pane_on_ragged_panes(rng):
    panes = _linear_panes(rng, 16, [3, 5, 9, 16, 2, 8, 12])
    batched = batched_window_dmd(panes, rank=4, n_features=16)
    assert len(batched) == len(panes)
    for pane, got in zip(panes, batched):
        want = window_dmd(pane, rank=4, n_features=16)
        assert got.shape == want.shape
        finite = np.isfinite(want)
        assert np.array_equal(finite, np.isfinite(got))
        assert np.allclose(got[finite], want[finite], atol=1e-5), \
            f"pane m={len(pane)}"


def test_batched_window_dmd_empty_and_short_panes(rng):
    out = batched_window_dmd([], rank=4)
    assert out == []
    # <3 snapshots cannot support a one-step fit: sentinel zero eigenvalue
    short = batched_window_dmd([[rng.randn(8).astype(np.float32)]],
                               rank=4, n_features=8)
    assert np.array_equal(short[0], np.zeros(1, np.complex64))


def test_window_solve_jit_cache_is_bucketed(rng):
    """Pane (m, d) shapes pad to power-of-two buckets in m (d is fixed by
    ``n_features``), so streaming ragged panes compiles O(log) solver
    variants, not one per shape."""
    before = dmd._window_operator._cache_size()
    for m in range(3, 18):
        pane = [rng.randn(20).astype(np.float32) for _ in range(m)]
        window_dmd(pane, rank=4, n_features=20)
    # d=20 stays 20; m in 3..17 pads to row buckets {4,8,16,32}
    assert dmd._window_operator._cache_size() - before <= 4

    solver = dmd._batched_operator(4)
    before_b = solver._cache_size()
    for k in (1, 2, 3, 5, 7, 9):
        panes = _linear_panes(rng, 20, [6] * k)
        batched_window_dmd(panes, rank=4, n_features=20)
    # k in 1..9 pads to batch buckets {1,2,4,8,16}: bounded, not per-k
    assert solver._cache_size() - before_b <= 5


def test_make_dmd_aggregate_prepares_and_scores(rng):
    panes = _linear_panes(rng, 12, [8, 10])
    fn = dmd.make_dmd_aggregate(rank=4, n_features=12)
    outs = fn([("a", panes[0]), ("b", panes[1])])
    assert len(outs) == 2
    for eigs in outs:
        assert np.isfinite(unit_circle_distance(eigs))


# ------------------------------------------------------ slab of the batched solve
def _per_row_slab(panes, k_pad, m_pad, d):
    """Reference fill: each row trimmed or zero-padded to d, written one at
    a time into a (k_pad, m_pad, d) zero slab."""
    slab = np.zeros((k_pad, m_pad, d), np.float32)
    for slot, rows in enumerate(panes):
        for j, r in enumerate(rows):
            r = np.asarray(r, np.float32).reshape(-1)[:d]
            slab[slot, j, : r.size] = r
    return slab


@pytest.mark.parametrize("lengths, k_pad, m_pad", [
    ([8, 8, 8], 4, 8),                 # uniform panes, one padding pane
    ([3, 5, 8, 7, 4], 8, 8),           # ragged m: padding rows and panes
])
def test_joined_slab_matches_per_row_fill(rng, lengths, k_pad, m_pad):
    d = 24
    panes = [dmd._pane_rows(rng.randn(m, d).astype(np.float32))
             for m in lengths]
    slab, copies = dmd._slab(panes, k_pad, m_pad, d)
    assert copies == 1
    assert slab.shape == (k_pad, m_pad, d) and slab.dtype == np.float32
    assert slab.tobytes() == _per_row_slab(panes, k_pad, m_pad, d).tobytes()


@pytest.mark.parametrize("form", ["ragged_width", "strided"])
def test_slab_fallback_matches_joined_gather(rng, form):
    """Rows the join cannot take whole (another width than d, or not
    contiguous) are copied one at a time into the same slab."""
    d, lengths, k_pad, m_pad = 24, [3, 5, 8, 7], 4, 8
    if form == "ragged_width":
        widths = [d - 5, d, d + 6]
        panes = [dmd._pane_rows(rng.randn(widths[j % 3]).astype(np.float32)
                                for j in range(m)) for m in lengths]
    else:                              # columns of (d, m) arrays
        panes = [dmd._pane_rows(rng.randn(d, m).astype(np.float32).T)
                 for m in lengths]
    assert not all(r.size == d and r.flags.c_contiguous
                   for p in panes for r in p)
    slab, copies = dmd._slab(panes, k_pad, m_pad, d)
    assert copies == sum(lengths)
    want = _per_row_slab(panes, k_pad, m_pad, d)
    assert slab.tobytes() == want.tobytes()
    whole = [list(want[slot, :m]) for slot, m in enumerate(lengths)]
    joined, one = dmd._slab(whole, k_pad, m_pad, d)
    assert one == 1 and joined.tobytes() == slab.tobytes()


def test_unpadded_width_matches_padded_bucket(rng):
    """d = 2304 as the configuration gives it solves as the same panes
    zero-padded to the power-of-two bucket 4096 did."""
    d = 2304
    panes = _linear_panes(rng, d, [32, 32, 20, 9])
    got = batched_window_dmd(panes, rank=4, n_features=d)
    want = batched_window_dmd(panes, rank=4, n_features=4096)
    for g, w in zip(got, want):
        finite = np.isfinite(w)
        assert finite.sum() >= 3
        assert np.array_equal(finite, np.isfinite(g))
        assert np.allclose(g[finite], w[finite], atol=1e-5)


# ------------------------------------------------ StreamingDMD: cache + donation
def test_eigenvalues_cached_until_next_update(rng):
    sd = StreamingDMD(n_features=16, window=8, rank=4)
    sd.update_batch(rng.randn(6, 16).astype(np.float32))
    e1 = sd.eigenvalues()
    calls, d2h = sd.device_calls, sd.d2h_transfers
    e2 = sd.eigenvalues()
    assert sd.device_calls == calls and sd.d2h_transfers == d2h, \
        "repeat eigenvalues() with no update must not touch the device"
    assert np.array_equal(e1, e2)
    sd.update(rng.randn(16).astype(np.float32))
    sd.eigenvalues()
    assert sd.device_calls > calls, "an update must invalidate the cache"


@pytest.mark.parametrize("use_kernel", [False, True])
def test_donation_parity(rng, use_kernel):
    snaps = rng.randn(24, 16).astype(np.float32)
    sds = [StreamingDMD(n_features=16, window=12, rank=4,
                        use_kernel=use_kernel, donate=don)
           for don in (True, False)]
    for sd in sds:
        for i in range(0, len(snaps), 6):
            sd.update_batch(snaps[i:i + 6])
    ea, eb = sds[0].eigenvalues(), sds[1].eigenvalues()
    fin = np.isfinite(ea)
    assert np.array_equal(fin, np.isfinite(eb))
    assert np.allclose(ea[fin], eb[fin], atol=1e-5)


# ------------------------------------------------- block-config registry
def test_block_config_registry_roundtrip():
    base = ops.get_block_config("gram_pair")
    try:
        ops.set_block_config("gram_pair", block_d=64)
        assert ops.get_block_config("gram_pair")["block_d"] == 64
        assert ops.get_block_config("gram_pair")["block_n"] == base["block_n"]
        with pytest.raises(KeyError, match="unknown op"):
            ops.set_block_config("nope", block_d=64)
        with pytest.raises(KeyError, match="unknown block params"):
            ops.set_block_config("gram_pair", block_z=64)
        ops.set_block_config("gram_pair")           # no sizes = reset
        assert ops.get_block_config("gram_pair") == base
    finally:
        ops.set_block_config("gram_pair")


def test_autotune_installs_winner(rng):
    x = rng.randn(64, 128).astype(np.float32)
    try:
        out = ops.autotune("quant",
                           [{"block_rows": 32}, {"block_rows": 64}],
                           lambda: (x,), repeats=1)
        assert out["op"] == "quant"
        assert out["best"]["block_rows"] in (32, 64)
        assert (ops.get_block_config("quant")["block_rows"]
                == out["best"]["block_rows"])
        assert len(out["timings_us"]) == 2
    finally:
        ops.set_block_config("quant")


# --------------------------------------------------- kernel edge shapes
def test_gram_pair_kernel_edge_shapes(rng):
    for n, d in ((1, 100), (5, 130), (3, 1)):
        x = rng.randn(n, d).astype(np.float32)
        y = rng.randn(n, d).astype(np.float32)
        g = rng.randn(d, d).astype(np.float32)
        a = rng.randn(d, d).astype(np.float32)
        gw, aw = ref.gram_pair_ref(x, y, g, a)
        gk, ak = ops.gram_pair_accumulate(x, y, g, a)
        assert np.allclose(gk, gw, atol=1e-4) and np.allclose(ak, aw, atol=1e-4)
        # all-zero padding rows are exactly invariant
        xz = np.concatenate([x, np.zeros((3, d), np.float32)])
        yz = np.concatenate([y, np.zeros((3, d), np.float32)])
        gz, az = ops.gram_pair_accumulate(xz, yz, g, a)
        assert np.allclose(gz, gk, atol=1e-5) and np.allclose(az, ak, atol=1e-5)


def test_quant_kernel_edge_shapes(rng):
    for nb, q, block in ((1, 256, 256), (5, 64, 4), (7, 1, 2)):
        x = rng.randn(nb, q).astype(np.float32)
        qr, sr = ref.quant_ref(x)
        qk, sk = ops.quantize(x, block_rows=block)
        assert np.array_equal(np.asarray(qk), np.asarray(qr))
        assert np.array_equal(np.asarray(sk), np.asarray(sr))
        back = ops.dequantize(qk, sk, block_rows=block)
        assert np.allclose(np.asarray(back), np.asarray(ref.dequant_ref(qr, sr)))


# ------------------------------------------------- Pallas codec byte parity
@pytest.fixture
def quant_backend_guard():
    prev = get_quant_backend()
    yield
    set_quant_backend(prev)


def _batch(rng, n=9, dim=300):
    return [StreamRecord("vel", 0, r % 3, r, rng.randn(dim).astype(np.float32))
            for r in range(n)]


@pytest.mark.parametrize("compress", ["int8", "int8+zstd"])
def test_pallas_numpy_int8s_frames_byte_identical(rng, quant_backend_guard,
                                                  compress):
    """The wire-format guarantee both ways: frames encoded under either
    backend are byte-identical, and either backend decodes either frame."""
    recs = _batch(rng)
    set_quant_backend("numpy")
    frame_np = encode_batch(recs, compress=compress)
    set_quant_backend("pallas")
    frame_pl = encode_batch(recs, compress=compress)
    assert frame_np == frame_pl

    for frame in (frame_np, frame_pl):
        for backend in ("numpy", "pallas"):
            set_quant_backend(backend)
            out = decode_batch(frame)
            assert len(out) == len(recs)
            for r, o in zip(recs, out):
                err = np.abs(o.payload - r.payload).max()
                scale = np.abs(r.payload).max() / 127
                assert err <= scale * 0.51 + 1e-7


def test_pallas_codec_ragged_and_tiny_payloads(rng, quant_backend_guard):
    """Edge widths around the QBLOCK boundary (1, 255..257) through the
    rows codec: parity must hold where block padding kicks in."""
    for dim in (1, 255, 256, 257):
        recs = [StreamRecord("f", 0, 0, s, rng.randn(dim).astype(np.float32))
                for s in range(4)]
        set_quant_backend("numpy")
        a = encode_batch(recs, compress="int8")
        set_quant_backend("pallas")
        b = encode_batch(recs, compress="int8")
        assert a == b, f"dim={dim}"
        out = decode_batch(b)
        assert all(o.payload.shape == (dim,) for o in out)


def test_set_quant_backend_validates(quant_backend_guard):
    prev = set_quant_backend("numpy")
    assert prev in ("auto", "numpy", "pallas")
    assert get_quant_backend() == "numpy"
    with pytest.raises(ValueError, match="quant backend"):
        set_quant_backend("cuda")
