"""The plain reference against textbook exact DMD, and the control."""
import numpy as np
import pytest

from bench import control, reference
from bench.payloads import snapshot_pool


def _exact_dmd_svd(snaps, k):
    X, Y = snaps[:-1].T.astype(np.float64), snaps[1:].T.astype(np.float64)
    U, s, Vt = np.linalg.svd(X, full_matrices=False)
    At = (U[:, :k].T @ Y @ Vt[:k].T) / s[:k][None, :]
    return np.sort_complex(np.linalg.eigvals(At))


@pytest.mark.parametrize("n,d", [(24, 64), (80, 40)])   # both Gram sides
def test_reference_matches_svd_exact_dmd(n, d):
    snaps = snapshot_pool(3, 1, n, d)[0]
    got, s2 = reference.dmd_eigs(snaps, 4)
    np.testing.assert_allclose(np.sort_complex(got), _exact_dmd_svd(snaps, 4),
                               atol=1e-9)
    assert np.all(np.diff(s2) <= 0)


def test_reference_finds_the_payload_oscillators():
    # 3 damped oscillators -> 3 conjugate pairs on or inside the unit circle
    snaps = snapshot_pool(5, 1, 64, 128, noise=0.0)[0]
    eigs, _ = reference.dmd_eigs(snaps, 6)
    assert np.all(np.abs(eigs) <= 1.0 + 1e-9)
    assert np.all(np.abs(eigs) >= 0.96)


def test_int8_roundtrip_matches_the_program_codec():
    from repro.core import records
    x = np.random.default_rng(0).standard_normal((5, 2304)).astype(np.float32)
    recs = [records.StreamRecord("f", 0, r, 0, x[r]) for r in range(5)]
    prev = records.set_quant_backend("numpy")
    try:
        back = records.decode_batch(records.encode_batch(recs,
                                                         compress="int8+zstd"))
    finally:
        records.set_quant_backend(prev)
    np.testing.assert_array_equal(np.stack([r.payload for r in back]),
                                  reference.int8_roundtrip(x))


def test_compare_reads_the_widest_gap_and_truncation():
    snaps = snapshot_pool(7, 1, 40, 96)[0]
    want, _ = reference.dmd_eigs(snaps, 4)
    err, gap = reference.compare(want, snaps, 4, 1e-4)
    assert err < 1e-9 and gap == 0
    err, _ = reference.compare(want + 0.01, snaps, 4, 1e-4)
    assert err == pytest.approx(0.01, rel=1e-6)
    err, gap = reference.compare(want[:3], snaps, 4, 1e-4)
    assert gap == 1
    assert reference.compare(np.full(4, np.nan), snaps, 4, 1e-4)[0] == np.inf


def test_control_is_the_reference_one_precision_step_down():
    snaps = snapshot_pool(11, 1, 33, 512)[0]
    want, _ = reference.dmd_eigs(snaps, 4)
    got = control.eigs(snaps, 4, 1e-4)
    err, _ = reference.compare(got, snaps, 4, 1e-4)
    # close to float64, but off by far more than float32 rounding
    assert 1e-6 < err < 0.5
    a = np.random.default_rng(1).standard_normal((64, 64)).astype(np.float32)
    rel = np.abs(control.matmul_high(a, a) - a.astype(np.float64) @ a)
    assert rel.max() > 1e-6 and rel.max() < 1e-2
