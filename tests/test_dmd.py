"""DMD analysis: eigenvalue recovery on known linear systems, streaming ==
exact agreement, Fig-5 stability metric semantics."""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.analysis.dmd import exact_dmd, gram_update, gram_eigs, StreamingDMD
from repro.analysis.metrics import unit_circle_distance, region_stability


def _linear_system_snapshots(n_feat=32, n_steps=40, decay=0.98, freq=0.2, seed=0):
    """x_{t+1} = A x_t with known complex eigenvalues decay*exp(+-i freq)."""
    rng = np.random.RandomState(seed)
    rot = decay * np.array([[np.cos(freq), -np.sin(freq)],
                            [np.sin(freq), np.cos(freq)]])
    mix = np.linalg.qr(rng.randn(n_feat, 2))[0]
    z = np.array([1.0, 0.0])
    snaps = []
    for _ in range(n_steps):
        snaps.append(mix @ z)
        z = rot @ z
    return np.stack(snaps, axis=1), decay


def test_exact_dmd_recovers_eigenvalues():
    snaps, decay = _linear_system_snapshots()
    eigs, energy = exact_dmd(jnp.asarray(snaps), rank=4)
    eigs = np.asarray(eigs)
    eigs = eigs[np.isfinite(eigs)]      # drop null-direction padding
    mods = np.sort(np.abs(eigs))[::-1][:2]
    np.testing.assert_allclose(mods, [decay, decay], atol=1e-3)
    assert float(energy) > 0.99


def test_streaming_matches_exact():
    snaps, decay = _linear_system_snapshots(n_steps=60)
    sd = StreamingDMD(n_features=32, window=16, rank=4)
    for t in range(snaps.shape[1]):
        sd.update(snaps[:, t])
    eigs = sd.eigenvalues()
    eigs = eigs[np.isfinite(eigs)]      # drop rank padding
    top = np.sort(np.abs(eigs))[::-1][:2]
    np.testing.assert_allclose(top, [decay, decay], atol=5e-3)


def test_gram_update_matches_outer():
    rng = np.random.RandomState(0)
    G = jnp.zeros((8, 8)); A = jnp.zeros((8, 8))
    xs = rng.randn(5, 8).astype(np.float32)
    for i in range(4):
        G, A = gram_update(G, A, jnp.asarray(xs[i]), jnp.asarray(xs[i + 1]))
    Gw = sum(np.outer(xs[i], xs[i]) for i in range(4))
    Aw = sum(np.outer(xs[i + 1], xs[i]) for i in range(4))
    np.testing.assert_allclose(np.asarray(G), Gw, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(A), Aw, rtol=1e-5, atol=1e-5)


def test_stability_metric_semantics():
    stable = np.exp(1j * np.linspace(0, 2, 5))            # on unit circle
    decaying = 0.7 * stable
    assert unit_circle_distance(stable) < 1e-10
    assert unit_circle_distance(decaying) == pytest.approx(0.09, abs=1e-6)
    panel = region_stability({"r0": stable, "r1": decaying})
    assert panel["r0"] < panel["r1"]          # paper: closer to 0 = stable
