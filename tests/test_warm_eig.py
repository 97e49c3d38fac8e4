"""The streaming route's warm-started Gram eigensolve (``StreamingDMD``).

Past its snapshot window a stream solves G's top-p eigenspace by block
subspace iteration from the previous solve's Ritz basis, and falls back to
the full ``eigh`` (the cold route, ``gram_eigs``) when the iteration does
not converge.  On CFD slab streams the warm route has to stay as close to a
float64 reference as the cold route is; on a stream built so that it
cannot converge it has to give the cold route's answer and start the next
solve from the cold route's basis."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.dmd import (_WARM_MAX_ITERS, _WARM_MIN_ITERS,
                                StreamingDMD, _gram_operator, _no_basis,
                                _ritz_eigs, gram_eigs)
from repro.sim.cfd import CFDConfig, init_state, region_fields, step

RANK, WINDOW, REL_TOL = 4, 16, 1e-4
CFD = CFDConfig(nx=64, nz=16, n_regions=4)    # 4 slabs of 512 floats
OUTPUTS = 32                                  # every 5th step


def _cfd_slabs(seed: int) -> np.ndarray:
    """(OUTPUTS, regions, 512) slab snapshots of a seeded CFD run: the
    benchmark deployment's 0.05·N(0, 1) disturbance of u and w, every 5th
    step."""
    rng = np.random.default_rng(seed)
    state = init_state(CFD)
    mask = np.asarray(state["mask"])

    def disturbance():
        return jnp.asarray(np.float32(0.05) * mask * rng.standard_normal(
            mask.shape, dtype=np.float32))

    state = dict(state, u=state["u"] + disturbance(), w=disturbance())
    run = jax.jit(step, static_argnums=1)
    out = []
    for _ in range(OUTPUTS):
        out.append(np.stack(region_fields(state, CFD)))
        for _ in range(5):
            state = run(state, CFD)
    return np.asarray(out, np.float32)


def _reference(snaps: np.ndarray) -> np.ndarray:
    """Online DMD over (n, d) ``snaps`` in float64, truncated as
    StreamingDMD truncates."""
    X, Y = snaps[:-1].astype(np.float64), snaps[1:].astype(np.float64)
    s, U = np.linalg.eigh(X.T @ X)
    s, U = s[::-1][:RANK], U[:, ::-1][:, :RANK]
    good = s > REL_TOL * s[0]
    U, s = U[:, good], s[good]
    return np.linalg.eigvals(U.T @ (Y.T @ X) @ U / s[None, :])


def _widest_gap(got: np.ndarray, want: np.ndarray) -> float:
    """Each reference eigenvalue, largest first, matched to the nearest
    finite one of ``got`` not yet matched; the widest distance."""
    pool = list(np.asarray(got)[np.isfinite(got)])
    assert len(pool) == len(want)
    widest = 0.0
    for w in want[np.argsort(-np.abs(want))]:
        j = int(np.argmin([abs(g - w) for g in pool]))
        widest = max(widest, float(abs(pool.pop(j) - w)))
    return widest


@pytest.mark.parametrize("seed", [0, 1])
def test_warm_route_is_as_close_to_float64_as_the_cold_route(seed):
    """Every region, one snapshot at a time past its window: after each
    update the warm route's eigenvalues lie within the cold route's widest
    gap to the float64 reference of the cold route's own, and both routes
    within 1e-5 of float64 (the projection taken without deflation reached
    4e-5 to 1e-4 on these streams).  The first Gram solve of a stream is
    cold, every later one warm; none falls back."""
    slabs = _cfd_slabs(seed)
    warm_gaps, cold_gaps, apart = [], [], []
    for region in range(CFD.n_regions):
        sd = StreamingDMD(n_features=slabs.shape[2], window=WINDOW,
                          rank=RANK)
        solves = 0
        for n in range(OUTPUTS):
            sd.update_batch(slabs[n:n + 1, region])
            if sd.n_seen <= WINDOW:
                continue
            warm = sd.eigenvalues()
            solves += 1
            assert sd.warm_solves == solves - 1
            cold = gram_eigs(sd._G, sd._A, rank=RANK)
            want = _reference(slabs[: n + 1, region])
            warm_gaps.append(_widest_gap(warm, want))
            cold_gaps.append(_widest_gap(cold, want))
            apart.append(_widest_gap(warm, cold[np.isfinite(cold)]))
        assert solves == OUTPUTS - WINDOW
        assert sd.eig_fallbacks == 0
    tol = max(cold_gaps)
    assert tol < 1e-5
    assert max(warm_gaps) < 1e-5
    assert max(apart) <= tol


def _cluster_stream(d: int = 64):
    """Snapshot blocks whose Gram spectrum is 16, 8, 4 and then ten
    directions from 1.00 down to 0.92: eigenvalue p + 1 = 9 lies 3.6%
    below eigenvalue ``RANK`` = 4, so each warm iteration shrinks that
    direction's error by only ~4%.  The first block sets the spectrum
    (its trailing zero row pairs every scaled direction into G); the
    second adds x xᵀ with x of norm 0.3 in the cluster, which turns the
    cluster's eigenvectors by far more than the iteration recovers in
    ``_WARM_MAX_ITERS``; the third adds a 1e-3 perturbation that the
    iteration absorbs."""
    rng = np.random.default_rng(11)
    lam = np.array([16, 8, 4] + list(np.linspace(1.0, 0.92, 10)))
    V = np.linalg.qr(rng.standard_normal((d, lam.size)))[0]
    first = np.vstack([np.sqrt(lam)[:, None] * V.T, np.zeros((1, d))])

    def in_cluster(scale):
        c = rng.standard_normal(10)
        return scale * (V[:, 3:] @ c) / np.linalg.norm(c)

    second = np.stack([in_cluster(0.3), np.zeros(d)])
    third = np.stack([in_cluster(1e-3), np.zeros(d)])
    return [b.astype(np.float32) for b in (first, second, third)]


def _cold(G, A):
    """The cold route's (UᵀGU, UᵀAU, basis U) on G and A."""
    B, C, basis, iters, fell_back = _gram_operator(
        G, A, _no_basis(G.shape[0], RANK), jnp.bool_(False), rank=RANK)
    assert int(iters) == 0 and not bool(fell_back)
    return B, C, basis


def test_unconverged_warm_solve_falls_back_to_the_cold_route():
    first, second, third = _cluster_stream()
    sd = StreamingDMD(n_features=first.shape[1], window=4, rank=RANK)
    sd.update_batch(first)
    sd.eigenvalues()                     # the stream's first Gram solve
    assert (sd.warm_solves, sd.eig_fallbacks) == (0, 0)

    before = sd._basis
    sd.update_batch(second)
    # this stream really runs the warm iteration to its limit
    *_, iters, fell_back = _gram_operator(sd._G, sd._A, before,
                                          jnp.bool_(True), rank=RANK)
    assert int(iters) == _WARM_MAX_ITERS and bool(fell_back)
    got = sd.eigenvalues()
    assert (sd.warm_solves, sd.eig_fallbacks) == (0, 1)
    B, C, basis = _cold(sd._G, sd._A)
    np.testing.assert_array_equal(got, _ritz_eigs(B, C, RANK))
    np.testing.assert_array_equal(got, gram_eigs(sd._G, sd._A, rank=RANK))
    np.testing.assert_array_equal(np.asarray(sd._basis), np.asarray(basis))

    # the next solve starts from the fallback's basis and converges
    sd.update_batch(third)
    B, C, after, iters, fell_back = _gram_operator(
        sd._G, sd._A, basis, jnp.bool_(True), rank=RANK)
    assert _WARM_MIN_ITERS <= int(iters) < _WARM_MAX_ITERS
    assert not bool(fell_back)
    got = sd.eigenvalues()
    assert (sd.warm_solves, sd.eig_fallbacks) == (1, 1)
    np.testing.assert_array_equal(got, _ritz_eigs(B, C, RANK))
    np.testing.assert_array_equal(np.asarray(sd._basis), np.asarray(after))


def test_cold_and_warm_solves_share_one_compiled_program():
    """The benchmark compiles the Gram route with one cold solve in its
    warm-up; the warm solves after it must find that program compiled."""
    rng = np.random.default_rng(3)
    d = 96
    sd = StreamingDMD(n_features=d, window=4, rank=RANK)
    sd.update_batch(rng.standard_normal((6, d)).astype(np.float32))
    sd.eigenvalues()
    compiled = _gram_operator._cache_size()
    for _ in range(3):
        sd.update_batch(rng.standard_normal((1, d)).astype(np.float32))
        sd.eigenvalues()
    assert sd.warm_solves == 3
    assert _gram_operator._cache_size() == compiled


def test_gram_eigs_is_the_cold_route_whatever_came_before():
    """``gram_eigs`` keeps no basis: the same G and A give the same answer
    before and after a stream has warm-started on them."""
    rng = np.random.default_rng(4)
    d = 80
    sd = StreamingDMD(n_features=d, window=4, rank=RANK)
    sd.update_batch(rng.standard_normal((12, d)).astype(np.float32))
    first = gram_eigs(sd._G, sd._A, rank=RANK)
    sd.eigenvalues()
    B, C, _ = _cold(sd._G, sd._A)
    np.testing.assert_array_equal(first, _ritz_eigs(B, C, RANK))
    np.testing.assert_array_equal(first, gram_eigs(sd._G, sd._A, rank=RANK))
