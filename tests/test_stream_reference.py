"""Per-region streaming DMD through ``Session`` against a plain reference.

Three regions write seeded snapshots of d = 48 floats with one output step
in flight: step s is written once every region's ordered ``StreamingDMD``
stage has analysed step s − 1, so each micro-batch holds one snapshot and
each is followed by one eigensolve (exact DMD within the snapshot window,
the Gram route past it).  Every result is compared with online DMD over
its region's whole history in float64 ``jax.numpy``: G = Σ x xᵀ,
A = Σ y xᵀ, the rank-r projection M = U_rᵀ A U_r S_r⁻¹ onto G's leading
eigenvectors, and its eigenvalues.

Tolerance: ``TOL`` = 1e-5 on the widest eigenvalue gap.  The stage
accumulates G and A in float32 at full precision; on these well-separated
modes float32 rounding moves an eigenvalue by under 2e-6 (seeds 0–11: at
most 1.7e-6 over a run).  The same reference with every product taken in
three bfloat16 passes (the bf16×3 control, the precision one step below)
moves the worst eigenvalue of a run by 1e-4 or more, and must fail it.
"""
import threading

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np
import pytest

from repro.analysis.dmd import StreamingDMD
from repro.workflow import OperatorPipeline, Session, WorkflowConfig

D, REGIONS, STEPS, RANK, WINDOW = 48, 3, 24, 4, 8
REL_TOL = 1e-4               # StreamingDMD's truncation (dmd._REL_TOL)
TOL = 1e-5


def _histories(seed: int) -> np.ndarray:
    """(REGIONS, STEPS, D) snapshots: per region, RANK/2 slowly damped mode
    pairs whose seeded frequencies lie apart (so that no pair hides behind
    another and every kept direction stands well above the truncation),
    with seeded shapes and phases, plus 1e-3 noise."""
    rng = np.random.default_rng(seed)
    t = np.arange(STEPS)
    pairs = RANK // 2
    out = []
    for _ in range(REGIONS):
        freq = rng.uniform(0.3, 0.6, pairs) + 0.6 * np.arange(pairs)
        lam = rng.uniform(0.98, 1.0, pairs) * np.exp(1j * freq)
        modes = (rng.standard_normal((D, pairs))
                 + 1j * rng.standard_normal((D, pairs)))
        amp = rng.uniform(0.9, 1.1, pairs) * np.exp(1j * rng.uniform(0, 6,
                                                                      pairs))
        field = 2 * (modes @ (amp[:, None] * lam[:, None] ** t)).real.T
        out.append(field + 1e-3 * rng.standard_normal(field.shape))
    return np.asarray(out, np.float32)


def _run_session(hist: np.ndarray) -> dict:
    """Write every step through an ordered per-region ``StreamingDMD``
    stage, one output step in flight; returns each region's results and
    the rows of every micro-batch."""
    states = [StreamingDMD(n_features=D, window=WINDOW, rank=RANK)
              for _ in range(REGIONS)]
    analysed = [-1] * REGIONS
    rows: list[int] = []
    cond = threading.Condition()

    def stage(key, batch):
        region = int(key.rsplit("/r", 1)[1])
        recs = sorted(batch, key=lambda r: r.step)
        sd = states[region]
        sd.update_batch(np.stack([r.payload for r in recs]))
        eigs = sd.eigenvalues() if sd.n_seen >= 3 else None
        with cond:
            analysed[region] = recs[-1].step
            rows.append(len(recs))
            cond.notify_all()
        return recs[-1].step, eigs

    pipeline = (OperatorPipeline(granularity="batch")
                .key_by("region", lambda key, batch: key)
                .map("dmd", stage, ordering="ordered")
                .sink("eigs"))
    session = Session(WorkflowConfig(
        n_producers=REGIONS, n_groups=1, executors_per_group=REGIONS,
        compress="zstd", backpressure="block", trigger_interval=0.01,
        transport="inprocess"), pipeline=pipeline)
    field = session.open_field("velocity", shape=(D,))
    for s in range(STEPS):
        with cond:
            assert cond.wait_for(lambda: min(analysed) >= s - 1, timeout=60)
        field.write_batch(s, list(hist[:, s]), ranks=list(range(REGIONS)),
                          t=float(s))
    with cond:
        assert cond.wait_for(lambda: min(analysed) == STEPS - 1, timeout=60)
    session.flush(timeout=60.0)
    session.close()
    results = {}
    for key, (step, eigs), _t in session.results("eigs"):
        results[(int(key.rsplit("/r", 1)[1]), step)] = eigs
    return {"results": results, "rows": rows,
            "n_seen": [sd.n_seen for sd in states]}


def _split(a: np.ndarray):
    hi = a.astype(ml_dtypes.bfloat16).astype(np.float32)
    return hi, (a - hi).astype(ml_dtypes.bfloat16).astype(np.float32)


def _matmul_bf16x3(a, b) -> np.ndarray:
    """float32 a @ b from three bfloat16 passes (hi·hi + hi·lo + lo·hi)."""
    ah, al = _split(np.asarray(a, np.float32))
    bh, bl = _split(np.asarray(b, np.float32))
    return ah @ bh + (ah @ bl + al @ bh)


def _matmul_f64(a, b) -> np.ndarray:
    return np.asarray(jnp.asarray(a) @ jnp.asarray(b))


def _online_dmd(snaps: np.ndarray, matmul, dtype) -> np.ndarray:
    """Eigenvalues of online DMD over (n, D) ``snaps``, each product by
    ``matmul``, truncated as StreamingDMD truncates (top RANK directions
    of G with s² above REL_TOL of the largest)."""
    X, Y = snaps[:-1].astype(dtype), snaps[1:].astype(dtype)
    G, A = matmul(X.T, X), matmul(Y.T, X)
    s, U = np.linalg.eigh(np.asarray(G, dtype))
    s, U = s[::-1][:RANK], U[:, ::-1][:, :RANK]
    good = s > REL_TOL * s[0]
    U, s = U[:, good], s[good]
    M = matmul(matmul(U.T, A), U) / s[None, :]
    return np.linalg.eigvals(np.asarray(M, np.float64))


def _reference(snaps: np.ndarray) -> np.ndarray:
    with jax.enable_x64(True), jax.default_matmul_precision("highest"):
        return _online_dmd(snaps, _matmul_f64, np.float64)


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


@pytest.fixture(scope="module", params=[0, 1, 2])
def session_run(request):
    hist = _histories(request.param)
    return hist, _run_session(hist)


def test_one_step_in_flight_gives_one_snapshot_per_update(session_run):
    _hist, out = session_run
    assert out["rows"] == [1] * (REGIONS * STEPS)
    assert out["n_seen"] == [STEPS] * REGIONS
    assert sorted(out["results"]) == [(r, s) for r in range(REGIONS)
                                      for s in range(STEPS)]


def test_every_result_matches_the_float64_reference(session_run):
    hist, out = session_run
    routes = set()
    for (region, step), eigs in out["results"].items():
        if step < 2:                 # fewer than 3 snapshots: no solve
            assert eigs is None
            continue
        routes.add("exact" if step < WINDOW else "gram")
        want = _reference(hist[region, : step + 1])
        assert _widest_gap(eigs, want) <= TOL, (region, step)
    assert routes == {"exact", "gram"}


def test_the_bf16x3_control_fails_the_tolerance(session_run):
    hist, out = session_run
    widest = max(
        _widest_gap(_online_dmd(hist[region, : step + 1], _matmul_bf16x3,
                                np.float32),
                    _reference(hist[region, : step + 1]))
        for region, step in out["results"] if step >= 2)
    assert widest > 3 * TOL
