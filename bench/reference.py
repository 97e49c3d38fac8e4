"""The plain reference the benchmark holds the system to: numpy, float64.

Nothing here imports the program.  Two pieces, both copied in spirit from
``chip_smoke.py`` and written out again so that no change to the program
can move the yardstick:

* :func:`int8_roundtrip` — the wire codec's documented loss (per record,
  blocks of ``QBLOCK`` floats, scale ``max|block| * (1/127)`` in float32,
  round half to even, clip to ±127), so the reference sees the snapshots
  exactly as the analysis receives them;
* :func:`dmd_eigs` — exact DMD truncated to ``k`` directions: X = the
  snapshots but the last, Y = the snapshots but the first, the reduced
  operator Uᵀ Y V S⁻¹ of X = U S Vᵀ, and its eigenvalues.  It takes U and
  S from the Gram matrix of whichever side of X is smaller (the method of
  snapshots when there are fewer snapshots than features), which in
  float64 keeps every direction the truncation rule admits to ~1e-12.

:func:`compare` matches the system's eigenvalues to the reference's,
nearest first, and returns the widest gap.
"""
from __future__ import annotations

import numpy as np

QBLOCK = 256


def int8_roundtrip(x: np.ndarray) -> np.ndarray:
    """(n, d) snapshots as the int8 wire codec delivers them."""
    x = np.asarray(x, np.float32)
    n, d = x.shape
    nb = -(-d // QBLOCK)
    b = np.pad(x, ((0, 0), (0, nb * QBLOCK - d))).reshape(n * nb, QBLOCK)
    scale = (np.maximum(np.abs(b).max(axis=1), np.float32(1e-20))
             * np.float32(1.0 / 127.0))
    q = np.clip(np.round(b / scale[:, None]), -127, 127)
    return (q * scale[:, None]).reshape(n, nb * QBLOCK)[:, :d]


def dmd_eigs(snaps: np.ndarray, k: int) -> tuple[np.ndarray, np.ndarray]:
    """Eigenvalues of exact DMD truncated to ``k`` directions, float64.

    ``snaps``: (n, d), one snapshot per row in time order.  Returns the
    eigenvalues and the squared singular values s² of X, descending."""
    X = np.asarray(snaps, np.float64)[:-1].T        # (d, m)
    Y = np.asarray(snaps, np.float64)[1:].T
    d, m = X.shape
    if m <= d:
        # X = U S Vᵀ with V, S² from XᵀX; Uᵀ Y V S⁻¹ = S⁻¹ Vᵀ (XᵀY) V S⁻¹
        s2, V = np.linalg.eigh(X.T @ X)
        s2, V = s2[::-1], V[:, ::-1]
        k = min(k, m)
        inv = 1.0 / np.sqrt(s2[:k])
        At = (V[:, :k].T @ (X.T @ Y) @ V[:, :k]) * inv[:, None] * inv[None, :]
    else:
        # U, S² from X Xᵀ; Uᵀ Y V S⁻¹ = Uᵀ (Y Xᵀ) U S⁻²
        s2, U = np.linalg.eigh(X @ X.T)
        s2, U = s2[::-1], U[:, ::-1]
        k = min(k, d)
        At = (U[:, :k].T @ (Y @ X.T) @ U[:, :k]) / s2[:k][None, :]
    return np.linalg.eigvals(At), np.maximum(s2, 0.0)


def kept_directions(s2: np.ndarray, rank: int, rel_tol: float) -> int:
    """How many of the top ``rank`` directions the truncation rule keeps:
    those with s² above ``rel_tol`` times the largest."""
    r = min(rank, s2.size)
    return int((s2[:r] > rel_tol * s2[0]).sum())


def compare(got: np.ndarray, snaps: np.ndarray, rank: int,
            rel_tol: float) -> tuple[float, int]:
    """(widest eigenvalue gap, |directions kept - reference rule|).

    ``got``: the system's eigenvalues for ``snaps`` (non-finite entries are
    the truncated directions).  The reference is taken at the same number
    of directions, and each of its eigenvalues, largest first, is matched
    to the nearest system eigenvalue not yet matched.  No finite
    eigenvalue at all reads as an infinite gap."""
    got = np.asarray(got)
    got = got[np.isfinite(got)]
    if got.size == 0:
        return float("inf"), rank
    want, s2 = dmd_eigs(snaps, got.size)
    rank_gap = abs(got.size - kept_directions(s2, rank, rel_tol))
    pool = list(got)
    widest = 0.0
    for w in want[np.argsort(-np.abs(want))]:
        j = int(np.argmin([abs(g - w) for g in pool]))
        widest = max(widest, float(abs(pool.pop(j) - w)))
    return widest, rank_gap
