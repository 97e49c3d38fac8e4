#!/usr/bin/env python3
"""The control: the analysis one precision step down.

The configurations state float32 matrix products at full precision (JAX's
``highest``).  The step below, the one that would tempt a later change, is
``high``: each product from three bfloat16 passes.  The program has that
path of its own, ``repro.analysis.dmd._PRECISION``, one setting for every
device product of the analysis, and the program run with it at ``high`` is
the control a cell's limit is set against:

    python bench/control.py --program-high --workload <cell> --seed <n> --seconds <s>

On the CPU that setting changes nothing, so :func:`eigs` stands in for it
there: ``reference.dmd_eigs`` in float32 with every matrix product taken
in three bfloat16 passes (each operand split into a bfloat16 high part and
a bfloat16 remainder, hi·hi + hi·lo + lo·hi accumulated in float32, as the
MXU does it), whose readings do not depend on the device.  Without
``--program-high`` the script runs the cell as ``bench/run.py`` does but
compares, for every sampled result, these eigenvalues of the same
snapshots in place of the system's.  Either way ``correct`` has to come
out false.
"""
from __future__ import annotations

import time

T_PROCESS = time.time()

import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

import numpy as np  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def _split(a: np.ndarray):
    import ml_dtypes
    hi = a.astype(ml_dtypes.bfloat16).astype(np.float32)
    lo = (a - hi).astype(ml_dtypes.bfloat16).astype(np.float32)
    return hi, lo


def matmul_high(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """float32 a @ b from three bfloat16 passes."""
    ah, al = _split(np.asarray(a, np.float32))
    bh, bl = _split(np.asarray(b, np.float32))
    return ah @ bh + (ah @ bl + al @ bh)


def eigs(snaps: np.ndarray, rank: int, rel_tol: float) -> np.ndarray:
    """Exact DMD eigenvalues of (n, d) ``snaps`` at the control's precision,
    truncated as the system truncates (top ``rank`` directions with s²
    above ``rel_tol`` of the largest)."""
    X = np.asarray(snaps, np.float32)[:-1].T
    Y = np.asarray(snaps, np.float32)[1:].T
    d, m = X.shape
    if m <= d:
        s2, V = np.linalg.eigh(matmul_high(X.T, X))
        s2, V = s2[::-1], V[:, ::-1]
    else:
        s2, V = np.linalg.eigh(matmul_high(X, X.T))
        s2, V = s2[::-1], V[:, ::-1]
    r = min(rank, s2.size)
    k = int((s2[:r] > rel_tol * max(s2[0], 1e-30)).sum())
    if k == 0:
        return np.full(rank, np.nan, np.complex64)
    if m <= d:
        inv = 1.0 / np.sqrt(s2[:k])
        At = matmul_high(matmul_high(V[:, :k].T, matmul_high(X.T, Y)),
                         V[:, :k]) * inv[:, None] * inv[None, :]
    else:
        At = matmul_high(matmul_high(V[:, :k].T, matmul_high(Y, X.T)),
                         V[:, :k]) / s2[:k][None, :]
    out = np.full(rank, np.nan, np.complex64)
    out[:k] = np.linalg.eigvals(At.astype(np.float64))
    return out


if __name__ == "__main__":
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    from bench.harness import main
    argv = sys.argv[1:]
    program = "--program-high" in argv
    if program:
        argv.remove("--program-high")
        from repro.analysis import dmd
        dmd._PRECISION = "high"
    sys.exit(main(argv, t_process=T_PROCESS, control=not program))
