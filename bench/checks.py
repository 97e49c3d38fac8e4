"""Sampling and comparison shared by the deployment drivers.

Once the window has closed, a driver draws a sample of the results the
window emitted (from the run's seed) and compares each with the plain
reference over the same snapshots, int8 round trip included, beside the
control (``bench/control.py``) over the same snapshots.  With
``ctx.control`` the control's eigenvalues stand in for the system's.
"""
from __future__ import annotations

import numpy as np

from bench import control, reference


def sample(items: list, n: int, rng: np.random.Generator) -> list:
    """``n`` items drawn without replacement from ``rng``, in their order."""
    if len(items) <= n:
        return list(items)
    return [items[i] for i in sorted(rng.choice(len(items), n, replace=False))]


def gap_ratio_median(pairs, rank: int, rel_tol: float, ctx,
                     what: str) -> tuple[float, int]:
    """(median over the results of the system's widest eigenvalue gap over
    the control's, widest truncation gap) over ``pairs`` of (system
    eigenvalues, snapshots as written).

    Both gaps are to the float64 reference over the same snapshots, int8
    round trip included.  How far rounding moves DMD eigenvalues depends
    on the data — how close the kept directions lie to the dropped ones —
    and one result's gap swings over decades from pane to pane, the
    control's with it; their ratio does not, and its median over many
    results is steady where the widest is not.  With ``ctx.control`` the
    control stands in for the system, and every ratio is 1."""
    ratios, rank_gap = [], 0
    sys_gap = ctl_gap = 0.0
    for got, snaps in pairs:
        received = reference.int8_roundtrip(snaps)
        ctl = control.eigs(received, rank, rel_tol)
        e, g = reference.compare(ctl if ctx.control else got, received,
                                 rank, rel_tol)
        ce, _ = reference.compare(ctl, received, rank, rel_tol)
        sys_gap, ctl_gap = max(sys_gap, e), max(ctl_gap, ce)
        rank_gap = max(rank_gap, g)
        ratios.append(e / ce if ce > 0 else float("inf"))
    median = float(np.median(ratios)) if ratios else float("inf")
    lo, hi = (float(q) for q in np.quantile(ratios or [0.0], [0.1, 0.9]))
    ctx.log(f"{what}: median gap ratio {median!r} (10%: {lo!r}, 90%: "
            f"{hi!r}) over {len(pairs)} results; widest gap {sys_gap!r}, "
            f"control's {ctl_gap!r}")
    return median, rank_gap
