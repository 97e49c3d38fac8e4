"""The benchmark's arithmetic: tails over all results, rates over the window.

Every end-to-end number is computed here from raw facts a run records
(emission and creation stamps, counters at the window's two edges), so no
reader can quietly narrow what a metric covers.
"""
from __future__ import annotations

import math


def percentile(values, p: float) -> float | None:
    """Nearest-rank percentile (``p`` in [0, 100]) over every value given:
    the smallest value with at least p% of the sample at or below it.
    None for an empty sample."""
    vals = sorted(values)
    if not vals:
        return None
    rank = max(1, math.ceil(p / 100.0 * len(vals)))
    return float(vals[rank - 1])


def in_window(t: float, window: tuple[float, float]) -> bool:
    """Whether a stamp falls inside the measured window ``[t0, t1]``."""
    return window[0] <= t <= window[1]


def tail_ms(results, window, p: float = 95.0) -> float | None:
    """p-th percentile, in ms, of ``t_emit - t_newest`` over every result
    emitted inside the window (all stages together)."""
    return _ms(percentile([r.t_emit - r.t_newest for r in results
                           if in_window(r.t_emit, window)], p))


def rate(count: float, window) -> float | None:
    """A count completed inside the window per second of the window."""
    span = window[1] - window[0]
    return None if span <= 0 else count / span


def delta(begin: dict, end: dict, key: str) -> float:
    """How far a monotone counter moved across the window."""
    return end[key] - begin[key]


def mean_span_ms(spans, name: str, window) -> float | None:
    """Mean duration, in ms, of the named host spans that started inside
    the window."""
    ds = [t1 - t0 for n, t0, t1 in spans if n == name and in_window(t0, window)]
    return _ms(sum(ds) / len(ds)) if ds else None


def _ms(seconds: float | None) -> float | None:
    return None if seconds is None else seconds * 1e3


def idle_share(trace: dict | None) -> float | None:
    """Share of the traced window, in %, in which no operation ran on the
    device: 1 - busy union / window.  None without a trace."""
    if not trace or not trace["n_devices"] or trace["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
