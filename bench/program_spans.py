"""The program's own spans in a profiler trace, beside the device's time.

The program marks its units of work (a frame, a micro-batch, a window
solve) with ``repro.<layer>.<what>`` host annotations
(``repro.runtime.telemetry.span``) whose arguments carry the unit's counts;
the benchmark marks its calls into the program with ``bench.<name>``
(``bench/harness.py`` ``Context.span``).  Both land on the host plane of
the ``.xplane.pb`` that :mod:`bench.trace` reduces, one line per thread,
on the clock of the device's operations.  :func:`reduce` returns what
:func:`bench.trace.reduce` returns, with two differences:

* ``program_spans``: per program span (name without ``repro.``), over the
  units of work that start inside ``bench.measured_window`` (the outermost
  spans of a thread, each with every span nested in it, so that a solve
  and its copy count together or not at all): ``calls``, ``seconds``,
  ``self_seconds`` (the duration less what child spans on the same thread
  cover) and ``args``, the sum of each numeric argument;
* ``idle_gaps``: each stretch in which the device ran nothing goes, by
  :mod:`bench.trace`'s rule of most overlap, to the innermost span open on
  some thread, bench or program, so a gap inside ``bench.window_solve``
  goes to ``repro.analysis.fill`` where that is what ran.  Bench spans keep
  their short names, program spans their ``repro.`` prefix, and a gap
  under no span is ``(no span)``.  With bench spans alone it is
  :mod:`bench.trace`'s attribution.

:func:`reduce_dir` takes the arguments of :func:`bench.trace.reduce_dir`.
"""
from __future__ import annotations

import bisect
from pathlib import Path

from bench import trace

PROGRAM_PREFIX = "repro."
NO_SPAN = "(no span)"
_WINDOW = trace.SPAN_PREFIX + trace.WINDOW_SPAN


def host_spans(path: Path) -> list[tuple]:
    """Every bench and program span on the trace's host planes:
    ``(name, thread, start_ns, end_ns, args)``, with the full name
    (``bench.write``, ``repro.broker.encode``) and the thread as
    (plane, line) indices."""
    import jax
    out = []
    planes = jax.profiler.ProfileData.from_file(str(path)).planes
    for p, plane in enumerate(planes):
        if not plane.name.startswith("/host:"):
            continue
        for li, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith((trace.SPAN_PREFIX, PROGRAM_PREFIX)):
                    args = dict(e.stats) if e.name.startswith(
                        PROGRAM_PREFIX) else {}
                    out.append((e.name, (p, li), e.start_ns,
                                e.start_ns + e.duration_ns, args))
    return out


def load(path: Path) -> dict:
    """:func:`bench.trace.load`'s data, plus ``host``: :func:`host_spans`."""
    data = trace.load(path)
    data["host"] = host_spans(path)
    return data


def innermost(spans) -> tuple[dict, list[float], list[float]]:
    """Split each thread's time into pieces over which one span is the
    innermost open one.  ``spans``: ``(name, thread, start, end, ...)``,
    nested on each thread.  Returns ``{thread: [(start, end, i), ...]}``,
    pieces in time order with ``i`` the span's index in ``spans``; each
    span's self time (the sum of its pieces); and the start of each span's
    outermost enclosing span on its thread (its own, if none)."""
    self_ns = [0.0] * len(spans)
    root = [sp[2] for sp in spans]
    by_thread: dict = {}
    for i, sp in enumerate(spans):
        by_thread.setdefault(sp[1], []).append(i)
    pieces: dict = {}
    for thread, idxs in by_thread.items():
        idxs.sort(key=lambda i: (spans[i][2], -spans[i][3]))
        out: list[tuple[float, float, int]] = []
        stack: list[int] = []
        t = float("-inf")

        def emit(j: int, until: float) -> None:
            nonlocal t
            if until > t:
                out.append((t, until, j))
                self_ns[j] += until - t
                t = until

        for i in idxs:
            start = spans[i][2]
            while stack and spans[stack[-1]][3] <= start:
                j = stack.pop()
                emit(j, spans[j][3])
            if stack:
                emit(stack[-1], start)
                root[i] = root[stack[0]]
            stack.append(i)
            t = max(t, start)
        while stack:
            j = stack.pop()
            emit(j, spans[j][3])
        pieces[thread] = out
    return pieces, self_ns, root


class _Innermost:
    """The innermost pieces of every thread, for finding the one that
    overlaps a gap most: per thread, the pieces that can overlap it are one
    run found by bisection."""

    def __init__(self, pieces: dict, labels: list[str]):
        self.labels = labels
        self.threads = [([s for s, _e, _i in ps], [e for _s, e, _i in ps],
                         [i for _s, _e, i in ps]) for ps in pieces.values()]

    def attribute(self, g0: float, g1: float) -> str:
        """Most overlap wins; of equal overlaps, the later start (the rule
        of :meth:`bench.trace._Spans.attribute`)."""
        best, best_key = NO_SPAN, (0, float("-inf"))
        for starts, ends, idx in self.threads:
            k = bisect.bisect_right(ends, g0)
            while k < len(starts) and starts[k] < g1:
                key = (min(ends[k], g1) - max(starts[k], g0), starts[k])
                if key[0] > 0 and key > best_key:
                    best, best_key = self.labels[idx[k]], key
                k += 1
        return best


def _label(name: str) -> str:
    return name[len(trace.SPAN_PREFIX):] if name.startswith(
        trace.SPAN_PREFIX) else name


def reduce(data: dict, window_s: float) -> dict:
    """:func:`bench.trace.reduce` of :func:`load`'s data, with the idle
    gaps by innermost span and ``program_spans`` added."""
    win = [(s, e) for n, _t, s, e, _a in data["host"] if n == _WINDOW]
    lo, hi = win[0] if win else (float("-inf"), float("inf"))
    out = trace.reduce(
        {"devices": data["devices"],
         "spans": [sp for sp in data["spans"] if sp[0] == trace.WINDOW_SPAN]},
        window_s)
    spans = [sp for sp in data["host"] if sp[0] != _WINDOW]
    pieces, self_ns, root = innermost(spans)
    who = _Innermost(pieces, [_label(sp[0]) for sp in spans])
    gaps: dict[str, float] = {}
    for dev in data["devices"].values():
        busy = trace.union((max(s, lo), min(e, hi))
                           for _t, s, e in dev["ops"] if e > lo and s < hi)
        bounds = [(lo, lo)] + busy + [(hi, hi)] if win else busy
        for (_s0, e0), (s1, _e1) in zip(bounds, bounds[1:]):
            if s1 > e0:
                name = who.attribute(e0, s1)
                gaps[name] = gaps.get(name, 0.0) + (s1 - e0) * 1e-9
    out["idle_gaps"] = [[k, v] for k, v in
                        sorted(gaps.items(), key=lambda kv: -kv[1])[:trace.TOP]]
    out["program_spans"] = totals(spans, self_ns, root, lo, hi)
    return out


def totals(spans, self_ns, root, lo: float, hi: float) -> dict:
    """Per program span name, over the spans whose outermost enclosing
    span starts in ``[lo, hi)``: calls, seconds, self seconds and the sum
    of each numeric argument."""
    out: dict[str, dict] = {}
    for (name, _t, s, e, args), own, r in zip(spans, self_ns, root):
        if not name.startswith(PROGRAM_PREFIX) or not lo <= r < hi:
            continue
        row = out.setdefault(name[len(PROGRAM_PREFIX):], {
            "calls": 0, "seconds": 0.0, "self_seconds": 0.0, "args": {}})
        row["calls"] += 1
        row["seconds"] += (e - s) * 1e-9
        row["self_seconds"] += own * 1e-9
        for k, v in args.items():
            if isinstance(v, (int, float)) and not isinstance(v, bool):
                row["args"][k] = row["args"].get(k, 0) + v
    return out


def reduce_dir(trace_dir: Path, window: tuple[float, float]) -> dict | None:
    """Reduce the one ``.xplane.pb`` a traced window wrote; None if the
    profiler wrote none."""
    files = sorted(Path(trace_dir).glob("**/*.xplane.pb"))
    if not files:
        return None
    return reduce(load(files[-1]), window[1] - window[0])
