"""Reduce a profiler trace (``.xplane.pb``) to the benchmark's device numbers.

* busy time: the union of the intervals in which an operation ran on a
  device (the ``XLA Ops`` line of each ``/device:TPU:n`` plane), averaged
  over the devices traced;
* device time per program, by stable name: each jitted program is one
  event on the ``XLA Modules`` line, named after the Python function
  (``jit__gram_operator``, ``jit__quantize``, ...);
* Pallas kernel calls (``tpu_custom_call``), and the ``pad`` operations
  that feed them, in time order with their device time and HLO text, for
  roofline shares; :func:`arrays` reads the text's shapes and the memory
  space each array lives in;
* idle gaps: every stretch between busy intervals, attributed to the
  benchmark's host span (``bench.<name>`` annotations on the host plane)
  that overlaps it most, or to ``(no bench span)``.

A ``bench.measured_window`` annotation spans the measured window; device
intervals are clipped to it, so the trace's own edges do not count.  Op
events are named by their HLO text; their short name is the instruction's
name without its numeric suffix (``%_quantize.1 = ...`` -> ``_quantize``).

Only ``jax.profiler.ProfileData`` is used to read the file.
"""
from __future__ import annotations

import bisect
import re
from pathlib import Path

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."
NO_SPAN = "(no bench span)"
WINDOW_SPAN = "measured_window"
KERNEL_TARGET = 'custom_call_target="tpu_custom_call"'
TOP = 10

_ARRAY = re.compile(r"\b(f32|s8|bf16|s32)\[([0-9,]*)\](?:\{([^}]*)\})?")
_SPACE = re.compile(r"S\((\d+)\)")
_ATTRS = re.compile(r"\), [a-z_]+=")      # end of the operand list
_OP = re.compile(r"^%([A-Za-z_][\w-]*?)(?:\.\d+)? = ")


def program_name(event_name: str) -> str:
    """``jit__gram_operator(123)`` -> ``_gram_operator``: the jitted
    function's own name, stable across runs and refactors of its body."""
    name = event_name.split("(", 1)[0]
    return name[4:] if name.startswith("jit_") else name


def op_name(text: str) -> str:
    """Short name of an op event named by its HLO text."""
    m = _OP.match(text)
    return m.group(1) if m else text


def arrays(text: str) -> list[tuple[str, tuple[int, ...], int]]:
    """(dtype, dims, memory space) of the results and then the operands of
    an op, from its HLO text; the attributes after the operand list (such
    as ``operand_layout_constraints``) are left out.  Memory space 0 is
    HBM; a layout that ends in ``S(1)`` places the array in the core's
    VMEM, where XLA's own copies put it before or take it from after."""
    m = _ATTRS.search(text)
    head = text[:m.start() + 1] if m else text
    return [(dt, tuple(int(x) for x in dims.split(",") if x),
             int(sp.group(1)) if (sp := _SPACE.search(layout)) else 0)
            for dt, dims, layout in _ARRAY.findall(head)]


def union(intervals) -> list[tuple[int, int]]:
    """Merge overlapping [start, end) intervals, sorted."""
    out: list[list[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def load(path: Path) -> dict:
    """The parts of one trace file the reduction needs, as plain data:
    per device, its op and module events; the host's bench spans."""
    import jax
    pd = jax.profiler.ProfileData.from_file(str(path))
    devices, spans = {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:") and "SparseCore" not in plane.name:
            dev = {"ops": [], "modules": []}
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(line.name)
                if key is None:
                    continue
                for e in line.events:
                    dev[key].append((e.name, e.start_ns, e.start_ns + e.duration_ns))
            devices[plane.name] = dev
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append((e.name[len(SPAN_PREFIX):], e.start_ns,
                                      e.start_ns + e.duration_ns))
    return {"devices": devices, "spans": spans}


def reduce(data: dict, window_s: float) -> dict:
    """Busy and idle time, per-program device time, the longest device
    operations, kernel calls and the idle gaps by host span, from
    :func:`load`'s data."""
    win = [(s, e) for n, s, e in data["spans"] if n == WINDOW_SPAN]
    lo, hi = (win[0][0], win[0][1]) if win else (float("-inf"), float("inf"))
    if win:
        window_s = (hi - lo) * 1e-9
    spans = _Spans([sp for sp in data["spans"] if sp[0] != WINDOW_SPAN])
    busy_total, programs, ops_time = 0.0, {}, {}
    kernel_calls: list[tuple[str, float, str]] = []
    gaps_by_span: dict[str, float] = {}
    for dev in data["devices"].values():
        ops = sorted((s, e, t) for t, s, e in dev["ops"] if e > lo and s < hi)
        busy = union((max(s, lo), min(e, hi)) for s, e, _t in ops)
        busy_total += sum(e - s for s, e in busy) * 1e-9
        for s, e, text in ops:
            name = op_name(text)
            ops_time[name] = ops_time.get(name, 0.0) + (e - s) * 1e-9
            if KERNEL_TARGET in text or name == "pad":
                kernel_calls.append((name, (e - s) * 1e-9, text))
        for name, s, e in dev["modules"]:
            if lo <= s < hi:
                p = programs.setdefault(program_name(name), [0.0, 0])
                p[0] += (e - s) * 1e-9
                p[1] += 1
        # idle stretches, the window's own edges included
        bounds = [(lo, lo)] + busy + [(hi, hi)] if win else busy
        for (_s0, e0), (s1, _e1) in zip(bounds, bounds[1:]):
            if s1 > e0:
                who = spans.attribute(e0, s1)
                gaps_by_span[who] = gaps_by_span.get(who, 0.0) + (s1 - e0) * 1e-9
    n = max(1, len(data["devices"]))
    top_ops = sorted(ops_time.items(), key=lambda kv: -kv[1])[:TOP]
    top_gaps = sorted(gaps_by_span.items(), key=lambda kv: -kv[1])[:TOP]
    return {"busy_s": busy_total / n, "window_s": window_s,
            "n_devices": len(data["devices"]),
            "programs": {k: {"seconds": v[0], "calls": v[1]}
                         for k, v in programs.items()},
            "kernel_calls": kernel_calls,
            "device_ops": [[k, v] for k, v in top_ops],
            "idle_gaps": [[k, v] for k, v in top_gaps]}


class _Spans:
    """Host spans sorted by start, for finding the one that overlaps a gap
    most without scanning them all."""

    def __init__(self, spans):
        self.spans = sorted(spans, key=lambda sp: sp[1])
        self.starts = [sp[1] for sp in self.spans]
        self.longest = max((e - s for _n, s, e in self.spans), default=0)

    def attribute(self, g0: int, g1: int) -> str:
        best, best_overlap = NO_SPAN, 0
        j = bisect.bisect_left(self.starts, g1) - 1
        while j >= 0 and self.starts[j] > g0 - self.longest:
            name, s, e = self.spans[j]
            overlap = min(e, g1) - max(s, g0)
            if overlap > best_overlap:
                best, best_overlap = name, overlap
            j -= 1
        return best


def reduce_dir(trace_dir: Path, window: tuple[float, float]) -> dict | None:
    """Reduce the one ``.xplane.pb`` a traced window wrote; None if the
    profiler wrote none."""
    files = sorted(Path(trace_dir).glob("**/*.xplane.pb"))
    if not files:
        return None
    return reduce(load(files[-1]), window[1] - window[0])
