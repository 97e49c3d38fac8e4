"""Run one cell of ``BENCHMARK.json`` once and print its result line.

Everything is found by name, so a later cell, configuration or metric is a
new file and a new entry, never an edit:

* the cell (``workloads``) names its configuration and its traffic;
* ``bench/configs/<config>.json`` holds the deployment's sizes and names
  its ``family``, whose driver is ``bench/deployments/<family>.py``;
* ``bench/traffic/<traffic>.json`` holds the traffic's parameters;
* each metric ``<name>`` is read by ``read(run)`` in
  ``bench/metrics/<name>.py``, or, for a name with a dotted suffix
  (``idle_share.sat``) that has no file of its own, in the file of the
  name before the first dot.  A reader that finds nothing returns None and
  the metric is left out of the line.

A driver exposes ``run(ctx, config, traffic) -> Outcome``.  It builds the
deployment, warms every shape the traffic uses, calls
``ctx.begin_window()``, drives the traffic for ``ctx.seconds``, calls
``ctx.end_window()``, and then checks what the window produced against the
plain reference (``bench/reference.py``).  Set-up is everything from
process start to ``begin_window``.
"""
from __future__ import annotations

import importlib.util
import json
import shutil
import sys
import threading
import time
from contextlib import contextmanager, nullcontext
from dataclasses import dataclass, field
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
TRACE_DIR = ROOT / ".bench_trace"           # one traced window, deleted after

# every lowering of a jitted program is one of these; none may fall inside
# the measured window (it would mean a shape the warm-up missed)
_LOWERING_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"


class NoAccelerator(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


@dataclass
class Emitted:
    """One analysis result: which stage, when it was emitted, the creation
    stamp of the newest snapshot in it, and how many snapshots it covers."""
    stage: str
    t_emit: float
    t_newest: float
    n: int


@dataclass
class Check:
    """One number compared with its limit; ``value <= limit`` passes."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value <= self.limit


@dataclass
class Outcome:
    """What a driver hands back after its window and its checks."""
    checks: list[Check]
    attempted: int
    failed: int
    results: list[Emitted] = field(default_factory=list)
    begin: dict = field(default_factory=dict)      # counters at window start
    end: dict = field(default_factory=dict)        # counters at window end
    facts: dict = field(default_factory=dict)      # other raw measurements


@dataclass
class Run:
    """Everything a metric reader may read."""
    setup_s: float
    window: tuple[float, float]
    spans: list
    results: list[Emitted]
    begin: dict
    end: dict
    facts: dict
    trace: dict | None
    device_kind: str


class Context:
    """The run's clock, spans and window, handed to the deployment driver.

    Host stamps are ``time.time()`` throughout, the clock the program's
    sinks stamp results with.  Spans are kept in memory; in a traced run
    each is also a ``TraceAnnotation`` named ``bench.<name>``, so the trace
    reduction can attribute device idle time to them."""

    def __init__(self, *, seed: int, seconds: float, trace: bool,
                 t_process: float, device_kind: str, control: bool = False,
                 log=print):
        self.seed = seed
        self.control = control          # compare the control, not the system
        self.seconds = seconds
        self.trace = trace
        self.t_process = t_process
        self.device_kind = device_kind
        self.log = log
        self.spans: list[tuple[str, float, float]] = []
        self.window: tuple[float, float] | None = None
        self.memory_peak_bytes: int | None = None
        self._lock = threading.Lock()
        self._in_window = False
        self.lowered_in_window: list[str] = []
        self._annotation = None
        if trace:
            import jax
            self._annotation = jax.profiler.TraceAnnotation
        import jax.monitoring
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def close(self) -> None:
        import jax.monitoring
        jax.monitoring.unregister_event_duration_listener(self._on_event)

    def _on_event(self, event: str, _secs: float, fun_name: str = "?",
                  **_kw) -> None:
        if event == _LOWERING_EVENT and self._in_window:
            with self._lock:
                self.lowered_in_window.append(fun_name)

    @contextmanager
    def span(self, name: str):
        ann = self._annotation(f"bench.{name}") if self._annotation \
            else nullcontext()
        t0 = time.time()
        try:
            with ann:
                yield
        finally:
            t1 = time.time()
            with self._lock:
                self.spans.append((name, t0, t1))

    def begin_window(self) -> float:
        if self.trace:
            import jax
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0       # Python calls: far too many
            opts.host_tracer_level = 1         # the bench.* annotations only
            jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
            # marks the window on the trace's own clock (bench/trace.py)
            self._window_span = jax.profiler.TraceAnnotation(
                "bench.measured_window")
            self._window_span.__enter__()
        t0 = time.time()
        self._in_window = True
        self.window = (t0, t0)
        return t0

    def end_window(self) -> float:
        t1 = time.time()
        self._in_window = False
        self.window = (self.window[0], t1)
        if self.trace:
            import jax
            self._window_span.__exit__(None, None, None)
            jax.profiler.stop_trace()
        self.memory_peak_bytes = _memory_peak_bytes()
        return t1


def _memory_peak_bytes() -> int | None:
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.local_devices()]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def pow2_buckets(upto: int, start: int = 1) -> list[int]:
    """Powers of two from ``start`` to the first at or above ``upto``: the
    padded sizes the program compiles one variant for."""
    out = [start]
    while out[-1] < upto:
        out.append(out[-1] * 2)
    return out


def load_module(path: Path):
    """Import a file by path (names may hold dots, as metric names do)."""
    spec = importlib.util.spec_from_file_location(
        f"bench_{path.parent.name}_{path.stem.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def cell_parts(bench: dict, workload: str, root: Path = ROOT):
    """(cell, configuration file, traffic file) of a cell, by name."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    cell = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    config = json.loads((root / configs[cell["config"]]["file"]).read_text())
    traffic = json.loads(
        (root / "bench" / "traffic" / f"{cell['traffic']}.json").read_text())
    return cell, config, traffic


def cell_metrics(bench: dict, workload: str, traced: bool) -> list[dict]:
    """The metrics a run of the cell reports: its end-to-end metrics with
    ``--trace 0``, its per-layer metrics with ``--trace 1``.  A metric
    without ``workloads`` covers every cell (a per-layer one: every cell
    that reports the end-to-end metric it moves)."""
    e2e = [m for m in bench["end_to_end"]
           if workload in m.get("workloads", [workload])]
    if not traced:
        return e2e
    reported = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if workload in m.get("workloads", [workload])
            and ("workloads" in m or m["moves"] in reported)]


def reader_path(name: str, root: Path = ROOT) -> Path:
    """The reader of metric ``name``: its own file, else that of the name
    before the first dot."""
    own = root / "bench" / "metrics" / f"{name}.py"
    return own if own.is_file() else own.with_name(
        f"{name.split('.', 1)[0]}.py")


def read_metrics(metrics: list[dict], run: Run,
                 root: Path = ROOT) -> dict:
    out = {}
    for m in metrics:
        value = load_module(reader_path(m["name"], root)).read(run)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def device_info(chips: int, *, require_tpu: bool = True) -> dict:
    """The devices as JAX reports them; raises NoAccelerator when there is
    no TPU or fewer chips than the cell asks for."""
    import jax
    try:
        devices = jax.devices()
    except RuntimeError as e:                      # no backend at all
        raise NoAccelerator(str(e)) from e
    dev = devices[0]
    if require_tpu and (dev.platform != "tpu" or len(devices) < chips):
        raise NoAccelerator(
            f"the cell needs {chips} TPU chip(s); JAX reports "
            f"{len(devices)} {dev.platform} device(s)")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices)}


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             t_process: float, root: Path = ROOT, require_tpu: bool = True,
             bench: dict | None = None, config: dict | None = None,
             traffic: dict | None = None, control: bool = False,
             log=print) -> dict:
    """Run the cell once and return its result line as a dict.

    ``bench``/``config``/``traffic`` default to the files the cell names;
    tests pass small ones and ``require_tpu=False`` to drive a whole run on
    the CPU."""
    bench = bench if bench is not None else load_benchmark(root)
    cell, cfg_file, traffic_file = cell_parts(bench, workload, root)
    config = config if config is not None else cfg_file
    traffic = traffic if traffic is not None else traffic_file
    device = device_info(cell["chips"], require_tpu=require_tpu)
    log(f"device platform={device['platform']} device_kind={device['kind']} "
        f"count={device['count']}")
    driver = load_module(root / "bench" / "deployments"
                         / f"{config['family']}.py")
    ctx = Context(seed=seed, seconds=seconds, trace=trace,
                  t_process=t_process, device_kind=device["kind"],
                  control=control, log=log)
    try:
        out = driver.run(ctx, config, traffic)
    finally:
        ctx.close()
    setup_s = ctx.window[0] - t_process
    log(f"lowerings_in_window {len(ctx.lowered_in_window)} "
        f"{sorted(set(ctx.lowered_in_window))} (any is a warm-up defect: a "
        "shape compiled inside the window)")
    reduced = None
    if trace:
        from bench import trace as trace_mod
        reduced = trace_mod.reduce_dir(TRACE_DIR, ctx.window)
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    run = Run(setup_s=setup_s, window=ctx.window, spans=ctx.spans,
              results=out.results, begin=out.begin, end=out.end,
              facts=out.facts, trace=reduced, device_kind=device["kind"])
    metrics = read_metrics(cell_metrics(bench, workload, trace), run, root)
    device["memory_peak_bytes"] = ctx.memory_peak_bytes
    line = {"correct": all(c.ok for c in out.checks),
            "attempted": out.attempted, "failed": out.failed,
            "metrics": metrics, "device": device}
    if reduced is not None:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        line["breakdown"] = {"device_ops": reduced["device_ops"],
                             "idle_gaps": reduced["idle_gaps"]}
    line["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                      for c in out.checks}
    return line


def main(argv=None, *, t_process: float, control: bool = False) -> int:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        line = run_cell(args.workload, args.seed, args.seconds,
                        bool(args.trace), t_process=t_process,
                        control=control, log=lambda msg: print(msg, flush=True))
    except NoAccelerator as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    for name, c in line["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r} "
              f"{'ok' if c['value'] <= c['limit'] else 'FAILED'}",
              file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0
