"""The reduction of the program's spans (``bench/program_spans.py``): on
plain data, on a trace recorded here on the CPU with two threads of nested
``repro.*`` spans, and on the trace recorded on a TPU v5e
(``testdata/v5e_probe.xplane.pb``, bench spans only), where it must read
what ``bench/trace.py`` reads."""
import threading
import time
from pathlib import Path

import pytest

from bench import program_spans as ps
from bench import trace
from bench.harness import Run, load_module

TESTDATA = Path(__file__).resolve().parents[1] / "testdata"
METRICS = Path(__file__).resolve().parents[1] / "metrics"
MS = 1_000_000


def _data():
    """A device busy 0-10 and 40-50 ms of a 0-60 ms window; on thread A a
    bench span with a program child, on thread B a program span with two
    children."""
    host = [
        ("bench.measured_window", "A", 0, 60 * MS, {}),
        ("bench.window_solve", "A", 10 * MS, 40 * MS, {}),
        ("repro.analysis.fill", "A", 12 * MS, 36 * MS,
         {"slab_bytes": 100, "valid_bytes": 75}),
        ("repro.engine.run", "B", 45 * MS, 59 * MS,
         {"stream": "f/g0/r1", "seq": 3, "records": 8}),
        ("repro.operators.insert", "B", 46 * MS, 49 * MS, {"records": 8}),
        ("repro.operators.insert", "B", 52 * MS, 58 * MS, {"records": 4}),
        ("repro.broker.encode", "C", 70 * MS, 71 * MS, {"records": 2}),
    ]
    devices = {"/device:TPU:0": {
        "ops": [("%fusion.1 = f32[8]{0} fusion()", 0, 10 * MS),
                ("%fusion.2 = f32[8]{0} fusion()", 40 * MS, 50 * MS)],
        "modules": []}}
    spans = [(ps._label(n), s, e) for n, _t, s, e, _a in host]
    return {"devices": devices, "spans": spans, "host": host}


def test_gaps_go_to_the_innermost_span():
    r = ps.reduce(_data(), window_s=1.0)
    gaps = dict(r["idle_gaps"])
    # 10-40 ms: window_solve is open, and inside it analysis.fill ran
    # 12-36 ms; 50-60 ms: engine.run is open, and the insert inside it ran
    # 52-58 ms
    assert gaps == {"repro.analysis.fill": pytest.approx(0.030),
                    "repro.operators.insert": pytest.approx(0.010)}
    # the rule of bench/trace.py alone gives each gap to the outer span
    plain = dict(trace.reduce(_data(), window_s=1.0)["idle_gaps"])
    assert plain == {"window_solve": pytest.approx(0.030),
                     "repro.engine.run": pytest.approx(0.010)}
    assert r["busy_s"] == pytest.approx(0.020)
    assert r["window_s"] == pytest.approx(0.060)


def test_program_span_totals():
    tot = ps.reduce(_data(), window_s=1.0)["program_spans"]
    assert set(tot) == {"analysis.fill", "engine.run", "operators.insert"}
    run = tot["engine.run"]
    assert run["calls"] == 1
    assert run["seconds"] == pytest.approx(0.014)
    assert run["self_seconds"] == pytest.approx(0.014 - 0.003 - 0.006)
    assert run["args"] == {"seq": 3, "records": 8}       # strings left out
    ins = tot["operators.insert"]
    assert (ins["calls"], ins["args"]) == (2, {"records": 12})
    assert ins["self_seconds"] == pytest.approx(ins["seconds"])
    assert tot["analysis.fill"]["args"] == {"slab_bytes": 100,
                                            "valid_bytes": 75}


def test_units_of_work_count_whole_at_the_window_edge():
    """A solve that started before the window is left out with the copy
    it made inside it; one that started inside counts with all of its
    children, even those that end after the window."""
    host = [("bench.measured_window", "A", 10 * MS, 20 * MS, {}),
            ("repro.analysis.solve", "B", 5 * MS, 12 * MS, {"panes": 1}),
            ("repro.analysis.transfer", "B", 11 * MS, 12 * MS, {"bytes": 7}),
            ("repro.analysis.solve", "B", 18 * MS, 25 * MS, {"panes": 2}),
            ("repro.analysis.transfer", "B", 21 * MS, 22 * MS, {"bytes": 9})]
    data = {"devices": {}, "host": host,
            "spans": [(ps._label(n), s, e) for n, _t, s, e, _a in host]}
    tot = ps.reduce(data, window_s=1.0)["program_spans"]
    assert tot["analysis.solve"]["args"] == {"panes": 2}
    assert tot["analysis.transfer"]["args"] == {"bytes": 9}
    assert tot["analysis.solve"]["self_seconds"] == pytest.approx(0.006)


def test_no_span_and_no_window():
    data = _data()
    data["host"] = [sp for sp in data["host"]
                    if sp[0] != "bench.measured_window"]
    data["spans"] = [sp for sp in data["spans"] if sp[0] != "measured_window"]
    data["devices"]["/device:TPU:0"]["ops"] += [
        ("%fusion.3 = f32[8]{0} fusion()", 75 * MS, 76 * MS),
        ("%fusion.4 = f32[8]{0} fusion()", 80 * MS, 81 * MS)]
    r = ps.reduce(data, window_s=2.0)
    assert dict(r["idle_gaps"]) == {
        "repro.analysis.fill": pytest.approx(0.030),
        "repro.operators.insert": pytest.approx(0.025),     # 50-75 ms
        ps.NO_SPAN: pytest.approx(0.004)}                   # 76-80 ms
    assert r["program_spans"]["broker.encode"]["args"] == {"records": 2}


def _recorded(tmp_path):
    """Two threads of nested ``repro.*`` spans inside a measured window,
    recorded by the JAX profiler on the CPU."""
    import jax

    from repro.runtime.telemetry import span

    def worker(seq):
        with span("engine.run", stream=f"s{seq}", seq=seq, records=4):
            with span("operators.insert", records=4):
                time.sleep(0.01)
            with span("operators.fire") as sp:
                time.sleep(0.005)
                sp.set_metadata(panes=seq)

    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("bench.measured_window"):
            threads = [threading.Thread(target=worker, args=(i,))
                       for i in (1, 2)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=30)
        with span("engine.run", records=99):     # after the window
            pass
    finally:
        jax.profiler.stop_trace()
    assert not any(t.is_alive() for t in threads)
    (path,) = tmp_path.glob("**/*.xplane.pb")
    return path


def test_recorded_cpu_trace(tmp_path):
    data = ps.load(_recorded(tmp_path))
    runs = [sp for sp in data["host"] if sp[0] == "repro.engine.run"]
    assert len(runs) == 3
    assert len({sp[1] for sp in runs[:3]}) >= 2       # threads apart
    r = ps.reduce(data, window_s=1.0)
    tot = r["program_spans"]
    assert tot["engine.run"]["calls"] == 2               # in the window
    assert tot["engine.run"]["args"] == {"seq": 3, "records": 8}
    assert tot["operators.insert"]["args"] == {"records": 8}
    assert tot["operators.fire"]["args"] == {"panes": 3}
    assert tot["operators.insert"]["seconds"] >= 0.02
    run = tot["engine.run"]
    assert 0 <= run["self_seconds"] < run["seconds"] - 0.02
    assert run["seconds"] == pytest.approx(
        run["self_seconds"] + tot["operators.insert"]["seconds"]
        + tot["operators.fire"]["seconds"])


def _reader(name):
    return load_module(METRICS / f"{name}.py")


def test_v5e_trace_reads_as_bench_trace():
    """Bench spans alone: every reading of ``bench/trace.py`` is unchanged,
    bit for bit, and so is each metric read from it."""
    path = TESTDATA / "v5e_probe.xplane.pb"
    old = trace.reduce(trace.load(path), window_s=1.0)
    data = ps.load(path)
    assert {sp[0] for sp in data["host"]} == {"bench.codec", "bench.stage",
                                             "bench.window"}
    new = ps.reduce(data, window_s=1.0)
    assert new["program_spans"] == {}
    for key in ("busy_s", "window_s", "n_devices", "programs",
                "kernel_calls", "device_ops"):
        assert new[key] == old[key]
    assert new["idle_gaps"] == [
        [ps.NO_SPAN if k == trace.NO_SPAN else k, v]
        for k, v in old["idle_gaps"]]

    def run(reduced):
        return Run(setup_s=0, window=(0, 1), spans=[], results=[], begin={},
                   end={}, facts={}, trace=reduced,
                   device_kind="TPU v5 lite")
    for name in ("idle_share", "quant_roofline"):
        assert _reader(name).read(run(new)) == _reader(name).read(run(old))
