"""The CFD → per-region streaming DMD cell (``cfd16.w5.q1``): a whole run on
the CPU at a small size, the faults that must turn it incorrect, the gate
that keeps one output step in flight, the cell's metric readers and the
``gram_pair`` kernel's counts."""
import copy
import re
import threading
import time
from pathlib import Path

import numpy as np
import pytest

from bench import counts, gram_counts, harness, trace
from bench.harness import Emitted, Run

CELL = "cfd16.w5.q1"
TESTDATA = Path(__file__).resolve().parents[1] / "testdata"


def _cfd():
    return harness.load_module(harness.BENCH / "deployments" / "cfd.py")


def _tiny(control: bool = False, seed: int = 2 ** 33 + 11) -> dict:
    """One run of the cell at 4 regions of a 32x16 grid (d = 256) and an
    8-snapshot window, the traffic and every limit as committed."""
    bench = harness.load_benchmark()
    _cell, cfg, traffic = harness.cell_parts(bench, CELL)
    cfg = copy.deepcopy(cfg)
    cfg["grid"].update(nx=32, nz=16, regions=4)
    cfg["record_floats"] = 256
    cfg["workflow"].update(executors_per_group=4, trigger_interval=0.02)
    cfg["analysis"].update(window=8)
    traffic = dict(traffic, warmup_s=0.3)
    logged = []
    line = harness.run_cell(CELL, seed, 1.5, False, t_process=time.time(),
                            require_tpu=False, bench=bench, config=cfg,
                            traffic=traffic, control=control,
                            log=logged.append)
    return dict(line, log=logged)


@pytest.fixture(scope="module")
def sound():
    return _tiny()


def test_sound_run_is_correct(sound):
    assert sound["correct"], sound["checks"]
    assert sound["attempted"] > 0 and sound["failed"] == 0
    assert sound["metrics"]["snapshots_per_s"]["value"] > 0
    assert set(sound["checks"]) == {"dropped", "unanalysed", "raised",
                                    "out_of_order", "corrupted",
                                    "gap_ratio_median", "rank_gap"}
    assert any(m.startswith("lowerings_in_window 0 ") for m in sound["log"])


def test_gate_keeps_one_output_step_in_flight(sound):
    """No region's micro-batch ever ran with a later step already written,
    so each micro-batch held one snapshot."""
    (line,) = [m for m in sound["log"] if m.startswith("window ")]
    assert "at most 0 steps written past the one analysed" in line
    updates, rows = re.search(r"(\d+) updates of (\d+) snapshots",
                              line).groups()
    assert int(updates) == int(rows) > 0


def test_horizon_opens_when_the_slowest_region_catches_up():
    horizon = _cfd().Horizon(np.array([4, 5, 6]), 1)
    assert horizon.open(5) and not horizon.open(6)
    opened = []

    def writer():
        opened.append(horizon.wait(6, deadline=time.time() + 5.0))

    t = threading.Thread(target=writer)
    t.start()
    time.sleep(0.05)
    assert not opened                       # region 0 is at step 4
    horizon.analysed[0] = 5
    t.join(timeout=5.0)
    assert opened == [True] and horizon.waited_s > 0
    assert not horizon.wait(7, deadline=time.time() + 0.05)


def test_control_in_the_systems_place_is_incorrect():
    line = _tiny(control=True)
    assert not line["correct"]
    assert line["checks"]["gap_ratio_median"]["value"] == 1.0


def _record_corrupted(monkeypatch):
    """One record corrupted where the endpoint decodes it: region 1's
    snapshot of output step 2 gets one float set to 100 times its largest
    magnitude."""
    from repro.core import records
    decode = records.decode_batch

    def altered(data):
        out = decode(data)
        for rec in out:
            if rec.step == 2 and rec.rank == 1:
                rec.payload[0] = 100.0 * np.abs(rec.payload).max()
        return out
    monkeypatch.setattr(records, "decode_batch", altered)


def _batch_dropped(monkeypatch):
    """The ordered stage skipped for the fifth micro-batch the engine runs:
    its record never reaches its region's StreamingDMD, and at one step in
    flight the gate then never opens again."""
    from repro.streaming.operators import ExecutionPlan
    run_post = ExecutionPlan.run_post
    calls = []

    def dropping(self, key, pre_out, records):
        calls.append(key)
        if len(calls) == 5:
            return len(records)
        return run_post(self, key, pre_out, records)
    monkeypatch.setattr(ExecutionPlan, "run_post", dropping)


def _answer_altered(monkeypatch):
    """Every eigenvalue a region's solve returns moved by 0.1%."""
    from repro.analysis.dmd import StreamingDMD
    eigenvalues = StreamingDMD.eigenvalues
    monkeypatch.setattr(StreamingDMD, "eigenvalues",
                        lambda self: np.asarray(eigenvalues(self)) * 1.001)


@pytest.mark.parametrize("fault", [_record_corrupted, _batch_dropped,
                                   _answer_altered],
                         ids=lambda f: f.__name__[1:])
def test_fault_makes_the_run_incorrect(monkeypatch, fault):
    fault(monkeypatch)
    line = _tiny()
    assert not line["correct"], line["checks"]


def _run(**kw) -> Run:
    base = dict(setup_s=1.0, window=(10.0, 20.0), spans=[], results=[],
                begin={}, end={}, facts={}, trace=None,
                device_kind="TPU v5 lite")
    return Run(**dict(base, **kw))


def _read(name, run):
    return harness.load_module(harness.reader_path(name)).read(run)


def test_readers_on_a_synthetic_run():
    run = _run(begin={"updates": 10, "rows": 10},
               end={"updates": 42, "rows": 42})
    assert _read("rows_per_update", run) == 1.0
    assert _read("rows_per_update", _run(begin={"updates": 3, "rows": 9},
                                         end={"updates": 3, "rows": 9})) is None
    spans = [("stream_solve", 11.0, 11.2), ("stream_solve", 12.0, 12.4),
             ("stream_solve", 5.0, 9.0), ("stream_update", 11.0, 11.1)]
    assert _read("stream_solve_ms", _run(spans=spans)) == pytest.approx(300.0)
    assert _read("stream_solve_ms", _run()) is None
    programs = {"_gram_operator": {"seconds": 0.5, "calls": 4}}
    traced = {"programs": programs, "kernel_calls": []}
    assert _read("gram_operator_ms", _run(trace=traced)) == pytest.approx(125.0)
    assert _read("gram_operator_ms", _run()) is None
    assert _read("gram_operator_ms",
                 _run(trace={"programs": {}, "kernel_calls": []})) is None
    # freshness 500, 510, ... 590 ms; the 95th percentile by nearest rank
    # is the largest of ten
    results = [Emitted("stream", 10.0 + i, 9.5 - 0.01 * i + i, 1)
               for i in range(10)]
    assert _read("latency_p95_ms.cfd16", _run(results=results)) == \
        pytest.approx(590.0)
    busy = {"busy_s": 9.0, "window_s": 10.0, "n_devices": 1,
            "programs": {}, "kernel_calls": []}
    assert _read("idle_share.cfd16", _run(trace=busy)) == pytest.approx(10.0)


def _pair_call(n: int, d: int, space: int = 0) -> str:
    dd = f"f32[{d},{d}]{{1,0:T(8,128)}}"
    nd = f"f32[{n},{d}]{{1,0:T(8,128){'S(1)' if space else ''}}}"
    return (f"%_gram_pair_raw.1 = ({dd}, {dd}) custom-call({nd} %x.1, {nd} "
            f"%x.1, {nd} %y.1, {dd} %g.1, {dd} %a.1), "
            'custom_call_target="tpu_custom_call"')


def test_gram_pair_counts_by_hand():
    n, d = 1, 2304
    ops, nbytes = gram_counts.gram_pair(n, d)
    # 2·n·d² multiply-adds in each of XᵀX and YᵀX
    assert ops == 4 * 2304 * 2304 == 21_233_664
    # X and Y read once; G and A read once and written once
    assert nbytes == (2 * 2304 + 4 * 2304 * 2304) * 4 == 84_953_088
    assert gram_counts.call_counts(trace.arrays(_pair_call(n, d))) == \
        (ops, nbytes)
    # X and Y in VMEM: only G and A are HBM's
    assert gram_counts.call_counts(trace.arrays(_pair_call(n, d, 1))) == \
        (ops, 4 * 2304 * 2304 * 4)
    assert gram_counts.call_counts([("f32", (8, 8), 0)]) is None


def test_gram_pair_roofline_reader():
    n, d = 1, 2304
    _ops, nbytes = gram_counts.gram_pair(n, d)
    least = nbytes / 819e9                  # bytes bound it at this shape
    calls = [(gram_counts.KERNEL, 2 * least, _pair_call(n, d)),
             ("_quantize", 1.0, "%_quantize.1 = f32[8] custom-call()")]
    run = _run(trace={"kernel_calls": calls, "programs": {}})
    assert _read("gram_pair_roofline", run) == pytest.approx(50.0)
    assert _read("gram_pair_roofline", _run(trace={"kernel_calls": [],
                                                   "programs": {}})) is None
    assert _read("gram_pair_roofline", _run()) is None


def test_readers_on_the_recorded_v5e_trace():
    """The probe trace holds one gram_pair call of 8 rows and one
    ``_gram_operator`` at d = 2304 (bench/tests/test_trace.py)."""
    data = trace.load(TESTDATA / "v5e_probe.xplane.pb")
    run = _run(trace=trace.reduce(data, 1.0))
    share = _read("gram_pair_roofline", run)
    assert 0.0 < share < 100.0
    assert _read("gram_operator_ms", run) > 0.0
    assert counts.peaks(run.device_kind)
