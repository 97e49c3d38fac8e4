"""The trace reduction, on plain data and on a small trace recorded on a
TPU v5e (``testdata/v5e_probe.xplane.pb``: three int8 codec round trips of
36 rows inside a ``bench.codec`` span, one ``gram_pair`` and one
``_gram_operator`` at d=2304 inside ``bench.stage``, one batched window
solve inside ``bench.window``)."""
from pathlib import Path

import pytest

from bench import trace
from bench.harness import Run

TESTDATA = Path(__file__).resolve().parents[1] / "testdata"
MS = 1_000_000


def _data(with_window: bool):
    spans = [("write", 12 * MS, 19 * MS), ("eigensolve", 26 * MS, 101 * MS),
             ("write", 30 * MS, 31 * MS)]
    if with_window:
        spans.append(("measured_window", 2 * MS, 110 * MS))
    return {"devices": {"/device:TPU:0": {
        "ops": [("%fusion.1 = f32[8]{0} fusion()", 0, 10 * MS),
                ("%fusion.2 = f32[8]{0} fusion()", 5 * MS, 12 * MS),
                ('%_quantize.1 = (s8[288,256]{1,0}, f32[288,1]) custom-call('
                 'f32[288,256]{1,0} %x.1), custom_call_target="tpu_custom_call"',
                 20 * MS, 25 * MS),
                ("%cond.4 = f32[2304,2304] conditional()", 40 * MS, 100 * MS)],
        "modules": [("jit__quantize(7)", 20 * MS, 25 * MS),
                    ("jit__gram_operator(3)", 40 * MS, 100 * MS)]}},
        "spans": spans}


def test_busy_union_programs_and_gaps():
    r = trace.reduce(_data(False), window_s=0.2)
    assert r["window_s"] == 0.2
    assert r["busy_s"] == pytest.approx((12 + 5 + 60) * 1e-3)
    assert r["programs"]["_gram_operator"] == {"seconds": pytest.approx(0.06),
                                               "calls": 1}
    assert r["programs"]["_quantize"]["calls"] == 1
    assert r["device_ops"][0] == ["cond", pytest.approx(0.06)]
    assert [k for k, _s, _t in r["kernel_calls"]] == ["_quantize"]
    gaps = dict(r["idle_gaps"])
    assert gaps["write"] == pytest.approx(0.008)          # 12..20 ms
    assert gaps["eigensolve"] == pytest.approx(0.015)     # 25..40 ms
    assert r["n_devices"] == 1


def test_measured_window_clips_busy_and_counts_its_edges():
    r = trace.reduce(_data(True), window_s=123.0)
    assert r["window_s"] == pytest.approx(0.108)
    assert r["busy_s"] == pytest.approx((10 + 5 + 60) * 1e-3)   # from 2 ms
    gaps = dict(r["idle_gaps"])
    assert gaps["eigensolve"] == pytest.approx(0.015 + 0.010)    # + 100..110


def test_names_and_shapes():
    assert trace.union([(5, 7), (0, 3), (2, 4), (7, 9)]) == [(0, 4), (5, 9)]
    assert trace.program_name("jit__gram_operator(1234)") == "_gram_operator"
    assert trace.op_name("%copy-start.13 = (f32[36]) copy-start()") == "copy-start"
    assert trace.op_name("%_dequantize.1 = f32[36,256]") == "_dequantize"
    assert trace.arrays(
        "%c = (s8[288,256]{1,0:T(8,128)(4,1)}, f32[288,1]{1,0:T(8,128)S(1)}) "
        "custom-call(f32[288,256]{1,0} %p), custom_call_target=\"tpu_custom_call\", "
        "operand_layout_constraints={f32[288,256]{1,0}}") == [
        ("s8", (288, 256), 0), ("f32", (288, 1), 1), ("f32", (288, 256), 0)]
    assert trace.arrays("%pad.3 = f32[512,256]{1,0} pad(f32[288,256]{1,0} %x, "
                        "f32[] %c), padding=0_224x0_0") == [
        ("f32", (512, 256), 0), ("f32", (288, 256), 0), ("f32", (), 0)]


def test_quant_roofline_counts_given_rows_in_hbm_over_kernel_time():
    from bench.harness import load_module
    reader = load_module(Path(__file__).resolve().parents[1]
                         / "metrics" / "quant_roofline.py")
    calls = [
        ("pad", 1e-7, "%pad.1 = f32[512,256]{1,0} pad(f32[288,256]{1,0} %x, "
                      "f32[] %c), padding=0_224x0_0"),
        ("_quantize", 2e-6, "%_quantize.1 = (s8[512,256]{1,0}, f32[512,1]{1,0:"
                            "T(8,128)S(1)}) custom-call(f32[512,256]{1,0} %pad.1)"
                            ", custom_call_target=\"tpu_custom_call\""),
        ("_dequantize", 1e-6, "%_dequantize.1 = f32[36,256]{1,0} custom-call("
                              "s8[36,256]{1,0:S(1)} %a, f32[36,1]{1,0:S(1)} %b)"
                              ", custom_call_target=\"tpu_custom_call\""),
    ]
    run = Run(setup_s=0, window=(0, 1), spans=[], results=[], begin={}, end={},
              facts={}, trace={"kernel_calls": calls}, device_kind="TPU v5 lite")
    # 288 rows given of the 512 quantized: codes and input in HBM, scales not
    hbm = 288 * 256 * (1 + 4) + 36 * 256 * 4
    assert reader.read(run) == pytest.approx(100 * hbm / 819e9 / 3e-6)


def test_recorded_v5e_trace():
    data = trace.load(TESTDATA / "v5e_probe.xplane.pb")
    assert list(data["devices"]) == ["/device:TPU:0"]
    assert {n for n, _s, _e in data["spans"]} == {"codec", "stage", "window"}
    r = trace.reduce(data, window_s=1.0)
    progs = r["programs"]
    assert progs["_quantize"]["calls"] == 3 and progs["_dequantize"]["calls"] == 3
    assert progs["_gram_pair_raw"]["calls"] == 1
    assert progs["_gram_operator"]["calls"] == 1
    # the d=2304 eigensolve dominates the device's busy time
    assert progs["_gram_operator"]["seconds"] > 0.5 * r["busy_s"]
    assert 0 < r["busy_s"] < 1.0
    kernels = [k for k, _s, _t in r["kernel_calls"]]
    assert kernels.count("_quantize") == 3 and kernels.count("_dequantize") == 3
    # the codec's roofline share from the recorded calls, 36 rows each: the
    # quantize reads its input from HBM and writes its codes there, its
    # scales to VMEM; the dequantize reads codes and scales that XLA copied
    # into VMEM and writes its output to HBM
    calls = [(k, s, trace.arrays(t)) for k, s, t in r["kernel_calls"]
             if k in ("_quantize", "_dequantize")]
    assert all(a == [("s8", (36, 256), 0), ("f32", (36, 1), 1),
                     ("f32", (36, 256), 0)] for k, _s, a in calls
               if k == "_quantize")
    assert all(a == [("f32", (36, 256), 0), ("s8", (36, 256), 1),
                     ("f32", (36, 1), 1)] for k, _s, a in calls
               if k == "_dequantize")
    kernel_s = sum(s for _k, s, _a in calls)
    # the kernels alone, inside their jitted programs
    assert 0 < kernel_s < (progs["_quantize"]["seconds"]
                           + progs["_dequantize"]["seconds"])
    from bench.harness import load_module
    reader = load_module(Path(__file__).resolve().parents[1]
                         / "metrics" / "quant_roofline.py")
    run = Run(setup_s=0, window=(0, 1), spans=[], results=[], begin={}, end={},
              facts={}, trace=r, device_kind="TPU v5 lite")
    hbm = 3 * (36 * 256 * (1 + 4)) + 3 * (36 * 256 * 4)
    assert reader.read(run) == pytest.approx(100 * hbm / 819e9 / kernel_s)
    assert 0 < reader.read(run) < 100
