"""Configurations, traffic, drivers and metric readers are found by name:
a new one is a new file and a new BENCHMARK.json entry, never an edit."""
import json
import shutil
import time
from pathlib import Path

from bench import harness

ROOT = Path(__file__).resolve().parents[2]

ECHO_DRIVER = '''
from bench.harness import Check, Emitted, Outcome


def run(ctx, config, traffic):
    t0 = ctx.begin_window()
    t1 = ctx.end_window()
    res = [Emitted("echo", t0 + i * 1e-3, t0, config["n"]) for i in range(traffic["k"])]
    return Outcome(checks=[Check("echo", 0, 0)], attempted=traffic["k"],
                   failed=0, results=res, facts={"n": config["n"]})
'''


def test_new_cell_config_family_traffic_and_metric_need_no_edit(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    before = {p: p.read_bytes() for p in (tmp_path / "bench").rglob("*")
              if p.is_file()}
    # the additions: one configuration of a new family with its driver, one
    # traffic, one cell, one per-layer metric with its reader
    (tmp_path / "bench/configs/echo_one.json").write_text(
        json.dumps({"family": "echo", "n": 3}))
    (tmp_path / "bench/deployments/echo.py").write_text(ECHO_DRIVER)
    (tmp_path / "bench/traffic/echo.burst.json").write_text(json.dumps({"k": 5}))
    (tmp_path / "bench/metrics/echo_n.py").write_text(
        "def read(run):\n    return run.facts['n'] * len(run.results)\n")
    bench = json.loads((tmp_path / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "echo_one", "source": "x",
                             "file": "bench/configs/echo_one.json",
                             "reduced": [], "why": "x"})
    bench["workloads"].append({"name": "echo_one.burst", "config": "echo_one",
                               "traffic": "echo.burst", "chips": 1, "why": "x"})
    bench["per_layer"].append({"name": "echo_n", "unit": "1", "better": "higher",
                               "source": "program_counter", "layer": "x",
                               "moves": "setup_s", "workloads": ["echo_one.burst"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    line = harness.run_cell("echo_one.burst", 1, 0.0, True,
                            t_process=time.time(), root=tmp_path,
                            require_tpu=False, log=lambda _m: None)
    assert line["correct"] and line["attempted"] == 5
    assert line["metrics"]["echo_n"]["value"] == 15
    line0 = harness.run_cell("echo_one.burst", 1, 0.0, False,
                             t_process=time.time(), root=tmp_path,
                             require_tpu=False, log=lambda _m: None)
    assert set(line0["metrics"]) == {"setup_s"}
    # nothing that was there changed
    after = {p: p.read_bytes() for p in before}
    assert after == before


def test_dotted_metric_names_share_a_reader():
    metrics = ROOT / "bench/metrics"
    assert harness.reader_path("idle_share.sat") == metrics / "idle_share.py"
    assert harness.reader_path("idle_share") == metrics / "idle_share.py"
    assert harness.reader_path("latency_p95_ms.sat") == \
        metrics / "latency_p95_ms.py"


def test_each_cell_reports_its_metrics():
    bench = harness.load_benchmark()
    for cell in bench["workloads"]:
        e2e = {m["name"] for m in harness.cell_metrics(bench, cell["name"], False)}
        layer = harness.cell_metrics(bench, cell["name"], True)
        assert "setup_s" in e2e and len(e2e) >= 2
        assert layer and all(m["moves"] in e2e for m in layer)
        for m in layer + harness.cell_metrics(bench, cell["name"], False):
            assert harness.reader_path(m["name"]).is_file()
        _cell, cfg, _traffic = harness.cell_parts(bench, cell["name"])
        assert (ROOT / "bench/deployments" / f"{cfg['family']}.py").is_file()
