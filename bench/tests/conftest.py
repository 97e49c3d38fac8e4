import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

import copy  # noqa: E402
import time  # noqa: E402

import pytest  # noqa: E402


def tiny_parts(workload: str):
    """(benchmark, configuration, traffic) of a cell, the configuration cut
    to a size a CPU test run holds (every width and ratio kept small,
    every limit as committed)."""
    from bench import harness
    bench = harness.load_benchmark()
    _cell, cfg, traffic = harness.cell_parts(bench, workload)
    cfg = copy.deepcopy(cfg)
    cfg.update(producers=8, record_floats=256, pool_steps=32)
    cfg["workflow"].update(n_groups=1, executors_per_group=8)
    cfg["analysis"].update(pane_steps=8, lateness_steps=16)
    return bench, cfg, dict(traffic, warmup_s=0.3)


@pytest.fixture
def run_tiny():
    """Drive a whole run of a cell on the CPU at a tiny size, skipping the
    harness's look for a chip; returns the result line."""
    from bench import harness

    def go(workload: str, seed: int = 2 ** 33 + 7, control: bool = False):
        bench, cfg, traffic = tiny_parts(workload)
        return harness.run_cell(workload, seed, 1.0, False,
                                t_process=time.time(), require_tpu=False,
                                bench=bench, config=cfg, traffic=traffic,
                                control=control, log=lambda _msg: None)
    return go
