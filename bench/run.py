#!/usr/bin/env python3
"""Run one benchmark cell once on the chip and print its result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics from a traced window.  The last line of standard output
is one JSON object (``correct``, ``attempted``, ``failed``, ``metrics``,
``device``, with ``--trace 1`` also ``breakdown``, and last ``checks``:
every number compared with the plain reference, beside its limit).  Exits
2, printing no result, when JAX finds no TPU or fewer chips than the cell
asks for.  JAX's compile cache is kept in ``.jax_cache/`` of this checkout.
"""
import time

T_PROCESS = time.time()                 # set-up is counted from here

import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    from bench.harness import main
    sys.exit(main(t_process=T_PROCESS))
