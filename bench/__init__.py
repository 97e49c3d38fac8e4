"""The chip benchmark: cells, traffic, references and metric readers.

``python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json`` once; see ``bench/run.py``.
"""
