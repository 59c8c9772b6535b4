"""Chip benchmark of the served PRISM denoise path.

``python bench/run.py --workload <config>.<traffic> --seed <n> --seconds <s>
--trace <0|1>`` times one cell of ``BENCHMARK.json`` from the camera's side
and prints one JSON result line. Configurations (``configs/<name>.json``),
traffic mixes (``traffic/<name>.json``) and per-layer metric readers
(``metrics/<name>.py``) are found by the names ``BENCHMARK.json`` gives, so
a new cell needs new files only.
"""
