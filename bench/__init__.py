"""The on-chip benchmark of the 3LA co-simulation serving path.

``python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json``. Configurations, traffic mixes and
per-layer metrics are files found by name under ``bench/configs``,
``bench/traffic`` and ``bench/metrics``.
"""
