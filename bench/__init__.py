"""On-chip benchmark of the analyzer and the serving lane it watches.

``python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json`` once.  Everything that belongs to one
configuration, traffic mix, driver or per-layer metric is a file of its own
under ``bench/configs``, ``bench/traffic``, ``bench/drivers`` and
``bench/metrics``, found by the name ``BENCHMARK.json`` gives it.
"""
