"""Device benchmark of the search fleet: open-loop traffic through the gateway.

``python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json`` once. Everything the yardstick needs
(data generator, traffic generator, reference, trace reduction, work counts,
peaks) lives in this directory and imports nothing of ``src/repro``; only
``run.py`` drives the system under test.
"""
