"""chipbench — the benchmark of record (BENCHMARK.json names its cells).

The yardstick lives here so that later PRs can change the program and not
the measurement. It imports the system under test (``mxnet_tpu``) and reads
its spans, counters and kernel names; nothing else of the repository.
See README.md for how a cell, configuration, traffic mix, per-layer metric
or runner is added as a file.
"""
