"""The on-chip benchmark of the completion server (``BENCHMARK.json``).

``bench/run.py`` runs one cell; ``bench/harness.py`` finds a cell's
configuration, traffic, driver and per-layer readers by name;
``drivers/`` drive the traffic with what ``loadgen.py`` shares,
``trace.py`` reduces the profiler's trace, and ``references/`` holds the
plain references that decide ``correct`` (``control.py`` their
control).
"""
