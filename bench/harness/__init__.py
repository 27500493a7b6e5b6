"""The on-chip benchmark's yardstick: traffic generation, the served-path
load loop, the reduction from spans and profiler traces to metrics, operation
and byte counts, and the comparison that decides ``correct``.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric lives in a data file or reader of its own under ``bench/``
and is found by the name ``BENCHMARK.json`` gives it (see :mod:`.spec`).
"""
