"""The repo's benchmark: harness, yardstick and plain references.

Everything the driver measures lives here and under ``tests/benchmark/``
(``BENCHMARK.json``'s ``paths``). From the program it takes the system
under test and its spans, counters and kernel names; nothing else.
"""
