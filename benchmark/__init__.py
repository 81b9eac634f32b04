"""The benchmark: harness, yardstick and data (see BENCHMARK.json, PERF.md).

Everything a cell is measured with lives here, so that a PR which claims
a gain cannot change it: traffic generation, the table of peaks, the
counts of required operations, the trace reduction, each
configuration's plain reference and the comparison that decides
``correct``. From the program it takes only the system under test.
"""
