"""perfbench — the benchmark of the served path (see PERF.md, BENCHMARK.json).

Everything the yardstick needs lives here: traffic generation, the reduction
from chunk logs, spans, counters and device traces to metrics, the table of
peaks, the plain reference of each configuration and the comparison that
decides ``correct``. From the program it takes only the system under test
(``finchat_tpu``), its spans, counters and kernel names.
"""
