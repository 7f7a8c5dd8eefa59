"""The on-chip benchmark: one command runs one cell of BENCHMARK.json once.

Everything that decides a number lives here, where a PR that claims a gain
cannot change it: traffic generation, the reduction from spans, counters and
the profiler's trace to metrics, the table of peaks, the functions that count
a kernel's operations, the plain reference of each configuration and the
comparison that decides `correct`. From the program (`flexflow_tpu`) it takes
only the system under test and its spans, counters and kernel names.
"""
