"""Readers: how a per-layer metric is taken from a run's counters, request
rows, spans or reduced device trace. One small module per way of reading,
found by the `reader.name` of a file under `benchmark/metrics/`; the file's
other `reader` keys are the arguments of `read(run, **args)`. A reader that
finds nothing to read returns None and the metric is left out of the line.
"""
