"""A count the run kept (`Run.counters`), optionally as a share of another
and times a scale: `{"name": "counter", "key": "padded_rows", "over":
"launch_rows", "scale": 100}`."""


def read(run, key, over=None, scale=1.0):
    if key not in run.counters:
        return None
    value = float(run.counters[key])
    if over is not None:
        base = float(run.counters.get(over, 0.0))
        if base <= 0.0:
            return None
        value /= base
    return value * float(scale)
