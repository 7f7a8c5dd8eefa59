"""A count the program left as an attribute of its spans inside the
window: `{"name": "span_counter", "span": "launch_dispatch", "key":
"experts_hit", "over": "experts_held", "scale": 100}` sums `key` over the
spans named `span` (an attribute that is a list, one entry a layer, is
summed too), optionally as a share of the sum of `over`, times `scale`;
`"how": "last"` takes the newest span's value instead of the sum (a
constant the program reports on every launch). None where no span of the
window carries `key`: a program without the counter, and the metric is
left out.
"""


def _total(value) -> float:
    return float(sum(value)) if isinstance(value, (list, tuple)) else float(
        value)


def read(run, span, key, over=None, scale=1.0, how="sum"):
    found = [ev[4] for ev in sorted(run.spans, key=lambda ev: ev[1])
             if ev[0] == span and ev[4] and key in ev[4]]
    if not found:
        return None
    if how == "last":
        return _total(found[-1][key]) * float(scale)
    value = sum(_total(a[key]) for a in found)
    if over is not None:
        base = sum(_total(a[over]) for a in found if over in a)
        if base <= 0.0:
            return None
        value /= base
    return value * float(scale)
