"""A percentile over the judged requests' rows (`Run.requests`):
`{"name": "request_quantile", "field": "queue_wait_ms", "q": 90}`."""

from benchmark.stats import percentile


def read(run, field, q):
    values = [r[field] for r in run.requests if field in r]
    if not values:
        return None
    return percentile(values, float(q))
