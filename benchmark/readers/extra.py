"""A number the traffic kind measured itself and left in `Run.extras`:
`{"name": "extra", "key": "step_ms"}`."""


def read(run, key):
    value = run.extras.get(key)
    return None if value is None else float(value)
