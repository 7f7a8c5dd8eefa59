"""Device time in operations whose name matches a pattern, as a percentage
of the device's busy time or of the traced window, on the first chip:
`{"name": "trace_share", "pattern": "ragged_paged_attention", "of":
"busy"}`; `"pattern": "@collective"` stands for the collective operations
(`xplane.COLLECTIVE`), taken as a union so that nested ones count once."""

from benchmark import xplane


def read(run, pattern, of="busy"):
    red = run.reduction
    if not red or not red["per_chip"]:
        return None
    chip = red["per_chip"][min(red["per_chip"])]
    base = chip["busy_s"] if of == "busy" else red["window_s"]
    if base <= 0.0:
        return None
    if pattern == "@collective":
        part = chip["collective_s"]
    else:
        part = xplane.time_matching(red, pattern)
    return 100.0 * part / base
