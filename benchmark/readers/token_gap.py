"""A percentile of the gaps between one request's consecutive tokens, in ms,
over every token emitted inside the window (host clock).

`{"name": "token_gap", "q": 99}`. A `commit` span names in `rids` the
requests (`_GenRequest.seq`) that gained a token in it, and the token is the
caller's at the span's end, so a request's token times are the ends of the
commits that name it: no per-token stamp in the program. What `tpot_p90`
averages away shows here: a decode tick that waited behind a prefill chunk,
a preemption, a slow commit.
"""

from benchmark.harness import log
from benchmark.stats import median, percentile


def read(run, q):
    last = {}
    gaps = []
    commits = [(ev[1] + ev[2], ev[4]["rids"]) for ev in run.spans
               if ev[0] == "commit" and ev[4] and "rids" in ev[4]]
    for end, rids in sorted(commits, key=lambda c: c[0]):
        for seq in rids:
            if seq in last:
                gaps.append(end - last[seq])
            last[seq] = end
    if not gaps:
        return None
    log(f"token_gap: {len(gaps)} gaps of {len(last)} requests, ms p50 "
        f"{median(gaps) / 1e6:.3f} p90 {percentile(gaps, 90) / 1e6:.3f} "
        f"p99 {percentile(gaps, 99) / 1e6:.3f} max {max(gaps) / 1e6:.3f}")
    return percentile(gaps, float(q)) / 1e6
