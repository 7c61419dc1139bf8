"""95th percentile of the gap between consecutive tokens of a request, as
the client sees them, over every gap that closed inside the window. A
prefill run between two decode steps lies inside the gap, so the tail sits
where gaps with a prefill begin and moves with their share."""
from bench.record import p95


def read(run):
    t0, t1 = run.window
    gaps = []
    for r in run.requests:
        ts = r.token_times
        gaps.extend((b - a) * 1e3 for a, b in zip(ts, ts[1:]) if t0 < b <= t1)
    return p95(gaps)
