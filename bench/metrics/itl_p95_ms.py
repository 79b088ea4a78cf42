"""95th percentile of every gap between consecutive tokens of a request,
both received inside the window (host clock)."""
from bench.readings import percentile


def read(run):
    s = run.get("serve")
    if not s:
        return None
    lo, hi = s["t_open"], s["t_close"]
    gaps = []
    for times in s["token_times"].values():
        inside = [t for t in times if lo < t <= hi]
        gaps += [1e3 * (b - a) for a, b in zip(inside, inside[1:])]
    return percentile(gaps, 95)
