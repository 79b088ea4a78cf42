"""Generated tokens that reached the host inside the window, over the
window's seconds (host clock)."""


def read(run):
    s = run.get("serve")
    if not s:
        return None
    lo, hi = s["t_open"], s["t_close"]
    n = sum(1 for times in s["token_times"].values() for t in times if lo < t <= hi)
    return n / (hi - lo)
