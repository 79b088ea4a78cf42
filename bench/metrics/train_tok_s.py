"""Text tokens of every train step run in the window, over the window's
seconds; the window ends when the last step's result is on the host
(host clock)."""


def read(run):
    t = run.get("train")
    if not t:
        return None
    return t["steps"] * t["tokens_per_step"] / (t["t_close"] - t["t_open"])
