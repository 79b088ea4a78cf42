"""Process start to window open: imports, weights from the seed, compile
or compile-cache load, warm-up (host clock)."""


def read(run):
    return run.get("setup_s")
