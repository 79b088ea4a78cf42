"""Find the highest rate a Poisson serving cell sustains (its knee).
Not part of a benchmark run; a cell's rate is then written into its
traffic file as a number.

    python3 bench/sweep.py --workload <cell> --rates 0.8,1.2,1.6 --seconds 40

For each rate, in this one process and on the same weights: the cell's
traffic at that rate, the cell's warm-up, then a window of ``seconds``.
Printed per rate: requests due and served in the window, the backlog
(due but without a first token) when the window opens and when it
closes, and the time-to-first-token percentiles.  A rate is sustained
when the backlog does not grow over the window.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import run as bench_run  # noqa: E402


def backlog_at(rec: dict, t: float) -> int:
    return sum(1 for uid, r in rec["requests"].items()
               if r["arrival_s"] <= t
               and not (rec["token_times"][uid]
                        and rec["token_times"][uid][0] <= t))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args(argv)

    manifest, cell, mc, traffic, limits = bench_run.load_cell(args.workload)
    bench_run.check_device(cell["chips"])
    bench_run.enable_compile_cache()
    from bench import serve
    from bench.generators import poisson

    for rate in [float(r) for r in args.rates.split(",")]:
        t = dict(traffic, rate_per_s=rate,
                 requests=int(rate * (args.seconds + 120)) + traffic["block"])
        engine = serve.build(mc, t, args.seed)
        serve.warm_compile(engine)
        rec = serve.drive(engine, poisson.generate(t, args.seed, mc["vocab_size"]),
                          t, args.seconds)
        lo, hi = rec["t_open"], rec["t_close"]
        due = [u for u, r in rec["requests"].items() if lo <= r["arrival_s"] < hi]
        ttft = [1e3 * ((rec["token_times"][u][0] if rec["token_times"][u]
                        and rec["token_times"][u][0] <= hi else hi)
                       - rec["requests"][u]["arrival_s"]) for u in due]
        print(json.dumps({
            "rate_per_s": rate, "window_s": hi - lo, "due": len(due),
            "first_token_in_window": sum(
                1 for u, ts in rec["token_times"].items()
                if ts and lo < ts[0] <= hi),
            "backlog_open": backlog_at(rec, lo),
            "backlog_close": backlog_at(rec, hi),
            "ttft_p50_ms": float(np.percentile(ttft, 50)) if ttft else None,
            "ttft_p90_ms": float(np.percentile(ttft, 90)) if ttft else None,
        }), flush=True)
        del engine, rec
        gc.collect()


if __name__ == "__main__":
    main()
