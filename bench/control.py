"""The control of a cell's correctness check, and the readings its
limits are set from.  Not part of a benchmark run.

    python3 bench/control.py --workload <cell> --seeds 11,12,13 --seconds 5

For each seed, in this one process: one run of the cell as the
benchmark makes it (a short window at the cell's own load), then the
numbers the check compares, for the program and for the control, the
reference put in the program's place at float8 e4m3, the precision
below the configuration's bfloat16.  Serving: the widest and the mean
logit gap of the served tokens, and of the control's first choices at
the same positions.  Training: the three numbers of
``bench/check_train.py`` for the control's three steps against the
float32 reference.  The control's numbers go through the cell's own
limits (``bench/limits/<cell>.json``) as the program's do.  One JSON
line per seed: ``correct`` is the program's run, ``control_correct``
the control's, which has to come out false; a limit lies between the
program's largest reading and the control's smallest.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import run as bench_run  # noqa: E402


def within(got: dict, limits: dict) -> bool:
    """The cells' rule: every number the limits name is within its limit."""
    return all(got[k] <= limits[k] for k in limits)


def serve_control(out: dict, mc: dict, traffic: dict, limits: dict) -> dict:
    from bench import check_serve

    got = check_serve.readings(mc, out["weights"], out["sample"],
                               traffic["engine"]["max_len"], with_control=True)
    control = {"logit_gap": got["control_gap"],
               "mean_logit_gap": got["control_mean_gap"]}
    return {"program": {"logit_gap": got["logit_gap"],
                        "mean_logit_gap": got["mean_logit_gap"]},
            "control": control, "served_tokens": got["served_tokens"],
            "control_correct": within(control, limits)}


def train_control(out: dict, mc: dict, traffic: dict, seed: int,
                  limits: dict) -> dict:
    from bench import check_train, seeds, weights

    gen, _ = bench_run.modules(traffic)
    batch = gen.batch_fn(traffic, mc)
    bkey = seeds.jax_key(seed, "traffic.batches")
    make_w = weights.maker(mc)
    wkey = seeds.jax_key(seed, "weights")
    batches = [batch(bkey, j) for j in range(3)]
    low = check_train.reference_readings(mc, make_w(wkey), batches, prec="fp8")
    low["change_norms"] = check_train.change_norms(low.pop("params"),
                                                   make_w(wkey))
    ref = out["reference_readings"]
    worst = {}
    control = check_train.compare(low, ref, worst)
    return {"program": check_train.compare(out["program_readings"], ref),
            "control": control, "control_correct": within(control, limits),
            "control_worst_leaves": worst, "control_losses": low["losses"],
            "reference_losses": ref["losses"]}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)

    manifest, cell, mc, traffic, limits = bench_run.load_cell(args.workload)
    dev, count = bench_run.check_device(cell["chips"])
    bench_run.enable_compile_cache()
    gen, driver = bench_run.modules(traffic)
    counter = bench_run.CompileCounter()
    for seed in [int(s) for s in args.seeds.split(",")]:
        out = driver.run(args.workload, seed, args.seconds, mc, traffic,
                         limits, gen, None, counter,
                         setup_from=bench_run.process_start_perf())
        if gen.DRIVER == "serve":
            row = serve_control(out, mc, traffic, limits)
        else:
            row = train_control(out, mc, traffic, seed, limits)
        row.update(workload=args.workload, seed=seed, correct=out["correct"],
                   checks=out["checks"],
                   device=bench_run.device_info(dev, count))
        print(json.dumps(row), flush=True)
        del out
        gc.collect()


if __name__ == "__main__":
    main()
