"""Faults planted in the program's timed path, for checking that the
correctness check catches them.  Not part of a benchmark run.

    python3 bench/faults.py --workload <cell> --seeds 21,22,23 --seconds 3

Each fault is a context manager that patches the program for the runs
inside it.  Serving: a token altered where it is produced; a step that
returns the KV cache unchanged (its writes dropped).  Training: a step
that returns its state unchanged; half of the batch left out, the mean
taken over the rest; the loss altered where it is produced.  Every cell
runs on one chip, so no exchange between chips can be left out.  The
script prints, per seed and fault, the numbers the check compares: a
training number's upper reading is the least of what its faults read
(``bench/check_train.py``).
"""
from __future__ import annotations

import argparse
import contextlib
import gc
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


@contextlib.contextmanager
def _patched(module, name, value):
    old = getattr(module, name)
    setattr(module, name, value)
    try:
        yield
    finally:
        setattr(module, name, old)


def token_altered(vocab_size: int):
    import repro.serving.continuous as cont

    real = cont._sample_rows

    def altered(logits, *a, **k):
        return (real(logits, *a, **k) + 1) % vocab_size

    return _patched(cont, "_sample_rows", altered)


def kv_unchanged():
    import repro.serving.continuous as cont

    real = cont.paged_update_attention

    def unchanged(q, k, v, k_pool, v_pool, *a, **kw):
        out, _, _ = real(q, k, v, k_pool, v_pool, *a, **kw)
        return out, k_pool, v_pool

    return _patched(cont, "paged_update_attention", unchanged)


def state_unchanged():
    import repro.train.trainer as trainer

    real = trainer.make_train_step

    def make(cfg, tc, opt):
        step = real(cfg, tc, opt)

        def same(state, batch):
            new, metrics = step(state, batch)
            return new._replace(params=state.params), metrics
        return same

    return _patched(trainer, "make_train_step", make)


def half_batch():
    import repro.train.trainer as trainer

    real = trainer.make_loss_fn

    def make(cfg):
        loss_fn = real(cfg)

        def half(params, batch, ctx=None):
            n = batch["tokens"].shape[0] // 2
            return loss_fn(params, {k: v[:n] for k, v in batch.items()}, ctx)
        return half

    return _patched(trainer, "make_loss_fn", make)


def loss_altered():
    import repro.train.trainer as trainer

    real = trainer.total_loss

    def altered(logits, labels, aux):
        loss, metrics = real(logits, labels, aux)
        return loss * 1.001, dict(metrics, loss=loss * 1.001)

    return _patched(trainer, "total_loss", altered)


def faults(mc: dict, driver: str) -> dict:
    """{name: context manager factory} of the faults a cell can have."""
    if driver == "serve":
        return {"token_altered": lambda: token_altered(mc["vocab_size"]),
                "kv_unchanged": kv_unchanged}
    return {"state_unchanged": state_unchanged, "half_batch": half_batch,
            "loss_altered": loss_altered}


def main(argv=None):
    from bench import run as bench_run

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--faults", default="")
    args = ap.parse_args(argv)

    manifest, cell, mc, traffic, limits = bench_run.load_cell(args.workload)
    bench_run.check_device(cell["chips"])
    bench_run.enable_compile_cache()
    from bench import program

    program.import_program()
    gen, driver = bench_run.modules(traffic)
    chosen = faults(mc, gen.DRIVER)
    if args.faults:
        chosen = {k: chosen[k] for k in args.faults.split(",")}
    counter = bench_run.CompileCounter()
    for seed in [int(s) for s in args.seeds.split(",")]:
        for name, fault in chosen.items():
            with fault():
                out = driver.run(args.workload, seed, args.seconds, mc, traffic,
                                 limits, gen, None, counter,
                                 setup_from=bench_run.process_start_perf())
            print(json.dumps({"workload": args.workload, "seed": seed,
                              "fault": name, "correct": out["correct"],
                              "checks": out["checks"]}), flush=True)
            del out
            gc.collect()


if __name__ == "__main__":
    main()
