"""Smoke run of M6-base at full width on TPU, through the normal entry points.

  python chip_smoke.py              # one chip: serve (unquantized and int8
                                    # KV), then train
  python chip_smoke.py --chips 4    # four chips: mesh serving and a sharded
                                    # train step, each against one chip

Everything runs in this one process (a chip belongs to one process at a
time): ``repro.launch.serve.main`` and ``repro.launch.train.main`` are
called in-process with the arguments a user would pass.  Weights are
random, made from a seed.  The model is the paper's M6-base (5 layers,
d_model 1024, 16 heads, 32 experts of d_ff 4096) at its published widths.

Lines before the last are smoke observations — compile seconds, tokens
served, losses, device memory — not benchmark metrics.  The last line is
one JSON object naming the device.  Any failed phase raises, so the
script exits non-zero and prints no result; so does a run that finds no
TPU, or a copy of this script without the repository next to it.
"""
from __future__ import annotations

import argparse
import gc
import json
import math
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(ROOT, "src")

SERVE_ARGS = ["--arch", "m6-base", "--engine", "continuous",
              "--moe-impl", "dropless", "--capacity-factor", "none",
              "--qps", "1000", "--max-slots", "8", "--max-len", "256",
              "--seed", "0"]
TRAIN_ARGS = ["--arch", "m6-base", "--optimizer", "adafactor",
              "--max-restarts", "0", "--batch", "8", "--seq", "128",
              "--log-every", "1", "--seed", "0"]
# Relative gap allowed between the loss of one train step on a 2x2 mesh
# and on one chip: bf16 compute reduces in a different order once the
# batch and the experts are split.
MESH_LOSS_RTOL = 1e-2


def fail(msg: str) -> None:
    print(f"chip_smoke: {msg}", file=sys.stderr)
    sys.exit(1)


class CompileClock:
    """Sums JAX's compile-duration events (trace, lowering, backend)."""

    def __init__(self):
        from jax import monitoring

        self.seconds = 0.0
        monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, name, secs, **kw):
        if name.startswith("/jax/core/compile/"):
            self.seconds += secs

    def lap(self) -> float:
        s, self.seconds = self.seconds, 0.0
        return s


def observe(label: str, clock: CompileClock, t0: float, **kv) -> None:
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    fields = {"phase": label, "wall_s": round(time.time() - t0, 1),
              "compile_s": round(clock.lap(), 1),
              "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
              "bytes_in_use": stats.get("bytes_in_use"), **kv}
    print("smoke observation:", json.dumps(fields), flush=True)


def released(label: str) -> None:
    """Collect what a finished phase left behind and print what the device
    still holds (the next phase needs the room)."""
    import jax

    gc.collect()
    stats = jax.devices()[0].memory_stats() or {}
    print("smoke observation:", json.dumps(
        {"phase": f"after {label}",
         "bytes_in_use": stats.get("bytes_in_use")}), flush=True)


def check_served(res) -> int:
    """Every request finished with all of its tokens; returns the count."""
    missing = [r.uid for r in res.requests if r.uid not in res.outputs]
    if missing:
        raise AssertionError(f"requests never finished: {missing}")
    short = {r.uid: (len(res.outputs[r.uid]), r.max_new_tokens)
             for r in res.requests
             if len(res.outputs[r.uid]) != r.max_new_tokens}
    if short:
        raise AssertionError(f"requests cut short (got, wanted): {short}")
    return sum(len(res.outputs[r.uid]) for r in res.requests)


def check_kernels(engine) -> int:
    """The compiled serving step holds the Pallas kernels (paged attention
    and the ragged expert FFN), not their jnp references."""
    text = engine.lower_step().compile().as_text()
    n = text.count('custom_call_target="tpu_custom_call"')
    if n < 2:
        raise AssertionError(
            f"serving step holds {n} tpu_custom_call ops; expected the paged "
            "attention and ragged FFN kernels")
    return n


def check_losses(logs, steps: int):
    losses = [m["loss"] for m in logs]
    if len(losses) != steps or not all(math.isfinite(x) for x in losses):
        raise AssertionError(f"train losses {losses} (wanted {steps} finite)")
    return losses


def serve_phase(serve_main, clock, label, extra):
    t0 = time.time()
    res = serve_main(SERVE_ARGS + extra)
    tokens = check_served(res)
    kernels = check_kernels(res.engine)
    observe(label, clock, t0, requests=len(res.requests),
            tokens_served=tokens, tpu_custom_calls=kernels,
            kv_pool_dtype=str(res.engine.cache.k_pool.dtype))
    return res


def one_chip(clock) -> None:
    from repro.launch.serve import main as serve_main
    from repro.launch.train import main as train_main

    res = serve_phase(serve_main, clock, "serve unquantized KV",
                      ["--requests", "16"])
    del res
    released("serve unquantized KV")
    res = serve_phase(serve_main, clock, "serve int8 KV",
                      ["--requests", "8", "--kv-quant", "int8"])
    del res
    released("serve int8 KV")

    t0 = time.time()
    steps = 4
    losses = check_losses(train_main(TRAIN_ARGS + ["--steps", str(steps)]),
                          steps)
    observe("train adafactor", clock, t0, steps=steps, losses=losses)


def first_divergence(a, b):
    for i, (x, y) in enumerate(zip(a, b)):
        if x != y:
            return i
    return min(len(a), len(b)) if len(a) != len(b) else None


def four_chips(clock) -> None:
    import jax

    from repro.launch.mesh import make_debug_mesh
    from repro.launch.serve import main as serve_main
    from repro.launch.train import main as train_main

    if len(jax.devices()) < 4:
        fail(f"--chips 4 needs four devices, JAX sees {len(jax.devices())}")

    # serving: (data=2, expert=2) mesh vs the single-chip engine
    ref = serve_phase(serve_main, clock, "serve one chip", ["--requests", "16"])
    ref_out = ref.outputs
    del ref
    released("serve one chip")
    res = serve_phase(serve_main, clock, "serve mesh data=2,expert=2",
                      ["--requests", "16", "--mesh", "data=2,expert=2"])
    mesh_ids = {d.id for d in res.engine.mesh.devices.flat}
    if len(mesh_ids) != 4:
        raise AssertionError(f"serving mesh spans devices {sorted(mesh_ids)}")
    diverged = {uid: first_divergence(ref_out[uid], res.outputs[uid])
                for uid in ref_out if ref_out[uid] != res.outputs[uid]}
    print("smoke observation:", json.dumps(
        {"phase": "mesh serving parity", "requests": len(ref_out),
         "mesh_devices": sorted(mesh_ids),
         "diverged_at_token": diverged}), flush=True)
    if diverged:
        raise AssertionError(
            f"mesh serving tokens differ from one chip: {diverged}")
    del res
    released("serve mesh")

    # training: one step on a (data=2, model=2) mesh vs one chip
    mesh = make_debug_mesh(2, 2)
    mesh_ids = {d.id for d in mesh.devices.flat}
    if len(mesh_ids) != 4:
        raise AssertionError(f"train mesh spans devices {sorted(mesh_ids)}")
    t0 = time.time()
    (one,) = check_losses(train_main(TRAIN_ARGS + ["--steps", "1"]), 1)
    (sharded,) = check_losses(train_main(
        TRAIN_ARGS + ["--steps", "1", "--data", "2", "--model", "2"]), 1)
    rel = abs(sharded - one) / max(abs(one), 1e-12)
    observe("train step one chip vs data=2,model=2", clock, t0,
            loss_one_chip=one, loss_mesh=sharded, rel_diff=rel,
            mesh_devices=sorted(mesh_ids))
    if rel > MESH_LOSS_RTOL:
        raise AssertionError(
            f"sharded loss {sharded} vs one chip {one}: rel diff {rel:.3g}")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=[1, 4],
                    help="1: serve and train on one chip; 4: the mesh "
                         "serving and sharded training checks only")
    args = ap.parse_args(argv)

    if not os.path.isdir(os.path.join(SRC, "repro")):
        fail(f"no repro package under {SRC}: run this from a checkout")
    sys.path.insert(0, SRC)

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        fail(f"no TPU found: JAX's first device is {dev.platform!r}")

    from repro.launch.compile_cache import enable_compile_cache

    print("smoke observation:", json.dumps(
        {"device_kind": dev.device_kind, "device_count": len(jax.devices()),
         "jax": jax.__version__, "compile_cache": enable_compile_cache()}),
        flush=True)
    clock = CompileClock()
    if args.chips == 4:
        four_chips(clock)
    else:
        one_chip(clock)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)


if __name__ == "__main__":
    main()
