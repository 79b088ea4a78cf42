"""Benchmark entry point.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Looks the cell up in ``BENCHMARK.json`` and loads, by name, the cell's
configuration (``bench/configs``), the module of the architectural form
whose plain reference the configuration names (``bench/arch``), its traffic
mix (``bench/traffic``), the traffic's generator
(``bench/generators/<kind>.py``), the cell's correctness limits
(``bench/limits/<cell>.json``) and one reader per metric
(``bench/metrics/<metric>.py``, or for ``<quantity>.<cell group>`` such
as ``device_idle.chat`` the quantity's own
``bench/metrics/<quantity>.py``).  A new cell, mix, configuration, form
or metric is new files and entries, never an edit here.

Runs only on a TPU whose kind is in ``bench/peaks.py``: with no
accelerator, too few chips or an unknown kind it exits non-zero and
prints no result.  The last line of standard output is one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
end-to-end metrics, or with ``--trace 1`` its per-layer ones),
``device``, with ``--trace 1`` ``breakdown``, and last ``checks``: each
number the correctness check compared, with its limit.
"""
from __future__ import annotations

import time

T0_WALL, T0_PERF = time.time(), time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "bench")
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import arch  # noqa: E402


def process_start_perf() -> float:
    """The process's start on the ``perf_counter`` clock (Linux: from
    /proc), so that set-up counts the interpreter's own start too."""
    try:
        with open("/proc/self/stat") as f:
            start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        age = uptime - start_ticks / os.sysconf("SC_CLK_TCK")
        return T0_PERF - max(age - (time.time() - T0_WALL), 0.0)
    except (OSError, ValueError, IndexError):
        return T0_PERF


def fail(msg: str, code: int = 2):
    print(f"bench: {msg}", file=sys.stderr)
    sys.exit(code)


def load_json(*parts):
    path = os.path.join(*parts)
    if not os.path.isfile(path):
        fail(f"missing {os.path.relpath(path, ROOT)}")
    with open(path) as f:
        return json.load(f)


def load_cell(name: str):
    manifest = load_json(ROOT, "BENCHMARK.json")
    cells = {c["name"]: c for c in manifest["workloads"]}
    if name not in cells:
        fail(f"no workload {name!r} in BENCHMARK.json; known: {sorted(cells)}")
    cell = cells[name]
    configs = {c["name"]: c for c in manifest["configs"]}
    mc = load_json(ROOT, configs[cell["config"]]["file"])
    arch.load(mc)
    traffic = load_json(BENCH, "traffic", cell["traffic"] + ".json")
    limits = load_json(BENCH, "limits", name + ".json")
    return manifest, cell, mc, traffic, limits


def metrics_of(manifest: dict, cell: str, trace: bool) -> list:
    """The cell's metric entries: those that list it under ``workloads``
    or list no workloads at all."""
    group = manifest["per_layer" if trace else "end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


def reader(metric: str):
    """The metric's ``read``: from its own file where it has one, else
    from the file of the quantity before its first dot."""
    path = os.path.join(BENCH, "metrics", metric + ".py")
    if not os.path.isfile(path):
        path = os.path.join(BENCH, "metrics", metric.split(".")[0] + ".py")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{metric}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def modules(traffic: dict):
    """The traffic's generator (``bench/generators/<kind>.py``) and the
    cell driver its ``DRIVER`` names (``bench/serve_cell.py`` or
    ``bench/train_cell.py``)."""
    gen = importlib.import_module(f"bench.generators.{traffic['generator']}")
    return gen, importlib.import_module(f"bench.{gen.DRIVER}_cell")


def check_device(chips: int):
    """The first device, or exit: no TPU, too few chips, unknown kind."""
    import jax

    from bench import peaks

    try:
        devs = jax.devices()
    except RuntimeError as e:
        fail(f"JAX finds no accelerator: {e}")
    if devs[0].platform != "tpu":
        fail(f"no TPU: JAX's first device is {devs[0].platform!r}")
    if len(devs) < chips:
        fail(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    peaks.peak(devs[0].device_kind)
    return devs[0], len(devs)


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache at a fixed path in the checkout
    (or where ``JAX_COMPILATION_CACHE_DIR`` says), every program in it."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileCounter:
    """Counts backend compilations (cache hits excluded) as they happen."""

    def __init__(self):
        from jax import monitoring

        self.count = 0
        monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, secs, **kw):
        if name == "/jax/core/compile/backend_compile_duration":
            self.count += 1


def device_info(dev, count: int) -> dict:
    stats = dev.memory_stats() or {}
    return {"platform": dev.platform, "kind": dev.device_kind, "count": count,
            "memory_peak_bytes": stats.get("peak_bytes_in_use")}


def trace_dir(cell: str) -> str:
    path = os.path.join(ROOT, ".bench_trace", cell)
    shutil.rmtree(path, ignore_errors=True)
    return path


def run_cell(cell_name: str, seed: int, seconds: float, trace: bool, *,
             manifest=None, cell=None, mc=None, traffic=None, limits=None,
             dev=None, count: int = 1, peak=None) -> dict:
    """One run of one cell; returns the result object.  The keyword
    arguments let a test drive the run on the CPU with its own small
    configuration and skip the look for a chip."""
    gen, driver = modules(traffic)
    counter = CompileCounter()
    out = driver.run(cell_name, seed, seconds, mc, traffic, limits, gen,
                     trace_dir(cell_name) if trace else None, counter,
                     setup_from=process_start_perf())
    run = dict(out["run"], mc=mc, traffic=traffic, peak=peak)
    metrics = {}
    for m in metrics_of(manifest, cell_name, trace):
        value = reader(m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = dict(device_info(dev, count) if dev is not None else {},
                  **out["device"])
    result = {"correct": out["correct"], "attempted": out["attempted"],
              "failed": out["failed"], "metrics": metrics, "device": device}
    if trace and run.get("trace"):
        result["breakdown"] = run["trace"]["breakdown"]
        shutil.rmtree(os.path.join(ROOT, ".bench_trace"), ignore_errors=True)
    result["checks"] = out["checks"]
    return result


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    args = ap.parse_args(argv)

    manifest, cell, mc, traffic, limits = load_cell(args.workload)
    dev, count = check_device(cell["chips"])
    enable_compile_cache()
    from bench import peaks

    result = run_cell(args.workload, args.seed, args.seconds, bool(args.trace),
                      manifest=manifest, cell=cell, mc=mc, traffic=traffic,
                      limits=limits, dev=dev, count=count,
                      peak=peaks.peak(dev.device_kind))
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
