import os
# MUST run before anything imports jax: it locks device count on first
# init.  Append to (never clobber) caller-set XLA_FLAGS, and respect a
# device count the caller already forced; REPRO_DRYRUN_DEVICES overrides
# the 512 default for small-scale CI runs.
_existing = os.environ.get("XLA_FLAGS", "")
if "--xla_force_host_platform_device_count" not in _existing:
    _count = os.environ.get("REPRO_DRYRUN_DEVICES", "512")
    os.environ["XLA_FLAGS"] = " ".join(
        f for f in (_existing, f"--xla_force_host_platform_device_count={_count}")
        if f)

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell on
the production mesh with 512 virtual host devices, proving the sharding
config is coherent (no real hardware, no real allocation: inputs are
ShapeDtypeStructs).  Records memory_analysis / cost_analysis / collective
traffic for the roofline (EXPERIMENTS.md S Dry-run / S Roofline).

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch all --mesh both
  PYTHONPATH=src python -m repro.launch.dryrun --arch olmoe-1b-7b \
      --shape train_4k --mesh single --out experiments/dryrun.json
"""
import argparse
import json
import time
import traceback
from typing import Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig, ShapeConfig, TrainConfig
from repro.configs.registry import ARCH_IDS, ALL_IDS, get_config
from repro.configs.shapes import SHAPES, shapes_for
from repro.distributed.costs import bytes_for, flops_for
from repro.distributed.hlo import collective_bytes, op_histogram
from repro.distributed.roofline import (
    Roofline, model_flops_forward, model_flops_train)
from repro.distributed.sharding import (
    Rules, activation_shardings, make_rules, param_shardings, use_rules)
from repro.launch.mesh import make_production_mesh
from repro.models.registry import get_family
from repro.nn import abstract as abstract_params
from repro.nn import count_params
from repro.nn.spec import ParamSpec, map_specs
from repro.optim import make_optimizer, warmup_constant
from repro.optim.zero import zero1_shardings
from repro.train.state import TrainState
from repro.train.trainer import make_train_step

# The chip the production mesh is made of; its peaks set the roofline.
TARGET_DEVICE_KIND = "TPU v5 lite"


def active_param_count(cfg: ModelConfig, specs) -> float:
    """Parameters touched per token: non-expert + experts * k/E."""
    total = count_params(specs)
    if cfg.moe.num_experts == 0:
        return float(total)
    expert = 0
    flat, _ = jax.tree_util.tree_flatten(
        specs, is_leaf=lambda x: isinstance(x, ParamSpec))
    for leaf in flat:
        if not isinstance(leaf, ParamSpec):
            continue
        axes = leaf.logical_axes
        if axes and axes[0] == "layers":  # stacked scan params
            axes = axes[1:]
        if axes and axes[0] == "expert":  # expert weights (router excluded:
            n = 1                         # its axes start with "embed")
            for d in leaf.shape:
                n *= d
            expert += n
    frac = cfg.moe.active_k / cfg.moe.num_experts
    return float(total - expert + expert * frac)


def _batch_shardings(batch_specs: Dict, shape: ShapeConfig, cfg, rules: Rules):
    return activation_shardings(batch_specs, cfg, shape.global_batch,
                                shape.seq_len, rules)


def _auto_microbatches(cfg: ModelConfig, shape: ShapeConfig, mesh,
                       rules: Optional[Rules] = None) -> int:
    """Grad-accumulation so per-layer saved activations (scan+remat keeps
    one carry per layer) fit the HBM budget: tokens/dev/mb * d * 2B * L
    <= ~2.5GB, mb a power of two dividing the per-device batch."""
    if rules is not None:
        dp = rules.axis_size(rules.acts.get("batch"))
    else:
        dp = mesh.shape.get("data", 1) * mesh.shape.get("pod", 1)
    tokens_per_dev = shape.tokens / dp
    budget = 2.5e9
    # recurrent families hold chunk-scan residuals beyond the d_model
    # carry; weight their activation footprint accordingly
    family_factor = {"xlstm": 16.0, "zamba": 2.0}.get(cfg.family, 1.0)
    need = (tokens_per_dev * cfg.d_model * 2.0 * max(cfg.num_layers, 1)
            * family_factor / budget)
    mb = 1
    while mb < need and mb < 32 and (shape.global_batch // dp) % (mb * 2) == 0:
        mb *= 2
    return mb


def _train_cell(cfg: ModelConfig, shape: ShapeConfig, mesh, rules: Rules,
                expert_axis: Optional[str] = None):
    fam = get_family(cfg)
    specs = fam.specs(cfg)
    params_abs = abstract_params(specs)
    n_total = count_params(specs)
    tp = mesh.shape.get("model", 1)
    if rules.params.get("mlp") is None and rules.params.get("expert") is None \
            and rules.params.get("heads") is None:
        tp = 1  # pure-DP sharding: params fully replicated without FSDP
    # FSDP when replicated (~2 bytes/param grads + params) per device is big
    wb = 2.0 if cfg.param_dtype == "bfloat16" else 4.0
    if n_total * 2 * wb / tp > 6e9 and not cfg.fsdp:
        cfg = cfg.replace(fsdp=True)
        rules = make_rules(cfg, mesh, expert_axis=expert_axis)  # param rules change
    import os as _os

    tc = TrainConfig(optimizer="adafactor" if n_total > 3e11 else "adamw",
                     microbatches=_auto_microbatches(cfg, shape, mesh, rules),
                     grad_compression=_os.environ.get("REPRO_GRAD_COMPRESSION", "none"))
    opt = make_optimizer(tc, warmup_constant(tc.learning_rate, tc.warmup_steps))

    state_abs = jax.eval_shape(
        lambda p: TrainState(p, opt.init(p), jnp.zeros((), jnp.int32), None),
        params_abs)
    p_shard = param_shardings(specs, rules)
    opt_shard = zero1_shardings(state_abs.opt_state,
                                jax.tree_util.tree_map(lambda s: s.spec, p_shard,
                                                       is_leaf=lambda x: isinstance(x, NamedSharding)),
                                rules)
    state_shard = TrainState(p_shard, opt_shard, NamedSharding(mesh, P()), None)

    batch_abs = fam.input_specs(cfg, shape)
    b_shard = _batch_shardings(batch_abs, shape, cfg, rules)

    step = make_train_step(cfg, tc, opt)

    def wrapped(state, batch):
        with use_rules(rules):
            return step(state, batch)

    jitted = jax.jit(wrapped, in_shardings=(state_shard, b_shard),
                     donate_argnums=(0,))
    lowered = jitted.lower(state_abs, batch_abs)
    n_active = active_param_count(cfg, specs)
    mf = model_flops_train(n_active, shape.tokens)
    return lowered, mf


def _prefill_cell(cfg: ModelConfig, shape: ShapeConfig, mesh, rules: Rules):
    fam = get_family(cfg)
    specs = fam.specs(cfg)
    params_abs = abstract_params(specs)
    p_shard = param_shardings(specs, rules)
    batch_abs = fam.input_specs(cfg, shape)
    b_shard = _batch_shardings(batch_abs, shape, cfg, rules)

    if fam.prefill is not None:
        def wrapped(params, batch):
            with use_rules(rules):
                return fam.prefill(params, batch, cfg, max_len=shape.seq_len)
    else:
        def wrapped(params, batch):
            with use_rules(rules):
                return fam.forward(params, batch, cfg)

    jitted = jax.jit(wrapped, in_shardings=(p_shard, b_shard))
    lowered = jitted.lower(params_abs, batch_abs)
    n_active = active_param_count(cfg, specs)
    mf = model_flops_forward(n_active, shape.tokens)
    return lowered, mf


def _decode_cell(cfg: ModelConfig, shape: ShapeConfig, mesh, rules: Rules):
    fam = get_family(cfg)
    specs = fam.specs(cfg)
    params_abs = abstract_params(specs)
    p_shard = param_shardings(specs, rules)
    dspec = fam.decode_input_specs(cfg, shape)
    tok_abs, state_abs = dspec["tokens"], dspec["state"]
    t_shard = activation_shardings(tok_abs, cfg, shape.global_batch, shape.seq_len, rules)
    s_shard = activation_shardings(state_abs, cfg, shape.global_batch, shape.seq_len, rules)

    def wrapped(params, tokens, state):
        with use_rules(rules):
            return fam.decode(params, tokens, state, cfg)

    jitted = jax.jit(wrapped, in_shardings=(p_shard, t_shard, s_shard),
                     donate_argnums=(2,))
    lowered = jitted.lower(params_abs, tok_abs, state_abs)
    n_active = active_param_count(cfg, specs)
    mf = model_flops_forward(n_active, shape.global_batch)  # 1 token / seq
    return lowered, mf


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             moe_impl: Optional[str] = None, save_hlo: Optional[str] = None,
             remat: Optional[bool] = None, expert_axis: Optional[str] = None,
             group_size: Optional[int] = None) -> Dict:
    cfg = get_config(arch)
    if os.environ.get("REPRO_PARAM_DTYPE"):
        cfg = cfg.replace(param_dtype=os.environ["REPRO_PARAM_DTYPE"])
    if moe_impl and cfg.moe.num_experts:
        cfg = cfg.replace_moe(impl=moe_impl)
    if group_size and cfg.moe.num_experts:
        cfg = cfg.replace_moe(group_size=group_size)
    if remat is not None:
        cfg = cfg.replace(remat=remat)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    rules = make_rules(cfg, mesh, expert_axis=expert_axis)
    t0 = time.time()
    if shape.kind == "train":
        lowered, mf = _train_cell(cfg, shape, mesh, rules, expert_axis=expert_axis)
    elif shape.kind == "prefill":
        lowered, mf = _prefill_cell(cfg, shape, mesh, rules)
    else:
        lowered, mf = _decode_cell(cfg, shape, mesh, rules)
    t_lower = time.time() - t0

    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    cost = compiled.cost_analysis()
    mem = compiled.memory_analysis()
    hlo = compiled.as_text()
    coll = collective_bytes(hlo)
    chips = mesh.size
    # Roofline terms use the analytic models (tests/test_costs.py validates
    # them against unrolled probes) because XLA's cost analysis counts scan
    # bodies once; collectives come from the trip-count-aware HLO parse.
    specs = get_family(cfg).specs(cfg)
    n_params = count_params(specs)
    a_flops = flops_for(cfg, shape)
    a_bytes = bytes_for(cfg, shape, n_params)
    rl = Roofline(
        flops=a_flops,
        bytes_accessed=a_bytes,
        collective_bytes=float(coll["total"]),
        chips=chips,
        device_kind=TARGET_DEVICE_KIND,
        model_flops=mf,
    )
    result = {
        "arch": arch,
        "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "chips": chips,
        "status": "ok",
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        "memory": {
            "argument_bytes_per_device": mem.argument_size_in_bytes,
            "output_bytes_per_device": mem.output_size_in_bytes,
            "temp_bytes_per_device": mem.temp_size_in_bytes,
            "alias_bytes_per_device": mem.alias_size_in_bytes,
            "peak_bytes_per_device": (mem.argument_size_in_bytes
                                      + mem.temp_size_in_bytes),
            "fits_16gb": (mem.argument_size_in_bytes + mem.temp_size_in_bytes)
                         < rl.peaks.hbm_bytes,
        },
        "collectives": coll,
        "roofline": rl.to_dict(),
        "raw_cost_analysis": {   # undercounts scan bodies — recorded for
            "flops": float(cost.get("flops", 0.0)),          # transparency
            "bytes_accessed": float(cost.get("bytes accessed", 0.0)),
        },
        "n_params": n_params,
        "op_histogram": op_histogram(hlo),
    }
    if save_hlo:
        with open(save_hlo, "w") as f:
            f.write(hlo)
    return result


def cells(arch_filter: str, shape_filter: str, mesh_filter: str):
    archs = ARCH_IDS if arch_filter == "all" else [arch_filter]
    for arch in archs:
        cfg = get_config(arch)
        shapes = shapes_for(cfg)
        for shape in shapes:
            if shape_filter != "all" and shape.name != shape_filter:
                continue
            if mesh_filter in ("single", "both"):
                yield arch, shape.name, False
            if mesh_filter in ("multi", "both"):
                yield arch, shape.name, True


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all", choices=["all"] + ALL_IDS)
    ap.add_argument("--shape", default="all", choices=["all"] + list(SHAPES))
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    from repro.core.dispatch import available_dispatchers
    ap.add_argument("--moe-impl", default=None,
                    choices=[None, *available_dispatchers()])
    ap.add_argument("--expert-axis", default=None)
    ap.add_argument("--group-size", type=int, default=None)
    ap.add_argument("--no-remat", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun.json")
    ap.add_argument("--save-hlo", default=None)
    ap.add_argument("--tag", default=None, help="suffix results key (perf experiments)")
    args = ap.parse_args()

    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    results = {}
    if os.path.exists(args.out):
        with open(args.out) as f:
            results = json.load(f)

    for arch, shape_name, multi in cells(args.arch, args.shape, args.mesh):
        key = f"{arch}|{shape_name}|{'multi' if multi else 'single'}"
        if args.tag:
            key += f"|{args.tag}"
        print(f"=== {key} ===", flush=True)
        try:
            res = run_cell(arch, shape_name, multi, moe_impl=args.moe_impl,
                           save_hlo=args.save_hlo, expert_axis=args.expert_axis,
                           group_size=args.group_size,
                           remat=False if args.no_remat else None)
            rl = res["roofline"]
            print(f"  compile {res['compile_s']}s | mem/dev "
                  f"{res['memory']['peak_bytes_per_device']/1e9:.2f}GB | "
                  f"t_comp {rl['t_compute']*1e3:.2f}ms t_mem {rl['t_memory']*1e3:.2f}ms "
                  f"t_coll {rl['t_collective']*1e3:.2f}ms -> {rl['dominant']}", flush=True)
        except Exception as e:  # noqa: BLE001 — record the failure
            res = {"arch": arch, "shape": shape_name,
                   "mesh": "2x16x16" if multi else "16x16",
                   "status": "error", "error": f"{type(e).__name__}: {e}",
                   "traceback": traceback.format_exc()[-2000:]}
            print(f"  FAILED: {res['error']}", flush=True)
        results[key] = res
        with open(args.out, "w") as f:
            json.dump(results, f, indent=1)
    print(f"wrote {args.out}")


if __name__ == "__main__":
    main()
