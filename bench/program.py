"""The seam between the benchmark and the system under test.

Everything the benchmark knows about the program's own API sits here:
how a configuration file becomes the program's ``ModelConfig``, how an
engine section becomes a ``ServeConfig``, and how the benchmark's weight
arrays are placed in the program's parameter tree.  What differs by
architectural form (the sizes a ``ModelConfig`` takes, where each weight
goes) comes from the configuration's module under ``bench/arch``.  The
reference (``bench/reference``) never imports this module.
"""
from __future__ import annotations

import os
import sys

from bench import arch

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def import_program():
    """Put the checkout's ``src`` on the path; fail where it is missing."""
    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        raise SystemExit(f"bench: no program under {src}")
    if src not in sys.path:
        sys.path.insert(0, src)


def model_config(mc: dict, mode: str):
    """The program's ``ModelConfig`` for configuration file ``mc`` run as
    ``mode`` ("serve" or "train").  The program's registry supplies the
    family and layer kinds; the configuration's form (``bench/arch``)
    puts in every size from the file and checks the kinds against what
    the file states; the program's parameters must have the shapes of
    the benchmark's weights."""
    import_program()
    from repro.configs.registry import get_config

    form = arch.load(mc)
    cfg = form.model_config(mc, mode, get_config(mc["registry_id"]))
    if program_shapes(cfg, form.LAYOUT) != form.shapes(mc):
        raise SystemExit("bench: the program's parameter shapes differ from "
                         "the benchmark's weights")
    return cfg


def serve_config(engine: dict):
    import_program()
    from repro.configs.base import ServeConfig

    return ServeConfig(max_slots=engine["max_slots"],
                       kv_block_size=engine["kv_block_size"],
                       prefill_chunk=engine["prefill_chunk"],
                       max_len=engine["max_len"],
                       num_blocks=engine.get("num_blocks"))


def program_shapes(cfg, layout: dict) -> dict:
    """{benchmark weight name: shape} of the program's parameter tree,
    checked leaf for leaf against ``layout`` (weight name -> path)."""
    import jax

    from repro.models.registry import get_family
    from repro.nn import abstract

    tree = abstract(get_family(cfg).specs(cfg))
    flat = {tuple(getattr(k, "key", k) for k in path): leaf.shape
            for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]}
    names = {v: k for k, v in layout.items()}
    unknown = sorted("/".join(p) for p in flat if p not in names)
    if unknown:
        raise SystemExit(f"bench: program parameters the benchmark does not "
                         f"make: {unknown}")
    return {names[p]: tuple(s) for p, s in flat.items()}


def program_params(mc: dict, arrays: dict) -> dict:
    """The program's parameter tree holding the benchmark's arrays."""
    layout = arch.load(mc).LAYOUT
    tree: dict = {}
    for name, arr in arrays.items():
        node = tree
        path = layout[name]
        for k in path[:-1]:
            node = node.setdefault(k, {})
        node[path[-1]] = arr
    return tree


def leaf_names(mc: dict, tree) -> list:
    """Benchmark weight names in the program tree's leaf order."""
    import jax

    names = {v: k for k, v in arch.load(mc).LAYOUT.items()}
    return [names[tuple(getattr(k, "key", k) for k in path)]
            for path, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]
