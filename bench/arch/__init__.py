"""What the benchmark knows of each architectural form, one module a form.

A configuration file names its plain reference under ``"reference"``,
``bench/reference/<form>.py``, and ``load`` returns the module of the
same form, ``bench/arch/<form>.py``.  The generic modules (``weights``,
``program``, ``readings``, ``check_train``, the cell drivers, the
generators and the metric readers) read everything form-specific
through it, so a configuration of a new form brings its module as a new
file and edits none.  A module gives:

* ``shapes(mc)``: {weight name: shape} of the benchmark's float32
  weights, and ``is_scale(name)``: whether a weight is a norm scale,
  made 1 plus noise;
* ``LAYOUT``: weight name -> path in the program's parameter tree;
* ``model_config(mc, mode, cfg)``: the program registry's ``cfg`` with
  the file's sizes and the settings of ``mode`` ("serve" or "train"),
  refusing a form the program does not run;
* the work counts of the metric readers: ``row_flops(mc, length)``,
  ``unembed_flops(mc)``, and ``kernel_work(kernel, mc, step)``, one
  ``(flops, bytes)`` per layer that calls the kernel in a traced step
  (None where the step's census holds nothing to count it by);
* ``KERNELS``: {kernel key: the kernel's name in the device trace} of
  the kernels whose rooflines the readers take;
* for a form with a training cell: ``train_step_flops(mc, batch,
  text_len)``; ``train_prefix(mc)``, (positions, width) of the features
  a training batch places before its text, (0, 0) for none;
  ``MATMUL_WEIGHTS`` and ``grad_parts(names)``, the weights the
  training reference's control rounds and the passes in which it takes
  the gradient.
"""
from __future__ import annotations

import importlib
import importlib.util
import re


def load(mc: dict):
    """The module of the form configuration ``mc`` states, or exit."""
    name = mc.get("reference")
    if name is None:
        raise SystemExit(f"bench: configuration {mc.get('name')!r} states no "
                         f"\"reference\": the form whose modules under "
                         f"bench/arch/ and bench/reference/ describe it")
    if not (isinstance(name, str) and re.fullmatch(r"[a-z_][a-z0-9_]*", name)
            and importlib.util.find_spec(f"{__name__}.{name}") is not None):
        raise SystemExit(f"bench: configuration {mc.get('name')!r} states "
                         f"\"reference\": {name!r}, and bench/arch/ has no "
                         f"such module")
    return importlib.import_module(f"{__name__}.{name}")
