"""The control of each correctness check goes through the cell's own
``correct`` and comes out false, at a size a test run holds.

Serving: the reference put in the program's place at float8 e4m3 (the
precision below the configurations' bfloat16) reads a mean logit gap
far above the program's own on every seed; under a limit between the
two, set from these CPU readings as the chip's readings at the cells'
own sizes set each cell's (``bench/control.py``), the program's run is
correct and the control is not.  Training: the cell's own limits file
passes the program's bfloat16 steps and fails the control's."""
import json
import os

import pytest

from bench import control, run, serve_cell
from bench.generators import backlog
from bench.tests.conftest import ROOT

# mean logit gap, CPU, over the served tokens of the check's sample.
# M6, seeds 1-8 and 2**34 + 2 at >= 2048 tokens: program <= 8.5e-4,
# control >= 5.2e-3.  A token whose top-1 expert flips reads a gap of up
# to 1.23; the reference with its operands rounded to bfloat16 flips at
# the same tokens by the same gaps, so the flips are the configuration's
# precision, and one of them lifts a mean over some 200 tokens as high
# as 6.2e-3, above the control's there (down to 5.4e-4): M6 is compared,
# as its cell is, over thousands of tokens.  OLMoE, seeds 1 and
# 2**34 + 2 at >= 200 tokens: program <= 1.7e-5, control >= 8.3e-4.
SERVE_LIMITS = {"m6": {"mean_logit_gap": 2.5e-3},
                "olmoe": {"mean_logit_gap": 1.2e-4}}
# (served tokens at least, requests at most, window seconds)
SERVE_SAMPLE = {"m6": (2048, 400, 6.0), "olmoe": (200, 30, 0.5)}


@pytest.mark.parametrize("family", ["m6", "olmoe"])
def test_control_fails_where_the_program_passes(family, tiny_m6, tiny_olmoe,
                                                tiny_backlog):
    mc = dict(tiny_m6, initializer_range=0.1) if family == "m6" else \
        dict(tiny_olmoe, initializer_range=0.02)
    mc["dtype"] = "bfloat16"
    tokens, requests, seconds = SERVE_SAMPLE[family]
    traffic = dict(tiny_backlog, check={"tokens_min": tokens,
                                        "requests_max": requests})
    limits = SERVE_LIMITS[family]
    program, ctrl = [], []
    for seed in (1, 2**34 + 2):
        out = serve_cell.run("tiny", seed, seconds, mc, traffic, limits,
                             backlog, None, run.CompileCounter(), 0.0)
        assert out["correct"], out["checks"]
        got = control.serve_control(out, mc, traffic, limits)
        assert got["served_tokens"] >= tokens
        assert not got["control_correct"], got
        program.append(got["program"]["mean_logit_gap"])
        ctrl.append(got["control"]["mean_logit_gap"])
    assert min(ctrl) >= 3 * max(program), (program, ctrl)


def test_train_control_fails_where_the_program_passes(tiny_m6, tiny_pretrain):
    """Training: the reference's three steps computed at float8 (forward
    and backward) against the float32 reference read far above the
    program's bfloat16 steps; the gradient of the smallest leaves
    underflows in float8."""
    from bench import train_cell
    from bench.generators import train_batches

    with open(os.path.join(ROOT, "bench", "limits", "m6-base.train.json")) as f:
        limits = json.load(f)
    mc = dict(tiny_m6, dtype="bfloat16")
    out = train_cell.run("tiny", 5, 0.5, mc, tiny_pretrain, limits,
                         train_batches, None, run.CompileCounter(), 0.0)
    assert out["correct"], out["checks"]
    got = control.train_control(out, mc, tiny_pretrain, 5, limits)
    assert not got["control_correct"], got
    assert got["program"]["grad_norm_gap"] < 0.01
    assert got["control"]["grad_norm_gap"] > 0.5
