"""The rest of a run, on the CPU at a small size, with the look for a
chip skipped: a sound run comes out correct, and the run comes out not
correct for each fault the cell can have (``bench/faults.py``), planted
in the timed path.

The small configurations run in float32, where the program and the
reference agree to rounding, so the limits here are tight.
"""
import pytest

from bench import faults, run
from bench.tests.conftest import MANIFEST, PEAK

SERVE_LIMITS = {"logit_gap": 1e-3}
TRAIN_LIMITS = {"loss_gap": 1e-4, "grad_norm_gap": 1e-3,
                "update_norm_gap": 1e-3}


def _serve(mc, traffic):
    return run.run_cell("tiny.serve", 2**35 + 3, 0.5, False, manifest=MANIFEST,
                        mc=mc, traffic=traffic, limits=SERVE_LIMITS, peak=PEAK)


def _train(mc, traffic):
    return run.run_cell("tiny.train", 2**33 + 9, 0.5, False, manifest=MANIFEST,
                        mc=mc, traffic=traffic, limits=TRAIN_LIMITS, peak=PEAK)


@pytest.mark.parametrize("family", ["m6", "olmoe"])
def test_serve_sound_and_faults(family, tiny_m6, tiny_olmoe, tiny_backlog):
    # weights large enough that every part of the step moves the argmax
    mc = dict(tiny_m6 if family == "m6" else tiny_olmoe, initializer_range=0.5)
    assert _serve(mc, tiny_backlog)["correct"]
    for name, fault in faults.faults(mc, "serve").items():
        with fault():
            assert not _serve(mc, tiny_backlog)["correct"], name


def test_train_sound_and_faults(tiny_m6, tiny_pretrain):
    assert _train(tiny_m6, tiny_pretrain)["correct"]
    for name, fault in faults.faults(tiny_m6, "train").items():
        with fault():
            out = _train(tiny_m6, tiny_pretrain)
        assert not out["correct"], name
        if name == "state_unchanged":
            assert out["checks"]["update_norm_gap"]["value"] > 0.99
