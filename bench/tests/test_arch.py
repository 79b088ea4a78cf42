"""The architectural form a configuration states (``bench/arch``).

* The M6 and OLMoE files' form, ``moe_lm``, gives the weights, counts
  and readings that the harness gave when it knew that one form alone:
  the numbers below were taken from that harness, so a change to the
  seam that moves any of them shows here.
* A form the harness has never seen runs from new files alone: the
  dense decoder under ``fixtures/`` is served on the CPU through the
  lookups ``bench/run.py`` uses and checked against its own reference.
* A configuration that names no reference, or one whose form has no
  module, is refused.
"""
import hashlib
import json
import os
import sys

import numpy as np
import pytest

import bench.arch
import bench.reference
from bench import arch, faults, program, readings, run, weights
from bench.arch import moe_lm
from bench.tests.conftest import MANIFEST, PEAK, ROOT

FIXTURES = os.path.join(ROOT, "bench", "tests", "fixtures")


def _config(name):
    with open(os.path.join(ROOT, "bench", "configs", name + ".json")) as f:
        return json.load(f)


# sha256 (first 16 hex digits) of each float32 array of
# weights.make(file with 1 layer, 4 experts, vocabulary 512, seed 2**33 + 5)
WEIGHT_SUMS = {
    "m6-base": {
        "embed": "5804ad5a99df94cd", "final_bias": "33b7b9b68f076474",
        "final_scale": "a49b67ba7c857fe3", "ln1_bias": "f895f21ab19c18aa",
        "ln1_scale": "d4647305f4f9bb73", "ln2_bias": "ae6e48da1a1bd9cc",
        "ln2_scale": "b2c40653a8e26fc5", "pos_embed": "65da540cc480f9c1",
        "router": "9ad5ca78dd08a2a2", "w_down": "72af8dee68617ce2",
        "w_up": "269c1b18b36b0a30", "wk": "f5b95077ba6ffd80",
        "wo": "79386431ba2b6e73", "wq": "a5506c5a6f1631f5",
        "wv": "5adf4d871f1da47e"},
    "olmoe-1b-7b-4l": {
        "embed": "17ec7158fd8b0f5d", "final_scale": "2a9e90849b0b77f6",
        "k_norm": "0a799127654ed31a", "ln1_scale": "435ce18573ce8382",
        "ln2_scale": "7da488819de0f859", "q_norm": "1db47fe948bdf7e3",
        "router": "73bcbf201b34dc76", "unembed": "e84fd54d4ca4e2b9",
        "w_down": "f644ebaa75d9eb68", "w_gate": "11d23cb854499e4c",
        "w_up": "8d7cf6da6876109f", "wk": "095f221267d1f807",
        "wo": "ef6916099710d91d", "wq": "fb1926f60c70e196",
        "wv": "3beb1df6f402f6eb"},
}


@pytest.mark.parametrize("name", sorted(WEIGHT_SUMS))
def test_weights_bit_for_bit(name):
    mc = dict(_config(name), num_hidden_layers=1, num_experts=4, vocab_size=512)
    w = weights.make(mc, 2**33 + 5)
    assert list(w) == sorted(WEIGHT_SUMS[name])
    got = {k: hashlib.sha256(np.asarray(v).tobytes()).hexdigest()[:16]
           for k, v in w.items()}
    assert got == WEIGHT_SUMS[name]
    assert all(v.dtype == np.float32 for v in w.values())


ROWS = [(0, 1), (3, 40), (3, 41), (7, 255)]
COUNTS = {
    "m6-base": {
        "row_flops": [126177280, 126914560, 131399680],
        "unembed_flops": 43270144, "train_step_flops": 574055251968,
        "attention_work": [(1380352, 1232896)] * 5,
        "hits": [16, 17, 18, 19, 20],
        "expert_work": [(67108864, 268451840), (67108864, 285229056),
                        (67108864, 302006272), (67108864, 318783488),
                        (67108864, 335560704)]},
    "olmoe-1b-7b-4l": {
        "row_flops": [537952256, 539131904, 546308096],
        "unembed_flops": 206045184, "train_step_flops": 2291952254976,
        "attention_work": [(2760704, 2465792)] * 4,
        "hits": [32, 33, 34, 35],
        "expert_work": [(402653184, 402915328), (402653184, 415498240),
                        (402653184, 428081152), (402653184, 440664064)]},
}


@pytest.mark.parametrize("name", sorted(COUNTS))
def test_counts_unchanged(name):
    mc, want = _config(name), COUNTS[name]
    assert [moe_lm.row_flops(mc, n) for n in (1, 37, 256)] == want["row_flops"]
    assert moe_lm.unembed_flops(mc) == want["unembed_flops"]
    assert moe_lm.train_step_flops(mc, 8, 128) == want["train_step_flops"]
    step = {"rows": ROWS, "experts_hit": want["hits"]}
    assert moe_lm.kernel_work("paged_attn", mc, step) == want["attention_work"]
    assert moe_lm.kernel_work("moe_ffn", mc, step) == want["expert_work"]
    assert moe_lm.kernel_work("moe_ffn", mc, dict(step, experts_hit=None)) is None


# the readers on one hand-made run record, as repr of the float returned
READS = {
    "m6-base": {"mfu": "0.001000484385786802",
                "prefill_mfu": "0.008138824771573605",
                "paged_attn_roofline": "0.28056849816849816",
                "moe_ffn_roofline": "10.243250793650795",
                "mfu.train": "2.913986050598985"},
    "olmoe-1b-7b-4l": {"mfu": "0.004342375439593909",
                       "prefill_mfu": "0.03529005482233503",
                       "paged_attn_roofline": "0.4489095970695971",
                       "moe_ffn_roofline": "10.917865494505497",
                       "mfu.train": "11.634275405969543"},
}


def _record(mc):
    L = mc["num_hidden_layers"]
    census = [{"rows": ROWS, "sampled": 3, "kind": "mixed",
               "experts_hit": COUNTS[mc["name"]]["hits"]},
              {"rows": [(0, 2), (7, 256)], "sampled": 2, "kind": "decode",
               "experts_hit": [2] * L}]
    calls = {"paged_attn": 1, "moe_ffn": 1}
    trace = {"window_s": 0.5, "busy_s": 0.4, "modules": [(0.0, 0.1)],
             "steps": [{"device_s": 0.04, "kernel_calls": calls,
                        "kernel_s": {"paged_attn": 0.003, "moe_ffn": 0.011}},
                       {"device_s": 0.03, "kernel_calls": calls,
                        "kernel_s": {"paged_attn": 0.002, "moe_ffn": 0.009}}]}
    serve = {"mc": mc, "peak": PEAK, "census": census, "trace": trace}
    train = {"mc": mc, "peak": PEAK, "train": {"batch": 8, "text_len": 128},
             "trace": dict(trace, modules=[(0, 1)] * 5)}
    return serve, train


@pytest.mark.parametrize("name", sorted(READS))
def test_readers_unchanged(name):
    serve, train = _record(_config(name))
    got = {q: repr(run.reader(q)(train if q == "mfu.train" else serve))
           for q in READS[name]}
    assert got == READS[name]


def test_a_configuration_must_state_its_form():
    mc = _config("olmoe-1b-7b-4l")
    del mc["reference"]
    for bad in (mc, dict(mc, reference="no_such_form"),
                dict(mc, reference="../moe_lm"), dict(mc, reference=7)):
        with pytest.raises(SystemExit, match='"reference"'):
            arch.load(bad)
    with pytest.raises(SystemExit, match='"reference"'):
        program.model_config(mc, "serve")
    with pytest.raises(SystemExit, match='"reference"'):
        weights.make(mc, 1)


@pytest.fixture
def dense_form(monkeypatch):
    """The fixture's form and reference, found as if their files sat in
    bench/arch/ and bench/reference/."""
    monkeypatch.setattr(bench.arch, "__path__",
                        list(bench.arch.__path__) + [os.path.join(FIXTURES, "arch")])
    monkeypatch.setattr(bench.reference, "__path__",
                        list(bench.reference.__path__)
                        + [os.path.join(FIXTURES, "reference")])
    with open(os.path.join(FIXTURES, "configs", "dense-tiny.json")) as f:
        mc = json.load(f)
    yield mc
    for mod in ("bench.arch.dense_lm", "bench.reference.dense_lm"):
        sys.modules.pop(mod, None)


def test_a_new_form_needs_new_files_only(dense_form, tiny_backlog):
    mc = dense_form
    form = arch.load(mc)
    assert form.__file__.startswith(FIXTURES)
    cfg = program.model_config(mc, "serve")
    assert cfg.moe.num_experts == 0 and cfg.num_kv_heads == 2

    def serve():
        return run.run_cell("dense.serve", 2**35 + 11, 0.5, False,
                            manifest=MANIFEST, mc=mc, traffic=tiny_backlog,
                            limits={"logit_gap": 1e-3}, peak=PEAK)

    out = serve()
    assert out["correct"], out["checks"]
    assert out["checks"]["served_tokens"]["value"] >= 24
    assert out["checks"]["logit_gap"]["value"] < 1e-4
    with faults.token_altered(mc["vocab_size"]):
        assert not serve()["correct"]

    # the readers count the new form's work by its own module
    rec = {"mc": mc, "peak": PEAK,
           "census": [{"rows": [(0, 3), (1, 5)], "sampled": 2,
                       "experts_hit": None, "kind": "decode"}],
           "trace": {"window_s": 1.0, "steps": [{
               "device_s": 0.5, "kernel_calls": {"paged_attn": 1},
               "kernel_s": {"paged_attn": 0.25}}]}}
    rows = 2 * form.layer_matmul_flops(mc) + form.attention_flops(mc, 8)
    want = 100.0 * (2 * rows + 2 * 2 * 64 * 300) / 197e12
    assert readings.mfu(rec) == pytest.approx(want, rel=1e-12)
    assert readings.roofline(rec, "paged_attn") > 0
    assert readings.roofline(rec, "moe_ffn") is None
