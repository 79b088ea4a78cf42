"""The DeepSeek-V3 form (``bench/arch/mla_moe_lm.py``): its work counts
by hand, its weights where the program keeps them, the forms it
refuses, and a small configuration of it served on the CPU through the
lookups ``bench/run.py`` uses and checked against its reference."""
import json
import os

import pytest

from bench import arch, faults, program, run
from bench.arch import mla_moe_lm as form
from bench.tests.conftest import MANIFEST, PEAK, ROOT


def _moonlight(**over):
    with open(os.path.join(ROOT, "bench", "configs",
                           "moonlight-16b-a3b-5l.json")) as f:
        mc = json.load(f)
    mc.update(over)
    return mc


TINY = dict(hidden_size=8, num_attention_heads=2, kv_lora_rank=4,
            qk_nope_head_dim=3, qk_rope_head_dim=2, v_head_dim=5,
            intermediate_size=16, moe_intermediate_size=6, n_routed_experts=2,
            n_shared_experts=2, num_experts_per_tok=3, num_hidden_layers=3,
            first_k_dense_replace=1, vocab_size=50)


def test_counts_by_hand():
    mc = _moonlight(**TINY)
    mc["expert_parallel"] = dict(mc["expert_parallel"], n_routed_experts=4)
    # q 2*8*2*5, kv_a 2*8*6, absorb 2*2*3*4, v up 2*2*4*5, o 2*2*5*8
    attn = 160 + 96 + 48 + 80 + 160
    # latent attention a position: 2 heads x (4 + 2 score lanes + 4 value lanes) x 2
    lat = 2 * 2 * (4 + 2 + 4)
    dense = 2 * 8 * 16 * 3
    # router 2*8*4, shared 2*8*12*3, held choices 3 * 2/4 of 2*8*6*3
    moe = 2 * 8 * 4 + 2 * 8 * 12 * 3 + 1.5 * 2 * 8 * 6 * 3
    assert form.row_flops(mc, 7) == 3 * (attn + lat * 7) + dense + 2 * moe
    assert form.unembed_flops(mc) == 2 * 8 * 50
    # two rows of slot 3 (lengths 5, 6) and a decode row of slot 7 (10)
    rows = [(3, 5), (3, 6), (7, 10)]
    flops, nbytes = form.latent_attention_work(mc, rows)
    assert flops == lat * (5 + 6 + 10)
    # each slot's entries once (6 lanes, bf16); each row's query (2 x 6)
    # and latent output (2 x 4)
    assert nbytes == (6 + 10) * 6 * 2 + 3 * 2 * (6 + 4) * 2
    step = {"rows": rows, "experts_hit": [0, 2, 1]}
    assert form.kernel_work("paged_attn", mc, step) == [(flops, nbytes)] * 3
    assert form.kernel_work("moe_ffn", mc, step) == [
        (2 * 4.5 * 8 * 6 * 3, hit * 3 * 8 * 6 * 2 + 2 * 4.5 * 8 * 2)
        for hit in (2, 1)]
    assert form.kernel_work("moe_ffn", mc, dict(step, experts_hit=None)) is None


def test_layout_matches_program():
    """Every weight lands on a program leaf of its shape, at the
    published widths and at a small size alike (``model_config`` checks
    the shapes leaf for leaf and exits where they differ)."""
    for mc in (_moonlight(), _moonlight(**TINY)):
        cfg = program.model_config(mc, "serve")
        assert cfg.mla and cfg.moe.held_experts == mc["n_routed_experts"]
        assert cfg.moe.num_experts == mc["expert_parallel"]["n_routed_experts"]
        assert program.program_shapes(cfg, form.LAYOUT) == form.shapes(mc)


@pytest.mark.parametrize("key,value", [
    ("q_lora_rank", 1536), ("n_group", 8), ("topk_group", 4),
    ("scoring_func", "softmax"), ("topk_method", "greedy"),
    ("hidden_act", "gelu"), ("first_k_dense_replace", 0),
    ("rope_scaling", {"type": "yarn", "factor": 40})])
def test_refuses_a_form_the_program_lacks(key, value):
    with pytest.raises(SystemExit):
        program.model_config(_moonlight(**{key: value}), "serve")


@pytest.fixture
def tiny_moonlight():
    mc = _moonlight(**dict(TINY, hidden_size=64, num_attention_heads=4,
                           kv_lora_rank=32, qk_nope_head_dim=16,
                           qk_rope_head_dim=8, v_head_dim=16,
                           intermediate_size=96, moe_intermediate_size=32,
                           n_routed_experts=4, vocab_size=300,
                           max_position_embeddings=64, dtype="float32"))
    mc["expert_parallel"] = dict(mc["expert_parallel"], n_routed_experts=8)
    return mc


def test_served_on_the_cpu(tiny_moonlight, tiny_backlog):
    mc = tiny_moonlight
    assert arch.load(mc) is form

    def serve():
        return run.run_cell("mla.serve", 2**35 + 16, 0.5, False,
                            manifest=MANIFEST, mc=mc, traffic=tiny_backlog,
                            limits={"logit_gap": 1e-3}, peak=PEAK)

    out = serve()
    assert out["correct"], out["checks"]
    assert out["checks"]["logit_gap"]["value"] < 1e-4
    with faults.token_altered(mc["vocab_size"]):
        assert not serve()["correct"]
    with faults.kv_unchanged():             # latent entries never written
        assert not serve()["correct"]


def test_readers_of_the_new_metrics():
    """The cell's readers on a hand-made run record at published widths:
    the latent op's share of the step programs' device time, its
    roofline through this form's ``kernel_work``, and nothing read from a
    record without a trace (an untraced run, or a program with no such
    kernel)."""
    mc = _moonlight()
    L = mc["num_hidden_layers"]
    census = [{"rows": [(0, 700), (1, 900)], "sampled": 2, "kind": "decode",
               "experts_hit": [0] + [20] * (L - 1)}]
    trace = {"window_s": 0.2, "busy_s": 0.05, "steps": [{
        "device_s": 0.05, "kernel_calls": {"paged_attn": 1, "moe_ffn": 1},
        "kernel_s": {"paged_attn": 0.02, "moe_ffn": 0.01}}]}
    rec = {"mc": mc, "peak": PEAK, "census": census, "trace": trace}
    assert run.reader("attn_share.mla")(rec) == pytest.approx(40.0)
    flops, nbytes = form.latent_attention_work(mc, census[0]["rows"])
    assert flops == 2 * 16 * (512 + 64 + 512) * 1600
    assert nbytes == 1600 * 576 * 2 + 2 * 16 * (576 + 512) * 2
    want = 100.0 * L * max(flops / 197e12, nbytes / 819e9) / 0.02
    assert run.reader("paged_attn_roofline.mla")(rec) == pytest.approx(
        want, rel=1e-12)
    assert run.reader("moe_ffn_roofline.mla")(rec) > 0
    assert run.reader("device_idle.mla")(rec) == pytest.approx(75.0)
    bare = {"mc": mc, "peak": PEAK}
    for name in ("attn_share.mla", "paged_attn_roofline.mla", "mfu.mla"):
        assert run.reader(name)(bare) is None
    no_kernel = dict(rec, trace=dict(trace, steps=[{
        "device_s": 0.05, "kernel_calls": {}, "kernel_s": {}}]))
    assert run.reader("attn_share.mla")(no_kernel) == 0.0


# mean logit gap, CPU, bfloat16, >= 200 served tokens, seeds 1, 2, 5, 6, 7,
# 2**34 + 2 and 3000016101: program 4.3e-6 to 3.1e-5, float8 control
# 5.5e-4 to 1.7e-3; the limit lies between, with room on both sides
SMALL_LIMIT = {"mean_logit_gap": 1.5e-4}


def test_control_fails_where_the_program_passes(tiny_moonlight, tiny_backlog):
    """The cell's control (the reference at float8 e4m3 in the program's
    place) goes through the check and comes out false where the
    program's bfloat16 run passes, at a size a test run holds."""
    from bench import control, serve_cell
    from bench.generators import backlog

    mc = dict(tiny_moonlight, dtype="bfloat16")
    traffic = dict(tiny_backlog, check={"tokens_min": 200, "requests_max": 30})
    program_gap, control_gap = [], []
    for seed in (1, 2**34 + 2):
        out = serve_cell.run("tiny", seed, 0.5, mc, traffic, SMALL_LIMIT,
                             backlog, None, run.CompileCounter(), 0.0)
        assert out["correct"], out["checks"]
        got = control.serve_control(out, mc, traffic, SMALL_LIMIT)
        assert got["served_tokens"] >= 200
        assert not got["control_correct"], got
        program_gap.append(got["program"]["mean_logit_gap"])
        control_gap.append(got["control"]["mean_logit_gap"])
    assert min(control_gap) >= 3 * max(program_gap), (program_gap, control_gap)
