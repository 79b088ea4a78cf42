"""Each count function against arithmetic done by hand."""
from bench.arch import moe_lm as model
from bench.counts import attention, moe_ffn


def test_paged_attention_counts():
    # two rows of slot 3 (a prefill chunk at lengths 5 and 6) and one
    # decode row of slot 7 at length 10; 4 query heads, 2 KV heads, D 8
    rows = [(3, 5), (3, 6), (7, 10)]
    flops, nbytes = attention.paged_attention(rows, heads=4, kv_heads=2,
                                              head_dim=8)
    assert flops == 4 * (5 + 6 + 10) * 4 * 8
    kv = (6 + 10) * 2 * 2 * 8 * 2        # slot contexts once, K and V, bf16
    qo = 3 * 2 * 4 * 8 * 2               # query in, output out, per row
    assert nbytes == kv + qo


def test_ragged_ffn_counts():
    flops, nbytes = moe_ffn.ragged_ffn(choices=10, experts_hit=3, d_model=16,
                                       d_ff=32, n_mats=3)
    assert flops == 2 * 10 * 16 * 32 * 3
    assert nbytes == 3 * 3 * 16 * 32 * 2 + 2 * 10 * 16 * 2


def test_model_flops():
    mc = {"hidden_size": 8, "num_attention_heads": 2, "num_key_value_heads": 1,
          "head_dim": 4, "num_experts": 4, "num_experts_per_tok": 2,
          "intermediate_size": 16, "hidden_act": "silu",
          "num_hidden_layers": 3, "vocab_size": 50, "num_image_tokens": 1}
    proj = 2 * 8 * (2 * 4 + 2 * 1 * 4) + 2 * 2 * 4 * 8
    per_layer = proj + 2 * 8 * 4 + 2 * 2 * 8 * 16 * 3
    assert model.layer_matmul_flops(mc) == per_layer
    assert model.row_flops(mc, 5) == 3 * (per_layer + 4 * 5 * 2 * 4)
    assert model.unembed_flops(mc) == 2 * 8 * 50
    # batch 2 of 1 image + 3 text positions: causal lengths 1..4
    fwd = 4 * 3 * per_layer + 3 * sum(4 * t * 2 * 4 for t in (1, 2, 3, 4))
    fwd += 3 * 2 * 8 * 50
    assert model.train_step_flops(mc, batch=2, text_len=3) == 3 * 2 * fwd
