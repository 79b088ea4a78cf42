"""Latent attention, layer groups, sigmoid routing, shared experts and the
held share (the DeepSeek-V3 form of ``moonlight-16b-a3b``) against the
benchmark's plain float32 reference, ``bench/reference/mla_moe_lm.py``.

Smoke size: d 64, 4 heads, kv_lora_rank 32, nope/rope/v 16/8/16, 8
routed experts of which 4 are held, top-3, one dense and two expert
layers, float32.  Weights come from the benchmark's own maker and are
placed by its layout, so the program and the reference read the same
arrays.

Tolerances: every path computes in float32 here, and what differs from
the reference is the order of summation (the absorbed query and value
up-projection against per-head keys and values, online softmax against
a materialised one, blocked experts against a ragged grouped matmul),
a few float32 ulps of values of order 1: 1e-5 of the largest logit
covers that with a factor of about ten to spare.  A bfloat16 path is
off by 1e-3 and more, which ``test_bfloat16_absorbed_path_fails``
shows.
"""
import dataclasses
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import program, weights  # noqa: E402
from bench.reference import mla_moe_lm as ref  # noqa: E402
from repro.configs.base import ServeConfig, SLOConfig, SpecConfig  # noqa: E402
from repro.core.moe import moe_ffn_apply  # noqa: E402
from repro.core.routers.sigmoid import sigmoid_bias_plan  # noqa: E402
from repro.kernels.decode_attention.kernel import (  # noqa: E402
    paged_decode_attention_kernel,
)
from repro.kernels.decode_attention.ref import (  # noqa: E402
    paged_decode_attention_ref,
)
from repro.models.attention import mla_absorb_q, mla_scale, mla_v_up  # noqa: E402
from repro.models.registry import get_family  # noqa: E402
from repro.serving.continuous import ContinuousEngine  # noqa: E402
from repro.serving.request import Request  # noqa: E402

RTOL = 1e-5       # of the largest |logit|: float32 summation order (above)
SEED = 2**33 + 16


def _file(**over):
    with open(os.path.join(ROOT, "bench", "configs",
                           "moonlight-16b-a3b-5l.json")) as f:
        mc = json.load(f)
    mc.update(hidden_size=64, num_attention_heads=4, kv_lora_rank=32,
              qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
              intermediate_size=96, moe_intermediate_size=32,
              n_routed_experts=4, num_experts_per_tok=3, num_hidden_layers=3,
              vocab_size=300, max_position_embeddings=64, dtype="float32",
              initializer_range=0.1)
    mc["expert_parallel"] = dict(mc["expert_parallel"], n_routed_experts=8)
    mc.update(over)
    return mc


@pytest.fixture(scope="module")
def case():
    mc = _file()
    cfg = program.model_config(mc, "serve")
    w = weights.make(mc, SEED)
    return mc, cfg, w, program.program_params(mc, w)


def _ref_logits(w, mc, seq):
    return np.asarray(ref.logits(w, mc, jnp.asarray(seq, jnp.int32)))


def _served_logits(cfg, params, prompt, n_new, serve):
    """Prefill then decode one request; the engine's logits by position."""
    seen = {}

    def tap(lg, slots, pos, lens):
        for r in np.nonzero(np.asarray(lens))[0]:
            seen[int(pos[r])] = np.asarray(lg[r])

    eng = ContinuousEngine(cfg, params, serve, logit_tap=tap)
    out, _ = eng.run([Request(uid=0, prompt=prompt, max_new_tokens=n_new)])
    seq = np.concatenate([prompt, out[0][:-1]])
    return seq, np.stack([seen[t] for t in range(seq.size)]), eng


SERVE = ServeConfig(max_slots=2, kv_block_size=4, prefill_chunk=4, max_len=32)
PROMPT = np.array([5, 9, 3, 7, 11, 2, 250, 17, 40, 1], np.int32)


def _gap(a, b, vocab):
    return float(np.abs(a[:, :vocab] - b[:, :vocab]).max()
                 / np.abs(b[:, :vocab]).max())


def test_prefill_then_decode_matches_reference(case):
    """Chunked prefill (chunks of 4 over 3 blocks) then decode through the
    latent pool and the absorbed paged op, against the reference's full
    forward over the same sequence."""
    mc, cfg, w, params = case
    seq, got, eng = _served_logits(cfg, params, PROMPT, 8, SERVE)
    assert eng.cache.v_pool is None                       # no V pool
    # one pool per layer group (1 dense, 2 expert layers), one entry
    # (latent 32 + rope 8, zero-padded to 128 lanes) a token: a block of 4
    # costs 3 x 4 x 128 x 4 B
    assert [p.shape for p in eng.cache.k_pool] == [(1, 17, 1, 4, 128),
                                                   (2, 17, 1, 4, 128)]
    assert eng.cache.block_bytes == 3 * 4 * 128 * 4
    assert _gap(got, _ref_logits(w, mc, seq), mc["vocab_size"]) < RTOL


def test_bfloat16_absorbed_path_fails(case):
    """The tolerance is tight enough that the same path in bfloat16, where
    the configuration says float32, falls outside it."""
    mc, cfg, w, params = case
    seq, got, _ = _served_logits(cfg.replace(dtype="bfloat16"), params,
                                 PROMPT, 4, SERVE)
    assert _gap(got, _ref_logits(w, mc, seq), mc["vocab_size"]) > 10 * RTOL


def test_full_sequence_forward_matches_reference(case):
    """The training-side forward (keys and values per head, no cache)."""
    mc, cfg, w, params = case
    seq = np.arange(1, 20, dtype=np.int32) * 13 % mc["vocab_size"]
    lg, aux = get_family(cfg).forward(params, {"tokens": seq[None],
                                               "labels": seq[None]}, cfg)
    assert _gap(np.asarray(lg[0]), _ref_logits(w, mc, seq),
                mc["vocab_size"]) < RTOL
    # per-layer telemetry: the dense layer first, then the expert layers
    assert aux["moe_expert_tokens"].shape == (3, 4)
    assert float(aux["moe_held_choices"][0]) == 0.0


def test_full_sequence_forward_takes_a_gradient(case):
    mc, cfg, w, params = case
    fam = get_family(cfg)
    seq = np.arange(1, 13, dtype=np.int32)[None]

    def loss(p):
        lg, _ = fam.forward(p, {"tokens": seq, "labels": seq}, cfg)
        return jnp.mean(jax.nn.logsumexp(lg[..., :mc["vocab_size"]], -1))

    g = jax.grad(loss)(params)
    norms = [float(jnp.linalg.norm(x)) for x in jax.tree_util.tree_leaves(g)]
    assert all(np.isfinite(norms)) and max(norms) > 0


@pytest.mark.parametrize("path", ["reference", "interpret"])
def test_latent_paged_op_matches_plain_attention(case, path):
    """The one paged op in latent mode (V = the leading kv_lora_rank lanes
    of each cached entry, scale (nope + rope)^-0.5), with the absorbed
    query and the value up-projection, against attention over per-head
    keys and values.  Rows: a prefill chunk of slot 0 (lengths 5..8, the
    chunk's own entries among them) and decode rows of slots 1 and 2."""
    mc, cfg, w, params = case
    ap = jax.tree_util.tree_map(lambda a: a[0], params["blocks"]["attn"])
    H, n, rp, r = 4, 16, 8, 32
    rng = np.random.default_rng(7)
    bs, MB, P = 4, 3, 10
    lat = rng.standard_normal((P, 1, bs, r + rp)).astype(np.float32)
    tables = np.array([[1, 4, 9]] * 4 + [[2, 3, 9], [5, 6, 7]], np.int32)
    lengths = np.array([5, 6, 7, 8, 11, 3], np.int32)
    q_nope = rng.standard_normal((6, H, n)).astype(np.float32)
    q_pe = rng.standard_normal((6, H, rp)).astype(np.float32)
    q = mla_absorb_q(ap, q_nope, q_pe, cfg)
    scale = mla_scale(cfg)
    if path == "reference":
        o_lat = paged_decode_attention_ref(q, lat, None, tables, lengths,
                                           scale=scale, v_dim=r)
    else:
        o_lat = paged_decode_attention_kernel(
            q.reshape(6, 1, H, r + rp), lat, None, tables, lengths,
            scale=scale, v_dim=r, interpret=True).reshape(6, H, r)
    got = np.asarray(mla_v_up(ap, o_lat, cfg))

    wkv_b = np.asarray(ap["wkv_b"]["kernel"]).reshape(r, H, n + 16)
    for i in range(6):
        ent = lat[tables[i]].reshape(MB * bs, r + rp)[:lengths[i]]
        k_nope = np.einsum("tr,rhn->thn", ent[:, :r], wkv_b[..., :n])
        v = np.einsum("tr,rhv->thv", ent[:, :r], wkv_b[..., n:])
        s = (np.einsum("hn,thn->ht", q_nope[i], k_nope)
             + np.einsum("he,te->ht", q_pe[i], ent[:, r:])) * scale
        p = np.exp(s - s.max(-1, keepdims=True))
        want = np.einsum("ht,thv->hv", p / p.sum(-1, keepdims=True), v)
        # float32 on both sides, summed in another order (online softmax,
        # absorbed query): a few 1e-7 on outputs below 1, so atol 1e-5
        # leaves a factor of about 40
        np.testing.assert_allclose(got[i], want, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("path", ["reference", "interpret"])
def test_latent_paged_op_reads_its_layer_in_place(path):
    """With ``layer``, the op takes a layer group's whole pool: this
    step's entries land in that layer only, and the read (the jnp
    reference, or the Pallas kernel in interpret mode) is the op's over
    that layer alone, bit for bit."""
    from repro.kernels.decode_attention.ops import paged_update_attention

    rng = np.random.default_rng(11)
    L, P, bs, D, r, H = 3, 10, 4, 40, 32, 4
    pools = jnp.asarray(rng.standard_normal((L, P, 1, bs, D)), jnp.float32)
    q = jnp.asarray(rng.standard_normal((5, H, D)), jnp.float32)
    entries = jnp.asarray(rng.standard_normal((5, 1, D)), jnp.float32)
    wb = jnp.array([1, 1, 4, 9, 0], jnp.int32)      # row 4 masked: garbage block
    wo = jnp.array([0, 1, 3, 2, 0], jnp.int32)
    tables = jnp.array([[1, 4, 9]] * 3 + [[9, 3, 2], [5, 6, 7]], jnp.int32)
    lengths = jnp.array([1, 2, 8, 11, 0], jnp.int32)
    kw = dict(scale=0.3, v_dim=r)

    def attend(pool, layer=None):
        if path == "reference":
            return paged_decode_attention_ref(q, pool, None, tables, lengths,
                                              layer=layer, **kw)
        return paged_decode_attention_kernel(
            q.reshape(5, 1, H, D), pool, None, tables, lengths, layer=layer,
            interpret=True, **kw).reshape(5, H, r)

    _, new, _ = paged_update_attention(q, entries, None, pools, None, wb, wo,
                                       tables, lengths, layer=jnp.int32(1),
                                       **kw)
    _, one, _ = paged_update_attention(q, entries, None, pools[1], None, wb,
                                       wo, tables, lengths, **kw)
    np.testing.assert_array_equal(np.asarray(new[1]), np.asarray(one))
    for other in (0, 2):
        np.testing.assert_array_equal(np.asarray(new[other]),
                                      np.asarray(pools[other]))
    got = np.asarray(attend(new, jnp.int32(1)))
    np.testing.assert_array_equal(got, np.asarray(attend(one)))
    assert np.abs(got[:4]).min() > 0 and not got[4].any()


def test_held_shares_add_up_to_the_whole_layer(case):
    """Experts [0, 4) and [4, 8) of 8 on two chips: their outputs, with the
    shared experts counted once, add up to the uncut reference layer."""
    mc, cfg, w, params = case
    mc_all = _file(n_routed_experts=8)
    w_all = weights.make(mc_all, SEED + 1)
    lw = ref.layer_weights(w_all, mc_all, 1)
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 16, 64))
    whole = np.asarray(ref.moe(lw, x[0], mc_all, "f32"))

    fp = jax.tree_util.tree_map(lambda a: a[0],
                                program.program_params(mc_all, w_all)["blocks"]["ffn"])
    outs = []
    for first in (0, 4):
        share = dict(fp, **{k: fp[k][first:first + 4]
                            for k in ("up", "gate", "down")})
        c = cfg.replace_moe(held_offset=first)
        y, aux = moe_ffn_apply(share, x, c)
        outs.append(np.asarray(y[0]))
    shared = np.asarray(ref.ffn(x[0], lw["shared_w_gate"], lw["shared_w_up"],
                                lw["shared_w_down"], "f32"))
    total = outs[0] + outs[1] - shared
    # float32 sums split in two: about 2e-7 on outputs near 2, against
    # an atol of RTOL x the largest output (about 2e-5)
    np.testing.assert_allclose(total, whole, rtol=1e-4,
                               atol=RTOL * np.abs(whole).max())
    # and one share alone is not the whole
    assert np.abs(outs[0] - whole).max() > 100 * RTOL * np.abs(whole).max()


def test_correction_bias_selects_but_does_not_weigh(case):
    """The bias flips some selections, never the gate values: the gates
    are the unbiased scores of the chosen experts over their sum, times
    the scaling factor."""
    mc, cfg, w, params = case
    m = cfg.moe
    logits = jax.random.normal(jax.random.PRNGKey(5), (1, 64, 8))
    bias = 0.3 * jax.random.normal(jax.random.PRNGKey(6), (8,))
    plain = sigmoid_bias_plan(logits, jnp.zeros(8), m, capacity=64)
    biased = sigmoid_bias_plan(logits, bias, m, capacity=64)
    flips = (np.sort(np.asarray(plain.expert_index), -1)
             != np.sort(np.asarray(biased.expert_index), -1)).any(-1)
    assert flips.any() and not flips.all()
    s = np.asarray(jax.nn.sigmoid(logits))
    for plan in (plain, biased):
        e = np.asarray(plan.expert_index)
        chosen = np.take_along_axis(s, e, -1)
        want = chosen / chosen.sum(-1, keepdims=True) * m.routed_scaling_factor
        np.testing.assert_allclose(np.asarray(plan.gate), want, rtol=1e-6)
        np.testing.assert_allclose(np.asarray(plan.gate).sum(-1),
                                   m.routed_scaling_factor, rtol=1e-6)
        # top-k of the biased scores
        top = np.sort(np.argsort(-(s + np.asarray(
            bias if plan is biased else 0)), -1)[..., :m.top_k], -1)
        np.testing.assert_array_equal(np.sort(e, -1), top)


@pytest.mark.parametrize("serve", [
    dict(prefix_cache=True), dict(kv_quant="int8"),
    dict(spec=SpecConfig(gamma=2)), dict(mesh=(("data", 1), ("expert", 1))),
    dict(slo=SLOConfig())], ids=["prefix", "kv_quant", "spec", "mesh", "swap"])
def test_unsupported_combinations_raise_at_construction(case, serve):
    mc, cfg, w, params = case
    with pytest.raises(NotImplementedError, match="latent attention"):
        ContinuousEngine(cfg, params,
                         ServeConfig(max_slots=2, kv_block_size=4,
                                     prefill_chunk=4, max_len=32, **serve))


def test_layer_groups_with_kv_heads_raise_at_construction(case):
    """A leading dense group is served only with the latent pool: with K
    and V heads the engine refuses it rather than run it."""
    mc, cfg, w, params = case
    with pytest.raises(NotImplementedError, match="latent attention only"):
        ContinuousEngine(dataclasses.replace(cfg, attention="mha"), params,
                         SERVE)
