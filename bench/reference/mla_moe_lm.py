"""Plain float32 reference of a DeepSeek-V3-form language model
(``model_type`` deepseek_v3; Moonlight-16B-A3B): multi-head latent
attention, leading dense layers, then sparse expert layers with sigmoid
routing, a correction bias and shared experts.

Written from ``modeling_deepseek.py`` (DeepSeek-V3) in straightforward
``jax.numpy`` at ``precision="highest"``, with no kernel, cache, latent
absorption or batching trick.  It imports nothing of the program under
test and reads weights by the benchmark's own names
(``bench/arch/mla_moe_lm.py``).

Per layer (pre-norm RMSNorm):
  q = h Wq -> per head [q_nope (nope) | q_pe (rope)]
  [c | k_pe] = h Wkv_a;  c = RMSNorm(c) (kv_a_layernorm)
  [k_nope | v] = c Wkv_b per head
  q_pe, k_pe roped: channels de-interleaved (evens, then odds), then the
      first half rotated against the second (apply_rotary_pos_emb)
  x += Wo · softmax(causal([q_nope|q_pe]·[k_nope|k_pe]ᵀ · (nope+rope)^-½)) v
  dense layers (the first ``first_k_dense_replace``):
      x += Wdown (silu(h Wgate) ⊙ h Wup)
  expert layers: s = sigmoid(h Wr) over the router's experts; a token's
      experts are the top ``num_experts_per_tok`` of s + b (the
      correction bias); gates are their s over the sum of those s, times
      ``routed_scaling_factor``;
      x += Σ gate_e · FFN_e(h) + FFN_shared(h).
The held share (``expert_parallel``): this chip holds experts
[held_first, held_first + n_routed_experts) of the router's; only those
add, each with the gate normalised over all of the token's choices --
what one chip of an expert-parallel layer computes, before the exchange.

Attention and experts run over blocks of query rows, so that a sequence
of the configuration's whole context fits beside the weights.

``prec="fp8"`` is the control: every operand of a matrix product that
the configuration computes in bfloat16 is rounded to float8 e4m3 first;
the router, norms and softmax stay in float32.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
FP8_MAX = 448.0
BLOCK = 512                  # query rows per block (one block if T is not a multiple)


def fp8(a):
    return jnp.clip(a, -FP8_MAX, FP8_MAX).astype(jnp.float8_e4m3fn).astype(
        jnp.float32)


def mm(spec, a, b, prec):
    if prec == "fp8":
        a, b = fp8(a), fp8(b)
    return jnp.einsum(spec, a, b, precision=HI)


def rms(x, scale, eps):
    var = jnp.mean(jnp.square(x), -1, keepdims=True)
    return x / jnp.sqrt(var + eps) * scale


def rope(x, positions, theta):
    """DeepSeek's rotary embedding on x (T, ..., D): de-interleave the
    channels, then rotate the first half against the second."""
    T, D = x.shape[0], x.shape[-1]
    x = jnp.swapaxes(x.reshape(*x.shape[:-1], D // 2, 2), -1, -2).reshape(x.shape)
    inv = 1.0 / theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    ang = positions[:, None].astype(jnp.float32) * inv               # (T, D/2)
    emb = jnp.concatenate([ang, ang], -1).reshape((T,) + (1,) * (x.ndim - 2) + (D,))
    rot = jnp.concatenate([-x[..., D // 2:], x[..., :D // 2]], -1)
    return x * jnp.cos(emb) + rot * jnp.sin(emb)


def block_rows(T: int) -> int:
    return BLOCK if T % BLOCK == 0 else T


def blocked(fn, T: int):
    """fn(start) -> (rows, ...) over the query blocks of T rows, joined."""
    out = jax.lax.map(fn, jnp.arange(0, T, block_rows(T)))
    return out.reshape((T,) + out.shape[2:])


def attention(lw, h, mc, prec):
    """Causal latent attention over one sequence h: (T, d)."""
    T = h.shape[0]
    H, n, rp, r, dv = (mc["num_attention_heads"], mc["qk_nope_head_dim"],
                       mc["qk_rope_head_dim"], mc["kv_lora_rank"],
                       mc["v_head_dim"])
    pos = jnp.arange(T)
    q = mm("td,de->te", h, lw["wq"], prec).reshape(T, H, n + rp)
    kv_a = mm("td,de->te", h, lw["wkv_a"], prec)
    c = rms(kv_a[:, :r], lw["kv_norm"], mc["rms_norm_eps"])
    kv = mm("tr,re->te", c, lw["wkv_b"], prec).reshape(T, H, n + dv)
    k_pe = rope(kv_a[:, None, r:], pos, mc["rope_theta"])           # (T, 1, rp)
    q = jnp.concatenate([q[..., :n], rope(q[..., n:], pos, mc["rope_theta"])], -1)
    k = jnp.concatenate([kv[..., :n], jnp.broadcast_to(k_pe, (T, H, rp))], -1)
    v = kv[..., n:]
    scale = (n + rp) ** -0.5
    B = block_rows(T)

    def one(s0):
        qb = jax.lax.dynamic_slice_in_dim(q, s0, B)
        s = mm("qhe,khe->hqk", qb, k, prec) * scale
        rows = s0 + jnp.arange(B)
        s = jnp.where(jnp.arange(T)[None, None, :] <= rows[None, :, None],
                      s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return mm("hqk,khv->qhv", p, v, prec).reshape(B, H * dv)

    o = blocked(one, T)
    return mm("te,ed->td", o, lw["wo"], prec)


def ffn(h, gate, up, down, prec):
    a = jax.nn.silu(mm("td,df->tf", h, gate, prec)) * mm("td,df->tf", h, up, prec)
    return mm("tf,fd->td", a, down, prec)


def gates(lw, h, mc):
    """(T, held) combine weight of each held expert for each token: the
    sigmoid router over all of its experts, top-k on the biased scores,
    gates from the unbiased ones, normalised over the k, scaled."""
    ep = mc["expert_parallel"]
    s = jax.nn.sigmoid(jnp.einsum("td,de->te", h, lw["router"], precision=HI))
    _, choice = jax.lax.top_k(s + lw["router_bias"], mc["num_experts_per_tok"])
    g = jnp.take_along_axis(s, choice, -1)
    if mc["norm_topk_prob"]:
        g = g / (jnp.sum(g, -1, keepdims=True) + 1e-20)
    g = g * mc["routed_scaling_factor"]
    dense = jnp.zeros_like(s).at[jnp.arange(h.shape[0])[:, None], choice].set(g)
    first = ep["held_first"]
    return dense[:, first:first + mc["n_routed_experts"]]


def moe(lw, h, mc, prec):
    """Held experts, each over every row weighted by its gate (0 where
    the token did not choose it), plus the shared experts."""
    T = h.shape[0]
    B = block_rows(T)
    g = gates(lw, h, mc)
    experts = (lw["w_gate"], lw["w_up"], lw["w_down"], jnp.moveaxis(g, 1, 0))

    def one(s0):
        hb = jax.lax.dynamic_slice_in_dim(h, s0, B)

        def add(y, e):
            wg, wu, wd, ge = e
            gb = jax.lax.dynamic_slice_in_dim(ge, s0, B)
            return y + gb[:, None] * ffn(hb, wg, wu, wd, prec), None

        y, _ = jax.lax.scan(add, jnp.zeros_like(hb), experts)
        return y + ffn(hb, lw["shared_w_gate"], lw["shared_w_up"],
                       lw["shared_w_down"], prec)

    return blocked(one, T)


ATTN = ("ln1_scale", "ln2_scale", "wq", "wkv_a", "kv_norm", "wkv_b", "wo")
EXPERT = ("router", "router_bias", "w_gate", "w_up", "w_down",
          "shared_w_gate", "shared_w_up", "shared_w_down")


def layer_weights(w, mc, i: int) -> dict:
    """Layer i's weights, out of its group's stacks."""
    nd = mc["first_k_dense_replace"]
    g, j = ("dense", i) if i < nd else ("moe", i - nd)
    lw = {k: w[f"{g}_{k}"][j] for k in ATTN}
    if g == "dense":
        lw.update({k: w[f"dense_{k}"][j] for k in ("w_gate", "w_up", "w_down")})
    else:
        lw.update({k: w[k][j] for k in EXPERT})
    return lw


def hidden(w, mc, tokens, prec="f32"):
    """Final hidden states (T, d) of one sequence (T,)."""
    eps = mc["rms_norm_eps"]
    x = w["embed"][tokens]
    for i in range(mc["num_hidden_layers"]):
        lw = layer_weights(w, mc, i)
        x = x + attention(lw, rms(x, lw["ln1_scale"], eps), mc, prec)
        h = rms(x, lw["ln2_scale"], eps)
        if i < mc["first_k_dense_replace"]:
            x = x + ffn(h, lw["w_gate"], lw["w_up"], lw["w_down"], prec)
        else:
            x = x + moe(lw, h, mc, prec)
    return rms(x, w["final_scale"], eps)


def logits(w, mc, tokens, prec="f32"):
    """(T, vocab_size) next-token logits of one text sequence (T,)."""
    with jax.default_matmul_precision("highest"):
        x = hidden(w, mc, tokens, prec)
        return mm("td,vd->tv", x, w["unembed"][:mc["vocab_size"]], prec)
