"""Plain float32 reference of a decoder-only language model with sparse
expert FFNs: M6 (the M6-T paper, Table 5) and OLMoE (arXiv:2409.02060).

Written from the published descriptions, in straightforward
``jax.numpy`` at ``precision="highest"``, with no kernel, cache or
batching trick.  It imports nothing of the program under test and reads
weights by the benchmark's own names (``bench/weights.py``).

Per layer (pre-norm):
  x += Wo · softmax(causal(q kᵀ / sqrt(D))) v,  q, k, v = norm(x) Wq, Wk, Wv
      (RMS QK-norm per head where the config says "per_head", and rotary
       positions where it says "rope")
  x += Σ_{k chosen} p_k · FFN_{e_k}(norm(x)),   p = softmax(norm(x) Wr)
      the top-``num_experts_per_tok`` experts of p, gates not renormalised;
      FFN = Wdown · act(Wup x) (gelu) or Wdown · (silu(Wgate x) ⊙ Wup x).
With a capacity factor (training), tokens are routed in GShard groups:
each expert keeps the first C = k·T/E·factor choices of its group in
token order (all first choices before any second), the rest are dropped.

``prec="fp8"`` is the control: every operand of a matrix product that
the configuration computes in bfloat16 is rounded to float8 e4m3 first;
the router, norms and softmax stay in float32, as the configuration
states.  ``prec="fp8w"`` is the same for weights already rounded by the
caller (which keeps a gradient's working memory that of float32).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
FP8_MAX = 448.0


def fp8(a):
    """Round to float8 e4m3 (saturating at its largest value).  Under
    ``jax.grad`` the cotangent is rounded the same way, so a backward
    pass computes in float8 too."""
    return jnp.clip(a, -FP8_MAX, FP8_MAX).astype(jnp.float8_e4m3fn).astype(
        jnp.float32)


def mm(spec, a, b, prec, b_is_weight=True):
    """A matrix product in the reference's precision.  ``"fp8w"`` rounds
    only the activations: the caller has rounded the weights already."""
    if prec in ("fp8", "fp8w"):
        a = fp8(a)
        if prec == "fp8" or not b_is_weight:
            b = fp8(b)
    return jnp.einsum(spec, a, b, precision=HI)


def norm(x, mc, scale, bias=None):
    if mc["norm"] == "layernorm":
        mu = jnp.mean(x, -1, keepdims=True)
        var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
        return (x - mu) / jnp.sqrt(var + mc["layer_norm_eps"]) * scale + bias
    var = jnp.mean(jnp.square(x), -1, keepdims=True)
    return x / jnp.sqrt(var + mc["rms_norm_eps"]) * scale


def rope(x, positions, theta):
    """Rotary positions, first half of each head rotated against the second."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions[:, None].astype(jnp.float32) * freq            # (T, half)
    sin, cos = jnp.sin(ang)[:, None], jnp.cos(ang)[:, None]         # (T,1,half)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(lw, h, mc, prec):
    """Causal multi-head attention over one sequence h: (T, d); ``lw``
    holds one layer's weights."""
    T = h.shape[0]
    H, Hkv, D = (mc["num_attention_heads"], mc["num_key_value_heads"],
                 mc["head_dim"])
    q = mm("td,de->te", h, lw["wq"], prec).reshape(T, H, D)
    k = mm("td,de->te", h, lw["wk"], prec).reshape(T, Hkv, D)
    v = mm("td,de->te", h, lw["wv"], prec).reshape(T, Hkv, D)
    if mc["qk_norm"]:
        if mc["qk_norm"] != "per_head":
            raise ValueError(f"QK-norm form {mc['qk_norm']!r} is not written here")
        eps = mc["rms_norm_eps"]
        q = q / jnp.sqrt(jnp.mean(q * q, -1, keepdims=True) + eps) * lw["q_norm"]
        k = k / jnp.sqrt(jnp.mean(k * k, -1, keepdims=True) + eps) * lw["k_norm"]
    if mc["position_embedding"] == "rope":
        pos = jnp.arange(T)
        q, k = rope(q, pos, mc["rope_theta"]), rope(k, pos, mc["rope_theta"])
    rep = H // Hkv
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    s = mm("qhd,khd->hqk", q, k, prec, b_is_weight=False) / math.sqrt(D)
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = mm("hqk,khd->qhd", p, v, prec, b_is_weight=False).reshape(T, H * D)
    return mm("te,ed->td", o, lw["wo"], prec)


def expert(ew, h, mc, prec):
    """One expert's FFN over h: (T, d); ``ew`` holds its matrices."""
    up = mm("td,df->tf", h, ew["w_up"], prec)
    if mc["hidden_act"] == "silu":
        a = jax.nn.silu(mm("td,df->tf", h, ew["w_gate"], prec)) * up
    else:
        a = jax.nn.gelu(up, approximate=True)
    return mm("tf,fd->td", a, ew["w_down"], prec)


def route(lw, h, mc, capacity_factor=None):
    """Each expert's buffer: ``(idx, gate)`` of shape (E, G, C), the
    token in each slot (T where empty) and its combine weight, for the
    top-k softmax choices of every token (gates not renormalised).
    Without a capacity factor every choice is kept (C = tokens)."""
    T = h.shape[0]
    E, K = mc["num_experts"], mc["num_experts_per_tok"]
    probs = jax.nn.softmax(jnp.einsum("td,de->te", h, lw["router"],
                                      precision=HI), axis=-1)
    gate, choice = jax.lax.top_k(probs, K)                          # (T, K)
    if capacity_factor is None:
        G, cap = 1, T
    else:
        G = _groups(T, mc["group_size"])
        cap = max(int(K * (T // G) / E * capacity_factor), 1)
    per = T // G
    onehot = jax.nn.one_hot(choice, E, dtype=jnp.int32)             # (T, K, E)
    # position of each choice in its expert's buffer, per group, in
    # (choice rank, token) order
    oh = onehot.reshape(G, per, K, E).transpose(0, 2, 1, 3).reshape(G, K * per, E)
    pos = (jnp.cumsum(oh, axis=1) - oh).reshape(G, K, per, E)
    slot = jnp.sum(pos.transpose(0, 2, 1, 3).reshape(T, K, E) * onehot, -1)
    slot = jnp.where(slot < cap, slot, cap)                         # cap: dropped
    group = jnp.broadcast_to((jnp.arange(T) // per)[:, None], (T, K))
    token = jnp.broadcast_to(jnp.arange(T)[:, None], (T, K))
    idx = jnp.full((E, G, cap), T).at[choice, group, slot].set(token, mode="drop")
    gates = jnp.zeros((E, G, cap)).at[choice, group, slot].set(gate, mode="drop")
    return idx, gates


def _groups(n: int, group_size: int) -> int:
    g = min(max(n // group_size, 1), n)
    while n % g:
        g -= 1
    return g


EXPERT_WEIGHTS = ("w_up", "w_gate", "w_down")


def moe(lw, h, mc, prec, capacity_factor=None):
    """Each expert runs its FFN over the tokens in its buffer; outputs go
    back to their tokens times the combine weight.  One expert at a time
    (recomputed in the backward pass)."""
    T, d = h.shape
    idx, gates = route(lw, h, mc, capacity_factor)
    h_pad = jnp.concatenate([h, jnp.zeros((1, d), h.dtype)])
    ews = {k: lw[k] for k in EXPERT_WEIGHTS if k in lw}

    @jax.checkpoint
    def one(y, xs):
        ew, i, g = xs
        out = expert(ew, h_pad[i.reshape(-1)], mc, prec)
        return y.at[i.reshape(-1)].add(g.reshape(-1, 1) * out), None

    y, _ = jax.lax.scan(one, jnp.zeros((T + 1, d), h.dtype), (ews, idx, gates))
    return y[:T]


LAYER_WEIGHTS = ("ln1_scale", "ln1_bias", "ln2_scale", "ln2_bias", "wq", "wk",
                 "wv", "wo", "q_norm", "k_norm", "router") + EXPERT_WEIGHTS


def hidden(w, mc, tokens, prefix=None, prec="f32", capacity_factor=None):
    """Final hidden states (B, T, d) of a batch of sequences (B, S);
    ``prefix`` (B, P, d) are image features placed before the text.
    Attention runs per sequence, expert routing over the whole batch;
    layers run one after another over the stacked weights."""
    x = w["embed"][tokens]
    if prefix is not None:
        x = jnp.concatenate([prefix, x], axis=1)
    B, T, d = x.shape
    if mc["position_embedding"] == "learned":
        x = x + w["pos_embed"][:T]
    ln = mc["norm"] == "layernorm"

    def layer(x, lw):
        h = norm(x, mc, lw["ln1_scale"], lw.get("ln1_bias") if ln else None)
        x = x + jax.vmap(lambda s: attention(lw, s, mc, prec))(h)
        h = norm(x, mc, lw["ln2_scale"], lw.get("ln2_bias") if ln else None)
        y = moe(lw, h.reshape(B * T, d), mc, prec, capacity_factor)
        return x + y.reshape(B, T, d), None

    stacked = {k: w[k] for k in LAYER_WEIGHTS if k in w}
    x, _ = jax.lax.scan(layer, x, stacked)
    return norm(x, mc, w["final_scale"], w["final_bias"] if ln else None)


def _table(w, mc):
    return w["unembed" if "unembed" in w else "embed"][:mc["vocab_size"]]


def logits(w, mc, tokens, prec="f32"):
    """(T, vocab_size) next-token logits of one text sequence (T,)."""
    with jax.default_matmul_precision("highest"):
        x = hidden(w, mc, tokens[None], prec=prec)[0]
        return mm("td,vd->tv", x, _table(w, mc), prec)


def loss(w, mc, batch, prec="f32"):
    """Mean next-token cross entropy over every text position of the
    batch, image features before the text where the batch has them,
    experts at the configuration's capacity factor."""
    with jax.default_matmul_precision("highest"):
        prefix = batch.get("patch_embeds")
        x = hidden(w, mc, batch["tokens"], prefix, prec,
                   mc.get("capacity_factor"))
        P = 0 if prefix is None else prefix.shape[1]
        lg = mm("btd,vd->btv", x[:, P:], _table(w, mc), prec)
        lse = jax.nn.logsumexp(lg, axis=-1)
        gold = jnp.take_along_axis(lg, batch["labels"][..., None], -1)[..., 0]
        return jnp.mean(lse - gold)
