"""Plain float32 reference of a dense decoder-only language model:
grouped-query attention with rotary positions, RMSNorm, a swiglu FFN in
every layer, the output table its own.

Attention, the norms and the precisions (``prec``) are those of
``bench/reference/moe_lm.py``, imported; only the FFN is written here.
It imports nothing of the program under test and reads weights by the
names of ``arch/dense_lm.py``.
"""
from __future__ import annotations

import jax

from bench.reference.moe_lm import attention, fp8, mm, norm  # noqa: F401

LAYER_WEIGHTS = ("ln1_scale", "ln2_scale", "wq", "wk", "wv", "wo", "w_up",
                 "w_gate", "w_down")


def ffn(lw, h, prec):
    """Wdown · (silu(Wgate h) ⊙ Wup h) over h: (T, d)."""
    a = (jax.nn.silu(mm("td,df->tf", h, lw["w_gate"], prec))
         * mm("td,df->tf", h, lw["w_up"], prec))
    return mm("tf,fd->td", a, lw["w_down"], prec)


def logits(w, mc, tokens, prec="f32"):
    """(T, vocab_size) next-token logits of one text sequence (T,)."""
    with jax.default_matmul_precision("highest"):
        x = w["embed"][tokens]

        def layer(x, lw):
            x = x + attention(lw, norm(x, mc, lw["ln1_scale"]), mc, prec)
            return x + ffn(lw, norm(x, mc, lw["ln2_scale"]), prec), None

        x, _ = jax.lax.scan(layer, x, {k: w[k] for k in LAYER_WEIGHTS})
        x = norm(x, mc, w["final_scale"])
        return mm("td,vd->tv", x, w["unembed"][:mc["vocab_size"]], prec)
