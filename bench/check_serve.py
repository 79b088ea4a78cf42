"""What decides ``correct`` in a serving cell.

After the window, a sample of finished requests (the longest one and
others drawn from the seed) is run through the float32 reference, once
over each prompt followed by its served tokens.  Per served token, the
gap by which its reference logit lies below the reference's best logit
at that position: 0 for a token the reference would have chosen, small
where two candidates are within rounding of each other.  Decoding is
greedy, so every served token is the program's own first choice.  Two
numbers come of it, the widest gap and the mean gap over the sample;
a cell's limits file (``bench/limits/<cell>.json``) names the ones it
holds to a limit.

The control puts the reference itself in the program's place at float8
e4m3, the precision below the configuration's bfloat16: at each
position of the same sequences, the gap of the token the float8
reference ranks first.
"""
from __future__ import annotations

import importlib

import numpy as np


def _reference(mc: dict):
    return importlib.import_module(f"bench.reference.{mc['reference']}")


def gap_fns(mc: dict):
    """Jitted (served_gaps, control_gaps) over one padded sequence."""
    import jax
    import jax.numpy as jnp

    ref = _reference(mc)

    def gaps_of(lg, targets):
        best = jnp.max(lg, axis=-1)
        return best - jnp.take_along_axis(lg, targets[:, None], -1)[:, 0]

    def served(w, tokens, targets):
        return gaps_of(ref.logits(w, mc, tokens), targets)

    def control(w, tokens):
        low = ref.logits(w, mc, tokens, prec="fp8")
        return gaps_of(ref.logits(w, mc, tokens),
                       jnp.argmax(low, axis=-1).astype(jnp.int32))

    return jax.jit(served), jax.jit(control)


def _padded(prompt, generated, length: int):
    seq = np.concatenate([prompt, np.asarray(generated[:-1], np.int32)])
    if seq.size > length:
        raise SystemExit(f"bench: a served sequence of {seq.size} tokens "
                         f"exceeds the check's {length}")
    targets = np.concatenate([prompt[1:], np.asarray(generated, np.int32)])
    tok = np.zeros(length, np.int32)
    tgt = np.zeros(length, np.int32)
    tok[:seq.size] = seq
    tgt[:targets.size] = targets
    lo, hi = prompt.size - 1, seq.size      # positions of served tokens
    return tok, tgt, lo, hi


def readings(mc: dict, w, sample, length: int, with_control: bool = False):
    """{"logit_gap": widest served-token gap, "served_tokens": n,
    "short_requests": requests missing tokens} and with ``with_control``
    the control's widest gap over the same positions."""
    served_fn, control_fn = gap_fns(mc)
    gaps, ctrl, short = [], [], 0
    for prompt, generated, max_new in sample:
        short += len(generated) != max_new
        tok, tgt, lo, hi = _padded(prompt, generated, length)
        gaps.append(np.asarray(served_fn(w, tok, tgt))[lo:hi])
        if with_control:
            ctrl.append(np.asarray(control_fn(w, tok))[lo:hi])
    gaps = np.concatenate(gaps) if gaps else np.zeros(1)
    out = {"logit_gap": float(gaps.max()), "mean_logit_gap": float(gaps.mean()),
           "served_tokens": int(gaps.size), "short_requests": short}
    if with_control:
        ctrl = np.concatenate(ctrl)
        out["control_gap"] = float(ctrl.max())
        out["control_mean_gap"] = float(ctrl.mean())
    return out
