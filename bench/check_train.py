"""What decides ``correct`` in a training cell.

The float32 reference (``bench/reference``) takes the three steps the
program took in set-up, from the same weights on the same batches:
loss and gradient of the reference forward, clipping to the global norm
the configuration states, and Adafactor (Shazeer & Stern 2018) as the
configuration states it, written here from the paper.  Three numbers
are compared, each by the worst case:

* ``loss_gap``: the largest |program − reference| of the three losses;
* ``grad_norm_gap``: over the leaves, the largest gap between the
  program's and the reference's norm of the first (clipped) gradient,
  over the larger of the reference's norm of that leaf and of the
  median leaf (some gradients are all but zero);
* ``update_norm_gap``: the same for each leaf's change over the three
  steps, leaving out leaves whose reference gradient is under a
  thousandth of the median leaf's (they move under Adafactor by
  round-off alone).
"""
from __future__ import annotations

import importlib

import numpy as np

from bench import arch

IGNORE_BELOW = 1e-3


def _adafactor(mc: dict):
    """init, update of Adafactor with the configuration's settings:
    decay 1 − t^−0.8, update clipped at RMS 1, relative step scaled by
    max(RMS(p), 1e-3), factored second moments for matrices (the last
    two axes), linear warm-up to the learning rate."""
    import jax.numpy as jnp

    eps1, eps2, clip, decay = 1e-30, 1e-3, 1.0, 0.8
    lr, warm = mc["learning_rate"], mc["warmup_steps"]

    def init(p):
        if p.ndim >= 2:
            return {"vr": jnp.zeros(p.shape[:-1]), "vc": jnp.zeros(
                p.shape[:-2] + p.shape[-1:])}
        return {"v": jnp.zeros(p.shape)}

    def update(p, g, v, t):
        beta2 = 1.0 - t ** -decay
        g2 = g * g + eps1
        if p.ndim >= 2:
            vr = beta2 * v["vr"] + (1 - beta2) * g2.mean(-1)
            vc = beta2 * v["vc"] + (1 - beta2) * g2.mean(-2)
            vhat = vr[..., None] * vc[..., None, :] / jnp.maximum(
                vr.mean(-1)[..., None, None], eps1)
            new = {"vr": vr, "vc": vc}
        else:
            vhat = beta2 * v["v"] + (1 - beta2) * g2
            new = {"v": vhat}
        u = g / jnp.sqrt(vhat + eps1)
        u = u / jnp.maximum(1.0, jnp.sqrt(jnp.mean(u * u) + eps1) / clip)
        scale = lr * jnp.minimum(t / max(warm, 1), 1.0) * jnp.maximum(
            jnp.sqrt(jnp.mean(p * p)), eps2)
        return p - scale * u, new

    return init, update


def reference_readings(mc: dict, w: dict, batches: list, prec: str = "f32"):
    """Losses, first-gradient norms and the weights after three steps of
    the reference, per leaf by the benchmark's weight names.

    The gradient is taken in passes of a few leaves each (the form's
    ``grad_parts``, ``bench/arch``), each pass's part kept on the host,
    so that the chip holds the weights and one part of the gradient at a
    time; the full gradient comes back for the global-norm clipping and
    the update.  ``prec="fp8"`` is the control: the gradient is taken at
    the weights rounded to float8 (the form's ``MATMUL_WEIGHTS``; the
    optimizer keeps float32 weights, as the program does), the float32
    weights waiting on the host meanwhile."""
    import jax
    import jax.numpy as jnp

    ref = importlib.import_module(f"bench.reference.{mc['reference']}")
    form = arch.load(mc)
    inner = "fp8w" if prec == "fp8" else prec
    names = sorted(w)
    parts = form.grad_parts(names)
    grad_fns = {
        part: jax.jit(jax.value_and_grad(
            lambda a, rest, b: ref.loss({**a, **rest}, mc, b, inner)))
        for part in parts}
    rounded = jax.jit(lambda w: {k: ref.fp8(v) if k in form.MATMUL_WEIGHTS else v
                                 for k, v in w.items()})
    init, update = _adafactor(mc)
    step_fn = jax.jit(lambda w, g, v, t: {n: update(w[n], g[n], v[n], t)
                                          for n in names}, donate_argnums=(0, 1))
    norms = jax.jit(lambda t: {n: jnp.linalg.norm(t[n].ravel()) for n in names})
    v = {n: init(w[n]) for n in names}
    losses, grad_norms = [], None
    for i, b in enumerate(batches):
        at = rounded(w) if prec == "fp8" else w
        host_w = None
        if prec == "fp8":
            host_w = jax.device_get(w)
            for x in w.values():        # the caller may still hold them
                x.delete()
            del w
        g = {}
        for part in parts:
            a = {n: at[n] for n in part}
            rest = {n: at[n] for n in names if n not in part}
            loss, gp = grad_fns[part](a, rest, b)
            g.update(jax.device_get(gp))
            del gp
        del at, a, rest
        w = jax.device_put(host_w) if host_w is not None else w
        del host_w
        g = jax.device_put(g)
        total = jnp.sqrt(sum(jnp.sum(x * x) for x in g.values()))
        g = jax.tree_util.tree_map(
            lambda x: x * jnp.minimum(1.0, mc["grad_clip_norm"]
                                      / jnp.maximum(total, 1e-9)), g)
        if i == 0:
            grad_norms = {n: float(x) for n, x in jax.device_get(norms(g)).items()}
        losses.append(float(loss))
        out = step_fn(w, g, v, float(i + 1))
        del g
        w = {n: out[n][0] for n in names}
        v = {n: out[n][1] for n in names}
    return {"losses": losses, "grad_norms": grad_norms, "params": w}


def change_norms(w3: dict, w0: dict) -> dict:
    import jax
    import jax.numpy as jnp

    names = sorted(w3)
    fn = jax.jit(lambda a, b: {n: jnp.linalg.norm((a[n] - b[n]).ravel())
                               for n in names})
    return {n: float(x) for n, x in jax.device_get(fn(w3, w0)).items()}


def _worst(prog: dict, ref: dict, keep):
    """(gap, leaf) of the leaf where the program's norm is farthest from
    the reference's, relative to the larger of that leaf's and the
    median leaf's reference norm."""
    floor = float(np.median([ref[n] for n in ref]))
    return max((abs(prog[n] - ref[n]) / max(ref[n], floor), n)
               for n in ref if keep(n))


def compare(prog: dict, ref: dict, worst: dict = None) -> dict:
    """The three numbers (see the module's docstring); ``ref`` carries
    ``change_norms`` besides the reference's losses and gradient norms.
    ``worst``, where given, receives the leaf behind each norm gap."""
    g = ref["grad_norms"]
    floor = float(np.median(list(g.values())))
    moved = lambda n: g[n] >= IGNORE_BELOW * floor  # noqa: E731
    grad, grad_leaf = _worst(prog["grad_norms"], g, lambda n: True)
    upd, upd_leaf = _worst(prog["change_norms"], ref["change_norms"], moved)
    if worst is not None:
        worst.update(grad_norm_gap=grad_leaf, update_norm_gap=upd_leaf)
    return {
        "loss_gap": max(abs(a - b) for a, b in zip(prog["losses"],
                                                    ref["losses"])),
        "grad_norm_gap": grad,
        "update_norm_gap": upd,
    }
