"""Continuous-batching serving engine: mixed prefill/decode steps over a
paged KV cache, with optional speculative decoding.

The static :class:`~repro.serving.engine.ServingEngine` runs one batch
in lockstep: one prompt length, one generation length, the whole batch
finishes together.  This engine instead keeps a fixed pool of
``max_slots`` decode slots full: requests are admitted as slots and KV
blocks free up (admission policy pluggable — see
``repro.serving.scheduler``), prompts are ingested in
``prefill_chunk``-token chunks *interleaved with* one decode step for
every active slot, and finished requests are evicted immediately so
their slot is refilled.

Every engine step is one call of a jit'd function of **static shape**:

    rows = [max_slots decode rows] + [prefill_chunk chunk rows]

Row ``i < max_slots`` is slot ``i``'s decode token (masked when the slot
is idle or mid-prefill); the tail rows carry the current chunk of the
oldest prefilling request (masked when nothing is prefilling — a
decode-only variant with ``rows = max_slots`` also exists, so steady
state does not pay for empty chunk rows).  Each row carries its token
id, slot, absolute position and context length; K/V are projected,
written into the slot's pool blocks, and attention reads back through
the block table (:func:`repro.kernels.decode_attention.paged_decode_attention`)
— writing the chunk's K/V *before* the attention read makes per-row
"attend to my own prefix" exactly causal attention, which is what lets
prefill and decode share one kernel and one compiled step.  Requests
entering/leaving only change *values* (tables, lengths, tokens), never
shapes: no recompilation as traffic churns.

**Speculative decoding** (``ServeConfig.spec``) multiplies decode
throughput by making tokens-per-slot-per-step variable while the step
stays static-shape.  When no request is mid-prefill, the engine runs a
*verify* step instead of a decode step: a drafter
(``repro.serving.speculative``) proposes up to ``gamma`` continuation
tokens per slot, and the step scores ``gamma + 1`` rows per slot —
row ``j`` is exactly a prefill-chunk-style row (token ``j`` of the
draft at absolute position ``c + j``), so the verify variant reuses the
mixed-step machinery unchanged, per-row positions/token ids threading
through :class:`~repro.core.context.MoEContext` exactly as chunk rows
do.  The acceptance rule (``speculative.accept``) emits the accepted
draft prefix plus one bonus token: temperature 0 is token-identical to
non-speculative decoding, temperature > 0 preserves the target
distribution.  (Token-identity assumes batch-composition-invariant
routing — dense FFN or dropless dispatch; a finite ``capacity_factor``
derives per-expert capacity from the row count, which differs between
the decode and verify step shapes, so capacity-limited MoE dispatch can
drop differently across them — the same caveat non-speculative
continuous serving already carries vs the static engine.)  Rejected draft positions are undone by
``PagedKVCache.truncate_slot`` — a pure length rewind through the block
table, over-allocated blocks back on the free list, no copying.  The
compiled-variant census stays tiny: the two existing shapes plus one
verify shape (``rows = max_slots * (gamma + 1)``), still zero
recompiles as traffic churns.

Temperature > 0 sampling uses a **per-row key** folded from the fixed
engine key, the row's slot and its absolute position: samples are
independent across slots and reproducible under slot reuse (a replayed
trace samples identically however admission interleaves).

Recurrent families (xlstm) keep O(1) state keyed by slot: every step is
a decode step of shape ``(max_slots, 1)``; "prefill" feeds prompt tokens
one per step into the slot's state, which is zero-reset at admission.
Speculative mode requires the paged cache (recurrent slot states have
no cheap rollback).  Hybrid zamba (shared-attention cache with a single
batch-wide length scalar) and encdec (per-request encoder memory) are
not supported yet.
"""
from __future__ import annotations

import time
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig, ServeConfig
from repro.core.context import MoEContext
from repro.core.moe import moe_ffn_apply
from repro.distributed.sharding import Rules, shard, use_rules
from repro.kernels.decode_attention import (
    paged_update_attention,
    quantized_paged_update_attention,
    sharded_paged_update_attention,
    sharded_quantized_paged_update_attention,
)
from repro.models import layers as L
from repro.models.attention import (
    _project_qkv,
    mla_absorb_q,
    mla_project,
    mla_scale,
    mla_v_up,
)
from repro.models.registry import get_family
from repro.models.transformer import _is_moe_layer
from repro.nn import ParamSpec
from repro.obs import Observability
from repro.serving.kv_cache import PagedKVCache, ShardedPagedKVCache
from repro.serving.request import Request, RequestState, Status
from repro.serving.scheduler import Scheduler
from repro.serving.speculative.accept import accept_greedy_ids, accept_rejection
from repro.serving.speculative.base import DraftItem

_PAGED_FAMILIES = ("decoder_lm", "vlm", "m6")
_RECURRENT_FAMILIES = ("xlstm",)


# ---------------------------------------------------------------------------
# Paged transformer forward (one mixed prefill/decode/verify step)
# ---------------------------------------------------------------------------

def _paged_block(bp, x, cfg: ModelConfig, *, moe_layer: bool, positions,
                 lengths, row_tables, wb, wo, kp, vp, ctx, mesh=None,
                 ksc=None, vsc=None, policy=None, layer=None):
    """One pre-norm block over the flat row batch ``x: (1, N, d)``.

    K/V for every row are written into the pool at (wb, wo) *before* the
    paged-attention read, so chunk rows see their same-step predecessors
    — exact causal semantics for prefill and decode alike.  Masked rows
    write into the garbage block and read length 0.

    With ``mesh``, the write + attention pair runs under shard_map over
    the data axis: rows are laid out shard-major (each shard's rows
    cover its own slots) and (wb, wo)/row_tables carry shard-local block
    ids into the shard's private pool slice.  This is sequential with —
    never nested inside — the MoE dispatcher's own shard_map.

    With ``layer`` (latent attention), ``kp`` is the layer group's whole
    latent pool and the block writes and reads its layer ``layer``.
    """
    N = x.shape[1]
    h = L.norm_apply(bp["ln_attn"], x, cfg)
    if cfg.mla:
        out, kp = _paged_latent_attention(bp["attn"], h, cfg, positions,
                                          lengths, row_tables, wb, wo, kp,
                                          layer)
    else:
        out, kp, vp, ksc, vsc = _paged_mha(bp["attn"], h, cfg, positions,
                                           lengths, row_tables, wb, wo, kp,
                                           vp, mesh, ksc, vsc, policy)
    attn_out = L.dense_apply(bp["attn"]["wo"], out.reshape(1, N, -1), cfg)
    x = x + attn_out
    x = shard(x, "batch", "seq", "embed")

    h = L.norm_apply(bp["ln_ffn"], x, cfg)
    if moe_layer:
        with jax.named_scope("moe_ffn"):
            ffn_out, aux = moe_ffn_apply(bp["ffn"], h, cfg, ctx=ctx)
        telem = _layer_telemetry(aux, cfg)
    else:
        with jax.named_scope("dense_ffn"):
            ffn_out = L.ffn_apply(bp["ffn"], h, cfg)
        telem = _layer_telemetry(None, cfg)
    x = x + ffn_out
    x = shard(x, "batch", "seq", "embed")
    return x, kp, vp, ksc, vsc, telem


def _paged_latent_attention(ap, h, cfg: ModelConfig, positions, lengths,
                            row_tables, wb, wo, kp, layer):
    """Absorbed latent attention over layer ``layer`` of the layer
    group's latent pool ``kp`` (no V pool): each row's entry [normed
    latent | roped key], padded with the pool's zero lanes, is written
    at (wb, wo), then its absorbed query reads the slot's entries
    through the block table in the one paged op.  Returns (per-head
    values (N, H, v), kp)."""
    q_nope, q_pe, entry = mla_project(ap, h, cfg, positions)
    q = mla_absorb_q(ap, q_nope, q_pe, cfg)[0]               # (N, H, r + rope)
    pad = kp.shape[-1] - q.shape[-1]        # the pool's zero lanes (kv_entry_dim)
    q = jnp.pad(q, ((0, 0), (0, 0), (0, pad)))
    entry = jnp.pad(entry[0], ((0, 0), (0, pad)))
    with jax.named_scope("paged_attention"):
        o_lat, kp, _ = paged_update_attention(
            q, entry[:, None], None, kp, None, wb, wo, row_tables, lengths,
            scale=mla_scale(cfg), v_dim=cfg.kv_lora_rank, layer=layer)
    return mla_v_up(ap, o_lat, cfg), kp


def _paged_mha(ap, h, cfg: ModelConfig, positions, lengths, row_tables, wb,
               wo, kp, vp, mesh, ksc, vsc, policy):
    """Attention with K and V heads: write this step's K/V, read through
    the block tables (quantized and/or under the mesh where asked)."""
    q, k, v = _project_qkv(ap, h, cfg, positions)               # (1, N, H*, D)
    with jax.named_scope("paged_attention"):
        if policy is not None:
            if mesh is None:
                out, kp, vp, ksc, vsc = quantized_paged_update_attention(
                    q[0], k[0], v[0], kp, vp, ksc, vsc, wb, wo, row_tables,
                    lengths, policy=policy)
            else:
                (out, kp, vp, ksc,
                 vsc) = sharded_quantized_paged_update_attention(
                    q[0], k[0], v[0], kp, vp, ksc, vsc, wb, wo, row_tables,
                    lengths, policy=policy, mesh=mesh, axis="data")
        elif mesh is None:
            out, kp, vp = paged_update_attention(
                q[0], k[0], v[0], kp, vp, wb, wo, row_tables, lengths)
        else:
            out, kp, vp = sharded_paged_update_attention(
                q[0], k[0], v[0], kp, vp, wb, wo, row_tables, lengths,
                mesh=mesh, axis="data")
    return out, kp, vp, ksc, vsc


def _layer_telemetry(aux, cfg: ModelConfig) -> dict:
    """Per-layer routing telemetry with a shape uniform across MoE and
    dense layers, so the per-layer stack (scan ys or manual) is a clean
    ``(L, ...)`` pytree.  Dense layers contribute exact zeros.  A layer
    that holds a share of its experts reports its held experts' loads
    and ``held_choices``, the routed choices it computes."""
    held = cfg.moe.held_experts
    if aux is None:
        out = {"expert_tokens": jnp.zeros((cfg.moe.held,), jnp.float32),
               "gate_entropy": jnp.zeros((), jnp.float32),
               "dropped": jnp.zeros((), jnp.float32),
               "routed_choices": jnp.zeros((), jnp.float32)}
        if held:
            out["held_choices"] = jnp.zeros((), jnp.float32)
        return out
    choices = aux["moe_routed_choices"]
    out = {"expert_tokens": aux["moe_expert_tokens"],
           "gate_entropy": aux["moe_gate_entropy"],
           # drop *count* (fraction × denominator): summable across
           # steps, and exactly 0.0 when the fraction is exactly 0.0
           "dropped": aux["moe_dropped_fraction"] * choices,
           "routed_choices": choices}
    if held:
        out["held_choices"] = aux["moe_held_choices"]
    return out


def _paged_logits(params, cfg: ModelConfig, tokens, ctx_ids, positions,
                  lengths, row_tables, wb, wo, k_pools, v_pools, mesh=None,
                  k_scales=None, v_scales=None, policy=None):
    """Flat-row forward: embed -> blocks (scan or unrolled) -> logits.

    Returns (float32 logits (N, V), new k_pools, new v_pools, new
    k_scales, new v_scales, telem) — ``telem`` is the per-layer routing
    telemetry stack ({} for dense models; see ``_layer_telemetry``).
    Shared by the decode/mixed step (which samples on top) and the
    speculative verify step (which ships the logits to the host
    acceptance rule).  ``policy`` (a quantized
    :class:`repro.quant.KVQuantPolicy`) switches the K/V write +
    attention to the quantized ops, with the (L, P, Hkv) scale pools
    threading alongside the code pools; None keeps the full-precision
    path byte-identical (the scale leaves stay None)."""
    x = L.embedding_apply(params["embed"], tokens[None], cfg)   # (1, N, d)
    pos2 = positions[None]
    if cfg.pos_embed == "learned":
        x = x + params["pos_embed"].astype(x.dtype)[positions][None]
    ctx = MoEContext(is_training=False).replace(token_ids=ctx_ids[None],
                                                positions=pos2)
    x = shard(x, "batch", "seq", "embed")

    blocks = params["blocks"]
    quantized = policy is not None
    if cfg.first_dense_layers:
        # layer groups (a leading dense group, then the MoE group), each
        # scanned over its stacked weights with its latent pool carried
        # whole: a layer writes and reads its own layer of the pool in
        # place, so the step copies no pool
        k_pools, telems = list(k_pools), []
        for g, (key, n) in enumerate(cfg.layer_groups):
            def body(carry, scanned, moe_layer=key == "blocks"):
                h, kp = carry
                bp, i = scanned
                h, kp, _, _, _, tl = _paged_block(
                    bp, h, cfg, moe_layer=moe_layer, positions=pos2,
                    lengths=lengths, row_tables=row_tables, wb=wb, wo=wo,
                    kp=kp, vp=None, ctx=ctx, mesh=mesh, layer=i)
                return (h, kp), tl

            (x, k_pools[g]), tl = jax.lax.scan(
                body, (x, k_pools[g]), (params[key], jnp.arange(n)))
            telems.append(tl)
        k_pools = tuple(k_pools)
        telem = {k: jnp.concatenate([t[k] for t in telems])
                 for k in telems[0]}
    elif isinstance(blocks, (list, tuple)):     # unrolled (mixed layer kinds)
        ks, vs, kss, vss, telems = [], [], [], [], []
        for i, bp in enumerate(blocks):
            x, kp, vp, ksc, vsc, tl = _paged_block(
                bp, x, cfg, moe_layer=_is_moe_layer(cfg, i), positions=pos2,
                lengths=lengths, row_tables=row_tables, wb=wb, wo=wo,
                kp=k_pools[i], vp=v_pools[i], ctx=ctx, mesh=mesh,
                ksc=k_scales[i] if quantized else None,
                vsc=v_scales[i] if quantized else None, policy=policy)
            ks.append(kp)
            vs.append(vp)
            kss.append(ksc)
            vss.append(vsc)
            telems.append(tl)
        k_pools, v_pools = jnp.stack(ks), jnp.stack(vs)
        if quantized:
            k_scales, v_scales = jnp.stack(kss), jnp.stack(vss)
        telem = {k: jnp.stack([t[k] for t in telems]) for k in telems[0]}
    elif quantized:
        moe_layer = _is_moe_layer(cfg, 0)

        def qbody(h, scanned):
            bp, kp, vp, ksc, vsc = scanned
            h, kp, vp, ksc, vsc, tl = _paged_block(
                bp, h, cfg, moe_layer=moe_layer, positions=pos2,
                lengths=lengths, row_tables=row_tables, wb=wb, wo=wo,
                kp=kp, vp=vp, ctx=ctx, mesh=mesh, ksc=ksc, vsc=vsc,
                policy=policy)
            return h, (kp, vp, ksc, vsc, tl)

        x, (k_pools, v_pools, k_scales, v_scales, telem) = jax.lax.scan(
            qbody, x, (blocks, k_pools, v_pools, k_scales, v_scales))
    else:
        moe_layer = _is_moe_layer(cfg, 0)

        def body(h, scanned):
            bp, kp, vp = scanned
            h, kp, vp, _, _, tl = _paged_block(
                bp, h, cfg, moe_layer=moe_layer, positions=pos2,
                lengths=lengths, row_tables=row_tables, wb=wb, wo=wo,
                kp=kp, vp=vp, ctx=ctx, mesh=mesh)
            return h, (kp, vp, tl)

        x, (k_pools, v_pools, telem) = jax.lax.scan(
            body, x, (blocks, k_pools, v_pools))
    if cfg.moe.num_experts == 0:
        telem = {}      # dense model: nothing to report, nothing to ship

    x = L.norm_apply(params["final_norm"], x, cfg)
    unembed = params.get("unembed", params["embed"])
    logits = L.unembed_apply(unembed, x, cfg)[0].astype(jnp.float32)  # (N, V)
    return logits, k_pools, v_pools, k_scales, v_scales, telem


def _row_buffers(N: int, blocks_per_slot: int, garbage_block: int):
    """Host-side flat-row operands for one step, every row masked: token 0,
    no identity, length 0, writes into the garbage block."""
    return dict(
        tokens=np.zeros(N, np.int32),
        ctx_ids=np.full(N, -1, np.int32),
        positions=np.zeros(N, np.int32),
        lengths=np.zeros(N, np.int32),
        slots=np.zeros(N, np.int32),
        wb=np.full(N, garbage_block, np.int32),
        wo=np.zeros(N, np.int32),
        row_tables=np.full((N, blocks_per_slot), garbage_block, np.int32),
    )


def _fill_row(b, cache, r: int, slot: int, token: int, pos: int) -> None:
    """One live row: ``token`` of ``slot`` at absolute position ``pos``
    (decode, prefill-chunk and verify rows all have this shape)."""
    b["tokens"][r] = b["ctx_ids"][r] = token
    b["positions"][r] = pos
    b["lengths"][r] = pos + 1
    b["slots"][r] = slot
    b["wb"][r], b["wo"][r] = cache.write_coords(slot, pos)
    b["row_tables"][r] = cache.row_table(slot)


def _sample_rows(logits, slots, positions, *, temperature: float, key):
    """Greedy argmax, or per-row categorical with a key folded from
    (engine key, slot, absolute position): independent across slots,
    reproducible under slot reuse."""
    if temperature <= 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)

    def one(lg, s, p):
        k = jax.random.fold_in(jax.random.fold_in(key, s), p)
        return jax.random.categorical(k, lg / temperature)

    return jax.vmap(one)(logits, slots, positions).astype(jnp.int32)


# ---------------------------------------------------------------------------
# Serving weights
# ---------------------------------------------------------------------------

def _stored_in_param_dtype(specs_fn, cfg: ModelConfig):
    """Bool tree over the family's parameters: True where the spec's dtype
    follows ``cfg.param_dtype`` (kernels, expert stacks, embedding tables,
    learned positions), False where it is fixed (float32 norms, biases,
    routers).  Read off the specs at two probe dtypes, where exactly those
    leaves differ — with ``param_dtype`` float32 the dtypes alone cannot
    tell a kernel from a norm scale."""
    lo = specs_fn(cfg.replace(param_dtype="bfloat16"))
    hi = specs_fn(cfg.replace(param_dtype="float32"))
    return jax.tree_util.tree_map(lambda a, b: a.dtype != b.dtype, lo, hi,
                                  is_leaf=lambda s: isinstance(s, ParamSpec))


def serving_params(specs_fn, cfg: ModelConfig, params):
    """``params`` with every ``param_dtype`` leaf cast to ``cfg.dtype``, in
    one jitted call on the device, each leaf keeping its sharding; every
    other leaf is passed through.  The layers cast exactly these leaves
    at use, so the step computes with the same values and its casts are
    no-ops that XLA drops.  ``param_dtype == dtype`` returns ``params``."""
    dt = jnp.dtype(cfg.dtype)
    if jnp.dtype(cfg.param_dtype) == dt:
        return params
    leaves, treedef = jax.tree_util.tree_flatten(params)
    flags = treedef.flatten_up_to(_stored_in_param_dtype(specs_fn, cfg))
    idx = [i for i, f in enumerate(flags) if f]
    src = [leaves[i] for i in idx]
    # an elementwise cast keeps each input's sharding, and its
    # commitment: explicit out_shardings would commit an uncommitted tree,
    # and the step would compile again once its pools come back committed
    cast = jax.jit(lambda xs: [x.astype(dt) for x in xs])(src)
    for i, x in zip(idx, cast):
        leaves[i] = x
    return jax.tree_util.tree_unflatten(treedef, leaves)


# ---------------------------------------------------------------------------
# The engine
# ---------------------------------------------------------------------------

class ContinuousEngine:
    """Continuous-batching engine over a fixed slot pool.

    ``temperature`` is engine-level (0 = greedy, matching the static
    engine's sampling math token for token).  ``serve.spec`` switches on
    speculative decoding; ``draft_model=(cfg, params)`` optionally hands
    the ``model`` drafter an explicit draft model.  Drive it either with
    :meth:`run` (trace of :class:`Request`, virtual clock, per-request
    latencies) or the batch-parity convenience :meth:`generate`.
    ``check_invariants=True`` re-asserts slot/block/reservation
    conservation after every step (tests, benchmarks, paranoid prod).
    """

    def __init__(self, cfg: ModelConfig, params, serve: ServeConfig = ServeConfig(),
                 *, temperature: float = 0.0, seed: int = 0,
                 rules: Optional[Rules] = None,
                 draft_model: Optional[Tuple] = None,
                 check_invariants: bool = False,
                 obs: Optional[Observability] = None,
                 logit_tap: Optional[Callable] = None):
        if cfg.family in _PAGED_FAMILIES:
            self.mode = "paged"
            if cfg.attn_logit_softcap > 0:
                raise NotImplementedError(
                    "paged decode attention does not implement logit softcap")
            if cfg.moe.moe_attention:
                raise NotImplementedError(
                    "moe_attention has no cached decode path")
            if cfg.first_dense_layers and not cfg.mla:
                raise NotImplementedError(
                    "layer groups are served with latent attention only")
            if cfg.mla or cfg.first_dense_layers:
                # the latent pool (and a pool per layer group) is read and
                # written only by the plain paged step
                for on, what in (
                        (serve.prefix_cache, "prefix caching"),
                        (serve.kv_quant != "none", "a quantized KV cache"),
                        (serve.spec is not None, "speculative decoding"),
                        (serve.mesh is not None, "a serving mesh"),
                        (serve.slo is not None and serve.slo.preemption,
                         "preemption with KV swap")):
                    if on:
                        raise NotImplementedError(
                            f"{what} is not supported with latent attention "
                            f"or layer groups: their pools take the plain "
                            f"paged step only")
        elif cfg.family in _RECURRENT_FAMILIES:
            self.mode = "recurrent"
        else:
            raise NotImplementedError(
                f"continuous batching not implemented for family "
                f"{cfg.family!r} (zamba's shared-attention cache keeps one "
                f"batch-wide length; encdec needs per-request encoder memory)")
        self.cfg = cfg
        self.fam = get_family(cfg)
        # paged steps read the weights already in the compute dtype: cast
        # once here, not on every step (recurrent mode keeps the caller's)
        self.params = (serving_params(self.fam.specs, cfg, params)
                       if self.mode == "paged" else params)
        self.serve = serve
        self.temperature = float(temperature)
        self.rules = rules
        self.seed = seed
        self._key = jax.random.PRNGKey(seed)   # fixed base key; per-row folds
        self.steps = 0
        self.check_invariants = check_invariants
        # debug spy on the per-step logits matrix (paged mode): called
        # via jax.debug.callback with (logits, slots, positions, lengths)
        # host arrays each engine step (length 0 marks a padding row) —
        # reads, never steers (the benchmark quant sweep uses it to
        # measure logit divergence across kv_quant policies)
        self._logit_tap = logit_tap
        self.obs = obs if obs is not None else Observability()
        held: Dict[str, int] = {}           # weight bytes by dtype
        for x in jax.tree_util.tree_leaves(self.params):
            held[x.dtype.name] = held.get(x.dtype.name, 0) + x.nbytes
        for dtype, nbytes in held.items():
            self.obs.metrics.gauge("engine_weight_bytes", dtype=dtype).set(nbytes)
        self._moe_acc = None        # device-side telemetry accumulator
        self._moe_rows = 0          # host row count backing the entropy mean
        self._seen_variants = 0     # compiled-variant census (recompile det.)

        self.mesh = None
        self.data_shards = serve.data_shards
        if serve.mesh is not None:
            if self.mode != "paged":
                raise NotImplementedError(
                    "mesh serving needs the paged KV cache (recurrent slot "
                    "states have no block partition)")
            if serve.spec is not None:
                raise NotImplementedError(
                    "speculative decoding is not supported with "
                    "ServeConfig.mesh yet (the verify row layout is not "
                    "shard-major)")
            if serve.slo is not None:
                raise NotImplementedError(
                    "SLO scheduling is not supported with ServeConfig.mesh "
                    "yet (KV swap-to-host assumes a single device pool)")
            from repro.launch.mesh import make_serve_mesh

            self.mesh = make_serve_mesh(serve.mesh)
            if rules is None:
                from repro.distributed.sharding import make_rules

                # data axis carries slots/groups, expert axis the FFN
                # experts — exactly what the ragged EP dispatch wants
                rules = make_rules(cfg, self.mesh, expert_axis="expert")
                self.rules = rules

        self.spec = serve.spec
        self.drafter = None
        if self.spec is not None:
            if self.mode != "paged":
                raise NotImplementedError(
                    "speculative decoding needs the paged KV cache "
                    "(recurrent slot states have no cheap rollback)")
            from repro.serving.speculative import make_drafter

            self.drafter = make_drafter(self.spec, cfg, serve, seed=seed,
                                        draft_model=draft_model)

        if serve.prefix_cache and self.mode != "paged":
            raise NotImplementedError(
                "prefix caching needs the paged KV cache (recurrent slot "
                "states are not content-addressable blocks)")
        if serve.kv_quant != "none" and self.mode != "paged":
            raise NotImplementedError(
                "KV quantization needs the paged KV cache (recurrent slot "
                "states are not block pools)")
        if (serve.slo is not None and serve.slo.preemption
                and self.mode != "paged"):
            raise NotImplementedError(
                "preemption needs the paged KV cache (recurrent slot states "
                "have no block-level swap); use SLOConfig(preemption=False) "
                "for priority/deadline ordering alone")

        if self.mode == "paged":
            from repro.serving.kv_cache import make_kv_cache

            self.cache: Optional[PagedKVCache] = make_kv_cache(cfg, serve)
            self.scheduler = Scheduler(serve.max_slots, serve.max_len,
                                       self.cache, policy=serve.sched_policy,
                                       slo=serve.slo, obs=self.obs)
            temp = self.temperature
            mesh = self.mesh
            # The quantized policy rides in the step closures (jit
            # static); None keeps the full-precision path bit-identical
            # — the scale args are then None pytree leaves, which add
            # nothing to the traced computation.
            if serve.kv_quant != "none":
                from repro.quant import get_kv_quant

                kv_policy = get_kv_quant(serve.kv_quant)
            else:
                kv_policy = None
            self._kv_policy = kv_policy
            tap = logit_tap

            def step_fn(p, k_pools, v_pools, tokens, ctx_ids, positions,
                        lengths, row_tables, wb, wo, slots, key,
                        k_scales=None, v_scales=None):
                with use_rules(rules):
                    (logits, k_pools, v_pools, k_scales, v_scales,
                     telem) = _paged_logits(
                        p, cfg, tokens, ctx_ids, positions, lengths,
                        row_tables, wb, wo, k_pools, v_pools, mesh=mesh,
                        k_scales=k_scales, v_scales=v_scales,
                        policy=kv_policy)
                    if tap is not None:
                        jax.debug.callback(tap, logits, slots, positions,
                                           lengths)
                    tok = _sample_rows(logits, slots, positions,
                                       temperature=temp, key=key)
                return tok, k_pools, v_pools, k_scales, v_scales, telem

            # Static shapes only: N = max_slots (decode-only),
            # N = max_slots + data_shards * prefill_chunk (mixed), and —
            # speculative — N = max_slots * (gamma + 1) (verify); jit
            # caches each once.  The scale pools are donated alongside
            # the code pools when quantized (args 12, 13).
            donate = (1, 2, 12, 13) if kv_policy is not None else (1, 2)
            self._step_fn_raw = step_fn    # structural tests trace this
            self._step_fn = jax.jit(step_fn, donate_argnums=donate)

            def verify_fn(p, k_pools, v_pools, tokens, ctx_ids, positions,
                          lengths, row_tables, wb, wo,
                          k_scales=None, v_scales=None):
                with use_rules(rules):
                    (logits, k_pools, v_pools, k_scales, v_scales,
                     telem) = _paged_logits(
                        p, cfg, tokens, ctx_ids, positions, lengths,
                        row_tables, wb, wo, k_pools, v_pools,
                        k_scales=k_scales, v_scales=v_scales,
                        policy=kv_policy)
                # greedy acceptance only compares token ids: ship N int32
                # argmaxes, not the (N, V) logits matrix, to the host
                if temp <= 0.0:
                    return (jnp.argmax(logits, axis=-1).astype(jnp.int32),
                            k_pools, v_pools, k_scales, v_scales, telem)
                return logits, k_pools, v_pools, k_scales, v_scales, telem

            vdonate = (1, 2, 10, 11) if kv_policy is not None else (1, 2)
            self._verify_fn = jax.jit(verify_fn, donate_argnums=vdonate)
            # the documented compiled census: {mixed, decode-only} for
            # the step fn, plus the verify shape when speculating —
            # anything beyond this is a recompile worth flagging
            self._expected_variants = 3 if self.spec is not None else 2
        else:
            self.cache = None
            self.scheduler = Scheduler(serve.max_slots, serve.max_len, None,
                                       policy=serve.sched_policy,
                                       slo=serve.slo, obs=self.obs)
            self._expected_variants = 1         # one (max_slots, 1) shape
            self._state = self.fam.init_state(cfg, serve.max_slots, serve.max_len)
            temp = self.temperature
            serve_ctx = MoEContext(is_training=False)
            fam = self.fam
            S = serve.max_slots

            def rec_step(p, state, tokens, positions, key):
                with use_rules(rules):
                    logits, new_state = fam.decode(p, tokens, state, cfg,
                                                   ctx=serve_ctx)
                lg = logits[:, -1, :].astype(jnp.float32)
                tok = _sample_rows(lg, jnp.arange(S), positions,
                                   temperature=temp, key=key)
                return tok, new_state

            def reset_slot(state, slot):
                return jax.tree_util.tree_map(
                    lambda a: a.at[slot].set(jnp.zeros_like(a[slot])), state)

            self._step_fn = jax.jit(rec_step, donate_argnums=(1,))
            self._reset_fn = jax.jit(reset_slot, donate_argnums=(0,))

    def lower_step(self):
        """Lower the paged step at its decode-only row shape over the
        engine's current params and pools — the program :meth:`step`
        runs, for reading its compiled text."""
        serve, cache = self.serve, self.cache
        b = _row_buffers(serve.max_slots, serve.blocks_per_slot,
                         cache.garbage_block)
        return self._step_fn.lower(
            self.params, cache.k_pool, cache.v_pool, b["tokens"],
            b["ctx_ids"], b["positions"], b["lengths"], b["row_tables"],
            b["wb"], b["wo"], b["slots"], self._key, cache.k_scales,
            cache.v_scales)

    # -- observability ------------------------------------------------------

    @property
    def spec_stats(self) -> Dict[str, int]:
        """Legacy dict view over the speculative-decoding counters."""
        m = self.obs.metrics
        return {"verify_steps": int(m.get("spec_verify_steps_total")),
                "proposed": int(m.get("spec_proposed_total")),
                "accepted": int(m.get("spec_accepted_total")),
                "emitted": int(m.get("spec_emitted_total"))}

    def compiled_variants(self) -> int:
        """Jit-cache entry count for the engine's step functions — the
        compiled-shape census the recompile detector watches."""
        n = 0
        for fn in (getattr(self, "_step_fn", None),
                   getattr(self, "_verify_fn", None)
                   if self.spec is not None else None):
            size = getattr(fn, "_cache_size", None)
            if size is not None:
                n += int(size())
        return n

    def _obs_step(self, kind: str, live_rows: int, total_rows: int) -> None:
        """Per-step registry publication: step/row counters, queue and
        pool gauges, prefix-cache counter mirror, recompile check."""
        m = self.obs.metrics
        sched = self.scheduler
        m.counter("engine_steps_total", kind=kind).inc()
        m.counter("engine_rows_total", state="live").inc(live_rows)
        m.counter("engine_rows_total",
                  state="padded").inc(total_rows - live_rows)
        m.gauge("queue_depth").set(len(sched.waiting))
        m.gauge("running_slots").set(len(sched.running))
        m.gauge("serve_peak_running").set_max(len(sched.running))
        if self.cache is not None:
            for d, occ in enumerate(self.cache.occupancy()):
                for state in ("free", "live", "cached"):
                    m.gauge("kv_blocks", state=state, shard=d).set(occ[state])
                m.gauge("kv_reserved_blocks", shard=d).set(occ["reserved"])
                # device footprint of the shard's whole pool (every
                # block row incl. the garbage block, at the per-block
                # byte cost — int8 + scales when quantized)
                rows = occ["free"] + occ["live"] + occ["cached"] + 1
                m.gauge("kv_pool_bytes", shard=d).set(
                    rows * occ["block_bytes"])
            if self.serve.prefix_cache:
                for k, v in self.cache.stats.items():
                    m.counter(f"prefix_{k}_total").set_to(v)
        n = self.compiled_variants()
        if n != self._seen_variants:
            m.gauge("engine_compiled_variants").set(n)
            if n > self._expected_variants:
                m.counter("engine_recompiles_total").inc(
                    n - max(self._seen_variants, self._expected_variants))
                self.obs.tracer.instant("recompile", variants=n,
                                        expected=self._expected_variants)
            self._seen_variants = n
        self.obs.maybe_metrics_row(self.steps)

    # -- MoE routing telemetry ----------------------------------------------
    # Device-side accumulation (four tiny adds per step, no sync); the
    # host pull happens once per run() — or at a metrics-JSONL flush —
    # via _moe_pull().

    def _moe_reset(self) -> None:
        self._moe_acc = None
        self._moe_rows = 0

    def _moe_accum(self, telem, rows: int) -> None:
        if not telem:
            return
        add = {"expert_tokens": telem["expert_tokens"],          # (L, E)
               "gate_entropy": telem["gate_entropy"] * float(rows),  # (L,)
               "dropped": telem["dropped"],                      # (L,)
               "routed_choices": telem["routed_choices"]}        # (L,)
        if "held_choices" in telem:                              # (L,)
            add["held_choices"] = telem["held_choices"]
        if self._moe_acc is None:
            self._moe_acc = add
        else:
            self._moe_acc = jax.tree_util.tree_map(
                jnp.add, self._moe_acc, add)
        self._moe_rows += rows

    def _moe_pull(self) -> Dict[str, float]:
        """Host pull of the accumulated routing telemetry: publish the
        per-layer gauges and return the run-level scalar stats."""
        if self._moe_acc is None:
            return {}
        from repro.core.metrics import load_entropy

        acc = jax.device_get(self._moe_acc)
        tok = np.asarray(acc["expert_tokens"], np.float64)      # (L, E)
        ent = np.asarray(acc["gate_entropy"], np.float64)       # (L,)
        drop = np.asarray(acc["dropped"], np.float64)           # (L,)
        choices = np.asarray(acc["routed_choices"], np.float64)  # (L,)
        rows = max(self._moe_rows, 1)
        m = self.obs.metrics
        for layer in range(tok.shape[0]):
            if choices[layer] <= 0:
                continue                    # dense layer (or never ran)
            tot = tok[layer].sum()
            for e in range(tok.shape[1]):
                m.gauge("moe_expert_load_share", layer=layer, expert=e).set(
                    tok[layer, e] / max(tot, 1.0))
            m.gauge("moe_load_entropy", layer=layer).set(
                load_entropy(tok[layer]))
            m.gauge("moe_gate_entropy", layer=layer).set(ent[layer] / rows)
            m.gauge("moe_dropped_fraction", layer=layer).set(
                drop[layer] / choices[layer])
            if "held_choices" in acc:
                m.counter("moe_held_choices_total", layer=layer).set_to(
                    float(acc["held_choices"][layer]))
        total_choices = choices.sum()
        moe_layers = choices > 0
        loads = tok[moe_layers].sum(axis=0)
        mean = loads.mean() if loads.size else 0.0
        stats = {
            # exact 0.0 on dropless paths: drop is a sum of exact zeros
            "moe_dropped_fraction": float(
                drop.sum() / max(total_choices, 1.0)),
            "moe_gate_entropy": float(
                ent[moe_layers].mean() / rows) if moe_layers.any() else 0.0,
            "moe_load_entropy": float(load_entropy(loads)),
            "moe_load_cv": float(loads.std() / (mean + 1e-9)),
        }
        m.gauge("moe_dropped_fraction_overall").set(
            stats["moe_dropped_fraction"])
        return stats

    # -- one engine step ----------------------------------------------------

    def step(self, clock_ms: float = 0.0) -> List[RequestState]:
        """Admit, run one mixed prefill/decode (or speculative verify)
        step, process samples.  Returns the requests that finished.
        With preemption enabled (``serve.slo``), the step first lets the
        scheduler evict lower-priority victims for urgent arrivals that
        could not otherwise be admitted — eviction and re-admission both
        happen here, at step granularity, never mid-forward.

        The whole step is one ``engine_step`` span; the paged steps nest
        the phase spans ``engine_schedule`` → ``engine_rows`` →
        ``engine_launch`` → ``engine_wait`` → ``engine_samples`` →
        ``engine_publish`` inside it (``docs/observability.md``)."""
        tr = self.obs.tracer
        with tr.span("engine_step") as sp:
            with tr.span("engine_schedule"):
                self.scheduler.maybe_preempt(clock_ms)
                # deadline-aware shedding (slo.shed): provably-late
                # requests are finished with Status.SHED at the door,
                # surfaced alongside the step's completions so
                # run()/callers see them resolve
                shed = self.scheduler.shed_unmeetable(clock_ms)
                admitted = self.scheduler.admit(clock_ms)
            if self.mode == "recurrent":
                for st in admitted:
                    self._state = self._reset_fn(self._state,
                                                 jnp.int32(st.slot))
            if not self.scheduler.running:
                sp.args.update(kind="idle", step=self.steps, rows=0,
                               live_rows=0)
                return shed
            if self.mode == "paged":
                # speculate only in decode-only steps: mid-prefill, the
                # mixed step makes prompt progress and decode slots emit
                # one token
                if self.spec is not None and self.scheduler.prefilling is None:
                    finished = self._verify_host_step(clock_ms, sp)
                else:
                    finished = self._paged_host_step(clock_ms, sp)
            else:
                finished = self._recurrent_host_step(clock_ms, sp)
            self.steps += 1
            if self.check_invariants:
                self.scheduler.check_conservation()
        return shed + finished

    def _paged_host_step(self, clock_ms: float, sp) -> List[RequestState]:
        serve, cache, sched = self.serve, self.cache, self.scheduler
        tr = self.obs.tracer
        S = serve.max_slots
        pre = sched.prefilling
        chunk = 0
        stream = target = None
        with tr.span("engine_rows"):
            if pre is not None:
                # the prefill stream is the *confirmed* token sequence,
                # not just the prompt: a restored preempted request
                # re-ingests (or re-bound) prompt + fed-back samples up
                # to the exact position it was evicted at — identical
                # K/V, identical routing, by construction
                stream = pre.confirmed_tokens
                target = pre.prefill_target
                chunk = min(serve.prefill_chunk, target - pre.prefill_pos)
            # Shard-major row layout over the mesh's data axis (D = 1
            # reduces to the original [S decode rows] + [chunk rows]):
            # shard d owns rows [d * per, (d+1) * per) — its own slots'
            # decode rows first, then chunk rows, which live on (and are
            # masked on all but) the shard of the prefilling slot.
            # shard_map then splits the row batch along the data axis
            # with no data movement.
            D = self.data_shards
            spd = S // D
            per = spd + (serve.prefill_chunk if pre is not None else 0)
            N = D * per

            def row_of(slot: int) -> int:
                return (slot // spd) * per + slot % spd

            b = _row_buffers(N, serve.blocks_per_slot, cache.garbage_block)
            sample_rows: List[Tuple[int, RequestState]] = []

            for slot, st in sched.running.items():
                if st.status is not Status.DECODE:
                    continue
                pos = st.context_len
                cache.ensure_capacity(slot, pos + 1)
                _fill_row(b, cache, row_of(slot), slot, st.last_token, pos)
                sample_rows.append((row_of(slot), st))

            if pre is not None:
                cache.ensure_capacity(pre.slot, pre.prefill_pos + chunk)
                base = (pre.slot // spd) * per + spd
                for j in range(chunk):
                    row, p = base + j, pre.prefill_pos + j
                    _fill_row(b, cache, row, pre.slot, stream[p], p)
                    # sample off the last *prompt* row only on first
                    # ingest: a resume past it already holds that sample
                    # in generated
                    if p == pre.request.prompt_len - 1 and not pre.generated:
                        sample_rows.append((row, pre))

            kind = "mixed" if pre is not None else "decode"
            live = len(sample_rows) + (chunk if pre is not None else 0)
            if pre is not None and any(st is pre for _, st in sample_rows):
                live -= 1       # pre's sample row is one of its chunk rows
            sp.args.update(kind=kind, step=self.steps, rows=N,
                           live_rows=live)
            if self.mesh is not None:
                # each shard's slice of the row batch (rows [d*per,
                # (d+1)*per)) with its own live-row census, shard 0 first
                sp.args["live_rows_by_shard"] = " ".join(
                    str(np.count_nonzero(b["lengths"][d * per:(d + 1) * per]))
                    for d in range(D))
        with tr.span("engine_launch"):
            (next_tok, k_pools, v_pools, k_scales, v_scales,
             telem) = self._step_fn(
                self.params, cache.k_pool, cache.v_pool, b["tokens"],
                b["ctx_ids"], b["positions"], b["lengths"], b["row_tables"],
                b["wb"], b["wo"], b["slots"], self._key,
                cache.k_scales, cache.v_scales)
            cache.update_pools(k_pools, v_pools, k_scales, v_scales)
            self._moe_accum(telem, N)
        with tr.span("engine_wait"):
            next_tok = np.asarray(next_tok)
        with tr.span("engine_samples"):
            if pre is not None:
                pre.prefill_pos += chunk
                if pre.prefill_pos == target:
                    pre.status = Status.DECODE
                    self.obs.request_phase(pre.request.uid, "decode",
                                           slot=pre.slot)
            finished = self._collect_samples(next_tok, sample_rows, clock_ms)
            self._commit_running()
        with tr.span("engine_publish"):
            self._obs_step(kind, live, N)
        return finished

    def _commit_running(self) -> None:
        """Prefix caching: confirm every still-running slot's written
        token contents so newly full blocks publish into the index —
        live publication is what lets *concurrent* requests of one
        tenant share blocks, not just later arrivals.  (Slots that just
        finished were committed by ``Scheduler.finish`` before their
        blocks were released.)"""
        if not self.serve.prefix_cache:
            return
        bs, cache = self.cache.block_size, self.cache
        for slot, st in self.scheduler.running.items():
            stream = st.confirmed_tokens
            written = (st.prefill_pos if st.status is Status.PREFILL
                       else stream.size)
            if written // bs > cache.committed_blocks(slot):
                cache.commit(slot, stream[:written])

    # -- speculative verify step --------------------------------------------

    def _host_rng(self, slot: int, position: int) -> np.random.Generator:
        """Deterministic per-(slot, position) generator for host-side
        acceptance sampling — the numpy twin of the on-device per-row
        fold keys."""
        return np.random.default_rng(
            np.random.SeedSequence(entropy=[self.seed, slot, position]))

    def _verify_host_step(self, clock_ms: float, sp) -> List[RequestState]:
        serve, cache, sched = self.serve, self.cache, self.scheduler
        tr = self.obs.tracer
        S, gamma = serve.max_slots, self.spec.gamma
        W = gamma + 1
        N = S * W

        with tr.span("engine_rows"):
            items: List[DraftItem] = []
            for slot, st in sorted(sched.running.items()):
                # remaining >= 1 in DECODE (a drained budget evicts); a
                # draft never needs to run past it, and clamping keeps
                # every draft KV write below total_len — inside the
                # admission reservation
                remaining = st.request.max_new_tokens - len(st.generated)
                context = np.concatenate(
                    [st.request.prompt,
                     np.asarray(st.generated, np.int32)]).astype(np.int32)
                items.append(DraftItem(slot=slot, context=context,
                                       max_tokens=min(gamma, remaining)))
            proposals = self.drafter.propose(items)
            drafts = [np.asarray(d, np.int32).reshape(-1)[:it.max_tokens]
                      for it, d in zip(items, proposals)]
            nothing_drafted = all(d.size == 0 for d in drafts)
            if not nothing_drafted:
                b = _row_buffers(N, serve.blocks_per_slot,
                                 cache.garbage_block)
                per_slot: Dict[int, Tuple[RequestState, np.ndarray, int]] = {}
                for it, d in zip(items, drafts):
                    slot = it.slot
                    st = sched.running[slot]
                    g = int(d.size)
                    c = st.context_len
                    cache.ensure_capacity(slot, c + g + 1)
                    row_toks = [st.last_token, *d.tolist()]
                    for j in range(g + 1):
                        _fill_row(b, cache, slot * W + j, slot, row_toks[j],
                                  c + j)
                    per_slot[slot] = (st, d, c)
                live = sum(int(d.size) + 1 for _, d, _ in per_slot.values())
                sp.args.update(kind="verify", step=self.steps, rows=N,
                               live_rows=live)
        if nothing_drafted:
            # nothing to verify anywhere: an ordinary decode step costs
            # 1/(gamma+1) the rows for the same one token per slot (the
            # decode-only shape is already in the compiled census)
            return self._paged_host_step(clock_ms, sp)

        with tr.span("engine_launch"):
            (scores, k_pools, v_pools, k_scales, v_scales,
             telem) = self._verify_fn(
                self.params, cache.k_pool, cache.v_pool, b["tokens"],
                b["ctx_ids"], b["positions"], b["lengths"], b["row_tables"],
                b["wb"], b["wo"], cache.k_scales, cache.v_scales)
            cache.update_pools(k_pools, v_pools, k_scales, v_scales)
            self._moe_accum(telem, N)
        with tr.span("engine_wait"):
            scores = np.asarray(scores)  # (N,) argmax ids | (N, V) logits

        m = self.obs.metrics
        finished = []
        with tr.span("engine_samples"):
            for slot, (st, d, c) in per_slot.items():
                g = int(d.size)
                rows = scores[slot * W: slot * W + g + 1]
                if self.temperature <= 0.0:
                    emitted, n_acc = accept_greedy_ids(d, rows)
                else:
                    emitted, n_acc = accept_rejection(
                        d, rows, self.temperature,
                        lambda j, slot=slot, c=c: self._host_rng(slot, c + j))
                remaining = st.request.max_new_tokens - len(st.generated)
                emitted = emitted[:remaining]
                eos = st.request.eos_id
                if eos is not None and eos in emitted:
                    emitted = emitted[:emitted.index(eos) + 1]
                assert emitted, "verify step must emit at least the bonus token"
                m.counter("spec_proposed_total").inc(g)
                # accepted = draft tokens actually *used*: the EOS/budget
                # cut can discard accepted drafts, which must not inflate
                # the rate
                m.counter("spec_accepted_total").inc(min(len(emitted), n_acc))
                st.generated.extend(int(t) for t in emitted)
                if st.first_token_ms is None:
                    st.first_token_ms = clock_ms
                m.counter("spec_emitted_total").inc(len(emitted))
                if st.done():
                    self.scheduler.finish(st, clock_ms)
                    finished.append(st)
                else:
                    # rollback: positions [0, c + len(emitted)) stay
                    # written (row j wrote draft token j at position
                    # c + j, which for every kept row IS the fed-back
                    # token); rejected rows beyond rewind, their spill
                    # blocks return to the pool
                    cache.truncate_slot(slot, c + len(emitted))
            self._commit_running()
        with tr.span("engine_publish"):
            m.counter("spec_verify_steps_total").inc()
            self._obs_step("verify", live, N)
        return finished

    def _recurrent_host_step(self, clock_ms: float, sp) -> List[RequestState]:
        S = self.serve.max_slots
        tokens = np.zeros((S, 1), np.int32)
        positions = np.zeros(S, np.int32)
        sample_rows: List[Tuple[int, RequestState]] = []
        prefill_advanced: List[RequestState] = []
        for slot, st in self.scheduler.running.items():
            positions[slot] = st.context_len
            if st.status is Status.PREFILL:
                tokens[slot, 0] = st.request.prompt[st.prefill_pos]
                positions[slot] = st.prefill_pos
                prefill_advanced.append(st)
                if st.prefill_pos + 1 == st.request.prompt_len:
                    sample_rows.append((slot, st))
            else:
                tokens[slot, 0] = st.last_token
                sample_rows.append((slot, st))

        live = len(self.scheduler.running)
        sp.args.update(kind="decode", step=self.steps, rows=S,
                       live_rows=live)
        next_tok, self._state = self._step_fn(self.params, self._state,
                                              tokens, positions, self._key)
        for st in prefill_advanced:
            st.prefill_pos += 1
            if st.prefill_pos == st.request.prompt_len:
                st.status = Status.DECODE
                self.obs.request_phase(st.request.uid, "decode", slot=st.slot)
        self._obs_step("decode", live, S)
        return self._collect_samples(np.asarray(next_tok), sample_rows, clock_ms)

    def _collect_samples(self, next_tok: np.ndarray, sample_rows, clock_ms: float
                         ) -> List[RequestState]:
        finished = []
        for row, st in sample_rows:
            st.generated.append(int(next_tok[row]))
            if st.first_token_ms is None:
                st.first_token_ms = clock_ms
            if st.done():
                self.scheduler.finish(st, clock_ms)
                finished.append(st)
        return finished

    # -- drivers ------------------------------------------------------------

    def run(self, requests: List[Request], *,
            on_finish: Optional[Callable[[RequestState], None]] = None
            ) -> Tuple[Dict[int, List[int]], Dict[str, float]]:
        """Serve a trace to completion.  The clock is wall time since the
        call, fast-forwarded over idle gaps to the next arrival (so a
        sparse trace doesn't busy-wait); request latency = finish - arrival
        on that clock.  Returns ({uid: generated tokens}, stats) —
        every counter-derived stat is a registry delta over this run
        (``repro.obs``), not a hand-kept snapshot."""
        m = self.obs.metrics
        for r in requests:
            self.scheduler.add(r)
        t0 = time.perf_counter()
        mark = m.mark()
        m.gauge("serve_peak_running").set(0.0)
        self._moe_reset()
        sched = self.scheduler
        clock = 0.0
        done: List[RequestState] = []
        while self.scheduler.has_work():
            clock = max(clock, (time.perf_counter() - t0) * 1e3)
            if not self.scheduler.running:
                nxt = self.scheduler.next_arrival_ms()
                if nxt is not None and nxt > clock:
                    clock = nxt                      # idle: jump to next arrival
            finished = self.step(clock)
            # finished requests were still running when the step began;
            # shed requests never ran, so they don't count toward peak
            ran = [st for st in finished if st.status is not Status.SHED]
            m.gauge("serve_peak_running").set_max(
                len(self.scheduler.running) + len(ran))
            for st in finished:
                done.append(st)
                if on_finish is not None:
                    on_finish(st)
        total_ms = max(clock, (time.perf_counter() - t0) * 1e3)
        self.scheduler.check_conservation()

        from repro.serving.trace import latency_stats, slo_class_stats

        # shed requests resolved without serving a token: excluding them
        # from latency/goodput stats keeps "met deadline" meaning "was
        # served by its deadline" (a shed finish beats its deadline on
        # the clock but delivered nothing)
        served = [st for st in done if st.status is not Status.SHED]
        stats = latency_stats([st.latency_ms() for st in served], total_ms,
                              sum(len(st.generated) for st in served))
        stats["steps"] = m.delta(mark, "engine_steps_total")
        stats["peak_running"] = m.get("serve_peak_running")
        # per-class percentiles + goodput: global p50/p95 hide exactly
        # the targeted degradation SLO scheduling is for
        stats.update(slo_class_stats(served))
        if self.serve.slo is not None and self.serve.slo.shed:
            stats["requests_shed"] = m.delta(mark, "requests_shed_total")
        if sched.swap is not None:
            stats["preemptions"] = m.delta(mark, "sched_preemptions_total")
            stats["restore_tokens"] = m.delta(mark,
                                              "sched_restore_tokens_total")
            stats["recompute_tokens"] = m.delta(
                mark, "sched_recompute_tokens_total")
            stats["swapped_blocks"] = m.delta(mark,
                                              "swap_swapped_blocks_total")
            stats["restored_blocks"] = m.delta(mark,
                                               "swap_restored_blocks_total")
        if self.serve.prefix_cache:
            cached = m.delta(mark, "prefix_cached_tokens_total")
            prompt = m.delta(mark, "prefix_prompt_tokens_total")
            stats["cached_tokens"] = cached
            stats["prompt_tokens"] = prompt
            stats["cached_token_ratio"] = cached / max(prompt, 1)
        if self.spec is not None:
            proposed = m.delta(mark, "spec_proposed_total")
            vsteps = m.delta(mark, "spec_verify_steps_total")
            stats["acceptance_rate"] = (
                m.delta(mark, "spec_accepted_total") / max(proposed, 1))
            stats["spec_tokens_per_step"] = (
                m.delta(mark, "spec_emitted_total") / max(vsteps, 1))
        stats.update(self._moe_pull())
        return {st.request.uid: list(st.generated) for st in done}, stats

    def generate(self, prompts: jax.Array, num_tokens: int, seed: int = 0):
        """Static-engine-compatible entry: (B, S) prompts, all admitted at
        t=0, each generating ``num_tokens``.  Returns ((B, num_tokens)
        int32, stats) — token-identical to ``ServingEngine.generate``
        under greedy decoding."""
        del seed  # sampling keys are engine-level (slot/position folds)
        prompts = np.asarray(prompts)
        reqs = [Request(uid=i, prompt=prompts[i], max_new_tokens=num_tokens)
                for i in range(prompts.shape[0])]
        out, stats = self.run(reqs)
        toks = jnp.asarray(np.stack([out[i] for i in range(prompts.shape[0])]),
                           jnp.int32)
        return toks, stats
