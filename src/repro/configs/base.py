"""Config dataclasses shared by every architecture.

A single :class:`ModelConfig` covers all assigned families; family-specific
fields are ignored by families that do not use them.  Configs are plain
frozen dataclasses so they hash/compare cleanly and can key jit caches.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    """Mixture-of-Experts + expert-prototyping (M6-T) configuration."""

    num_experts: int = 0                 # 0 => dense FFN
    # Routing strategy: a key into the repro.core.routers registry.
    # Built-ins: "topk" (GShard/Switch sequential top-k, looping argmax),
    # "prototype" (M6-T k top-1 expert prototyping), "expert_choice"
    # (experts pick their top-C tokens), "hash" (stateless position hash).
    routing: str = "topk"
    top_k: int = 1                       # k for topk/expert_choice/hash routing
    num_prototypes: int = 1              # Z for prototype routing
    prototype_top_k: int = 1             # k' inside each prototype (paper: 1)
    # Capacity convention (M6-T 3.2): "k" => C = k*T/N*gamma ; "one" => C = 1*T/N*gamma
    capacity_mode: str = "k"
    # gamma (paper Table 5).  None => *dropless*: capacity is effectively
    # infinite (no token is ever dropped) and requires an execution
    # backend that never allocates (E, C) buffers (impl="dropless" —
    # validated in __post_init__ against the dispatcher registry).
    capacity_factor: Optional[float] = 1.25
    aux_loss_coef: float = 0.01          # 0 disables the balancing loss
    router_z_loss_coef: float = 0.0      # beyond-paper stability option
    router_dtype: str = "float32"        # routers always f32 (stability)
    # Renormalise each token's kept gates to sum to 1.  Applies to every
    # router (including prototype, where pre-registry code ignored it;
    # Fig. 8 itself uses raw softmax gates — hence the False default).
    normalize_gates: bool = False
    group_size: int = 2048               # tokens per routing group (GShard "d")
    combine_dtype: str = "auto"          # "auto": activation dtype (mesh-tf bf16)
    # Execution backend: a key into the repro.core.dispatch registry.
    # Built-ins: "einsum" (paper-faithful GShard one-hot einsums),
    # "gather" (index-view gather/scatter), "pallas" (grouped-GEMM
    # kernel), "alltoall" (explicit expert-parallel shard_map dispatch).
    impl: str = "einsum"
    moe_attention: bool = False          # M6-T 3.4 (negative result)
    expert_axis: str = "model"           # mesh axis experts are sharded over
    # Gates are multiplied by this after any renormalisation
    # (DeepSeek-V3's routed_scaling_factor).
    routed_scaling_factor: float = 1.0
    # Always-on experts beside the routed ones: one swiglu/gelu FFN of
    # this width (n_shared * expert width), added to every token's
    # output.  0 => none.
    shared_d_ff: int = 0
    # The held share: the layer routes over all ``num_experts`` at the
    # router's width and k, holds the weights of experts
    # [held_offset, held_offset + held_experts) only, and computes only
    # the choices that land on them (the rest add nothing) -- one
    # chip's share of an expert-parallel layer.  0 => every expert.
    held_experts: int = 0
    held_offset: int = 0

    def __post_init__(self):
        if self.num_experts > 0:
            # Lazy imports: the registries live above configs in the layer
            # graph, but validation only runs at instance creation, after
            # repro.core.{routers,dispatch} have had a chance to register
            # plugins.
            from repro.core.dispatch import get_dispatcher
            from repro.core.routers import get_router

            get_router(self.routing)      # raises with the registry key list
            dispatcher = get_dispatcher(self.impl)  # likewise for backends
            if self.capacity_factor is None and not getattr(
                    dispatcher, "supports_dropless", False):
                from repro.core.dispatch import available_dispatchers
                capable = [n for n in available_dispatchers() if getattr(
                    get_dispatcher(n), "supports_dropless", False)]
                raise ValueError(
                    f"capacity_factor=None (dropless) needs a capacity-free "
                    f"execution backend, but impl={self.impl!r} allocates "
                    f"(E, C) buffers; dropless-capable dispatchers: "
                    f"{', '.join(capable) or '(none registered)'}")
            if self.capacity_factor is None and self.moe_attention:
                raise ValueError(
                    "capacity_factor=None (dropless) is incompatible with "
                    "moe_attention=True: attention experts run the dense "
                    "einsum path, whose (G, T, E, C) view would be "
                    "O(G*T^2*E) at the dropless capacity C=T")
            if self.held_experts and not (
                    0 <= self.held_offset
                    and self.held_offset + self.held_experts
                    <= self.num_experts):
                raise ValueError(
                    f"held experts [{self.held_offset}, "
                    f"{self.held_offset + self.held_experts}) do not lie "
                    f"within the {self.num_experts} routed experts")

    @property
    def held(self) -> int:
        """Experts whose weights the layer holds (all unless a share)."""
        return self.held_experts or self.num_experts

    @property
    def active_k(self) -> int:
        """Expert choices per token (expected, for capacity/metrics)."""
        if self.num_experts == 0:
            return 0
        if self.routing == "prototype":
            return self.num_prototypes * self.prototype_top_k
        return self.top_k

    @property
    def experts_per_prototype(self) -> int:
        if self.routing != "prototype":
            return self.num_experts
        assert self.num_experts % self.num_prototypes == 0, (
            f"num_experts={self.num_experts} not divisible by "
            f"num_prototypes={self.num_prototypes}"
        )
        return self.num_experts // self.num_prototypes

    @property
    def dropless(self) -> bool:
        """True when capacity_factor=None: no token is ever dropped."""
        return self.capacity_factor is None

    def capacity(self, tokens_per_shard: int) -> int:
        """Per-expert capacity C = k*T/N*gamma (Eq. 2), or 1x variant.

        Dropless mode returns T: a token's K choices target distinct
        experts, so no expert can ever hold more than T slots per group —
        every choice is valid and the routing quality is exactly the
        capacity-infinity limit.  Only the dense (G,T,E,C) views would
        pay for this bound, and dropless backends never build them.
        """
        if self.capacity_factor is None:
            return max(tokens_per_shard, 1)
        k_eff = 1 if self.capacity_mode == "one" else max(self.active_k, 1)
        c = int(k_eff * tokens_per_shard / max(self.num_experts, 1) * self.capacity_factor)
        return max(c, 1)


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str = "model"
    family: str = "decoder_lm"   # decoder_lm | encdec | xlstm | zamba | vlm | m6
    num_layers: int = 2
    d_model: int = 128
    num_heads: int = 4
    num_kv_heads: int = 4
    head_dim: int = 0            # 0 => d_model // num_heads
    d_ff: int = 512              # dense FFN hidden (or per-expert hidden for MoE)
    vocab_size: int = 1024
    max_seq_len: int = 8192
    # attention details
    qk_norm: bool = False        # qwen3-style per-head RMSNorm on q,k
    qkv_bias: bool = False       # qwen2.5-style bias on QKV
    pos_embed: str = "rope"      # rope | learned (M6/BERT style)
    rope_theta: float = 1e6
    attn_logit_softcap: float = 0.0
    # "mha": q/k/v heads (GQA with num_kv_heads); "mla": DeepSeek-V2/V3
    # multi-head latent attention -- keys and values come from one
    # normed latent of kv_lora_rank lanes plus a shared roped key of
    # qk_rope_head_dim; queries are qk_nope_head_dim + qk_rope_head_dim
    # per head, values v_head_dim.  The paged engine caches only the
    # latent and the roped key (576 lanes at Moonlight's widths).
    attention: str = "mha"
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # "auto": chunked online-softmax when S*T is large (O(S*block) memory),
    # reference otherwise; "reference"/"chunked" force a path.
    attention_impl: str = "auto"
    attention_block: int = 512
    # FFN
    ffn_activation: str = "swiglu"   # swiglu | gelu | relu
    # norms / embeddings
    norm: str = "rmsnorm"        # rmsnorm | layernorm
    norm_eps: float = 1e-6
    tie_embeddings: bool = False
    # MoE
    moe: MoEConfig = dataclasses.field(default_factory=MoEConfig)
    moe_layer_period: int = 1    # apply MoE FFN every k-th layer (1 = all)
    # Leading dense layers (DeepSeek's first_k_dense_replace): a group of
    # its own, stacked and scanned before the MoE group, with an FFN of
    # dense_d_ff (0 => d_ff).
    first_dense_layers: int = 0
    dense_d_ff: int = 0
    # enc-dec
    num_encoder_layers: int = 0
    # xLSTM
    xlstm_slstm_period: int = 0  # every k-th block is sLSTM (0 = none/all-mLSTM)
    # SSM / Mamba2 (zamba)
    ssm_state: int = 0
    ssm_heads: int = 0
    ssm_expand: int = 2
    ssm_conv_width: int = 4
    ssm_chunk: int = 128
    zamba_shared_period: int = 6  # shared attn block applied every k mamba layers
    # VLM / multimodal stubs
    num_image_tokens: int = 0    # image/audio prefix embeddings (precomputed)
    # numerics
    dtype: str = "bfloat16"      # activation/compute dtype
    param_dtype: str = "float32"
    initializer_range: float = 0.02   # M6-T Table 5 (0.002 for 1T)
    # distribution
    remat: bool = True
    scan_layers: bool = True
    fsdp: bool = False           # shard params over data axis too (ZeRO-3 style)
    # training details
    dropout: float = 0.0         # paper uses 0.1; synthetic runs use 0

    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim or self.d_model // self.num_heads

    @property
    def mla(self) -> bool:
        return self.attention == "mla"

    @property
    def kv_entry_dim(self) -> int:
        """Lanes a cached token takes per KV head in the pool: the head
        size, or for latent attention the latent plus the roped key,
        padded with zero lanes to a multiple of 128.  Unpadded, a latent
        entry (512 + 64 lanes) is no multiple of the TPU's 128 lanes, so
        the pool's default device layout puts the token axis minor and
        every step copies each layer's pool into the paged kernel's
        layout and back."""
        if self.mla:
            return -(-(self.kv_lora_rank + self.qk_rope_head_dim) // 128) * 128
        return self.resolved_head_dim

    @property
    def layer_groups(self) -> Tuple[Tuple[str, int], ...]:
        """(parameter key, layer count) of each stacked layer group, in
        order: the leading dense group (if any), then the rest."""
        nd = self.first_dense_layers
        rest = (("blocks", self.num_layers - nd),)
        return ((("dense_blocks", nd),) + rest) if nd else rest

    @property
    def activation_dtype(self):
        return jnp.dtype(self.dtype)

    @property
    def weight_dtype(self):
        return jnp.dtype(self.param_dtype)

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)

    def replace_moe(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, moe=dataclasses.replace(self.moe, **kw))


@dataclasses.dataclass(frozen=True)
class SpecConfig:
    """Speculative decoding (``repro.serving.speculative``).

    A drafter proposes up to ``gamma`` cheap continuation tokens per
    decode slot; the engine then scores all ``gamma + 1`` positions in a
    single verify step (they are ordinary prefill-chunk-style rows) and
    accepts a prefix of the drafts under the greedy / rejection-sampling
    rule — temperature 0 stays token-identical to non-speculative
    decoding, temperature > 0 preserves the target distribution.
    """

    # Drafter: a key into the repro.serving.speculative registry.
    # Built-ins: "ngram" (prompt-lookup self-drafting from the slot's own
    # prompt + generated context, no extra params) and "model" (a small
    # draft model sharing the target's vocab).
    drafter: str = "ngram"
    gamma: int = 4               # max draft tokens per slot per verify step
    # Registered config id (configs/registry ALL_IDS) for the "model"
    # drafter's draft model; smoke-sized at serve time.  Tests and
    # benchmarks may instead hand the engine a (cfg, params) pair.
    draft: Optional[str] = None
    max_ngram: int = 3           # longest context suffix the ngram drafter matches

    def __post_init__(self):
        if self.gamma < 1:
            raise ValueError("SpecConfig.gamma must be >= 1")
        if self.max_ngram < 1:
            raise ValueError("SpecConfig.max_ngram must be >= 1")
        # Lazy import, mirroring MoEConfig's router/dispatcher checks:
        # the drafter registry lives above configs in the layer graph and
        # plugins must have a chance to register before validation.
        from repro.serving.speculative import get_drafter_cls

        get_drafter_cls(self.drafter)   # raises with the registry key list


@dataclasses.dataclass(frozen=True)
class SLOConfig:
    """SLO-aware scheduling (``repro.serving.slo``): preemption with KV
    swap-to-host.  Attach to ``ServeConfig.slo`` to let the scheduler
    evict a running lower-priority victim (KV blocks copied to a
    host-side numpy pool, re-admission restores them and resumes at the
    exact token) whenever a higher-priority arrival cannot be admitted.
    Pairs with the ``priority_strict`` / ``edf`` / ``cache_aware``
    admission policies, but works under any policy.
    """

    preemption: bool = True
    # Host-pool size in KV blocks.  None => mirror the device pool (a
    # preempted working set can never exceed what was resident).
    host_blocks: Optional[int] = None
    # Per-request preemption cap: after this many round trips a request
    # is pinned (never picked as victim again) so repeated preemption
    # cannot livelock a long job under sustained high-priority load.
    max_preemptions: int = 8
    # Only waiting requests whose priority class value is <= this
    # trigger preemption (0 = HIGH only, the default).  Every class
    # still jumps the *queue* under a priority-aware admission policy;
    # the threshold decides who may evict running work — swap round
    # trips are not free, and letting every NORMAL arrival churn LOW
    # requests out of their slots costs more throughput than the queue
    # reordering buys.
    preempt_threshold: int = 0
    # Deadline-aware admission shedding: reject (finish with status
    # "shed", counted in requests_shed_total) queued requests whose
    # effective_deadline_ms is provably unmeetable given their prefill
    # length and the measured decode ms/token.  Off by default — a shed
    # request gets *no* tokens, so the gate must be an explicit opt-in
    # (--slo-shed).  Requests without a deadline are never shed.
    shed: bool = False

    def __post_init__(self):
        if self.host_blocks is not None and self.host_blocks < 1:
            raise ValueError("SLOConfig.host_blocks must be >= 1")
        if self.max_preemptions < 0:
            raise ValueError("SLOConfig.max_preemptions must be >= 0")


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    """Continuous-batching serving shapes (``repro.serving.continuous``).

    All three sizes are *static*: the engine's jit'd step functions close
    over them, so requests entering and leaving the pool never trigger a
    recompile.  ``max_len`` bounds prompt_len + max_new_tokens per
    request (KV block tables are sized ceil(max_len / kv_block_size)).
    """

    max_slots: int = 8           # decode slots (concurrent requests)
    kv_block_size: int = 16      # tokens per KV block (paged cache page)
    prefill_chunk: int = 32      # prompt tokens ingested per mixed step
    max_len: int = 256           # per-request context bound
    # Total KV blocks in the pool.  None => fully provisioned
    # (max_slots * ceil(max_len / kv_block_size)): admission can never
    # deadlock mid-flight.  Smaller pools exercise queueing on blocks.
    num_blocks: Optional[int] = None
    # Speculative decoding; None => one token per slot per decode step.
    spec: Optional[SpecConfig] = None
    # Admission policy: a key into the repro.serving.scheduler registry
    # ("fcfs" | "sjf" | "prefill_first").
    sched_policy: str = "fcfs"
    # Block-level prefix caching (repro.serving.prefix_cache): requests
    # whose prompt prefix hashes to already-resident KV blocks bind and
    # share them (refcounted, copy-on-write), skip their prefill, and
    # charge admission only the unshared footprint.  Default off keeps
    # the exact PagedKVCache behaviour.
    prefix_cache: bool = False
    # SLO-aware scheduling: priority preemption with KV swap-to-host
    # (repro.serving.slo).  None => no preemption; priorities and
    # deadlines still order admission under the slo policies.
    slo: Optional[SLOConfig] = None
    # KV-cache quantization: a key into the repro.quant policy registry
    # ("none" | "int8" | "fp8").  Quantized pools store int8 codes plus
    # per-(layer, block, kv_head) float32 absmax scales; decode
    # attention dequantizes in-kernel.  "none" keeps the full-precision
    # pools bitwise identical to the pre-quant engine.
    kv_quant: str = "none"
    # Serving device mesh as ((axis, size), ...) — must name exactly
    # ("data", "expert"), in that order; size-1 axes are allowed.  Slots
    # and KV block pools partition over "data" (contiguous slot ranges,
    # one allocator per shard), expert FFN weights over "expert" (ragged
    # all-to-all dispatch for the dropless backend).  None => the
    # single-device engine, bit-for-bit the pre-mesh behaviour.
    mesh: Optional[Tuple[Tuple[str, int], ...]] = None

    def __post_init__(self):
        if self.max_slots < 1 or self.kv_block_size < 1 or self.prefill_chunk < 1:
            raise ValueError("max_slots, kv_block_size, prefill_chunk must be >= 1")
        if self.max_len < 2:
            raise ValueError("max_len must be >= 2 (one prompt + one generated)")
        if self.mesh is not None:
            names = tuple(a for a, _ in self.mesh)
            if names != ("data", "expert"):
                raise ValueError(
                    f"ServeConfig.mesh axes must be ('data', 'expert'), got {names}; "
                    "use size 1 for an axis you don't shard over")
            if any(int(n) < 1 for _, n in self.mesh):
                raise ValueError("ServeConfig.mesh axis sizes must be >= 1")
            d = self.data_shards
            if self.max_slots % d:
                raise ValueError(
                    f"max_slots={self.max_slots} must divide evenly over "
                    f"{d} data shards")
            if self.resolved_num_blocks % d:
                raise ValueError(
                    f"num_blocks={self.resolved_num_blocks} must divide evenly "
                    f"over {d} data shards")
        from repro.serving.scheduler import get_policy

        get_policy(self.sched_policy)   # raises with the registry key list
        from repro.quant import get_kv_quant

        get_kv_quant(self.kv_quant)     # likewise for KV quantization

    @property
    def data_shards(self) -> int:
        """Slot/KV-pool shards along the mesh's data axis (1 if unsharded)."""
        return dict(self.mesh).get("data", 1) if self.mesh else 1

    @property
    def expert_shards(self) -> int:
        return dict(self.mesh).get("expert", 1) if self.mesh else 1

    @property
    def blocks_per_slot(self) -> int:
        return -(-self.max_len // self.kv_block_size)

    @property
    def resolved_num_blocks(self) -> int:
        return self.num_blocks if self.num_blocks is not None else (
            self.max_slots * self.blocks_per_slot)


@dataclasses.dataclass(frozen=True)
class ShapeConfig:
    """One (input-shape) cell: what gets lowered and at what size."""

    name: str
    seq_len: int
    global_batch: int
    kind: str                    # "train" | "prefill" | "decode"

    @property
    def tokens(self) -> int:
        return self.seq_len * self.global_batch


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    learning_rate: float = 8e-5      # paper: AdamW 8e-5
    optimizer: str = "adamw"         # adamw | adafactor (paper 1T: adafactor @5e-3)
    warmup_steps: int = 500          # paper Table 5
    weight_decay: float = 0.01
    grad_clip_norm: float = 1.0
    zero1: bool = True               # shard optimizer state over DP axis
    grad_compression: str = "none"   # none | bf16 | int8
    microbatches: int = 1            # grad accumulation
    seed: int = 0
