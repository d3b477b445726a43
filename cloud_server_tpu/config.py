"""Typed configuration system.

Plain frozen dataclasses so configs are hashable (usable as jit static
arguments) and serialise cleanly to/from JSON for checkpoint metadata.
"""

from __future__ import annotations

import dataclasses
import json
from typing import Any, Mapping


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    """Decoder-only transformer family configuration."""

    vocab_size: int = 32000
    embed_dim: int = 2048
    num_layers: int = 16
    num_heads: int = 16
    num_kv_heads: int = 16  # < num_heads => grouped-query attention
    head_dim: int = 128
    mlp_dim: int = 8192
    max_seq_len: int = 2048
    rope_theta: float = 10000.0
    # RoPE frequency scaling for long-context checkpoints:
    # "none" | "linear" (divide all frequencies by factor) | "llama3"
    # (Llama 3.1 band-wise interpolation; see ops/rope.py:_scale_inv_freq)
    rope_scaling: str = "none"
    rope_scaling_factor: float = 1.0
    rope_low_freq_factor: float = 1.0
    rope_high_freq_factor: float = 4.0
    rope_original_max_len: int = 8192
    norm_eps: float = 1e-6
    tie_embeddings: bool = False
    # numerics
    dtype: str = "bfloat16"  # activation/compute dtype
    param_dtype: str = "float32"  # master parameter dtype
    # attention implementation: "xla" | "flash" | "ring" | "ulysses"
    # ("ring" and "ulysses" are the two sequence-parallel schemes over sp:
    #  ppermute kv rotation vs all-to-all head re-sharding)
    attention_impl: str = "xla"
    # flash-attention block sizes (the pallas kernel's q/kv tiling).
    # Measured v5e sweep (r3, 330M bench, S=1024): 1024 single-block with
    # the fused whole-sequence backward is optimal at 221 ms/step;
    # 512-blocks lose BOTH ways despite the causal block skip — 236 ms
    # with the staged-dq single-recompute backward (staging traffic) and
    # 242 ms with the two-pass backward (second recompute + grid
    # overhead). At S=2048/1024-blocks the two-pass backward also edges
    # the staged one (65.2 vs 67.2 ms) — the backward is bandwidth-bound,
    # so recompute is cheaper than dq-staging HBM round trips.
    flash_block_q: int = 1024
    flash_block_kv: int = 1024
    # decode-time (cached) attention: "xla" | "pallas". "pallas" selects
    # the paged-attention kernel and is only meaningful with the paged
    # serving stack (inference.paged_server); inference.engine's
    # contiguous cache always uses the XLA path.
    decode_attention_impl: str = "xla"
    # KV-cache storage: "model" (cfg.dtype) | "int8" (symmetric
    # per-(position, head) absmax quantization — halves cache memory;
    # scales fold into the attention einsums / kernel rows, so no
    # dequantized cache copy is ever materialised)
    kv_cache_dtype: str = "model"
    # mixture of experts (0 experts => dense MLP)
    num_experts: int = 0
    num_experts_per_token: int = 2
    expert_capacity_factor: float = 1.25
    # the gated MLP's gate activation, dense or expert: "silu" (SwiGLU) |
    # "relu" (ReGLU)
    mlp_activation: str = "silu"
    # what the router reads: "mlp_norm" (the normed post-attention
    # stream, beside the experts) | "layer_input" (the layer's input
    # before its attention norm: the router placed before attention)
    router_input: str = "mlp_norm"
    # A pattern of layers. `sliding_window` > 0 is the number of keys a
    # query of a window layer reads, its own included (i - j <
    # sliding_window); `window_layout` says which layers are window layers
    # and `rope_layout` which layers rotate q and k (a layer with 0 carries
    # no position at all). Each is a period of 0/1 flags repeated over the
    # depth, or one flag a layer; empty = no window layer / every layer
    # rotary, which is every configuration that states neither.
    sliding_window: int = 0
    window_layout: tuple = ()
    rope_layout: tuple = ()
    # Latent attention (MLA), `kv_lora_rank` > 0: queries through a
    # low-rank bottleneck of `q_lora_rank`, keys and values expanded from
    # one latent vector of `kv_lora_rank` a token beside one rotary key of
    # `qk_rope_head_dim` that every head shares. `head_dim` is then the
    # query/key width (the position-free part and the rotary part) and
    # `v_head_dim` the value width, apart. The paged cache holds the latent
    # vector and the rotary key, `latent_dim` values a token an attention
    # block, and is read in the absorbed form (models/latent.py).
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # The layer's body: "single" (attention, then the MLP or the experts)
    # | "double_shortcut": two attention blocks and two dense MLPs of
    # `mlp_dim` in one layer, the experts reading the first half's normed
    # stream and added after the second half (models/latent.py)
    # | "parallel_mixer": a Mamba-2 mixer and the attention block side by
    # side on one normed input, then the dense MLP (models/mixer.py).
    layer_body: str = "single"
    # The mixer of the parallel body, `ssm_heads` > 0: `ssm_heads` heads of
    # `ssm_head_dim` channels, each with a state of (ssm_head_dim,
    # ssm_state_dim); `ssm_groups` groups share the state's input and
    # output projections (B and C); a depthwise causal convolution of
    # `ssm_conv_width` taps before the scan; `ssm_chunk` tokens a chunk of
    # the chunked scan. The paged cache holds, beside the pages, one
    # recurrent state a slot a layer in float32 and the convolution's last
    # `ssm_conv_width - 1` inputs (`PagedKVCache.ssm`, `.conv`).
    ssm_heads: int = 0
    ssm_head_dim: int = 0
    ssm_state_dim: int = 0
    ssm_groups: int = 1
    ssm_conv_width: int = 4
    ssm_chunk: int = 128
    # Scalar multipliers a model is published with (maximal-update
    # parametrisation), each 1 where a model states none: on the embedding
    # (applied by every walk of the paged server and by the expert model's
    # scans) and, applied only by the parallel body, on the logits, on
    # the attention block's input, its keys and its output, on the
    # mixer's input and output, on the five parts of the mixer's input
    # projection (gate, x, B, C, dt) and on the MLP's gate and output.
    embedding_multiplier: float = 1.0
    lm_head_multiplier: float = 1.0
    attention_in_multiplier: float = 1.0
    attention_out_multiplier: float = 1.0
    key_multiplier: float = 1.0
    ssm_in_multiplier: float = 1.0
    ssm_out_multiplier: float = 1.0
    ssm_multipliers: tuple = (1.0, 1.0, 1.0, 1.0, 1.0)
    mlp_multipliers: tuple = (1.0, 1.0)
    # One chip's share of a wider router. `num_experts` routed experts are
    # held (the first of `num_routed_experts`, the router's columns for
    # experts that have weights; 0 = every expert is held), behind them
    # `num_zero_experts` columns for experts that compute nothing and add
    # gate * token. `routed_scaling_factor` > 0: the choice is the top k of
    # probability + bias, the gate probability * factor, not renormalised
    # (0: the renormalised top-k gates). `expert_mlp_dim`: an expert's
    # width where the layer also has a dense MLP of `mlp_dim` (0 = mlp_dim).
    num_routed_experts: int = 0
    num_zero_experts: int = 0
    routed_scaling_factor: float = 0.0
    expert_mlp_dim: int = 0
    # Leading dense layers before the expert layers: the first
    # `num_dense_layers` layers have a dense MLP of `mlp_dim`, the others
    # experts of `expert_mlp_dim`, and the parameters are two stacks with
    # different leaves (`layer_stack`). `shared_expert_dim` > 0: beside
    # its routed experts an expert layer has one always-on gated MLP of
    # that width, added ungated.
    num_dense_layers: int = 0
    shared_expert_dim: int = 0
    # The router's score: "softmax" (the renormalised top-k gates) |
    # "sigmoid": a score an expert, the choice the top k of score + a
    # balancing bias (a leaf, in the choice only), the gates the kept
    # scores divided by their sum, times `route_scale`.
    router_score: str = "softmax"
    route_scale: float = 1.0
    # The attention block of a single layer: an RMSNorm a head on q and k
    # before the rotation (`qk_norm`, one scale vector of `head_dim` a
    # layer each); the kernel's output times the sigmoid of a projection of
    # the block's normed input, before the output projection
    # (`attention_gate`); a norm on each block's output before it is added
    # to the stream (`post_norms`, behind the attention and behind the MLP).
    qk_norm: bool = False
    attention_gate: bool = False
    post_norms: bool = False
    # rematerialisation policy for the layer scan:
    # "none" | "full" | "dots" | "attn" (save only flash-attention residuals)
    remat: str = "full"
    # lax.scan unroll factor for the layer stack (1 = no unrolling).
    # Unrolling lets XLA fuse/overlap across layer boundaries at the
    # cost of a proportionally larger program; measured v5e r4 sweep at
    # the 330M bench config it LOSES outright (215.9 ms at 1, 240.9 at
    # 2, 254.0 at 4 — bigger programs schedule worse here). Kept as a
    # knob because the tradeoff is model/chip dependent.
    scan_layers_unroll: int = 1
    logits_softcap: float = 0.0
    # Training-loss vocab chunk size. 0 = dense path (materialise the full
    # (B, S, V) f32 logits). >0 = fused blockwise CE: the unembed matmul,
    # softcap and logsumexp run one vocab chunk at a time inside a
    # rematerialised scan, so peak loss-path memory is (B, S, chunk) and
    # the ~1 GB logits tensor never hits HBM.
    vocab_chunk: int = 0
    # Training-loss implementation:
    #   "dense"  — materialise (B, S, V) f32 logits (XLA path);
    #              vocab_chunk > 0 selects the scan-chunked variant.
    #   "pallas" — ops/fused_ce.py kernels: online-logsumexp forward
    #              (no logits in HBM), single-recompute backward whose
    #              one (B*S, V) buffer is the MODEL-dtype d_logits —
    #              half the dense path's f32 logits — with gradient
    #              matmuls in the model dtype. Requires
    #              logits_softcap == 0 and B*S, vocab divisible by 128.
    ce_impl: str = "dense"

    def __post_init__(self) -> None:
        for name in ("window_layout", "rope_layout"):
            # JSON gives lists; a jit static argument must be hashable
            object.__setattr__(self, name, tuple(
                int(bool(f)) for f in getattr(self, name)))
        for name, n in (("ssm_multipliers", 5), ("mlp_multipliers", 2)):
            vals = tuple(float(v) for v in getattr(self, name))
            if len(vals) != n:
                raise ValueError(f"{name} takes {n} scalars, got {vals}")
            object.__setattr__(self, name, vals)
        if self.mlp_activation not in ("silu", "relu"):
            raise ValueError(
                f"unknown mlp_activation: {self.mlp_activation!r}")
        if self.router_input not in ("mlp_norm", "layer_input"):
            raise ValueError(f"unknown router_input: {self.router_input!r}")
        if any(self.window_layout) and self.sliding_window <= 0:
            raise ValueError("window_layout names window layers but "
                             "sliding_window is not set")
        if self.layer_body not in ("single", "double_shortcut",
                                   "parallel_mixer"):
            raise ValueError(f"unknown layer_body: {self.layer_body!r}")
        if (self.layer_body == "parallel_mixer") != (self.ssm_heads > 0):
            raise ValueError(
                "the mixer's sizes (ssm_heads) and the parallel body "
                "(layer_body='parallel_mixer') come together: no program "
                "serves one without the other")
        if self.ssm_heads and (
                min(self.ssm_head_dim, self.ssm_state_dim, self.ssm_chunk,
                    self.ssm_conv_width - 1) < 1
                or self.ssm_heads % max(self.ssm_groups, 1)):
            raise ValueError(
                "a mixer needs ssm_head_dim, ssm_state_dim and ssm_chunk "
                "of 1 or more, 2 or more convolution taps, and ssm_groups "
                "dividing ssm_heads")
        if self.ssm_heads and (self.has_window_layers or self.kv_lora_rank
                               or self.kv_cache_dtype != "model"
                               or self.num_experts >= 2):
            raise ValueError(
                "a slot's recurrent state is float32 beside full pages in "
                "the model's dtype and a dense MLP: no window layers, no "
                "latent cache, no int8 cache or state, no experts")
        if (self.layer_body == "double_shortcut") != (self.kv_lora_rank > 0):
            raise ValueError(
                "latent attention (kv_lora_rank) and the double layer "
                "(layer_body='double_shortcut') come together: no program "
                "serves one without the other")
        if self.kv_lora_rank and (self.has_window_layers
                                  or self.kv_cache_dtype != "model"):
            raise ValueError(
                "a latent cache holds one kind of page in the model's "
                "dtype: no window layers, no int8 cache")
        if (self.num_routed_experts or self.num_zero_experts) and not (
                self.routed_scaling_factor > 0
                and self.num_routed_experts >= self.num_experts >= 2):
            raise ValueError(
                "a router wider than the experts held needs "
                "routed_scaling_factor > 0 and 2 <= num_experts <= "
                "num_routed_experts")
        if self.router_score not in ("softmax", "sigmoid"):
            raise ValueError(f"unknown router_score: {self.router_score!r}")
        if self.router_score == "softmax" and self.route_scale != 1.0:
            raise ValueError(
                "route_scale is the sigmoid router's: the softmax router's "
                "kept gates are renormalised and unscaled")
        if self.router_score == "sigmoid" and (
                self.num_experts < 2 or self.routed_scaling_factor > 0):
            raise ValueError(
                "a sigmoid router chooses among experts that are all held "
                "here: it needs num_experts >= 2, and no program serves one "
                "chip's share of it (routed_scaling_factor)")
        if (self.num_dense_layers or self.shared_expert_dim) and (
                self.num_experts < 2 or self.routed_scaling_factor > 0
                or self.layer_body != "single"
                or not 0 <= self.num_dense_layers < self.num_layers):
            raise ValueError(
                "leading dense layers and a shared expert belong to single "
                "layers of experts that are all held here, with at least "
                "one expert layer behind the dense ones")
        if self.layer_body != "single" and (
                self.qk_norm or self.attention_gate or self.post_norms):
            raise ValueError(
                "qk_norm, attention_gate and post_norms are the single "
                "layer's attention block's: the double layer and the "
                "parallel body have blocks of their own")
        if self.num_heads % max(self.num_kv_heads, 1) != 0:
            raise ValueError(
                f"num_heads={self.num_heads} must be a multiple of "
                f"num_kv_heads={self.num_kv_heads}"
            )
        if self.ce_impl not in ("dense", "pallas"):
            raise ValueError(f"unknown ce_impl: {self.ce_impl!r}")
        if self.ce_impl == "pallas" and self.logits_softcap != 0.0:
            raise ValueError(
                "ce_impl='pallas' does not implement logits_softcap; "
                "use the dense/chunked CE path")
        if self.ce_impl == "pallas" and self.vocab_chunk > 0:
            raise ValueError(
                "ce_impl='pallas' and vocab_chunk are mutually "
                "exclusive CE implementations")

    @property
    def q_per_kv(self) -> int:
        return self.num_heads // self.num_kv_heads

    def layer_window(self, layer: int) -> int:
        """Keys a query of `layer` reads, its own included; 0 = all."""
        lay = self.window_layout
        return self.sliding_window if lay and lay[layer % len(lay)] else 0

    def layer_rope(self, layer: int) -> bool:
        lay = self.rope_layout
        return bool(lay[layer % len(lay)]) if lay else True

    @property
    def layer_kinds(self) -> tuple:
        """Per layer its cache kind, "full" or "window": a kind is a page
        pool of its own in the paged cache."""
        return tuple("window" if self.layer_window(i) else "full"
                     for i in range(self.num_layers))

    @property
    def has_window_layers(self) -> bool:
        return "window" in self.layer_kinds

    @property
    def has_layer_pattern(self) -> bool:
        """Layers differ: the scans carry per-layer flags."""
        return self.has_window_layers or not all(
            self.layer_rope(i) for i in range(self.num_layers))

    @property
    def latent_dim(self) -> int:
        """Values a token an attention block in a latent cache: the latent
        vector and the shared rotary key; 0 without latent attention."""
        return (self.kv_lora_rank + self.qk_rope_head_dim
                if self.kv_lora_rank else 0)

    @property
    def ssm_inner(self) -> int:
        """The mixer's inner width: heads x channels a head."""
        return self.ssm_heads * self.ssm_head_dim

    @property
    def ssm_conv_dim(self) -> int:
        """Channels through the mixer's convolution: x, B and C."""
        return self.ssm_inner + 2 * self.ssm_groups * self.ssm_state_dim

    @property
    def attention_blocks(self) -> int:
        """Attention blocks, each with cache layers of its own, a layer."""
        return 2 if self.layer_body == "double_shortcut" else 1

    @property
    def router_width(self) -> int:
        """The router's columns: the routed experts as published (held or
        not), then the experts that compute nothing."""
        return ((self.num_routed_experts or self.num_experts)
                + self.num_zero_experts)

    @property
    def expert_width(self) -> int:
        return self.expert_mlp_dim or self.mlp_dim

    def layer_stack(self, layer: int) -> tuple:
        """(name of the stack of parameters `layer` lies in, its index
        there): the leading dense layers are `lead_layers`, every other
        layer, and every layer of a model without them, `layers`."""
        lead = self.num_dense_layers
        return ("lead_layers", layer) if layer < lead else (
            "layers", layer - lead)

    def layer_pool(self, layer: int) -> tuple:
        """(kind, index of `layer` among the layers of its kind)."""
        kinds = self.layer_kinds
        return kinds[layer], kinds[:layer].count(kinds[layer])


@dataclasses.dataclass(frozen=True)
class MeshConfig:
    """Logical device mesh. Axis sizes of 1 are always legal.

    Canonical axis order (outer→inner, DCN-friendly outer, ICI-friendly
    inner): dp, pp, fsdp, ep, sp, tp. Tensor parallelism is innermost so its
    collectives ride the fastest ICI links.
    """

    dp: int = 1
    pp: int = 1
    fsdp: int = 1
    ep: int = 1
    sp: int = 1
    tp: int = 1

    AXIS_ORDER = ("dp", "pp", "fsdp", "ep", "sp", "tp")

    @property
    def num_devices(self) -> int:
        n = 1
        for a in self.AXIS_ORDER:
            n *= getattr(self, a)
        return n

    def axis_sizes(self) -> dict[str, int]:
        return {a: getattr(self, a) for a in self.AXIS_ORDER}


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    learning_rate: float = 3e-4
    # "warmup_cosine" | "wsd" (warmup-stable-decay: hold peak, then a
    # linear cooldown over the last lr_decay_frac of training — the
    # schedule that lets one run branch into many cooldown lengths) |
    # "constant" (warmup then hold)
    lr_schedule: str = "warmup_cosine"
    lr_decay_frac: float = 0.1  # wsd cooldown fraction of total_steps
    warmup_steps: int = 100
    total_steps: int = 1000
    weight_decay: float = 0.1
    beta1: float = 0.9
    beta2: float = 0.95
    grad_clip_norm: float = 1.0
    batch_size: int = 8  # global batch, in sequences
    microbatch_steps: int = 1  # gradient accumulation factor
    seq_len: int = 2048
    z_loss_coef: float = 0.0
    seed: int = 0
    moe_aux_loss_coef: float = 0.01
    moe_router_z_coef: float = 0.0
    # Exponential moving average of params (0 = disabled). The EMA tree
    # rides inside the optimizer state (sharded + checkpointed for free);
    # extract with training.optim.ema_params(state.opt_state).
    ema_decay: float = 0.0


@dataclasses.dataclass(frozen=True)
class InferConfig:
    max_decode_len: int = 256
    temperature: float = 1.0
    top_k: int = 0  # 0 => disabled
    top_p: float = 1.0  # 1.0 => disabled
    eos_token_id: int = -1  # -1 => never stop early
    pad_token_id: int = 0
    # Tokens per mixed iteration: all live decode rows (times their
    # round count) plus however many prefill-chunk tokens fit. 0 = auto:
    # max_slots * (decode window * decode_chunk + prefill_chunk) —
    # effectively work-conserving; set lower to trade admission speed
    # for a per-iteration latency (ITL) bound.
    mixed_token_budget: int = 0
    # Scheduler flight recorder: how many per-iteration records the
    # paged server's ring buffer retains for /stats post-mortems
    # (token-budget utilization, prefill/decode split, occupancy,
    # compaction, preemptions). Constructor argument of the same name
    # overrides; records are small dicts, so even thousands are cheap.
    flight_recorder_size: int = 256
    # Multi-tenant QoS (inference/qos.py): a JSON object as a string,
    # or a path to a JSON file, declaring per-tenant weights, priority
    # classes, token-bucket rate limits, and pending bounds (schema in
    # docs/serving.md). "" (the default) disables QoS entirely — the
    # schedulers run the byte-identical single-tenant FIFO paths. A
    # string (not a dict) keeps this dataclass hashable for jit static
    # arguments; servers parse it at construction. Constructor argument
    # `qos=` overrides.
    qos_config: str = ""
    # Per-request distributed tracing (inference/request_trace.py):
    # head-based sampling probability in [0, 1]. 0.0 (the default)
    # disables tracing entirely — the schedulers run the byte-identical
    # pre-trace paths. Sampled requests carry a span tree (queue /
    # prefill / decode / preempt_gap / emit phases plus per-iteration
    # scheduler spans) retrievable via GET /debug/requests/<id> and
    # exported Chrome-trace-style via GET /traces; W3C `traceparent`
    # headers propagate in and out. Constructor argument `tracing=`
    # (a rate or a ready TraceRecorder) overrides.
    trace_sample_rate: float = 0.0
    # Finished-trace ring capacity: how many completed head-sampled
    # span trees the recorder retains for GET /traces and
    # GET /debug/requests/<id> (oldest evicted). Previously hardcoded
    # at 256 inside the recorder. Constructor argument `tracing=` with
    # a ready TraceRecorder overrides.
    trace_capacity: int = 256
    # Tail-based trace retention: capacity of the SEPARATE bounded
    # ring that keeps the span trees of requests that proved anomalous
    # at finish (failed / deadline-expired / cancelled, migrated or
    # retried, missed their class SLO target, preempted repeatedly, or
    # finished inside an open anomaly window) even when head sampling
    # skipped them — the "1% sampling, broken requests always
    # inspectable" mode. 0 (the default) disables tail retention
    # entirely (no provisional traces, byte-identical pre-tail
    # serving).
    trace_tail_capacity: int = 0
    # Adaptive speculative decoding (inference/spec_control.py): a JSON
    # object as a string, or a path to a JSON file, with the controller
    # knobs (low/high accept-rate hysteresis thresholds, ewma, cooldown,
    # probe_period, initial draft length — schema in the module
    # docstring and docs/serving.md). "" (the default) enables the
    # DEFAULT adaptive controller whenever speculation is configured
    # (spec_drafts > 0); the literal "off" pins the fixed spec_drafts
    # draft length (the pre-adaptive behavior). A string keeps this
    # dataclass hashable for jit static arguments; the paged server
    # parses it at construction. Constructor argument `spec_control=`
    # (a config, a ready SpecController, or False) overrides.
    spec_control_config: str = ""
    # Iteration-phase profiler (inference/iteration_profile.py): stamp
    # every scheduler iteration's phase boundaries (sweep / admission /
    # build / device / commit / epilogue) with a bounded number of
    # perf_counter reads — zero added dispatches or syncs. Feeds the
    # flight recorder (`phases_ms`, `host_ms`, `device_wait_ms`,
    # `host_gap_frac`), the `cloud_server_iter_phase_ms` histograms,
    # the /stats `iteration_profile` summary, and the
    # GET /debug/scheduler_trace Perfetto export. False restores the
    # exact pre-profiler clock behavior (two reads per busy
    # iteration). Constructor argument `iteration_profile=` overrides.
    iteration_profile: bool = True
    # Per-class SLO targets (inference/slo.py): a JSON object as a
    # string, or a path to a JSON file, declaring per-priority-class
    # latency targets (ttft/itl/queue_wait/e2e) and attainment
    # objectives plus the rolling windows (schema in the module
    # docstring; surfaced via GET /slo and the slo_attainment /
    # slo_burn_rate gauges). "" (the default) disables SLO tracking
    # entirely. A string keeps this dataclass hashable for jit static
    # arguments; servers parse it at construction. Constructor
    # argument `slo=` overrides.
    slo_config: str = ""
    # Deterministic fault injection (inference/faults.py): a JSON
    # object as a string, or a path to a JSON file, arming named fault
    # sites (submit_reject / dispatch / iteration_stall / wedge /
    # alloc_famine) with seeded after/count/p windows — the lever that
    # makes every recovery path (router failover, breakers, _fail_all)
    # provable instead of aspirational. "" (the default) disables
    # injection entirely: every guarded call site short-circuits and
    # the schedulers run the byte-identical pre-fault paths (pinned by
    # the dispatch/device_get-count regression clones). A string keeps
    # this dataclass hashable for jit static arguments; servers parse
    # it at construction. Constructor argument `faults=` overrides.
    fault_plan: str = ""
    # Overload brownout (inference/faults.py): a JSON object as a
    # string, or a path to a JSON file, with the OverloadDetector
    # thresholds (pending_age_s / budget_utilization / host_gap_frac
    # EWMAs), hysteresis, shed sets per level, and the jittered
    # Retry-After base. "" (the default) disables brownout. Requires a
    # QoS registry (shed sets are priority classes). Paged server
    # only; constructor argument `brownout=` overrides.
    brownout_config: str = ""
    # Anomaly watchdog (inference/anomaly.py): a JSON object as a
    # string, or a path to a JSON file, with the rule thresholds
    # (slo_burn / latency_shift / cache_collapse / breaker_flap /
    # deadline_spike / preempt_spike / host_gap / wedged), hysteresis
    # hold, warm-up, and optional auto-capture knobs (schema in the
    # module docstring). "" (the default) disables the watchdog
    # entirely: every guarded call site short-circuits and the
    # schedulers run the byte-identical pre-watchdog paths.
    # Constructor argument `anomaly=` overrides.
    anomaly_config: str = ""
    # Auto-capture a forensic debug bundle (the GET /debug/bundle
    # artifact: metrics, flight window, retained traces, cache/SLO/
    # brownout/anomaly state) into a bounded ring each time a watchdog
    # rule activates. Requires anomaly_config; off by default.
    bundle_on_anomaly: bool = False

    def __post_init__(self) -> None:
        if self.flight_recorder_size <= 0:
            raise ValueError("flight_recorder_size must be positive")
        if not 0.0 <= self.trace_sample_rate <= 1.0:
            raise ValueError("trace_sample_rate must be in [0, 1]")
        if self.trace_capacity <= 0:
            raise ValueError("trace_capacity must be positive")
        if self.trace_tail_capacity < 0:
            raise ValueError("trace_tail_capacity must be >= 0")


def to_json(cfg: Any) -> str:
    return json.dumps(dataclasses.asdict(cfg), sort_keys=True)


def from_json(cls: type, payload: str | Mapping[str, Any]):
    data = json.loads(payload) if isinstance(payload, str) else dict(payload)
    fields = {f.name for f in dataclasses.fields(cls)}
    return cls(**{k: v for k, v in data.items() if k in fields})
