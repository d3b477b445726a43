"""Autoregressive inference engine: KV cache, prefill + decode, generate.

TPU-first shape discipline: the cache is a static (L, B, max_len, KH, Dh)
buffer; prefill fills the prompt region in one batched pass (full MXU
utilisation), then decode steps run S=1 attention against the cache under a
single `lax.scan` inside one jit — no per-token dispatch, no dynamic
shapes, no host round-trips. Sampling (greedy/temp/top-k/top-p) happens
on-device between steps; finished sequences keep "generating" pad tokens so
shapes stay static (standard SPMD practice).

Ragged batches are first-class: right-pad prompts to a common length and
pass `lengths` (B,) — prefill tracks per-sequence cache lengths, decode
writes each sequence's k/v at its own position and masks attention to the
valid cache region, and RoPE positions are per-sequence. (Causality means
real tokens never attend to the trailing pads, so right-padding is exact.)

The transformer math itself (qkv projection + rope, output projection, MLP,
unembed) is imported from `models.transformer` — the engine owns only the
cache plumbing, so inference can never drift numerically from training.

Sharding: cache heads ride the same `tp` axis as attention weights; batch
rides (dp, fsdp). `generate` is jit-compatible and can be wrapped with
shardings by the serving layer.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from cloud_server_tpu.config import InferConfig, ModelConfig
from cloud_server_tpu.inference.sampling import sample_logits
from cloud_server_tpu.models import transformer
from cloud_server_tpu.ops import causal_attention, rms_norm, rope_table


class KVCache(NamedTuple):
    k: jnp.ndarray  # (L, B, max_len, KH, Dh) — cfg.dtype, or int8 when
    #                 cfg.kv_cache_dtype == "int8"
    v: jnp.ndarray  # (L, B, max_len, KH, Dh)
    length: jnp.ndarray  # (B,) int32 — valid entries per sequence
    # int8 mode only: per-(position, head) absmax scales, else None
    k_scale: jnp.ndarray | None = None  # (L, B, max_len, KH, 1) f32
    v_scale: jnp.ndarray | None = None


def _kv_quant(x: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Symmetric int8 quantization over the last (head_dim) axis.

    Per-(position, head) absmax scaling keeps error ~0.5% while halving
    cache MEMORY vs bf16 — the cap on concurrent slots x context.
    Decode-time cost: the scales fold into attention scores/probs
    (`causal_attention(k_scale=...)` and the paged kernel), so no
    dequantized cache copy is ever materialised; see docs/serving.md for
    the measured throughput numbers."""
    scale = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1,
                    keepdims=True) / 127.0
    scale = jnp.maximum(scale, 1e-8)
    q = jnp.round(x.astype(jnp.float32) / scale)
    return q.astype(jnp.int8), scale


def _kv_dequant(q: jnp.ndarray, scale: jnp.ndarray, dtype) -> jnp.ndarray:
    """Inverse of `_kv_quant` — test/reference use only. The hot paths
    never call it: materialising the dequantized cache costs a full-cache
    HBM round-trip per layer (a measured ~36% of decode throughput), so
    attention instead folds the scales into scores/probs and consumes the
    int8 buffers directly (`causal_attention(k_scale=..., v_scale=...)`)."""
    return (q.astype(jnp.float32) * scale).astype(dtype)


def _mlp_apply(x, lp, cfg: ModelConfig, lora=None, stack=None,
               layer_in=None):
    """Dense or MoE MLP residual block, chosen by cfg.num_experts.

    MoE routing at inference is per-call: prefill routes over the prompt
    batch, each decode step over its B single tokens, and a mixed step
    of the paged server that walks the layers once
    (`paged_engine.forward_sets`) over its chunk tokens and its decode
    rows together. Capacity therefore differs from training's full-batch
    routing — exact parity with the training forward, and of one call
    with two, holds only when nothing drops (generous
    expert_capacity_factor), which is also the sane serving configuration.
    At such a factor (experts / experts per token, or more) a call of
    `moe.grouped_min_tokens(cfg)` tokens or more, a prefill group with the
    decode rows beside it, runs `moe_mlp`'s sorted dispatch, a grouped
    matmul over the T * k rows sorted by expert (each expert's on row
    tiles of its own where the call's shape makes that fewer tiles to
    compute), if the caller unrolls its layers and says
    so: `stack` is (params["layers"], layer index), where the kernel
    finds the experts' weights without a copy. A decode round alone, a
    short chunk, a factor that can drop, a scan over the layers,
    quantized weights and any call under a mesh of several devices run
    the dense one-hot dispatch over E * capacity rows
    (`moe._dispatch_grouped`).

    `lora`: per-row multi-adapter deltas (dense MLP only; the server
    rejects MLP-targeting adapters on MoE bases). `layer_in`: the
    layer's input, for a router that reads it (`moe.moe_mlp_block`).
    """
    if cfg.num_experts >= 2:
        from cloud_server_tpu.models import moe
        x, _ = moe.moe_mlp_block(x, lp, cfg, stack, layer_in)
        return x
    with jax.named_scope("mlp"):
        return transformer.mlp_block(x, lp, cfg, lora=lora)


def init_cache(cfg: ModelConfig, batch: int, max_len: int) -> KVCache:
    transformer.one_stack(cfg, "the contiguous cache of inference.engine")
    if cfg.has_layer_pattern:
        raise ValueError(
            "the contiguous cache of inference.engine holds one kind of "
            "layer; a model with window or position-free layers is served "
            "by the paged server (inference.paged_engine)")
    shape = (cfg.num_layers, batch, max_len, cfg.num_kv_heads, cfg.head_dim)
    if cfg.kv_cache_dtype == "int8":
        sshape = shape[:-1] + (1,)
        return KVCache(k=jnp.zeros(shape, jnp.int8),
                       v=jnp.zeros(shape, jnp.int8),
                       length=jnp.zeros((batch,), jnp.int32),
                       k_scale=jnp.zeros(sshape, jnp.float32),
                       v_scale=jnp.zeros(sshape, jnp.float32))
    if cfg.kv_cache_dtype != "model":
        raise ValueError(
            f"unknown kv_cache_dtype: {cfg.kv_cache_dtype!r} "
            "(expected 'model' or 'int8')")
    dtype = jnp.dtype(cfg.dtype)
    return KVCache(k=jnp.zeros(shape, dtype), v=jnp.zeros(shape, dtype),
                   length=jnp.zeros((batch,), jnp.int32))


# ---------------------------------------------------------------------------
# Prefill
# ---------------------------------------------------------------------------

def prefill(params, tokens: jnp.ndarray, cfg: ModelConfig, cache: KVCache,
            lengths: jnp.ndarray | None = None
            ) -> tuple[jnp.ndarray, KVCache]:
    """Run the prompt (B, P) through the model, populating cache[:, :, :P].

    Args:
      tokens: (B, P) int32, right-padded when ragged.
      lengths: optional (B,) int32 valid prompt lengths (defaults to P).

    Returns (logits at each sequence's last valid position (B, V) f32, cache).
    """
    b, p = tokens.shape
    max_len = cache.k.shape[2]
    cos, sin = rope_table(cfg, max_len)
    x = params["embed"]["tokens"].astype(cfg.dtype)[tokens]
    # honour cfg.attention_impl (flash for long prompts); decode keeps the
    # dense cache path since a single query can't use the blockwise kernel.
    attn_fn = transformer._get_attention_fn(cfg)

    def scan_body(carry, lp):
        x = carry
        q, k, v = transformer.attention_qkv(x, lp, cfg, cos, sin)
        o = attn_fn(q, k, v)
        x = transformer.attention_out(x, o, lp, cfg)
        x = _mlp_apply(x, lp, cfg)
        return x, (k, v)

    x, (ks, vs) = lax.scan(scan_body, x, params["layers"])
    x = rms_norm(x, params["final_norm"]["scale"], cfg.norm_eps)
    if lengths is None:
        lengths = jnp.full((b,), p, jnp.int32)
        x_last = x[:, -1]
    else:
        x_last = x[jnp.arange(b), lengths - 1]
    logits = transformer.unembed(x_last, params, cfg)

    if cfg.kv_cache_dtype == "int8":
        kq, ksc = _kv_quant(ks)
        vq, vsc = _kv_quant(vs)
        return logits, KVCache(
            lax.dynamic_update_slice(cache.k, kq, (0, 0, 0, 0, 0)),
            lax.dynamic_update_slice(cache.v, vq, (0, 0, 0, 0, 0)),
            lengths,
            lax.dynamic_update_slice(cache.k_scale, ksc, (0, 0, 0, 0, 0)),
            lax.dynamic_update_slice(cache.v_scale, vsc, (0, 0, 0, 0, 0)))
    new_k = lax.dynamic_update_slice(cache.k, ks, (0, 0, 0, 0, 0))
    new_v = lax.dynamic_update_slice(cache.v, vs, (0, 0, 0, 0, 0))
    return logits, KVCache(new_k, new_v, lengths)


# ---------------------------------------------------------------------------
# Decode
# ---------------------------------------------------------------------------

def decode_step(params, token: jnp.ndarray, cfg: ModelConfig,
                cache: KVCache) -> tuple[jnp.ndarray, KVCache]:
    """One decode step. token: (B,) int32; sequence i sits at position
    cache.length[i] (per-sequence — ragged batches are handled exactly)."""
    max_len = cache.k.shape[2]
    pos = cache.length  # (B,)
    cos, sin = rope_table(cfg, max_len)
    positions = pos[:, None]  # (B, 1)

    x = params["embed"]["tokens"].astype(cfg.dtype)[token[:, None]]  # (B,1,D)

    int8_kv = cfg.kv_cache_dtype == "int8"
    if cfg.decode_attention_impl == "pallas":
        raise ValueError(
            "the contiguous engine's pallas decode kernel was removed (it "
            "measured slower than XLA at every serving shape); "
            "decode_attention_impl='pallas' selects ops.paged_attention "
            "in the paged serving stack (inference.paged_server) instead")
    if cfg.decode_attention_impl != "xla":
        raise ValueError(
            f"unknown decode_attention_impl: {cfg.decode_attention_impl!r}")

    # int8 caches: scales fold into scores/probs inside the op, so the
    # int8 buffers feed the einsums raw — no dequantized HBM copy.
    def attend(q, k_cache, v_cache, k_scale=None, v_scale=None):
        return causal_attention(q, k_cache, v_cache,
                                q_positions=positions,
                                kv_length=cache.length + 1,
                                k_scale=k_scale, v_scale=v_scale)

    # Unrolled layer loop with in-place slice updates. A lax.scan with the
    # cache as stacked ys re-materialises the full (L, B, S, KH, Dh) k/v
    # buffers every token (~1 GB of pure copies per step at the 330M bench
    # config — measured ~5 ms/step of `copy.*` ops on TPU v5e). Unrolling
    # lets XLA chain donated dynamic-update-slices on the same buffers, so
    # per-step cache traffic is just the (B, 1, KH, Dh) writes plus the
    # attention reads.
    k_all, v_all = cache.k, cache.v
    ks_all, vs_all = cache.k_scale, cache.v_scale
    batch_idx = jnp.arange(token.shape[0])
    for layer_idx in range(cfg.num_layers):
        lp = jax.tree.map(lambda w: w[layer_idx], params["layers"])
        q, k, v = transformer.attention_qkv(x, lp, cfg, cos, sin, positions)
        # scatter the new (B, KH, Dh) entries straight into the stacked
        # cache — no read-modify-write of the whole 32MB layer slice
        if int8_kv:
            kq, ksc = _kv_quant(k[:, 0])
            vq, vsc = _kv_quant(v[:, 0])
            k_all = k_all.at[layer_idx, batch_idx, pos].set(kq)
            v_all = v_all.at[layer_idx, batch_idx, pos].set(vq)
            ks_all = ks_all.at[layer_idx, batch_idx, pos].set(ksc)
            vs_all = vs_all.at[layer_idx, batch_idx, pos].set(vsc)
            o = attend(q, k_all[layer_idx], v_all[layer_idx],
                       ks_all[layer_idx], vs_all[layer_idx])
        else:
            k_all = k_all.at[layer_idx, batch_idx, pos].set(k[:, 0])
            v_all = v_all.at[layer_idx, batch_idx, pos].set(v[:, 0])
            o = attend(q, k_all[layer_idx], v_all[layer_idx])
        x = transformer.attention_out(x, o, lp, cfg)
        x = _mlp_apply(x, lp, cfg, stack=(params["layers"], layer_idx))
    x = rms_norm(x, params["final_norm"]["scale"], cfg.norm_eps)
    logits = transformer.unembed(x[:, 0], params, cfg)
    return logits, KVCache(k_all, v_all, cache.length + 1, ks_all, vs_all)


def verify_step(params, tokens: jnp.ndarray, cfg: ModelConfig,
                cache: KVCache) -> tuple[jnp.ndarray, KVCache]:
    """Process a (B, K) window of tokens starting at each sequence's
    current cache position in ONE forward pass, returning logits at every
    window position — the target-model half of speculative decoding
    (score K draft tokens for the price of one memory-bound pass).

    Sequence i's window occupies positions [length[i], length[i] + K); its
    kv entries are written into the cache, but `length` is NOT advanced —
    the caller commits however many positions verification accepts (stale
    entries beyond the commit point are masked by `kv_length` and
    overwritten by later writes at the same positions, so rollback is just
    "don't advance").

    Returns (logits (B, K, V) f32, cache with entries written).
    """
    b, kk = tokens.shape
    max_len = cache.k.shape[2]
    cos, sin = rope_table(cfg, max_len)
    pos = cache.length[:, None] + jnp.arange(kk)[None, :]  # (B, K)

    x = params["embed"]["tokens"].astype(cfg.dtype)[tokens]  # (B, K, D)
    int8_kv = cfg.kv_cache_dtype == "int8"
    k_all, v_all = cache.k, cache.v
    ks_all, vs_all = cache.k_scale, cache.v_scale
    batch_idx = jnp.arange(b)
    for layer_idx in range(cfg.num_layers):
        lp = jax.tree.map(lambda w: w[layer_idx], params["layers"])
        q, k, v = transformer.attention_qkv(x, lp, cfg, cos, sin, pos)
        scales = {}
        if int8_kv:
            kq, ksc = _kv_quant(k)
            vq, vsc = _kv_quant(v)
            k_all = k_all.at[layer_idx, batch_idx[:, None], pos].set(kq)
            v_all = v_all.at[layer_idx, batch_idx[:, None], pos].set(vq)
            ks_all = ks_all.at[layer_idx, batch_idx[:, None], pos].set(ksc)
            vs_all = vs_all.at[layer_idx, batch_idx[:, None], pos].set(vsc)
            # scales fold into scores/probs inside the op — no (B, max_len,
            # KH, Dh)-sized dequantized copy per layer per round (that copy
            # used to erase int8's memory win on every speculative round
            # and prefix admission)
            scales = dict(k_scale=ks_all[layer_idx],
                          v_scale=vs_all[layer_idx])
        else:
            k_all = k_all.at[layer_idx, batch_idx[:, None], pos].set(k)
            v_all = v_all.at[layer_idx, batch_idx[:, None], pos].set(v)
        # q_positions give the in-window causal structure; kv_length masks
        # both stale cache entries and the other sequences' longer windows.
        o = causal_attention(q, k_all[layer_idx], v_all[layer_idx],
                             q_positions=pos, kv_length=cache.length + kk,
                             **scales)
        x = transformer.attention_out(x, o, lp, cfg)
        x = _mlp_apply(x, lp, cfg, stack=(params["layers"], layer_idx))
    x = rms_norm(x, params["final_norm"]["scale"], cfg.norm_eps)
    logits = transformer.unembed(x, params, cfg)  # (B, K, V)
    return logits, KVCache(k_all, v_all, cache.length, ks_all, vs_all)


# ---------------------------------------------------------------------------
# Embeddings
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("cfg",))
def encode(params, tokens: jnp.ndarray, lengths: jnp.ndarray, *,
           cfg: ModelConfig) -> jnp.ndarray:
    """Sequence embeddings: the decoder run WITHOUT unembedding,
    final-norm hidden states mean-pooled over each sequence's valid
    positions, L2-normalised. tokens: (B, P) right-padded int32;
    lengths: (B,) int32. Returns (B, embed_dim) f32, unit norm.

    Right-padding is exact under causal attention (real positions never
    attend to the trailing pads; pad positions are masked out of the
    pool), so one batched pass serves ragged inputs."""
    b, p = tokens.shape
    cos, sin = rope_table(cfg, p)
    x = params["embed"]["tokens"].astype(cfg.dtype)[tokens]
    attn_fn = transformer._get_attention_fn(cfg)

    def scan_body(x, lp):
        q, k, v = transformer.attention_qkv(x, lp, cfg, cos, sin)
        o = attn_fn(q, k, v)
        x = transformer.attention_out(x, o, lp, cfg)
        x = _mlp_apply(x, lp, cfg)
        return x, None

    x, _ = lax.scan(scan_body, x, params["layers"])
    x = rms_norm(x, params["final_norm"]["scale"], cfg.norm_eps)
    mask = jnp.arange(p)[None, :] < lengths[:, None]
    pooled = (x.astype(jnp.float32) * mask[..., None]).sum(axis=1)
    pooled = pooled / jnp.maximum(lengths[:, None], 1)
    norm = jnp.linalg.norm(pooled, axis=-1, keepdims=True)
    return pooled / jnp.maximum(norm, 1e-9)


# ---------------------------------------------------------------------------
# Generate
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("cfg", "infer_cfg", "max_len"))
def generate(params, prompt: jnp.ndarray, rng: jax.Array, *,
             cfg: ModelConfig, infer_cfg: InferConfig,
             max_len: int | None = None,
             prompt_lengths: jnp.ndarray | None = None) -> jnp.ndarray:
    """Batched generation. prompt: (B, P) int32, right-padded when ragged
    (pass prompt_lengths (B,) for the true lengths).

    Returns (B, max_decode_len) int32. Sequences that hit eos_token_id emit
    pad_token_id afterwards. Runs exactly max_decode_len - 1 decode steps:
    the first token is sampled from prefill logits and the last sampled
    token is never fed back through the model.
    """
    b, p = prompt.shape
    n_new = infer_cfg.max_decode_len
    max_len = max_len or (p + n_new)
    if max_len < p + n_new:
        raise ValueError(
            f"max_len={max_len} < prompt ({p}) + max_decode_len ({n_new}); "
            "the cache would silently wrap")
    cache = init_cache(cfg, b, max_len)
    logits, cache = prefill(params, prompt, cfg, cache, prompt_lengths)

    def step(carry, rng_t):
        logits, cache, done = carry
        tok = sample_logits(logits, rng_t, infer_cfg)
        tok = jnp.where(done, infer_cfg.pad_token_id, tok)
        done = jnp.logical_or(done, tok == infer_cfg.eos_token_id)
        logits, cache = decode_step(params, tok, cfg, cache)
        return (logits, cache, done), tok

    rngs = jax.random.split(rng, n_new)
    done0 = jnp.zeros((b,), bool)
    (logits, _, done), tokens = lax.scan(
        step, (logits, cache, done0), rngs[:-1])
    last = sample_logits(logits, rngs[-1], infer_cfg)
    last = jnp.where(done, infer_cfg.pad_token_id, last)
    tokens = jnp.concatenate([tokens, last[None]], axis=0)
    return tokens.T  # (B, n_new)
