"""Flagship dense decoder-only LM (LLaMA-style: RMSNorm, RoPE, GQA, SwiGLU).

Pure-functional: parameters are a plain dict pytree; `forward` is a pure
function. Layers are *stacked* (leading layer axis on every block parameter)
and executed with `lax.scan`, which keeps compile time O(1) in depth and
lets us apply one remat policy per layer. All heavy math is expressed as
einsums over bfloat16 activations so XLA tiles it onto the MXU.

Logical sharding axes are declared next to each parameter in
`param_logical_axes`; the actual mesh layout comes from
`parallel.sharding.DEFAULT_RULES`.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from cloud_server_tpu.config import ModelConfig
from cloud_server_tpu.ops import (apply_rope, causal_attention, gated,
                                  rms_norm, rope_table)
from cloud_server_tpu.parallel.mesh import kernel_mesh
from cloud_server_tpu.parallel.sharding import constrain

Params = dict

NEG_INF = -1e30  # finite stand-in for -inf (keeps exp/where NaN-free)


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def dense_layer_shapes(cfg: ModelConfig, n_layers: int) -> dict[str, Any]:
    """The leaves of a stack of `n_layers` dense layers: the attention
    block's, with what the model states of it beside q, k, v and o, and a
    dense MLP of `mlp_dim`."""
    L, D, H, KH, Dh, F = (n_layers, cfg.embed_dim, cfg.num_heads,
                          cfg.num_kv_heads, cfg.head_dim, cfg.mlp_dim)
    shapes = {
        "attn_norm": (L, D),
        "mlp_norm": (L, D),
        "wq": (L, D, H, Dh),
        "wk": (L, D, KH, Dh),
        "wv": (L, D, KH, Dh),
        "wo": (L, H, Dh, D),
        "w_gate": (L, D, F),
        "w_up": (L, D, F),
        "w_down": (L, F, D),
    }
    if cfg.qk_norm:
        shapes.update(q_norm=(L, Dh), k_norm=(L, Dh))
    if cfg.attention_gate:
        shapes["wg"] = (L, D, H, Dh)
    if cfg.post_norms:
        shapes.update(attn_post_norm=(L, D), mlp_post_norm=(L, D))
    return shapes


def param_shapes(cfg: ModelConfig) -> dict[str, Any]:
    D, V = cfg.embed_dim, cfg.vocab_size
    shapes = {
        "embed": {"tokens": (V, D)},
        "layers": dense_layer_shapes(cfg, cfg.num_layers),
        "final_norm": {"scale": (D,)},
    }
    if not cfg.tie_embeddings:
        shapes["lm_head"] = {"kernel": (D, V)}
    return shapes


def dense_layer_axes(cfg: ModelConfig) -> dict[str, Any]:
    """`dense_layer_shapes`' leaves as tuples of logical axis names."""
    axes = {
        "attn_norm": ("layers", "norm"),
        "mlp_norm": ("layers", "norm"),
        "wq": ("layers", "embed", "heads", "head_dim"),
        "wk": ("layers", "embed", "kv_heads", "head_dim"),
        "wv": ("layers", "embed", "kv_heads", "head_dim"),
        "wo": ("layers", "heads", "head_dim", "embed"),
        "w_gate": ("layers", "embed", "mlp"),
        "w_up": ("layers", "embed", "mlp"),
        "w_down": ("layers", "mlp", "embed"),
    }
    if cfg.qk_norm:
        axes.update(q_norm=("layers", "norm"), k_norm=("layers", "norm"))
    if cfg.attention_gate:
        axes["wg"] = ("layers", "embed", "heads", "head_dim")
    if cfg.post_norms:
        axes.update(attn_post_norm=("layers", "norm"),
                    mlp_post_norm=("layers", "norm"))
    return axes


def param_logical_axes(cfg: ModelConfig) -> dict[str, Any]:
    """Same structure as params; leaves are tuples of logical axis names."""
    axes = {
        "embed": {"tokens": ("vocab", "embed")},
        "layers": dense_layer_axes(cfg),
        "final_norm": {"scale": ("norm",)},
    }
    if not cfg.tie_embeddings:
        axes["lm_head"] = {"kernel": ("embed", "vocab")}
    return axes


def one_stack(cfg: ModelConfig, what: str) -> None:
    """Raise for `what`, a walker of `params["layers"]` alone, on a model
    whose layers lie in two stacks (`cfg.layer_stack`)."""
    if cfg.num_dense_layers:
        raise ValueError(
            f"{what} walks one stack of layers (params['layers']) and a "
            "model with leading dense layers has them in a stack of their "
            "own before it (params['lead_layers']): not supported for such "
            "a model")


def _fan_in(name: str, cfg: ModelConfig) -> int:
    D, H, KH, Dh, F = (cfg.embed_dim, cfg.num_heads, cfg.num_kv_heads,
                       cfg.head_dim, cfg.mlp_dim)
    table = {"tokens": D, "kernel": D, "wq": D, "wk": D, "wv": D, "wg": D,
             "wo": H * Dh, "w_gate": D, "w_up": D, "w_down": F}
    return table[name]


def init_params(cfg: ModelConfig, rng: jax.Array) -> Params:
    """Truncated-normal init, std 1/sqrt(fan_in); norm scales init to 1."""
    dtype = jnp.dtype(cfg.param_dtype)
    shapes = param_shapes(cfg)
    paths, treedef = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda x: isinstance(x, tuple))
    keys = jax.random.split(rng, len(paths))

    out = []
    for (path, shape), key in zip(paths, keys):
        name = path[-1].key
        path_str = "/".join(p.key for p in path)
        if "norm" in path_str:
            out.append(jnp.ones(shape, dtype))
        else:
            std = 1.0 / math.sqrt(_fan_in(name, cfg))
            out.append(
                (jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)
                 * std).astype(dtype))
    return jax.tree.unflatten(treedef, out)


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def lora_row_delta(h, ab) -> jnp.ndarray:
    """Per-ROW low-rank delta for multi-adapter serving: each batch row
    carries its own (A, B) pair (gathered from a stacked adapter set by
    the row's adapter id). h: (B, S, Din); ab = (a (B, Din, r),
    b (B, r, Dout), scale (B,)) -> (B, S, Dout)."""
    a, b, scale = ab
    z = jnp.einsum("bsd,bdr->bsr", h, a.astype(h.dtype))
    d = jnp.einsum("bsr,bro->bso", z, b.astype(h.dtype))
    return d * scale[:, None, None].astype(h.dtype)


def attention_qkv(x, lp, cfg: ModelConfig, cos, sin, positions=None,
                  lora=None, rope=True):
    """Pre-norm + q/k/v projection + rope. Single source of truth for the
    attention input path — the inference engine's prefill/decode reuse this
    so cached inference can never drift numerically from training.

    `lora` (serving only): {target: (a, b, scale)} per-row adapters —
    deltas land BEFORE rope, exactly where a merged weight would.

    `rope`: whether this layer rotates q and k (`cfg.layer_rope`): a
    bool where the caller unrolls its layers, a traced flag where it
    scans over them."""
    if cfg.layer_body != "single":
        raise NotImplementedError(
            f"layer_body={cfg.layer_body!r} is walked by the paged server "
            "alone (inference/paged_engine.forward_sets): the training "
            "scans and the contiguous cache run single layers and hold no "
            "latent pages and no state a slot")
    h = rms_norm(x, lp["attn_norm"], cfg.norm_eps)
    q = jnp.einsum("bsd,dhk->bshk", h, lp["wq"].astype(cfg.dtype))
    k = jnp.einsum("bsd,dhk->bshk", h, lp["wk"].astype(cfg.dtype))
    v = jnp.einsum("bsd,dhk->bshk", h, lp["wv"].astype(cfg.dtype))
    if lora:
        if "wq" in lora:
            q = q + lora_row_delta(h, lora["wq"]).reshape(q.shape)
        if "wk" in lora:
            k = k + lora_row_delta(h, lora["wk"]).reshape(k.shape)
        if "wv" in lora:
            v = v + lora_row_delta(h, lora["wv"]).reshape(v.shape)
    if cfg.qk_norm:  # a head, over its channels, before the rotation
        q = rms_norm(q, lp["q_norm"], cfg.norm_eps)
        k = rms_norm(k, lp["k_norm"], cfg.norm_eps)
    if rope is False:
        return q, k, v
    qr = apply_rope(q, cos, sin, positions)
    kr = apply_rope(k, cos, sin, positions)
    if rope is True:
        return qr, kr, v
    return jnp.where(rope, qr, q), jnp.where(rope, kr, k), v


def attention_out(x, o, lp, cfg: ModelConfig, lora=None):
    """Output projection + residual add (the attention block's second
    half). `x` is the block's input: under `cfg.attention_gate` its norm,
    the one q, k and v were projected from, is projected once more and the
    kernel's output `o` multiplied by that gate's sigmoid before `wo`;
    under `cfg.post_norms` the block's output is normed before the add."""
    if cfg.attention_gate:
        with jax.named_scope("attn_gate"):
            h = rms_norm(x, lp["attn_norm"], cfg.norm_eps)
            g = jnp.einsum("bsd,dhk->bshk", h, lp["wg"].astype(cfg.dtype))
            o = (o.astype(jnp.float32)
                 * jax.nn.sigmoid(g.astype(jnp.float32))).astype(o.dtype)
    y = jnp.einsum("bshk,hkd->bsd", o, lp["wo"].astype(cfg.dtype))
    if lora and "wo" in lora:
        b_, s_ = o.shape[:2]
        y = y + lora_row_delta(o.reshape(b_, s_, -1), lora["wo"])
    if cfg.post_norms:
        y = rms_norm(y, lp["attn_post_norm"], cfg.norm_eps)
    return x + y


def layer_flags(cfg: ModelConfig):
    """What a scan over the layers carries beside their parameters when
    the layers differ (`cfg.has_layer_pattern`): per layer its window (0
    = every key) and whether it rotates q and k; None otherwise."""
    if not cfg.has_layer_pattern:
        return None
    if cfg.attention_impl != "xla":
        raise ValueError(
            "a pattern of window or position-free layers needs "
            f"attention_impl='xla'; {cfg.attention_impl!r} has no lower "
            "bound of the keys")
    n = range(cfg.num_layers)
    return {"window": jnp.asarray([cfg.layer_window(i) for i in n],
                                  jnp.int32),
            "rope": jnp.asarray([cfg.layer_rope(i) for i in n], bool)}


def _attention_block(x, lp, cfg: ModelConfig, cos, sin, attn_fn,
                     positions=None, flags=None):
    """`flags`: this layer's entry of `layer_flags`, or None."""
    if flags is None:
        q, k, v = attention_qkv(x, lp, cfg, cos, sin, positions)
        o = attn_fn(q, k, v)
    else:
        q, k, v = attention_qkv(x, lp, cfg, cos, sin, positions,
                                rope=flags["rope"])
        o = attn_fn(q, k, v, window=flags["window"])
    return attention_out(x, o, lp, cfg)


def mlp_block(x, lp, cfg: ModelConfig, lora=None):
    h = rms_norm(x, lp["mlp_norm"], cfg.norm_eps)
    gate = jnp.einsum("bsd,df->bsf", h, lp["w_gate"].astype(cfg.dtype))
    up = jnp.einsum("bsd,df->bsf", h, lp["w_up"].astype(cfg.dtype))
    if lora:
        if "w_gate" in lora:
            gate = gate + lora_row_delta(h, lora["w_gate"])
        if "w_up" in lora:
            up = up + lora_row_delta(h, lora["w_up"])
    act = gated(gate, up, cfg.mlp_activation)
    down = jnp.einsum("bsf,fd->bsd", act, lp["w_down"].astype(cfg.dtype))
    if lora and "w_down" in lora:
        down = down + lora_row_delta(act, lora["w_down"])
    if cfg.post_norms:
        down = rms_norm(down, lp["mlp_post_norm"], cfg.norm_eps)
    return x + down


def _unembed_head(params: Params, cfg: ModelConfig) -> jnp.ndarray:
    return (params["embed"]["tokens"].T if cfg.tie_embeddings
            else params["lm_head"]["kernel"])


def unembed(x, params: Params, cfg: ModelConfig) -> jnp.ndarray:
    """Final-norm'd hidden states (..., D) -> softcapped f32 logits (..., V)."""
    head = _unembed_head(params, cfg)
    logits = jnp.einsum("...d,dv->...v", x, head.astype(cfg.dtype),
                        preferred_element_type=jnp.float32)
    return apply_logits_softcap(logits, cfg)


def _block(x, layer_params, cfg: ModelConfig, cos, sin, attn_fn,
           positions=None, flags=None):
    x = _attention_block(x, layer_params, cfg, cos, sin, attn_fn, positions,
                         flags)
    x = mlp_block(x, layer_params, cfg)
    return x


def apply_remat(block, cfg: ModelConfig):
    """Wrap a layer-block fn with the configured remat policy.

    The single policy-selection point for the dense stack, the MoE stack,
    and the pipelined stack — keep them identical. Policies:
      * "none": save everything (no checkpoint).
      * "full": recompute everything.
      * "dots": save matmul outputs AND the flash kernel's (out, lse)
        residuals — pallas calls aren't dots, so without the name policy
        the backward re-runs the whole flash forward just to rebuild them.
      * "attn": save ONLY the flash residuals; recompute everything else
        (incl. the big (B, S, mlp_dim) gate/up tensors, whose dots-policy
        saves can cost more HBM traffic than their recompute FLOPs). Only
        meaningful with attention_impl="flash" — other impls emit no named
        residuals, making this equivalent to "full".
    """
    if cfg.remat == "none":
        return block
    if cfg.remat == "full":
        return jax.checkpoint(block)
    if cfg.remat == "dots":
        return jax.checkpoint(
            block, policy=jax.checkpoint_policies.save_from_both_policies(
                jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
                jax.checkpoint_policies.save_only_these_names(
                    "flash_out", "flash_lse")))
    if cfg.remat == "attn":
        return jax.checkpoint(
            block, policy=jax.checkpoint_policies.save_only_these_names(
                "flash_out", "flash_lse"))
    raise ValueError(f"unknown remat policy: {cfg.remat!r}")


def _get_attention_fn(cfg: ModelConfig, segment_ids=None, mesh=None):
    """The one attention-impl dispatch table, with or without a packed
    segment mask (both callers — plain and packed forward — use this, so
    segment support for a new impl lands everywhere at once). `mesh`
    (parallel.mesh.kernel_mesh(), from callers that trace under plain
    jit) runs the flash kernel per device under shard_map; callers
    already inside a shard_map (the pipeline) pass none."""
    if cfg.attention_impl == "xla":
        if segment_ids is None:
            return causal_attention
        return partial(causal_attention, segment_ids=segment_ids)
    if cfg.attention_impl == "flash":
        from cloud_server_tpu.ops.flash_attention import (
            flash_attention, flash_attention_sharded)
        fn = (flash_attention if mesh is None
              else partial(flash_attention_sharded, mesh=mesh))
        return partial(fn, segment_ids=segment_ids,
                       block_q=cfg.flash_block_q,
                       block_kv=cfg.flash_block_kv)
    if cfg.attention_impl == "ring":
        from cloud_server_tpu.parallel.mesh import current_mesh
        from cloud_server_tpu.parallel.ring_attention import (
            ring_attention_sharded)

        mesh = current_mesh()

        def ring_fn(q, k, v):
            return ring_attention_sharded(q, k, v, mesh,
                                          segment_ids=segment_ids)

        return ring_fn
    if cfg.attention_impl == "ulysses":
        from cloud_server_tpu.parallel.mesh import current_mesh
        from cloud_server_tpu.parallel.ulysses import (
            ulysses_attention_sharded)

        mesh = current_mesh()

        def ulysses_fn(q, k, v):
            return ulysses_attention_sharded(q, k, v, mesh,
                                             segment_ids=segment_ids)

        return ulysses_fn
    raise ValueError(f"unknown attention_impl: {cfg.attention_impl!r}")


def _packed_attention_fn(cfg: ModelConfig, segment_ids):
    """Back-compat alias: the packed variant of the dispatch table."""
    return _get_attention_fn(cfg, segment_ids)


def apply_segment_loss_mask(batch: dict) -> dict:
    """If the batch is packed, fold the segment boundary/padding mask into
    batch['mask'] (shared by the dense and MoE losses). No-op otherwise."""
    seg = batch.get("segment_ids")
    if seg is None:
        return batch
    from cloud_server_tpu.ops.segments import segment_target_mask
    tmask = segment_target_mask(seg)
    if batch.get("mask") is not None:
        tmask = tmask * batch["mask"].astype(tmask.dtype)
    return {**batch, "mask": tmask}


def forward_hidden(params: Params, tokens: jnp.ndarray,
                   cfg: ModelConfig,
                   segment_ids: jnp.ndarray | None = None) -> jnp.ndarray:
    """(B, S) int32 -> final-normed hidden states (B, S, D) in cfg.dtype.

    segment_ids: optional (B, S) packed-sequence ids (data/packing.py) —
    attention becomes block-diagonal causal and RoPE positions restart per
    document, so each packed document sees exactly the math it would see
    alone.
    """
    cos, sin = rope_table(cfg, tokens.shape[1])
    # Unshard the table's embed dim BEFORE the lookup: a tp-sharded D at
    # the gather makes XLA produce a D-sharded (B, S, D) it must then
    # replicate-and-repartition to the batch/sequence layout ("Involuntary
    # full rematerialization" in the SPMD partitioner). One table
    # all-gather per forward is strictly cheaper.
    table = constrain(params["embed"]["tokens"].astype(cfg.dtype),
                      ("vocab", None))
    x = table[tokens]
    # Anchor the residual stream to (batch, sequence, -) so that with
    # sp > 1 every per-position op (norms, MLP, fused CE) computes S/sp per
    # device; only ring attention's shard_map sees the full sequence.
    x = constrain(x, ("batch", "sequence", None))
    positions = None
    if segment_ids is not None:
        from cloud_server_tpu.ops.segments import positions_from_segments
        positions = positions_from_segments(segment_ids)
    attn_fn = _get_attention_fn(cfg, segment_ids, mesh=kernel_mesh())

    block = partial(_block, cfg=cfg, cos=cos, sin=sin, attn_fn=attn_fn,
                    positions=positions)
    block = apply_remat(block, cfg)

    flags = layer_flags(cfg)

    def scan_body(carry, xs):
        if flags is None:
            return block(carry, xs), None
        return block(carry, xs[0], flags=xs[1]), None

    x, _ = lax.scan(scan_body, x, params["layers"] if flags is None
                    else (params["layers"], flags),
                    unroll=cfg.scan_layers_unroll)
    x = constrain(x, ("batch", "sequence", None))
    return rms_norm(x, params["final_norm"]["scale"], cfg.norm_eps)


def forward(params: Params, tokens: jnp.ndarray, cfg: ModelConfig,
            segment_ids: jnp.ndarray | None = None) -> jnp.ndarray:
    """Full-sequence forward pass: (B, S) int32 -> (B, S, V) float32 logits."""
    return unembed(forward_hidden(params, tokens, cfg, segment_ids),
                   params, cfg)


# ---------------------------------------------------------------------------
# Loss
# ---------------------------------------------------------------------------

def apply_logits_softcap(logits: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    if cfg.logits_softcap > 0:
        return cfg.logits_softcap * jnp.tanh(logits / cfg.logits_softcap)
    return logits


def masked_cross_entropy(logits: jnp.ndarray, batch: dict,
                         z_loss_coef: float = 0.0):
    """Shared next-token CE over full-S logits.

    logits: (B, S, V) f32 for the full sequence (the last position is
    dropped here); batch: {"tokens": (B, S), optional "mask": (B, S)}.
    Returns (loss, metrics).
    """
    tokens = batch["tokens"]
    logits = logits[:, :-1]
    targets = tokens[:, 1:]
    mask = batch.get("mask")
    mask = jnp.ones(targets.shape, jnp.float32) if mask is None else (
        mask[:, 1:].astype(jnp.float32))

    logz = jax.nn.logsumexp(logits, axis=-1)
    target_logit = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    nll = logz - target_logit
    denom = jnp.maximum(mask.sum(), 1.0)
    loss = (nll * mask).sum() / denom
    metrics = {"loss": loss, "ppl_log": loss,
               "accuracy": ((logits.argmax(-1) == targets) * mask).sum() / denom}
    if z_loss_coef > 0.0:
        z = (jnp.square(logz) * mask).sum() / denom
        loss = loss + z_loss_coef * z
        metrics["z_loss"] = z
    return loss, metrics


def _chunked_logz_target_argmax(x, head, targets, cfg: ModelConfig):
    """Blockwise-vocab logsumexp + target-logit gather + running argmax.

    x: (B, S, D) activations; head: (D, V); targets: (B, S) int32.
    Returns (logz, target_logit, argmax_idx), each (B, S) f32/f32/int32,
    numerically identical (up to accumulation order) to the dense path —
    without ever materialising (B, S, V) logits. The scan body is
    `jax.checkpoint`ed, so the backward pass also recomputes logits one
    chunk at a time instead of saving them.
    """
    D, V = head.shape
    C = cfg.vocab_chunk
    nc = -(-V // C)
    if nc * C != V:
        head = jnp.pad(head, ((0, 0), (0, nc * C - V)))
    head_c = jnp.moveaxis(head.reshape(D, nc, C), 1, 0)  # (nc, D, C)
    B, S, _ = x.shape

    def body(carry, inp):
        m, l, tgt, bidx = carry
        base, hc = inp
        logits = jnp.einsum("bsd,dc->bsc", x, hc.astype(cfg.dtype),
                            preferred_element_type=jnp.float32)
        logits = apply_logits_softcap(logits, cfg)
        col = base + jax.lax.broadcasted_iota(jnp.int32, (1, 1, C), 2)
        logits = jnp.where(col < V, logits, NEG_INF)  # padded tail
        mc = logits.max(-1)
        # m doubles as the running best-logit, so the argmax update must
        # compare against the pre-update m.
        am = base + jnp.argmax(logits, axis=-1).astype(jnp.int32)
        bidx = jnp.where(mc > m, am, bidx)
        m_new = jnp.maximum(m, mc)
        l = l * jnp.exp(m - m_new) + jnp.exp(
            logits - m_new[..., None]).sum(-1)
        in_chunk = (targets >= base) & (targets < base + C)
        off = jnp.clip(targets - base, 0, C - 1)
        tl = jnp.take_along_axis(logits, off[..., None], axis=-1)[..., 0]
        tgt = jnp.where(in_chunk, tl, tgt)
        return (m_new, l, tgt, bidx), None

    neg = jnp.full((B, S), NEG_INF, jnp.float32)
    init = (neg, jnp.zeros((B, S), jnp.float32), neg,
            jnp.zeros((B, S), jnp.int32))
    bases = jnp.arange(nc, dtype=jnp.int32) * C
    (m, l, tgt, bidx), _ = lax.scan(
        jax.checkpoint(body), init, (bases, head_c))
    return m + jnp.log(l), tgt, bidx


def _shifted_targets_mask(batch: dict):
    """The full-length next-token pairing both hidden-state CE impls
    share: position i predicts token i+1; the last position is masked
    out, so the sequence dim keeps its full (sp-divisible) length."""
    tokens = batch["tokens"]
    targets = jnp.concatenate([tokens[:, 1:], tokens[:, :1]], axis=1)
    mask = batch.get("mask")
    mask = jnp.ones(tokens.shape, jnp.float32) if mask is None else (
        mask.astype(jnp.float32))
    mask = jnp.concatenate(
        [mask[:, 1:], jnp.zeros_like(mask[:, :1])], axis=1)
    return targets, mask


def _stats_loss(logz, target_logit, argmax_idx, targets, mask,
                z_loss_coef: float):
    """(loss, metrics) from per-position CE statistics — the single
    epilogue for every stats-producing CE implementation."""
    nll = logz - target_logit
    denom = jnp.maximum(mask.sum(), 1.0)
    loss = (nll * mask).sum() / denom
    metrics = {"loss": loss, "ppl_log": loss,
               "accuracy": ((argmax_idx == targets) * mask).sum() / denom}
    if z_loss_coef > 0.0:
        z = (jnp.square(logz) * mask).sum() / denom
        loss = loss + z_loss_coef * z
        metrics["z_loss"] = z
    return loss, metrics


def fused_cross_entropy(x, params: Params, batch: dict, cfg: ModelConfig,
                        z_loss_coef: float = 0.0):
    """Next-token CE over final hidden states, chunked over the vocab
    (lax.scan; see `_chunked_logz_target_argmax`). Same contract and
    metrics as `masked_cross_entropy`."""
    targets, mask = _shifted_targets_mask(batch)
    head = _unembed_head(params, cfg)
    logz, target_logit, argmax_idx = _chunked_logz_target_argmax(
        x, head, targets, cfg)
    return _stats_loss(logz, target_logit, argmax_idx, targets, mask,
                       z_loss_coef)


def pallas_cross_entropy(x, params: Params, batch: dict,
                         cfg: ModelConfig, z_loss_coef: float = 0.0):
    """Next-token CE via the fused pallas kernels (ops/fused_ce.py):
    same contract and metrics as `fused_cross_entropy`, but the
    per-row (logz, target_logit, argmax) statistics come out of an
    online-logsumexp kernel — no f32 logits in HBM, and the backward's
    matmuls run in the model dtype (its one (B*S, V) buffer is the
    model-dtype d_logits; see ops/fused_ce.py)."""
    from cloud_server_tpu.ops.fused_ce import (
        fused_ce_stats, fused_ce_stats_sharded)

    b, s = batch["tokens"].shape
    targets, mask = _shifted_targets_mask(batch)
    head = _unembed_head(params, cfg).astype(cfg.dtype)
    mesh = kernel_mesh()
    stats = (fused_ce_stats if mesh is None
             else partial(fused_ce_stats_sharded, mesh=mesh))
    logz, target_logit, argmax_idx = stats(
        x.reshape(b * s, -1), head, targets.reshape(-1))
    return _stats_loss(logz.reshape(b, s), target_logit.reshape(b, s),
                       argmax_idx.reshape(b, s), targets, mask,
                       z_loss_coef)


def hidden_state_loss(x, params: Params, batch: dict, cfg: ModelConfig,
                      z_loss_coef: float = 0.0):
    """Next-token CE from final hidden states — THE dispatch point for
    every hidden-state loss path (dense stack, MoE, pipelined), so a
    ce_impl/vocab_chunk setting can never be silently ignored by one
    of them: ce_impl='pallas' -> fused kernels; vocab_chunk > 0 ->
    scan-chunked; else dense unembed + masked CE."""
    if cfg.ce_impl == "pallas":
        return pallas_cross_entropy(x, params, batch, cfg, z_loss_coef)
    if cfg.vocab_chunk > 0:
        return fused_cross_entropy(x, params, batch, cfg, z_loss_coef)
    logits = unembed(x, params, cfg)
    return masked_cross_entropy(logits, batch, z_loss_coef)


def next_token_loss(params: Params, batch: dict, cfg: ModelConfig,
                    z_loss_coef: float = 0.0):
    """Causal LM loss. batch: {"tokens": (B, S) int32, optional
    "mask": (B, S), optional "segment_ids": (B, S) for packed rows}.

    Predicts tokens[:, 1:] from tokens[:, :-1]. Forward runs on the full S
    (not S-1) so the sequence stays divisible for sp-sharded attention; the
    last position is dropped inside the loss. With cfg.vocab_chunk > 0 the
    logits never materialise (see `fused_cross_entropy`); with
    cfg.ce_impl == "pallas" they never do either, via the fused kernels
    (see `pallas_cross_entropy`). With segment_ids,
    attention/positions follow the packing (see `forward_hidden`) and
    targets crossing a document boundary (or in padding) are masked out
    of the loss.
    """
    seg = batch.get("segment_ids")
    batch = apply_segment_loss_mask(batch)
    if cfg.ce_impl == "pallas" or cfg.vocab_chunk > 0:
        x = forward_hidden(params, batch["tokens"], cfg, segment_ids=seg)
        return hidden_state_loss(x, params, batch, cfg, z_loss_coef)
    logits = forward(params, batch["tokens"], cfg, segment_ids=seg)
    return masked_cross_entropy(logits, batch, z_loss_coef)
