"""Latent attention (MLA) and the double layer with shortcut experts
(LongCat-Flash), as the paged server runs them.

One layer, input h, for i in (0, 1):

    a = h + MLA_i(rms(h, attn_norm[i]))
    u = rms(a, mlp_norm[i])
    if i == 0:  s = MoE(u)          # the shortcut: `models/moe.py`
    h = a + SwiGLU_i(u)             # dense, `mlp_dim` wide
    if i == 1:  h = h + s

so the experts and the second attention block are independent work. The
walk itself is `inference/paged_engine.forward_sets`; this module holds
what is per token: the low-rank projections, the absorption of the
key/value expansion into the query and the output, the dense halves.

MLA(x): c_q = rms(x W_qa) * sqrt(D / q_lora_rank); q = c_q W_qb as heads
of [q_n (head_dim - rope) ; q_r (rope)], q_r rotated. [c ; k_r] = x W_kva;
c = rms(c) * sqrt(D / kv_lora_rank); k_r rotated, one head shared by all.
[k_n ; v] = c W_kvb a head. The cache holds [c ; k_r]: `cfg.latent_dim`
values a token a block, nothing expanded. It is read in the ABSORBED form:
q~ = q_n W_kvb[keys, head] (kv_lora_rank wide), scores q~ . c + q_r . k_r
over the one shared "key head" [c ; k_r], out = (P c) W_kvb[values,
head]: the values are the keys' first kv_lora_rank entries, which is the
contract `ops.paged_attention` serves as `latent_dv`.

The rotary part is rotated in interleaved pairs (2i, 2i + 1) and stored
with the pairs' first halves before their second halves: queries and keys
share the order, so every score is the interleaved rotation's.

Leaves of `params["layers"]` lead with the layer axis; what a layer has
twice (norms, attention, the dense MLP) is stacked on a second axis of 2.
Serving only: the training scans raise for this model by name.
"""

from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp

from cloud_server_tpu.config import ModelConfig
from cloud_server_tpu.ops import gated, rms_norm
from cloud_server_tpu.ops.rope import rope_frequencies

def param_shapes(cfg: ModelConfig) -> dict[str, Any]:
    L, D, H, V = cfg.num_layers, cfg.embed_dim, cfg.num_heads, cfg.vocab_size
    rq, rkv, dr = cfg.q_lora_rank, cfg.kv_lora_rank, cfg.qk_rope_head_dim
    dq, dv = cfg.head_dim, cfg.v_head_dim or cfg.head_dim
    F, E, Fe, R = (cfg.mlp_dim, cfg.num_experts, cfg.expert_width,
                   cfg.router_width)
    shapes = {
        "embed": {"tokens": (V, D)},
        "layers": {
            "attn_norm": (L, 2, D),
            "mlp_norm": (L, 2, D),
            "wq_a": (L, 2, D, rq),
            "q_norm": (L, 2, rq),
            "wq_b": (L, 2, rq, H, dq),
            "wkv_a": (L, 2, D, rkv + dr),
            "kv_norm": (L, 2, rkv),
            "wkv_b": (L, 2, rkv, H, dq - dr + dv),
            "wo": (L, 2, H, dv, D),
            "ffn_gate": (L, 2, D, F),
            "ffn_up": (L, 2, D, F),
            "ffn_down": (L, 2, F, D),
            "router": (L, D, R),
            "router_bias": (L, R),
            "w_gate": (L, E, D, Fe),
            "w_up": (L, E, D, Fe),
            "w_down": (L, E, Fe, D),
        },
        "final_norm": {"scale": (D,)},
    }
    if not cfg.tie_embeddings:
        shapes["lm_head"] = {"kernel": (D, V)}
    return shapes


def forward_hidden(*_, **__):
    raise NotImplementedError(
        "the double layer with latent attention (layer_body="
        "'double_shortcut') is served by the paged server only: no "
        "training scan, no contiguous cache")


_HALF_LEAVES = ("attn_norm", "mlp_norm", "wq_a", "q_norm", "wq_b", "wkv_a",
                "kv_norm", "wkv_b", "wo", "ffn_gate", "ffn_up", "ffn_down")


def half(layers: dict, layer: int, i: int) -> dict:
    """Half `i` of layer `layer`'s leaves that a layer has twice, each cut
    from the stacked (L, 2, ...) leaf in one static slice: a matmul then
    reads its weights where they lie. Cut in two steps, the layer's pair
    has two readers and XLA writes it out first (288 MB a dense matrix
    at the published widths, six a layer)."""
    return {name: layers[name][layer, i] for name in _HALF_LEAVES}


def rope_table(cfg: ModelConfig, seq_len: int):
    """(cos, sin) of the rotary part alone."""
    return rope_frequencies(cfg.qk_rope_head_dim, seq_len, cfg.rope_theta)


def _rope_pairs(x, cos, sin, positions):
    """Rotate interleaved pairs of x (B, W, ..., rope); the result holds
    the pairs' first halves, then their second halves."""
    c, s = cos[positions], sin[positions]  # (B, W, rope // 2)
    while c.ndim < x.ndim:
        c, s = c[:, :, None], s[:, :, None]
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., 0::2], xf[..., 1::2]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s],
                           axis=-1).astype(x.dtype)


def latent_qkv(h, hp: dict, cfg: ModelConfig, cos, sin, positions):
    """Normed stream h (B, W, D) -> (absorbed queries (B, W, H,
    latent_dim), what the cache holds of these tokens (B, W,
    latent_dim))."""
    dt = cfg.dtype
    rkv, dr = cfg.kv_lora_rank, cfg.qk_rope_head_dim
    dn = cfg.head_dim - dr
    with jax.named_scope("mla_proj"):
        # the scale after each latent norm rides the norm's float32 gain
        cq = rms_norm(h @ hp["wq_a"].astype(dt),
                      hp["q_norm"].astype(jnp.float32)
                      * math.sqrt(cfg.embed_dim / cfg.q_lora_rank),
                      cfg.norm_eps)
        q = jnp.einsum("bwr,rhk->bwhk", cq, hp["wq_b"].astype(dt))
        q_r = _rope_pairs(q[..., dn:], cos, sin, positions)
        ckr = h @ hp["wkv_a"].astype(dt)
        c = rms_norm(ckr[..., :rkv], hp["kv_norm"].astype(jnp.float32)
                     * math.sqrt(cfg.embed_dim / rkv), cfg.norm_eps)
        k_r = _rope_pairs(ckr[..., rkv:], cos, sin, positions)
        # the keys' expansion absorbed into the query
        q_abs = jnp.einsum("bwhn,rhn->bwhr", q[..., :dn],
                           hp["wkv_b"][..., :dn].astype(dt))
        return (jnp.concatenate([q_abs, q_r], axis=-1),
                jnp.concatenate([c, k_r], axis=-1))


def latent_out(x, o_lat, hp: dict, cfg: ModelConfig):
    """x + the attention's output: o_lat (B, W, H, kv_lora_rank), the
    probabilities over the latent vectors, through the values' expansion
    and the output projection."""
    dt = cfg.dtype
    dn = cfg.head_dim - cfg.qk_rope_head_dim
    with jax.named_scope("mla_proj"):
        o = jnp.einsum("bwhr,rhv->bwhv", o_lat,
                       hp["wkv_b"][..., dn:].astype(dt))
        return x + jnp.einsum("bwhv,hvd->bwd", o, hp["wo"].astype(dt))


def dense_mlp(u, hp: dict, cfg: ModelConfig):
    """The half's dense gated MLP on the normed stream u."""
    dt = cfg.dtype
    with jax.named_scope("mlp"):
        act = gated(u @ hp["ffn_gate"].astype(dt),
                    u @ hp["ffn_up"].astype(dt), cfg.mlp_activation)
        return act @ hp["ffn_down"].astype(dt)
