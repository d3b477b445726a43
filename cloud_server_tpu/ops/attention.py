"""Attention ops (XLA reference path).

Grouped-query causal attention expressed as two large einsums so XLA can map
them straight onto the MXU. Softmax runs in float32 (bfloat16 exp/sum loses
mass at long context). The pallas flash kernel and the ring-attention
sequence-parallel path share this module's conventions:

  q: (B, S, H,  Dh)      k, v: (B, S, KH, Dh)      H = KH * q_per_kv

and return (B, S, H, Dh).
"""

from __future__ import annotations

import jax.numpy as jnp


NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


def causal_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    scale: float | None = None,
    kv_segment_start: int = 0,
    q_positions: jnp.ndarray | None = None,
    kv_length: jnp.ndarray | None = None,
    segment_ids: jnp.ndarray | None = None,
    k_scale: jnp.ndarray | None = None,
    v_scale: jnp.ndarray | None = None,
    window=0,
) -> jnp.ndarray:
    """Causal grouped-query attention, dense XLA implementation.

    Args:
      q: (B, Sq, H, Dh).
      k, v: (B, Skv, KH, Dh) with H a multiple of KH.
      scale: qk scale; defaults to Dh ** -0.5.
      kv_segment_start: absolute position of k[:, 0] (used by ring attention
        where each shard holds a different sequence chunk).
      q_positions: optional (B, Sq) absolute positions of the queries
        (decode-time: a single position per sequence). Defaults to
        arange(Sq) + kv_segment_start... i.e. aligned with the kv chunk.
      kv_length: optional (B,) number of valid kv entries (decode-time
        cache masking). Defaults to all valid.
      segment_ids: optional (B, S) packed-sequence ids (Sq == Skv case):
        attention is additionally masked to same-segment pairs, giving the
        block-diagonal causal structure packed training needs. The causal
        mask itself stays on global row positions (within a segment the
        global and local orders agree; across segments this mask wins).
      window: keys a query reads, its own included: a key at position j
        is kept for a query at i where i - j < window. 0 = every key. An
        int, or an int32 scalar where a scan carries it per layer.
      k_scale, v_scale: optional (B, Skv, KH, 1) f32 absmax scales for an
        int8 k/v (engine `_kv_quant` layout). Dequantization is folded
        into the attention math — scales are per (position, head), so
        `q . (k*ks) == (q . k_int8) * ks` and `sum_s p_s*(v_s*vs_s) ==
        sum_s (p_s*vs_s)*v_int8_s` — which means the int8 cache feeds the
        einsums directly and NO dequantized full-cache copy is ever
        materialised in HBM (the former dequant-then-attend path cost a
        measured ~36% of decode throughput at B=8/S=1024).

    Returns:
      (B, Sq, H, Dh) in q.dtype.
    """
    b, sq, h, dh = q.shape
    _, skv, kh, _ = k.shape
    g = h // kh
    if scale is None:
        scale = dh**-0.5
    if (k_scale is None) != (v_scale is None):
        raise ValueError("k_scale and v_scale must be given together")
    out_dtype = q.dtype
    if k_scale is not None:
        # int8 values are exact in bf16 (|x| <= 127 << 256); the dot runs
        # with f32 accumulation either way.
        k = k.astype(q.dtype)

    qg = q.reshape(b, sq, kh, g, dh)
    # (B, KH, G, Sq, Skv)
    scores = jnp.einsum("bqkgd,bskd->bkgqs", qg, k, preferred_element_type=jnp.float32)
    scores *= scale
    if k_scale is not None:
        scores *= jnp.transpose(k_scale[..., 0], (0, 2, 1))[:, :, None, None, :]

    if q_positions is None:
        q_pos = (jnp.arange(sq) + kv_segment_start)[None, :]  # (1, Sq)
    else:
        q_pos = q_positions  # (B, Sq)
    kv_pos = (jnp.arange(skv) + kv_segment_start)[None, :]  # (1, Skv)

    causal = q_pos[:, :, None] >= kv_pos[:, None, :]  # (B|1, Sq, Skv)
    if not (isinstance(window, int) and window == 0):
        near = q_pos[:, :, None] - kv_pos[:, None, :] < window
        causal = jnp.logical_and(causal, jnp.logical_or(near, window <= 0))
    if kv_length is not None:
        valid = kv_pos < kv_length[:, None]  # (B, Skv)
        causal = jnp.logical_and(causal, valid[:, None, :])
    if segment_ids is not None:
        same = segment_ids[:, :, None] == segment_ids[:, None, :]  # (B,Sq,Skv)
        causal = jnp.logical_and(causal, same)
    scores = jnp.where(causal[:, None, None, :, :], scores, NEG_INF)

    probs = jnp.exp(scores - jnp.max(scores, axis=-1, keepdims=True))
    probs = probs / jnp.sum(probs, axis=-1, keepdims=True)
    if v_scale is not None:
        probs = probs * jnp.transpose(v_scale[..., 0],
                                      (0, 2, 1))[:, :, None, None, :]
        v = v.astype(out_dtype)
    out = jnp.einsum(
        "bkgqs,bskd->bqkgd", probs.astype(out_dtype), v
    )
    return out.reshape(b, sq, h, v.shape[-1])  # values may be narrower
