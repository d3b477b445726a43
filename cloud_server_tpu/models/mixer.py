"""The parallel body: a Mamba-2 mixer beside grouped-query attention on one
normed input, then a dense gated MLP (Falcon-H1), as the paged server runs
it.

One layer, input h, with the configuration's multipliers (`ModelConfig`:
each 1 where a model states none):

    u = rms(h, attn_norm)
    h = h + ssm_out_multiplier * Mixer(u)
          + attention_out_multiplier * Attn(attention_in_multiplier * u)
    h = h + MLP(rms(h, mlp_norm))

Attn: q = x W_q, k = (x W_k) * key_multiplier, v = x W_v, rotary over the
whole head, the paged kernels of every other model. MLP(x) = ((silu((x
W_gate) * mlp_multipliers[0]) * (x W_up)) W_down) * mlp_multipliers[1].

Mixer(u): p = ((u * ssm_in_multiplier) W_in) * m, W_in's columns the gate
z, then x, B and C (the convolution's channels), then dt, and m the vector
of `ssm_multipliers` over those five parts. (x, B, C) = silu(conv(xBC) +
bias), depthwise and causal, the last tap on the token itself. dt =
softplus(dt + dt_bias); A is held as it is used (`ssm_a`: a checkpoint's
-exp(A_log), taken once when it is loaded). The scan is `ops/ssd.py`; y = scan
+ D x; y = rms_groups(y * silu(z)) * ssm_norm, the norm over each group's
share of the channels; Mixer = y W_out.

What a slot carries from one call to the next beside its pages is the
mixer's: the recurrent state (heads, head_dim, state_dim) in float32 and
the convolution's last `ssm_conv_width - 1` inputs, one each a layer
(`PagedKVCache.ssm`, `.conv`). A row that stands at position 0 enters with
both zero; a row's padding past its real width advances neither (dt is
masked to 0 there, and the convolution's inputs are taken from before the
real width's end). The walk itself is `inference/paged_engine.forward_sets`;
this module holds what is per token and what is per row set.

Device-trace scopes, inside `ssm/`: `proj` (the two projections), `conv`,
`scan` (the chunked scan or the one-token update), `norm`.

Leaves of `params["layers"]` lead with the layer axis. Serving only.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from cloud_server_tpu.config import ModelConfig
from cloud_server_tpu.ops import apply_rope, gated
from cloud_server_tpu.ops.ssd import ssd_chunked, ssd_step

F32 = jnp.float32


def param_shapes(cfg: ModelConfig) -> dict[str, Any]:
    L, D, H, KH, Dh, F, V = (cfg.num_layers, cfg.embed_dim, cfg.num_heads,
                             cfg.num_kv_heads, cfg.head_dim, cfg.mlp_dim,
                             cfg.vocab_size)
    inner, conv = cfg.ssm_inner, cfg.ssm_conv_dim
    shapes = {
        "embed": {"tokens": (V, D)},
        "layers": {
            "attn_norm": (L, D),
            "mlp_norm": (L, D),
            "wq": (L, D, H, Dh),
            "wk": (L, D, KH, Dh),
            "wv": (L, D, KH, Dh),
            "wo": (L, H, Dh, D),
            "w_gate": (L, D, F),
            "w_up": (L, D, F),
            "w_down": (L, F, D),
            # columns: gate z, then x, B, C, then dt
            "ssm_in": (L, D, inner + conv + cfg.ssm_heads),
            # tap k multiplies the input ssm_conv_width - 1 - k tokens back
            "ssm_conv": (L, cfg.ssm_conv_width, conv),
            "ssm_conv_bias": (L, conv),
            "ssm_dt_bias": (L, cfg.ssm_heads),
            # A itself, a checkpoint's -exp(A_log)
            "ssm_a": (L, cfg.ssm_heads),
            "ssm_d": (L, cfg.ssm_heads),
            "ssm_norm": (L, inner),
            "ssm_out": (L, inner, D),
        },
        "final_norm": {"scale": (D,)},
    }
    if not cfg.tie_embeddings:
        shapes["lm_head"] = {"kernel": (D, V)}
    return shapes


def state_shapes(cfg: ModelConfig, slots: int) -> tuple:
    """(recurrent state, convolution inputs) of one layer for `slots`
    slots: (slots, heads, head_dim, state_dim) float32 and (slots, taps - 1,
    channels) in the model's dtype, the channels on the lanes."""
    return ((slots, cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state_dim),
            (slots, cfg.ssm_conv_width - 1, cfg.ssm_conv_dim))


def attention_qkv(u, lp, cfg: ModelConfig, cos, sin, positions):
    """The normed stream u (B, W, D) -> rotated q, k and v."""
    dt = cfg.dtype
    x = u * cfg.attention_in_multiplier
    q = jnp.einsum("bsd,dhk->bshk", x, lp["wq"].astype(dt))
    # a multiplier meets its product in float32: rounded to the model's
    # dtype first, 0.011 or 0.354 would be off by up to 0.4%, every time
    k = (jnp.einsum("bsd,dhk->bshk", x, lp["wk"].astype(dt),
                    preferred_element_type=F32)
         * cfg.key_multiplier).astype(dt)
    v = jnp.einsum("bsd,dhk->bshk", x, lp["wv"].astype(dt))
    return (apply_rope(q, cos, sin, positions),
            apply_rope(k, cos, sin, positions), v)


def attention_out(o, lp, cfg: ModelConfig):
    return jnp.einsum("bshk,hkd->bsd", o, lp["wo"].astype(cfg.dtype))


def project_in(u, lp, cfg: ModelConfig):
    """u (B, W, D) -> (gate z (B, W, inner), xBC (B, W, conv channels), dt
    (B, W, heads) float32 before its bias and softplus), each part under
    its multiplier."""
    inner, conv = cfg.ssm_inner, cfg.ssm_conv_dim
    gn = cfg.ssm_groups * cfg.ssm_state_dim
    with jax.named_scope("proj"):
        p = jnp.matmul(u * cfg.ssm_in_multiplier,
                       lp["ssm_in"].astype(cfg.dtype),
                       preferred_element_type=F32)
        p = p * np.repeat(np.asarray(cfg.ssm_multipliers, np.float32),
                          (inner, inner, gn, gn, cfg.ssm_heads))
        return (p[..., :inner].astype(cfg.dtype),
                p[..., inner:inner + conv].astype(cfg.dtype),
                p[..., inner + conv:])


def _conv(xbc, held, widths, lp, cfg: ModelConfig):
    """The causal depthwise convolution of a row set: xbc (B, W, C) behind
    the rows' held inputs `held` (B, taps - 1, C). Returns (silu(conv +
    bias) (B, W, C), the inputs to hold next: the taps - 1 before each
    row's real width's end)."""
    k = cfg.ssm_conv_width
    w = xbc.shape[1]
    with jax.named_scope("conv"):
        full = jnp.concatenate([held.astype(xbc.dtype), xbc], axis=1)
        taps = lp["ssm_conv"].astype(F32)
        out = sum(full[:, j:j + w].astype(F32) * taps[j] for j in range(k))
        out = jax.nn.silu(out + lp["ssm_conv_bias"].astype(F32))
        if widths is None:
            keep = full[:, w:]
        else:
            at = widths[:, None] + jnp.arange(k - 1)[None, :]  # (B, taps-1)
            keep = jnp.take_along_axis(full, at[:, :, None], axis=1)
        return out.astype(xbc.dtype), keep


def _split(xbc, dt_raw, widths, lp, cfg: ModelConfig):
    """The convolution's output and the raw dt of a row set as the scan's
    operands: x (B, W, H, P), B and C (B, W, G, N), dt (B, W, H) float32
    with 0 past each row's real width, A (H,)."""
    b_, w = xbc.shape[:2]
    inner, g, n = cfg.ssm_inner, cfg.ssm_groups, cfg.ssm_state_dim
    x = xbc[..., :inner].reshape(b_, w, cfg.ssm_heads, cfg.ssm_head_dim)
    bm = xbc[..., inner:inner + g * n].reshape(b_, w, g, n)
    cm = xbc[..., inner + g * n:].reshape(b_, w, g, n)
    dt = jax.nn.softplus(dt_raw + lp["ssm_dt_bias"].astype(F32))
    if widths is not None:
        dt = jnp.where(jnp.arange(w)[None, :, None] < widths[:, None, None],
                       dt, 0.0)
    return x, bm, cm, dt, lp["ssm_a"].astype(F32)


def _skip(y, x, lp):
    return y + lp["ssm_d"].astype(F32)[:, None] * x.astype(F32)


# rows of one pass of `mix_rows`: a row's state is as large as 2,000 of its
# tokens' activations, so a group of 64 rows (a warm-up's anchors) goes
# through 8 at a time, its temporaries those of 8 rows
_ROW_BLOCK = 8


def mix_rows(xbc, dt_raw, ssm, conv, slots, lengths, widths, lp,
             cfg: ModelConfig):
    """The convolution and the scan of one row set against the layer's
    state pools, rows gathered and scattered by their slots.

    xbc (B, W, C), dt_raw (B, W, H); `ssm` (slots, H, P, N) float32 and
    `conv` (slots, taps - 1, C), the layer's pools; `slots` (B,) the rows'
    slots, any id past the pools' for a row that must leave no trace
    (padding, a dead row): its writes drop. A row at `lengths` 0 enters
    with zero state. Returns (y (B, W, H, P) float32 with the skip term,
    ssm', conv'). More than `_ROW_BLOCK` rows go through in blocks, one
    after another."""
    rows = xbc.shape[0]
    if rows <= _ROW_BLOCK or rows % _ROW_BLOCK:
        return _mix_block(xbc, dt_raw, ssm, conv, slots, lengths, widths,
                          lp, cfg)

    def block(i, carry):
        ssm, conv, ys = carry
        at = i * _ROW_BLOCK

        def take(t):
            return None if t is None else jax.lax.dynamic_slice_in_dim(
                t, at, _ROW_BLOCK)

        y, ssm, conv = _mix_block(take(xbc), take(dt_raw), ssm, conv,
                                  take(slots), take(lengths), take(widths),
                                  lp, cfg)
        return ssm, conv, jax.lax.dynamic_update_slice_in_dim(ys, y, at, 0)

    ys = jnp.zeros(xbc.shape[:2] + (cfg.ssm_heads, cfg.ssm_head_dim), F32)
    ssm, conv, ys = jax.lax.fori_loop(0, rows // _ROW_BLOCK, block,
                                      (ssm, conv, ys))
    return ys, ssm, conv


def _mix_block(xbc, dt_raw, ssm, conv, slots, lengths, widths, lp,
               cfg: ModelConfig):
    n_slots = ssm.shape[0]
    at = jnp.clip(slots, 0, n_slots - 1)
    fresh = lengths == 0
    held = jnp.where(fresh[:, None, None], 0, conv[at])
    xbc, keep = _conv(xbc, held, widths, lp, cfg)
    with jax.named_scope("scan"):
        x, bm, cm, dt, a = _split(xbc, dt_raw, widths, lp, cfg)
        s0 = jnp.where(fresh[:, None, None, None], 0.0, ssm[at])
        y, s1 = ssd_chunked(x, dt, a, bm, cm, s0, cfg.ssm_chunk)
        y = _skip(y, x, lp)
        ssm = ssm.at[slots].set(s1, mode="drop")
    with jax.named_scope("conv"):
        conv = conv.at[slots].set(keep.astype(conv.dtype), mode="drop")
    return y, ssm, conv


def step_slots(xbc, dt_raw, ssm, conv, slots, lengths, lp,
               cfg: ModelConfig):
    """One real token a row (a set without `widths`, one token wide: the
    decode rows): the update runs over the layer's whole pools in place, a
    slot no live row stands on keeping its state bit for bit, so the state
    is read once and written once whatever the rows' number and order.
    Operands as `mix_rows`' with W = 1."""
    n_slots = ssm.shape[0]
    at = jnp.clip(slots, 0, n_slots - 1)
    fresh = lengths == 0
    held = jnp.where(fresh[:, None, None], 0, conv[at])
    xbc, keep = _conv(xbc, held, None, lp, cfg)
    with jax.named_scope("conv"):
        conv = conv.at[slots].set(keep.astype(conv.dtype), mode="drop")
    with jax.named_scope("scan"):
        x, bm, cm, dt, a = _split(xbc, dt_raw, None, lp, cfg)
        # the rows' operands laid out by slot: one small scatter
        h, g = cfg.ssm_heads, cfg.ssm_groups
        p, n = cfg.ssm_head_dim, cfg.ssm_state_dim
        packed = jnp.concatenate(
            [x[:, 0].reshape(-1, h * p).astype(F32),
             bm[:, 0].reshape(-1, g * n).astype(F32),
             cm[:, 0].reshape(-1, g * n).astype(F32), dt[:, 0]], axis=-1)
        by_slot = jnp.zeros((n_slots, packed.shape[1]), F32).at[slots].set(
            packed, mode="drop")
        live = jnp.zeros((n_slots,), bool).at[slots].set(True, mode="drop")
        zeroed = jnp.zeros((n_slots,), bool).at[slots].set(fresh,
                                                           mode="drop")
        cut = (h * p, h * p + g * n, h * p + 2 * g * n)
        y, ssm = ssd_step(
            by_slot[:, :cut[0]].reshape(n_slots, h, p),
            by_slot[:, cut[2]:], a,
            by_slot[:, cut[0]:cut[1]].reshape(n_slots, g, n),
            by_slot[:, cut[1]:cut[2]].reshape(n_slots, g, n),
            jnp.where(zeroed[:, None, None, None], 0.0, ssm), live)
        y = _skip(y[at], x[:, 0], lp)
    return y[:, None], ssm, conv


def project_out(y, z, lp, cfg: ModelConfig):
    """The scan's output y (B, W, H, P) float32 and the gate z (B, W,
    inner) -> Mixer (B, W, D): gate, the norm over each group's channels,
    the output projection."""
    b_, w = z.shape[:2]
    g = cfg.ssm_groups
    with jax.named_scope("norm"):
        y = y.reshape(b_, w, cfg.ssm_inner) * jax.nn.silu(z.astype(F32))
        y = y.reshape(b_, w, g, cfg.ssm_inner // g)
        y = y * jax.lax.rsqrt(jnp.mean(jnp.square(y), axis=-1,
                                       keepdims=True) + cfg.norm_eps)
        y = (y.reshape(b_, w, cfg.ssm_inner)
             * lp["ssm_norm"].astype(F32)).astype(cfg.dtype)
    with jax.named_scope("proj"):
        return y @ lp["ssm_out"].astype(cfg.dtype)


def mlp(h, lp, cfg: ModelConfig):
    """The dense gated MLP on the normed stream h, under its two
    multipliers."""
    dt = cfg.dtype
    m_gate, m_down = cfg.mlp_multipliers
    with jax.named_scope("mlp"):
        gate = jnp.matmul(h, lp["w_gate"].astype(dt),
                          preferred_element_type=F32) * m_gate
        act = gated(gate, h @ lp["w_up"].astype(dt), cfg.mlp_activation)
        return (jnp.matmul(act.astype(dt), lp["w_down"].astype(dt),
                           preferred_element_type=F32) * m_down).astype(dt)
