"""The AFMoE family (Arcee Trinity): what a `config.json` of `model_type`
`afmoe` means to this program, and the plain reference of its forward
pass.

Written from the published description (the `config.json` keys of
arcee-ai/Trinity-Mini, the public `afmoe` model of Hugging Face
`transformers` and Arcee's description of AFMoE, 2025-12), not from the
program's model code. `D` is `hidden_size`, `norm` RMSNorm with
`rms_norm_eps`:

- `x = sqrt(D) * embed[token]` (`mup_enabled`); the head is untied;
- attention of layer l: `h = norm_in(x)`; `q = norm_q(W_q h)`,
  `k = norm_k(W_k h)`, each head normed over its `head_dim` channels by one
  scale vector a layer; `v = W_v h`; `g = W_g h`, as wide as q; no bias.
  `layer_types[l] == "sliding_attention"`: q and k are rotated (half-split
  `rotate_half`, base `rope_theta`) and a query at i reads the keys j <= i
  with i - j < `sliding_window`; `"full_attention"`: no rotation, no
  position at all, every key j <= i. `o = softmax(q k^T / sqrt(head_dim))
  v`; `a = W_o (o * sigmoid(g))`; `x = x + norm_post_attn(a)`;
- MLP of layer l: `u = norm_pre_mlp(x)`. For l < `num_dense_layers` a
  SwiGLU of `intermediate_size`. Else `s = sigmoid(W_r u)` in float32 over
  `num_experts`; the `num_experts_per_tok` experts are the top of `s + b`
  (b the balancing bias, a buffer, in the choice only); the gates are
  `route_scale * s_kept / (sum(s_kept) + 1e-20)` (`route_norm`); `m =
  shared(u) + sum_e w_e expert_e(u)`, every expert and the
  `num_shared_experts` shared ones a SwiGLU of `moe_intermediate_size`.
  `n_group` and `topk_group` of 1: the grouped choice is the plain top-k.
  `x = x + norm_post_mlp(m)`;
- `logits = W_head norm_final(x)`.

Departures, each marked `# departure:` where it is made: none in the
arithmetic; three in how the result is held (rows of queries one block at
a time, logits computed when they are asked for, an expert run over the
rows the router sent it and not over all of them), because a 14,848-token
sequence at the published widths does not fit the chip otherwise, and
because every token through all 128 experts is sixteen times the work of
its eight.

The reference is straightforward `jax.numpy` float32 at `highest` matmul
precision, with no kernel, no cache and no batching, one layer and one
expert at a time, each cast to float32 as it is used, so it fits beside
bfloat16 weights that fill half the chip.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

F32 = jnp.float32

# the CPU rehearsal: 8 query heads a key head as published, a window
# shorter than the tiny contexts and no multiple of any page size, both
# dense layers and one whole period behind them, top-3 of 8 experts
TINY = {"hidden_size": 64, "intermediate_size": 96,
        "moe_intermediate_size": 32, "num_attention_heads": 8,
        "num_key_value_heads": 1, "head_dim": 16, "vocab_size": 512,
        "num_hidden_layers": 6, "num_experts": 8, "num_experts_per_tok": 3,
        "sliding_window": 200,
        "serving": {"decode_attention_impl": "xla", "dtype": "float32",
                    "param_dtype": "float32",
                    "expert_capacity_factor": 8 / 3}}

# queries of one block of the reference's attention: (4, 8, 512, 16384)
# float32 scores are 1.07 GB
_Q_BLOCK = 512

# rows of one expert's stretch, as a multiple of the even share (tokens x
# experts a token / experts); a router that sends one expert more gets the
# dense product instead. 3 where SmallThinker's file has 2: an even share
# of 128 experts is half as many rows and spreads wider about its mean
_ROWS_OVER_SHARE = 3.0

_NORMS = ("attn_norm", "attn_post_norm", "mlp_norm", "mlp_post_norm",
          "q_norm", "k_norm", "scale")


def _layer_types(cfg: dict) -> tuple:
    """The first `num_hidden_layers` of the published `layer_types`: the
    file keeps all 32 and a cut in depth reads them off the front."""
    types = tuple(cfg["layer_types"][:int(cfg["num_hidden_layers"])])
    if set(types) - {"sliding_attention", "full_attention"}:
        raise ValueError(f"afmoe: layer_types {sorted(set(types))}")
    return types


def model_config(cfg: dict):
    """The program's ModelConfig from the file's published keys and its
    `serving` options."""
    from cloud_server_tpu.config import ModelConfig
    sv = cfg.get("serving", {})
    if cfg.get("rope_scaling") is not None:
        raise ValueError("afmoe: rope_scaling is not mapped")
    if (cfg["score_func"] != "sigmoid" or not cfg["route_norm"]
            or cfg["hidden_act"] != "silu"):
        raise ValueError("afmoe: the program's sigmoid router renormalises "
                         "the kept scores (route_norm) and the family's "
                         "MLPs are SwiGLU")
    if not (cfg["n_group"] == cfg["topk_group"] == 1):
        raise ValueError("afmoe: a choice by groups of experts is not "
                         "mapped (n_group, topk_group of 1 are the plain "
                         "top-k)")
    window = tuple(t == "sliding_attention" for t in _layer_types(cfg))
    # every layer a leading dense one: the program's dense model, whose
    # one stack has the dense layers' leaves
    experts = ({} if cfg["num_dense_layers"] >= cfg["num_hidden_layers"]
               else dict(
        expert_mlp_dim=cfg["moe_intermediate_size"],
        shared_expert_dim=(cfg["num_shared_experts"]
                           * cfg["moe_intermediate_size"]),
        num_dense_layers=cfg["num_dense_layers"],
        num_experts=cfg["num_experts"],
        num_experts_per_token=cfg["num_experts_per_tok"],
        expert_capacity_factor=sv.get("expert_capacity_factor", 1.25),
        router_score="sigmoid", route_scale=float(cfg["route_scale"])))
    return ModelConfig(
        vocab_size=cfg["vocab_size"], embed_dim=cfg["hidden_size"],
        num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        mlp_dim=cfg["intermediate_size"], **experts,
        max_seq_len=cfg["max_position_embeddings"],
        rope_theta=float(cfg["rope_theta"]), norm_eps=cfg["rms_norm_eps"],
        tie_embeddings=cfg["tie_word_embeddings"],
        embedding_multiplier=(math.sqrt(cfg["hidden_size"])
                              if cfg["mup_enabled"] else 1.0),
        dtype=sv.get("dtype", "bfloat16"),
        param_dtype=sv.get("param_dtype", "bfloat16"),
        decode_attention_impl=sv.get("decode_attention_impl", "pallas"),
        kv_cache_dtype=sv.get("kv_cache_dtype", "model"),
        qk_norm=True, attention_gate=True, post_norms=True,
        sliding_window=cfg["sliding_window"],
        # the window layers are the rotary ones, the full ones carry no
        # position
        window_layout=window, rope_layout=window)


def param_shapes(mcfg) -> dict:
    """The leaves of the program module that serves the block: the expert
    model (a stack of leading dense layers and a stack of expert layers),
    or the dense transformer where every layer is dense."""
    from cloud_server_tpu.models import moe, transformer
    module = moe if mcfg.num_experts >= 2 else transformer
    return module.param_shapes(mcfg)


def fan_in(path: tuple, shape: tuple) -> int:
    """Inputs summed into one output of the leaf's matmul; 0 (the leaf is
    all ones) for a norm's scale and for the router's bias, under which
    the choice is the scores' own. Layer leaves lead with the layer axis,
    expert leaves with (layer, expert)."""
    name = path[-1]
    if name in _NORMS or name == "router_bias":
        return 0
    if name == "wo":  # (L, H, Dh, D)
        return shape[1] * shape[2]
    if name == "kernel":  # (D, V)
        return shape[0]
    if name in ("wq", "wk", "wv", "wg", "tokens"):  # (L, D, H, Dh), (V, D)
        return shape[1]
    if name in ("router", "w_gate", "w_up", "w_down", "shared_w_gate",
                "shared_w_up", "shared_w_down"):  # (..., in, out)
        return shape[-2]
    raise KeyError(f"the afmoe family knows no leaf "
                   f"{'/'.join(path)} {shape}")


def cuts(cfg: dict) -> dict:
    """Two leading dense layers, then a period of four (three window, one
    full); depth, the experts held and the vocabulary may be the chip's
    share."""
    return {"depth": "num_hidden_layers", "experts": "num_experts",
            "vocab": "vocab_size",
            "period": int(cfg["global_attn_every_n_layers"]),
            "leading_dense": int(cfg["num_dense_layers"])}


def blocks(cfg: dict) -> dict:
    """The file as it is drives both kinds of attention, the dense layers
    and the expert layers; with every layer a leading dense one it is the
    family's dense block alone."""
    return {"experts": cfg,
            "dense": {**cfg, "num_dense_layers": cfg["num_hidden_layers"]}}


def _rms_norm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(F32)


def _rope(x, theta):
    """x: (S, H, Dh), positions 0..S-1, half-split rotation."""
    s, _, dh = x.shape
    half = dh // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=F32) / half))
    ang = jnp.arange(s, dtype=F32)[:, None] * inv[None, :]
    c, sn = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * sn, x2 * c + x1 * sn], axis=-1)


def _attention(x, w, *, eps, theta, window):
    """x: (S, D) -> x + norm_post(W_o (attention(norm(x)) * sigmoid(g))).
    `window`: keys a query reads, 0 for all; a window layer rotates q and
    k, a full layer carries no position."""
    with jax.default_matmul_precision("highest"):
        h = _rms_norm(x, w["attn_norm"], eps)
        q = jnp.einsum("sd,dhk->shk", h, w["wq"].astype(F32))
        k = jnp.einsum("sd,dhk->shk", h, w["wk"].astype(F32))
        v = jnp.einsum("sd,dhk->shk", h, w["wv"].astype(F32))
        g = jnp.einsum("sd,dhk->shk", h, w["wg"].astype(F32))
        q = _rms_norm(q, w["q_norm"], eps)  # a head, over its channels
        k = _rms_norm(k, w["k_norm"], eps)
        if window:
            q, k = _rope(q, theta), _rope(k, theta)
        s, nh, dh = q.shape
        nkv = k.shape[1]
        q = q.reshape(s, nkv, nh // nkv, dh)  # 8 query heads a key head
        # departure: _Q_BLOCK queries at a time, against every key (a
        # full layer) or the stretch of keys that holds their windows (a
        # window layer), where the published forward makes one (S, S)
        # matrix a head; the mask and every sum are the same
        blk = min(_Q_BLOCK, s)
        pad = -s % blk
        q = jnp.pad(q, ((0, pad), (0, 0), (0, 0), (0, 0)))
        span = min(s, window - 1 + blk) if window else s

        def rows(q0):
            k0 = jnp.clip(q0 + blk - span, 0, s - span) if window else 0
            qb = jax.lax.dynamic_slice_in_dim(q, q0, blk)
            kb = jax.lax.dynamic_slice_in_dim(k, k0, span)
            vb = jax.lax.dynamic_slice_in_dim(v, k0, span)
            i = (q0 + jnp.arange(blk))[:, None]
            j = (k0 + jnp.arange(span))[None, :]
            mask = j <= i
            if window:
                mask = mask & (i - j < window)
            scores = jnp.einsum("sgrk,tgk->grst", qb, kb) / jnp.sqrt(F32(dh))
            scores = jnp.where(mask[None, None], scores, -jnp.inf)
            probs = jax.nn.softmax(scores, axis=-1)
            return jnp.einsum("grst,tgk->sgrk", probs, vb)

        o = jax.lax.map(rows, jnp.arange(0, s + pad, blk))
        o = o.reshape(s + pad, nh, dh)[:s] * jax.nn.sigmoid(g)
        a = jnp.einsum("shk,hkd->sd", o, w["wo"].astype(F32))
        return x + _rms_norm(a, w["attn_post_norm"], eps)


def _swiglu(h, w_gate, w_up, w_down):
    """One SwiGLU on already-normed h: (S, D) -> (S, D)."""
    with jax.default_matmul_precision("highest"):
        gate = h @ w_gate.astype(F32)
        up = h @ w_up.astype(F32)
        return (jax.nn.silu(gate) * up) @ w_down.astype(F32)


def _route(u, router, bias, *, top_k, norm, scale):
    """From the normed stream u, per token: the `top_k` experts of the
    largest score + bias, (S, k); their gates, the kept scores renormalised
    (`norm`) and scaled, the bias left out, (S, k); and the router's gap,
    (S,): the last kept expert's score + bias minus the first dropped
    one's. A token whose gap is within rounding goes to another expert in
    a lower precision, and its output then differs by far more than
    rounding: `reference.compare` sets such tokens apart."""
    with jax.default_matmul_precision("highest"):
        s = jax.nn.sigmoid(u @ router.astype(F32))
    top, idx = jax.lax.top_k(s + bias.astype(F32), top_k + 1)
    kept = jnp.take_along_axis(s, idx[:, :top_k], axis=1)
    if norm:
        kept = kept / (kept.sum(axis=1, keepdims=True) + 1e-20)
    return idx[:, :top_k], scale * kept, top[:, top_k - 1] - top[:, top_k]


def _experts(h, idx, vals, w_gate, w_up, w_down, *, rows):
    """The layer's routed experts on normed h: (S, D) -> (S, D), each
    token's `idx` (S, k) experts weighted by `vals` (S, k). w_*: (E, in,
    out). One expert at a time, every token through all of its experts."""
    s, d = h.shape
    k, n_experts = idx.shape[1], w_gate.shape[0]
    counts = jnp.sum(idx[:, :, None] == jnp.arange(n_experts), axis=(0, 1))

    def every_row():
        # every token through every expert, weighted 0 where the router
        # did not pick it
        def add(e, y):
            weight = jnp.sum(jnp.where(idx == e, vals, 0.0), axis=1)
            return y + weight[:, None] * _swiglu(h, w_gate[e], w_up[e],
                                                 w_down[e])
        return jax.lax.fori_loop(0, n_experts, add, jnp.zeros_like(h))

    def chosen_rows():
        # departure: an expert runs on the rows that chose it, not on all
        # of them. The (token, expert) pairs are put in the experts'
        # order, so an expert's rows are one stretch of at most `rows`;
        # what it makes of the next expert's rows behind its own is
        # thrown away. Each pair's output is weighted and the k of a
        # token are summed, largest score first.
        order = jnp.argsort(idx.reshape(-1), stable=True)
        starts = jnp.cumsum(counts) - counts
        xs = jnp.pad(jnp.take(h, order // k, axis=0), ((0, rows), (0, 0)))

        def run(e, ys):
            at = (starts[e], 0)
            y = _swiglu(jax.lax.dynamic_slice(xs, at, (rows, d)),
                        w_gate[e], w_up[e], w_down[e])
            own = (jnp.arange(rows) < counts[e])[:, None]
            old = jax.lax.dynamic_slice(ys, at, (rows, d))
            return jax.lax.dynamic_update_slice(
                ys, jnp.where(own, y, old), at)

        ys = jax.lax.fori_loop(0, n_experts, run, jnp.zeros_like(xs))
        ys = ys[:s * k] * jnp.take(vals.reshape(-1), order)[:, None]
        back = jnp.argsort(order)
        return jnp.take(ys, back, axis=0).reshape(s, k, d).sum(axis=1)

    if rows >= s:
        return every_row()
    # dropless by construction: where an expert was chosen by more rows
    # than a stretch holds, the layer takes the dense product
    return jax.lax.cond(jnp.max(counts) <= rows, chosen_rows, every_row)


@partial(jax.jit, static_argnames=("eps", "theta", "window", "top_k",
                                   "norm", "scale", "rows"))
def _layer(x, gap, w, *, eps, theta, window, top_k, norm, scale, rows):
    """One decoder layer on the stream x (S, D), and the smallest router
    gap so far. A leading dense layer has no router among its leaves and
    leaves the gap as it is. One compiled program a sequence length and
    kind of layer."""
    x = _attention(x, w, eps=eps, theta=theta, window=window)
    u = _rms_norm(x, w["mlp_norm"], eps)
    if "router" not in w:
        m = _swiglu(u, w["w_gate"], w["w_up"], w["w_down"])
    else:
        idx, vals, g = _route(u, w["router"], w["router_bias"], top_k=top_k,
                              norm=norm, scale=scale)
        gap = jnp.minimum(gap, g)
        m = (_swiglu(u, w["shared_w_gate"], w["shared_w_up"],
                     w["shared_w_down"])
             + _experts(u, idx, vals, w["w_gate"], w["w_up"], w["w_down"],
                        rows=rows))
    return x + _rms_norm(m, w["mlp_post_norm"], eps), gap


@partial(jax.jit, static_argnames=("eps",))
def _final(x, scale, head, *, eps):
    with jax.default_matmul_precision("highest"):
        return _rms_norm(x, scale, eps) @ head.astype(F32)


class _Logits:
    """(S, V) float32 logits, held as the (S, D) final stream.

    departure: at 14,848 positions of 200,192 words the array is 11.9 GB,
    which does not fit beside the weights; `reference.teacher_forced`
    reads only the answer's rows, so rows are computed when they are
    asked for. `logits[a:b]`, `np.asarray(logits)` and `.shape` are what
    an array's would be."""

    def __init__(self, x, scale, head, eps):
        self._x, self._scale, self._head, self._eps = x, scale, head, eps
        self.shape = (x.shape[0], head.shape[1])
        self.dtype = jnp.dtype(F32)

    def __getitem__(self, rows):
        if isinstance(rows, tuple):
            return self[rows[0]][(slice(None),) + rows[1:]]
        x = self._x[rows]
        if x.ndim == 1:
            return _final(x[None], self._scale, self._head,
                          eps=self._eps)[0]
        return _final(x, self._scale, self._head, eps=self._eps)

    def __jax_array__(self):
        return self[:]

    def __array__(self, dtype=None, copy=None):
        import numpy as np
        return np.asarray(self[:], dtype=dtype)


def forward_logits(weights: dict, tokens, cfg: dict):
    """(S,) token ids -> ((S, V) float32 logits, (S,) router gap: the
    smallest over the expert layers of the last kept against the first
    dropped score + bias). Of `cfg` it reads the norm's epsilon, the
    rotary base, the kinds of layer, the window, the embedding's
    multiplier and the router's count, normalisation and scale; every
    size is the weights' own: a layer is a leading dense one while the
    stack `lead_layers` has one left, and a layer without a router among
    its leaves is dense."""
    eps, theta = float(cfg["rms_norm_eps"]), float(cfg["rope_theta"])
    top_k = int(cfg["num_experts_per_tok"])
    window = int(cfg["sliding_window"])
    lead, lw = weights.get("lead_layers", {}), weights["layers"]
    n_lead = lead["wq"].shape[0] if lead else 0
    x = weights["embed"]["tokens"][jnp.asarray(tokens)].astype(F32)
    if cfg["mup_enabled"]:
        x = x * math.sqrt(x.shape[1])
    gap = jnp.full((x.shape[0],), jnp.inf, F32)
    rows = x.shape[0]
    if "router" in lw:
        share = x.shape[0] * top_k / lw["w_gate"].shape[1]
        rows = min(rows, -(-int(_ROWS_OVER_SHARE * share) // 8) * 8)
    for i, kind in enumerate(_layer_types(cfg)):
        stack, at = (lead, i) if i < n_lead else (lw, i - n_lead)
        x, gap = _layer(
            x, gap, {k: w[at] for k, w in stack.items()}, eps=eps,
            theta=theta, top_k=top_k, norm=bool(cfg["route_norm"]),
            scale=float(cfg["route_scale"]), rows=rows,
            window=window if kind == "sliding_attention" else 0)
    head = (weights["embed"]["tokens"].T if "lm_head" not in weights
            else weights["lm_head"]["kernel"])
    return _Logits(x, weights["final_norm"]["scale"], head, eps), gap
