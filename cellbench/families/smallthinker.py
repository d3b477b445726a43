"""The SmallThinker family: what a `config.json` of `model_type`
`smallthinker` means to this program, and the plain reference of its
forward pass.

Written from the published description (PowerInfer, SmallThinker-21BA3B and
4BA0.6B, 2025-07: the model card's architecture table and the
`config.json` keys), not from the program's model code:

- pre-norm RMSNorm decoder, grouped-query attention without bias and
  without a norm on q or k, untied head;
- `sliding_window_layout[l] == 1`: layer l is a sliding-window layer, a
  query at i reads the keys j <= i with i - j < `sliding_window_size`
  (the query's own key included); 0: the layer reads every key;
- `rope_layout[l] == 1`: layer l rotates q and k (the half-split
  `rotate_half` convention, base `rope_theta`); 0: the layer carries no
  position at all (NoPE). In the published layouts the full layers are the
  position-free ones;
- every layer's MLP is `moe_num_primary_experts` ReGLU experts of width
  `moe_ffn_hidden_size`; the router reads the layer's INPUT, the residual
  stream before the attention norm ("router placed before attention"),
  takes the `moe_num_active_primary_experts` largest logits and weighs them
  by the softmax over those (`moe_primary_router_apply_softmax`,
  `norm_topk_prob`: softmax over all 64 then renormalised over the kept is
  the same numbers); the experts read the post-attention norm. No capacity:
  every token reaches all of its experts. No shared expert, and this
  `config.json` holds no secondary experts and no dense layer.

Departures, each marked `# departure:` where it is made: none in the
arithmetic; three in how the result is held (rows of queries one block at
a time, logits computed when they are asked for, an expert run over the
rows the router sent it and not over all of them), because a 14,848-token
sequence at the published widths does not fit the chip otherwise, and
because every token through all 64 experts is ten times the work of its
six (a layer's experts over 10,240 tokens: 0.34 s dense and expert by
expert from the host, 0.055 s so; my chip run, PR 35), which a run of
360 s cannot give to the two dozen requests the check samples.

The reference is straightforward `jax.numpy` float32 at `highest` matmul
precision, with no kernel, no cache and no batching, one layer and one
expert at a time, each cast to float32 as it is used, so it fits beside
bfloat16 weights that fill half the chip.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

F32 = jnp.float32

# the CPU rehearsal: 7 query heads a key head as published, a window
# shorter than the tiny contexts and no multiple of any page size, two
# whole periods, top-3 of 8 experts
TINY = {"hidden_size": 64, "moe_ffn_hidden_size": 32,
        "num_attention_heads": 7, "num_key_value_heads": 1, "head_dim": 16,
        "vocab_size": 512, "num_hidden_layers": 8,
        "moe_num_primary_experts": 8, "moe_num_active_primary_experts": 3,
        "sliding_window_size": 200,
        "serving": {"decode_attention_impl": "xla", "dtype": "float32",
                    "param_dtype": "float32",
                    "expert_capacity_factor": 8 / 3}}

# queries of one block of the reference's attention: (4, 7, 512, 16384)
# float32 scores are 0.9 GB
_Q_BLOCK = 512

# rows of one expert's stretch, as a multiple of the even share (tokens x
# experts a token / experts): 861 to 1,049 of 10,240 tokens chose an expert
# of a seeded layer where the share is 960 (my chip run, PR 35); a router
# that sends one expert twice its share gets the dense product instead
_ROWS_OVER_SHARE = 2.0


def _layout(cfg: dict, key: str) -> tuple:
    """The first `num_hidden_layers` flags of a published layout: the file
    keeps all 52 and a cut in depth reads whole periods off its front."""
    return tuple(int(f) for f in cfg[key][:int(cfg["num_hidden_layers"])])


def model_config(cfg: dict):
    """The program's ModelConfig from the file's published keys and its
    `serving` options."""
    from cloud_server_tpu.config import ModelConfig
    sv = cfg.get("serving", {})
    if cfg.get("rope_scaling") is not None:
        raise ValueError("smallthinker: rope_scaling is not mapped")
    if not (cfg["moe_primary_router_apply_softmax"]
            and cfg["norm_topk_prob"]):
        raise ValueError("smallthinker: the program's router is softmax "
                         "with the kept gates renormalised")
    return ModelConfig(
        vocab_size=cfg["vocab_size"], embed_dim=cfg["hidden_size"],
        num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        mlp_dim=cfg["moe_ffn_hidden_size"],
        max_seq_len=cfg["max_position_embeddings"],
        rope_theta=float(cfg["rope_theta"]), norm_eps=cfg["rms_norm_eps"],
        tie_embeddings=cfg["tie_word_embeddings"],
        dtype=sv.get("dtype", "bfloat16"),
        param_dtype=sv.get("param_dtype", "bfloat16"),
        decode_attention_impl=sv.get("decode_attention_impl", "pallas"),
        kv_cache_dtype=sv.get("kv_cache_dtype", "model"),
        num_experts=cfg["moe_num_primary_experts"],
        num_experts_per_token=cfg["moe_num_active_primary_experts"],
        expert_capacity_factor=sv.get("expert_capacity_factor", 1.25),
        mlp_activation="relu", router_input="layer_input",
        sliding_window=cfg["sliding_window_size"],
        window_layout=_layout(cfg, "sliding_window_layout"),
        rope_layout=_layout(cfg, "rope_layout"))


def param_shapes(mcfg) -> dict:
    """The leaves of the program's expert model: every layer has the same."""
    from cloud_server_tpu.models import moe
    return moe.param_shapes(mcfg)


def fan_in(path: tuple, shape: tuple) -> int:
    """Inputs summed into one output of the leaf's matmul, 0 for a norm's
    scale. Layer leaves lead with the layer axis, expert leaves with
    (layer, expert).
    Every projection states its true inputs: with them the window-off
    control already fails the check by a factor of 18 (PERF.md 6.1), and
    a sharpened `wq` (a quarter or a sixteenth of its inputs, tried on the
    chip in PR 35) raised the sound reading six- and fifty-fold and the
    control's not at all."""
    name = path[-1]
    if name in ("attn_norm", "mlp_norm", "scale"):
        return 0
    if name == "wo":  # (L, H, Dh, D)
        return shape[1] * shape[2]
    if name == "kernel":  # (D, V)
        return shape[0]
    if name in ("wq", "wk", "wv", "tokens"):  # (L, D, H, Dh), (V, D)
        return shape[1]
    if name in ("router", "w_gate", "w_up", "w_down"):  # (..., in, out)
        return shape[-2]
    raise KeyError(f"the smallthinker family knows no leaf "
                   f"{'/'.join(path)} {shape}")


def cuts(cfg: dict) -> dict:
    """A period of four layers (one full, three window), no leading dense
    layer; depth, the experts held and the vocabulary may be the chip's
    share."""
    return {"depth": "num_hidden_layers",
            "experts": "moe_num_primary_experts",
            "vocab": "vocab_size", "period": 4, "leading_dense": 0}


def blocks(cfg: dict) -> dict:
    """Every layer is an expert layer: the file as it is drives both kinds
    of attention and the experts."""
    return {"experts": cfg}


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


def _attention(x, attn_norm, wq, wk, wv, wo, *, eps, theta, rope, window):
    """x: (S, D) -> x + attention(norm(x)). `rope`: whether the layer
    rotates q and k; `window`: keys a query reads, 0 for all."""
    with jax.default_matmul_precision("highest"):
        h = _rms_norm(x, attn_norm, eps)
        q = jnp.einsum("sd,dhk->shk", h, wq.astype(F32))
        k = jnp.einsum("sd,dhk->shk", h, wk.astype(F32))
        v = jnp.einsum("sd,dhk->shk", h, wv.astype(F32))
        if rope:
            q, k = _rope(q, theta), _rope(k, theta)
        s, nh, dh = q.shape
        nkv = k.shape[1]
        q = q.reshape(s, nkv, nh // nkv, dh)  # 7 query heads a key head
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
        o = o.reshape(s + pad, nh, dh)[:s]
        return x + jnp.einsum("shk,hkd->sd", o, wo.astype(F32))


def _reglu(h, w_gate, w_up, w_down):
    """One ReGLU expert on already-normed h: (S, D) -> (S, D)."""
    with jax.default_matmul_precision("highest"):
        gate = h @ w_gate.astype(F32)
        up = h @ w_up.astype(F32)
        return (jax.nn.relu(gate) * up) @ w_down.astype(F32)


def _route(x, router, *, top_k):
    """From the layer's input x, per token: the `top_k` experts of the
    largest logits, (S, k); their weights, the softmax over those logits,
    (S, k); and the router's gap, (S,): the last kept expert's logit
    minus the first dropped one's. A token whose gap is within rounding
    goes to another expert in a lower precision, and its output then
    differs by far more than rounding: `reference.compare` sets such
    tokens apart."""
    with jax.default_matmul_precision("highest"):
        logits = x @ router.astype(F32)
    top, idx = jax.lax.top_k(logits, top_k + 1)
    return (idx[:, :top_k], jax.nn.softmax(top[:, :top_k], axis=-1),
            top[:, top_k - 1] - top[:, top_k])


def _experts(h, idx, vals, w_gate, w_up, w_down, *, rows):
    """The layer's experts on normed h: (S, D) -> (S, D), each token's
    `idx` (S, k) experts weighted by `vals` (S, k). w_*: (E, in, out).
    One expert at a time, every token through all of its experts."""
    s, d = h.shape
    k, n_experts = idx.shape[1], w_gate.shape[0]
    counts = jnp.sum(idx[:, :, None] == jnp.arange(n_experts), axis=(0, 1))

    def every_row():
        # every token through every expert, weighted 0 where the router
        # did not pick it
        def add(e, y):
            weight = jnp.sum(jnp.where(idx == e, vals, 0.0), axis=1)
            return y + weight[:, None] * _reglu(h, w_gate[e], w_up[e],
                                                w_down[e])
        return jax.lax.fori_loop(0, n_experts, add, jnp.zeros_like(h))

    def chosen_rows():
        # departure: an expert runs on the rows that chose it, not on all
        # of them. The (token, expert) pairs are put in the experts'
        # order, so an expert's rows are one stretch of at most `rows`;
        # what it makes of the next expert's rows behind its own is
        # thrown away. Each pair's output is weighted and the k of a
        # token are summed, largest weight first.
        order = jnp.argsort(idx.reshape(-1), stable=True)
        starts = jnp.cumsum(counts) - counts
        xs = jnp.pad(jnp.take(h, order // k, axis=0), ((0, rows), (0, 0)))

        def run(e, ys):
            at = (starts[e], 0)
            y = _reglu(jax.lax.dynamic_slice(xs, at, (rows, d)),
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


@partial(jax.jit, static_argnames=("eps", "theta", "rope", "window",
                                   "top_k", "rows"))
def _layer(x, gap, w, *, eps, theta, rope, window, top_k, rows):
    """One decoder layer on the stream x (S, D), and the smallest router
    gap so far. One compiled program a sequence length and kind of layer:
    the check meets eight padded lengths in a run, and a dozen small
    programs a length, made as each operation met a new shape, were a
    third of its time."""
    idx, vals, g = _route(x, w["router"], top_k=top_k)  # the layer's INPUT
    x = _attention(x, w["attn_norm"], w["wq"], w["wk"], w["wv"], w["wo"],
                   eps=eps, theta=theta, rope=rope, window=window)
    h = _rms_norm(x, w["mlp_norm"], eps)
    x = x + _experts(h, idx, vals, w["w_gate"], w["w_up"], w["w_down"],
                     rows=rows)
    return x, jnp.minimum(gap, g)


@partial(jax.jit, static_argnames=("eps",))
def _final(x, scale, head, *, eps):
    with jax.default_matmul_precision("highest"):
        return _rms_norm(x, scale, eps) @ head.astype(F32)


class _Logits:
    """(S, V) float32 logits, held as the (S, D) final stream.

    departure: at 14,848 positions of 151,936 words the array is 9 GB,
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
    smallest over the layers of the last kept against the first dropped
    logit). Of `cfg` it reads the norm's epsilon, the rotary base, the two
    layouts, the window and the experts per token; every size is the
    weights' own."""
    eps, theta = float(cfg["rms_norm_eps"]), float(cfg["rope_theta"])
    top_k = int(cfg["moe_num_active_primary_experts"])
    window_l = _layout(cfg, "sliding_window_layout")
    rope_l = _layout(cfg, "rope_layout")
    window = int(cfg["sliding_window_size"])
    lw = weights["layers"]
    x = weights["embed"]["tokens"][jnp.asarray(tokens)].astype(F32)
    gap = jnp.full((x.shape[0],), jnp.inf, F32)
    share = x.shape[0] * top_k / lw["w_gate"].shape[1]
    rows = min(x.shape[0], -(-int(_ROWS_OVER_SHARE * share) // 8) * 8)
    for i in range(lw["wq"].shape[0]):
        x, gap = _layer(x, gap, {k: w[i] for k, w in lw.items()}, eps=eps,
                        theta=theta, top_k=top_k, rows=rows,
                        rope=bool(rope_l[i]),
                        window=window if window_l[i] else 0)
    head = (weights["embed"]["tokens"].T if "lm_head" not in weights
            else weights["lm_head"]["kernel"])
    return _Logits(x, weights["final_norm"]["scale"], head, eps), gap
