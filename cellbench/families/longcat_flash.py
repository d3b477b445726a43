"""The LongCat-Flash family: what a `config.json` of `model_type`
`longcat_flash` means to this program, and the plain reference of its
forward pass.

Written from the published description (Meituan LongCat-Flash technical
report, arXiv 2509.01322, and the `config.json` keys of
LongCat-Flash-Chat), not from the program's model code:

- `num_layers` double layers. One layer, input h, for i in (0, 1):

      a = h + MLA_i(rms(h, g_in[i]))
      u = rms(a, g_post[i])
      if i == 0:  s = MoE(u)       # the shortcut: the first half's stream
      h = a + SwiGLU_i(u)          # dense, `ffn_hidden_size` wide
      if i == 1:  h = h + s        # added after the second half

- MLA(x): c_q = rms(x W_qa) * sqrt(hidden / q_lora_rank)
  (`mla_scale_q_lora`); q = c_q W_qb as heads of [q_n (qk_nope) ; q_r
  (qk_rope)], q_r rotated. [c ; k_r] = x W_kva; c = rms(c) * sqrt(hidden /
  kv_lora_rank) (`mla_scale_kv_lora`); k_r rotated, one head shared by all.
  [k_n ; v] = c W_kvb a head. Scores (q_n . k_n + q_r . k_r) / sqrt(qk_nope
  + qk_rope), causal softmax, out = P v, then W_o. Written here in the
  EXPANDED form: keys and values of every head are made, nothing is
  absorbed. Rotary in interleaved pairs (2i, 2i + 1), base `rope_theta`.
- MoE(u): p = softmax(u W_r) over `n_routed_experts + zero_expert_num`
  columns in float32; the `moe_topk` experts are the top of p + b (b a
  bias an expert); gate g_e = `routed_scaling_factor` * p_e (p without b,
  not renormalised). A chosen expert e < n_routed_experts adds g_e
  SwiGLU_e(u) (width `expert_ffn_hidden_size`); a chosen e at or past it
  is a zero-computation expert (`zero_expert_type` identity) and adds
  g_e u.

The chip's share. The configuration's file may hold fewer experts than
the router has columns for: `n_routed_experts` is then the experts HELD,
the first of `reduced_from.n_routed_experts` published ones, and the
router, b, `moe_topk` and the identity experts keep their published
width. The reference computes the terms of the held experts and the
identity terms and leaves the absent experts' terms out, as the chip of a
deployment does before the exchange that would bring them; nothing stands
in for them. `forward_logits(..., experts=(lo, hi))` is the share of the
chip that holds experts lo to hi - 1 of weights that have them all (the
tests add the shares up to the uncut layer). A sliced vocabulary is a
smaller vocabulary.

`gap` is the margin of the top-k choice counted only over flips that
change this chip's sum: a held or an identity expert entering or leaving
the chosen set. Flips among absent experts change nothing here.

Departures, each marked `# departure:` where it is made: none in the
arithmetic; queries go through attention one block at a time and logits
are made when they are asked for, because a 9,728-token sequence at the
published widths does not fit the chip otherwise.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

F32 = jnp.float32

# the CPU rehearsal: 4 heads whose key and value widths differ, both
# low-rank widths in the published ratios to the hidden size (a 4th and a
# 12th), 4 experts held of 8 routed and 4 that compute nothing (the router
# 12 wide), 3 a token, two double layers
TINY = {"hidden_size": 96, "ffn_hidden_size": 128,
        "expert_ffn_hidden_size": 32, "num_attention_heads": 4,
        "q_lora_rank": 24, "kv_lora_rank": 8, "qk_nope_head_dim": 16,
        "qk_rope_head_dim": 8, "v_head_dim": 16, "vocab_size": 512,
        "num_layers": 2, "n_routed_experts": 4, "zero_expert_num": 4,
        "moe_topk": 3,
        "reduced_from": {"num_layers": 28, "n_routed_experts": 8,
                         "vocab_size": 4096},
        "serving": {"decode_attention_impl": "xla", "dtype": "float32",
                    "param_dtype": "float32"}}

# queries of one block of the reference's attention: (64, 256, 9728)
# float32 scores are 0.64 GB
_Q_BLOCK = 256

# the router's weights are drawn this many times wider than 1/sqrt(fan-in)
# (`fan_in` states a quarter of its inputs). With 1/sqrt(6144) weights the
# 768 logits have unit spread, the largest probability is about 0.02 and
# every gate about 0.05 to 0.1: the held experts' terms would be a
# hundredth of the dense halves' and no check could see them. A trained
# router is far from flat. At twice the spread the largest probability is
# about 0.1, the twelve gates sum to about 2 and the identity term is most
# of the stream it is added to. At four times the spread (tried first, my
# chip run, PR 45) a gate follows its logit's rounding: probabilities move
# by a tenth under bfloat16 and the sound median read 0.20.
_ROUTER_SHARPER = 2

# `mla_scale_q_lora` and `mla_scale_kv_lora` give each latent vector the
# norm of a hidden-wide vector (sqrt(hidden / rank) after its norm: 2 and
# sqrt(12) at the published ranks), so the matrices that read them are
# drawn for that width: `fan_in` states rank x hidden / rank. Drawn for
# the rank, queries and keys come out 2 and 3.5 times wider, the scores'
# spread is 6 where standard attention starts at 1, nearly all of a row's
# weight lies on one key, and bfloat16's rounding of the scores moves a
# log-probability by 0.2 to 0.4 at the median (my chip run, PR 45: kernel
# and XLA attention alike, 0.18 with float32 activations), which no limit
# could tell from a fault. The published ratios; TINY keeps them.
_LATENT_AS_WIDE = {"wq_b": 4, "wkv_b": 12}

# rows of one expert's stretch in the reference, as a multiple of the even
# share (tokens x experts a token / the router's columns)
_ROWS_OVER_SHARE = 4


def _routed_total(cfg: dict) -> int:
    """The router's columns for experts with weights: the published count
    where the file holds a share, else the file's own."""
    return int(cfg.get("reduced_from", {}).get(
        "n_routed_experts", cfg["n_routed_experts"]))


def model_config(cfg: dict):
    """The program's ModelConfig from the file's published keys and its
    `serving` options."""
    from cloud_server_tpu.config import ModelConfig
    sv = cfg.get("serving", {})
    if cfg.get("attention_method", "MLA") != "MLA":
        raise ValueError("longcat_flash: attention_method is MLA")
    if not (cfg["mla_scale_q_lora"] and cfg["mla_scale_kv_lora"]):
        raise ValueError("longcat_flash: the program scales both latent "
                         "vectors after their norms")
    if cfg["zero_expert_type"] != "identity":
        raise ValueError("longcat_flash: zero experts are identity experts")
    if cfg.get("attention_bias"):
        raise ValueError("longcat_flash: attention_bias is not mapped")
    return ModelConfig(
        vocab_size=cfg["vocab_size"], embed_dim=cfg["hidden_size"],
        num_layers=cfg["num_layers"],
        num_heads=cfg["num_attention_heads"], num_kv_heads=1,
        head_dim=cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"], q_lora_rank=cfg["q_lora_rank"],
        kv_lora_rank=cfg["kv_lora_rank"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        mlp_dim=cfg["ffn_hidden_size"],
        expert_mlp_dim=cfg["expert_ffn_hidden_size"],
        max_seq_len=cfg["max_position_embeddings"],
        rope_theta=float(cfg["rope_theta"]), norm_eps=cfg["rms_norm_eps"],
        tie_embeddings=False,
        dtype=sv.get("dtype", "bfloat16"),
        param_dtype=sv.get("param_dtype", "bfloat16"),
        decode_attention_impl=sv.get("decode_attention_impl", "pallas"),
        kv_cache_dtype=sv.get("kv_cache_dtype", "model"),
        layer_body="double_shortcut",
        num_experts=cfg["n_routed_experts"],
        num_routed_experts=_routed_total(cfg),
        num_zero_experts=cfg["zero_expert_num"],
        num_experts_per_token=cfg["moe_topk"],
        routed_scaling_factor=float(cfg["routed_scaling_factor"]))


def param_shapes(mcfg) -> dict:
    """The leaves of the program module that serves the double layer."""
    from cloud_server_tpu.models import latent
    return latent.param_shapes(mcfg)


def fan_in(path: tuple, shape: tuple) -> int:
    """Inputs summed into one output of the leaf's matmul, 0 for a norm's
    scale and for the router's bias (the leaf is then all ones: the same
    for every expert, which moves no choice, as the zero the configuration
    assumes). Layer leaves lead with the layer axis, what a layer has twice
    with (layer, half), expert leaves with (layer, expert). The router
    states a quarter of its inputs (`_ROUTER_SHARPER`), the matrices that
    read a scaled latent vector the hidden size (`_LATENT_AS_WIDE`)."""
    name = path[-1]
    if name in ("attn_norm", "mlp_norm", "q_norm", "kv_norm", "scale",
                "router_bias"):
        return 0
    if name == "wo":  # (L, 2, H, Dv, D)
        return shape[2] * shape[3]
    if name == "kernel":  # (D, V)
        return shape[0]
    if name == "tokens":  # (V, D)
        return shape[1]
    if name in ("wq_b", "wkv_b"):  # (L, 2, rank, H, Dh)
        return shape[2] * _LATENT_AS_WIDE[name]
    if name == "router":  # (L, D, R)
        return max(1, shape[-2] // _ROUTER_SHARPER ** 2)
    if name in ("wq_a", "wkv_a", "ffn_gate", "ffn_up", "ffn_down",
                "w_gate", "w_up", "w_down"):  # (..., in, out)
        return shape[-2]
    raise KeyError(f"the longcat_flash family knows no leaf "
                   f"{'/'.join(path)} {shape}")


def cuts(cfg: dict) -> dict:
    """Every layer is the same double layer (period 1, no leading dense
    layer); depth, the experts held and the vocabulary may be the chip's
    share."""
    return {"depth": "num_layers", "experts": "n_routed_experts",
            "vocab": "vocab_size", "period": 1, "leading_dense": 0}


def blocks(cfg: dict) -> dict:
    """Every layer is the double layer: the file as it is drives latent
    attention, both dense halves, the held experts and the identity
    experts."""
    return {"shortcut": cfg}


def _rms_norm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(F32)


def _rope(x, theta):
    """x: (S, ..., R), positions 0..S-1, interleaved pairs (2i, 2i + 1)."""
    s, r = x.shape[0], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, r, 2, dtype=F32) / r))
    ang = jnp.arange(s, dtype=F32)[:, None] * inv[None, :]
    ang = ang.reshape((s,) + (1,) * (x.ndim - 2) + (r // 2,))
    c, sn = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * c - x2 * sn, x2 * c + x1 * sn],
                     axis=-1).reshape(x.shape)


def _mla(x, w, *, eps, theta, nope, rope):
    """x: (S, D) the normed stream -> MLA(x) (S, D), expanded form."""
    with jax.default_matmul_precision("highest"):
        d = x.shape[-1]
        wq_a, wq_b = w["wq_a"].astype(F32), w["wq_b"].astype(F32)
        wkv_a, wkv_b = w["wkv_a"].astype(F32), w["wkv_b"].astype(F32)
        rq, rkv = wq_a.shape[1], wkv_b.shape[0]
        c_q = _rms_norm(x @ wq_a, w["q_norm"], eps) * jnp.sqrt(F32(d / rq))
        q = jnp.einsum("sr,rhk->shk", c_q, wq_b)
        q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], theta)], -1)
        ckr = x @ wkv_a
        c = _rms_norm(ckr[:, :rkv], w["kv_norm"], eps) * jnp.sqrt(
            F32(d / rkv))
        k_r = _rope(ckr[:, rkv:], theta)  # (S, rope): one head for all
        kv = jnp.einsum("sr,rhk->shk", c, wkv_b)
        k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(
            k_r[:, None, :], kv.shape[:2] + (rope,))], -1)
        v = kv[..., nope:]
        s, nh, dh = q.shape
        # departure: _Q_BLOCK queries at a time against every key, where
        # the published forward makes one (S, S) matrix a head; the mask
        # and every sum are the same
        blk = min(_Q_BLOCK, s)
        pad = -s % blk
        q = jnp.pad(q, ((0, pad), (0, 0), (0, 0)))

        def rows(q0):
            qb = jax.lax.dynamic_slice_in_dim(q, q0, blk)
            i = (q0 + jnp.arange(blk))[:, None]
            mask = jnp.arange(s)[None, :] <= i
            scores = jnp.einsum("shk,thk->hst", qb, k) / jnp.sqrt(F32(dh))
            scores = jnp.where(mask[None], scores, -jnp.inf)
            probs = jax.nn.softmax(scores, axis=-1)
            return jnp.einsum("hst,thk->shk", probs, v)

        o = jax.lax.map(rows, jnp.arange(0, s + pad, blk))
        o = o.reshape((s + pad,) + v.shape[1:])[:s]
        return jnp.einsum("shk,hkd->sd", o, w["wo"].astype(F32))


def _swiglu(h, w_gate, w_up, w_down):
    with jax.default_matmul_precision("highest"):
        gate = h @ w_gate.astype(F32)
        up = h @ w_up.astype(F32)
        return (jax.nn.silu(gate) * up) @ w_down.astype(F32)


def _moe(u, w, *, top_k, routed, factor, lo, hi):
    """MoE(u) as the chip that holds experts lo to hi - 1 computes it:
    (S, D) -> ((S, D), gap (S,)). `w["w_gate"]` holds the experts lo to
    hi - 1 only, or all `routed` of them."""
    with jax.default_matmul_precision("highest"):
        logits = u @ w["router"].astype(F32)
    p = jax.nn.softmax(logits, axis=-1)
    score = p + w["router_bias"].astype(F32)
    top, idx = jax.lax.top_k(score, top_k)
    gates = factor * jnp.take_along_axis(p, idx, axis=1)
    e = jnp.arange(score.shape[1])
    here = ((e >= lo) & (e < hi)) | (e >= routed)  # held, or identity
    chosen = jnp.any(idx[:, :, None] == e, axis=1)
    inf = jnp.float32(jnp.inf)
    kept = jnp.where(chosen, score, inf)
    left = jnp.where(chosen, -inf, score)
    # a flip changes this chip's sum where the expert that leaves or the
    # one that enters is held or an identity expert
    gap = jnp.minimum(
        jnp.min(jnp.where(here, kept, inf), axis=1) - jnp.max(left, axis=1),
        jnp.min(kept, axis=1) - jnp.max(jnp.where(here, left, -inf), axis=1))
    zero = jnp.sum(jnp.where(idx >= routed, gates, 0.0), axis=1)
    y = zero[:, None] * u
    at = 0 if w["w_gate"].shape[0] == hi - lo else lo

    # departure: an expert runs on the rows that chose it, `rows` of them
    # at most and the chosen ones first (the others in the stretch weigh
    # 0), not on all of them: a sixteenth of the rows where the even share
    # is a sixty-fourth. An expert that more rows chose runs on every row.
    s = u.shape[0]
    rows = min(s, -(-int(_ROWS_OVER_SHARE * s * top_k) // score.shape[1]
                    // 8) * 8)

    def add(j, y):
        mine = idx == lo + j
        weight = jnp.sum(jnp.where(mine, gates, 0.0), axis=1)
        expert = [w[k][at + j] for k in ("w_gate", "w_up", "w_down")]

        def chosen_rows():
            first = jnp.argsort(~jnp.any(mine, axis=1), stable=True)[:rows]
            return y.at[first].add(
                weight[first, None] * _swiglu(u[first], *expert))

        def every_row():
            return y + weight[:, None] * _swiglu(u, *expert)

        if rows >= s:
            return every_row()
        return jax.lax.cond(jnp.sum(mine) <= rows, chosen_rows, every_row)

    return (jax.lax.fori_loop(0, hi - lo, add, y) if hi > lo else y), gap


@partial(jax.jit, static_argnames=("eps", "theta", "nope", "rope", "top_k",
                                   "routed", "factor", "lo", "hi"))
def _layer(h, gap, w, *, eps, theta, nope, rope, top_k, routed, factor,
           lo, hi):
    """One double layer on the stream h (S, D), and the smallest router
    gap so far."""
    s = None
    for i in (0, 1):
        wi = {k: w[k][i] for k in ("wq_a", "q_norm", "wq_b", "wkv_a",
                                   "kv_norm", "wkv_b", "wo")}
        a = h + _mla(_rms_norm(h, w["attn_norm"][i], eps), wi, eps=eps,
                     theta=theta, nope=nope, rope=rope)
        u = _rms_norm(a, w["mlp_norm"][i], eps)
        if i == 0:
            s, g = _moe(u, w, top_k=top_k, routed=routed, factor=factor,
                        lo=lo, hi=hi)
        h = a + _swiglu(u, w["ffn_gate"][i], w["ffn_up"][i],
                        w["ffn_down"][i])
    return h + s, jnp.minimum(gap, g)


@partial(jax.jit, static_argnames=("eps",))
def _final(x, scale, head, *, eps):
    with jax.default_matmul_precision("highest"):
        return _rms_norm(x, scale, eps) @ head.astype(F32)


class _Logits:
    """(S, V) float32 logits, held as the (S, D) final stream.

    departure: `reference.teacher_forced` reads only the answer's rows, so
    rows are computed when they are asked for. `logits[a:b]`,
    `np.asarray(logits)` and `.shape` are what an array's would be."""

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


def forward_logits(weights: dict, tokens, cfg: dict, experts=None):
    """(S,) token ids -> ((S, V) float32 logits, (S,) router gap: the
    smallest over the layers of the choice's margin against a flip that
    changes this chip's sum). Of `cfg` it reads the norm's epsilon, the
    rotary base, the head's split, the experts a token, the scaling factor
    and how many routed experts the router has columns for; every other
    size is the weights' own. `experts`: (lo, hi), the share of the chip
    that holds those of the routed experts; the file's own share, the
    first `n_routed_experts`, without it."""
    lw = weights["layers"]
    lo, hi = experts or (0, int(lw["w_gate"].shape[1]))
    kw = dict(eps=float(cfg["rms_norm_eps"]), theta=float(cfg["rope_theta"]),
              nope=int(cfg["qk_nope_head_dim"]),
              rope=int(cfg["qk_rope_head_dim"]), top_k=int(cfg["moe_topk"]),
              routed=int(lw["router"].shape[2]) - int(cfg["zero_expert_num"]),
              factor=float(cfg["routed_scaling_factor"]), lo=lo, hi=hi)
    x = weights["embed"]["tokens"][jnp.asarray(tokens)].astype(F32)
    gap = jnp.full((x.shape[0],), jnp.inf, F32)
    for i in range(lw["wq_a"].shape[0]):
        x, gap = _layer(x, gap, {k: w[i] for k, w in lw.items()}, **kw)
    return _Logits(x, weights["final_norm"]["scale"],
                   weights["lm_head"]["kernel"], kw["eps"]), gap
