"""The Falcon-H1 family: what a `config.json` of `model_type` `falcon_h1`
means to this program, and the plain reference of its forward pass.

Written from the published description (the `falcon_h1` modelling code of
the transformers library as its `config.json` keys are read there, and the
Mamba-2 paper's state-space duality, arXiv 2405.21060), not from the
program's model code. With `h` the residual stream, `rms` an RMS norm with
a learned scale and `rms_norm_eps`:

- `h = E[token] * embedding_multiplier`; `logits = (rms(h) @ W_head) *
  lm_head_multiplier`.
- A layer, each of `num_hidden_layers` alike: `u = rms(h)`;
  `h = h + ssm_out_multiplier * Mixer(u) + attention_out_multiplier *
  Attn(attention_in_multiplier * u)`; then `h = h + MLP(rms(h))`.
- `Attn(x)`: `q = x W_q`, `k = (x W_k) * key_multiplier`, `v = x W_v`;
  rotary on q and k over the whole head in half-rotation (first half
  against second half), base `rope_theta`; causal softmax at
  `head_dim ** -0.5`, `num_attention_heads / num_key_value_heads` queries a
  key-value head; `W_o`.
- `MLP(x) = ((silu((x W_gate) * mlp_multipliers[0]) * (x W_up)) W_down) *
  mlp_multipliers[1]`.
- `Mixer(u)`: `p = ((u * ssm_in_multiplier) W_in) * m`, the columns of
  `W_in` being the gate `z` (`mamba_d_ssm`), then `x` (`mamba_d_ssm`), `B`
  and `C` (`mamba_n_groups * mamba_d_state` each), then `dt`
  (`mamba_n_heads`), and `m` the vector that holds `ssm_multipliers[0..4]`
  over those five parts in that order. `(x, B, C) = silu(conv(xBC) + b)`:
  depthwise and causal, `mamba_d_conv` taps, the last tap on the token
  itself, zeros before the sequence. `dt = softplus(dt + dt_bias)`,
  `A = -exp(A_log)`, one each a head (the leaf is `A` itself: the last
  departure below). Head `i` reads the `B` and `C` of group
  `i // (heads / groups)`; its state `S_i` is (`mamba_d_head`,
  `mamba_d_state`), zero before the sequence:

      S_i[t] = exp(dt_i[t] A_i) S_i[t-1] + dt_i[t] * outer(x_i[t], B_g[t])
      y_i[t] = S_i[t] C_g[t] + D_i x_i[t]

  `mamba_rms_norm` true and `mamba_norm_before_gate` false:
  `y = rms_groups(y * silu(z))`, the norm over each of the groups' shares
  of the `mamba_d_ssm` channels, one learned scale over them all.
  `Mixer = y W_out`.

The recurrence is the equation above under a `lax.scan` over tokens: no
chunked form, no cache, no batching. The forward makes no discrete choice,
so `gap` is infinite at every position.

Departures, each marked `# departure:` where it is made: none in the
arithmetic; queries go through attention one block at a time and logits
are made when they are asked for and one block of the vocabulary at a time,
because (S, 261,120) float32 logits are 1.07 GB a thousand positions and
the head in float32 is 5.3 GB beside 10.5 GB of weights. And one in what a
leaf holds: the program keeps `A` as it is used (`ssm_a`, a checkpoint's
`-exp(A_log)` taken once at loading, as serving engines hold it), so the
reference reads that leaf as `A` too (`_REMEMBERS` says what the seed
draws for it).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

F32 = jnp.float32

# the CPU rehearsal: the published ratios where they are structure (the
# attention heads' width is half the hidden size, 2 queries a key-value
# head, 2 groups of mixer heads, 4 taps), a scan chunk of 16 so that a
# 256-token prefill chunk holds 16 of them, two layers
TINY = {"hidden_size": 128, "intermediate_size": 192,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
        "mamba_d_ssm": 64, "mamba_n_heads": 4, "mamba_d_head": 16,
        "mamba_d_state": 32, "mamba_n_groups": 2, "mamba_d_conv": 4,
        "mamba_chunk_size": 16, "vocab_size": 512, "num_hidden_layers": 2,
        "reduced_from": {"num_hidden_layers": 72},
        "serving": {"decode_attention_impl": "xla", "dtype": "float32",
                    "param_dtype": "float32"}}

# queries of one block of the reference's attention, and columns of one
# block of its head
_Q_BLOCK = 256
_V_BLOCK = 16320

# The multipliers are those of a maximal-update parametrisation: the
# trained matrices behind them are as large as the multipliers are small.
# `weights.py` draws every leaf with the spread 1/sqrt(fan-in), and under
# such weights the published multipliers leave the logits with a spread of
# 0.0078 (every token as likely as every other: no comparison of
# log-probabilities sees anything), the attention scores with a spread of
# 0.011 (flat attention: a wrong page reads like the right one), and the
# MLP's output at 0.001 of a stream whose embedding is 0.079 and whose
# mixer adds 0.088 a layer. So `fan_in` states for a leaf that stands
# behind a multiplier m the fan-in times m squared (1 at least): the
# product of multiplier and matrix then has the spread 1/sqrt(fan-in), as
# every other family's matrices have, and a program that leaves a
# multiplier out is off by 1/m, which the check sees. The leaf, the key of
# its multiplier, and which of a list's entries. The mixer's input
# projection stands behind `ssm_in_multiplier` and five different
# `ssm_multipliers`; it is drawn for the x part's (0.25 x 0.25): the gate
# and dt then have the spread 1.41, B 0.71 and C 2.
_BEHIND = {"tokens": ("embedding_multiplier", None),
           "kernel": ("lm_head_multiplier", None),
           "wk": ("key_multiplier", None),
           "wo": ("attention_out_multiplier", None),
           "w_gate": ("mlp_multipliers", 0),
           "w_down": ("mlp_multipliers", 1),
           "ssm_in": ("ssm_multipliers", 1),
           "ssm_out": ("ssm_out_multiplier", None)}
# The multipliers `fan_in` reads: the published ones of every
# configuration of this family in the catalog's row, which `model_config`
# checks the file against (a file with other multipliers needs its own
# numbers here, not a silent other draw).
_PUBLISHED = {"embedding_multiplier": 5.656854249492381,
              "lm_head_multiplier": 0.0078125,
              "key_multiplier": 0.011048543456039804,
              "attention_out_multiplier": 0.0375,
              "mlp_multipliers": (0.1767766952966369, 0.011160714285714284),
              "ssm_in_multiplier": 0.25,
              "ssm_multipliers": (0.3535533905932738, 0.25,
                                  0.1767766952966369, 0.5,
                                  0.3535533905932738),
              "ssm_out_multiplier": 0.08838834764831845}

# What the seeded mixer remembers. `weights.py` draws every leaf around
# zero, and the published form of the decay a token, exp(-softplus(dt +
# dt_bias) exp(A_log)), forgets under any such draw: with `dt_bias` and
# `A_log` at the widest spread `weights.py` draws (1), 2.2% of the heads
# hold a tenth of an input after 32 tokens and 0.07% after 128, where a
# trained model (dt 0.001 to 0.1, A -1 to -16) holds one for thousands. A
# check on such weights cannot see how the state is carried: its first run
# on the chip read the state held in bfloat16 as sound, and a state dropped
# between two prefill chunks 2.3 times the sound median (PERF.md 6.1). The
# leaf is `A` itself, though (`ssm_a`, the module's docstring), and a draw
# around zero with the spread 0.001 (`fan_in` states `_REMEMBERS`) gives
# what trained heads have and no zero-mean `A_log` can: exp(dt A) within
# 0.5% of 1 a token. dt = softplus(N(0, 1.41) + N(0, 1)) is 0.88 at the
# mean, so |dt A| is 0.0005 at the median head, 0.0013 at one spread and
# 0.0044 at three: over a request's 700 tokens such heads lose or gain a
# factor of 1.4, 2.4 and 22, over the longest context (2,048) 2.6, 14 and
# e^9, inside float32. Every one of the 192 heads holds a tenth of an input
# after 32 and 128 tokens, 99.9% of them after 512
# (`remembering_share`). Half the seeded heads have A above zero, which no
# checkpoint has (its A is -exp of something): such a head weighs an old
# token a little more than a new one where a trained head weighs it a
# little less. Both forms of the scan take either sign; what the check
# needs of the weights is that a token's trace stays in the state for the
# length of a request, so that a state lost, rounded or handed on wrongly
# shows in every later token.
_REMEMBERS = 1_000_000


def remembering_share(tokens: int, n: int = 100_000, seed: int = 0) -> float:
    """The share of seeded mixer heads whose state still holds a tenth of
    an input after `tokens` tokens: the numbers of the comment above."""
    import numpy as np
    rng = np.random.default_rng(seed)
    a = rng.normal(size=n) / np.sqrt(_REMEMBERS)
    bias = rng.normal(size=n)
    # the head's mean dt over tokens: dt_raw has the spread sqrt(2)
    raw = rng.normal(size=(64, 1)) * np.sqrt(2.0)
    dt = np.log1p(np.exp(raw + bias[None, :])).mean(axis=0)
    return float(np.mean(np.exp(dt * a * tokens) >= 0.1))


def _mixer_sizes(cfg: dict) -> tuple:
    heads, dh = int(cfg["mamba_n_heads"]), int(cfg["mamba_d_head"])
    return (heads, dh, int(cfg["mamba_d_state"]), int(cfg["mamba_n_groups"]),
            int(cfg["mamba_d_conv"]), heads * dh)


def model_config(cfg: dict):
    """The program's ModelConfig from the file's published keys and its
    `serving` options."""
    from cloud_server_tpu.config import ModelConfig
    sv = cfg.get("serving", {})
    heads, dh, n, groups, taps, inner = _mixer_sizes(cfg)
    if cfg.get("mamba_d_ssm", inner) != inner:
        raise ValueError("falcon_h1: mamba_d_ssm is mamba_n_heads x "
                         "mamba_d_head")
    if not cfg["mamba_rms_norm"] or cfg["mamba_norm_before_gate"]:
        raise ValueError("falcon_h1: the program norms the gated output "
                         "(mamba_rms_norm true, mamba_norm_before_gate "
                         "false)")
    if not cfg["mamba_conv_bias"]:
        raise ValueError("falcon_h1: the convolution has a bias")
    for key in ("attention_bias", "mlp_bias", "mamba_proj_bias",
                "projectors_bias", "tie_word_embeddings"):
        if cfg.get(key):
            raise ValueError(f"falcon_h1: {key} is not mapped")
    if cfg.get("rope_scaling") or cfg.get("attn_layer_indices"):
        raise ValueError("falcon_h1: rope_scaling and attn_layer_indices "
                         "are not mapped")
    if cfg.get("hidden_act", "silu") != "silu":
        raise ValueError("falcon_h1: hidden_act is silu")
    for key, want in _PUBLISHED.items():
        got = cfg[key]
        got = tuple(got) if isinstance(got, list) else got
        if got != want:
            raise ValueError(
                f"falcon_h1: {key} {got} is not the {want} the family "
                "draws its weights for (_PUBLISHED)")
    return ModelConfig(
        vocab_size=cfg["vocab_size"], embed_dim=cfg["hidden_size"],
        num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        mlp_dim=cfg["intermediate_size"],
        max_seq_len=cfg["max_position_embeddings"],
        rope_theta=float(cfg["rope_theta"]), norm_eps=cfg["rms_norm_eps"],
        tie_embeddings=False,
        dtype=sv.get("dtype", "bfloat16"),
        param_dtype=sv.get("param_dtype", "bfloat16"),
        decode_attention_impl=sv.get("decode_attention_impl", "pallas"),
        kv_cache_dtype=sv.get("kv_cache_dtype", "model"),
        layer_body="parallel_mixer", ssm_heads=heads, ssm_head_dim=dh,
        ssm_state_dim=n, ssm_groups=groups, ssm_conv_width=taps,
        ssm_chunk=cfg["mamba_chunk_size"],
        embedding_multiplier=cfg["embedding_multiplier"],
        lm_head_multiplier=cfg["lm_head_multiplier"],
        attention_in_multiplier=cfg["attention_in_multiplier"],
        attention_out_multiplier=cfg["attention_out_multiplier"],
        key_multiplier=cfg["key_multiplier"],
        ssm_in_multiplier=cfg["ssm_in_multiplier"],
        ssm_out_multiplier=cfg["ssm_out_multiplier"],
        ssm_multipliers=tuple(cfg["ssm_multipliers"]),
        mlp_multipliers=tuple(cfg["mlp_multipliers"]))


def param_shapes(mcfg) -> dict:
    """The leaves of the program module that serves the parallel body."""
    from cloud_server_tpu.models import mixer
    return mixer.param_shapes(mcfg)


def fan_in(path: tuple, shape: tuple) -> int:
    """Inputs summed into one output of the leaf's matmul, 0 for a norm's
    scale (the leaf is all ones); times the square of the multiplier the
    leaf stands behind (`_BEHIND`), 1 at least. Layer leaves lead with the
    layer axis. `dt_bias` and `D` state 1 (the spread 1), `A`
    `_REMEMBERS`, the convolution's taps and bias the taps."""
    name = path[-1]
    if name in ("attn_norm", "mlp_norm", "ssm_norm", "scale"):
        return 0
    if name in ("ssm_dt_bias", "ssm_d"):  # (L, heads)
        return 1
    if name == "ssm_a":  # (L, heads)
        return _REMEMBERS
    if name == "ssm_conv":  # (L, taps, channels)
        return shape[1]
    if name == "ssm_conv_bias":  # (L, channels): as its mamba_d_conv taps
        return 4
    if name == "wo":  # (L, H, Dh, D)
        true = shape[1] * shape[2]
    elif name == "kernel":  # (D, V)
        true = shape[0]
    elif name == "tokens":  # (V, D)
        true = shape[1]
    elif name in ("wq", "wk", "wv"):  # (L, D, heads, Dh)
        true = shape[1]
    elif name in ("w_gate", "w_up", "w_down", "ssm_in", "ssm_out"):
        true = shape[-2]  # (L, in, out)
    else:
        raise KeyError(f"the falcon_h1 family knows no leaf "
                       f"{'/'.join(path)} {shape}")
    if name not in _BEHIND:
        return true
    key, at = _BEHIND[name]
    m = _PUBLISHED[key] if at is None else _PUBLISHED[key][at]
    if name == "ssm_in":
        m *= _PUBLISHED["ssm_in_multiplier"]
    return max(1, round(true * m * m))


def cuts(cfg: dict) -> dict:
    """Every layer is the same parallel block (period 1, no leading dense
    layer); depth alone may be cut: there are no experts, and the
    vocabulary is held whole."""
    return {"depth": "num_hidden_layers", "experts": None, "vocab": None,
            "period": 1, "leading_dense": 0}


def blocks(cfg: dict) -> dict:
    """Every layer is the parallel block: the file as it is drives the
    mixer, attention and the dense MLP."""
    return {"parallel": cfg}


def _rms_norm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(F32)


def _rope(x, theta):
    """x: (S, heads, Dh), positions 0..S-1, half-rotation over the whole
    head: entry i turns against entry i + Dh / 2."""
    s, dh = x.shape[0], x.shape[-1]
    half = dh // 2
    inv = 1.0 / (theta ** (jnp.arange(0, half, dtype=F32) / half))
    ang = jnp.arange(s, dtype=F32)[:, None] * inv[None, :]
    c, sn = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * sn, x2 * c + x1 * sn], axis=-1)


def _attention(x, w, *, theta, key_mult):
    """x: (S, D) -> Attn(x) (S, D)."""
    with jax.default_matmul_precision("highest"):
        q = jnp.einsum("sd,dhk->shk", x, w["wq"].astype(F32))
        k = jnp.einsum("sd,dhk->shk", x, w["wk"].astype(F32)) * key_mult
        v = jnp.einsum("sd,dhk->shk", x, w["wv"].astype(F32))
        q, k = _rope(q, theta), _rope(k, theta)
        s, nh, dh = q.shape
        per = nh // k.shape[1]
        k, v = jnp.repeat(k, per, axis=1), jnp.repeat(v, per, axis=1)
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
            scores = jnp.einsum("shk,thk->hst", qb, k) * dh ** -0.5
            scores = jnp.where(mask[None], scores, -jnp.inf)
            probs = jax.nn.softmax(scores, axis=-1)
            return jnp.einsum("hst,thk->shk", probs, v)

        o = jax.lax.map(rows, jnp.arange(0, s + pad, blk))
        o = o.reshape((s + pad,) + v.shape[1:])[:s]
        return jnp.einsum("shk,hkd->sd", o, w["wo"].astype(F32))


def _mixer(u, w, *, eps, heads, dh, n, groups, in_mult, mults):
    """u: (S, D) the normed stream -> Mixer(u) (S, inner) before W_out's
    multiplier: the recurrence token by token from a zero state."""
    inner, gn = heads * dh, groups * n
    with jax.default_matmul_precision("highest"):
        m = jnp.concatenate([jnp.full((size,), mult, F32) for size, mult in
                             zip((inner, inner, gn, gn, heads), mults)])
        p = ((u * in_mult) @ w["ssm_in"].astype(F32)) * m
        z, xbc, dt = (p[:, :inner], p[:, inner:2 * inner + 2 * gn],
                      p[:, 2 * inner + 2 * gn:])
        taps = w["ssm_conv"].astype(F32)  # (taps, channels)
        k = taps.shape[0]
        padded = jnp.pad(xbc, ((k - 1, 0), (0, 0)))
        s = xbc.shape[0]
        xbc = jax.nn.silu(sum(padded[j:j + s] * taps[j] for j in range(k))
                          + w["ssm_conv_bias"].astype(F32))
        x = xbc[:, :inner].reshape(s, heads, dh)
        per = heads // groups
        b = jnp.repeat(xbc[:, inner:inner + gn].reshape(s, groups, n), per,
                       axis=1)
        c = jnp.repeat(xbc[:, inner + gn:].reshape(s, groups, n), per,
                       axis=1)
        dt = jax.nn.softplus(dt + w["ssm_dt_bias"].astype(F32))  # (S, H)
        # departure: the leaf is A, a checkpoint's -exp(A_log)
        a = w["ssm_a"].astype(F32)  # (H,)
        d = w["ssm_d"].astype(F32)

        def step(state, t):
            x_t, b_t, c_t, dt_t = t
            state = (jnp.exp(dt_t * a)[:, None, None] * state
                     + dt_t[:, None, None] * x_t[:, :, None]
                     * b_t[:, None, :])
            y = jnp.einsum("hpn,hn->hp", state, c_t) + d[:, None] * x_t
            return state, y

        _, y = jax.lax.scan(step, jnp.zeros((heads, dh, n), F32),
                            (x, b, c, dt))
        y = y.reshape(s, inner) * jax.nn.silu(z)
        y = y.reshape(s, groups, inner // groups)
        y = y * jax.lax.rsqrt(jnp.mean(jnp.square(y), axis=-1,
                                       keepdims=True) + eps)
        y = y.reshape(s, inner) * w["ssm_norm"].astype(F32)
        return y @ w["ssm_out"].astype(F32)


def _mlp(x, w, *, gate_mult, down_mult):
    with jax.default_matmul_precision("highest"):
        gate = (x @ w["w_gate"].astype(F32)) * gate_mult
        up = x @ w["w_up"].astype(F32)
        return ((jax.nn.silu(gate) * up) @ w["w_down"].astype(F32)) \
            * down_mult


@partial(jax.jit, static_argnames=(
    "eps", "theta", "heads", "dh", "n", "groups", "attn_in", "attn_out",
    "key_mult", "ssm_in", "ssm_out", "ssm_mults", "mlp_mults"))
def _layer(h, w, *, eps, theta, heads, dh, n, groups, attn_in, attn_out,
           key_mult, ssm_in, ssm_out, ssm_mults, mlp_mults):
    """One parallel block on the stream h (S, D)."""
    u = _rms_norm(h, w["attn_norm"], eps)
    h = (h + ssm_out * _mixer(u, w, eps=eps, heads=heads, dh=dh, n=n,
                              groups=groups, in_mult=ssm_in,
                              mults=ssm_mults)
         + attn_out * _attention(attn_in * u, w, theta=theta,
                                 key_mult=key_mult))
    return h + _mlp(_rms_norm(h, w["mlp_norm"], eps), w,
                    gate_mult=mlp_mults[0], down_mult=mlp_mults[1])


@partial(jax.jit, static_argnames=("eps", "mult"))
def _final(x, scale, head, *, eps, mult):
    """(rows, D) of the final stream -> (rows, V) logits."""
    x = _rms_norm(x, scale, eps)
    v = head.shape[1]
    blk = _V_BLOCK if v % _V_BLOCK == 0 else v

    def cols(v0):
        # departure: one block of the vocabulary's columns at a time; each
        # logit is the same sum
        part = jax.lax.dynamic_slice_in_dim(head, v0, blk, axis=1)
        with jax.default_matmul_precision("highest"):
            return (x @ part.astype(F32)) * mult

    out = jax.lax.map(cols, jnp.arange(0, v, blk))  # (blocks, rows, blk)
    return jnp.moveaxis(out, 0, 1).reshape(x.shape[0], v)


class _Logits:
    """(S, V) float32 logits, held as the (S, D) final stream.

    departure: `reference.teacher_forced` reads only the answer's rows, so
    rows are computed when they are asked for. `logits[a:b]`,
    `np.asarray(logits)` and `.shape` are what an array's would be."""

    def __init__(self, x, scale, head, eps, mult):
        self._x, self._scale, self._head = x, scale, head
        self._eps, self._mult = eps, mult
        self.shape = (x.shape[0], head.shape[1])
        self.dtype = jnp.dtype(F32)

    def __getitem__(self, rows):
        if isinstance(rows, tuple):
            return self[rows[0]][(slice(None),) + rows[1:]]
        x = self._x[rows]
        one = x.ndim == 1
        out = _final(x[None] if one else x, self._scale, self._head,
                     eps=self._eps, mult=self._mult)
        return out[0] if one else out

    def __jax_array__(self):
        return self[:]

    def __array__(self, dtype=None, copy=None):
        import numpy as np
        return np.asarray(self[:], dtype=dtype)


def forward_logits(weights: dict, tokens, cfg: dict):
    """(S,) token ids -> ((S, V) float32 logits, (S,) gap, infinite: the
    forward makes no discrete choice). Of `cfg` it reads the norm's
    epsilon, the rotary base, the mixer's head split and every multiplier;
    every other size is the weights' own."""
    lw = weights["layers"]
    heads, dh, n, groups, _, _ = _mixer_sizes(cfg)
    kw = dict(eps=float(cfg["rms_norm_eps"]), theta=float(cfg["rope_theta"]),
              heads=heads, dh=dh, n=n, groups=groups,
              attn_in=float(cfg["attention_in_multiplier"]),
              attn_out=float(cfg["attention_out_multiplier"]),
              key_mult=float(cfg["key_multiplier"]),
              ssm_in=float(cfg["ssm_in_multiplier"]),
              ssm_out=float(cfg["ssm_out_multiplier"]),
              ssm_mults=tuple(float(v) for v in cfg["ssm_multipliers"]),
              mlp_mults=tuple(float(v) for v in cfg["mlp_multipliers"]))
    x = weights["embed"]["tokens"][jnp.asarray(tokens)].astype(F32) \
        * float(cfg["embedding_multiplier"])
    for i in range(lw["wq"].shape[0]):
        x = _layer(x, {k: w[i] for k, w in lw.items()}, **kw)
    return _Logits(x, weights["final_norm"]["scale"],
                   weights["lm_head"]["kernel"], kw["eps"],
                   float(cfg["lm_head_multiplier"])), \
        jnp.full((x.shape[0],), jnp.inf, F32)
