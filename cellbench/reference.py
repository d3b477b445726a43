"""The plain reference: a decoder's forward pass in straightforward
`jax.numpy` float32 at `highest` matmul precision, with no kernels, no
cache and no batching, written from the published descriptions:

- Mistral-7B (arXiv 2310.06825; `modeling_mistral.py`): pre-norm RMSNorm,
  grouped-query causal attention with rotary embeddings (the half-split
  `rotate_half` convention), SwiGLU MLP, untied head. No sliding window
  (v0.3 sets none).
- Mixtral-8x7B (arXiv 2401.04088; `modeling_mixtral.py`): the same
  attention; the MLP is 8 SwiGLU experts, the router's softmax over all
  experts, the top 2 kept and renormalised to sum 1, and NO capacity:
  every token reaches both of its experts (dropless).

It takes token ids and the weights made from the seed
(`cellbench/weights.py`), nothing the program made. Layers and experts
are applied one at a time, each cast to float32 as it is used, so the
reference fits beside bfloat16 weights that nearly fill the chip.

`teacher_forced` gives, for a served request, what `correct` compares:
the reference's log-probability of each served token given the prompt
and the served tokens before it, and how far the served token's
reference logit lies under the reference's best.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


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


@partial(jax.jit, static_argnames=("eps", "theta"))
def _attention(x, attn_norm, wq, wk, wv, wo, *, eps, theta):
    """x: (S, D) -> x + attention(norm(x))."""
    with jax.default_matmul_precision("highest"):
        h = _rms_norm(x, attn_norm, eps)
        q = jnp.einsum("sd,dhk->shk", h, wq.astype(F32))
        k = jnp.einsum("sd,dhk->shk", h, wk.astype(F32))
        v = jnp.einsum("sd,dhk->shk", h, wv.astype(F32))
        q, k = _rope(q, theta), _rope(k, theta)
        s, nh, dh = q.shape
        nkv = k.shape[1]
        q = q.reshape(s, nkv, nh // nkv, dh)
        scores = jnp.einsum("sgrk,tgk->grst", q, k) / jnp.sqrt(F32(dh))
        mask = jnp.tril(jnp.ones((s, s), bool))
        scores = jnp.where(mask[None, None], scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        o = jnp.einsum("grst,tgk->sgrk", probs, v).reshape(s, nh, dh)
        return x + jnp.einsum("shk,hkd->sd", o, wo.astype(F32))


@jax.jit
def _swiglu(h, w_gate, w_up, w_down):
    """One SwiGLU MLP on already-normed h: (S, D) -> (S, D)."""
    with jax.default_matmul_precision("highest"):
        gate = h @ w_gate.astype(F32)
        up = h @ w_up.astype(F32)
        return (jax.nn.silu(gate) * up) @ w_down.astype(F32)


@partial(jax.jit, static_argnames=("top_k",))
def _route(h, router, *, top_k):
    """Per-token weight of each expert, (S, E): softmax over all the
    experts, the top `top_k` kept and renormalised, the rest 0. And
    per token the router's gap, (S,): the last kept expert's logit
    minus the first dropped one's. A token whose gap is within rounding
    goes to another expert in a lower precision, and its output then
    differs by far more than rounding: `compare` sets such tokens
    apart."""
    with jax.default_matmul_precision("highest"):
        logits = h @ router.astype(F32)
    probs = jax.nn.softmax(logits, axis=-1)
    vals, idx = jax.lax.top_k(probs, top_k)
    vals = vals / jnp.sum(vals, axis=-1, keepdims=True)
    onehot = jax.nn.one_hot(idx, probs.shape[-1], dtype=F32)  # (S, k, E)
    top = jax.lax.top_k(logits, top_k + 1)[0]
    return (jnp.einsum("sk,ske->se", vals, onehot),
            top[:, top_k - 1] - top[:, top_k])


@partial(jax.jit, static_argnames=("eps",))
def _final(x, scale, head, *, eps):
    with jax.default_matmul_precision("highest"):
        return _rms_norm(x, scale, eps) @ head.astype(F32)


def forward_logits(weights: dict, tokens, spec: dict):
    """(S,) token ids -> ((S, V) float32 logits, (S,) router gap: the
    smallest over the layers, infinite for a dense MLP). `spec`:
    `norm_eps`, `rope_theta`, `num_experts` (0 for a dense MLP),
    `num_experts_per_token`."""
    eps, theta = float(spec["norm_eps"]), float(spec["rope_theta"])
    n_exp = int(spec.get("num_experts", 0))
    lw = weights["layers"]
    x = weights["embed"]["tokens"][jnp.asarray(tokens)].astype(F32)
    gap = jnp.full((x.shape[0],), jnp.inf, F32)
    for i in range(lw["wq"].shape[0]):
        x = _attention(x, lw["attn_norm"][i], lw["wq"][i], lw["wk"][i],
                       lw["wv"][i], lw["wo"][i], eps=eps, theta=theta)
        h = _rms_norm(x, lw["mlp_norm"][i], eps)
        if n_exp >= 2:
            gates, g = _route(h, lw["router"][i],
                              top_k=int(spec["num_experts_per_token"]))
            gap = jnp.minimum(gap, g)
            y = jnp.zeros_like(x)
            for e in range(n_exp):
                # every token through every expert, weighted 0 where
                # the router did not pick it: dropless by construction
                y = y + gates[:, e:e + 1] * _swiglu(
                    h, lw["w_gate"][i, e], lw["w_up"][i, e],
                    lw["w_down"][i, e])
            x = x + y
        else:
            x = x + _swiglu(h, lw["w_gate"][i], lw["w_up"][i],
                            lw["w_down"][i])
    head = (weights["embed"]["tokens"].T if "lm_head" not in weights
            else weights["lm_head"]["kernel"])
    return _final(x, weights["final_norm"]["scale"], head, eps=eps), gap


def teacher_forced(weights: dict, prompt, served, spec: dict,
                   pad_to: int = 1):
    """The reference's view of one served request. Returns
    (logprobs (n,), margins (n,), gaps (n,)) for the n served tokens:
    the reference's log-probability of served token j given prompt +
    served[:j]; the reference's best logit minus the served token's (0
    where the reference would have chosen the same token); and the
    router's gap at the position that predicted it. The sequence is
    padded at its end to a multiple of `pad_to`, which under a causal
    mask changes no position before the padding and keeps the number of
    compiled shapes small."""
    prompt, served = list(prompt), list(served)
    seq = prompt + served[:-1]
    n = len(seq)
    seq = seq + [0] * (-n % max(int(pad_to), 1))
    logits, gap = forward_logits(weights, np.asarray(seq, np.int32), spec)
    rows = logits[len(prompt) - 1:n]  # position p-1+j predicts served[j]
    lps = jax.nn.log_softmax(rows, axis=-1)
    idx = jnp.asarray(served, jnp.int32)[:, None]
    lp = jnp.take_along_axis(lps, idx, axis=-1)[:, 0]
    margin = jnp.max(rows, axis=-1) - jnp.take_along_axis(
        rows, idx, axis=-1)[:, 0]
    return (np.asarray(lp, np.float64), np.asarray(margin, np.float64),
            np.asarray(gap[len(prompt) - 1:n], np.float64))


def teacher_force_all(weights: dict, spec: dict, items, pad_to: int = 1,
                      until: float | None = None) -> tuple[dict, int]:
    """Teacher-force (prompt, served tokens, served log-probabilities)
    items one by one. Stops after the item during which
    `time.monotonic()` passes `until`. Returns ({"served", "ref",
    "margin", "gap", "context"}: one entry per served token, `context`
    the tokens before it; items used)."""
    import time
    out = {k: [] for k in ("served", "ref", "margin", "gap", "context")}
    used = 0
    for prompt, tokens, lps in items:
        lp, mg, gap = teacher_forced(weights, prompt, tokens, spec,
                                     pad_to=pad_to)
        out["served"].extend(lps)
        out["ref"].extend(lp.tolist())
        out["margin"].extend(mg.tolist())
        out["gap"].extend(gap.tolist())
        out["context"].extend(range(len(prompt), len(prompt) + len(tokens)))
        used += 1
        if until is not None and time.monotonic() > until:
            break
    return out, used


def compare(per_token: dict, *, stable_gap: float = 0.0,
            diff_over: float = 0.25, margin_over: float = 0.25) -> dict:
    """The numbers `correct` is decided on, from `teacher_force_all`'s
    per-token lists.

    Over all tokens: the median, 90th percentile, mean and largest
    absolute difference of log-probability. The median reads the
    precision the model was served in; the mean and the largest cannot
    be judged for an expert model, whose router sends a few tokens in a
    hundred to another expert on bfloat16 rounding, and those read a
    hundred times the rest.

    Over the STABLE tokens, those whose router gap in the reference is
    at least `stable_gap` in every layer (all tokens of a dense model):
    the share whose log-probability differs by more than `diff_over`,
    and the share whose served token lies more than `margin_over` under
    the reference's best logit. Rounding puts next to none there, so a
    fault in a few tokens in a hundred (a dropped expert token, a bad
    page, a wrong sample) shows, which a median cannot see."""
    d = np.abs(np.asarray(per_token["served"], np.float64)
               - np.asarray(per_token["ref"], np.float64))
    m = np.asarray(per_token["margin"], np.float64)
    stable = np.asarray(per_token["gap"], np.float64) >= stable_gap
    n_st = max(int(stable.sum()), 1)
    return {"tokens": int(d.size),
            "logprob_median_abs_diff": float(np.median(d)),
            "logprob_p90_abs_diff": float(np.quantile(d, 0.9)),
            "logprob_mean_abs_diff": float(d.mean()),
            "logprob_max_abs_diff": float(d.max()),
            "stable_share": float(stable.mean()),
            "stable_diff_over_share": float(
                (d[stable] > diff_over).sum() / n_st),
            "stable_margin_over_share": float(
                (m[stable] > margin_over).sum() / n_st),
            "margin_max": float(m.max())}
