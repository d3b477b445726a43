"""Sparse Mixture-of-Experts decoder LM (Mixtral-style) with expert
parallelism.

TPU-first design: routing uses the GShard/Mesh-TF dense-dispatch algorithm —
top-k assignment becomes a (tokens, experts, capacity) one-hot dispatch
tensor contracted with two einsums. Everything is static-shaped, so XLA
tiles it onto the MXU, and the expert axis carries a sharding constraint
(`ep`) so XLA inserts the all-to-all for expert parallelism automatically.
No gather/scatter, no dynamic shapes, no host round-trips.

Attention/norms/rope are shared with the dense model; only the MLP is
replaced by the expert layer. Layers are stacked and scanned like
`models/transformer.py`; the router aux losses ride the scan carry.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from cloud_server_tpu.config import ModelConfig
from cloud_server_tpu.models import transformer
from cloud_server_tpu.ops import rms_norm, rope_table

Params = dict


# ---------------------------------------------------------------------------
# Routing (GShard dense dispatch)
# ---------------------------------------------------------------------------

def _capacity(cfg: ModelConfig, num_tokens: int) -> int:
    cap = int(math.ceil(cfg.expert_capacity_factor * num_tokens
                        * cfg.num_experts_per_token / cfg.num_experts))
    return max(cap, 4)


def top_k_routing(router_logits: jnp.ndarray, k: int, capacity: int):
    """Build dispatch/combine tensors from router logits.

    Args:
      router_logits: (T, E) float32.
      k: experts per token.
      capacity: per-expert buffer size C.

    Returns:
      dispatch: (T, E, C) bool-ish float — token t occupies slot c of
        expert e.
      combine: (T, E, C) float32 — dispatch weighted by the (renormalised)
        router probability.
      aux: dict with load-balance / z-loss ingredients.
    """
    t, e = router_logits.shape
    probs = jax.nn.softmax(router_logits, axis=-1)  # (T, E)

    # Top-k gating with renormalised weights.
    gate_vals, gate_idx = lax.top_k(probs, k)  # (T, k)
    gate_vals = gate_vals / jnp.maximum(
        gate_vals.sum(axis=-1, keepdims=True), 1e-9)

    # One-hot per assignment: (T, k, E).
    assign = jax.nn.one_hot(gate_idx, e, dtype=jnp.float32)

    # Position of each assignment within its expert's buffer. Priority is
    # (k-slot, token-order): all primary assignments rank before secondary,
    # matching GShard. Flatten (k, T) so cumsum runs per expert.
    assign_kt = assign.transpose(1, 0, 2).reshape(k * t, e)  # (k*T, E)
    pos_kt = jnp.cumsum(assign_kt, axis=0) * assign_kt - 1.0  # slot index
    keep_kt = jnp.logical_and(pos_kt >= 0, pos_kt < capacity)
    pos = pos_kt.reshape(k, t, e).transpose(1, 0, 2)  # (T, k, E)
    keep = keep_kt.reshape(k, t, e).transpose(1, 0, 2)

    slot_onehot = jax.nn.one_hot(
        pos.astype(jnp.int32), capacity, dtype=jnp.float32)  # (T,k,E,C)
    slot_onehot *= keep[..., None]
    dispatch = slot_onehot.sum(axis=1)  # (T, E, C)
    combine = (slot_onehot * gate_vals[:, :, None, None]).sum(axis=1)

    # Aux stats: fraction of tokens routed to each expert (top-1 view) and
    # mean router prob, per GShard load-balancing loss.
    frac_tokens = assign[:, 0, :].mean(axis=0)  # (E,)
    mean_probs = probs.mean(axis=0)  # (E,)
    aux = {
        "load_balance": (frac_tokens * mean_probs).sum() * e,
        "router_z": jnp.square(jax.nn.logsumexp(router_logits, -1)).mean(),
        "dropped_frac": 1.0 - keep[:, 0, :].sum() / t,
    }
    return dispatch, combine, aux


def moe_mlp(x: jnp.ndarray, lp: dict, cfg: ModelConfig):
    """Expert-parallel SwiGLU MoE layer.

    x: (B, S, D). lp: router (D, E), w_gate/w_up (E, D, F), w_down (E, F, D).
    Returns (out (B, S, D), aux dict of scalars).
    """
    b, s, d = x.shape
    tokens = x.reshape(b * s, d)
    capacity = _capacity(cfg, b * s)

    # scope names are what a device trace tells the einsums apart by
    # (`tf_op` of an op's event metadata; cellbench/hostplane.py)
    with jax.named_scope("moe_route"):
        router_logits = jnp.einsum(
            "td,de->te", tokens.astype(jnp.float32),
            lp["router"].astype(jnp.float32))
        dispatch, combine, aux = top_k_routing(
            router_logits, cfg.num_experts_per_token, capacity)

    # (T, E, C) x (T, D) -> (E, C, D): the all-to-all, inserted by XLA from
    # the `ep` sharding of the expert axis.
    with jax.named_scope("moe_dispatch"):
        xs = jnp.einsum("tec,td->ecd", dispatch.astype(cfg.dtype), tokens)
    with jax.named_scope("moe_experts"):
        gate = jnp.einsum("ecd,edf->ecf", xs,
                          lp["w_gate"].astype(cfg.dtype))
        up = jnp.einsum("ecd,edf->ecf", xs, lp["w_up"].astype(cfg.dtype))
        act = jax.nn.silu(gate) * up
        ys = jnp.einsum("ecf,efd->ecd", act,
                        lp["w_down"].astype(cfg.dtype))
    with jax.named_scope("moe_combine"):
        out = jnp.einsum("tec,ecd->td", combine.astype(cfg.dtype), ys)
    return out.reshape(b, s, d), aux


# ---------------------------------------------------------------------------
# Model: dense attention + MoE MLP blocks
# ---------------------------------------------------------------------------

def param_shapes(cfg: ModelConfig) -> dict[str, Any]:
    shapes = transformer.param_shapes(cfg)
    L, D, E, F = (cfg.num_layers, cfg.embed_dim, cfg.num_experts, cfg.mlp_dim)
    layers = shapes["layers"]
    for k in ("w_gate", "w_up", "w_down"):
        del layers[k]
    layers["router"] = (L, D, E)
    layers["w_gate"] = (L, E, D, F)
    layers["w_up"] = (L, E, D, F)
    layers["w_down"] = (L, E, F, D)
    return shapes


def param_logical_axes(cfg: ModelConfig) -> dict[str, Any]:
    axes = transformer.param_logical_axes(cfg)
    layers = axes["layers"]
    for k in ("w_gate", "w_up", "w_down"):
        del layers[k]
    layers["router"] = ("layers", "embed", None)
    layers["w_gate"] = ("layers", "experts", "embed", "expert_mlp")
    layers["w_up"] = ("layers", "experts", "embed", "expert_mlp")
    layers["w_down"] = ("layers", "experts", "expert_mlp", "embed")
    return axes


def init_params(cfg: ModelConfig, rng: jax.Array) -> Params:
    if cfg.num_experts < 2:
        raise ValueError("MoE model needs num_experts >= 2")
    dtype = jnp.dtype(cfg.param_dtype)
    shapes = param_shapes(cfg)
    paths, treedef = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda x: isinstance(x, tuple))
    keys = jax.random.split(rng, len(paths))
    fan_in = {"router": cfg.embed_dim, "w_gate": cfg.embed_dim,
              "w_up": cfg.embed_dim, "w_down": cfg.mlp_dim,
              "tokens": cfg.embed_dim, "kernel": cfg.embed_dim,
              "wq": cfg.embed_dim, "wk": cfg.embed_dim, "wv": cfg.embed_dim,
              "wo": cfg.num_heads * cfg.head_dim}
    out = []
    for (path, shape), key in zip(paths, keys):
        name = path[-1].key
        path_str = "/".join(p.key for p in path)
        if "norm" in path_str:
            out.append(jnp.ones(shape, dtype))
        else:
            std = 1.0 / math.sqrt(fan_in[name])
            out.append((jax.random.truncated_normal(
                key, -2.0, 2.0, shape, jnp.float32) * std).astype(dtype))
    return jax.tree.unflatten(treedef, out)


def moe_mlp_block(x, lp, cfg: ModelConfig):
    """Residual MoE MLP sub-block: norm -> route/experts -> add.

    The single definition shared by training (`_moe_block`) and the
    inference engine (`engine._mlp_apply`), so serve-time MoE math can
    never drift from the trained model.
    """
    h = rms_norm(x, lp["mlp_norm"], cfg.norm_eps)
    out, aux = moe_mlp(h, lp, cfg)
    return x + out, aux


def _moe_block(x, lp, cfg: ModelConfig, cos, sin, attn_fn, positions=None):
    x = transformer._attention_block(x, lp, cfg, cos, sin, attn_fn, positions)
    return moe_mlp_block(x, lp, cfg)


def forward_hidden(params: Params, tokens: jnp.ndarray, cfg: ModelConfig,
                   segment_ids: jnp.ndarray | None = None):
    """(B, S) -> (final-normed hidden (B, S, D), aux dict of router stats).

    segment_ids: optional packed-sequence ids — same block-diagonal
    attention + per-document RoPE semantics as the dense family
    (transformer.forward_hidden)."""
    cos, sin = rope_table(cfg, tokens.shape[1])
    # Unshard the table's embed dim BEFORE the lookup: a tp-sharded D at
    # the gather makes XLA produce a D-sharded (B, S, D) it must then
    # replicate-and-repartition to the batch/sequence layout ("Involuntary
    # full rematerialization" in the SPMD partitioner). One table
    # all-gather per forward is strictly cheaper.
    table = transformer.constrain(params["embed"]["tokens"].astype(cfg.dtype),
                      ("vocab", None))
    x = table[tokens]
    x = transformer.constrain(x, ("batch", "sequence", None))
    positions = None
    if segment_ids is not None:
        from cloud_server_tpu.ops.segments import positions_from_segments
        positions = positions_from_segments(segment_ids)
    attn_fn = transformer._get_attention_fn(
        cfg, segment_ids, mesh=transformer.kernel_mesh())

    block = partial(_moe_block, cfg=cfg, cos=cos, sin=sin, attn_fn=attn_fn,
                    positions=positions)
    block = transformer.apply_remat(block, cfg)

    def scan_body(carry, lp):
        x, lb, rz, dropped = carry
        x, aux = block(x, lp)
        return (x, lb + aux["load_balance"], rz + aux["router_z"],
                dropped + aux["dropped_frac"]), None

    zero = jnp.zeros((), jnp.float32)
    (x, lb, rz, dropped), _ = lax.scan(
        scan_body, (x, zero, zero, zero), params["layers"])
    x = rms_norm(x, params["final_norm"]["scale"], cfg.norm_eps)
    n = cfg.num_layers
    aux = {"load_balance": lb / n, "router_z": rz / n, "dropped_frac": dropped / n}
    return x, aux


def forward(params: Params, tokens: jnp.ndarray, cfg: ModelConfig,
            segment_ids: jnp.ndarray | None = None):
    """(B, S) -> (logits (B, S, V) f32, aux dict of scalar router stats)."""
    x, aux = forward_hidden(params, tokens, cfg, segment_ids)
    return transformer.unembed(x, params, cfg), aux


def next_token_loss(params: Params, batch: dict, cfg: ModelConfig,
                    z_loss_coef: float = 0.0, aux_loss_coef: float = 0.01,
                    router_z_coef: float = 0.0):
    seg = batch.get("segment_ids")
    batch = transformer.apply_segment_loss_mask(batch)
    if cfg.ce_impl == "pallas" or cfg.vocab_chunk > 0:
        x, aux = forward_hidden(params, batch["tokens"], cfg, segment_ids=seg)
        loss, metrics = transformer.hidden_state_loss(
            x, params, batch, cfg, z_loss_coef)
    else:
        logits, aux = forward(params, batch["tokens"], cfg, segment_ids=seg)
        loss, metrics = transformer.masked_cross_entropy(
            logits, batch, z_loss_coef)
    metrics.update(load_balance=aux["load_balance"],
                   router_z=aux["router_z"],
                   dropped_frac=aux["dropped_frac"])
    loss = loss + aux_loss_coef * aux["load_balance"]
    if router_z_coef > 0.0:
        loss = loss + router_z_coef * aux["router_z"]
    return loss, metrics
