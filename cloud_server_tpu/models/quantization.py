"""Weight-only int8 quantization for inference.

TPU-first rationale: decode is HBM-bandwidth-bound — every step streams all
weights once per token. Storing weights as int8 with per-output-channel
float32 scales halves (vs bf16) the bytes streamed, and XLA fuses the
dequantize (`convert` + `multiply`) into the consuming matmul, so the MXU
still sees bf16 operands and there is no extra HBM round-trip.

Mechanism: every weight in this codebase is consumed via
`w.astype(cfg.dtype)` immediately before its einsum
(models/transformer.py:119-151, models/moe.py:106-118). `QTensor` is a
registered pytree node whose `.astype()` performs the dequantize — so
quantized parameter trees flow through the *unmodified* model, engine, and
`lax.scan` layer-stacking machinery (scan slices the leading layer axis of
both the int8 payload and its scales in lockstep).

Scales are symmetric per-output-channel, constant along every contracted
axis of the consuming einsum (`_REDUCE_AXES` below), which is what makes
scaling-after-matmul exact. Router weights, norm scales, and embeddings are
left in full precision: routers are numerically sensitive, norms are tiny,
and the embedding is consumed by gather (not a contraction) — its lm_head
use when `tie_embeddings=True` would need a transpose-aware scale.

Inference-only: `QTensor` defines no VJP — training stays in bf16/f32.

Reference parity note: view-sonic/Cloud-Server @ v0 is an empty tree
(SURVEY.md); this subsystem is part of the re-scoped build inventory.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

# (leaf name, ndim) -> axes of the *stacked* weight that are contracted by
# its consuming einsum. Scales reduce over exactly these axes, so they stay
# per-output-channel (and per-layer, per-expert) everywhere else.
_REDUCE_AXES: dict[tuple[str, int], tuple[int, ...]] = {
    # dense attention (L, D, H|KH, Dh): contract D
    ("wq", 4): (1,), ("wk", 4): (1,), ("wv", 4): (1,), ("wg", 4): (1,),
    # attention out (L, H, Dh, D): contract H, Dh
    ("wo", 4): (1, 2),
    # dense MLP (L, D, F) / (L, F, D): contract axis 1
    ("w_gate", 3): (1,), ("w_up", 3): (1,), ("w_down", 3): (1,),
    # an expert layer's shared expert, a dense MLP beside the routed ones
    ("shared_w_gate", 3): (1,), ("shared_w_up", 3): (1,),
    ("shared_w_down", 3): (1,),
    # MoE experts (L, E, D, F) / (L, E, F, D): contract axis 2
    ("w_gate", 4): (2,), ("w_up", 4): (2,), ("w_down", 4): (2,),
    # untied lm_head (D, V): contract D
    ("kernel", 2): (0,),
}


@jax.tree_util.register_pytree_node_class
class QTensor:
    """int8 payload + broadcastable f32 scales; dequantizes on `.astype`."""

    def __init__(self, q: jnp.ndarray, scale: jnp.ndarray):
        self.q = q
        self.scale = scale

    # -- pytree protocol ----------------------------------------------------
    def tree_flatten(self):
        return (self.q, self.scale), None

    @classmethod
    def tree_unflatten(cls, _aux, children):
        return cls(*children)

    # -- array-like surface used by the models ------------------------------
    @property
    def shape(self):
        return self.q.shape

    @property
    def ndim(self):
        return self.q.ndim

    @property
    def dtype(self):
        return self.q.dtype

    def astype(self, dtype) -> jnp.ndarray:
        """Dequantize. f32 multiply keeps full scale precision; the final
        cast (and the multiply itself) fuse into the consuming matmul."""
        return (self.q.astype(jnp.float32) * self.scale).astype(dtype)

    def dequantize(self) -> jnp.ndarray:
        return self.q.astype(jnp.float32) * self.scale

    def __repr__(self):
        return f"QTensor(q={self.q.shape}, scale={self.scale.shape})"


def quantize(w: jnp.ndarray, reduce_axes: tuple[int, ...]) -> QTensor:
    """Symmetric int8 quantization with scales reduced over `reduce_axes`."""
    w = w.astype(jnp.float32)
    amax = jnp.max(jnp.abs(w), axis=reduce_axes, keepdims=True)
    scale = jnp.maximum(amax, 1e-8) / 127.0
    q = jnp.clip(jnp.round(w / scale), -127, 127).astype(jnp.int8)
    return QTensor(q, scale)


def quantize_params(params: Any) -> Any:
    """Quantize every weight with a `_REDUCE_AXES` entry; pass the rest
    through untouched. Works for dense, MoE, and LoRA-merged trees."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    out = []
    for path, leaf in flat:
        name = path[-1].key if hasattr(path[-1], "key") else None
        # ("kernel", 2) is keyed on the generic name "kernel"; its axis-0
        # scales are only correct for the lm_head (D, V) matrix, so gate on
        # the parent key rather than quantizing any stray 2-D "kernel".
        if name == "kernel" and not (
                len(path) >= 2 and getattr(path[-2], "key", None) == "lm_head"):
            out.append(leaf)
            continue
        axes = _REDUCE_AXES.get((name, getattr(leaf, "ndim", -1)))
        out.append(quantize(leaf, axes) if axes is not None else leaf)
    return jax.tree_util.tree_unflatten(treedef, out)


def dequantize_params(params: Any) -> Any:
    """Inverse of `quantize_params` (lossy): QTensor leaves -> f32 arrays."""
    return jax.tree.map(
        lambda x: x.dequantize() if isinstance(x, QTensor) else x,
        params, is_leaf=lambda x: isinstance(x, QTensor))


def quantized_bytes(params: Any) -> tuple[int, int]:
    """(bytes as stored, bytes if everything were bf16) — for reporting."""
    stored = 0
    bf16 = 0
    for leaf in jax.tree.leaves(
            params, is_leaf=lambda x: isinstance(x, QTensor)):
        if isinstance(leaf, QTensor):
            stored += leaf.q.size + 4 * leaf.scale.size
            bf16 += 2 * leaf.q.size
        else:
            stored += leaf.dtype.itemsize * leaf.size
            bf16 += 2 * leaf.size
    return stored, bf16


def quantized_shardings(qparams: Any, logical_tree: Any, mesh,
                        rules=None) -> Any:
    """Sharding tree for a quantized param tree, for `jax.device_put`.

    `logical_tree` is the model's `param_logical_axes(cfg)` (unquantized
    structure: one axis tuple per weight). For each QTensor the int8
    payload takes the weight's own spec; its scales take the same spec with
    the *contracted* axes replaced by None — those dims are size 1 and
    cannot be sharded, and replicating scales along the contraction is what
    keeps the post-matmul rescale local to each shard.
    """
    from jax.sharding import NamedSharding

    from cloud_server_tpu.parallel.sharding import (
        DEFAULT_RULES, spec_from_logical)

    rules = rules or DEFAULT_RULES
    is_q = lambda x: isinstance(x, QTensor)

    def leaf(path, qleaf, axes):
        spec = spec_from_logical(axes, rules)
        if not is_q(qleaf):
            return NamedSharding(mesh, spec)
        name = path[-1].key if hasattr(path[-1], "key") else None
        reduce_axes = _REDUCE_AXES[(name, qleaf.ndim)]
        scale_axes = tuple(None if i in reduce_axes else a
                           for i, a in enumerate(axes))
        return QTensor(NamedSharding(mesh, spec),
                       NamedSharding(mesh, spec_from_logical(scale_axes,
                                                             rules)))

    flat, treedef = jax.tree_util.tree_flatten_with_path(
        qparams, is_leaf=is_q)
    axes_flat = jax.tree.leaves(
        logical_tree,
        is_leaf=lambda x: isinstance(x, (tuple, list)) and
        all(isinstance(a, str) or a is None for a in x))
    assert len(flat) == len(axes_flat), "param/axes tree mismatch"
    out = [leaf(path, q, axes) for (path, q), axes in zip(flat, axes_flat)]
    return jax.tree_util.tree_unflatten(treedef, out)
