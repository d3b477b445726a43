"""Gated activations."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def swiglu(gate: jnp.ndarray, up: jnp.ndarray) -> jnp.ndarray:
    """SwiGLU gate: silu(gate) * up. Elementwise; XLA fuses it into the
    surrounding matmuls so it never round-trips through HBM on its own."""
    return jax.nn.silu(gate) * up


def gated(gate: jnp.ndarray, up: jnp.ndarray, activation: str) -> jnp.ndarray:
    """The gated MLP's middle, by `ModelConfig.mlp_activation`:
    act(gate) * up with act "silu" (SwiGLU) or "relu" (ReGLU)."""
    if activation == "silu":
        return swiglu(gate, up)
    if activation == "relu":
        return jax.nn.relu(gate) * up
    raise ValueError(f"unknown mlp_activation: {activation!r}")
