"""Weights from the seed: one jitted call, on the device, in the type
they are served in.

Only the names and shapes of the leaves come from the program
(`param_shapes`); every value is the benchmark's own: norm scales are 1,
every other leaf is normal with standard deviation 1/sqrt(fan-in), fan-in
read off the leaf's shape. The reference and the program are handed the
same arrays, so neither takes anything the other has made.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp


def seed_key(seed: int) -> jax.Array:
    """A key from any whole number (the driver's seeds pass 2**31)."""
    seed = int(seed)
    key = jax.random.key(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


def _fan_in(name: str, shape: tuple) -> int:
    """Inputs summed into one output of the leaf's matmul. Layer leaves
    lead with the layer axis, expert leaves with (layer, expert)."""
    if name == "wo":  # (L, H, Dh, D)
        return shape[1] * shape[2]
    if name == "kernel":  # (D, V)
        return shape[0]
    if name in ("wq", "wk", "wv", "tokens"):  # (L, D, H, Dh), (V, D)
        return shape[1]
    return shape[-2]  # router and MLP leaves: (..., in, out)


def _leaf(key, name: str, shape: tuple, dtype):
    std = 1.0 / math.sqrt(_fan_in(name, shape))

    def draw(k, shp):
        return (jax.random.normal(k, shp, jnp.float32) * std).astype(dtype)

    if len(shape) >= 3:
        # one leading index at a time, so the float32 draw of a whole
        # stacked leaf (gigabytes at these widths) is never alive at once
        keys = jax.random.split(key, shape[0])
        return jax.lax.map(lambda k: draw(k, shape[1:]), keys)
    return draw(key, shape)


@partial(jax.jit, static_argnames=("shapes_items", "dtype"))
def _make(key, shapes_items, dtype):
    out = {}
    keys = jax.random.split(key, len(shapes_items))
    for k, (path, shape) in zip(keys, shapes_items):
        if "norm" in "/".join(path):
            leaf = jnp.ones(shape, dtype)
        else:
            leaf = _leaf(k, path[-1], shape, dtype)
        node = out
        for p in path[:-1]:
            node = node.setdefault(p, {})
        node[path[-1]] = leaf
    return out


def flat_shapes(shapes: dict, prefix: tuple = ()) -> tuple:
    """A nested dict of shape tuples as a sorted, hashable tuple of
    (path, shape)."""
    items = []
    for name in sorted(shapes):
        v = shapes[name]
        if isinstance(v, dict):
            items.extend(flat_shapes(v, prefix + (name,)))
        else:
            items.append((prefix + (name,), tuple(v)))
    return tuple(items)


def make_weights(shapes: dict, seed: int, dtype: str = "bfloat16") -> dict:
    """The parameter tree for `shapes` (as the program's `param_shapes`
    gives them) from `seed`, made on the default device in one call."""
    return _make(seed_key(seed), flat_shapes(shapes), jnp.dtype(dtype))
