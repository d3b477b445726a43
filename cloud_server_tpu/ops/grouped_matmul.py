"""The experts' way in as one grouped matmul: act(x @ w_gate) * (x @ w_up).

Rows sorted by expert meet that expert's two matrices in one Pallas
kernel, written from megablox's `gmm` (jax.experimental.pallas.ops.tpu.
megablox) and run on its `make_group_metadata`: the kernel visits a row
tile once for every group with a row in it, and a visit stores the rows
of its group alone, as megablox masks its store.

What it does that two `gmm` calls and an activation's pass do not:

  * a visit's row tile spans the whole contraction and is fetched once
    for both matrices and for every column tile of an output block
    (`tiling`'s block width): with the block as wide as the output the
    visits are the outer loop and a row tile is read once a visit; with
    the block one column tile wide the columns are the outer loop, as in
    megablox, and a weight tile stays in VMEM across an expert's
    consecutive row tiles. Which of the two a call wants is its caller's
    to say from its shape (`models/moe.py:_gmm_tilings`);
  * the two products stay in float32 in VMEM until the activation has
    been applied: one (rows, F) array is written where three were, and
    none is read back;
  * its VMEM is sized from its tiles (`_vmem_bytes`), not the compiler's
    16 MiB default: two weight tiles, double-buffered, are that much by
    themselves at an expert's 4,096 x 512.

The arithmetic is the two calls': operands in the compute dtype, sums in
float32, each product rounded to the compute dtype before the activation
(where `gmm(preferred_element_type=lhs.dtype)` rounded it), the
activation and the product with `up` in float32 (where XLA:TPU computes
a fusion of bfloat16 elementwise operations), rounded once.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.experimental.pallas.ops.tpu.megablox.gmm import make_group_metadata

from cloud_server_tpu.ops.activations import gated


def _vmem_bytes(tm: int, d: int, tn: int, tw: int, itemsize: int) -> int:
    """The scoped VMEM the kernel asks for, from its tiles: the row tile,
    the two weight tiles and the output block, each double-buffered by
    the pipeline; the two float32 products, the activation and the masked
    store's operands of one column tile; a fifth more and 2 MiB for what
    Mosaic keeps beside them."""
    buffers = 2 * itemsize * (tm * d + 2 * d * tn + tm * tw)
    temporaries = 6 * 4 * tm * tn
    return int(1.2 * (buffers + temporaries)) + (2 << 20)


@partial(jax.jit, static_argnames=("activation", "tiling", "interpret"))
def gated_grouped_matmul(rows, w_gate, w_up, group_sizes, *,
                         activation: str, tiling: tuple,
                         interpret: bool = False):
    """rows (M, D) sorted by group, w_gate and w_up (G, D, F), group_sizes
    (G,) int32 -> (M, F) in rows.dtype: row r of group g is
    act(rows[r] @ w_gate[g]) * (rows[r] @ w_up[g]). An empty group's
    weights are not read; a row past the last group is not computed and
    what stands there is not defined.

    tiling: (row tile, column tile, block width). M is whole row tiles;
    the column tile divides the block width and the block width F. The
    grid is (F / block width, visits, block width / column tile)."""
    m, d = rows.shape
    n_groups, _, f = w_gate.shape
    tm, tn, tw = tiling
    if m % tm or tw % tn or f % tw:
        raise ValueError(f"tiling {tiling} does not divide ({m}, {f})")
    n_inner = tw // tn
    metadata, visits = make_group_metadata(
        group_sizes=group_sizes, m=m, tm=tm, start_group=0,
        num_nonzero_groups=n_groups, visit_empty_groups=False)
    # dtype-determined precision, stated at the products: a global
    # "highest" would ask Mosaic for an fp32 contraction of bf16 tiles
    dot = partial(
        lax.dot_general, dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=(lax.Precision.DEFAULT if rows.dtype == jnp.bfloat16
                   else lax.Precision.HIGHEST))

    def kernel(offsets, group_ids, m_tile_ids, x_ref, gate_ref, up_ref,
               out_ref):
        visit = pl.program_id(1)
        x = x_ref[...]
        gate, up = (dot(x, w[...]).astype(out_ref.dtype).astype(jnp.float32)
                    for w in (gate_ref, up_ref))
        act = gated(gate, up, activation)
        # the rows of this visit's group in this tile; the tile's other
        # rows keep what an earlier visit stored there
        group = group_ids[visit]
        row = m_tile_ids[visit] * tm + lax.broadcasted_iota(
            jnp.int32, (tm, tn), 0)
        mine = jnp.logical_and(row >= offsets[group],
                               row < offsets[group + 1])
        cols = (slice(None),
                pl.ds(pl.multiple_of(pl.program_id(2) * tn, tn), tn)
                if n_inner > 1 else slice(None))
        out_ref[cols] = jnp.where(
            mine, act, out_ref[cols].astype(jnp.float32)).astype(
                out_ref.dtype)

    def weight_tile(block, visit, col, offsets, group_ids, m_tile_ids):
        return group_ids[visit], 0, block * n_inner + col

    weights = pl.BlockSpec((None, d, tn), weight_tile)
    itemsize = rows.dtype.itemsize
    max_visits = metadata[1].size
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((m, f), rows.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            in_specs=[
                pl.BlockSpec((tm, d), lambda block, visit, col, offsets,
                             group_ids, m_tile_ids: (m_tile_ids[visit], 0)),
                weights, weights],
            out_specs=pl.BlockSpec(
                (tm, tw), lambda block, visit, col, offsets, group_ids,
                m_tile_ids: (m_tile_ids[visit], block)),
            grid=(f // tw, visits, n_inner)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_vmem_bytes(tm, d, tn, tw, itemsize)),
        # as megablox counts: every possible visit reads its weights
        cost_estimate=pl.CostEstimate(
            flops=4 * m * d * f, transcendentals=m * f,
            bytes_accessed=itemsize * (
                m * d * (f // tw) + 2 * d * f * max_visits + m * f)),
        interpret=interpret,
        name="gated_grouped_matmul",
    )(*metadata, rows, w_gate, w_up)
