"""The experts' way in as one grouped matmul: act(x @ w_gate) * (x @ w_up).

Rows sorted by expert meet that expert's two matrices in one Pallas
kernel, written from megablox's `gmm` (jax.experimental.pallas.ops.tpu.
megablox) and run on its `make_group_metadata`: the kernel visits a row
tile once for every group with a row in it, and a visit stores the rows
of its group alone, as megablox masks its store. A visit computes the
rows its group has, not the row tile: of the tile's sub-tiles of
`SUB_ROWS` rows only those that hold a real row of the group meet the
weights (a group's extent may be padded to whole row tiles,
`models/moe.py:_aligned_layout`; its real rows lead it).

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


# The rows of a sub-tile. A visit whose group has a real row in every
# sub-tile of its row tile computes the tile in one product, as it always
# did; any other computes the sub-tiles that hold one, each in a product
# of its own. Placed by the v5e sweep of PERF.md (PR 53), ms a layer under
# sub-tiles of 128 | 64 | 32 rows (and a visit that computes its whole
# tile): 8 experts of (4,096 x 14,336), 1,088 tokens 8.01 | 7.71 | 7.64
# (8.00), 2,112 skewed 11.42 | 11.21 | 11.13 (11.73); 128 experts of
# (2,048 x 1,024), 1,088 tokens 3.20 | 3.10 | 3.14 (3.40), 1,088 skewed
# 2.39 | 2.35 | 2.48 (2.48), 2,112 tokens 3.84 | 3.84 | 4.00 (3.85): 32
# rows a product lose at the narrow widths what they win at the wide ones.
SUB_ROWS = 64


def rows_computed(group_rows):
    """The rows the kernel computes for groups of `group_rows` (..., G)
    real rows that each start on a row tile (`_aligned_layout` with room;
    packed, a group that starts inside a sub-tile may meet one more): each
    count rounded up to whole sub-tiles, summed."""
    return (-(-group_rows // SUB_ROWS) * SUB_ROWS).sum()


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
def gated_grouped_matmul(rows, w_gate, w_up, group_sizes, group_rows, *,
                         activation: str, tiling: tuple,
                         interpret: bool = False):
    """rows (M, D) sorted by group, w_gate and w_up (G, D, F), group_sizes
    and group_rows (G,) int32 -> (M, F) in rows.dtype. Group g's extent is
    `group_sizes[g]` rows of the buffer, behind the extents before it; the
    first `group_rows[g]` of them are real (all of them where the two are
    equal), and real row r of group g is
    act(rows[r] @ w_gate[g]) * (rows[r] @ w_up[g]). An empty group's
    weights are not read. A row that is no group's real row (the rest of a
    padded extent, a row past the last group) may not be computed, and what
    stands there is not defined: of a visited row tile only the sub-tiles
    of `SUB_ROWS` rows that hold a real row of the visit's group are.

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

    # the row behind each group's last real row
    ends = metadata[0][:-1] + group_rows
    sub = SUB_ROWS
    if tm % sub:
        raise ValueError(f"row tile {tm} is not whole sub-tiles of {sub}")

    def kernel(offsets, group_ids, m_tile_ids, ends, x_ref, gate_ref, up_ref,
               out_ref):
        visit = pl.program_id(1)
        group = group_ids[visit]
        # the rows [lo, hi) of this tile that are real rows of this
        # visit's group; the tile's other rows keep what stands there
        # (what an earlier visit stored, in a tile that groups share)
        tile = m_tile_ids[visit] * tm
        lo = jnp.maximum(offsets[group] - tile, 0)
        hi = jnp.minimum(ends[group] - tile, tm)
        cols = (pl.ds(pl.multiple_of(pl.program_id(2) * tn, tn), tn)
                if n_inner > 1 else slice(None))

        def compute(start, n):  # rows [start, start + n) of the tile
            span = pl.ds(start, n)
            x = x_ref[span, :]
            gate, up = (
                dot(x, w[...]).astype(out_ref.dtype).astype(jnp.float32)
                for w in (gate_ref, up_ref))
            act = gated(gate, up, activation)
            row = start + lax.broadcasted_iota(jnp.int32, (n, tn), 0)
            mine = jnp.logical_and(row >= lo, row < hi)
            out_ref[span, cols] = jnp.where(
                mine, act, out_ref[span, cols].astype(jnp.float32)).astype(
                    out_ref.dtype)

        # the sub-tiles [first, last] that meet [lo, hi); none where the
        # group has no real row in this tile
        first, last = lo // sub, (hi - 1) // sub
        whole = jnp.logical_and(first == 0, last == tm // sub - 1)

        @pl.when(whole)
        def _():
            compute(0, tm)

        @pl.when(jnp.logical_not(whole))
        def _():
            def one(s, carry):
                compute(pl.multiple_of(s * sub, sub), sub)
                return carry
            lax.fori_loop(first, last + 1, one, 0)

    def weight_tile(block, visit, col, offsets, group_ids, m_tile_ids, ends):
        return group_ids[visit], 0, block * n_inner + col

    weights = pl.BlockSpec((None, d, tn), weight_tile)
    itemsize = rows.dtype.itemsize
    max_visits = metadata[1].size
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((m, f), rows.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            in_specs=[
                pl.BlockSpec((tm, d), lambda block, visit, col, offsets,
                             group_ids, m_tile_ids, ends:
                             (m_tile_ids[visit], 0)),
                weights, weights],
            out_specs=pl.BlockSpec(
                (tm, tw), lambda block, visit, col, offsets, group_ids,
                m_tile_ids, ends: (m_tile_ids[visit], block)),
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
    )(*metadata, ends, rows, w_gate, w_up)
