"""Sparse Mixture-of-Experts decoder LM (Mixtral-style) with expert
parallelism.

`moe_mlp` has two dispatches and chooses between them from what it can
observe (`_dispatch_grouped`):

  * The GShard/Mesh-TF dense dispatch: top-k assignment becomes a (tokens,
    experts, capacity) one-hot tensor contracted with two einsums, and the
    experts run as batched einsums over (experts, capacity) rows. Static
    shapes, no gather/scatter; the expert axis is sharded over `ep` and XLA
    inserts the all-to-all. An expert's buffer holds `_capacity` rows
    whether tokens fill them or not, and what overflows is dropped: this
    is training's dispatch (`expert_capacity_factor` 1.25), every call
    under a mesh of more than one device, and every call of few tokens,
    where the experts' weight stream hides the empty rows.
  * The sorted, dropless dispatch: where the capacity could drop nothing
    anyway (`_capacity >= T`, a served Mixtral), T is
    `GROUPED_MIN_TOKENS` or more and the caller says where the layer's
    weights lie in the stacked parameters (`stack`), the T x k
    assignments are sorted by expert, gathered to (T*k, D) rows and run
    through one grouped matmul per weight, then gathered back: T*k expert
    rows where the dense dispatch computes E*T. The kernel computes a
    whole row tile for every expert with a row in it, so where the call's
    shape lets that save enough (`_sorted_buffer_rows`) every expert's
    rows start on a row tile of a somewhat longer buffer and no tile is
    computed twice (`_aligned_layout`). Same routing, same gates, same
    `aux`, same products in the same precision; the shapes stay static
    (`group_sizes` and the rows' places are data).

No dynamic shapes, no host round-trips on either.

One chip's share of a wider router (`cfg.routed_scaling_factor` > 0:
LongCat-Flash, served only): the router has columns for experts held on
other chips and for experts that compute nothing. The choice is the top k
of probability + bias and the gate probability x factor (`_share_gates`);
both dispatches compute the held experts' terms alone (the one-hot one
builds no column for another index, the sorted one gives it no group and no
row), the identity experts' term is added beside them (`_share_identity`)
and the absent experts' terms are left out: the partial sum a chip holds
before the exchange.

A router balanced by a bias (`cfg.router_score` "sigmoid": Trinity-Mini's
family): a sigmoid score an expert, the choice the top k of score + bias,
the gates the kept scores renormalised and scaled (`_sigmoid_gates`); both
dispatches carry them, and `aux["load"]` is the call's assignments an
expert. Beside the routed experts a layer may have a shared expert, one
always-on gated MLP added once in `moe_mlp_block`, and the model leading
dense layers in a stack of their own before the expert stack
(`param_shapes`; the scans below run one stack after the other).

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
from cloud_server_tpu.ops import gated, rms_norm, rope_table
from cloud_server_tpu.ops.grouped_matmul import gated_grouped_matmul
from cloud_server_tpu.parallel.mesh import maybe_current_mesh

Params = dict


# ---------------------------------------------------------------------------
# Routing
# ---------------------------------------------------------------------------

# The fewest tokens of a call that the sorted dispatch takes at 8 experts, 2
# a token. Placed by the v5e measurements of PERF.md (PR 26, PR 32, PR 39):
# below it the experts' weight stream bounds both dispatches and the dense
# one has no sort and no gathers. A whole layer at Mixtral's widths, dense
# and sorted: 256 tokens 4.24 and 4.71 ms, 288 tokens 4.62 and 4.62, 320
# tokens 5.01 and 4.63 (from 288 tokens every expert's rows lie on a tile
# of their own: 8 visits).
GROUPED_MIN_TOKENS = 288


def grouped_min_tokens(cfg: ModelConfig) -> int:
    """The fewest tokens of a call that the sorted dispatch takes, from the
    experts and the experts a token. The dense dispatch's bookkeeping (the
    (T, k, E, C) one-hots and the two contractions over (T, E, C)) grows
    with E * k * T * T where the sort and the gathers grow with k * T, so
    the more assignments there are to place, the sooner the sorted one
    wins. Measured on the v5e at two configurations (PERF.md, PR 32, PR
    35 and PR 39): 8 experts of 14,336, 2 a token: dense wins at 256
    tokens and loses from 320; 64 experts of 768, 6 a token: dense 1.13
    and sorted 1.22 ms a layer at 128 tokens, 1.25 and 1.24 at 160, 1.34
    and 1.28 at 192. The fifth root of E * k passes through both (288
    and 153) and is a fit, not a law: a third configuration tests it, and
    nothing was measured under 16 assignments, where the placed value
    stands. The widths do not enter: both dispatches leave the weight
    stream near 240 tokens a call at any width (peak FLOPs over peak
    bytes).

    The third configuration (PERF.md, PR 45) is one chip's share: 16
    experts of 2,048 held of a 768-wide router, 12 a token, so that of a
    token's 12 assignments 0.25 land on a held expert and the dispatches
    place 4 assignments' worth of a full router's. Measured, ms a layer,
    dense and sorted: 1.74 and 1.82 at 128 tokens, 1.88 and 1.99 at 192,
    2.04 and 2.03 at 256, 2.61 and 2.17 at 320, 5.01 and 2.80 at 576. The
    crossing is at 256, under 16 assignments where the placed value (288)
    stands: the fit holds, with a share's assignments counted as those
    that land here. (At 64 tokens the sorted dispatch reads 1.29 against
    1.69: 16 assignments reach some ten of the 16 experts and it fetches
    no expert without a row; one threshold cannot say that, and a
    deployment's exchange brings every expert 32 times the rows.)

    The fourth configuration (PERF.md, PR 52): 128 experts of 1,024, 8 a
    token, beside a shared expert, 1,024 assignments' worth where the fit
    says 125 tokens. Measured, ms a layer, dense and sorted: 2.26 and 2.35
    at 64 tokens, 2.29 and 2.41 at 96, 2.58 and 2.46 at 128, 2.70 and 2.49
    at 160, 2.79 and 2.52 at 192, 3.69 and 2.55 at 256, 4.23 and 2.61 at
    320. The crossing lies between 96 and 128: the fit holds."""
    assignments = (cfg.num_experts * cfg.num_experts_per_token
                   * cfg.num_experts / cfg.router_width)
    return round(GROUPED_MIN_TOKENS * min(1.0, (16 / assignments) ** 0.2))


def _capacity(cfg: ModelConfig, num_tokens: int) -> int:
    if cfg.routed_scaling_factor > 0:
        # one chip's share of a wider router (`_share_gates`): a held
        # expert gets every row at most, and room for every row
        return max(num_tokens, 4)
    cap = int(math.ceil(cfg.expert_capacity_factor * num_tokens
                        * cfg.num_experts_per_token / cfg.num_experts))
    return max(cap, 4)


def _dispatch_grouped(cfg: ModelConfig, num_tokens: int, stack) -> bool:
    """Whether `moe_mlp` sorts and runs grouped matmuls, from what the call
    can observe:

      * a capacity under which no expert can overflow, so both dispatches
        compute the same function;
      * enough tokens for the dense dispatch's empty rows to cost more
        than the sort and the row tiles computed for more rows than an
        expert has;
      * the experts' weights usable where they lie: the caller gave the
        stacked parameters and the layer's index (`stack`), and they are
        plain arrays of the compute dtype. XLA gives a custom call no view
        of a slice, a cast or a dequantized `QTensor`: it would copy the
        layer's experts first (2.8 GB at Mixtral's widths), where the
        dense einsums fuse all three;
      * no mesh of more than one device: a Mosaic kernel under a mesh
        needs `shard_map`, and the all-to-all over `ep` is the dense
        dispatch's. Not in this path yet.
    """
    if stack is None:
        return False
    mesh = maybe_current_mesh()
    in_place = all(
        isinstance(stack[0][name], jax.Array)
        and stack[0][name].dtype == jnp.dtype(cfg.dtype)
        for name in ("w_gate", "w_up", "w_down"))
    return (num_tokens >= grouped_min_tokens(cfg)
            and _capacity(cfg, num_tokens) >= num_tokens
            and in_place
            and (mesh is None or mesh.size == 1))


def _top_k_gates(router_logits: jnp.ndarray, k: int):
    """(T, E) logits -> probs (T, E), renormalised top-k gates (T, k) and
    their experts (T, k)."""
    probs = jax.nn.softmax(router_logits, axis=-1)  # (T, E)
    gate_vals, gate_idx = lax.top_k(probs, k)  # (T, k)
    gate_vals = gate_vals / jnp.maximum(
        gate_vals.sum(axis=-1, keepdims=True), 1e-9)
    return probs, gate_vals, gate_idx


def _sigmoid_gates(router_logits: jnp.ndarray, bias, cfg: ModelConfig):
    """A sigmoid router (`cfg.router_score`): (T, E) logits -> scores (T,
    E), a sigmoid an expert; the k chosen experts (T, k), the top of score
    + `bias` (E,), the balancing bias that moves the choice and never a
    gate; and their gates (T, k) float32, the kept scores divided by their
    sum and times `cfg.route_scale`."""
    scores = jax.nn.sigmoid(router_logits)
    _, gate_idx = lax.top_k(scores + bias.astype(jnp.float32),
                            cfg.num_experts_per_token)
    gate_vals = jnp.take_along_axis(scores, gate_idx, axis=1)
    gate_vals = gate_vals / (gate_vals.sum(axis=-1, keepdims=True) + 1e-20)
    return scores, cfg.route_scale * gate_vals, gate_idx


def _gates(router_logits: jnp.ndarray, bias, cfg: ModelConfig):
    """(scores (T, E), gates (T, k), experts (T, k)) of a router over
    experts that are all held here, by the model's score function; `bias`
    (E,) is the sigmoid router's, the layer's leaf `router_bias`."""
    if cfg.router_score == "sigmoid":
        return _sigmoid_gates(router_logits, bias, cfg)
    return _top_k_gates(router_logits, cfg.num_experts_per_token)


def _share_gates(router_logits: jnp.ndarray, bias, cfg: ModelConfig):
    """One chip's share of a wider router (`cfg.routed_scaling_factor` >
    0): (T, R) logits over the `cfg.router_width` columns -> the k chosen
    columns (T, k), the top of probability + `bias` (R,), and their gates
    (T, k) float32, probability * factor, the bias left out and nothing
    renormalised. Columns under `cfg.num_experts` are the experts held
    here; from there to `cfg.num_routed_experts` the experts other chips
    hold, whose terms this chip leaves out; behind them the experts that
    compute nothing and add gate * token (`_share_identity`)."""
    probs = jax.nn.softmax(router_logits, axis=-1)
    _, gate_idx = lax.top_k(probs + bias.astype(jnp.float32),
                            cfg.num_experts_per_token)
    gate_vals = cfg.routed_scaling_factor * jnp.take_along_axis(
        probs, gate_idx, axis=1)
    return gate_vals, gate_idx


def _share_identity(tokens, gate_vals, gate_idx, cfg: ModelConfig):
    """The identity experts' term of a share, (T, D) in the tokens' dtype,
    and the call's assignments to (held, identity, absent) experts, (3,)
    int32."""
    with jax.named_scope("moe_zero"):
        zero = gate_idx >= (cfg.num_routed_experts or cfg.num_experts)
        gate = jnp.sum(jnp.where(zero, gate_vals, 0.0), axis=1)
        term = (gate[:, None] * tokens.astype(jnp.float32)).astype(
            tokens.dtype)
    n_held = jnp.sum(gate_idx < cfg.num_experts, dtype=jnp.int32)
    n_zero = jnp.sum(zero, dtype=jnp.int32)
    return term, jnp.stack([n_held, n_zero, gate_idx.size - n_held - n_zero])


def _router_aux(router_logits, probs, top1_onehot, dropped_frac):
    """Aux stats: fraction of tokens routed to each expert (top-1 view) and
    mean router prob, per GShard load-balancing loss."""
    e = probs.shape[-1]
    frac_tokens = top1_onehot.mean(axis=0)  # (E,)
    mean_probs = probs.mean(axis=0)  # (E,)
    return {
        "load_balance": (frac_tokens * mean_probs).sum() * e,
        "router_z": jnp.square(jax.nn.logsumexp(router_logits, -1)).mean(),
        "dropped_frac": dropped_frac,
    }


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
    return _routing(router_logits, *_top_k_gates(router_logits, k), capacity)


def _routing(router_logits, probs, gate_vals, gate_idx, capacity: int):
    """`top_k_routing` from gates already chosen (`_gates`); beside the
    router's stats `aux` holds `load` (E,) int32, the call's assignments
    an expert."""
    dispatch, combine, assign, keep = _one_hot_routing(
        gate_vals, gate_idx, router_logits.shape[1], capacity)
    aux = _router_aux(router_logits, probs, assign[:, 0, :],
                      1.0 - keep[:, 0, :].sum() / router_logits.shape[0])
    aux["load"] = assign.sum(axis=(0, 1)).astype(jnp.int32)
    return dispatch, combine, aux


def _one_hot_routing(gate_vals, gate_idx, e: int, capacity: int):
    """(dispatch, combine) (T, E, C) of `top_k_routing` from the chosen
    experts (T, k) and their gates, and the (T, k, E) one-hots of the
    assignments and of those kept. A chosen index of `e` or more (an
    expert this chip does not hold) has no column: its row is all zeros
    and the assignment takes no slot."""
    t, k = gate_idx.shape

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
    return dispatch, combine, assign, keep


# ---------------------------------------------------------------------------
# The expert layer
# ---------------------------------------------------------------------------

# (rows, contraction, columns) tiles of megablox's grouped matmul kernel on
# the TPU, the way out's (act @ w_down). A row tile that straddles two
# experts is computed for both (the kernel visits it once for each), so
# fewer rows waste less; a weight tile is fetched once per row tile
# unless it spans the contraction, so more rows fetch less. `_gmm_tiling`
# places them from the widths: the weight tile is the largest of 4 MiB or
# less that spans the contraction where the contraction is 4,096 or less,
# and takes 1,024 of it otherwise. At (14,336 x 4,096) that is the
# (256, 1024, 2048) of the v5e sweep of PERF.md (PR 26); at (768 x 2,560)
# an expert's whole matrix is one tile (the sweep of PR 35).
# `_GMM_ROWS` is the row tile of the way in and of the way out, and the
# unit of the sorted rows' layout: the buffer they are gathered out to is
# whole row tiles (`_sorted_buffer_rows`), and where it has room an
# expert's extent is rounded up to whole tiles too (`_aligned_layout`), so
# the next expert starts on a tile and the tile is visited once. The rows
# of an extent that no assignment landed on are never gathered back
# (`row_of` names only rows an assignment landed on): the way in is told
# each expert's real rows beside its extent and computes, of a visited
# tile, only the sub-tiles that hold one (`ops/grouped_matmul.py`,
# `SUB_ROWS`); what stands in the others is not defined and the way out,
# megablox's, multiplies it row by row into rows as little read. Rows past
# the last extent belong to no expert and are not computed. A mixed step's
# one walk brings k * (chunk tokens + decode rows) of them, never whole
# tiles: padding each matmul's operand instead copied the (rows, F)
# activation once a layer.
_GMM_ROWS = 256
_GMM_WEIGHT_TILE_BYTES = 4 << 20


def _gmm_tiling(k: int, n: int, itemsize: int = 2) -> tuple:
    """(rows, contraction, columns) of the grouped matmul (M, k) @ (k, n)."""
    tk = k if k <= 4096 else 1024
    tn = _GMM_WEIGHT_TILE_BYTES // (tk * itemsize) // 128 * 128
    return (_GMM_ROWS, tk, max(128, min(n, tn)))


# The way in's weight tiles, one of `w_gate` and one of `w_up` a step, may
# be this large each. Placed by the v5e sweep of PERF.md (PR 51), ms a
# layer at one row tile an expert: at (4,096 x 14,336) 512 columns 4.05,
# 1,024 (8 MiB) 4.19, 2,048 4.28; at (6,144 x 2,048) 256 columns (3 MiB)
# 3.88, 512 (6 MiB) 3.81, 1,024 3.82.
_GATED_WEIGHT_TILE_BYTES = 6 << 20


def _gated_tiling(d: int, f: int, n_assignments: int, e: int,
                  itemsize: int = 2) -> tuple:
    """(rows, columns, block width) of the way in, x @ w_gate, x @ w_up
    and the activation as one kernel (`ops/grouped_matmul.py`, under VMEM
    it sizes from these), for (M, d) rows and `e` experts of (d, f). The
    row tile spans the contraction; each of the two weight tiles is the
    widest of `_GATED_WEIGHT_TILE_BYTES` or less that spans it too and
    divides the columns (an expert's whole matrix at (2,560 x 768)).
    Where an even router gives every expert one row tile or less the
    block is the whole width: the visits are the outer loop, a visit's
    row tile is fetched once and its expert's weights stream past it.
    Where experts have several row tiles the block is one column tile:
    the columns are the outer loop, as in megablox, and a weight tile is
    fetched once for an expert's consecutive row tiles, the rows once a
    column tile."""
    tn = max((tn for tn in range(128, f + 1, 128) if f % tn == 0
              and d * tn * itemsize <= _GATED_WEIGHT_TILE_BYTES), default=f)
    one_tile = n_assignments <= e * _GMM_ROWS
    return (_GMM_ROWS, tn, f if one_tile else tn)


def _gmm_tilings(cfg: ModelConfig, n_assignments: int) -> tuple:
    """The tilings of the way in (D -> F, `_gated_tiling`) and of the way
    out (F -> D, `_gmm_tiling`) for a call of `n_assignments` sorted
    rows, of which a share's router lands its held experts' part here."""
    held = n_assignments * cfg.num_experts // cfg.router_width
    return (_gated_tiling(cfg.embed_dim, cfg.expert_width, held,
                          cfg.num_experts),
            _gmm_tiling(cfg.expert_width, cfg.embed_dim))


def _grouped_matmul(lhs, rhs, group_sizes, tiling, kernel: bool):
    """lhs (M, K) rows sorted by group, M whole row tiles, rhs (G, K, N),
    group_sizes (G,) int32 -> (M, N) in lhs.dtype, accumulated in float32:
    row r of group g is lhs[r] @ rhs[g], an empty group's weights are not
    read, and a row past the last group is not computed. The
    megablox Pallas kernel on the TPU, `lax.ragged_dot` elsewhere (XLA:TPU's
    own lowering of it is the same kernel at a (512, 512, 512) tiling that
    a caller cannot choose)."""
    if not kernel:
        return lax.ragged_dot(
            lhs, rhs, group_sizes,
            preferred_element_type=jnp.float32).astype(lhs.dtype)
    from jax.experimental.pallas.ops.tpu.megablox import gmm
    k = lhs.shape[1]
    tm, tk, tn = tiling  # lhs is whole row tiles: `_moe_grouped`
    # dtype-determined precision, as this repo's own kernels have it: a
    # global "highest" would ask Mosaic for an fp32 contraction of bf16
    # tiles, which it refuses ("Bad lhs type")
    with jax.default_matmul_precision(
            "default" if lhs.dtype == jnp.bfloat16 else "highest"):
        return gmm(lhs, rhs, group_sizes, preferred_element_type=lhs.dtype,
                   tiling=(tm, min(tk, k), min(tn, rhs.shape[2])))


# jitted so that its trace (two Pallas kernels on the TPU) is cached by
# shape: every layer of every step program calls it, at a few row counts
@partial(jax.jit, static_argnames=("kernel", "activation", "tilings"))
def _grouped_experts(rows, w_gate, w_up, w_down, group_sizes, group_rows,
                     kernel: bool, activation: str, tilings: tuple):
    """Gated experts over rows sorted by expert: (M, D) -> (M, D).
    `group_sizes` are the experts' extents in the buffer, `group_rows` the
    rows at the head of each that an assignment landed on
    (`_aligned_layout`); the rest of an extent is defined in no result.
    `tilings`: `_gmm_tilings`' pair. On the TPU the way in is one kernel
    (`gated_grouped_matmul`: no (M, F) product is written before the
    activation, and of a row tile only the sub-tiles with a real row are
    computed), the way out megablox's, which multiplies an extent's every
    row; `lax.ragged_dot` elsewhere."""
    t_in, t_out = tilings
    if kernel:
        act = gated_grouped_matmul(rows, w_gate, w_up, group_sizes,
                                   group_rows, activation=activation,
                                   tiling=t_in)
    else:
        act = gated(_grouped_matmul(rows, w_gate, group_sizes, None, False),
                    _grouped_matmul(rows, w_up, group_sizes, None, False),
                    activation)
    return _grouped_matmul(act, w_down, group_sizes, t_out, kernel)


# The aligned layout is taken where, for an even router, its visits and
# its longer buffer together cost this share of the packed layout's visits
# or less: an expert's first visit then fetches a tile of rows with its
# tile of weights, where a visit inside a shared tile fetches the weights
# alone, so a visit saved is not a whole visit's time. Placed by the v5e
# measurements of PERF.md (PR 39), ms a layer, packed then aligned: at
# Mixtral's widths 8 visits for 10 and 16 for 20 win (5.26 and 4.63, 11.03
# and 9.54), 16 for 18 and 22 for 23 tie, 16 for 16 loses a millisecond;
# at 64 experts of 768, 64 for 113 and for 101 win (3.06 and 2.44, 2.45
# and 2.22), 64 for 89 ties, 64 for 83 loses a tenth.
_ALIGNED_COST_SHARE = 7 / 8


def _sorted_buffer_rows(n_assignments: int, cfg: ModelConfig) -> int:
    """The rows of the buffer the sorted assignments are laid out in, from
    the call's shape and the widths. Packed end to end the assignments
    fill `ceil(n / tile)` row tiles and the kernel visits a tile once for
    every expert with a row in it: `tiles + E - 1` visits for an even
    router, each about the time of an expert's weights on the bus. With
    every expert's rows on tiles of their own it visits
    `E * ceil(n / E / tile)` tiles, in a buffer of as many and an eighth
    of `E` more for a router that is not even, and every tile of the
    buffer beyond the packed ones costs the gather and the activation
    their bytes (a tile of rows in and out at D, three times at F),
    counted here in visits: over the bytes of an expert's three matrices.
    Where that sum is few enough (`_ALIGNED_COST_SHARE`) the buffer is the
    longer one; elsewhere it is the packed rows' whole tiles, in which
    `_aligned_layout` finds no room to pad."""
    e, d, f = cfg.num_experts, cfg.embed_dim, cfg.expert_width
    packed = -(-n_assignments // _GMM_ROWS)
    visits = e * -(-n_assignments // (e * _GMM_ROWS))
    aligned = max(packed, visits + e // 8)
    tile_in_visits = _GMM_ROWS * (4 * d + 6 * f) / (6 * d * f)
    pays = (visits + tile_in_visits * (aligned - packed)
            <= _ALIGNED_COST_SHARE * (packed + e - 1))
    return (aligned if pays else packed) * _GMM_ROWS


def _aligned_layout(counts, n_rows: int):
    """The extents of the experts in a buffer of `n_rows` rows. counts (E,)
    int32: the sorted assignments of each expert. Returns (sizes (E,),
    shift (E,)) int32: the rows the kernel is told an expert has, its
    count rounded up to whole row tiles for as many of the first experts
    as the buffer has room to pad (all of them where `_sorted_buffer_rows`
    made room and the router is near even; an expert without rows stays
    0), and how far an expert's rows lie behind their place in the packed
    order: an expert whose predecessors are all padded starts on a row
    tile, and no tile of its extent is computed for another expert. The
    expert's `counts` rows lead its extent: what the kernel is handed
    beside `sizes` as the rows that are real."""
    padded = -(-counts // _GMM_ROWS) * _GMM_ROWS
    room = n_rows - counts.sum()
    sizes = jnp.where(jnp.cumsum(padded - counts) <= room, padded, counts)
    return sizes, jnp.cumsum(sizes - counts) - (sizes - counts)


@jax.jit  # traced once a shape, like `_grouped_experts`
def _weighted_sum(ys, row_of, gates):
    """out[t] = sum over j of gates[t, j] * ys[row_of[t, j]]: ys (M, D),
    row_of (T, k) int32, gates (T, k) float32 -> (T, D) in ys.dtype. A
    token's k rows are gathered one slot after the other, multiplied and
    added in float32 and rounded once: no (T, k, D) array is written (in
    float32 a quarter of the layer's time at 2,112 tokens of 6 x 2,560).
    The gates stay float32: the v5e takes no round trip to bfloat16 inside
    one fusion, so rounding them would hang on where XLA cuts its fusions.
    Behind the barrier the sum is one array a layer: without it XLA keeps
    every layer's k gathered arrays to the program's end (PERF.md, PR 41)."""
    acc = jnp.zeros((row_of.shape[0], ys.shape[1]), jnp.float32)
    for j in range(row_of.shape[1]):
        acc += gates[:, j, None] * ys[row_of[:, j]].astype(jnp.float32)
    return lax.optimization_barrier(acc.astype(ys.dtype))


@jax.jit
def _weighted_sum_held(ys, row_of, gates, held):
    """`_weighted_sum` for a share: only the assignments `held` (T, k)
    have a row in `ys`; the others add nothing, whatever `row_of` names
    for them and whatever that row holds (it may never have been
    computed)."""
    acc = jnp.zeros((row_of.shape[0], ys.shape[1]), jnp.float32)
    for j in range(row_of.shape[1]):
        acc += jnp.where(
            held[:, j, None],
            gates[:, j, None] * ys[row_of[:, j]].astype(jnp.float32), 0.0)
    return lax.optimization_barrier(acc.astype(ys.dtype))


def _moe_grouped(tokens, router_logits, layers, layer, cfg: ModelConfig,
                 chosen=None):
    """The sorted, dropless dispatch of `moe_mlp`: tokens (T, D) -> (T, D).
    `layers` holds the stacked (L, E, ...) expert weights, `layer` (an int
    or an int32 scalar) says which of them is this call's. The combine is
    `_weighted_sum`: a token's k rows, by the router's float32 gates.
    `chosen`: (gates, experts) of a share (`_share_gates`), where the
    router is wider than the `e` experts held: an assignment to an index
    of `e` or more sorts behind every held expert's, belongs to no group,
    lands on no row of the buffer and adds nothing to the combine."""
    t = tokens.shape[0]
    k, e = cfg.num_experts_per_token, cfg.num_experts
    n_layers = layers["w_gate"].shape[0]
    if chosen is not None:
        gate_vals, gate_idx = chosen
        aux = {}
    else:
        with jax.named_scope("moe_route"):
            bias = (layers["router_bias"][layer]
                    if cfg.router_score == "sigmoid" else None)
            probs, gate_vals, gate_idx = _gates(router_logits, bias, cfg)
            aux = _router_aux(
                router_logits, probs,
                jax.nn.one_hot(gate_idx[:, 0], e, dtype=jnp.float32),
                jnp.zeros((), jnp.float32))
    with jax.named_scope("moe_dispatch"):
        # assignment a = token * k + slot; a stable sort, so an expert's
        # rows keep token order
        expert_of = gate_idx.reshape(t * k)
        if chosen is not None:
            expert_of = jnp.minimum(expert_of, e)
        ranks = jnp.arange(t * k, dtype=jnp.int32)
        expert_of_rank, order = lax.sort_key_val(expert_of, ranks)
        # compared and summed, not scattered and gathered: a scatter-add
        # of 12,672 ones cost 0.11 ms on the v5e, these a hundredth
        experts = jnp.arange(e, dtype=jnp.int32)
        counts = (expert_of[:, None] == experts).sum(0, dtype=jnp.int32)
        aux["load"] = counts
        n_rows = _sorted_buffer_rows(t * k, cfg)
        sizes, shift = _aligned_layout(counts, n_rows)
        row_of_rank = ranks + jnp.where(
            expert_of_rank[:, None] == experts, shift, 0).sum(1)
        # the token every row of the buffer reads: a row no assignment
        # landed on (the rest of a padded extent, the rows behind the last
        # expert's) reads token 0, finite like any other
        if chosen is None:
            src = jnp.zeros((n_rows,), jnp.int32).at[row_of_rank].set(
                order // k, indices_are_sorted=True, unique_indices=True)
        else:  # what no held expert was chosen for lands past the buffer
            row_of_rank = jnp.where(expert_of_rank < e, row_of_rank, n_rows)
            src = jnp.zeros((n_rows,), jnp.int32).at[row_of_rank].set(
                order // k, mode="drop")
        rows = tokens[src]
        # the stack seen as L * E groups, every other layer's empty: the
        # kernel then reads this layer's experts where they lie
        group_sizes, group_rows = (
            lax.dynamic_update_slice(
                jnp.zeros((n_layers * e,), jnp.int32), of_layer,
                (jnp.asarray(layer, jnp.int32) * e,))
            for of_layer in (sizes, counts))
    with jax.named_scope("moe_experts"), jax.named_scope("grouped"):
        ys = _grouped_experts(
            rows, *(layers[name].reshape((n_layers * e,)
                                         + layers[name].shape[2:])
                    for name in ("w_gate", "w_up", "w_down")),
            group_sizes, group_rows, kernel=jax.default_backend() == "tpu",
            activation=cfg.mlp_activation, tilings=_gmm_tilings(cfg, t * k))
    with jax.named_scope("moe_combine"):
        # every assignment's row, by sorting the permutation back: a sort
        # of 12,672 pairs cost 8 us on the v5e, the scatter 75
        row_of = lax.sort_key_val(order, row_of_rank)[1]
        if chosen is None:
            out = _weighted_sum(ys, row_of.reshape(t, k), gate_vals)
        else:
            out = _weighted_sum_held(
                ys, jnp.minimum(row_of, n_rows - 1).reshape(t, k),
                gate_vals, gate_idx < e)
    return out, aux


def moe_mlp(x: jnp.ndarray, lp: dict, cfg: ModelConfig, stack=None,
            router_x=None):
    """Expert-parallel gated MoE layer (`cfg.mlp_activation`).

    x: (B, S, D). lp: router (D, E), w_gate/w_up (E, D, F), w_down (E, F, D).
    router_x: (B, S, D) what the router reads where that is not `x`
    (`cfg.router_input` "layer_input": the layer's input travels here
    beside the normed activations the experts read).
    stack: optionally (layers, index), the stacked (L, ...) parameters
    that `lp` is layer `index` of: a caller that unrolls its layers says
    so, and the sorted dispatch may then run (`_dispatch_grouped`); one
    that scans over them cannot, and keeps the dense dispatch.
    Returns (out (B, S, D), aux dict of scalars).
    """
    b, s, d = x.shape
    tokens = x.reshape(b * s, d)

    # scope names are what a device trace tells the einsums apart by
    # (`tf_op` of an op's event metadata; cellbench/hostplane.py)
    share = cfg.routed_scaling_factor > 0
    with jax.named_scope("moe_route"):
        if share:
            # 768 columns: the compute dtype's products summed in float32
            # are the float32 product of the same values, in one pass
            router_logits = jnp.einsum(
                "td,de->te", tokens, lp["router"].astype(cfg.dtype),
                preferred_element_type=jnp.float32)
        else:
            router_logits = jnp.einsum(
                "td,de->te",
                (tokens if router_x is None
                 else router_x.reshape(b * s, d)).astype(jnp.float32),
                lp["router"].astype(jnp.float32))
    if share:
        # one chip's share of a wider router: the held experts' terms by
        # either dispatch, the identity experts' term beside them, the
        # absent experts' terms left out
        with jax.named_scope("moe_route"):
            chosen = _share_gates(router_logits, lp["router_bias"], cfg)
        identity, assign = _share_identity(tokens, *chosen, cfg)
    if _dispatch_grouped(cfg, b * s, stack):
        out, aux = _moe_grouped(tokens, router_logits, *stack, cfg,
                                chosen if share else None)
        if share:
            out, aux = out + identity, {"assign": assign}
        return out.reshape(b, s, d), aux
    with jax.named_scope("moe_route"):
        if share:
            dispatch, combine, _, _ = _one_hot_routing(
                *chosen, cfg.num_experts, _capacity(cfg, b * s))
            aux = {"assign": assign}
        else:
            dispatch, combine, aux = _routing(
                router_logits,
                *_gates(router_logits, lp.get("router_bias"), cfg),
                _capacity(cfg, b * s))

    # (T, E, C) x (T, D) -> (E, C, D): the all-to-all, inserted by XLA from
    # the `ep` sharding of the expert axis.
    with jax.named_scope("moe_dispatch"):
        xs = jnp.einsum("tec,td->ecd", dispatch.astype(cfg.dtype), tokens)
    with jax.named_scope("moe_experts"):
        gate = jnp.einsum("ecd,edf->ecf", xs,
                          lp["w_gate"].astype(cfg.dtype))
        up = jnp.einsum("ecd,edf->ecf", xs, lp["w_up"].astype(cfg.dtype))
        act = gated(gate, up, cfg.mlp_activation)
        ys = jnp.einsum("ecf,efd->ecd", act,
                        lp["w_down"].astype(cfg.dtype))
    with jax.named_scope("moe_combine"):
        out = jnp.einsum("tec,ecd->td", combine.astype(cfg.dtype), ys)
    if share:
        out = out + identity
    return out.reshape(b, s, d), aux


# ---------------------------------------------------------------------------
# Model: dense attention + MoE MLP blocks
# ---------------------------------------------------------------------------

def param_shapes(cfg: ModelConfig) -> dict[str, Any]:
    """The expert model's leaves. `layers` is the stack of expert layers;
    a model with leading dense layers has them in a stack of their own
    before it, `lead_layers`, with the dense model's leaves
    (`cfg.layer_stack`)."""
    shapes = transformer.param_shapes(cfg)
    lead = cfg.num_dense_layers
    L, D, E, F = (cfg.num_layers - lead, cfg.embed_dim, cfg.num_experts,
                  cfg.expert_width)
    layers = transformer.dense_layer_shapes(cfg, L)
    layers["router"] = (L, D, E)
    layers["w_gate"] = (L, E, D, F)
    layers["w_up"] = (L, E, D, F)
    layers["w_down"] = (L, E, F, D)
    if cfg.router_score == "sigmoid":
        layers["router_bias"] = (L, E)
    if cfg.shared_expert_dim:
        Fs = cfg.shared_expert_dim
        layers.update(shared_w_gate=(L, D, Fs), shared_w_up=(L, D, Fs),
                      shared_w_down=(L, Fs, D))
    shapes["layers"] = layers
    if lead:
        shapes["lead_layers"] = transformer.dense_layer_shapes(cfg, lead)
    return shapes


def param_logical_axes(cfg: ModelConfig) -> dict[str, Any]:
    axes = transformer.param_logical_axes(cfg)
    layers = axes["layers"]
    layers["router"] = ("layers", "embed", None)
    layers["w_gate"] = ("layers", "experts", "embed", "expert_mlp")
    layers["w_up"] = ("layers", "experts", "embed", "expert_mlp")
    layers["w_down"] = ("layers", "experts", "expert_mlp", "embed")
    if cfg.router_score == "sigmoid":
        layers["router_bias"] = ("layers", None)
    if cfg.shared_expert_dim:
        layers.update(shared_w_gate=("layers", "embed", "mlp"),
                      shared_w_up=("layers", "embed", "mlp"),
                      shared_w_down=("layers", "mlp", "embed"))
    if cfg.num_dense_layers:
        axes["lead_layers"] = transformer.dense_layer_axes(cfg)
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
              "w_up": cfg.embed_dim, "shared_w_gate": cfg.embed_dim,
              "shared_w_up": cfg.embed_dim,
              "tokens": cfg.embed_dim, "kernel": cfg.embed_dim,
              "wq": cfg.embed_dim, "wk": cfg.embed_dim, "wv": cfg.embed_dim,
              "wg": cfg.embed_dim, "wo": cfg.num_heads * cfg.head_dim}
    out = []
    for (path, shape), key in zip(paths, keys):
        name = path[-1].key
        path_str = "/".join(p.key for p in path)
        if "norm" in path_str:
            out.append(jnp.ones(shape, dtype))
        elif name == "router_bias":  # the balancing bias starts at zero
            out.append(jnp.zeros(shape, dtype))
        else:
            # a way out, dense, expert or shared, is (..., width, D)
            std = 1.0 / math.sqrt(shape[-2] if name.endswith("w_down")
                                  else fan_in[name])
            out.append((jax.random.truncated_normal(
                key, -2.0, 2.0, shape, jnp.float32) * std).astype(dtype))
    return jax.tree.unflatten(treedef, out)


def moe_mlp_block(x, lp, cfg: ModelConfig, stack=None, layer_in=None):
    """Residual MoE MLP sub-block: norm -> route/experts -> add.

    The single definition shared by training (`_moe_block`) and the
    inference engine (`engine._mlp_apply`), so serve-time MoE math can
    never drift from the trained model. `stack`: see `moe_mlp`.
    `layer_in`: the layer's input, before its attention; what the router
    reads under `cfg.router_input` "layer_input", and then required.
    """
    h = rms_norm(x, lp["mlp_norm"], cfg.norm_eps)
    router_x = None
    if cfg.router_input == "layer_input":
        if layer_in is None:
            raise ValueError("router_input='layer_input': the caller has "
                             "to hand the layer's input to the MLP block")
        router_x = layer_in
    out, aux = moe_mlp(h, lp, cfg, stack, router_x)
    if cfg.shared_expert_dim:
        # the always-on expert: once on the normed rows, ungated, whichever
        # dispatch the routed experts took
        with jax.named_scope("moe_shared"):
            act = gated(h @ lp["shared_w_gate"].astype(cfg.dtype),
                        h @ lp["shared_w_up"].astype(cfg.dtype),
                        cfg.mlp_activation)
            out = out + act @ lp["shared_w_down"].astype(cfg.dtype)
    if cfg.post_norms:
        out = rms_norm(out, lp["mlp_post_norm"], cfg.norm_eps)
    return x + out, aux


def _moe_block(x, lp, cfg: ModelConfig, cos, sin, attn_fn, positions=None,
               flags=None):
    y = transformer._attention_block(x, lp, cfg, cos, sin, attn_fn,
                                     positions, flags)
    return moe_mlp_block(y, lp, cfg, layer_in=x)


def forward_hidden(params: Params, tokens: jnp.ndarray, cfg: ModelConfig,
                   segment_ids: jnp.ndarray | None = None):
    """(B, S) -> (final-normed hidden (B, S, D), aux dict of router stats).

    segment_ids: optional packed-sequence ids — same block-diagonal
    attention + per-document RoPE semantics as the dense family
    (transformer.forward_hidden)."""
    if cfg.layer_body != "single" or cfg.routed_scaling_factor > 0:
        raise NotImplementedError(
            "moe.forward_hidden scans single layers of renormalised top-k "
            "experts; the double layer with shortcut experts and one "
            "chip's share of a wider router (LongCat-Flash) are served by "
            "the paged server only")
    cos, sin = rope_table(cfg, tokens.shape[1])
    # Unshard the table's embed dim BEFORE the lookup: a tp-sharded D at
    # the gather makes XLA produce a D-sharded (B, S, D) it must then
    # replicate-and-repartition to the batch/sequence layout ("Involuntary
    # full rematerialization" in the SPMD partitioner). One table
    # all-gather per forward is strictly cheaper.
    table = transformer.constrain(params["embed"]["tokens"].astype(cfg.dtype),
                      ("vocab", None))
    x = table[tokens]
    if cfg.embedding_multiplier != 1.0:
        x = x * cfg.embedding_multiplier
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

    flags = transformer.layer_flags(cfg)
    lead = cfg.num_dense_layers
    if lead:
        # the leading dense layers: a scan of their own stack, the dense
        # model's block, before the scan over the expert layers
        dense = transformer.apply_remat(
            partial(transformer._block, cfg=cfg, cos=cos, sin=sin,
                    attn_fn=attn_fn, positions=positions), cfg)
        x, _ = lax.scan(
            lambda c, xs: ((dense(c, xs) if flags is None
                            else dense(c, xs[0], flags=xs[1])), None),
            x, params["lead_layers"] if flags is None else (
                params["lead_layers"],
                jax.tree.map(lambda f: f[:lead], flags)))
        if flags is not None:
            flags = jax.tree.map(lambda f: f[lead:], flags)

    def scan_body(carry, xs):
        x, lb, rz, dropped = carry
        x, aux = (block(x, xs) if flags is None
                  else block(x, xs[0], flags=xs[1]))
        return (x, lb + aux["load_balance"], rz + aux["router_z"],
                dropped + aux["dropped_frac"]), None

    zero = jnp.zeros((), jnp.float32)
    (x, lb, rz, dropped), _ = lax.scan(
        scan_body, (x, zero, zero, zero),
        params["layers"] if flags is None else (params["layers"], flags))
    x = rms_norm(x, params["final_norm"]["scale"], cfg.norm_eps)
    n = cfg.num_layers - lead
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
