"""Fused unembed + cross-entropy statistics as pallas TPU kernels.

The dense training loss materialises (B*S, V) f32 logits (~1 GB at the
330M bench config), reads them back for logsumexp/gather, and runs the
backward's two big matmuls with an f32 d_logits operand — f32 MXU
passes are several times slower than bf16. r5's step decomposition
(benchmarks/step_decomposition.py) measured the CE block at ~16.5 ms
of the 220 ms step against an ~8 ms bf16-matmul floor.

This module computes the SAME statistics with no f32 logits in HBM
(the backward deliberately emits ONE model-dtype (N, V) buffer — the
d_logits operand for the dW matmul; half the dense path's f32 logits,
and a measured win over recomputing it):

  forward   — one kernel, online logsumexp over vocab tiles: for each
              row tile, stream W's vocab tiles through VMEM, matmul on
              the MXU, fold the tile into running (max, sumexp),
              gather the target logit and the running argmax. Outputs
              (logz, target_logit, argmax) — 3 scalars per row.
  backward  — d_logits = g * softmax + h * onehot is rebuilt ON THE
              FLY per tile from the saved logz (no second online
              pass), cast to the model dtype, and consumed by two
              accumulation kernels: dx (rows outer, vocab inner) and
              dW (vocab outer, rows inner). The recompute costs one
              extra matmul pass each — cheaper than the dense path's
              f32 passes + logits round trips.

Gradient numerics: the d_logits operand is cast to x.dtype before the
MXU (bf16 on the bench config). The dense path promotes that matmul to
f32 — so gradients differ at bf16 resolution, the same resolution
every other activation gradient in the model already has. With an f32
model the kernels are bit-comparable to the dense path (tested).

Used by `transformer.next_token_loss` when `cfg.ce_impl == "pallas"`.
`interpret=True` (automatic off-TPU) runs the same kernels through the
pallas interpreter so numerics are verified on CPU.

Reference parity note: view-sonic/Cloud-Server @ v0 is an empty tree
(SURVEY.md); this kernel is part of the re-scoped build inventory
(training-loss hot path).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as _np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# dtype-determined MXU precision: under a global
# jax_default_matmul_precision="highest" a plain jnp.dot of bf16 tiles
# asks Mosaic for an fp32 contraction, which it refuses ("Bad lhs type")
from cloud_server_tpu.ops.flash_attention import _dot

NEG_INF = -1e30


def _pick_tile(n: int, want: int, unit: int) -> int:
    """Largest multiple of `unit` that divides n, capped at `want`."""
    t = min(want, n)
    t -= t % unit
    while t >= unit and n % t:
        t -= unit
    return t


# ---------------------------------------------------------------------------
# forward: (logz, target_logit, argmax) per row
# ---------------------------------------------------------------------------


def _fwd_kernel(x_ref, w_ref, t_ref, logz_ref, tl_ref, am_ref,
                m_ref, l_ref, tla_ref, amv_ref, *, tv: int, nv: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        tla_ref[:] = jnp.zeros_like(tla_ref)
        amv_ref[:] = jnp.full_like(amv_ref, NEG_INF)
        am_ref[:] = jnp.zeros_like(am_ref)

    logits = _dot(x_ref[:], w_ref[:], ((1,), (0,)))  # (TN, TV) f32
    cols = j * tv + jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
    hit = cols == t_ref[:]  # (TN, 1) broadcasts
    tla_ref[:] += jnp.sum(jnp.where(hit, logits, 0.0), axis=1,
                          keepdims=True)

    bm = jnp.max(logits, axis=1, keepdims=True)          # (TN, 1)
    bi = jnp.argmax(logits, axis=1).astype(jnp.int32)    # (TN,)
    m_old = m_ref[:]
    m_new = jnp.maximum(m_old, bm)
    l_ref[:] = (l_ref[:] * jnp.exp(m_old - m_new)
                + jnp.sum(jnp.exp(logits - m_new), axis=1,
                          keepdims=True))
    m_ref[:] = m_new
    upd = bm > amv_ref[:]
    am_ref[:] = jnp.where(upd, j * tv + bi[:, None], am_ref[:])
    amv_ref[:] = jnp.maximum(amv_ref[:], bm)

    @pl.when(j == nv - 1)
    def _emit():
        logz_ref[:] = jnp.log(l_ref[:]) + m_ref[:]
        tl_ref[:] = tla_ref[:]


# ---------------------------------------------------------------------------
# backward: dx and dW from rebuilt per-tile d_logits
# ---------------------------------------------------------------------------


def _dlogits(x, w, t_col, logz_col, g_col, h_col, j, tv):
    logits = _dot(x, w, ((1,), (0,)))
    p = jnp.exp(logits - logz_col)
    cols = j * tv + jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
    d = g_col * p + jnp.where(cols == t_col, h_col, 0.0)
    return d.astype(x.dtype)  # model-dtype MXU pass (see module doc)


def _dx_kernel(x_ref, w_ref, t_ref, logz_ref, g_ref, h_ref, dx_ref,
               d_ref, *, tv: int, nv: int):
    """Rebuild d_logits per tile, accumulate dx = d @ W^T, and WRITE
    the model-dtype d tile out — dW then needs no second recompute
    pass (it's one plain XLA matmul over the emitted d)."""
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        dx_ref[:] = jnp.zeros_like(dx_ref)

    d = _dlogits(x_ref[:], w_ref[:], t_ref[:], logz_ref[:], g_ref[:],
                 h_ref[:], j, tv)
    d_ref[:] = d
    dx_ref[:] += _dot(d, w_ref[:], ((1,), (1,)))  # d @ W^T


# ---------------------------------------------------------------------------
# public op with custom vjp
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def fused_ce_stats(x, head, targets, interpret: bool | None = None):
    """x: (N, D) model dtype; head: (D, V) model dtype; targets: (N,)
    int32. Returns (logz (N,) f32, target_logit (N,) f32,
    argmax (N,) int32) — the statistics the CE loss and metrics need.
    The FORWARD materialises no (N, V) array; the backward emits one
    model-dtype (N, V) d_logits buffer for the dW matmul (see module
    docstring). Differentiable wrt x and head. N must tile by 128 and
    V by 128."""
    out, _ = _fwd(x, head, targets, interpret)
    return out


def _resolve(interpret):
    return jax.default_backend() != "tpu" if interpret is None else bool(
        interpret)


def _fwd(x, head, targets, interpret):
    interpret = _resolve(interpret)
    n, d = x.shape
    v = head.shape[1]
    tn = _pick_tile(n, 256, 128)
    tv = _pick_tile(v, 3200, 128)
    if tn == 0 or tv == 0:
        raise ValueError(
            f"fused_ce_stats needs N ({n}) and V ({v}) divisible by "
            "128; pad the batch or use the dense/chunked CE path")
    nr, nv = n // tn, v // tv
    t2 = targets.astype(jnp.int32)[:, None]
    logz, tl, am = pl.pallas_call(
        functools.partial(_fwd_kernel, tv=tv, nv=nv),
        grid=(nr, nv),
        in_specs=[
            pl.BlockSpec((tn, d), lambda i, j: (i, 0)),
            pl.BlockSpec((d, tv), lambda i, j: (0, j)),
            pl.BlockSpec((tn, 1), lambda i, j: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((tn, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((tn, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((tn, 1), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, 1), jnp.float32),
            jax.ShapeDtypeStruct((n, 1), jnp.float32),
            jax.ShapeDtypeStruct((n, 1), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((tn, 1), jnp.float32),
            pltpu.VMEM((tn, 1), jnp.float32),
            pltpu.VMEM((tn, 1), jnp.float32),
            pltpu.VMEM((tn, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=100 * 1024 * 1024),
        interpret=interpret,
        name="fused_ce_fwd",
    )(x, head, t2)
    out = (logz[:, 0], tl[:, 0], am[:, 0])
    return out, (x, head, t2, logz)


def _bwd(interpret, res, cts):
    interpret = _resolve(interpret)
    x, head, t2, logz = res
    d_logz, d_tl, _ = cts  # argmax cotangent is float0
    n, d = x.shape
    v = head.shape[1]
    tn = _pick_tile(n, 256, 128)
    # the bwd kernels carry an f32 accumulator (dx or dW) in VMEM on
    # top of the double-buffered inputs, so they need the scoped-vmem
    # limit raised past the 16 MB default (v5e has 128 MB physical);
    # big vocab tiles keep the MXU busy and the grid short
    tv = _pick_tile(v, 3200, 128)
    nr, nv = n // tn, v // tv
    bwd_params = pltpu.CompilerParams(vmem_limit_bytes=100 * 1024 * 1024)
    g = d_logz.astype(jnp.float32)[:, None]
    h = d_tl.astype(jnp.float32)[:, None]
    row_specs = [
        pl.BlockSpec((tn, d), lambda i, j: (i, 0)),
        pl.BlockSpec((d, tv), lambda i, j: (0, j)),
        pl.BlockSpec((tn, 1), lambda i, j: (i, 0)),
        pl.BlockSpec((tn, 1), lambda i, j: (i, 0)),
        pl.BlockSpec((tn, 1), lambda i, j: (i, 0)),
        pl.BlockSpec((tn, 1), lambda i, j: (i, 0)),
    ]
    dx, d_full = pl.pallas_call(
        functools.partial(_dx_kernel, tv=tv, nv=nv),
        grid=(nr, nv),
        in_specs=row_specs,
        out_specs=[
            pl.BlockSpec((tn, d), lambda i, j: (i, 0)),
            pl.BlockSpec((tn, tv), lambda i, j: (i, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, d), jnp.float32),
            jax.ShapeDtypeStruct((n, v), x.dtype),
        ],
        compiler_params=bwd_params,
        interpret=interpret,
        name="fused_ce_dx",
    )(x, head, t2, logz, g, h)
    # dW = x^T @ d over the emitted tiles: one model-dtype matmul XLA
    # already runs near peak — no hand-rolled kernel, and no second
    # recompute pass (the old two-kernel scheme rebuilt the logits for
    # dW a third time)
    dw = jax.lax.dot_general(x, d_full, (((0,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    zeros_t = _np.zeros(t2.shape[:1], jax.dtypes.float0)
    return dx.astype(x.dtype), dw.astype(head.dtype), zeros_t


fused_ce_stats.defvjp(_fwd, _bwd)


def fused_ce_stats_sharded(x, head, targets, mesh,
                           interpret: bool | None = None):
    """`fused_ce_stats` under a device mesh. jit cannot partition a
    Mosaic kernel, so the rows are split over EVERY mesh axis (each
    device owns N / mesh.size rows, a multiple of 128) and each device
    runs the kernels on its rows against the whole head under shard_map.
    Rows are independent, so the forward has no collectives; the
    backward sums the per-device head gradients (shard_map's transpose
    of the replicated operand)."""
    from jax.sharding import PartitionSpec as P

    rows = P(mesh.axis_names)
    return jax.shard_map(
        lambda x, h, t: fused_ce_stats(x, h, t, interpret), mesh=mesh,
        in_specs=(P(mesh.axis_names, None), P(), rows),
        out_specs=(rows, rows, rows), check_vma=False)(x, head, targets)
