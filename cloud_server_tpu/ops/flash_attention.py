"""Pallas TPU flash attention (forward + backward).

Blockwise causal attention with online softmax. The grid is
(batch, q_heads, q_blocks, kv_blocks); the kv axis is innermost so the f32
accumulators (o_acc, running max m, running sum l) live in VMEM scratch
across kv iterations of one q block — TPU grids execute sequentially on a
core, which is what makes carrying scratch across grid steps sound.

GQA is handled in the index maps: kv blocks for q-head h come from kv-head
h // (H // KH); no materialised repeat of k/v.

The backward pass recomputes p blockwise (flash style) in ONE
kv-stationary (batch, heads, kv_blocks, q_blocks) pass that yields dk/dv
(scratch-accumulated) and per-kv-block dq partials (summed by XLA
outside). Sequences that fit one block skip the staging entirely via a
fused whole-sequence kernel. Blocked + single recompute is what lets
block sizes shrink to where the causal block skip pays (a lone S-sized
block computes the full S x S square, twice the needed FLOPs).

On non-TPU backends (tests), `interpret=True` runs the same kernels through
the pallas interpreter so numerics are verified on CPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
# LSE is logically (B, H, S); it is stored rank-4 as (B, H, S, LSE_LANES).
# A rank-3 (1, 1, block_q) block spec does not lower on TPU (Mosaic needs
# the last two block dims (8, 128)-tileable *or* equal to the array dims).
# With LSE_LANES=1 the trailing block dim equals the array dim, which is
# legal, and HBM storage/traffic stays 1 lane instead of a 128x broadcast.
LSE_LANES = 1


def _default_block(seq: int, want: int) -> int:
    b = min(seq, want)
    while seq % b:
        b //= 2
    return max(b, 1)



def _dot(a, b, dims):
    """dot_general with f32 accumulation and dtype-determined precision.

    bf16 operands must use DEFAULT precision — a global
    jax_default_matmul_precision="highest" (tests/conftest.py sets it for
    CPU numerics) would request an fp32 contraction on bf16 vectors, which
    Mosaic rejects ("Bad lhs type"). f32 operands keep HIGHEST so the
    interpret-mode parity tests stay exact.
    """
    prec = (jax.lax.Precision.DEFAULT if a.dtype == jnp.bfloat16
            else jax.lax.Precision.HIGHEST)
    return jax.lax.dot_general(a, b, (dims, ((), ())), precision=prec,
                               preferred_element_type=jnp.float32)


# ---------------------------------------------------------------------------
# Forward kernel
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, *refs, scale, block_q, block_kv,
                kv_seq_len, has_seg):
    if has_seg:
        sq_ref, skv_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref = refs
    else:
        o_ref, lse_ref, acc_ref, m_ref, l_ref = refs
    i, j = pl.program_id(2), pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    # Block-level causal skip: kv block strictly after the q block's end.
    @pl.when(j * block_kv <= i * block_q + block_q - 1)
    def _compute():
        # Feed the MXU its native operand dtype (bf16 in, f32 accumulate);
        # casting to f32 first would force multi-pass f32 matmuls.
        q = q_ref[0, 0]  # (bq, d)
        k = k_ref[0, 0]  # (bkv, d)
        v = v_ref[0, 0]
        s = _dot(q, k, ((1,), (1,))) * scale  # (bq, bkv)

        q_pos = i * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_kv), 0)
        kv_pos = j * block_kv + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_kv), 1)
        mask = q_pos >= kv_pos
        if has_seg:
            # (bq, 1) rows vs (1, bkv) lanes -> (bq, bkv), no transpose
            mask &= sq_ref[0, 0] == skv_ref[0, 0]
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[:, :1]  # (bq, 1)
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_new = l_ref[:, :1] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[:] = acc_ref[:] * corr + _dot(p.astype(v.dtype), v, ((1,), (0,)))
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(j == pl.num_programs(3) - 1)
    def _finalize():
        l = l_ref[:, :1]
        o_ref[0, 0] = (acc_ref[:] / l).astype(o_ref.dtype)
        # LSE is logically (bq,) but stored lane-padded as (bq, 128):
        # Mosaic requires the last two block dims to be (8,128)-tileable,
        # so a rank-3 (1, 1, bq) block spec does not lower on TPU.
        lse_ref[0, 0] = jnp.broadcast_to(m_ref[:, :1] + jnp.log(l),
                                         lse_ref.shape[2:])


def _seg_views(segment_ids):
    """(B, S) ids -> ((B,1,S,1) row view, (B,1,1,S) lane view). Rank-4 with
    singleton trailing/leading dims keeps the block shapes Mosaic-legal
    (same trick as LSE_LANES) and lets kernels compare (bq,1) == (1,bkv)
    without an in-kernel transpose."""
    return segment_ids[:, None, :, None], segment_ids[:, None, None, :]


def _seg_specs(block_q, block_kv, qs_order=True):
    """(row-view spec, lane-view spec); qs_order: grid is (..., i, j) with
    q index first, else (..., j, i) kv-stationary."""
    if qs_order:
        row = pl.BlockSpec((1, 1, block_q, 1),
                           lambda bi, hi, i, j: (bi, 0, i, 0))
        lane = pl.BlockSpec((1, 1, 1, block_kv),
                            lambda bi, hi, i, j: (bi, 0, 0, j))
    else:
        row = pl.BlockSpec((1, 1, block_q, 1),
                           lambda bi, hi, j, i: (bi, 0, i, 0))
        lane = pl.BlockSpec((1, 1, 1, block_kv),
                            lambda bi, hi, j, i: (bi, 0, 0, j))
    return row, lane


def _fwd(q, k, v, segment_ids, *, scale, block_q, block_kv, interpret):
    b, h, sq, d = q.shape
    _, kh, skv, _ = k.shape
    g = h // kh
    has_seg = segment_ids is not None
    grid = (b, h, pl.cdiv(sq, block_q), pl.cdiv(skv, block_kv))

    kv_spec = pl.BlockSpec((1, 1, block_kv, d),
                           lambda bi, hi, i, j: (bi, hi // g, j, 0))
    in_specs = [
        pl.BlockSpec((1, 1, block_q, d), lambda bi, hi, i, j: (bi, hi, i, 0)),
        kv_spec,
        kv_spec,
    ]
    inputs = [q, k, v]
    if has_seg:
        in_specs.extend(_seg_specs(block_q, block_kv))
        inputs.extend(_seg_views(segment_ids))
    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, block_q=block_q,
                          block_kv=block_kv, kv_seq_len=skv,
                          has_seg=has_seg),
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda bi, hi, i, j: (bi, hi, i, 0)),
            pl.BlockSpec((1, 1, block_q, LSE_LANES),
                         lambda bi, hi, i, j: (bi, hi, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, sq, d), q.dtype),
            jax.ShapeDtypeStruct((b, h, sq, LSE_LANES), jnp.float32),
        ],
        scratch_shapes=[
            _vmem((block_q, d), jnp.float32),
            _vmem((block_q, 128), jnp.float32),
            _vmem((block_q, 128), jnp.float32),
        ],
        interpret=interpret,
        name="flash_fwd",
    )(*inputs)
    # Named so remat policies can choose to save these instead of re-running
    # the kernel in the backward pass (see models/transformer.py remat="dots").
    from jax.ad_checkpoint import checkpoint_name
    out = checkpoint_name(out, "flash_out")
    lse = checkpoint_name(lse, "flash_lse")
    return out, lse


def _vmem(shape, dtype):
    return pltpu.VMEM(shape, dtype)


# ---------------------------------------------------------------------------
# Backward kernels (flash-style recompute)
# ---------------------------------------------------------------------------

def _bwd_dkdv_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, do_ref, *refs,
                     scale, block_q, block_kv, has_seg):
    """kv-stationary backward producing dk, dv (q innermost so they
    accumulate in scratch); dq runs as a second q-stationary pass.

    Negative result (v5e, r3): a single-pass variant that staged
    per-kv-block dq partials in a (nkv, ...) f32 HBM array — trading the
    second recompute pass for nkv x dq-bytes of traffic — measured SLOWER
    at every shape tried (S=2048/1024-blocks: 67.2 vs 65.2 ms;
    S=1024/512-blocks: 242 vs 236) because the backward is
    bandwidth-bound, not compute-bound. The staged path was deleted in r4;
    this two-pass layout is the keeper."""
    if has_seg:
        sq_ref, skv_ref, dk_ref, dv_ref, dk_acc, dv_acc = refs
    else:
        dk_ref, dv_ref, dk_acc, dv_acc = refs
    j, i = pl.program_id(2), pl.program_id(3)  # kv-stationary: q innermost

    @pl.when(i == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    @pl.when(i * block_q + block_q - 1 >= j * block_kv)
    def _compute():
        # Raw (bf16) operands into every dot; f32 only for the softmax math.
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        o = o_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0]
        lse = lse_ref[0, 0][:, :1]  # lane-padded (bq, LSE_LANES) -> (bq, 1)

        s = _dot(q, k, ((1,), (1,))) * scale
        q_pos = i * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_kv), 0)
        kv_pos = j * block_kv + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_kv), 1)
        mask = q_pos >= kv_pos
        if has_seg:
            mask &= sq_ref[0, 0] == skv_ref[0, 0]
        p = jnp.where(mask, jnp.exp(s - lse), 0.0)  # (bq, bkv)

        dv_acc[:] += _dot(p.astype(do.dtype), do, ((0,), (0,)))
        delta = jnp.sum(do.astype(jnp.float32) * o, axis=-1,
                        keepdims=True)  # (bq, 1)
        dp = _dot(do, v, ((1,), (1,)))
        ds = p * (dp - delta) * scale
        dk_acc[:] += _dot(ds.astype(q.dtype), q, ((0,), (0,)))

    @pl.when(i == pl.num_programs(3) - 1)
    def _finalize():
        dk_ref[0, 0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[:].astype(dv_ref.dtype)


def _bwd_fused_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, do_ref, *refs,
                      scale, sq, has_seg):
    """Whole-sequence backward: one grid cell per (batch, head) computes
    dq, dk, dv together, so s and p are built once instead of once per
    kernel. Only used when the sequence fits a single block (S <= block);
    the blocked two-kernel path below handles longer sequences."""
    if has_seg:
        sq_ref, skv_ref, dq_ref, dk_ref, dv_ref = refs
    else:
        dq_ref, dk_ref, dv_ref = refs
    q = q_ref[0, 0]
    k = k_ref[0, 0]
    v = v_ref[0, 0]
    o = o_ref[0, 0].astype(jnp.float32)
    do = do_ref[0, 0]
    lse = lse_ref[0, 0][:, :1]

    s = _dot(q, k, ((1,), (1,))) * scale
    q_pos = jax.lax.broadcasted_iota(jnp.int32, (sq, sq), 0)
    kv_pos = jax.lax.broadcasted_iota(jnp.int32, (sq, sq), 1)
    mask = q_pos >= kv_pos
    if has_seg:
        mask &= sq_ref[0, 0] == skv_ref[0, 0]
    p = jnp.where(mask, jnp.exp(s - lse), 0.0)

    pc = p.astype(do.dtype)
    dv_ref[0, 0] = _dot(pc, do, ((0,), (0,))).astype(dv_ref.dtype)
    delta = jnp.sum(do.astype(jnp.float32) * o, axis=-1, keepdims=True)
    dp = _dot(do, v, ((1,), (1,)))
    ds = (p * (dp - delta) * scale).astype(q.dtype)
    dq_ref[0, 0] = _dot(ds, k, ((1,), (0,))).astype(dq_ref.dtype)
    dk_ref[0, 0] = _dot(ds, q, ((0,), (0,))).astype(dk_ref.dtype)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, do_ref, *refs,
                   scale, block_q, block_kv, has_seg):
    """q-stationary dq pass (kv innermost, dq accumulates in scratch).
    Recomputes s/p a second time — measured cheaper than staging dq
    partials through HBM on v5e (see _bwd_dkdv_kernel's docstring)."""
    if has_seg:
        sq_ref, skv_ref, dq_ref, dq_acc = refs
    else:
        dq_ref, dq_acc = refs
    i, j = pl.program_id(2), pl.program_id(3)  # q-stationary: kv innermost

    @pl.when(j == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    @pl.when(j * block_kv <= i * block_q + block_q - 1)
    def _compute():
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        o = o_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0]
        lse = lse_ref[0, 0][:, :1]  # lane-padded (bq, LSE_LANES) -> (bq, 1)

        s = _dot(q, k, ((1,), (1,))) * scale
        q_pos = i * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_kv), 0)
        kv_pos = j * block_kv + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_kv), 1)
        mask = q_pos >= kv_pos
        if has_seg:
            mask &= sq_ref[0, 0] == skv_ref[0, 0]
        p = jnp.where(mask, jnp.exp(s - lse), 0.0)

        delta = jnp.sum(do.astype(jnp.float32) * o, axis=-1, keepdims=True)
        dp = _dot(do, v, ((1,), (1,)))
        ds = p * (dp - delta) * scale
        dq_acc[:] += _dot(ds.astype(k.dtype), k, ((1,), (0,)))

    @pl.when(j == pl.num_programs(3) - 1)
    def _finalize():
        dq_ref[0, 0] = dq_acc[:].astype(dq_ref.dtype)


# ---------------------------------------------------------------------------
# custom_vjp wrapper
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _flash_bhsd(q, k, v, segment_ids, scale, block_q, block_kv, interpret):
    out, _ = _fwd(q, k, v, segment_ids, scale=scale, block_q=block_q,
                  block_kv=block_kv, interpret=interpret)
    return out


def _flash_fwd_rule(q, k, v, segment_ids, scale, block_q, block_kv,
                    interpret):
    out, lse = _fwd(q, k, v, segment_ids, scale=scale, block_q=block_q,
                    block_kv=block_kv, interpret=interpret)
    return out, (q, k, v, segment_ids, out, lse)


def _flash_bwd_rule(scale, block_q, block_kv, interpret, res, do):
    q, k, v, segment_ids, out, lse = res
    b, h, sq, d = q.shape
    _, kh, skv, _ = k.shape
    g = h // kh

    if sq == skv and sq <= block_q and skv <= block_kv:
        return _flash_bwd_fused(q, k, v, segment_ids, out, lse, do,
                                scale=scale, interpret=interpret)

    nq, nkv = pl.cdiv(sq, block_q), pl.cdiv(skv, block_kv)
    has_seg = segment_ids is not None
    seg_inputs = list(_seg_views(segment_ids)) if has_seg else []

    # Pass 1 (kv-stationary, q innermost): dk, dv accumulate in scratch.
    # Outputs are per *q-head*; dk/dv sum over the GQA group afterwards.
    q_spec_ks = pl.BlockSpec((1, 1, block_q, d), lambda bi, hi, j, i: (bi, hi, i, 0))
    kv_spec_ks = pl.BlockSpec((1, 1, block_kv, d),
                              lambda bi, hi, j, i: (bi, hi // g, j, 0))
    lse_spec_ks = pl.BlockSpec((1, 1, block_q, LSE_LANES),
                               lambda bi, hi, j, i: (bi, hi, i, 0))
    dkv_out_spec = pl.BlockSpec((1, 1, block_kv, d),
                                lambda bi, hi, j, i: (bi, hi, j, 0))

    dkdv_in_specs = [q_spec_ks, kv_spec_ks, kv_spec_ks, q_spec_ks,
                     lse_spec_ks, q_spec_ks]
    if has_seg:
        dkdv_in_specs.extend(_seg_specs(block_q, block_kv, qs_order=False))
    dk_h, dv_h = pl.pallas_call(
        functools.partial(_bwd_dkdv_kernel, scale=scale, block_q=block_q,
                          block_kv=block_kv, has_seg=has_seg),
        grid=(b, h, nkv, nq),
        in_specs=dkdv_in_specs,
        out_specs=[dkv_out_spec, dkv_out_spec],
        out_shape=[jax.ShapeDtypeStruct((b, h, skv, d), jnp.float32),
                   jax.ShapeDtypeStruct((b, h, skv, d), jnp.float32)],
        scratch_shapes=[_vmem((block_kv, d), jnp.float32),
                        _vmem((block_kv, d), jnp.float32)],
        interpret=interpret,
        name="flash_bwd_dkdv",
    )(q, k, v, out, lse, do, *seg_inputs)

    # Pass 2 (q-stationary, kv innermost): dq accumulates in scratch.
    q_spec_qs = pl.BlockSpec((1, 1, block_q, d),
                             lambda bi, hi, i, j: (bi, hi, i, 0))
    kv_spec_qs = pl.BlockSpec((1, 1, block_kv, d),
                              lambda bi, hi, i, j: (bi, hi // g, j, 0))
    lse_spec_qs = pl.BlockSpec((1, 1, block_q, LSE_LANES),
                               lambda bi, hi, i, j: (bi, hi, i, 0))
    dq_in_specs = [q_spec_qs, kv_spec_qs, kv_spec_qs, q_spec_qs,
                   lse_spec_qs, q_spec_qs]
    if has_seg:
        dq_in_specs.extend(_seg_specs(block_q, block_kv))
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, block_q=block_q,
                          block_kv=block_kv, has_seg=has_seg),
        grid=(b, h, nq, nkv),
        in_specs=dq_in_specs,
        out_specs=q_spec_qs,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[_vmem((block_q, d), jnp.float32)],
        interpret=interpret,
        name="flash_bwd_dq",
    )(q, k, v, out, lse, do, *seg_inputs)
    dk = dk_h.reshape(b, kh, g, skv, d).sum(axis=2).astype(k.dtype)
    dv = dv_h.reshape(b, kh, g, skv, d).sum(axis=2).astype(v.dtype)
    return dq, dk, dv, None


def _flash_bwd_fused(q, k, v, segment_ids, out, lse, do, *, scale,
                     interpret):
    b, h, sq, d = q.shape
    _, kh, _, _ = k.shape
    g = h // kh
    has_seg = segment_ids is not None

    q_spec = pl.BlockSpec((1, 1, sq, d), lambda bi, hi: (bi, hi, 0, 0))
    kv_spec = pl.BlockSpec((1, 1, sq, d), lambda bi, hi: (bi, hi // g, 0, 0))
    lse_spec = pl.BlockSpec((1, 1, sq, LSE_LANES),
                            lambda bi, hi: (bi, hi, 0, 0))
    in_specs = [q_spec, kv_spec, kv_spec, q_spec, lse_spec, q_spec]
    inputs = [q, k, v, out, lse, do]
    if has_seg:
        in_specs.append(pl.BlockSpec((1, 1, sq, 1),
                                     lambda bi, hi: (bi, 0, 0, 0)))
        in_specs.append(pl.BlockSpec((1, 1, 1, sq),
                                     lambda bi, hi: (bi, 0, 0, 0)))
        inputs.extend(_seg_views(segment_ids))

    dq, dk_h, dv_h = pl.pallas_call(
        functools.partial(_bwd_fused_kernel, scale=scale, sq=sq,
                          has_seg=has_seg),
        grid=(b, h),
        in_specs=in_specs,
        out_specs=[q_spec, q_spec, q_spec],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct((b, h, sq, d), jnp.float32),
                   jax.ShapeDtypeStruct((b, h, sq, d), jnp.float32)],
        interpret=interpret,
        name="flash_bwd_fused",
    )(*inputs)
    dk = dk_h.reshape(b, kh, g, sq, d).sum(axis=2).astype(k.dtype)
    dv = dv_h.reshape(b, kh, g, sq, d).sum(axis=2).astype(v.dtype)
    return dq, dk, dv, None


_flash_bhsd.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def flash_attention(q, k, v, *, scale=None, block_q: int = 1024,
                    block_kv: int = 1024, interpret: bool | None = None,
                    segment_ids=None):
    """Causal flash attention, (B, S, H, Dh) layout like ops.attention.

    q: (B, S, H, Dh); k, v: (B, S, KH, Dh). Returns (B, S, H, Dh).
    segment_ids: optional (B, S) int32 packed-sequence ids — attention is
    additionally masked to same-segment pairs (block-diagonal causal; see
    data/packing.py), in forward and backward.
    """
    b, sq, h, d = q.shape
    if scale is None:
        scale = d**-0.5
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    block_q = _default_block(sq, block_q)
    block_kv = _default_block(k.shape[1], block_kv)
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    seg = (None if segment_ids is None
           else jnp.asarray(segment_ids, jnp.int32))
    out = _flash_bhsd(qt, kt, vt, seg, scale, block_q, block_kv, interpret)
    return out.transpose(0, 2, 1, 3)


def flash_attention_sharded(q, k, v, mesh, *, segment_ids=None,
                            batch_axes=("dp", "fsdp"), head_axis="tp",
                            **kwargs):
    """`flash_attention` under a device mesh. jit cannot partition a
    Mosaic kernel, so each device runs it on its own block under
    shard_map: batch over `batch_axes`, heads over `head_axis` (whole GQA
    groups, so it must divide the kv heads), the sequence whole (sharding
    it belongs to ring/ulysses). The kernel is independent per (batch,
    head), so there are no collectives, forward or backward."""
    from jax.sharding import PartitionSpec as P

    spec = P(batch_axes, None, head_axis, None)
    args, specs = [q, k, v], [spec, spec, spec]
    if segment_ids is not None:
        args.append(segment_ids)
        specs.append(P(batch_axes, None))

    def local(q, k, v, *seg):
        return flash_attention(q, k, v, segment_ids=seg[0] if seg else None,
                               **kwargs)

    return jax.shard_map(local, mesh=mesh, in_specs=tuple(specs),
                         out_specs=spec, check_vma=False)(*args)
