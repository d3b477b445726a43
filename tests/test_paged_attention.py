"""Paged attention: kernel vs gather-reference vs dense causal_attention."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cloud_server_tpu.inference.paged_engine import quantize_pool
from cloud_server_tpu.ops.attention import causal_attention
from cloud_server_tpu.ops.paged_attention import (
    gather_pages, paged_attention, paged_attention_xla)


def _make_case(rng, *, b=3, w=1, h=4, kh=2, d=16, ps=8, mp=6, L=2,
               num_pages=32, dtype=jnp.float32):
    """Random pools + a random (valid) paging of each slot's history.
    Pools are TRANSPOSED pages: (L, P, KH, Dh, ps)."""
    ks = jax.random.split(rng, 6)
    k_pool = jax.random.normal(ks[0], (L, num_pages, kh, d, ps), dtype)
    v_pool = jax.random.normal(ks[1], (L, num_pages, kh, d, ps), dtype)
    q = jax.random.normal(ks[2], (b, w, h, d), dtype)
    # distinct random pages per slot => aliasing bugs show as mismatches
    perm = np.random.RandomState(0).permutation(num_pages)[:b * mp]
    tables = jnp.asarray(perm.reshape(b, mp), jnp.int32)
    lengths = jnp.asarray(
        np.random.RandomState(1).randint(w, mp * ps + 1, size=(b,)),
        jnp.int32)
    return q, k_pool, v_pool, lengths, tables


def _dense_ref(q, k_pool, v_pool, lengths, tables, layer):
    b, w = q.shape[:2]
    k = gather_pages(k_pool, tables, layer)
    v = gather_pages(v_pool, tables, layer)
    pos = lengths[:, None] - w + jnp.arange(w)[None, :]
    return causal_attention(q, k, v, q_positions=pos, kv_length=lengths)


@pytest.mark.parametrize("w", [1, 4])
@pytest.mark.parametrize("h,kh", [(4, 4), (4, 2)])
def test_xla_reference_matches_dense(w, h, kh):
    q, k_pool, v_pool, lengths, tables = _make_case(
        jax.random.key(0), w=w, h=h, kh=kh)
    for layer in range(k_pool.shape[0]):
        got = paged_attention_xla(q, k_pool, v_pool, lengths, tables, layer)
        want = _dense_ref(q, k_pool, v_pool, lengths, tables, layer)
        np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("w", [1, 4])
@pytest.mark.parametrize("h,kh", [(4, 4), (4, 2)])
@pytest.mark.parametrize("pages_per_block", [1, 2, 4])
def test_kernel_interpret_matches_dense(w, h, kh, pages_per_block):
    q, k_pool, v_pool, lengths, tables = _make_case(
        jax.random.key(1), w=w, h=h, kh=kh)
    for layer in range(k_pool.shape[0]):
        got = paged_attention(q, k_pool, v_pool, lengths, tables, layer,
                              pages_per_block=pages_per_block,
                              interpret=True)
        want = _dense_ref(q, k_pool, v_pool, lengths, tables, layer)
        np.testing.assert_allclose(got, want, atol=2e-4, rtol=2e-4)


def test_kernel_interpret_short_lengths():
    """Lengths inside the first block, including an empty slot."""
    q, k_pool, v_pool, _, tables = _make_case(jax.random.key(2), w=1)
    lengths = jnp.asarray([1, 0, 5], jnp.int32)
    got = paged_attention(q, k_pool, v_pool, lengths, tables, 0,
                          pages_per_block=2, interpret=True)
    want = _dense_ref(q, k_pool, v_pool, lengths, tables, 0)
    # slot 1 is inactive (length 0): its output is unspecified garbage
    np.testing.assert_allclose(got[0], want[0], atol=2e-4, rtol=2e-4)
    np.testing.assert_allclose(got[2], want[2], atol=2e-4, rtol=2e-4)
    assert bool(jnp.isfinite(got).all())




@pytest.mark.parametrize("w", [48, 64])
@pytest.mark.parametrize("h,kh", [(4, 4), (4, 2)])
def test_wide_kernel_interpret_matches_dense(w, h, kh):
    """w > 32 routes the grid-over-(slot, head) wide kernel — the
    chunked-prefill path; parity with the dense reference."""
    q, k_pool, v_pool, lengths, tables = _make_case(
        jax.random.key(5), w=w, h=h, kh=kh, mp=16, num_pages=64)
    for layer in range(k_pool.shape[0]):
        got = paged_attention(q, k_pool, v_pool, lengths, tables, layer,
                              pages_per_block=2, interpret=True)
        want = _dense_ref(q, k_pool, v_pool, lengths, tables, layer)
        np.testing.assert_allclose(got, want, atol=2e-4, rtol=2e-4)


def test_wide_kernel_big_batch_narrow_window():
    """b > 16 routes the wide kernel even at W=1 (the narrow kernel's
    static slot unroll would bloat code size at serving batches)."""
    q, k_pool, v_pool, lengths, tables = _make_case(
        jax.random.key(6), b=20, w=1, mp=4, num_pages=96)
    got = paged_attention(q, k_pool, v_pool, lengths, tables, 0,
                          pages_per_block=2, interpret=True)
    want = _dense_ref(q, k_pool, v_pool, lengths, tables, 0)
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=2e-4)


def test_wide_kernel_int8():
    q, k_pool, v_pool, lengths, tables = _make_case(
        jax.random.key(7), w=48, mp=16, num_pages=64)
    kq, ksc = quantize_pool(k_pool)
    vq, vsc = quantize_pool(v_pool)
    k_deq = (kq.astype(jnp.float32) * ksc[:, :, :, None, :])
    v_deq = (vq.astype(jnp.float32) * vsc[:, :, :, None, :])
    want = _dense_ref(q, k_deq, v_deq, lengths, tables, 1)
    got = paged_attention(q, kq, vq, lengths, tables, 1,
                          pages_per_block=2, interpret=True,
                          k_scale_pool=ksc, v_scale_pool=vsc)
    np.testing.assert_allclose(got, want, atol=5e-3, rtol=5e-3)


@pytest.mark.parametrize("impl", ["xla", "kernel"])
def test_int8_scales_paths(impl):
    q, k_pool, v_pool, lengths, tables = _make_case(jax.random.key(3), w=2)
    kq, ksc = quantize_pool(k_pool)
    vq, vsc = quantize_pool(v_pool)
    # oracle: dequantize then dense (scales broadcast over the Dh axis)
    k_deq = (kq.astype(jnp.float32) * ksc[:, :, :, None, :])
    v_deq = (vq.astype(jnp.float32) * vsc[:, :, :, None, :])
    want = _dense_ref(q, k_deq, v_deq, lengths, tables, 1)
    if impl == "xla":
        got = paged_attention_xla(q, kq, vq, lengths, tables, 1,
                                  k_scale_pool=ksc, v_scale_pool=vsc)
    else:
        got = paged_attention(q, kq, vq, lengths, tables, 1,
                              pages_per_block=2, interpret=True,
                              k_scale_pool=ksc, v_scale_pool=vsc)
    np.testing.assert_allclose(got, want, atol=5e-3, rtol=5e-3)


@pytest.mark.on_tpu
def test_compiled_on_tpu_paged_attention():
    """The narrow kernel's real Mosaic lowering on chip."""
    q, k_pool, v_pool, lengths, tables = _make_case(
        jax.random.key(4), b=4, w=4, h=8, kh=8, d=64, ps=128, mp=4,
        num_pages=32, dtype=jnp.bfloat16)
    fn = jax.jit(functools.partial(paged_attention, pages_per_block=2,
                                   interpret=False))
    got = fn(q, k_pool, v_pool, lengths, tables, 0)
    want = _dense_ref(q.astype(jnp.float32), k_pool.astype(jnp.float32),
                      v_pool.astype(jnp.float32), lengths, tables, 0)
    np.testing.assert_allclose(np.asarray(got, np.float32), want,
                               atol=2e-2, rtol=2e-2)


@pytest.mark.on_tpu
def test_compiled_on_tpu_wide_kernel():
    """The wide (grid) kernel's Mosaic lowering on chip, bf16 and
    int8, at a prefill-chunk width."""
    q, k_pool, v_pool, lengths, tables = _make_case(
        jax.random.key(8), b=4, w=64, h=8, kh=8, d=64, ps=128, mp=4,
        num_pages=32, dtype=jnp.bfloat16)
    fn = jax.jit(functools.partial(paged_attention, pages_per_block=2,
                                   interpret=False))
    got = fn(q, k_pool, v_pool, lengths, tables, 0)
    want = _dense_ref(q.astype(jnp.float32), k_pool.astype(jnp.float32),
                      v_pool.astype(jnp.float32), lengths, tables, 0)
    np.testing.assert_allclose(np.asarray(got, np.float32), want,
                               atol=2e-2, rtol=2e-2)

    kq, ksc = quantize_pool(k_pool.astype(jnp.float32))
    vq, vsc = quantize_pool(v_pool.astype(jnp.float32))
    got8 = jax.jit(functools.partial(
        paged_attention, pages_per_block=2, interpret=False))(
            q, kq, vq, lengths, tables, 0,
            k_scale_pool=ksc, v_scale_pool=vsc)
    k_deq = (kq.astype(jnp.float32) * ksc[:, :, :, None, :])
    v_deq = (vq.astype(jnp.float32) * vsc[:, :, :, None, :])
    want8 = _dense_ref(q.astype(jnp.float32), k_deq, v_deq, lengths,
                       tables, 0)
    np.testing.assert_allclose(np.asarray(got8, np.float32), want8,
                               atol=5e-2, rtol=5e-2)


def _ragged_ref(q, k_pool, v_pool, lengths, tables, widths, layer):
    """Row-by-row oracle: each row computed as its OWN uniform-width
    window (slice the dispatch's W down to widths[b]); rows past their
    width are unspecified."""
    outs = []
    for i in range(q.shape[0]):
        wi = int(widths[i])
        row = paged_attention_xla(
            q[i:i + 1, :max(wi, 1)], k_pool, v_pool, lengths[i:i + 1],
            tables[i:i + 1], layer)
        outs.append(row[0])
    return outs


@pytest.mark.parametrize("h,kh", [(4, 4), (4, 2)])
def test_ragged_widths_xla_matches_per_row(h, kh):
    """The mixed scheduler's dispatch shape: decode rows (width 1) and
    prefill rows (width = chunk) in ONE call via per-row `widths` —
    every valid query must equal the row's own uniform-width dispatch."""
    w = 6
    q, k_pool, v_pool, lengths, tables = _make_case(
        jax.random.key(3), w=w, h=h, kh=kh)
    widths = jnp.asarray([1, 6, 3], jnp.int32)
    # lengths INCLUDE the row's own window: re-derive from a base
    base = jnp.asarray([5, 9, 2], jnp.int32)
    lengths = base + widths
    got = paged_attention_xla(q, k_pool, v_pool, lengths, tables, 0,
                              widths=widths)
    refs = _ragged_ref(q, k_pool, v_pool, lengths, tables, widths, 0)
    for i in range(q.shape[0]):
        wi = int(widths[i])
        np.testing.assert_allclose(got[i, :wi], refs[i][:wi],
                                   atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("narrow", [True, False])
def test_ragged_widths_kernel_matches_xla(narrow):
    """Pallas kernels (narrow batch-unrolled AND wide grid variants)
    implement the identical ragged rule as the XLA fallback."""
    w = 6 if narrow else 40  # > _NARROW_MAX_W selects the wide kernel
    q, k_pool, v_pool, _, tables = _make_case(
        jax.random.key(4), w=w, mp=8, num_pages=40)
    widths = jnp.asarray([1, w, w // 2], jnp.int32)
    base = jnp.asarray([7, 3, 11], jnp.int32)
    lengths = base + widths
    got = paged_attention(q, k_pool, v_pool, lengths, tables, 0,
                          pages_per_block=2, interpret=True,
                          widths=widths)
    want = paged_attention_xla(q, k_pool, v_pool, lengths, tables, 0,
                               widths=widths)
    for i in range(q.shape[0]):
        wi = int(widths[i])
        np.testing.assert_allclose(got[i, :wi], want[i, :wi],
                                   atol=2e-4, rtol=2e-4)


def _gqa_case(rng, *, b, w, int8=False):
    """Llama-3.2-1B attention geometry (32 query / 8 kv heads of 64, G=4,
    128-token pages) in bf16: the shape the serving path meets on chip."""
    q, k_pool, v_pool, _, tables = _make_case(
        rng, b=b, w=w, h=32, kh=8, d=64, ps=128, mp=8, L=2,
        num_pages=b * 8 + 3, dtype=jnp.bfloat16)
    return q, k_pool, v_pool, tables


def _check_ragged(got, q, k_pool, v_pool, lengths, tables, widths, layer,
                  atol, **scales):
    want = paged_attention_xla(
        q.astype(jnp.float32), k_pool, v_pool, lengths, tables, layer,
        widths=widths, **scales)
    for i, wi in enumerate(np.asarray(widths)):
        np.testing.assert_allclose(
            np.asarray(got[i, :wi], np.float32), np.asarray(want[i, :wi]),
            atol=atol, rtol=atol, err_msg=f"row {i} width {wi}")


@pytest.mark.on_tpu
@pytest.mark.parametrize("w", [1, 4])
def test_compiled_on_tpu_narrow_gqa_ragged(w):
    """Narrow kernel, GQA row folding (W*G = 4 rows at decode, below the
    bf16 sublane tile) with ragged per-row widths — the decode and
    speculative-verify dispatch of a mixed batch."""
    b = 8
    q, k_pool, v_pool, tables = _gqa_case(jax.random.key(11), b=b, w=w)
    widths = jnp.asarray(([1, w, max(w // 2, 1), w] * 2)[:b], jnp.int32)
    base = jnp.asarray([0, 5, 127, 128, 300, 511, 640, 1000], jnp.int32)
    lengths = base + widths
    got = jax.jit(functools.partial(
        paged_attention, pages_per_block=8, interpret=False))(
            q, k_pool, v_pool, lengths, tables, 1, widths=widths)
    _check_ragged(got, q, k_pool.astype(jnp.float32),
                  v_pool.astype(jnp.float32), lengths, tables, widths, 1,
                  2e-2)


@pytest.mark.on_tpu
def test_compiled_on_tpu_wide_gqa_ragged():
    """Wide kernel at the default prefill chunk (W = 256, G = 4: 1,024
    query rows a cell) with ragged widths — decode rows and prefill
    rows of a mixed batch in one call."""
    b, w = 4, 256
    q, k_pool, v_pool, tables = _gqa_case(jax.random.key(12), b=b, w=w)
    widths = jnp.asarray([1, 256, 128, 17], jnp.int32)
    base = jnp.asarray([700, 0, 256, 500], jnp.int32)
    lengths = base + widths
    got = jax.jit(functools.partial(
        paged_attention, pages_per_block=4, interpret=False))(
            q, k_pool, v_pool, lengths, tables, 0, widths=widths)
    _check_ragged(got, q, k_pool.astype(jnp.float32),
                  v_pool.astype(jnp.float32), lengths, tables, widths, 0,
                  2e-2)


@pytest.mark.on_tpu
def test_compiled_on_tpu_gqa_int8_kv():
    """int8 KV at the GQA geometry, decode width, narrow kernel."""
    b = 8
    q, k_pool, v_pool, tables = _gqa_case(jax.random.key(13), b=b, w=1)
    kq, ksc = quantize_pool(k_pool.astype(jnp.float32))
    vq, vsc = quantize_pool(v_pool.astype(jnp.float32))
    widths = jnp.ones((b,), jnp.int32)
    lengths = jnp.asarray([1, 6, 128, 129, 301, 512, 641, 1001], jnp.int32)
    got = jax.jit(functools.partial(
        paged_attention, pages_per_block=8, interpret=False))(
            q, kq, vq, lengths, tables, 1, widths=widths,
            k_scale_pool=ksc, v_scale_pool=vsc)
    _check_ragged(got, q, kq, vq, lengths, tables, widths, 1, 5e-2,
                  k_scale_pool=ksc, v_scale_pool=vsc)
