"""Paged attention: kernel vs gather-reference vs dense causal_attention."""

import functools
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cloud_server_tpu.inference.paged_engine import quantize_pool
from cloud_server_tpu.ops.attention import causal_attention
from cloud_server_tpu.ops.paged_attention import (
    gather_pages, paged_attention, paged_attention_xla)


@pytest.fixture(scope="module", autouse=True)
def _drop_compiled_programs():
    """Every interpreted kernel case compiles a CPU program of its own,
    and a test process dies inside XLA's compile once it holds 65,530
    memory mappings (run_tests.sh): give this file's back when it ends,
    for the files the same worker runs after it."""
    yield
    jax.clear_caches()


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


def _ragged_cells(b, blk, mp_keys):
    """Lengths of `b` decode rows whose cells of the wide kernel's grid
    run 1, 2 and 5 blocks of `blk` keys one after the other, with rows of
    length 0 between live rows and as the first and the last row."""
    pattern = [0, blk - 3, 2 * blk - 1, 4 * blk + 1, 5, 0, blk, blk + 1,
               5 * blk, 1, 2 * blk]
    lens = [min(pattern[i % len(pattern)], mp_keys) for i in range(b)]
    lens[-1] = 0
    return lens


def _decode_case(rng, *, g, pages_per_block, b=64, kh=2, d=16, ps=8):
    """The serving batch's decode call at toy widths: `b` rows of one
    query, `g` query heads a key head, a table of five blocks a row."""
    blk = ps * pages_per_block
    mp = 5 * pages_per_block
    q, k_pool, v_pool, _, tables = _make_case(
        rng, b=b, w=1, h=g * kh, kh=kh, d=d, ps=ps, mp=mp,
        num_pages=b * mp + 7)
    lengths = jnp.asarray(_ragged_cells(b, blk, mp * ps), jnp.int32)
    return q, k_pool, v_pool, lengths, tables


@pytest.mark.parametrize("g", [4, 7])
@pytest.mark.parametrize("pages_per_block", [2, 4, 8])
def test_wide_kernel_decode_rows_chain_across_cells(g, pages_per_block):
    """W = 1 and 64 rows take the wide kernel: every cell's first block is
    fetched by the cell before it, through cells of 1, 2 and 5 blocks and
    rows that hold nothing (first, last and between live rows)."""
    q, k_pool, v_pool, lengths, tables = _decode_case(
        jax.random.key(20 + g), g=g, pages_per_block=pages_per_block)
    got = paged_attention(q, k_pool, v_pool, lengths, tables, 1,
                          pages_per_block=pages_per_block, interpret=True)
    want = _dense_ref(q, k_pool, v_pool, lengths, tables, 1)
    live = np.asarray(lengths) > 0
    assert live[1] and not live[0] and not live[-1] and not live[5]
    np.testing.assert_allclose(np.asarray(got)[live], np.asarray(want)[live],
                               atol=2e-4, rtol=2e-4)
    assert bool(jnp.isfinite(got).all())


def test_wide_kernel_decode_rows_int8():
    q, k_pool, v_pool, lengths, tables = _decode_case(
        jax.random.key(30), g=4, pages_per_block=4)
    kq, ksc = quantize_pool(k_pool)
    vq, vsc = quantize_pool(v_pool)
    k_deq = (kq.astype(jnp.float32) * ksc[:, :, :, None, :])
    v_deq = (vq.astype(jnp.float32) * vsc[:, :, :, None, :])
    want = _dense_ref(q, k_deq, v_deq, lengths, tables, 0)
    got = paged_attention(q, kq, vq, lengths, tables, 0, pages_per_block=4,
                          interpret=True, k_scale_pool=ksc, v_scale_pool=vsc)
    live = np.asarray(lengths) > 0
    np.testing.assert_allclose(np.asarray(got)[live], np.asarray(want)[live],
                               atol=5e-3, rtol=5e-3)
    assert bool(jnp.isfinite(got).all())


def test_wide_kernel_ragged_chunks_of_unequal_blocks():
    """Chunks 48 wide with ragged widths, their cells 1 to 8 blocks long
    and one row empty: the chain's parity follows the blocks run."""
    w, b = 48, 6
    q, k_pool, v_pool, _, tables = _make_case(
        jax.random.key(31), b=b, w=w, mp=16, num_pages=b * 16 + 3)
    widths = jnp.asarray([48, 1, 13, 0, 40, 7], jnp.int32)
    base = jnp.asarray([80, 3, 0, 0, 20, 55], jnp.int32)
    lengths = base + widths
    got = paged_attention(q, k_pool, v_pool, lengths, tables, 0,
                          pages_per_block=2, interpret=True, widths=widths)
    want = paged_attention_xla(q, k_pool, v_pool, lengths, tables, 0,
                               widths=widths)
    for i, wi in enumerate(np.asarray(widths)):
        np.testing.assert_allclose(got[i, :wi], want[i, :wi], atol=2e-4,
                                   rtol=2e-4)
    assert bool(jnp.isfinite(got).all())


def _poisoned(k_pool, v_pool, tables, lengths, poison, *, ps, behind=None):
    """The pools with every page that no row's table names inside the
    row's bound filled with `poison`, and every table entry outside the
    bound (past the row's last key; with `behind`, also the pages wholly
    behind row i's lower bound behind[i]) pointing at such a page."""
    tab = np.asarray(tables).copy()
    used = np.zeros(k_pool.shape[1], bool)
    for i, n in enumerate(np.asarray(lengths)):
        first = 0 if behind is None else int(behind[i]) // ps
        last = -(-int(n) // ps)
        used[tab[i, first:last]] = True
        tab[i, :first] = tab[i, last:] = k_pool.shape[1]
    bad = jnp.asarray(~used)[None, :, None, None, None]

    def spoil(pool):
        return jnp.concatenate([jnp.where(bad, poison, pool),
                                jnp.full_like(pool[:, :1], poison)], axis=1)

    return spoil(k_pool), spoil(v_pool), jnp.asarray(tab)


@pytest.mark.parametrize("poison", [np.nan, np.inf], ids=["nan", "inf"])
@pytest.mark.parametrize("shape", ["decode", "chunks"])
def test_wide_kernel_poisoned_pool(shape, poison):
    """No byte of a page outside a row's bound reaches an output: with
    every such page NaN or inf, and scratch that starts as NaN (the
    interpreter's uninitialised memory), the outputs are finite and the
    clean pool's bit for bit. A page that is not fetched leaves its stale
    buffer to weights of exactly 0, and 0 x NaN is NaN: the kernel's
    first cell zeroes the value buffers."""
    if shape == "decode":
        q, k_pool, v_pool, lengths, tables = _decode_case(
            jax.random.key(40), g=7, pages_per_block=4)
        kw = dict(pages_per_block=4)
    else:
        q, k_pool, v_pool, _, tables = _make_case(
            jax.random.key(41), b=5, w=48, mp=16, num_pages=5 * 16 + 3)
        widths = jnp.asarray([48, 1, 13, 0, 40], jnp.int32)
        lengths = jnp.asarray([80, 3, 0, 0, 20], jnp.int32) + widths
        kw = dict(pages_per_block=2, widths=widths)
    clean = paged_attention(q, k_pool, v_pool, lengths, tables, 0,
                            interpret=True, **kw)
    kp, vp, tab = _poisoned(k_pool, v_pool, tables, lengths, poison, ps=8)
    got = paged_attention(q, kp, vp, lengths, tab, 0, interpret=True, **kw)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_array_equal(np.asarray(got), np.asarray(clean))


# sha256 of the narrow kernel's jaxpr at b = 8, w = 4 as commit ecc80bc
# (PR 41, before the wide kernel's fetch chain) traced it: the narrow
# kernel's callers lower to what they lowered to
_NARROW_JAXPR = {
    ("float32", False):
        "2129358b1becf1a0e6591b4aaa8e485e7058b35715415fb867520b1a3836846e",
    ("float32", True):
        "54d9c3b59c463df4b6938adcd4b5aa23b306cea2d29cf55ec7cdfef2aab2b681",
    ("bfloat16", False):
        "ad364585fe7566ce03367d1ca58af9b013f6d2f24f5554e8ed1400c145df7944",
    ("bfloat16", True):
        "4525e3bc9046aa8be0616e2966d8d755179cacc4930f82c24cd89820efb7836f",
}


@pytest.mark.parametrize("dtype,int8", sorted(_NARROW_JAXPR))
def test_narrow_kernel_traces_to_what_it_did(dtype, int8):
    """The narrow kernel shares its block body with the wide one; its
    jaxpr (ragged widths, a window, two pages a block) is letter for
    letter the one it had before they shared it."""
    b, w, h, kh, d, ps, mp, pages = 8, 4, 8, 2, 16, 8, 6, 48
    S = jax.ShapeDtypeStruct
    pool = S((2, pages, kh, d, ps), jnp.int8 if int8 else dtype)
    args = [S((b, w, h, d), dtype), pool, pool, S((b,), jnp.int32),
            S((b, mp), jnp.int32), S((), jnp.int32), S((b,), jnp.int32)]
    if int8:
        args += [S((2, pages, kh, ps), jnp.float32)] * 2

    def f(q, k, v, lens, tabs, layer, widths, *scales):
        kw = (dict(k_scale_pool=scales[0], v_scale_pool=scales[1])
              if scales else {})
        return paged_attention(q, k, v, lens, tabs, layer, pages_per_block=2,
                               interpret=True, widths=widths, window=20, **kw)

    text = str(jax.make_jaxpr(f)(*args))
    assert "paged_attention_narrow" in text
    assert hashlib.sha256(text.encode()).hexdigest() == \
        _NARROW_JAXPR[dtype, int8]


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


@pytest.mark.on_tpu
@pytest.mark.parametrize("pages_per_block", [2, 8])
def test_compiled_on_tpu_wide_decode_rows_poisoned(pages_per_block):
    """The serving batch's decode call compiled (20 rows take the wide
    kernel): cells of 1 to 4 blocks and empty rows chained on the chip,
    right against the reference and untouched by NaN in every page
    outside a row's bound."""
    b = 20
    q, k_pool, v_pool, tables = _gqa_case(jax.random.key(14), b=b, w=1)
    lengths = jnp.asarray([0, 1, 128, 129, 700, 1024, 0, 300, 513, 1000,
                           5, 0, 0, 257, 1023, 640, 64, 900, 384, 0],
                          jnp.int32)
    fn = jax.jit(functools.partial(
        paged_attention, pages_per_block=pages_per_block, interpret=False))
    clean = fn(q, k_pool, v_pool, lengths, tables, 1)
    kp, vp, tab = _poisoned(k_pool, v_pool, tables, lengths, np.nan, ps=128)
    got = fn(q, kp, vp, lengths, tab, 1)
    assert np.isfinite(np.asarray(got, np.float32)).all()
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(clean, np.float32))
    live = np.asarray(lengths) > 0
    want = paged_attention_xla(
        q.astype(jnp.float32), k_pool.astype(jnp.float32),
        v_pool.astype(jnp.float32), lengths, tables, 1)
    np.testing.assert_allclose(np.asarray(got, np.float32)[live],
                               np.asarray(want)[live], atol=2e-2, rtol=2e-2)
