"""The paged kernels with a lower bound of the keys against
`paged_attention_xla`, in interpret mode: decode rows and ragged chunks,
7 query heads a key head, bounds inside a page, inside a block and on both
their edges (2e-4: the tolerance of tests/test_paged_attention.py)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from test_paged_attention import (  # noqa: F401
    _decode_case, _drop_compiled_programs, _poisoned)
from window_model import (  # noqa: F401
    CHUNK, LOGITS_ATOL, LOGPROB_ATOL, PAGE, WINDOW, assert_pages_balance,
    make_model, make_server, ref_logits, serve_all, tokens_of,
    worst_logprob_diff)
from cloud_server_tpu.config import InferConfig, ModelConfig  # noqa: F401
from cloud_server_tpu.inference import paged_engine, paged_server  # noqa: F401
from cloud_server_tpu.inference.block_allocator import WindowPagePool  # noqa: F401
from cloud_server_tpu.inference.paged_server import PagedInferenceServer  # noqa: F401
from cloud_server_tpu.models import moe  # noqa: F401
from cloud_server_tpu.ops.paged_attention import (  # noqa: F401
    paged_attention, paged_attention_xla)
from cellbench import reference  # noqa: F401


@pytest.fixture(scope="module")
def model():
    return make_model()


def kernel_case(b, w, lengths, *, ps=8, mp=12, g=7, kh=2, d=16, seed=0):
    ks = jax.random.split(jax.random.key(seed), 3)
    pages = b * mp
    k_pool = jax.random.normal(ks[0], (2, pages, kh, d, ps), jnp.float32)
    v_pool = jax.random.normal(ks[1], (2, pages, kh, d, ps), jnp.float32)
    q = jax.random.normal(ks[2], (b, w, g * kh, d), jnp.float32)
    perm = np.random.RandomState(seed).permutation(pages)
    return (q, k_pool, v_pool, jnp.asarray(lengths, jnp.int32),
            jnp.asarray(perm.reshape(b, mp), jnp.int32))


# page 8, blocks of 2 pages (16 keys): bounds inside a page (5, 13), on a
# page's edge (8, 24), on a block's edge (16, 32), inside a block (20),
# one key, and wider than every context (200)
BOUNDS = [1, 5, 8, 13, 16, 20, 24, 32, 200]


@pytest.mark.parametrize("window", BOUNDS)
@pytest.mark.parametrize("b", [3, 17], ids=["narrow", "wide"])
def test_decode_rows_with_a_lower_bound(b, window):
    """W = 1 and G = 7: the folded rows are 7, no multiple of 8. Contexts
    from one key to the whole table, ends on and off the page edges."""
    lengths = [1, 37, 96, 40, 64, 17, 88, 9, 16, 33, 72, 95, 24, 48, 57,
               80, 41][:b]
    q, kp, vp, lens, tables = kernel_case(b, 1, lengths, seed=window)
    want = paged_attention_xla(q, kp, vp, lens, tables, 1, window=window)
    got = paged_attention(q, kp, vp, lens, tables, 1, pages_per_block=2,
                          interpret=True, window=window)
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=2e-4)
    if window < 30:  # the bound bites: not what reading every key gives
        free = paged_attention_xla(q, kp, vp, lens, tables, 1)
        assert np.abs(np.asarray(free) - np.asarray(want)).max() > 1e-3


@pytest.mark.parametrize("window", BOUNDS)
def test_ragged_chunks_with_a_lower_bound_never_fetch_behind_it(window):
    """Chunks 48 wide with ragged widths (the wide kernel), each query
    row with its own bound. The table's entries for pages wholly behind
    the first query's bound point at a poisoned page, as after the
    hand-back: the kernel never fetches a block that is wholly behind it,
    and masks inside the block that holds it."""
    lengths, widths = [96, 50, 77], [48, 13, 40]
    q, kp, vp, lens, tables = kernel_case(3, 48, lengths, seed=window)
    wid = jnp.asarray(widths, jnp.int32)
    want = paged_attention_xla(q, kp, vp, lens, tables, 0, widths=wid,
                               window=window)
    poison = kp.shape[1]
    kp = jnp.concatenate([kp, jnp.full_like(kp[:, :1], 1e4)], axis=1)
    vp = jnp.concatenate([vp, jnp.full_like(vp[:, :1], 1e4)], axis=1)
    tab = np.asarray(tables).copy()
    for i, (n, w) in enumerate(zip(lengths, widths)):
        tab[i, :max(n - w - (window - 1), 0) // 8] = poison
    got = paged_attention(q, kp, vp, lens, jnp.asarray(tab), 0,
                          pages_per_block=2, interpret=True, widths=wid,
                          window=window)
    for i, w in enumerate(widths):  # rows past a row's width are garbage
        np.testing.assert_allclose(got[i, :w], want[i, :w], atol=2e-4,
                                   rtol=2e-4)
    assert np.isfinite(np.asarray(got)).all()


def test_a_chunk_as_wide_as_the_serving_chunk(model):
    """256 queries, 7 heads a key head, pages of 128 as on the chip, the
    bound inside the chunk itself."""
    q, kp, vp, lens, tables = kernel_case(2, 256, [700, 300], ps=128, mp=6,
                                          kh=1, seed=9)
    wid = jnp.asarray([256, 44], jnp.int32)
    for window in (100, 128, 300):
        want = paged_attention_xla(q, kp, vp, lens, tables, 0, widths=wid,
                                   window=window)
        got = paged_attention(q, kp, vp, lens, tables, 0, pages_per_block=2,
                              interpret=True, widths=wid, window=window)
        np.testing.assert_allclose(got[0], want[0], atol=2e-4, rtol=2e-4)
        np.testing.assert_allclose(got[1, :44], want[1, :44], atol=2e-4,
                                   rtol=2e-4)


@pytest.mark.parametrize("g", [4, 7])
@pytest.mark.parametrize("pages_per_block", [2, 4, 8])
def test_a_serving_batch_of_decode_rows_with_a_lower_bound(
        g, pages_per_block):
    """64 decode rows (the wide kernel), cells of 1, 2 and 5 blocks and
    empty rows between them; the bound puts the long rows' first block
    past block 0 and falls inside a block and inside a page."""
    q, kp, vp, lens, tables = _decode_case(
        jax.random.key(50 + g), g=g, pages_per_block=pages_per_block)
    window = 2 * 8 * pages_per_block + 5
    want = paged_attention_xla(q, kp, vp, lens, tables, 1, window=window)
    got = paged_attention(q, kp, vp, lens, tables, 1,
                          pages_per_block=pages_per_block, interpret=True,
                          window=window)
    live = np.asarray(lens) > 0
    np.testing.assert_allclose(np.asarray(got)[live], np.asarray(want)[live],
                               atol=2e-4, rtol=2e-4)
    free = paged_attention_xla(q, kp, vp, lens, tables, 1)
    assert np.abs(np.asarray(free) - np.asarray(want))[live].max() > 1e-3
    assert np.isfinite(np.asarray(got)).all()


@pytest.mark.parametrize("poison", [np.nan, np.inf], ids=["nan", "inf"])
@pytest.mark.parametrize("shape", ["decode", "chunks"])
def test_no_page_outside_a_rows_bounds_reaches_an_output(shape, poison):
    """Every page wholly behind a row's lower bound or past its last key,
    and every page no table names, is NaN or inf, page by page and not
    block by block: the outputs are finite and the clean pool's, bit for
    bit."""
    if shape == "decode":
        q, kp, vp, lens, tables = _decode_case(
            jax.random.key(60), g=7, pages_per_block=4)
        wid = jnp.ones_like(lens)
        window, kw = 2 * 32 + 5, dict(pages_per_block=4)
    else:
        q, kp, vp, lens, tables = kernel_case(3, 48, [96, 50, 77], seed=61)
        wid = jnp.asarray([48, 13, 40], jnp.int32)
        window, kw = 20, dict(pages_per_block=2, widths=wid)
    clean = paged_attention(q, kp, vp, lens, tables, 0, interpret=True,
                            window=window, **kw)
    behind = np.maximum(np.asarray(lens) - np.asarray(wid) - (window - 1), 0)
    kp, vp, tab = _poisoned(kp, vp, tables, lens, poison, ps=8,
                            behind=behind)
    got = paged_attention(q, kp, vp, lens, tab, 0, interpret=True,
                          window=window, **kw)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_array_equal(np.asarray(got), np.asarray(clean))
