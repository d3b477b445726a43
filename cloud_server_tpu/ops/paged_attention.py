"""Pallas TPU paged attention: uniform query windows vs. a block-table cache.

This is the serving attention kernel. One kernel covers every cached
forward the server issues, because they are all the same computation at
different window widths W:

  * decode:                    W = 1
  * speculative verification:  W = draft length + 1
  * prefix-cache continuation: W = remainder bucket
  * chunked prefill:           W = chunk
  * mixed batch (stall-free):  W = max over rows, RAGGED per-row widths

The last row is the token-budget mixed scheduler's dispatch: decode rows
(width 1, or drafts+1 under speculation) and prefill-chunk rows (width =
chunk) share ONE call. Per-row `widths` make the window ragged: row b's
valid queries are window indices [0, widths[b]) at absolute positions
[lengths[b] - widths[b], lengths[b]) — i.e. `lengths` still counts kv
INCLUDING the row's (own-width) window, and the causal mask anchors each
row at `lengths[b] - widths[b]` instead of the uniform `lengths[b] - W`.
Rows past their width produce garbage (masked by the caller), exactly
like inactive slots. The XLA fallback implements the identical ragged
rule, so both bucket shapes (decode window and prefill chunk) ride one
dispatch on every backend.

The KV cache is PAGED: a global pool of fixed-size pages plus a per-slot
int32 page table, so slot memory scales with actual context (not
max_slots x max_len) and pages can be shared between slots (refcounted
prefix reuse — see inference/block_allocator.py).

A sliding-window layer passes `window`, the keys a query reads with its
own: the key at j is kept for the query at i where i - j < window, per
query row of a wide chunk. Both kernels start at the block that holds the
first query's oldest key (`_first_block`), not at block 0, so the pages
wholly behind the bound are never fetched and the table's entries for
them may be anything: the host gives those pages back to their pool
(inference/paged_engine.py, `PagedKVCache`). With 7 query heads a key
head the folded W*G rows of a decode call are 7, no multiple of the
sublane tile; Mosaic pads them (AOT for a v5e, PR 35).

Design (and why it can beat streaming the cache through XLA einsums):

  * The pool lives in HBM (`memory_space=ANY`); the kernel issues its own
    double-buffered async page copies. Each slot's loop runs
    `cdiv(kv_len, page_size * pages_per_block)` iterations, so pages past
    a slot's length are never fetched — XLA's dense path always streams
    the full padded cache. While one block computes, the next block's
    pages are already in flight, and at a slot's last block the next
    slot's (in the grid kernel: the next cell's) first block is: neither
    kernel starts a slot with an empty pipeline. The grid kernel bounds
    the fetch page by page as well: of a block it fetches only the pages
    that hold a key some query of the row reads.
  * Page layout is (num_pages, KH, Dh, page_size) — pages are stored
    TRANSPOSED, positions on the minor (lane) dim. One page holds every
    kv head for `page_size` positions, so a page is ONE contiguous DMA;
    a per-head slice is a contiguous (Dh, ps) view — exactly the
    transposed right-hand operand the qk matmul wants, with a lane dim
    (ps = 128) that satisfies Mosaic's minor-dim tiling for manual DMA
    slices regardless of head_dim (a position-minor layout would put Dh
    on lanes, and Dh = 64 is not 128-tileable).
  * Online softmax in f32 with per-(head, slot) running m/l/acc carried
    through the loop as values (never re-read from scratch memory).
  * int8 cache: pages are stored int8 with per-(position, head) absmax
    scales in a sibling (num_pages, KH, page_size) f32 pool. Scales are
    algebraically folded into score/prob ROWS (`q.(k*s) == (q.k_int8)*s`
    since s is constant along Dh), so the kernel streams half the HBM
    bytes and never materialises a dequantized page.

The q/o layout is (B, KH, W*G, Dh) — grouped-query rows pre-folded per kv
head — produced by the host-side wrapper below, so in-kernel q slices
are contiguous too.

A LATENT pool (latent attention read in the absorbed form, `latent_dv`;
`models/latent.py`) is (L, num_pages, 1, Dl, page_size): one entry of Dl
values a token, the latent vector of `latent_dv` values and behind it the
rotary key every head shares, positions on the lanes like every page.
There is no value pool. The contract: every query head reads the entry
as its key (the one "key head"), and THE VALUES ARE THE KEYS' FIRST
`latent_dv` ROWS, so a page is fetched once and the output is
`latent_dv` wide. The grid kernel serves it with its second axis over
tiles of the slot's W * H query rows (`_paged_attention_latent`).

Numerics match `ops.attention.causal_attention` (f32 scores and
accumulators); parity is tested against `paged_attention_xla` in
interpret mode on CPU and compiled on TPU
(tests/test_paged_attention.py).

Forward-only by design — serving never backprops.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


def _dot(a, b, dims):
    """dot_general with f32 accumulation and dtype-determined precision:
    bf16 operands must use DEFAULT precision (a global
    jax_default_matmul_precision="highest" would request an fp32
    contraction on bf16 vectors, which Mosaic rejects — "Bad lhs type");
    f32 operands keep HIGHEST so interpret-mode parity stays exact."""
    prec = (lax.Precision.DEFAULT if a.dtype == jnp.bfloat16
            else lax.Precision.HIGHEST)
    return lax.dot_general(a, b, (dims, ((), ())), precision=prec,
                           preferred_element_type=jnp.float32)


def _first_block(kv_len, width, window: int, blk: int, n_blocks):
    """The first block of keys a row's window of queries reads: block 0
    without a lower bound (`window` 0), else the block that holds the
    first query's oldest key, position (kv_len - width) - (window - 1).
    Blocks wholly behind it are never fetched; inside it the mask
    decides. Never past the row's last block, so every row runs one."""
    if not window:
        return 0
    lo = jnp.maximum(kv_len - width - (window - 1), 0)
    return jnp.minimum(lax.div(lo, blk), n_blocks - 1)


def _softmax_block(qh, mask, state, pages, *, scale, ps, dot_dtype):
    """One block of keys folded into a (W*G, Dh) query tile's running
    softmax: `state` is (m, l, acc) in float32, `pages` one tuple a page
    of callables (k, v, k_scale, v_scale) that read its (Dh, ps) slices
    and, for an int8 pool, its (1, ps) scales (else None). Both kernels
    run this body; they differ in where a page's slices lie."""
    m_prev, l_prev, acc_prev = state
    cols = []
    for k_page, _, k_scale, _ in pages:
        s_p = _dot(qh, k_page().astype(dot_dtype), ((1,), (0,)))  # (WG, ps)
        if k_scale is not None:
            s_p = s_p * k_scale()
        cols.append(s_p)
    qk = jnp.concatenate(cols, axis=1) * scale  # (WG, blk)
    qk = jnp.where(mask, qk, NEG_INF)

    m_cur = jnp.max(qk, axis=1, keepdims=True)   # (WG, 1)
    m_new = jnp.maximum(m_prev, m_cur)
    p_full = jnp.exp(qk - m_new)                 # (WG, blk)
    corr = jnp.exp(m_prev - m_new)
    l_new = l_prev * corr + jnp.sum(p_full, axis=1, keepdims=True)
    pv = jnp.zeros(acc_prev.shape, jnp.float32)
    for p, (_, v_page, _, v_scale) in enumerate(pages):
        p_blk = p_full[:, p * ps:(p + 1) * ps]
        if v_scale is not None:
            p_blk = p_blk * v_scale()
        vp = v_page().astype(dot_dtype)
        pv = pv + _dot(p_blk.astype(dot_dtype), vp, ((1,), (1,)))  # (WG, Dh)
    return m_new, l_new, acc_prev * corr + pv


# ---------------------------------------------------------------------------
# Kernel
# ---------------------------------------------------------------------------


def _paged_attention_kernel(
    # scalar prefetch
    lens_ref,          # (B,) i32 — kv length per slot INCLUDING the window
    tables_ref,        # (B, max_pages) i32
    widths_ref,        # (B,) i32 — per-row valid window width (<= W)
    layer_ref,         # (1,) i32 — which pool layer this call attends to
    # inputs
    q_ref,             # (B, KH, WG, Dh) VMEM
    k_pool_ref,        # (L, P, KH, Dh, ps) HBM (ANY) — transposed pages
    v_pool_ref,        # (L, P, KH, Dh, ps) HBM (ANY)
    *refs,             # [k_scale_pool, v_scale_pool,] o_ref, scratch...
    scale: float,
    batch: int,
    w: int,
    g: int,
    kh: int,
    ps: int,
    npages: int,
    int8_kv: bool,
    window: int,
):
    if int8_kv:
        (ks_pool_ref, vs_pool_ref, o_ref,
         kbuf, vbuf, ksbuf, vsbuf, sems) = refs
    else:
        o_ref, kbuf, vbuf, sems = refs
        ks_pool_ref = vs_pool_ref = ksbuf = vsbuf = None
    wg = w * g
    d = q_ref.shape[-1]
    num_pages_total = k_pool_ref.shape[1]
    layer = layer_ref[0]
    blk = ps * npages
    # MXU prefers bf16 operands with f32 accumulation; int8 values are
    # exact in bf16. f32 pools (CPU interpret tests) keep f32.
    dot_dtype = (jnp.float32 if k_pool_ref.dtype == jnp.float32
                 else jnp.bfloat16)

    def n_blocks(b):
        # every slot runs >= 1 block so the cross-slot DMA prefetch chain
        # stays uniform (each started copy has exactly one matching wait)
        return jnp.maximum(1, lax.div(lens_ref[b] + blk - 1, blk))

    def first_block(b):
        return _first_block(lens_ref[b], widths_ref[b], window, blk,
                            n_blocks(b))

    def _copies(buf_idx, page_ids):
        """The async-copy descriptors of one block fetch; `start` on each
        begins it, `wait` blocks until its bytes landed. The pool keeps
        its layer dim so the SAME pool arrays serve every layer's call —
        slicing the layer outside pallas would materialise a full-layer
        copy per call."""
        out = []
        for i in range(npages):
            page = page_ids[i]
            sem = sems.at[buf_idx, i]
            out.append(pltpu.make_async_copy(
                k_pool_ref.at[layer, page], kbuf.at[buf_idx, i], sem))
            out.append(pltpu.make_async_copy(
                v_pool_ref.at[layer, page], vbuf.at[buf_idx, i], sem))
            if int8_kv:
                out.append(pltpu.make_async_copy(
                    ks_pool_ref.at[layer, page], ksbuf.at[buf_idx, i], sem))
                out.append(pltpu.make_async_copy(
                    vs_pool_ref.at[layer, page], vsbuf.at[buf_idx, i], sem))
        return out

    def _block_pages(b, blk_idx):
        """Page ids of block `blk_idx` of slot `b`, clamped into range so
        out-of-bounds blocks fetch (masked) garbage instead of faulting."""
        return [
            jnp.clip(
                tables_ref[b, jnp.clip(blk_idx * npages + i, 0,
                                       tables_ref.shape[1] - 1)],
                0, num_pages_total - 1)
            for i in range(npages)
        ]

    def start_fetch(b, blk_idx, buf_idx):
        for c in _copies(buf_idx, _block_pages(b, blk_idx)):
            c.start()

    def wait_fetch(buf_idx):
        # waits pair up 1:1 with the starts issued into this buffer (the
        # source index is irrelevant to wait; sizes match the starts)
        for c in _copies(buf_idx, [0] * npages):
            c.wait()

    # prologue: first block of slot 0 into buffer 0
    start_fetch(0, first_block(0), 0)

    buf_idx = jnp.int32(0)
    for b in range(batch):  # static unroll over slots
        kv_len = lens_ref[b]
        # window row wi sits at absolute position kv_len - widths[b] + wi
        # (ragged anchor: widths[b] == W for uniform windows); rows of
        # the folded (W*G, ...) layout map to window position row // G
        row_pos = (kv_len - widths_ref[b]) + lax.broadcasted_iota(
            jnp.int32, (wg, blk), 0) // g

        def body(i, carry, b=b, kv_len=kv_len, row_pos=row_pos):
            buf_idx = carry[0]
            state = carry[1:]
            nb = n_blocks(b)

            # prefetch next block (or the next slot's first block) into
            # the other buffer while this one computes
            is_last = i == nb - 1
            nxt = jnp.where(
                is_last, first_block(b + 1) if b + 1 < batch else 0, i + 1)
            if b + 1 < batch:
                nxt_b = jnp.where(is_last, b + 1, b)
                start_fetch(nxt_b, nxt, 1 - buf_idx)
            else:
                @pl.when(jnp.logical_not(is_last))
                def _():
                    start_fetch(b, nxt, 1 - buf_idx)

            wait_fetch(buf_idx)

            col_pos = i * blk + lax.broadcasted_iota(
                jnp.int32, (wg, blk), 1)
            # col < kv_len is implied by col <= row for the last row but
            # not for earlier window rows; both bounds are needed
            mask = jnp.logical_and(col_pos <= row_pos, col_pos < kv_len)
            if window:
                mask = jnp.logical_and(mask, row_pos - col_pos < window)

            new_state = []
            for h in range(kh):
                pages = [
                    (lambda p=p, h=h: kbuf[buf_idx, p, h],
                     lambda p=p, h=h: vbuf[buf_idx, p, h],
                     (lambda p=p, h=h: ksbuf[buf_idx, p, h].reshape(1, ps))
                     if int8_kv else None,
                     (lambda p=p, h=h: vsbuf[buf_idx, p, h].reshape(1, ps))
                     if int8_kv else None)
                    for p in range(npages)]
                new_state += _softmax_block(
                    q_ref[b, h].astype(dot_dtype), mask,
                    state[3 * h:3 * h + 3], pages, scale=scale, ps=ps,
                    dot_dtype=dot_dtype)
            return tuple([1 - buf_idx] + new_state)

        init = [buf_idx]
        for _ in range(kh):
            init += [jnp.full((wg, 1), NEG_INF, jnp.float32),
                     jnp.zeros((wg, 1), jnp.float32),
                     jnp.zeros((wg, d), jnp.float32)]
        out = lax.fori_loop(first_block(b), n_blocks(b), body, tuple(init))
        buf_idx = out[0]
        for h in range(kh):
            # inactive slots (kv_len 0) divide garbage by blk — finite,
            # masked by the caller
            l_h = jnp.maximum(out[1 + 3 * h + 1], 1e-30)
            o_ref[b, h] = (out[1 + 3 * h + 2] / l_h).astype(o_ref.dtype)


# ---------------------------------------------------------------------------
# Host wrapper
# ---------------------------------------------------------------------------

# The batch-unrolled narrow kernel keeps every (slot, head) query row in
# one VMEM block and its code size scales with B x KH x npages; past
# these bounds the grid-over-(slot, head) wide kernel takes over (same
# math, per-cell blocks).
_NARROW_MAX_W = 32
_NARROW_MAX_B = 16
# Block buffers of the wide kernel: one computes while the fetches of the
# three blocks after it are in flight. At a decode call's 4 or 7 query rows
# a block's compute is shorter than its fetch, and one fetch in flight
# leaves the copy engines short of work: 2, 4 and 8 buffers measured 443,
# 391 and 392 us a call at 64 rows of 300 to 2,000 keys (8 kv heads) and
# 648, 533 and 534 at a window layer's mix (the copies unrolled; PERF.md
# section 6, PR 44). A
# power of two: the scalar core pays for a remainder by anything else (6
# buffers measured slower than 2).
_WIDE_BUFFERS = 4
# Query rows of one cell of the grid kernel over a latent pool: a chunk
# of 256 tokens is 64 heads x 256 = 16,384 rows of 576 on the one key
# head, 19 MB, so the rows are cut into tiles and the grid's second axis
# runs over them. 512 rows of bfloat16 are 0.6 MB of queries, 0.5 MB of
# output and 4 MB of float32 scores, weights and accumulator beside 2.4
# MB of page buffers, inside the 16 MB a kernel may use (1,024 rows are
# not: AOT, PR 45); every tile fetches the row's pages again, 1,152 B a
# key for 512 x 1,088 flops.
LATENT_TILE = 512


def paged_attention(q, k_pool, v_pool, lengths, tables, layer=0, *,
                    scale=None, pages_per_block: int = 4,
                    interpret: bool | None = None,
                    k_scale_pool=None, v_scale_pool=None, widths=None,
                    window: int = 0, latent_dv: int = 0,
                    latent_tile: int = LATENT_TILE):
    """Uniform- or ragged-window attention against a paged KV cache.

    Args:
      q: (B, W, H, Dh) — W new positions per slot; slot b's window
        occupies absolute positions [lengths[b] - W, lengths[b]). Its kv
        entries must already be written to the pool (write-then-attend,
        same contract as engine.verify_step).
      widths: optional (B,) int32 per-row valid window widths (<= W) for
        RAGGED mixed batches: row b's window then occupies
        [lengths[b] - widths[b], lengths[b]) and query rows at window
        index >= widths[b] are garbage (mask downstream). None = uniform
        width W for every row.
      k_pool, v_pool: (L, num_pages, KH, Dh, page_size) TRANSPOSED page
        pools (cfg.dtype, or int8 with the scale pools given). The layer
        dim stays on the operand — `layer` selects inside the kernel, so
        no per-layer pool slice is ever materialised. On TPU, page_size
        must be a multiple of 128 (the manual-DMA lane tiling).
      lengths: (B,) int32 — valid kv entries per slot INCLUDING the
        window. Slots with length 0 are inactive (their output rows are
        garbage; mask downstream).
      tables: (B, max_pages_per_slot) int32 page table. Entries past a
        slot's length may be arbitrary: the narrow kernel clamps them
        and masks what it fetched, the wide kernel does not fetch them.
      layer: int or scalar int32 — pool layer to attend against.
      k_scale_pool, v_scale_pool: (L, num_pages, KH, page_size) f32
        absmax scales when the pools are int8.
      window: static int, the keys a query reads, its own included (a
        sliding-window layer): the key at j is kept for the query at i
        where i - j < window; 0 = every key. Blocks wholly behind the
        first query's bound are never fetched, so the table's entries
        for them may be anything (their pages given back).
      latent_dv: static int > 0 for a LATENT pool (latent attention read
        in the absorbed form): `k_pool` is (L, num_pages, 1, Dl, page_size),
        one entry of Dl values a token that every query head reads as
        its key, and the values are the keys' first `latent_dv` rows, so
        `v_pool` is None and a page is fetched once. q is (B, W, H, Dl),
        the result (B, W, H, latent_dv). Always the grid kernel, whose
        second axis then runs over tiles of `latent_tile` of the W * H
        query rows (a chunk's rows do not fit VMEM at once).

    Returns (B, W, H, Dh) in q.dtype. Equivalent to gathering each slot's
    pages into a contiguous cache and running
    `causal_attention(q, k, v, q_positions=lengths[:,None]-W+arange(W),
    kv_length=lengths)` — see `paged_attention_xla` and the parity tests.
    """
    b, w, h, d = q.shape
    _, num_pages, kh, _, ps = k_pool.shape
    g = h // kh
    if scale is None:
        scale = d ** -0.5
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if not interpret and ps % 128:
        raise ValueError(
            f"page_size={ps} must be a multiple of 128 on TPU (Mosaic "
            "manual-DMA slices tile the minor dim by 128)")
    int8_kv = k_scale_pool is not None
    npages = max(1, min(pages_per_block, tables.shape[1]))
    if widths is None:
        widths = jnp.full((b,), w, jnp.int32)

    if latent_dv:
        if kh != 1 or v_pool is not None or int8_kv or window:
            raise ValueError("a latent pool has one entry a token and no "
                             "value pool, no scales and no window")
        # (B, W, H, Dl) is already rows of the one key head, query-major;
        # cut into tiles, which take the place of the grid's head axis
        tile = w * h if (w * h) % latent_tile else min(latent_tile, w * h)
        out = _paged_attention_latent(
            q.reshape(b, w * h // tile, tile, d), k_pool, lengths, tables,
            widths, layer, scale=scale, npages=npages, interpret=interpret,
            w=w, g=g, dv=int(latent_dv))
        return out.reshape(b, w, h, latent_dv)

    # fold (W, G) query rows per kv head: (B, W, KH, G, Dh) -> (B, KH, WG, Dh)
    qg = q.reshape(b, w, kh, g, d).transpose(0, 2, 1, 3, 4).reshape(
        b, kh, w * g, d)

    if w > _NARROW_MAX_W or b > _NARROW_MAX_B:
        out = _paged_attention_wide(
            qg, k_pool, v_pool, lengths, tables, widths, layer, scale=scale,
            npages=npages, interpret=interpret,
            k_scale_pool=k_scale_pool, v_scale_pool=v_scale_pool, w=w, g=g,
            window=window)
        return out.reshape(b, kh, w, g, d).transpose(0, 2, 1, 3, 4).reshape(
            b, w, h, d)

    def _full(shape):
        return pl.BlockSpec(shape, lambda i, *_: (0,) * len(shape))

    in_specs = [
        _full(qg.shape),
        pl.BlockSpec(memory_space=pl.ANY),
        pl.BlockSpec(memory_space=pl.ANY),
    ]
    inputs = [qg, k_pool, v_pool]
    if int8_kv:
        in_specs += [pl.BlockSpec(memory_space=pl.ANY),
                     pl.BlockSpec(memory_space=pl.ANY)]
        inputs += [k_scale_pool, v_scale_pool]

    scratch = [
        pltpu.VMEM((2, npages, kh, d, ps), k_pool.dtype),   # k pages
        pltpu.VMEM((2, npages, kh, d, ps), v_pool.dtype),   # v pages
    ]
    if int8_kv:
        scratch += [pltpu.VMEM((2, npages, kh, ps), jnp.float32),
                    pltpu.VMEM((2, npages, kh, ps), jnp.float32)]
    scratch += [pltpu.SemaphoreType.DMA((2, npages))]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(1,),
        in_specs=in_specs,
        out_specs=_full((b, kh, w * g, d)),
        scratch_shapes=scratch,
    )
    kernel = functools.partial(
        _paged_attention_kernel, scale=float(scale), batch=b, w=w, g=g,
        kh=kh, ps=ps, npages=npages, int8_kv=int8_kv, window=int(window))
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kh, w * g, d), q.dtype),
        interpret=interpret,
        name="paged_attention_narrow",
    )(lengths.astype(jnp.int32), tables.astype(jnp.int32),
      widths.astype(jnp.int32),
      jnp.asarray(layer, jnp.int32).reshape(1), *inputs)
    # (B, KH, WG, Dh) -> (B, W, H, Dh)
    return out.reshape(b, kh, w, g, d).transpose(0, 2, 1, 3, 4).reshape(
        b, w, h, d)


@functools.partial(jax.jit, static_argnames=(
    "scale", "npages", "interpret", "w", "g", "window"))
def _paged_attention_wide(qg, k_pool, v_pool, lengths, tables, widths,
                          layer, *, scale, npages, interpret, k_scale_pool,
                          v_scale_pool, w, g, window=0):
    """Grid-over-(slot, kv head) dispatch for wide windows / big batches.
    qg: (B, KH, WG, Dh) folded queries; returns the same layout.

    Jitted, with `layer` an operand: the unrolled walk of a serving
    program calls it once a layer and row set, and the layers of one kind
    share one trace and one Mosaic lowering (4 of each where a program of
    8 layers made 16: 2.6 of the 6.4 s it took to trace and lower)."""
    b, kh, wg, d = qg.shape
    ps = k_pool.shape[-1]
    int8_kv = k_scale_pool is not None

    cell = pl.BlockSpec((1, 1, wg, d), lambda bi, hi, *_: (bi, hi, 0, 0))
    in_specs = [
        cell,
        pl.BlockSpec(memory_space=pl.ANY),
        pl.BlockSpec(memory_space=pl.ANY),
    ]
    inputs = [qg, k_pool, v_pool]
    if int8_kv:
        in_specs += [pl.BlockSpec(memory_space=pl.ANY),
                     pl.BlockSpec(memory_space=pl.ANY)]
        inputs += [k_scale_pool, v_scale_pool]

    nbuf = _WIDE_BUFFERS
    scratch = [
        pltpu.VMEM((nbuf, npages, d, ps), k_pool.dtype),
        pltpu.VMEM((nbuf, npages, d, ps), v_pool.dtype),
    ]
    if int8_kv:
        scratch += [pltpu.VMEM((nbuf, npages, 1, ps), jnp.float32),
                    pltpu.VMEM((nbuf, npages, 1, ps), jnp.float32)]
    scratch += [pltpu.SemaphoreType.DMA((nbuf, npages)),
                pltpu.SMEM((5,), jnp.int32)]  # the fetch chain's state

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(b, kh),
        in_specs=in_specs,
        out_specs=cell,
        scratch_shapes=scratch,
    )
    kernel = functools.partial(
        _paged_attention_wide_kernel, scale=float(scale), w=w, g=g,
        ps=ps, npages=npages, int8_kv=int8_kv, window=int(window))
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kh, wg, d), qg.dtype),
        interpret=interpret,
        name="paged_attention_wide",
        # the fetch chain runs from each cell to the next: nothing may
        # reorder the cells or split them over cores
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
    )(lengths.astype(jnp.int32), tables.astype(jnp.int32),
      widths.astype(jnp.int32),
      jnp.asarray(layer, jnp.int32).reshape(1), *inputs)


@functools.partial(jax.jit, static_argnames=(
    "scale", "npages", "interpret", "w", "g", "dv"))
def _paged_attention_latent(qg, pool, lengths, tables, widths, layer, *,
                            scale, npages, interpret, w, g, dv):
    """`_paged_attention_wide` over a latent pool (L, P, 1, Dl, ps): the
    same kernel, its grid over (slot, tile of query rows), one fetch a
    page, the values the keys' first `dv` rows. qg: (B, tiles, tile, Dl)
    -> (B, tiles, tile, dv). Jitted with `layer` an operand, as the wide
    dispatch is: a program's 8 attention blocks share one trace a shape."""
    b, tiles, tile, d = qg.shape
    ps = pool.shape[-1]
    nbuf = _WIDE_BUFFERS
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(b, tiles),
        in_specs=[
            pl.BlockSpec((1, 1, tile, d), lambda bi, ti, *_: (bi, ti, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, 1, tile, dv),
                               lambda bi, ti, *_: (bi, ti, 0, 0)),
        scratch_shapes=[pltpu.VMEM((nbuf, npages, d, ps), pool.dtype),
                        pltpu.SemaphoreType.DMA((nbuf, npages)),
                        pltpu.SMEM((5,), jnp.int32)],
    )
    kernel = functools.partial(
        _paged_attention_wide_kernel, scale=float(scale), w=w, g=g, ps=ps,
        npages=npages, int8_kv=False, window=0, latent_dv=dv)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, tiles, tile, dv), qg.dtype),
        interpret=interpret,
        name="paged_attention_latent",
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
    )(lengths.astype(jnp.int32), tables.astype(jnp.int32),
      widths.astype(jnp.int32),
      jnp.asarray(layer, jnp.int32).reshape(1), qg, pool)


def _paged_attention_wide_kernel(
    # scalar prefetch
    lens_ref,          # (B,) i32 — kv length per slot INCLUDING the window
    tables_ref,        # (B, max_pages) i32
    widths_ref,        # (B,) i32 — per-row valid window width (<= W)
    layer_ref,         # (1,) i32
    # inputs
    q_ref,             # (1, 1, WG, Dh) VMEM — this (slot, kv head)'s rows
    k_pool_ref,        # (L, P, KH, Dh, ps) HBM (ANY)
    *refs,             # [v_pool, [k_scale_pool, v_scale_pool,]] o_ref, ...
    scale: float,
    w: int,
    g: int,
    ps: int,
    npages: int,
    int8_kv: bool,
    window: int,
    latent_dv: int = 0,
):
    """Grid variant: one cell per (slot, kv head) instead of a
    whole-batch unroll. It serves the prefill chunks (W > 32) and every
    call of more than 16 rows, a serving batch's decode rows among them.

    Why a second kernel: the narrow kernel keeps all B x KH x W*G query
    rows in one VMEM block and statically unrolls slots, so its VMEM
    footprint and code size scale with B x KH: wide chunks and serving
    batches do not fit. Here each cell holds only its own (W*G, Dh) rows
    and a few blocks of page slices, and the length-bounded page reads
    beat the XLA path's full-padded-cache gather per layer per chunk.

    The fetches chain across cells as the narrow kernel's chain across
    slots. The cells run in grid order on the one core (both axes
    "arbitrary"), so the blocks of a call are one sequence: a cell's
    blocks, then those of the cell after it, (b, h + 1) or (b + 1, 0).
    While a block computes, the fetches of the `ahead` blocks after it
    in that sequence are in flight (one buffer each), whichever cells
    they belong to: each block starts the fetch of the block `ahead`
    after it (`advance`), and only the grid's first cell fetches for
    itself and waits with nothing to compute. At a chunk's width the
    bubble this closes is noise (a cell has dozens of blocks of real
    arithmetic intensity); at W = 1 a cell is one or a few blocks of 4
    or 7 query rows, and the bubble was the kernel. Cells differ in
    their count of blocks, so the buffer a cell starts in and the newest
    block in flight are carried from cell to cell in SMEM scratch
    (`chain`).

    A block's fetch is bounded by the row: a page wholly past the row's
    last key, or wholly behind the first query's lower bound, is neither
    started nor waited for (`_page_span`, one span for both, so every
    started copy keeps exactly one wait), and its table entry may be
    anything. What such a page's buffer holds is stale. The scores are
    selected by the mask, so stale keys never reach them; the weights
    there are exactly 0, and 0 x NaN would still poison `pv`: the grid's
    first cell therefore zeroes the value buffers (and the value scales)
    once, after which they only ever hold zeros or pages some row named
    inside its bound.

    Over a latent pool (`latent_dv`, `_paged_attention_latent`) the pool
    has one "head", the entry every query head reads, and the grid's
    second axis runs over tiles of the slot's W * G query rows: cell
    (b, t) holds rows [t * tile, (t + 1) * tile), query-major, and reads
    the slot's pages as any cell does. There is no value pool: a page's
    values are its keys' first `latent_dv` rows, fetched once with them,
    so it is the key buffers that the first cell zeroes.
    """
    v_pool_ref = vbuf = ks_pool_ref = vs_pool_ref = ksbuf = vsbuf = None
    if latent_dv:
        o_ref, kbuf, sems, chain = refs
    elif int8_kv:
        (v_pool_ref, ks_pool_ref, vs_pool_ref, o_ref,
         kbuf, vbuf, ksbuf, vsbuf, sems, chain) = refs
    else:
        v_pool_ref, o_ref, kbuf, vbuf, sems, chain = refs
    nbuf = kbuf.shape[0]  # a power of two: a slot is a counter's low bits
    ahead = nbuf - 1  # blocks in flight while one computes
    b = pl.program_id(0)
    h = pl.program_id(1)
    nb, nh = pl.num_programs(0), pl.num_programs(1)
    wg = q_ref.shape[2]
    d = q_ref.shape[-1]
    num_pages_total = k_pool_ref.shape[1]
    layer = layer_ref[0]
    blk = ps * npages
    kv_len = lens_ref[b]
    dot_dtype = (jnp.float32 if k_pool_ref.dtype == jnp.float32
                 else jnp.bfloat16)

    def blocks_of(row):
        """(first, end) of the blocks a row's cells run; end - first >= 1
        so that every cell has a block to be fetched and waited for."""
        end = jnp.maximum(1, lax.div(lens_ref[row] + blk - 1, blk))
        return _first_block(lens_ref[row], widths_ref[row], window, blk,
                            end), end

    def _page_span(row, blk_idx):
        """[lo, hi) of the block's pages that hold a key some query of
        the row reads: not those wholly past the last key, nor those
        wholly behind the first query's lower bound."""
        n = lens_ref[row]
        behind = (lax.div(jnp.maximum(n - widths_ref[row] - (window - 1), 0),
                          ps) if window else 0)
        base = blk_idx * npages
        lo = jnp.clip(behind - base, 0, npages)
        return lo, jnp.clip(lax.div(n + ps - 1, ps) - base, lo, npages)

    def _copies(buf_idx, i, head, page):
        """The async copies of one page of a block — PER-HEAD (Dh, ps)
        slices here (the narrow kernel fetches whole pages; a cell only
        needs its head)."""
        sem = sems.at[buf_idx, i]
        if latent_dv:  # one entry a token, whichever tile the cell is
            return [pltpu.make_async_copy(
                k_pool_ref.at[layer, page, 0], kbuf.at[buf_idx, i], sem)]
        cs = [pltpu.make_async_copy(
                  k_pool_ref.at[layer, page, head], kbuf.at[buf_idx, i], sem),
              pltpu.make_async_copy(
                  v_pool_ref.at[layer, page, head], vbuf.at[buf_idx, i], sem)]
        if int8_kv:
            # pl.ds keeps the copy rank-2 ((1, ps), lane-aligned)
            cs += [pltpu.make_async_copy(
                       ks_pool_ref.at[layer, page, pl.ds(head, 1)],
                       ksbuf.at[buf_idx, i], sem),
                   pltpu.make_async_copy(
                       vs_pool_ref.at[layer, page, pl.ds(head, 1)],
                       vsbuf.at[buf_idx, i], sem)]
        return cs

    # a loop over the span and not a branch a page: the program is traced
    # and lowered for every step program of a server, and eight branches
    # a site cost more of that than the whole kernel had

    def start_fetch(row, head, blk_idx, buf_idx):
        def one(i, _):
            page = jnp.clip(
                tables_ref[row, jnp.minimum(blk_idx * npages + i,
                                            tables_ref.shape[1] - 1)],
                0, num_pages_total - 1)
            for c in _copies(buf_idx, i, head, page):
                c.start()
            return 0
        lax.fori_loop(*_page_span(row, blk_idx), one, 0)

    def wait_fetch(blk_idx, buf_idx):
        # waits pair up 1:1 with the starts issued into this buffer for
        # this cell's block, by this cell or by one before it (the source
        # index is irrelevant to wait; sizes match the starts)
        def one(i, _):
            for c in _copies(buf_idx, i, h, 0):
                c.wait()
            return 0
        lax.fori_loop(*_page_span(b, blk_idx), one, 0)

    def advance(pos):
        """The block the grid runs after `pos` = (row, head, block, live):
        the cell's next block, or the first block of the cell after it,
        (row, head + 1) or (row + 1, 0); not `live` past the last cell."""
        row, head, blk_idx, live = pos
        more = blk_idx + 1 < blocks_of(row)[1]
        last_head = head + 1 == nh
        nxt_row = jnp.where(jnp.logical_or(more, jnp.logical_not(last_head)),
                            row, row + 1)
        live = jnp.logical_and(live, nxt_row < nb)
        nxt_row = jnp.minimum(nxt_row, nb - 1)
        return (nxt_row,
                jnp.where(more, head, jnp.where(last_head, 0, head + 1)),
                jnp.where(more, blk_idx + 1, blocks_of(nxt_row)[0]), live)

    def start_at(pos, buf_idx):
        @pl.when(pos[3])
        def _():
            start_fetch(pos[0], pos[1], pos[2], buf_idx)

    first, n_blocks = blocks_of(b)

    @pl.when(jnp.logical_and(b == 0, h == 0))
    def _():
        if latent_dv:
            kbuf[...] = jnp.zeros(kbuf.shape, kbuf.dtype)
        else:
            vbuf[...] = jnp.zeros(vbuf.shape, vbuf.dtype)
        if int8_kv:
            vsbuf[...] = jnp.zeros(vsbuf.shape, vsbuf.dtype)
        # the grid's first `ahead` blocks are fetched here; every later
        # one by the block that runs `ahead` before it
        pos = (b, h, first, jnp.bool_(True))
        start_at(pos, 0)

        def fill(k, pos):
            pos = advance(pos)
            start_at(pos, k)
            return pos
        pos = lax.fori_loop(1, ahead, fill, pos)
        chain[0] = 0
        for k in range(4):
            chain[1 + k] = jnp.asarray(pos[k], jnp.int32)

    # the buffer this cell's first block is in, and the newest block in
    # flight: both run on from cell to cell
    slot0 = chain[0]
    newest0 = (chain[1], chain[2], chain[3], chain[4] != 0)
    row = lax.broadcasted_iota(jnp.int32, (wg, blk), 0)
    if latent_dv:  # the cell's tile of the slot's rows
        row = row + h * wg
    row_pos = (kv_len - widths_ref[b]) + row // g
    qh = q_ref[0, 0].astype(dot_dtype)  # (WG, Dh)

    def body(i, carry):
        newest, state = carry[:4], carry[4:]
        buf_idx = (slot0 + (i - first)) & (nbuf - 1)

        # while this block computes, the `ahead` blocks the grid runs
        # after it are in flight: start the last of them, this cell's or
        # a later cell's, into the buffer the block before this one left
        newest = advance(newest)
        start_at(newest, (buf_idx + ahead) & (nbuf - 1))

        wait_fetch(i, buf_idx)

        col_pos = i * blk + lax.broadcasted_iota(jnp.int32, (wg, blk), 1)
        mask = jnp.logical_and(col_pos <= row_pos, col_pos < kv_len)
        if window:
            mask = jnp.logical_and(mask, row_pos - col_pos < window)
        pages = [
            (lambda p=p: kbuf[buf_idx, p],
             (lambda p=p: kbuf[buf_idx, p, :latent_dv]) if latent_dv
             else (lambda p=p: vbuf[buf_idx, p]),
             (lambda p=p: ksbuf[buf_idx, p]) if int8_kv else None,
             (lambda p=p: vsbuf[buf_idx, p]) if int8_kv else None)
            for p in range(npages)]
        return newest + _softmax_block(qh, mask, state, pages, scale=scale,
                                       ps=ps, dot_dtype=dot_dtype)

    m0 = jnp.full((wg, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((wg, 1), jnp.float32)
    a0 = jnp.zeros((wg, latent_dv or d), jnp.float32)
    out = lax.fori_loop(first, n_blocks, body, newest0 + (m0, l0, a0))
    chain[0] = (slot0 + (n_blocks - first)) & (nbuf - 1)
    for k in range(4):
        chain[1 + k] = out[k].astype(jnp.int32)
    l_f, acc_f = out[5:]
    o_ref[0, 0] = (acc_f / jnp.maximum(l_f, 1e-30)).astype(o_ref.dtype)


def paged_attention_tp(q, k_pool, v_pool, lengths, tables, layer=0, *,
                       mesh, axis_name: str = "tp", scale=None,
                       pages_per_block: int = 4,
                       interpret: bool | None = None,
                       k_scale_pool=None, v_scale_pool=None, widths=None,
                       window: int = 0):
    """`paged_attention` under tensor parallelism: kv heads shard over
    `axis_name`, each device runs the kernel on its local heads.

    The kernel is embarrassingly parallel over kv heads (per-head
    m/l/acc state, per-head page slices), so the tp split needs NO
    collectives — the head-sharded output feeds the attention-out
    projection, whose row-parallel matmul does the psum exactly as in
    training. pallas_call cannot be partitioned automatically by jit
    (hence shard_map); everything XLA-side in the serving path still
    relies on plain propagation.

    Constraints: tp must divide num_kv_heads (so each device owns whole
    GQA groups — q heads are ordered kv-head-major, so a contiguous H
    split aligns with the KH split).
    """
    from jax.sharding import PartitionSpec as P

    kh = k_pool.shape[2]
    h = q.shape[2]
    ntp = mesh.shape[axis_name]
    if kh % ntp or h % ntp:
        raise ValueError(
            f"tp={ntp} must divide num_kv_heads={kh} (and heads={h}) to "
            "shard the paged-attention kernel")
    if widths is None:
        widths = jnp.full((q.shape[0],), q.shape[1], jnp.int32)
    head_spec = P(None, None, axis_name, None)
    pool_spec = P(None, None, axis_name, None, None)
    rep = P()
    in_specs = [head_spec, pool_spec, pool_spec, rep, rep, rep]
    args = [q, k_pool, v_pool, lengths, tables, widths]
    if k_scale_pool is not None:
        in_specs += [P(None, None, axis_name, None)] * 2
        args += [k_scale_pool, v_scale_pool]

    def local(q_l, k_l, v_l, lens, tabs, wid, *scales):
        return paged_attention(
            q_l, k_l, v_l, lens, tabs, layer, scale=scale,
            pages_per_block=pages_per_block, interpret=interpret,
            k_scale_pool=scales[0] if scales else None,
            v_scale_pool=scales[1] if scales else None, widths=wid,
            window=window)

    return jax.shard_map(local, mesh=mesh, in_specs=tuple(in_specs),
                         out_specs=head_spec, check_vma=False)(*args)


# ---------------------------------------------------------------------------
# XLA reference (CPU tests / fallback)
# ---------------------------------------------------------------------------


def gather_pages(pool, tables, layer=0):
    """(L, num_pages, KH, Dh, ps), (B, MP) -> contiguous
    (B, MP*ps, KH, Dh) for `layer`."""
    b, mp = tables.shape
    _, _, kh, d, ps = pool.shape
    lay = pool[layer]  # (P, KH, D, ps)
    pages = lay[jnp.clip(tables, 0, lay.shape[0] - 1)]  # (B, MP, KH, D, ps)
    return pages.transpose(0, 1, 4, 2, 3).reshape(b, mp * ps, kh, d)


def gather_scale_pages(scale_pool, tables, layer=0):
    """(L, num_pages, KH, ps), (B, MP) -> (B, MP*ps, KH, 1) f32."""
    b, mp = tables.shape
    _, _, kh, ps = scale_pool.shape
    lay = scale_pool[layer]
    pages = lay[jnp.clip(tables, 0, lay.shape[0] - 1)]
    return pages.transpose(0, 1, 3, 2).reshape(b, mp * ps, kh, 1)


def paged_attention_xla(q, k_pool, v_pool, lengths, tables, layer=0, *,
                        scale=None, k_scale_pool=None, v_scale_pool=None,
                        widths=None, window: int = 0, latent_dv: int = 0):
    """Dense-XLA equivalent of `paged_attention` (gather + masked attention).

    The test oracle, and the serving fallback on non-TPU backends. The
    gather materialises each slot's full padded cache view per call, so on
    TPU the pallas kernel is strictly preferred. `widths` follows the
    kernel's ragged rule in lockstep: row b's queries anchor at
    lengths[b] - widths[b] (rows past their width are garbage, masked by
    the caller).
    """
    from cloud_server_tpu.ops.attention import causal_attention

    b, w, _, _ = q.shape
    k = gather_pages(k_pool, tables, layer)
    # a latent pool: the values are the keys' first `latent_dv` entries
    v = (k[..., :latent_dv] if latent_dv
         else gather_pages(v_pool, tables, layer))
    scales = {}
    if k_scale_pool is not None:
        scales = dict(k_scale=gather_scale_pages(k_scale_pool, tables, layer),
                      v_scale=gather_scale_pages(v_scale_pool, tables, layer))
    anchor = lengths - (jnp.full((b,), w, jnp.int32) if widths is None
                        else widths)
    pos = anchor[:, None] + jnp.arange(w)[None, :]
    return causal_attention(q, k, v, scale=scale, q_positions=pos,
                            kv_length=lengths, window=int(window), **scales)
