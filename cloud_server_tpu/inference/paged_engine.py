"""Paged inference engine: the window-forward primitive over a page pool.

Where `inference.engine` owns a contiguous (L, B, max_len, KH, Dh) cache,
this module owns the PAGED cache: one global pool of fixed-size pages per
layer plus per-slot page tables, so device memory scales with the tokens
actually resident (not max_slots x max_len) and pages can be SHARED
between slots (refcounted prefix reuse — inference/block_allocator.py).

A model whose layers are of two kinds (`ModelConfig.layer_kinds`: full
layers read every key, window layers the last `sliding_window`) has a
pool and a table for each kind. The full kind is the pool above. The
window kind's pages are private to a slot, addressed by the same logical
index (position // page size), and given back by the host once every
position in them lies behind the window of every query still to come;
the table's entry is then the sentinel, and the kernels never fetch a
block wholly behind the bound. A model of one kind has one pool.

A model with latent attention (`ModelConfig.latent_dim` > 0) has one pool
of the LATENT kind in the full kind's place: a page is (1, latent_dim,
page size), one entry a token of the normed, scaled latent vector and the
rotated shared rotary key (`models/latent.py`), with no value pool and no
scales, and the pool is as deep as the model has attention blocks (two a
double layer: layer l's block i is pool layer 2 l + i). Keys and values
are never stored expanded: the kernels read the entry as the one key
"head" of every query head and take a page's values to be its keys' first
`kv_lora_rank` rows (`ops.paged_attention`, `latent_dv`). To the
allocator, the tables, preemption and the prefix cache it is pages, as
the full kind is.

Everything the paged server dispatches is one primitive,
`forward_sets`, a walk of the layer stack over one or more row sets that
share the pool; a mixed step hands it its prefill group and its decode
round together where that computes the same function, so that a layer's
weights are read once for both. Its one-set case is
`window_forward(tokens (B, W))`: embed W new positions per slot at
absolute positions [lengths, lengths + W), write their kv into the pool
through the page table, and attend each window row against the slot's
whole paged history (pallas kernel `ops.paged_attention` on TPU, gather +
dense XLA elsewhere). The server's flows are just widths:

  * plain decode             W = 1
  * speculative verification W = drafts + 1   (logits="all")
  * prefill / chunked prefill / prefix-cache continuation: W = chunk,
    with per-slot start offsets carried by `lengths` (a slot resuming
    after `n` shared-prefix tokens simply starts at lengths=n)
  * MIXED batch (stall-free scheduling): per-row `widths` — decode rows
    (width 1 or drafts+1) and prefill-chunk rows (width chunk) share ONE
    ragged dispatch; writes past a row's width drop, attention anchors
    each row at its own width (ops.paged_attention ragged rule)

`window_forward` does NOT advance `lengths` — the caller commits however
many window positions survive (sampling, speculative acceptance), exactly
like `engine.verify_step`: stale entries past the commit point are masked
by `lengths` and overwritten by later writes at the same positions.

Write discipline and sharing safety: a write at absolute position p goes
to page `tables[b, p // ps]`, offset `p % ps`. The allocator guarantees
shared (refcount > 1 or cached) pages only ever cover positions < every
sharing slot's private start, and all writes happen at positions >=
lengths >= private start — so shared pages are immutable by
construction. Freed slots get sentinel tables (page id == num_pages):
their writes drop (`mode="drop"`), which is what makes it safe to keep
dispatching the full slot batch while some slots are empty.
"""

from __future__ import annotations

import contextlib
from typing import NamedTuple

import jax
import jax.numpy as jnp

from cloud_server_tpu.config import ModelConfig
from cloud_server_tpu.inference import multi_lora
from cloud_server_tpu.inference.engine import _kv_quant, _mlp_apply
from cloud_server_tpu.models import latent, mixer, moe, transformer
from cloud_server_tpu.ops import grouped_matmul, rms_norm, rope_table
from cloud_server_tpu.ops.paged_attention import (
    paged_attention, paged_attention_tp, paged_attention_xla)


class PagedKVCache(NamedTuple):
    """Page pools + per-slot view. One pool serves every slot and every
    layer of a kind: `k`/`v` the full layers (all of them, for a model of
    one kind), `wk`/`wv` the window layers of a model that has them. A
    layer's place is `cfg.layer_pool(layer)`: (kind, index in its pool).

    `tables` holds a table per kind side by side: columns
    [0, max_pages_per_slot) the full kind's, and with a window pool
    columns [max_pages_per_slot, 2 * max_pages_per_slot) the window
    kind's. Both are indexed by position // page size; each kind's
    sentinel ("no page") is any id >= its own pool's pages."""

    k: jnp.ndarray        # (L_full, num_pages, KH, Dh, ps) cfg.dtype | int8
    #                       latent kind: (blocks, num_pages, 1, latent_dim, ps)
    v: jnp.ndarray | None  # (L_full, num_pages, KH, Dh, ps) — transposed
    #                       pages (positions on lanes; ops/paged_attention);
    #                       None for the latent kind
    lengths: jnp.ndarray  # (B,) int32 — committed kv entries per slot
    tables: jnp.ndarray   # (B, kinds * max_pages_per_slot) int32
    k_scale: jnp.ndarray | None = None  # (L_full, num_pages, KH, ps) f32
    v_scale: jnp.ndarray | None = None
    wk: jnp.ndarray | None = None  # (L_window, window_num_pages, KH, Dh, ps)
    wv: jnp.ndarray | None = None
    wk_scale: jnp.ndarray | None = None
    wv_scale: jnp.ndarray | None = None
    # int32 running counts of the router's assignments over every walk so
    # far, modulo 2**32, one for each of `assign_names(cfg)`: a model whose
    # router is wider than the experts held counts those to held, identity
    # and absent experts; a model whose router is balanced by a bias counts
    # them all, the most any one expert of any layer received in a walk,
    # and the rows the sorted dispatch's way in computes for them (each
    # expert's count rounded up to the kernel's sub-tiles). It rides the
    # pools through every program, so the host reads it with a step's
    # results and takes differences.
    assign: jnp.ndarray | None = None
    # A model with a mixer beside its attention (`ModelConfig.ssm_heads`):
    # a kind of its own beside the full kind's pages, one STATE a slot and
    # not pages. Per layer (a tuple of `num_layers` arrays, so that a
    # layer's update is its own buffer's, in place) the recurrent state
    # (slots, heads, head_dim, state_dim) in float32 and the convolution's
    # last inputs (slots, taps - 1, channels). Its rule: a row at position 0
    # enters with zero, every row set hands on what it leaves, nothing is
    # trimmed, shared or keyed (`models/mixer.py`).
    ssm: tuple | None = None
    conv: tuple | None = None

    @property
    def page_size(self) -> int:
        return self.k.shape[4]

    @property
    def num_pages(self) -> int:
        return self.k.shape[1]

    @property
    def max_pages_per_slot(self) -> int:
        return self.tables.shape[1] // (1 if self.wk is None else 2)

    @property
    def max_context(self) -> int:
        return self.max_pages_per_slot * self.page_size

    def of_kind(self, kind: str, tables: jnp.ndarray) -> "PagedKVCache":
        """One kind's pools and its columns of `tables` as a cache of
        one kind: what `_write_window` and the kernels take."""
        mp = tables.shape[1] // (1 if self.wk is None else 2)
        if kind == "full":
            return PagedKVCache(self.k, self.v, self.lengths,
                                tables[:, :mp], self.k_scale, self.v_scale)
        return PagedKVCache(self.wk, self.wv, self.lengths, tables[:, mp:],
                            self.wk_scale, self.wv_scale)

    def with_kind(self, kind: str, view: "PagedKVCache") -> "PagedKVCache":
        """The pools of `view` (an `of_kind` cache) put back."""
        if kind == "full":
            return self._replace(k=view.k, v=view.v, k_scale=view.k_scale,
                                 v_scale=view.v_scale)
        return self._replace(wk=view.k, wv=view.v, wk_scale=view.k_scale,
                             wv_scale=view.v_scale)


def assign_names(cfg: ModelConfig) -> tuple:
    """What `PagedKVCache.assign` counts for this model, as the flight
    record names it; empty for a model that keeps no such counts."""
    if cfg.routed_scaling_factor > 0:
        return ("assign_held", "assign_zero", "assign_absent")
    if cfg.router_score == "sigmoid":
        return ("assign_total", "assign_peak", "assign_rows_computed")
    return ()


def window_pages_per_slot(window: int, page_size: int, max_write: int,
                          max_pages_per_slot: int) -> int:
    """The most pages of the window kind one slot can hold: those that
    cover the `window - 1` keys behind the oldest query not yet
    committed and the `max_write` positions written ahead of it (the
    widest dispatch, times the dispatches in flight), plus one for
    where the span starts inside a page."""
    return min(-(-(window - 1 + max_write) // page_size) + 1,
               max_pages_per_slot)


def init_paged_cache(cfg: ModelConfig, *, num_pages: int, page_size: int,
                     batch: int, max_pages_per_slot: int,
                     window_num_pages: int | None = None) -> PagedKVCache:
    """Zeroed pools; all tables at the sentinel ("no page"). `num_pages`
    is the full kind's pool. The window kind's, of a model that has
    window layers, is `window_num_pages`: the paged server gives the
    size it reckoned from its own widths, and without one every slot
    gets `window_pages_per_slot` for the widest window the kernels
    serve, written twice ahead."""
    kinds = cfg.layer_kinds
    if "window" in kinds and window_num_pages is None:
        window_num_pages = batch * window_pages_per_slot(
            cfg.sliding_window, page_size, 2 * PALLAS_MAX_W,
            max_pages_per_slot)
    if cfg.kv_cache_dtype not in ("model", "int8"):
        raise ValueError(f"unknown kv_cache_dtype: {cfg.kv_cache_dtype!r}")
    int8 = cfg.kv_cache_dtype == "int8"
    dtype = jnp.int8 if int8 else jnp.dtype(cfg.dtype)
    names = assign_names(cfg)
    assign = jnp.zeros((len(names),), jnp.int32) if names else None
    if cfg.latent_dim:  # one latent entry a token a block, no values
        return PagedKVCache(
            jnp.zeros((cfg.num_layers * cfg.attention_blocks, num_pages, 1,
                       cfg.latent_dim, page_size), dtype), None,
            jnp.zeros((batch,), jnp.int32),
            jnp.full((batch, max_pages_per_slot), num_pages, jnp.int32),
            assign=assign)

    def pools(kind: str, pages: int):
        shape = (kinds.count(kind), pages, cfg.num_kv_heads, cfg.head_dim,
                 page_size)
        k, v = jnp.zeros(shape, dtype), jnp.zeros(shape, dtype)
        if not int8:
            return k, v, None, None
        sshape = shape[:3] + (page_size,)
        return (k, v, jnp.zeros(sshape, jnp.float32),
                jnp.zeros(sshape, jnp.float32))

    k, v, ks, vs = pools("full", num_pages)
    lengths = jnp.zeros((batch,), jnp.int32)
    tables = jnp.full((batch, max_pages_per_slot), num_pages, jnp.int32)
    if cfg.ssm_heads:  # a state for every slot: admission never waits
        s_shape, c_shape = mixer.state_shapes(cfg, batch)
        return PagedKVCache(
            k, v, lengths, tables,
            ssm=tuple(jnp.zeros(s_shape, jnp.float32)
                      for _ in range(cfg.num_layers)),
            conv=tuple(jnp.zeros(c_shape, dtype)
                       for _ in range(cfg.num_layers)))
    if "window" not in kinds:
        return PagedKVCache(k, v, lengths, tables, ks, vs, assign=assign)
    wk, wv, wks, wvs = pools("window", window_num_pages)
    tables = jnp.concatenate(
        [tables, jnp.full((batch, max_pages_per_slot), window_num_pages,
                          jnp.int32)], axis=1)
    return PagedKVCache(k, v, lengths, tables, ks, vs, wk, wv, wks, wvs,
                        assign=assign)


def quantize_pool(pool: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """int8-quantize a TRANSPOSED page pool (L, P, KH, Dh, ps): absmax
    over Dh (axis 3) — the same per-(position, head) granularity
    `_write_window` stores via `engine._kv_quant`. Single source of truth
    for tests and benches building pools wholesale.

    Returns (int8 pool, (L, P, KH, ps) f32 scales)."""
    sc = jnp.maximum(
        jnp.max(jnp.abs(pool.astype(jnp.float32)), axis=3,
                keepdims=True) / 127.0, 1e-8)
    q = jnp.round(pool.astype(jnp.float32) / sc).astype(jnp.int8)
    return q, sc[:, :, :, 0, :]


def hbm_bytes(cache: PagedKVCache) -> int:
    """Device bytes held by the pools of every kind (the capacity
    comparison the paged layout exists to win — see
    tests/test_paged_server.py)."""
    return state_bytes(cache) + sum(
        p.size * p.dtype.itemsize
        for p in (cache.k, cache.v, cache.k_scale, cache.v_scale,
                  cache.wk, cache.wv, cache.wk_scale, cache.wv_scale)
        if p is not None)


def state_bytes(cache: PagedKVCache) -> int:
    """Device bytes of the per-slot states of a model with a mixer."""
    return sum(p.size * p.dtype.itemsize
               for p in (cache.ssm or ()) + (cache.conv or ()))


def _write_window(cache: PagedKVCache, layer: int, k, v, pos):
    """Write fresh (B, W, KH, Dh) k/v at absolute positions (B, W)
    through the page table. Out-of-chain positions (sentinel table
    entries) drop. A latent pool has no values: `v` is None and `k` the
    tokens' latent entries, (B, W, 1, latent_dim).

    Implementation note: a direct elementwise scatter into the transposed
    (.., Dh, ps) pages would write 2-byte elements at stride ps — an XLA
    scatter slow path that dominated the decode step when measured. So
    writes go page-at-a-time instead: GATHER each touched page (a W-token
    window touches at most 2 consecutive pages per slot — both
    slot-PRIVATE by the allocator's sharing invariant, so whole-page
    read-modify-write races nothing), merge the window's positions in
    with a one-hot lane mask, and SET the whole page back — one
    single-index scatter of contiguous page blocks, ~2 pages of traffic
    per slot per layer instead of thousands of strided element writes."""
    ps = cache.page_size
    b, w = pos.shape
    max_slot = cache.tables.shape[1] - 1
    int8 = cache.k_scale is not None
    if int8:
        kq, ksc = _kv_quant(k)
        vq, vsc = _kv_quant(v)
        k_src = kq.astype(cache.k.dtype)
        v_src = vq.astype(cache.v.dtype)
    else:
        k_src = k.astype(cache.k.dtype)
        v_src = None if v is None else v.astype(cache.v.dtype)

    new = {"k": cache.k, "v": cache.v,
           "k_scale": cache.k_scale, "v_scale": cache.v_scale}
    # a W-token window starting mid-page touches ceil(W/ps)+1 consecutive
    # page slots; W=1 touches exactly one
    n_groups = 1 if w == 1 else (-(-w // ps) + 1)
    first_slot = jnp.clip(pos[:, 0] // ps, 0, max_slot)  # (B,)
    lane = jnp.arange(ps)
    for g in range(n_groups):
        slot_g = jnp.clip(first_slot + g, 0, max_slot)
        page_g = jnp.take_along_axis(cache.tables, slot_g[:, None],
                                     axis=1)[:, 0]          # (B,)
        in_page = (pos // ps) == slot_g[:, None]            # (B, W)
        # one-hot over lanes for each window position in this page
        oh = (in_page[:, :, None]
              & (lane[None, None, :] == (pos % ps)[:, :, None]))  # (B,W,ps)
        ohf = oh.astype(jnp.float32)
        any_write = ohf.sum(axis=1)                          # (B, ps)
        for name, src in (("k", k_src), ("v", v_src)):
            if src is None:
                continue
            pool = new[name]
            pages_old = pool[layer, jnp.clip(page_g, 0, pool.shape[1] - 1)]
            upd = jnp.einsum("bwhd,bwp->bhdp",
                             src.astype(jnp.float32), ohf)
            merged = (pages_old.astype(jnp.float32)
                      * (1.0 - any_write[:, None, None, :]) + upd)
            new[name] = pool.at[layer, page_g].set(
                merged.astype(pool.dtype), mode="drop")
        if int8:
            for name, sc in (("k_scale", ksc), ("v_scale", vsc)):
                spool = new[name]
                sp_old = spool[layer, jnp.clip(page_g, 0,
                                               spool.shape[1] - 1)]
                upd = jnp.einsum("bwh,bwp->bhp", sc[..., 0], ohf)
                merged = sp_old * (1.0 - any_write[:, None, :]) + upd
                new[name] = spool.at[layer, page_g].set(merged,
                                                        mode="drop")
    return cache._replace(k=new["k"], v=new["v"],
                          k_scale=new["k_scale"], v_scale=new["v_scale"])


# Widest window the pallas path serves. Thin windows (<= 32) of at most
# 16 rows take the batch-unrolled kernel; wider windows (prefill chunks)
# and bigger batches (a serving batch's decode rows) dispatch the
# grid-over-(slot, head) wide kernel
# (ops.paged_attention._paged_attention_wide). Both chain their page
# fetches from one slot or cell to the next — length-bounded page
# reads instead of the XLA path's full-padded-cache gather per layer
# per chunk. A wider window is refused, never handed to the XLA
# reference: a config that asks for the kernel either runs the kernel
# or fails (the paged server checks its prefill chunk at construction).
PALLAS_MAX_W = 256
# The latent kind's kernel holds a tile of a window's query rows at a time
# (`ops.paged_attention.LATENT_TILE`), whatever the window's width: its
# cap is what was compiled and measured.
LATENT_MAX_W = 1024


def max_window(cfg: ModelConfig) -> int:
    """The widest window the pallas path serves for this model."""
    return LATENT_MAX_W if cfg.latent_dim else PALLAS_MAX_W


class RowSet(NamedTuple):
    """One set of rows of a forward (`forward_sets`): the tokens, where
    each row stands in the pools, and what is wanted back."""

    tokens: jnp.ndarray    # (B, W) int32, positions [lengths, lengths + W)
    lengths: jnp.ndarray   # (B,) int32: committed kv entries per row
    tables: jnp.ndarray    # (B, max_pages_per_slot) int32
    widths: jnp.ndarray | None = None     # (B,) valid widths, ragged rows
    logits_at: jnp.ndarray | None = None  # (B,) in-window index to unembed
    scope: str | None = None  # names the set's own ops in a device trace
    # (B,) int32, a model with a mixer: the slot whose state each row reads
    # and leaves advanced; any id past the slots for a row that must leave
    # none (padding, a decode row that is not live)
    slots: jnp.ndarray | None = None


def _side_by_side(parts):
    """Per-set (B, W, ...) arrays laid out as one row of tokens
    (1, sum(B * W), ...); one set stays as it is."""
    if len(parts) == 1:
        return parts[0]
    return jnp.concatenate(
        [p.reshape((1, -1) + p.shape[2:]) for p in parts], axis=1)


def _part(y, sets, i: int):
    """Set i's (B, W, ...) rows of an array laid out by `_side_by_side`."""
    if len(sets) == 1:
        return y
    at = sum(s.tokens.size for s in sets[:i])
    shape = sets[i].tokens.shape
    return y[0, at:at + shape[0] * shape[1]].reshape(shape + y.shape[2:])


def _scope(name: str | None):
    return (contextlib.nullcontext() if name is None
            else jax.named_scope(name))


def forward_sets(params, cfg: ModelConfig, cache: PagedKVCache,
                 sets: list[RowSet], *, all_logits: bool = False,
                 pages_per_block: int | None = None,
                 mesh=None, tp_axis: str = "tp", lora=None, aid=None):
    """One walk of the layer stack over one or more row sets that share
    `cache`'s pools (its own `lengths` and `tables` are not read: every
    set brings its rows').

    Per layer, everything that is per token (the norms, the q/k/v and
    output projections, the MLP or the experts) runs ONCE over all
    sets' tokens laid side by side, so a layer's weights are read once
    whatever the number of sets; the cache write and the paged kernel
    run once per set, each at its own window width (a decode row as a
    width-1 row of a 256-wide window would waste the wide kernel). The
    sets' rows are disjoint slots, so the order of their writes does
    not matter. The tail of the walk (final norm, head, its multiplier
    and soft cap) runs once too where more than one set names a
    `logits_at`: each set's wanted rows are taken out first and meet the
    head, the model's largest matrix, in one product, of which each set
    is handed its rows. With more than one set the shared work lies
    under the scope `joined_walk` (that product under
    `joined_walk/unembed`) and each set's own (cache write, kernel, the
    pick of its wanted rows) under its `scope`; one set adds no scope of
    its own and is exactly `window_forward`.

    Joining is the caller's decision: the result equals separate walks
    only where a token's MLP output does not depend on which other
    tokens share the call (a dense MLP, or experts at a capacity that
    cannot overflow), and per-row `lora` deltas need rows, so they come
    with one set only.

    Returns ([logits per set], cache'): per set (B, V) f32 at its
    `logits_at`, (B, W, V) with `all_logits`, None with neither.
    `cache'` has every window written and keeps `cache`'s lengths and
    tables.
    """
    joined = len(sets) > 1
    if joined and lora is not None:
        raise ValueError("per-row lora deltas need one row set")
    if cfg.latent_dim and (lora is not None or mesh is not None):
        raise ValueError("the double layer with latent attention takes no "
                         "adapter and no mesh")
    if cfg.ssm_heads and (lora is not None or mesh is not None
                          or all_logits
                          or any(s.slots is None for s in sets)):
        raise ValueError(
            "the parallel body takes no adapter and no mesh, every row set "
            "names its rows' slots, and a window is not verified position "
            "by position: a state has no roll-back")
    use_pallas = cfg.decode_attention_impl == "pallas"
    shared = "joined_walk" if joined else None
    rows = []  # per set: positions, write positions, lengths after, block
    for s in sets:
        w = s.tokens.shape[1]
        if use_pallas and w > max_window(cfg):
            raise ValueError(
                f"window width {w} exceeds the pallas paged-attention cap "
                f"({max_window(cfg)}); use a narrower window or "
                "decode_attention_impl='xla'")
        with _scope(s.scope):
            ar = jnp.arange(w, dtype=jnp.int32)[None, :]
            pos = s.lengths[:, None] + ar
            # ragged rows: positions past a row's width write nowhere (pos
            # -1 never matches a page slot in _write_window, so the page
            # merge is an identity rewrite of the row's own private pages —
            # shared pages are never touched because writes start at
            # lengths >= private start)
            wpos = pos if s.widths is None else jnp.where(
                ar < s.widths[:, None], pos, -1)
            lens_after = s.lengths + (w if s.widths is None else s.widths)
        # wider windows leave less VMEM for the double-buffered page
        # blocks; 8 pages measured fastest at W=1 on v5e
        rows.append((pos, wpos, lens_after,
                     pages_per_block if pages_per_block is not None
                     else 8 if w <= 8 else 4))
    with _scope(shared):
        cos, sin = (latent.rope_table if cfg.latent_dim else rope_table)(
            cfg, cache.max_context)
        embed = params["embed"]["tokens"].astype(cfg.dtype)
        x = _side_by_side([embed[s.tokens] for s in sets])  # (B, W, D)
        if cfg.embedding_multiplier != 1.0:
            x = x * cfg.embedding_multiplier
        pos_all = _side_by_side([r[0] for r in rows])
    pools = cache

    # each layer's kind is static (the walk is unrolled): a model of one
    # kind adds no scope and no argument, and builds the program it built
    two_kinds = cfg.has_window_layers
    assigned, loads = 0, []
    for layer_idx in range(cfg.num_layers):
        # the stack the layer lies in, and its place there: static too
        stack_name, at_stack = cfg.layer_stack(layer_idx)
        lp = jax.tree.map(lambda p: p[at_stack], params[stack_name])
        if cfg.layer_body == "double_shortcut":
            # the layer's body is the configuration's: a trace-time branch
            x, pools, counts = _double_layer(
                x, lp, layer_idx, params, cfg, pools, sets, rows, cos, sin,
                pos_all, shared, use_pallas)
            assigned = assigned + counts
            continue
        if cfg.layer_body == "parallel_mixer":
            x, pools = _parallel_layer(x, lp, layer_idx, cfg, pools, sets,
                                       rows, cos, sin, pos_all, shared,
                                       use_pallas)
            continue
        ll = (None if lora is None
              else multi_lora.layer_lora(lora, aid, layer_idx))
        kind, at = cfg.layer_pool(layer_idx)
        window = cfg.layer_window(layer_idx)
        x_in = x
        with _scope(shared), jax.named_scope("attn"):
            qkv = transformer.attention_qkv(x, lp, cfg, cos, sin, pos_all,
                                            lora=ll,
                                            rope=cfg.layer_rope(layer_idx))
        outs = []
        for i, (s, (_, wpos, lens_after, ppb)) in enumerate(zip(sets, rows)):
            with _scope(s.scope), jax.named_scope("attn"), \
                    _scope(kind if two_kinds else None):
                q, k, v = (_part(y, sets, i) for y in qkv)
                view = _write_window(
                    pools.of_kind(kind, s.tables)._replace(
                        lengths=s.lengths), at, k, v, wpos)
                pools = pools.with_kind(kind, view)
                kw = dict(k_scale_pool=view.k_scale,
                          v_scale_pool=view.v_scale, widths=s.widths)
                if window:
                    kw["window"] = window
                if not use_pallas:
                    o = paged_attention_xla(
                        q, view.k, view.v, lens_after, view.tables,
                        at, **kw)
                elif mesh is not None and mesh.shape.get(tp_axis, 1) > 1:
                    o = paged_attention_tp(
                        q, view.k, view.v, lens_after, view.tables,
                        at, mesh=mesh, axis_name=tp_axis,
                        pages_per_block=ppb, **kw)
                else:
                    o = paged_attention(
                        q, view.k, view.v, lens_after, view.tables,
                        at, pages_per_block=ppb, **kw)
            outs.append(o)
        with _scope(shared):
            with jax.named_scope("attn"):
                x = transformer.attention_out(x, _side_by_side(outs), lp,
                                              cfg, lora=ll)
            if stack_name == "lead_layers":
                with jax.named_scope("lead_dense"):
                    x = transformer.mlp_block(x, lp, cfg, lora=ll)
            elif cfg.router_score == "sigmoid":
                # a router balanced by a bias: how even its load is rides
                # home with the step's results
                x, aux = moe.moe_mlp_block(
                    x, lp, cfg, (params[stack_name], at_stack), x_in)
                loads.append(aux["load"])
            else:
                x = _mlp_apply(x, lp, cfg, lora=ll,
                               stack=(params[stack_name], at_stack),
                               layer_in=x_in)

    def head(xs):  # final-norm'd rows to logits
        out = transformer.unembed(xs, params, cfg)
        return (out if cfg.lm_head_multiplier == 1.0
                else out * cfg.lm_head_multiplier)

    def at_rows(xs, s):  # (B, W, D) -> (B, D) at the set's `logits_at`
        b, w = s.tokens.shape
        return xs[jnp.arange(b), jnp.clip(s.logits_at, 0, w - 1)]

    scale = params["final_norm"]["scale"]
    wanted = [i for i, s in enumerate(sets) if s.logits_at is not None]
    logits = [None] * len(sets)
    if len(wanted) > 1 and not all_logits:
        # the head is the model's largest matrix: the wanted rows of all
        # sets meet it in ONE product, so a step streams it once
        picked = []
        for i in wanted:
            with _scope(sets[i].scope), jax.named_scope("unembed"):
                picked.append(at_rows(_part(x, sets, i), sets[i]))
        with _scope(shared), jax.named_scope("unembed"):
            both = head(rms_norm(jnp.concatenate(picked),  # (sum B, D)
                                 scale, cfg.norm_eps))
        at = 0
        for i, p in zip(wanted, picked):
            with _scope(sets[i].scope), jax.named_scope("unembed"):
                logits[i] = both[at:at + p.shape[0]]
            at += p.shape[0]
    else:
        for i, s in enumerate(sets):
            if not all_logits and s.logits_at is None:
                continue
            with _scope(s.scope), jax.named_scope("unembed"):
                xs = rms_norm(_part(x, sets, i), scale, cfg.norm_eps)
                logits[i] = head(xs if all_logits else at_rows(xs, s))
    if pools.assign is not None:
        if loads:  # (expert layers, E): this walk's assignments
            load = jnp.stack(loads)
            assigned = jnp.stack([load.sum(), load.max(),
                                  grouped_matmul.rows_computed(load)])
        pools = pools._replace(assign=pools.assign + assigned)
    return logits, pools._replace(lengths=cache.lengths,
                                  tables=cache.tables)


def _double_layer(x, lp, layer_idx: int, params, cfg: ModelConfig, pools,
                  sets, rows, cos, sin, pos_all, shared, use_pallas: bool):
    """One double layer of `forward_sets`' walk (`models/latent.py`): two
    latent attention blocks, each with a pool layer of its own, two dense
    MLPs, and the experts computed from the first half's normed stream and
    added after the second half. What is per token runs once over all
    sets' tokens; each set writes its tokens' latent entries and runs the
    kernel in the absorbed form, under `attn/latent` in its own scope.
    Returns (x', pools', the layer's (held, identity, absent) assignment
    counts)."""
    shortcut = None
    for i in (0, 1):
        hp = latent.half(params["layers"], layer_idx, i)
        at = cfg.attention_blocks * layer_idx + i
        with _scope(shared), jax.named_scope("attn"):
            q_all, entries = latent.latent_qkv(
                rms_norm(x, hp["attn_norm"], cfg.norm_eps), hp, cfg, cos,
                sin, pos_all)
        outs = []
        for j, (s, (_, wpos, lens_after, ppb)) in enumerate(zip(sets, rows)):
            with _scope(s.scope), jax.named_scope("attn"), \
                    jax.named_scope("latent"):
                view = _write_window(
                    pools.of_kind("full", s.tables)._replace(
                        lengths=s.lengths), at,
                    _part(entries, sets, j)[:, :, None, :], None, wpos)
                kw = dict(widths=s.widths, latent_dv=cfg.kv_lora_rank,
                          scale=cfg.head_dim ** -0.5)
                if use_pallas:
                    kw["pages_per_block"] = ppb
                o = (paged_attention if use_pallas else paged_attention_xla)(
                    _part(q_all, sets, j), view.k, None, lens_after,
                    view.tables, at, **kw)
                # the next write into the pool waits for this read of it:
                # left to itself XLA keeps the pool as this call read it
                # and writes into a copy (3.5 GB a block where a group of
                # one row makes the write a slice update)
                o, pool = jax.lax.optimization_barrier((o, view.k))
                pools = pools.with_kind("full", view._replace(k=pool))
                outs.append(o)
        with _scope(shared):
            with jax.named_scope("attn"):
                x = latent.latent_out(x, _side_by_side(outs), hp, cfg)
            u = rms_norm(x, hp["mlp_norm"], cfg.norm_eps)
            if i == 0:
                with jax.named_scope("moe_shortcut"):
                    shortcut, aux = moe.moe_mlp(
                        u, lp, cfg, stack=(params["layers"], layer_idx))
            x = x + latent.dense_mlp(u, hp, cfg)
    with _scope(shared):
        return x + shortcut, pools, aux["assign"]


def _parallel_layer(x, lp, layer_idx: int, cfg: ModelConfig, pools, sets,
                    rows, cos, sin, pos_all, shared, use_pallas: bool):
    """One parallel layer of `forward_sets`' walk (`models/mixer.py`): the
    mixer and the attention block read one normed input and are added to
    the stream together, then the dense MLP. What is per token (the norms,
    both blocks' projections, the mixer's gate and norm, the MLP) runs once
    over all sets' tokens; each set writes its keys and values and runs the
    paged kernel under `attn`, and runs its convolution and its scan
    against its rows' states under `ssm`: a window of chunk rows through
    the chunked scan, gathered and scattered by slot, the decode rows (a
    set without `widths`, one token a row) through the update over the
    layer's whole state in place. Returns (x', pools')."""
    with _scope(shared):
        u = rms_norm(x, lp["attn_norm"], cfg.norm_eps)
        with jax.named_scope("attn"):
            qkv = mixer.attention_qkv(u, lp, cfg, cos, sin, pos_all)
        with jax.named_scope("ssm"):
            z, xbc, dt_raw = mixer.project_in(u, lp, cfg)
    ssm, conv = list(pools.ssm), list(pools.conv)
    outs, mixed = [], []
    for i, (s, (_, wpos, lens_after, ppb)) in enumerate(zip(sets, rows)):
        with _scope(s.scope), jax.named_scope("attn"):
            q, k, v = (_part(y, sets, i) for y in qkv)
            view = _write_window(
                pools.of_kind("full", s.tables)._replace(lengths=s.lengths),
                layer_idx, k, v, wpos)
            pools = pools.with_kind("full", view)
            if use_pallas:
                o = paged_attention(q, view.k, view.v, lens_after,
                                    view.tables, layer_idx,
                                    pages_per_block=ppb, widths=s.widths)
            else:
                o = paged_attention_xla(q, view.k, view.v, lens_after,
                                        view.tables, layer_idx,
                                        widths=s.widths)
        outs.append(o)
        with _scope(s.scope), jax.named_scope("ssm"):
            operands = (_part(xbc, sets, i), _part(dt_raw, sets, i),
                        ssm[layer_idx], conv[layer_idx], s.slots, s.lengths)
            if s.widths is None and s.tokens.shape[1] == 1:
                # the decode rows: every row one real token
                y, ssm[layer_idx], conv[layer_idx] = mixer.step_slots(
                    *operands, lp, cfg)
            else:
                y, ssm[layer_idx], conv[layer_idx] = mixer.mix_rows(
                    *operands, s.widths, lp, cfg)
        mixed.append(y)
    with _scope(shared):
        with jax.named_scope("attn"):
            attn = mixer.attention_out(_side_by_side(outs), lp, cfg)
        with jax.named_scope("ssm"):
            mix = mixer.project_out(_side_by_side(mixed), z, lp, cfg)
        f32 = jnp.float32
        x = (x.astype(f32) + cfg.ssm_out_multiplier * mix.astype(f32)
             + cfg.attention_out_multiplier * attn.astype(f32)
             ).astype(x.dtype)
        x = x + mixer.mlp(rms_norm(x, lp["mlp_norm"], cfg.norm_eps), lp,
                          cfg)
    return x, pools._replace(ssm=tuple(ssm), conv=tuple(conv))


def window_forward(params, tokens: jnp.ndarray, cfg: ModelConfig,
                   cache: PagedKVCache, *, logits_at: jnp.ndarray | None,
                   all_logits: bool = False,
                   pages_per_block: int | None = None,
                   mesh=None, tp_axis: str = "tp",
                   lora=None, aid=None, widths: jnp.ndarray | None = None,
                   slots: jnp.ndarray | None = None):
    """Forward W new positions per slot against the paged cache: the
    one-set case of `forward_sets`.

    Args:
      tokens: (B, W) int32 — slot b's tokens for absolute positions
        [lengths[b], lengths[b] + W). Pad rows/slots freely: writes
        through sentinel tables drop, outputs are masked by the caller.
      logits_at: (B,) int32 in-window indices — return logits only at
        that position per slot ((B, V) f32); the chunked-prefill path
        needs one sampled position per chunk, never the (B, W, V) tensor.
      all_logits: return (B, W, V) f32 (speculative verification).
        With neither, returns None (interior prefill chunks).
      widths: optional (B,) int32 — per-row VALID window widths for
        ragged mixed batches. Positions at window index >= widths[b]
        neither write kv nor anchor attention: their writes drop (the
        page-table scatter masks them) and attention treats row b's
        window as [lengths[b], lengths[b] + widths[b]) exactly as a
        width-widths[b] uniform dispatch would. Rows with width 0 are
        fully inert (sentinel-table discipline still applies on top).
      slots: (B,) int32, a model with a mixer: `RowSet.slots`.
      lora, aid: multi-adapter serving — (stacks, scales) from
        inference.multi_lora.AdapterSet.device_args + per-slot adapter
        ids (B,); each layer gathers its per-row (a, b, scale) and the
        transformer blocks add the low-rank deltas (id 0 = exact base).
      mesh, tp_axis: tensor-parallel serving. The XLA parts (matmuls,
        gathers, unembed) need nothing — params carry NamedShardings and
        jit propagates them, as in the contiguous engine. Only the
        pallas kernel cannot be auto-partitioned; with a mesh whose
        `tp_axis` is > 1 it runs under shard_map with kv heads sharded
        (ops.paged_attention.paged_attention_tp).

    Returns (logits, cache') — cache' has the window written but lengths
    UNCHANGED (see module docstring).
    """
    (logits,), cache = forward_sets(
        params, cfg, cache,
        [RowSet(tokens, cache.lengths, cache.tables, widths, logits_at,
                slots=slots)],
        all_logits=all_logits, pages_per_block=pages_per_block, mesh=mesh,
        tp_axis=tp_axis, lora=lora, aid=aid)
    return logits, cache


def _token_logprobs(logits: jnp.ndarray, toks: jnp.ndarray) -> jnp.ndarray:
    """log P(tok) under the model's raw (pre-filter) distribution — the
    one serving-API logprob convention, shared by admission and decode."""
    return jnp.take_along_axis(jax.nn.log_softmax(logits, axis=-1),
                               toks[:, None], axis=-1)[:, 0]
