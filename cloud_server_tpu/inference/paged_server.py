"""Paged continuous-batching server: block-table KV, shared prefixes,
chunked prefill, and in-server speculative decoding.

What the paged design buys over one contiguous cache row a slot:

  * Memory scales with resident tokens, not max_slots x max_len: the pool
    is `num_pages` fixed-size pages; a slot holds ceil(context / ps)
    pages. More concurrent requests fit in the same HBM whenever requests
    are shorter than max_context or share prefixes.
  * Prefix reuse is GENERAL (radix-style, page granularity): any request
    whose token prefix matches cached pages — same system prompt, same
    few-shot header, a multi-turn follow-up replaying the conversation
    (generated tokens included) — skips prefill for the shared pages.
    No server-lifetime single prefix; the cache is learned from traffic
    and LRU-evicted under memory pressure (inference/block_allocator.py).
  * Chunked prefill: admissions run as a sequence of bounded window
    dispatches (`prefill_chunk` tokens each), so one long prompt never
    stalls active decodes for its whole prefill — inter-token latency
    stays bounded (the serving bench measures it).
  * STALL-FREE MIXED BATCHING: while any admission is in flight, each
    scheduler iteration fuses ONE ragged prefill group (every admitting
    slot the token budget selected, each at its own width, no
    remainder-bucket grouping) and the full multi-round decode
    dispatch into a single jitted program with a single host sync
    (`_mixed_step`), so decode keeps its round count while admissions
    run and a landing prompt never waits out a decode dispatch. Greedy
    and seeded outputs are token-for-token the dense engine's
    (tests/test_mixed_scheduler.py). `mixed_token_budget` caps the
    tokens packed per iteration (decode rows first, prefill fills the
    rest, one minimal chunk guaranteed so TTFT stays bounded); the
    default is work-conserving.
  * Decode batch COMPACTION: decode dispatches carry one row per LIVE
    slot (pow2-padded) with a slot_ids indirection into the per-slot
    device state, so attention gathers and matmuls scale with
    occupancy instead of max_slots: a half-admitted batch does not pay
    full-batch decode cost. Fully-live batches skip the indirection
    entirely.
  * Speculative decoding IS the decode loop (spec_drafts > 0): per-slot
    n-gram proposals drafted on device from each slot's token history,
    verified batch-wide in one W = drafts+1 window, committed per slot
    with the exact accept/residual rule (`speculative._accept_point_mass`
    — output distribution provably unchanged; token-for-token greedy).
    No draft model, no extra memory; repetition-heavy decodes commit
    several tokens per model pass. With a DRAFT MODEL
    (`draft_params`/`draft_cfg`) the classic draft/verify loop runs the
    same way, and BOTH sources compose with mixed batching: the draft
    model's chunk prefill and per-round decode discipline are part of
    the one fused `_mixed_step` program.
  * ADAPTIVE speculation (on by default whenever spec_drafts > 0;
    `spec_control=` / `--spec-control`, inference/spec_control.py): a
    host-side controller tracks a rolling accept rate per slot from
    the per-round counts the scheduler already syncs and tunes each
    slot's draft length between 0 (plain decode) and spec_drafts with
    hysteresis; each row commits at most its own length (exact
    truncation; dispatch width quantized to {0, spec_drafts} — one
    compiled program per static width). Low-acceptance
    workloads converge to plain decode instead of paying dead verify
    windows; QoS generated-token buckets are charged only for
    committed tokens while rejected draft work lands on a per-tenant
    wasted-speculation counter.

  * ONE WAY THROUGH A STEP: PLAN, LAUNCH, COMMIT (`step`). JAX
    dispatch is async, so the scheduler pipelines the loop instead of
    serializing host policy against the device. Each step plans
    iteration N+1 — sweep, QoS/DRR admission, deadline checks, chain
    growth, and the whole numpy dispatch build — against the last
    COMMITTED ledger plus the in-flight dispatch's deterministic
    effects (job cursors advance by the takes it was launched with;
    planned lengths use the worst-case rounds*window bound) WHILE the
    device executes iteration N; then it LAUNCHES N+1 onto the device's
    queue behind N — lengths, live flags and table rows from that
    planned frame, which is exact while no draft tokens are in play,
    and each decode row's last token from the per-slot copy every step
    program leaves on the device (`state["last"]`; the patch says row
    by row which) — and only then pays the one sanctioned `device_get`
    commit of N, under N+1. The chip passes from one program to the
    next with no host in between, and nothing of the host's loop is
    serialized against it while the loop is shorter than the program.
    Where the launch needs what only the commit knows (draft tokens, a
    constrained row, a hand-off to prefetch: `_launch_waits`, read per
    iteration from the plan) the step commits N first, patches from
    the just-committed ledger and launches, and `host_gap_frac` in the
    flight records measures that residual tail. With nothing in flight
    (a cold start, a drained pipeline) the step FILLS the pipeline: it
    plans against the committed ledger and launches, and the next step
    commits. Write-safety: a plan made under a dispatch in flight never
    releases pages (no preemption, no slot teardown — sweep reaps are
    deferred to just after the commit), statically enforced by the
    dispatch-discipline pass's DD5 rule. A COMMIT does release the
    pages of the rows that end in it, while the dispatch launched ahead
    may still write them: every later writer of a page takes the pools
    from `self.state`, that dispatch's output, and so is ordered behind
    it (`_launch_plan`). On page famine such a plan degrades its round
    count and the pipeline drains; the fill's plan, made with nothing
    in flight, runs the full preemption escalation. Greedy and seeded
    outputs are token-for-token identical whichever order a step takes:
    scheduling is output-invariant (tests/test_overlap.py).

Scheduling state is HOST-authoritative (tables, lengths, active,
last_token live in numpy and ride into each dispatch as small inputs);
the device owns only the big buffers (page pools + per-slot token
history) and a copy of each slot's last token, which a launch made
ahead of a commit reads in place of the host's, all donated through
every dispatch. One device_get per scheduler
iteration, amortised over `decode_chunk` (speculative) rounds
(multi-token scheduling).

Write-safety rules the scheduler maintains (see paged_engine for why
writes through sentinel tables drop):
  * decode dispatches get SENTINEL table rows for every non-live slot, so
    a slot mid-admission can never have its freshly prefilled pages
    clobbered by the concurrent batch-wide decode window;
  * a slot's chain always covers its next dispatch's window writes —
    either reserved whole at admission (allocation="reserve": prompt +
    max_new + window slack, no mid-flight OOM possible) or grown
    just-in-time per dispatch (allocation="ondemand", the default:
    admission takes prompt + one window; `_extend_chains` allocates
    ahead of each decode dispatch and, on pool exhaustion, preempts the
    youngest slot — its pages release into the radix cache and its
    request requeues as a continuation whose re-prefill is mostly cache
    hits). On-demand never parks worst-case max_new headroom, so
    sustained concurrency at equal HBM is strictly higher
    (tests/test_paged_server.py::test_ondemand_concurrency_beyond_reservation).
"""

from __future__ import annotations

import collections
import dataclasses
import math
import os
import threading
import time
from functools import partial
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from cloud_server_tpu.config import InferConfig, ModelConfig
from cloud_server_tpu.inference import paged_engine, sampling
from cloud_server_tpu.inference.block_allocator import (
    BlockAllocator, WindowPagePool)
from cloud_server_tpu.inference.grammar import DEAD as _GDEAD
from cloud_server_tpu.inference.iteration_profile import (
    OVERLAP_PHASES, derive_gap_fields)
from cloud_server_tpu.inference.paged_engine import _token_logprobs
from cloud_server_tpu.inference.sampling import (
    SamplingParams, SamplingRows, make_rows, sample_from_probs,
    sample_logits, sample_logits_rows, sampling_probs,
    sampling_probs_rows)
from cloud_server_tpu.inference.request import (
    QueueFullError, Request, _bucket, emit_token, resolve_seed)
from cloud_server_tpu.inference.spec_control import resolve_controller
from cloud_server_tpu.inference.speculative import (
    _TAG_DRAFT, _accept_drafts, _accept_point_mass, _ngram_drafts,
    _row_pos_keys, sample_from_probs_keyed)
from cloud_server_tpu.models import moe
from cloud_server_tpu.utils.serving_metrics import (
    FlightRecorder, ServingMetrics)
from cloud_server_tpu.utils.tracing import _StepTracer


def _pow2_buckets(lo: int, hi: int) -> list[int]:
    out, b = [], lo
    while b < hi:
        out.append(b)
        b *= 2
    return out + [hi]


def _pad_pow2(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


# Neutral per-field fills for PADDING rows of a gathered SamplingRows
# (temp 0 = greedy, rep/top_p 1, bias slots out-of-vocab): padding
# samples are discarded, but rep=0 would divide to inf/NaN and trip
# jax_debug_nans even on discarded rows. Fields absent here fill with 0.
_SAMP_PAD_FILLS = {"top_p": 1.0, "rep": 1.0,
                   "bias_ids": sampling._BIAS_PAD}


def _gather_samp_rows(samp_rows, idx, n_real):
    """Per-slot SamplingRows rows gathered at `idx` (pre-clipped), with
    rows past n_real overwritten by the neutral pad fills."""
    out = []
    for name, dst in zip(SamplingRows._fields, samp_rows):
        rows = dst[idx].copy()
        rows[n_real:] = _SAMP_PAD_FILLS.get(name, 0)
        out.append(rows)
    return SamplingRows(*out)


def _gather_slot_state(state, slot_ids, batch_idx):
    """Compaction prologue shared by the decode cores: row views of the
    per-slot device state (see _decode_plain_core's COMPACTION note).
    slot_ids=None means rows ARE slots (no gathers)."""
    full_gstate = state["gstate"]
    n_slots = full_gstate.shape[0]
    sids = batch_idx if slot_ids is None else slot_ids
    sids_r = (batch_idx if slot_ids is None
              else jnp.clip(slot_ids, 0, n_slots - 1))
    pm = state.get("prompt_mask")  # None until penalties materialize
    if pm is not None and slot_ids is not None:
        pm = pm[sids_r]
    full_oc = state.get("out_counts")
    oc0 = (full_oc if slot_ids is None or full_oc is None
           else full_oc[sids_r])
    gstate0 = full_gstate if slot_ids is None else full_gstate[sids_r]
    return sids, sids_r, pm, oc0, gstate0, full_oc, full_gstate


def _scatter_slot_state(new_state, slot_ids, sids, oc, gstate,
                        full_oc, full_gstate):
    """Compaction epilogue: gathered gstate/out_counts rows back into the
    full per-slot state (sentinel rows drop)."""
    if slot_ids is None:
        new_state["gstate"] = gstate
        if oc is not None:
            new_state["out_counts"] = oc
        return
    new_state["gstate"] = full_gstate.at[sids].set(gstate, mode="drop")
    if oc is not None:
        new_state["out_counts"] = full_oc.at[sids].set(oc, mode="drop")


# ---------------------------------------------------------------------------
# Jitted dispatches (module-level so compiles are shared across servers)
# ---------------------------------------------------------------------------


def _grammar_mask(grammar, gid, st, eos_id):
    """Next-state row(s) + allowed-token mask from DFA state(s).

    gid: (B,) or (B, 1); st: (B,) or (B, W). DEAD states allow nothing
    (their garbage samples are never committed). EOS is allowed exactly
    at accepting states. gid 0 (the identity grammar) is unconditionally
    live at state 0 — a stale device state left by a slot's previous
    constrained occupant must never mask an unconstrained request. THE
    single mask construction — prefill, decode, and both speculative
    walks all call this."""
    tb, ac = grammar
    ident = gid == 0
    idx = jnp.where(ident, 0, jnp.maximum(st, 0))
    nrow = tb[gid, idx]
    live_st = (st != _GDEAD) | ident
    amask = (nrow != _GDEAD) & live_st[..., None]
    if eos_id >= 0:
        amask = amask.at[..., eos_id].set(ac[gid, idx] & live_st)
    return nrow, amask


_POOL_NAMES = ("k", "v", "k_scale", "v_scale",
               "wk", "wv", "wk_scale", "wv_scale", "assign", "ssm", "conv")


def _make_cache(pools, lengths, tables):
    return paged_engine.PagedKVCache(
        lengths=lengths, tables=tables,
        **{name: pools.get(name) for name in _POOL_NAMES})


def _split_cache(cache):
    """The cache's pools by name; a pool the model does not have (the
    scales of a bf16 cache, the window kind of a model without window
    layers, the values of a latent cache) is left out. `assign`, the
    assignment counts of a model with a routed share, rides with them, as
    do the per-slot states of a model with a mixer (`ssm`, `conv`): every
    program that carries the pages carries them, donated as they are, so
    a step launched ahead of the last one's commit reads the states that
    one wrote."""
    return {name: getattr(cache, name) for name in _POOL_NAMES
            if getattr(cache, name) is not None}


# A dispatch's data-dependent decode inputs cross to the device as ONE
# int32 array, a row a decode row: its length, last token, live flag,
# the dispatch's count (the same in every row) and its page table. The
# layout follows from the shapes of what is packed, so a program takes
# it apart with static slices and no layout is handed over beside it.
_PATCH_HEAD = 4

# The live flag of a patch row says besides where the row's last token
# is: in the patch (a row the host knows: every row of a launch that
# followed its commit), or in the per-slot copy the programs keep on
# the device (`state["last"]`: a row of a launch made AHEAD of the
# commit that will bring that token home).
_ROW_DEAD, _ROW_LIVE, _ROW_LAST_ON_DEVICE = 0, 1, 2


def _pack_patch(count: int, lengths, last_token, live,
                tables) -> np.ndarray:
    """The patch of one dispatch, in a buffer of its own: the transfer
    is asynchronous and may read (on the CPU backend, alias) the host
    memory after the call returns, so nothing writes it again. `live`
    is a row's flag, bools or the `_ROW_*` codes: a row at
    `_ROW_LAST_ON_DEVICE` is live and `last_token` says nothing of it."""
    buf = np.empty((tables.shape[0], _PATCH_HEAD + tables.shape[1]),
                   np.int32)
    buf[:, 0] = lengths
    buf[:, 1] = last_token
    buf[:, 2] = live
    buf[:, 3] = count
    buf[:, _PATCH_HEAD:] = tables
    return buf


@jax.named_scope("decode_rounds")
def _unpack_patch(patch, rng, kept=None, slot_ids=None):
    """(lengths, tables, last_token, live, key) inside a program. The
    key is the server's one key with the dispatch's count folded in: the
    nth dispatch draws from n on every scheduler path, the count is an
    operand like any other (no compile follows it), and no program but
    the step's own runs to make a key. The slices are the decode half's
    inputs and lie under its scope in a device trace.

    `kept` is the per-slot last tokens the program before this one left
    on the device (`state["last"]`), `slot_ids` the rows' slots (None:
    rows are slots): a row flagged `_ROW_LAST_ON_DEVICE` takes its last
    token from there, every other row from the patch."""
    last = patch[:, 1]
    if kept is not None:
        rows = (kept if slot_ids is None
                else kept[jnp.clip(slot_ids, 0, kept.shape[0] - 1)])
        last = jnp.where(patch[:, 2] == _ROW_LAST_ON_DEVICE, rows, last)
    return (patch[:, 0], patch[:, _PATCH_HEAD:], last,
            patch[:, 2] != _ROW_DEAD, jax.random.fold_in(rng, patch[0, 3]))


# What a plan stages crosses the same way: a (rows, columns) int32 array
# for the decode rows and one for the prefill group, a column a field,
# each value's 32 bits as they are (float32 and uint32 through a view on
# the host and `lax.bitcast_convert_type` in the program, never a cast;
# bools as 0 and 1). A `SamplingRows` lies in its tuple's order, a column
# a leaf and `MAX_LOGIT_BIAS` columns each bias leaf.
_SAMP_DTYPES = SamplingRows(
    temperature=np.float32, top_k=np.int32, top_p=np.float32,
    min_p=np.float32, rep=np.float32, pres=np.float32, freq=np.float32,
    seed=np.uint32, bias_ids=np.int32, bias_vals=np.float32,
    min_new=np.int32, plen=np.int32)
_SAMP_WIDTHS = tuple(sampling.MAX_LOGIT_BIAS if f.startswith("bias_")
                     else 1 for f in SamplingRows._fields)
_SAMP_COLS = sum(_SAMP_WIDTHS)

# The decode rows' buffer: a row's token limit, grammar, adapter and
# draft limit, its sampler, and behind them the row's slot where the
# rows are a gathered subset of the slots. Where rows are slots the
# column is absent, so the buffer's width says which program this is.
_ROWS_HEAD = 4

# The prefill group's buffer: the per-row fields below, the row's
# sampler, its table row (as wide as the patch's), its chunk tokens and
# its prompt. Chunk and prompt are both a bucket wide and only their sum
# shows in the shape, so the chunk's width reaches the program as a
# static (`chunk_w`).
_GROUP_FIELDS = ("widths", "g_lens", "sample_at", "slot_ids", "prompt_lens",
                 "orig_lens", "count_mask", "scatter_mask", "gid",
                 "gstate0", "aid")
_GROUP_HEAD = len(_GROUP_FIELDS)


def _pack_samp(buf, col: int, samp_rows) -> int:
    for leaf, w in zip(samp_rows, _SAMP_WIDTHS):
        buf[:, col:col + w] = leaf.reshape(len(leaf), w).view(np.int32)
        col += w
    return col


def _unpack_samp(buf, col: int):
    leaves = []
    for dtype, w in zip(_SAMP_DTYPES, _SAMP_WIDTHS):
        leaf = buf[:, col] if w == 1 else buf[:, col:col + w]
        leaves.append(leaf if dtype == np.int32
                      else lax.bitcast_convert_type(leaf, dtype))
        col += w
    return SamplingRows(*leaves), col


def _pack_rows(stop_len, gid, aid, draft_limit, samp_rows,
               slot_ids=None) -> np.ndarray:
    """The decode rows' launch-stable inputs in a buffer of their own
    (`_pack_patch` says why a fresh one). `slot_ids` None: rows are
    slots."""
    n = len(stop_len)
    buf = np.empty((n, _ROWS_HEAD + _SAMP_COLS + (slot_ids is not None)),
                   np.int32)
    buf[:, 0] = stop_len
    buf[:, 1] = gid
    buf[:, 2] = aid
    buf[:, 3] = draft_limit
    col = _pack_samp(buf, _ROWS_HEAD, samp_rows)
    if slot_ids is not None:
        buf[:, col] = slot_ids
    return buf


@jax.named_scope("decode_rounds")
def _unpack_rows(rows):
    """(stop_len, gid, aid, draft_limit, samp_rows, slot_ids) inside a
    program; `slot_ids` None where the buffer has no such column."""
    samp_rows, col = _unpack_samp(rows, _ROWS_HEAD)
    slot_ids = rows[:, col] if rows.shape[1] > col else None
    return (rows[:, 0], rows[:, 1], rows[:, 2], rows[:, 3], samp_rows,
            slot_ids)


def _pack_group(chunk, g_tables, prompt_rows, samp_rows,
                **head) -> np.ndarray:
    """The prefill group of one mixed step in a buffer of its own;
    `head` is the `_GROUP_FIELDS`, each (rows,)."""
    gp, w = chunk.shape
    t = g_tables.shape[1]
    assert len(head) == _GROUP_HEAD
    buf = np.empty((gp, _GROUP_HEAD + _SAMP_COLS + t + w
                    + prompt_rows.shape[1]), np.int32)
    for col, name in enumerate(_GROUP_FIELDS):
        buf[:, col] = head[name]
    col = _pack_samp(buf, _GROUP_HEAD, samp_rows)
    buf[:, col:col + t] = g_tables
    buf[:, col + t:col + t + w] = chunk
    buf[:, col + t + w:] = prompt_rows
    return buf


@jax.named_scope("prefill_group")
def _unpack_group(group, chunk_w: int, table_cols: int) -> dict:
    """`_pack_group`'s arguments by name inside a program."""
    out = {name: group[:, col] for col, name in enumerate(_GROUP_FIELDS)}
    out["count_mask"] = out["count_mask"] != 0
    out["scatter_mask"] = out["scatter_mask"] != 0
    out["samp_rows"], col = _unpack_samp(group, _GROUP_HEAD)
    out["g_tables"] = group[:, col:col + table_cols]
    col += table_cols
    out["chunk"] = group[:, col:col + chunk_w]
    out["prompt_rows"] = group[:, col + chunk_w:]
    return out


def _assign_out(state):
    """The pools' running assignment counts as the program leaves them
    (`PagedKVCache.assign`), as an output of their own: the state's leaf
    is donated to the next program, and a launch made ahead dispatches
    that one before these are read back. None for a model without a
    routed share."""
    assign = state["pools"].get("assign")
    return None if assign is None else jnp.copy(assign)


def _state_slots(cfg: ModelConfig, state, sids, live):
    """A decode round's `RowSet.slots`, for a model with a mixer: a live
    row's slot, and for a row that is not live (dead, or padding) the id
    past the slots, under which its state is neither advanced nor
    written. None for a model without per-slot state."""
    if not cfg.ssm_heads:
        return None
    return jnp.where(live, sids, state["last"].shape[0]).astype(jnp.int32)


def _keep_last(new_state, state, slots, mask, tokens):
    """Leave `tokens` in the per-slot last tokens of `new_state` for the
    rows under `mask` (rows' `slots`; a padding row's sentinel drops):
    what the next program reads where its launch did not wait for the
    host to learn them (`_unpack_patch`)."""
    kept = state["last"]
    new_state["last"] = kept.at[
        jnp.where(mask, slots, kept.shape[0])].set(tokens, mode="drop")


# The scopes below name the two halves of every step program in a
# device trace (`tf_op` of an op's event metadata): ops of an admission
# window lie under `prefill_group/`, ops of the decode or speculative
# rounds under `decode_rounds/`, and inside either under `attn`, the
# `moe_*` scopes of models/moe.py (`mlp` for a dense block), `unembed`
# and `sample`; what a one-walk step runs once for both halves lies
# under `joined_walk/`, its one product with the head (`unembed`)
# included. Names are HLO metadata only: no program, cache key or
# result changes with them.
@jax.named_scope("prefill_group")
def _prefill_core(params, state, chunk, g_lens, g_tables, sample_at,
                  slot_ids, prompt_rows, prompt_lens, rng,
                  samp_rows, orig_lens, count_mask,
                  gid=None, gstate0=None, grammar=None,
                  lora=None, aid=None,
                  draft_params=None, widths=None, scatter_mask=None,
                  logits=None, *,
                  cfg: ModelConfig, infer_cfg: InferConfig,
                  scatter_prompt: bool, mesh=None, draft_cfg=None,
                  use_rows: bool = False, use_bias: bool = False):
    """One admission window for a (padded) G-row group: the prefill
    half of `_mixed_step`. The group is RAGGED: per-row `widths`, since
    the token budget hands every admitting row a different width in the
    same call, and a per-row `scatter_mask`, since rows at different
    admission progress share one dispatch.

    chunk: (G, Wc) tokens for positions [g_lens, g_lens + Wc) per row —
    rows at different offsets, which is how shared prefixes resume deeper
    and how successive chunks continue. sample_at: in-window index of
    each row's LAST true prompt token (clamped; the caller keeps the
    sample only when it truly falls inside this chunk). On the first
    chunk (`scatter_prompt`, further restricted to `scatter_mask` rows
    when given) each row's full prompt is written into its slot's device
    history for n-gram drafting. Padding rows carry slot_id == max_slots
    and sentinel tables: every scatter drops.

    Per-request sampling state: `orig_lens` (G,) marks the original
    prompt / generated boundary inside `prompt_rows` (continuations from
    a preemption carry already-generated tokens, which must count as
    OUTPUT for presence/frequency penalties); `count_mask` (G,) flags
    the chunk where each row's first-token sample is truly captured.
    `samp_rows` always lands in the slots' row state; `use_rows`
    (static) additionally samples the first token through it.

    `logits` (G, V): the window's forward is the caller's (`_mixed_step`
    where it walks the layers once for both halves): `state["pools"]`
    already holds the window's writes, and everything after the forward
    runs here as it always does.

    Returns (state', first-token candidates (G,), their logprobs (G,)).
    """
    new_state = dict(state)
    if logits is None:
        cache = _make_cache(state["pools"], g_lens, g_tables)
        logits, cache = paged_engine.window_forward(
            params, chunk, cfg, cache, logits_at=sample_at, mesh=mesh,
            lora=lora, aid=aid, widths=widths,
            slots=slot_ids if cfg.ssm_heads else None)
        new_state["pools"] = _split_cache(cache)

    has_pen = "prompt_mask" in state  # buffers materialize lazily
    pm = oc = None
    if has_pen:
        pm, oc = state["prompt_mask"], state["out_counts"]
        g, pb = prompt_rows.shape
        vsz = pm.shape[-1]
        rowi = jnp.arange(g)
        if scatter_prompt:
            # rebuild the slots' penalty state from the admission
            # prompt: positions < orig_len are PROMPT presence,
            # [orig_len, prompt_len) are generated-before-preemption
            # OUTPUT counts
            pos = jnp.broadcast_to(jnp.arange(pb)[None, :], (g, pb))
            pm_cols = jnp.where(pos < orig_lens[:, None], prompt_rows,
                                vsz)
            pm_rows = jnp.zeros((g, vsz), bool).at[
                rowi[:, None], pm_cols].set(True, mode="drop")
            oc_cols = jnp.where((pos >= orig_lens[:, None])
                                & (pos < prompt_lens[:, None]),
                                prompt_rows, vsz)
            oc_rows = jnp.zeros((g, vsz), jnp.int32).at[
                rowi[:, None], oc_cols].add(1, mode="drop")
            sc_ids = (slot_ids if scatter_mask is None
                      else jnp.where(scatter_mask, slot_ids, pm.shape[0]))
            pm = pm.at[sc_ids].set(pm_rows, mode="drop")
            oc = oc.at[sc_ids].set(oc_rows, mode="drop")
    amask = None
    if grammar is not None:
        # constrained rows: allowed first tokens from each row's resume
        # state; EOS allowed only at accepting states
        nrow, amask = _grammar_mask(grammar, gid, gstate0,
                                    infer_cfg.eos_token_id)
    with jax.named_scope("sample"):
        if use_rows:
            toks = sample_logits_rows(
                logits, samp_rows, prompt_lens,
                prompt_mask=pm[slot_ids] if has_pen else None,
                out_counts=oc[slot_ids] if has_pen else None,
                eos_id=infer_cfg.eos_token_id, use_bias=use_bias,
                allowed_mask=amask)
        else:
            toks = sample_logits(logits, rng, infer_cfg)
        lps = _token_logprobs(logits, toks)
    if gstate0 is not None:
        # advance ONLY the rows captured THIS chunk — a multi-chunk job
        # revisits rows whose sample landed in an earlier chunk, and
        # rewriting those would reset their already-advanced state.
        # Grammar-free groups still SCATTER (their gstate0, i.e. 0):
        # admission must overwrite whatever DFA state the slot's
        # previous occupant left behind — DEAD is sticky, and a stale
        # DEAD row would mask every token for the new request the
        # moment any other live slot is constrained.
        if grammar is not None:
            g_rows = prompt_rows.shape[0]
            nstate = nrow[jnp.arange(g_rows), toks]
        else:
            nstate = gstate0
        gs = state["gstate"]
        cap_idx = jnp.where(count_mask, slot_ids, gs.shape[0])
        new_state["gstate"] = gs.at[cap_idx].set(nstate, mode="drop")
    if has_pen:
        # the captured first token is this slot's first generated token
        oc = oc.at[slot_ids, toks].add(count_mask.astype(jnp.int32),
                                       mode="drop")
        new_state["prompt_mask"] = pm
        new_state["out_counts"] = oc
    if draft_cfg is not None:
        # the draft model prefills the same chunk into ITS pools (same
        # page ids / tables, draft geometry) so in-server draft-model
        # speculation has the full context cached — including shared
        # prefix pages, which carry the draft kv alongside the target's.
        # The mixed scheduler's RAGGED groups pass per-row `widths`:
        # the draft's writes and attention honor each row's true
        # progress exactly like the target's call above
        dcache = _make_cache(state["draft_pools"], g_lens, g_tables)
        _, dcache = paged_engine.window_forward(
            draft_params, chunk, draft_cfg, dcache, logits_at=None,
            mesh=mesh, widths=widths)
        new_state["draft_pools"] = _split_cache(dcache)
    hist = state["hist"]
    if scatter_prompt:
        pb = prompt_rows.shape[1]
        cols = jnp.broadcast_to(jnp.arange(pb)[None, :], prompt_rows.shape)
        keep = cols < prompt_lens[:, None]
        if scatter_mask is not None:
            keep &= scatter_mask[:, None]
        cols = jnp.where(keep, cols, hist.shape[1])
        hist = hist.at[slot_ids[:, None], cols].set(prompt_rows,
                                                    mode="drop")
    new_state["hist"] = hist
    # the first token of an admission that completes here is its slot's
    # last token: the host writes it into its ledger at the commit
    # (`_complete_admission_chunks`), the device keeps it from now
    _keep_last(new_state, state, slot_ids, count_mask, toks)
    return new_state, toks, lps


@jax.named_scope("decode_rounds")
def _decode_plain_core(params, state, lengths, tables, last_token, live,
                       rng, samp_rows, gid=None, grammar=None,
                       lora=None, aid=None, slot_ids=None, logits=None, *,
                       cfg: ModelConfig,
                       infer_cfg: InferConfig, n_rounds: int, mesh=None,
                       use_rows: bool = False, use_bias: bool = False):
    """n_rounds plain decode steps (W=1) in one dispatch (lax.scan).
    Traced body shared by `_decode_rounds` and `_mixed_step`.

    `live` slots advance one token per round; the rest are frozen (their
    writes drop through the sentinel tables the caller passes).
    `use_rows` (static) samples through the per-request SamplingRows,
    advancing the generated-token counts for penalties.

    COMPACTION (`slot_ids`): rows may be a gathered subset of slots —
    row i is slot slot_ids[i] (padding rows carry the max_slots
    sentinel, so their per-slot state scatters drop). The per-slot
    device state (hist / gstate / penalty counts) stays full-size;
    lengths / tables / last / samp_rows arrive already gathered. A
    half-empty batch then dispatches at half the rows — attention
    gathers and matmuls scale with LIVE slots, not max_slots, which is
    what keeps decode affordable while admissions hold slots.
    slot_ids=None means rows ARE slots (the uncompacted layout).

    `logits` (Bg, V): the one round's forward is the caller's, as in
    `_prefill_core` (n_rounds == 1; `state["pools"]` holds its writes).

    Returns (state', lengths', last', (toks (R, Bg), lps (R, Bg),
    counts (R, Bg) int32)) — rows in the caller's gathered order.
    """
    pad = infer_cfg.pad_token_id
    batch_idx = jnp.arange(lengths.shape[0])
    (sids, sids_r, pm, oc0, gstate0,
     full_oc, full_gstate) = _gather_slot_state(state, slot_ids, batch_idx)
    state_slots = _state_slots(cfg, state, sids, live)

    def body(carry, rng_t):
        lengths, last, hist, pools, oc, gstate = carry
        # `last` is the committed token at sequence position `lengths`
        # (this round writes its kv there); record it in the history so
        # drafting/multi-turn reads see an unbroken token sequence
        cols = jnp.where(live, lengths, hist.shape[1])
        hist = hist.at[sids, cols].set(last, mode="drop")
        if logits is None:
            cache = _make_cache(pools, lengths, tables)
            round_logits, cache = paged_engine.window_forward(
                params, last[:, None], cfg, cache,
                logits_at=jnp.zeros_like(lengths), mesh=mesh,
                lora=lora, aid=aid, slots=state_slots)
            pools = _split_cache(cache)
        else:
            round_logits = logits
        amask = None
        if grammar is not None:
            nrow, amask = _grammar_mask(grammar, gid, gstate,
                                        infer_cfg.eos_token_id)
        with jax.named_scope("sample"):
            if use_rows:
                # the sampled token sits at position lengths + 1 (`last`
                # occupies `lengths`); the admission chunk folds the prompt
                # length, so positions never collide within a request
                tok = sample_logits_rows(round_logits, samp_rows,
                                         lengths + 1,
                                         prompt_mask=pm, out_counts=oc,
                                         eos_id=infer_cfg.eos_token_id,
                                         use_bias=use_bias,
                                         allowed_mask=amask)
                if oc is not None:
                    oc = oc.at[batch_idx, tok].add(live.astype(jnp.int32))
            else:
                tok = sample_logits(round_logits, rng_t, infer_cfg)
        if grammar is not None:
            # sticky DEAD: a dead row (post-EOS scan tail) must never
            # resurrect through the max(st, 0) clamp
            gstate = jnp.where(live & (gstate != _GDEAD),
                               nrow[batch_idx, tok], gstate)
        with jax.named_scope("sample"):
            lp = _token_logprobs(round_logits, tok)
        tok = jnp.where(live, tok, pad)
        new_len = jnp.where(live, lengths + 1, lengths)
        last = jnp.where(live, tok, last)
        return ((new_len, last, hist, pools, oc, gstate),
                (tok, lp, live.astype(jnp.int32)))

    (lengths, last, hist, pools, oc, gstate), out = lax.scan(
        body, (lengths, last_token, state["hist"], state["pools"],
               oc0, gstate0),
        jax.random.split(rng, n_rounds))
    new_state = dict(state)
    new_state["pools"] = pools
    new_state["hist"] = hist
    _scatter_slot_state(new_state, slot_ids, sids, oc, gstate,
                        full_oc, full_gstate)
    _keep_last(new_state, state, sids, live, last)
    return new_state, lengths, last, out


@partial(jax.jit,
         static_argnames=("cfg", "infer_cfg", "n_rounds", "mesh",
                          "use_rows", "use_bias"),
         donate_argnums=(1,))
def _decode_rounds(params, state, patch, rows, rng, grammar=None,
                   lora=None, *,
                   cfg: ModelConfig, infer_cfg: InferConfig, n_rounds: int,
                   mesh=None, use_rows: bool = False,
                   use_bias: bool = False):
    """`_decode_plain_core` as a program of its own, fed the packed
    patch, the packed rows (`_unpack_rows`) and the server's key
    (`_unpack_patch`; a row's last token from the patch or from
    `state["last"]`, as the patch says), with `_assign_out` behind the
    core's results."""
    _, gid, aid, _, samp_rows, slot_ids = _unpack_rows(rows)
    lengths, tables, last_token, live, key = _unpack_patch(
        patch, rng, state["last"], slot_ids)
    state, lengths, last, out = _decode_plain_core(
        params, state, lengths, tables, last_token, live, key, samp_rows,
        gid, grammar, lora, aid, slot_ids,
        cfg=cfg, infer_cfg=infer_cfg, n_rounds=n_rounds, mesh=mesh,
        use_rows=use_rows, use_bias=use_bias)
    return state, lengths, last, out, _assign_out(state)


@jax.named_scope("decode_rounds")
def _spec_core(params, state, lengths, tables, last_token, live,
               stop_len, rng, samp_rows, gid=None, grammar=None,
               lora=None, aid=None,
               draft_params=None, slot_ids=None, draft_limit=None, *,
               cfg: ModelConfig, infer_cfg: InferConfig, n_rounds: int,
               n_drafts: int, mesh=None, draft_cfg=None,
               use_rows: bool = False, use_bias: bool = False):
    """n_rounds speculative rounds in one dispatch. Traced body shared
    by `_spec_rounds` and `_mixed_step`.

    Each round drafts `n_drafts` tokens per slot — from a DRAFT MODEL
    decoding against its own paged cache (draft_params/draft_cfg;
    classic speculative decoding) or from the slot's device-resident
    history (prompt-lookup n-grams) — scores the (drafts+1)-token window
    in ONE batched window_forward, and commits each slot's accepted
    prefix plus the corrective/bonus token (exact accept rule — see
    speculative._accept_drafts / _accept_point_mass). Commits are
    capped at stop_len so a slot never outruns its page chain.

    Draft-model cache discipline (mirrors speculative_generate): G+1
    draft decode steps per round — step j writes the draft kv of its
    input token at position lengths + j, so accepted positions are
    already cached and the corrective token's kv lands when the next
    round's step 0 feeds it. Stale draft entries past the commit point
    are masked by lengths and overwritten by later rounds, exactly like
    the target pool.

    Per-request sampling (`use_rows`): penalties stay EXACT through the
    window — target probabilities at window position i use the counts as
    of that position (base counts + the drafts committed before i, a
    shifted cumulative one-hot), and the draft model's q at step j uses
    the same construction, so the accept rule compares the identical
    distributions plain per-token decoding would have sampled from.

    COMPACTION (`slot_ids`): as in `_decode_plain_core` — rows may be a
    gathered subset of slots; per-slot device state stays full-size and
    scatters go through slot_ids (sentinel rows drop).

    ADAPTIVE draft lengths (`draft_limit`, (Bg,) int32): each row
    commits at most draft_limit + 1 tokens per round — the exact same
    truncation the stop_len cap performs, so a row at limit 0 is plain
    decode riding the speculative window (its one committed token is
    the draft if accepted else the corrective: the marginal is the
    target distribution either way, and at temperature 0 it is THE
    greedy token). The dispatch still drafts/verifies n_drafts
    positions for every row; the host drops n_drafts to 0 (the plain
    program) once every live slot is off (spec_control.py).

    Seeded requests (`use_rows`): the draft-model proposal, accept
    uniform, and corrective draws are POSITION-KEYED on tagged streams
    of the request's seed (speculative._row_pos_keys), so at a fixed
    draft length a seeded speculative stream is identical under both
    schedulers, and commit truncation (stop_len / draft_limit) replays
    transparently. Mid-stream LENGTH changes keep distributional
    exactness but not draw-for-draw replay at temperature > 0 (see
    speculative.py's stream-tag note); greedy is exact throughout.

    Returns (state', lengths', last',
    (toks (R, Bg, G+1), lps (R, Bg, G+1), counts (R, Bg))).
    """
    g = n_drafts
    b = lengths.shape[0]
    pad = infer_cfg.pad_token_id
    batch_idx = jnp.arange(b)
    j = jnp.arange(g + 1)[None, :]
    use_draft = draft_cfg is not None
    (sids, sids_r, pm, oc0, gstate_init,
     full_oc, full_gstate) = _gather_slot_state(state, slot_ids, batch_idx)

    def body(carry, rng_t):
        lengths, last, hist, pools, dpools, oc, gstate = carry
        rng_acc, rng_draft = jax.random.split(rng_t)
        can_commit = live & (lengths < stop_len)

        # `last` is the committed token at sequence position `lengths`;
        # write it into the history BEFORE drafting so bigram lookups
        # spanning the prompt/generated boundary see the true sequence
        cols_last = jnp.where(live, lengths, hist.shape[1])
        hist = hist.at[sids, cols_last].set(last, mode="drop")
        hist_rows = hist if slot_ids is None else hist[sids_r]
        valid = lengths + 1  # committed tokens = [0, lengths] incl. last
        if use_draft:
            def d_step(dc, inp):
                tok, off, rng_d, cnt, st_d = inp
                dcache = _make_cache(dc, lengths + off, tables)
                dlogits, dcache = paged_engine.window_forward(
                    draft_params, tok[:, None], draft_cfg, dcache,
                    logits_at=jnp.zeros_like(lengths), mesh=mesh)
                dmask = None
                if grammar is not None:
                    _, dmask = _grammar_mask(grammar, gid, st_d,
                                             infer_cfg.eos_token_id)
                if use_rows:
                    qp = sampling_probs_rows(
                        dlogits, samp_rows, prompt_mask=pm,
                        out_counts=cnt, positions=lengths + 1 + off,
                        eos_id=infer_cfg.eos_token_id, use_bias=use_bias,
                        allowed_mask=dmask)
                else:
                    qp = sampling_probs(dlogits, infer_cfg)
                if use_rows:
                    # position-keyed proposal stream: schedule- and
                    # draft-length-invariant for seeded requests
                    dkeys = _row_pos_keys(samp_rows.seed,
                                          lengths + 1 + off, _TAG_DRAFT)
                    nxt = sample_from_probs_keyed(qp, dkeys)
                else:
                    nxt = sample_from_probs(qp, rng_d)
                return _split_cache(dcache), (nxt, qp)

            # inputs step j: the token at position lengths + j; step 0
            # feeds `last`, later steps feed the previous step's sample
            # — expressed as a scan whose carried token rides in the
            # iteration outputs, so unroll manually (G is tiny/static)
            toks_j, qps = [], []
            tok = last
            run_cnt = oc  # counts as of each draft position (exactness)
            st_d = gstate
            for step in range(g + 1):
                rng_draft, rd = jax.random.split(rng_draft)
                dpools, (nxt, qp) = d_step(
                    dpools, (tok, jnp.int32(step), rd, run_cnt, st_d))
                if use_rows and run_cnt is not None and step < g:
                    run_cnt = run_cnt.at[batch_idx, nxt].add(1)
                if grammar is not None and step < g:
                    tb, _ = grammar
                    st_d = jnp.where(
                        st_d == _GDEAD, st_d,
                        tb[gid, jnp.maximum(st_d, 0), nxt])
                tok = nxt
                toks_j.append(tok)
                qps.append(qp)
            drafts = jnp.stack(toks_j[:g], axis=1)        # (B, G)
            q_probs = jnp.stack(qps[:g], axis=1)          # (B, G, V)
        else:
            t_prev2 = hist_rows[batch_idx, jnp.maximum(valid - 2, 0)]
            drafts = _ngram_drafts(hist_rows, valid, t_prev2, last, g, pad)
        window = jnp.concatenate([last[:, None], drafts], axis=1)

        cache = _make_cache(pools, lengths, tables)
        vlogits, cache = paged_engine.window_forward(
            params, window, cfg, cache, logits_at=None, all_logits=True,
            mesh=mesh, lora=lora, aid=aid)
        amask_w = None
        if grammar is not None:
            # walk the DFA through the drafts: position i's mask comes
            # from the state AFTER drafts[:i] (exactly the state plain
            # per-token decoding would be in)
            tb, _ = grammar
            sts = [gstate]
            for jj in range(g):
                cur = sts[-1]
                nxt_st = tb[gid, jnp.maximum(cur, 0), drafts[:, jj]]
                sts.append(jnp.where(cur == _GDEAD, cur, nxt_st))
            sts_m = jnp.stack(sts, axis=1)  # (B, G+1)
            _, amask_w = _grammar_mask(grammar, gid[:, None], sts_m,
                                       infer_cfg.eos_token_id)
        with jax.named_scope("sample"):
            if use_rows and pm is not None:
                # counts at window position i = base + drafts committed
                # before i (position 0 scores the token after `last`, which
                # is already in the base counts)
                cum = jnp.cumsum(
                    jax.nn.one_hot(drafts, vlogits.shape[-1],
                                   dtype=jnp.int32), axis=1)
                counts_w = oc[:, None, :] + jnp.concatenate(
                    [jnp.zeros_like(cum[:, :1]), cum], axis=1)
                p_probs = sampling_probs_rows(
                    vlogits, samp_rows, prompt_mask=pm, out_counts=counts_w,
                    positions=(lengths + 1)[:, None] + j,
                    eos_id=infer_cfg.eos_token_id, use_bias=use_bias,
                    allowed_mask=amask_w)
            elif use_rows:
                p_probs = sampling_probs_rows(
                    vlogits, samp_rows,
                    positions=(lengths + 1)[:, None] + j,
                    eos_id=infer_cfg.eos_token_id, use_bias=use_bias,
                    allowed_mask=amask_w)
            else:
                p_probs = sampling_probs(vlogits, infer_cfg)  # (B, G+1, V)
            seeds = samp_rows.seed if use_rows else None
            pos0 = (lengths + 1) if use_rows else None
            if use_draft:
                n_acc, x = _accept_drafts(drafts, q_probs, p_probs, rng_acc,
                                          seeds=seeds, pos0=pos0)
            else:
                n_acc, x = _accept_point_mass(drafts, p_probs, rng_acc,
                                              seeds=seeds, pos0=pos0)

        drafts_x = jnp.concatenate([drafts, x[:, None]], axis=1)
        committed = jnp.where(j < n_acc[:, None], drafts_x,
                              jnp.where(j == n_acc[:, None],
                                        x[:, None], pad))
        count = jnp.where(can_commit, n_acc + 1, 0)
        if draft_limit is not None:
            # adaptive per-slot draft length (see docstring): the same
            # exact truncation as the stop_len cap below
            count = jnp.minimum(count, draft_limit + 1)
        count = jnp.minimum(count, jnp.maximum(stop_len - lengths, 0))
        toks = jnp.where(j < count[:, None], committed, pad)
        # log P(tok) under the raw target distribution at each window
        # position (position i's logits score the token committed there)
        lps = jnp.take_along_axis(
            jax.nn.log_softmax(vlogits, axis=-1),
            jnp.maximum(toks, 0)[..., None], axis=-1)[..., 0]

        new_len = lengths + count
        # committed[j] is the token at sequence position lengths + 1 + j
        # (position `lengths` holds `last`, written above)
        cols = (lengths + 1)[:, None] + j
        cols = jnp.where(j < count[:, None], cols, hist.shape[1])
        hist = hist.at[sids[:, None], cols].set(toks, mode="drop")
        if use_rows and oc is not None:
            vsz = oc.shape[-1]
            cnt_cols = jnp.where(j < count[:, None], toks, vsz)
            oc = oc.at[batch_idx[:, None], cnt_cols].add(1, mode="drop")
        if grammar is not None:
            tb, _ = grammar
            st = gstate
            for jj in range(g + 1):
                step_st = tb[gid, jnp.maximum(st, 0), toks[:, jj]]
                st = jnp.where((jj < count) & (st != _GDEAD), step_st, st)
            gstate = st
        last_idx = jnp.maximum(count - 1, 0)
        last2 = jnp.where(count > 0, committed[batch_idx, last_idx], last)
        return ((new_len, last2, hist, _split_cache(cache), dpools, oc,
                 gstate),
                (toks, lps, count))

    (lengths, last, hist, pools, dpools, oc, gstate), out = lax.scan(
        body, (lengths, last_token, state["hist"], state["pools"],
               state.get("draft_pools"), oc0, gstate_init),
        jax.random.split(rng, n_rounds))
    new_state = dict(state)
    new_state["pools"] = pools
    new_state["hist"] = hist
    _scatter_slot_state(new_state, slot_ids, sids, oc, gstate,
                        full_oc, full_gstate)
    _keep_last(new_state, state, sids, live, last)
    if dpools is not None:
        new_state["draft_pools"] = dpools
    return new_state, lengths, last, out


@partial(jax.jit,
         static_argnames=("cfg", "infer_cfg", "n_rounds", "n_drafts",
                          "mesh", "draft_cfg", "use_rows", "use_bias"),
         donate_argnums=(1,))
def _spec_rounds(params, state, patch, rows, rng, grammar=None, lora=None,
                 draft_params=None, *,
                 cfg: ModelConfig, infer_cfg: InferConfig, n_rounds: int,
                 n_drafts: int, mesh=None, draft_cfg=None,
                 use_rows: bool = False, use_bias: bool = False):
    """`_spec_core` as a program of its own, fed like `_decode_rounds`."""
    stop_len, gid, aid, draft_limit, samp_rows, slot_ids = _unpack_rows(
        rows)
    lengths, tables, last_token, live, key = _unpack_patch(
        patch, rng, state["last"], slot_ids)
    state, lengths, last, out = _spec_core(
        params, state, lengths, tables, last_token, live, stop_len, key,
        samp_rows, gid, grammar, lora, aid, draft_params, slot_ids,
        draft_limit,
        cfg=cfg, infer_cfg=infer_cfg, n_rounds=n_rounds,
        n_drafts=n_drafts, mesh=mesh, draft_cfg=draft_cfg,
        use_rows=use_rows, use_bias=use_bias)
    return state, lengths, last, out, _assign_out(state)


def _walks_once(cfg: ModelConfig, n_tokens: int, n_rounds: int,
                n_drafts: int, draft_cfg, lora) -> bool:
    """Whether `_mixed_step` walks the layer stack once for both of its
    halves (`paged_engine.forward_sets`), from what the call can observe,
    all of it static: the one walk computes what the two compute where

      * the decode half is one plain round: further rounds are a scan
        over the cache the first one wrote, `_spec_core` verifies
        windows of drafts, and a draft model walks layers of its own;
      * a token's MLP output does not depend on which other tokens
        share the call: a dense MLP, or experts at a capacity under
        which none of the `n_tokens` (chunk and decode together) can
        overflow, the test `moe._dispatch_grouped` makes. A capacity
        that can drop would drop other tokens in one call than in two;
      * no adapter is live: per-row low-rank deltas need rows, and the
        one walk lays every token in one row.
    """
    return (n_rounds == 1 and n_drafts == 0 and draft_cfg is None
            and lora is None
            and (cfg.num_experts < 2
                 or moe._capacity(cfg, n_tokens) >= n_tokens))


def _sorts_experts(cfg: ModelConfig, params, joined: bool,
                   chunk_tokens: int, decode_tokens: int) -> bool:
    """Whether an expert call of a step program takes `moe_mlp`'s sorted
    dispatch: the rule the trace applies (`moe._dispatch_grouped`: the
    call's token count, the capacity, the stack, the mesh), read on the
    host when the plan is built. A one-walk step makes one call a layer
    over both halves' tokens, any other program one for each half it has
    (`decode_tokens`: the rows of a round times its window). A dense MLP
    has no such call."""
    if cfg.num_experts < 2:
        return False
    stack = (params["layers"], 0)
    calls = ((chunk_tokens + decode_tokens,) if joined
             else (chunk_tokens, decode_tokens))
    return any(n > 0 and moe._dispatch_grouped(cfg, n, stack)
               for n in calls)


@partial(jax.jit,
         static_argnames=("cfg", "infer_cfg", "n_rounds", "n_drafts",
                          "scatter_prompt", "chunk_w", "mesh",
                          "draft_cfg", "use_rows_p", "use_bias_p",
                          "use_rows_d", "use_bias_d"),
         donate_argnums=(1,))
def _mixed_step(params, state, group, patch, rows, rng, grammar=None,
                lora=None, draft_params=None, *,
                cfg: ModelConfig, infer_cfg: InferConfig, n_rounds: int,
                n_drafts: int, scatter_prompt: bool, chunk_w: int,
                mesh=None, draft_cfg=None,
                use_rows_p: bool = False, use_bias_p: bool = False,
                use_rows_d: bool = False, use_bias_d: bool = False):
    """ONE token-budget mixed iteration, ONE jitted program, ONE host
    sync: the ragged prefill group (every admitting row the budget
    selected, each at its own width — `_prefill_core` with per-row
    `widths`/`scatter_mask`) followed by the full multi-round decode
    dispatch (`_decode_plain_core` / `_spec_core`, n_rounds of W = 1 or
    drafts + 1).

    DRAFT-MODEL speculation is fused too (`draft_params`/`draft_cfg`):
    the draft model's chunk prefill rides inside `_prefill_core`
    (ragged widths included) and its per-round G+1 decode discipline
    rides inside `_spec_core`, so the fastest decode path keeps
    stall-free batching. Draft rounds are funded as decode rows under
    the token budget — a live slot's decode claim is window =
    n_drafts + 1 tokens per round, charged against prefill funding by
    the host's budget split.

    This is what "fused" means here and why it is stall-free WITHOUT
    extra compute: decode keeps its full round count while admissions
    run, and every prefill chunk retires in the same dispatch, so
    decode throughput under churn stays at its steady-state slope and
    a step pays one host round trip, not one per admission group and
    one more for the decode rows.

    ONE WALK OF THE LAYERS where that computes the same function
    (`_walks_once`: one plain decode round, no drafts, no draft model,
    no live adapter, an MLP under which no token can be dropped): the
    chunk tokens and the decode round's rows meet every layer's weights
    in one call (`paged_engine.forward_sets`), so a step streams them
    once, not twice, and the head with them: the group's `sample_at`
    rows and the decode rows are unembedded in one product. Each half
    keeps its own cache write, paged kernel and sampler, and everything
    of the two cores around their forward runs as it is (they are handed
    the logits). Elsewhere each half walks the layers itself, head and
    all. Greedy/seeded outputs are
    token-for-token the dense engine's either way
    (tests/test_mixed_scheduler.py, tests/test_joined_walk.py).

    The decode half's lengths, tables, last tokens and live flags
    arrive as the one packed `patch`, and `rng` is the server's one key:
    `_unpack_patch` takes the array apart and folds the dispatch's count
    into the key, so the step is the only program a dispatch runs.
    Everything else of the two halves arrives as two more packed
    arrays, staged while the program before this one ran: `group`
    (`_unpack_group`, whose chunk is `chunk_w` wide) and `rows`
    (`_unpack_rows`).

    Prefill rows and decode rows are DISJOINT slots (a slot is live xor
    mid-admission), so program order between the halves is irrelevant,
    as is the order of their cache writes inside the one walk;
    slots in neither half ride along fully inert (width 0 and sentinel
    tables in the prefill group, live=False and sentinel tables in the
    decode half) — the sentinel-safety invariant for mid-admission rows.

    Returns (state', first-token candidates (G,), their logprobs (G,),
    lengths', last', (toks (R, B, S), lps (R, B, S), counts (R, B)),
    `_assign_out`) with S = n_drafts + 1; n_rounds == 0 (no live decode
    slot) skips the decode half and returns R = 0 outputs. Each slot's
    last token stays in `state'["last"]` besides: a decode row's `last'`,
    and the first token of an admission the step completes.
    """
    (stop_len, gid_b, aid_b, draft_limit, samp_rows_b,
     slot_ids_d) = _unpack_rows(rows)
    lengths, tables, last_token, live, key = _unpack_patch(
        patch, rng, state["last"], slot_ids_d)
    g = _unpack_group(group, chunk_w, tables.shape[1])
    chunk, widths, g_lens, g_tables, sample_at = (
        g["chunk"], g["widths"], g["g_lens"], g["g_tables"],
        g["sample_at"])
    rng_p, rng_d = jax.random.split(key)
    plogits = dlogits = None
    if _walks_once(cfg, chunk.size + lengths.size, n_rounds, n_drafts,
                   draft_cfg, lora):
        (plogits, dlogits), cache = paged_engine.forward_sets(
            params, cfg, _make_cache(state["pools"], g_lens, g_tables),
            [paged_engine.RowSet(
                chunk, g_lens, g_tables, widths, sample_at, "prefill_group",
                g["slot_ids"] if cfg.ssm_heads else None),
             paged_engine.RowSet(
                 last_token[:, None], lengths, tables, None,
                 jnp.zeros_like(lengths), "decode_rounds",
                 _state_slots(cfg, state, jnp.arange(lengths.shape[0])
                              if slot_ids_d is None else slot_ids_d, live))],
            mesh=mesh)
        state = {**state, "pools": _split_cache(cache)}
    state, ptoks, plps = _prefill_core(
        params, state, chunk, g_lens, g_tables, sample_at, g["slot_ids"],
        g["prompt_rows"], g["prompt_lens"], rng_p, g["samp_rows"],
        g["orig_lens"], g["count_mask"], g["gid"], g["gstate0"], grammar,
        lora, g["aid"], draft_params, widths, g["scatter_mask"], plogits,
        cfg=cfg, infer_cfg=infer_cfg, scatter_prompt=scatter_prompt,
        mesh=mesh, draft_cfg=draft_cfg, use_rows=use_rows_p,
        use_bias=use_bias_p)
    s = n_drafts + 1
    if n_rounds == 0:
        b = lengths.shape[0]
        out = (jnp.zeros((0, b, s), jnp.int32),
               jnp.zeros((0, b, s), jnp.float32),
               jnp.zeros((0, b), jnp.int32))
        return (state, ptoks, plps, lengths, last_token, out,
                _assign_out(state))
    if n_drafts > 0:
        state, lengths, last, out = _spec_core(
            params, state, lengths, tables, last_token, live, stop_len,
            rng_d, samp_rows_b, gid_b, grammar, lora, aid_b,
            draft_params, slot_ids_d, draft_limit,
            cfg=cfg, infer_cfg=infer_cfg, n_rounds=n_rounds,
            n_drafts=n_drafts, mesh=mesh, draft_cfg=draft_cfg,
            use_rows=use_rows_d, use_bias=use_bias_d)
    else:
        state, lengths, last, (dtoks, dlps, dcnts) = _decode_plain_core(
            params, state, lengths, tables, last_token, live, rng_d,
            samp_rows_b, gid_b, grammar, lora, aid_b, slot_ids_d, dlogits,
            cfg=cfg, infer_cfg=infer_cfg, n_rounds=n_rounds, mesh=mesh,
            use_rows=use_rows_d, use_bias=use_bias_d)
        out = (dtoks[:, :, None], dlps[:, :, None], dcnts)
    return state, ptoks, plps, lengths, last, out, _assign_out(state)


# ---------------------------------------------------------------------------
# Host-side scheduler
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class _Slot:
    req: Request
    prompt: list[int]           # admission prompt (original + any tokens
    #                             generated before a preemption)
    pages: list[int]            # chain so far, shared prefix first
    shared_len: int
    stop_len: int               # prompt + max_new (absolute positions)
    admit_seq: int = 0          # admission order — preemption picks max


@dataclasses.dataclass
class _AdmitJob:
    """One slot's chunked admission, with token-granular progress:
    `done` advances by whatever width the token budget granted its
    chunk, so admissions stay individually preemptible and there is no
    fixed chunk schedule."""

    slot: int
    rows: np.ndarray               # (rem_len,) the tokens to prefill
    rem_len: int
    base_len: int                  # the slot's shared_len
    prompt_row: np.ndarray         # (prompt_len,) the whole prompt
    prompt_len: int
    tok: int = 0                   # captured first-token candidate
    lp: float = 0.0
    got: bool = False              # sample captured yet
    done: int = 0                  # remainder tokens prefilled (committed)
    # remainder tokens DISPATCHED (committed done + whatever the
    # in-flight dispatch carries). The planner selects chunks from this
    # cursor so a plan never re-prefills tokens already in flight;
    # `done` catches up at each commit, and the two are equal whenever
    # nothing is in flight.
    planned: int = 0


@dataclasses.dataclass
class _Plan:
    """An immutable-by-convention PLANNED iteration:
    everything the launch needs, built against the planned frame while
    the previous dispatch runs. The only fields `_launch_plan` rewrites
    are the data-dependent decode inputs (d_lens / d_last / d_tables /
    live_g — a handful of (rows,) gathers): from the planned `frame`
    where the launch goes ahead of the commit (`waits` is None), from
    the just-committed ledger where it has to wait for it; every policy
    decision and every other array is frozen here."""

    kind: str                       # "mixed" | "decode"
    sel: list                       # [(job, take, d0)] — empty for decode
    activating: list                # slot ids whose admission completes
    n_rounds: int
    win: int                        # g_iter + 1
    g_iter: int
    spec_lens: list | None
    live_ids: np.ndarray
    sl_d: np.ndarray | None
    live_g: np.ndarray
    d_lens: np.ndarray
    d_tables: np.ndarray
    d_last: np.ndarray
    rows: object                    # the decode rows, packed (_pack_rows)
    owners: list                    # _Slot per live row (identity guard)
    pf: dict | None                 # prefill half (mixed only)
    chunk_w: int                    # its chunk's bucket (_chunk_bucket)
    scatter_prompt: bool
    use_rows_p: bool
    use_bias_p: bool
    use_rows_d: bool
    use_bias_d: bool
    use_grammar: bool
    use_lora: bool
    stats: dict
    spans: list
    # why this launch has to follow the commit of the dispatch in flight
    # when it was planned (`_launch_waits`: "fill", "drafts", "grammar",
    # "handoff"); None: it goes ahead of that commit, from `frame`, the
    # per-slot (lengths, live, last token on the device) that dispatch
    # leaves, which the planned frame knows exactly
    waits: "str | None" = "fill"
    frame: "tuple | None" = None


@dataclasses.dataclass
class _Inflight:
    """One launched-but-uncommitted dispatch: the
    device futures plus exactly the host context `_commit_inflight`
    needs to scatter the synced results back — and the deterministic
    effects (`activating`, per-row upper bounds via n_rounds*win) the
    NEXT plan's frame is built from."""

    kind: str
    futures: tuple
    sel: list
    activating: list
    live_ids: np.ndarray
    owners: list
    n_rounds: int
    win: int
    g_iter: int
    spec_lens: list | None
    stats: dict
    spans: list
    t_launch: float


class PagedInferenceServer:
    """Continuous-batching server over the paged KV cache.

    The client API is submit / generate / step / start / stop /
    run_until_idle, from any thread; the module docstring says what
    happens inside.
    """

    def __init__(self, params, cfg: ModelConfig, infer_cfg: InferConfig, *,
                 max_slots: int = 8, max_context: int = 1024,
                 page_size: int = 128, num_pages: int | None = None,
                 prompt_buckets: Sequence[int] | None = None,
                 decode_chunk: int = 8, spec_drafts: int = 0,
                 prefill_chunk: int = 256, seed: int = 0,
                 mesh=None, tp_axis: str = "tp",
                 allocation: str = "ondemand",
                 draft_params=None, draft_cfg: ModelConfig | None = None,
                 tokenizer=None, max_pending: int | None = None,
                 mixed_token_budget: int | None = None,
                 metrics: ServingMetrics | None = None,
                 flight_recorder_size: int | None = None,
                 qos=None, tracing=None, slo=None, spec_control=None,
                 iteration_profile=None, faults=None, brownout=None,
                 anomaly=None):
        from cloud_server_tpu.models.quantization import QTensor
        target = jnp.dtype(cfg.dtype)

        def cast_leaf(w):
            if isinstance(w, QTensor):
                return w
            if getattr(w, "dtype", None) == jnp.float32 and w.ndim >= 1:
                return w.astype(target)
            return w

        self.params = jax.tree.map(
            cast_leaf, params, is_leaf=lambda x: isinstance(x, QTensor))
        self.cfg = cfg
        self.infer_cfg = infer_cfg
        self.max_slots = max_slots
        self.page_size = page_size
        self.spec_drafts = spec_drafts
        self.decode_chunk = max(1, decode_chunk)
        self.window = spec_drafts + 1  # kv slack per decode round
        if max_context % page_size:
            raise ValueError(f"{max_context=} must be a multiple of "
                             f"{page_size=}")
        if (cfg.decode_attention_impl == "pallas"
                and jax.default_backend() == "tpu" and page_size % 128):
            # fail at construction, not at the first dispatch — the TPU
            # kernel's manual-DMA slices tile the minor dim by 128
            raise ValueError(
                f"page_size={page_size} must be a multiple of 128 for the "
                "pallas decode path on TPU")
        self.max_context = max_context
        self.max_pages_per_slot = max_context // page_size
        if num_pages is None:
            # default: the same HBM the contiguous layout would reserve
            num_pages = max_slots * self.max_pages_per_slot
        self.allocator = BlockAllocator(num_pages, page_size)
        self.prefill_chunk = max(page_size, min(prefill_chunk, max_context))
        if self.prefill_chunk % page_size:
            raise ValueError("prefill_chunk must be a page multiple")
        if (cfg.decode_attention_impl == "pallas"
                and self.prefill_chunk > paged_engine.max_window(cfg)):
            # the widest window this server dispatches; refuse here
            # rather than at the first long prompt's trace
            raise ValueError(
                f"prefill_chunk={self.prefill_chunk} exceeds the pallas "
                f"paged-attention window cap ({paged_engine.max_window(cfg)})"
                "; lower prefill_chunk (and page_size with it) or use "
                "decode_attention_impl='xla'")
        if prompt_buckets is None:
            prompt_buckets = _pow2_buckets(16, max_context)
        self.prompt_buckets = sorted(prompt_buckets)
        # continuations (preempted requests re-admitted with their
        # generated tokens appended) can exceed the client-facing
        # buckets, so admission sizing always has max_context available
        self._admit_buckets = sorted(set(self.prompt_buckets)
                                     | {max_context})
        # remainders bucket to a pow2 <= prefill_chunk (single-chunk jobs)
        # or a prefill_chunk multiple (multi-chunk jobs) — chunk WIDTHS
        # stay a small fixed set, chunk COUNTS are host-side loops
        self._rem_buckets = _pow2_buckets(16, self.prefill_chunk)

        # Tensor-parallel serving: the XLA side needs only the params'
        # NamedShardings (jit propagates). The mesh is kept for two
        # things — sharding the page pools on their kv-head axis so the
        # layout is intentional rather than inferred, and running the
        # pallas kernel under shard_map (it cannot be auto-partitioned).
        self.mesh = mesh
        self.tp_axis = tp_axis
        tp = 1 if mesh is None else int(mesh.shape.get(tp_axis, 1))
        if tp > 1 and cfg.num_kv_heads % tp:
            raise ValueError(
                f"tp={tp} must divide num_kv_heads={cfg.num_kv_heads} "
                "for tensor-parallel paged serving")

        # In-server draft-model speculation: a second (small) model
        # drafts against its OWN paged pools, indexed by the SAME page
        # tables/chains — one allocator covers both, and shared prefix
        # pages carry draft kv alongside the target's.
        if (draft_params is None) != (draft_cfg is None):
            raise ValueError("pass draft_params and draft_cfg together")
        if draft_cfg is not None and spec_drafts <= 0:
            raise ValueError("a draft model needs spec_drafts > 0")
        if draft_cfg is not None and draft_cfg.vocab_size != cfg.vocab_size:
            # fail at construction — a mismatch otherwise only explodes
            # (shape error in the accept rule) at the first speculative
            # dispatch, taking every in-flight request with it
            raise ValueError(
                f"draft vocab_size={draft_cfg.vocab_size} != target "
                f"vocab_size={cfg.vocab_size}; speculative verification "
                "compares their token distributions elementwise")
        if cfg.latent_dim and (draft_cfg is not None or mesh is not None):
            raise ValueError(
                "a model with latent attention (LongCat-Flash's double "
                "layer) is served without a draft model, whose pools hold "
                "keys and values by the target's tables, and without a "
                "mesh, which shards pools over key heads it does not have")
        if cfg.num_dense_layers and mesh is not None:
            raise ValueError(
                "a model with leading dense layers has its parameters in "
                "two stacks of layers, and serving under a mesh places "
                "one: not supported for such a model")
        if cfg.ssm_heads and (spec_drafts > 0 or mesh is not None):
            raise ValueError(
                "a model with a recurrent state a slot is served without "
                "speculative decoding (spec_drafts, a draft model), which "
                "verifies a window and keeps part of it where a state has "
                "no roll-back, and without a mesh, over which nothing "
                "shards the states")
        self.draft_cfg = draft_cfg
        self.draft_params = (None if draft_params is None else jax.tree.map(
            cast_leaf, draft_params,
            is_leaf=lambda x: isinstance(x, QTensor)))

        # A model with window layers has a second pool, for them. The
        # program sizes it from what it knows, at the most pages every
        # slot can hold at once, so it takes no option and can never be
        # the pool that forces a preemption; `num_pages` stays the full
        # kind's. A model of one kind has one pool and one table.
        self.window_pool: WindowPagePool | None = None
        self.window_pages_per_slot = 0
        if cfg.has_window_layers:
            if draft_cfg is not None:
                raise ValueError(
                    "draft-model speculation shares the target's page "
                    "tables, which hold one kind of page for the draft; "
                    "a target with window layers takes n-gram speculation "
                    "(spec_drafts without a draft model)")
            # two dispatches' writes: the one in flight and the one
            # planned behind it
            self.window_pages_per_slot = paged_engine.window_pages_per_slot(
                cfg.sliding_window, page_size,
                2 * max(self.prefill_chunk,
                        self.decode_chunk * self.window),
                self.max_pages_per_slot)
            self.window_pool = WindowPagePool(
                max_slots * self.window_pages_per_slot)
        # what the prefix cache cannot vouch for: the window kind's pages,
        # or a state for which it holds no snapshot. Such a model's slots
        # key and share nothing, so every prompt starts at position 0
        self._keys_nothing = (self.window_pool is not None
                              or bool(cfg.ssm_heads))
        # the tables' width and their "no page": a table per kind side
        # by side (`PagedKVCache.tables`), one id past both pools
        kinds = 2 if self.window_pool is not None else 1
        self._table_cols = kinds * self.max_pages_per_slot
        self._no_page = max(num_pages, 0 if self.window_pool is None
                            else self.window_pool.num_pages)
        # per slot the logical pages [lo, hi) of the window kind it holds
        self._win_lo = np.zeros((max_slots,), np.int64)
        self._win_hi = np.zeros((max_slots,), np.int64)
        self.window_pages_peak_slot = 0
        self._keys_stage: dict = {}
        # the running assignment counts last read back (`_count_assign`)
        self._assign_names = paged_engine.assign_names(cfg)
        self._assign_seen = np.zeros((len(self._assign_names),), np.uint32)
        self._assign_last = dict.fromkeys(self._assign_names, 0)
        cache = paged_engine.init_paged_cache(
            cfg, num_pages=num_pages, page_size=page_size, batch=max_slots,
            max_pages_per_slot=self.max_pages_per_slot,
            window_num_pages=(None if self.window_pool is None
                              else self.window_pool.num_pages))
        self.ssm_state_bytes = paged_engine.state_bytes(cache)
        # per-request sampling penalty state ("prompt_mask" /
        # "out_counts", (B, V) per slot) is NOT allocated here — the
        # first admission that needs penalties materializes it
        # (_ensure_penalty_state), so penalty-free serving never pays
        # its HBM or scatter cost
        self.state = {
            "pools": _split_cache(cache),
            "hist": jnp.zeros((max_slots, max_context), jnp.int32),
            # per-slot grammar DFA state (constrained decoding); slots
            # without a grammar sit at state 0 of the identity grammar
            "gstate": jnp.zeros((max_slots,), jnp.int32),
            # per-slot last token, as the newest program left it: what
            # a launch made ahead of a commit reads (`_unpack_patch`)
            "last": jnp.zeros((max_slots,), jnp.int32),
        }
        if draft_cfg is not None:
            dcache = paged_engine.init_paged_cache(
                draft_cfg, num_pages=num_pages, page_size=page_size,
                batch=max_slots,
                max_pages_per_slot=self.max_pages_per_slot)
            self.state["draft_pools"] = _split_cache(dcache)
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P
            ax = tp_axis if tp > 1 else None
            if (tp > 1 and draft_cfg is not None
                    and draft_cfg.num_kv_heads % tp):
                raise ValueError(
                    f"tp={tp} must divide the draft model's num_kv_heads="
                    f"{draft_cfg.num_kv_heads} too")

            def put(x, spec):
                return jax.device_put(x, NamedSharding(mesh, spec))

            def shard_pools(pools):
                return {
                    name: put(pool,
                              P(None, None, ax, None, None)
                              if pool.ndim == 5 else P(None, None, ax, None))
                    for name, pool in pools.items()}

            self.state = {
                name: (shard_pools(val) if name.endswith("pools")
                       else put(val, P()))
                for name, val in self.state.items()}
        # host-authoritative scheduling state
        self.tables = np.full((max_slots, self._table_cols),
                              self._no_page, np.int32)
        self.lengths = np.zeros((max_slots,), np.int32)
        self.active = np.zeros((max_slots,), bool)
        self.last_token = np.zeros((max_slots,), np.int32)
        self.stop_len = np.zeros((max_slots,), np.int32)
        # per-slot sampling parameter rows (numpy, set at admission) and
        # which slots actually need the device rows path
        self.samp_rows = make_rows([None] * max_slots, infer_cfg,
                                   [0] * max_slots)
        self._needs_rows = np.zeros((max_slots,), bool)
        self._has_bias = np.zeros((max_slots,), bool)
        # regex-constrained decoding: registry of compiled token-DFAs
        # stacked into one device table; per-slot grammar id + the DFA
        # state to resume from at (re-)admission
        self.tokenizer = tokenizer
        # multi-LoRA serving: stacked adapter set + per-slot adapter ids
        from cloud_server_tpu.inference.multi_lora import AdapterSet
        self.adapters = AdapterSet(cfg, mesh=mesh)
        self._aid = np.zeros((max_slots,), np.int32)
        self._grammar_cache = None  # lazy GrammarCache
        self._patterns: list[str] = []
        self._pattern_gid: dict[str, int] = {}
        self._grammar_dev = None  # (tables (Gn,S,V) i32, accept (Gn,S))
        self._gid = np.zeros((max_slots,), np.int32)
        self._gstate0 = np.zeros((max_slots,), np.int32)
        self.orig_len = np.zeros((max_slots,), np.int32)
        self._host_rng = np.random.default_rng(seed)

        # Page-allocation policy:
        #   "ondemand" (default) — admission reserves only the prompt +
        #     one decode window; decode dispatches extend each live
        #     slot's chain just-in-time. On pool exhaustion the YOUNGEST
        #     slot is preempted: its pages release into the radix cache
        #     (content-keyed, fully written — valid KV), its request
        #     requeues as a continuation (prompt + generated so far),
        #     and re-admission re-prefills almost entirely from cache.
        #     Worst-case max_new headroom is never parked, so sustained
        #     concurrency is higher at equal HBM.
        #   "reserve" — the r3 behavior: the whole chain (prompt +
        #     max_new + window slack) reserved at admission; no
        #     mid-flight preemption, lower host bookkeeping.
        if allocation not in ("ondemand", "reserve"):
            raise ValueError(f"unknown allocation policy: {allocation!r}")
        self.allocation = allocation

        # speculative-efficiency counters: committed tokens per model
        # round (mean accepted length + 1); plain decode reports ~1.0
        self.decode_rounds = 0
        self.decode_tokens_committed = 0
        # speculation accounting: tokens drafted on committing rows'
        # behalf (each row's own draft length per round) vs the drafts
        # that actually committed — the wasted-work ledger the adaptive
        # controller and the per-tenant QoS counters read from
        self.spec_tokens_drafted = 0
        self.spec_tokens_accepted = 0
        # adaptive draft-length control (inference/spec_control.py):
        # host-side, fed by the per-round counts the scheduler syncs
        # anyway — zero extra dispatches or syncs (regression-tested).
        # None = fixed spec_drafts length (spec_control=False / "off",
        # or no speculation at all)
        self.spec_control = resolve_controller(
            spec_control, infer_cfg.spec_control_config, spec_drafts,
            has_draft_model=draft_cfg is not None)
        self.tokens_emitted = 0  # lifetime emitted tokens (bench/metrics)
        self.preemptions = 0
        self._admit_seq = 0
        # request-lifecycle telemetry (histograms + counters, observed
        # at host moments the scheduler already owns — zero extra syncs,
        # guarded by tests/test_observability.py's dispatch-count test)
        self.metrics = metrics if metrics is not None else ServingMetrics()
        self.metrics.registry.add_collector(self._collect_metrics)
        self.tracer = _StepTracer()  # /debug/trace on-demand profiling
        # scheduler flight recorder: one record per busy iteration
        # (token-budget utilization, prefill/decode split, occupancy,
        # compaction, preemptions) for post-mortem churn debugging
        fr_size = (flight_recorder_size if flight_recorder_size is not None
                   else infer_cfg.flight_recorder_size)
        self.flight = FlightRecorder(fr_size)
        self._iter_stats: dict = {}
        # iteration-phase profiler (inference/iteration_profile.py):
        # per-phase host-gap attribution of every busy iteration —
        # pure host-side clock marks at boundaries the scheduler
        # already crosses, zero extra dispatches/syncs (the
        # dispatch-count regression test runs a profiling-enabled
        # clone, and the clock-read count per mixed iteration is
        # asserted constant). None (iteration_profile=False / config
        # off) keeps the exact pre-profiler two-read clock behavior.
        from cloud_server_tpu.inference.iteration_profile import (
            register_phase_hists, resolve_profiler)
        from cloud_server_tpu.utils.tracing import annotate
        self._profiler = resolve_profiler(iteration_profile,
                                          infer_cfg.iteration_profile,
                                          annotate)
        # eager per-phase histogram registration: the families exist
        # (and the docs drift check sees them) before any traffic, and
        # the per-iteration observe path is a dict lookup, not a
        # registry get-or-create
        self._phase_hists = ({} if self._profiler is None else
                             register_phase_hists(self.metrics.registry))
        # cache/memory observability (inference/cache_telemetry.py):
        # the allocator's ledger gets the registry's fixed-ladder
        # histogram families (chain depth per walk, page age at
        # eviction, per-iteration evictable fraction) — eager
        # registration, same rationale as the phase histograms; the
        # observe paths are a dict lookup + Histogram.observe, zero
        # dispatches/syncs (the dispatch-count clone covers a
        # QoS+cache-telemetry server)
        from cloud_server_tpu.inference.cache_telemetry import (
            register_cache_hists)
        self._cache_hists = register_cache_hists(self.metrics.registry)
        self.allocator.telemetry.attach_hists(self._cache_hists)
        # idle-iteration visibility: a dead scheduler and an idle one
        # must not look identical from /stats — an idle one keeps
        # incrementing idle_iterations while last_busy_ts ages, a dead
        # one freezes both. Plain int/float writes on the scheduler
        # thread; mirrored on the scrape path only.
        self.idle_iterations = 0
        self.last_busy_ts = 0.0
        # per-request distributed tracing + per-class SLO tracking
        # (inference/request_trace.py, inference/slo.py): both None
        # unless configured — every guarded call site short-circuits,
        # so the scheduler is byte-identical to the pre-trace build.
        # All span timestamps reuse the iteration t0/now pair the
        # flight recorder already reads: zero extra dispatches/syncs
        # (the dispatch-count regression test covers a tracing+SLO
        # clone at 100% sampling).
        from cloud_server_tpu.inference.request_trace import (
            resolve_recorder)
        from cloud_server_tpu.inference.slo import resolve_slo
        self.trace_recorder = resolve_recorder(
            tracing, infer_cfg.trace_sample_rate,
            capacity=infer_cfg.trace_capacity,
            tail_capacity=infer_cfg.trace_tail_capacity)
        self.slo = resolve_slo(slo, infer_cfg.slo_config)
        if self.slo is not None:
            self.metrics.slo = self.slo
        # anomaly watchdog (inference/anomaly.py): online rule engine
        # fed from host state the scheduler already owns — the
        # per-iteration feed is caller-passed clocks and int deltas,
        # zero extra dispatches/syncs (the dispatch-count regression
        # test covers an armed watchdog + tail-retention clone). None
        # unless configured; every call site short-circuits.
        from cloud_server_tpu.inference.anomaly import resolve_anomaly
        self._anomaly = resolve_anomaly(anomaly, infer_cfg.anomaly_config)
        if self._anomaly is not None:
            self._anomaly.bind_slo(self.slo)
        # one-shot forensic debug bundles: bounded ring of auto-captured
        # JSON artifacts (bundle_on_anomaly), plus GET /debug/bundle
        self._bundle_on_anomaly = bool(infer_cfg.bundle_on_anomaly)
        self._bundles: collections.deque = collections.deque(maxlen=8)
        self._bundles_captured = 0
        # per-iteration prefix-cache delta baseline for the watchdog's
        # cache-collapse signal (lifetime counters diffed on the
        # scheduler thread; plain int reads)
        self._anomaly_cache_base = (0, 0)
        # iteration-granular spans staged by the dispatch paths and
        # stamped with the shared (t0, now, iteration) frame by
        # _record_iteration — one list append per traced participant
        self._iter_spans: list = []

        self._slots: list[_Slot | None] = [None] * max_slots
        self._jobs: list[_AdmitJob] = []
        self._pending: collections.deque[Request] = collections.deque()
        # backpressure: submit() past this bound raises QueueFullError
        # (HTTP 429) instead of growing host memory without limit;
        # None = unbounded (library use, trusted callers)
        self.max_pending = max_pending
        # multi-tenant QoS (inference/qos.py): a TenantRegistry, a
        # config dict / JSON string / file path, or None (falls back to
        # InferConfig.qos_config). None disables QoS entirely — every
        # guarded call site below short-circuits, so the scheduler is
        # byte-identical to the pre-QoS FIFO/youngest-preemption paths
        # (pinned by tests/test_mixed_scheduler.py and test_qos.py's
        # single-tenant parity test). All QoS decisions run on host
        # state the scheduler already owns: zero extra dispatches or
        # host syncs (the dispatch-count regression tests cover a
        # QoS-enabled server too).
        from cloud_server_tpu.inference.qos import resolve_registry
        self.qos = resolve_registry(qos, infer_cfg.qos_config)
        # failure-domain layer (inference/faults.py): deterministic
        # fault injection + overload brownout. Both None unless
        # configured — every guarded call site short-circuits, so the
        # scheduler is byte-identical to the pre-fault build (the
        # dispatch/device_get-count regression clones pin it, incl. a
        # clone with a never-firing plan + brownout armed).
        from cloud_server_tpu.inference.faults import (resolve_brownout,
                                                       resolve_fault_plan)
        self._faults = resolve_fault_plan(faults, infer_cfg.fault_plan)
        self._brownout = resolve_brownout(brownout,
                                          infer_cfg.brownout_config)
        if self._brownout is not None and self.qos is None:
            raise ValueError(
                "brownout needs a QoS registry: shed sets are priority "
                "classes, and without tenants nothing can be shed")
        # live request migration (inference/migration.py): the ledger
        # is always present — its record hooks are int adds under a
        # leaf lock, and the migration counter families must exist
        # (zeros) for the docs drift check whether or not a migration
        # ever runs
        from cloud_server_tpu.inference.migration import MigrationLedger
        self._migration = MigrationLedger()
        # _fail_all teardown accounting: how many times the bounded
        # _step_lock acquire timed out and teardown proceeded
        # UNSERIALIZED against a wedged scheduler (the
        # cloud_server_unserialized_teardown_total counter; the
        # timeout is an attribute so the wedged-step test does not
        # wait out the production default)
        self.unserialized_teardowns = 0
        self._teardown_lock_timeout_s = 5.0
        # how long stop() waits for the scheduler thread to leave its
        # step. A step that meets a dispatch shape no earlier step
        # drove traces and compiles it: 15 to 30 s and more at serving
        # depth on the chip. A thread given up on inside such a step
        # ends it later all the same and writes `self.state` back, so
        # the pools outlive a caller that dropped them after stop()
        # (PERF.md, PR 41: a check that then found no room)
        self._scheduler_join_timeout_s = 120.0
        self._draining = False
        # stall-free token-budget batching: every iteration fuses all
        # live decode rows and as many prefill-chunk tokens as fit under
        # `mixed_token_budget` into ONE program, so decodes never stall
        # behind a prefill dispatch and admissions never wait out a
        # decode dispatch
        budget = (mixed_token_budget if mixed_token_budget is not None
                  else infer_cfg.mixed_token_budget)
        if budget is None or budget <= 0:
            # auto: effectively work-conserving — a full decode burst
            # plus a full chunk for every slot fits, so the budget only
            # bites when set explicitly. Lower it to trade admission
            # speed for a per-iteration latency (ITL) bound.
            budget = max_slots * (self.window * self.decode_chunk
                                  + self.prefill_chunk)
        if budget < self.window:
            raise ValueError(
                f"mixed_token_budget={budget} cannot fit one decode "
                f"window ({self.window} tokens)")
        self.mixed_token_budget = int(budget)
        # dispatch-width buckets for the mixed path (compile-cache bound)
        self._mixed_buckets = sorted(
            set(_pow2_buckets(16, self.prefill_chunk))
            | {_pad_pow2(self.window)})
        self._lock = threading.Lock()
        # submit() notifies this condition (same mutex as _lock) so
        # an idle serve_forever parks in a bounded wait instead of
        # busy-polling — new work wakes it immediately (cancel needs
        # no notify: an idle-waiting scheduler implies nothing left
        # to cancel); stop() notifies for prompt shutdown
        self._work = threading.Condition(self._lock)
        self._step_lock = threading.Lock()
        # the launched-but-uncommitted dispatch (the module docstring's
        # pipeline section); None: the next step fills the pipeline
        self._inflight: _Inflight | None = None
        # the dispatch launched AHEAD of `_inflight`'s commit, inside
        # one steady step: `_commit_inflight` moves it up, so outside a
        # step at most one dispatch is uncommitted, as ever
        self._ahead: _Inflight | None = None
        # deferred sweep reaps: (slot_id, _Slot, reason) marked while a
        # dispatch is in flight; released right after its commit
        self._reaped: list[tuple[int, _Slot, str]] = []
        # deferred delivery: what a commit would have woken somebody
        # with, in the order it would have run — (req, token) for a
        # stream call, (req, None) for the request's completion.
        # `_deliver` runs it after the step's launch
        self._deliveries: list[tuple[Request, int | None]] = []
        # disaggregated prefill/decode handoff (the ReplicatedRouter's
        # role-specialized fleets): requests whose chunked prefill
        # completed THIS iteration and that carry a submit-time
        # `handoff=` callback queue here; step() fires the callbacks
        # AFTER releasing _step_lock (the callback typically enqueues a
        # migrate_export, which needs that lock). Scheduler-thread-only
        # state — appended under _step_lock, drained on the same thread
        # right after it is released.
        self._handoff_ready: list[Request] = []
        # request_id -> (page_ids, device gathers with their host
        # copies already started): KV prefetched by _handoff_prefetch
        # BEFORE the final prefill chunk's dispatch (donation
        # invalidates the pools after launch), consumed by
        # _export_request_locked so the handoff export pays only the
        # pages the last chunks wrote. Popped on export or request
        # completion, whichever comes first.
        self._handoff_stash: dict[str, tuple[tuple[int, ...], dict]] = {}
        # perf_counter stamp of the launch performed THIS iteration
        # (consumed by _record_iteration into the flight record's
        # t_launch — the Perfetto inflight track's left edge)
        self._iter_launch_ts: float | None = None
        # the one key every program draws from, made once: a dispatch
        # carries its count (`_next_dispatch`) and the program folds it
        # in (`_unpack_patch`), so no key is ever made between programs
        self._rng = jax.random.key(seed)
        self._dispatches = 0
        # host arrays handed to the device (`_to_device`), and what the
        # launch performed THIS iteration added: the flight record's
        # `launch_h2d`, beside its `launch` phase. What the plan built
        # THIS iteration staged, and how long that took inside `build`:
        # the record's `plan_h2d` and `stage_ms`
        self._h2d = 0
        self._iter_launch_h2d = 0
        self._iter_plan_h2d = 0
        self._iter_stage_ms: float | None = None
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None

    # -- client API ---------------------------------------------------------

    def submit(self, prompt: Sequence[int], *,
               max_new_tokens: int | None = None, stream=None,
               sampling: SamplingParams | None = None,
               adapter: str | None = None,
               tenant: str | None = None,
               trace_ctx: tuple | None = None,
               deadline_s: float | None = None,
               fail_handler=None, handoff=None,
               _migration=None) -> Request:
        if self._stop.is_set():
            raise RuntimeError("server is stopped; not accepting requests")
        if self._faults is not None:
            self._faults.check("submit_reject")
        if deadline_s is not None and not (
                math.isfinite(deadline_s) and deadline_s > 0):
            # `not (x > 0)` rather than `x <= 0`: NaN compares False
            # BOTH ways and would otherwise slip through as a silent
            # never-expiring deadline
            raise ValueError("deadline_s must be a finite positive "
                             "number of seconds")
        if (adapter is not None
                and self.adapters.adapter_id(adapter) is None):
            raise ValueError(
                f"unknown adapter {adapter!r}; registered: "
                f"{self.adapters.names}")
        if len(prompt) == 0:
            raise ValueError("empty prompt")
        _bucket(len(prompt), self.prompt_buckets)  # raises if too long
        max_new = (self.infer_cfg.max_decode_len if max_new_tokens is None
                   else max_new_tokens)
        # leave room for the last speculative window's writes
        max_new = min(max_new, self.max_context - len(prompt) - self.window)
        if max_new <= 0:
            raise ValueError(
                f"prompt of {len(prompt)} tokens leaves no room to decode "
                f"within max_context={self.max_context}")
        if sampling is not None and sampling.regex is not None:
            if self.infer_cfg.eos_token_id < 0:
                raise ValueError(
                    "regex-constrained decoding needs eos_token_id >= 0 "
                    "(completion is signalled by EOS at an accepting "
                    "state)")
            self._grammar_gid(sampling.regex)  # compile now; 400 here
        if self.qos is not None:
            tenant = self.qos.resolve(tenant)
            if self._brownout is not None and _migration is None:
                # overload brownout: shed this class's admissions with
                # (migration continuations are exempt: the stream's
                # tokens are already paid for and delivered — shedding
                # one loses strictly more work than it saves)
                # a jittered Retry-After (429) while the detector
                # grades the replica overloaded — interactive traffic
                # keeps its SLO instead of every class degrading
                cls = self.qos.priority_class(tenant)
                if self._brownout.shed(cls):
                    from cloud_server_tpu.inference.faults import (
                        BrownoutShedError)
                    raise BrownoutShedError(
                        f"overloaded: shedding {cls!r} admissions "
                        "(brownout); retry later", tenant=tenant,
                        priority_class=cls,
                        retry_after_s=self._brownout.retry_hint())
        else:
            # no registry = no frozen tenant set to bound cardinality:
            # a caller-supplied string must not mint per-tenant labeled
            # metric series (observe_emit labels by req.tenant)
            tenant = None
        req = Request(prompt=list(prompt), max_new_tokens=max_new,
                      stream=stream, sampling=sampling, adapter=adapter,
                      tenant=tenant,
                      seed_used=(_migration.seed_used
                                 if _migration is not None else
                                 resolve_seed(sampling, self._host_rng,
                                              self._lock)),
                      submit_time=time.perf_counter())
        if _migration is not None:
            # migration continuation (inference/migration.py): resume
            # another replica's stream. The generated state is filled
            # in BEFORE the append below makes the request visible to
            # the scheduler, which then admits it as a CONTINUATION
            # (admission prompt = prompt + tokens, the preemption-
            # resume path) and decode picks up at the exact next
            # token. seed_used above is the SOURCE's seed: RNG streams
            # are position-keyed, so seed + token index reproduces
            # every future draw exactly — no generator state crosses.
            req.tokens = list(_migration.tokens)
            req.logprobs = list(_migration.logprobs)
            req.emit_times = list(_migration.emit_times)
        if deadline_s is None and self.qos is not None:
            # per-QoS-class default deadline (None when the tenant's
            # class declares none)
            deadline_s = self.qos.default_deadline(tenant)
        if deadline_s is not None:
            req.deadline = req.submit_time + float(deadline_s)
        if self.slo is not None:
            # class mapping: the tenant's QoS priority class; plain
            # "default" without a registry
            req.slo_class = (self.qos.priority_class(tenant)
                             if self.qos is not None else None)
        # the router's failover hook rides in THROUGH submit (not
        # installed after it returns): once the request is in the
        # pending queue any scheduler crash may complete it, and a
        # hook landing late would miss its own failure
        req._fail_handler = fail_handler
        # disaggregated handoff callback (role-specialized fleets):
        # fired once, outside _step_lock, when this request's chunked
        # prefill completes with decode budget left — the router's
        # hook migrates it to a decode replica. Rides IN through
        # submit for the same reason fail_handler does.
        if handoff is not None:
            self._refuse_slot_state("the disaggregated hand-off (handoff=)")
        req._handoff = handoff
        req._on_cancel = self._handle_cancel  # before it can be seen
        with self._lock:
            # under the lock: drain() flips _draining under the same
            # lock, so a submit either lands before drain observes the
            # queue or is rejected — never appended-then-abandoned
            if self._draining:
                raise RuntimeError(
                    "server is draining; not accepting requests")
            if (self.max_pending is not None
                    and len(self._pending) >= self.max_pending):
                raise QueueFullError(
                    f"pending queue is full ({self.max_pending} requests);"
                    " retry later")
            if self.qos is not None:
                # per-tenant backpressure AFTER the global bound: one
                # tenant at its pending cap or out of prompt-bucket
                # budget 429s while every other tenant keeps admitting.
                # On failure nothing was mutated for this request; on
                # success the tenant's pending count advances atomically
                # with the append below. A migration continuation bills
                # ZERO prompt tokens: its prompt was already charged on
                # the source replica and its salvaged tokens were never
                # prompt tokens — re-billing would double-charge the
                # tenant fleet-wide for one request.
                self.qos.gate_submit(
                    tenant, len(prompt),
                    charge_tokens=0 if _migration is not None else None)
            # telemetry BEFORE the append: once the request is in the
            # queue the scheduler thread may admit (even finish) it, and
            # the timeline must stay in lifecycle order. The trace
            # opens here too — AFTER every rejection path above, so a
            # refused submit (queue full, tenant 429, draining) can
            # never leak into the recorder's live set, and before the
            # append, so the scheduler cannot finish the request ahead
            # of its trace existing.
            if self.trace_recorder is not None:
                tr = self.trace_recorder.begin(req, trace_ctx)
                if tr is not None and tenant is not None:
                    tr.annotate(tenant=tenant)
            req.record_event("submit", req.submit_time)
            self.metrics.observe_submit(req)
            self._pending.append(req)
            # wake an idle scheduler thread parked on the bounded
            # condition wait (serve_forever) — submit latency must not
            # pay the idle-wait timeout
            self._work.notify()
        return req

    def _handle_cancel(self, req: Request) -> None:
        """Client-thread half of Request.cancel(): a request still in
        the pending queue finishes here, immediately. One that is
        already admitted (slot or admission job) is reaped by the
        scheduler's sweep at the start of the next step()."""
        with self._lock:
            try:
                self._pending.remove(req)
            except ValueError:
                return  # admitted: the step sweep owns the teardown
            if self.qos is not None:
                self.qos.on_pending_removed(req.tenant)
        req.finish_reason = "cancelled"
        self._complete(req)

    def _complete(self, req: Request) -> None:
        """Terminal bookkeeping for any request leaving the server:
        observe lifecycle metrics, then unblock waiters. Every path
        that ends a request (finish / cancel / fail) goes through here
        so the telemetry can never miss a terminal state.

        Failure interception: a request completing with an "error:"
        reason is offered to its `_fail_handler` (installed by the
        ReplicatedRouter at submit) AFTER the telemetry — the failure
        really happened here — but BEFORE `_done`: a True return means
        a failover retry on another replica now owns completion, so
        waiters stay blocked until the retry finishes and mirrors its
        outcome back."""
        now = self.metrics.observe_finish(req)
        if self._anomaly is not None:
            ttft = (req.emit_times[0] - req.submit_time
                    if req.emit_times and req.submit_time is not None
                    else None)
            itl = (None if len(req.emit_times) < 2 else
                   (req.emit_times[-1] - req.emit_times[0])
                   / (len(req.emit_times) - 1))
            fired = self._anomaly.observe_request(
                now=now, ttft_s=ttft, itl_s=itl,
                finish_reason=req.finish_reason)
            if fired:
                self._on_anomaly(fired)
        # analysis: allow[lock-discipline] GIL-atomic dict pop: drop
        # any unconsumed handoff KV prefetch (the request ended
        # locally before the export fired) — safe from any completing
        # thread, no compound read-modify-write
        self._handoff_stash.pop(req.request_id, None)
        if self.trace_recorder is not None and (
                req.trace is not None or req.tail_trace is not None):
            slo_violated = False
            if req.trace is None and self.slo is not None:
                e2e = (None if req.submit_time is None
                       else now - req.submit_time)
                ttft = (req.emit_times[0] - req.submit_time
                        if req.emit_times and req.submit_time is not None
                        else None)
                slo_violated = (
                    (e2e is not None and self.slo.exceeds_target(
                        req.slo_class, "e2e", e2e))
                    or (ttft is not None and self.slo.exceeds_target(
                        req.slo_class, "ttft", ttft)))
            in_anomaly = (self._anomaly is not None
                          and req.trace is None
                          and self._anomaly.active_count(now) > 0)
            self.trace_recorder.finish(req, slo_violated=slo_violated,
                                       in_anomaly=in_anomaly)
        h = req._fail_handler
        if (h is not None and req.finish_reason is not None
                and req.finish_reason.startswith("error") and h(req)):
            return
        req._done.set()
        cb = req._on_done
        if cb is not None:
            cb(req)

    def generate(self, prompts, *, max_new_tokens=None):
        reqs = [self.submit(p, max_new_tokens=max_new_tokens)
                for p in prompts]
        self.run_until_idle()
        return [r.tokens for r in reqs]

    def embed(self, prompts: Sequence[Sequence[int]]) -> "np.ndarray":
        """Mean-pooled, L2-normalised sequence embeddings for the base
        model (engine.encode), padded per prompt bucket so repeat calls
        hit the jit cache. Runs under the scheduler lock — it shares
        the device with decode dispatches. Returns (N, embed_dim) f32."""
        from cloud_server_tpu.inference import engine as _engine
        if not prompts:
            return np.zeros((0, self.cfg.embed_dim), np.float32)
        out = np.zeros((len(prompts), self.cfg.embed_dim), np.float32)
        by_bucket: dict[int, list[int]] = {}
        for i, p in enumerate(prompts):
            if len(p) == 0:
                raise ValueError("empty prompt")
            by_bucket.setdefault(_bucket(len(p), self.prompt_buckets),
                                 []).append(i)
        with self._step_lock:
            for pb, idxs in by_bucket.items():
                g = _pad_pow2(len(idxs))  # bound compile cache by shape
                rows = np.full((g, pb), self.infer_cfg.pad_token_id,
                               np.int32)
                lens = np.ones((g,), np.int32)  # padding rows: 1 token
                for r, i in enumerate(idxs):
                    rows[r, :len(prompts[i])] = prompts[i]
                    lens[r] = len(prompts[i])
                vecs = _engine.encode(self.params, jnp.asarray(rows),
                                      jnp.asarray(lens), cfg=self.cfg)
                # analysis: allow[lock-discipline] deliberate sync under
                # _step_lock: embeddings share the device with decode
                # dispatches — serializing on the step lock is the point
                out[idxs] = np.asarray(jax.device_get(vecs))[:len(idxs)]
        return out

    @property
    def num_active(self) -> int:
        # analysis: allow[lock-discipline] racy-by-design monitoring
        # read: len-stable list, GIL-atomic element loads; staleness is
        # bounded by one iteration and only steers placement/idle checks
        return sum(s is not None for s in self._slots)

    @property
    def num_pending(self) -> int:
        with self._lock:
            return len(self._pending)

    @property
    def pending_prefill_tokens(self) -> int:
        """Prefill tokens this replica still owes: the unprefilled
        remainder of every in-flight admission job plus the full
        admission length of everything queued. The ReplicatedRouter's
        role-aware placement reads this as a PREFILL replica's load
        signal (a 4k-token prompt is not the same backlog as a
        4-token one, which request counts cannot see)."""
        # analysis: allow[lock-discipline] racy-by-design monitoring
        # read of _jobs (scheduler-thread state): list() snapshots the
        # container, element reads are GIL-atomic, staleness is bounded
        # by one iteration and only steers placement
        jobs = list(self._jobs)
        n = sum(max(job.rem_len - job.done, 0)
                for job in jobs)
        with self._lock:
            n += sum(len(r.prompt) + len(r.tokens)
                     for r in self._pending)
        return n

    def prefix_cache_stats(self):
        """Allocator snapshot (AllocatorStats). Called from the scrape
        path and the router WITHOUT the scheduler locks — audited
        under the lock-discipline passes (LD1–LD4): every field is
        derived from plain ints and `len()`s of containers the
        scheduler thread mutates under `_step_lock`; each read is
        GIL-atomic, so a snapshot can lag the running iteration by a
        few pages but can never tear a single value. Taking
        `_step_lock` here would stall every scrape behind a whole
        dispatch — the same racy-by-design monitoring trade `num_active`
        documents."""
        return self.allocator.stats()

    # -- internals ----------------------------------------------------------

    def _next_dispatch(self) -> int:
        """The count the next program folds into the server's key: the
        nth dispatch draws from n, whichever scheduler path launches
        it (wrapped into int32, the patch's dtype)."""
        self._dispatches = (self._dispatches + 1) & 0x7FFFFFFF
        return self._dispatches

    def _to_device(self, host_array):
        """One asynchronous host-to-device transfer, counted."""
        self._h2d += 1
        return jax.device_put(host_array)

    def _feed_patch(self, lengths, last_token, live, tables):
        """The next dispatch's packed patch (`_pack_patch`), on its way
        to the device: the one array a launch hands over."""
        return self._to_device(_pack_patch(
            self._next_dispatch(), lengths, last_token, live, tables))

    def add_adapter(self, name: str, lora_params: dict,
                    lora_cfg) -> int:
        """Register a LoRA adapter for per-request serving; returns its
        id. Requests select it via submit(..., adapter=name). Restacks
        the device tensors (one recompile of the dispatch shapes)."""
        if (self.cfg.num_experts >= 2 and
                {"w_gate", "w_up", "w_down"} & set(lora_cfg.targets)):
            raise ValueError(
                "MLP-targeting adapters cannot be served per-request on "
                "an MoE base (expert-stacked MLP); use attention targets "
                "or merged serving")
        with self._lock:
            return self.adapters.add(name, lora_params, lora_cfg)

    def _grammar_gid(self, pattern: str) -> int:
        """Register (compile + restack) a pattern; returns its grammar
        id. Called from submit() so compilation errors surface on the
        CLIENT thread as ValueError, never killing the scheduler."""
        # analysis: allow[lock-discipline] double-checked fast path: a
        # GIL-atomic dict probe; the locked re-check below is authoritative
        gid = self._pattern_gid.get(pattern)
        if gid is not None:
            return gid
        if self.tokenizer is None:
            raise ValueError(
                "regex-constrained requests need a tokenizer: construct "
                "PagedInferenceServer(..., tokenizer=...)")
        from cloud_server_tpu.inference import grammar as _g
        if self._grammar_cache is None:
            self._grammar_cache = _g.GrammarCache(self.tokenizer,
                                                  self.cfg.vocab_size)
        self._grammar_cache.get(pattern)  # compile (raises on bad regex)
        with self._lock:
            if pattern not in self._pattern_gid:
                self._patterns.append(pattern)
                self._pattern_gid[pattern] = len(self._patterns)
                self._rebuild_grammar_stack()
            return self._pattern_gid[pattern]

    def _rebuild_grammar_stack(self) -> None:
        """(Gn, S_max, V) device stack: gid 0 = the identity grammar
        (everything allowed, state stays 0), gid i = pattern i-1. Rows
        past a grammar's state count are DEAD (unreachable)."""
        from cloud_server_tpu.inference import grammar as _g
        dfas = [self._grammar_cache.get(pat) for pat in self._patterns]
        s_max = max([d.num_states for d in dfas] + [1])
        v = self.cfg.vocab_size
        tables = np.full((len(dfas) + 1, s_max, v), _g.DEAD, np.int32)
        accept = np.zeros((len(dfas) + 1, s_max), bool)
        tables[0] = 0
        accept[0] = True
        for i, d in enumerate(dfas, start=1):
            tables[i, :d.num_states] = d.next_state
            accept[i, :d.num_states] = d.accept
        tb, ac = jnp.asarray(tables), jnp.asarray(accept)
        if self.mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P
            tb = jax.device_put(tb, NamedSharding(self.mesh, P()))
            ac = jax.device_put(ac, NamedSharding(self.mesh, P()))
        self._grammar_dev = (tb, ac)

    def _ensure_penalty_state(self) -> None:
        """Materialize the (B, V) penalty buffers on first need (one-time
        recompile; pre-materialization slots carry neutral penalties,
        for which the buffers are read-irrelevant)."""
        if "prompt_mask" in self.state:
            return
        pm = jnp.zeros((self.max_slots, self.cfg.vocab_size), bool)
        oc = jnp.zeros((self.max_slots, self.cfg.vocab_size), jnp.int32)
        if self.mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P
            pm = jax.device_put(pm, NamedSharding(self.mesh, P()))
            oc = jax.device_put(oc, NamedSharding(self.mesh, P()))
        self.state["prompt_mask"] = pm
        self.state["out_counts"] = oc

    def _emit(self, req: Request, token: int, logprob: float) -> bool:
        n0 = len(req.emit_times)
        done = emit_token(req, token, logprob, self.infer_cfg,
                          self._deliveries)
        if not (done and req.finish_reason == "eos"):
            self.tokens_emitted += 1  # stop-truncated tokens still count
            if self.qos is not None:
                # bill the generated token: the tenant's bucket takes
                # the debt (deprioritizing future admissions) and the
                # lifetime counter feeds the fair-share stats
                self.qos.charge_generated(req.tenant)
        if len(req.emit_times) > n0:  # a stop match truncates instead
            self.metrics.observe_emit(req)
        return done

    def _committed(self, slot_id: int) -> list[int]:
        """The slot's committed token stream, truncated to the device's
        written-KV watermark (self.lengths). slot.prompt already folds
        in any tokens generated before a preemption, so only tokens
        generated SINCE this admission are appended. The truncation
        matters at page boundaries: the newest sampled token has no KV
        written yet (its window runs next dispatch), so releasing the
        untruncated stream could key a full page whose final lane is
        garbage — a future prefix hit would serve invalid KV."""
        slot = self._slots[slot_id]
        since = len(slot.prompt) - len(slot.req.prompt)
        stream = slot.prompt + slot.req.tokens[since:]
        return stream[:int(self.lengths[slot_id])]

    # -- the window kind's pages ----------------------------------------------

    def _window_cover(self, sid: int, upto: int) -> None:
        """Hold window pages for every position of slot `sid` below
        `upto` that a dispatch about to be built may write. The pool
        cannot run short (`WindowPagePool`). No-op for a model without
        window layers."""
        if self.window_pool is None:
            return
        hi = int(self._win_hi[sid])
        need = min(-(-int(upto) // self.page_size), self.max_pages_per_slot)
        if need > hi:
            mp = self.max_pages_per_slot
            self.tables[sid, mp + hi:mp + need] = self.window_pool.alloc(
                need - hi)
            self._win_hi[sid] = need
            held = need - int(self._win_lo[sid])
            self.window_pages_peak_slot = max(self.window_pages_peak_slot,
                                              held)
            assert held <= self.window_pages_per_slot, (
                f"slot {sid} holds {held} window pages, over the "
                f"{self.window_pages_per_slot} its pool was sized for")

    def _window_cover_rounds(self, n_rounds: int, lengths, active) -> None:
        """`_window_cover` for `n_rounds` decode rounds of every live
        slot, from `lengths` (the planned frame's under overlap)."""
        if self.cfg.latent_dim and n_rounds > 0:
            # the keys one latent attention block reads for the decode
            # rows: every row's context, its new token included
            live = np.asarray(lengths)[np.asarray(active, bool)]
            self._keys_stage["keys_latent_decode"] = int(sum(
                np.sum(live + r * self.window)
                for r in range(1, n_rounds + 1)))
        if self.window_pool is None or n_rounds <= 0:
            return
        ids = [sid for sid in np.flatnonzero(active)
               if self._slots[sid] is not None]
        for sid in ids:
            self._window_cover(sid, min(
                int(lengths[sid]) + n_rounds * self.window,
                self._slots[sid].stop_len + self.window))
        for r in range(1, n_rounds + 1):
            self._stage_keys(np.asarray(lengths)[ids] + r * self.window,
                             self.window, decode=True)

    def _stage_keys(self, kv_len, width: int, decode: bool) -> None:
        """Count the keys one layer of each kind reads for rows whose
        `width` queries end at `kv_len` (an int, or an array of rows):
        every key for a full layer, for a window layer those from the
        first query's bound on."""
        ks = self._keys_stage
        full = int(np.sum(kv_len))
        win = int(np.sum(np.minimum(
            kv_len, self.cfg.sliding_window - 1 + width)))
        ks["keys_full"] = ks.get("keys_full", 0) + full
        ks["keys_window"] = ks.get("keys_window", 0) + win
        if decode:
            ks["keys_window_decode"] = ks.get("keys_window_decode", 0) + win

    def _count_assign(self, stats: dict, assign=None) -> None:
        """The flight record's assignment counts, by the names the model
        keeps them under (`paged_engine.assign_names`: `assign_held`,
        `assign_zero` and `assign_absent` of a routed share;
        `assign_total`, `assign_peak` and `assign_rows_computed` of a
        router balanced by a bias):
        what the step added to the running counts `assign`, as read back;
        nothing without them."""
        if assign is not None:
            now = np.asarray(assign).astype(np.uint32)
            self._assign_last = dict(zip(
                self._assign_names, (now - self._assign_seen).tolist()))
            self._assign_seen = now
            stats.update(self._assign_last)

    def _take_keys(self) -> dict:
        """The flight record's `keys_full`, `keys_window` and
        `keys_window_decode` of the dispatch being built, or its
        `keys_latent_decode`; nothing for a model without window layers
        or a latent cache."""
        ks, self._keys_stage = self._keys_stage, {}
        return ks

    def _window_free(self, sid: int, before: int) -> None:
        """Give back slot `sid`'s window pages of logical index below
        `before`."""
        lo = int(self._win_lo[sid])
        before = min(int(before), int(self._win_hi[sid]))
        if self.window_pool is None or before <= lo:
            return
        mp = self.max_pages_per_slot
        self.window_pool.free(self.tables[sid, mp + lo:mp + before])
        self.tables[sid, mp + lo:mp + before] = self._no_page
        self._win_lo[sid] = before
        self._iter_stats["pages_returned"] = (
            self._iter_stats.get("pages_returned", 0) + before - lo)

    def _window_trim(self, sid: int, committed: int) -> None:
        """At a commit that moved slot `sid`'s cursor to `committed`: give
        back the window pages whose every position lies behind the
        window of every query still to come. The next query stands at
        `committed` or later and reads back to `committed -
        (sliding_window - 1)`; a program built before this commit and
        launched after it starts at `committed` too (the planned cursor
        never lags the committed one), so it reads none of them either,
        whatever its copy of the table says."""
        if self.window_pool is not None:
            self._window_free(sid, max(
                0, int(committed) - (self.cfg.sliding_window - 1))
                // self.page_size)

    def _release_slot(self, slot_id: int, keyed_tokens: list[int]) -> _Slot:
        """The slot-teardown invariant, in ONE place: release the page
        chain (keyed by `keyed_tokens` — pass [] to key nothing), clear
        the slot, sentinel its table row, deactivate. Every path that
        retires a slot (finish, preemption, failure) goes through here;
        what happens to the request afterwards is the caller's story."""
        slot = self._slots[slot_id]
        # a model whose slots hold more than the full kind's pages keys
        # nothing: a later hit on those pages would find the window
        # kind's given back, or no snapshot of the state at their end
        self.allocator.release(
            slot.pages, [] if self._keys_nothing else keyed_tokens,
            namespace=slot.req.adapter or "", tenant=slot.req.tenant)
        self._slots[slot_id] = None
        self._window_free(slot_id, int(self._win_hi[slot_id]))
        self.tables[slot_id, :] = self._no_page  # sentinel
        self.active[slot_id] = False
        self.lengths[slot_id] = 0
        self._needs_rows[slot_id] = False  # don't pin rows-mode dispatch
        self._has_bias[slot_id] = False
        self._gid[slot_id] = 0
        self._gstate0[slot_id] = 0
        self._aid[slot_id] = 0
        if self.spec_control is not None:
            self.spec_control.on_release(slot_id)
        return slot

    def _finish(self, slot_id: int) -> None:
        slot = self._release_slot(slot_id, self._committed(slot_id))
        self._complete_later(slot.req)

    def _complete_later(self, req: Request) -> None:
        """A commit's `_complete(req)`: the request's fate is decided
        and its slot released now, its waiters are woken by `_deliver`,
        behind the tokens the same commit recorded for it."""
        self._deliveries.append((req, None))

    def _deliver(self) -> None:
        """Run the delivery list in order: each token to its stream
        callback, each finished request through `_complete`. The count
        joins the flight record as `delivered`. A callback that raises
        ends the step as it always did, but only after the rest of the
        list ran: a completion queued behind it has no slot any more,
        so `_fail_all` could not find its request."""
        out, self._deliveries = self._deliveries, []
        st = self._iter_stats
        if st:
            st["delivered"] = st.get("delivered", 0) + len(out)
        err = None
        for req, token in out:
            try:
                if token is None:
                    self._complete(req)
                else:
                    req.stream(token)
            except BaseException as e:  # noqa: BLE001 — raised below
                err = err or e
        if err is not None:
            raise err

    # -- admission ----------------------------------------------------------

    def _start_admissions(self) -> None:
        """Pop pending requests into slots (pages permitting) and build
        bucketed chunked-prefill jobs.

        A request that already carries generated tokens is a
        CONTINUATION (it was preempted): its admission prompt is
        prompt + tokens, so the prefix walk re-hits the pages its
        preemption released into the cache and the sampled first token
        is simply the next token of the stream."""
        staged: list[int] = []
        doomed: list[Request] = []  # impossible requests, completed
        #                             AFTER the lock: _complete may run
        #                             a router fail-handler that takes
        #                             the ROUTER lock, and a router
        #                             thread holding that lock reads
        #                             num_pending (our _lock) — calling
        #                             it here would be an ABBA deadlock
        with self._lock:
            free = [i for i, s in enumerate(self._slots) if s is None]
            while self._pending and free:
                if self.qos is not None:
                    # deficit-round-robin over tenants: which pending
                    # request funds the next free slot (FIFO within a
                    # tenant; single-tenant degenerates to index 0 —
                    # exactly the FIFO below)
                    idx = self.qos.next_admission_index(self._pending)
                else:
                    idx = 0
                req = self._pending[idx]
                prompt = list(req.prompt) + list(req.tokens)
                remaining = req.max_new_tokens - len(req.tokens)
                if self._keys_nothing:
                    # no prefix hit for such a model (see `_release_slot`):
                    # every admission prefills its whole prompt, through
                    # both pools or from a zeroed state
                    shared, shared_len = [], 0
                else:
                    shared, shared_len = self.allocator.lookup_prefix(
                        prompt, namespace=req.adapter or "",
                        tenant=req.tenant)
                if self.allocation == "ondemand":
                    # prompt + one decode window; chains grow per
                    # dispatch in _extend_chains
                    total = len(prompt) + self.window
                else:
                    total = len(prompt) + remaining + self.window
                need = -(-total // self.page_size) - len(shared)
                if (self._faults is not None
                        and self._faults.fire("alloc_famine")
                        is not None):
                    # injected TRANSIENT page famine: release the walk
                    # refs and retry next iteration — exercises the
                    # famine-retry path without shrinking the pool or
                    # permanently failing the request
                    self.allocator.release(shared, prompt[:shared_len],
                                           namespace=req.adapter or "",
                                           tenant=req.tenant)
                    break
                fresh = self.allocator.alloc(max(0, need),
                                             tenant=req.tenant)
                if fresh is None:
                    self.allocator.release(shared, prompt[:shared_len],
                                           namespace=req.adapter or "",
                                           tenant=req.tenant)
                    if self.num_active == 0 and not self._jobs:
                        # nothing running will ever free pages: the pool
                        # is simply too small for this request. Marked
                        # REQUEST-caused: the router must not retry it
                        # (it fails identically on every same-sized
                        # replica) nor count it against the breaker
                        del self._pending[idx]
                        if self.qos is not None:
                            self.qos.on_pending_removed(req.tenant)
                        req.finish_reason = (
                            "error: request needs more pages than the "
                            "pool can ever provide")
                        req._request_fault = True
                        doomed.append(req)
                        continue
                    break
                del self._pending[idx]
                if self.qos is not None:
                    # consume the tenant's DRR deficit only now that
                    # the admission actually succeeded (a page-famine
                    # break above leaves it intact for the retry)
                    self.qos.charge_admission(req.tenant, len(prompt))
                    self.qos.on_pending_removed(req.tenant)
                if shared_len:
                    # REALIZED prefill savings: recorded only once the
                    # admission holds its pages (the walk above already
                    # counted the optimistic hit tokens; a page-famine
                    # release-and-retry must not double-count savings)
                    self.allocator.telemetry.record_saved(req.tenant,
                                                          shared_len)
                slot_id = free.pop(0)
                self._admit_seq += 1
                slot = _Slot(req=req, prompt=prompt,
                             pages=shared + fresh, shared_len=shared_len,
                             stop_len=len(prompt) + remaining,
                             admit_seq=self._admit_seq)
                self._slots[slot_id] = slot
                self.tables[slot_id, :] = self._no_page
                self.tables[slot_id, :len(slot.pages)] = slot.pages
                self._win_lo[slot_id] = self._win_hi[slot_id] = 0
                self.lengths[slot_id] = shared_len
                self.stop_len[slot_id] = slot.stop_len
                self.active[slot_id] = False  # live once admission is done
                # per-request sampling rows (seed stable across
                # preemption: seed_used was fixed at submit)
                row = make_rows([req.sampling], self.infer_cfg,
                                [req.seed_used],
                                prompt_lens=[len(req.prompt)])
                for dst, src in zip(self.samp_rows, row):
                    dst[slot_id] = src[0]
                self._needs_rows[slot_id] = (
                    req.sampling is not None
                    and req.sampling.needs_device_rows(self.infer_cfg))
                self._has_bias[slot_id] = (
                    req.sampling is not None
                    and bool(req.sampling.logit_bias))
                if (req.sampling is not None
                        and req.sampling.regex is not None):
                    # direct registry read, NOT _grammar_gid(): that
                    # helper takes _lock — already held here — and
                    # submit() guarantees every admitted request's
                    # pattern is registered (patterns are never
                    # removed, so continuations re-hit it too)
                    self._gid[slot_id] = self._pattern_gid[
                        req.sampling.regex]
                    # continuations resume mid-pattern: replay the
                    # already-generated tokens host-side
                    self._gstate0[slot_id] = self._grammar_cache.get(
                        req.sampling.regex).walk(req.tokens)
                else:
                    self._gid[slot_id] = 0
                    self._gstate0[slot_id] = 0
                self._aid[slot_id] = (
                    0 if req.adapter is None
                    else self.adapters.adapter_id(req.adapter))
                if (req.sampling is not None
                        and req.sampling.needs_penalty_state()):
                    self._ensure_penalty_state()
                self.orig_len[slot_id] = len(req.prompt)
                if self.spec_control is not None:
                    # fresh controller state at the initial draft
                    # length; a continuation re-prefills the draft
                    # cache, so any staleness clears with it
                    self.spec_control.on_admit(slot_id)
                staged.append(slot_id)
        for req in doomed:
            self._complete(req)
        if not staged:
            return
        now = time.perf_counter()  # one clock read per admission burst
        for slot_id in staged:
            self.metrics.observe_admit(self._slots[slot_id].req, now)
        for slot_id in staged:
            slot = self._slots[slot_id]
            prompt = np.asarray(slot.prompt, np.int32)
            self._jobs.append(_AdmitJob(
                slot=slot_id, rows=prompt[slot.shared_len:],
                rem_len=len(prompt) - slot.shared_len,
                base_len=slot.shared_len, prompt_row=prompt,
                prompt_len=len(prompt)))

    # -- decode -------------------------------------------------------------

    def _preempt_youngest(self, protect: int) -> bool:
        """Free one live slot's pages (content-keyed into the radix
        cache — fully-written, valid KV) and requeue its request at the
        FRONT of the queue as a continuation. Victim selection: the
        YOUNGEST slot (max admit_seq) without QoS; with a TenantRegistry
        the order becomes (lowest priority class, most over fair share,
        youngest) — an interactive tenant's slots outlive a best-effort
        flood's. Returns False when no slot other than `protect` can be
        preempted."""
        candidates = [sid for sid, s in enumerate(self._slots)
                      if s is not None and self.active[sid]
                      and sid != protect]
        if not candidates:
            return False
        if self.qos is not None:
            sid = max(candidates,
                      key=lambda s: (*self.qos.victim_rank(
                          self._slots[s].req.tenant),
                          self._slots[s].admit_seq))
        else:
            sid = max(candidates, key=lambda s: self._slots[s].admit_seq)
        slot = self._release_slot(sid, self._committed(sid))
        self.preemptions += 1
        self.metrics.observe_requeue(slot.req, time.perf_counter())
        if self.qos is not None:
            self.qos.on_requeue(slot.req.tenant)
            # the flight-recorder iteration record tags preempt-requeues
            # with the victim tenant (post-mortem: WHO got evicted)
            self._iter_stats.setdefault("preempt_tenants", []).append(
                slot.req.tenant)
        with self._lock:
            self._pending.appendleft(slot.req)
        return True

    def _extend_chains(self, n_rounds: int) -> int:
        """On-demand policy: before a decode dispatch of n_rounds, grow
        every live slot's page chain to cover its worst-case window
        writes (lengths + n_rounds * window, clamped past stop_len where
        writes still span one final window).

        Pool exhaustion is handled in escalating order: take whatever
        pages ARE available (partial growth), preempt youngest-first,
        and — when nothing is preemptable (e.g. the other slots are
        still mid-admission) — BOUND this dispatch to the rounds every
        chain already covers instead of killing anyone: exhaustion is
        transient whenever admissions/queued work can free or activate
        slots by the next step. Returns the dispatchable round count
        (0 = skip this decode dispatch). A request is failed only when
        nothing can ever change: it is alone, the pool is fully drained
        into its chain, and it still cannot cover one round."""
        n_eff = n_rounds
        for sid in range(self.max_slots):
            slot = self._slots[sid]
            if slot is None or not self.active[sid]:
                continue
            while True:
                need_len = min(
                    int(self.lengths[sid]) + n_rounds * self.window,
                    slot.stop_len + self.window)
                delta = -(-need_len // self.page_size) - len(slot.pages)
                if delta <= 0:
                    break
                grab = min(delta, self.allocator.available)
                fresh = (self.allocator.alloc(grab,
                                              tenant=slot.req.tenant)
                         if grab > 0 else None)
                if fresh:
                    start = len(slot.pages)
                    slot.pages.extend(fresh)
                    self.tables[sid, start:len(slot.pages)] = fresh
                    if grab == delta:
                        break
                    continue  # partial fill; loop tries preemption next
                if self._preempt_youngest(protect=sid):
                    continue
                covered = len(slot.pages) * self.page_size
                r_ok = max(0, (covered - int(self.lengths[sid]))
                           // self.window)
                if (r_ok == 0 and not self._jobs
                        and self.num_pending == 0
                        and self.allocator.available == 0
                        and self.num_active == 1):
                    # genuinely impossible: alone with the whole pool.
                    # REQUEST-caused, like the admission-time twin —
                    # the router must not retry it on an identically-
                    # sized replica or charge the breaker for it
                    slot = self._release_slot(sid, self._committed(sid))
                    slot.req.finish_reason = (
                        "error: request needs more pages than the pool "
                        "can ever provide")
                    slot.req._request_fault = True
                    self._complete(slot.req)
                    break
                n_eff = min(n_eff, r_ok)
                break
        return n_eff

    def _chunk_rounds(self, active) -> int:
        """Rounds of a decode-only dispatch: bounded by decode_chunk and
        by the tightest remaining budget (in rounds), rounded down to a
        power of two. `active` is the plan's live mask (the PLANNED
        frame; its slightly stale remaining budgets can only overshoot,
        which the host emit loop already truncates — the mid-scan EOS
        case)."""
        rem = [s.req.max_new_tokens - len(s.req.tokens)
               for i, s in enumerate(self._slots)
               if s is not None and active[i]]
        if not rem:
            return 1
        n = max(1, min(self.decode_chunk, -(-min(rem) // self.window)))
        p = 1
        while p * 2 <= n:
            p *= 2
        return p

    def _gather_decode_rows(self, active, g_iter: int = 0,
                            spec_lens=None):
        """COMPACTED decode sub-batch: one row per LIVE slot, padded to
        a power of two (compile cache). Rows carry sentinel slot ids /
        tables past the live count, so their writes drop everywhere
        (the cores' slot_ids indirection). Dispatching only live rows
        is what keeps decode cost proportional to occupancy: a batch
        half-full of mid-admission slots does not pay full max_slots
        gathers and matmuls every round.

        A fully-live batch skips the indirection (sl = None, rows ARE
        slots): the steady state never pays the identity gathers of
        gstate / penalty rows.

        Returns (live_ids, sl, live_g, lengths, tables, last, rows):
        the patch's four arrays loose, because a launch writes them
        anew, and everything else the decode cores take of a row as the
        one packed buffer (`_pack_rows`). `g_iter`, `spec_lens` are the
        dispatch's `_spec_plan`: a row's draft limit is its own where
        the controller gave one, else the dispatch's width, which caps
        nothing. `active` is the plan's live mask (the planned frame);
        the gathered lengths/last rows are placeholders: `_launch_plan`
        writes them right before the launch."""
        live_ids = np.flatnonzero(active)
        nl = len(live_ids)
        live_limits = g_iter if spec_lens is None else spec_lens
        if nl == self.max_slots:
            return (live_ids, None, active.copy(), self.lengths,
                    self.tables, self.last_token,
                    _pack_rows(self.stop_len, self._gid, self._aid,
                               live_limits, self.samp_rows))
        bg = _pad_pow2(max(nl, 1))
        sl = np.full((bg,), self.max_slots, np.int32)
        sl[:nl] = live_ids
        slr = np.clip(sl, 0, self.max_slots - 1)
        live_g = np.zeros((bg,), bool)
        live_g[:nl] = True
        lengths = self.lengths[slr].copy()
        tables = self.tables[slr].copy()
        tables[nl:] = self._no_page
        last = self.last_token[slr].copy()
        gid = self._gid[slr]
        gid[nl:] = 0
        aid = self._aid[slr]
        aid[nl:] = 0
        limits = np.zeros((bg,), np.int32)
        limits[:nl] = live_limits
        rows = _pack_rows(self.stop_len[slr], gid, aid, limits,
                          _gather_samp_rows(self.samp_rows, slr, nl), sl)
        return live_ids, sl, live_g, lengths, tables, last, rows

    def _spec_plan(self, live_ids):
        """Per-iteration speculation plan: (dispatch draft count,
        per-live-row draft caps). Fixed-length servers (no controller)
        plan (spec_drafts, None) — the pre-adaptive program, no
        draft_limit input at all. With the adaptive controller the
        dispatch width is QUANTIZED to {0, spec_drafts}: per-row caps
        already bound each slot's commits (and its drafted-token
        accounting) at its own length, and `n_drafts` is a static
        shape — one compiled program per distinct value — so
        intermediate widths would trade a sliver of verify compute for
        spec_drafts-many extra compiles. All-zero lengths plan
        (0, None): plain decode, no draft passes at all — the floor
        adaptive control promises on low-acceptance workloads."""
        if self.spec_drafts <= 0 or len(live_ids) == 0:
            return 0, None
        if self.spec_control is None:
            return self.spec_drafts, None
        lens = [self.spec_control.draft_len(int(s)) for s in live_ids]
        if max(lens) <= 0:
            return 0, None
        return self.spec_drafts, lens

    def _drafted_rows(self, g_iter: int, spec_lens, nl: int):
        """Per-live-row drafted-token counts for this dispatch's
        accounting (None = plain decode ran, nothing was drafted)."""
        if g_iter <= 0:
            return None
        return spec_lens if spec_lens is not None else [g_iter] * nl

    def _stage_spec_stats(self, g_iter: int, n_live: int,
                          st: dict | None = None) -> None:
        """Flight-recorder speculation fields for this iteration:
        draft rows funded, the dispatch draft count, and (adaptive)
        the current per-slot draft lengths. Token drafted/accepted
        fields land post-commit in `_commit_decode_rows`. `st`
        overrides the destination (a launch-ahead plan's staged
        stats)."""
        if self.spec_drafts <= 0:
            return
        if st is None:
            st = self._iter_stats
        st["spec_rows"] = n_live if g_iter > 0 else 0
        st["spec_window"] = g_iter + 1 if g_iter > 0 else 1
        if self.spec_control is not None:
            st["spec_draft_lens"] = self.spec_control.draft_lengths()

    def _commit_decode_rows(self, live_ids, toks, lps, counts, lens,
                            last, drafted, owners) -> None:
        """Scatter a compacted decode dispatch's results back to slots
        and record the tokens (`_commit_inflight`'s decode half).
        Nobody is woken here: stream calls and completions go on the
        delivery list, which `step` runs after its launch.

        `owners`: the _Slot object each row was planned for. Between a
        launch and its commit a whole step ran — a row's slot may have
        been released and RE-OCCUPIED by a new admission, so the ledger
        writes and the emit loop are identity-guarded per row, not just
        active-guarded.

        `drafted` (per-live-row drafted-token counts, None when no
        draft rows ran) funds the speculation ledger from numbers the
        host already has: per committed round, a row drafted its own
        length and accepted `count - 1` of them. The adaptive
        controller is fed per round (its feedback signal), the
        per-tenant wasted-speculation counters once per dispatch —
        all plain host arithmetic on the synced counts, zero extra
        device work."""
        nl = len(live_ids)
        lens = np.asarray(lens)
        last = np.asarray(last)
        counts = np.asarray(counts)
        for i in range(nl):
            sid = int(live_ids[i])
            if self._slots[sid] is owners[i] and self.active[sid]:
                self.lengths[sid] = lens[i]
                self.last_token[sid] = last[i]
        if self.window_pool is not None:
            # a row passes a page's end once in `page_size` tokens: find
            # those rows in one comparison, on the serialized commit
            ids = np.asarray(live_ids)[self.active[live_ids]]
            due = ids[np.maximum(self.lengths[ids]
                                 - (self.cfg.sliding_window - 1), 0)
                      // self.page_size > self._win_lo[ids]]
            for sid in due:
                self._window_trim(sid, self.lengths[sid])
        self.decode_rounds += int(counts.shape[0]) * nl
        self.decode_tokens_committed += int(counts.sum())
        sp_drafted = sp_accepted = 0
        spec_by_tenant: dict = {}
        for r in range(toks.shape[0]):
            for i, sid in enumerate(live_ids):
                slot = self._slots[sid]
                if slot is None or not self.active[sid] \
                        or slot is not owners[i]:
                    continue
                c = int(counts[r, i])
                if drafted is not None and c > 0:
                    d = int(drafted[i])
                    a = min(max(c - 1, 0), d)
                    sp_drafted += d
                    sp_accepted += a
                    if self.spec_control is not None:
                        self.spec_control.observe(sid, d, a)
                    if self.qos is not None and d > 0:
                        dd, aa = spec_by_tenant.get(slot.req.tenant,
                                                    (0, 0))
                        spec_by_tenant[slot.req.tenant] = (dd + d, aa + a)
                for t in range(c):
                    if self._emit(slot.req, int(toks[r, i, t]),
                                  float(lps[r, i, t])):
                        self._finish(sid)
                        break
        if drafted is not None:
            self.spec_tokens_drafted += sp_drafted
            self.spec_tokens_accepted += sp_accepted
            st = self._iter_stats
            st["spec_tokens_drafted"] = (
                st.get("spec_tokens_drafted", 0) + sp_drafted)
            st["spec_tokens_accepted"] = (
                st.get("spec_tokens_accepted", 0) + sp_accepted)
            for tenant, (dd, aa) in spec_by_tenant.items():
                self.qos.charge_speculation(tenant, dd, aa)

    def _complete_admission_chunks(self, sel, ptoks, plps) -> None:
        """Prefill progress on the synced first-token candidates
        (`_commit_inflight`'s prefill half): capture samples in range,
        advance `done`, and ACTIVATE completed admissions, the
        cancel-at-activation check included."""
        ptoks, plps = np.asarray(ptoks), np.asarray(plps)
        for i, (job, take, d0) in enumerate(sel):
            sid = job.slot
            rl = job.rem_len
            if d0 <= rl - 1 < d0 + take:
                job.tok = int(ptoks[i])
                job.lp = float(plps[i])
                job.got = True
            job.done = d0 + take
            self._window_trim(sid, job.base_len + job.done)
            if job.done < rl:
                continue
            slot = self._slots[sid]
            assert job.got, "first-token sample never captured"
            self.lengths[sid] = len(slot.prompt)
            self.last_token[sid] = job.tok
            if slot.req._cancel.is_set():
                # cancelled mid-admission: release without ever
                # activating (the prefilled KV keys into the radix
                # cache — a resubmit would reuse it)
                slot = self._release_slot(sid, self._committed(sid))
                slot.req.finish_reason = "cancelled"
                self._complete_later(slot.req)
            else:
                self.active[sid] = True
                if self._emit(slot.req, job.tok, job.lp):
                    self._finish(sid)
                elif getattr(slot.req, "_handoff", None) is not None:
                    # prefill complete with decode budget left: queue
                    # the disaggregation handoff callback; fired
                    # OUTSIDE _step_lock at the end of this step
                    self._handoff_ready.append(slot.req)
            self._jobs.remove(job)

    # -- mixed (stall-free) scheduling --------------------------------------

    def _mixed_rounds(self, n_live: int, prefill_demand: int,
                      win: int, active) -> int:
        """Decode rounds for a mixed iteration: the full steady-state
        count (not stalling decode is the point), then squeezed to
        leave the budget at least one minimal prefill chunk when
        admissions are waiting, floored at one round and kept a power
        of two (compile cache). `win` is THIS iteration's decode window
        (current max draft length + 1 — adaptive speculation shrinks it
        with demand), so a slot's decode claim against the budget is
        its honest token count. `active` is the plan's live mask (the
        planned frame; see _chunk_rounds)."""
        rem = [s.req.max_new_tokens - len(s.req.tokens)
               for i, s in enumerate(self._slots)
               if s is not None and active[i]]
        if not rem or not n_live:
            return 0
        n = max(1, min(self.decode_chunk, -(-min(rem) // win)))
        if prefill_demand > 0:
            fit = (self.mixed_token_budget - self._rem_buckets[0]) \
                // (n_live * win)
            n = min(n, max(fit, 1))
        p = 1
        while p * 2 <= n:
            p *= 2
        return p

    def _select_prefill(self, jobs, n_live: int, win: int,
                        n_rounds: int):
        """Token-budget prefill selection: the policy half of a mixed
        iteration (the array-staging half is `_build_prefill_group`).
        A job's cursor is `planned`, which counts what the dispatch in
        flight carries. QoS virtual-time (or FIFO) order; decode rows
        are funded first, each selected job takes up to `prefill_chunk`
        tokens of the remainder, and when decode alone saturates the
        budget the OLDEST admission still gets one minimal chunk (TTFT
        stays bounded). Returns [(job, take, cursor_offset)]."""
        if self.qos is not None and jobs:
            order = self.qos.order_jobs(
                [self._slots[j.slot].req.tenant for j in jobs])
            jobs = [jobs[i] for i in order]
        sel: list[tuple[_AdmitJob, int, int]] = []
        left = room = self.mixed_token_budget - n_live * win * n_rounds
        widest = 0
        for job in jobs:
            if left <= 0:
                break
            rem_left = job.rem_len - job.planned
            take = min(rem_left, left, self.prefill_chunk)
            if take <= 0:
                continue
            # the budget bounds what the step computes, and the group is
            # computed at its padded size (`_build_prefill_group`: rows
            # to a power of two, every row at the widest chunk's bucket):
            # a row that would pad the group past the budget's room
            # waits, or a 9th chunk of a few tokens doubles a step of 8
            slots = _pad_pow2(len(sel) + 1) * _bucket(
                max(widest, take), self._mixed_buckets)
            if sel and slots > room:
                break
            widest = max(widest, take)
            sel.append((job, take, job.planned))
            left -= take
        if jobs and not sel:
            job = jobs[0]
            take = min(job.rem_len - job.planned, self._rem_buckets[0])
            sel = [(job, take, job.planned)]
        return sel

    def _chunk_bucket(self, sel) -> int:
        """The width a prefill group's chunk is padded to: the bucket of
        its widest take (`_mixed_step`'s static `chunk_w`)."""
        return _bucket(max([t for _, t, _ in sel] + [1]),
                       self._mixed_buckets)

    def _build_prefill_group(self, sel, w: int) -> dict:
        """Numpy staging for the ragged prefill half of one mixed
        iteration: one row per selected admission chunk, each at its
        own width, padded to a pow2 row count and a bucketed max width
        `w` (`_chunk_bucket`; compile cache). `sel` entries are (job,
        take, d0): d0 is the remainder offset this chunk starts at, the
        job's PLANNED cursor, so a plan never re-prefills tokens already
        in flight. `group` is the one array the half hands the device
        (`_pack_group`), the rest is what the host reads of it."""
        pad_tok = self.infer_cfg.pad_token_id
        b = self.max_slots
        g = len(sel)
        gp = _pad_pow2(max(g, 1))
        chunk = np.full((gp, w), pad_tok, np.int32)
        widths = np.zeros((gp,), np.int32)
        g_lens = np.zeros((gp,), np.int32)
        g_tables = np.full((gp, self._table_cols), self._no_page, np.int32)
        sample_at = np.zeros((gp,), np.int32)
        slot_ids = np.full((gp,), self.max_slots, np.int32)
        countm = np.zeros((gp,), bool)
        scatm = np.zeros((gp,), bool)
        scat_plens = []
        for i, (job, take, d0) in enumerate(sel):
            sid = job.slot
            rl = job.rem_len
            chunk[i, :take] = job.rows[d0:d0 + take]
            widths[i] = take
            g_lens[i] = job.base_len + d0
            self._window_cover(sid, int(g_lens[i]) + take)
            if self.window_pool is not None:
                self._stage_keys(int(g_lens[i]) + take, take, decode=False)
            g_tables[i] = self.tables[sid]
            sample_at[i] = min(max(rl - 1 - d0, 0), take - 1)
            slot_ids[i] = sid
            countm[i] = d0 <= rl - 1 < d0 + take
            scatm[i] = d0 == 0
            if d0 == 0:
                scat_plens.append(job.prompt_len)
        pb = (_bucket(max(scat_plens), self._admit_buckets)
              if scat_plens else self._admit_buckets[0])
        prompt_rows = np.full((gp, pb), pad_tok, np.int32)
        prompt_lens = np.zeros((gp,), np.int32)
        orig_lens = np.zeros((gp,), np.int32)
        for i, (job, take, d0) in enumerate(sel):
            sid = job.slot
            pl = job.prompt_len
            prompt_lens[i] = pl
            orig_lens[i] = self.orig_len[sid]
            if d0 == 0:
                prompt_rows[i, :pl] = job.prompt_row[:pl]
        sl_real = np.clip(slot_ids, 0, self.max_slots - 1)
        gid_g = self._gid[sl_real]
        gid_g[g:] = 0
        gst0_g = self._gstate0[sl_real]
        gst0_g[g:] = 0
        aid_g = self._aid[sl_real]
        aid_g[g:] = 0
        sel_mask = np.zeros((b,), bool)
        sel_mask[[job.slot for job, _, _ in sel]] = True
        group = _pack_group(
            chunk, g_tables, prompt_rows,
            _gather_samp_rows(self.samp_rows, sl_real, g),
            widths=widths, g_lens=g_lens, sample_at=sample_at,
            slot_ids=slot_ids, prompt_lens=prompt_lens,
            orig_lens=orig_lens, count_mask=countm, scatter_mask=scatm,
            gid=gid_g, gstate0=gst0_g, aid=aid_g)
        return {"group": group, "chunk_tokens": chunk.size,
                "scatter_prompt": bool(scatm.any()), "sel_mask": sel_mask}

    def _handoff_prefetch(self, sel) -> None:
        """Overlapped KV export for the disaggregation handoff: for
        every selected admission that COMPLETES its prefill in the
        dispatch about to launch and carries a `handoff=` callback,
        gather the pages PRIOR chunks fully committed and start their
        device->host copies now — the transfer rides under the final
        chunk's compute, so the export at the handoff's commit point
        pays only the last chunk's pages (≤1 iteration of exposed
        latency). Must run BEFORE the dispatch statement: the dispatch
        donates `self.state`, so the pool buffers are invalid after
        the launch. Read-only — allocates nothing, releases nothing —
        so it is safe on the DD5 plan/launch path; the stash is
        validated (page-id prefix match) and consumed by
        `_export_request_locked`, or dropped at request completion."""
        ps = self.page_size
        for job, take, d0 in sel:
            if d0 + take < job.rem_len:
                continue  # not the final chunk
            sid = job.slot
            slot = self._slots[sid]
            if slot is None or getattr(slot.req, "_handoff", None) is None:
                continue
            n_full = (job.base_len + d0) // ps
            if n_full <= 0 or slot.req.request_id in self._handoff_stash:
                continue
            ids = np.asarray(slot.pages[:n_full])
            gathered = {name: pool[:, ids]
                        for name, pool in self.state["pools"].items()}
            draft = self.state.get("draft_pools")
            if draft is not None:
                for name, pool in draft.items():
                    gathered["draft/" + name] = pool[:, ids]
            for arr in gathered.values():
                # analysis: allow[dispatch-discipline] async D2H copy
                # START, not a host sync: nothing blocks here — the
                # copy overlaps the final prefill chunk and the
                # export's sanctioned device_get collects it
                arr.copy_to_host_async()
            self._handoff_stash[slot.req.request_id] = (
                tuple(slot.pages[:n_full]), gathered)

    # -- plan, launch, commit -----------------------------------------------
    #
    # The pipelined loop (see the module docstring): each step plans
    # iteration N+1 against the PLANNED frame while the device runs
    # iteration N, launches it, and pays the one sanctioned device_get
    # commit of N. Functions on this path obey one extra invariant the
    # dispatch-discipline pass checks statically (DD5): a plan made
    # under a dispatch in flight never releases pages or tears down
    # slots — a page freed under that dispatch could be re-allocated
    # while the device still writes it.

    def _extend_chains_planned(self, n_rounds: int, planned_len,
                               planned_active) -> int:
        """Chain growth for a plan made under a dispatch in flight:
        cover each planned-live slot's worst-case window writes using
        the PLANNED length upper bound (committed length + the
        in-flight dispatch's rounds*window). Unlike `_extend_chains`
        this NEVER preempts or fails a request (DD5 — no page releases
        while a dispatch is in flight): on famine it takes whatever
        pages are available and bounds the dispatch to the rounds
        every chain already covers. 0 drops the decode half; the
        pipeline then drains, and the next plan, made with nothing in
        flight, runs the full preemption escalation."""
        n_eff = n_rounds
        for sid in range(self.max_slots):
            slot = self._slots[sid]
            if slot is None or not planned_active[sid]:
                continue
            need_len = min(int(planned_len[sid])
                           + n_rounds * self.window,
                           slot.stop_len + self.window)
            delta = -(-need_len // self.page_size) - len(slot.pages)
            if delta > 0:
                grab = min(delta, self.allocator.available)
                fresh = (self.allocator.alloc(grab,
                                              tenant=slot.req.tenant)
                         if grab > 0 else None)
                if fresh:
                    start = len(slot.pages)
                    slot.pages.extend(fresh)
                    self.tables[sid, start:len(slot.pages)] = fresh
            covered = len(slot.pages) * self.page_size
            r_ok = max(0, (covered - int(planned_len[sid]))
                       // self.window)
            n_eff = min(n_eff, r_ok)
        return n_eff

    def _plan_iteration(self) -> "_Plan | None":
        """Plan — and numpy-build — the NEXT dispatch against the
        PLANNED frame: the committed ledger plus the in-flight
        dispatch's deterministic effects (job cursors advanced by the
        takes it carries; slots it completes counted live; lengths at
        their rounds*window upper bound). This is the host policy work
        the pipeline hides under the device: QoS/DRR funding order,
        token-budget split, chain growth, and all array staging happen
        here, so after the commit only a (rows,)-sized patch and the
        launch remain serialized.

        With nothing in flight (the pipeline's fill) the planned frame
        IS the committed ledger, and the plan may release pages: chains
        grow through `_extend_chains`, which preempts on famine, and
        the speculation plan is made again for the slots that are left.
        Under a dispatch in flight the plan never releases pages (DD5):
        `_extend_chains_planned` degrades the round count instead, and
        a plan with nothing to dispatch drains the pipeline.

        Returns None when there is nothing to dispatch. Never mutates
        the committed ledger beyond job.planned cursors, QoS prefill
        charges, chain growth and, with nothing in flight, preemption.

        The injected-fault "dispatch" site is `step`'s, once a step
        before the plan."""
        prof = self._profiler
        if prof is not None:
            # planned-frame budget/round planning, chain growth, QoS
            # funding order, selection (a no-op boundary right after
            # step()'s admissions)
            prof.enter("admission")
        b = self.max_slots
        infl = self._inflight
        # --- the planned frame --------------------------------------------
        planned_active = self.active.copy()
        planned_len = self.lengths.copy()
        # slots whose last token the in-flight dispatch makes: the host
        # learns it at the commit, the device has it before
        made = np.zeros((b,), bool)
        if infl is not None:
            if infl.n_rounds > 0:
                for i, sid_ in enumerate(infl.live_ids):
                    sid = int(sid_)
                    if planned_active[sid] \
                            and self._slots[sid] is infl.owners[i]:
                        planned_len[sid] = min(
                            int(planned_len[sid])
                            + infl.n_rounds * infl.win,
                            int(self.stop_len[sid]) + self.window)
                        made[sid] = True
            for sid in infl.activating:
                slot = self._slots[sid]
                if slot is not None:
                    planned_active[sid] = True
                    planned_len[sid] = len(slot.prompt)
                    made[sid] = True
        jobs = [j for j in self._jobs if j.planned < j.rem_len]
        if not jobs and not planned_active.any():
            return None
        # --- rounds, and the chains that cover them -----------------------
        # draft rounds are funded as decode rows: a slot's claim is
        # `win` tokens per round, charged against prefill funding
        g_iter, spec_lens = self._spec_plan(np.flatnonzero(planned_active))
        if jobs:
            n_rounds = self._mixed_rounds(
                int(planned_active.sum()),
                sum(j.rem_len - j.planned for j in jobs), g_iter + 1,
                planned_active)
        else:
            n_rounds = self._chunk_rounds(planned_active)
        if self.allocation == "ondemand" and n_rounds > 0:
            if infl is None:
                n_eff = self._extend_chains(n_rounds)
                # a preemption takes its slot out of the live set
                planned_active = self.active.copy()
                planned_len = self.lengths.copy()
                g_iter, spec_lens = self._spec_plan(
                    np.flatnonzero(planned_active))
            else:
                n_eff = self._extend_chains_planned(
                    n_rounds, planned_len, planned_active)
            if n_eff <= 0 or not planned_active.any():
                n_rounds = 0  # transient page famine: prefill-only
            else:
                while n_rounds > n_eff:  # keep round counts powers of
                    n_rounds //= 2       # two (compile cache)
                n_rounds = max(1, n_rounds)
        if not jobs and n_rounds == 0:
            return None
        self._window_cover_rounds(n_rounds, planned_len, planned_active)
        win = g_iter + 1
        stats: dict = {}
        spans: list = []
        if jobs:
            # --- token-budget mixed iteration ------------------------------
            live = (planned_active if n_rounds > 0
                    else np.zeros((b,), bool))
            n_live = int(live.sum())
            # weighted-fair funding of the iteration's prefill chunks
            # (QoS virtual-time order inside _select_prefill; called
            # even for a single job — it also advances the global
            # virtual time, so a tenant arriving after an idle gap
            # resumes at the current time instead of replaying idle
            # credit)
            sel = self._select_prefill(jobs, n_live, win, n_rounds)
            if not sel and not n_rounds:
                return None
            if self.qos is not None:
                for job, take, _ in sel:
                    self.qos.charge_prefill(
                        self._slots[job.slot].req.tenant, take)
            activating: list[int] = []
            for job, take, d0 in sel:
                job.planned = d0 + take
                if job.planned >= job.rem_len:
                    activating.append(job.slot)
            stats.update(
                n_live=n_live, decode_rounds=n_rounds,
                decode_tokens=n_live * win * n_rounds,
                prefill_tokens=sum(t for _, t, _ in sel))
            if self.cfg.ssm_heads:
                # real tokens through the chunked scan, and the rows that
                # stand at position 0 and enter with a zeroed state
                stats.update(
                    ssm_chunk_tokens=stats["prefill_tokens"],
                    ssm_resets=sum(job.base_len + d0 == 0
                                   for job, _, d0 in sel))
            if n_rounds > 0:
                self._stage_spec_stats(g_iter, n_live, st=stats)
            if self.trace_recorder is not None:
                for job, take, d0 in sel:
                    r = self._slots[job.slot].req
                    if r.trace is not None:
                        spans.append(
                            (r, "prefill_chunk",
                             {"slot": job.slot, "tokens": take,
                              "offset": d0}))
            if prof is not None:
                prof.enter("build")
            chunk_w = self._chunk_bucket(sel)
            pf = self._build_prefill_group(sel, chunk_w)
            sel_mask = pf["sel_mask"]
            # a plan whose decode half was dropped gathers no row, and
            # the controller's lengths are the planned-live slots'
            (live_ids, sl_d, live_g, d_lens, d_tables, d_last,
             rows) = self._gather_decode_rows(
                 live, g_iter, spec_lens if n_rounds else None)
            stats.update(self._take_keys())
            stats.update(
                decode_rows=int(live_g.shape[0]) if n_rounds else 0,
                compaction_ratio=(n_live / max(int(live_g.shape[0]), 1)
                                  if n_rounds else 1.0))
            if self.trace_recorder is not None and n_rounds > 0:
                self._stage_decode_spans(live_ids, n_rounds, out=spans)
            if n_rounds == 0:
                live_g = np.zeros_like(live_g)
            plan = _Plan(
                kind="mixed", sel=sel, activating=activating,
                n_rounds=n_rounds, win=win, g_iter=g_iter,
                spec_lens=spec_lens, live_ids=live_ids, sl_d=sl_d,
                live_g=live_g, d_lens=d_lens, d_tables=d_tables,
                d_last=d_last, rows=rows,
                owners=[self._slots[int(s)] for s in live_ids],
                pf=pf, chunk_w=chunk_w,
                scatter_prompt=pf["scatter_prompt"],
                use_rows_p=bool((self._needs_rows & sel_mask).any()),
                use_bias_p=bool((self._has_bias & sel_mask).any()),
                use_rows_d=bool((self._needs_rows & live).any()),
                use_bias_d=bool((self._has_bias & live).any()),
                use_grammar=bool(
                    ((self._gid > 0) & (live | sel_mask)).any()),
                use_lora=bool(
                    ((self._aid > 0) & (live | sel_mask)).any()),
                stats=stats, spans=spans)
        else:
            # --- pure-decode iteration -------------------------------------
            if prof is not None:
                prof.enter("build")
            (live_ids, sl_d, live_g, d_lens, d_tables, d_last,
             rows) = self._gather_decode_rows(
                 planned_active, g_iter, spec_lens)
            stats.update(self._take_keys())
            stats.update(
                n_live=len(live_ids), decode_rounds=n_rounds,
                decode_tokens=len(live_ids) * win * n_rounds,
                decode_rows=int(live_g.shape[0]),
                compaction_ratio=(len(live_ids)
                                  / max(int(live_g.shape[0]), 1)))
            self._stage_spec_stats(g_iter, len(live_ids), st=stats)
            if self.trace_recorder is not None:
                self._stage_decode_spans(live_ids, n_rounds, out=spans)
            plan = _Plan(
                kind="decode", sel=[], activating=[], n_rounds=n_rounds,
                win=win, g_iter=g_iter, spec_lens=spec_lens,
                live_ids=live_ids, sl_d=sl_d, live_g=live_g,
                d_lens=d_lens, d_tables=d_tables, d_last=d_last,
                rows=rows,
                owners=[self._slots[int(s)] for s in live_ids],
                pf=None, chunk_w=0, scatter_prompt=False,
                use_rows_p=False, use_bias_p=False,
                use_rows_d=bool(
                    (self._needs_rows & planned_active).any()),
                use_bias_d=bool(
                    (self._has_bias & planned_active).any()),
                use_grammar=bool(
                    ((self._gid > 0) & planned_active).any()),
                use_lora=bool(((self._aid > 0) & planned_active).any()),
                stats=stats, spans=spans)
        # stage the launch-stable inputs onto the device NOW, inside
        # the overlap window: an asynchronous host->device feed (DD2
        # deliberately never flags those), so these transfers ride
        # behind the in-flight program: the decode rows' packed buffer
        # and, of a mixed plan, the prefill group's. `_launch_plan`
        # passes them through as they are and hands over one array, the
        # patch. The step's own record says how many went and how long
        # the block took (`plan_h2d`, `stage_ms`): this plan's stats are
        # recorded a step later, with the program's commit.
        t_stage = prof.lap() if prof is not None else None
        h2d0 = self._h2d
        if plan.pf is not None:
            plan.pf["group"] = self._to_device(plan.pf["group"])
        plan.rows = self._to_device(plan.rows)
        self._iter_plan_h2d = self._h2d - h2d0
        if prof is not None:
            self._iter_stage_ms = (prof.lap() - t_stage) * 1e3
        plan.waits = self._launch_waits(plan, infl)
        stats["launch_ahead"] = plan.waits is None
        if plan.waits is None:
            # with no drafts in play a live row advances one token a
            # round, so the planned lengths are the lengths; a row they
            # put at its token limit ends at the commit and rides dead
            live = planned_active & (planned_len < self.stop_len - 1)
            if plan.kind == "decode" and not live.any():
                return None  # every row ends at the commit: drain
            plan.frame = (planned_len, live, made)
        else:
            stats["launch_waits"] = plan.waits
        return plan

    def _launch_waits(self, plan: "_Plan", infl) -> "str | None":
        """Why the launch of `plan` has to follow the commit of `infl`,
        the dispatch in flight when it was planned, or None: it may go
        onto the device's queue ahead of that commit. Read from what the
        plan shows, for this iteration alone (no option chooses):

          "fill"     nothing is in flight: there is no commit to go
                     ahead of, and the launch fills the pipeline;
          "drafts"   either dispatch runs speculative rounds: how far a
                     row advances is known at the commit, not before;
          "grammar"  a constrained row among the plan's: the order such
                     rows were built and tested under (the DFA state is
                     the device's, the resume state the host's);
          "handoff"  an admission the plan completes carries a
                     disaggregation hand-off: `_handoff_prefetch` copies
                     the pages its earlier chunks committed.

        Everything else a launch reads is in the planned frame
        (`_Plan.frame`) or staged already: lengths, live flags and table
        rows exactly, and each row's last token on the device where the
        in-flight dispatch makes it."""
        if infl is None:
            return "fill"
        if infl.g_iter > 0 or plan.g_iter > 0:
            return "drafts"
        if plan.use_grammar:
            return "grammar"
        for job, take, d0 in plan.sel:
            if d0 + take >= job.rem_len and getattr(
                    self._slots[job.slot].req, "_handoff",
                    None) is not None:
                return "handoff"
        return None

    def _stage_program_kind(self, stats: dict, chunk_tokens: int,
                            decode_rows: int, n_rounds: int, n_drafts: int,
                            lora) -> None:
        """The record's `joined` and `grouped`, known when the program is
        chosen: whether it walks the layers once for a prefill group and
        the decode round together (`_walks_once`; a program of one half
        alone has nothing to join), and whether its expert calls take the
        sorted dispatch (`_sorts_experts`)."""
        joined = chunk_tokens > 0 and _walks_once(
            self.cfg, chunk_tokens + decode_rows, n_rounds, n_drafts,
            self.draft_cfg, lora)
        stats["joined"] = joined
        stats["grouped"] = _sorts_experts(
            self.cfg, self.params, joined, chunk_tokens,
            decode_rows * (n_drafts + 1) * (n_rounds > 0))

    def _launch_plan(self, plan: "_Plan") -> None:
        """Fill in the plan's data-dependent decode inputs, then launch
        it ASYNCHRONOUSLY — no device_get here; the sync is a later
        `_commit_inflight`. The host crosses to the device once and
        dispatches one program: the patch (lengths / last tokens / live
        flags / table rows) goes over as one packed array with the
        dispatch's count (`_feed_patch`), the program makes its own key
        from it, and everything else was staged by `_plan_iteration` and
        is passed through untouched. The flight record's `launch_h2d`
        counts the host arrays handed over here.

        Where the patch comes from is the plan's (`_Plan.waits`):

        AHEAD of the commit (None; the steady state). The dispatch in
        flight is still running, or still to be read back, and this one
        goes onto the device's queue behind it, so the chip passes from
        one to the other with no host in between. The patch is the
        planned `frame`: lengths and live flags as that dispatch leaves
        them, the table rows as `_extend_chains_planned` grew them, and
        for a row whose last token that dispatch makes the flag
        `_ROW_LAST_ON_DEVICE` in place of the token. A row that ends at
        the commit still to come (end token, stop string, cancel or
        deadline seen after this launch) is live here all the same: it
        computes, it writes past its committed length into pages its
        slot held at this launch, and `owners` throws its results away
        at this dispatch's own commit. THE INVARIANT ON RELEASED PAGES:
        that commit-to-come may release them while this program has yet
        to write them, and that is safe because every later writer of a
        page takes the pools from `self.state`, which from this launch
        on is THIS program's output: a step program of a plan built
        later (a new admission, a prefix hit that shares the page), a
        migration import (`_import_pages`) and anything else that
        scatters into the pools is ordered behind this program by that
        data dependence, on the one device queue. A window trim and a
        slot's release write nothing; they hand pages to such a writer.
        What this program writes there lies past every length the
        prefix cache keyed (`_committed`), and is the true entry of the
        row's last token besides.

        AFTER the commit (a reason: `_launch_waits`). The patch is the
        just-committed ledger; rows whose slot died at the commit are
        deadened: their sentinel tables drop every device write, and
        `owners` masks their host commit."""
        prof = self._profiler
        if prof is not None:
            prof.enter("launch")
        h2d0 = self._h2d
        live_ids = plan.live_ids
        nl = len(live_ids)
        ahead = plan.waits is None
        if nl and plan.n_rounds > 0:
            # per slot: the planned frame, or the ledger (dead slots
            # carry sentinel tables and active=False from _release_slot)
            if ahead:
                # a row at its token limit rides dead behind a sentinel
                # table, as a slot released at the commit does
                lens, live, made = plan.frame
                flag = np.where(live, np.where(made, _ROW_LAST_ON_DEVICE,
                                               _ROW_LIVE), _ROW_DEAD)
                tables = np.where(live[:, None], self.tables, self._no_page)
            else:
                lens, flag, tables = self.lengths, self.active, self.tables
            if plan.sl_d is None:
                # rows ARE slots: the per-slot arrays are the patch's
                plan.live_g, plan.d_lens, plan.d_tables = flag, lens, tables
                plan.d_last = self.last_token
            else:
                rows = flag[live_ids]
                if not ahead:
                    slots, owners = self._slots, plan.owners
                    rows &= np.fromiter(
                        (slots[sid] is owners[i]
                         for i, sid in enumerate(live_ids.tolist())),
                        bool, nl)
                plan.live_g = np.zeros(plan.live_g.shape, np.int32)
                plan.live_g[:nl] = rows
                plan.d_lens[:nl] = lens[live_ids]
                plan.d_last[:nl] = self.last_token[live_ids]
                plan.d_tables[:nl] = tables[live_ids]
            if plan.kind == "decode" and not plan.live_g[:nl].any():
                # no planned row lives (each died at the commit, or
                # stands at its token limit): nothing left to dispatch
                # — drain the pipeline instead of paying a fully-inert
                # program
                return
        # analysis: allow[lock-discipline] atomically-swapped
        # reference, rebuilt under _lock pre-admission
        grammar = self._grammar_dev if plan.use_grammar else None
        lora = self.adapters.device_args() if plan.use_lora else None
        patch = self._feed_patch(plan.d_lens, plan.d_last, plan.live_g,
                                 plan.d_tables)
        if self.cfg.ssm_heads:
            # rows whose state this program advances by one token a round
            plan.stats["ssm_decode_rows"] = plan.n_rounds * int(
                np.count_nonzero(plan.live_g))
        self._stage_program_kind(
            plan.stats,
            plan.pf["chunk_tokens"] if plan.kind == "mixed" else 0,
            plan.live_g.size, plan.n_rounds, plan.g_iter, lora)
        if ahead:
            # who sets the pace, known here: had the program in flight
            # finished already, the device stood with nothing queued
            # until the call below (a query: no wait, no transfer)
            infl = self._inflight
            infl.stats["host_late"] = infl.futures[0].is_ready()
        if plan.kind == "mixed":
            # disaggregation handoff (such a plan waited for the
            # commit, `_launch_waits`): the plan's sel cursors equal
            # the committed ones — start the D2H copies for admissions
            # the plan completes, before the dispatch donates self.state
            self._handoff_prefetch(plan.sel)
            (self.state, ptoks, plps, lens, last, (toks, lps, counts),
             assign) = \
                _mixed_step(
                    self.params, self.state, plan.pf["group"], patch,
                    plan.rows, self._rng, grammar, lora,
                    self.draft_params,
                    cfg=self.cfg, infer_cfg=self.infer_cfg,
                    n_rounds=plan.n_rounds, n_drafts=plan.g_iter,
                    scatter_prompt=plan.scatter_prompt,
                    chunk_w=plan.chunk_w, mesh=self.mesh,
                    draft_cfg=self.draft_cfg,
                    use_rows_p=plan.use_rows_p,
                    use_bias_p=plan.use_bias_p,
                    use_rows_d=plan.use_rows_d,
                    use_bias_d=plan.use_bias_d)
            futures = (ptoks, plps, toks, lps, counts, lens, last)
        elif plan.g_iter > 0:
            (self.state, lens, last, (toks, lps, counts),
             assign) = _spec_rounds(
                self.params, self.state, patch, plan.rows, self._rng,
                grammar, lora, self.draft_params,
                cfg=self.cfg, infer_cfg=self.infer_cfg,
                n_rounds=plan.n_rounds, n_drafts=plan.g_iter,
                mesh=self.mesh, draft_cfg=self.draft_cfg,
                use_rows=plan.use_rows_d, use_bias=plan.use_bias_d)
            futures = (toks, lps, counts, lens, last)
        else:
            (self.state, lens, last, (toks, lps, counts),
             assign) = _decode_rounds(
                self.params, self.state, patch, plan.rows, self._rng,
                grammar, lora,
                cfg=self.cfg, infer_cfg=self.infer_cfg,
                n_rounds=plan.n_rounds, mesh=self.mesh,
                use_rows=plan.use_rows_d, use_bias=plan.use_bias_d)
            futures = (toks, lps, counts, lens, last)
        futures += (assign,)
        self._iter_launch_h2d = self._h2d - h2d0
        # the launch's end: the start of the wait for the program
        # before it where the launch went ahead, else of the delivery
        # (`step` wakes the streaming threads under the program
        # launched here)
        t = (prof.enter("device" if ahead else "deliver")
             if prof is not None else time.perf_counter())
        self._iter_launch_ts = t
        launched = _Inflight(
            kind=plan.kind, futures=futures, sel=plan.sel,
            activating=plan.activating, live_ids=live_ids,
            owners=plan.owners, n_rounds=plan.n_rounds, win=plan.win,
            g_iter=plan.g_iter, spec_lens=plan.spec_lens,
            stats=plan.stats, spans=plan.spans, t_launch=t)
        if ahead:
            self._ahead = launched
        else:
            self._inflight = launched

    def _commit_inflight(self) -> None:
        """Sync and commit the OLDEST uncommitted dispatch. One
        device_get brings the sampled tokens home; the ledger writes,
        the tokens recorded on their requests, activations, speculation
        feedback, and deferred sweep reaps all run on the synced values
        — guarded per row by the owners identity captured at plan time
        (a whole step ran since the launch). Nobody is told: the stream
        calls and completions wait on the delivery list (`_deliver`).

        In the steady state the next dispatch is on the device's queue
        already (`_launch_plan`, ahead: `_ahead`), so the device_get
        waits on a program behind which the chip finds its next, and
        the read-back, this commit and the delivery run under that one;
        the dispatch launched ahead moves up to `_inflight` here. Where
        the launch had to wait (`_launch_waits`) this is, as it was, the
        serialized critical path: everything that launch reads is
        written here. Pages a row's end releases here may still be
        written by the program launched ahead: `_launch_plan` says why
        that is safe."""
        infl, self._inflight, self._ahead = self._inflight, self._ahead, None
        prof = self._profiler
        st = infl.stats
        # who set the pace: the program had already finished when the
        # host put the next one behind it (`_launch_plan` asked), or,
        # where that launch waits for this commit, when the host came
        # for its results. Either way the device stood idle for the
        # host in this iteration (a query: no wait, no transfer)
        if "host_late" not in st:
            st["host_late"] = infl.futures[0].is_ready()
        t_wait = (prof.enter("device") if prof is not None
                  else time.perf_counter())
        # analysis: allow[lock-discipline] THE sanctioned per-iteration
        # host sync — one launched dispatch, one device_get, under the
        # step lock that serializes the scheduler by design
        vals = jax.device_get(infl.futures)
        if prof is not None:
            prof.enter("commit")
        *vals, assign = vals
        self._count_assign(st, assign)
        st["overlap"] = True
        # how long the device ran ahead of the host needing results:
        # launch -> the moment this step's overlapped work finished
        # and the sync began. Residual device phase > 0 means the
        # device was still busy through the whole overlap window.
        st["overlap_launch_lead_ms"] = (t_wait - infl.t_launch) * 1e3
        # install BEFORE the commit work below: _commit_decode_rows
        # appends its spec-token fields to self._iter_stats, and they
        # belong to THIS record
        self._iter_stats = st
        self._iter_spans = infl.spans
        n_rounds, g_iter = infl.n_rounds, infl.g_iter
        if infl.kind == "mixed":
            ptoks, plps, toks, lps, counts, lens, last = vals
        else:
            toks, lps, counts, lens, last = vals
            if g_iter == 0:
                toks = np.asarray(toks)[:, :, None]
                lps = np.asarray(lps)[:, :, None]
        if n_rounds > 0:
            if (g_iter == 0 and self.spec_drafts > 0
                    and self.spec_control is not None):
                self.spec_control.on_plain_dispatch(
                    [int(s) for s in infl.live_ids], n_rounds)
            self._commit_decode_rows(
                infl.live_ids, np.asarray(toks), np.asarray(lps),
                counts, lens, last,
                self._drafted_rows(g_iter, infl.spec_lens,
                                   len(infl.live_ids)),
                infl.owners)
        if infl.kind == "mixed":
            self._complete_admission_chunks(infl.sel, ptoks, plps)
        self._apply_reaps()

    def _sweep(self) -> None:
        """A step's sweep: cancelled / deadline-expired SLOT holders
        are only MARKED (active=False + queued on _reaped): a dispatch
        in flight is still writing their pages, and releasing then
        could hand a page to a new admission while the device writes
        it. `_apply_reaps` releases them right after the commit, in
        this same step (with nothing in flight, right after the
        sweep): the KV they wrote is fully committed by then, so it
        stays reusable in the prefix cache. Slots still inside an
        admission job are left to finish their (bounded) chunks: the
        commit checks the cancel flag at activation, and an expired
        request is reaped by the next sweep. Expired PENDING requests
        are reaped here too (pure host state), so a deadline is honored
        even if the request never reaches a slot. The expiry clock is
        read lazily: zero reads per iteration when no live request
        carries a deadline."""
        job_slots = {job.slot for job in self._jobs}
        marked = {sid for sid, _, _ in self._reaped}
        now = None
        for sid, slot in enumerate(self._slots):
            if slot is None or sid in job_slots or sid in marked:
                continue
            if slot.req._cancel.is_set():
                self.active[sid] = False
                self._reaped.append((sid, slot, "cancelled"))
                continue
            if slot.req.deadline is not None:
                if now is None:
                    now = time.perf_counter()
                if now > slot.req.deadline:
                    self.active[sid] = False
                    self._reaped.append((sid, slot, "deadline"))
        self._expire_pending(now)

    def _apply_reaps(self) -> None:
        """Deferred-release half of `_sweep`, run just after the commit
        (with nothing in flight, right after the sweep): the marked
        slots' pages are fully committed KV now, so they release
        through the normal content-keyed path (reusable in the prefix
        cache) and the requests complete."""
        if not self._reaped:
            return
        reaped, self._reaped = self._reaped, []
        for sid, slot, reason in reaped:
            if self._slots[sid] is not slot:
                continue  # already torn down (failure path)
            s = self._release_slot(sid, self._committed(sid))
            s.req.finish_reason = reason
            self._complete_later(s.req)

    def _drain_handoff_ready(self) -> None:
        """Fire the queued disaggregation handoff callbacks — OUTSIDE
        `_step_lock`, on the scheduler thread, right after the step
        that activated them: the callback (the ReplicatedRouter's
        hook) enqueues a `migrate_export`, which needs the step lock
        this thread just released. Each request's callback fires at
        most once; a request that finished or cancelled between
        activation and here is skipped. Callback exceptions are the
        router's problem, never the scheduler's — the request keeps
        decoding locally either way (the handoff is an optimization,
        not a correctness event)."""
        # analysis: allow[lock-discipline] scheduler-thread-only list:
        # appended inside the step (under _step_lock) and drained here
        # on the SAME thread right after the lock releases — no second
        # accessor exists, the guard inference is a false positive
        if not self._handoff_ready:
            return
        # analysis: allow[lock-discipline] same scheduler-thread-only
        # swap as above
        ready, self._handoff_ready = self._handoff_ready, []
        for req in ready:
            h = req._handoff
            req._handoff = None  # at most once
            if (h is None or req._done.is_set()
                    or req._cancel.is_set()):
                continue
            try:
                h(req)
            except Exception:  # noqa: BLE001 — router-side failure
                pass

    # -- scheduler ----------------------------------------------------------

    def _expire_pending(self, now: float | None) -> None:
        """Reap deadline-expired PENDING requests (pure host-queue
        state, safe whether or not a dispatch is in flight). The expiry
        clock stays lazy: zero reads when nothing pending carries a
        deadline."""
        with self._lock:
            expired = []
            if any(r.deadline is not None for r in self._pending):
                if now is None:
                    now = time.perf_counter()
                keep = collections.deque()
                for r in self._pending:
                    if r.deadline is not None and now > r.deadline:
                        expired.append(r)
                    else:
                        keep.append(r)
                self._pending = keep
            for r in expired:
                if self.qos is not None:
                    self.qos.on_pending_removed(r.tenant)
        for r in expired:
            r.finish_reason = "deadline"
            self._complete(r)

    def step(self) -> int:
        """One scheduler iteration: plan the next dispatch, launch it,
        commit the one before it. Nothing else dispatches. Thread-safe.

        With a dispatch in flight: plan iteration N+1 (sweep marks,
        QoS/DRR admission, the whole numpy build) WHILE the device runs
        iteration N, then LAUNCH N+1 onto the device's queue behind N,
        sync+commit N, and deliver N's tokens and completions to their
        clients — one fused dispatch and one device_get per step, and
        the chip goes from N to N+1 with no host in between: for the
        length of the commit two dispatches are uncommitted (`_ahead`
        behind `_inflight`), at plan time one, as the planned frame
        assumes. The order is the plan's, chosen per iteration from
        what it shows (`_launch_waits`): where the launch needs what
        only the commit knows (draft tokens in play, a constrained
        row, a hand-off to prefetch) the step commits N, patches from
        the ledger, launches N+1 and delivers. The same two functions
        either way; each dispatch's flight record says which
        (`launch_ahead`, else `launch_waits`).

        With nothing in flight (cold start, post-drain, famine) the
        step FILLS the pipeline: it releases what the sweep marked,
        plans against the committed ledger (such a plan may preempt,
        `_plan_iteration`) and launches; the next step commits it. A
        fill step commits nothing, so its flight record holds what the
        step itself did (`fill`, its phases, page flow, preemptions,
        what the plan staged and the launch handed over) and no token
        or gap field; the launched program's own record is written by
        the step that commits it, as every program's is.

        The injected-fault "dispatch" site is checked once a step that
        has something to dispatch, before the plan: a raise there
        crashes the iteration before any device work, the way a
        poisoned program would (serve_forever catches, `_fail_all`
        drops the in-flight futures and unblocks every waiter, the
        router's breaker/retry path takes it from there).

        With the iteration profiler enabled (the default) every phase
        boundary is stamped and the iteration's t0 is the profiler's,
        so a busy flight record's `duration_ms` covers the WHOLE
        iteration. Handoff callbacks queued by the step fire after the
        lock releases (`_drain_handoff_ready`)."""
        with self._step_lock:
            self.tracer.step_start()
            prof = self._profiler
            try:
                if self._faults is not None:
                    # injected host stall (the scheduler thread pays
                    # it like a slow host/device round) and the wedge
                    # site: block holding _step_lock until stop() —
                    # the scenario _fail_all's bounded acquire covers
                    self._faults.maybe_stall()
                    self._faults.maybe_wedge(self._stop)
                al = self.allocator
                # page-flow baseline for this iteration's flight record
                # (sweep + admission allocate/release too, so capture
                # before both) + the telemetry recency stamp: the
                # flight index THIS iteration will get if it is busy
                # (the profiler's trace events carry it too)
                al.telemetry.iteration = self.flight.iterations + 1
                if prof is not None:
                    prof.begin(al.telemetry.iteration)
                c0 = (al.pages_allocated, al.pages_released,
                      al.evictions)
                fill = self._inflight is None
                self._sweep()
                if fill:
                    self._apply_reaps()
                if prof is not None:
                    prof.enter("admission")
                self._start_admissions()
                p0 = self.preemptions
                t0 = prof.t0 if prof is not None else time.perf_counter()
                if fill:
                    self._iter_stats = {}
                busy = not fill or bool(self._jobs) or bool(
                    self.active.any())
                try:
                    if busy:
                        if self._faults is not None:
                            self._faults.check("dispatch")
                        plan = self._plan_iteration()
                        if fill:
                            if plan is not None:
                                self._launch_plan(plan)
                                self._iter_stats["fill"] = True
                        elif plan is not None and plan.waits is None:
                            # N+1 onto the device's queue behind N,
                            # then N home: a launch that raised still
                            # commits N
                            try:
                                self._launch_plan(plan)
                            finally:
                                self._commit_inflight()
                        else:
                            self._commit_inflight()
                            if plan is not None:
                                self._launch_plan(plan)
                finally:
                    # after the launch, not before: the streaming
                    # threads these calls wake run under the next
                    # program. A launch that raised still leaves the
                    # committed tokens with their clients before
                    # _fail_all ends the requests
                    if busy and prof is not None:
                        prof.enter("deliver")
                    self._deliver()
                self._record_iteration(t0, p0, c0)
                if self._iter_stats:
                    self.last_busy_ts = self._iter_stats["ts"]
                else:
                    self.idle_iterations += 1
                ret = self.num_active
            finally:
                self.tracer.step_end()
        self._drain_handoff_ready()
        return ret

    def _stage_decode_spans(self, live_ids, n_rounds: int,
                            out: list | None = None) -> None:
        """Stage one decode_segment span per traced live slot for this
        iteration's decode dispatch (stamped with the shared iteration
        frame by _record_iteration). `out` overrides the destination
        (a launch-ahead plan's staged spans)."""
        if out is None:
            out = self._iter_spans
        for sid in live_ids:
            s = self._slots[int(sid)]
            if s is not None and s.req.trace is not None:
                out.append(
                    (s.req, "decode_segment",
                     {"slot": int(sid), "rounds": n_rounds}))

    def _record_iteration(self, t0: float, p0: int,
                          c0: tuple[int, int, int]) -> None:
        """Flight-recorder epilogue for one busy scheduler iteration:
        `_iter_stats` is the committed dispatch's, with the token split
        its plan staged (a fill step's own: `step`); this adds the
        budget/occupancy derived fields and appends ONE ring-buffer
        record. Idle iterations (nothing dispatched) leave
        `_iter_stats` empty and record nothing, so the ring holds the
        last N *busy* iterations.

        Tracing epilogue too: spans the plan staged for the committed
        dispatch are stamped with the SAME (t0, now) frame and the
        flight-recorder iteration index — the cross-link that lets a
        slow span answer "what else was the scheduler doing that
        iteration" in one hop, at the cost of zero extra clock reads
        beyond the duration_ms one the recorder already pays."""
        spans, self._iter_spans = self._iter_spans, []
        st = self._iter_stats
        prof = self._profiler
        if not st:
            if prof is not None:
                prof.close()
            return
        if prof is not None:
            # everything from here to the closing clock read (the
            # stats assembly below, fair-share scans included)
            prof.enter("epilogue")
        decode_tokens = st.get("decode_tokens", 0)
        st["tokens_scheduled"] = decode_tokens + st.get("prefill_tokens", 0)
        st["budget_tokens"] = self.mixed_token_budget
        st["budget_utilization"] = (st["tokens_scheduled"]
                                    / self.mixed_token_budget)
        # every preemption requeues its request at the queue front, so
        # this single field IS both the preemption and the requeue count
        st["preemptions"] = self.preemptions - p0
        if self.qos is not None:
            # per-tenant fair-share gauge (generated share over
            # weighted entitlement, 1.0 = fair) — the post-mortem view
            # of WHO the iteration's tokens went to
            st["tenant_fair_share"] = {
                k: round(v, 4)
                for k, v in self.qos.fair_shares().items()}
        st["n_jobs"] = len(self._jobs)
        st["pending"] = self.num_pending
        # what the committed program was (`_stage_program_kind`); a
        # step that committed none joined and sorted nothing
        st.setdefault("joined", False)
        st.setdefault("grouped", False)
        # host arrays handed to the device in this iteration's `launch`
        # phase (0: it launched nothing)
        st["launch_h2d"] = self._iter_launch_h2d
        self._iter_launch_h2d = 0
        # what THIS step's planning staged onto the device for the
        # program it launched (0: it planned nothing), and the time of
        # that block inside `build`
        st["plan_h2d"] = self._iter_plan_h2d
        self._iter_plan_h2d = 0
        if self._iter_stage_ms is not None:
            st["stage_ms"] = self._iter_stage_ms
            self._iter_stage_ms = None
        # KV-pool telemetry (joins phases_ms in the record): the
        # iteration's page flow (deltas against the step-start
        # baseline — sweep/admission included) and the occupancy split
        # at record time. Plain int reads/len()s on state this thread
        # owns; the evictable-fraction histogram is the HBM-pressure
        # watermark /metrics carries.
        al = self.allocator
        st["pages_allocated"] = al.pages_allocated - c0[0]
        st["pages_released"] = al.pages_released - c0[1]
        st["pages_evicted"] = al.evictions - c0[2]
        free, cached = len(al._free), len(al._evictable)
        st["pool_free"] = free
        st["pool_cached"] = cached
        st["pool_active"] = al.num_pages - free - cached
        if self.window_pool is not None:
            # the window kind beside the full kind: `pool_*` and
            # `pages_total` go on describing the full kind alone
            st["window_pool_active"] = self.window_pool.active
            st["window_num_pages"] = self.window_pool.num_pages
            st.setdefault("pages_returned", 0)
        frac = (free + cached) / max(al.num_pages, 1)
        st["pool_evictable_frac"] = frac
        h = self._cache_hists.get("evictable_frac")
        if h is not None:
            h.observe(frac)
        # live-migration flow (deltas accrued since the last busy
        # record): requests resumed here / evacuated from here — only
        # present on records that saw one, so unmigrated records stay
        # byte-identical
        mig_in, mig_out = self._migration.drain_flight_deltas()
        if mig_in or mig_out:
            st["migrated_in"] = mig_in
            st["migrated_out"] = mig_out
        if prof is not None:
            now = prof.end()
            phases = prof.phases_ms()
            st["t_start"] = t0
            st["phases_ms"] = phases
            st["duration_ms"] = (now - t0) * 1e3
            if prof.between_ms is not None:
                # since the previous busy step's closing stamp: with
                # `duration_ms` the scheduler's period, in no phase
                st["between_ms"] = prof.between_ms
            overlapped = bool(st.get("overlap"))
            hists = self._phase_hists
            if overlapped:
                # the step committed a program (a fill waited on none
                # and has no gap fields)
                st.update(derive_gap_fields(phases, st["duration_ms"]))
                # sweep/admission/build ran under the in-flight
                # device program and deliver under the one launched
                # since: fold them into the `overlap` series
                # so the histogram-derived host-gap stays honest (the
                # fine split survives in this flight record)
                hists["overlap"].observe(
                    sum(phases.get(p, 0.0) for p in OVERLAP_PHASES))
                for p, v in phases.items():
                    if p not in OVERLAP_PHASES:
                        hists[p].observe(v)
            else:
                for p, v in phases.items():
                    hists[p].observe(v)
        else:
            now = time.perf_counter()
            st["duration_ms"] = (now - t0) * 1e3
        if self._iter_launch_ts is not None:
            # the launch performed THIS step (the Perfetto inflight
            # track pairs it with the NEXT record's residual device
            # wait)
            st["t_launch"] = self._iter_launch_ts
            self._iter_launch_ts = None
        if self._brownout is not None:
            # overload grading over signals this record already owns;
            # the pending head's age is the queue-growth signal (one
            # deque peek under the state lock)
            with self._lock:
                head = self._pending[0] if self._pending else None
                age = (0.0 if head is None or head.submit_time is None
                       else now - head.submit_time)
            st["brownout_level"] = self._brownout.observe(
                pending_age_s=age,
                budget_utilization=st["budget_utilization"],
                host_gap_frac=st.get("host_gap_frac"))
        if self._anomaly is not None:
            # watchdog feed: every signal is a field this record
            # already owns (the epilogue clock mark, int deltas) —
            # zero extra dispatches/syncs/clock reads
            hb = self._anomaly_cache_base
            cur = (al.prefix_hit_pages, al.prefix_miss_pages)
            self._anomaly_cache_base = cur
            hit_d = cur[0] - hb[0]
            fired = self._anomaly.observe_iteration(
                now=now, host_gap_frac=st.get("host_gap_frac"),
                pending=st["pending"],
                preempt_delta=st["preemptions"],
                cache_lookup_delta=hit_d + (cur[1] - hb[1]),
                cache_hit_delta=hit_d,
                overload_level=st.get("brownout_level", 0))
            if fired:
                self._on_anomaly(fired)
        st["ts"] = time.time()
        self.flight.record(**st)
        if spans:
            idx = self.flight.iterations
            for req, name, tags in spans:
                req.trace.add_span(name, t0, now, iteration=idx,
                                   **tags)

    # -- observability ------------------------------------------------------

    def _collect_metrics(self) -> None:
        """Scrape-path mirror of host scheduler + allocator state into
        the registry (never touched on the serving hot path)."""
        reg = self.metrics.registry
        reg.gauge("active_slots",
                  "Requests currently decoding").set(self.num_active)
        reg.gauge("pending_requests",
                  "Queued requests awaiting admission").set(
                      self.num_pending)
        reg.gauge("admission_jobs",
                  "Chunked-prefill admission jobs in flight").set(
                      # analysis: allow[lock-discipline] scrape-path
                      # len() of a GIL-atomic list; a gauge may lag
                      # the iteration that is mutating it
                      len(self._jobs))
        reg.counter("tokens_emitted_total",
                    "Lifetime generated tokens").set_total(
                        self.tokens_emitted)
        reg.counter("decode_rounds_total",
                    "Lifetime decode dispatch rounds").set_total(
                        self.decode_rounds)
        reg.counter("decode_tokens_committed_total",
                    "Lifetime tokens committed by decode rounds"
                    ).set_total(self.decode_tokens_committed)
        reg.counter("preemptions_total",
                    "Lifetime on-demand-paging preemptions").set_total(
                        self.preemptions)
        # idle-vs-dead disambiguation: an idle scheduler keeps
        # incrementing the counter while the gauge ages; a dead one
        # freezes both
        reg.counter("idle_iterations_total",
                    "step() calls that dispatched nothing").set_total(
                        self.idle_iterations)
        reg.gauge("last_busy_ts",
                  "Unix time of the last busy iteration (0 until the "
                  "first)").set(self.last_busy_ts)
        reg.counter("spec_tokens_drafted_total",
                    "Draft tokens proposed on committing rows' behalf"
                    ).set_total(self.spec_tokens_drafted)
        reg.counter("spec_tokens_accepted_total",
                    "Draft tokens accepted and committed"
                    ).set_total(self.spec_tokens_accepted)
        rate = (self.spec_control.accept_rate()
                if self.spec_control is not None else
                self.spec_tokens_accepted
                / max(self.spec_tokens_drafted, 1))
        reg.gauge("spec_accept_rate",
                  "Rolling speculative accept rate (accepted/drafted "
                  "per committed round; lifetime ratio without the "
                  "adaptive controller)").set(rate)
        # failure-domain observability (inference/faults.py): the
        # families register unconditionally (zeros when nothing is
        # configured) so the docs drift check — and dashboards — see
        # them before the first incident, which is the whole point
        reg.counter("unserialized_teardown_total",
                    "_fail_all teardowns that proceeded after the "
                    "bounded _step_lock acquire timed out (slot state "
                    "torn down against a wedged scheduler)").set_total(
                        self.unserialized_teardowns)
        from cloud_server_tpu.inference.faults import SITES
        from cloud_server_tpu.inference.qos import PRIORITY_CLASSES
        fstats = (self._faults.stats() if self._faults is not None
                  else None)
        for site in SITES:
            reg.counter("faults_injected_total",
                        "Deliberately injected faults that fired, "
                        "per site (inference/faults.py; zero without "
                        "an armed FaultPlan)",
                        labels={"site": site}).set_total(
                            0 if fstats is None
                            else fstats["fired"][site])
        bstats = (self._brownout.stats() if self._brownout is not None
                  else None)
        reg.gauge("brownout_level",
                  "Current overload brownout level (0 healthy, "
                  "1 shedding best_effort, 2 shedding batch too)").set(
                      0 if bstats is None else bstats["level"])
        for cls in PRIORITY_CLASSES:
            reg.counter("brownout_shed_total",
                        "Admissions refused by overload brownout, per "
                        "priority class (429 with jittered "
                        "Retry-After)",
                        labels={"class": cls}).set_total(
                            0 if bstats is None
                            else bstats["shed_total"].get(cls, 0))
        # live-migration counters (inference/migration.py): same
        # unconditional-registration rule as the fault families —
        # export and import halves each count one operation
        mstats = self._migration.stats()
        reg.counter("migrations_started_total",
                    "Live-migration operations started (request "
                    "exports + imports; inference/migration.py)"
                    ).set_total(mstats["started"])
        reg.counter("migrations_completed_total",
                    "Live-migration operations completed (the "
                    "request left this replica with its state, or "
                    "resumed here at the exact next token)"
                    ).set_total(mstats["completed"])
        reg.counter("migrations_failed_total",
                    "Live-migration operations that failed — the "
                    "request fell back to fail-fast "
                    "(`retriable: false`) or to the normal drain "
                    "wait").set_total(mstats["failed"])
        stats = self.allocator.stats()
        reg.gauge("pages_total",
                  "KV page pool size").set(stats.pages_total)
        reg.gauge("pages_free",
                  "Unallocated KV pages").set(stats.pages_free)
        reg.gauge("pages_cached",
                  "Refcount-0 prefix-cached KV pages (evictable)").set(
                      stats.pages_cached)
        reg.gauge("pages_active",
                  "KV pages referenced by live slots").set(
                      stats.pages_active)
        reg.counter("prefix_hit_pages_total",
                    "Admission pages served from the radix prefix cache"
                    ).set_total(stats.prefix_hit_pages)
        reg.counter("prefix_miss_pages_total",
                    "Admission pages that missed the radix prefix cache"
                    ).set_total(stats.prefix_miss_pages)
        reg.counter("prefix_evictions_total",
                    "Prefix-cache pages evicted under memory pressure"
                    ).set_total(stats.evictions)
        reg.counter("prefix_hit_tokens_total",
                    "Token value of prefix-cache page hits (prefill "
                    "work the cache absorbed)").set_total(
                        stats.hits_tokens)
        reg.counter("pages_allocated_total",
                    "Fresh KV pages handed out by the allocator"
                    ).set_total(self.allocator.pages_allocated)
        reg.counter("pages_released_total",
                    "KV pages whose refcount reached zero (cached or "
                    "freed)").set_total(self.allocator.pages_released)
        # the window kind's pool of a model with sliding-window layers;
        # always registered (0 for a model of one kind)
        wp = self.window_pool
        reg.gauge("window_pages_active",
                  "Pages of the window kind's pool held by slots (a "
                  "model with sliding-window layers; given back behind "
                  "the window at every commit)"
                  ).set(0 if wp is None else wp.active)
        reg.counter("window_pages_returned_total",
                    "Window-kind pages given back to their pool"
                    ).set_total(0 if wp is None else wp.pages_returned)
        # a router balanced by a bias (a sigmoid router): how even its
        # load was in the newest step read back; always registered (0 for
        # any other model)
        # analysis: allow[lock-discipline] racy-by-design monitoring: the
        # dict is swapped whole by `_count_assign`, never updated in place
        load = self._assign_last
        reg.gauge("expert_assign_total",
                  "Router assignments of the newest step read back, over "
                  "all expert layers (a model whose router is balanced "
                  "by a bias)").set(load.get("assign_total", 0))
        reg.gauge("expert_assign_peak",
                  "The most assignments any one expert of any layer "
                  "received in a walk of the newest step read back"
                  ).set(load.get("assign_peak", 0))
        reg.gauge("expert_assign_rows_computed",
                  "Rows the sorted dispatch's way in computes for the "
                  "newest step's assignments: each expert's count rounded "
                  "up to the kernel's sub-tiles, over all expert layers"
                  ).set(load.get("assign_rows_computed", 0))
        reg.gauge("cache_namespaces",
                  "Distinct KV namespaces (base model + LoRA "
                  "adapters) that touched the prefix cache").set(
                      stats.namespaces)
        if self.qos is not None:
            # per-tenant cache attribution mirrors, following the QoS
            # cardinality rule: labeled series exist only when a
            # TenantRegistry bounds the tenant set (the ledger's keys
            # are names the registry already resolved). Eager over the
            # registry's configured tenants — the families exist (and
            # the docs drift check sees them) before any traffic.
            tstats = self.allocator.telemetry.tenant_stats()
            for name in set(self.qos.tenants()) | set(tstats):
                led = tstats.get(name, {})
                lbl = {"tenant": name}
                reg.counter(
                    "tenant_prefix_hit_tokens_total",
                    "Prompt tokens served from prefix-cache hits at "
                    "lookup, per tenant", labels=lbl).set_total(
                        led.get("hit_tokens", 0))
                reg.counter(
                    "tenant_prefix_miss_tokens_total",
                    "Prompt tokens the cache could not serve "
                    "(freshly prefilled, tail included), per tenant",
                    labels=lbl).set_total(
                        led.get("miss_tokens", 0))
                reg.counter(
                    "tenant_prefix_evicted_tokens_total",
                    "Token value of the tenant's cached chains "
                    "evicted under memory pressure", labels=lbl
                    ).set_total(
                        led.get("evicted_pages", 0) * self.page_size)
                reg.counter(
                    "tenant_prefix_saved_tokens_total",
                    "Prefill tokens the tenant actually skipped at "
                    "admission (realized savings; diverges from hit "
                    "tokens exactly when page-famine retries wasted "
                    "lookups)", labels=lbl).set_total(
                        led.get("saved_tokens", 0))
                reg.gauge(
                    "tenant_cache_pages_held",
                    "KV pages currently referenced by the tenant's "
                    "slots (shared pages count once per holder)",
                    labels=lbl).set(led.get("pages_held", 0))
            self.qos.mirror_metrics(reg)
        if self.slo is not None:
            self.slo.mirror_metrics(reg)
        # anomaly watchdog + tail retention: families registered
        # unconditionally (zeros) so the /metrics catalog is stable —
        # the faults_injected_total pattern
        from cloud_server_tpu.inference.anomaly import RULES
        astats = (self._anomaly.stats(events=0)
                  if self._anomaly is not None else None)
        for rule in RULES:
            reg.gauge("anomaly_active",
                      "1 while the watchdog rule's anomaly window is "
                      "open (inference/anomaly.py; zero without an "
                      "anomaly config)",
                      labels={"rule": rule}).set(
                          0.0 if astats is None
                          else float(rule in astats["active"]))
            reg.counter("anomalies_total",
                        "Watchdog rule activations (one per anomaly "
                        "window opened, per rule)",
                        labels={"rule": rule}).set_total(
                            0 if astats is None
                            else astats["fired_total"][rule])
        rec = self.trace_recorder
        tstats = (rec.tail_stats() if rec is not None
                  and rec.tail_capacity > 0 else None)
        reg.counter("trace_tail_retained_total",
                    "Head-unsampled finished requests whose span "
                    "trees the tail-retention predicate kept"
                    ).set_total(0 if tstats is None else
                                sum(tstats["retained_total"].values()))
        reg.counter("trace_tail_evicted_total",
                    "Tail-retained trees evicted from the bounded "
                    "tail ring").set_total(
                        0 if tstats is None
                        else tstats["evicted_total"])
        reg.counter("anomaly_bundles_total",
                    "Forensic debug bundles auto-captured on anomaly "
                    "activation (bundle_on_anomaly)").set_total(
                        self._bundles_captured)

    def metrics_snapshot(self) -> dict:
        """Mergeable snapshot of every registered metric (the /metrics
        and /stats source; ReplicatedRouter merges these across
        replicas)."""
        return self.metrics.registry.snapshot()

    def iteration_profile_stats(self) -> dict | None:
        """The /stats `iteration_profile` summary: per-phase
        count/mean/p50/p99 ms + the aggregate host-gap fraction,
        computed from the per-phase histograms (so behind the router
        the same helper over the fleet-merged snapshot reports true
        fleet percentiles). None with profiling disabled."""
        from cloud_server_tpu.inference.iteration_profile import (
            profile_summary)
        return profile_summary(self.metrics_snapshot())

    def speculation_stats(self) -> dict:
        """The /stats `speculation` summary. Counts are fleet-mergeable
        (ReplicatedRouter sums them and recomputes `accept_rate` from
        the merged totals, like `tenant_fair_share`); `draft_lens` is
        this server's live per-slot view and is dropped by the fleet
        merge."""
        out = {
            "enabled": self.spec_drafts > 0,
            "source": ("off" if self.spec_drafts <= 0 else
                       "draft_model" if self.draft_cfg is not None
                       else "ngram"),
            "max_drafts": self.spec_drafts,
            "adaptive": self.spec_control is not None,
            "tokens_drafted": self.spec_tokens_drafted,
            "tokens_accepted": self.spec_tokens_accepted,
            "accept_rate": (self.spec_tokens_accepted
                            / max(self.spec_tokens_drafted, 1)),
        }
        if self.spec_control is not None:
            out["rolling_accept_rate"] = self.spec_control.accept_rate()
            out["draft_lens"] = {
                str(k): v
                for k, v in self.spec_control.draft_lengths().items()}
        return out

    def cache_stats(self) -> dict:
        """The /stats `cache` block and GET /debug/cache source: pool
        occupancy, lifetime prefix hit/miss/eviction counts with the
        hit rate, the per-tenant attribution table, the hot-prefix
        top-K sketch, and the eviction forensics (recent ring +
        victim×forcer matrix). Counts are fleet-mergeable —
        `ReplicatedRouter.cache_stats()` sums them and recomputes
        `hit_rate` / `evictable_frac` from the merged totals via
        `cache_telemetry.merge_cache_stats` (the `tenant_fair_share`
        rule: ratios never add). Scrape-path only; same lock-free
        monitoring reads as `prefix_cache_stats` (see its audit
        note)."""
        from cloud_server_tpu.inference.cache_telemetry import hit_rate
        s = self.allocator.stats()
        tel = self.allocator.telemetry
        tstats = tel.tenant_stats()
        # full-page-granular hit/miss (the ledger counts every
        # un-shared full prompt page as a miss, where the allocator's
        # walk counter records one break per walk) — so `hit_rate`
        # here is the true page hit rate, the number item 3's
        # prefix-aware routing scores against
        hit_pages = sum(led["hit_pages"] for led in tstats.values())
        miss_pages = sum(led["miss_pages"] for led in tstats.values())
        return {
            "pool": {
                "pages_total": s.pages_total,
                "pages_free": s.pages_free,
                "pages_cached": s.pages_cached,
                "pages_active": s.pages_active,
                "evictable_frac": ((s.pages_free + s.pages_cached)
                                   / max(s.pages_total, 1)),
                # the window kind's pool, of a model that has one; the
                # keys above describe the full kind alone
                **({} if self.window_pool is None else {
                    "window_pool_active": self.window_pool.active,
                    "window_num_pages": self.window_pool.num_pages,
                    "window_pages_per_slot": self.window_pages_per_slot}),
                # the per-slot states of a model with a mixer: a state for
                # every slot, held whether the slot is or not
                **({} if not self.cfg.ssm_heads else {
                    "ssm_state_bytes": self.ssm_state_bytes}),
            },
            "prefix": {
                "hit_pages": hit_pages,
                "miss_pages": miss_pages,
                "hit_tokens": s.hits_tokens,
                "evictions": s.evictions,
                "hit_rate": hit_rate(hit_pages, miss_pages),
            },
            "namespaces": s.namespaces,
            # the SAME snapshot the hit/miss aggregate above came
            # from — a second tenant_stats() could observe newer walks
            # and ship a payload whose tenants table contradicts its
            # own prefix block
            "tenants": tstats,
            "top_prefixes": tel.top_prefixes(),
            "recent_evictions": tel.recent_evictions(64),
            "eviction_matrix": tel.eviction_matrix(),
        }

    def overlap_stats(self) -> dict:
        """The /stats `overlap` block: the live pipeline depth and
        `launch_ahead_share`. Scrape path only."""
        ahead = [r["launch_ahead"] for r in self.flight.window()
                 if "launch_ahead" in r]
        return {
            # analysis: allow[lock-discipline] racy-by-design
            # monitoring read; staleness bounded by one iteration
            "inflight_depth": 0 if self._inflight is None else 1,
            # of the dispatches in the flight window, the % that went
            # onto the device's queue ahead of the commit before
            # them (the others' records say why not:
            # `launch_waits`); nothing before the first
            **({"launch_ahead_share": 100.0 * sum(ahead) / len(ahead)}
               if ahead else {}),
        }

    def brownout_stats(self) -> dict | None:
        """The /stats `brownout` block (level, signal EWMAs vs
        thresholds, per-class shed counts); None with brownout
        disabled. Scrape path only."""
        return None if self._brownout is None else self._brownout.stats()

    def fault_stats(self) -> dict | None:
        """Per-site injected-fault hit/fired counts (the /stats
        `faults` block); None with no FaultPlan. Scrape path only."""
        return None if self._faults is None else self._faults.stats()

    def migration_stats(self) -> dict:
        """Live-migration counters (the /stats `migration` block):
        export/import starts, completions, failures, tokens salvaged,
        KV pages moved. Counts are fleet-mergeable —
        `ReplicatedRouter.migration_stats()` sums them and recomputes
        the success rate from the merged totals. Scrape path only."""
        return self._migration.stats()

    @property
    def ready(self) -> bool:
        """Readiness (vs the liveness /healthz always reported): False
        while draining or stopped, so load balancers — and the
        ReplicatedRouter's placement — stop routing new work here
        while in-flight requests finish."""
        # analysis: allow[lock-discipline] benign racy read: a stale
        # verdict delays placement by one pick; taking _lock here would
        # put a contended acquire on every router _pick
        return not self._draining and not self._stop.is_set()

    def lookup_trace(self, request_id: str) -> dict | None:
        """Span tree for one sampled request id (live or retained),
        else None (unsampled, evicted, or tracing disabled)."""
        rec = self.trace_recorder
        return None if rec is None else rec.lookup(request_id)

    def trace_trees(self, n: int | None = None) -> list[dict]:
        """Span trees of the sampled ring + live requests (the
        /traces export source)."""
        rec = self.trace_recorder
        return [] if rec is None else rec.trees(n)

    def slo_report(self) -> dict | None:
        """Per-class SLO attainment + burn rates (the /slo source;
        ReplicatedRouter merges these across replicas). None when no
        SLO config is set."""
        return None if self.slo is None else self.slo.report()

    def flight_window(self, n: int | None = None) -> list[dict]:
        """The last `n` (default: all retained) per-iteration flight
        recorder records, oldest first."""
        return self.flight.window(n)

    def request_trace(self, n_steps: int,
                      logdir: str | os.PathLike) -> None:
        """Arm the /debug/trace capture: the next `n_steps` scheduler
        iterations run inside utils.tracing.capture_trace(logdir)."""
        self.tracer.request(n_steps, logdir)

    def anomaly_stats(self) -> dict | None:
        """The /stats `anomaly` block (active windows, per-rule
        activation counts, the bounded event ring); None with no
        watchdog. Scrape path only."""
        return None if self._anomaly is None else self._anomaly.stats()

    def anomaly_events(self, n: int | None = None) -> list[dict]:
        """Watchdog event dicts for the Perfetto marker track; empty
        with no watchdog."""
        return ([] if self._anomaly is None
                else self._anomaly.events(n))

    def tail_trace_trees(self, n: int | None = None) -> list[dict]:
        """Span trees of the tail-retained ring (anomalous requests
        kept past head sampling); empty with tail retention off."""
        rec = self.trace_recorder
        return ([] if rec is None or rec.tail_capacity <= 0
                else rec.tail_trees(n))

    def tail_trace_stats(self) -> dict | None:
        """The /stats tail-retention block; None with tail retention
        off."""
        rec = self.trace_recorder
        return (None if rec is None or rec.tail_capacity <= 0
                else rec.tail_stats())

    def _on_anomaly(self, fired) -> None:
        """Activation-edge reactions (rare by construction): snapshot
        a forensic bundle into the bounded ring when
        `bundle_on_anomaly` is set, and arm the existing /debug/trace
        capture machinery when the watchdog config asks for one.
        Forensics must never take the scheduler down — arming races
        (a capture already running) and bundle failures are
        swallowed."""
        if self._bundle_on_anomaly:
            try:
                self._bundles.append(self.debug_bundle(
                    trigger="anomaly:" + ",".join(fired)))
                self._bundles_captured += 1
            except Exception:  # noqa: BLE001 — see docstring
                pass
        wd = self._anomaly
        if wd is not None and wd.capture_iters > 0 and wd.capture_dir:
            try:
                self.tracer.request(wd.capture_iters, wd.capture_dir)
            except ValueError:
                pass  # a capture is already armed/running

    def debug_bundle(self, n: int = 64, *,
                     trigger: str = "manual") -> dict:
        """One-shot forensic artifact (the GET /debug/bundle payload):
        everything an incident post-mortem would otherwise stitch
        from six endpoints — metrics, the scheduler flight window,
        retained + tail span trees, cache/brownout/migration state,
        SLO report, fault/anomaly state — as one JSON-ready dict.
        `n` bounds the ring exports (flight records and trace trees).
        Scrape path only (auto-capture calls it once per activation
        edge, which is rare by the watchdog's hysteresis)."""
        return {
            "schema": "cloud_server.debug_bundle/v1",
            "trigger": trigger,
            "ts": time.time(),
            "anomaly": self.anomaly_stats(),
            "metrics": self.metrics_snapshot(),
            "profile": self.iteration_profile_stats(),
            "flight": self.flight_window(n),
            "traces": self.trace_trees(n),
            "tail_traces": self.tail_trace_trees(n),
            "tail_retention": self.tail_trace_stats(),
            "slo": self.slo_report(),
            "cache": self.cache_stats(),
            "brownout": self.brownout_stats(),
            "migration": self.migration_stats(),
            "faults": self.fault_stats(),
            "overlap": self.overlap_stats(),
        }

    def debug_bundles(self, n: int | None = None) -> list[dict]:
        """The bounded ring of auto-captured bundles (oldest first;
        `n` bounds from the newest end, n <= 0 means none)."""
        if n is not None and n <= 0:
            return []
        bundles = list(self._bundles)
        return bundles if n is None else bundles[-n:]

    def run_until_idle(self) -> None:
        # analysis: allow[lock-discipline] idle-polling bool() of a
        # GIL-atomic list; step() below observes the exact state
        while self.num_pending or self.num_active or self._jobs:
            self.step()

    # -- live migration -----------------------------------------------------

    def _refuse_slot_state(self, mechanism: str) -> None:
        """Raise for a mechanism that carries a request from one server
        to another as pages of keys and values of the full kind, on a
        model whose slots hold what it cannot carry: pages of a second
        kind, latent entries, or a state beside the pages."""
        if self.cfg.latent_dim:
            held = ("its pages hold latent entries, which nothing on the "
                    "other side could read")
        elif self.window_pool is not None:
            held = ("it also has sliding-window layers, whose pages would "
                    "be left behind")
        elif self.cfg.ssm_heads:
            held = ("a slot also holds a recurrent state beside its pages, "
                    "which has no export and no import")
        else:
            return
        raise ValueError(
            f"{mechanism} moves pages of keys and values of the full kind "
            f"only and this model's slots hold more: {held}; not supported "
            "for such a model")

    def migrate_export(self, req: Request, *, reason: str = "failover",
                       evacuate: bool = True):
        """Snapshot one live (slot or pending) request for migration
        to another replica (inference/migration.py).

        Runs at the scheduler's sanctioned commit point: under
        `_step_lock`, with any in-flight dispatch committed first, so
        the host token stream, the KV watermark, and the grammar
        position are exact. The chain's committed full pages ride
        along via the export's one sanctioned `device_get` (off the
        plan path, so DD5 holds — see analysis/dispatch.py's
        sanctioned-sync inventory).

        With `evacuate=True` (default) the request leaves this server
        atomically with the snapshot: its slot releases through the
        normal content-keyed path (the committed KV stays reusable in
        the local prefix cache) and NOBODY completes the handle — the
        caller re-admits the snapshot elsewhere and mirrors the
        outcome back. A request mid-admission (chunked prefill still
        dispatching) is not exportable and raises RuntimeError; the
        caller lets it finish or fail normally."""
        self._refuse_slot_state("live migration (migrate_export)")
        led = self._migration
        led.record_export_start()
        try:
            if self._faults is not None:
                self._faults.check("migrate_export")
            with self._step_lock:
                if self._inflight is not None:
                    # drain the pipeline first: the in-flight
                    # dispatch's tokens belong to the stream being
                    # exported, and reach its client before the
                    # destination streams the next one
                    self._commit_inflight()
                    self._deliver()
                snap, sid, committed = self._export_request_locked(
                    req, reason)
                if evacuate:
                    self._evacuate_request_locked(req, sid, committed)
        except BaseException:
            led.record_export_failed()
            raise
        led.record_export_done(len(snap.tokens), snap.n_kv_pages())
        return snap

    def migrate_salvage(self, req: Request, *,
                        reason: str = "failover"):
        """Crash-path export: a host-only snapshot (no KV — a failed
        scheduler's `_fail_all` already released its pages unkeyed)
        built from the Request handle alone. Token exactness does not
        depend on the pages: the destination re-prefills
        prompt + tokens and resumes at the exact next token; the KV
        transfer is only ever a prefill-cost optimization."""
        led = self._migration
        led.record_export_start()
        try:
            if self._faults is not None:
                self._faults.check("migrate_export")
            snap = self._build_snapshot(req, reason, (), None)
        except BaseException:
            led.record_export_failed()
            raise
        led.record_export_done(len(snap.tokens), 0)
        return snap

    def _build_snapshot(self, req: Request, reason: str,
                        chain_tokens, kv: dict | None):
        from cloud_server_tpu.inference.migration import (
            MIGRATION_VERSION, MigrationSnapshot)
        now = time.perf_counter()
        tr = req.trace
        return MigrationSnapshot(
            version=MIGRATION_VERSION, request_id=req.request_id,
            reason=reason, prompt=tuple(req.prompt),
            tokens=tuple(req.tokens), logprobs=tuple(req.logprobs),
            emit_times=tuple(req.emit_times), seed_used=req.seed_used,
            sampling=req.sampling, adapter=req.adapter,
            tenant=req.tenant, slo_class=req.slo_class,
            max_new_tokens=req.max_new_tokens,
            # the REMAINDER, not the absolute stamp: deadlines are
            # per-host monotonic clocks and must not cross machines
            deadline_remaining_s=(None if req.deadline is None
                                  else req.deadline - now),
            trace_ctx=(None if tr is None
                       else (tr.trace_id, tr.root_span_id, True)),
            chain_tokens=tuple(chain_tokens), kv_pages=kv)

    def _export_request_locked(self, req: Request, reason: str):
        """Locate `req` (slot or pending) and snapshot it. Caller
        holds `_step_lock` with no dispatch in flight. Returns
        (snapshot, slot_id | None, committed_tokens)."""
        sid = next((i for i, s in enumerate(self._slots)
                    if s is not None and s.req is req), None)
        if sid is None:
            with self._lock:
                if req not in self._pending:
                    raise RuntimeError(
                        "request is not live on this server (already "
                        "finished, failed, or cancelled)")
            return self._build_snapshot(req, reason, (), None), None, []
        if any(sid == job.slot for job in self._jobs):
            raise RuntimeError(
                "request is mid-admission (chunked prefill in "
                "flight); not exportable until prefill completes")
        committed = self._committed(sid)
        ps = self.page_size
        n_full = len(committed) // ps
        kv = None
        stash = self._handoff_stash.pop(req.request_id, None)
        if n_full:
            slot = self._slots[sid]
            page_ids = list(slot.pages[:n_full])
            # handoff prefetch (see _handoff_prefetch): pages gathered
            # before the final prefill chunk's dispatch, host copies
            # already overlapped under its compute. Valid only while
            # they are still a PREFIX of the slot's chain (a
            # preemption/re-admission in between re-keys the pages —
            # the stash is then stale and the full gather below pays
            # the whole transfer, a missed optimization, never a
            # correctness event).
            pre: dict = {}
            n_pre = 0
            if stash is not None:
                sids_, gathers = stash
                if list(sids_) == page_ids[:len(sids_)]:
                    pre, n_pre = gathers, len(sids_)
            gathered: dict = {}
            if n_pre < n_full:
                rem = np.asarray(page_ids[n_pre:])
                for name, pool in self.state["pools"].items():
                    gathered[name] = pool[:, rem]
                draft = self.state.get("draft_pools")
                if draft is not None:
                    for name, pool in draft.items():
                        gathered["draft/" + name] = pool[:, rem]
            # analysis: allow[lock-discipline] the migration export's
            # ONE sanctioned host sync — at the commit point, off the
            # plan path (DD5), under the step lock that serializes
            # the scheduler by design (analysis/dispatch.py
            # SANCTIONED_SYNCS). The prefetched half completes
            # instantly (its D2H copy already ran under the final
            # prefill chunk); only the remainder pays transfer here.
            pre_h, rem_h = jax.device_get((pre, gathered))
            if not rem_h:
                kv = pre_h
            elif not pre_h:
                kv = rem_h
            else:
                kv = {name: np.concatenate((pre_h[name], rem_h[name]),
                                           axis=1)
                      for name in rem_h}
        return (self._build_snapshot(req, reason,
                                     committed[:n_full * ps], kv),
                sid, committed)

    def _evacuate_request_locked(self, req: Request, sid: int | None,
                                 committed: list) -> None:
        """Remove the exported request from this server WITHOUT
        completing it — the caller now owns the handle's fate. The
        slot (if any) releases content-keyed, so its committed KV
        stays reusable in the local prefix cache. The source half of
        the trace closes here; the destination joins the same tree
        via the snapshot's trace context."""
        if sid is not None:
            if (self._slots[sid] is None
                    or self._slots[sid].req is not req):
                raise RuntimeError("slot changed under export")
            self._release_slot(sid, committed)
        else:
            with self._lock:
                try:
                    self._pending.remove(req)
                except ValueError:
                    raise RuntimeError(
                        "request left the pending queue during "
                        "export") from None
                if self.qos is not None:
                    self.qos.on_pending_removed(req.tenant)
        # a `finish:` event so the SOURCE half of the trace closes as
        # a complete, gap-free tree (build_tree keys the root's end on
        # the final finish event; the destination's continuation tree
        # carries the rest of the request under the same trace id)
        req.record_event("finish:migrated", time.perf_counter())
        if self.trace_recorder is not None and (
                req.trace is not None or req.tail_trace is not None):
            if req.trace is None:
                # deterministic tail retention: the SOURCE half of a
                # migrated tree always retains (mirrors the
                # destination's migrate_of/handoff_of tag), so a
                # router-merged tree is never half-missing
                req.tail_trace.annotate(migrated_out=True)
            self.trace_recorder.finish(req)

    def migrate_import(self, snap, *, stream=None, fail_handler=None,
                       trace_ctx: tuple | None = None,
                       deadline_s: float | None = None) -> Request:
        """Re-admit a migrated request on THIS server. The snapshot's
        KV pages are keyed into the pool under their radix chain keys
        (shared prefixes dedupe on arrival — BlockAllocator.
        import_chain) and scattered back with a device_put + one
        dispatch, no host sync (DD2 holds). The request then enters
        through the NORMAL continuation admission: its admission
        prompt is prompt + generated tokens, so the prefix walk
        re-hits the imported pages and decode resumes at the exact
        next token. A failed or partial KV import degrades to plain
        re-prefill — a cache miss, never a correctness event.

        Returns the new Request handle. Only NEW tokens are emitted
        on `stream`; the snapshot's already-delivered tokens are
        pre-filled so the client keeps one contiguous stream."""
        self._refuse_slot_state("live migration (migrate_import)")
        from cloud_server_tpu.inference.migration import (
            MIGRATION_VERSION)
        led = self._migration
        led.record_import_start()
        try:
            if self._faults is not None:
                self._faults.check("migrate_import")
            if snap.version != MIGRATION_VERSION:
                raise ValueError(
                    f"migration snapshot version {snap.version} != "
                    f"{MIGRATION_VERSION}")
            if snap.remaining_new_tokens() <= 0:
                raise ValueError(
                    "snapshot has no decode budget left to resume")
            if snap.kv_pages:
                try:
                    self._import_pages(snap)
                except Exception:
                    pass  # re-prefill instead; exactness unaffected
            if deadline_s is None:
                deadline_s = snap.deadline_remaining_s
            req = self.submit(
                list(snap.prompt),
                max_new_tokens=snap.max_new_tokens, stream=stream,
                sampling=snap.sampling, adapter=snap.adapter,
                tenant=snap.tenant,
                trace_ctx=(snap.trace_ctx if trace_ctx is None
                           else trace_ctx),
                deadline_s=deadline_s, fail_handler=fail_handler,
                _migration=snap)
        except BaseException:
            led.record_import_failed()
            raise
        led.record_import_done()
        return req

    def _import_pages(self, snap) -> int:
        """Scatter the snapshot's KV pages into the pool under their
        chain keys. Holds `_step_lock` so the keyed-but-not-yet-
        written window is invisible: admissions (the only readers)
        run inside the step, which serializes behind this scatter.
        Returns the number of pages installed (0 = full dedupe or a
        skipped transfer)."""
        tenant = (self.qos.resolve(snap.tenant)
                  if self.qos is not None else None)
        # BOUNDED acquire: a migrating drain can run in both
        # directions at once (A evacuating into B while B evacuates
        # into A), and each evacuation holds its own step lock while
        # importing into the other — an unbounded acquire here would
        # be that ABBA deadlock. Timing out just skips the KV
        # transfer: the continuation re-prefills (a cache miss).
        if not self._step_lock.acquire(timeout=5.0):
            return 0
        try:
            fill = self.allocator.import_chain(
                list(snap.chain_tokens), namespace=snap.adapter or "",
                tenant=tenant)
            if not fill:
                return 0
            idxs = np.asarray([i for i, _ in fill])
            ids = np.asarray([p for _, p in fill])
            pools = self.state["pools"]
            for name, pool in pools.items():
                src = snap.kv_pages.get(name)
                if src is not None:
                    pools[name] = pool.at[:, ids].set(
                        jnp.asarray(src[:, idxs]))
            draft = self.state.get("draft_pools")
            if draft is not None:
                for name, pool in draft.items():
                    src = snap.kv_pages.get("draft/" + name)
                    if src is not None:
                        draft[name] = pool.at[:, ids].set(
                            jnp.asarray(src[:, idxs]))
            return len(fill)
        finally:
            self._step_lock.release()

    def _evacuate(self, migrate) -> None:
        """drain(migrate=...)'s zero-token-loss evacuation: under ONE
        `_step_lock` hold — so no decode can interleave between a
        snapshot and its release, and no token is ever generated on
        two replicas — snapshot every live slot and pending request
        and offer each to the `migrate(snapshot, request) -> bool`
        callback (the ReplicatedRouter's drain wires this to a
        healthy replica's import). True = evacuated (released here,
        resumed there, handle mirrored by the caller); False or an
        export failure leaves the request in place for the normal
        drain wait. Requests mid-admission finish their (bounded)
        prefill normally."""
        led = self._migration
        with self._step_lock:
            if self._inflight is not None:
                self._commit_inflight()
                self._deliver()
            job_slots = {job.slot for job in self._jobs}
            for sid, slot in enumerate(self._slots):
                if slot is None or sid in job_slots:
                    continue
                req = slot.req
                if req._cancel.is_set() or req._done.is_set():
                    continue
                led.record_export_start()
                try:
                    if self._faults is not None:
                        self._faults.check("migrate_export")
                    snap, sid2, committed = (
                        self._export_request_locked(req, "drain"))
                except Exception:
                    led.record_export_failed()
                    continue
                if not migrate(snap, req):
                    led.record_export_failed()
                    continue
                self._evacuate_request_locked(req, sid2, committed)
                led.record_export_done(len(snap.tokens),
                                       snap.n_kv_pages())
            with self._lock:
                pend = list(self._pending)
            for req in pend:
                if req._cancel.is_set():
                    continue
                led.record_export_start()
                try:
                    if self._faults is not None:
                        self._faults.check("migrate_export")
                    snap = self._build_snapshot(req, "drain", (), None)
                except Exception:
                    led.record_export_failed()
                    continue
                if not migrate(snap, req):
                    led.record_export_failed()
                    continue
                try:
                    self._evacuate_request_locked(req, None, [])
                except RuntimeError:
                    # cancelled out of the queue mid-offer; the
                    # destination's copy completes (or cancels) on
                    # its own — nothing was lost here
                    led.record_export_failed()
                    continue
                led.record_export_done(len(snap.tokens), 0)

    def _fail_all(self, exc: BaseException) -> None:
        # BOUNDED step-lock acquire: teardown serializes against any
        # concurrent step() (another thread may be mid-iteration when
        # stop() gives up on a drain), so slot state is never torn
        # down under a live dispatch — but a scheduler thread WEDGED
        # inside a dispatch (device hang) still holds _step_lock, and
        # failing everyone must unblock waiters rather than hang with
        # it, so after the timeout teardown proceeds unserialized
        # (nothing else will ever release that lock). The crashed
        # serve_forever path acquires instantly — its step() exited.
        got = self._step_lock.acquire(
            timeout=self._teardown_lock_timeout_s)
        if not got:
            # make the unserialized teardown VISIBLE: before this
            # counter, a timed-out acquire proceeded with no trace
            # that slot state was torn down against a possibly-live
            # dispatch (cloud_server_unserialized_teardown_total)
            self.unserialized_teardowns += 1
        try:
            # what a step that raised inside its commit left recorded:
            # to the clients first (nothing, otherwise)
            self._deliver()
            with self._lock:
                pending, self._pending = (list(self._pending),
                                          collections.deque())
            for sid in range(self.max_slots):
                if self._slots[sid] is not None:
                    # keyed_tokens=[] — drops the refs (keeping the
                    # allocator consistent for any future recovery
                    # path) but keys NOTHING: a failed dispatch may
                    # have left these pages half-written, so they must
                    # not enter the prefix cache as valid KV
                    slot = self._release_slot(sid, [])
                    slot.req.finish_reason = f"error: {exc!r}"
                    self._complete(slot.req)
            self._jobs.clear()
            # drop the launched-but-uncommitted dispatch's futures (its results belong to requests that
            # just failed; like the wedged-teardown case, any still-
            # running device work finishes into buffers nothing reads)
            self._inflight = self._ahead = None
            self._reaped.clear()
        finally:
            if got:
                self._step_lock.release()
        for req in pending:
            if self.qos is not None:
                self.qos.on_pending_removed(req.tenant)
            req.finish_reason = f"error: {exc!r}"
            self._complete(req)

    def serve_forever(self, idle_sleep_s: float = 0.05) -> None:
        while not self._stop.is_set():
            try:
                busy = self.step()
            except Exception as exc:  # noqa: BLE001 — must not hang clients
                import traceback
                traceback.print_exc()
                self._fail_all(exc)
                self._stop.set()
                return
            # cooperative yield after every busy step: the pipelined
            # loop's syncs can return instantly (the program finished
            # under the host's own work), so without an explicit yield
            # a fast scheduler can emit a whole request before a
            # streaming client's writer thread (SSE writers, result()
            # waiters) runs once — delaying disconnect detection to
            # the end
            if busy:
                time.sleep(0)
            # analysis: allow[lock-discipline] idle-polling read on the
            # scheduler's own thread — the only _jobs writer
            if busy == 0 and self.num_pending == 0 and not self._jobs:
                # bounded CONDITION wait, not a short sleep poll: an
                # idle fleet must not spin step() hundreds of times a
                # second (the idle_iterations_total growth-rate
                # regression test pins this). submit() notifies _work,
                # so admission latency never pays the timeout; the
                # timeout itself keeps pending-deadline sweeps and
                # stop() responsive even if a notify is missed.
                if self._profiler is not None:
                    # a wait for work is no step's `between_ms`
                    self._profiler.close()
                with self._work:
                    if not self._pending and not self._stop.is_set():
                        self._work.wait(idle_sleep_s)

    def start(self) -> "PagedInferenceServer":
        self._stop.clear()
        with self._lock:
            # under the state lock like every other _draining flip: a
            # stopped-then-restarted server serves again, and a racing
            # submit sees either verdict cleanly, never a torn latch
            self._draining = False
        self._thread = threading.Thread(target=self.serve_forever,
                                        daemon=True,
                                        name="paged-inference-server")
        self._thread.start()
        return self

    def drain(self, timeout: float | None = None, *, migrate=None,
              _resume_on_timeout: bool = True) -> bool:
        """Graceful drain: refuse new submissions, let everything
        already accepted run to completion. Returns True once idle —
        and STAYS draining (quiesced): call resume() to accept again,
        or stop() to shut down. On timeout returns False and RESUMES
        accepting (the in-flight work keeps running; call stop() to
        actually shut down — it fails whatever is still live so no
        waiter hangs). Safe with or without the background scheduler
        thread. `_resume_on_timeout=False` is stop(drain=True)'s
        internal latch: a timed-out drain there must NOT reopen
        submission in the window before _stop is set, or a request
        could be accepted just to be failed.

        `migrate` turns the drain into a zero-token-loss EVACUATION:
        a `migrate(snapshot, request) -> bool` callback (see
        `_evacuate`; `ReplicatedRouter.drain(migrate=True)` builds
        one) is offered every live request, and each accepted offer
        moves the request to another replica instead of waiting it
        out. Whatever the callback declines drains normally."""
        with self._lock:
            self._draining = True
        if migrate is not None:
            self._refuse_slot_state("drain(migrate=...)")
            self._evacuate(migrate)
        deadline = (None if timeout is None
                    else time.perf_counter() + timeout)

        def busy() -> bool:
            # analysis: allow[lock-discipline] idle-polling bool() of a
            # GIL-atomic list; drain only needs eventual quiescence
            return bool(self.num_pending or self.num_active or self._jobs)

        while busy():
            if deadline is not None and time.perf_counter() > deadline:
                if _resume_on_timeout:
                    with self._lock:
                        self._draining = False
                return False
            if self._thread is None:
                self.step()
            else:
                time.sleep(0.002)
        return True

    def resume(self) -> None:
        """Clear a successful drain's quiesce: accept submissions again
        (no thread restart needed — the scheduler never stopped)."""
        with self._lock:
            self._draining = False

    def stop(self, drain: bool = False,
             timeout: float | None = None) -> None:
        if drain and not self._stop.is_set():
            # keep _draining latched across a timed-out drain: between
            # drain() returning False and _stop.set() below, a submit()
            # must be rejected, not accepted-then-failed by _fail_all
            self.drain(timeout, _resume_on_timeout=False)
        self._stop.set()
        with self._lock:
            # wake a scheduler thread parked on the idle condition
            # wait so shutdown does not pay the wait timeout
            self._work.notify_all()
        if self._thread is not None:
            self._thread.join(timeout=self._scheduler_join_timeout_s)
            self._thread = None
        # analysis: allow[lock-discipline] post-join read: the scheduler
        # thread is dead (or never ran) by this point
        if self.num_pending or self.num_active or self._jobs:
            # a timed-out (or skipped) drain left live requests behind:
            # nothing will ever step them now — unblock their waiters
            # (_fail_all drops page refs without caching them, which is
            # the conservative teardown for possibly-mid-write KV)
            self._fail_all(RuntimeError(
                "server stopped before the request completed"))
