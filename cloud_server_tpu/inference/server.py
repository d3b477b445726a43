"""Serving layer: slot-based continuous batching over the inference engine.

The TPU-first serving design: ONE persistent KV cache of static shape
(L, max_slots, max_len, KH, Dh) lives on device for the server's lifetime.
Each in-flight request owns a *slot* (a batch row). Admission prefills the
prompt into its slot; a single jitted decode advances ALL active slots one
token per call. Requests join and leave between decode steps — new work
never waits for old work to finish (continuous batching), and shapes never
change (no recompiles, no cache reallocation).

Two jitted functions do all device work:
  * admit:  one batched prefill (G, Pb) for the whole admission burst →
    scatter the slots' cache regions + sample the first tokens. Padded
    lengths are bucketed (next power of two) and the group row count is
    padded to a power of two, so compiles are bounded; slot indices are
    traced (no recompiles on slot choice).
  * decode: one step over the full slot batch. Inactive slots are masked —
    their length doesn't advance and they emit pad. Their cache writes
    land at their frozen length position, which any later occupant
    overwrites before it can ever be attended (write-at-pos happens before
    attention reads pos), so no cross-request leakage is possible.

The host side is a small scheduler: a pending queue, per-request token
accumulation, EOS / max-token completion, optional streaming callbacks.
One device_get of the sampled-token block per scheduler iteration is the
only host↔device sync; with `decode_chunk > 1` (multi-token scheduling)
that iteration covers up to decode_chunk tokens per slot via an on-device
`lax.scan`, amortising dispatch latency at the cost of up to chunk-1
steps of admission latency.

Sharding: wrap `params` (and the server's jits inherit via input
shardings) with tp/fsdp NamedShardings for multi-chip serving; the slot
batch rides (dp, fsdp) exactly like training batches.
"""

from __future__ import annotations

import collections
import dataclasses
import itertools
import math
import os
import threading
import time
import uuid
from functools import partial
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from cloud_server_tpu.config import InferConfig, ModelConfig
from cloud_server_tpu.inference import engine
from cloud_server_tpu.inference.iteration_profile import OVERLAP_PHASES
from cloud_server_tpu.inference.sampling import (
    SamplingParams, SamplingRows, make_rows, sample_logits,
    sample_logits_rows, set_rows, zero_rows)
from cloud_server_tpu.utils.serving_metrics import ServingMetrics


def _token_logprobs(logits: jnp.ndarray, toks: jnp.ndarray) -> jnp.ndarray:
    """log P(tok) under the model's raw (pre-filter) distribution — the
    one serving-API logprob convention, shared by admission and decode."""
    return jnp.take_along_axis(jax.nn.log_softmax(logits, axis=-1),
                               toks[:, None], axis=-1)[:, 0]


class SlotState:
    """Device-resident server state (a pytree)."""

    def __init__(self, k, v, length, last_token, active,
                 k_scale=None, v_scale=None, samp=None,
                 prompt_mask=None, out_counts=None):
        self.k = k                    # (L, B, max_len, KH, Dh)
        self.v = v
        self.length = length          # (B,) int32
        self.last_token = last_token  # (B,) int32
        self.active = active          # (B,) bool
        self.k_scale = k_scale        # int8 kv cache only, else None
        self.v_scale = v_scale
        # per-request sampling state: parameter rows, prompt-token
        # presence (B, V) bool and generated-token counts (B, V) int32
        # for penalties. Rows are written by every admission; the count
        # buffers are None until the FIRST penalty-using request
        # materializes them (penalty-free deployments never pay their
        # HBM or scatter cost; pre-materialization slots carry neutral
        # penalties, for which the buffers are read-irrelevant) and then
        # advance only in rows-mode decode dispatches.
        self.samp = samp              # SamplingRows of (B,) arrays
        self.prompt_mask = prompt_mask
        self.out_counts = out_counts

    def tree_flatten(self):
        return (self.k, self.v, self.length, self.last_token,
                self.active, self.k_scale, self.v_scale, self.samp,
                self.prompt_mask, self.out_counts), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


jax.tree_util.register_pytree_node(
    SlotState, SlotState.tree_flatten, SlotState.tree_unflatten)


def init_slot_state(cfg: ModelConfig, max_slots: int,
                    max_len: int) -> SlotState:
    cache = engine.init_cache(cfg, max_slots, max_len)
    return SlotState(
        k=cache.k, v=cache.v, length=cache.length,
        last_token=jnp.zeros((max_slots,), jnp.int32),
        active=jnp.zeros((max_slots,), bool),
        k_scale=cache.k_scale, v_scale=cache.v_scale,
        samp=zero_rows(max_slots), prompt_mask=None, out_counts=None)


def _prompt_presence(token_rows: jnp.ndarray, true_lens: jnp.ndarray,
                     vocab: int) -> jnp.ndarray:
    """(G, Pb) token rows + true lengths -> (G, vocab) bool presence."""
    g, pb = token_rows.shape
    rowi = jnp.arange(g)
    valid = jnp.arange(pb)[None, :] < true_lens[:, None]
    cols = jnp.where(valid, token_rows, vocab)
    return jnp.zeros((g, vocab), bool).at[rowi[:, None], cols].set(
        True, mode="drop")


def _admit_sampling_state(state: SlotState, samp_rows: SamplingRows,
                          slots: jnp.ndarray, pm_rows, first_toks):
    """Shared admission bookkeeping for per-request sampling: write the
    group's parameter rows and — when the penalty buffers have been
    materialized (`pm_rows` from `_prompt_presence`, else None) — the
    slots' prompt-presence masks and generated-token counts reset to the
    first sampled token.

    Returns (samp, prompt_mask, out_counts)."""
    samp = set_rows(state.samp, slots, samp_rows)
    if state.prompt_mask is None:
        return samp, None, None
    g, v = pm_rows.shape
    oc = jnp.zeros((g, v), jnp.int32).at[jnp.arange(g), first_toks].add(1)
    return (samp,
            state.prompt_mask.at[slots].set(pm_rows, mode="drop"),
            state.out_counts.at[slots].set(oc, mode="drop"))


@partial(jax.jit,
         static_argnames=("cfg", "infer_cfg", "use_rows", "use_bias"),
         donate_argnums=(1,))
def _admit_batch(params, state: SlotState, prompts: jnp.ndarray,
                 true_lens: jnp.ndarray, slots: jnp.ndarray, rng: jax.Array,
                 samp_rows: SamplingRows, *, cfg: ModelConfig,
                 infer_cfg: InferConfig, use_rows: bool = False,
                 use_bias: bool = False):
    """Prefill G prompts (G, Pb) into `slots` (G,); sample first tokens.

    A whole admission burst is ONE batched prefill (full MXU batch) instead
    of G sequential (1, Pb) prefills. Rows whose slot index is out of range
    (>= max_slots) are padding — `mode="drop"` scatters discard them — so
    one compilation serves any group of size <= G. `slots` values are
    traced, so slot choice never recompiles; only (G, Pb) does (both are
    bucketed by the caller).

    `use_rows` (static) switches first-token sampling to the per-request
    SamplingRows path; the rows themselves are always recorded so later
    rows-mode decodes see this group's parameters.

    Returns (state', first_tokens (G,), their logprobs (G,) f32).
    """
    g, pb = prompts.shape
    tmp = engine.init_cache(cfg, g, pb)
    logits, tmp = engine.prefill(params, prompts, cfg, tmp, true_lens)
    has_pen = state.prompt_mask is not None
    pm_g = (_prompt_presence(prompts, true_lens, logits.shape[-1])
            if has_pen else None)
    if use_rows:
        # first generated token: no output counts yet
        toks = sample_logits_rows(
            logits, samp_rows, true_lens, prompt_mask=pm_g,
            out_counts=(jnp.zeros_like(logits, jnp.int32)
                        if has_pen else None),
            eos_id=infer_cfg.eos_token_id, use_bias=use_bias)
    else:
        toks = sample_logits(logits, rng, infer_cfg)  # (G,)
    lps = _token_logprobs(logits, toks)  # (G,)

    k = state.k.at[:, slots, :pb].set(tmp.k, mode="drop")
    v = state.v.at[:, slots, :pb].set(tmp.v, mode="drop")
    k_scale = v_scale = None
    if state.k_scale is not None:
        k_scale = state.k_scale.at[:, slots, :pb].set(tmp.k_scale,
                                                      mode="drop")
        v_scale = state.v_scale.at[:, slots, :pb].set(tmp.v_scale,
                                                      mode="drop")
    samp, pmask, counts = _admit_sampling_state(
        state, samp_rows, slots, pm_g, toks)
    return SlotState(
        k=k, v=v,
        length=state.length.at[slots].set(true_lens, mode="drop"),
        last_token=state.last_token.at[slots].set(toks, mode="drop"),
        active=state.active.at[slots].set(True, mode="drop"),
        k_scale=k_scale, v_scale=v_scale, samp=samp, prompt_mask=pmask,
        out_counts=counts), toks, lps


@partial(jax.jit,
         static_argnames=("cfg", "infer_cfg", "use_rows", "use_bias"),
         donate_argnums=(1,))
def _admit_batch_prefixed(params, state: SlotState, prefix_kv,
                          remainders: jnp.ndarray,
                          true_lens: jnp.ndarray, slots: jnp.ndarray,
                          rng: jax.Array, samp_rows: SamplingRows,
                          prefix_toks: jnp.ndarray, *, cfg: ModelConfig,
                          infer_cfg: InferConfig, use_rows: bool = False,
                          use_bias: bool = False):
    """Admission via a cached common-prefix KV (prefix caching).

    The prefix's cache entries (prefix_kv: dict with k/v (L, 1, P0, KH,
    Dh) and optional k_scale/v_scale) are broadcast into a temp cache and
    only the REMAINDER tokens (G, Rb) run through the model — as a
    `verify_step` continuation at offset prefix_len, so the remainder
    attends to the cached prefix exactly as a full prefill would. Cost
    per admission drops from O(P0 + R) to O(R) model FLOPs.

    Returns (state', first_tokens (G,), logprobs (G,)).
    """
    g, rb = remainders.shape
    p0 = prefix_kv["k"].shape[2]
    tmp = engine.init_cache(cfg, g, p0 + rb)

    def put_prefix(buf, pre):
        pre = jnp.broadcast_to(pre, (pre.shape[0], g) + pre.shape[2:])
        return lax.dynamic_update_slice(
            buf, pre.astype(buf.dtype), (0, 0, 0, 0, 0))

    k = put_prefix(tmp.k, prefix_kv["k"])
    v = put_prefix(tmp.v, prefix_kv["v"])
    ks = vs = None
    if tmp.k_scale is not None:
        ks = put_prefix(tmp.k_scale, prefix_kv["k_scale"])
        vs = put_prefix(tmp.v_scale, prefix_kv["v_scale"])
    lengths0 = jnp.full((g,), p0, jnp.int32)  # static prefix width
    tmp = engine.KVCache(k, v, lengths0, ks, vs)

    logits, tmp = engine.verify_step(params, remainders, cfg, tmp)
    last = logits[jnp.arange(g), true_lens - 1]  # (G, V)
    new_lens = p0 + true_lens
    # the slot's true prompt is prefix + remainder: build the padded
    # full-prompt rows once and share them with the sampling-state scatter
    full_rows = jnp.concatenate(
        [jnp.broadcast_to(prefix_toks[None, :], (g, p0)), remainders],
        axis=1)
    has_pen = state.prompt_mask is not None
    pm_g = (_prompt_presence(full_rows, new_lens, last.shape[-1])
            if has_pen else None)
    if use_rows:
        toks = sample_logits_rows(
            last, samp_rows, new_lens, prompt_mask=pm_g,
            out_counts=(jnp.zeros_like(last, jnp.int32)
                        if has_pen else None),
            eos_id=infer_cfg.eos_token_id, use_bias=use_bias)
    else:
        toks = sample_logits(last, rng, infer_cfg)
    lps = _token_logprobs(last, toks)

    width = p0 + rb
    k = state.k.at[:, slots, :width].set(tmp.k, mode="drop")
    v = state.v.at[:, slots, :width].set(tmp.v, mode="drop")
    k_scale = v_scale = None
    if state.k_scale is not None:
        k_scale = state.k_scale.at[:, slots, :width].set(tmp.k_scale,
                                                         mode="drop")
        v_scale = state.v_scale.at[:, slots, :width].set(tmp.v_scale,
                                                         mode="drop")
    samp, pmask, counts = _admit_sampling_state(
        state, samp_rows, slots, pm_g, toks)
    return SlotState(
        k=k, v=v,
        length=state.length.at[slots].set(new_lens, mode="drop"),
        last_token=state.last_token.at[slots].set(toks, mode="drop"),
        active=state.active.at[slots].set(True, mode="drop"),
        k_scale=k_scale, v_scale=v_scale, samp=samp, prompt_mask=pmask,
        out_counts=counts), toks, lps


def _decode_core(params, state: SlotState, rng: jax.Array,
                 cfg: ModelConfig, infer_cfg: InferConfig,
                 use_rows: bool = False, use_bias: bool = False):
    """One decode step over all slots; inactive slots are frozen."""
    cache = engine.KVCache(state.k, state.v, state.length,
                           state.k_scale, state.v_scale)
    logits, cache = engine.decode_step(params, state.last_token, cfg, cache)
    out_counts = state.out_counts
    if use_rows:
        # the sampled token sits at absolute position length + 1 (`last`
        # occupies `length`); admission folds the prompt length for the
        # first token, so positions never collide within a request
        tok = sample_logits_rows(logits, state.samp, state.length + 1,
                                 prompt_mask=state.prompt_mask,
                                 out_counts=out_counts,
                                 eos_id=infer_cfg.eos_token_id,
                                 use_bias=use_bias)
        if out_counts is not None:
            out_counts = out_counts.at[
                jnp.arange(tok.shape[0]), tok].add(
                    state.active.astype(jnp.int32))
    else:
        tok = sample_logits(logits, rng, infer_cfg)
    lp = _token_logprobs(logits, tok)
    tok = jnp.where(state.active, tok, infer_cfg.pad_token_id)
    length = jnp.where(state.active, cache.length, state.length)
    return SlotState(k=cache.k, v=cache.v, length=length, last_token=tok,
                     active=state.active, k_scale=cache.k_scale,
                     v_scale=cache.v_scale, samp=state.samp,
                     prompt_mask=state.prompt_mask,
                     out_counts=out_counts), (tok, lp)


@partial(jax.jit,
         static_argnames=("cfg", "infer_cfg", "use_rows", "use_bias"),
         donate_argnums=(1,))
def _decode(params, state: SlotState, rng: jax.Array, *, cfg: ModelConfig,
            infer_cfg: InferConfig, use_rows: bool = False,
            use_bias: bool = False):
    """Returns (state', (tokens (B,) int32, logprobs (B,) f32)) with pad
    in inactive rows."""
    return _decode_core(params, state, rng, cfg, infer_cfg, use_rows,
                        use_bias)


@partial(jax.jit, static_argnames=("cfg", "infer_cfg", "n_steps",
                                   "use_rows", "use_bias"),
         donate_argnums=(1,))
def _decode_chunk(params, state: SlotState, rng: jax.Array, *,
                  cfg: ModelConfig, infer_cfg: InferConfig, n_steps: int,
                  use_rows: bool = False, use_bias: bool = False):
    """n_steps decode steps in ONE dispatch (lax.scan on device).

    Multi-token scheduling: the host syncs (device_get of the sampled
    tokens) once per chunk instead of once per token, amortising dispatch
    and host<->device latency over n_steps tokens. The host discards any
    in-chunk tokens past a request's EOS / budget afterwards, so chunking
    trades at most n_steps - 1 wasted decode steps (and that much admission
    latency) for steady-state throughput.

    Returns (state', (tokens (n_steps, B) int32,
    logprobs (n_steps, B) f32)).
    """
    def body(st, r):
        return _decode_core(params, st, r, cfg, infer_cfg, use_rows,
                            use_bias)

    return lax.scan(body, state, jax.random.split(rng, n_steps))


@partial(jax.jit, donate_argnums=(0,))
def _deactivate(state: SlotState, slot: jnp.ndarray) -> SlotState:
    return SlotState(k=state.k, v=state.v, length=state.length,
                     last_token=state.last_token,
                     active=state.active.at[slot].set(False),
                     k_scale=state.k_scale, v_scale=state.v_scale,
                     samp=state.samp, prompt_mask=state.prompt_mask,
                     out_counts=state.out_counts)


class _StepTracer:
    """On-demand profiling of the next N scheduler iterations into a
    jax profiler trace (utils.tracing.capture_trace), armed from any
    thread (the HTTP /debug/trace endpoint) and driven by the
    scheduler's own step() — the capture window aligns exactly with
    iteration boundaries, so a dump shows whole dispatches, not
    fragments. Trace failures are swallowed with a stderr note: the
    profiler is process-global and telemetry must never take the
    scheduler (and every in-flight request) down with it."""

    def __init__(self):
        self._lock = threading.Lock()
        self._pending: tuple[int, str] | None = None
        self._cm = None
        self._left = 0

    def request(self, n_steps: int, logdir: str | os.PathLike) -> None:
        if n_steps <= 0:
            raise ValueError("trace step count must be positive")
        with self._lock:
            if self._pending is not None or self._cm is not None:
                raise ValueError("a trace capture is already in progress")
            self._pending = (int(n_steps), os.fspath(logdir))

    @property
    def active(self) -> bool:
        return self._pending is not None or self._cm is not None

    def step_start(self) -> None:
        with self._lock:
            if self._pending is None:
                return
            n, logdir = self._pending
            self._pending = None
            from cloud_server_tpu.utils import tracing
            try:
                cm = tracing.capture_trace(logdir)
                cm.__enter__()
            except Exception as exc:  # noqa: BLE001 — see class docstring
                import sys
                print(f"[server] trace capture failed to start: {exc!r}",
                      file=sys.stderr)
                return
            self._cm, self._left = cm, n

    def step_end(self) -> None:
        with self._lock:
            if self._cm is None:
                return
            self._left -= 1
            if self._left > 0:
                return
            cm, self._cm = self._cm, None
            try:
                cm.__exit__(None, None, None)
            except Exception as exc:  # noqa: BLE001
                import sys
                print(f"[server] trace capture failed to stop: {exc!r}",
                      file=sys.stderr)


class QueueFullError(RuntimeError):
    """submit() refused: the pending queue is at its configured bound.
    Backpressure, not failure — the HTTP front-end maps this to 429 so
    clients retry instead of piling unbounded host memory."""


@dataclasses.dataclass
class Request:
    """A generation request; thread-safe completion via `result()`."""

    prompt: list[int]
    max_new_tokens: int
    stream: Callable[[int], None] | None = None
    # per-request sampling controls (None = server defaults). Device-side
    # fields ride into dispatches as SamplingRows; stop / ignore_eos are
    # enforced host-side in emit_token.
    sampling: SamplingParams | None = None
    # the seed actually used for this request's device rows (the request's
    # own, or one drawn from the server's host RNG at submit) — stable
    # across preemption/re-admission
    seed_used: int = 0
    # multi-LoRA serving: registered adapter name (paged server)
    adapter: str | None = None
    # multi-tenant QoS (inference/qos.py): resolved tenant name, set at
    # submit. None = QoS disabled (no registry configured); requests on
    # a QoS-enabled server always carry a concrete name ("default" when
    # the client sent none).
    tenant: str | None = None
    # distributed tracing (inference/request_trace.py): the request's
    # RequestTrace when head sampling selected it at submit, else None
    # (unsampled, or tracing disabled — zero cost either way)
    trace: object | None = None
    # tail-based retention: the provisional lightweight trace a
    # head-UNSAMPLED request carries when the recorder runs a tail
    # ring; judged (retain or forget) at finish. None when head-
    # sampled or tail retention is off.
    tail_trace: object | None = None
    # SLO class (inference/slo.py): the tenant's QoS priority class
    # name, resolved once at submit when SLO tracking is configured;
    # None otherwise (the tracker maps None onto its "default" entry)
    slo_class: str | None = None
    tokens: list[int] = dataclasses.field(default_factory=list)
    # log P(token) under the model's raw (pre-filter) distribution,
    # aligned with `tokens`
    logprobs: list[float] = dataclasses.field(default_factory=list)
    _done: threading.Event = dataclasses.field(
        default_factory=threading.Event)
    finish_reason: str | None = None  # "eos" | "length" | "error: ..."
    # request-level latency accounting (host wall clock, perf_counter):
    # submit_time set at submit(); one emit_times entry per token, set by
    # the scheduler at the host moment the token is surfaced. TTFT =
    # emit_times[0] - submit_time; inter-token latencies = diffs. Tokens
    # committed in one multi-token dispatch share one host moment —
    # near-zero ITLs inside a burst are real (burst delivery), the tail
    # percentiles are where scheduling stalls show.
    submit_time: float | None = None
    emit_times: list[float] = dataclasses.field(default_factory=list)
    # request deadline (absolute perf_counter moment, set at submit
    # from deadline_s or the tenant's QoS-class default): the
    # scheduler sweep cancels expired requests (finish_reason
    # "deadline", pages released through the normal path) and the
    # router stops failover retries past it. None = no deadline.
    deadline: float | None = None
    # lifecycle telemetry: a stable id (access logs / timelines) plus an
    # event trail of (name, perf_counter time) pairs appended at host
    # moments the scheduler already owns — submit, every (re-)admission,
    # first token, preempt-requeue, finish:<reason>. admit_time is the
    # FIRST admission (queue-wait semantics survive preemption).
    request_id: str = dataclasses.field(
        default_factory=lambda: uuid.uuid4().hex[:12])
    admit_time: float | None = None
    events: list[tuple[str, float]] = dataclasses.field(
        default_factory=list)
    # client-side cancellation: the flag is checked by the scheduler;
    # `_on_cancel` is installed by the owning server at submit so a
    # still-PENDING request can be finished without waiting for a step
    _cancel: threading.Event = dataclasses.field(
        default_factory=threading.Event)
    _on_cancel: Callable[["Request"], None] | None = None
    # failure interception (ReplicatedRouter failover): when a request
    # completes with an "error:" finish_reason, _complete offers it to
    # this hook BEFORE unblocking waiters; a True return means the
    # hook took ownership (a retry on another replica will complete
    # the request), so _done stays unset. None (the default, and
    # always for direct-server submits) keeps completion unchanged.
    _fail_handler: Callable[["Request"], bool] | None = None
    # completion callback invoked AFTER _done is set (the router's
    # retry-mirroring path); None for everything else.
    _on_done: Callable[["Request"], None] | None = None
    # True when an "error:" completion was caused by the REQUEST
    # itself (e.g. it can never fit the page pool) rather than the
    # replica: the router must neither retry it elsewhere — it fails
    # identically everywhere — nor count it against the replica's
    # circuit breaker.
    _request_fault: bool = False

    def cancel(self) -> None:
        """Abort this request. Pending requests finish immediately with
        finish_reason "cancelled"; a request mid-admission or decoding
        is torn down by its server's scheduler within one step (its
        slot and pages go back through the normal release path, so the
        KV it wrote stays reusable in the prefix cache). Idempotent;
        a no-op once the request has finished."""
        if self._done.is_set() or self._cancel.is_set():
            return
        self._cancel.set()
        if self._on_cancel is not None:
            self._on_cancel(self)

    @property
    def cancelled(self) -> bool:
        return self._cancel.is_set()

    def record_event(self, name: str, t: float | None = None) -> None:
        self.events.append((name, time.perf_counter() if t is None
                            else t))

    def timeline(self) -> list[tuple[str, float]]:
        """The request's lifecycle events as (name, perf_counter time)
        pairs, in the order they happened: "submit", "admit" (repeated
        on re-admission after a preemption), "first_token",
        "preempt_requeue", "finish:<reason>". Token-level timing lives
        in `emit_times`."""
        return list(self.events)

    def latency_stats(self) -> dict | None:
        """TTFT and inter-token-latency summary (seconds); None until
        two tokens have been emitted."""
        if self.submit_time is None or len(self.emit_times) < 2:
            return None
        itl = [b - a for a, b in zip(self.emit_times, self.emit_times[1:])]
        itl.sort()

        def pct(p):
            return itl[min(len(itl) - 1, int(p * len(itl)))]

        return {"ttft": self.emit_times[0] - self.submit_time,
                "itl_p50": pct(0.50), "itl_p99": pct(0.99),
                "itl_max": itl[-1]}

    def result(self, timeout: float | None = None) -> list[int]:
        if not self._done.wait(timeout):
            raise TimeoutError("generation not finished")
        if self.finish_reason and self.finish_reason.startswith("error"):
            raise RuntimeError(f"generation failed: {self.finish_reason}")
        return self.tokens

    @property
    def done(self) -> bool:
        return self._done.is_set()


def resolve_seed(sampling: SamplingParams | None, host_rng, lock) -> int:
    """The request's own seed, or a fresh draw from the server's host
    RNG (under `lock`) — fixed once at submit so a preempted request
    re-admits with the same rows. Shared by both servers."""
    if sampling is not None and sampling.seed is not None:
        return int(sampling.seed)
    with lock:
        return int(host_rng.integers(0, 2 ** 32))


def emit_token(req: Request, token: int, logprob: float | None,
               infer_cfg: InferConfig,
               deliveries: list | None = None) -> bool:
    """Record one generated token on `req`; True when the request just
    finished (eos / stop sequence / length). The single emit rule both
    servers share. Handed `deliveries` (the paged server's list, run
    after its next launch), the stream call is put there as
    `(req, token)` and nobody is woken here.

    Stop sequences are token-level: when the output's tail equals one of
    `req.sampling.stop`, the matched tokens are removed (OpenAI
    semantics) and finish_reason is "stop". The final token of a match is
    never streamed, but earlier tokens of the sequence were streamed as
    they arrived — the final `tokens` list is authoritative."""
    sp = req.sampling
    if token == infer_cfg.eos_token_id and not (sp and sp.ignore_eos):
        req.finish_reason = "eos"
        return True
    req.tokens.append(token)
    req.emit_times.append(time.perf_counter())
    if logprob is not None:
        # append before stream(): a consumer woken by the stream
        # callback may read logprobs[len(tokens)-1]
        req.logprobs.append(float(logprob))
    if sp and sp.stop:
        for s in sp.stop:
            ls = len(s)
            if len(req.tokens) >= ls and req.tokens[-ls:] == list(s):
                del req.tokens[-ls:]
                del req.emit_times[-ls:]
                # logprobs may cover only a PREFIX of tokens (the
                # logprob=None path appends nothing): drop exactly the
                # entries past the kept-token count — a blanket [-ls:]
                # would strip logprobs belonging to kept tokens
                drop = len(req.logprobs) - len(req.tokens)
                if drop > 0:
                    del req.logprobs[-drop:]
                req.finish_reason = "stop"
                return True
    if req.stream is not None:
        if deliveries is None:
            req.stream(token)
        else:
            deliveries.append((req, token))
    if len(req.tokens) >= req.max_new_tokens:
        req.finish_reason = "length"
        return True
    return False


def _bucket(n: int, buckets: Sequence[int]) -> int:
    for b in buckets:
        if n <= b:
            return b
    raise ValueError(f"prompt of {n} tokens exceeds largest bucket "
                     f"{buckets[-1]}")


class InferenceServer:
    """Continuous-batching generation server.

    submit() is thread-safe and returns immediately; step() performs one
    scheduler iteration (admissions + one decode for all active slots).
    Run steps manually, or `serve_forever()` on a thread via start()/stop().
    """

    def __init__(self, params, cfg: ModelConfig, infer_cfg: InferConfig, *,
                 max_slots: int = 8, max_len: int = 1024,
                 prompt_buckets: Sequence[int] | None = None, seed: int = 0,
                 decode_chunk: int = 1, max_pending: int | None = None,
                 prefix_tokens: Sequence[int] | None = None,
                 prefix_remainder_cap: int = 1024,
                 metrics: ServingMetrics | None = None,
                 qos=None, tracing=None, slo=None,
                 iteration_profile=None, faults=None, anomaly=None,
                 overlap: bool | None = None):
        # Serving never needs f32 master weights: pre-cast float32 leaves to
        # the compute dtype once, instead of streaming 2x the bytes and
        # converting on every decode step. QTensor leaves stay quantized
        # (their .astype dequantizes — applied at use, not here).
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
        if cfg.decode_attention_impl != "xla":
            # fail at construction, not deep inside the first jitted
            # decode trace (engine.decode_step raises the detailed error;
            # PagedInferenceServer validates eagerly the same way)
            raise ValueError(
                f"decode_attention_impl={cfg.decode_attention_impl!r} is "
                "not supported by the contiguous InferenceServer — the "
                "pallas decode kernel lives in the paged serving stack "
                "(inference.paged_server.PagedInferenceServer); use "
                "'xla' here")
        self.cfg = cfg
        self.infer_cfg = infer_cfg
        self.max_slots = max_slots
        self.max_len = max_len
        # Max decode steps per scheduler iteration (multi-token scheduling).
        # 1 = sync every token (lowest admission latency); larger values
        # amortise dispatch/host-sync overhead over the chunk. The actual
        # chunk never exceeds any active request's remaining budget, so no
        # request overshoots its max_new_tokens or the cache.
        self.decode_chunk = max(1, decode_chunk)
        if prompt_buckets is None:
            # powers of two, with max_len itself always the last bucket so
            # any prompt the cache can hold is admissible
            prompt_buckets = [b for b in itertools.takewhile(
                lambda b: b < max_len,
                (2 ** i for i in range(4, 31)))] + [max_len]
        self.prompt_buckets = sorted(prompt_buckets)
        if self.prompt_buckets[-1] > max_len:
            raise ValueError(
                f"largest prompt bucket ({self.prompt_buckets[-1]}) exceeds "
                f"max_len ({max_len}); the slot cache could not hold it")
        self.state = init_slot_state(cfg, max_slots, max_len)
        # Prefix caching: prefill the shared prompt prefix (e.g. a system
        # prompt) ONCE; admissions whose prompt extends it reuse the cached
        # KV and only run their remainder through the model.
        self._prefix: list[int] | None = None
        self._prefix_kv: dict | None = None
        self.prefix_remainder_cap = prefix_remainder_cap
        self.prefix_hits = 0
        self.prefix_misses = 0
        self._warned_prefix_miss = False
        if prefix_tokens:
            pfx = list(prefix_tokens)
            if len(pfx) >= max_len:
                raise ValueError(
                    f"prefix of {len(pfx)} tokens leaves no room within "
                    f"max_len={max_len}")
            tmp = engine.init_cache(cfg, 1, len(pfx))
            _, tmp = engine.prefill(
                self.params, jnp.asarray([pfx], jnp.int32), cfg, tmp)
            self._prefix = pfx
            self._prefix_kv = {"k": tmp.k, "v": tmp.v}
            if tmp.k_scale is not None:
                self._prefix_kv["k_scale"] = tmp.k_scale
                self._prefix_kv["v_scale"] = tmp.v_scale
            # remainder bucket list is a constant; precompute for the
            # per-request predicate on the scheduler hot path
            rcap = min(max_len - len(pfx), prefix_remainder_cap)
            self._rem_buckets = ([b for b in self.prompt_buckets
                                  if b < rcap] + [rcap])
        self.tokens_emitted = 0  # lifetime emitted tokens (bench/metrics)
        # request-lifecycle telemetry: histograms + counters observed at
        # host moments the scheduler already owns (no extra syncs); the
        # snapshot is the /metrics + /stats source of truth
        self.metrics = metrics if metrics is not None else ServingMetrics()
        self.metrics.registry.add_collector(self._collect_metrics)
        self.tracer = _StepTracer()  # /debug/trace on-demand profiling
        # iteration-phase profiler (inference/iteration_profile.py):
        # sweep/admission/build/device/commit/epilogue clock marks at
        # host moments the scheduler already crosses — zero extra
        # dispatches/syncs. The contiguous server has no flight
        # recorder, so phases feed only the per-phase histograms
        # (which is also where /stats' `iteration_profile` summary and
        # host_gap_frac come from). None (disabled) short-circuits
        # every guarded call site.
        from cloud_server_tpu.inference.iteration_profile import (
            register_phase_hists, resolve_profiler)
        from cloud_server_tpu.utils.tracing import annotate
        self._profiler = resolve_profiler(iteration_profile,
                                          infer_cfg.iteration_profile,
                                          annotate)
        self._phase_hists = ({} if self._profiler is None else
                             register_phase_hists(self.metrics.registry))
        # idle-vs-dead disambiguation (see the paged server): an idle
        # scheduler keeps incrementing idle_iterations while
        # last_busy_ts ages; a dead one freezes both
        self.idle_iterations = 0
        self.last_busy_ts = 0.0
        self._iter_busy = False  # scheduler-thread scratch (under
        #                          _step_lock): did this step dispatch?
        # backpressure: submit() past this bound raises QueueFullError
        # (HTTP 429); None = unbounded (library use, trusted callers)
        self.max_pending = max_pending
        # multi-tenant QoS (inference/qos.py): `qos` may be a ready
        # TenantRegistry, a config dict / JSON string / file path, or
        # None (falls back to InferConfig.qos_config). None disables
        # QoS: every guarded call site below short-circuits and the
        # scheduler is byte-identical to the pre-QoS server. Imported
        # lazily — qos.py imports QueueFullError from this module.
        from cloud_server_tpu.inference.qos import resolve_registry
        self.qos = resolve_registry(qos, infer_cfg.qos_config)
        # per-request distributed tracing + per-class SLO tracking
        # (inference/request_trace.py, inference/slo.py): both None
        # unless configured — every guarded call site short-circuits
        # and the scheduler is byte-identical to the pre-trace build
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
        # anomaly watchdog (inference/anomaly.py): None unless
        # configured — every guarded call site short-circuits and the
        # scheduler is byte-identical to the pre-watchdog build. The
        # contiguous server feeds the per-finish rules plus a thin
        # per-step signal (no flight recorder here); bundle
        # auto-capture shares the paged server's contract.
        from cloud_server_tpu.inference.anomaly import resolve_anomaly
        self._anomaly = resolve_anomaly(anomaly, infer_cfg.anomaly_config)
        if self._anomaly is not None:
            self._anomaly.bind_slo(self.slo)
        self._bundle_on_anomaly = bool(infer_cfg.bundle_on_anomaly)
        self._bundles: collections.deque = collections.deque(maxlen=8)
        self._bundles_captured = 0
        # deterministic fault injection (inference/faults.py): None
        # unless configured — every guarded call site short-circuits,
        # so the scheduler is byte-identical to the pre-fault build
        # (the dispatch-count regression test pins it). The contiguous
        # server arms submit_reject / dispatch / iteration_stall;
        # wedge and alloc_famine are paged-scheduler shapes.
        from cloud_server_tpu.inference.faults import resolve_fault_plan
        self._faults = resolve_fault_plan(faults, infer_cfg.fault_plan)
        self._draining = False
        self._slots: list[Request | None] = [None] * max_slots
        self._pending: collections.deque[Request] = collections.deque()
        self._lock = threading.Lock()
        # submit notifies this condition (same mutex as _lock) so an
        # idle serve_forever parks in a bounded wait instead of
        # busy-polling (see the paged server's twin)
        self._work = threading.Condition(self._lock)
        # Async launch-ahead decode (`InferConfig.overlap` / overlap=,
        # default on): the decode chunk launched at the END of a step
        # commits at the START of the next one, so the sweep, the
        # admission burst (its own prefill dispatch included), and the
        # step epilogue all run while the device decodes. The launch
        # always happens AFTER the commit against the fully-committed
        # ledger — the contiguous server's simpler shape of the paged
        # server's double-buffered scheduler (no planned frame, no
        # patching). overlap=False keeps the sequential loop
        # byte-identical.
        ov = infer_cfg.overlap if overlap is None else bool(overlap)
        self.overlap = bool(ov)
        self._overlap_enabled = self.overlap
        # (decode output futures, _slots snapshot at launch) — the
        # snapshot identity-guards the commit: a slot freed and
        # re-admitted while the chunk was in flight must not receive
        # the old occupant's tokens
        self._inflight: tuple | None = None
        self._iter_overlapped = False  # scheduler-thread scratch
        # Serialises whole scheduler iterations: step() mutates self.state
        # through buffer-donating jits, so two concurrent step() calls
        # (e.g. run_until_idle() on an already start()ed server) would hand
        # one thread a buffer the other just donated.
        self._step_lock = threading.Lock()
        self._rng = jax.random.key(seed)
        # host RNG: default per-request seeds for unseeded requests
        self._host_rng = np.random.default_rng(seed)
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None

    # -- client API ---------------------------------------------------------

    def submit(self, prompt: Sequence[int], *,
               max_new_tokens: int | None = None,
               stream: Callable[[int], None] | None = None,
               sampling: SamplingParams | None = None,
               tenant: str | None = None,
               trace_ctx: tuple | None = None,
               deadline_s: float | None = None,
               fail_handler=None) -> Request:
        if self._stop.is_set():
            # stop() was called or serve_forever died on a fatal error —
            # accepting now would enqueue work nothing will ever drain and
            # hang the caller's result() forever.
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
        if sampling is not None and sampling.regex is not None:
            raise ValueError(
                "regex-constrained decoding is served by the paged "
                "server (PagedInferenceServer), not the contiguous one")
        if len(prompt) == 0:
            raise ValueError("empty prompt")
        _bucket(len(prompt), self.prompt_buckets)  # raises if too long
        max_new = (self.infer_cfg.max_decode_len if max_new_tokens is None
                   else max_new_tokens)
        max_new = min(max_new, self.max_len - len(prompt))
        if max_new <= 0:
            raise ValueError(
                f"prompt of {len(prompt)} tokens leaves no room to decode "
                f"within max_len={self.max_len}")
        if self.qos is not None:
            tenant = self.qos.resolve(tenant)
        else:
            # no registry = no frozen tenant set to bound cardinality:
            # a caller-supplied string must not mint per-tenant labeled
            # metric series (observe_emit labels by req.tenant)
            tenant = None
        req = Request(prompt=list(prompt), max_new_tokens=max_new,
                      stream=stream, sampling=sampling, tenant=tenant,
                      seed_used=resolve_seed(sampling, self._host_rng,
                                             self._lock),
                      submit_time=time.perf_counter())
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
        req._on_cancel = self._handle_cancel
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
                    f"pending queue is full ({self.max_pending} "
                    "requests); retry later")
            if self.qos is not None:
                # per-tenant backpressure AFTER the global bound: a
                # TenantQueueFullError here leaves no trace — the
                # tenant's pending count only advances on success,
                # atomically with the append below
                self.qos.gate_submit(tenant, len(prompt))
            # telemetry BEFORE the append: once the request is in the
            # queue the scheduler thread may admit (even finish) it, and
            # the timeline must stay in lifecycle order. The trace
            # opens here too — AFTER every rejection path above, so a
            # refused submit can never leak into the recorder's live
            # set, and before the append, so the scheduler cannot
            # finish the request ahead of its trace existing.
            if self.trace_recorder is not None:
                tr = self.trace_recorder.begin(req, trace_ctx)
                if tr is not None and tenant is not None:
                    tr.annotate(tenant=tenant)
            req.record_event("submit", req.submit_time)
            self.metrics.observe_submit(req)
            self._pending.append(req)
            # wake an idle scheduler thread parked on the bounded
            # condition wait (serve_forever)
            self._work.notify()
        return req

    def _handle_cancel(self, req: Request) -> None:
        """Client-thread half of Request.cancel(): pending requests
        finish immediately; an active slot is reaped by the sweep at
        the start of the next step()."""
        with self._lock:
            try:
                self._pending.remove(req)
            except ValueError:
                return  # active: the step sweep owns the teardown
            if self.qos is not None:
                self.qos.on_pending_removed(req.tenant)
        req.finish_reason = "cancelled"
        self._complete(req)

    def _complete(self, req: Request) -> None:
        """Terminal bookkeeping for any request leaving the server:
        observe lifecycle metrics (finish reason, e2e latency), then
        unblock waiters. Every path that ends a request goes through
        here so the telemetry can never miss a terminal state.

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

    def _sweep_cancelled(self) -> None:
        now = None
        for slot, req in enumerate(self._slots):
            if req is None:
                continue
            if req._cancel.is_set():
                req.finish_reason = "cancelled"
                self._finish(slot, req)
                continue
            if req.deadline is not None:
                if now is None:  # lazily: zero reads with no deadlines
                    now = time.perf_counter()
                if now > req.deadline:
                    req.finish_reason = "deadline"
                    self._finish(slot, req)
        # expired PENDING requests: reaped here too, so a deadline is
        # honored even if the request never reaches a slot
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

    def generate(self, prompts: Sequence[Sequence[int]], *,
                 max_new_tokens: int | None = None) -> list[list[int]]:
        """Synchronous convenience: submit all, drive to completion."""
        reqs = [self.submit(p, max_new_tokens=max_new_tokens)
                for p in prompts]
        self.run_until_idle()
        return [r.tokens for r in reqs]

    # -- scheduler ----------------------------------------------------------

    def _next_rng(self) -> jax.Array:
        self._rng, sub = jax.random.split(self._rng)
        return sub

    def _emit(self, req: Request, token: int,
              logprob: float | None = None) -> bool:
        """Record one generated token; True if the request just finished."""
        n0 = len(req.emit_times)
        done = emit_token(req, token, logprob, self.infer_cfg)
        # count every token the model computed and the stream accepted —
        # a stop-sequence match truncates the request's token list but
        # those tokens were still generated (throughput accounting)
        if not (done and req.finish_reason == "eos"):
            self.tokens_emitted += 1
            if self.qos is not None:
                self.qos.charge_generated(req.tenant)
        if len(req.emit_times) > n0:  # a stop match truncates instead
            self.metrics.observe_emit(req)
        return done

    def _finish(self, slot: int, req: Request) -> None:
        self._slots[slot] = None
        self.state = _deactivate(self.state, jnp.int32(slot))
        self._complete(req)

    def _admit_pending(self) -> None:
        """Admit every admissible pending request in ONE batched prefill.

        A burst of K pending requests costs one `_admit_batch` dispatch and
        one device_get (the first tokens), so active decode slots stall for
        a single prefill round-trip rather than K of them. The group's
        padded length is the bucket of its longest prompt and its row count
        is padded to a power of two, bounding compilations to
        O(len(prompt_buckets) * log2(max_slots)).
        """
        with self._lock:
            if not self._pending:
                return
            free = [i for i, r in enumerate(self._slots) if r is None]
            group: list[tuple[int, Request]] = []
            while self._pending and len(group) < len(free):
                if self.qos is not None:
                    # deficit-round-robin over tenants (FIFO within a
                    # tenant; degenerates to plain FIFO with a single
                    # tenant) — the fair-share admission policy
                    idx = self.qos.next_admission_index(self._pending)
                    req = self._pending[idx]
                    del self._pending[idx]
                    self.qos.charge_admission(req.tenant,
                                              len(req.prompt))
                    self.qos.on_pending_removed(req.tenant)
                else:
                    req = self._pending.popleft()
                slot = free[len(group)]
                self._slots[slot] = req
                group.append((slot, req))
        if not group:
            return
        self._iter_busy = True
        if self._profiler is not None:
            # the QoS/DRR group selection under the lock was
            # `admission`; the burst's padding and dispatch below are
            # build/device/commit. The boundary's timestamp doubles as
            # the admit moment below — one clock read serves both
            now = self._profiler.enter("build")
        else:
            now = time.perf_counter()  # one read per admission burst
        for _, req in group:
            self.metrics.observe_admit(req, now)
        prefixed, plain = [], []
        for gr in group:  # one predicate evaluation per request
            (prefixed if self._use_prefix(gr[1]) else plain).append(gr)
        if plain:
            self._admit_group_plain(plain)
        if prefixed:
            self._admit_group_prefixed(prefixed)

    def _use_prefix(self, req: Request) -> bool:
        """Fast-path predicate; also tracks hit/miss counters. A miss is
        NOT necessarily an error (mixed traffic is expected) but a server
        that never hits usually means the prefix isn't a token-level
        prefix of the prompts — e.g. a BPE tokenizer merging across the
        prefix/remainder text boundary — so the first miss warns once.
        """
        pfx = self._prefix
        if pfx is None:
            return False
        ok = (len(req.prompt) > len(pfx)
              # cap: verify_step's dense attention is fine for moderate
              # remainders but would materialise O(R x (P0+R)) scores
              # for huge ones — the plain (flash-capable) prefill wins
              and len(req.prompt) - len(pfx) <= self._rem_buckets[-1]
              and req.prompt[:len(pfx)] == pfx)
        if ok:
            self.prefix_hits += 1
        else:
            self.prefix_misses += 1
            if not self._warned_prefix_miss:
                self._warned_prefix_miss = True
                import sys
                print("[server] request did not match the cached prefix "
                      "(token-level comparison) — with a BPE tokenizer, "
                      "text that merges across the prefix boundary never "
                      "matches; check prefix_hits/prefix_misses",
                      file=sys.stderr)
        return ok

    def _pad_group(self, group, token_rows, buckets):
        """Padded (token rows, true_lens, slot indices) numpy arrays for
        an admission burst: width = the bucket of the longest entry, row
        count = next power of two; rows filled, padding rows target
        slot == max_slots (out of range -> dropped by the scatters)."""
        pb = _bucket(max(len(t) for t in token_rows), buckets)
        gpad = 1
        while gpad < len(group):
            gpad *= 2
        rows = np.full((gpad, pb), self.infer_cfg.pad_token_id, np.int32)
        true_lens = np.ones((gpad,), np.int32)
        slots = np.full((gpad,), self.max_slots, np.int32)
        for i, toks_i in enumerate(token_rows):
            rows[i, :len(toks_i)] = toks_i
            true_lens[i] = len(toks_i)
            slots[i] = group[i][0]
        return rows, true_lens, slots

    def _ensure_penalty_state(self, group) -> None:
        """Materialize the (B, V) penalty buffers on the first admission
        that needs them (one-time recompile of the dispatches; slots
        admitted before materialization carry neutral penalties, for
        which the buffers are read-irrelevant)."""
        if self.state.prompt_mask is not None or not any(
                req.sampling is not None
                and req.sampling.needs_penalty_state()
                for _, req in group):
            return
        s = self.state
        v = self.cfg.vocab_size
        self.state = SlotState(
            k=s.k, v=s.v, length=s.length, last_token=s.last_token,
            active=s.active, k_scale=s.k_scale, v_scale=s.v_scale,
            samp=s.samp,
            prompt_mask=jnp.zeros((self.max_slots, v), bool),
            out_counts=jnp.zeros((self.max_slots, v), jnp.int32))

    def _group_rows(self, group, gpad: int) -> tuple[SamplingRows, bool]:
        """SamplingRows for an admission burst, padded to `gpad` rows
        (the row count `_pad_group` chose — the jitted admission needs
        the two paddings in lockstep) + whether any member needs the
        device rows path. Padding rows are zeros (their slot index drops
        every scatter anyway)."""
        params_list = [req.sampling for _, req in group]
        seeds = [req.seed_used for _, req in group]
        plens = [len(req.prompt) for _, req in group]
        params_list += [None] * (gpad - len(group))
        seeds += [0] * (gpad - len(group))
        plens += [0] * (gpad - len(group))
        rows = make_rows(params_list, self.infer_cfg, seeds,
                         prompt_lens=plens)
        use = any(sp is not None and sp.needs_device_rows(self.infer_cfg)
                  for sp in params_list)
        bias = any(sp is not None and bool(sp.logit_bias)
                   for sp in params_list)
        return rows, use, bias

    def _rows_mode(self) -> tuple[bool, bool]:
        """(use_rows, use_bias): whether any ACTIVE request needs
        per-request device sampling / logit_bias — such a request's
        whole lifetime then runs rows-mode dispatches, which is what
        keeps its penalty counts advancing."""
        live = [r.sampling for r in self._slots
                if r is not None and r.sampling is not None]
        return (any(sp.needs_device_rows(self.infer_cfg) for sp in live),
                any(bool(sp.logit_bias) for sp in live))

    def _admit_group(self, group, token_rows, buckets, run_fn) -> None:
        """Shared burst plumbing: pad, dispatch one batched admission,
        emit first tokens."""
        prof = self._profiler
        if prof is not None:
            prof.enter("build")  # a boundary for a step's second burst
        rows, true_lens, slots = self._pad_group(group, token_rows,
                                                 buckets)
        self._ensure_penalty_state(group)
        samp_rows, use_rows, use_bias = self._group_rows(
            group, rows.shape[0])
        if prof is not None:
            prof.enter("device")
        self.state, toks, lps = run_fn(
            jnp.asarray(rows), jnp.asarray(true_lens), jnp.asarray(slots),
            jax.tree.map(jnp.asarray, samp_rows), use_rows, use_bias)
        toks, lps = jax.device_get((toks, lps))
        if prof is not None:
            prof.enter("commit")
        for i, (slot, req) in enumerate(group):
            if self._emit(req, int(toks[i]), float(lps[i])):
                self._finish(slot, req)

    def _admit_group_plain(self, group) -> None:
        def run(rows, tl, sl, samp, use_rows, use_bias):
            return _admit_batch(self.params, self.state, rows, tl, sl,
                                self._next_rng(), samp, cfg=self.cfg,
                                infer_cfg=self.infer_cfg,
                                use_rows=use_rows, use_bias=use_bias)

        self._admit_group(group, [r.prompt for _, r in group],
                          self.prompt_buckets, run)

    def _admit_group_prefixed(self, group) -> None:
        p0 = len(self._prefix)

        def run(rows, tl, sl, samp, use_rows, use_bias):
            return _admit_batch_prefixed(
                self.params, self.state, self._prefix_kv, rows, tl, sl,
                self._next_rng(), samp,
                jnp.asarray(self._prefix, jnp.int32), cfg=self.cfg,
                infer_cfg=self.infer_cfg, use_rows=use_rows,
                use_bias=use_bias)

        self._admit_group(group, [req.prompt[p0:] for _, req in group],
                          self._rem_buckets, run)

    @property
    def num_active(self) -> int:
        return sum(r is not None for r in self._slots)

    @property
    def num_pending(self) -> int:
        with self._lock:
            return len(self._pending)

    def _chunk_len(self) -> int:
        """Decode steps to run this iteration: bounded by decode_chunk and
        by the tightest remaining token budget among active requests (so a
        chunk can never decode past a request's max_new_tokens, which also
        bounds its cache length — submit() guarantees prompt + max_new <=
        max_len). Rounded down to a power of two to bound compilations."""
        remaining = min(r.max_new_tokens - len(r.tokens)
                        for r in self._slots if r is not None)
        n = min(self.decode_chunk, max(1, remaining))
        p = 1
        while p * 2 <= n:
            p *= 2
        return p

    def step(self) -> int:
        """One scheduler iteration; returns number of active slots.

        Thread-safe: concurrent callers serialise on an internal lock.
        """
        with self._step_lock:
            self.tracer.step_start()
            prof = self._profiler
            try:
                if self._faults is not None:
                    # injected host stall: the scheduler thread pays
                    # it exactly like a slow host/device round would
                    self._faults.maybe_stall()
                if prof is not None:
                    prof.begin()
                self._iter_busy = False
                self._iter_overlapped = False
                n_active = self._step_locked()
                if self._iter_busy:
                    if prof is not None:
                        # epilogue = the post-commit tail of the step;
                        # phases feed the rolling histograms (the
                        # contiguous server's only phase sink). An
                        # overlapped step's sweep/admission/build ran
                        # under the in-flight decode — fold them into
                        # the `overlap` series (see iteration_profile)
                        prof.enter("epilogue")
                        prof.end()
                        hists = self._phase_hists
                        phases = prof.phases_ms()
                        if self._iter_overlapped:
                            hists["overlap"].observe(
                                sum(phases.get(p, 0.0)
                                    for p in OVERLAP_PHASES))
                            for p, v in phases.items():
                                if p not in OVERLAP_PHASES:
                                    hists[p].observe(v)
                        else:
                            for p, v in phases.items():
                                hists[p].observe(v)
                    self.last_busy_ts = time.time()
                    if self._anomaly is not None:
                        # thin per-step feed (no flight recorder here):
                        # one clock read, matching the brownout
                        # detector's per-observe budget
                        with self._lock:
                            pending = len(self._pending)
                        fired = self._anomaly.observe_iteration(
                            now=time.perf_counter(), pending=pending)
                        if fired:
                            self._on_anomaly(fired)
                else:
                    self.idle_iterations += 1
                    if prof is not None:
                        prof.close()
                return n_active
            finally:
                self.tracer.step_end()

    def _step_locked(self) -> int:
        if self._overlap_enabled:
            return self._step_locked_overlap()
        prof = self._profiler
        self._sweep_cancelled()
        if prof is not None:
            prof.enter("admission")
        self._admit_pending()
        if self.num_active == 0:
            return 0
        self._iter_busy = True
        if self._faults is not None:
            # injected dispatch failure: raises before any device work,
            # crashing this iteration the way a poisoned program would
            # (serve_forever catches, _fail_all unblocks every waiter)
            self._faults.check("dispatch")
        if prof is not None:
            prof.enter("admission")  # a boundary after a burst's commit
        n = self._chunk_len()
        use_rows, use_bias = self._rows_mode()
        if prof is not None:
            # decode planning was `admission`; the dispatch statements
            # below (arg transfer + launch + the sanctioned device_get)
            # are the device phase — the contiguous decode stages no
            # host arrays, so its build phase is empty by construction
            prof.enter("device")
        if n == 1:
            self.state, out = _decode(
                self.params, self.state, self._next_rng(),
                cfg=self.cfg, infer_cfg=self.infer_cfg,
                use_rows=use_rows, use_bias=use_bias)
            toks, lps = jax.device_get(out)
            chunk = np.asarray(toks)[None]       # (1, B)
            lchunk = np.asarray(lps)[None]
        else:
            self.state, out = _decode_chunk(
                self.params, self.state, self._next_rng(),
                cfg=self.cfg, infer_cfg=self.infer_cfg, n_steps=n,
                use_rows=use_rows, use_bias=use_bias)
            toks, lps = jax.device_get(out)
            chunk = np.asarray(toks)             # (n, B)
            lchunk = np.asarray(lps)
        if prof is not None:
            prof.enter("commit")
        for t in range(chunk.shape[0]):
            for slot, req in enumerate(self._slots):
                if req is not None and self._emit(
                        req, int(chunk[t, slot]),
                        float(lchunk[t, slot])):
                    self._finish(slot, req)
        return self.num_active

    def _launch_decode(self, use_rows: bool, use_bias: bool):
        """Launch one decode chunk asynchronously (no device_get) —
        the ONE dispatch site the overlap steady-state launch and the
        pipeline-fill prime share, so a signature change can never
        desync them. The round count comes from `_chunk_len` HERE
        (the audited pow2 planner — DD4's boundedness requires the
        static `n_steps` to be derived inside the dispatching
        function, not passed through an unbounded parameter). Returns
        the output futures."""
        n = self._chunk_len()
        if n == 1:
            self.state, out = _decode(
                self.params, self.state, self._next_rng(),
                cfg=self.cfg, infer_cfg=self.infer_cfg,
                use_rows=use_rows, use_bias=use_bias)
        else:
            self.state, out = _decode_chunk(
                self.params, self.state, self._next_rng(),
                cfg=self.cfg, infer_cfg=self.infer_cfg, n_steps=n,
                use_rows=use_rows, use_bias=use_bias)
        return out

    def _commit_decode_chunk(self, out, slots, prof) -> None:
        """Sync one decode chunk and emit its tokens against `slots`
        (a _slots snapshot for a launch-ahead commit, the live list on
        the pipeline-fill path) with the per-row identity guard — THE
        one commit-emit block both overlap paths share, and the
        sanctioned per-iteration host sync of the pipelined
        contiguous loop (dispatch-discipline DD2)."""
        if prof is not None:
            prof.enter("device")
        toks, lps = jax.device_get(out)
        if prof is not None:
            prof.enter("commit")
        chunk, lchunk = np.asarray(toks), np.asarray(lps)
        if chunk.ndim == 1:
            chunk, lchunk = chunk[None], lchunk[None]
        for t in range(chunk.shape[0]):
            for slot, req in enumerate(slots):
                if req is not None and self._slots[slot] is req \
                        and self._emit(req, int(chunk[t, slot]),
                                       float(lchunk[t, slot])):
                    self._finish(slot, req)

    def _step_locked_overlap(self) -> int:
        """Pipelined iteration (overlap on): commit the decode chunk
        launched at the END of the previous step, then launch the next
        chunk and return with it in flight — the sweep, the admission
        burst (its own prefill dispatch and sanctioned sync included),
        and the next step's epilogue all run while the device decodes.

        Unlike the paged server's planner, nothing here reads stale
        state: the launch always follows the commit, so the chunk
        length and the slot snapshot see the fully-committed ledger.
        The snapshot identity-guards the commit (a slot freed by the
        sweep and re-admitted mid-flight must not receive the old
        occupant's tokens; its device row is overwritten by the
        admission program, which chains after the in-flight decode).
        With nothing in flight (cold start / post-idle) the step runs
        the sequential dispatch-sync-commit, then PRIMES the pipeline
        with a launch-ahead before returning — so per-step emission
        counts match the sequential loop exactly."""
        prof = self._profiler
        self._sweep_cancelled()
        if prof is not None:
            prof.enter("admission")
        self._admit_pending()
        committed = False
        if self._inflight is not None:
            self._iter_busy = True
            self._iter_overlapped = True
            out, snap = self._inflight
            self._inflight = None
            self._commit_decode_chunk(out, snap, prof)
            committed = True
        if self.num_active == 0:
            return 0
        self._iter_busy = True
        if self._faults is not None:
            # injected dispatch failure: raises before any device work
            # (with a chunk possibly in flight the commit above already
            # ran, so no synced tokens are ever lost to the injection)
            # analysis: allow[lifecycle-discipline] deliberate raise point: a dispatch fault fails the whole step and _fail_all tears every slot down, so the _iter_busy/_inflight pair is never read torn
            self._faults.check("dispatch")
        if prof is not None:
            # steady state: the launch tail after the commit; on the
            # fill path the dispatch through _commit_decode_chunk's
            # sync is the device phase
            prof.enter("launch" if committed else "device")
        use_rows, use_bias = self._rows_mode()
        out = self._launch_decode(use_rows, use_bias)
        if committed:
            # steady state: leave the chunk in flight (launch-ahead)
            self._inflight = (out, list(self._slots))
            return self.num_active
        # pipeline fill: sequential commit of the chunk just launched
        self._commit_decode_chunk(out, list(self._slots), prof)
        if self.num_active:
            # prime: the next chunk overlaps the NEXT step's host work
            # (its injected-fault site is the NEXT step's check — one
            # check per step, matching the sequential hit pacing)
            if prof is not None:
                prof.enter("launch")
            use_rows, use_bias = self._rows_mode()
            out = self._launch_decode(use_rows, use_bias)
            self._inflight = (out, list(self._slots))
        return self.num_active

    def _fail_all(self, exc: BaseException) -> None:
        """Unblock every in-flight and pending request after a fatal
        scheduler error (otherwise result() waiters hang forever)."""
        # drop any launched-but-uncommitted decode chunk's futures:
        # their tokens belong to requests failed below
        self._inflight = None
        with self._lock:
            pending, self._pending = list(self._pending), collections.deque()
        for slot, req in enumerate(self._slots):
            if req is not None:
                self._slots[slot] = None
                req.finish_reason = f"error: {exc!r}"
                self._complete(req)
        for req in pending:
            if self.qos is not None:
                self.qos.on_pending_removed(req.tenant)
            req.finish_reason = f"error: {exc!r}"
            self._complete(req)

    # -- observability ------------------------------------------------------

    def _collect_metrics(self) -> None:
        """Scrape-path mirror of host scheduler state into the registry
        (occupancy gauges + lifetime counters the server already keeps)."""
        reg = self.metrics.registry
        reg.gauge("active_slots",
                  "Requests currently decoding").set(self.num_active)
        reg.gauge("pending_requests",
                  "Queued requests awaiting admission").set(
                      self.num_pending)
        reg.counter("tokens_emitted_total",
                    "Lifetime generated tokens").set_total(
                        self.tokens_emitted)
        # idle-vs-dead disambiguation (mirrors the paged server)
        reg.counter("idle_iterations_total",
                    "step() calls that dispatched nothing").set_total(
                        self.idle_iterations)
        reg.gauge("last_busy_ts",
                  "Unix time of the last busy iteration (0 until the "
                  "first)").set(self.last_busy_ts)
        from cloud_server_tpu.inference.faults import SITES
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
        reg.counter("prefix_hits_total",
                    "Admissions served from the cached prefix"
                    ).set_total(self.prefix_hits)
        reg.counter("prefix_misses_total",
                    "Admissions that missed the cached prefix"
                    ).set_total(self.prefix_misses)
        if self.qos is not None:
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
        """The /stats `iteration_profile` summary (see the paged
        server's docstring). None with profiling disabled."""
        from cloud_server_tpu.inference.iteration_profile import (
            profile_summary)
        return profile_summary(self.metrics_snapshot())

    @property
    def ready(self) -> bool:
        """Readiness (vs the liveness /healthz always reported): False
        while draining or stopped, so load balancers — and the
        ReplicatedRouter's placement — stop routing new work here
        while in-flight requests finish."""
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

    def fault_stats(self) -> dict | None:
        """Per-site injected-fault hit/fired counts (the /stats
        `faults` block); None with no FaultPlan. Scrape path only."""
        return None if self._faults is None else self._faults.stats()

    def overlap_stats(self) -> dict:
        """The /stats `overlap` block (see the paged server's twin):
        launch-ahead decode pipelining state. Scrape path only."""
        return {
            "enabled": self.overlap,
            "active": self._overlap_enabled,
            "inflight_depth": 0 if self._inflight is None else 1,
        }

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
        from five endpoints — metrics, retained + tail span trees,
        SLO report, fault/anomaly state — as one JSON-ready dict.
        `n` bounds the ring exports. Scrape path only (auto-capture
        calls it once per activation edge, which is rare by the
        watchdog's hysteresis)."""
        return {
            "schema": "cloud_server.debug_bundle/v1",
            "trigger": trigger,
            "ts": time.time(),
            "anomaly": self.anomaly_stats(),
            "metrics": self.metrics_snapshot(),
            "profile": self.iteration_profile_stats(),
            "traces": self.trace_trees(n),
            "tail_traces": self.tail_trace_trees(n),
            "tail_retention": self.tail_trace_stats(),
            "slo": self.slo_report(),
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
        while self.num_pending or self.num_active:
            self.step()

    # -- background serving -------------------------------------------------

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
            # cooperative yield after every busy step (see the paged
            # server's twin): stream-consumer threads must get a
            # drain window even when the pipelined syncs return
            # instantly
            if busy:
                time.sleep(0)
            if busy == 0 and self.num_pending == 0:
                # bounded condition wait, not a short sleep poll: idle
                # CPU iterations stay bounded while submit() wakes the
                # thread immediately (see the paged server's twin)
                with self._work:
                    if not self._pending and not self._stop.is_set():
                        self._work.wait(idle_sleep_s)

    def drain(self, timeout: float | None = None, *,
              _resume_on_timeout: bool = True) -> bool:
        """Graceful drain: refuse new submissions, let everything
        already accepted finish. Returns True once idle — and STAYS
        draining (quiesced): call resume() to accept again, or stop()
        to shut down. On timeout returns False and RESUMES accepting
        (the in-flight work keeps running; call stop() to actually shut
        down — it fails whatever is still live so no waiter hangs).
        Same contract as the paged server's, including the
        `_resume_on_timeout=False` internal latch stop(drain=True) uses
        so a timed-out drain cannot reopen submission in the window
        before _stop is set."""
        with self._lock:
            self._draining = True
        deadline = (None if timeout is None
                    else time.perf_counter() + timeout)
        while self.num_pending or self.num_active:
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
            # keep _draining latched across a timed-out drain (see the
            # paged server's stop() for why)
            self.drain(timeout, _resume_on_timeout=False)
        self._stop.set()
        with self._lock:
            # wake a scheduler thread parked on the idle wait
            self._work.notify_all()
        if self._thread is not None:
            self._thread.join(timeout=30)
            self._thread = None
        if self.num_pending or self.num_active:
            # a timed-out (or skipped) drain left live requests behind:
            # nothing will ever step them now — unblock their waiters
            self._fail_all(RuntimeError(
                "server stopped before the request completed"))

    def start(self) -> "InferenceServer":
        self._stop.clear()
        self._draining = False  # a stopped-then-restarted server serves
        self._thread = threading.Thread(target=self.serve_forever,
                                        daemon=True, name="inference-server")
        self._thread.start()
        return self
