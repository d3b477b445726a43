"""The request: what a client submits and waits on, and the rules every
server, router and front end shares about it.

`Request` carries the prompt, the sampling controls, the tokens as they
arrive, the latency and lifecycle trail, cancellation and the router's
failover hooks; `emit_token` is the one rule for recording a generated
token (eos, stop sequences, length); `resolve_seed` fixes a request's
seed at submit; `QueueFullError` is the backpressure signal that
`http_server`, `router`, `qos` and `faults` map to a retryable 429 or
503. The module sits under all of them and under `paged_server`, and
imports no server.
"""

from __future__ import annotations

import dataclasses
import threading
import time
import uuid
from typing import Callable, Sequence

from cloud_server_tpu.config import InferConfig
from cloud_server_tpu.inference.sampling import SamplingParams


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
    # multi-LoRA serving: registered adapter name
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
    re-admits with the same rows."""
    if sampling is not None and sampling.seed is not None:
        return int(sampling.seed)
    with lock:
        return int(host_rng.integers(0, 2 ** 32))


def emit_token(req: Request, token: int, logprob: float | None,
               infer_cfg: InferConfig,
               deliveries: list | None = None) -> bool:
    """Record one generated token on `req`; True when the request just
    finished (eos / stop sequence / length). The single emit rule.
    Handed `deliveries` (the server's list, run after its next launch),
    the stream call is put there as `(req, token)` and nobody is woken
    here.

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
