"""Profiling hooks around jax.profiler.

`annotate` names a host-side region on the profiler's clock: the
schedulers' `IterationProfiler` opens its `sched/<phase>` events with it
(inference/iteration_profile.py stays jax-free and is handed this
function). `capture_trace` is the one place the program starts a trace
(`POST /debug/trace`, the server's `_StepTracer`); `start_profiler_server`
enables on-demand remote capture (`generate --profiler-port`).
"""

from __future__ import annotations

import contextlib
import os
import sys
import threading
from typing import Iterator

import jax


def annotate(name: str, **stats):
    """A trace event named `name` around a `with` block (a `TraceMe`),
    `stats` readable as the event's stats in the host plane. With no
    capture running it is an inactive check."""
    return jax.profiler.TraceAnnotation(name, **stats)


@contextlib.contextmanager
def capture_trace(logdir: str | os.PathLike) -> Iterator[None]:
    """Capture a device+host trace for the enclosed block into `logdir`
    (view with TensorBoard's profile plugin or Perfetto): the device's
    programs and ops, and above them the host's `annotate` events.

    The Python tracer is off: JAX's default (`python_tracer_level` 1)
    hooks every Python call of the traced threads, under which the
    scheduler loop it captures ran 1.2 to 1.6 times slower, against 1.0
    times with it off (PERF.md, PR 25). `host_tracer_level` 1 is the lowest
    that keeps `TraceMe`s of level 1, which `annotate`'s events are;
    the device's planes depend on neither."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(os.fspath(logdir), profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


class _StepTracer:
    """On-demand profiling of the next N scheduler iterations into a
    jax profiler trace (`capture_trace`), armed from any thread (the
    HTTP /debug/trace endpoint) and driven by the
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
            try:
                cm = capture_trace(logdir)
                cm.__enter__()
            except Exception as exc:  # noqa: BLE001 — see class docstring
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
                print(f"[server] trace capture failed to stop: {exc!r}",
                      file=sys.stderr)


def start_profiler_server(port: int = 9999):
    """Expose this process to on-demand profiling (tensorboard capture)."""
    return jax.profiler.start_server(port)
