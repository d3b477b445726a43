"""Profiling hooks around jax.profiler.

`annotate` names a host-side region on the profiler's clock: the
schedulers' `IterationProfiler` opens its `sched/<phase>` events with it
(inference/iteration_profile.py stays jax-free and is handed this
function). `capture_trace` is the one place the program starts a trace
(`POST /debug/trace`, the servers' `_StepTracer`); `start_profiler_server`
enables on-demand remote capture (`generate --profiler-port`).
"""

from __future__ import annotations

import contextlib
import os
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


def start_profiler_server(port: int = 9999):
    """Expose this process to on-demand profiling (tensorboard capture)."""
    return jax.profiler.start_server(port)
