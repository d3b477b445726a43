"""Runtime capability gates for network-dependent tests.

The streaming-disconnect lifecycle test
(test_lifecycle.py::test_disconnect_aborts_streaming_request) relies
on the OS surfacing a peer close as a SEND error (BrokenPipeError /
ECONNRESET) on a loopback socket within a bounded number of writes —
that error is exactly what makes the HTTP front-end cancel the
request. Some sandboxed network stacks never deliver it: the client's
close is swallowed and the server's writes keep succeeding (or block)
until the generation runs to completion. That is an ENVIRONMENT
ceiling, not a code regression — so the test is gated on a one-shot
runtime probe that reproduces the exact mechanism (server keeps
writing after the client closed) and reports whether an error ever
surfaced. Gated-off, the test skips with an explicit reason instead
of failing red."""

from __future__ import annotations

import functools
import socket
import time

import pytest


@functools.lru_cache(maxsize=1)
def loopback_disconnect_detectable(max_writes: int = 100,
                                   write_gap_s: float = 0.01) -> bool:
    """True when a loopback peer's close surfaces as a send error on
    this host within ~max_writes small writes (the streaming-server
    shape: repeated chunk + flush). A send that merely BLOCKS (buffer
    full, no RST ever delivered) counts as NOT detectable — that is
    precisely the sandbox failure mode being probed."""
    listener = socket.socket()
    conn = cli = None
    try:
        listener.bind(("127.0.0.1", 0))
        listener.listen(1)
        cli = socket.create_connection(listener.getsockname(), timeout=5)
        conn, _ = listener.accept()
        conn.settimeout(2)
        cli.close()  # the client walks away
        chunk = b"x" * 4096
        try:
            for _ in range(max_writes):
                conn.sendall(chunk)
                time.sleep(write_gap_s)  # let the peer's RST arrive
        except socket.timeout:
            return False  # writes blocked, no error ever surfaced
        except OSError:
            return True  # BrokenPipe / ECONNRESET: capability present
        return False  # every write "succeeded" into the void
    except OSError:
        return False  # no loopback at all: the gated test cannot run
    finally:
        for s in (conn, cli, listener):
            if s is not None:
                try:
                    s.close()
                except OSError:
                    pass


requires_loopback_disconnect = pytest.mark.skipif(
    not loopback_disconnect_detectable(),
    reason=("environment limitation, not a regression: a loopback "
            "peer's close never surfaces as a send error in this "
            "sandbox, so a streaming client disconnect cannot be "
            "observed by the server (probe: tests/net_compat.py)"))
