"""The window-end rule against stub servers: the generator ends on time,
exits 0, writes its timeline, and waits for nothing."""
import json
import os
import socket
import subprocess
import sys
import threading
import time

import pytest

from cellbench import stats

LOADGEN = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "loadgen.py")


class Stub:
    """A TCP server that accepts and reads a request and then either
    never answers (`mode="silent"`) or streams a token line every
    `every` seconds for ever (`mode="slow"`)."""

    def __init__(self, mode, every=0.05):
        self.mode, self.every = mode, every
        self.sock = socket.socket()
        self.sock.bind(("127.0.0.1", 0))
        self.sock.listen(64)
        self.port = self.sock.getsockname()[1]
        self.broken = 0
        self.stop = threading.Event()
        threading.Thread(target=self.accept, daemon=True).start()

    def accept(self):
        while not self.stop.is_set():
            try:
                conn, _ = self.sock.accept()
            except OSError:
                return
            threading.Thread(target=self.serve, args=(conn,),
                             daemon=True).start()

    def serve(self, conn):
        try:
            conn.recv(1 << 20)
            if self.mode == "slow":
                conn.sendall(b"HTTP/1.1 200 OK\r\nContent-Type: "
                             b"application/x-ndjson\r\nConnection: close"
                             b"\r\n\r\n")
                while not self.stop.is_set():
                    conn.sendall(b'{"token": 5, "logprob": -1.0}\n')
                    time.sleep(self.every)
            else:
                while not self.stop.is_set():
                    if conn.recv(1) == b"":
                        self.broken += 1
                        return
        except OSError:
            self.broken += 1
        finally:
            conn.close()

    def close(self):
        self.stop.set()
        self.sock.close()


def run_loadgen(tmp_path, port, requests, end_s, end_file_at=None, **spec):
    """Run the generator to its end. `end_s` is in the spec from the
    start, or, with `end_file_at`, written into the spec's `end_file`
    that many seconds after the start, as `serve.end_phase` does."""
    req_file = tmp_path / "requests.json"
    req_file.write_text(json.dumps(requests))
    out = tmp_path / "timeline.json"
    t0 = time.monotonic() + 0.3
    spec_file = tmp_path / "spec.json"
    if end_file_at is None:
        spec["end_s"] = end_s
    else:
        spec["end_file"] = str(tmp_path / "end.json")
    spec_file.write_text(json.dumps(dict(
        host="127.0.0.1", port=port,
        requests_file=str(req_file), t0=t0, grace_s=1.0,
        out=str(out), **spec)))
    started = time.monotonic()
    proc = subprocess.Popen([sys.executable, LOADGEN, str(spec_file)],
                            stdout=subprocess.PIPE, stderr=subprocess.PIPE)
    if end_file_at is not None and end_file_at < spec["max_s"]:
        time.sleep(end_file_at)
        from cellbench import serve
        serve.end_phase(str(tmp_path), end_s)
    proc.stderr_text = proc.communicate(timeout=end_s + 15)[1].decode()
    took = time.monotonic() - started
    return proc, took, json.loads(out.read_text())


def test_a_server_that_never_answers_does_not_hold_the_run(tmp_path):
    stub = Stub("silent")
    try:
        reqs = [{"id": i, "tokens": [1, 2, 3], "max_new": 4}
                for i in range(9)]
        proc, took, tl = run_loadgen(tmp_path, stub.port, reqs, 1.5,
                                     clients=5, ramp_s=1.0)
    finally:
        time.sleep(0.2)
        broken = stub.broken
        stub.close()
    assert proc.returncode == 0, proc.stderr_text
    assert took < 1.5 + 0.3 + 1.0 + 3.0  # end, lead, grace, start-up
    assert tl["threads_left"] == 0
    recs = tl["records"]
    assert len(recs) == 5 and all(r["sent"] is not None for r in recs)
    # unfinished at the end is not a failure, and is not waited for
    assert all(r["error"] is None and not r["done"] for r in recs)
    assert stats.count_attempted_failed(recs, 0.0, 1.5) == (5, 0)
    samples, censored = stats.ttft_samples(recs, 0.0, 1.5)
    assert censored == 5 and max(samples) == pytest.approx(1.5, abs=0.05)
    assert broken == 5  # the server saw every connection break


def test_closed_loop_ramps_and_stops_mid_stream(tmp_path):
    stub = Stub("slow", every=0.05)
    try:
        reqs = [{"id": i, "tokens": [1], "max_new": 1000}
                for i in range(20)]
        proc, took, tl = run_loadgen(tmp_path, stub.port, reqs, 2.0,
                                     clients=4, ramp_s=1.0)
    finally:
        stub.close()
    assert proc.returncode == 0, proc.stderr_text
    recs = tl["records"]
    assert len(recs) == 4  # one endless request per client, none finished
    sent = sorted(r["sent"] for r in recs)
    assert sent[-1] - sent[0] == pytest.approx(0.75, abs=0.2)  # the ramp
    assert all(r["error"] is None for r in recs)
    n = stats.tokens_in_window(recs, 1.0, 2.0)
    assert 50 <= n <= 90  # 4 streams at 20 tokens/s for 1 s
    assert all(t < 2.0 + 1.1 for r in recs for t in r["token_times"])
    assert took < 2.0 + 0.3 + 1.0 + 3.0


@pytest.mark.parametrize("written_at,max_s,ends", [
    (0.8, 30.0, 2.0), (99.0, 1.5, 1.5)],
    ids=["the-end-the-parent-writes", "max_s-when-it-writes-none"])
def test_the_end_comes_from_the_parents_file(tmp_path, written_at, max_s,
                                             ends):
    stub = Stub("slow", every=0.05)
    try:
        reqs = [{"id": i, "tokens": [1], "max_new": 1000}
                for i in range(8)]
        proc, took, tl = run_loadgen(tmp_path, stub.port, reqs, 2.0,
                                     end_file_at=written_at, max_s=max_s,
                                     clients=2, ramp_s=0.2)
    finally:
        stub.close()
    assert proc.returncode == 0, proc.stderr_text
    assert tl["end_s"] == ends
    assert tl["ended_at"] == pytest.approx(ends, abs=0.3)
    assert took < ends + 0.3 + 1.0 + 3.0
    last = max(t for r in tl["records"] for t in r["token_times"])
    assert ends - 0.3 < last < ends + 1.1
