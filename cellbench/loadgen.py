"""The load generator: a child process that never imports JAX.

    python cellbench/loadgen.py <spec.json>

The spec names the server (`host`, `port`), the closed loop (`clients`
clients, started evenly over `ramp_s`, each sending its next request
when the last one ends), the file of requests, the monotonic instant
`t0` that is time 0 of the phase, and when the phase ends: `end_s`, or
`end_file`, a file the parent writes once it knows (`{"end_s": ...}`;
the window starts when the server's ramp is over, which the parent sees
and the generator does not), with `max_s` as the latest end whatever
happens. All times written are seconds from `t0` on `time.monotonic()`,
which on Linux is one clock for every process of the machine.

At `end_s` the generator stops sending and does NOT wait for the
backlog: every open connection is shut down (the front-end cancels a
request on a broken pipe), the threads get `grace_s` to notice, and the
timeline is written as it stands. A request unfinished then is not an
error; one the server refused, broke, or ended with an error line is.
The exit code is 0 whenever the timeline was written.
"""

from __future__ import annotations

import http.client
import json
import os
import socket
import sys
import threading
import time


class Phase:
    """One phase's shared state: the clock, the stop flag, the open
    connections and the records."""

    def __init__(self, spec: dict, requests: list[dict]):
        self.spec = spec
        self.requests = requests
        self.t0 = float(spec["t0"])
        self.stop = threading.Event()
        self.lock = threading.Lock()
        self.socks: set[socket.socket] = set()
        self.records: list[dict] = []
        self.next_index = 0
        # the phase's end, seconds from t0: known from the start, or
        # `max_s` until the parent's `end_file` says
        self.end_s = float(spec.get("end_s", spec.get("max_s", 0.0)))

    def now(self) -> float:
        return time.monotonic() - self.t0

    def take(self) -> dict | None:
        """The next unsent request of a closed loop, or None."""
        with self.lock:
            if self.next_index >= len(self.requests):
                return None
            r = self.requests[self.next_index]
            self.next_index += 1
            return r

    def send(self, req: dict) -> None:
        """Send one request and record its token lines until it ends,
        the server fails it, or the phase stops."""
        rec = {"id": req["id"], "prompt_len": len(req["tokens"]),
               "max_new": req["max_new"], "sent": None, "token_times": [],
               "tokens": [], "logprobs": [], "done": False,
               "finish": None, "error": None, "status": None,
               "ended": None}
        with self.lock:
            self.records.append(rec)
        conn = http.client.HTTPConnection(
            self.spec["host"], int(self.spec["port"]),
            timeout=float(self.spec.get("socket_timeout_s", 120.0)))
        sock = None
        body = json.dumps({"tokens": req["tokens"],
                           "max_new_tokens": req["max_new"]})
        try:
            rec["sent"] = self.now()
            conn.request("POST", "/generate", body=body,
                         headers={"Content-Type": "application/json"})
            # the response takes the socket over (`Connection: close`),
            # so the connection forgets it: keep it to break it at the end
            sock = conn.sock
            with self.lock:
                self.socks.add(sock)
            resp = conn.getresponse()
            rec["status"] = resp.status
            if resp.status != 200:
                rec["error"] = f"http {resp.status}: " \
                    f"{resp.read(300).decode('utf-8', 'replace')}"
                return
            while True:
                line = resp.readline()
                t = self.now()
                if not line:
                    break
                msg = json.loads(line)
                if "token" in msg:
                    rec["token_times"].append(t)
                    rec["tokens"].append(msg["token"])
                    rec["logprobs"].append(msg.get("logprob"))
                elif msg.get("done"):
                    rec["done"] = True
                    rec["finish"] = msg.get("finish_reason")
                    break
                elif "error" in msg:
                    rec["error"] = str(msg["error"])[:300]
                    break
            if not rec["done"] and not rec["error"] \
                    and not self.stop.is_set():
                rec["error"] = "stream ended without a done line"
        except (OSError, http.client.HTTPException, ValueError) as exc:
            # the phase's own shutdown breaks the sockets on purpose:
            # only a failure before the stop is the server's
            if not self.stop.is_set():
                rec["error"] = f"{type(exc).__name__}: {exc}"[:300]
        finally:
            rec["ended"] = self.now()
            with self.lock:
                self.socks.discard(sock)
            try:
                conn.close()
            except OSError:
                pass

    def close_all(self) -> None:
        """Break every open connection: no request is waited for."""
        with self.lock:
            socks = list(self.socks)
        for sock in socks:
            try:
                sock.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass


def run_closed(ph: Phase) -> list[threading.Thread]:
    clients = int(ph.spec["clients"])
    ramp = float(ph.spec.get("ramp_s", 0.0))
    # a generator that came up late still ramps: the clients' starts are
    # spread over `ramp_s` from now, never bunched behind a passed t0
    base = max(0.0, ph.now())

    def client(i: int) -> None:
        wait = base + ramp * i / max(clients, 1) - ph.now()
        if wait > 0 and ph.stop.wait(wait):
            return
        while not ph.stop.is_set() and ph.now() < ph.end_s:
            req = ph.take()
            if req is None:
                return
            ph.send(req)

    threads = [threading.Thread(target=client, args=(i,), daemon=True)
               for i in range(clients)]
    for th in threads:
        th.start()
    return threads


def wait_for_end(ph: Phase) -> None:
    """Sleep until the phase's end. With an `end_file`, look for it 50
    times a second until it is there and take the end from it; `max_s`
    ends a phase whose parent never wrote one."""
    end_file = ph.spec.get("end_file")
    while True:
        if end_file and os.path.exists(end_file):
            with open(end_file) as f:
                ph.end_s = min(float(json.load(f)["end_s"]), ph.end_s)
            end_file = None
        wait = ph.end_s - ph.now()
        if wait <= 0:
            return
        time.sleep(min(wait, 0.02) if end_file else wait)


def main(argv: list[str]) -> int:
    with open(argv[1]) as f:
        spec = json.load(f)
    with open(spec["requests_file"]) as f:
        requests = json.load(f)
    ph = Phase(spec, requests)
    threads = run_closed(ph)
    wait_for_end(ph)
    end_s = ph.end_s
    ph.stop.set()
    ph.close_all()
    deadline = time.monotonic() + float(spec.get("grace_s", 2.0))
    for th in threads:
        th.join(max(0.0, deadline - time.monotonic()))
    with ph.lock:
        records = [dict(r) for r in ph.records]
    out = {"ended_at": ph.now(), "end_s": end_s,
           "threads_left": sum(th.is_alive() for th in threads),
           "records": records}
    tmp = spec["out"] + ".tmp"
    with open(tmp, "w") as f:
        json.dump(out, f)
    os.replace(tmp, spec["out"])
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
