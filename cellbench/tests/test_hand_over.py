"""The ramp is a closed loop on the server's state (`serve.hand_over`):
however slow the scheduler is, no more slots than `retire_outstanding`
are ever without a decoding row, and a server that takes no request ends
the run with an error, not with a window that measured nothing."""
import threading
import time

import numpy as np
import pytest

from cellbench import serve


class Holder:
    def __init__(self):
        self.finish_reason = None
        self.cancelled = False

    def cancel(self):
        self.cancelled = True


class FakeServer:
    """Slots with a decoding flag, a queue of waiting requests, and a
    scheduler thread: each step releases cancelled holders, admits a
    waiting request into each free slot, and turns a slot to decoding
    `prefill_steps` steps after its admission. `stall` is (from, to)
    seconds after the start in which the scheduler does not step."""

    def __init__(self, slots, pending, step_s, prefill_steps=2,
                 stall=(0.0, 0.0), dead=False):
        self.active = np.ones((slots,), bool)
        self.holders = [Holder() for _ in range(slots)]
        self.owner = list(self.holders)
        self.prefill = {}
        self.pending = pending
        self.step_s, self.prefill_steps = step_s, prefill_steps
        self.stall, self.dead = stall, dead
        self.most_outstanding = 0
        self.stop = threading.Event()
        self.t_start = time.monotonic()
        self.thread = threading.Thread(target=self.loop, daemon=True)
        self.thread.start()

    @property
    def num_pending(self):
        return self.pending

    def loop(self):
        while not self.stop.is_set():
            time.sleep(self.step_s)
            since = time.monotonic() - self.t_start
            if self.dead or self.stall[0] <= since < self.stall[1]:
                continue
            for sid, own in enumerate(self.owner):
                if isinstance(own, Holder) and own.cancelled:
                    own.finish_reason = "cancelled"
                    self.owner[sid] = None
                    self.active[sid] = False
            for sid in list(self.prefill):
                self.prefill[sid] -= 1
                if self.prefill[sid] <= 0:
                    del self.prefill[sid]
                    self.active[sid] = True
            for sid, own in enumerate(self.owner):
                if own is None and self.pending > 0:
                    self.pending -= 1
                    self.owner[sid] = "request"
                    self.prefill[sid] = self.prefill_steps
            self.most_outstanding = max(
                self.most_outstanding,
                len(self.active) - int(np.count_nonzero(self.active)))


PLAN = {"retire_after_s": 0.05, "retire_by_s": 0.5,
        "retire_outstanding": 3, "ramp_deadline_s": 6.0}


@pytest.mark.parametrize("stall", [(0.0, 0.0), (0.2, 1.2)],
                         ids=["sound", "stalled-for-a-second"])
def test_never_more_slots_without_a_row_than_the_limit(stall):
    srv = FakeServer(slots=16, pending=20, step_s=0.01, stall=stall)
    t0 = time.monotonic()
    try:
        end = serve.hand_over(srv, srv.holders, PLAN, t0)
    finally:
        srv.stop.set()
        srv.thread.join()
    assert all(h.finish_reason == "cancelled" for h in srv.holders)
    assert srv.most_outstanding <= PLAN["retire_outstanding"]
    assert srv.owner.count("request") == 16
    # on the timetable when nothing holds it up, after the stall when
    # something does
    assert end - t0 >= max(0.45, stall[1])
    assert end - t0 < max(0.5, stall[1]) + 1.5


def test_a_server_that_takes_no_request_fails_the_run():
    srv = FakeServer(slots=8, pending=10, step_s=0.01, dead=True)
    plan = dict(PLAN, ramp_deadline_s=0.6)
    t0 = time.monotonic()
    try:
        with pytest.raises(SystemExit, match="nothing was measured"):
            serve.hand_over(srv, srv.holders, plan, t0)
    finally:
        srv.stop.set()
        srv.thread.join()
    assert time.monotonic() - t0 < 3.0
