"""dp-replicated serving: the replica router (scale-out axis)."""

import json

import jax
import pytest

from cloud_server_tpu.config import InferConfig, ModelConfig
from cloud_server_tpu.inference.paged_server import PagedInferenceServer
from cloud_server_tpu.inference.router import ReplicatedRouter
from cloud_server_tpu.models import transformer

CFG = ModelConfig(
    vocab_size=64, embed_dim=32, num_layers=2, num_heads=4, num_kv_heads=2,
    head_dim=8, mlp_dim=64, max_seq_len=256, dtype="float32",
    param_dtype="float32", remat="none")
GREEDY = InferConfig(max_decode_len=8, temperature=0.0, eos_token_id=-1,
                     pad_token_id=0)
SRV_KW = dict(max_slots=2, max_context=64, page_size=8, prefill_chunk=16,
              prompt_buckets=[16])
PROMPT = [5, 9, 3]


@pytest.fixture(scope="module")
def params():
    return transformer.init_params(CFG, jax.random.key(0))


@pytest.fixture(scope="module")
def router(params):
    return ReplicatedRouter.over_devices(
        params, CFG, GREEDY, devices=jax.devices()[:2], **SRV_KW)


def test_replica_parity_and_balance(router):
    """Identical greedy requests produce identical outputs regardless
    of which replica serves them, and the router uses every replica."""
    reqs = [router.submit(PROMPT, max_new_tokens=6) for _ in range(4)]
    router.run_until_idle()
    outs = [r.tokens for r in reqs]
    assert all(o == outs[0] for o in outs)
    assert all(len(o) == 6 for o in outs)
    assert all(r.tokens_emitted > 0 for r in router.replicas)


def test_single_replica_reference(router, params):
    """The fleet's output equals a lone server's output."""
    lone = PagedInferenceServer(params, CFG, GREEDY, **SRV_KW)
    want = lone.generate([PROMPT], max_new_tokens=8)[0]
    got = router.generate([PROMPT], max_new_tokens=8)[0]
    assert got == want


def test_least_loaded_placement(params):
    r = ReplicatedRouter.over_devices(
        params, CFG, GREEDY, devices=jax.devices()[:2], **SRV_KW)
    # each replica's cache is born on its own device, not on device 0
    for rep, d in zip(r.replicas, jax.devices()[:2]):
        assert all(x.devices() == {d} for x in jax.tree.leaves(rep.state))
    # replica 0 is busy: 3 queued requests
    for _ in range(3):
        r.replicas[0].submit(PROMPT, max_new_tokens=4)
    req = r.submit(PROMPT, max_new_tokens=4)
    assert req in list(r.replicas[1]._pending)  # went to the idle one
    r.run_until_idle()


def test_router_over_http(router):
    from urllib import request as urq
    from cloud_server_tpu.inference.http_server import HttpFrontend
    router.start()
    front = HttpFrontend(router).start()
    try:
        host, port = front.address
        body = json.dumps({"prompt": PROMPT, "max_tokens": 4}).encode()
        with urq.urlopen(urq.Request(
                f"http://{host}:{port}/v1/completions", data=body),
                timeout=300) as resp:
            out = json.loads(resp.read())
        assert len(out["choices"][0]["tokens"]) == 4
        with urq.urlopen(f"http://{host}:{port}/healthz",
                         timeout=30) as resp:
            health = json.loads(resp.read())
        assert health["ok"]
    finally:
        front.stop()
        router.stop()


def test_router_embeddings_and_adapters(params):
    import numpy as np
    from cloud_server_tpu.models.lora import LoRAConfig, init_lora_params
    r = ReplicatedRouter.over_devices(
        params, CFG, GREEDY, devices=jax.devices()[:2], **SRV_KW)
    vecs = r.embed([[5, 9, 3], [60]])
    assert vecs.shape == (2, CFG.embed_dim)
    np.testing.assert_allclose(np.linalg.norm(vecs, axis=-1), 1.0,
                               rtol=1e-5)
    lcfg = LoRAConfig(rank=2, alpha=4.0, targets=("wq",))
    lp = init_lora_params(CFG, lcfg, jax.random.key(1))
    aid = r.add_adapter("ad", lp, lcfg)
    assert aid == 1 and r.adapters.adapter_id("ad") == 1
    # adapter-routed requests work wherever they land
    reqs = [r.submit(PROMPT, max_new_tokens=4, adapter="ad")
            for _ in range(4)]
    r.run_until_idle()
    assert all(len(q.tokens) == 4 for q in reqs)


def test_router_skips_draining_replica(params):
    """A draining replica advertises ready=False and stops receiving
    NEW work from the router (its in-flight requests finish); resume()
    puts it back in rotation, and a fully-draining fleet surfaces the
    replica's own refusal instead of hanging or index-erroring."""
    r = ReplicatedRouter.over_devices(
        params, CFG, GREEDY, devices=jax.devices()[:2], **SRV_KW)
    inflight = r.replicas[0].submit(PROMPT, max_new_tokens=6)
    assert r.replicas[0].drain(timeout=0.0) is False  # still busy
    # quiesce-style drain latch without waiting for idle: use the
    # stop(drain)-internal latch semantics via drain on the now-idle
    # replica after finishing its work
    r.run_until_idle()
    assert inflight.done
    assert r.replicas[0].drain() is True  # idle: latched draining
    assert r.replicas[0].ready is False
    assert r.ready is True  # fleet still ready: replica 1 serves
    reqs = [r.submit(PROMPT, max_new_tokens=4) for _ in range(4)]
    r.run_until_idle()
    assert all(len(q.tokens) == 4 for q in reqs)
    # every request landed on the non-draining replica
    snap0 = r.replicas[0].metrics_snapshot()
    snap1 = r.replicas[1].metrics_snapshot()
    assert snap0["cloud_server_requests_submitted_total"]["value"] == 1
    assert snap1["cloud_server_requests_submitted_total"]["value"] == 4
    # back in rotation after resume
    r.replicas[0].resume()
    assert r.replicas[0].ready is True
    # whole fleet draining: submit surfaces the replicas' refusal
    for rep in r.replicas:
        assert rep.drain() is True
    assert r.ready is False
    with pytest.raises(RuntimeError, match="draining"):
        r.submit(PROMPT, max_new_tokens=2)
    for rep in r.replicas:
        rep.resume()


def test_drainless_stop_counted_and_logged(caplog):
    """ReplicatedRouter.stop()'s TypeError fallback (a replica whose
    stop() takes no drain/timeout) must be visible: counted in
    cloud_server_router_drainless_stops_total and logged — before this
    it silently retried without drain."""
    import logging

    class _NoDrainStub:
        def __init__(self):
            self.stopped = False
            self.num_active = 0
            self.num_pending = 0

        def submit(self, prompt, **kw):
            return prompt

        def stop(self):  # no drain/timeout kwargs
            self.stopped = True

    stub = _NoDrainStub()
    r = ReplicatedRouter([stub])
    with caplog.at_level(logging.WARNING,
                         logger="cloud_server_tpu.inference.router"):
        r.stop(drain=True, timeout=0.1)
    assert stub.stopped
    assert any("without drain" in rec.message for rec in caplog.records)
    snap = r.metrics_snapshot()
    assert snap["cloud_server_router_drainless_stops_total"][
        "value"] == 1


def test_breaker_open_half_open_close_cycle():
    """Per-replica circuit breaker: consecutive submit failures OPEN
    the breaker (placement stops routing there), the reset window
    half-opens it for one probe submit, a failed probe re-opens, and
    a successful probe closes it."""
    import time as _time

    class _FlakyStub:
        def __init__(self, preload=0):
            self.fail = False
            self.got = []
            self.num_active = 0
            self._preload = preload

        @property
        def num_pending(self):
            return self._preload  # static: placement stays stable

        def submit(self, prompt, **kw):
            if self.fail:
                raise RuntimeError("replica exploded")
            self.got.append(prompt)
            return prompt

    flaky, good = _FlakyStub(), _FlakyStub(preload=1)
    r = ReplicatedRouter([flaky, good], breaker_threshold=2,
                         breaker_reset_s=0.1)
    flaky.fail = True
    # two failing submits: each picks flaky (least loaded), trips a
    # failure, and FAILS OVER to good — the client never sees them
    for k in range(2):
        assert r.submit([k]) == [k]
    assert [g for g in good.got] == [[0], [1]]
    states = r.breaker_states()
    assert states[0]["state"] == "open"
    assert states[0]["consecutive_failures"] == 2
    snap = r.metrics_snapshot()
    assert snap["cloud_server_router_submit_failovers_total"][
        "value"] == 2
    assert snap["cloud_server_router_breaker_open_total"]["value"] == 1
    # while open: placement avoids flaky entirely (no new failures)
    r.submit([2])
    assert good.got[-1] == [2]
    assert r.breaker_states()[0]["consecutive_failures"] == 2
    # reset elapses -> half_open -> the probe submit fails -> re-open
    _time.sleep(0.12)
    assert r.breaker_states()[0]["state"] == "half_open"
    r.submit([3])  # probe fails over to good, breaker re-opens
    assert good.got[-1] == [3]
    assert r.breaker_states()[0]["state"] == "open"
    # reset again, replica recovered -> probe succeeds -> closed
    _time.sleep(0.12)
    flaky.fail = False
    r.submit([4])
    assert flaky.got == [[4]]
    assert r.breaker_states()[0]["state"] == "closed"
    assert r.breaker_states()[0]["consecutive_failures"] == 0


def test_half_open_probe_released_on_client_refusal():
    """A probe submit that resolves with a CLIENT-class refusal
    (QueueFullError) is neither a breaker success nor a failure — but
    it must release the half-open probe slot, or the breaker wedges
    with `probing` latched and the replica never rejoins."""
    import time as _time

    from cloud_server_tpu.inference.request import QueueFullError

    class _Stub:
        def __init__(self, preload=0):
            self.mode = "ok"
            self.got = []
            self.num_active = 0
            self._preload = preload

        @property
        def num_pending(self):
            return self._preload

        def submit(self, prompt, **kw):
            if self.mode == "boom":
                raise RuntimeError("boom")
            if self.mode == "full":
                raise QueueFullError("queue full")
            self.got.append(prompt)
            return prompt

    flaky, good = _Stub(), _Stub(preload=1)
    r = ReplicatedRouter([flaky, good], breaker_threshold=1,
                         breaker_reset_s=0.05)
    flaky.mode = "boom"
    r.submit([0])  # fails over; breaker opens at threshold 1
    assert r.breaker_states()[0]["state"] == "open"
    _time.sleep(0.06)
    flaky.mode = "full"  # the probe gets a 429, not a failure
    with pytest.raises(QueueFullError):
        r.submit([1])
    st = r.breaker_states()[0]
    assert st["state"] == "half_open"
    # the probe slot was released: the next submit probes again and
    # the recovered replica closes its breaker
    flaky.mode = "ok"
    r.submit([2])
    assert flaky.got == [[2]]
    assert r.breaker_states()[0]["state"] == "closed"


def test_drain_resume_racing_concurrent_submits():
    """drain()/resume() toggling on one replica while submitter
    threads hammer the router: the ready-flag race (picked while
    ready, draining by the time submit lands) is absorbed by submit
    failover, so no client ever sees a refusal and every request
    lands on exactly one replica."""
    import threading
    import time as _time

    class _DrainStub:
        def __init__(self):
            self._draining = False
            self.got = []
            self._lock = threading.Lock()
            self.num_active = 0

        @property
        def ready(self):
            return not self._draining

        @property
        def num_pending(self):
            return 0  # static load: the toggle is the only variable

        def submit(self, prompt, **kw):
            with self._lock:
                if self._draining:
                    raise RuntimeError(
                        "server is draining; not accepting requests")
                self.got.append(prompt)
            return prompt

        def drain(self):
            with self._lock:
                self._draining = True
            return True

        def resume(self):
            with self._lock:
                self._draining = False

    r0, r1 = _DrainStub(), _DrainStub()
    router = ReplicatedRouter([r0, r1])
    errors = []
    done = threading.Event()

    def toggler():
        while not done.is_set():
            r0.drain()
            _time.sleep(0.0005)
            r0.resume()
            _time.sleep(0.0005)

    def submitter(base):
        try:
            for k in range(50):
                router.submit([base + k])
        except Exception as exc:  # noqa: BLE001 — the assertion
            errors.append(exc)

    tog = threading.Thread(target=toggler, daemon=True)
    tog.start()
    subs = [threading.Thread(target=submitter, args=(1000 * i,))
            for i in range(4)]
    for t in subs:
        t.start()
    for t in subs:
        t.join(30)
    done.set()
    tog.join(5)
    assert not errors, f"submits failed through the race: {errors!r}"
    landed = r0.got + r1.got
    assert len(landed) == 200
    assert len({tuple(p) for p in landed}) == 200  # exactly-once
    # the drain window really diverted traffic (r1 saw the overflow)
    assert r1.got


def test_burst_submit_sees_inflight_picks():
    """A submit still blocked inside its replica (the router
    lock is not held across replica.submit) must be visible to
    concurrent _pick()s via the in-router in-flight counter — otherwise
    a burst piles onto the replica whose queue insert is slowest.

    Stubs make the race deterministic: replica A's submit blocks on a
    gate while replica B starts one request more loaded. The second
    submit must see A's in-flight pick (load 0+1) tie with B and rotate
    to B — without the counter it reads A as empty and piles on."""
    import threading
    import time as _time

    class _Stub:
        def __init__(self, preload=0):
            self.got = []
            self.gate = threading.Event()
            self.gate.set()
            self.num_active = 0
            self._preload = preload

        @property
        def num_pending(self):
            return len(self.got) + self._preload

        def submit(self, prompt, **kw):
            assert self.gate.wait(10)
            self.got.append(prompt)
            return prompt

    a, b = _Stub(), _Stub(preload=1)
    a.gate.clear()  # A's first submit hangs inside the replica
    router = ReplicatedRouter([a, b])
    t = threading.Thread(target=lambda: router.submit([1]))
    t.start()
    deadline = _time.time() + 10
    while not any(router._inflight) and _time.time() < deadline:
        _time.sleep(0.001)
    assert router._inflight == [1, 0]  # picked A (least loaded), mid-flight
    router.submit([2])  # must NOT pile onto A
    assert b.got == [[2]]
    a.gate.set()
    t.join(10)
    assert a.got == [[1]]
    assert router._inflight == [0, 0]  # settled after both complete


# ---------------------------------------------------------------------------
# runtime fleet mutation (the autoscaler's actuation surface)
# ---------------------------------------------------------------------------


def test_add_remove_replica_live(params):
    """add_replica() grows a serving fleet in place; remove_replica
    (migrate=True) evacuates the victim's in-flight work and returns
    the quiesced replica. Indices are TOMBSTONED, never shifted, and
    a later add_replica reuses the detached slot."""
    mk = lambda: PagedInferenceServer(params, CFG, GREEDY,  # noqa: E731
                                      **SRV_KW)
    router = ReplicatedRouter([mk()])
    assert router.attached_indices() == [0]
    i = router.add_replica(mk())
    assert i == 1 and router.attached_indices() == [0, 1]
    reqs = [router.submit(PROMPT, max_new_tokens=6) for _ in range(6)]
    router.step()
    assert all(r.num_active + r.num_pending > 0 for r in router.replicas)
    import threading
    import time as _time
    stepper = threading.Thread(
        target=lambda: [router.step() or _time.sleep(0.002)
                        for _ in range(3000)], daemon=True)
    stepper.start()
    gone = router.remove_replica(0, migrate=True, timeout=60.0)
    assert gone is not None and gone.num_active == 0
    assert router.attached_indices() == [1]
    assert 0 not in router.breaker_states()
    deadline = _time.monotonic() + 60.0
    while (not all(r.done for r in reqs)
           and _time.monotonic() < deadline):
        _time.sleep(0.01)
    assert all(len(r.tokens) == 6 for r in reqs), (
        [(len(r.tokens), r.finish_reason) for r in reqs])
    # a racing submit that captured the dead index is refused by the
    # tombstone, not misrouted
    with pytest.raises(RuntimeError):
        router.replicas[0].submit(PROMPT)
    # new traffic still flows, and re-adding reuses the detached slot
    after = router.submit(PROMPT, max_new_tokens=4)
    assert router.add_replica(mk()) == 0
    assert router.attached_indices() == [0, 1]
    router.run_until_idle()
    assert len(after.tokens) == 4
    gone.stop()
    router.stop()


def test_remove_replica_validation(params):
    srv = PagedInferenceServer(params, CFG, GREEDY, **SRV_KW)
    router = ReplicatedRouter([srv])
    with pytest.raises(ValueError):
        router.remove_replica(0)      # never strand the fleet at zero
    with pytest.raises(ValueError):
        router.remove_replica(7)
    with pytest.raises(ValueError):
        router.add_replica(object(), role="chaos")
    router.stop()


def test_remove_replica_racing_concurrent_submits():
    """Submitter threads hammer the router while a replica is removed
    mid-run: racing submits that captured the victim's index hit the
    detached tombstone and FAIL OVER — the client sees zero refusals
    and every request lands exactly once."""
    import threading
    import time as _time

    class _RemovableStub:
        def __init__(self):
            self._draining = False
            self.got = []
            self._lock = threading.Lock()
            self.num_active = 0

        @property
        def ready(self):
            return not self._draining

        @property
        def num_pending(self):
            return 0

        def submit(self, prompt, **kw):
            with self._lock:
                if self._draining:
                    raise RuntimeError("server is draining")
                self.got.append(prompt)
            return prompt

        def drain(self, *a, **kw):
            with self._lock:
                self._draining = True
            return True

        def resume(self):
            with self._lock:
                self._draining = False

        def stop(self):
            pass

    victim, survivor = _RemovableStub(), _RemovableStub()
    router = ReplicatedRouter([victim, survivor])
    errors = []
    removed = threading.Event()

    def submitter(base):
        try:
            for k in range(80):
                router.submit([base + k])
                if k == 20 and base == 0:
                    removed.set()
        except Exception as exc:  # noqa: BLE001 — the assertion
            errors.append(exc)

    def remover():
        assert removed.wait(30)
        got = router.remove_replica(0, migrate=True, timeout=10.0)
        assert got is victim

    subs = [threading.Thread(target=submitter, args=(1000 * i,))
            for i in range(4)]
    rem = threading.Thread(target=remover)
    for t in subs + [rem]:
        t.start()
    for t in subs + [rem]:
        t.join(30)
    assert not errors, f"submits refused through removal: {errors!r}"
    landed = victim.got + survivor.got
    assert len(landed) == 320
    assert len({tuple(p) for p in landed}) == 320  # exactly-once
    assert router.attached_indices() == [1]
    # the tail of the run was served by the survivor alone
    assert survivor.got
