"""The parallel body (a Mamba-2 mixer beside attention, Falcon-H1) on the
paged server, at the family's tiny widths on the CPU in float32: the
chunked scan against the token-by-token recurrence, the program (prefill
in chunks, then decode through each slot's state) against the family's
plain reference `forward_logits`, what must leave a state untouched, what
must start from a zeroed one, and what refuses such a model."""
import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from serial_order import waits

from cellbench import families, reference, serve
from cellbench.families import falcon_h1
from cloud_server_tpu.config import InferConfig, ModelConfig
from cloud_server_tpu.inference import engine, paged_engine
from cloud_server_tpu.inference.paged_server import PagedInferenceServer
from cloud_server_tpu.inference.router import ReplicatedRouter
from cloud_server_tpu.models import mixer, transformer
from cloud_server_tpu.ops.ssd import ssd_chunked, ssd_step

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PAGE, CHUNK = 16, 256
ATOL = 2e-4  # float32 serving against the float32 reference, logits
# multipliers a tiny variant states where the published file states 1
VARIANT = {"attention_in_multiplier": 0.5}


@pytest.fixture(scope="module")
def model():
    """(configuration as served, ModelConfig, weights, reference forward)
    at the family's tiny widths."""
    with open(os.path.join(ROOT, "cellbench", "configs",
                           "falcon-h1-34b-instruct.json")) as f:
        cfg_file = json.load(f)
    cfg, mcfg, weights = serve.make_model(
        {**cfg_file, **VARIANT}, falcon_h1.TINY, 2**31 + 49)
    yield cfg, mcfg, weights, families.forward_of(cfg, weights)
    jax.clear_caches()


def tokens_of(n, seed=0):
    return np.random.default_rng(seed).integers(1, 512, n)


# ---------------------------------------------------------------------------
# the scan in its two forms
# ---------------------------------------------------------------------------

def scan_operands(b, w, seed=0, h=4, p=16, g=2, n=32):
    rng = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)  # noqa: E731
    return (f(b, w, h, p), jax.nn.softplus(f(b, w, h)), -jnp.exp(f(h)),
            f(b, w, g, n), f(b, w, g, n), f(b, h, p, n))


def token_by_token(x, dt, a, bm, cm, state):
    ys = []
    for t in range(x.shape[1]):
        y, state = ssd_step(x[:, t], dt[:, t], a, bm[:, t], cm[:, t], state)
        ys.append(y)
    return jnp.stack(ys, axis=1), state


@pytest.mark.parametrize("w,widths", [
    (16, None), (128, None), (256, None), (256, (256, 17, 100, 1, 0))],
    ids=["16", "128", "256", "ragged-256"])
def test_chunked_scan_is_the_recurrence(w, widths):
    """Chunks of 16 entered with a carried state: the outputs and the
    state left are the one-token recurrence's, and a row's padding past
    its real width advances nothing."""
    b = len(widths) if widths else 2
    x, dt, a, bm, cm, s0 = scan_operands(b, w, seed=w)
    real = np.asarray(widths) if widths else np.full(b, w)
    dt = jnp.where(jnp.arange(w)[None, :, None] < real[:, None, None], dt, 0)
    y, s1 = ssd_chunked(x, dt, a, bm, cm, s0, 16)
    for i in range(b):
        n = int(real[i])
        if not n:  # width 0: the state bit for bit
            assert np.array_equal(np.asarray(s1[i]), np.asarray(s0[i]))
            continue
        want_y, want_s = token_by_token(
            x[i:i + 1, :n], dt[i:i + 1, :n], a, bm[i:i + 1, :n],
            cm[i:i + 1, :n], s0[i:i + 1])
        np.testing.assert_allclose(np.asarray(s1[i]), np.asarray(want_s[0]),
                                   rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(np.asarray(y[i, :n]),
                                   np.asarray(want_y[0]),
                                   rtol=2e-5, atol=2e-4)


def test_many_rows_go_through_in_blocks_of_eight(model):
    """A group of 16 rows (a warm-up's anchors are 64) is two passes of 8
    over the same pools: what one pass over all 16 computes."""
    _, mcfg, weights, _ = model
    lp = jax.tree.map(lambda p: p[0], weights["layers"])
    rng = np.random.default_rng(6)
    f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)  # noqa: E731
    s_shape, c_shape = mixer.state_shapes(mcfg, 20)
    args = (f(16, 32, mcfg.ssm_conv_dim), f(16, 32, mcfg.ssm_heads),
            f(*s_shape), f(*c_shape),
            jnp.asarray(rng.permutation(20)[:16], jnp.int32),
            jnp.asarray([0, 5] * 8, jnp.int32),
            jnp.asarray(rng.integers(0, 33, 16), jnp.int32), lp, mcfg)
    for got, want in zip(mixer.mix_rows(*args), mixer._mix_block(*args)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# the program against the reference, logits
# ---------------------------------------------------------------------------

def fresh_cache(mcfg, slots=2, pages_per_slot=48):
    cache = paged_engine.init_paged_cache(
        mcfg, num_pages=slots * pages_per_slot, page_size=PAGE, batch=slots,
        max_pages_per_slot=pages_per_slot)
    tables = np.arange(slots * pages_per_slot, dtype=np.int32).reshape(
        slots, pages_per_slot)
    return cache._replace(tables=jnp.asarray(tables))


_forward = jax.jit(paged_engine.window_forward,
                   static_argnames=("cfg",))


def program_logits(mcfg, weights, tokens, n_prompt, slot=0, cache=None):
    """Slot `slot` of a two-slot cache: the prompt in chunks of 256 (the
    last one ragged inside its padded width), then one token a step; the
    other slot rides along as a padding row. Returns (the logits that
    predict tokens[n_prompt:] and the one after them, the cache)."""
    cache = cache or fresh_cache(mcfg)
    slots = jnp.asarray([slot, 2], jnp.int32)  # row 1: no slot
    at = jnp.asarray([slot, 1 - slot])
    tables = cache.tables
    rows, done = [], 0

    def call(toks, width, done, cache):
        padded = np.zeros((2, toks.shape[0]), np.int32)
        padded[0] = toks
        lengths = jnp.asarray([done, 0], jnp.int32)
        view = cache._replace(lengths=lengths, tables=tables[at])
        logits, out = _forward(
            weights, jnp.asarray(padded), cfg=mcfg, cache=view,
            logits_at=jnp.asarray([width - 1, 0], jnp.int32),
            widths=jnp.asarray([width, 0], jnp.int32), slots=slots)
        return logits[0], out._replace(tables=tables)

    while done < n_prompt:
        take = min(CHUNK, n_prompt - done)
        chunk = np.zeros((CHUNK,), np.int32)
        chunk[:take] = tokens[done:done + take]
        last, cache = call(chunk, take, done, cache)
        done += take
    rows.append(last)
    for t in tokens[n_prompt:]:
        last, cache = call(np.asarray([t], np.int32), 1, done, cache)
        rows.append(last)
        done += 1
    return np.asarray(jnp.stack(rows)), cache


def test_chunked_prefill_then_decode_is_the_reference(model):
    """A whole request: 600 prompt tokens in chunks of 256, 256 and a
    ragged 88 hand the state on, then 8 tokens advance it one at a time;
    every logit is the one-pass reference's."""
    _, mcfg, weights, fwd = model
    toks = tokens_of(608, 1)
    got, _ = program_logits(mcfg, weights, toks, 600)
    want, gap = fwd(toks)
    assert np.isinf(np.asarray(gap)).all()
    np.testing.assert_allclose(got, np.asarray(want[599:608]), atol=ATOL)


def test_a_slot_holds_no_trace_of_the_request_before(model):
    """Two requests in turn through one slot: a row at position 0 enters
    with a zeroed state, so the second reads what it reads in a fresh
    cache, bit for bit."""
    _, mcfg, weights, _ = model
    first, second = tokens_of(300, 2), tokens_of(280, 3)
    _, cache = program_logits(mcfg, weights, first, 296)
    after, _ = program_logits(mcfg, weights, second, 276, cache=cache)
    alone, _ = program_logits(mcfg, weights, second, 276)
    assert np.array_equal(after, alone)


@pytest.mark.parametrize("rows", [3, 2], ids=["most-slots", "few-rows"])
def test_dead_and_padding_rows_leave_their_states(model, rows):
    """A decode round over 4 slots' states: the live row's state moves,
    a dead row's (a slot id past the slots) and every slot no row stands
    on stay bit for bit, however many rows the round has (the one-token
    update runs over the whole pools for any decode set)."""
    _, mcfg, weights, _ = model
    cache = fresh_cache(mcfg, slots=4, pages_per_slot=8)
    rng = np.random.default_rng(4)
    cache = cache._replace(
        ssm=tuple(jnp.asarray(rng.normal(size=s.shape), s.dtype)
                  for s in cache.ssm),
        conv=tuple(jnp.asarray(rng.normal(size=c.shape), c.dtype)
                   for c in cache.conv))
    slots = jnp.asarray([2, 4, 4][:rows], jnp.int32)  # row 0 live on slot 2
    at = jnp.asarray([2, 1, 3][:rows])
    view = cache._replace(lengths=jnp.asarray([5, 7, 9][:rows], jnp.int32),
                          tables=cache.tables[at])
    _, out = paged_engine.window_forward(
        weights, jnp.ones((rows, 1), jnp.int32), mcfg, view,
        logits_at=jnp.zeros((rows,), jnp.int32), slots=slots)
    for before, after in zip(cache.ssm + cache.conv, out.ssm + out.conv):
        before, after = np.asarray(before), np.asarray(after)
        assert not np.array_equal(before[2], after[2])
        for slot in (0, 1, 3):
            assert np.array_equal(before[slot], after[slot])


def test_the_one_token_update_is_the_chunked_scan_at_width_one(model):
    """`step_slots` (the decode rows' path) against `mix_rows` on the same
    one-token rows, one of them at position 0: it enters zeroed on both."""
    _, mcfg, weights, _ = model
    lp = jax.tree.map(lambda p: p[0], weights["layers"])
    rng = np.random.default_rng(8)
    f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)  # noqa: E731
    s_shape, c_shape = mixer.state_shapes(mcfg, 6)
    xbc, dt_raw = f(4, 1, mcfg.ssm_conv_dim), f(4, 1, mcfg.ssm_heads)
    ssm, conv = f(*s_shape), f(*c_shape)
    slots = jnp.asarray([4, 0, 6, 2], jnp.int32)  # row 2 leaves no trace
    lengths = jnp.asarray([7, 0, 3, 40], jnp.int32)
    got = mixer.step_slots(xbc, dt_raw, ssm, conv, slots, lengths, lp, mcfg)
    want = mixer.mix_rows(xbc, dt_raw, ssm, conv, slots, lengths,
                          jnp.ones((4,), jnp.int32), lp, mcfg)
    live = np.asarray([0, 1, 3])  # a dead row's y is the caller's to mask
    for g, w in zip((got[0][live],) + got[1:], (want[0][live],) + want[1:]):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=2e-5, atol=2e-5)
    for slot in (1, 3, 5):  # no row stands there
        assert np.array_equal(np.asarray(got[1][slot]), np.asarray(ssm[slot]))


# every scalar the configuration multiplies by, as (field, index)
MULTIPLIERS = [(f, None) for f in (
    "embedding_multiplier", "lm_head_multiplier", "attention_in_multiplier",
    "attention_out_multiplier", "key_multiplier", "ssm_in_multiplier",
    "ssm_out_multiplier")] + [("ssm_multipliers", i) for i in range(5)] + [
    ("mlp_multipliers", i) for i in range(2)]


@pytest.mark.parametrize("field,index", MULTIPLIERS, ids=[
    f if i is None else f"{f}-{i}" for f, i in MULTIPLIERS])
def test_a_multiplier_left_out_of_the_program_fails(model, field, index):
    _, mcfg, weights, fwd = model
    toks = tokens_of(41, 5)
    want = np.asarray(fwd(toks)[0][39:41])
    if index is None:
        dropped = dataclasses.replace(mcfg, **{field: 1.0})
    else:
        vals = list(getattr(mcfg, field))
        vals[index] = 1.0
        dropped = dataclasses.replace(mcfg, **{field: tuple(vals)})
    assert dropped != mcfg
    got, _ = program_logits(dropped, weights, toks, 40)
    assert np.abs(got - want).max() > 50 * ATOL


# ---------------------------------------------------------------------------
# the served path
# ---------------------------------------------------------------------------

def make_server(model, **kw):
    _, mcfg, weights, _ = model
    opts = dict(max_slots=4, max_context=512, page_size=PAGE, num_pages=128,
                prefill_chunk=32, decode_chunk=1)
    opts.update(kw)
    waiting = opts.pop("waits", False)
    return waits(PagedInferenceServer(
        weights, mcfg, InferConfig(max_decode_len=64, temperature=0.0,
                                   eos_token_id=-1), **opts), waiting)


def serve_all(srv, prompts, max_new=12):
    handles = [srv.submit(p, max_new_tokens=max_new) for p in prompts]
    while any(h.finish_reason is None for h in handles):
        srv.step()
    return handles


def worst_logprob_diff(model, prompts, handles):
    worst = 0.0
    for p, h in zip(prompts, handles):
        assert h.finish_reason == "length", h.finish_reason
        lp, margin, _ = reference.teacher_forced(model[3], p, h.tokens)
        worst = max(worst, float(np.abs(lp - np.asarray(h.logprobs)).max()))
        assert margin.max() < ATOL
    return worst


PROMPTS = [list(map(int, tokens_of(n, 10 + n))) for n in (70, 33, 100, 45, 64,
                                                         90)]


@pytest.mark.parametrize("waiting", [False, True],
                         ids=["launch-ahead", "commit-first"])
def test_served_requests_are_the_reference(model, waiting):
    """Six requests through four slots, ragged prefill groups beside
    decode rows in one program: every served log-probability is the
    reference's, the counters count, and nothing is keyed or shared.
    A step launched ahead of the last one's commit reads the states that
    one wrote, on the device: it serves what steps run one by one serve."""
    srv = make_server(model, waits=waiting)
    handles = serve_all(srv, PROMPTS)
    assert worst_logprob_diff(model, PROMPTS, handles) < ATOL
    recs = srv.flight_window(4096)
    assert sum(r.get("ssm_resets", 0) for r in recs) == len(PROMPTS)
    assert sum(r.get("ssm_chunk_tokens", 0) for r in recs) == sum(
        map(len, PROMPTS))
    # a request's first token is its last chunk's; the other 11 are
    # decode rows'
    assert sum(r.get("ssm_decode_rows", 0) for r in recs) >= 11 * len(PROMPTS)
    assert any(r.get("launch_ahead") for r in recs) != waiting
    pool = srv.cache_stats()["pool"]
    assert pool["ssm_state_bytes"] == paged_engine.state_bytes(
        paged_engine.PagedKVCache(None, None, None, None,
                                  **{k: srv.state["pools"][k]
                                     for k in ("ssm", "conv")}))
    assert srv.allocator.stats().pages_cached == 0
    again = serve_all(srv, PROMPTS[:1])  # the same prompt: no prefix hit
    assert srv.allocator.stats().prefix_hit_pages == 0
    assert again[0].tokens == handles[0].tokens


def test_both_orders_of_a_step_serve_the_same_bits(model):
    ahead = serve_all(make_server(model), PROMPTS)
    serial = serve_all(make_server(model, waits=True), PROMPTS)
    for a, s in zip(ahead, serial):
        assert a.tokens == s.tokens and a.logprobs == s.logprobs


def test_a_preempted_request_is_prefilled_again_from_a_zeroed_state(model):
    """A pool too small for four growing rows: the youngest gives its
    pages back and re-queues; admitted again it is prefilled from position
    0, its generated tokens behind its prompt, and goes on as the
    reference does."""
    srv = make_server(model, num_pages=20)
    prompts = [list(map(int, tokens_of(n, 30 + n))) for n in (60, 50, 70, 40)]
    handles = serve_all(srv, prompts, max_new=40)
    assert srv.preemptions > 0
    assert worst_logprob_diff(model, prompts, handles) < ATOL


# ---------------------------------------------------------------------------
# what has no answer for a state refuses the model, by its mechanism
# ---------------------------------------------------------------------------

REFUSALS = {
    "router": (lambda m: ReplicatedRouter([make_server(m)]), "recurrent state"),
    "handoff": (lambda m: make_server(m).submit(
        [1, 2, 3], max_new_tokens=2, handoff=lambda *a: None),
        "no export and no import"),
    "migrate_export": (lambda m: (lambda s: s.migrate_export(
        s.submit([1, 2, 3], max_new_tokens=2)))(make_server(m)),
        "live migration .*recurrent state"),
    "migrate_import": (lambda m: make_server(m).migrate_import(None),
                       "live migration .*recurrent state"),
    "drain": (lambda m: make_server(m).drain(migrate=lambda *a: None),
              "drain.*recurrent state"),
    "speculation": (lambda m: make_server(m, spec_drafts=2), "no roll-back"),
    "draft-model": (lambda m: make_server(
        m, spec_drafts=2, draft_params=m[2], draft_cfg=m[1]), "no roll-back"),
    "mesh": (lambda m: make_server(m, mesh=jax.sharding.Mesh(
        np.asarray(jax.devices()[:2]), ("tp",))), "shards the states"),
    "int8-state": (lambda m: dataclasses.replace(
        m[1], kv_cache_dtype="int8"), "no int8 cache or state"),
    "window-layers": (lambda m: dataclasses.replace(
        m[1], sliding_window=8, window_layout=(1, 0)), "no window layers"),
    "sizes-without-body": (lambda m: ModelConfig(ssm_heads=4),
                           "come together"),
    "body-without-sizes": (lambda m: ModelConfig(
        layer_body="parallel_mixer"), "come together"),
    "verify-window": (lambda m: paged_engine.window_forward(
        m[2], jnp.ones((1, 2), jnp.int32), m[1], fresh_cache(m[1]),
        logits_at=None, all_logits=True, slots=jnp.zeros((1,), jnp.int32)),
        "no roll-back"),
}


@pytest.mark.parametrize("what", sorted(REFUSALS))
def test_refusal_names_its_mechanism(model, what):
    call, says = REFUSALS[what]
    with pytest.raises(ValueError, match=says):
        call(model)


@pytest.mark.parametrize("what", ["training", "contiguous-cache", "embed"])
def test_single_layer_scans_refuse_the_body(model, what):
    _, mcfg, weights, _ = model
    toks = jnp.ones((1, 8), jnp.int32)
    with pytest.raises(NotImplementedError, match="paged server alone"):
        if what == "training":
            transformer.forward_hidden(weights, toks, mcfg)
        elif what == "embed":
            make_server(model).embed([[1, 2, 3]])
        else:
            engine.prefill(weights, toks, mcfg,
                           engine.init_cache(mcfg, 1, 16))


def test_refusals_name_no_model():
    """The messages say what a slot holds, not whose model it is."""
    import inspect

    from cloud_server_tpu.inference import paged_server, router
    for fn in (paged_server.PagedInferenceServer._refuse_slot_state,
               router.ReplicatedRouter.__init__):
        src = inspect.getsource(fn)
        for name in ("Falcon", "LongCat", "SmallThinker", "Mixtral"):
            assert name not in src, (fn.__qualname__, name)


def test_state_shapes_and_leaves_are_the_configurations(model):
    _, mcfg, weights, _ = model
    shapes = mixer.param_shapes(mcfg)
    assert jax.tree.map(lambda x: tuple(x.shape), weights) == shapes
    cache = fresh_cache(mcfg, slots=3)
    assert len(cache.ssm) == len(cache.conv) == mcfg.num_layers
    assert cache.ssm[0].shape == (3, 4, 16, 32)
    assert cache.ssm[0].dtype == jnp.float32
    assert cache.conv[0].shape == (3, 3, 64 + 2 * 2 * 32)
