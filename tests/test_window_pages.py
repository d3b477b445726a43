"""The window kind's page pool on the paged server: how it is sized, that a
freed page is never read, the order of hand-back and launch under the
overlapped scheduler, preemption, the int8 cache, the records, and what
refuses a model with window layers (tests/test_window_layers.py says what
the model is and why each tolerance)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from window_model import (  # noqa: F401
    CHUNK, LOGITS_ATOL, LOGPROB_ATOL, PAGE, WINDOW, assert_pages_balance,
    make_model, make_server, ref_logits, serve_all, tokens_of,
    worst_logprob_diff)
from cloud_server_tpu.config import InferConfig, ModelConfig  # noqa: F401
from cloud_server_tpu.inference import paged_engine, paged_server  # noqa: F401
from cloud_server_tpu.inference.block_allocator import WindowPagePool  # noqa: F401
from cloud_server_tpu.inference.paged_server import PagedInferenceServer  # noqa: F401
from cloud_server_tpu.models import moe  # noqa: F401
from cloud_server_tpu.ops.paged_attention import (  # noqa: F401
    paged_attention, paged_attention_xla)
from cellbench import reference  # noqa: F401


@pytest.fixture(scope="module")
def model():
    return make_model()


def test_the_pool_is_sized_by_the_program_from_what_it_knows(model):
    """ceil((window - 1 + writes ahead) / page) + 1 pages a slot, two
    dispatches ahead (the plan of the next is built while one runs). No
    option of the server sets it."""
    import inspect
    srv = make_server(model)
    assert srv.window_pages_per_slot == -(-(WINDOW - 1 + 2 * CHUNK)
                                          // PAGE) + 1
    assert srv.window_pool.num_pages == 4 * srv.window_pages_per_slot
    # the published sizes: 4,096 keys, pages of 128, chunks of 256
    assert paged_engine.window_pages_per_slot(4096, 128, 256, 128) == 35
    assert paged_engine.window_pages_per_slot(4096, 128, 512, 128) == 37
    assert not [p for p in inspect.signature(
        PagedInferenceServer.__init__).parameters if "window" in p]
    # a model of one kind of layer has one pool and one table, as before
    _, mcfg, weights, _ = model
    plain = PagedInferenceServer(
        weights, dataclasses.replace(mcfg, window_layout=(), sliding_window=0),
        InferConfig(), max_slots=2, max_context=64, page_size=PAGE)
    assert plain.window_pool is None and plain.tables.shape == (2, 4)
    assert set(plain.state["pools"]) == {"k", "v"}
    assert plain.state["pools"]["k"].shape[0] == 8


def test_a_freed_page_is_never_read(model):
    """After every step the free pages of the window pool are filled with
    1e4 on the device (and the sentinel rows of the tables point at
    them): the served log-probabilities are the reference's all the
    same."""
    def poison(srv):
        free = jnp.asarray(sorted(srv.window_pool._free), jnp.int32)
        if free.size:
            pools = srv.state["pools"]
            srv.state["pools"] = {
                **pools, "wk": pools["wk"].at[:, free].set(1e4),
                "wv": pools["wv"].at[:, free].set(1e4)}

    srv = make_server(model, waits=True)
    prompts, handles = serve_all(srv, each_step=poison)
    assert worst_logprob_diff(model, prompts, handles) < LOGPROB_ATOL
    assert_pages_balance(srv)


def test_no_launched_program_can_read_a_returned_page(model):
    """The plan of step N + 1 is built
    before step N commits and launched after it. After every step, every
    page the launched program's rows read (from its first query's bound
    to its last write) is held by its slot, and nothing behind the
    committed bound is."""
    ps, mp = PAGE, 256 // PAGE
    seen = {"launched": 0, "returned_before_launch": 0}

    def check(srv):
        infl = srv._inflight
        pool = srv.window_pool
        if infl is None:
            return
        seen["launched"] += 1
        rows = [(job.slot, job.base_len + d0, take)
                for job, take, d0 in infl.sel]
        if infl.n_rounds:
            rows += [(int(sid), int(srv.lengths[sid]), infl.win)
                     for i, sid in enumerate(infl.live_ids)
                     if srv._slots[int(sid)] is infl.owners[i]
                     and srv.active[int(sid)]]
        for sid, start, width in rows:
            first = max(start - (WINDOW - 1), 0) // ps
            last = (start + width - 1) // ps
            held = srv.tables[sid, mp + first:mp + last + 1]
            assert (held < pool.num_pages).all(), (sid, start, width)
            assert all(pool._held[int(p)] for p in held)
            assert int(srv._win_lo[sid]) <= first
            seen["returned_before_launch"] += int(srv._win_lo[sid] > 0)

    srv = make_server(model)
    prompts, handles = serve_all(srv, each_step=check)
    assert seen["launched"] > 40 and seen["returned_before_launch"] > 20
    assert worst_logprob_diff(model, prompts, handles) < LOGPROB_ATOL


def test_preemption_and_resume(model):
    """A full pool too small for the three requests together: the
    youngest is preempted, gives back the pages of both kinds, and
    resumes by prefilling prompt and answer so far through both pools."""
    srv = make_server(model, num_pages=22, waits=True)
    prompts, handles = serve_all(srv)
    assert srv.preemptions >= 1
    assert worst_logprob_diff(model, prompts, handles) < LOGPROB_ATOL
    assert_pages_balance(srv)


def test_the_int8_cache_has_scale_pools_of_both_kinds(model):
    srv = make_server(model, kv_cache_dtype="int8")
    pools = srv.state["pools"]
    assert set(pools) == {"k", "v", "k_scale", "v_scale",
                          "wk", "wv", "wk_scale", "wv_scale"}
    assert pools["wk"].dtype == jnp.int8
    assert pools["wk_scale"].shape == pools["wk"].shape[:3] + (PAGE,)
    assert paged_engine.hbm_bytes(paged_server._make_cache(
        pools, None, None)) == sum(
            p.size * p.dtype.itemsize for p in pools.values())
    prompts, handles = serve_all(srv)
    diffs = np.concatenate([
        np.abs(reference.teacher_forced(model[3], p, h.tokens)[0]
               - np.asarray(h.logprobs)) for p, h in zip(prompts, handles)])
    # the median: a key rounded to 1/254 of its largest entry under
    # scores of standard deviation 4 also sends a token in a few dozen to
    # another expert, and that token reads off by a whole unit
    assert 1e-3 < np.median(diffs) < 0.5  # the float32 cache's: 1e-5
    assert_pages_balance(srv)


def test_records_and_stats_describe_both_pools(model):
    srv = make_server(model, flight_recorder_size=512)
    serve_all(srv)
    recs = srv.flight_window()
    assert sum(r.get("pages_returned", 0) for r in recs) \
        == srv.window_pool.pages_returned
    busy = [r for r in recs if r.get("keys_full")]
    assert busy and all(
        r["keys_window"] <= r["keys_full"]
        and r.get("keys_window_decode", 0) <= r["keys_window"] for r in busy)
    assert any(r["keys_window"] < r["keys_full"] for r in busy)
    assert all(r["window_num_pages"] == srv.window_pool.num_pages
               and r["pool_free"] + r["pool_cached"] + r["pool_active"]
               == 64 for r in recs)
    pool = srv.cache_stats()["pool"]
    assert pool["pages_total"] == 64
    assert pool["window_num_pages"] == srv.window_pool.num_pages
    assert pool["window_pool_active"] == 0
    snap = srv.metrics_snapshot()
    assert any("window_pages_active" in k for k in snap)


def test_what_moves_or_shares_one_kind_of_page_refuses(model):
    """The prefix cache keeps nothing of a model with window layers;
    migration, the drain's evacuation, a draft model and the router refuse
    it, each with an error that names the mechanism."""
    from cloud_server_tpu.inference.router import ReplicatedRouter
    _, mcfg, weights, _ = model
    srv = make_server(model)
    prompt = list(map(int, tokens_of(70, 5)))
    for _ in range(2):
        h = srv.submit(prompt, max_new_tokens=4)
        while h.finish_reason is None:
            srv.step()
    stats = srv.allocator.stats()
    assert (stats.prefix_hit_pages, stats.pages_cached) == (0, 0)
    with pytest.raises(ValueError, match="live migration"):
        srv.migrate_export(h)
    with pytest.raises(ValueError, match="drain"):
        srv.drain(migrate=lambda snap, req: True)
    with pytest.raises(ValueError, match="ReplicatedRouter"):
        ReplicatedRouter([srv])
    with pytest.raises(ValueError, match="draft-model speculation"):
        make_server(model, spec_drafts=2, draft_params=weights,
                    draft_cfg=mcfg)
    from cloud_server_tpu.inference import engine
    with pytest.raises(ValueError, match="contiguous cache"):
        engine.init_cache(mcfg, 1, 64)


def test_the_window_pool_raises_on_a_page_it_does_not_hold():
    pool = WindowPagePool(4)
    pages = pool.alloc(3)
    pool.free(pages[:2])
    with pytest.raises(RuntimeError, match="not held"):
        pool.free(pages[:1])
    with pytest.raises(RuntimeError, match="not held"):
        pool.free([7])
    with pytest.raises(RuntimeError, match="cannot happen"):
        pool.alloc(4)
    assert (pool.active, pool.pages_allocated, pool.pages_returned) \
        == (1, 3, 2)
