"""Latent attention and the double layer with shortcut experts
(LongCat-Flash) on the paged server, at the family's tiny widths on the
CPU: the kernel over latent pages against the XLA path, absorbed against
expanded attention, the two dispatches of one chip's share against each
other and against the family's plain reference, the shares adding up to
the uncut layer, and the served path (chunked prefill, then decode through
the latent pages) against the family's `forward_logits`."""
import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from serial_order import waits

from cellbench import families, reference, serve
from cellbench.families import longcat_flash
from cloud_server_tpu.config import InferConfig, ModelConfig
from cloud_server_tpu.inference import paged_engine
from cloud_server_tpu.inference.paged_server import PagedInferenceServer
from cloud_server_tpu.inference.router import ReplicatedRouter
from cloud_server_tpu.models import latent, moe
from cloud_server_tpu.ops.paged_attention import (
    paged_attention, paged_attention_xla)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PAGE, CHUNK = 16, 32
LOGPROB_ATOL = 1e-2


@pytest.fixture(scope="module")
def model():
    """(configuration as served, ModelConfig, weights, reference forward)
    at the family's tiny widths: 4 experts held of 8 routed, 4 identity
    experts, 3 a token."""
    with open(os.path.join(ROOT, "cellbench", "configs",
                           "longcat-flash-chat.json")) as f:
        cfg_file = json.load(f)
    cfg, mcfg, weights = serve.make_model(
        cfg_file, longcat_flash.TINY, 2**31 + 45)
    yield cfg, mcfg, weights, families.forward_of(cfg, weights)
    jax.clear_caches()


def tokens_of(n, seed=0):
    return np.random.default_rng(seed).integers(1, 512, n)


# ---------------------------------------------------------------------------
# the kernel over latent pages
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("tile,ragged,w", [
    (8, False, 8), (16, True, 8), (512, True, 8), (512, False, 1)],
    ids=["4-tiles", "2-tiles-ragged", "1-tile-ragged", "decode"])
def test_latent_kernel_is_the_xla_path(tile, ragged, w):
    """One entry a token, the values the keys' first rows: the grid kernel
    over tiles of query rows reads what gather + dense attention reads."""
    rng = np.random.default_rng(0)
    n_l, n_p, dl, ps, dv, b, h, mp = 3, 40, 24, 16, 16, 5, 4, 6
    pool = jnp.asarray(rng.normal(size=(n_l, n_p, 1, dl, ps)), jnp.float32)
    tables = jnp.asarray(rng.permutation(n_p)[:b * mp].reshape(b, mp),
                         jnp.int32)
    lengths = jnp.asarray([8, 30, 96, 50, 17], jnp.int32)
    widths = (jnp.asarray([w, w, max(w - 3, 1), 1, w], jnp.int32)
              if ragged else None)
    q = jnp.asarray(rng.normal(size=(b, w, h, dl)), jnp.float32)
    kw = dict(widths=widths, latent_dv=dv, scale=0.3)
    want = np.asarray(paged_attention_xla(q, pool, None, lengths, tables, 1,
                                          **kw))
    got = np.asarray(paged_attention(q, pool, None, lengths, tables, 1,
                                     latent_tile=tile, pages_per_block=2,
                                     **kw))
    assert got.shape == (b, w, h, dv)
    valid = np.asarray(widths) if ragged else np.full(b, w)
    for i in range(b):
        np.testing.assert_allclose(got[i, :valid[i]], want[i, :valid[i]],
                                   atol=2e-6)


def test_a_latent_pool_takes_no_values_scales_or_window():
    pool = jnp.zeros((1, 4, 1, 24, 16))
    q = jnp.zeros((1, 1, 4, 24))
    one = jnp.ones((1,), jnp.int32)
    with pytest.raises(ValueError, match="latent pool"):
        paged_attention(q, pool, pool, one, jnp.zeros((1, 2), jnp.int32),
                        latent_dv=16)
    with pytest.raises(ValueError, match="latent pool"):
        paged_attention(q, pool, None, one, jnp.zeros((1, 2), jnp.int32),
                        latent_dv=16, window=8)


# ---------------------------------------------------------------------------
# absorbed against expanded
# ---------------------------------------------------------------------------

def test_absorbed_attention_over_latent_pages_is_the_expanded_form(model):
    """The program's block (low-rank projections, the keys' expansion
    absorbed into the query, latent entries written to pages, attention
    over them, the values' expansion after it) against the reference's
    expanded MLA on one sequence."""
    cfg, mcfg, weights, _ = model
    s = 50
    x = jnp.asarray(np.random.default_rng(1).normal(size=(1, s, 96)),
                    jnp.float32)
    lp = jax.tree.map(lambda p: p[1], weights["layers"])
    hp = latent.half(weights["layers"], 1, 1)
    cos, sin = latent.rope_table(mcfg, 64)
    pos = jnp.arange(s)[None, :]
    q, entries = latent.latent_qkv(x, hp, mcfg, cos, sin, pos)
    assert q.shape == (1, s, 4, 16) and entries.shape == (1, s, 16)
    cache = paged_engine.init_paged_cache(
        mcfg, num_pages=8, page_size=PAGE, batch=1, max_pages_per_slot=4)
    assert cache.v is None and cache.k.shape == (4, 8, 1, 16, PAGE)
    cache = cache._replace(tables=jnp.asarray([[5, 2, 7, 0]], jnp.int32))
    cache = paged_engine._write_window(cache, 3, entries[:, :, None, :],
                                       None, pos)
    o = paged_attention_xla(q, cache.k, None, jnp.asarray([s]),
                            cache.tables, 3, latent_dv=mcfg.kv_lora_rank,
                            scale=mcfg.head_dim ** -0.5)
    got = latent.latent_out(jnp.zeros_like(x), o, hp, mcfg)[0]
    want = longcat_flash._mla(
        x[0], {k: lp[k][1] for k in ("wq_a", "q_norm", "wq_b", "wkv_a",
                                     "kv_norm", "wkv_b", "wo")},
        eps=mcfg.norm_eps, theta=mcfg.rope_theta, nope=16, rope=8)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


# ---------------------------------------------------------------------------
# the router and the share
# ---------------------------------------------------------------------------

def _layer_params(weights, layer=0):
    return jax.tree.map(lambda p: p[layer], weights["layers"])


def _with_bias(weights, seed=7):
    """The weights with a router bias that moves choices (the seed's is
    the same for every expert)."""
    lw = dict(weights["layers"])
    lw["router_bias"] = jnp.asarray(np.random.default_rng(seed).normal(
        size=lw["router_bias"].shape) * 0.05, jnp.float32)
    return {**weights, "layers": lw}


@pytest.mark.parametrize("tokens", [64, 320], ids=["64", "320"])
def test_one_hot_and_sorted_dispatch_are_the_references_share(model, tokens):
    """Held experts by either dispatch, the identity term beside them, the
    absent experts left out: both are the family's `_moe`, choice by
    probability plus bias, gate by probability times the factor."""
    cfg, mcfg, weights, _ = model
    weights = _with_bias(weights)
    lp = _layer_params(weights)
    u = jnp.asarray(np.random.default_rng(2).normal(size=(1, tokens, 96)),
                    jnp.float32)
    stack = (weights["layers"], 0)
    assert moe._dispatch_grouped(mcfg, tokens, stack) == (tokens == 320)
    one_hot, aux = moe.moe_mlp(u, lp, mcfg)
    served, aux2 = moe.moe_mlp(u, lp, mcfg, stack)
    want, _ = longcat_flash._moe(
        u[0], lp, top_k=3, routed=8, factor=6.0, lo=0, hi=4)
    np.testing.assert_allclose(np.asarray(one_hot[0]), np.asarray(want),
                               atol=2e-5)
    np.testing.assert_allclose(np.asarray(served[0]), np.asarray(want),
                               atol=2e-5)
    counts = np.asarray(aux["assign"])
    np.testing.assert_array_equal(counts, np.asarray(aux2["assign"]))
    assert counts.sum() == tokens * 3 and (counts > 0).all()


def test_the_identity_term_is_the_gates_times_the_token(model):
    cfg, mcfg, weights, _ = model
    lp = _layer_params(weights)
    u = jnp.asarray(np.random.default_rng(3).normal(size=(1, 40, 96)),
                    jnp.float32)
    logits = u[0] @ lp["router"]
    gates, idx = moe._share_gates(logits, lp["router_bias"], mcfg)
    term, counts = moe._share_identity(u[0], gates, idx, mcfg)
    zero = np.asarray(idx) >= 8
    want = (np.asarray(gates) * zero).sum(1)[:, None] * np.asarray(u[0])
    np.testing.assert_allclose(np.asarray(term), want, atol=1e-6)
    assert int(counts[1]) == zero.sum()
    # the gates are probability x 6, never renormalised
    p = np.asarray(jax.nn.softmax(logits, axis=-1))
    np.testing.assert_allclose(
        np.asarray(gates), 6 * np.take_along_axis(p, np.asarray(idx), 1),
        rtol=1e-6)


@pytest.mark.parametrize("tokens", [48, 320], ids=["one-hot", "sorted"])
def test_the_shares_add_up_to_the_uncut_layer(model, tokens):
    """The guide's share test, in the program: both chips' shares (4
    experts each of the 8 routed; a share holds the first experts of its
    router, so the other chip's router has its columns first), the
    identity term counted once, are the layer with all 8 experts held."""
    cfg, mcfg, weights, _ = model
    rng = np.random.default_rng(4)
    whole = dataclasses.replace(mcfg, num_experts=8)
    lp = _layer_params(_with_bias(weights))
    experts = {k: jnp.asarray(rng.normal(size=(1, 8) + lp[k].shape[1:])
                              / 8, jnp.float32)
               for k in ("w_gate", "w_up", "w_down")}
    u = jnp.asarray(rng.normal(size=(1, tokens, 96)), jnp.float32)

    def run(cfg_, order, held):
        lw = {**lp, "router": lp["router"][:, order],
              "router_bias": lp["router_bias"][order]}
        stacked = {k: (experts[k][:, held] if k in experts
                       else v[None]) for k, v in lw.items()}
        lw.update({k: stacked[k][0] for k in experts})
        return moe.moe_mlp(u, lw, cfg_, (stacked, 0))

    ident = np.arange(12)
    uncut, _ = run(whole, ident, np.arange(8))
    first, _ = run(mcfg, ident, np.arange(4))
    other_first = np.concatenate([np.arange(4, 8), np.arange(4),
                                  np.arange(8, 12)])
    second, _ = run(mcfg, other_first, np.arange(4, 8))
    logits = u[0] @ lp["router"]
    identity, _ = moe._share_identity(
        u[0], *moe._share_gates(logits, lp["router_bias"], mcfg), mcfg)
    np.testing.assert_allclose(
        np.asarray(first + second)[0] - np.asarray(identity),
        np.asarray(uncut)[0], atol=2e-5)


def test_the_training_scans_raise_for_this_model_by_name(model):
    _, mcfg, weights, _ = model
    with pytest.raises(NotImplementedError, match="LongCat-Flash"):
        moe.forward_hidden(weights, jnp.zeros((1, 8), jnp.int32), mcfg)
    with pytest.raises(NotImplementedError, match="double layer"):
        latent.forward_hidden(weights, jnp.zeros((1, 8), jnp.int32), mcfg)
    with pytest.raises(ValueError, match="come together"):
        ModelConfig(kv_lora_rank=16)
    with pytest.raises(ValueError, match="routed_scaling_factor"):
        ModelConfig(num_experts=4, num_zero_experts=4)


# ---------------------------------------------------------------------------
# the served path
# ---------------------------------------------------------------------------

PROMPTS = (150, 37, 90)


def make_server(model, **kw):
    _, mcfg, weights, _ = model
    opts = dict(max_slots=4, max_context=256, page_size=PAGE, num_pages=64,
                prefill_chunk=CHUNK, decode_chunk=1)
    opts.update(kw)
    if "decode_attention_impl" in opts:
        mcfg = dataclasses.replace(
            mcfg, decode_attention_impl=opts.pop("decode_attention_impl"))
    # `waits`: every launch after the commit before it (serial_order)
    waiting = opts.pop("waits", False)
    return waits(PagedInferenceServer(
        weights, mcfg, InferConfig(max_decode_len=64, temperature=0.0,
                                   eos_token_id=-1), **opts), waiting)


def serve_all(srv, prompts, max_new=24):
    handles = [srv.submit(p, max_new_tokens=max_new) for p in prompts]
    while any(h.finish_reason is None for h in handles):
        srv.step()
    return handles


def worst_logprob_diff(model, prompts, handles):
    """The largest |served - reference| log-probability over the served
    tokens whose router gap in the reference is 1e-4 or more in every
    layer (`tests/window_model.py` says why; the gap here is one of
    probabilities over 12 columns, and float32 serving lies 1e-6 from
    the float32 reference)."""
    worst, stable, total = 0.0, 0, 0
    for p, h in zip(prompts, handles):
        assert h.finish_reason == "length", h.finish_reason
        lp, margin, gap = reference.teacher_forced(model[3], p, h.tokens)
        keep = gap >= 1e-4
        stable, total = stable + int(keep.sum()), total + keep.size
        worst = max(worst, float(
            np.abs(lp - np.asarray(h.logprobs))[keep].max()))
        assert margin[keep].max() < 0.02
    assert stable > 0.8 * total
    return worst


@pytest.mark.parametrize("mode", [
    dict(),
    dict(waits=True),
    dict(mixed_token_budget=40),
    dict(decode_attention_impl="pallas",
         page_size=128, max_context=256, num_pages=16, prefill_chunk=128),
], ids=["ahead", "waits", "budget", "kernel"])
def test_served_requests_are_the_reference(model, mode):
    """Three requests of 174, 61 and 114 tokens through the server:
    chunked prefill, then decode through the latent pages, every served
    log-probability against the family's float32 reference, and every
    page back at the end."""
    srv = make_server(model, **mode)
    pools = srv.state["pools"]
    assert set(pools) == {"k", "assign"}
    assert pools["k"].shape[0] == 4 and pools["k"].shape[2:4] == (1, 16)
    prompts = [list(map(int, tokens_of(n, 10 + n))) for n in PROMPTS]
    handles = serve_all(srv, prompts)
    assert worst_logprob_diff(model, prompts, handles) < LOGPROB_ATOL
    assert srv.allocator.stats().pages_active == 0
    recs = [r for r in srv.flight.window() if "assign_held" in r]
    assert recs and all(
        r["assign_held"] + r["assign_zero"] + r["assign_absent"] > 0
        for r in recs)
    assert any(r.get("keys_latent_decode", 0) > 0
               for r in srv.flight.window())


def test_the_prefix_cache_serves_latent_pages(model):
    """One kind of page: a second request with the first's prompt as its
    prefix starts from the cached latent pages and is still the
    reference."""
    srv = make_server(model)
    first = list(map(int, tokens_of(100, 21)))
    serve_all(srv, [first], max_new=4)
    longer = first + list(map(int, tokens_of(30, 22)))
    hits0 = srv.allocator.stats().prefix_hit_pages
    handles = serve_all(srv, [longer], max_new=16)
    assert srv.allocator.stats().prefix_hit_pages >= hits0 + 100 // PAGE
    assert worst_logprob_diff(model, [longer], handles) < LOGPROB_ATOL


def test_what_cannot_carry_latent_pages_refuses_the_model_by_name(model):
    _, mcfg, weights, _ = model
    srv = make_server(model)
    with pytest.raises(ValueError, match="latent"):
        ReplicatedRouter([srv])
    with pytest.raises(ValueError, match="latent entries"):
        srv.submit([1, 2, 3], max_new_tokens=2, handoff=lambda *a: None)
    h = srv.submit([1, 2, 3], max_new_tokens=2)
    with pytest.raises(ValueError, match="latent entries"):
        srv.migrate_export(h)
    with pytest.raises(ValueError, match="latent entries"):
        srv.drain(migrate=lambda *a: None)
    with pytest.raises(ValueError, match="no int8 cache"):
        dataclasses.replace(mcfg, kv_cache_dtype="int8")
    with pytest.raises(ValueError, match="draft model"):
        PagedInferenceServer(
            weights, mcfg, InferConfig(max_decode_len=8), max_slots=2,
            max_context=64, page_size=PAGE, num_pages=8, spec_drafts=2,
            draft_params=weights, draft_cfg=mcfg)
    with pytest.raises(ValueError, match="window cap"):
        make_server(model, decode_attention_impl="pallas", page_size=128,
                    max_context=4096, prefill_chunk=2048)
