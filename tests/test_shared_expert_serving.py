"""Two stacks of layers on the paged server (Trinity-Mini's family, `afmoe`,
at its tiny widths on the CPU, float32): two leading dense layers before
four expert layers, a sigmoid router balanced by a bias beside a shared
expert, gated QK-normed attention with a norm behind each block, three
window layers to one full. The layer's block against a hand computation
under both dispatches, the served path (chunked prefill over a ragged last
chunk, then decode past the window through both pools) against the
program's own forward without a cache and against the family's plain
reference, a joined walk against separate walks, the counters the model
brings, and every mechanism that walks one stack refusing the model."""
import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from serial_order import waits

from cellbench import families, reference, serve
from cellbench.families import afmoe
from cloud_server_tpu.config import InferConfig, ModelConfig
from cloud_server_tpu.inference import engine, paged_engine, paged_server
from cloud_server_tpu.inference.paged_server import PagedInferenceServer
from cloud_server_tpu.models import hf_convert, lora, moe, transformer
from cloud_server_tpu.models.quantization import QTensor, quantize_params
from cloud_server_tpu.ops import grouped_matmul

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WINDOW, PAGE, CHUNK = 40, 16, 32
# float32 end to end: the server and the program's own forward are the
# reference to summation order (1e-6 on logits of spread 1); a served
# log-probability is taken from logits of 512 words
LOGITS_ATOL, LOGPROB_ATOL = 5e-4, 1e-3
PROMPTS = (150, 37, 90)  # 150 and 90: ragged last chunks of 22 and 26


@pytest.fixture(scope="module")
def model():
    """(configuration as served, ModelConfig, weights, reference forward)
    at the family's tiny widths with a 40-token window and a drawn bias:
    8 experts of 32, 3 a token, one shared expert."""
    with open(os.path.join(ROOT, "cellbench", "configs",
                           "trinity-mini.json")) as f:
        cfg_file = json.load(f)
    cfg, mcfg, weights = serve.make_model(
        cfg_file, {**afmoe.TINY, "sliding_window": WINDOW}, 2**31 + 52)
    bias = weights["layers"]["router_bias"]
    weights["layers"]["router_bias"] = 0.1 * jax.random.normal(
        jax.random.key(3), bias.shape, bias.dtype)
    assert mcfg.q_per_kv == 8 and WINDOW % PAGE
    yield cfg, mcfg, weights, families.forward_of(cfg, weights)
    jax.clear_caches()


def tokens_of(n, seed=0):
    return np.random.default_rng(seed).integers(1, 512, n)


def make_server(model, **kw):
    _, mcfg, weights, _ = model
    opts = dict(max_slots=4, max_context=256, page_size=PAGE, num_pages=64,
                prefill_chunk=CHUNK, decode_chunk=1)
    opts.update(kw)
    waiting = opts.pop("waits", False)
    return waits(PagedInferenceServer(
        weights, mcfg, InferConfig(max_decode_len=64, temperature=0.0,
                                   eos_token_id=-1), **opts), waiting)


def serve_all(srv, max_new=40):
    prompts = [list(map(int, tokens_of(n, 10 + n))) for n in PROMPTS]
    handles = [srv.submit(p, max_new_tokens=max_new) for p in prompts]
    while any(h.finish_reason is None for h in handles):
        srv.step()
    return prompts, handles


# ---------------------------------------------------------------------------
# the leaves, and the expert layer's block by hand
# ---------------------------------------------------------------------------

def test_the_model_is_two_stacks_with_different_leaves(model):
    _, mcfg, weights, _ = model
    assert [mcfg.layer_stack(i) for i in range(6)] == [
        ("lead_layers", 0), ("lead_layers", 1), ("layers", 0),
        ("layers", 1), ("layers", 2), ("layers", 3)]
    # the pools keep counting over all six layers
    assert [mcfg.layer_pool(i) for i in range(6)] == [
        ("window", 0), ("window", 1), ("window", 2), ("full", 0),
        ("window", 3), ("window", 4)]
    attention = {"attn_norm", "attn_post_norm", "mlp_norm", "mlp_post_norm",
                 "q_norm", "k_norm", "wq", "wk", "wv", "wg", "wo"}
    mlp = {"w_gate", "w_up", "w_down"}
    assert set(weights["lead_layers"]) == attention | mlp
    assert set(weights["layers"]) == attention | mlp | {
        "router", "router_bias", "shared_w_gate", "shared_w_up",
        "shared_w_down"}
    assert weights["lead_layers"]["w_gate"].shape == (2, 64, 96)
    assert weights["layers"]["w_gate"].shape == (4, 8, 64, 32)
    assert weights["layers"]["shared_w_down"].shape == (4, 32, 64)
    assert weights["layers"]["q_norm"].shape == (4, 16)


def _np_norm(x, scale, eps):
    return x / np.sqrt(np.mean(x * x, -1, keepdims=True) + eps) * scale


def _np_swiglu(h, wg, wu, wd):
    g = h @ wg
    return (g / (1 + np.exp(-g)) * (h @ wu)) @ wd


@pytest.mark.parametrize("dispatch", ["sorted", "one_hot"])
def test_the_expert_block_is_the_hand_computation(model, monkeypatch,
                                                   dispatch):
    """norm, sigmoid scores, the top 3 of score + bias, the kept scores
    renormalised and scaled, every chosen expert, the shared expert ONCE,
    the norm behind, the add: whichever dispatch the routed experts take."""
    _, mcfg, weights, _ = model
    layers = weights["layers"]
    lp = jax.tree.map(lambda p: p[1], layers)
    x = jax.random.normal(jax.random.key(5), (2, 24, 64), jnp.float32)
    monkeypatch.setattr(moe, "GROUPED_MIN_TOKENS",
                        1 if dispatch == "sorted" else 10 ** 9)
    assert moe._dispatch_grouped(mcfg, 48, (layers, 1)) == (
        dispatch == "sorted")
    got, aux = moe.moe_mlp_block(x, lp, mcfg, (layers, 1))
    w = {k: np.asarray(v, np.float64) for k, v in lp.items()}
    xs = np.asarray(x, np.float64).reshape(48, 64)
    h = _np_norm(xs, w["mlp_norm"], mcfg.norm_eps)
    s = 1 / (1 + np.exp(-(h @ w["router"])))
    chosen = np.argsort(-(s + w["router_bias"]), axis=1)[:, :3]
    m = _np_swiglu(h, w["shared_w_gate"], w["shared_w_up"],
                   w["shared_w_down"])
    for t in range(48):
        kept = s[t, chosen[t]]
        gates = mcfg.route_scale * kept / kept.sum()
        for e, g in zip(chosen[t], gates):
            m[t] += g * _np_swiglu(h[t], w["w_gate"][e], w["w_up"][e],
                                   w["w_down"][e])
    want = xs + _np_norm(m, w["mlp_post_norm"], mcfg.norm_eps)
    np.testing.assert_allclose(np.asarray(got).reshape(48, 64), want,
                               atol=2e-5)
    # the bias moved a choice here, or the test shows nothing
    assert (np.sort(chosen, 1) != np.sort(
        np.argsort(-s, axis=1)[:, :3], 1)).any()
    np.testing.assert_array_equal(
        np.asarray(aux["load"]), np.bincount(chosen.ravel(), minlength=8))


# ---------------------------------------------------------------------------
# the served path
# ---------------------------------------------------------------------------

def test_the_programs_own_forward_is_the_reference(model):
    """`moe.forward`, the scans over both stacks without a cache, against
    the family's plain reference on a sequence past the window."""
    _, mcfg, weights, forward = model
    tokens = tokens_of(180, 4)
    got, _ = moe.forward(weights, jnp.asarray(tokens)[None], mcfg)
    np.testing.assert_allclose(np.asarray(got[0]),
                               np.asarray(forward(tokens)[0]),
                               atol=LOGITS_ATOL)


@pytest.mark.parametrize("mode", [
    dict(), dict(waits=True), dict(mixed_token_budget=40)],
    ids=["ahead", "waits", "budget"])
def test_served_requests_are_both_forwards_and_pages_go_back(model, mode):
    """Three requests of 190, 77 and 130 tokens through the server, past
    the window by up to nine pages: every served log-probability against
    the family's reference and against the program's own forward, every
    window page returned, and the model's counters in every record."""
    _, mcfg, weights, forward = model
    srv = make_server(model, **mode)
    pools = srv.state["pools"]
    assert {"k", "v", "wk", "wv", "assign"} <= set(pools)
    assert pools["k"].shape[0] == 1 and pools["wk"].shape[0] == 5
    prompts, handles = serve_all(srv)
    for p, h in zip(prompts, handles):
        assert h.finish_reason == "length"
        lp, margin, gap = reference.teacher_forced(forward, p, h.tokens)
        keep = gap >= 1e-4  # a router's near tie goes either way
        assert keep.mean() > 0.9
        served = np.asarray(h.logprobs)
        assert np.abs(lp - served)[keep].max() < LOGPROB_ATOL
        assert margin[keep].max() < 0.02
        seq = np.asarray(p + h.tokens[:-1])
        own, _ = moe.forward(weights, jnp.asarray(seq)[None], mcfg)
        own = jax.nn.log_softmax(own[0, len(p) - 1:], axis=-1)
        own = np.asarray(own)[np.arange(len(h.tokens)), h.tokens]
        assert np.abs(own - served)[keep].max() < LOGPROB_ATOL
    pool = srv.window_pool
    assert pool.active == 0 and pool.pages_allocated == pool.pages_returned
    assert pool.pages_returned > 0
    assert srv.allocator.stats().pages_active == 0
    assert srv.window_pages_per_slot < -(-190 // PAGE)
    recs = [r for r in srv.flight.window() if "assign_total" in r]
    assert recs and any(r.get("joined") for r in recs)
    for r in recs:
        # 3 experts a token in each of 4 expert layers; the fullest expert
        # has its even share at least, and at most every row of a walk
        assert r["assign_total"] % 12 == 0 and r["assign_total"] > 0
        rows = r["assign_total"] // 12
        assert rows * 3 / 8 <= r["assign_peak"] <= rows
        assert "assign_held" not in r
        # each expert's rows of each layer rounded up to whole sub-tiles
        sub = grouped_matmul.SUB_ROWS
        assert r["assign_rows_computed"] % sub == 0
        assert r["assign_total"] <= r["assign_rows_computed"] \
            < r["assign_total"] + 4 * 8 * sub
    snap = srv.metrics_snapshot()
    assert snap["cloud_server_expert_assign_total"]["value"] == \
        recs[-1]["assign_total"]
    assert snap["cloud_server_expert_assign_peak"]["value"] == \
        recs[-1]["assign_peak"]
    assert snap["cloud_server_expert_assign_rows_computed"]["value"] == \
        recs[-1]["assign_rows_computed"]


@pytest.mark.parametrize("router", ["seeded", "one_sided"])
def test_a_walk_counts_the_rows_the_way_in_computes(model, monkeypatch,
                                                    router):
    """`assign_rows_computed`, the third running count of `PagedKVCache.
    assign`: over a walk's four expert layers, each expert's assignments
    rounded up to the kernel's sub-tiles (`grouped_matmul.SUB_ROWS`),
    summed; under the fixture's drawn bias, and under one that sends every
    token to the same three experts."""
    _, mcfg, weights, _ = model
    if router == "one_sided":
        bias = weights["layers"]["router_bias"]
        weights = {**weights, "layers": {
            **weights["layers"],
            "router_bias": jnp.zeros_like(bias).at[:, 2:5].set(1e3)}}
    b, w = 4, 75
    cache = paged_engine.init_paged_cache(
        mcfg, num_pages=32, page_size=PAGE, batch=b, max_pages_per_slot=8)
    tokens = jnp.asarray(tokens_of(b * w, 7).reshape(b, w), jnp.int32)

    def walk(weights, cache):
        loads = []
        real = moe.moe_mlp_block

        def spy(*a, **kw):
            out, aux = real(*a, **kw)
            loads.append(aux["load"])
            return out, aux

        monkeypatch.setattr(moe, "moe_mlp_block", spy)
        _, pools = paged_engine.window_forward(
            weights, tokens, mcfg, cache,
            logits_at=jnp.zeros((b,), jnp.int32))
        monkeypatch.setattr(moe, "moe_mlp_block", real)
        return pools.assign, jnp.stack(loads)

    assign, loads = (np.asarray(a) for a in jax.jit(walk)(weights, cache))
    sub = grouped_matmul.SUB_ROWS
    assert loads.shape == (4, 8) and (loads.sum(1) == 3 * b * w).all()
    total, peak, computed = assign.tolist()
    assert (total, peak) == (loads.sum(), loads.max())
    assert computed == (-(-loads // sub) * sub).sum()
    if router == "one_sided":  # three experts a layer hold every row
        assert (loads[:, 2:5] == b * w).all()
        assert computed == 4 * 3 * -(-b * w // sub) * sub
    else:
        assert (loads > 0).sum() > 4 * 3 and computed > total


def test_the_scopes_are_in_the_lowered_walk_and_in_no_other_models(model):
    """`moe_shared`, `lead_dense` and `attn_gate` name the ops of what the
    model states, in its walk; a model that states none of it (the second
    cell's family here) lowers to none of them and keeps no counts."""
    from window_model import make_model
    names = ("moe_shared", "lead_dense", "attn/attn_gate")

    def lowered(mcfg, weights):
        cache = paged_engine.init_paged_cache(
            mcfg, num_pages=8, page_size=PAGE, batch=2, max_pages_per_slot=4)
        tokens = jnp.ones((2, 8), jnp.int32)
        return cache, jax.jit(lambda w, c: paged_engine.window_forward(
            w, tokens, mcfg, c, logits_at=jnp.zeros((2,), jnp.int32))[0]
        ).lower(weights, cache).as_text(debug_info=True)

    cache, text = lowered(model[1], model[2])
    assert all(n + "/" in text for n in names)
    assert cache.assign.shape == (3,)  # total, peak, rows computed
    _, other, weights, _ = make_model()
    cache, text = lowered(other, weights)
    assert not any(n in text for n in ("moe_shared", "lead_dense",
                                       "attn_gate"))
    assert cache.assign is None and "moe_experts" in text


def test_a_joined_walk_is_separate_walks(model, monkeypatch):
    """The mixed step's one walk over chunk tokens and decode rows against
    the same traffic with two walks a step: the same tokens, the same
    log-probabilities, the same counts."""
    joined = serve_all(make_server(model, waits=True))[1]
    monkeypatch.setattr(paged_server, "_walks_once", lambda *a, **k: False)
    srv = make_server(model, waits=True)
    apart = serve_all(srv)[1]
    assert not any(r.get("joined") for r in srv.flight.window())
    for a, b in zip(joined, apart):
        assert a.tokens == b.tokens
        np.testing.assert_allclose(a.logprobs, b.logprobs, atol=1e-5)


def test_int8_weights_run_both_stacks(model):
    _, mcfg, weights, _ = model
    q = quantize_params(weights)
    for stack, leaf in (("lead_layers", "w_down"), ("lead_layers", "wg"),
                        ("layers", "w_up"), ("layers", "shared_w_gate")):
        assert isinstance(q[stack][leaf], QTensor), (stack, leaf)
    assert not isinstance(q["layers"]["router_bias"], QTensor)
    tokens = jnp.asarray(tokens_of(48, 2))[None]
    want, _ = moe.forward(weights, tokens, mcfg)
    got, _ = moe.forward(q, tokens, mcfg)
    # int8 rounding, and the few tokens it sends to another expert
    diff = np.abs(np.asarray(got) - np.asarray(want))
    assert diff.max() > 1e-4 and np.median(diff) < 0.1


# ---------------------------------------------------------------------------
# what serves no such model says so
# ---------------------------------------------------------------------------

SMALL = dict(vocab_size=64, embed_dim=32, num_layers=4, num_heads=4,
             num_kv_heads=2, head_dim=8, mlp_dim=64, max_seq_len=64,
             dtype="float32", param_dtype="float32", remat="none")
EXPERTS = dict(SMALL, num_experts=4, num_experts_per_token=2,
               expert_mlp_dim=16)


@pytest.mark.parametrize("fields,match", [
    (dict(SMALL, num_dense_layers=1), "leading dense layers"),
    (dict(SMALL, shared_expert_dim=16), "shared expert"),
    (dict(EXPERTS, num_dense_layers=4), "at least one expert layer"),
    (dict(EXPERTS, num_dense_layers=1, num_routed_experts=8,
          routed_scaling_factor=2.0), "all held here"),
    (dict(SMALL, router_score="sigmoid"), "num_experts >= 2"),
    (dict(EXPERTS, router_score="sigmoid", num_routed_experts=8,
          routed_scaling_factor=2.0), "share of it"),
    (dict(EXPERTS, router_score="tanh"), "unknown router_score"),
    (dict(EXPERTS, route_scale=2.0), "sigmoid router's"),
    (dict(SMALL, layer_body="parallel_mixer", ssm_heads=2, ssm_head_dim=8,
          ssm_state_dim=8, qk_norm=True), "single layer's attention"),
], ids=["dense-layers-without-experts", "shared-without-experts",
        "no-expert-layer-left", "lead-of-a-share", "sigmoid-without-experts",
        "sigmoid-share", "unknown-score", "scale-on-softmax",
        "qk-norm-on-a-mixer"])
def test_a_combination_no_program_serves_is_refused(fields, match):
    with pytest.raises(ValueError, match=match):
        ModelConfig(**fields)


def test_walkers_of_one_stack_refuse_the_model_at_construction(model):
    _, mcfg, weights, _ = model
    two = ModelConfig(**dict(EXPERTS, num_dense_layers=1))
    refusals = [
        lambda: engine.init_cache(two, 2, 32),
        lambda: lora.init_lora_params(two, lora.LoRAConfig(
            rank=2, targets=("wq",)), jax.random.key(0), base_module=moe),
        lambda: make_server(model).add_adapter(
            "a", {}, lora.LoRAConfig(rank=2, targets=("wq",))),
    ]
    for refuse in refusals:
        with pytest.raises(ValueError, match="lead_layers"):
            refuse()
    with pytest.raises(ValueError, match="two stacks"):
        make_server(model, mesh=jax.sharding.Mesh(
            np.asarray(jax.devices()[:1]), ("tp",)))
    with pytest.raises(ValueError, match="LLaMA family's leaves"):
        hf_convert.params_to_hf(weights, mcfg)
    with pytest.raises(ValueError, match="LLaMA family's leaves"):
        hf_convert.params_from_hf({}, dataclasses.replace(
            ModelConfig(**SMALL), qk_norm=True))
    # the window layers' refusals hold for this model as for any
    srv = make_server(model)
    h = srv.submit([1, 2, 3], max_new_tokens=2)
    with pytest.raises(ValueError, match="sliding-window layers"):
        srv.migrate_export(h)
