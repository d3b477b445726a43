"""One walk of the layers in a mixed step (CPU, tiny widths).

`paged_engine.forward_sets` over a ragged prefill group and a decode
round computes what two `window_forward` calls compute (logits and
pools); `_mixed_step` on that walk computes what the two cores called
apart compute (tokens, log-probabilities, state), greedy and seeded;
and where the one walk would be another function (further rounds,
drafts, a capacity that can drop, a live adapter) `_walks_once` says
no and the program keeps its two walks.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from serial_order import waits

from cloud_server_tpu.config import InferConfig, ModelConfig
from cloud_server_tpu.inference import engine, paged_engine
from cloud_server_tpu.inference import paged_server as ps
from cloud_server_tpu.inference.paged_server import PagedInferenceServer
from cloud_server_tpu.inference.sampling import SamplingParams, make_rows
from cloud_server_tpu.models import moe, transformer

DENSE = ModelConfig(
    vocab_size=64, embed_dim=32, num_layers=2, num_heads=4, num_kv_heads=2,
    head_dim=8, mlp_dim=64, max_seq_len=256, dtype="float32",
    param_dtype="float32", remat="none")
# experts over experts per token: `moe._capacity(cfg, T) >= T` at every T
DROPLESS = dataclasses.replace(DENSE, num_experts=4, num_experts_per_token=2,
                               expert_capacity_factor=2.0)
CAN_DROP = dataclasses.replace(DROPLESS, expert_capacity_factor=1.25)
CONFIGS = {"dense": DENSE, "dropless_moe": DROPLESS, "can_drop": CAN_DROP}
GREEDY = InferConfig(max_decode_len=8, temperature=0.0, eos_token_id=-1,
                     pad_token_id=0)

PAGES, PS, SLOTS, CONTEXT = 24, 8, 8, 64
G, WC = 4, 16


def _params(cfg):
    init = moe.init_params if cfg.num_experts else transformer.init_params
    return init(cfg, jax.random.key(0))


def _rows(cfg):
    """A prefill group with ragged widths, a width-0 row on sentinel
    tables and rows resuming mid-page, beside a decode round with a dead
    row; pools full of somebody's history."""
    cache = paged_engine.init_paged_cache(
        cfg, num_pages=PAGES, page_size=PS, batch=SLOTS,
        max_pages_per_slot=CONTEXT // PS)
    kk, kv, kc, kl = jax.random.split(jax.random.key(1), 4)
    cache = cache._replace(
        k=jax.random.normal(kk, cache.k.shape, cache.k.dtype),
        v=jax.random.normal(kv, cache.v.shape, cache.v.dtype))
    per = CONTEXT // PS

    def table(first):  # a chain of 3 own pages, then no page
        t = np.full((per,), PAGES, np.int32)
        if first is not None:
            t[:3] = np.arange(first, first + 3)
        return t

    group = dict(
        chunk=jax.random.randint(kc, (G, WC), 1, cfg.vocab_size),
        g_lens=jnp.asarray([0, 8, 3, 5], jnp.int32),
        widths=jnp.asarray([16, 7, 0, 16], jnp.int32),
        sample_at=jnp.asarray([15, 6, 0, 15], jnp.int32),
        g_tables=jnp.asarray(np.stack(
            [table(0), table(3), table(None), table(6)])))
    decode = dict(
        last=jax.random.randint(kl, (3,), 1, cfg.vocab_size),
        lengths=jnp.asarray([5, 11, 0], jnp.int32),
        live=jnp.asarray([True, True, False]),
        tables=jnp.asarray(np.stack([table(9), table(12), table(None)])))
    return cache, group, decode


@pytest.mark.parametrize("name", ["dense", "dropless_moe"])
def test_forward_sets_equals_two_window_forwards(name):
    cfg = CONFIGS[name]
    params = _params(cfg)
    cache, g, d = _rows(cfg)
    at0 = jnp.zeros_like(d["lengths"])
    want_p, c = paged_engine.window_forward(
        params, g["chunk"], cfg,
        cache._replace(lengths=g["g_lens"], tables=g["g_tables"]),
        logits_at=g["sample_at"], widths=g["widths"])
    want_d, c = paged_engine.window_forward(
        params, d["last"][:, None], cfg,
        c._replace(lengths=d["lengths"], tables=d["tables"]), logits_at=at0)
    (got_p, got_d), got = paged_engine.forward_sets(
        params, cfg, cache,
        [paged_engine.RowSet(g["chunk"], g["g_lens"], g["g_tables"],
                             g["widths"], g["sample_at"], "prefill_group"),
         paged_engine.RowSet(d["last"][:, None], d["lengths"], d["tables"],
                             None, at0, "decode_rounds")])
    np.testing.assert_allclose(got_p, want_p, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(got_d, want_d, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(got.k, c.k, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(got.v, c.v, rtol=1e-5, atol=1e-6)
    # the windows were written: the pools moved, and only where a live
    # row's table points
    assert not np.array_equal(np.asarray(got.k), np.asarray(cache.k))
    np.testing.assert_array_equal(got.k[:, 15:], cache.k[:, 15:])
    # the caller's view of the pools is handed back as it came
    np.testing.assert_array_equal(got.lengths, cache.lengths)
    np.testing.assert_array_equal(got.tables, cache.tables)


def test_forward_sets_refuses_adapters_over_two_sets():
    cache, g, d = _rows(DENSE)
    sets = [paged_engine.RowSet(g["chunk"], g["g_lens"], g["g_tables"]),
            paged_engine.RowSet(d["last"][:, None], d["lengths"],
                                d["tables"])]
    with pytest.raises(ValueError, match="one row set"):
        paged_engine.forward_sets(_params(DENSE), DENSE, cache, sets,
                                  lora=({}, {}), aid=jnp.zeros((7,)))


# -- `_mixed_step` against `_prefill_core` and `_decode_plain_core` apart ----

def _step_args(cfg, icfg, sampling, penalties):
    cache, g, d = _rows(cfg)
    state = {"pools": ps._split_cache(cache),
             "hist": jnp.zeros((SLOTS, CONTEXT), jnp.int32),
             "gstate": jnp.zeros((SLOTS,), jnp.int32),
             "last": jnp.zeros((SLOTS,), jnp.int32)}
    if penalties:
        state["prompt_mask"] = jnp.zeros((SLOTS, cfg.vocab_size), bool)
        state["out_counts"] = jnp.zeros((SLOTS, cfg.vocab_size), jnp.int32)
    prompt_lens = np.asarray([16, 15, 0, 21], np.int32)
    samp_g = jax.tree.map(jnp.asarray, make_rows(
        [sampling] * G, icfg, [7, 8, 9, 10], prompt_lens))
    samp_d = jax.tree.map(jnp.asarray, make_rows(
        [sampling] * 3, icfg, [11, 12, 13]))
    group = (g["chunk"], g["widths"], g["g_lens"], g["g_tables"],
             g["sample_at"],
             jnp.asarray([0, 1, SLOTS, 3], jnp.int32),      # slot_ids
             jnp.pad(g["chunk"], ((0, 0), (0, 16))),        # prompt_rows
             jnp.asarray(prompt_lens), samp_g,
             jnp.asarray(prompt_lens),                      # orig_lens
             jnp.asarray([True, False, False, True]),       # count_mask
             jnp.asarray([True, False, False, False]),      # scatter_mask
             jnp.zeros((G,), jnp.int32), jnp.zeros((G,), jnp.int32))
    decode = (d["lengths"], d["tables"], d["last"], d["live"],
              jnp.asarray([40, 40, 0], jnp.int32),          # stop_len
              samp_d, jnp.zeros((3,), jnp.int32),
              jnp.asarray([4, 5, SLOTS], jnp.int32))        # slot_ids_d
    return state, group, decode


COUNT = 3  # the dispatch's count, folded into the key by the program

_prefill_apart = jax.jit(ps._prefill_core, static_argnames=(
    "cfg", "infer_cfg", "scatter_prompt", "use_rows"))
_decode_apart = jax.jit(ps._decode_plain_core, static_argnames=(
    "cfg", "infer_cfg", "n_rounds", "use_rows"))


def _apart(params, state, group, decode, rng, *, cfg, icfg, n_rounds,
           use_rows):
    """The two cores, each its own program with its own walk, under the
    keys `_mixed_step` hands them."""
    (chunk, widths, g_lens, g_tables, sample_at, slot_ids, prompt_rows,
     prompt_lens, samp_g, orig_lens, count_mask, scatter_mask, gid_g,
     gstate0_g) = group
    (lengths, tables, last, live, _, samp_d, gid_d, slot_ids_d) = decode
    rng_p, rng_d = jax.random.split(jax.random.fold_in(rng, COUNT))
    state, ptoks, plps = _prefill_apart(
        params, state, chunk, g_lens, g_tables, sample_at, slot_ids,
        prompt_rows, prompt_lens, rng_p, samp_g, orig_lens, count_mask,
        gid_g, gstate0_g, None, None, None, None, widths, scatter_mask,
        cfg=cfg, infer_cfg=icfg, scatter_prompt=True, use_rows=use_rows)
    state, lens, last, (toks, lps, counts) = _decode_apart(
        params, state, lengths, tables, last, live, rng_d, samp_d, gid_d,
        None, None, None, slot_ids_d, cfg=cfg, infer_cfg=icfg,
        n_rounds=n_rounds, use_rows=use_rows)
    return state, ptoks, plps, lens, last, (toks[:, :, None],
                                            lps[:, :, None], counts)


def _mixed(params, state, group, decode, rng, *, cfg, icfg, n_rounds,
           use_rows, lower=False):
    fn = ps._mixed_step.lower if lower else ps._mixed_step
    (chunk, widths, g_lens, g_tables, sample_at, slot_ids, prompt_rows,
     prompt_lens, samp_g, orig_lens, count_mask, scatter_mask, gid_g,
     gstate0_g) = jax.tree.map(np.asarray, group)
    (lengths, tables, last, live, stop_len, samp_d, gid_d,
     slot_ids_d) = jax.tree.map(np.asarray, decode)
    patch = jnp.asarray(ps._pack_patch(COUNT, lengths, last, live, tables))
    packed = jnp.asarray(ps._pack_group(
        chunk, g_tables, prompt_rows, samp_g, widths=widths, g_lens=g_lens,
        sample_at=sample_at, slot_ids=slot_ids, prompt_lens=prompt_lens,
        orig_lens=orig_lens, count_mask=count_mask,
        scatter_mask=scatter_mask, gid=gid_g, gstate0=gstate0_g,
        aid=np.zeros_like(gid_g)))
    rows = jnp.asarray(ps._pack_rows(
        stop_len, gid_d, np.zeros_like(gid_d), np.zeros_like(gid_d), samp_d,
        slot_ids_d))
    return fn(params, state, packed, patch, rows, rng,
              cfg=cfg, infer_cfg=icfg, n_rounds=n_rounds, n_drafts=0,
              scatter_prompt=True, chunk_w=chunk.shape[1],
              use_rows_p=use_rows, use_rows_d=use_rows)


SAMPLED = dataclasses.replace(GREEDY, temperature=1.0)
SEEDED = SamplingParams(seed=123, temperature=0.9, top_p=0.9,
                        presence_penalty=0.4)
# (model, server sampling config, per-request rows, rounds, one walk?)
STEP_CASES = {
    "dense-greedy": ("dense", GREEDY, None, 1, True),
    "dense-sampled": ("dense", SAMPLED, None, 1, True),
    "dense-seeded_rows": ("dense", SAMPLED, SEEDED, 1, True),
    "moe-greedy": ("dropless_moe", GREEDY, None, 1, True),
    "moe-seeded_rows": ("dropless_moe", SAMPLED, SEEDED, 1, True),
    "fallback-two_rounds": ("dropless_moe", SAMPLED, None, 2, False),
    "fallback-can_drop": ("can_drop", SAMPLED, None, 1, False),
}


@pytest.mark.parametrize("case", list(STEP_CASES))
def test_mixed_step_equals_the_two_cores_apart(case):
    name, icfg, sampling, n_rounds, one_walk = STEP_CASES[case]
    cfg = CONFIGS[name]
    params = _params(cfg)
    state, group, decode = _step_args(cfg, icfg, sampling,
                                      penalties=sampling is not None)
    kw = dict(cfg=cfg, icfg=icfg, n_rounds=n_rounds,
              use_rows=sampling is not None)
    rng = jax.random.key(5)
    n_tokens = G * WC + 3
    assert ps._walks_once(cfg, n_tokens, n_rounds, 0, None, None) == one_walk
    text = _mixed(params, state, group, decode, rng, lower=True,
                  **kw).as_text(debug_info=True)
    assert ("/joined_walk/" in text) == one_walk

    want = _apart(params, jax.tree.map(jnp.copy, state), group, decode,
                  rng, **kw)
    got = _mixed(params, jax.tree.map(jnp.copy, state), group, decode,
                 rng, **kw)
    w_state, w_ptoks, w_plps, w_lens, w_last, (w_t, w_lp, w_n) = want
    g_state, g_ptoks, g_plps, g_lens, g_last, (g_t, g_lp, g_n), _ = got
    np.testing.assert_array_equal(g_ptoks, w_ptoks)
    np.testing.assert_array_equal(g_t, w_t)
    np.testing.assert_array_equal(g_n, w_n)
    np.testing.assert_array_equal(g_lens, w_lens)
    np.testing.assert_array_equal(g_last, w_last)
    np.testing.assert_allclose(g_plps, w_plps, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(g_lp, w_lp, rtol=1e-5, atol=1e-5)
    assert sorted(g_state) == sorted(w_state)
    for key in ("hist", "gstate", "prompt_mask", "out_counts"):
        if key in w_state:
            np.testing.assert_array_equal(g_state[key], w_state[key])
    for key, pool in w_state["pools"].items():
        np.testing.assert_allclose(g_state["pools"][key], pool,
                                   rtol=1e-5, atol=1e-6)
    assert int(np.asarray(g_n).sum()) == 2 * n_rounds  # two live rows


@pytest.mark.parametrize("why,kw", [
    ("two_rounds", dict(n_rounds=2)),
    ("no_decode_round", dict(n_rounds=0)),
    ("drafts", dict(n_drafts=2)),
    ("draft_model", dict(draft_cfg=DENSE)),
    ("live_adapter", dict(lora=({}, {}))),
    ("can_drop", dict(cfg=CAN_DROP)),
])
def test_walks_once_says_no(why, kw):
    base = dict(cfg=DROPLESS, n_tokens=320, n_rounds=1, n_drafts=0,
                draft_cfg=None, lora=None)
    assert ps._walks_once(**base)
    assert ps._walks_once(**{**base, "cfg": DENSE})
    assert not ps._walks_once(**{**base, **kw})


# -- the server: streams equal to the alternating scheduler's ---------------

SRV_KW = dict(max_slots=4, max_context=64, page_size=8, prefill_chunk=16,
              prompt_buckets=[16, 32], decode_chunk=1)
LONG = [(i * 7) % 60 + 1 for i in range(30)]
PROMPTS = [[5, 9, 3], [17, 2, 40, 8, 21], LONG, list(range(1, 14))]


def _staggered(srv, sampling):
    reqs = [srv.submit(p, max_new_tokens=12, sampling=s)
            for p, s in zip(PROMPTS[:2], sampling[:2])]
    for _ in range(3):
        srv.step()
    reqs += [srv.submit(p, max_new_tokens=12, sampling=s)
             for p, s in zip(PROMPTS[2:], sampling[2:])]
    srv.run_until_idle()
    return [(r.result(), r.logprobs) for r in reqs]


def _server(params, cfg, icfg, order, **kw):
    return waits(PagedInferenceServer(params, cfg, icfg, **kw),
                 order == "waits")


@pytest.mark.parametrize("order", ["ahead", "waits"])
@pytest.mark.parametrize("name", ["dense", "dropless_moe"])
@pytest.mark.parametrize("seeded", [False, True])
def test_joined_server_streams_equal_two_walks(name, seeded, order):
    """At one decode round a step the programs take the one walk; what
    the clients get is the stream of the programs that walk the layers
    twice (two rounds a step: `_walks_once`), token for token, with its
    log-probabilities, and greedy the dense engine's."""
    cfg = CONFIGS[name]
    params = _params(cfg)
    icfg = SAMPLED if seeded else GREEDY
    sampling = [SamplingParams(seed=100 + i, temperature=0.9, top_p=0.9,
                               presence_penalty=0.4)
                if seeded else None
                for i in range(len(PROMPTS))]
    joined = _server(params, cfg, icfg, order, **SRV_KW)
    two = PagedInferenceServer(params, cfg, icfg,
                               **dict(SRV_KW, decode_chunk=2))
    got, want = _staggered(joined, sampling), _staggered(two, sampling)
    for (g_toks, g_lps), (w_toks, w_lps) in zip(got, want):
        assert g_toks == w_toks
        np.testing.assert_allclose(g_lps, w_lps, rtol=1e-4, atol=1e-5)
    if not seeded:
        for p, (g_toks, _) in zip(PROMPTS, got):
            ref = engine.generate(
                params, np.asarray([p], np.int32), jax.random.key(1),
                cfg=cfg, infer_cfg=dataclasses.replace(
                    icfg, max_decode_len=12))
            assert g_toks == list(np.asarray(ref)[0]), p
    records = joined.flight_window()
    assert any(r["joined"] for r in records)
    # a program of decode rounds alone has nothing to join
    assert all(not r["joined"] for r in records
               if not r.get("prefill_tokens"))
    # the reference did walk twice wherever it ran two rounds
    assert any(r.get("prefill_tokens") and r["decode_rounds"] == 2
               and not r["joined"] for r in two.flight_window())


@pytest.mark.parametrize("order", ["ahead", "waits"])
@pytest.mark.parametrize("name,threshold", [
    ("dropless_moe", 18), ("dropless_moe", 10 ** 9), ("can_drop", 18),
    ("dense", 18)])
def test_records_say_which_programs_sorted_their_experts(
        name, threshold, order, monkeypatch):
    """`grouped`: true on the record of a step whose joined call (16
    chunk tokens a prompt row and the decode rows) is over the threshold,
    false on a program of decode rounds alone (4 rows at most), at a
    threshold no call reaches, at a capacity that can drop and for a
    dense MLP: the host's reading of the rule the trace applies."""
    monkeypatch.setattr(moe, "GROUPED_MIN_TOKENS", threshold)
    sorts = name == "dropless_moe" and threshold == 18
    # where the calls sort, a configuration of its own, so its programs
    # are traced here, under this threshold, and the spy sees the dispatch
    # they take; the other cases' programs are the file's own (no call of
    # theirs sorts at any threshold this file sets), so they compile
    # nothing new in a process that never unmaps compiled code
    cfg = CONFIGS[name]
    if sorts:
        cfg = dataclasses.replace(
            cfg, norm_eps=1e-5 + 1e-9 * (1 + (order == "ahead")))
    traced = []
    real = moe._grouped_experts
    monkeypatch.setattr(moe, "_grouped_experts",
                        lambda rows, *a, **kw: traced.append(
                            rows.shape[0]) or real(rows, *a, **kw))
    srv = _server(_params(cfg), cfg, GREEDY, order, **SRV_KW)
    _staggered(srv, [None] * len(PROMPTS))
    records = srv.flight_window()
    assert all(isinstance(r["grouped"], bool) for r in records)
    assert any(r["grouped"] for r in records) == bool(traced)
    assert all(not r["grouped"] for r in records
               if not r.get("prefill_tokens"))
    if sorts:
        mixed = [r for r in records if r["joined"]]
        assert mixed and all(r["grouped"] for r in mixed)
        # every expert's rows on row tiles of their own
        assert traced and all(n % moe._GMM_ROWS == 0 for n in traced)
    else:
        assert not traced
