"""One program and one transfer in the launch (CPU, tiny widths).

Between a read-back and the next program the scheduler hands
the device ONE host array (the packed patch, with the dispatch's count
in it) and dispatches ONE program, which makes its own key from the
server's one key and that count: no `jax.random.split` runs outside a
trace, nothing that is on the device already is converted again, and
the patch comes out of the program bit for bit as it went in. What a
plan stages while the program before it runs crosses the same way: the
decode rows' launch-stable inputs as one packed array, a mixed plan's
prefill group as a second, every field bit for bit.
"""

import dataclasses
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from serial_order import waits

from cloud_server_tpu.config import InferConfig, ModelConfig
from cloud_server_tpu.data.tokenizer import ByteTokenizer
from cloud_server_tpu.inference import engine
from cloud_server_tpu.inference import paged_server as ps
from cloud_server_tpu.inference.paged_server import PagedInferenceServer
from cloud_server_tpu.inference.sampling import (
    MAX_LOGIT_BIAS, SamplingParams, SamplingRows, make_rows)
from cloud_server_tpu.models import transformer
from cloud_server_tpu.models.lora import LoRAConfig, init_lora_params

CFG = ModelConfig(
    vocab_size=64, embed_dim=32, num_layers=2, num_heads=4, num_kv_heads=2,
    head_dim=8, mlp_dim=64, max_seq_len=256, dtype="float32",
    param_dtype="float32", remat="none")
GREEDY = InferConfig(max_decode_len=8, temperature=0.0, eos_token_id=-1,
                     pad_token_id=0)
SAMPLED = InferConfig(max_decode_len=8, temperature=1.0, eos_token_id=-1,
                      pad_token_id=0)
SRV_KW = dict(max_slots=4, max_context=64, page_size=8, prefill_chunk=16,
              prompt_buckets=[16, 32])
LONG = [(i * 7) % 60 + 1 for i in range(30)]
PROMPTS = [[5, 9, 3], [17, 2, 40, 8, 21], LONG, list(range(1, 14))]
REP = [3, 4, 5, 6] * 5 + [3, 4]
STEP_PROGRAMS = ("_mixed_step", "_decode_rounds", "_spec_rounds")


@pytest.fixture(scope="module")
def params():
    return transformer.init_params(CFG, jax.random.key(0))


# -- (a) what a launch hands the device --------------------------------------

def _watch_launches(srv, monkeypatch):
    """Count, by the test's own means, what `_launch_plan` does: host
    arrays converted by jax's two conversion calls, host arrays among
    the step program's arguments (the call would transfer those itself),
    key splits made outside a trace anywhere in the step, and what the
    launched plan had staged (`plan_h2d`)."""
    seen = {"launches": [], "staged": [], "converted": 0, "host_args": 0,
            "eager_splits": 0, "in_launch": False}

    def counting(fn):
        def call(x, *args, **kwargs):
            if seen["in_launch"] and not isinstance(x, jax.Array):
                seen["converted"] += 1
            return fn(x, *args, **kwargs)
        return call

    monkeypatch.setattr(jax, "device_put", counting(jax.device_put))
    monkeypatch.setattr(jnp, "asarray", counting(jnp.asarray))
    split = jax.random.split

    def watched_split(key, *args, **kwargs):
        if not isinstance(key, jax.core.Tracer):
            seen["eager_splits"] += 1
        return split(key, *args, **kwargs)

    monkeypatch.setattr(jax.random, "split", watched_split)

    def program(fn):
        def call(*args, **kwargs):
            if seen["in_launch"]:
                seen["host_args"] += sum(
                    not isinstance(leaf, jax.Array)
                    for leaf in jax.tree.leaves(args[2:]))
            return fn(*args, **kwargs)
        return call

    for name in STEP_PROGRAMS:
        monkeypatch.setattr(ps, name, program(getattr(ps, name)))
    launch = srv._launch_plan

    def watched_launch(plan):
        before = seen["converted"]
        seen["in_launch"] = True
        try:
            launch(plan)
        finally:
            seen["in_launch"] = False
        if srv._inflight is not None:
            seen["launches"].append(
                (plan.kind, plan.sl_d is None, plan.g_iter > 0,
                 seen["converted"] - before, srv._iter_launch_h2d))
            seen["staged"].append(srv._iter_plan_h2d)

    monkeypatch.setattr(srv, "_launch_plan", watched_launch)
    return seen


@pytest.mark.parametrize("spec_drafts", [0, 2], ids=["plain", "drafts"])
def test_a_launch_hands_the_device_one_array(params, monkeypatch,
                                             spec_drafts):
    srv = PagedInferenceServer(
        params, CFG, GREEDY, flight_recorder_size=512, spec_drafts=spec_drafts, decode_chunk=1,
        **SRV_KW)
    seen = _watch_launches(srv, monkeypatch)
    first = [srv.submit(p, max_new_tokens=20) for p in (REP, PROMPTS[1])]
    for _ in range(4):
        srv.step()
    # two more fill the slots: mixed plans beside compacted decode rows,
    # then every slot live (rows are slots), then rows end one by one
    rest = [srv.submit(p, max_new_tokens=n)
            for p, n in ((LONG, 24), (PROMPTS[3], 12))]
    srv.run_until_idle()
    assert all(r.done for r in first + rest)
    kinds = {(kind, rows_are_slots)
             for kind, rows_are_slots, *_ in seen["launches"]}
    assert {("mixed", False), ("decode", False), ("decode", True)} <= kinds
    if spec_drafts:
        assert any(drafts for _, _, drafts, _, _ in seen["launches"])
    # one host array a launch, by the test's count and by the program's
    assert [n for *_, n, _ in seen["launches"]] \
        == [1] * len(seen["launches"])
    assert [n for *_, n in seen["launches"]] == [1] * len(seen["launches"])
    assert seen["host_args"] == 0
    assert seen["eager_splits"] == 0
    # each launch that went out sits in the record of the step that made
    # it; a step without one (the pipeline drains, or every planned row
    # is dead at the commit) records 0
    counts = [rec["launch_h2d"] for rec in srv.flight_window()]
    assert set(counts) == {0, 1}
    assert sum(counts) == len(seen["launches"])
    # what the plan staged while the program before it ran, one transfer
    # an array: the decode rows' packed buffer (token limits, grammars,
    # adapters, draft limits, samplers, the rows' slots where rows are
    # compacted) and, of a mixed plan, the prefill group's
    staged = {(kind, rows_are_slots): n for (kind, rows_are_slots, *_), n
              in zip(seen["launches"], seen["staged"])}
    assert staged == {("mixed", False): 2, ("decode", False): 1,
                      ("decode", True): 1}
    # on the record of the step that planned, beside that step's launch;
    # a plan whose rows all died at the commit was staged and not launched
    plans = [rec["plan_h2d"] for rec in srv.flight_window()]
    assert set(plans) <= set(staged.values()) | {0}
    assert [n for n, c in zip(plans, counts) if c] == seen["staged"]


# -- (b) the key is made inside the program ----------------------------------

def _server(params, cfg, icfg, waiting=False, **kw):
    """The server as it is, or (`waiting`) with every launch made to
    wait for the commit before it."""
    return waits(PagedInferenceServer(params, cfg, icfg,
                                      **{**SRV_KW, **kw}), waiting)


def _sampled_streams(params, seed, **kw):
    srv = _server(params, CFG, SAMPLED, seed=seed, **kw)
    reqs = [srv.submit(p, max_new_tokens=10) for p in PROMPTS]
    srv.run_until_idle()
    return [r.result() for r in reqs]


@pytest.mark.parametrize("kw", [dict(), dict(waiting=True)],
                         ids=["ahead", "waits"])
def test_b_a_server_seed_gives_its_stream_again(params, kw):
    again = _sampled_streams(params, 7, **kw)
    assert _sampled_streams(params, 7, **kw) == again
    assert _sampled_streams(params, 8, **kw) != again


def _decode_args(rows=4, per=8):
    cache = ps.paged_engine.init_paged_cache(
        CFG, num_pages=rows * 3 + 1, page_size=8, batch=rows,
        max_pages_per_slot=per)
    state = {"pools": ps._split_cache(cache),
             "hist": jnp.zeros((rows, 64), jnp.int32),
             "gstate": jnp.zeros((rows,), jnp.int32),
             "last": jnp.zeros((rows,), jnp.int32)}
    tables = np.full((rows, per), rows * 3 + 1, np.int32)
    tables[:, :3] = np.arange(rows * 3).reshape(rows, 3)
    ledger = (np.full((rows,), 9, np.int32),          # lengths
              np.full((rows,), 5, np.int32),          # the same last token
              np.ones((rows,), bool), tables)
    return state, ledger


def test_b_the_count_is_an_operand_and_moves_the_sample(params):
    """Identical rows, so identical logits: two dispatches sample apart
    because their counts differ, one count samples the same twice, and
    a new count compiles nothing."""
    state, ledger = _decode_args()
    rng = jax.random.key(3)
    zeros = np.zeros((4,), np.int32)
    rows = jnp.asarray(ps._pack_rows(
        zeros, zeros, zeros, zeros, make_rows([None] * 4, SAMPLED, zeros)))

    def dispatch(count):
        out = ps._decode_rounds(
            params, jax.tree.map(jnp.copy, state),
            jnp.asarray(ps._pack_patch(count, *ledger)), rows, rng,
            cfg=CFG, infer_cfg=SAMPLED, n_rounds=1)
        return np.asarray(out[3][0])

    one = dispatch(1)
    programs = ps._decode_rounds._cache_size()
    np.testing.assert_array_equal(dispatch(1), one)
    assert (dispatch(2) != one).any()
    assert (dispatch(2 ** 31 - 1) != one).any()
    assert ps._decode_rounds._cache_size() == programs


def test_b_the_dispatch_count_wraps_inside_int32(params):
    srv = PagedInferenceServer(params, CFG, GREEDY, **SRV_KW)
    srv._dispatches = 2 ** 31 - 1
    assert srv._next_dispatch() == 0
    assert srv._next_dispatch() == 1


# -- (c) the packed patch, out as it went in ---------------------------------

@pytest.mark.parametrize("rows,cols", [(64, 16), (64, 256), (8, 5), (1, 8)])
def test_c_patch_round_trip(rows, cols):
    r = np.random.default_rng(rows * 1000 + cols)
    big = np.iinfo(np.int32).max
    lengths = r.integers(0, big, rows, dtype=np.int32)
    last = r.integers(-1, big, rows, dtype=np.int32)
    live = r.integers(0, 2, rows).astype(bool)
    tables = r.integers(0, big, (rows, cols), dtype=np.int32)
    count = int(r.integers(0, big))
    buf = ps._pack_patch(count, lengths, last, live, tables)
    assert buf.dtype == np.int32 and buf.flags.c_contiguous
    assert buf.shape == (rows, ps._PATCH_HEAD + cols)
    rng = jax.random.key(11)
    g_lengths, g_tables, g_last, g_live, key = jax.jit(ps._unpack_patch)(
        jnp.asarray(buf), rng)
    for got, want in ((g_lengths, lengths), (g_tables, tables),
                      (g_last, last), (g_live, live)):
        assert got.dtype == want.dtype
        np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(
        jax.random.key_data(key),
        jax.random.key_data(jax.random.fold_in(rng, count)))


@pytest.mark.parametrize("compacted", [False, True],
                         ids=["rows_are_slots", "compacted"])
def test_c_a_row_takes_its_last_token_from_the_patch_or_the_device(
        compacted):
    """The live column's third value: that row's last token is the one
    the device kept for its slot, whatever the patch says; the other
    live rows and the dead ones read the patch, and all but the dead
    are live."""
    slots = 8
    kept = jnp.arange(100, 100 + slots, dtype=jnp.int32)
    slot_ids = (jnp.asarray([6, 2, 5, slots], jnp.int32) if compacted
                else None)
    rows = 4 if compacted else slots
    flags = np.resize([ps._ROW_LAST_ON_DEVICE, ps._ROW_LIVE,
                       ps._ROW_DEAD, ps._ROW_LAST_ON_DEVICE], rows)
    last = np.arange(rows, dtype=np.int32) + 40
    buf = ps._pack_patch(3, np.zeros(rows, np.int32), last, flags,
                         np.zeros((rows, 2), np.int32))
    _, _, g_last, g_live, _ = jax.jit(ps._unpack_patch)(
        jnp.asarray(buf), jax.random.key(0), kept, slot_ids)
    home = np.asarray(kept)[np.clip(
        np.arange(rows) if slot_ids is None else np.asarray(slot_ids),
        0, slots - 1)]
    np.testing.assert_array_equal(
        g_last, np.where(flags == ps._ROW_LAST_ON_DEVICE, home, last))
    np.testing.assert_array_equal(g_live, flags != ps._ROW_DEAD)


def test_c_a_patch_is_a_buffer_of_its_own():
    """The transfer may read the host memory after the call returns:
    the ledger goes on changing, the packed buffer does not."""
    lengths = np.arange(4, dtype=np.int32)
    tables = np.zeros((4, 3), np.int32)
    buf = ps._pack_patch(1, lengths, lengths, lengths > 1, tables)
    again = ps._pack_patch(2, lengths, lengths, lengths > 1, tables)
    assert not np.shares_memory(buf, again)
    for a in (lengths, tables):
        assert not np.shares_memory(buf, a)

# -- (f) what a plan stages: two packed arrays, one for decode rounds alone --

def _bits(x):
    x = np.asarray(x)
    return x.view(np.int32) if x.dtype.itemsize == 4 else x


def _same_bits(got, want):
    got, want = np.asarray(got), np.asarray(want)
    assert got.dtype == want.dtype and got.shape == want.shape
    np.testing.assert_array_equal(_bits(got), _bits(want))


def _sampler_rows(n, r):
    """Rows a cast would not survive: a seed with its top bit set, a
    negative bias value, `top_k` 0, floats with low mantissa bits."""
    def f32(lo, hi):
        return np.nextafter(r.uniform(lo, hi, n).astype(np.float32),
                            np.float32(hi))
    bias_ids = r.integers(0, 64, (n, MAX_LOGIT_BIAS), dtype=np.int32)
    bias_ids[:, 3:] = ps.sampling._BIAS_PAD
    rows = SamplingRows(
        temperature=f32(0, 2), top_k=r.integers(0, 3, n, dtype=np.int32),
        top_p=f32(0, 1), min_p=f32(0, 1), rep=f32(1, 2), pres=f32(-2, 2),
        freq=f32(-2, 2),
        seed=r.integers(2 ** 31, 2 ** 32, n, dtype=np.uint32),
        bias_ids=bias_ids,
        bias_vals=-r.uniform(0.1, 9, (n, MAX_LOGIT_BIAS)).astype(np.float32),
        min_new=r.integers(0, 9, n, dtype=np.int32),
        plen=r.integers(0, 2 ** 20, n, dtype=np.int32))
    rows.top_k[0] = 0
    return rows


def test_f_the_sampler_columns_are_make_rows_own():
    made = make_rows(
        [SamplingParams(seed=2 ** 32 - 1, logit_bias=((3, -2.5),))],
        SAMPLED, [0], [9])
    assert [leaf.dtype for leaf in made] == list(ps._SAMP_DTYPES)
    assert tuple(leaf[0].size for leaf in made) == ps._SAMP_WIDTHS
    buf = np.empty((1, ps._SAMP_COLS), np.int32)
    assert ps._pack_samp(buf, 0, made) == ps._SAMP_COLS
    got, col = jax.jit(ps._unpack_samp, static_argnums=1)(
        jnp.asarray(buf), 0)
    assert col == ps._SAMP_COLS
    for g, w in zip(got, made):
        _same_bits(g, w)


@pytest.mark.parametrize("rows,compacted", [
    (1, True), (4, False), (8, True), (64, False), (64, True)])
def test_f_rows_round_trip(rows, compacted):
    r = np.random.default_rng(rows * 2 + compacted)
    big = np.iinfo(np.int32).max
    stop, gid, aid, limit = (r.integers(0, big, rows, dtype=np.int32)
                             for _ in range(4))
    slot_ids = (r.permutation(rows).astype(np.int32) if compacted
                else None)
    samp = _sampler_rows(rows, r)
    buf = ps._pack_rows(stop, gid, aid, limit, samp, slot_ids)
    assert buf.dtype == np.int32 and buf.flags.c_contiguous
    assert buf.shape == (rows, ps._ROWS_HEAD + ps._SAMP_COLS + compacted)
    (g_stop, g_gid, g_aid, g_limit, g_samp,
     g_slots) = jax.jit(ps._unpack_rows)(jnp.asarray(buf))
    for got, want in ((g_stop, stop), (g_gid, gid), (g_aid, aid),
                      (g_limit, limit), *zip(g_samp, samp)):
        _same_bits(got, want)
    if compacted:
        _same_bits(g_slots, slot_ids)
    else:
        assert g_slots is None
    # a fresh buffer a plan: the transfer may read it after the call
    assert not np.shares_memory(
        buf, ps._pack_rows(stop, gid, aid, limit, samp, slot_ids))
    assert not any(np.shares_memory(buf, a) for a in (stop, *samp))


def _group_arrays(gp, w, pb, table_cols, r):
    big = np.iinfo(np.int32).max
    head = {name: r.integers(0, big, gp, dtype=np.int32)
            for name in ps._GROUP_FIELDS}
    head["count_mask"] = r.integers(0, 2, gp).astype(bool)
    head["scatter_mask"] = r.integers(0, 2, gp).astype(bool)
    return (r.integers(0, big, (gp, w), dtype=np.int32),
            r.integers(0, big, (gp, table_cols), dtype=np.int32),
            r.integers(0, big, (gp, pb), dtype=np.int32),
            _sampler_rows(gp, r), head)


# the last two are one packed shape: the static tells them apart
GROUP_SHAPES = [(1, 16, 16, 8), (2, 16, 32, 8), (8, 256, 2048, 16),
                (4, 64, 512, 37), (4, 512, 1024, 128), (4, 1024, 512, 128)]


@pytest.mark.parametrize("gp,w,pb,table_cols", GROUP_SHAPES)
def test_f_group_round_trip(gp, w, pb, table_cols):
    r = np.random.default_rng(gp * 7 + w + pb)
    chunk, tables, prompts, samp, head = _group_arrays(gp, w, pb,
                                                       table_cols, r)
    buf = ps._pack_group(chunk, tables, prompts, samp, **head)
    assert buf.dtype == np.int32 and buf.flags.c_contiguous
    assert buf.shape == (gp, ps._GROUP_HEAD + ps._SAMP_COLS + table_cols
                         + w + pb)
    got = jax.jit(ps._unpack_group, static_argnums=(1, 2))(
        jnp.asarray(buf), w, table_cols)
    assert set(got) == set(ps._GROUP_FIELDS) | {
        "samp_rows", "g_tables", "chunk", "prompt_rows"}
    for name in ps._GROUP_FIELDS:
        _same_bits(got[name], head[name])
    for g, want in zip(got["samp_rows"], samp):
        _same_bits(g, want)
    _same_bits(got["g_tables"], tables)
    _same_bits(got["chunk"], chunk)
    _same_bits(got["prompt_rows"], prompts)
    assert not any(np.shares_memory(buf, a)
                   for a in (chunk, tables, prompts, *samp))
    with pytest.raises(AssertionError):
        ps._pack_group(chunk, tables, prompts, samp,
                       **{k: v for k, v in head.items() if k != "aid"})


@pytest.mark.parametrize("prefill_chunk,prompt_buckets,max_context,pages", [
    (16, [16, 32], 64, 8),            # this file's servers
    (256, [512, 1024], 2048, 16),     # five chunk widths, three admissions
    (1024, [512, 1024, 4096], 10240, 80),  # a chunk as wide as a prompt
], ids=["tiny", "batch", "long"])
def test_f_no_two_layouts_share_a_shape_and_statics(
        prefill_chunk, prompt_buckets, max_context, pages):
    """What a compiled program is keyed by (the operand's shape and the
    static `chunk_w`) names one layout, over every group a server's
    bucket sets can build; the rows' buffer needs no static."""
    widths = ps._pow2_buckets(16, prefill_chunk)
    admits = sorted(set(prompt_buckets) | {max_context})
    r = np.random.default_rng(0)
    keyed = {}
    for gp, w, pb in itertools.product((1, 2, 4, 8), widths, admits):
        chunk, tables, prompts, samp, head = _group_arrays(gp, w, pb,
                                                           pages, r)
        buf = ps._pack_group(chunk, tables, prompts, samp, **head)
        assert keyed.setdefault((buf.shape, w), (gp, w, pb)) == (gp, w, pb)
    assert len(keyed) == 4 * len(widths) * len(admits)
    shared = len(keyed) - len({shape for shape, _ in keyed})
    assert (shared > 0) == (prefill_chunk == 1024)
    zeros = np.zeros((8,), np.int32)
    samp = _sampler_rows(8, r)
    assert ps._pack_rows(zeros, zeros, zeros, zeros, samp).shape \
        != ps._pack_rows(zeros, zeros, zeros, zeros, samp, zeros).shape


@pytest.mark.parametrize("kw", [
    dict(), dict(spec_drafts=2), dict(spec_drafts=2, spec_control=False),
], ids=["plain", "adaptive_drafts", "fixed_drafts"])
def test_f_a_plan_hands_the_device_two_arrays_or_one(params, monkeypatch,
                                                     kw):
    """Counted at `_to_device` itself while `_plan_iteration` runs: a
    mixed plan stages the group's buffer and the rows', decode rounds
    alone the rows', each a 2-D int32 array; the record of the step that
    planned says the same."""
    srv = PagedInferenceServer(
        params, CFG, GREEDY, flight_recorder_size=512, decode_chunk=1, **{**SRV_KW, **kw})
    handed, plans, staged = [], [], []
    put, plan_iteration = srv._to_device, srv._plan_iteration

    def counted(host_array):
        assert isinstance(host_array, np.ndarray)
        handed.append(host_array)
        return put(host_array)

    def watched_plan():
        del handed[:]
        plan = plan_iteration()
        if handed:  # also a plan staged and then dropped: its rows end
            staged.append(len(handed))
        if plan is not None:
            assert all(a.dtype == np.int32 and a.ndim == 2 for a in handed)
            plans.append((plan.kind, plan.sl_d is None, len(handed),
                          srv._iter_plan_h2d))
        return plan

    monkeypatch.setattr(srv, "_to_device", counted)
    monkeypatch.setattr(srv, "_plan_iteration", watched_plan)
    first = [srv.submit(p, max_new_tokens=20) for p in (REP, PROMPTS[1])]
    for _ in range(4):
        srv.step()
    rest = [srv.submit(p, max_new_tokens=n)
            for p, n in ((LONG, 24), (PROMPTS[3], 12))]
    srv.run_until_idle()
    assert all(r.done for r in first + rest)
    assert {(k, s) for k, s, *_ in plans} >= {
        ("mixed", False), ("decode", False), ("decode", True)}
    for kind, _, n, counted_by_server in plans:
        assert n == counted_by_server == (2 if kind == "mixed" else 1)
        assert n <= (4 if kind == "mixed" else 1)
    recorded = [rec["plan_h2d"] for rec in srv.flight_window()]
    assert {1, 2} <= set(recorded) <= {0, 1, 2}
    assert [n for n in recorded if n] == staged


def test_f_a_plan_without_a_decode_round_has_no_draft_limits(params):
    """A planned step whose decode half was dropped (no page for another
    round) while the controller holds draft lengths for the planned-live
    slots: its rows' buffer carries no row, so no limit either. Before
    PR 47 the padded limits of such a plan did not fit their one row and
    the scheduler thread died on a ValueError."""
    prompts = PROMPTS + [REP, [9, 9, 8]]

    def run(**kw):
        srv = _server(params, CFG, GREEDY, spec_drafts=2,
                      flight_recorder_size=512, **kw)
        reqs = [srv.submit(p, max_new_tokens=10) for p in prompts[:2]]
        for _ in range(3):
            srv.step()
        reqs += [srv.submit(p, max_new_tokens=10) for p in prompts[2:]]
        srv.run_until_idle()
        return srv, [r.result() for r in reqs]

    srv, served = run()
    assert any(rec.get("decode_rounds") == 0 and rec.get("prefill_tokens")
               for rec in srv.flight_window())
    for p, o in zip(prompts, served):
        ref = engine.generate(
            params, np.asarray([p], np.int32), jax.random.key(1), cfg=CFG,
            infer_cfg=dataclasses.replace(GREEDY, max_decode_len=10))
        assert o == list(np.asarray(ref)[0]), p


# -- (d) both orders of a step, greedy and seeded ----------------------------

SEEDED = [SamplingParams(seed=100 + i, temperature=0.9, top_p=0.9)
          for i in range(len(PROMPTS))]


def _both_orders(run):
    ahead = run()
    assert run(waiting=True, decode_chunk=1) == ahead
    return ahead


@pytest.mark.parametrize("sampling", [None, SEEDED],
                         ids=["greedy", "seeded"])
def test_d_orders_agree_token_for_token(params, sampling):
    def run(**kw):
        srv = _server(params, CFG, GREEDY, seed=len(kw), **kw)
        sp = sampling or [None] * len(PROMPTS)
        reqs = [srv.submit(p, max_new_tokens=8, sampling=s)
                for p, s in zip(PROMPTS[:2], sp[:2])]
        for _ in range(3):
            srv.step()
        reqs += [srv.submit(p, max_new_tokens=8, sampling=s)
                 for p, s in zip(PROMPTS[2:], sp[2:])]
        srv.run_until_idle()
        return [r.result() for r in reqs]

    _both_orders(run)


# every field of the packed buffers in play at once: a sampler row with
# its seed's top bit set, a filter chain, penalties and a bias of either
# sign; a row under a grammar; a row under an adapter; a seeded row.
# Drafts at their fixed length: every row's draft limit is the
# dispatch's width, and a seeded stream is the same in either order
BYTES = ByteTokenizer()
CFG_BYTES = dataclasses.replace(CFG, vocab_size=300)
EOS_BYTES = dataclasses.replace(GREEDY, eos_token_id=BYTES.eos_id)
LOADED = [
    SamplingParams(seed=2 ** 31 + 12345, temperature=0.8, top_k=20,
                   top_p=0.95, min_p=0.01, repetition_penalty=1.1,
                   presence_penalty=0.3, frequency_penalty=0.2,
                   logit_bias=((7, -1.5), (9, 2.25)), min_tokens=3),
    SamplingParams(regex=r"[0-9]{4,6}"),
    None,
    SamplingParams(seed=5, temperature=0.7),
]


@pytest.mark.parametrize("spec_drafts", [0, 2], ids=["plain", "drafts"])
def test_d_orders_agree_with_every_packed_field_in_play(spec_drafts):
    params = transformer.init_params(CFG_BYTES, jax.random.key(0))
    lcfg = LoRAConfig(rank=4, alpha=8.0, targets=("wq", "wv"))
    lora = init_lora_params(CFG_BYTES, lcfg, jax.random.key(1))
    for i, name in enumerate(sorted(lora["layers"])):
        shape = lora["layers"][name]["b"].shape
        lora["layers"][name]["b"] = 0.3 * jax.random.normal(
            jax.random.key(50 + i), shape)
    adapters = [None, None, "tuned", None]

    def run(**kw):
        srv = _server(
            params, CFG_BYTES, EOS_BYTES, seed=len(kw), tokenizer=BYTES,
            spec_drafts=spec_drafts, spec_control=False, **kw)
        srv.add_adapter("tuned", lora, lcfg)
        rows = list(zip(PROMPTS, LOADED, adapters))
        reqs = [srv.submit(p, max_new_tokens=8, sampling=s, adapter=a)
                for p, s, a in rows[:2]]
        for _ in range(3):
            srv.step()
        reqs += [srv.submit(p, max_new_tokens=8, sampling=s, adapter=a)
                 for p, s, a in rows[2:]]
        srv.run_until_idle()
        return [r.result() for r in reqs]

    outs = _both_orders(run)
    digits = BYTES.decode(outs[1])
    assert digits.isdigit() and 4 <= len(digits) <= 6
    # the adapter's row is not the base model's
    base = PagedInferenceServer(params, CFG_BYTES, EOS_BYTES, tokenizer=BYTES,
                                **SRV_KW)
    assert base.generate([PROMPTS[2]], max_new_tokens=8)[0] != outs[2]


# -- (e) the patch of a launch made ahead of the commit ----------------------

def test_e_a_launch_ahead_patches_from_the_planned_frame(params,
                                                         monkeypatch):
    """A launch that goes ahead of the commit hands over lengths the
    ledger does not hold yet and no last token for the rows the
    dispatch in flight advances; the commit that follows brings the
    ledger to exactly those lengths, and once the ledger has caught up
    its last tokens are the ones the device kept. A step still takes
    the time its phases
    say: `between_ms + duration_ms` over the window of steps that
    launched ahead is the window's length within 0.1%."""
    srv = PagedInferenceServer(
        params, CFG, GREEDY, flight_recorder_size=512, decode_chunk=1, **SRV_KW)
    patches = []
    pack = ps._pack_patch
    monkeypatch.setattr(ps, "_pack_patch", lambda *a: patches.append(
        pack(*a)) or patches[-1])
    launch = srv._launch_plan
    checked = {"ahead": 0, "waited": 0}

    def watched_launch(plan):
        n0 = len(patches)
        before = (srv.lengths.copy(), srv.last_token.copy())
        launch(plan)
        if len(patches) == n0:
            return
        patch = patches[-1]
        rows = (np.arange(srv.max_slots) if plan.sl_d is None
                else plan.live_ids)
        head = patch[:len(rows)]
        if plan.waits is not None:
            # the ledger's own rows, every live one with its token
            checked["waited"] += 1
            live = head[:, 2] != ps._ROW_DEAD
            assert set(head[:, 2].tolist()) <= {ps._ROW_DEAD, ps._ROW_LIVE}
            np.testing.assert_array_equal(head[live, 0],
                                          srv.lengths[rows][live])
            np.testing.assert_array_equal(head[live, 1],
                                          srv.last_token[rows][live])
            return
        checked["ahead"] += 1
        infl = srv._inflight           # still uncommitted behind it
        assert srv._ahead is not None and infl is not srv._ahead
        made = np.isin(rows, np.concatenate(
            [infl.live_ids if infl.n_rounds else [], infl.activating]))
        live = head[:, 2] != ps._ROW_DEAD
        np.testing.assert_array_equal(
            head[live, 2], np.where(made[live], ps._ROW_LAST_ON_DEVICE,
                                    ps._ROW_LIVE))
        # nothing was written into the ledger by the launch
        np.testing.assert_array_equal(before[0], srv.lengths)
        np.testing.assert_array_equal(before[1], srv.last_token)
        pending.append((rows[live], head[live, 0].copy(), made[live]))

    pending = []
    monkeypatch.setattr(srv, "_launch_plan", watched_launch)
    first = [srv.submit(p, max_new_tokens=30) for p in (REP, PROMPTS[1])]
    for _ in range(4):
        srv.step()
    rest = [srv.submit(p, max_new_tokens=n)
            for p, n in ((LONG, 24), (PROMPTS[3], 12))]
    while any(not r.done for r in first + rest):
        del pending[:]
        srv.step()
        if pending:
            # the commit that the launch went ahead of has run
            rows, lens, made = pending.pop()
            alive = srv.active[rows]
            np.testing.assert_array_equal(srv.lengths[rows][alive],
                                          lens[alive])
            if checked["ahead"] == 12 and srv._inflight is not None:
                # with the dispatch launched ahead brought home too, the
                # ledger has caught up with the device: its last tokens
                # are the ones the newest program kept
                srv._commit_inflight()
                srv._deliver()
                on = srv.active.copy()
                assert on.sum() >= 2
                np.testing.assert_array_equal(
                    np.asarray(srv.state["last"])[on], srv.last_token[on])
                checked["kept"] = True
    assert checked["ahead"] >= 20 and checked["waited"] >= 2
    assert checked.get("kept")
    window = srv.flight_window()
    ahead = [i for i, r in enumerate(window)
             if r.get("launch_ahead") and i and "between_ms" in r]
    runs, run = [], []
    for i in ahead:      # the longest unbroken run of such steps
        run = run + [i] if run and run[-1] == i - 1 else [i]
        runs.append(run)
    run = max(runs, key=len)
    assert len(run) >= 10
    recs = [window[i] for i in run]
    span = (recs[-1]["t_start"] + recs[-1]["duration_ms"] * 1e-3
            - recs[0]["t_start"] + recs[0]["between_ms"] * 1e-3) * 1e3
    total = sum(r["between_ms"] + r["duration_ms"] for r in recs)
    assert total == pytest.approx(span, rel=1e-3)
    # a record is its dispatch's; the step that wrote it launched the
    # next record's: all but the run's last step launched ahead
    for r in recs[:-1]:
        ph = r["phases_ms"]
        assert {"launch", "device", "commit", "deliver"} <= set(ph)
        assert sum(ph.values()) == pytest.approx(r["duration_ms"],
                                                 rel=1e-9, abs=1e-6)
