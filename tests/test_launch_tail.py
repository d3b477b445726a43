"""One program and one transfer in the launch (CPU, tiny widths).

Between a read-back and the next program the overlapped scheduler hands
the device ONE host array (the packed patch, with the dispatch's count
in it) and dispatches ONE program, which makes its own key from the
server's one key and that count: no `jax.random.split` runs outside a
trace, nothing that is on the device already is converted again, and
the patch comes out of the program bit for bit as it went in.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cloud_server_tpu.config import InferConfig, ModelConfig
from cloud_server_tpu.inference import paged_server as ps
from cloud_server_tpu.inference.paged_server import PagedInferenceServer
from cloud_server_tpu.inference.sampling import SamplingParams
from cloud_server_tpu.models import transformer

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
        params, CFG, GREEDY, scheduler="mixed", overlap=True,
        flight_recorder_size=512, spec_drafts=spec_drafts, decode_chunk=1,
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
    # it; a step without one (sequential, or every planned row dead at
    # the commit) records 0
    counts = [rec["launch_h2d"] for rec in srv.flight_window()]
    assert set(counts) == {0, 1}
    assert sum(counts) == len(seen["launches"])
    # what the plan staged while the program before it ran, one transfer
    # an array: 14 of the prefill group and the 12 leaves of its
    # samplers (a mixed plan), `d_stop`, the decode rows' 12 sampler
    # leaves, `gid_d`, `aid_d`, the rows' slots where rows are compacted,
    # the draft limits where drafts run
    staged = {(kind, rows_are_slots): n for (kind, rows_are_slots, *_), n
              in zip(seen["launches"], seen["staged"])}
    drafts = 1 if spec_drafts else 0
    assert staged == {("mixed", False): 42 + drafts,
                      ("decode", False): 16 + drafts,
                      ("decode", True): 15 + drafts}
    # on the record of the step that planned, beside that step's launch;
    # a plan whose rows all died at the commit was staged and not launched
    plans = [rec["plan_h2d"] for rec in srv.flight_window()]
    assert set(plans) <= set(staged.values()) | {0}
    assert [n for n, c in zip(plans, counts) if c] == seen["staged"]


# -- (b) the key is made inside the program ----------------------------------

def _sampled_streams(params, seed, **kw):
    srv = PagedInferenceServer(params, CFG, SAMPLED, seed=seed,
                               **{**SRV_KW, **kw})
    reqs = [srv.submit(p, max_new_tokens=10) for p in PROMPTS]
    srv.run_until_idle()
    return [r.result() for r in reqs]


@pytest.mark.parametrize("kw", [
    dict(scheduler="mixed", overlap=True),
    dict(scheduler="mixed", overlap=False),
    dict(scheduler="alternating"),
], ids=["overlapped", "no_overlap", "alternating"])
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

    def dispatch(count):
        out = ps._decode_rounds(
            params, jax.tree.map(jnp.copy, state),
            jnp.asarray(ps._pack_patch(count, *ledger)), rng, None,
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


# -- (d) the three scheduler paths, greedy and seeded ------------------------

SEEDED = [SamplingParams(seed=100 + i, temperature=0.9, top_p=0.9)
          for i in range(len(PROMPTS))]


@pytest.mark.parametrize("sampling", [None, SEEDED],
                         ids=["greedy", "seeded"])
def test_d_paths_agree_token_for_token(params, sampling):
    def run(**kw):
        srv = PagedInferenceServer(params, CFG, GREEDY, seed=len(kw),
                                   **{**SRV_KW, **kw})
        sp = sampling or [None] * len(PROMPTS)
        reqs = [srv.submit(p, max_new_tokens=8, sampling=s)
                for p, s in zip(PROMPTS[:2], sp[:2])]
        for _ in range(3):
            srv.step()
        reqs += [srv.submit(p, max_new_tokens=8, sampling=s)
                 for p, s in zip(PROMPTS[2:], sp[2:])]
        srv.run_until_idle()
        return [r.result() for r in reqs]

    overlapped = run(scheduler="mixed", overlap=True)
    assert run(scheduler="mixed", overlap=False, decode_chunk=1) \
        == overlapped
    assert run(scheduler="alternating") == overlapped


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
        params, CFG, GREEDY, scheduler="mixed", overlap=True,
        flight_recorder_size=512, decode_chunk=1, **SRV_KW)
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
