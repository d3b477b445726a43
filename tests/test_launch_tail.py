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
             "gstate": jnp.zeros((rows,), jnp.int32)}
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
