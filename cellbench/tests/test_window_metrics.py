"""The readers of a model with window layers on hand-made op events and
flight records, read through the harness's own loader: the shares of
device busy time under `/attn/window/`, `/attn/full/` and `moe_experts`,
the window pool's peak, and the window kernel's share of the memory
roofline, whose byte count lives in the reader's own file. Nothing to
read on a program without the scopes or counters (the parent commit's),
or on an untraced run."""
import os

import pytest

from cellbench import hostplane, run

MS = 1e6
CELL = "smallthinker-21b-a3b.longshort-closed"
NAMES = ("window_attn_time_share", "full_attn_time_share",
         "window_attn_roofline_share", "window_pages_peak_share",
         "moe_expert_time_share")
STEP = "jit(_mixed_step)/"


def ops(kinds: bool):
    win = "attn/window/" if kinds else "attn/"
    full = "attn/full/" if kinds else "attn/"
    return [
        ("%paged_attention_wide.1 = bf16[64,4,7,128] custom-call(",
         0 * MS, 4 * MS, STEP + "decode_rounds/" + win + "pallas_call:"),
        ("%fusion.2 = bf16[6,2368,4,128,128] fusion(", 4 * MS, 1 * MS,
         STEP + "decode_rounds/" + win + "scatter:"),
        ("%paged_attention_wide.3 = bf16[8,4,1792,128] custom-call(",
         5 * MS, 3 * MS, STEP + "prefill_group/" + win + "pallas_call:"),
        ("%paged_attention_wide.4 = bf16[64,4,7,128] custom-call(",
         8 * MS, 2 * MS, STEP + "decode_rounds/" + full + "pallas_call:"),
        ("%gmm.5 = bf16[12800,768] custom-call(", 12 * MS, 6 * MS,
         STEP + "joined_walk/moe_experts/grouped/jit(gmm)/pallas_call:"),
        ("%fusion.6 = bf16[2112,2560] fusion(", 18 * MS, 2 * MS,
         STEP + "joined_walk/attn/dot_general:")]


def ctx_of(events, records=(), traced=True):
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    bench = run.load_benchmark()
    return {
        "_hostplane": {"sched": {}, "devices": {"/device:TPU:0": {
            hostplane.OPS_LINE: events}}},
        "stats": {"flight_recorder": [dict(r, ts=10.0 + i)
                                      for i, r in enumerate(records)]},
        "wall_minus_mono": 0.0, "window_abs": (0.0, 100.0),
        "trace_span": (0.0, 100.0) if traced else None,
        "config": run.load_cell(bench, CELL)[2],
        "device": {"kind": "TPU v5e"},
        "peaks_file": os.path.join(here, "peaks.json")}


RECORDS = [
    {"keys_window_decode": 100_000, "n_live": 60, "decode_rounds": 1,
     "window_pool_active": 1200, "window_num_pages": 2368},
    {"keys_window_decode": 105_000, "n_live": 62, "decode_rounds": 1,
     "window_pool_active": 1480, "window_num_pages": 2368},
    {"prefill_tokens": 512, "window_pool_active": 1300,
     "window_num_pages": 2368}]


@pytest.fixture(scope="module")
def entries():
    bench = run.load_benchmark()
    got = [m for m in run.metric_entries(bench, CELL, "per_layer")
           if m["name"] in NAMES]
    assert sorted(m["name"] for m in got) == sorted(NAMES)
    assert all(m["moves"] == "out_tok_s" and m["workloads"] == [CELL]
               and m["unit"] == "%" for m in got)
    # none of them is asked of the cell that was there
    old = bench["workloads"][0]["name"]
    assert not {m["name"] for m in run.metric_entries(
        bench, old, "per_layer")} & set(NAMES)
    return got


def test_the_five_readers_on_a_program_with_both_kinds(entries):
    got = {k: v["value"] for k, v in run.read_metrics(
        entries, ctx_of(ops(kinds=True), RECORDS)).items()}
    busy = 10 + 8  # ms: 0-10 and 12-20
    assert got["window_attn_time_share"] == pytest.approx(100 * 8 / busy)
    assert got["full_attn_time_share"] == pytest.approx(100 * 2 / busy)
    assert got["moe_expert_time_share"] == pytest.approx(100 * 6 / busy)
    assert got["window_pages_peak_share"] == pytest.approx(
        100 * 1480 / 2368)
    # 6 window layers of the 8; a key and its value are 2 KiB (4 key
    # heads of 128, bfloat16); a row's query and output 2 x 28 x 128 x 2;
    # over the 4 ms of the decode rows' window kernel at 819 GB/s. The
    # cache write and the chunk's kernel are not the decode call's time.
    work = 6 * ((100_000 + 105_000) * 2048 + (60 + 62) * 14336)
    assert got["window_attn_roofline_share"] == pytest.approx(
        100 * work / 4e-3 / 819e9)
    assert 0 < got["window_attn_roofline_share"] < 100


@pytest.mark.parametrize("ctx", [
    ctx_of(ops(kinds=False), [{"pool_active": 3, "n_live": 60}]),
    ctx_of([], RECORDS[2:3]),
    {"_hostplane": None, "stats": {}, "wall_minus_mono": 0.0,
     "window_abs": (0.0, 1.0), "trace_span": None}],
    ids=["the_parents_program", "no_ops", "untraced"])
def test_nothing_to_read_leaves_the_metric_out(entries, ctx):
    got = run.read_metrics(entries, ctx)
    # `moe_experts` is a scope the parent's program has too
    got.pop("moe_expert_time_share", None)
    assert set(got) <= {"window_pages_peak_share"}
    assert ("window_pages_peak_share" in got) == any(
        r.get("window_num_pages")
        for r in ctx["stats"].get("flight_recorder", []))
