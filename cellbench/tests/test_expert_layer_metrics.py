"""The readers of a model with leading dense layers, a shared expert and a
router balanced by a bias, on hand-made op events and flight records, read
through the harness's own loader: the shares of device busy time under the
expert layer's scopes, `moe_shared` and `lead_dense`, the routed experts'
share of what the chip's peaks allow, whose counts live in the reader's own
file, and the fullest expert over the even share. Nothing to read on a
program without the scopes or counters (the parent commit's), or on an
untraced run."""
import os

import pytest

from cellbench import hostplane, run

MS = 1e6
CELL = "trinity-mini.longshort-closed"
NAMES = ("expert_layer_time_share", "moe_shared_time_share",
         "lead_dense_time_share", "sorted_experts_roofline_share",
         "expert_load_peak_ratio")
STEP = "jit(_mixed_step)/"


def ops(new: bool):
    shared = "joined_walk/moe_shared/" if new else "joined_walk/mlp/"
    lead = "joined_walk/lead_dense/" if new else "joined_walk/mlp/"
    return [
        ("%fusion.1 = f32[2112,128] fusion(", 0 * MS, 1 * MS,
         STEP + "joined_walk/moe_route/dot_general:"),
        ("%gated_grouped_matmul.2 = bf16[36864,1024] custom-call(", 1 * MS,
         12 * MS, STEP + "joined_walk/moe_experts/grouped/"
         "jit(_grouped_experts)/pallas_call:"),
        ("%gmm.3 = bf16[36864,2048] custom-call(", 13 * MS, 8 * MS,
         STEP + "joined_walk/moe_experts/grouped/jit(gmm)/pallas_call:"),
        ("%fusion.4 = bf16[2112,2048] fusion(", 21 * MS, 1 * MS,
         STEP + "joined_walk/moe_combine/gather:"),
        ("%fusion.5 = bf16[2112,1024] fusion(", 22 * MS, 2 * MS,
         STEP + shared + "dot_general:"),
        ("%fusion.6 = bf16[2112,6144] fusion(", 24 * MS, 3 * MS,
         STEP + lead + "dot_general:"),
        # a decode-only program's experts: dense, and no joined step's
        ("%fusion.7 = bf16[128,64,1024] fusion(", 30 * MS, 5 * MS,
         "jit(_decode_rounds)/decode_rounds/moe_experts/dot_general:"),
        ("%paged_attention_wide.8 = bf16[64,4,8,128] custom-call(",
         35 * MS, 3 * MS, STEP + "decode_rounds/attn/window/pallas_call:")]


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
    # a joined step of 2,112 tokens: 16,896 assignments over 4 layers each
    {"joined": True, "assign_total": 4 * 16896, "assign_peak": 250},
    # a joined step of 80 tokens: the weights' stream is its floor
    {"joined": True, "assign_total": 4 * 640, "assign_peak": 14},
    # a decode-only program's 64 rows: no joined step
    {"joined": False, "assign_total": 4 * 512, "assign_peak": 12}]


@pytest.fixture(scope="module")
def entries():
    bench = run.load_benchmark()
    got = [m for m in run.metric_entries(bench, CELL, "per_layer")
           if m["name"] in NAMES]
    assert sorted(m["name"] for m in got) == sorted(NAMES)
    assert all(m["moves"] == "out_tok_s" and m["workloads"] == [CELL]
               for m in got)
    # none of them is asked of a cell that was there
    for old in bench["workloads"][:4]:
        assert not {m["name"] for m in run.metric_entries(
            bench, old["name"], "per_layer")} & set(NAMES)
    return got


def test_the_five_readers_on_a_program_with_the_scopes(entries):
    got = {k: v["value"] for k, v in run.read_metrics(
        entries, ctx_of(ops(new=True), RECORDS)).items()}
    busy = 27 + 8  # ms: 0-27 and 30-38
    assert got["expert_layer_time_share"] == pytest.approx(
        100 * (24 + 5) / busy)
    assert got["moe_shared_time_share"] == pytest.approx(100 * 2 / busy)
    assert got["lead_dense_time_share"] == pytest.approx(100 * 3 / busy)
    # the fullest expert over the even share, all records of the window
    assert got["expert_load_peak_ratio"] == pytest.approx(
        (250 + 14 + 12) * 128 * 4 / (4 * (16896 + 640 + 512)))
    # a layer's 128 experts of 3 x 2,048 x 1,024 in bfloat16 at 819 GB/s,
    # 1.97 ms, against 16,896 rows x 3 x 2 x 2,048 x 1,024 at 197
    # TFLOP/s, 1.08 ms: the stream is the floor of both joined steps, 4
    # layers each, over the 20 ms of the joined steps' expert kernels
    stream = 128 * 3 * 2048 * 1024 * 2 / 819e9
    assert 16896 * 6 * 2048 * 1024 / 197e12 < stream
    assert got["sorted_experts_roofline_share"] == pytest.approx(
        100 * 2 * 4 * stream / 20e-3)
    assert 0 < got["sorted_experts_roofline_share"] < 100


def test_the_flops_are_the_floor_where_they_outlast_the_stream(entries):
    reader = run.load_reader("sorted_experts_roofline_share")
    cfg = ctx_of([])["config"]
    peak = {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12}
    rows = 4 * 40000  # 40,000 assignments a layer: 2.55 ms of products
    got = reader.floor_seconds(
        [{"joined": True, "assign_total": rows}], cfg, peak)
    assert got == pytest.approx(4 * 40000 * 6 * 2048 * 1024 / 197e12)
    assert reader.layer_weight_bytes(cfg) == pytest.approx(805.3e6 * 2,
                                                           rel=1e-3)


@pytest.mark.parametrize("ctx", [
    ctx_of(ops(new=False), [{"joined": True, "n_live": 60}]),
    ctx_of([], [{"joined": True}]),
    {"_hostplane": None, "stats": {}, "wall_minus_mono": 0.0,
     "window_abs": (0.0, 1.0), "trace_span": None}],
    ids=["the_parents_program", "no_ops", "untraced"])
def test_nothing_to_read_leaves_the_metric_out(entries, ctx):
    assert run.read_metrics(entries, ctx) == {}
