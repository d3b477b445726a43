"""`moe_grouped_expert_share` on hand-made op events: the share of device
busy time under `moe_experts/grouped`, read through the harness's own
loader; nothing to read on a program without the scope or an untraced
run."""
import pytest

from cellbench import hostplane, run

MS = 1e6
CELL = "mixtral-8x7b.batch-closed"


def ops(grouped: bool):
    chunk = "jit(_mixed_step)/prefill_group/moe_experts/"
    if grouped:
        chunk += "grouped/jit(_grouped_experts)/"
    return [
        ("%fusion.1 = bf16[8,64,14336]{2,1,0} fusion(", 0 * MS, 6 * MS,
         "jit(_mixed_step)/decode_rounds/moe_experts/ecd,edf->ecf/"
         "dot_general:"),
        ("%gmm.2 = bf16[1024,14336]{1,0} custom-call(", 6 * MS, 3 * MS,
         chunk + "jit(gmm)/pallas_call:"),
        ("%fusion.3 = bf16[1024,14336]{1,0} fusion(", 9 * MS, 1 * MS,
         chunk + "mul:"),
        # the sort and the gathers are the dispatch's, not the experts'
        ("%sort.4 = s32[1024]{0} sort(", 10 * MS, 2 * MS,
         "jit(_mixed_step)/prefill_group/moe_dispatch/sort:"),
        ("%paged_attention_wide.5 = bf16[64,8,4,128] custom-call(",
         16 * MS, 8 * MS, "jit(_mixed_step)/decode_rounds/attn/"
         "pallas_call:")]


def ctx_of(events):
    return {"_hostplane": {"sched": {}, "devices": {"/device:TPU:0": {
        hostplane.OPS_LINE: events}}}}


@pytest.fixture(scope="module")
def entries():
    bench = run.load_benchmark()
    got = [m for m in run.metric_entries(bench, CELL, "per_layer")
           if m["name"] == "moe_grouped_expert_share"]
    assert len(got) == 1 and got[0]["moves"] == "out_tok_s"
    assert got[0]["layer"] == "model" and got[0]["workloads"] == [CELL]
    return got


def test_share_of_busy_time_under_the_grouped_scope(entries):
    got = run.read_metrics(entries, ctx_of(ops(grouped=True)))
    busy = 12 + 8  # ms: 0-12, 16-24
    assert got["moe_grouped_expert_share"]["value"] == pytest.approx(
        100 * 4 / busy)
    assert got["moe_grouped_expert_share"]["unit"] == "%"
    # it is a part of what `moe_chunk_expert_share` reads, which keeps
    # reading the chunks' expert ops on either dispatch
    chunk = hostplane.scope_share(ctx_of(ops(grouped=True))["_hostplane"],
                                  "/prefill_group/", "/moe_experts/")
    assert chunk == pytest.approx(100 * 4 / busy)


@pytest.mark.parametrize("ctx", [
    ctx_of(ops(grouped=False)),       # the parent commit's program
    ctx_of([]), {"_hostplane": None}, {}],
    ids=["no_scope", "no_ops", "no_trace", "untraced"])
def test_nothing_to_read_leaves_the_metric_out(entries, ctx):
    assert run.read_metrics(entries, ctx) == {}
