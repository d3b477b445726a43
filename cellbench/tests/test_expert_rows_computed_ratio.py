"""`expert_rows_computed_ratio` on hand-made flight records, read through
the harness's own loader: the rows the experts' way in computes over the
assignments, across the window's joined steps. Nothing to read on a
program that keeps no such count (the parent commit's records have
`assign_total` and `assign_peak` alone)."""
import pytest

from cellbench import run

CELL = "trinity-mini.longshort-closed"
NAME = "expert_rows_computed_ratio"


def ctx_of(records):
    return {"stats": {"flight_recorder": [dict(r, ts=10.0 + i)
                                          for i, r in enumerate(records)]},
            "wall_minus_mono": 0.0, "window_abs": (0.0, 100.0),
            "trace_span": None}


@pytest.fixture(scope="module")
def entries():
    bench = run.load_benchmark()
    got = [m for m in run.metric_entries(bench, CELL, "per_layer")
           if m["name"] == NAME]
    assert got == [{"name": NAME, "unit": "x", "better": "lower",
                    "source": "program_counter", "layer": "kernels",
                    "moves": "out_tok_s", "workloads": [CELL]}]
    # the last entry of the file, and asked of no other cell
    assert bench["per_layer"][-1] == got[0]
    for cell in bench["workloads"]:
        if cell["name"] != CELL:
            assert NAME not in {m["name"] for m in run.metric_entries(
                bench, cell["name"], "per_layer")}
    return got


def test_the_ratio_is_over_the_windows_joined_steps(entries):
    records = [
        {"joined": True, "assign_total": 67584, "assign_peak": 700,
         "assign_rows_computed": 98304},
        {"joined": True, "assign_total": 2560, "assign_peak": 14,
         "assign_rows_computed": 40960},
        # a decode-only program's rows take the dense dispatch
        {"joined": False, "assign_total": 2048, "assign_peak": 12,
         "assign_rows_computed": 50000},
        {"joined": True, "n_live": 3}]
    got = run.read_metrics(entries, ctx_of(records))
    assert got[NAME]["value"] == pytest.approx(
        (98304 + 40960) / (67584 + 2560))


@pytest.mark.parametrize("records", [
    [{"joined": True, "assign_total": 67584, "assign_peak": 700}],
    [{"joined": False, "assign_total": 2048, "assign_rows_computed": 4096}],
    [{"joined": True, "n_live": 60}], []],
    ids=["the_parents_records", "no_joined_step", "no_counts", "no_records"])
def test_nothing_to_read_leaves_the_metric_out(entries, records):
    assert run.read_metrics(entries, ctx_of(records)) == {}
