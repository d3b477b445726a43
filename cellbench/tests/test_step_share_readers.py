"""The two readers of what a step's program was, `joined_step_share`
and `grouped_step_share`, on hand-made flight records through the
harness's own loader: the share of the window's busy iterations whose
record says so, asked of every cell, and nothing to read on a program
whose records lack the field (the parent commit's, for a reader new in
a PR)."""
import pytest

from cellbench import run

FIELD = {"joined_step_share": "joined", "grouped_step_share": "grouped"}


def ctx_of(records):
    return {"stats": {"flight_recorder": [dict(r, ts=10.0 + i)
                                          for i, r in enumerate(records)]},
            "wall_minus_mono": 0.0, "window_abs": (0.0, 100.0),
            "trace_span": None, "_hostplane": None}


@pytest.fixture(scope="module")
def bench():
    return run.load_benchmark()


@pytest.mark.parametrize("name", sorted(FIELD))
def test_share_of_the_windows_busy_iterations(bench, name):
    for cell in bench["workloads"]:
        entry, = [m for m in run.metric_entries(bench, cell["name"],
                                                "per_layer")
                  if m["name"] == name]
        assert "workloads" not in entry and entry["moves"] == "out_tok_s"
        assert entry["source"] == "program_counter"
        field = FIELD[name]
        # three mixed steps of which two say so, one decode-only program,
        # and a record closed outside the window
        records = [{field: True}, {field: False}, {field: True},
                   {field: False}, {field: True}]
        ctx = ctx_of(records)
        ctx["stats"]["flight_recorder"][-1]["ts"] = 1e9
        got = run.read_metrics([entry], ctx)
        assert got[name]["value"] == pytest.approx(50.0)
        assert run.read_metrics([entry], ctx_of(
            [{field: True}] * 3))[name]["value"] == 100.0


@pytest.mark.parametrize("name", sorted(FIELD))
@pytest.mark.parametrize("records", [[], [{"n_live": 60}, {"n_live": 62}]],
                         ids=["no_records", "the_parents_records"])
def test_nothing_to_read_leaves_the_metric_out(bench, name, records):
    entry, = [m for m in bench["per_layer"] if m["name"] == name]
    assert name not in run.read_metrics([entry], ctx_of(records))
