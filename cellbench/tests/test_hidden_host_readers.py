"""The six readers of the host's hidden work (`sched_build_ms`,
`sched_stage_ms`, `plan_h2d_mean`, `sched_between_ms`,
`sched_device_wait_ms`, `host_late_iter_share`) on hand-made flight
records through the harness's own loader: each reads the untraced part
of the window, is asked of every cell, and finds nothing to read on a
program whose records lack its field (the parent commit's, for a reader
new in a PR)."""
import pytest

from cellbench import run

# three overlapped steps (a mixed plan, a decode-only one, a mixed one
# whose program had finished before the step came for it), the
# sequential step that filled the pipeline, and what each reader makes
# of the first four
RECORDS = [
    {"overlap": False, "phases_ms": {"build": 90.0}, "device_wait_ms": 70.0,
     "plan_h2d": 42, "stage_ms": 9.0},
    {"overlap": True, "phases_ms": {"build": 10.0, "deliver": 1.0},
     "device_wait_ms": 6.0, "between_ms": 5.0, "plan_h2d": 42,
     "stage_ms": 4.0, "host_late": False},
    {"overlap": True, "phases_ms": {"build": 6.0}, "device_wait_ms": 0.25,
     "between_ms": 7.0, "plan_h2d": 16, "stage_ms": 2.0, "host_late": True},
    {"overlap": True, "phases_ms": {"build": 11.0}, "device_wait_ms": 8.0,
     "between_ms": 6.0, "plan_h2d": 0, "host_late": False},
]
WANT = {"sched_build_ms": ("ms", "program_span", 10.0),
        "sched_stage_ms": ("ms", "program_span", 4.0),
        "plan_h2d_mean": ("arrays", "program_counter", 25.0),
        "sched_between_ms": ("ms", "program_span", 6.0),
        "sched_device_wait_ms": ("ms", "program_span", 6.0),
        "host_late_iter_share": ("%", "program_counter", 100.0 / 3)}
# the parent's records: the phases and the wait, none of the new fields
PARENTS = [{"overlap": True, "inflight_depth": 1, "device_wait_ms": 5.0,
            "phases_ms": {"build": 10.0, "commit": 0.5}, "launch_h2d": 1}] * 2
READS_THE_PARENT = {"sched_build_ms": 10.0, "sched_device_wait_ms": 5.0}


def ctx_of(records, trace_span=None):
    return {"stats": {"flight_recorder": [dict(r, ts=10.0 + i)
                                          for i, r in enumerate(records)]},
            "wall_minus_mono": 0.0, "window_abs": (0.0, 100.0),
            "trace_span": trace_span, "_hostplane": None}


@pytest.fixture(scope="module")
def bench():
    return run.load_benchmark()


@pytest.mark.parametrize("name", sorted(WANT))
def test_reads_its_field_in_the_untraced_part(bench, name):
    unit, source, want = WANT[name]
    for cell in bench["workloads"]:
        entry, = [m for m in run.metric_entries(bench, cell["name"],
                                                "per_layer")
                  if m["name"] == name]
        assert "workloads" not in entry and entry["moves"] == "out_tok_s"
        assert (entry["unit"], entry["source"]) == (unit, source)
        assert entry["layer"] == "scheduler"
        # a record closed under the profiler and one outside the window
        # are not read
        traced = dict(RECORDS[2], between_ms=900.0, stage_ms=900.0,
                      plan_h2d=900, device_wait_ms=900.0,
                      phases_ms={"build": 900.0})
        ctx = ctx_of(RECORDS + [traced, traced], trace_span=(13.5, 14.5))
        ctx["stats"]["flight_recorder"][-1]["ts"] = 1e9
        got = run.read_metrics([entry], ctx)
        assert got[name] == {"value": pytest.approx(want), "unit": unit}
        # with no traced span the whole window is read
        whole = run.read_metrics([entry], ctx_of(RECORDS + [traced]))
        assert whole[name]["value"] != pytest.approx(want)


@pytest.mark.parametrize("name", sorted(WANT))
@pytest.mark.parametrize("records", [[], [{"n_live": 60}], PARENTS],
                         ids=["no_records", "bare_records",
                              "the_parents_records"])
def test_nothing_to_read_leaves_the_metric_out(bench, name, records):
    entry, = [m for m in bench["per_layer"] if m["name"] == name]
    got = run.read_metrics([entry], ctx_of(records))
    if records is PARENTS and name in READS_THE_PARENT:
        # `phases_ms["build"]` and `device_wait_ms` were on the record
        # before the reader was: the parent gives a value there
        assert got[name]["value"] == READS_THE_PARENT[name]
    else:
        assert name not in got
