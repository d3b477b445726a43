"""The benchmark's contract with the program, in the gate: what
`BENCHMARK.json` names exists, loads and reaches the program by name.

One case a cell, a configuration and a metric, no compile. It reads
`cellbench/` and changes nothing in it (the benchmark's own tests,
`python -m pytest cellbench -m "not slow"`, are not part of tier 1); a PR
that renames a constructor argument of `PagedInferenceServer`, a field of
`ModelConfig` or a file the benchmark names fails here, before the chip.
"""

import json
import os

import pytest

from cellbench import families, run, serve

with open(os.path.join(run.ROOT, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)
CELLS = [c["name"] for c in BENCH["workloads"]]
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]


def test_benchmark_json_loads():
    bench = run.load_benchmark()
    assert bench["command"] == ["python3", "cellbench/run.py"]
    assert bench["paths"] == ["cellbench"]
    assert CELLS and len(set(CELLS)) == len(CELLS)
    assert {c["config"] for c in bench["workloads"]} == {
        c["name"] for c in bench["configs"]}
    assert "setup_s" in {m["name"] for m in bench["end_to_end"]}


@pytest.mark.parametrize("cell", CELLS)
def test_cell_files_exist_and_options_reach_the_server(cell):
    _, wl, cfg = run.load_cell(BENCH, cell)
    for module in (wl["runner"], wl.get("generator", "traffic")):
        assert os.path.exists(os.path.join(run.HERE, module + ".py")), module
    # every key of the merged `server` object is a keyword of
    # PagedInferenceServer (server_options raises on one that is not)
    kw = serve.server_options(wl["server"])
    assert kw["num_pages"] > 0 and kw["max_context"] % kw["page_size"] == 0
    assert families.of(cfg).model_config(cfg).num_layers > 0
    assert run.metric_entries(BENCH, cell, "end_to_end")
    assert run.metric_entries(BENCH, cell, "per_layer")


@pytest.mark.parametrize("entry", BENCH["configs"], ids=lambda c: c["name"])
def test_configuration_maps_to_a_model_config(entry):
    from cloud_server_tpu.config import ModelConfig
    cfg = run.load_config(entry)
    assert cfg["source"].startswith("https://")
    mcfg = families.of(cfg).model_config(cfg)
    assert isinstance(mcfg, ModelConfig)
    assert mcfg.embed_dim > 0 and mcfg.num_layers > 0


@pytest.mark.parametrize("metric", METRICS, ids=lambda m: m["name"])
def test_metric_has_a_reader(metric):
    assert callable(run.load_reader(metric["name"]).read)
    for cell in metric.get("workloads", []):
        assert cell in CELLS
