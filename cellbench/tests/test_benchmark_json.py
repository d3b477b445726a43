"""BENCHMARK.json against the contract's letter, every file it names,
and the promise that a later PR adds a cell by adding files only."""
import json
import os
import re
import shutil

import pytest

from cellbench import run

ROOT = run.ROOT
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bench():
    return run.load_benchmark()


def test_top_level_keys_and_limits(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= bench["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024
    assert bench["command"][1].startswith(bench["paths"][0] + "/")
    four = [c for c in bench["workloads"] if c["chips"] == 4]
    assert len(four) <= max(1, len(bench["workloads"]) // 4)


def test_names_units_and_keys(bench):
    names = []
    for m in bench["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in {"host_clock", "device_trace"}
        assert 0.01 <= m["bound"] <= 0.1
    for m in bench["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["source"] in SOURCES
        assert m["moves"] in {e["name"] for e in bench["end_to_end"]}
        assert 1 <= len(m["layer"]) <= 200 and "\n" not in m["layer"]
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in {"lower", "higher"}
        names.append(m["name"])
    assert len(names) == len(set(names))
    assert "setup_s" in names
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and len(c["why"]) <= 200
        assert all(NAME.match(k) for k in c["reduced"])
    cells = bench["workloads"]
    for c in cells:
        assert set(c) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(c["name"]) and NAME.match(c["traffic"])
        assert 1 <= len(c["why"]) <= 200 and c["chips"] in (1, 4)
    assert len({c["name"] for c in cells}) == len(cells)
    assert len({(c["config"], c["traffic"]) for c in cells}) == len(cells)
    used = {c["config"] for c in cells}
    assert used == {c["name"] for c in bench["configs"]}


def test_every_cell_loads_and_every_metric_has_a_reader(bench):
    for cell in bench["workloads"]:
        _, wl, cfg = run.load_cell(bench, cell["name"])
        assert os.path.exists(os.path.join(
            run.HERE, wl["runner"] + ".py"))
        assert cfg["hidden_size"] > 0 and cfg["source"].startswith("https://")
        e2e = run.metric_entries(bench, cell["name"], "end_to_end")
        assert {"setup_s"} < {m["name"] for m in e2e}
        assert run.metric_entries(bench, cell["name"], "per_layer")
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert callable(run.load_reader(m["name"]).read)
        for w in m.get("workloads", []):
            assert w in {c["name"] for c in bench["workloads"]}


def test_reduced_names_depth_only_and_no_width(bench):
    for c in bench["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert c["reduced"] == ["num_hidden_layers"]
        assert cfg["num_hidden_layers"] < \
            cfg["reduced_from"]["num_hidden_layers"]
        assert (cfg["hidden_size"], cfg["intermediate_size"],
                cfg["head_dim"]) == (4096, 14336, 128)


def test_a_later_pr_adds_a_cell_by_adding_files_only(tmp_path, bench):
    """A configuration, a traffic mix, the cell's own file and a
    per-layer metric: one new file each and entries in BENCHMARK.json,
    no file that is there edited. The loader finds them all."""
    root = tmp_path / "repo"
    shutil.copytree(run.HERE, root / "cellbench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    before = {p: p.read_bytes() for p in root.rglob("*") if p.is_file()}
    cfg = json.loads((root / "cellbench/configs/mixtral-8x7b-v0.1.json")
                     .read_text())
    cfg["num_hidden_layers"] = 2
    (root / "cellbench/configs/new-model.json").write_text(json.dumps(cfg))
    (root / "cellbench/cells/new-model.new-mix.json").write_text(
        json.dumps({"server": {"num_pages": 512}}))
    wl = json.loads((root / "cellbench/workloads/batch-closed.json")
                    .read_text())
    wl["clients"] = 96
    (root / "cellbench/workloads/new-mix.json").write_text(json.dumps(wl))
    (root / "cellbench/metrics/new_counter.py").write_text(
        '"""Scheduler: a new counter."""\n\n\n'
        'def read(ctx):\n    return ctx.get("new_counter")\n')
    new = json.loads(json.dumps(bench))
    new["configs"].append({"name": "new-model", "source": "https://x/y",
                           "file": "cellbench/configs/new-model.json",
                           "reduced": ["num_hidden_layers"], "why": "new"})
    new["workloads"].append({"name": "new-model.new-mix",
                             "config": "new-model", "traffic": "new-mix",
                             "chips": 1, "why": "new"})
    new["per_layer"].append({"name": "new_counter", "unit": "count",
                             "better": "lower", "source": "program_counter",
                             "layer": "scheduler", "moves": "out_tok_s",
                             "workloads": ["new-model.new-mix"]})
    (root / "BENCHMARK.json").write_text(json.dumps(new))
    b2 = run.load_benchmark(str(root))
    cell, wl2, cfg2 = run.load_cell(b2, "new-model.new-mix", str(root))
    assert wl2["clients"] == 96 and cfg2["num_hidden_layers"] == 2
    # what belongs to the pair comes from the cell's own file
    assert wl2["server"]["num_pages"] == 512
    entries = run.metric_entries(b2, "new-model.new-mix", "per_layer")
    assert "new_counter" in {m["name"] for m in entries}
    old = run.metric_entries(b2, bench["workloads"][0]["name"], "per_layer")
    assert "new_counter" not in {m["name"] for m in old}
    reader = run.load_reader("new_counter", str(root))
    assert reader.read({"new_counter": 3}) == 3
    got = run.read_metrics(
        [m for m in entries if m["name"] == "new_counter"],
        {"new_counter": 3}, str(root))
    assert got == {"new_counter": {"value": 3.0, "unit": "count"}}
    assert all(p.read_bytes() == b for p, b in before.items())
