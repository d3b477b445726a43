"""Run one cell of BENCHMARK.json once.

    python cellbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything about a cell is data: `BENCHMARK.json` names the cell, its
configuration and its metrics; `cellbench/workloads/<traffic>.json` holds
the traffic and the server's options, `cellbench/configs/<name>.json` the
model, `cellbench/cells/<cell>.json` what belongs to the pair (pool size,
the check's limits), and `cellbench/metrics/<metric>.py` one reader per
metric. This
file only finds them, runs the cell's runner (`cellbench/<runner>.py`)
and prints the result line, the last line of standard output.

Exit code 0 and the line whenever a window was measured: failed
requests, unfinished ones, `correct: false` and compiles in the window
are reported, never raised. Non-zero, and no line, only when nothing
could be measured (no TPU, too few chips, the server or the load
generator never came up).
"""

from __future__ import annotations

import time

T_PROCESS = time.monotonic()  # as close to process start as Python allows

import argparse  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import traceback  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def load_benchmark(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def load_cell(bench: dict, name: str, root: str = ROOT):
    """(cell entry, workload file, configuration file) by the names in
    BENCHMARK.json. The traffic file is `workloads/<traffic>.json` of
    the benchmark's first path. What belongs to the pair of model and
    traffic (the cache pool, the check's limits) is in a file of the
    cell's own, `cells/<cell>.json`, and is laid over the traffic file:
    a new cell brings its own and edits neither of the other two."""
    cells = {c["name"]: c for c in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json; "
                         f"there are: {sorted(cells)}")
    cell = cells[name]
    base = os.path.join(root, bench["paths"][0])
    with open(os.path.join(base, "workloads",
                           cell["traffic"] + ".json")) as f:
        wl = json.load(f)
    cfg_entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    with open(os.path.join(root, cfg_entry["file"])) as f:
        cfg_file = json.load(f)
    with open(os.path.join(base, "cells", name + ".json")) as f:
        merge(wl, json.load(f))
    wl["chips"] = cell["chips"]
    return cell, wl, cfg_file


def merge(base: dict, over: dict) -> dict:
    """Deep-merge `over` into `base`."""
    for k, v in over.items():
        if isinstance(v, dict) and isinstance(base.get(k), dict):
            merge(base[k], v)
        else:
            base[k] = v
    return base


def metric_entries(bench: dict, cell_name: str, section: str) -> list[dict]:
    """The metrics of `section` this cell reports: those that list the
    cell under `workloads`, or list nothing."""
    return [m for m in bench[section]
            if "workloads" not in m or cell_name in m["workloads"]]


def load_reader(name: str, root: str = ROOT, path: str = "cellbench"):
    """The reader module of one metric, `metrics/<name>.py`."""
    file = os.path.join(root, path, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "cellbench_metric_" + name.replace(".", "_").replace("-", "_"), file)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read_metrics(entries: list[dict], ctx: dict, root: str = ROOT) -> dict:
    """{name: {"value", "unit"}} from each entry's reader. A reader that
    finds nothing to read returns None and the metric is left out; one
    that raises is reported on stderr and left out."""
    out = {}
    for m in entries:
        try:
            value = load_reader(m["name"], root).read(ctx)
        except Exception:  # noqa: BLE001 - a reader must not lose the run
            print(f"[cellbench] reader {m['name']} failed:\n"
                  f"{traceback.format_exc()}", file=sys.stderr)
            continue
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench = load_benchmark()
    cell, wl, cfg_file = load_cell(bench, args.workload)
    runner = importlib.import_module("cellbench." + wl["runner"])
    run_dir = tempfile.mkdtemp(prefix="cellbench-run-")
    try:
        ctx = runner.run_cell(
            wl, cfg_file, args.seed, args.seconds, bool(args.trace),
            run_dir, T_PROCESS)
        ctx["workload"] = wl
        ctx["config"] = cfg_file
        ctx["peaks_file"] = os.path.join(HERE, "peaks.json")
        section = "per_layer" if args.trace else "end_to_end"
        metrics = read_metrics(
            metric_entries(bench, args.workload, section), ctx)
        line = runner.result_line(ctx, metrics, bool(args.trace))
    finally:
        shutil.rmtree(run_dir, ignore_errors=True)
    sys.stderr.flush()
    # each number compared beside its limit, then the line, last
    print("[cellbench] check " + json.dumps(ctx["check"]), flush=True)
    print(json.dumps(line), flush=True)
    # the line is out and every child has been waited for: leave without
    # the interpreter's tear-down, in which a runtime thread that is still
    # inside the device library aborts the process (seen on the chip)
    os._exit(0)


if __name__ == "__main__":
    sys.exit(main())
