"""CPU rehearsal of a serving cell, for the harness's own tests only.

    JAX_PLATFORMS=cpu python cellbench/rehearse.py --workload <name> --seeds 24

Runs the cell end to end at tiny widths on the CPU (XLA attention, two
layers), once per seed, and prints what a CPU run can say: counts.
Programs compiled by the warm-up, by the pre-roll and inside the window
(the warm-up's coverage of the dispatch shapes does not depend on
widths), requests attempted and failed, whether the check passed. It
prints no time, rate or share, and no result line: `run.py` is the only
way to a device metric and refuses anything but the chip.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

TINY = {"hidden_size": 64, "intermediate_size": 128,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
        "vocab_size": 512, "num_hidden_layers": 2,
        "serving": {"decode_attention_impl": "xla", "dtype": "float32",
                    "param_dtype": "float32"}}


def rehearse(workload: str, seed: int, seconds: float,
             slow: float = 5.0) -> dict:
    """One tiny-width CPU run of `workload`; the counts of it."""
    import jax

    from cellbench import run, serve
    jax.clear_caches()  # every seed compiles anew, so a compile is a shape
    bench = run.load_benchmark()
    _, wl, cfg_file = run.load_cell(bench, workload)
    # a CPU iteration is several times a chip's: stretch the pacing by
    # the same factor, so arrivals per iteration stay what they are there
    for key in ("preroll_s", "ramp_s", "settle_s"):
        if key in wl:
            wl[key] = float(wl[key]) * slow
    for key in ("retire_after_s", "retire_by_s", "ramp_deadline_s"):
        if key in wl["warmup"]:
            wl["warmup"][key] = float(wl["warmup"][key]) * slow
    wl["check"]["samples"] = 2
    run_dir = tempfile.mkdtemp(prefix="cellbench-rehearse-")
    try:
        ctx = serve.run_cell(wl, cfg_file, seed, seconds, False, run_dir,
                             time.monotonic(), overrides=TINY,
                             require_tpu=False)
    finally:
        shutil.rmtree(run_dir, ignore_errors=True)
    from cellbench import stats
    w0, w1 = ctx["window"]
    attempted, failed = stats.count_attempted_failed(ctx["records"], w0, w1)
    clog = ctx["compile_log"]
    steps = [e for e in clog.events if "_mixed_step" in e[1]
             or "_decode" in e[1]]
    a0, a1 = ctx["window_abs"]
    return {"seed": seed, "attempted": attempted, "failed": failed,
            "tokens": stats.tokens_in_window(ctx["records"], w0, w1),
            "step_programs_warm": sum(
                1 for e in steps if e[0] < ctx["t0"]),
            "step_programs_preroll": sum(
                1 for e in steps if ctx["t0"] <= e[0] < a0),
            "step_programs_window": sum(
                1 for e in steps if a0 <= e[0] < a1),
            "shapes_after_warm": [e[2][-120:] for e in clog.shapes
                                  if e[0] >= ctx["t0"] and (
                                      "_mixed_step" in e[1]
                                      or "_decode" in e[1])],
            "iterations": len(ctx["stats"].get("flight_recorder", [])),
            "check": {k: ctx["check"].get(k) for k in (
                "correct", "requests", "tokens", "logprob_median_abs_diff",
                "logprob_max_abs_diff", "margin_max")}}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=24)
    ap.add_argument("--first-seed", type=int, default=1000)
    ap.add_argument("--seconds", type=float, default=6.0)
    ap.add_argument("--slow", type=float, default=5.0)
    args = ap.parse_args()
    for i in range(args.seeds):
        print(json.dumps(rehearse(args.workload, args.first_seed + i,
                                  args.seconds, slow=args.slow)),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
