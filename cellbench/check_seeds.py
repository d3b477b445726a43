"""The numbers a cell's `correct` limits are set from, many seeds in one
process: for each seed, weights from the seed, a server at the cell's own
widths and depth, a few prompts at lengths across the cell's range
served to the end, and the served log-probabilities against the plain
reference. Once as the
program runs it, and once for each control: the program's own paths in
the nearest precision below the bfloat16 the configuration states, int8
weights (`--quantize`) and the int8 cache (`--kv-cache-int8`).

    python cellbench/check_seeds.py --workload <name> \
        --seeds program:12,kv-int8:3,quantize:3

Seeds share the compiled programs (same shapes, same configuration), so
a dozen seeds cost little more than one. It needs the chip like
`run.py`; `--tiny` is the CPU form the tests use.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def delete_leaves(tree) -> None:
    import jax
    for leaf in jax.tree.leaves(tree):
        leaf.delete()


def quantize_weights(weights: dict, path: tuple = ()):
    """The program's `quantize_params` (what `--quantize` calls), one leaf
    at a time: jitted, so no float32 copy of a stacked leaf is ever
    alive, and each bfloat16 leaf deleted once its int8 copy exists.
    The weights nearly fill the chip; both copies at once do not fit."""
    import jax

    from cloud_server_tpu.models.quantization import QTensor, quantize_params
    if isinstance(weights, dict):
        return {k: quantize_weights(v, path + (k,))
                for k, v in weights.items()}
    sub = weights
    for k in reversed(path):  # `quantize_params` goes by the leaf's names
        sub = {k: sub}
    q = jax.jit(quantize_params)(sub)
    for k in path:
        q = q[k]
    if not isinstance(q, QTensor):
        return weights
    jax.block_until_ready(q)
    weights.delete()
    return q


def one_seed(wl: dict, cfg_file: dict, seed: int, control: str,
             overrides: dict | None, lengths: list[int], per_length: int,
             answer_tokens: int, dump: str | None = None) -> dict:
    """For each prompt length of `lengths`, one after the other, serve
    `per_length` random prompts of that length together for
    `answer_tokens` tokens each, through `submit` and `step` (equal
    lengths move in lockstep, so a mode compiles a handful of programs),
    and compare them all. `control` is "" (the program as the cell runs
    it), "quantize" or "kv-int8". `dump` is a file the per-token
    numbers are appended to, one JSON line a seed."""
    import random

    import numpy as np

    from cellbench import reference, serve
    ov = json.loads(json.dumps(overrides or {}))
    if control == "kv-int8":
        ov.setdefault("serving", {})["kv_cache_dtype"] = "int8"
    mcfg, weights = serve.make_model(cfg_file, ov, seed)
    served = quantize_weights(weights) if control == "quantize" else weights
    srv = serve.build_server(mcfg, served, wl["server"], answer_tokens)
    rng = random.Random(f"{int(seed)}/check_seeds")
    items = []
    t_end = time.monotonic() + 900.0
    for length in lengths:
        prompts = [[rng.randrange(1, mcfg.vocab_size) for _ in range(length)]
                   for _ in range(per_length)]
        handles = [srv.submit(p, max_new_tokens=answer_tokens)
                   for p in prompts]
        while any(h.finish_reason is None for h in handles):
            if time.monotonic() > t_end:
                raise TimeoutError(
                    "check_seeds: requests unfinished at 900 s")
            srv.step()
        items += [(p, h.tokens, h.logprobs)
                  for p, h in zip(prompts, handles)]
    srv.state = None
    if control == "quantize":
        # the reference takes the weights of the seed, not what was served
        delete_leaves(served)
        del srv, served
        gc.collect()
        srv = None
        _, weights = serve.make_model(cfg_file, ov, seed)
    per_token, _ = reference.teacher_force_all(
        weights, serve.reference_spec(cfg_file), items,
        pad_to=int(wl["check"].get("pad_to", 256)))
    out = reference.compare(per_token, **wl["check"].get("compare", {}))
    out.update(seed=seed, control=control,
               finite=bool(np.isfinite(out["logprob_max_abs_diff"])))
    if dump:
        with open(dump, "a") as f:
            f.write(json.dumps({"seed": seed, "control": control,
                                **per_token}) + "\n")
    # the next seed's weights need this seed's room: drop the arrays
    # themselves, not only the names (the server sits in reference cycles)
    delete_leaves(weights)
    del weights, srv
    gc.collect()
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="program:12,kv-int8:3,quantize:3",
                    help="mode:count pairs; program is the cell as it runs")
    ap.add_argument("--first-seed", type=int, default=2147483000)
    ap.add_argument("--lengths", default="272,423,620,1021",
                    help="prompt lengths, each served as one group")
    ap.add_argument("--per-length", type=int, default=3)
    ap.add_argument("--answer-tokens", type=int, default=32)
    ap.add_argument("--dump", default=None)
    ap.add_argument("--tiny", action="store_true")
    args = ap.parse_args()
    from cellbench import rehearse, run
    from cloud_server_tpu.utils.platform import (
        device_info, enable_compile_cache)
    enable_compile_cache()
    dev = device_info()
    print(json.dumps({"device": dev}), flush=True)
    if not args.tiny and dev["platform"] != "tpu":
        raise SystemExit("check_seeds reads the chip's numerics: no TPU")
    bench = run.load_benchmark()
    _, wl, cfg_file = run.load_cell(bench, args.workload)
    lengths = [int(x) for x in args.lengths.split(",")]
    if args.dump:
        os.makedirs(os.path.dirname(os.path.abspath(args.dump)),
                    exist_ok=True)
    for pair in args.seeds.split(","):
        mode, _, count = pair.partition(":")
        for i in range(int(count)):
            t = time.monotonic()
            out = one_seed(
                wl, cfg_file, args.first_seed + 7 * i,
                "" if mode == "program" else mode,
                rehearse.TINY if args.tiny else None, lengths,
                args.per_length, args.answer_tokens, args.dump)
            out["seconds"] = round(time.monotonic() - t, 1)
            print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
