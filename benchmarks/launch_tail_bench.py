"""The parts of one launch of the overlapped scheduler, on the host's clock.

Where a launch has to follow the read-back (`commit`) of the program
before it, the device has no work between the two and
`PagedInferenceServer._launch_plan` is serialized by construction
(PERF.md section 5); in the steady state it goes ahead of that commit
and costs the host's loop, not the device (PR 46), and what it costs is
the same. This script takes that phase apart at a cell's
dispatch shapes: decode rows (`--rows`), pages a row (`--pages-per-row`)
and the argument structure of a configuration's family (`--config`), at
the family's tiny widths: what the host pays for a launch follows from
the number and the sizes of the arrays it hands over and from the leaves
of the call, not from the model's widths, and a tiny program leaves the
device idle at every launch, as the closed loops of the cells do.

Three tables, one JSON line each:

* `loop`: a server driven by `submit` and `step` through some hundreds of
  steady-state launches, with the conversions `paged_server` makes and
  the step's call timed where they are made. By kind of plan, medians:
  the whole phase, host arrays handed to the device and how many,
  conversions of arrays that are on the device already and how many,
  the call of the step program, `_handoff_prefetch`, and the rest (the
  patch's copies, `_walks_once`, the profiler's boundary).
* `alone`: the two forms of the patch side by side, outside any server:
  a key split on the host and four arrays handed over one by one, then
  some thirty conversions of device arrays (the form before PR 36,
  whose loop table is in PERF.md section 6), against one packed buffer
  and one transfer (the form since); and a jitted call handed the
  staged patch against one handed the host buffer itself.

* `stage`: what a plan stages while the program before it runs
  (`_plan_iteration`'s staging block), outside any server: a mixed
  plan's arrays (a prefill group of 8 rows, `--rows` decode rows over
  compacted slots) and a decode-only plan's, handed over one
  `device_put` an array (the form before PR 47: 42 and 16) against
  packed (`_pack_group`, `_pack_rows`: 2 and 1, the packing timed with
  them and alone), each behind a running program of some 20 ms and with
  the device drained; medians in ms.

    python benchmarks/launch_tail_bench.py                    # on a TPU
    python benchmarks/launch_tail_bench.py --rows 64 --pages-per-row 128 \
        --config cellbench/configs/smallthinker-21b-a3b-instruct.json
    JAX_PLATFORMS=cpu python benchmarks/launch_tail_bench.py --requests 40

Prints one JSON line per table and appends them to
`chiprun_out/launch_tail_bench.jsonl`.
"""

from __future__ import annotations

import argparse
import json
import os
import random
import statistics
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from cloud_server_tpu.inference import paged_server as ps  # noqa: E402

PAGE = 128
STEP_PROGRAMS = ("_mixed_step", "_decode_rounds", "_spec_rounds")
PARTS = ("h2d", "on_device", "step_call", "prefetch")


class _Clock:
    """Where one launch's time went; `None` outside a launch."""

    def __init__(self):
        self.parts = None
        self.depth = 0  # > 0 inside a timed call: its callees are its own

    def timed(self, part, fn, count=False):
        def call(*args, **kwargs):
            if self.parts is None or self.depth:
                return fn(*args, **kwargs)
            self.depth += 1
            t0 = time.perf_counter()
            try:
                return fn(*args, **kwargs)
            finally:
                self.parts[part] += time.perf_counter() - t0
                self.depth -= 1
                if count:
                    self.parts[part + "_n"] += 1
        return call

    def conversion(self, fn, leaf_of):
        """`fn` timed as `h2d` or `on_device` by what it is handed."""
        to_dev = self.timed("h2d", fn, count=True)
        on_dev = self.timed("on_device", fn, count=True)

        def call(*args, **kwargs):
            leaf = leaf_of(*args)
            return (on_dev if isinstance(leaf, jax.Array)
                    else to_dev)(*args, **kwargs)
        return call


class _Proxy:
    """A module with some attributes replaced."""

    def __init__(self, real, **replaced):
        self.__dict__.update(replaced)
        self._real = real

    def __getattr__(self, name):
        return getattr(self._real, name)


def instrument(srv, clock: _Clock, records: list) -> None:
    """Time the conversions of `paged_server` and the step's call while a
    launch runs; one record a launch that dispatched a program."""
    def first_leaf(fn, tree, *rest):
        return jax.tree.leaves(tree)[0]

    ps.jnp = _Proxy(jnp, asarray=clock.conversion(
        jnp.asarray, lambda x, *a: x))
    ps.jax = _Proxy(
        jax, device_put=clock.conversion(jax.device_put, lambda x, *a: x),
        tree=_Proxy(jax.tree, map=clock.conversion(jax.tree.map,
                                                   first_leaf)))
    for name in STEP_PROGRAMS:
        setattr(ps, name, clock.timed("step_call", getattr(ps, name)))
    srv._handoff_prefetch = clock.timed("prefetch", srv._handoff_prefetch)
    launch = srv._launch_plan

    def timed_launch(plan):
        clock.parts = dict.fromkeys(
            PARTS + ("h2d_n", "on_device_n"), 0.0)
        t0 = time.perf_counter()
        launch(plan)
        total = time.perf_counter() - t0
        parts, clock.parts = clock.parts, None
        if parts["step_call"]:
            kind = plan.kind + ("" if plan.sl_d is None else "+slot_ids")
            parts["rest"] = total - sum(parts[p] for p in PARTS)
            records.append({"kind": kind, "launch": total, **parts})
    srv._launch_plan = timed_launch


def build_server(config: str, rows: int, pages_per_row: int, seed: int):
    from cellbench import families, serve
    with open(config) as f:
        cfg_file = json.load(f)
    _, mcfg, weights = serve.make_model(
        cfg_file, families.of(cfg_file).TINY, seed)
    opts = {"max_slots": rows, "max_len": pages_per_row * PAGE,
            "page_size": PAGE, "prefill_chunk": 256, "decode_chunk": 1,
            "num_pages": rows * 8, "flight_recorder": 4096}
    return serve.build_server(mcfg, weights, opts, 128), mcfg


def drive(srv, vocab: int, n_requests: int, seed: int) -> None:
    """A queue of prompts of two lengths and answers of 16 to 128 tokens:
    rows end one by one, so most steps carry a prefill group and some are
    decode rounds alone, as in the cells."""
    rng = random.Random(seed)
    reqs = [srv.submit([rng.randrange(1, vocab)
                        for _ in range(rng.choice((300, 520)))],
                       max_new_tokens=rng.randrange(16, 129))
            for _ in range(n_requests)]
    while not all(r._done.is_set() for r in reqs):
        srv.step()


def loop_table(records: list) -> dict:
    """Medians in microseconds by kind of plan, over the later two thirds
    of the launches (the first third meets the compiles)."""
    steady = records[len(records) // 3:]
    out = {}
    for kind in sorted({r["kind"] for r in steady}):
        rs = [r for r in steady if r["kind"] == kind]
        row = {"n": len(rs)}
        for k in ("launch",) + PARTS + ("rest",):
            row[k + "_us"] = round(
                statistics.median(r[k] for r in rs) * 1e6, 1)
        for k in ("h2d_n", "on_device_n"):
            row[k] = statistics.median(r[k] for r in rs)
        out[kind] = row
    return out


def _median_us(fn, reps: int) -> float:
    out = []
    for _ in range(reps):
        t0 = time.perf_counter()
        y = fn()
        out.append(time.perf_counter() - t0)
        jax.block_until_ready(y)  # not timed: the next call meets no queue
    return round(statistics.median(out) * 1e6, 1)


def alone_table(rows: int, table_cols: int, reps: int) -> dict:
    """The patch's two forms outside any server, microseconds a launch."""
    r = np.random.default_rng(0)
    lens = r.integers(0, 2000, rows).astype(np.int32)
    last = r.integers(0, 32000, rows).astype(np.int32)
    live = r.integers(0, 2, rows).astype(bool)
    tables = r.integers(0, 800, (rows, table_cols)).astype(np.int32)
    staged = [jnp.asarray(lens + i) for i in range(33)]
    jax.block_until_ready(staged)
    key = [jax.random.key(0)]

    def split_on_host():
        key[0], sub = jax.random.split(key[0])
        return sub

    def four_arrays():
        return (jnp.asarray(lens), jnp.asarray(tables), jnp.asarray(last),
                jnp.asarray(live))

    def one_array():
        return jax.device_put(ps._pack_patch(7, lens, last, live, tables))

    # the transfer as a call of its own against the transfer a jitted
    # call makes for a host array among its arguments
    program = jax.jit(lambda patch, *xs: patch[:, 0] + xs[0])
    on_device = one_array()
    jax.block_until_ready([program(on_device, *staged),
                           program(np.asarray(on_device), *staged)])
    return {
        "split_on_host_us": _median_us(split_on_host, reps),
        "four_arrays_us": _median_us(four_arrays, reps),
        "on_device_x33_us": _median_us(
            lambda: [jnp.asarray(x) for x in staged], reps),
        "one_array_us": _median_us(one_array, reps),
        "call_staged_patch_us": _median_us(
            lambda: program(on_device, *staged), reps),
        "call_host_patch_us": _median_us(
            lambda: program(ps._pack_patch(7, lens, last, live, tables),
                            *staged), reps)}


def _median_ms(fn, reps: int, busy=None) -> float:
    """`fn` on the host's clock; `busy` puts a program on the device's
    queue first, so the transfers go behind it as a plan's do."""
    out = []
    for _ in range(reps):
        running = busy() if busy is not None else None
        t0 = time.perf_counter()
        y = fn()
        out.append(time.perf_counter() - t0)
        jax.block_until_ready((y, running))
    return round(statistics.median(out) * 1e3, 4)


def stage_table(rows: int, table_cols: int, reps: int) -> dict:
    """A plan's staged arrays one by one against packed, milliseconds a
    plan: the group is 8 rows of a 256-token chunk and a 2,048-token
    prompt bucket, the decode rows `rows` over compacted slots."""
    from cloud_server_tpu.config import InferConfig
    from cloud_server_tpu.inference.sampling import make_rows
    r = np.random.default_rng(0)
    gp, w, pb = 8, 256, 2048

    def ints(*shape, hi=2000):
        return r.integers(0, hi, shape).astype(np.int32)

    def sampler(n):
        return make_rows([None] * n, InferConfig(), ints(n))

    chunk, g_tables, prompt_rows = ints(gp, w), ints(gp, table_cols), \
        ints(gp, pb)
    head = {name: ints(gp) for name in ps._GROUP_FIELDS}
    head["count_mask"] = head["count_mask"] > 1000
    head["scatter_mask"] = head["scatter_mask"] > 1000
    samp_g, samp_d = sampler(gp), sampler(rows)
    stop, gid, aid, sl = ints(rows), ints(rows), ints(rows), ints(rows)
    group_loose = [chunk, g_tables, prompt_rows, *head.values(), *samp_g]
    rows_loose = [stop, gid, aid, sl, *samp_d]

    def pack_group():
        return ps._pack_group(chunk, g_tables, prompt_rows, samp_g, **head)

    def pack_rows():
        return ps._pack_rows(stop, gid, aid, 0, samp_d, sl)

    forms = {
        "mixed_one_by_one": lambda: [
            jax.device_put(a) for a in group_loose + rows_loose],
        "mixed_packed": lambda: [jax.device_put(pack_group()),
                                 jax.device_put(pack_rows())],
        "decode_one_by_one": lambda: [
            jax.device_put(a) for a in rows_loose],
        "decode_packed": lambda: [jax.device_put(pack_rows())]}
    # a program of some 20 ms for the transfers to queue behind
    x = jnp.ones((1024, 1024), jnp.bfloat16)
    spin = jax.jit(lambda x, n: jax.lax.fori_loop(
        0, n, lambda _, y: (y @ x) * 1e-3, x))
    n, took = 4, 0.0
    while took < 0.02 and n < 2 ** 20:
        n *= 2
        jax.block_until_ready(spin(x, n))
        t0 = time.perf_counter()
        jax.block_until_ready(spin(x, n))
        took = time.perf_counter() - t0
    out = {"arrays": {"mixed_one_by_one": len(group_loose + rows_loose),
                      "mixed_packed": 2,
                      "decode_one_by_one": len(rows_loose),
                      "decode_packed": 1},
           "behind_program_of_ms": round(took * 1e3, 2),
           "pack_group_ms": _median_ms(pack_group, reps),
           "pack_rows_ms": _median_ms(pack_rows, reps)}
    for name, fn in forms.items():
        out[name + "_behind_ms"] = _median_ms(fn, reps,
                                              lambda: spin(x, n))
        out[name + "_drained_ms"] = _median_ms(fn, reps)
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config",
                    default="cellbench/configs/mixtral-8x7b-v0.1.json",
                    help="a configuration file of the benchmark: its "
                         "family's tiny widths are served")
    ap.add_argument("--rows", type=int, default=64)
    ap.add_argument("--pages-per-row", type=int, default=16)
    ap.add_argument("--requests", type=int, default=600)
    ap.add_argument("--reps", type=int, default=300)
    ap.add_argument("--seed", type=int, default=0)
    a = ap.parse_args()
    dev = jax.devices()[0]
    head = {"device": {"platform": dev.platform, "kind": dev.device_kind},
            "config": a.config, "rows": a.rows,
            "pages_per_row": a.pages_per_row}
    srv, mcfg = build_server(a.config, a.rows, a.pages_per_row, a.seed)
    lines = [{**head, "table": "alone", **alone_table(
        a.rows, srv.tables.shape[1], a.reps)}]
    print(json.dumps(lines[-1]), flush=True)
    lines.append({**head, "table": "stage", **stage_table(
        a.rows, srv.tables.shape[1], a.reps)})
    print(json.dumps(lines[-1]), flush=True)
    clock, records = _Clock(), []
    instrument(srv, clock, records)
    drive(srv, mcfg.vocab_size, a.requests, a.seed)
    lines.append({**head, "table": "loop", "launches": len(records),
                  **loop_table(records)})
    print(json.dumps(lines[-1]), flush=True)
    out = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, "launch_tail_bench.jsonl"), "a") as f:
        for line in lines:
            f.write(json.dumps(line) + "\n")


if __name__ == "__main__":
    main()
