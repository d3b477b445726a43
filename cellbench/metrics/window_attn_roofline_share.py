"""Kernels: what the decode rows' window-layer kernel calls of the traced
span had to read, over what the chip's memory could have delivered in
the device time they took.

The work, not what the kernel chose to fetch: for every iteration of the
span the flight record's `keys_window_decode` (the keys one window layer
has to read for the decode rows: per row its context or the window,
whichever is less), times the bytes of a key and its value, times the
window layers of the configuration (one call each), plus each row's
query read and output written. The time: device time of the ops under
`/decode_rounds/` and `/attn/window/` whose name holds `paged_attention`.
The peak: `peaks.json`'s bytes a second of the device the run reports.
It reads the same whatever implements the kernel, and nothing where the
program records no `keys_window_decode` or has no such scope."""
import json

from cellbench import hostplane, serve


def window_decode_bytes(records: list, cfg: dict) -> float:
    """Bytes the window layers' decode calls of `records` had to move."""
    layers = sum(cfg["sliding_window_layout"][:cfg["num_hidden_layers"]])
    sv = cfg.get("serving", {})
    cache_b = 1 if sv.get("kv_cache_dtype") == "int8" else 2
    act_b = 4 if sv.get("dtype") == "float32" else 2
    key_b = 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * cache_b
    row_b = 2 * cfg["num_attention_heads"] * cfg["head_dim"] * act_b
    return float(sum(
        layers * (r["keys_window_decode"] * key_b
                  + r.get("n_live", 0) * r.get("decode_rounds", 1) * row_b)
        for r in records if r.get("keys_window_decode")))


def read(ctx):
    trace = hostplane.trace_of(ctx)
    plane = hostplane.first_device(trace) if trace else None
    if not plane or not ctx.get("trace_span"):
        return None
    ops = [e for e in plane.get(hostplane.OPS_LINE, [])
           if e[3] and "/decode_rounds/" in e[3] and "/attn/window/" in e[3]
           and "paged_attention" in e[0]]
    seconds = hostplane.union_ns(ops) / 1e9
    work = window_decode_bytes(
        serve.flight_in(ctx, *ctx["trace_span"]), ctx["config"])
    if seconds <= 0 or work <= 0:
        return None
    with open(ctx["peaks_file"]) as f:
        peak = json.load(f)[ctx["device"]["kind"]]["hbm_bytes_per_s"]
    return 100.0 * work / seconds / peak
