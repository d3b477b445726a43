"""Kernels: what the decode rows' one-token updates of the mixer's state in
the traced span had to move, over what the chip's memory could have
delivered in the device time they took.

The work, not what an implementation fetches: for every iteration of the
span the flight record's `ssm_decode_rows` (rows whose state the step
advanced by one token, times rounds), times the layers, times a row's
bytes a layer: its state read and written (heads x head width x state
width x 4 B, twice), the convolution's held inputs read and written, the
row's x, B, C and dt read and its y written. The time: device time of the
ops under both `/decode_rounds/` and `/ssm/scan/` (a decode program's, and
the decode rows' share of a mixed program's walk). The peak: `peaks.json`'s
bytes a second of the device the run reports. It reads the same whatever
implements the update, and nothing where the program records no
`ssm_decode_rows` or has no such scope."""
import json

from cellbench import hostplane, serve


def ssm_decode_bytes(records: list, cfg: dict) -> float:
    """Bytes the decode rows' state updates of `records` had to move."""
    act_b = 4 if cfg.get("serving", {}).get("dtype") == "float32" else 2
    heads, dh, n = (cfg["mamba_n_heads"], cfg["mamba_d_head"],
                    cfg["mamba_d_state"])
    inner = heads * dh
    channels = inner + 2 * cfg["mamba_n_groups"] * n
    row = (2 * heads * dh * n * 4                      # the state, both ways
           + 2 * (cfg["mamba_d_conv"] - 1) * channels * act_b
           + channels * act_b + heads * 4              # x, B, C and dt
           + inner * 4)                                # y
    return float(cfg["num_hidden_layers"] * row * sum(
        r.get("ssm_decode_rows", 0) for r in records))


def read(ctx):
    trace = hostplane.trace_of(ctx)
    plane = hostplane.first_device(trace) if trace else None
    if not plane or not ctx.get("trace_span"):
        return None
    ops = [e for e in plane.get(hostplane.OPS_LINE, [])
           if e[3] and "/decode_rounds/" in e[3] and "/ssm/scan/" in e[3]]
    seconds = hostplane.union_ns(ops) / 1e9
    work = ssm_decode_bytes(
        serve.flight_in(ctx, *ctx["trace_span"]), ctx["config"])
    if seconds <= 0 or work <= 0:
        return None
    with open(ctx["peaks_file"]) as f:
        peak = json.load(f)[ctx["device"]["kind"]]["hbm_bytes_per_s"]
    return 100.0 * work / seconds / peak
