"""Kernels: the work of the routed experts in the joined steps of the
traced span, over the device time their ops took.

The work, not what an implementation fetches or computes, as the time the
chip's peaks allow it: for every joined step of the span and every expert
layer, the larger of the layer's routed experts' weights read once
(experts x 3 matrices x hidden x expert width, in the served type) over
`peaks.json`'s bytes a second, and the step's assignments to the layer
(the flight record's `assign_total` over the expert layers) x 3 products x
2 x hidden x expert width over its bf16 flops a second. The time: device
time of the ops under both `/joined_walk/` and `/moe_experts/`, the sorted
dispatch's kernels and, in a joined step under its threshold, the dense
dispatch's einsums. A step cannot do its experts' work in less, so the
share cannot pass 100; it reads the same whatever implements the layer,
and nothing where the program records no `assign_total` or has no such
scope."""
import json

from cellbench import hostplane, serve


def expert_layers(cfg: dict) -> int:
    return cfg["num_hidden_layers"] - cfg["num_dense_layers"]


def layer_weight_bytes(cfg: dict) -> float:
    """Bytes of one layer's routed experts, each matrix once."""
    b = 4 if cfg.get("serving", {}).get("param_dtype") == "float32" else 2
    return float(cfg["num_experts"] * 3 * cfg["hidden_size"]
                 * cfg["moe_intermediate_size"] * b)


def layer_flops(assignments: float, cfg: dict) -> float:
    """Operations of one layer's routed experts over `assignments` rows:
    the gate's, the up and the down product."""
    return (assignments * 3 * 2 * cfg["hidden_size"]
            * cfg["moe_intermediate_size"])


def floor_seconds(records: list, cfg: dict, peak: dict) -> float:
    """The least time the routed experts of `records`' joined steps take
    at the chip's peaks."""
    n = expert_layers(cfg)
    stream = layer_weight_bytes(cfg) / peak["hbm_bytes_per_s"]
    return float(sum(
        n * max(stream, layer_flops(r["assign_total"] / n, cfg)
                / peak["bf16_flops"])
        for r in records if r.get("joined") and r.get("assign_total")))


def read(ctx):
    trace = hostplane.trace_of(ctx)
    plane = hostplane.first_device(trace) if trace else None
    if not plane or not ctx.get("trace_span"):
        return None
    ops = [e for e in plane.get(hostplane.OPS_LINE, [])
           if e[3] and "/joined_walk/" in e[3] and "/moe_experts/" in e[3]]
    seconds = hostplane.union_ns(ops) / 1e9
    with open(ctx["peaks_file"]) as f:
        peak = json.load(f)[ctx["device"]["kind"]]
    floor = floor_seconds(serve.flight_in(ctx, *ctx["trace_span"]),
                          ctx["config"], peak)
    if seconds <= 0 or floor <= 0:
        return None
    return 100.0 * floor / seconds
