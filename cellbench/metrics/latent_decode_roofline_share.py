"""Kernels: what the decode rows' latent-attention kernel calls of the
traced span had to read, over what the chip's memory could have delivered
in the device time they took.

The work, not what the kernel chose to fetch: for every iteration of the
span the flight record's `keys_latent_decode` (the keys one attention
block has to read for the decode rows: the sum of their contexts), times
the bytes of a token's latent entry (kv_lora_rank + qk_rope_head_dim
values), times the attention blocks of the configuration (two a layer,
one call each), plus each row's absorbed queries read (heads x the
entry's width) and outputs written (heads x kv_lora_rank). The time:
device time of the ops under `/decode_rounds/` and `/attn/latent/` whose
name holds `paged_attention`. The peak: `peaks.json`'s bytes a second of
the device the run reports. It reads the same whatever implements the
kernel, and nothing where the program records no `keys_latent_decode` or
has no such scope."""
import json

from cellbench import hostplane, serve


def latent_decode_bytes(records: list, cfg: dict) -> float:
    """Bytes the attention blocks' decode calls of `records` had to move."""
    blocks = 2 * cfg["num_layers"]
    act_b = 4 if cfg.get("serving", {}).get("dtype") == "float32" else 2
    entry = cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]
    row_b = cfg["num_attention_heads"] * (entry + cfg["kv_lora_rank"]) * act_b
    return float(sum(
        blocks * (r["keys_latent_decode"] * entry * act_b
                  + r.get("n_live", 0) * r.get("decode_rounds", 1) * row_b)
        for r in records if r.get("keys_latent_decode")))


def read(ctx):
    trace = hostplane.trace_of(ctx)
    plane = hostplane.first_device(trace) if trace else None
    if not plane or not ctx.get("trace_span"):
        return None
    ops = [e for e in plane.get(hostplane.OPS_LINE, [])
           if e[3] and "/decode_rounds/" in e[3] and "/attn/latent/" in e[3]
           and "paged_attention" in e[0]]
    seconds = hostplane.union_ns(ops) / 1e9
    work = latent_decode_bytes(
        serve.flight_in(ctx, *ctx["trace_span"]), ctx["config"])
    if seconds <= 0 or work <= 0:
        return None
    with open(ctx["peaks_file"]) as f:
        peak = json.load(f)[ctx["device"]["kind"]]["hbm_bytes_per_s"]
    return 100.0 * work / seconds / peak
