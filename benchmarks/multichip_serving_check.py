"""Four chips, one process: the two ways the serving stack uses more than
one device, at the Llama-3.2-1B widths (configs/llama3_2_1b.json, depth
cut so that every replica's compile stays short), on the devices the
process has. A check, not a benchmark: it times nothing.

  a. scale-out: `ReplicatedRouter.over_devices`, one replica a chip.
     `device.memory_stats()` of every device after construction and
     after a few requests; every replica's cache must live on its own
     chip and identical greedy requests must agree across replicas.
  b. scale-up: `PagedInferenceServer(mesh=MeshConfig(tp=N))`, the paged
     kernel under shard_map with the kv heads split over the chips. In
     float32 at "highest" matmul precision its greedy output must equal
     the one-chip server's token for token; in bfloat16 (the deployed
     dtype, where the row-parallel psum reorders roundings and a near-tie
     may flip) it must serve every token asked for.

Run on the chip (one process holds all of them):
    python benchmarks/multichip_serving_check.py
"""

import dataclasses
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import jax

from cloud_server_tpu.config import (
    InferConfig, MeshConfig, ModelConfig, from_json)
from cloud_server_tpu.inference.paged_server import PagedInferenceServer
from cloud_server_tpu.inference.router import ReplicatedRouter
from cloud_server_tpu.models import transformer
from cloud_server_tpu.parallel.mesh import make_mesh
from cloud_server_tpu.parallel.sharding import logical_to_sharding
from cloud_server_tpu.utils.platform import (
    enable_compile_cache, require_tpu)

DEPTH = 4
SRV_KW = dict(max_slots=8, max_context=2048, page_size=128,
              prefill_chunk=256, decode_chunk=1)
GREEDY = InferConfig(max_decode_len=16, temperature=0.0, eos_token_id=-1,
                     pad_token_id=0)
PROMPTS = [[(7 * i + 3 * j) % 1000 + 1 for j in range(40 + 9 * i)]
           for i in range(2)]


def memory(tag: str) -> list[int]:
    used = []
    for d in jax.devices():
        st = d.memory_stats()
        used.append(st["bytes_in_use"])
        print(f"[memory] {tag}: {d} in_use "
              f"{st['bytes_in_use'] / 2**20:.0f} MiB peak "
              f"{st['peak_bytes_in_use'] / 2**20:.0f} MiB limit "
              f"{st['bytes_limit'] / 2**20:.0f} MiB", flush=True)
    return used


def scale_out(cfg: ModelConfig) -> None:
    devices = jax.devices()
    cfg = dataclasses.replace(cfg, param_dtype="bfloat16")
    params = transformer.init_params(cfg, jax.random.key(0))
    router = ReplicatedRouter.over_devices(params, cfg, GREEDY, **SRV_KW)
    del params  # the source copy sat on device 0
    built = memory("router built")
    for rep, d in zip(router.replicas, devices):
        homes = {dev for leaf in jax.tree.leaves((rep.state, rep.params))
                 for dev in leaf.devices()}
        assert homes == {d}, f"replica of {d} holds arrays on {homes}"
    assert max(built) - min(built) < 64 * 2**20, (
        f"replicas differ in device memory: {built}")
    reqs = [router.submit(PROMPTS[i % 2], max_new_tokens=8)
            for i in range(2 * len(devices))]
    router.run_until_idle()
    memory("router served")
    per_replica = [r.tokens_emitted for r in router.replicas]
    assert all(len(r.tokens) == 8 and r.finish_reason == "length"
               for r in reqs), [(r.finish_reason, r.tokens) for r in reqs]
    assert all(n > 0 for n in per_replica), per_replica
    for i in range(2):
        outs = [r.tokens for r in reqs[i::2]]
        assert all(o == outs[0] for o in outs), f"replicas disagree: {outs}"
    print(f"[scale-out] ok: {len(devices)} replicas, tokens/replica "
          f"{per_replica}, identical requests agree", flush=True)


def scale_up(cfg: ModelConfig) -> None:
    n = len(jax.devices())
    mesh = make_mesh(MeshConfig(tp=n))

    def serve(cfg, sharded: bool):
        params = transformer.init_params(cfg, jax.random.key(0))
        if sharded:
            params = jax.tree.map(
                jax.device_put, params, logical_to_sharding(
                    transformer.param_logical_axes(cfg), mesh))
        srv = PagedInferenceServer(params, cfg, GREEDY,
                                   mesh=mesh if sharded else None, **SRV_KW)
        return srv.generate(PROMPTS, max_new_tokens=16)

    f32 = dataclasses.replace(cfg, dtype="float32")
    with jax.default_matmul_precision("highest"):
        want = serve(f32, sharded=False)
        got = serve(f32, sharded=True)
    assert got == want, f"tp={n} differs from one chip:\n{got}\n{want}"
    print(f"[scale-up] ok: float32 tp={n} greedy output equals the "
          f"one-chip server's ({sum(map(len, got))} tokens)", flush=True)
    outs = serve(cfg, sharded=True)
    assert [len(o) for o in outs] == [16, 16], outs
    memory(f"tp={n} bfloat16 served")
    print(f"[scale-up] ok: bfloat16 tp={n} served "
          f"{sum(map(len, outs))} tokens", flush=True)


def main() -> None:
    enable_compile_cache()
    require_tpu("multichip")
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, "configs", "llama3_2_1b.json")) as f:
        cfg = from_json(ModelConfig, json.load(f)["model"])
    cfg = dataclasses.replace(cfg, num_layers=DEPTH,
                              decode_attention_impl="pallas")
    print(f"[multichip] Llama-3.2-1B widths, {DEPTH} of 16 layers, "
          f"{len(jax.devices())} devices", flush=True)
    scale_out(cfg)
    scale_up(cfg)
    print("[multichip] all ok", flush=True)


if __name__ == "__main__":
    main()
