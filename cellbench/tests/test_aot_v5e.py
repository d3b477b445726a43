"""Ahead-of-time compiles of each serving cell's largest program for the
v5e, with `memory_analysis()` printed: they settle depth and pool sizes
without chip minutes (`on-chip-measurement` guide, section 2).

The largest program of a cell is the mixed step of its widest warm-up
group (the most prompts, of the longest length, beside 64 decode rows). Its argument
shapes are captured from a tiny-width CPU server driven the same way;
the weights, the cache pools and the model configuration are then
swapped for the real ones as shapes on a described v5e device.

The topology is described inside a fixture (never at import), all in
this one file: only one process may hold the TPU's library.
"""

import json
import os
import time

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from cellbench import rehearse, run, serve, weights as wmod

HBM_LIMIT = 15.75 * 2**30  # what a v5e chip reports as bytes_limit (PR 21)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def capture_largest_call(wl, cfg_file, monkeypatch):
    """Drive a tiny CPU server through the widest warm-up group and
    return the (args, kwargs) of its `_mixed_step` call."""
    from cloud_server_tpu.inference import paged_server
    from cloud_server_tpu.models import moe, transformer
    calls = []
    real = paged_server._mixed_step

    def recorder(*args, **kwargs):
        calls.append((args, kwargs))
        return real(*args, **kwargs)

    monkeypatch.setattr(paged_server, "_mixed_step", recorder)
    mcfg = serve.model_config(cfg_file, rehearse.TINY)
    module = moe if mcfg.num_experts >= 2 else transformer
    w = wmod.make_weights(module.param_shapes(mcfg), 0, mcfg.param_dtype)
    srv = serve.build_server(mcfg, w, wl["server"], 16)
    groups = wl["warmup"]["groups"]
    g = max(groups, key=int)
    plan = dict(wl["warmup"], groups={g: [max(groups[g])]}, keep=[])
    serve.warm_up(srv, plan, mcfg.vocab_size, 0, serve.CompileLog(), 600.0)
    # the first-chunk program of the widest group: most rows, widest
    # prompt bucket
    return max(calls, key=lambda c: (c[0][2].shape[0] * c[0][2].shape[1],
                                     c[0][8].shape[1], c[1]["n_rounds"]))


@pytest.mark.parametrize("cell", [
    c["name"] for c in run.load_benchmark()["workloads"]])
def test_largest_program_fits_v5e(cell, one_chip, monkeypatch, capsys):
    from cloud_server_tpu.inference import paged_engine, paged_server
    from cloud_server_tpu.models import moe, transformer
    bench = run.load_benchmark()
    _, wl, cfg_file = run.load_cell(bench, cell)
    args, kwargs = capture_largest_call(wl, cfg_file, monkeypatch)
    monkeypatch.undo()
    # compiled kernels, not interpreted ones: the code under test asks
    # the backend, so the test answers for it
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    mcfg = serve.model_config(cfg_file)
    module = moe if mcfg.num_experts >= 2 else transformer

    def abstract(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    params = jax.tree.map(
        abstract, jax.eval_shape(
            lambda: wmod.make_weights(module.param_shapes(mcfg), 0,
                                      mcfg.param_dtype)))
    o = wl["server"]
    pages_per_slot = args[1]["pools"]["tables"].shape[1] \
        if "tables" in args[1]["pools"] else None
    cache = jax.eval_shape(lambda: paged_engine.init_paged_cache(
        mcfg, num_pages=o["num_pages"], page_size=o["page_size"],
        batch=o["max_slots"],
        max_pages_per_slot=pages_per_slot or o["max_len"] // o["page_size"]))
    state = dict(args[1])
    state["pools"] = paged_server._split_cache(cache)
    state = jax.tree.map(abstract, state)
    rest = [None if a is None else jax.tree.map(
        lambda x: abstract(jnp.asarray(x)), a) for a in args[2:]]
    kw = dict(kwargs, cfg=mcfg)
    kw = {k: (jax.tree.map(lambda x: abstract(jnp.asarray(x)), v)
              if k in ("grammar", "lora", "aid_g", "aid_b", "draft_params")
              and v is not None else v) for k, v in kw.items()}
    t0 = time.monotonic()
    lowered = paged_server._mixed_step.lower(params, state, *rest, **kw)
    t1 = time.monotonic()
    compiled = lowered.compile()
    t2 = time.monotonic()
    ma = compiled.memory_analysis()
    weights_b = sum(x.size * x.dtype.itemsize
                    for x in jax.tree.leaves(params))
    state_b = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(state))
    total = weights_b + state_b + ma.temp_size_in_bytes
    with capsys.disabled():
        print("\n" + json.dumps({
            "cell": cell, "layers": mcfg.num_layers,
            "num_pages": o["num_pages"], "chunk": list(args[2].shape),
            "prompt_rows": list(args[8].shape),
            "weights_GiB": round(weights_b / 2**30, 3),
            "state_GiB": round(state_b / 2**30, 3),
            "temp_GiB": round(ma.temp_size_in_bytes / 2**30, 3),
            "argument_GiB": round(ma.argument_size_in_bytes / 2**30, 3),
            "output_GiB": round(ma.output_size_in_bytes / 2**30, 3),
            "alias_GiB": round(ma.alias_size_in_bytes / 2**30, 3),
            "weights_state_temp_GiB": round(total / 2**30, 3),
            "limit_GiB": HBM_LIMIT / 2**30,
            "trace_lower_s_here": round(t1 - t0, 1),
            "compile_s_here": round(t2 - t1, 1)}))
    assert "tpu_custom_call" in compiled.as_text()
    assert total < HBM_LIMIT


@pytest.mark.parametrize("config", [
    c["name"] for c in run.load_benchmark()["configs"]])
def test_weights_from_seed_fit_v5e(config, one_chip, capsys):
    """The one jitted call that makes the weights: its temporaries beside
    its own output must fit the chip."""
    from cloud_server_tpu.models import moe, transformer
    with open(os.path.join(run.HERE, "configs", config + ".json")) as f:
        mcfg = serve.model_config(json.load(f))
    module = moe if mcfg.num_experts >= 2 else transformer
    key = jax.ShapeDtypeStruct((), jax.eval_shape(
        lambda: wmod.seed_key(0)).dtype, sharding=one_chip)
    compiled = wmod._make.lower(
        key, wmod.flat_shapes(module.param_shapes(mcfg)),
        jnp.dtype(mcfg.param_dtype)).compile()
    ma = compiled.memory_analysis()
    total = ma.output_size_in_bytes + ma.temp_size_in_bytes
    with capsys.disabled():
        print("\n" + json.dumps({
            "config": config,
            "output_GiB": round(ma.output_size_in_bytes / 2**30, 3),
            "temp_GiB": round(ma.temp_size_in_bytes / 2**30, 3)}))
    assert total < HBM_LIMIT
