"""Ahead-of-time compiles of `moe_mlp`'s sorted, dropless dispatch for the
v5e at the cell's widths: a tiling that Mosaic refuses (rows not tiled,
fast memory overrun) shows here, on the CPU, not in a chip run
(`on-chip-measurement` guide, section 2; `test_aot_v5e.py` does the same
for the whole step program and the paged kernel).

The topology is described inside a fixture, never at import: only one
process may hold the TPU's library.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from cellbench import run, serve

CELL = "mixtral-8x7b.batch-closed"


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


# a group of two, four and eight 256-token chunks
@pytest.mark.parametrize("tokens", [512, 1024, 2048])
def test_grouped_dispatch_compiles_for_v5e(tokens, one_chip, monkeypatch):
    from cloud_server_tpu.models import moe
    _, _, cfg_file = run.load_cell(run.load_benchmark(), CELL)
    mcfg = serve.model_config(cfg_file)
    # the compiled kernel, not `lax.ragged_dot`: the code under test asks
    # the backend, so the test answers for it
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    n, e, d, f = (mcfg.num_layers, mcfg.num_experts, mcfg.embed_dim,
                  mcfg.mlp_dim)
    dtype = jnp.dtype(mcfg.dtype)

    def abstract(*shape):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    layers = {"router": abstract(n, d, e), "w_gate": abstract(n, e, d, f),
              "w_up": abstract(n, e, d, f), "w_down": abstract(n, e, f, d)}

    def last_layer(x, layers):
        lp = jax.tree.map(lambda w: w[n - 1], layers)
        return moe.moe_mlp(x, lp, mcfg, (layers, n - 1))[0]

    compiled = jax.jit(last_layer).lower(
        abstract(tokens // 256, 256, d), layers).compile()
    text = compiled.as_text()
    # gate, up and down: three kernels over T * k rows, and no einsum over
    # the dense dispatch's E * T
    assert text.count('custom_call_target="tpu_custom_call"') == 3
    rows = tokens * mcfg.num_experts_per_token
    assert f"bf16[{rows},{f}]" in text
    assert f"[{e},{tokens},{f}]" not in text
    # the kernels read the layer's experts where they lie in the stack:
    # no copy of them (2.6 GiB) among the temporaries
    assert compiled.memory_analysis().temp_size_in_bytes < 0.5 * 2**30
