"""Ahead-of-time compiles of `moe_mlp`'s sorted dispatch for the v5e at
both cells' widths and at the token counts their joined calls have, with
the rows of the buffer the three kernels are handed: the packed rows'
whole tiles, or the longer buffer in which every expert's rows start on
a row tile, as `moe._sorted_buffer_rows` decides from the shape and the
widths (PERF.md, PR 39). `test_mixtral_grouped_v5e.py` beside this file
pins the packed buffer (`bf16[T * k, F]`) at 512, 1,024 and 2,048 tokens,
where the buffer is the longer one since PR 39: its three cases fail
until a `benchmark` PR repoints them, and these take their place.

The topology is described inside a fixture, never at import: only one
process may hold the TPU's library.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from cellbench import families, run

CELLS = [c["name"] for c in run.load_benchmark()["workloads"]]
TILE = 256


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


# (cell, tokens of a joined call, row tiles of the buffer): 8 x 2 at 320
# and 576 tokens has 8 visits for the packed 10 and 12 and gets 9 tiles,
# at 1,088 it has 16 for 16 and stays packed; 64 x 6 stays packed up to
# 1,088 tokens and gets 64 + 8 tiles at 2,112
@pytest.mark.parametrize("cell,tokens,tiles", [
    (CELLS[0], 320, 9), (CELLS[0], 576, 9), (CELLS[0], 1088, 9),
    (CELLS[0], 2112, 17), (CELLS[1], 576, 14), (CELLS[1], 1088, 26),
    (CELLS[1], 2112, 72)])
def test_sorted_dispatch_compiles_for_v5e(cell, tokens, tiles, one_chip,
                                          monkeypatch):
    from cloud_server_tpu.models import moe
    _, _, cfg_file = run.load_cell(run.load_benchmark(), cell)
    mcfg = families.of(cfg_file).model_config(cfg_file)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    n, e, d, f = (mcfg.num_layers, mcfg.num_experts, mcfg.embed_dim,
                  mcfg.mlp_dim)
    k = mcfg.num_experts_per_token
    dtype = jnp.dtype(mcfg.dtype)
    assert moe._GMM_ROWS == TILE
    assert moe._sorted_buffer_rows(tokens * k, mcfg) == tiles * TILE
    assert tiles >= -(-tokens * k // TILE)

    def abstract(*shape):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    layers = {"router": abstract(n, d, e), "w_gate": abstract(n, e, d, f),
              "w_up": abstract(n, e, d, f), "w_down": abstract(n, e, f, d)}

    def last_layer(x, layers):
        lp = jax.tree.map(lambda w: w[n - 1], layers)
        return moe.moe_mlp(x, lp, mcfg, (layers, n - 1))[0]

    compiled = jax.jit(last_layer).lower(
        abstract(1, tokens, d), layers).compile()
    text = compiled.as_text()
    # gate, up and down: three kernels over the buffer's rows, and no
    # einsum over the dense dispatch's E * T
    assert text.count('custom_call_target="tpu_custom_call"') == 3
    assert f"bf16[{tiles * TILE},{f}]" in text
    assert f"[{e},{tokens},{f}]" not in text
    # the kernels read the layer's experts where they lie in the stack:
    # no copy of them among the temporaries
    assert compiled.memory_analysis().temp_size_in_bytes < 0.5 * 2**30
