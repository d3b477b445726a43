"""Race/collective sanitizers: shard_map vma checking (always on in the
ring/pipeline wrappers) and the mesh-aware deadlock watchdog."""

import time

import jax
import jax.numpy as jnp
import pytest
from jax import lax
from jax.sharding import PartitionSpec as P

from cloud_server_tpu.config import MeshConfig
from cloud_server_tpu.parallel.mesh import make_mesh
from cloud_server_tpu.utils.failure import CollectiveWatchdog


def test_check_vma_catches_unvaried_carry(devices8):
    """The sanitizer the ring/pipeline wrappers run under (check_vma=True)
    must reject a scan whose carry hides a device-varying value behind an
    unvaried type — the class of bug where per-device state silently
    diverges (a data race across the mesh)."""
    mesh = make_mesh(MeshConfig(sp=8))

    def racy(x):
        def body(carry, _):
            # carry starts unvaried but accumulates device-varying data
            return carry + x.sum(), None
        out, _ = lax.scan(body, jnp.zeros(()), None, length=2)
        return out[None]

    with pytest.raises(Exception, match="vary|varying|pvary"):
        jax.shard_map(racy, mesh=mesh, in_specs=(P("sp"),),
                      out_specs=P("sp"), check_vma=True)(
            jnp.arange(8.0))


def test_ring_and_pipeline_run_under_check_vma(devices8):
    """The production wrappers hardcode check_vma=True; a smoke run proves
    the shipped collectives are vma-clean (regression guard: r1 shipped
    them with check_vma=False and they did not pass)."""
    import functools

    from cloud_server_tpu.parallel.pipeline import pipeline_spmd
    from cloud_server_tpu.parallel.ring_attention import (
        ring_attention_sharded)

    mesh = make_mesh(MeshConfig(fsdp=4, sp=2))
    q = jax.random.normal(jax.random.key(0), (4, 32, 4, 8), jnp.float32)
    out = ring_attention_sharded(q, q, q, mesh)
    assert out.shape == q.shape

    mesh2 = make_mesh(MeshConfig(pp=4, fsdp=2))
    micro = jax.random.normal(jax.random.key(3), (4, 2, 8), jnp.float32)
    stage_params = jnp.tile(
        jax.random.normal(jax.random.key(4), (1, 8, 8), jnp.float32),
        (4, 1, 1))

    def stage_fn(sp_, x):
        return jnp.tanh(x @ sp_[0])

    pipe = jax.shard_map(
        functools.partial(pipeline_spmd, stage_fn=stage_fn),
        mesh=mesh2, in_specs=(P("pp"), P(None, ("dp", "fsdp"))),
        out_specs=P(None, ("dp", "fsdp")), check_vma=True)
    assert pipe(stage_params, micro).shape == micro.shape


def test_collective_watchdog_names_comm_axes(devices8, capsys):
    mesh = make_mesh(MeshConfig(fsdp=4, sp=2))
    fired = []
    dog = CollectiveWatchdog(mesh, timeout_s=0.2, per_axis_s=0.05,
                             on_hang=fired.append, poll_s=0.05)
    # timeout extended once per comm-active axis (fsdp, sp)
    assert dog.timeout_s == pytest.approx(0.2 + 2 * 0.05)
    assert dog.comm_axes == {"fsdp": 4, "sp": 2}
    with dog:
        dog.beat()
        deadline = time.monotonic() + 5.0
        while not dog.fired and time.monotonic() < deadline:
            time.sleep(0.05)
    assert dog.fired and fired
    err = capsys.readouterr().err
    assert "collective deadlock" in err
    assert "fsdp" in err and "sp" in err


def test_collective_watchdog_disarmed_until_first_beat(devices8):
    mesh = make_mesh(MeshConfig(fsdp=8))
    fired = []
    with CollectiveWatchdog(mesh, timeout_s=0.1, per_axis_s=0.0,
                            on_hang=fired.append, poll_s=0.02):
        time.sleep(0.4)  # long "compile" before any beat
    assert not fired
