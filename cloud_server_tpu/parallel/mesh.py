"""Device mesh construction.

One canonical mesh for the whole framework, axes (dp, pp, fsdp, ep, sp, tp)
— see `MeshConfig`. On a real pod slice, `mesh_utils.create_device_mesh`
lays the logical mesh onto the physical ICI torus so the innermost axes
(tp, sp) get the shortest links; across slices/hosts the outer axes (dp, pp)
ride DCN. On CPU (tests / dry-run with --xla_force_host_platform_device_count)
there is no topology and `create_device_mesh` reshapes the device list as
is; on TPU devices a layout it cannot place is an error.
"""

from __future__ import annotations

import jax
from jax.experimental import mesh_utils
from jax.sharding import Mesh

from cloud_server_tpu.config import MeshConfig


_CURRENT_MESH: Mesh | None = None


def set_current_mesh(mesh: Mesh | None) -> Mesh | None:
    """Register the process-wide mesh (None clears it). Model code that
    needs mesh context outside an explicit shard_map (e.g.
    attention_impl="ring") reads it via `current_mesh()`."""
    global _CURRENT_MESH
    _CURRENT_MESH = mesh
    return mesh


def current_mesh() -> Mesh:
    if _CURRENT_MESH is None:
        raise RuntimeError(
            "no mesh registered — build one with make_mesh() (it registers "
            "itself) or call set_current_mesh()")
    return _CURRENT_MESH


def maybe_current_mesh() -> Mesh | None:
    """current_mesh() for callers that degrade gracefully without one
    (e.g. activation sharding anchors in model code)."""
    return _CURRENT_MESH


def kernel_mesh() -> Mesh | None:
    """The registered mesh if a compiled pallas kernel traced now must be
    wrapped in shard_map over it, else None. jit cannot partition a
    Mosaic kernel ("Mosaic kernels cannot be automatically partitioned"),
    so on TPU, under a mesh of more than one device, each device runs
    the kernel on its own block. Interpreted kernels (the CPU tests) are
    plain XLA ops and partition like any other."""
    mesh = _CURRENT_MESH
    if mesh is None or mesh.size == 1 or jax.default_backend() != "tpu":
        return None
    return mesh


def make_mesh(cfg: MeshConfig, devices=None) -> Mesh:
    """Build a named Mesh with canonical axis order from a MeshConfig.

    Axis sizes must multiply to the number of devices used. Axes of size 1
    are kept in the mesh (they are free) so sharding specs never need to
    special-case a missing axis.
    """
    if devices is None:
        devices = jax.devices()
    n = cfg.num_devices
    if n > len(devices):
        raise ValueError(
            f"MeshConfig wants {n} devices but only {len(devices)} available"
        )
    devices = devices[:n]
    shape = tuple(cfg.axis_sizes()[a] for a in MeshConfig.AXIS_ORDER)
    dev_array = mesh_utils.create_device_mesh(shape, devices=devices)
    return set_current_mesh(Mesh(dev_array, MeshConfig.AXIS_ORDER))


def mesh_for_devices(n_devices: int, *, tp: int = 1, sp: int = 1, pp: int = 1,
                     ep: int = 1, dp: int = 1) -> Mesh:
    """Convenience: put every explicitly-requested axis in place and absorb
    the remaining device count into fsdp."""
    used = tp * sp * pp * ep * dp
    if n_devices % used != 0:
        raise ValueError(f"{n_devices} devices not divisible by {used}")
    cfg = MeshConfig(dp=dp, pp=pp, fsdp=n_devices // used, ep=ep, sp=sp, tp=tp)
    return make_mesh(cfg)
