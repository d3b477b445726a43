"""Test harness: force an 8-device virtual CPU platform BEFORE jax imports.

All parallelism tests (dp/fsdp/tp/sp/ep/pp) run against this virtual mesh
with the pallas kernels interpreted; the chip runs chip_smoke.py, the
CST_TPU_TESTS=1 kernel tests and bench.py.
"""

import os

# CST_TPU_TESTS=1 keeps the real backend so the on-chip tests (marked
# `on_tpu`: the kernels compiled by Mosaic against their XLA references)
# run:  CST_TPU_TESTS=1 python -m pytest tests/ -m on_tpu
# Run only those tests this way — the rest of the suite assumes the
# 8-device virtual CPU mesh. Default (unset): virtual CPU platform, and
# the on_tpu tests are deselected.
_USE_TPU = os.environ.get("CST_TPU_TESTS") == "1"

# The entry points (generate/train/evaluate main) place a persistent
# compile cache in the checkout; tests call them in-process and in
# subprocesses, and must neither write one nor load programs from one.
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

if not _USE_TPU:
    os.environ["JAX_PLATFORMS"] = "cpu"
    _flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in _flags:
        os.environ["XLA_FLAGS"] = (
            _flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402
import pytest  # noqa: E402

jax.config.update("jax_threefry_partitionable", True)
# This JAX build defaults matmuls to bf16-style passes even on CPU; tests
# verify numerics, so force full f32 accumulation here (TPU prod path keeps
# the default and runs bf16 on the MXU).
jax.config.update("jax_default_matmul_precision", "highest")


def pytest_collection_modifyitems(config, items):
    """Mark the measured-slow tests (tests/slow_tests.txt, regenerated
    from `pytest --durations`) so the default run is a <6-minute fast set
    that still covers every parallelism family; `run_tests.sh --all`
    runs everything. Unlisted (new) tests default to fast until
    re-measured."""
    if not _USE_TPU:
        # on-chip tests cannot run here: deselect them (they run through
        # chip_smoke.py, or CST_TPU_TESTS=1 ... -m on_tpu, on the chip)
        on_chip = [it for it in items if "on_tpu" in it.keywords]
        if on_chip:
            config.hook.pytest_deselected(items=on_chip)
            items[:] = [it for it in items if "on_tpu" not in it.keywords]
    slow_file = os.path.join(os.path.dirname(__file__), "slow_tests.txt")
    try:
        with open(slow_file) as f:
            entries = [line.strip() for line in f if line.strip()]
    except OSError:
        return
    slow_ids = {e for e in entries if not e.endswith("*")}
    slow_prefixes = tuple(e[:-1] for e in entries if e.endswith("*"))
    for item in items:
        nodeid = item.nodeid.replace(os.sep, "/")
        if nodeid in slow_ids or (slow_prefixes
                                  and nodeid.startswith(slow_prefixes)):
            item.add_marker(pytest.mark.slow)


@pytest.fixture(scope="session")
def devices8():
    devs = jax.devices()
    assert len(devs) == 8, f"expected 8 virtual devices, got {len(devs)}"
    return devs


@pytest.fixture(autouse=True)
def _clear_registered_mesh():
    """Test isolation for the process-wide mesh: a test that builds a
    sharded mesh (make_mesh registers it globally) must not leak it into a
    later test's single-device jits — `constrain` would anchor their
    activations to a mesh whose axis sizes don't divide the tiny test
    shapes."""
    yield
    from cloud_server_tpu.parallel.mesh import set_current_mesh
    set_current_mesh(None)
