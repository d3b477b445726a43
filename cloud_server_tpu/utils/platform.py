"""Where the program runs: the persistent compile cache, the device
line every entry point prints at start-up, and the refusal to measure
without a chip.

Tests run on the CPU (`JAX_PLATFORMS=cpu`, kernels interpreted); every
time, rate or utilization comes from a TPU. Entry points call
`enable_compile_cache()` first and `device_line()` next, so a run's log
always names the device it ran on before any work starts.
"""

from __future__ import annotations

import json
import os
import sys

import jax

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
# One fixed path inside the checkout (the directory is part of the
# cache key, so a directory that moves never hits). Ignored by git.
DEFAULT_COMPILE_CACHE_DIR = os.path.join(_REPO_ROOT, ".jax_cache")


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache somewhere stable and
    return the directory. Where `JAX_COMPILATION_CACHE_DIR` is set the
    caller placed the cache: JAX reads the variable itself and nothing
    is set in code. Otherwise the cache lives at one fixed path in the
    checkout. Call before the first compilation."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir",
                      DEFAULT_COMPILE_CACHE_DIR)
    return DEFAULT_COMPILE_CACHE_DIR


def device_info() -> dict:
    """The device as JAX reports it: platform, device_kind, count."""
    devices = jax.devices()
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices)}


def device_line(tag: str) -> dict:
    """Print `[tag] device: {...}` (one JSON object, stderr) and return
    the object. The start-up line of every entry point."""
    info = device_info()
    print(f"[{tag}] device: {json.dumps(info)}", file=sys.stderr,
          flush=True)
    return info


def require_tpu(tag: str) -> dict:
    """`device_line(tag)`, then SystemExit unless JAX found a TPU: a
    measurement path that finds no chip fails; it does not fall back to
    the CPU."""
    info = device_line(tag)
    if info["platform"] != "tpu":
        raise SystemExit(
            f"{tag} measures the TPU and found platform "
            f"{info['platform']!r} ({info['kind']}): refusing to run. "
            "Tests run on the CPU; timings come from the chip.")
    return info
