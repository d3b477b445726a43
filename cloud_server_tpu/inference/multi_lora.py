"""Multi-LoRA serving: many adapters live on one base model, selected
PER REQUEST (cf. vLLM's multi-LoRA, re-built for XLA's static shapes).

Design: all registered adapters stack into one device tensor per target
— A: (N+1, L, fan_in, r_max), B: (N+1, L, r_max, fan_out) — with row 0
the NULL adapter (zeros: delta exactly 0) and ranks zero-padded to the
set's max (padding contributes nothing to A@B). Each slot of the
continuous batch carries an adapter id; every dispatch gathers its
per-row (a, b, scale) and the model applies the low-rank delta at the
same points a merged weight would land
(`transformer.lora_row_delta` — before rope for wq/wk, on the flattened
head output for wo, around swiglu for the mlp). Unadapted slots ride
id 0 and are bit-identical to the base model; mixing adapters in one
batch costs two thin einsums per target per layer, no recompiles, no
weight swapping.

Dense targets only (wq/wk/wv/wo/w_gate/w_up/w_down); adapters may
target different subsets and use different ranks/alphas.

Reference parity note: view-sonic/Cloud-Server @ v0 is an empty tree
(SURVEY.md); this subsystem is part of the re-scoped build inventory
(multi-adapter serving).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from cloud_server_tpu.config import ModelConfig
from cloud_server_tpu.models.lora import _DENSE_TARGETS, LoRAConfig


class AdapterSet:
    """Registry + stacked device tensors for per-request LoRA serving.

    `add` returns the adapter id (>= 1; 0 is the null adapter) and
    restacks the device tensors — a rare, admission-path operation.
    """

    def __init__(self, model_cfg: ModelConfig, mesh=None):
        self.model_cfg = model_cfg
        self.mesh = mesh
        self._names: list[str] = []
        self._ids: dict[str, int] = {}
        self._raw: list[tuple[dict, LoRAConfig]] = []
        self.stacks: dict | None = None  # {target: {"a","b"}} device
        self.scales: jnp.ndarray | None = None  # (cap,) f32
        # admission-cost amortization: stacks carry CAPACITY rows
        # (geometric growth) and a rank headroom, so a typical add is
        # one device row-scatter of the new adapter — not an O(total
        # adapter bytes) host restack + re-upload per registration
        self._cap = 0     # allocated adapter rows incl. the null row
        self._r_cap = 0   # allocated rank (stacks' r dimension)
        self.rebuilds = 0  # full restacks performed (observability)

    def __len__(self) -> int:
        return len(self._names)

    @property
    def names(self) -> list[str]:
        return list(self._names)

    def adapter_id(self, name: str) -> int | None:
        return self._ids.get(name)

    def add(self, name: str, lora_params: dict, lora_cfg: LoRAConfig
            ) -> int:
        if name in self._ids:
            raise ValueError(f"adapter {name!r} already registered")
        from cloud_server_tpu.models.transformer import one_stack
        one_stack(self.model_cfg, "a per-request adapter")
        bad = set(lora_cfg.targets) - set(_DENSE_TARGETS)
        if bad:
            raise ValueError(
                f"multi-LoRA serving supports dense targets only; "
                f"{sorted(bad)} are not servable per-request")
        layers = lora_params.get("layers", lora_params)
        missing = set(lora_cfg.targets) - set(layers)
        if missing:
            raise ValueError(f"adapter {name!r} missing params for "
                             f"targets {sorted(missing)}")
        # validate against the MODEL's shapes: a self-consistent but
        # wrong-sized adapter would otherwise register fine and explode
        # (or kill the scheduler) at the first dispatch
        from cloud_server_tpu.models.lora import _split_dims
        from cloud_server_tpu.models.transformer import param_shapes
        shapes = param_shapes(self.model_cfg)["layers"]
        for t in lora_cfg.targets:
            L = shapes[t][0]
            _, fan_in, fan_out = _split_dims(t, shapes[t])
            a = np.asarray(layers[t]["a"])
            b = np.asarray(layers[t]["b"])
            want_a = (L, fan_in, lora_cfg.rank)
            want_b = (L, lora_cfg.rank, fan_out)
            if a.shape != want_a or b.shape != want_b:
                raise ValueError(
                    f"adapter {name!r} target {t!r}: a{a.shape}/"
                    f"b{b.shape} do not match the base model's "
                    f"{want_a}/{want_b}")
        # TRANSACTIONAL: validation above is complete, so the fast path
        # can mutate safely; the rebuild path builds from a candidate
        # list first — a failure leaves the registry untouched (a
        # half-registered name would pass submit()'s validation and
        # clamp-gather some other adapter's weights)
        new_id = len(self._raw) + 1
        raw2 = self._raw + [(layers, lora_cfg)]
        fits = (self.stacks is not None
                and new_id + 1 <= self._cap
                and lora_cfg.rank <= self._r_cap)
        if fits:
            self._write_row(new_id, layers, lora_cfg)
        else:
            try:
                self._rebuild(raw2)  # with geometric headroom
            except (ValueError, TypeError) as exc:
                raise ValueError(
                    f"adapter {name!r} has inconsistent shapes: {exc}"
                ) from exc
        self._names.append(name)
        self._ids[name] = new_id  # id 0 = null adapter
        self._raw = raw2
        return new_id

    def _put(self, x):
        if self.mesh is None:
            return jnp.asarray(x)
        from jax.sharding import NamedSharding, PartitionSpec as P
        return jax.device_put(jnp.asarray(x),
                              NamedSharding(self.mesh, P()))

    def _zero_stack(self, t: str) -> dict[str, jnp.ndarray]:
        """Capacity-sized all-zero (= null-adapter) stacks for one
        target, shaped from the base model."""
        from cloud_server_tpu.models.lora import _split_dims
        from cloud_server_tpu.models.transformer import param_shapes
        shape = param_shapes(self.model_cfg)["layers"][t]
        L = shape[0]
        _, fan_in, fan_out = _split_dims(t, shape)
        return {"a": self._put(jnp.zeros((self._cap, L, fan_in,
                                          self._r_cap), jnp.float32)),
                "b": self._put(jnp.zeros((self._cap, L, self._r_cap,
                                          fan_out), jnp.float32))}

    def _write_row(self, i: int, layers: dict, cfg: LoRAConfig) -> None:
        """O(one adapter) admission: scatter the new adapter's rows into
        the device stacks (a target nobody used yet gets a fresh zero
        stack first — earlier adapters' rows in it are correctly the
        null adapter). The H2D traffic is the new adapter's bytes; the
        on-device buffer copy rides HBM bandwidth.

        Built on COPIES and swapped in at the end: the scheduler thread
        may be flattening device_args()' current dict for a dispatch
        right now (it holds _step_lock, not the registry lock), so the
        live containers must never mutate under a reader."""
        stacks = {t: dict(ab) for t, ab in self.stacks.items()}
        for t in cfg.targets:
            ab = stacks.get(t) or self._zero_stack(t)
            a = jnp.asarray(np.asarray(layers[t]["a"], np.float32))
            b = jnp.asarray(np.asarray(layers[t]["b"], np.float32))
            stacks[t] = {
                "a": ab["a"].at[i, :, :, :cfg.rank].set(a),
                "b": ab["b"].at[i, :, :cfg.rank, :].set(b)}
        scales = self.scales.at[i].set(cfg.scale)
        self.stacks = stacks
        self.scales = scales

    def _rebuild(self, raw) -> None:
        """Full restack (first add, capacity exhausted, or a rank above
        the allocated headroom): capacity doubles so rebuilds amortize
        to O(1) restacked rows per add."""
        self.rebuilds += 1
        r_max = max(cfg.rank for _, cfg in raw)
        targets = sorted({t for _, cfg in raw for t in cfg.targets})
        n = len(raw) + 1
        cap = r_cap = 1
        while cap < max(n, 4):
            cap *= 2
        while r_cap < r_max:
            r_cap *= 2
        stacks: dict[str, dict[str, np.ndarray]] = {}
        for t in targets:
            # shapes from the first adapter carrying the target
            ref = next(layers[t] for layers, cfg in raw
                       if t in cfg.targets)
            L, fan_in, _ = np.asarray(ref["a"]).shape
            fan_out = np.asarray(ref["b"]).shape[-1]
            a = np.zeros((cap, L, fan_in, r_cap), np.float32)
            b = np.zeros((cap, L, r_cap, fan_out), np.float32)
            for i, (layers, cfg) in enumerate(raw, start=1):
                if t in cfg.targets:
                    a[i, :, :, :cfg.rank] = np.asarray(layers[t]["a"],
                                                       np.float32)
                    b[i, :, :cfg.rank, :] = np.asarray(layers[t]["b"],
                                                       np.float32)
            stacks[t] = {"a": a, "b": b}
        scales = np.zeros((cap,), np.float32)
        scales[0] = 1.0
        scales[1:n] = [cfg.scale for _, cfg in raw]
        self.stacks = jax.tree.map(self._put, stacks)
        self.scales = self._put(scales)
        self._cap = cap
        self._r_cap = r_cap

    def device_args(self):
        """(stacks, scales) to pass into a dispatch (None when empty)."""
        if not self._raw:
            return None
        return (self.stacks, self.scales)


def layer_lora(adapters, aid: jnp.ndarray, layer_idx: int):
    """Per-layer, per-row adapter gather for `transformer.*(lora=...)`.

    adapters: (stacks, scales) from AdapterSet.device_args; aid: (B,)
    int32 adapter ids. Returns {target: (a (B, fan_in, r),
    b (B, r, fan_out), scale (B,))}."""
    if adapters is None:
        return None
    stacks, scales = adapters
    s = scales[aid]
    return {t: (ab["a"][aid, layer_idx], ab["b"][aid, layer_idx], s)
            for t, ab in stacks.items()}
