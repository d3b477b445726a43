"""LoRA fine-tuning: low-rank adapters over the dense or MoE LM.

Design (functional, jit-first):
  * Adapter params live BESIDE the frozen base in one pytree
    {"base": ..., "lora": {"layers": {target: {"a", "b"}}}} — one TrainState,
    one checkpoint, one sharded restore path; nothing else in the framework
    needs to know about LoRA.
  * The forward path *merges* W' = W + (alpha/r)·A@B per target and calls
    the base module unchanged (`merge_lora`), so every attention impl
    (xla/flash/ring), remat policy, and the inference engine work with
    adapters for free. The merge is a rank-r matmul per target — negligible
    next to the forward itself for r ≪ min(fan_in, fan_out).
  * The base is frozen two ways: `stop_gradient` in the loss (XLA dead-code
    eliminates the whole base backward pass) and an optimizer label mask
    (`param_labels`) that gives base params `optax.set_to_zero()` — so no
    Adam moments are allocated for them (the TrainState stays adapter-sized
    in optimizer memory, the point of LoRA at scale).
  * `export_merged` folds trained adapters back into plain base params for
    serving (the inference engine and server take them as-is).

A/B are stored flat — A: (L, fan_in, r), B: (L, r, fan_out) — replicated
across the mesh except the layer axis (they are tiny; sharding them would
only add collectives).
"""

from __future__ import annotations

import dataclasses
import json
import math
import os
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from cloud_server_tpu.config import ModelConfig
from cloud_server_tpu.models import transformer

# target name -> (stack axis names between the layer axis and fan-in,
# number of trailing output dims). Everything between the stack axes and
# the output dims is fan-in; adapters get one (A, B) pair per stack entry
# — for MoE expert weights (L, E, D, F) that means PER-EXPERT adapters
# A (L, E, D, r), B (L, E, r, F).
_DENSE_TARGETS: dict[str, tuple[tuple[str, ...], int]] = {
    "wq": ((), 2), "wk": ((), 2), "wv": ((), 2),  # (L, D, H, Dh)
    "wo": ((), 1),                                 # (L, H, Dh, D)
    "w_gate": ((), 1), "w_up": ((), 1),            # (L, D, F)
    "w_down": ((), 1),                             # (L, F, D)
}
_MOE_TARGETS: dict[str, tuple[tuple[str, ...], int]] = {
    "wq": ((), 2), "wk": ((), 2), "wv": ((), 2),
    "wo": ((), 1),
    "router": ((), 1),                             # (L, D, E)
    "w_gate": (("experts",), 1),                   # (L, E, D, F)
    "w_up": (("experts",), 1),
    "w_down": (("experts",), 1),                   # (L, E, F, D)
}
_TARGETS = {**_DENSE_TARGETS, **_MOE_TARGETS}  # union, for validation

DEFAULT_TARGETS = ("wq", "wk", "wv", "wo")


def _target_table(base_module) -> dict[str, tuple[tuple[str, ...], int]]:
    if base_module is transformer:
        return _DENSE_TARGETS
    from cloud_server_tpu.models import moe
    if base_module is moe:
        return _MOE_TARGETS
    raise NotImplementedError(
        f"LoRA target table not defined for module {base_module!r}")


@dataclasses.dataclass(frozen=True)
class LoRAConfig:
    rank: int = 8
    alpha: float = 16.0
    targets: tuple[str, ...] = DEFAULT_TARGETS

    def __post_init__(self):
        unknown = set(self.targets) - set(_TARGETS)
        if unknown:
            raise ValueError(f"unknown LoRA targets {sorted(unknown)}; "
                             f"valid: {sorted(_TARGETS)}")
        if self.rank < 1:
            raise ValueError(f"rank must be >= 1, got {self.rank}")

    @property
    def scale(self) -> float:
        return self.alpha / self.rank


_SIDECAR = "lora_config.json"


def save_lora_config(checkpoint_dir: str | os.PathLike,
                     cfg: LoRAConfig) -> None:
    """Persist the adapter hyperparameters next to the checkpoint. alpha
    only enters the math at merge time, so an unrecorded training alpha
    would silently rescale the served model."""
    os.makedirs(checkpoint_dir, exist_ok=True)
    with open(os.path.join(os.fspath(checkpoint_dir), _SIDECAR), "w") as f:
        json.dump(dataclasses.asdict(cfg), f)


def load_lora_config(checkpoint_dir: str | os.PathLike) -> LoRAConfig | None:
    path = os.path.join(os.fspath(checkpoint_dir), _SIDECAR)
    if not os.path.exists(path):
        return None
    with open(path) as f:
        data = json.load(f)
    data["targets"] = tuple(data["targets"])
    return LoRAConfig(**data)


def add_lora_args(parser) -> None:
    """The one definition of the --lora-* CLI surface (train + generate)."""
    parser.add_argument("--lora-rank", type=int, default=0, metavar="R",
                        help="rank-R LoRA adapters (0 = no LoRA)")
    parser.add_argument("--lora-alpha", type=float, default=16.0)
    parser.add_argument("--lora-targets", default=",".join(DEFAULT_TARGETS),
                        help="comma-separated projection names to adapt")


def lora_config_from_args(args) -> LoRAConfig | None:
    if args.lora_rank <= 0:
        return None
    return LoRAConfig(rank=args.lora_rank, alpha=args.lora_alpha,
                      targets=tuple(args.lora_targets.split(",")))


def _split_dims(name: str, shape: tuple[int, ...], table=None
                ) -> tuple[tuple[int, ...], int, int]:
    """(stack dims, fan_in, fan_out) of a stacked (L, *stack, ...) base
    weight, fan-in/out flattened."""
    stack_axes, n_out = (table or _DENSE_TARGETS)[name]
    n_stack = len(stack_axes)
    stack = shape[1:1 + n_stack]
    fan_in = math.prod(shape[1 + n_stack:-n_out])
    fan_out = math.prod(shape[-n_out:])
    return stack, fan_in, fan_out


def init_lora_params(model_cfg: ModelConfig, lora_cfg: LoRAConfig,
                     rng: jax.Array, base_module=transformer) -> dict:
    """A ~ N(0, 1/fan_in), B = 0 — the adapted delta starts at exactly 0.
    Stacked targets (MoE expert weights) get one adapter pair per stack
    entry: A (L, E, fan_in, r), B (L, E, r, fan_out)."""
    table = _target_table(base_module)
    bad = set(lora_cfg.targets) - set(table)
    if bad:
        raise ValueError(
            f"LoRA targets {sorted(bad)} do not exist for this model "
            f"family (valid here: {sorted(table)})")
    transformer.one_stack(model_cfg, "a LoRA adapter")
    shapes = base_module.param_shapes(model_cfg)["layers"]
    keys = jax.random.split(rng, len(lora_cfg.targets))
    out: dict[str, Any] = {"layers": {}}
    for key, name in zip(keys, sorted(lora_cfg.targets)):
        L = shapes[name][0]
        stack, fan_in, fan_out = _split_dims(name, shapes[name], table)
        a = (jax.random.truncated_normal(
            key, -2.0, 2.0, (L, *stack, fan_in, lora_cfg.rank),
            jnp.float32)
            / math.sqrt(fan_in)).astype(jnp.dtype(model_cfg.param_dtype))
        b = jnp.zeros((L, *stack, lora_cfg.rank, fan_out),
                      jnp.dtype(model_cfg.param_dtype))
        out["layers"][name] = {"a": a, "b": b}
    return out


def lora_logical_axes(model_cfg: ModelConfig, lora_cfg: LoRAConfig,
                      base_module=transformer) -> dict:
    table = _target_table(base_module)
    out = {}
    for name in sorted(lora_cfg.targets):
        stack_axes = table[name][0]
        out[name] = {"a": ("layers", *stack_axes, None, None),
                     "b": ("layers", *stack_axes, None, None)}
    return {"layers": out}


def merge_lora(base: dict, lora: dict, lora_cfg: LoRAConfig,
               dtype=None, base_module=transformer) -> dict:
    """base params + scale·A@B on each target; structure-preserving and
    shape-generic (stacked targets merge per stack entry — per expert for
    MoE). Family validation happens at init; `base_module` is accepted
    for API symmetry."""
    del base_module
    merged_layers = dict(base["layers"])
    for name, ab in lora["layers"].items():
        w = base["layers"][name]
        compute = jnp.dtype(dtype) if dtype is not None else w.dtype
        delta = jnp.einsum(
            "...ir,...ro->...io", ab["a"].astype(compute),
            ab["b"].astype(compute)) * lora_cfg.scale
        merged_layers[name] = (
            w + delta.reshape(w.shape).astype(w.dtype))
    out = dict(base)
    out["layers"] = merged_layers
    return out


def export_merged(params: dict, lora_cfg: LoRAConfig,
                  base_module=transformer) -> dict:
    """{"base","lora"} TrainState params -> plain servable base params."""
    return merge_lora(params["base"], params["lora"], lora_cfg,
                      base_module=base_module)


def make_lora_module(lora_cfg: LoRAConfig, base_module=transformer,
                     base_params: dict | None = None):
    """Build a loss-function module (same protocol as `models.transformer`)
    that trains only adapters.

    base_params: pretrained weights to adapt (the fine-tuning case). None
    random-inits the base — useful for tests and API symmetry only.

    The returned namespace provides `init_params`, `param_logical_axes`,
    `param_labels` (optimizer freeze mask) and `next_token_loss`, so it
    drops into `make_train_step` / `train_loop` / `Checkpointer` via their
    `loss_fn_module` argument — the same extension seam `models.moe` uses.
    """
    _target_table(base_module)  # raises for unknown module families

    class module:
        lora_config = lora_cfg

        @staticmethod
        def init_params(cfg: ModelConfig, rng: jax.Array) -> dict:
            rng_base, rng_lora = jax.random.split(rng)
            base = (base_params if base_params is not None
                    else base_module.init_params(cfg, rng_base))
            return {"base": base,
                    "lora": init_lora_params(cfg, lora_cfg, rng_lora,
                                             base_module)}

        @staticmethod
        def param_logical_axes(cfg: ModelConfig) -> dict:
            return {"base": base_module.param_logical_axes(cfg),
                    "lora": lora_logical_axes(cfg, lora_cfg, base_module)}

        @staticmethod
        def param_labels(cfg: ModelConfig) -> dict:
            """Optimizer labels: base frozen, adapters trained."""
            return {"base": jax.tree.map(lambda _: "frozen",
                                         base_module.param_logical_axes(cfg),
                                         is_leaf=lambda x: isinstance(x, tuple)),
                    "lora": jax.tree.map(
                        lambda _: "trainable",
                        lora_logical_axes(cfg, lora_cfg, base_module),
                        is_leaf=lambda x: isinstance(x, tuple))}

        @staticmethod
        def next_token_loss(params: dict, batch: dict, cfg: ModelConfig,
                            **kwargs):
            frozen = jax.tree.map(lax.stop_gradient, params["base"])
            merged = merge_lora(frozen, params["lora"], lora_cfg,
                                base_module=base_module)
            return base_module.next_token_loss(merged, batch, cfg, **kwargs)

    return module
