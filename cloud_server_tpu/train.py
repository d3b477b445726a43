"""Training CLI: `python -m cloud_server_tpu.train`.

Config comes from a JSON file with optional sections {"model", "train",
"mesh", "loop"} (each deserialised into the corresponding dataclass in
`config.py` / `training/loop.py`), with common fields overridable from the
command line. Data is either a flat binary token file (`--data`, the
`MemmapTokenDataset` format) or `--synthetic N` random examples for
smoke runs.

Multi-host: pass `--distributed` to call `jax.distributed.initialize()`
before anything touches the backend; every process runs this same command
and the data/checkpoint layers shard per-process automatically.
"""

from __future__ import annotations

import argparse
import dataclasses
import json


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="python -m cloud_server_tpu.train",
        description="Train a dense or MoE decoder LM on TPU.")
    p.add_argument("--config", help="JSON config file with optional "
                   "model/train/mesh/loop sections")
    p.add_argument("--data", action="append", default=None,
                   help="flat binary token file (uint16). Repeatable; "
                   "with several, pass 'path:weight' to train on a "
                   "deterministic weighted mixture (weight defaults to 1)")
    p.add_argument("--eval-data", help="eval token file (same format)")
    p.add_argument("--synthetic", type=int, default=0, metavar="N",
                   help="use N synthetic random examples instead of --data")
    p.add_argument("--steps", type=int, help="override train.total_steps")
    p.add_argument("--batch-size", type=int, help="override train.batch_size")
    p.add_argument("--seq-len", type=int, help="override train.seq_len")
    p.add_argument("--learning-rate", type=float,
                   help="override train.learning_rate")
    p.add_argument("--checkpoint-dir", help="override loop.checkpoint_dir")
    p.add_argument("--logdir", help="override loop.logdir")
    p.add_argument("--eval-interval", type=int,
                   help="override loop.eval_interval (defaults to 500 when "
                   "--eval-data is given and the config leaves it 0)")
    p.add_argument("--distributed", action="store_true",
                   help="call jax.distributed.initialize() (multi-host)")
    p.add_argument("--no-nan-guard", action="store_true",
                   help="disable the NaN/inf loss guard")
    from cloud_server_tpu.models.lora import add_lora_args
    add_lora_args(p)
    p.add_argument("--init-from", metavar="CKPT_DIR",
                   help="load pretrained base params from this training "
                   "checkpoint (requires --lora-rank)")
    p.add_argument("--watchdog", type=float, default=0.0, metavar="SECONDS",
                   help="abort (with stack dump) if a step makes no "
                   "progress for this long; 0 disables")
    return p


def configs_from_args(args) -> tuple:
    """(ModelConfig, TrainConfig, MeshConfig, LoopConfig, dcn MeshConfig or
    None) from file + flags. A "dcn_mesh" config section requests a hybrid
    ICI×DCN mesh (multi-slice training): its axes say how the "mesh"
    section's layout is replicated across slices."""
    from cloud_server_tpu.config import (
        MeshConfig, ModelConfig, TrainConfig, from_json)
    from cloud_server_tpu.training.loop import LoopConfig

    raw = {}
    if args.config:
        with open(args.config) as f:
            raw = json.load(f)
    model_cfg = from_json(ModelConfig, raw.get("model", {}))
    train_cfg = from_json(TrainConfig, raw.get("train", {}))
    mesh_cfg = from_json(MeshConfig, raw.get("mesh", {}))
    loop_cfg = from_json(LoopConfig, raw.get("loop", {}))
    dcn_cfg = (from_json(MeshConfig, raw["dcn_mesh"])
               if "dcn_mesh" in raw else None)

    train_over = {k: v for k, v in {
        "total_steps": args.steps, "batch_size": args.batch_size,
        "seq_len": args.seq_len, "learning_rate": args.learning_rate,
    }.items() if v is not None}
    if train_over:
        train_cfg = dataclasses.replace(train_cfg, **train_over)
    loop_over = {k: v for k, v in {
        "checkpoint_dir": args.checkpoint_dir, "logdir": args.logdir,
        "eval_interval": args.eval_interval,
    }.items() if v is not None}
    # --eval-data with eval_interval 0 would silently never evaluate.
    if getattr(args, "eval_data", None) and "eval_interval" not in loop_over \
            and loop_cfg.eval_interval == 0:
        loop_over["eval_interval"] = 500
        print("[train] --eval-data given without eval_interval; "
              "defaulting loop.eval_interval=500")
    if loop_over:
        loop_cfg = dataclasses.replace(loop_cfg, **loop_over)
    return model_cfg, train_cfg, mesh_cfg, loop_cfg, dcn_cfg


def main(argv=None) -> None:
    args = build_parser().parse_args(argv)
    from cloud_server_tpu.utils.platform import (
        device_line, enable_compile_cache)
    enable_compile_cache()
    if args.distributed:
        from cloud_server_tpu.parallel.distributed import initialize
        initialize()
    device_line("train")

    from cloud_server_tpu.data.dataset import (
        MemmapTokenDataset, MixtureDataset, SyntheticLMDataset)
    from cloud_server_tpu.models import moe as moe_module, transformer
    from cloud_server_tpu.training.loop import train_loop

    model_cfg, train_cfg, mesh_cfg, loop_cfg, dcn_cfg = configs_from_args(args)
    mesh = None
    if dcn_cfg is not None:
        from cloud_server_tpu.parallel.distributed import (
            global_mesh_config, make_hybrid_mesh)
        g = global_mesh_config(mesh_cfg, dcn_cfg)
        batch_shards = g.dp * g.fsdp
        if train_cfg.batch_size % batch_shards:
            raise SystemExit(
                f"batch_size {train_cfg.batch_size} not divisible by the "
                f"GLOBAL batch-sharding axes dp×fsdp = {g.dp}×{g.fsdp} = "
                f"{batch_shards} (mesh × dcn_mesh)")
        mesh = make_hybrid_mesh(mesh_cfg, dcn_cfg)

    if args.synthetic:
        dataset = SyntheticLMDataset(args.synthetic, train_cfg.seq_len,
                                     model_cfg.vocab_size,
                                     seed=train_cfg.seed)
    elif args.data:
        specs = []
        for entry in args.data:
            path, sep, w = entry.rpartition(":")
            if sep and path and not w:
                raise SystemExit(
                    f"--data entry {entry!r} has an empty weight after "
                    "':' — use path:weight (e.g. data.bin:2.0) or just "
                    "the path")
            try:
                weight, path = (float(w), path) if path else (1.0, entry)
            except ValueError:
                weight, path = 1.0, entry  # ':' was part of the path
            specs.append((path, weight))
        if len(specs) == 1:
            dataset = MemmapTokenDataset(specs[0][0], train_cfg.seq_len)
        else:
            dataset = MixtureDataset(
                [MemmapTokenDataset(p, train_cfg.seq_len)
                 for p, _ in specs],
                [w for _, w in specs], seed=train_cfg.seed)
    else:
        raise SystemExit("one of --data or --synthetic is required")
    eval_dataset = (MemmapTokenDataset(args.eval_data, train_cfg.seq_len)
                    if args.eval_data else None)

    loss_fn_module = moe_module if model_cfg.num_experts >= 2 else transformer
    if args.init_from and not args.lora_rank:
        raise SystemExit("--init-from currently requires --lora-rank "
                         "(full-model warm start is not wired up yet)")
    if args.lora_rank > 0:
        from cloud_server_tpu.models.lora import (
            lora_config_from_args, make_lora_module, save_lora_config)
        from cloud_server_tpu.parallel.mesh import make_mesh
        lcfg = lora_config_from_args(args)
        base_params = None
        if args.init_from:
            from cloud_server_tpu.generate import load_params
            # restore onto the run's real mesh — a default single-device
            # mesh would materialise the full base on one chip
            base_params = load_params(
                model_cfg, args.init_from, None, train_cfg.seed,
                mesh=mesh if mesh is not None else make_mesh(mesh_cfg))
        # dense OR MoE: the lora module generalises over the base family
        # (per-expert adapter stacks for the (L, E, ...) expert weights)
        loss_fn_module = make_lora_module(
            lcfg, base_module=loss_fn_module, base_params=base_params)
        if loop_cfg.checkpoint_dir:
            from cloud_server_tpu.parallel.distributed import is_primary
            if is_primary():  # shared ckpt dir: N writers would race
                save_lora_config(loop_cfg.checkpoint_dir, lcfg)

    import contextlib

    from cloud_server_tpu.utils.failure import (
        NaNGuard, PreemptionHandler, Watchdog)

    hooks = []
    with contextlib.ExitStack() as stack:
        preempt = stack.enter_context(PreemptionHandler())
        hooks.append(preempt)  # SIGTERM -> save + clean exit
        if not args.no_nan_guard:
            hooks.append(NaNGuard())
        if args.watchdog > 0:
            hooks.append(stack.enter_context(Watchdog(args.watchdog)))
        train_loop(model_cfg, train_cfg, dataset, mesh_cfg=mesh_cfg,
                   loop_cfg=loop_cfg, eval_dataset=eval_dataset,
                   loss_fn_module=loss_fn_module, hooks=hooks, mesh=mesh)


if __name__ == "__main__":
    main()
