"""Evaluation harness: corpus perplexity and per-request loglikelihood
scoring (the lm-eval-style primitive under multiple-choice accuracy).

Two entry points, one jitted teacher-forced forward each:

  * `perplexity(params, cfg, data_path)` — streams a flat binary token
    file (`data/tokenizer.prepare_corpus` format) through
    `next_token_loss` in fixed (B, S) windows and reports token-mean
    NLL, perplexity, and (when the tokenizer is byte-level)
    bits-per-byte. Shapes are static: one compile per (B, S).
  * `loglikelihoods(params, cfg, pairs)` — scores (context,
    continuation) token pairs: sum log P(continuation | context) under
    teacher forcing plus whether the continuation is the greedy
    argmax at every position (`is_greedy` — lm-eval's `acc` for
    multiple-choice tasks compares these sums across choices). Pairs
    are bucketed to power-of-two lengths and padded to a fixed batch,
    so arbitrary request mixes compile O(log S) times.

CLI (`python -m cloud_server_tpu.evaluate`):

  # corpus perplexity
  python -m cloud_server_tpu.evaluate --config cfg.json \
      --checkpoint-dir ckpt --data val.bin
  # loglikelihood / greedy-match scoring of JSONL requests
  python -m cloud_server_tpu.evaluate --config cfg.json \
      --checkpoint-dir ckpt --requests reqs.jsonl --tokenizer byte

Each `--requests` line is {"context": str, "continuation": str} (or
"context_tokens"/"continuation_tokens" id lists). Output is one JSON
line: aggregate for --data, per-request list + accuracy-style summary
for --requests.

Reference parity note: view-sonic/Cloud-Server @ v0 is an empty tree
(SURVEY.md); this subsystem is part of the re-scoped build inventory
(evaluation tooling over the serving/training stack).
"""

from __future__ import annotations

import json
import math
import sys
from functools import partial
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from cloud_server_tpu.config import ModelConfig
from cloud_server_tpu.models import transformer


@partial(jax.jit, static_argnames=("cfg",))
def _window_nll(params, tokens: jnp.ndarray, mask: jnp.ndarray, *,
                cfg: ModelConfig):
    """Summed next-token NLL + predicted-token count for (B, S) windows.
    Reuses the training loss (incl. the fused blockwise-vocab CE when
    cfg.vocab_chunk > 0 — logits never materialise)."""
    loss, _ = transformer.next_token_loss(
        params, {"tokens": tokens, "mask": mask}, cfg)
    n = mask[:, 1:].sum()
    return loss * n, n


@partial(jax.jit, static_argnames=("cfg",))
def _score_pairs(params, tokens: jnp.ndarray, ctx_lens: jnp.ndarray,
                 total_lens: jnp.ndarray, *, cfg: ModelConfig):
    """Teacher-forced continuation scoring.

    tokens: (B, S) = context + continuation + pad. Position i's logits
    predict token i+1; continuation tokens live at positions
    [ctx_len, total_len), so their scores come from positions
    [ctx_len - 1, total_len - 1).

    Returns (sum_logprob (B,) f32, is_greedy (B,) bool).
    """
    logits = transformer.forward(params, tokens, cfg)  # softcap inside
    lp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    b, s, _ = lp.shape
    targets = tokens[:, 1:]                        # (B, S-1)
    tok_lp = jnp.take_along_axis(lp[:, :-1], targets[..., None],
                                 axis=-1)[..., 0]  # (B, S-1)
    greedy = jnp.argmax(lp[:, :-1], axis=-1) == targets
    pos = jnp.arange(s - 1)[None, :]
    is_cont = ((pos >= (ctx_lens - 1)[:, None])
               & (pos < (total_lens - 1)[:, None]))
    sum_lp = jnp.where(is_cont, tok_lp, 0.0).sum(axis=1)
    all_greedy = jnp.where(is_cont, greedy, True).all(axis=1)
    return sum_lp, all_greedy


def perplexity(params, cfg: ModelConfig, data_path: str, *,
               batch_size: int = 8, seq_len: int | None = None,
               max_batches: int | None = None) -> dict:
    """Corpus perplexity over a flat binary token file."""
    from cloud_server_tpu.data.dataset import MemmapTokenDataset
    seq_len = seq_len or cfg.max_seq_len
    ds = MemmapTokenDataset(data_path, seq_len)
    total_nll = 0.0
    total_tokens = 0
    n_batches = len(ds) // batch_size  # full batches only: static shapes
    if max_batches is not None:
        n_batches = min(n_batches, max_batches)
    if n_batches == 0:
        raise ValueError(
            f"{data_path}: {len(ds)} windows of {seq_len} tokens cannot "
            f"fill one batch of {batch_size}")
    for bi in range(n_batches):
        rows = np.stack([ds[bi * batch_size + i]["tokens"]
                         for i in range(batch_size)])
        mask = np.ones_like(rows, np.float32)
        nll, n = _window_nll(params, jnp.asarray(rows), jnp.asarray(mask),
                             cfg=cfg)
        total_nll += float(nll)
        total_tokens += int(n)
    loss = total_nll / max(total_tokens, 1)
    return {"loss": loss, "ppl": math.exp(min(loss, 80.0)),
            "tokens": total_tokens, "windows": n_batches * batch_size}


def _pow2(n: int, lo: int = 16) -> int:
    p = lo
    while p < n:
        p *= 2
    return p


def loglikelihoods(params, cfg: ModelConfig,
                   pairs: Sequence[tuple[Sequence[int], Sequence[int]]],
                   *, batch_size: int = 8) -> list[dict]:
    """Score (context_tokens, continuation_tokens) pairs.

    Sequences longer than cfg.max_seq_len keep their TAIL (the
    continuation must stay intact; leading context is dropped — the
    lm-eval convention). Returns one {"sum_logprob", "is_greedy",
    "num_tokens"} per pair, in order.
    """
    prepared = []  # (orig_idx, tokens, ctx_len, total_len)
    for i, (ctx, cont) in enumerate(pairs):
        ctx, cont = list(ctx), list(cont)
        if not cont:
            raise ValueError(f"request {i}: empty continuation")
        if not ctx:
            # unconditional loglikelihood still needs one input position
            # to predict the first continuation token from; condition on
            # token 0 (the BOS/pad convention) so scores are consistent
            # across continuation lengths and not biased toward
            # self-repetition
            ctx = [0]
        total = ctx + cont
        if len(total) > cfg.max_seq_len:
            drop = len(total) - cfg.max_seq_len
            if drop >= len(ctx):
                raise ValueError(
                    f"request {i}: continuation of {len(cont)} tokens "
                    f"cannot fit max_seq_len={cfg.max_seq_len}")
            ctx = ctx[drop:]
            total = ctx + cont
        prepared.append((i, total, len(ctx), len(total)))

    # bucket by padded length; fixed batch rows => O(buckets) compiles
    by_bucket: dict[int, list] = {}
    for item in prepared:
        by_bucket.setdefault(
            _pow2(min(len(item[1]), cfg.max_seq_len)), []).append(item)
    out: list[dict | None] = [None] * len(prepared)
    for s, items in sorted(by_bucket.items()):
        for start in range(0, len(items), batch_size):
            chunk = items[start:start + batch_size]
            rows = np.zeros((batch_size, s), np.int32)
            ctx_lens = np.ones((batch_size,), np.int32)
            total_lens = np.ones((batch_size,), np.int32)
            for r, (_, toks, cl, tl) in enumerate(chunk):
                rows[r, :len(toks)] = toks
                ctx_lens[r] = cl
                total_lens[r] = tl
            sum_lp, greedy = jax.device_get(_score_pairs(
                params, jnp.asarray(rows), jnp.asarray(ctx_lens),
                jnp.asarray(total_lens), cfg=cfg))
            for r, (orig, toks, cl, tl) in enumerate(chunk):
                out[orig] = {"sum_logprob": float(sum_lp[r]),
                             "is_greedy": bool(greedy[r]),
                             "num_tokens": tl - cl}
    return out


def _load_requests(path: str, tokenizer) -> list[tuple[list, list]]:
    pairs = []
    with open(path) as f:
        for ln, line in enumerate(f):
            if not line.strip():
                continue
            req = json.loads(line)
            if "context_tokens" in req or "continuation_tokens" in req:
                if "continuation_tokens" not in req:
                    raise ValueError(
                        f"{path}:{ln + 1}: context_tokens without "
                        "continuation_tokens")
                pairs.append((list(req.get("context_tokens", [])),
                              list(req["continuation_tokens"])))
            else:
                if tokenizer is None:
                    raise ValueError(
                        f"{path}:{ln + 1}: text requests need --tokenizer")
                pairs.append((tokenizer.encode(req.get("context", "")),
                              tokenizer.encode(req["continuation"])))
    if not pairs:
        raise ValueError(f"{path}: no requests")
    return pairs


def main(argv=None) -> None:
    import argparse

    from cloud_server_tpu.config import from_json
    from cloud_server_tpu.generate import load_params

    p = argparse.ArgumentParser(
        prog="python -m cloud_server_tpu.evaluate",
        description="Perplexity / loglikelihood evaluation.")
    p.add_argument("--config", required=True,
                   help="JSON config with the model section")
    p.add_argument("--checkpoint-dir")
    p.add_argument("--step", type=int)
    p.add_argument("--ema", action="store_true",
                   help="evaluate the EMA-averaged weights")
    p.add_argument("--data", help="flat binary token file -> perplexity")
    p.add_argument("--requests",
                   help="JSONL context/continuation requests -> "
                        "loglikelihoods")
    p.add_argument("--tokenizer", default=None,
                   help='"byte" or a local tokenizer.json (text requests)')
    p.add_argument("--batch-size", type=int, default=8)
    p.add_argument("--seq-len", type=int,
                   help="perplexity window (default: model max_seq_len)")
    p.add_argument("--max-batches", type=int,
                   help="cap perplexity batches (quick looks)")
    p.add_argument("--seed", type=int, default=0)
    args = p.parse_args(argv)
    if not args.data and not args.requests:
        p.error("pass --data and/or --requests")
    from cloud_server_tpu.utils.platform import (
        device_line, enable_compile_cache)
    enable_compile_cache()
    device_line("evaluate")

    with open(args.config) as f:
        model_cfg = from_json(ModelConfig, json.load(f)["model"])
    if args.ema:
        if args.checkpoint_dir is None:
            p.error("--ema needs --checkpoint-dir")
        from cloud_server_tpu.config import MeshConfig
        from cloud_server_tpu.parallel.mesh import make_mesh
        from cloud_server_tpu.training.checkpoint import restore_ema_params
        params = restore_ema_params(args.checkpoint_dir, model_cfg,
                                    make_mesh(MeshConfig()),
                                    step=args.step)
    else:
        params = load_params(model_cfg, args.checkpoint_dir, args.step,
                             args.seed)
    tokenizer = None
    if args.tokenizer:
        from cloud_server_tpu.data.tokenizer import get_tokenizer
        tokenizer = get_tokenizer(args.tokenizer)

    result: dict = {}
    if args.data:
        result["perplexity"] = perplexity(
            params, model_cfg, args.data, batch_size=args.batch_size,
            seq_len=args.seq_len, max_batches=args.max_batches)
        if tokenizer is not None and getattr(tokenizer, "vocab_size",
                                             0) == 259:
            # byte tokenizer: tokens ARE bytes -> bits-per-byte
            result["perplexity"]["bits_per_byte"] = (
                result["perplexity"]["loss"] / math.log(2))
    if args.requests:
        pairs = _load_requests(args.requests, tokenizer)
        scores = loglikelihoods(params, model_cfg, pairs,
                                batch_size=args.batch_size)
        result["requests"] = scores
        result["summary"] = {
            "n": len(scores),
            "mean_logprob": (sum(s["sum_logprob"] for s in scores)
                             / len(scores)),
            "greedy_frac": (sum(s["is_greedy"] for s in scores)
                            / len(scores))}
    print(json.dumps(result))


if __name__ == "__main__":
    main()
