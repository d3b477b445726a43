"""What the tests of a model with window layers share (tests/test_window_*):
the family's tiny model with a 40-token window, its plain reference, and a
paged server over it. Not a test file."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from serial_order import waits

from cellbench import families, reference, serve
from cloud_server_tpu.config import InferConfig, ModelConfig
from cloud_server_tpu.inference import paged_engine, paged_server
from cloud_server_tpu.inference.block_allocator import WindowPagePool
from cloud_server_tpu.inference.paged_server import PagedInferenceServer
from cloud_server_tpu.models import moe
from cloud_server_tpu.ops.paged_attention import (
    paged_attention, paged_attention_xla)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WINDOW, PAGE, CHUNK = 40, 16, 32
LOGITS_ATOL, LOGPROB_ATOL = 5e-4, 1e-2


def make_model():
    """(configuration as served, ModelConfig, weights, reference forward)
    at the family's tiny widths with a 40-token window."""
    with open(os.path.join(ROOT, "cellbench", "configs",
                           "smallthinker-21b-a3b-instruct.json")) as f:
        cfg_file = json.load(f)
    family = families.of(cfg_file)
    cfg, mcfg, weights = serve.make_model(
        cfg_file, {**family.TINY, "sliding_window_size": WINDOW}, 2**31 + 35)
    assert mcfg.q_per_kv == 7 and WINDOW % PAGE
    return cfg, mcfg, weights, families.forward_of(cfg, weights)




def ref_logits(model, tokens):
    return np.asarray(model[3](np.asarray(tokens, np.int32))[0])


def tokens_of(n, seed=0):
    return np.random.default_rng(seed).integers(1, 512, n)


def make_server(model, **kw):
    _, mcfg, weights, _ = model
    opts = dict(max_slots=4, max_context=256, page_size=PAGE, num_pages=64,
                prefill_chunk=CHUNK, decode_chunk=1)
    opts.update(kw)
    if "kv_cache_dtype" in opts:
        mcfg = dataclasses.replace(mcfg,
                                   kv_cache_dtype=opts.pop("kv_cache_dtype"))
    # `waits`: every launch after the commit before it (serial_order)
    waiting = opts.pop("waits", False)
    return waits(PagedInferenceServer(
        weights, mcfg, InferConfig(max_decode_len=64, temperature=0.0,
                                   eos_token_id=-1), **opts), waiting)


PROMPTS = (150, 37, 90)


def serve_all(srv, each_step=None, max_new=40):
    prompts = [list(map(int, tokens_of(n, 10 + n))) for n in PROMPTS]
    handles = [srv.submit(p, max_new_tokens=max_new) for p in prompts]
    while any(h.finish_reason is None for h in handles):
        srv.step()
        if each_step is not None:
            each_step(srv)
    return prompts, handles


def worst_logprob_diff(model, prompts, handles):
    """The largest |served - reference| log-probability over the served
    tokens whose router gap in the reference is 1e-3 or more in every
    layer: a token the router nearly sent elsewhere goes to the other
    expert on float32's own rounding in one program and not in another,
    and then reads off by a tenth (`reference.compare` sets such tokens
    apart for the same reason). They are few."""
    worst, stable, total = 0.0, 0, 0
    for p, h in zip(prompts, handles):
        assert h.finish_reason == "length", h.finish_reason
        lp, margin, gap = reference.teacher_forced(model[3], p, h.tokens)
        keep = gap >= 1e-3
        stable, total = stable + int(keep.sum()), total + keep.size
        worst = max(worst, float(
            np.abs(lp - np.asarray(h.logprobs))[keep].max()))
        # greedy: the reference's own choice, or a near tie of its
        assert margin[keep].max() < 0.02
    assert stable > 0.9 * total
    return worst


def assert_pages_balance(srv):
    pool = srv.window_pool
    assert pool.active == 0
    assert pool.pages_allocated == pool.pages_returned > 0
    assert srv.allocator.stats().pages_active == 0
    # never more than the pool was sized for, and the bound is met
    assert 0 < srv.window_pages_peak_slot <= srv.window_pages_per_slot
