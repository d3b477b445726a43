"""The paged server over a model with window layers, in every scheduler
mode: served log-probabilities against the family's plain reference, and
the window kind's pages accounted for (tests/test_window_layers.py says
what the model is and why each tolerance)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from window_model import (  # noqa: F401
    CHUNK, LOGITS_ATOL, LOGPROB_ATOL, PAGE, WINDOW, assert_pages_balance,
    make_model, make_server, ref_logits, serve_all, tokens_of,
    worst_logprob_diff)
from cloud_server_tpu.config import InferConfig, ModelConfig  # noqa: F401
from cloud_server_tpu.inference import paged_engine, paged_server  # noqa: F401
from cloud_server_tpu.inference.block_allocator import WindowPagePool  # noqa: F401
from cloud_server_tpu.inference.paged_server import PagedInferenceServer  # noqa: F401
from cloud_server_tpu.models import moe  # noqa: F401
from cloud_server_tpu.ops.paged_attention import (  # noqa: F401
    paged_attention, paged_attention_xla)
from cellbench import reference  # noqa: F401


@pytest.fixture(scope="module")
def model():
    return make_model()


@pytest.mark.parametrize("mode", [
    dict(),
    dict(waits=True),
    dict(mixed_token_budget=40),
    dict(spec_drafts=2),
    dict(allocation="reserve"),
], ids=["ahead", "waits", "budget", "ngram-spec", "reserve"])
def test_served_requests_are_the_reference_and_pages_go_back(model, mode):
    """Three requests of 190, 77 and 130 tokens through the server, past
    the window by up to nine pages: every served log-probability against
    the reference, every window page returned exactly once
    (`WindowPagePool` raises on a second return), none held at the end."""
    srv = make_server(model, **mode)
    prompts, handles = serve_all(srv)
    assert worst_logprob_diff(model, prompts, handles) < LOGPROB_ATOL
    assert_pages_balance(srv)
    # without the hand-back the longest request alone holds 12 pages
    assert srv.window_pages_per_slot < -(-190 // PAGE)
