"""One product with the head in a one-walk step (CPU, the families' tiny
widths in float32, a vocabulary of 509 that no other dimension shares).

`paged_engine.forward_sets` over two row sets that both name a
`logits_at` takes each set's wanted rows out of the stream, norms them
together and makes ONE `transformer.unembed` call; each set is handed
the rows it would get from a product of its own. Here, for the four
layer bodies of the benchmark's cells and for every form of head (tied
or not, a multiplier, a soft cap): the logits row for row, the products
of the lowered text counted by their operand of the vocabulary's size,
a set that wants nothing getting nothing, and the step programs of a
server: one product where the step walks once, two where it walks twice.
"""
import dataclasses
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cellbench import families, serve
from cloud_server_tpu.config import InferConfig
from cloud_server_tpu.inference import paged_engine
from cloud_server_tpu.inference import paged_server as ps
from cloud_server_tpu.inference.paged_server import PagedInferenceServer

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
VOCAB = 509
PAGE, PER, G, WC, B = 16, 4, 3, 16, 4
BODIES = {"experts": "mixtral-8x7b-v0.1",
          "two_kinds": "smallthinker-21b-a3b-instruct",
          "double_shortcut": "longcat-flash-chat",
          "parallel_mixer": "falcon-h1-34b-instruct"}
# every form the tail takes: `transformer.unembed`'s head and soft cap,
# `forward_sets`' multiplier
HEADS = {"untied": {},
         "tied": {"tie_embeddings": True},
         "multiplier": {"lm_head_multiplier": 0.375},
         "softcap": {"logits_softcap": 1.5},
         "tied-multiplier-softcap": {"tie_embeddings": True,
                                     "lm_head_multiplier": 0.375,
                                     "logits_softcap": 1.5}}


@pytest.fixture(scope="module")
def models():
    made = {}

    def model(body):
        if body not in made:
            with open(os.path.join(ROOT, "cellbench", "configs",
                                   BODIES[body] + ".json")) as f:
                cfg_file = json.load(f)
            tiny = {**families.of(cfg_file).TINY, "vocab_size": VOCAB}
            _, mcfg, weights = serve.make_model(cfg_file, tiny, 2**31 + 50)
            made[body] = mcfg, weights
        return made[body]
    yield model
    jax.clear_caches()


def _sets(mcfg, at_p=True, at_d=True):
    """A ragged prefill group of G rows (one of width 0 on sentinel
    tables) beside B decode rows (one dead), every live row on pages of
    its own; each set names its `logits_at` or not."""
    pages = (G + B) * PER
    cache = paged_engine.init_paged_cache(
        mcfg, num_pages=pages, page_size=PAGE, batch=G + B,
        max_pages_per_slot=PER)
    tables = np.arange(pages, dtype=np.int32).reshape(G + B, PER)
    tables[[1, G + B - 1]] = pages  # the width-0 row and the dead row
    tables = jnp.asarray(tables)
    kc, kl = jax.random.split(jax.random.key(7))
    slots = jnp.arange(G + B, dtype=jnp.int32).at[
        jnp.asarray([1, G + B - 1])].set(G + B)
    ssm = bool(mcfg.ssm_heads)
    sets = [
        paged_engine.RowSet(
            jax.random.randint(kc, (G, WC), 1, VOCAB),
            jnp.zeros((G,), jnp.int32), tables[:G],
            jnp.asarray([WC, 0, 5], jnp.int32),
            jnp.asarray([WC - 1, 0, 4], jnp.int32) if at_p else None,
            "prefill_group", slots[:G] if ssm else None),
        paged_engine.RowSet(
            jax.random.randint(kl, (B, 1), 1, VOCAB),
            jnp.asarray([0, 3, 17, 0], jnp.int32), tables[G:], None,
            jnp.zeros((B,), jnp.int32) if at_d else None,
            "decode_rounds", slots[G:] if ssm else None)]
    return cache, sets


def _head_products(text: str) -> int:
    """The products of a lowered program one of whose operands has the
    vocabulary's size."""
    return sum(1 for ln in text.splitlines()
               if "stablehlo.dot_general" in ln
               and re.search(rf"[<x]{VOCAB}x", ln))


@pytest.mark.parametrize("head", list(HEADS))
@pytest.mark.parametrize("body", list(BODIES))
def test_each_set_gets_the_rows_of_a_product_of_its_own(models, body, head):
    """(a) and (c): the one product's rows are, row for row, what
    `transformer.unembed` gives that set's normed rows alone (the form
    `forward_sets` keeps where one set alone names a `logits_at`), and
    the set beside it that names none gets None."""
    mcfg, weights = models(body)
    mcfg = dataclasses.replace(mcfg, **HEADS[head])
    cache, both = _sets(mcfg)
    got_p, got_d = paged_engine.forward_sets(weights, mcfg, cache, both)[0]
    assert got_p.shape == (G, VOCAB) and got_d.shape == (B, VOCAB)
    assert got_p.dtype == got_d.dtype == jnp.float32
    _, only_p = _sets(mcfg, at_d=False)
    want_p, none_d = paged_engine.forward_sets(
        weights, mcfg, cache, only_p)[0]
    _, only_d = _sets(mcfg, at_p=False)
    none_p, want_d = paged_engine.forward_sets(
        weights, mcfg, cache, only_d)[0]
    assert none_d is None and none_p is None
    # the rows do not meet in the product: float32 sums over the same
    # hidden size in the same order, whatever rows lie beside them
    np.testing.assert_allclose(got_p, want_p, rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(got_d, want_d, rtol=1e-6, atol=1e-6)
    # and the rows are told apart: no two of a set's live rows alike
    assert np.abs(np.asarray(got_d[0]) - np.asarray(got_d[1])).max() > 1e-3
    if "logits_softcap" in HEADS[head]:
        assert np.abs(np.asarray(got_p)).max() <= 1.5 * (
            0.375 if "lm_head_multiplier" in HEADS[head] else 1.0) + 1e-6


@pytest.mark.parametrize("head", list(HEADS))
@pytest.mark.parametrize("body", list(BODIES))
def test_the_lowered_walk_holds_one_product_with_the_head(models, body, head):
    """(b) and (c) at the walk: two sets that both want logits lower to
    ONE product with the head; a set that wants none adds none; with
    `all_logits` (verification: every position of every set) each set
    keeps its own."""
    mcfg, weights = models(body)
    mcfg = dataclasses.replace(mcfg, **HEADS[head])

    def lowered(at_p=True, at_d=True, all_logits=False):
        cache, sets = _sets(mcfg, at_p, at_d)
        return jax.jit(lambda w, c: paged_engine.forward_sets(
            w, mcfg, c, sets, all_logits=all_logits)[0]
        ).lower(weights, cache).as_text(debug_info=True)

    text = lowered()
    assert _head_products(text) == 1
    assert "joined_walk/unembed" in text
    assert _head_products(lowered(at_d=False)) == 1
    assert _head_products(lowered(at_p=False)) == 1
    assert _head_products(lowered(at_p=False, at_d=False)) == 0
    if not mcfg.ssm_heads:  # a state has no roll-back: no verification
        assert _head_products(lowered(all_logits=True)) == 2


def test_one_set_lowers_to_what_it_did(models):
    """`window_forward` (one set) takes the other branch: norm the set's
    rows, pick, one product, under no scope of a walk shared."""
    mcfg, weights = models("experts")
    cache, sets = _sets(mcfg)
    one = sets[1]
    text = jax.jit(lambda w, c: paged_engine.window_forward(
        w, one.tokens, mcfg, c._replace(lengths=one.lengths,
                                        tables=one.tables),
        logits_at=one.logits_at)[0]).lower(weights, cache).as_text(
            debug_info=True)
    assert _head_products(text) == 1
    assert "joined_walk" not in text


# -- the step programs of a server -----------------------------------------

GREEDY = InferConfig(max_decode_len=8, temperature=0.0, eos_token_id=-1,
                     pad_token_id=0)


# the default eight rounds keep the two walks of the layers and a product
# with the head in each; one round a step joins them
@pytest.mark.parametrize("decode_chunk", [1, 8], ids=["one-walk", "two-walks"])
@pytest.mark.parametrize("body", list(BODIES))
def test_a_step_program_holds_one_product_a_walk(models, monkeypatch, body,
                                                 decode_chunk):
    mcfg, weights = models(body)
    srv = PagedInferenceServer(
        weights, mcfg, GREEDY, decode_chunk=decode_chunk, max_slots=4,
        max_context=128, page_size=PAGE, num_pages=32, prefill_chunk=32)
    seen = []
    orig = ps._mixed_step

    def lowering(*args, **kwargs):
        if not seen and kwargs["n_rounds"] > 0:  # both halves present
            seen.append(orig.lower(*args, **kwargs).as_text(
                debug_info=True))
        return orig(*args, **kwargs)

    monkeypatch.setattr(ps, "_mixed_step", lowering)
    first = srv.submit([5, 9, 3], max_new_tokens=8)
    srv.step()
    second = srv.submit([(k * 7) % 60 + 1 for k in range(40)],
                        max_new_tokens=2)
    srv.run_until_idle()
    assert first.done and second.done and seen
    joined, text = decode_chunk == 1, seen[0]
    assert ("joined_walk/" in text) == joined
    assert _head_products(text) == (1 if joined else 2)
    assert ("joined_walk/unembed" in text) == joined
