"""A model with a pattern of layers on the paged serving path: windowed
rotary layers beside full position-free ones, a page pool for each kind
with the window kind's pages given back behind the window, experts
routed from the layer's input, a ReGLU gate.

Everything is compared with the plain reference of the family that has
such a model (`cellbench/families/smallthinker.py`: float32 at `highest`,
no kernel, no cache, written from the published description and
independent of the program's model code), at its tiny widths: 7 query
heads a key head, top-3 of 8 experts, 8 layers (full, window, window,
window, twice), and a window that is shorter than the contexts by several
pages and no multiple of the page.

Tolerances. The program and the reference both compute in float32 here
and differ in the order of their sums only. Logits: 5e-4 absolute (the
largest seen is 8e-5 over 8 layers; one bfloat16 rounding of the stream
is 4e-3 and fails it): one program, one order of sums. Served
log-probabilities, over the tokens the router did not nearly send
elsewhere (`window_model.worst_logprob_diff`): 1e-2. Most modes read 3e-5;
but of 3,200 router decisions about ten have a gap under 1e-3, a program
that sums in another order (other chunk widths) flips one of them, and
every later token of that request then reads up to 4e-3 off. Serving the
same in bfloat16 reads a median of 0.16. The int8 cache: per-key absmax
rounding of 1/254 on keys and values; its own tolerance is a median
between 1e-3 and 0.5 (seen 0.01 to 0.17 over three scalings of the
seed's `wq`), a hundred times the float32 cache's median. Kernels
against `paged_attention_xla`: 2e-4, the tolerance of the kernels' tests
without a bound (tests/test_paged_attention.py).
"""
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


def paged_logits(mcfg, weights, tokens, n_prefill, *, page=PAGE):
    """Logits of every position through the paged engine: the first
    `n_prefill` tokens in chunks of CHUNK, the rest one at a time. Every
    page of both kinds stays in its table (the hand-back is the server's
    and is tested there), so a kernel that read behind the bound would
    read real keys."""
    s = len(tokens)
    mp = -(-s // page)
    cache = paged_engine.init_paged_cache(
        mcfg, num_pages=mp, page_size=page, batch=1, max_pages_per_slot=mp,
        window_num_pages=mp)
    ids = jnp.arange(mp, dtype=jnp.int32)[None]
    cache = cache._replace(tables=jnp.concatenate([ids, ids], axis=1)
                           if mcfg.has_window_layers else ids)
    toks = jnp.asarray(tokens, jnp.int32)[None]
    out, at = [], 0
    while at < s:
        w = min(CHUNK, n_prefill - at) if at < n_prefill else 1
        logits, cache = paged_engine.window_forward(
            weights, toks[:, at:at + w], mcfg, cache, logits_at=None,
            all_logits=True)
        cache = cache._replace(lengths=cache.lengths + w)
        out.append(np.asarray(logits[0]))
        at += w
    return np.concatenate(out, axis=0)


def test_uncached_forward_is_the_reference(model):
    _, mcfg, weights, _ = model
    tokens = tokens_of(150)
    got, aux = moe.forward(weights, jnp.asarray(tokens)[None], mcfg)
    assert float(aux["dropped_frac"]) < 1e-6
    np.testing.assert_allclose(np.asarray(got[0]), ref_logits(model, tokens),
                               atol=LOGITS_ATOL)


def test_chunked_prefill_and_decode_through_both_pools_is_the_reference(
        model):
    """150 tokens: 110 prefilled in chunks of 32 (the last one ragged),
    40 decoded; the context passes the 40-token window by 6 pages."""
    _, mcfg, weights, _ = model
    tokens = tokens_of(150, 1)
    got = paged_logits(mcfg, weights, tokens, 110)
    np.testing.assert_allclose(got, ref_logits(model, tokens),
                               atol=LOGITS_ATOL)


MUTATIONS = {
    "the window's bound off": {"window_layout": ()},
    "rotary on a full layer": {"rope_layout": (1, 1, 1, 1)},
    "the router reads the normed input": {"router_input": "mlp_norm"},
    "SiLU for ReLU": {"mlp_activation": "silu"},
}


@pytest.mark.parametrize("name", sorted(MUTATIONS))
def test_a_mutated_program_fails_the_comparison(model, name):
    """Each statement of the published layer taken out of the PROGRAM (the
    cached path and the un-cached one) moves its logits off the reference
    by more than a hundred times the tolerance."""
    _, mcfg, weights, _ = model
    bad = dataclasses.replace(mcfg, **MUTATIONS[name])
    tokens = tokens_of(150, 1)
    want = ref_logits(model, tokens)
    cached = paged_logits(bad, weights, tokens, 110)
    plain = np.asarray(moe.forward(weights, jnp.asarray(tokens)[None],
                                   bad)[0][0])
    for got in (cached, plain):
        assert np.abs(got - want).max() > 100 * LOGITS_ATOL
    if name == "the window's bound off":
        # inside the window nothing was taken out
        np.testing.assert_allclose(cached[:WINDOW], want[:WINDOW],
                                   atol=LOGITS_ATOL)


def test_a_bound_dropped_in_the_cached_path_alone_fails(model, monkeypatch):
    """The kernel call loses its `window` (what a served path with the
    bound taken out is, PERF.md 6.1): the pools, the tables and the
    un-cached forward are as they were, and the logits past the window
    are off."""
    _, mcfg, weights, _ = model
    real = paged_engine.paged_attention_xla
    monkeypatch.setattr(
        paged_engine, "paged_attention_xla",
        lambda *a, **kw: real(*a, **{k: v for k, v in kw.items()
                                     if k != "window"}))
    tokens = tokens_of(150, 1)
    got = paged_logits(mcfg, weights, tokens, 110)
    want = ref_logits(model, tokens)
    np.testing.assert_allclose(got[:WINDOW], want[:WINDOW], atol=LOGITS_ATOL)
    assert np.abs(got[WINDOW + PAGE:] - want[WINDOW + PAGE:]).max() > 0.05


def test_a_joined_mixed_step_is_the_reference(model):
    """`forward_sets` over a chunk of one row and a decode token of
    another, both past the window, in one walk of the layers: the
    router's input and the experts' travel side by side for both sets."""
    _, mcfg, weights, _ = model
    a, b = tokens_of(120, 2), tokens_of(90, 3)
    mp = 8
    cache = paged_engine.init_paged_cache(
        mcfg, num_pages=2 * mp, page_size=PAGE, batch=2,
        max_pages_per_slot=mp, window_num_pages=2 * mp)
    ids = jnp.arange(2 * mp, dtype=jnp.int32).reshape(2, mp)
    tables = jnp.concatenate([ids, ids], axis=1)
    cache = cache._replace(tables=tables)
    # both rows' histories: a up to 96, b up to 89
    for row, toks, upto in ((0, a, 96), (1, b, 89)):
        at = 0
        while at < upto:
            w = min(CHUNK, upto - at)
            feed = jnp.zeros((2, w), jnp.int32).at[row].set(
                jnp.asarray(toks[at:at + w]))
            widths = jnp.zeros((2,), jnp.int32).at[row].set(w)
            _, cache = paged_engine.window_forward(
                weights, feed, mcfg, cache, logits_at=None, widths=widths)
            cache = cache._replace(lengths=cache.lengths.at[row].add(w))
            at += w
    sets = [paged_engine.RowSet(jnp.asarray(a[96:120])[None],
                                cache.lengths[:1], tables[:1],
                                jnp.asarray([24]), scope="prefill_group"),
            paged_engine.RowSet(jnp.asarray(b[89:90])[None],
                                cache.lengths[1:], tables[1:],
                                scope="decode_rounds")]
    (chunk, dec), _ = paged_engine.forward_sets(
        weights, mcfg, cache, sets, all_logits=True)
    np.testing.assert_allclose(np.asarray(chunk[0]),
                               ref_logits(model, a)[96:120],
                               atol=LOGITS_ATOL)
    np.testing.assert_allclose(np.asarray(dec[0, 0]),
                               ref_logits(model, b)[89], atol=LOGITS_ATOL)


@pytest.mark.parametrize("dtype,atol", [("float32", 2e-5), ("bfloat16", 0.06)])
def test_both_dispatches_agree_at_64_experts_6_a_token(dtype, atol,
                                                       monkeypatch):
    """(64 experts, 6 a token), ReGLU, the router reading an input of its
    own: the sorted dispatch and the dense one compute the same function
    (float32: to the order of the sums; bfloat16: to its rounding of
    outputs of size 1, 2**-8 over a 6-term sum and the down projection)."""
    cfg = ModelConfig(embed_dim=64, mlp_dim=32, num_experts=64,
                      num_experts_per_token=6,
                      expert_capacity_factor=64 / 6, dtype=dtype,
                      mlp_activation="relu", router_input="layer_input",
                      num_layers=2)
    ks = jax.random.split(jax.random.key(0), 6)
    dt = jnp.dtype(dtype)

    def w(k, shape, fan_in):
        return (jax.random.normal(k, shape) * fan_in ** -0.5).astype(dt)

    layers = {"mlp_norm": jnp.ones((2, 64), dt),
              "router": w(ks[0], (2, 64, 64), 64),
              "w_gate": w(ks[1], (2, 64, 64, 32), 64),
              "w_up": w(ks[2], (2, 64, 64, 32), 64),
              "w_down": w(ks[3], (2, 64, 32, 64), 32)}
    x = jax.random.normal(ks[4], (2, 48, 64)).astype(dt)
    x_in = jax.random.normal(ks[5], (2, 48, 64)).astype(dt)
    lp = jax.tree.map(lambda p: p[1], layers)
    monkeypatch.setattr(moe, "grouped_min_tokens", lambda cfg: 1)
    assert moe._dispatch_grouped(cfg, 96, (layers, 1))
    grouped, _ = moe.moe_mlp_block(x, lp, cfg, (layers, 1), layer_in=x_in)
    monkeypatch.setattr(moe, "grouped_min_tokens", lambda cfg: 10 ** 9)
    dense, _ = moe.moe_mlp_block(x, lp, cfg, (layers, 1), layer_in=x_in)
    np.testing.assert_allclose(np.asarray(grouped, np.float32),
                               np.asarray(dense, np.float32), atol=atol)
    # the router's input is its own: routing from the stream differs
    other, _ = moe.moe_mlp_block(x, lp, cfg, (layers, 1), layer_in=x)
    assert np.abs(np.asarray(other, np.float32)
                  - np.asarray(dense, np.float32)).max() > 10 * atol
    with pytest.raises(ValueError, match="layer's input"):
        moe.moe_mlp_block(x, lp, cfg, (layers, 1))


def test_the_tiles_are_placed_from_the_widths():
    """Mixtral's tiles at Mixtral's widths, to the number; at the narrow
    widths an expert's whole matrix is one tile of under 4 MiB."""
    assert moe._gmm_tiling(4096, 14336) == (256, 4096, 512)
    assert moe._gmm_tiling(14336, 4096) == (256, 1024, 2048)
    assert moe._gmm_tiling(2560, 768) == (256, 2560, 768)
    assert moe._gmm_tiling(768, 2560) == (256, 768, 2560)
