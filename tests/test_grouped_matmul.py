"""The experts' way in as one kernel (`ops/grouped_matmul.py`), interpreted
on the CPU: against a float64 product of the same operands and the
activation's formula, in every layout `moe._moe_grouped` hands it and in
each grid order `moe._gated_tiling` can choose; what a sorted layer
lowers to; and that programs which never sort lower to what they did.
"""
import hashlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cloud_server_tpu.config import InferConfig, ModelConfig
from cloud_server_tpu.models import moe
from cloud_server_tpu.ops import gated, grouped_matmul
from cloud_server_tpu.ops.grouped_matmul import (
    _vmem_bytes, gated_grouped_matmul)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
D, F, TM = 32, 256, moe._GMM_ROWS
E, LAYERS, LAYER = 4, 3, 1


@pytest.fixture(scope="module", autouse=True)
def _give_programs_back():
    yield
    jax.clear_caches()


def _sizes(layout):
    """(the groups' extents (G,), the real rows at the head of each (G,),
    buffer rows) as `_moe_grouped` hands them."""
    one_layer = {
        # every extent whole row tiles (`_aligned_layout` with room)
        "aligned": ([TM, 2 * TM, TM, TM], None, 5 * TM),
        # packed end to end: tiles shared by two and by three experts
        "packed": ([100, 300, 40, 72], None, 2 * TM),
        "empty_expert": ([200, 0, 56, 256], None, 2 * TM),
        # the buffer longer than the extents: its last rows are no one's
        "rows_past": ([TM, 30, TM, 0], None, 4 * TM),
        # one chip's share: 270 of 13,056 rows land on a held expert
        "share": ([70, 61, 80, 59], None, 13056),
        # padded extents beside their real rows: experts that fill a
        # sub-tile's first row, part of one, one, one and a row, all but
        # a row of the tile, and the tile
        "padded_tiles": ([TM] * 6, [1, 16, 64, 65, 255, 256], 6 * TM),
        # an expert of two tiles and 16 rows of a third, between two of
        # less than a sub-tile
        "two_tiles_and_16": ([TM, 3 * TM, TM], [40, 2 * TM + 16, 3],
                             5 * TM),
        # packed: a group starts inside a tile and ends inside the next
        "packed_across": ([150, 200, 20], None, 2 * TM),
        # an empty group between two full ones
        "empty_between": ([TM, 0, TM], [TM, 0, TM], 2 * TM),
        # the first experts padded, the last packed where room ran out
        "room_ran_out": ([TM, TM, 70, 30], [130, 9, 70, 30], 3 * TM),
    }
    if layout == "stack":  # L * E groups, every other layer's empty
        sizes = np.zeros((LAYERS * E,), np.int32)
        sizes[LAYER * E:(LAYER + 1) * E] = [100, 300, 40, 72]
        return sizes, sizes, 2 * TM
    sizes, real, m = one_layer[layout]
    sizes = np.asarray(sizes, np.int32)
    return sizes, sizes if real is None else np.asarray(real, np.int32), m


def _real_rows(sizes, real):
    """The buffer rows that are some group's real rows."""
    starts = np.cumsum(sizes) - sizes
    return np.concatenate([np.arange(s, s + n) for s, n in zip(starts, real)])


def _float64(rows, w_gate, w_up, sizes, activation):
    """act(rows @ w_gate[g]) * (rows @ w_up[g]) of every row of every
    group's extent, in float64 from the operands as they are."""
    x = np.asarray(rows.astype(jnp.float32), np.float64)
    out = np.zeros((int(sizes.sum()), w_gate.shape[2]))
    start = 0
    for g, n in enumerate(sizes):
        wg, wu = (np.asarray(w[g].astype(jnp.float32), np.float64)
                  for w in (w_gate, w_up))
        gate, up = x[start:start + n] @ wg, x[start:start + n] @ wu
        act = (gate / (1.0 + np.exp(-gate)) if activation == "silu"
               else np.maximum(gate, 0.0))
        out[start:start + n] = act * up
        start += n
    return out


# the block the whole width (the visits outermost), one column tile (the
# columns outermost), and two column tiles of a half-width block
ORDERS = {"visits_outer": (TM, 128, F), "columns_outer": (TM, 128, 128),
          "blocks_of_two": (TM, 64, 128)}


@pytest.mark.parametrize("dtype,activation", [
    ("float32", "silu"), ("float32", "relu"), ("bfloat16", "silu"),
    ("bfloat16", "relu")])
@pytest.mark.parametrize("order", list(ORDERS))
@pytest.mark.parametrize("layout", [
    "stack", "aligned", "packed", "empty_expert", "rows_past", "share",
    "padded_tiles", "two_tiles_and_16", "packed_across", "empty_between",
    "room_ran_out"])
def test_the_kernel_is_the_products_and_the_activation(
        monkeypatch, layout, order, dtype, activation):
    sizes, real, m = _sizes(layout)
    ks = jax.random.split(jax.random.key(len(layout)), 3)
    rows = jax.random.normal(ks[0], (m, D)).astype(dtype)
    w_gate, w_up = (
        (jax.random.normal(k, (len(sizes), D, F)) * 0.3).astype(dtype)
        for k in ks[1:])
    # the rows no group owns hold NaN: none may reach a real row
    at = _real_rows(sizes, real)
    rows = jnp.full_like(rows, jnp.nan).at[at].set(rows[at])
    args = (rows, w_gate, w_up, jnp.asarray(sizes), jnp.asarray(real))
    got = gated_grouped_matmul(*args, activation=activation,
                               tiling=ORDERS[order], interpret=True)
    assert got.shape == (m, F) and got.dtype == jnp.dtype(dtype)
    # every real row is, bit for bit, what a visit that computes its whole
    # row tile in one product gives it (a sub-tile as long as the tile:
    # the kernel as it was before a visit knew its group's real rows)
    monkeypatch.setattr(grouped_matmul, "SUB_ROWS", TM)
    whole_tiles = gated_grouped_matmul.__wrapped__(
        *args, activation=activation, tiling=ORDERS[order], interpret=True)
    np.testing.assert_array_equal(
        np.asarray(got.astype(jnp.float32))[at],
        np.asarray(whole_tiles.astype(jnp.float32))[at])
    got = np.asarray(got.astype(jnp.float32), np.float64)[at]
    want = _float64(rows, w_gate, w_up, sizes, activation)[at]
    assert np.isfinite(got).all()
    if dtype == "float32":
        np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)
    else:
        # gate, up and the result each rounded to bfloat16 once
        np.testing.assert_allclose(
            got, want, rtol=2.0 ** -6, atol=2.0 ** -7 * np.abs(want).max())
    # and `lax.ragged_dot` with `gated`, the path off the TPU, agrees
    # with the kernel as the two dispatches agree (tests/test_moe.py)
    off_chip = gated(
        *(moe._grouped_matmul(rows, w, jnp.asarray(sizes), None, False)
          for w in (w_gate, w_up)), activation)
    np.testing.assert_allclose(
        got, np.asarray(off_chip.astype(jnp.float32))[at],
        rtol=2.0 ** -6 if dtype == "bfloat16" else 2e-5,
        atol=(2.0 ** -6 if dtype == "bfloat16" else 2e-5)
        * np.abs(want).max())


@pytest.mark.parametrize("counts,want", [
    # an even router at 2,112 tokens of 128 x 8: 132 rows an expert are
    # three sub-tiles of 64, where a whole row tile is 256
    ([132] * 128, 128 * 192),
    # a one-sided one: eight experts hold every row
    ([2112] * 8 + [0] * 120, 8 * 2112),
    ([[1, 128, 129, 0], [256, 257, 0, 0]], (1 + 2 + 3 + 4 + 5) * 64),
], ids=["even", "one_sided", "layers"])
def test_the_rows_computed_are_the_counts_in_whole_sub_tiles(counts, want):
    assert grouped_matmul.SUB_ROWS == 64 and TM % grouped_matmul.SUB_ROWS == 0
    got = grouped_matmul.rows_computed(jnp.asarray(counts, jnp.int32))
    assert got.dtype == jnp.int32 and int(got) == want


def test_a_tiling_that_does_not_divide_is_refused():
    rows, w = jnp.zeros((TM, D)), jnp.zeros((2, D, F))
    with pytest.raises(ValueError, match="does not divide"):
        gated_grouped_matmul(rows, w, w, *[jnp.zeros((2,), jnp.int32)] * 2,
                             activation="silu", tiling=(TM, 96, F),
                             interpret=True)


def _widths(e, k, d, f, **more):
    return ModelConfig(num_experts=e, num_experts_per_token=k, embed_dim=d,
                       mlp_dim=f, **more)


MIXTRAL = _widths(8, 2, 4096, 14336)
NARROW = _widths(64, 6, 2560, 768)
SHARE = _widths(16, 12, 6144, 12288, expert_mlp_dim=2048,
                num_routed_experts=512, num_zero_experts=256,
                routed_scaling_factor=6.0)


# which grid order the calls of the benchmark's cells take, as the v5e
# placed it (PERF.md, PR 51): the visits outermost where an even router
# gives every expert one row tile, the columns outermost past that
@pytest.mark.parametrize("cfg,tokens,want", [
    (MIXTRAL, 320, (256, 512, 14336)), (MIXTRAL, 576, (256, 512, 14336)),
    (MIXTRAL, 832, (256, 512, 14336)), (MIXTRAL, 1024, (256, 512, 14336)),
    (MIXTRAL, 1088, (256, 512, 512)), (MIXTRAL, 2112, (256, 512, 512)),
    (NARROW, 576, (256, 768, 768)), (NARROW, 2112, (256, 768, 768)),
    (NARROW, 4096, (256, 768, 768)),
    (SHARE, 1088, (256, 512, 2048)), (SHARE, 2112, (256, 512, 2048)),
], ids=lambda v: getattr(v, "num_experts", v) if not isinstance(v, tuple)
    else "x".join(map(str, v)))
def test_the_way_in_is_tiled_from_the_calls_shape(cfg, tokens, want):
    t_in, t_out = moe._gmm_tilings(cfg, tokens * cfg.num_experts_per_token)
    assert t_in == want
    assert t_out == moe._gmm_tiling(cfg.expert_width, cfg.embed_dim)
    tm, tn, tw = t_in
    # what the kernel asks of the v5e's 128 MiB of VMEM
    assert _vmem_bytes(tm, cfg.embed_dim, tn, tw, 2) < 100 << 20


@pytest.mark.on_tpu
@pytest.mark.parametrize("d,f,activation,tiling", [
    (4096, 14336, "silu", (256, 512, 14336)),    # Mixtral's, visits outermost
    (4096, 14336, "silu", (256, 512, 512)),      # and columns outermost
    (2560, 768, "relu", (256, 768, 768)),        # the narrow widths
], ids=["mixtral-visits", "mixtral-columns", "narrow"])
def test_compiled_on_tpu_at_the_cells_widths(d, f, activation, tiling):
    """The kernel as Mosaic compiles it at the widths the cells run,
    reading two experts out of a stack of four groups, against XLA's
    products and the activation on the chip."""
    assert jax.default_backend() == "tpu"
    sizes = jnp.asarray([0, 300, 212, 0], jnp.int32)  # a shared tile
    ks = jax.random.split(jax.random.key(d), 3)
    rows = jax.random.normal(ks[0], (3 * TM, d)).astype(jnp.bfloat16)
    w_gate, w_up = ((jax.random.normal(k, (4, d, f)) * d ** -0.5).astype(
        jnp.bfloat16) for k in ks[1:])
    got = gated_grouped_matmul(rows, w_gate, w_up, sizes, sizes,
                               activation=activation, tiling=tiling)
    def product(w):  # each group's rows by its matrix, rounded as `gmm`
        return jnp.concatenate([
            jnp.dot(rows[:300], w[1], preferred_element_type=jnp.float32),
            jnp.dot(rows[300:512], w[2], preferred_element_type=jnp.float32)
        ]).astype(jnp.bfloat16)

    want = gated(product(w_gate), product(w_up), activation)
    got, want = (np.asarray(a[:512].astype(jnp.float32))
                 for a in (got, want))
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, want, rtol=2.0 ** -6,
                               atol=2.0 ** -7 * np.abs(want).max())


# -- what a sorted layer lowers to ------------------------------------------

def _outer_eqns(jaxpr):
    """The equations of a jaxpr and of the calls it nests, a kernel's own
    body left out."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name == "pallas_call":
            continue
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _outer_eqns(sub)


@pytest.mark.parametrize("activation", ["silu", "relu"])
def test_a_sorted_layer_is_two_kernels_and_one_array_of_the_experts_width(
        activation):
    m, g, d, f = 2 * TM, LAYERS * E, 128, 512
    S = jax.ShapeDtypeStruct
    jaxpr = jax.make_jaxpr(lambda *xs: moe._grouped_experts(
        *xs, kernel=True, activation=activation,
        tilings=((TM, 128, f), (TM, f, d))))(
            S((m, d), jnp.bfloat16), S((g, d, f), jnp.bfloat16),
            S((g, d, f), jnp.bfloat16), S((g, f, d), jnp.bfloat16),
            S((g,), jnp.int32), S((g,), jnp.int32))
    eqns = list(_outer_eqns(jaxpr.jaxpr))
    kernels = [e for e in eqns if e.primitive.name == "pallas_call"]
    assert len(kernels) == 2
    # gate, up and the activation leave one (rows, F) array behind them:
    # the first kernel's result, which the second reads
    wide = [v for e in eqns if e.primitive.name not in ("jit", "pjit")
            for v in e.outvars if v.aval.shape == (m, f)]
    assert len(wide) == 1 and wide[0] is kernels[0].outvars[0]
    assert kernels[0].params["name"] == "gated_grouped_matmul"


# -- programs that never sort lower to what they did -------------------------

# sha256 of the lowered text (no locations) at commit f915cf5, PR 51's
# parent, at the families' tiny widths: Mixtral's decode-only program and
# the fourth cell's one-walk mixed step (a dense MLP: no experts). A PR
# that means to change these programs takes the hashes again
_PARENTS_TEXT = {
    ("mixtral-8x7b-v0.1", "_decode_rounds"):
        "ef31bf8e158b360819d6ee794968b3c16c8bc272e288819d2871b3549bae05c8",
    ("falcon-h1-34b-instruct", "_mixed_step"):
        "65d5d331687faf602d92272a2ca9f7bff7f7d92c4b0f4054c9304171f986e635",
}


@pytest.mark.parametrize("name,program", sorted(_PARENTS_TEXT))
def test_a_program_that_never_sorts_lowers_to_the_parents_text(
        monkeypatch, name, program):
    from cellbench import families, serve
    from cloud_server_tpu.inference import paged_server as ps
    with open(os.path.join(ROOT, "cellbench", "configs",
                           name + ".json")) as f:
        cfg_file = json.load(f)
    _, mcfg, weights = serve.make_model(
        cfg_file, families.of(cfg_file).TINY, 2**31 + 51)
    srv = ps.PagedInferenceServer(
        weights, mcfg,
        InferConfig(max_decode_len=8, temperature=0.0, eos_token_id=-1,
                    pad_token_id=0),
        decode_chunk=1, max_slots=4, max_context=128, page_size=16,
        num_pages=32, prefill_chunk=32)
    seen = []
    orig = getattr(ps, program)

    def lowering(*args, **kwargs):
        if not seen and kwargs.get("n_rounds", 1) > 0:
            seen.append(orig.lower(*args, **kwargs).as_text())
        return orig(*args, **kwargs)

    monkeypatch.setattr(ps, program, lowering)
    first = srv.submit([5, 9, 3], max_new_tokens=8)
    srv.step()
    second = srv.submit([(k * 7) % 60 + 1 for k in range(40)],
                        max_new_tokens=2)
    srv.run_until_idle()
    assert first.done and second.done and seen
    assert "gated_grouped_matmul" not in seen[0]
    assert hashlib.sha256(seen[0].encode()).hexdigest() == \
        _PARENTS_TEXT[name, program]
