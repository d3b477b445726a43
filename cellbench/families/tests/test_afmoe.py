"""The AFMoE family's own tests, beside its file: they name its leaves and
its published keys, which nothing outside `cellbench/families/` may."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cellbench import check_seeds, families, run, serve
from cellbench.families import afmoe

SEED = 2**31 + 20261004
CELL = "trinity-mini.longshort-closed"


def cell_config():
    return run.load_cell(run.load_benchmark(), CELL)[2]


def count(shapes) -> int:
    return sum(int(np.prod(s)) for s in jax.tree.leaves(
        shapes, is_leaf=lambda x: isinstance(x, tuple)))


@pytest.fixture(scope="module")
def tiny():
    served, mcfg, weights = serve.make_model(cell_config(), afmoe.TINY, SEED)
    # on the chip the bias is all ones and the choice the scores' own; here
    # it is drawn, so that it moves the choice and a gate that read it
    # would show
    bias = weights["layers"]["router_bias"]
    weights["layers"]["router_bias"] = 0.1 * jax.random.normal(
        jax.random.key(7), bias.shape, bias.dtype)
    return served, mcfg, weights


def test_the_mapping_states_what_the_file_publishes(tiny):
    full = afmoe.model_config(cell_config())
    assert (full.embed_dim, full.num_heads, full.num_kv_heads, full.head_dim,
            full.mlp_dim, full.expert_width, full.shared_expert_dim,
            full.num_experts, full.num_experts_per_token, full.vocab_size,
            full.sliding_window, full.num_layers, full.num_dense_layers) == (
                2048, 32, 4, 128, 6144, 1024, 1024, 128, 8, 200192, 2048, 6,
                2)
    # the first six of the published `layer_types`; the window layers are
    # the rotary ones
    assert full.layer_kinds == ("window",) * 3 + ("full",) + ("window",) * 2
    assert [full.layer_rope(i) for i in range(6)] == [
        True, True, True, False, True, True]
    assert (full.router_score, full.route_scale) == ("sigmoid", 2.826)
    assert full.qk_norm and full.attention_gate and full.post_norms
    assert full.embedding_multiplier == 2048 ** 0.5
    assert (full.rope_theta, full.norm_eps) == (10000.0, 1e-5)
    # nothing can overflow an expert: experts / experts a token
    assert full.expert_capacity_factor * 8 >= 128
    mcfg = tiny[1]
    assert mcfg.q_per_kv == 8 and mcfg.sliding_window == 200
    for change, match in (({"n_group": 2}, "groups of experts"),
                          ({"score_func": "softmax"}, "sigmoid"),
                          ({"route_norm": False}, "renormalises"),
                          ({"rope_scaling": {"type": "yarn"}}, "rope_scal")):
        with pytest.raises(ValueError, match=match):
            afmoe.model_config({**cell_config(), **change})


def test_a_leaf_the_family_does_not_know_is_an_error():
    assert afmoe.fan_in(("layers", "wo"), (4, 32, 128, 2048)) == 4096
    assert afmoe.fan_in(("layers", "wg"), (4, 2048, 32, 128)) == 2048
    assert afmoe.fan_in(("lead_layers", "w_down"), (2, 6144, 2048)) == 6144
    assert afmoe.fan_in(("layers", "w_down"), (4, 128, 1024, 2048)) == 1024
    assert afmoe.fan_in(("layers", "shared_w_up"), (4, 2048, 1024)) == 2048
    assert afmoe.fan_in(("layers", "router"), (4, 2048, 128)) == 2048
    # all ones: every norm's scale, and the bias, under which the choice
    # is the scores' own
    for leaf in ("attn_norm", "attn_post_norm", "mlp_norm", "mlp_post_norm",
                 "q_norm", "k_norm", "router_bias"):
        assert afmoe.fan_in(("layers", leaf), (4, 128)) == 0
    with pytest.raises(KeyError, match="layers/w_latent"):
        afmoe.fan_in(("layers", "w_latent"), (4, 64, 8))


def test_the_leaves_add_up_to_the_files_arithmetic():
    """`reduced_why`'s arithmetic against the program's leaves at the
    published widths (shapes only: nothing is allocated): 4,306.6 M
    parameters kept, 26.1 B uncut."""
    cfg = cell_config()
    mcfg = afmoe.model_config(cfg)
    shapes = afmoe.param_shapes(mcfg)
    assert abs(count(shapes) / 1e6 - 4306.6) < 0.1
    assert abs(count(shapes) * 2 / 2**30 - 8.02) < 0.01
    lead = sum(int(np.prod(s[1:])) for s in shapes["lead_layers"].values())
    layer = sum(int(np.prod(s[1:])) for s in shapes["layers"].values())
    experts = sum(int(np.prod(shapes["layers"][k][1:]))
                  for k in ("w_gate", "w_up", "w_down"))
    assert abs(lead / 1e6 - 65.0) < 0.1 and abs(layer / 1e6 - 839.1) < 0.1
    assert abs(experts / 1e6 - 805.3) < 0.1
    assert shapes["lead_layers"]["wq"][0] == 2
    assert shapes["layers"]["wq"][0] == 4
    uncut = afmoe.param_shapes(afmoe.model_config(
        {**cfg, "num_hidden_layers": 32}))
    assert abs(count(uncut) / 1e9 - 26.1) < 0.05
    assert jnp.dtype(mcfg.param_dtype) == jnp.bfloat16


def test_the_cells_file_is_cut_as_the_guide_allows():
    bench = run.load_benchmark()
    entry = next(e for e in bench["configs"] if e["name"] == "trinity-mini")
    cfg = run.load_config(entry)
    cuts = afmoe.cuts(cfg)
    assert families.cut_violations(entry["reduced"], cfg, cuts) == []
    assert (cuts["period"], cuts["leading_dense"]) == (4, 2)
    assert entry["reduced"] == ["num_hidden_layers"]
    assert cfg["num_hidden_layers"] == 6 and cfg["reduced_from"] == {
        "num_hidden_layers": 32}
    # every width as Trinity-Mini publishes it, and the pattern whole
    assert [cfg[k] for k in (
        "hidden_size", "intermediate_size", "moe_intermediate_size",
        "head_dim", "num_attention_heads", "num_key_value_heads",
        "num_experts", "num_experts_per_tok", "num_shared_experts",
        "num_dense_layers", "sliding_window", "vocab_size")] == [
            2048, 6144, 1024, 128, 32, 4, 128, 8, 1, 2, 2048, 200192]
    assert cfg["layer_types"] == (
        ["sliding_attention"] * 3 + ["full_attention"]) * 8
    # a fifth expert layer breaks the period; three are too few
    for depth in (7, 5):
        assert families.cut_violations(
            entry["reduced"], {**cfg, "num_hidden_layers": depth}, cuts)


def test_the_gap_is_the_routers_and_rows_are_made_when_asked_for(tiny):
    served, _, weights = tiny
    tokens = np.arange(1, 301, dtype=np.int32)
    logits, gap = afmoe.forward_logits(weights, tokens, served)
    assert logits.shape == (300, afmoe.TINY["vocab_size"])
    whole = np.asarray(logits)
    assert whole.shape == logits.shape and np.isfinite(whole).all()
    np.testing.assert_array_equal(np.asarray(logits[250:260]),
                                  whole[250:260])
    np.testing.assert_array_equal(np.asarray(logits[7]), whole[7])
    gap = np.asarray(gap)
    # a margin of sigmoid scores plus a bias of a tenth
    assert np.isfinite(gap).all() and (gap >= 0).all() and gap.max() < 1.5
    # causal: a later token moves no earlier logit
    other = tokens.copy()
    other[200:] = 7
    np.testing.assert_allclose(
        np.asarray(afmoe.forward_logits(weights, other, served)[0][:200]),
        whole[:200], atol=1e-5)


def test_queries_in_blocks_are_one_matrix_of_scores(tiny, monkeypatch):
    """The reference's departures change no number: queries in blocks of 64
    against one (S, S) matrix a head (a block as long as the sequence), and
    an expert over the rows that chose it against every row through every
    expert."""
    served, _, weights = tiny
    tokens = np.random.default_rng(5).integers(1, 512, 300)
    monkeypatch.setattr(afmoe, "_Q_BLOCK", 512)
    monkeypatch.setattr(afmoe, "_ROWS_OVER_SHARE", 100.0)
    want, want_gap = afmoe.forward_logits(weights, tokens, served)
    want = np.asarray(want)
    for block, over_share in ((64, 100.0), (512, 1.0), (512, 3.0)):
        monkeypatch.setattr(afmoe, "_Q_BLOCK", block)
        monkeypatch.setattr(afmoe, "_ROWS_OVER_SHARE", over_share)
        jax.clear_caches()
        got, gap = afmoe.forward_logits(weights, tokens, served)
        np.testing.assert_allclose(np.asarray(got), want, atol=1e-5)
        np.testing.assert_allclose(np.asarray(gap), np.asarray(want_gap),
                                   atol=1e-6)


def test_the_reference_reads_each_statement_of_the_description(tiny):
    """Each statement moves the reference's logits when it is changed, so
    the comparison of the served path with it can see the same fault in
    the program."""
    served, _, weights = tiny
    tokens = np.random.default_rng(3).integers(1, 512, 320)
    want = np.asarray(afmoe.forward_logits(weights, tokens, served)[0])
    full = ["full_attention"] * 32
    window = ["sliding_attention"] * 32
    for change in ({"sliding_window": 4096}, {"layer_types": full},
                   {"layer_types": window}, {"route_scale": 1.0},
                   {"route_norm": False}, {"mup_enabled": False},
                   {"num_experts_per_tok": 2}, {"rope_theta": 100.0}):
        got = np.asarray(afmoe.forward_logits(
            weights, tokens, {**served, **change})[0])
        assert np.abs(got[260:] - want[260:]).max() > 1e-2, change
    # positions inside the window of every layer read the same keys
    got = np.asarray(afmoe.forward_logits(
        weights, tokens, {**served, "sliding_window": 4096})[0])
    np.testing.assert_allclose(got[:190], want[:190], atol=1e-4)
    # the bias moves the choice, and so the logits; each leaf the
    # description adds to a plain block is read
    for leaf, stack in (("router_bias", "layers"), ("wg", "lead_layers"),
                        ("q_norm", "layers"), ("attn_post_norm", "layers"),
                        ("mlp_post_norm", "lead_layers"),
                        ("shared_w_down", "layers")):
        other = {**weights, stack: {**weights[stack],
                                    leaf: 0.5 * weights[stack][leaf]}}
        if leaf == "router_bias":
            other[stack][leaf] = jnp.zeros_like(weights[stack][leaf])
        got = np.asarray(afmoe.forward_logits(other, tokens, served)[0])
        assert np.abs(got - want).max() > 1e-3, leaf


@pytest.mark.parametrize("block", ["experts", "dense"])
def test_every_block_served_is_the_reference(block):
    """Both entries of `blocks` through the paged server at `TINY` (chunked
    prefill, both pools, decode) against the reference, in float32: at
    rounding. (`cellbench/tests/test_reference_vs_system.py` runs the same
    with the int8 controls beside it.)"""
    bench = run.load_benchmark()
    _, wl, cfg = run.load_cell(bench, CELL)
    variant = afmoe.blocks(cfg)[block]
    mcfg = afmoe.model_config(families.lay_over(variant, afmoe.TINY))
    assert (mcfg.num_experts >= 2) == (block == "experts")
    assert mcfg.num_dense_layers == (2 if block == "experts" else 0)
    out = check_seeds.one_seed(wl, variant, SEED, "", afmoe.TINY,
                               [272, 300], 2, 8)
    assert out["finite"] and out["logprob_mean_abs_diff"] < 2e-5
    assert out["margin_max"] < 1e-4 and out["stable_diff_over_share"] == 0
