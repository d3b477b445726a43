"""The SmallThinker family's own tests, beside its file: they name its
leaves and its published keys, which nothing outside `cellbench/families/`
may."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cellbench import families, run, serve
from cellbench.families import smallthinker

SEED = 2**31 + 20250930


def cell_config():
    bench = run.load_benchmark()
    cell = next(c for c in bench["workloads"]
                if c["config"].startswith("smallthinker"))
    return run.load_cell(bench, cell["name"])[2]


@pytest.fixture(scope="module")
def tiny():
    served, mcfg, weights = serve.make_model(
        cell_config(), smallthinker.TINY, SEED)
    return served, mcfg, weights


def test_the_mapping_states_the_pattern_the_file_publishes(tiny):
    served, mcfg, _ = tiny
    # the first 8 of the 52 published flags: full, then three windows
    assert mcfg.layer_kinds == ("full", "window", "window", "window") * 2
    assert [mcfg.layer_rope(i) for i in range(8)] == [
        False, True, True, True] * 2
    assert mcfg.q_per_kv == 7 and mcfg.sliding_window == 200
    assert (mcfg.mlp_activation, mcfg.router_input) == (
        "relu", "layer_input")
    full = smallthinker.model_config(cell_config())
    assert (full.sliding_window, full.num_experts,
            full.num_experts_per_token, full.mlp_dim) == (4096, 64, 6, 768)
    # nothing can overflow an expert: experts / experts a token
    assert full.expert_capacity_factor * 6 >= 64


def test_a_leaf_the_family_does_not_know_is_an_error():
    assert smallthinker.fan_in(("layers", "wo"), (8, 7, 16, 64)) == 112
    assert smallthinker.fan_in(("layers", "w_down"), (8, 8, 32, 64)) == 32
    assert smallthinker.fan_in(("final_norm", "scale"), (64,)) == 0
    assert smallthinker.fan_in(("layers", "wq"), (8, 2560, 28, 128)) == 2560
    with pytest.raises(KeyError, match="layers/w_latent"):
        smallthinker.fan_in(("layers", "w_latent"), (8, 64, 8))


def test_the_gap_is_the_routers_and_rows_are_made_when_asked_for(tiny):
    served, _, weights = tiny
    tokens = np.arange(1, 301, dtype=np.int32)
    logits, gap = smallthinker.forward_logits(weights, tokens, served)
    assert logits.shape == (300, smallthinker.TINY["vocab_size"])
    whole = np.asarray(logits)
    assert whole.shape == logits.shape and np.isfinite(whole).all()
    np.testing.assert_array_equal(np.asarray(logits[250:260]),
                                  whole[250:260])
    np.testing.assert_array_equal(np.asarray(logits[7]), whole[7])
    gap = np.asarray(gap)
    assert np.isfinite(gap).all() and (gap >= 0).all()


@pytest.mark.parametrize("over_share", [0.5, 1.0, 2.0])
def test_an_expert_over_its_rows_is_the_expert_over_all_rows(
        tiny, monkeypatch, over_share):
    """The reference runs an expert on the rows that chose it. 0.5 of the
    even share: every expert is over it and takes the dense product; 1.0:
    some are over and some under; 2.0, the file's own: none is. Each is
    the dense product's logits to float32 rounding (1e-5: sums of 8
    layers of unit-scale numbers in another order), and each token still
    reaches all of its experts."""
    served, _, weights = tiny
    tokens = np.random.default_rng(5).integers(1, 512, 300)
    monkeypatch.setattr(smallthinker, "_ROWS_OVER_SHARE", 100.0)
    want, want_gap = smallthinker.forward_logits(weights, tokens, served)
    monkeypatch.setattr(smallthinker, "_ROWS_OVER_SHARE", over_share)
    got, gap = smallthinker.forward_logits(weights, tokens, served)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)
    np.testing.assert_allclose(np.asarray(gap), np.asarray(want_gap),
                               atol=1e-5)


def test_the_reference_reads_the_window_the_layouts_and_the_layers_input(
        tiny):
    """Each statement of the published description moves the reference's
    logits past the window when it is taken away, so the comparison of
    the served path with it can see the same fault in the program."""
    served, _, weights = tiny
    tokens = np.random.default_rng(3).integers(1, 512, 320)
    want = np.asarray(smallthinker.forward_logits(weights, tokens, served)[0])
    for change in ({"sliding_window_size": 4096},
                   {"rope_layout": [1] * 52},
                   {"sliding_window_layout": [1] * 52}):
        got = np.asarray(smallthinker.forward_logits(
            weights, tokens, {**served, **change})[0])
        # positions inside the window of every layer read the same keys
        if "rope_layout" not in change:
            np.testing.assert_allclose(got[:190], want[:190], atol=1e-4)
        assert np.abs(got[260:] - want[260:]).max() > 1e-2, change


def test_the_cells_file_is_cut_as_the_guide_allows():
    bench = run.load_benchmark()
    entry = next(e for e in bench["configs"]
                 if e["name"].startswith("smallthinker"))
    cfg = run.load_config(entry)
    cuts = smallthinker.cuts(cfg)
    assert families.cut_violations(entry["reduced"], cfg, cuts) == []
    assert (cuts["period"], cuts["leading_dense"]) == (4, 0)
    assert entry["reduced"] == ["num_hidden_layers"]
    assert cfg["num_hidden_layers"] == 8 and cfg["reduced_from"] == {
        "num_hidden_layers": 52}
    # every width as SmallThinker-21BA3B-Instruct publishes it, and both
    # layouts whole
    assert [cfg[k] for k in (
        "hidden_size", "moe_ffn_hidden_size", "head_dim",
        "num_attention_heads", "num_key_value_heads",
        "moe_num_primary_experts", "moe_num_active_primary_experts",
        "sliding_window_size", "vocab_size")] == [
            2560, 768, 128, 28, 4, 64, 6, 4096, 151936]
    assert cfg["sliding_window_layout"] == [0, 1, 1, 1] * 13
    assert cfg["rope_layout"] == [0, 1, 1, 1] * 13
    # a cut that leaves a broken period is one
    assert families.cut_violations(
        entry["reduced"], {**cfg, "num_hidden_layers": 6}, cuts)


def test_the_bytes_the_file_states_are_the_leaves():
    """`reduced_why`'s arithmetic against the program's leaves at the
    published widths (shapes only: nothing is allocated)."""
    mcfg = smallthinker.model_config(cell_config())
    shapes = smallthinker.param_shapes(mcfg)
    leaves = jax.tree.leaves(shapes, is_leaf=lambda x: isinstance(x, tuple))
    total = sum(int(np.prod(s)) for s in leaves)
    layer = sum(int(np.prod(s[1:])) for s in jax.tree.leaves(
        shapes["layers"], is_leaf=lambda x: isinstance(x, tuple)))
    assert layer == 398_627_840  # 0.742 GiB in bfloat16
    assert abs(total * 2 / 2**30 - 7.39) < 0.01
    assert jnp.dtype(mcfg.param_dtype) == jnp.bfloat16
