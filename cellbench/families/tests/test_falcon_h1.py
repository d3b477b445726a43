"""The Falcon-H1 family's own tests, beside its file: they name its leaves
and its published keys, which nothing outside `cellbench/families/` may."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cellbench import families, run, serve
from cellbench.families import falcon_h1

SEED = 2**31 + 20261004


def cell_config():
    bench = run.load_benchmark()
    cell = next(c for c in bench["workloads"]
                if c["config"].startswith("falcon-h1"))
    return run.load_cell(bench, cell["name"])[2]


@pytest.fixture(scope="module")
def tiny():
    return serve.make_model(cell_config(), falcon_h1.TINY, SEED)


def test_the_mapping_states_what_the_file_publishes():
    full = falcon_h1.model_config(cell_config())
    assert (full.embed_dim, full.num_heads, full.num_kv_heads, full.head_dim,
            full.mlp_dim, full.vocab_size, full.num_layers) == (
                5120, 20, 4, 128, 21504, 261120, 6)
    assert (full.layer_body, full.ssm_heads, full.ssm_head_dim,
            full.ssm_state_dim, full.ssm_groups, full.ssm_conv_width,
            full.ssm_chunk, full.ssm_inner, full.ssm_conv_dim) == (
                "parallel_mixer", 32, 128, 256, 2, 4, 128, 4096, 5120)
    assert (full.rope_theta, full.norm_eps) == (1e11, 1e-5)
    assert full.ssm_multipliers == tuple(cell_config()["ssm_multipliers"])
    with pytest.raises(ValueError, match="norms the gated output"):
        falcon_h1.model_config({**cell_config(),
                                "mamba_norm_before_gate": True})
    with pytest.raises(ValueError, match="draws its weights for"):
        falcon_h1.model_config({**cell_config(), "key_multiplier": 1.0})
    with pytest.raises(ValueError, match="mamba_d_ssm"):
        falcon_h1.model_config({**cell_config(), "mamba_d_ssm": 10240})


def test_a_leaf_the_family_does_not_know_is_an_error():
    # a leaf behind a multiplier m states its fan-in times m squared
    assert falcon_h1.fan_in(("layers", "wq"), (6, 5120, 20, 128)) == 5120
    assert falcon_h1.fan_in(("layers", "wk"), (6, 5120, 4, 128)) == 1
    assert falcon_h1.fan_in(("layers", "wo"), (6, 20, 128, 5120)) == 4
    assert falcon_h1.fan_in(("layers", "w_gate"), (6, 5120, 21504)) == 160
    assert falcon_h1.fan_in(("layers", "w_down"), (6, 21504, 5120)) == 3
    assert falcon_h1.fan_in(("layers", "ssm_in"), (6, 5120, 9248)) == 20
    assert falcon_h1.fan_in(("layers", "ssm_out"), (6, 4096, 5120)) == 32
    assert falcon_h1.fan_in(("lm_head", "kernel"), (5120, 261120)) == 1
    assert falcon_h1.fan_in(("embed", "tokens"), (261120, 5120)) == 163840
    for leaf in ("ssm_dt_bias", "ssm_d"):
        assert falcon_h1.fan_in(("layers", leaf), (6, 32)) == 1
    # A itself, drawn so near zero that every seeded head remembers
    assert falcon_h1.fan_in(("layers", "ssm_a"), (6, 32)) == 1_000_000
    assert falcon_h1.fan_in(("layers", "ssm_conv"), (6, 4, 5120)) == 4
    assert falcon_h1.fan_in(("layers", "ssm_conv_bias"), (6, 5120)) == 4
    assert falcon_h1.fan_in(("layers", "ssm_norm"), (6, 4096)) == 0
    with pytest.raises(KeyError, match="layers/router"):
        falcon_h1.fan_in(("layers", "router"), (6, 5120, 8))


def test_the_reference_runs_and_makes_no_discrete_choice(tiny):
    served, mcfg, weights = tiny
    tokens = np.arange(1, 301, dtype=np.int32)
    logits, gap = falcon_h1.forward_logits(weights, tokens, served)
    assert logits.shape == (300, falcon_h1.TINY["vocab_size"])
    whole = np.asarray(logits)
    assert np.isfinite(whole).all()
    np.testing.assert_allclose(np.asarray(logits[250:260]), whole[250:260],
                               atol=1e-5)  # rows made when asked for
    assert np.isinf(np.asarray(gap)).all()
    # causal: a later token moves no earlier logit
    other = tokens.copy()
    other[200:] = 7
    np.testing.assert_allclose(
        np.asarray(falcon_h1.forward_logits(weights, other, served)[0][:200]),
        whole[:200], atol=1e-5)


def test_blocks_of_queries_and_of_the_vocabulary_change_no_number(
        tiny, monkeypatch):
    served, _, weights = tiny
    tokens = np.random.default_rng(5).integers(1, 512, 300)
    want = np.asarray(falcon_h1.forward_logits(weights, tokens, served)[0])
    monkeypatch.setattr(falcon_h1, "_Q_BLOCK", 64)
    monkeypatch.setattr(falcon_h1, "_V_BLOCK", 128)
    jax.clear_caches()
    got = np.asarray(falcon_h1.forward_logits(weights, tokens, served)[0])
    np.testing.assert_allclose(got, want, atol=1e-5)


def test_the_reference_reads_each_statement_of_the_description(tiny):
    """Each statement moves the reference's logits when it is changed, so
    the comparison of the served path with it can see the same fault in
    the program: every multiplier, the rotary base, the norm's epsilon
    through the gated norm, and the heads' split into groups."""
    served, _, weights = tiny
    tokens = np.random.default_rng(3).integers(1, 512, 120)
    want = np.asarray(falcon_h1.forward_logits(weights, tokens, served)[0])
    changes = [{k: 1.0} for k in (
        "embedding_multiplier", "lm_head_multiplier",
        "attention_out_multiplier", "key_multiplier", "ssm_in_multiplier",
        "ssm_out_multiplier")]
    changes += [{"attention_in_multiplier": 0.5}, {"rope_theta": 100.0}]
    for i in range(5):
        m = list(served["ssm_multipliers"])
        m[i] = 1.0
        changes.append({"ssm_multipliers": m})
    for i in range(2):
        m = list(served["mlp_multipliers"])
        m[i] = 1.0
        changes.append({"mlp_multipliers": m})
    changes.append({"mamba_n_groups": 1, "mamba_d_state": 64})
    for change in changes:
        got = np.asarray(falcon_h1.forward_logits(
            weights, tokens, {**served, **change})[0])
        assert np.abs(got[20:] - want[20:]).max() > 1e-3, change


def test_the_cells_file_is_cut_as_the_guide_allows():
    bench = run.load_benchmark()
    entry = next(e for e in bench["configs"]
                 if e["name"].startswith("falcon-h1"))
    cfg = run.load_config(entry)
    cuts = falcon_h1.cuts(cfg)
    assert families.cut_violations(entry["reduced"], cfg, cuts) == []
    assert (cuts["period"], cuts["leading_dense"], cuts["experts"],
            cuts["vocab"]) == (1, 0, None, None)
    assert entry["reduced"] == ["num_hidden_layers"]
    assert cfg["reduced_from"] == {"num_hidden_layers": 72}
    # every width as Falcon-H1-34B-Instruct publishes it
    assert [cfg[k] for k in (
        "hidden_size", "intermediate_size", "num_attention_heads",
        "num_key_value_heads", "head_dim", "mamba_d_ssm", "mamba_n_heads",
        "mamba_d_head", "mamba_d_state", "mamba_n_groups", "mamba_d_conv",
        "mamba_chunk_size", "vocab_size")] == [
            5120, 21504, 20, 4, 128, 4096, 32, 128, 256, 2, 4, 128, 261120]
    # fewer than 4 layers is not this model any more
    assert families.cut_violations(
        entry["reduced"], {**cfg, "num_hidden_layers": 3}, cuts)


def test_the_bytes_the_file_states_are_the_leaves():
    """`reduced_why`'s arithmetic against the program's leaves at the
    published widths (shapes only: nothing is allocated)."""
    mcfg = falcon_h1.model_config(cell_config())
    shapes = falcon_h1.param_shapes(mcfg)
    leaves = jax.tree.leaves(shapes, is_leaf=lambda x: isinstance(x, tuple))
    total = sum(int(np.prod(s)) for s in leaves)
    assert abs(total * 2 / 1e9 - 10.51) < 0.01
    lay = shapes["layers"]
    per_layer = sum(int(np.prod(s[1:])) for s in lay.values())
    assert abs(per_layer / 1e6 - 430.2) < 0.1
    mixer_leaves = sum(int(np.prod(lay[k][1:])) for k in lay
                       if k.startswith("ssm_"))
    assert abs(mixer_leaves / 1e6 - 68.4) < 0.1
    assert jnp.dtype(mcfg.param_dtype) == jnp.bfloat16
    from cloud_server_tpu.models import mixer
    state, conv = mixer.state_shapes(mcfg, 64)
    held = 6 * (int(np.prod(state)) * 4 + int(np.prod(conv)) * 2)
    assert abs(held / 1e9 - 1.622) < 0.001


def test_what_the_seeded_mixer_remembers_is_what_the_file_says():
    assert falcon_h1.remembering_share(32) == 1.0
    assert falcon_h1.remembering_share(128) == 1.0
    assert falcon_h1.remembering_share(512) > 0.99
