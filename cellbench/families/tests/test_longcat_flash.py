"""The LongCat-Flash family's own tests, beside its file: they name its
leaves and its published keys, which nothing outside `cellbench/families/`
may."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cellbench import families, run, serve
from cellbench.families import longcat_flash

SEED = 2**31 + 20261003


def cell_config():
    bench = run.load_benchmark()
    cell = next(c for c in bench["workloads"]
                if c["config"].startswith("longcat"))
    return run.load_cell(bench, cell["name"])[2]


@pytest.fixture(scope="module", params=sorted(
    longcat_flash.blocks(cell_config())))
def tiny(request):
    variant = longcat_flash.blocks(cell_config())[request.param]
    served, mcfg, weights = serve.make_model(
        variant, longcat_flash.TINY, SEED)
    return request.param, served, mcfg, weights


def test_the_mapping_states_what_the_file_publishes():
    full = longcat_flash.model_config(cell_config())
    assert (full.embed_dim, full.num_heads, full.head_dim, full.v_head_dim,
            full.q_lora_rank, full.kv_lora_rank, full.qk_rope_head_dim,
            full.mlp_dim, full.expert_width) == (
                6144, 64, 192, 128, 1536, 512, 64, 12288, 2048)
    assert (full.num_experts, full.num_routed_experts, full.num_zero_experts,
            full.router_width, full.num_experts_per_token,
            full.routed_scaling_factor) == (16, 512, 256, 768, 12, 6.0)
    assert (full.layer_body, full.latent_dim, full.attention_blocks,
            full.num_layers, full.vocab_size) == (
                "double_shortcut", 576, 2, 4, 16384)
    with pytest.raises(ValueError, match="scales both latent"):
        longcat_flash.model_config({**cell_config(),
                                    "mla_scale_kv_lora": False})
    with pytest.raises(ValueError, match="identity"):
        longcat_flash.model_config({**cell_config(),
                                    "zero_expert_type": "copy"})


def test_a_leaf_the_family_does_not_know_is_an_error():
    assert longcat_flash.fan_in(("layers", "wo"), (4, 2, 64, 128, 6144)) \
        == 8192
    # the matrices that read a scaled latent vector state the hidden size
    assert longcat_flash.fan_in(("layers", "wkv_b"),
                                (4, 2, 512, 64, 256)) == 6144
    assert longcat_flash.fan_in(("layers", "wq_b"),
                                (4, 2, 1536, 64, 192)) == 6144
    assert longcat_flash.fan_in(("layers", "w_down"),
                                (4, 16, 2048, 6144)) == 2048
    assert longcat_flash.fan_in(("layers", "router_bias"), (4, 768)) == 0
    # the router states a quarter of its inputs: twice the spread
    assert longcat_flash.fan_in(("layers", "router"), (4, 6144, 768)) == 1536
    with pytest.raises(KeyError, match="layers/wk"):
        longcat_flash.fan_in(("layers", "wk"), (4, 64, 8))


def test_every_block_runs_and_the_gap_is_the_routers(tiny):
    block, served, mcfg, weights = tiny
    assert (block, mcfg.router_width) == ("shortcut", 12)
    tokens = np.arange(1, 301, dtype=np.int32)
    logits, gap = longcat_flash.forward_logits(weights, tokens, served)
    assert logits.shape == (300, longcat_flash.TINY["vocab_size"])
    whole = np.asarray(logits)
    assert np.isfinite(whole).all()
    np.testing.assert_allclose(np.asarray(logits[250:260]), whole[250:260],
                               atol=1e-5)  # rows made when asked for
    gap = np.asarray(gap)
    assert np.isfinite(gap).all() and (gap >= 0).all()


def test_rows_in_blocks_and_stretches_are_rows_at_once(tiny, monkeypatch):
    """The reference's two departures change no number: queries 64 at a
    time, and an expert over the rows that chose it (at half the even
    share every expert is over its stretch and runs on every row; at 100
    times none is)."""
    _, served, _, weights = tiny
    tokens = np.random.default_rng(5).integers(1, 512, 300)
    monkeypatch.setattr(longcat_flash, "_ROWS_OVER_SHARE", 100)
    want = np.asarray(longcat_flash.forward_logits(weights, tokens,
                                                   served)[0])
    monkeypatch.setattr(longcat_flash, "_ROWS_OVER_SHARE", 0.5)
    jax.clear_caches()
    got = np.asarray(longcat_flash.forward_logits(weights, tokens,
                                                  served)[0])
    np.testing.assert_allclose(got, want, atol=1e-5)
    monkeypatch.setattr(longcat_flash, "_ROWS_OVER_SHARE", 1)
    monkeypatch.setattr(longcat_flash, "_Q_BLOCK", 64)
    jax.clear_caches()
    got = np.asarray(longcat_flash.forward_logits(weights, tokens,
                                                  served)[0])
    np.testing.assert_allclose(got, want, atol=1e-5)


def test_the_reference_reads_each_statement_of_the_description(tiny):
    """Each statement moves the reference's logits when it is taken away,
    so the comparison of the served path with it can see the same fault
    in the program: the latent scale, the rotary part of the score, the
    identity term, the factor, and which stream the experts read."""
    block, served, _, weights = tiny
    tokens = np.random.default_rng(3).integers(1, 512, 120)
    want = np.asarray(longcat_flash.forward_logits(weights, tokens,
                                                   served)[0])
    # with two identity experts fewer, two columns become absent experts'
    for change in ({"routed_scaling_factor": 1.0}, {"rope_theta": 100.0},
                   {"moe_topk": 2}, {"zero_expert_num": 2}):
        got = np.asarray(longcat_flash.forward_logits(
            weights, tokens, {**served, **change})[0])
        assert np.abs(got[20:] - want[20:]).max() > 1e-2, change


def test_the_shares_add_up_to_the_uncut_layers(tiny):
    """The guide's share test on the reference: with weights that hold all
    8 routed experts, the stream after one layer is what the shares of
    the two chips (experts 0 to 3 and 4 to 7) add up to, the dense part
    and the identity term counted once."""
    block, served, mcfg, weights = tiny
    rng = np.random.default_rng(9)
    lw = dict(weights["layers"])
    for k in ("w_gate", "w_up", "w_down"):
        lw[k] = jnp.asarray(rng.normal(size=(2, 8) + lw[k].shape[2:]) / 8,
                            jnp.float32)
    u = jnp.asarray(rng.normal(size=(40, 96)), jnp.float32)
    layer = {k: v[0] for k, v in lw.items()}
    kw = dict(top_k=3, routed=8, factor=6.0)
    uncut, _ = longcat_flash._moe(u, layer, lo=0, hi=8, **kw)
    first, _ = longcat_flash._moe(u, layer, lo=0, hi=4, **kw)
    second, _ = longcat_flash._moe(u, layer, lo=4, hi=8, **kw)
    none = {**layer, **{k: layer[k][:0] for k in ("w_gate", "w_up",
                                                   "w_down")}}
    identity, _ = longcat_flash._moe(u, none, lo=0, hi=0, **kw)
    np.testing.assert_allclose(np.asarray(first + second - identity),
                               np.asarray(uncut), atol=1e-5)
    # a share's gap never lies under the uncut layer's: fewer flips count
    g_all = longcat_flash._moe(u, layer, lo=0, hi=8, **kw)[1]
    g_first = longcat_flash._moe(u, layer, lo=0, hi=4, **kw)[1]
    assert (np.asarray(g_first) >= np.asarray(g_all) - 1e-7).all()


def test_the_cells_file_is_cut_as_the_guide_allows():
    bench = run.load_benchmark()
    entry = next(e for e in bench["configs"]
                 if e["name"].startswith("longcat"))
    cfg = run.load_config(entry)
    cuts = longcat_flash.cuts(cfg)
    assert families.cut_violations(entry["reduced"], cfg, cuts) == []
    assert (cuts["period"], cuts["leading_dense"]) == (1, 0)
    assert entry["reduced"] == ["num_layers", "n_routed_experts",
                                "vocab_size"]
    assert cfg["reduced_from"] == {"num_layers": 28, "n_routed_experts": 512,
                                   "vocab_size": 131072}
    assert [cfg[k] for k in ("num_layers", "n_routed_experts",
                             "vocab_size")] == [4, 16, 16384]
    # every width as LongCat-Flash-Chat publishes it
    assert [cfg[k] for k in (
        "hidden_size", "ffn_hidden_size", "expert_ffn_hidden_size",
        "num_attention_heads", "q_lora_rank", "kv_lora_rank",
        "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "moe_topk",
        "zero_expert_num", "routed_scaling_factor")] == [
            6144, 12288, 2048, 64, 1536, 512, 128, 64, 128, 12, 256, 6]
    # fewer than 8 experts held is no share of this model
    assert families.cut_violations(
        entry["reduced"], {**cfg, "n_routed_experts": 4}, cuts)


def test_the_bytes_the_file_states_are_the_leaves():
    """`reduced_why`'s arithmetic against the program's leaves at the
    published widths (shapes only: nothing is allocated)."""
    mcfg = longcat_flash.model_config(cell_config())
    shapes = longcat_flash.param_shapes(mcfg)
    leaves = jax.tree.leaves(shapes, is_leaf=lambda x: isinstance(x, tuple))
    total = sum(int(np.prod(s)) for s in leaves)
    assert abs(total / 1e9 - 5.17) < 0.01
    assert abs(total * 2 / 2**30 - 9.64) < 0.01
    lay = shapes["layers"]
    expert = sum(int(np.prod(lay[k][2:])) for k in ("w_gate", "w_up",
                                                    "w_down"))
    assert expert == 37_748_736  # 75.5 MB in bfloat16
    mla = sum(int(np.prod(lay[k][2:])) for k in (
        "wq_a", "wq_b", "wkv_a", "wkv_b", "wo"))
    assert abs(mla / 1e6 - 90.6) < 0.1
    assert jnp.dtype(mcfg.param_dtype) == jnp.bfloat16
