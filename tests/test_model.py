import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cloud_server_tpu.config import ModelConfig
from cloud_server_tpu.models import transformer


TINY = ModelConfig(
    vocab_size=64, embed_dim=32, num_layers=2, num_heads=4, num_kv_heads=2,
    head_dim=8, mlp_dim=64, max_seq_len=32, dtype="float32",
    param_dtype="float32", remat="none")


def test_init_shapes_match_declared():
    params = transformer.init_params(TINY, jax.random.key(0))
    got = jax.tree.map(lambda x: tuple(x.shape), params)
    assert got == transformer.param_shapes(TINY)


def test_logical_axes_structure_matches_params():
    params = transformer.init_params(TINY, jax.random.key(0))
    axes = transformer.param_logical_axes(TINY)
    jax.tree.map(
        lambda p, a: None if len(p.shape) == len(a) else pytest.fail(
            f"rank mismatch {p.shape} vs {a}"),
        params, axes, is_leaf=lambda x: isinstance(x, tuple) and
        all(isinstance(i, (str, type(None))) for i in x))


def test_forward_shape_and_dtype():
    params = transformer.init_params(TINY, jax.random.key(0))
    tokens = jax.random.randint(jax.random.key(1), (2, 16), 0, TINY.vocab_size)
    logits = transformer.forward(params, tokens, TINY)
    assert logits.shape == (2, 16, TINY.vocab_size)
    assert logits.dtype == jnp.float32


def test_forward_is_causal():
    params = transformer.init_params(TINY, jax.random.key(0))
    tokens = jax.random.randint(jax.random.key(1), (1, 16), 0, TINY.vocab_size)
    base = transformer.forward(params, tokens, TINY)
    perturbed = tokens.at[0, -1].set((tokens[0, -1] + 1) % TINY.vocab_size)
    pert = transformer.forward(params, perturbed, TINY)
    np.testing.assert_allclose(np.asarray(base[0, :-1]),
                               np.asarray(pert[0, :-1]), atol=1e-5)


def test_remat_matches_no_remat():
    cfg_r = ModelConfig(**{**TINY.__dict__, "remat": "full"})
    params = transformer.init_params(TINY, jax.random.key(0))
    tokens = jax.random.randint(jax.random.key(1), (2, 8), 0, TINY.vocab_size)

    def loss(p, cfg):
        return transformer.next_token_loss(p, {"tokens": tokens}, cfg)[0]

    l1, g1 = jax.value_and_grad(loss)(params, TINY)
    l2, g2 = jax.value_and_grad(loss)(params, cfg_r)
    np.testing.assert_allclose(float(l1), float(l2), rtol=1e-6)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        np.asarray(a), np.asarray(b), atol=1e-5), g1, g2)


def test_tied_embeddings():
    cfg = ModelConfig(**{**TINY.__dict__, "tie_embeddings": True})
    params = transformer.init_params(cfg, jax.random.key(0))
    assert "lm_head" not in params
    tokens = jnp.zeros((1, 4), jnp.int32)
    assert transformer.forward(params, tokens, cfg).shape == (1, 4, cfg.vocab_size)


def test_loss_decreases_under_sgd():
    """Tiny model memorises a fixed batch — end-to-end gradient sanity."""
    params = transformer.init_params(TINY, jax.random.key(0))
    tokens = jax.random.randint(jax.random.key(1), (4, 16), 0, TINY.vocab_size)
    batch = {"tokens": tokens}

    @jax.jit
    def step(p):
        (l, _), g = jax.value_and_grad(
            transformer.next_token_loss, has_aux=True)(p, batch, TINY)
        return l, jax.tree.map(lambda w, gw: w - 0.5 * gw, p, g)

    losses = []
    for _ in range(10):
        l, params = step(params)
        losses.append(float(l))
    assert losses[-1] < losses[0] * 0.7, losses


def test_loss_mask_ignores_padding():
    params = transformer.init_params(TINY, jax.random.key(0))
    tokens = jax.random.randint(jax.random.key(1), (2, 8), 0, TINY.vocab_size)
    full_mask = jnp.ones_like(tokens)
    half_mask = full_mask.at[:, 4:].set(0)
    l_full, _ = transformer.next_token_loss(params, {"tokens": tokens,
                                                     "mask": full_mask}, TINY)
    l_half, _ = transformer.next_token_loss(params, {"tokens": tokens,
                                                     "mask": half_mask}, TINY)
    # Changing tokens in the masked region must not change the masked loss.
    tokens2 = tokens.at[:, 6].set((tokens[:, 6] + 3) % TINY.vocab_size)
    l_half2, _ = transformer.next_token_loss(params, {"tokens": tokens2,
                                                      "mask": half_mask}, TINY)
    assert not np.isclose(float(l_full), float(l_half))
    # masked-out target positions don't contribute...
    # (tokens[:,6] is a target only at position 5 -> masked)
    np.testing.assert_allclose(float(l_half), float(l_half2), rtol=1e-5)


def test_fused_ce_matches_dense():
    """vocab_chunk>0 (blockwise CE) must match the dense logits path on
    loss, metrics, and gradients."""
    base = dict(vocab_size=97, embed_dim=32, num_layers=2, num_heads=4,
                num_kv_heads=2, head_dim=8, mlp_dim=64, max_seq_len=16,
                dtype="float32", param_dtype="float32", logits_softcap=30.0)
    dense_cfg = ModelConfig(**base)
    fused_cfg = ModelConfig(**base, vocab_chunk=32)  # 97 = 3*32 + 1 (pad)
    params = transformer.init_params(dense_cfg, jax.random.key(0))
    tokens = jax.random.randint(jax.random.key(1), (2, 16), 0, 97)
    mask = (jax.random.uniform(jax.random.key(2), (2, 16)) > 0.2)
    batch = {"tokens": tokens, "mask": mask}

    (ld, md), gd = jax.value_and_grad(
        transformer.next_token_loss, has_aux=True)(
            params, batch, dense_cfg, 1e-3)
    (lf, mf), gf = jax.value_and_grad(
        transformer.next_token_loss, has_aux=True)(
            params, batch, fused_cfg, 1e-3)

    np.testing.assert_allclose(float(lf), float(ld), rtol=1e-5)
    for k in md:
        np.testing.assert_allclose(float(mf[k]), float(md[k]), rtol=1e-5,
                                   err_msg=f"metric {k}")
    flat_d = jax.tree.leaves(gd)
    flat_f = jax.tree.leaves(gf)
    for a, b in zip(flat_f, flat_d):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-5, rtol=1e-4)


# -- what a model states of its blocks beside q, k, v, o and a gated MLP ----

def test_stated_leaves_are_declared_drawn_and_counted():
    """`qk_norm`, `attention_gate` and `post_norms` on the dense model, and
    the expert model's two stacks: every leaf declared is drawn, has its
    logical axes, and the count is the widths' arithmetic."""
    from cloud_server_tpu.models import moe
    dense = ModelConfig(**{**TINY.__dict__, "qk_norm": True,
                           "attention_gate": True, "post_norms": True})
    params = transformer.init_params(dense, jax.random.key(0))
    assert jax.tree.map(lambda x: tuple(x.shape),
                        params) == transformer.param_shapes(dense)
    extra = {"q_norm": (2, 8), "k_norm": (2, 8), "wg": (2, 32, 4, 8),
             "attn_post_norm": (2, 32), "mlp_post_norm": (2, 32)}
    assert {k: params["layers"][k].shape for k in extra} == extra
    assert set(transformer.param_shapes(TINY)["layers"]) == set(
        params["layers"]) - set(extra)
    for name in ("q_norm", "k_norm", "attn_post_norm", "mlp_post_norm"):
        assert (np.asarray(params["layers"][name]) == 1).all()
    two = ModelConfig(**{**dense.__dict__, "num_layers": 5,
                         "num_dense_layers": 2, "num_experts": 4,
                         "num_experts_per_token": 2, "expert_mlp_dim": 16,
                         "shared_expert_dim": 24,
                         # room for every row: a later token drops none
                         "expert_capacity_factor": 2.0,
                         "router_score": "sigmoid", "route_scale": 2.0})
    shapes = moe.param_shapes(two)
    params = moe.init_params(two, jax.random.key(1))
    assert jax.tree.map(lambda x: tuple(x.shape), params) == shapes
    axes = moe.param_logical_axes(two)
    is_axes = lambda x: isinstance(x, tuple) and all(  # noqa: E731
        isinstance(i, (str, type(None))) for i in x)
    assert jax.tree.map(len, axes, is_leaf=is_axes) == jax.tree.map(
        len, shapes, is_leaf=lambda x: isinstance(x, tuple))
    d, h, kh, dh = 32, 4, 2, 8
    attention = 2 * d * h * dh + d * h * dh + 2 * d * kh * dh + 4 * d + 2 * dh
    lead = attention + 3 * d * 64
    layer = (attention + 4 * 3 * d * 16 + 3 * d * 24 + d * 4 + 4)
    count = lambda t: sum(x.size for x in jax.tree.leaves(t))  # noqa: E731
    assert count(params["lead_layers"]) == 2 * lead
    assert count(params["layers"]) == 3 * layer
    assert shapes["lead_layers"]["w_gate"] == (2, 32, 64)
    assert shapes["layers"]["w_gate"] == (3, 4, 32, 16)
    assert (np.asarray(params["layers"]["router_bias"]) == 0).all()
    # the scans over both stacks: a forward, causal like any other
    tokens = jax.random.randint(jax.random.key(2), (1, 16), 0, 64)
    base, aux = moe.forward(params, tokens, two)
    assert base.shape == (1, 16, 64) and np.isfinite(np.asarray(base)).all()
    pert, _ = moe.forward(params, tokens.at[0, -1].add(1) % 64, two)
    np.testing.assert_allclose(np.asarray(base[0, :-1]),
                               np.asarray(pert[0, :-1]), atol=1e-5)
    assert set(aux) == {"load_balance", "router_z", "dropped_frac"}
