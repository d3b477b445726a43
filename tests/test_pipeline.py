import jax
import jax.numpy as jnp
import numpy as np

from cloud_server_tpu.config import MeshConfig, ModelConfig, TrainConfig
from cloud_server_tpu.models import transformer
from cloud_server_tpu.parallel.mesh import make_mesh
from cloud_server_tpu.parallel.pipeline import (
    make_pipelined_forward, make_pipelined_loss)
from cloud_server_tpu.parallel.sharding import DEFAULT_RULES
from cloud_server_tpu.training import init_train_state, make_train_step


TINY = ModelConfig(
    vocab_size=64, embed_dim=32, num_layers=4, num_heads=4, num_kv_heads=4,
    head_dim=8, mlp_dim=64, max_seq_len=32, dtype="float32",
    param_dtype="float32", remat="none")

PIPE_RULES = {**DEFAULT_RULES, "layers": "pp"}


def test_pipelined_forward_matches_plain(devices8):
    mesh = make_mesh(MeshConfig(pp=4))
    params = transformer.init_params(TINY, jax.random.key(0))
    tokens = jax.random.randint(jax.random.key(1), (8, 16), 0, 64)
    fwd = make_pipelined_forward(TINY, mesh, num_microbatches=4)
    got = fwd(params, tokens)
    want = transformer.forward(params, tokens, TINY)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-4)


def test_pipelined_forward_pp2_with_batch_sharding(devices8):
    mesh = make_mesh(MeshConfig(fsdp=4, pp=2))
    params = transformer.init_params(TINY, jax.random.key(0))
    tokens = jax.random.randint(jax.random.key(1), (8, 16), 0, 64)
    fwd = make_pipelined_forward(TINY, mesh, num_microbatches=2)
    got = fwd(params, tokens)
    want = transformer.forward(params, tokens, TINY)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-4)


def test_pipelined_training_step_runs_and_learns(devices8):
    mesh = make_mesh(MeshConfig(pp=4, fsdp=2))
    tcfg = TrainConfig(learning_rate=3e-3, warmup_steps=2, total_steps=10,
                       batch_size=8, seq_len=16)
    loss_fn = make_pipelined_loss(TINY, mesh, num_microbatches=4)
    state = init_train_state(TINY, tcfg, mesh, jax.random.key(0),
                             rules=PIPE_RULES)
    step, bsh = make_train_step(TINY, tcfg, mesh, rules=PIPE_RULES,
                                loss_fn=loss_fn)
    tokens = jax.device_put(
        jax.random.randint(jax.random.key(2), (8, 16), 0, 64), bsh)
    losses = []
    for _ in range(10):
        state, m = step(state, {"tokens": tokens})
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0], losses


def test_pipelined_grads_match_plain(devices8):
    mesh = make_mesh(MeshConfig(pp=2))
    params = transformer.init_params(TINY, jax.random.key(0))
    tokens = jax.random.randint(jax.random.key(1), (4, 16), 0, 64)
    batch = {"tokens": tokens}
    loss_pipe = make_pipelined_loss(TINY, mesh, num_microbatches=2)

    lp, gp = jax.value_and_grad(
        lambda p: loss_pipe(p, batch, TINY)[0])(params)
    ld, gd = jax.value_and_grad(
        lambda p: transformer.next_token_loss(p, batch, TINY)[0])(params)
    np.testing.assert_allclose(float(lp), float(ld), rtol=1e-5)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        np.asarray(a), np.asarray(b), atol=3e-4), gp, gd)


def test_pipelined_fused_ce_matches_plain(devices8):
    """Pipelined loss with vocab_chunk>0 == dense loss, values AND grads
    (the fused path's point is its checkpointed backward)."""
    import dataclasses
    cfg = dataclasses.replace(TINY, vocab_chunk=16)
    mesh = make_mesh(MeshConfig(pp=4))
    params = transformer.init_params(cfg, jax.random.key(0))
    tokens = jax.random.randint(jax.random.key(1), (8, 16), 0, 64)
    batch = {"tokens": tokens}
    loss_fn = make_pipelined_loss(cfg, mesh, num_microbatches=4)
    (got, _), g = jax.value_and_grad(loss_fn, has_aux=True)(
        params, batch, cfg)
    (want, _), g_want = jax.value_and_grad(
        transformer.next_token_loss, has_aux=True)(params, batch, TINY)
    np.testing.assert_allclose(float(got), float(want), rtol=1e-5)
    for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(g_want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-4, rtol=1e-3)


def test_pipelined_moe_matches_plain(devices8):
    """MoE stack pipelined over pp: forward logits, router aux, loss, and
    grads all match the unpipelined moe module.

    Capacity is generous so nothing drops: routing is per-token exact and
    batch-composition independent, making per-microbatch routing (the
    pipelined regime) comparable to full-batch routing. With drops, the two
    legitimately differ — capacity is a per-call batch property."""
    from cloud_server_tpu.models import moe
    from cloud_server_tpu.parallel.pipeline import make_pipelined_forward

    cfg = ModelConfig(
        vocab_size=64, embed_dim=32, num_layers=4, num_heads=4,
        num_kv_heads=4, head_dim=8, mlp_dim=64, max_seq_len=32,
        dtype="float32", param_dtype="float32", remat="none", num_experts=4,
        num_experts_per_token=2, expert_capacity_factor=8.0)
    mesh = make_mesh(MeshConfig(pp=4))
    params = moe.init_params(cfg, jax.random.key(0))
    tokens = jax.random.randint(jax.random.key(1), (8, 16), 0, 64)
    batch = {"tokens": tokens}

    fwd = make_pipelined_forward(cfg, mesh, num_microbatches=4,
                                 loss_fn_module=moe)
    got_logits, got_aux = fwd(params, tokens)
    want_logits, _ = moe.forward(params, tokens, cfg)
    np.testing.assert_allclose(np.asarray(got_logits),
                               np.asarray(want_logits), atol=2e-4)

    # Aux reference: router stats are per-microbatch quantities (the
    # load-balance product is nonlinear in batch partitioning), so the
    # pipelined value must equal the MEAN of per-microbatch forwards.
    def ref_aux(params):
        auxs = [moe.forward_hidden(params, tokens[i * 2:(i + 1) * 2], cfg)[1]
                for i in range(4)]
        return {k: sum(a[k] for a in auxs) / 4 for k in auxs[0]}

    want_aux = ref_aux(params)
    for k in want_aux:
        np.testing.assert_allclose(float(got_aux[k]), float(want_aux[k]),
                                   rtol=1e-5, err_msg=k)

    # Loss/grad reference: full-batch CE + microbatch-averaged aux loss.
    def ref_loss(params, batch, cfg):
        logits, _ = moe.forward(params, batch["tokens"], cfg)
        loss, metrics = transformer.masked_cross_entropy(logits, batch, 0.0)
        aux = ref_aux(params)
        metrics.update(aux)
        return loss + 0.01 * aux["load_balance"], metrics

    loss_fn = make_pipelined_loss(cfg, mesh, num_microbatches=4,
                                  loss_fn_module=moe)
    (lp, mp), gp = jax.value_and_grad(loss_fn, has_aux=True)(
        params, batch, cfg)
    (ld, md), gd = jax.value_and_grad(ref_loss, has_aux=True)(
        params, batch, cfg)
    np.testing.assert_allclose(float(lp), float(ld), rtol=1e-5)
    for k in ("loss", "accuracy", "load_balance", "router_z",
              "dropped_frac"):
        np.testing.assert_allclose(float(mp[k]), float(md[k]), rtol=1e-4,
                                   err_msg=f"metric {k}")
    for a, b in zip(jax.tree.leaves(gp), jax.tree.leaves(gd)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=3e-4, rtol=1e-3)


def test_pipeline_composes_with_grad_accum(devices8):
    """1F1B's liveness bound, compositionally: outer in-jit grad
    accumulation (microbatch_steps) around an inner pipelined loss must
    give the same loss as one big pipelined batch — so peak activation
    liveness can be held at M_inner regardless of global batch."""
    import dataclasses
    mesh = make_mesh(MeshConfig(pp=4))
    tcfg_small = TrainConfig(learning_rate=0.0, warmup_steps=1,
                             total_steps=10, microbatch_steps=2)
    tcfg_big = dataclasses.replace(tcfg_small, microbatch_steps=1)
    tokens = np.asarray(
        jax.random.randint(jax.random.key(1), (8, 16), 0, 64))

    losses = {}
    for name, tcfg, m_inner in (("accum", tcfg_small, 2),
                                ("flat", tcfg_big, 4)):
        loss_fn = make_pipelined_loss(TINY, mesh, num_microbatches=m_inner)
        state = init_train_state(TINY, tcfg, mesh, jax.random.key(0))
        step, bsh = make_train_step(TINY, tcfg, mesh, loss_fn=loss_fn)
        data = {"tokens": jax.device_put(tokens, bsh)}
        state, metrics = step(state, data)
        losses[name] = float(metrics["loss"])
    np.testing.assert_allclose(losses["accum"], losses["flat"], rtol=1e-5)
