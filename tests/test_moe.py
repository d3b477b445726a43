import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cloud_server_tpu.config import MeshConfig, ModelConfig, TrainConfig
from cloud_server_tpu.models import moe
from cloud_server_tpu.models.moe import top_k_routing
from cloud_server_tpu.parallel.mesh import make_mesh
from cloud_server_tpu.training import init_train_state, make_train_step

MOE_TINY = ModelConfig(
    vocab_size=64, embed_dim=32, num_layers=2, num_heads=4, num_kv_heads=4,
    head_dim=8, mlp_dim=64, max_seq_len=32, dtype="float32",
    param_dtype="float32", remat="none", num_experts=4,
    num_experts_per_token=2)


def test_routing_respects_capacity():
    t, e, cap = 16, 4, 3
    logits = jax.random.normal(jax.random.key(0), (t, e))
    dispatch, combine, aux = top_k_routing(logits, 2, cap)
    # no expert slot is double-booked and no expert exceeds capacity
    per_slot = np.asarray(dispatch).sum(axis=0)  # (E, C)
    assert per_slot.max() <= 1.0 + 1e-6
    per_expert = np.asarray(dispatch).sum(axis=(0, 2))
    assert per_expert.max() <= cap
    # combine weights live only where dispatch does
    assert np.all(np.asarray(combine)[np.asarray(dispatch) == 0] == 0)


def test_routing_top1_token_goes_to_argmax_expert():
    logits = jnp.array([[5.0, 0.0, 0.0, 0.0],
                        [0.0, 5.0, 0.0, 0.0]])
    dispatch, combine, _ = top_k_routing(logits, 1, capacity=4)
    assert float(dispatch[0, 0].sum()) == 1.0
    assert float(dispatch[1, 1].sum()) == 1.0


def test_moe_mlp_big_capacity_matches_dense_expert_mix():
    """With capacity >= T (nothing dropped), MoE == weighted expert sum."""
    cfg = ModelConfig(**{**MOE_TINY.__dict__,
                         "expert_capacity_factor": 100.0})
    d, e, f = cfg.embed_dim, cfg.num_experts, cfg.mlp_dim
    k1, k2, k3, k4, kx = jax.random.split(jax.random.key(0), 5)
    lp = {"router": jax.random.normal(k1, (d, e)) * 0.1,
          "w_gate": jax.random.normal(k2, (e, d, f)) * 0.1,
          "w_up": jax.random.normal(k3, (e, d, f)) * 0.1,
          "w_down": jax.random.normal(k4, (e, f, d)) * 0.1}
    x = jax.random.normal(kx, (2, 8, d))
    out, aux = moe.moe_mlp(x, lp, cfg)
    assert float(aux["dropped_frac"]) == 0.0

    # dense reference
    tokens = np.asarray(x).reshape(-1, d)
    probs = jax.nn.softmax(tokens @ np.asarray(lp["router"]), axis=-1)
    top = np.argsort(-np.asarray(probs), axis=-1)[:, :2]
    ref = np.zeros_like(tokens)
    for t in range(tokens.shape[0]):
        w = np.asarray(probs)[t, top[t]]
        w = w / w.sum()
        for j, ei in enumerate(top[t]):
            h = tokens[t] @ np.asarray(lp["w_gate"][ei])
            u = tokens[t] @ np.asarray(lp["w_up"][ei])
            act = (h / (1 + np.exp(-h))) * u
            ref[t] += w[j] * (act @ np.asarray(lp["w_down"][ei]))
    np.testing.assert_allclose(np.asarray(out).reshape(-1, d), ref, atol=2e-5)


def test_moe_forward_and_loss():
    params = moe.init_params(MOE_TINY, jax.random.key(0))
    tokens = jax.random.randint(jax.random.key(1), (2, 16), 0, 64)
    logits, aux = moe.forward(params, tokens, MOE_TINY)
    assert logits.shape == (2, 16, 64)
    loss, metrics = moe.next_token_loss(params, {"tokens": tokens}, MOE_TINY)
    assert np.isfinite(float(loss))
    assert "load_balance" in metrics and "dropped_frac" in metrics


def test_moe_trains_with_expert_parallelism(devices8):
    mesh = make_mesh(MeshConfig(fsdp=2, ep=4))
    tcfg = TrainConfig(learning_rate=3e-3, warmup_steps=2, total_steps=8,
                       batch_size=8, seq_len=16)
    state = init_train_state(MOE_TINY, tcfg, mesh, jax.random.key(0),
                             loss_fn_module=moe)
    step, bsh = make_train_step(MOE_TINY, tcfg, mesh, loss_fn_module=moe)
    tokens = jax.device_put(
        jax.random.randint(jax.random.key(2), (8, 16), 0, 64), bsh)
    losses = []
    for _ in range(8):
        state, m = step(state, {"tokens": tokens})
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0], losses
    # expert weights are actually sharded over ep
    wg = state.params["layers"]["w_gate"]  # (L, E, D, F): E on ep
    assert next(iter(wg.addressable_shards)).data.shape[1] == \
        MOE_TINY.num_experts // 4


def test_moe_fused_ce_matches_dense():
    """vocab_chunk>0 must match the dense MoE loss path (loss + grads)."""
    import dataclasses
    fused_cfg = dataclasses.replace(MOE_TINY, vocab_chunk=16)
    params = moe.init_params(MOE_TINY, jax.random.key(0))
    tokens = jax.random.randint(jax.random.key(1), (2, 16), 0, 64)
    batch = {"tokens": tokens}

    (ld, md), gd = jax.value_and_grad(moe.next_token_loss, has_aux=True)(
        params, batch, MOE_TINY)
    (lf, mf), gf = jax.value_and_grad(moe.next_token_loss, has_aux=True)(
        params, batch, fused_cfg)

    np.testing.assert_allclose(float(lf), float(ld), rtol=1e-5)
    for k in md:
        np.testing.assert_allclose(float(mf[k]), float(md[k]), rtol=1e-5,
                                   err_msg=f"metric {k}")
    for a, b in zip(jax.tree.leaves(gf), jax.tree.leaves(gd)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-5, rtol=1e-4)


# ---------------------------------------------------------------------------
# The sorted, dropless dispatch against the dense one
# ---------------------------------------------------------------------------

LAYERS, LAYER = 3, 1


def _stack(e, k, dtype, seed=0, d=32, f=64):
    """A no-drop configuration and LAYERS layers of stacked parameters,
    of the widths the configuration states (the grouped matmul's tiles
    are placed from them)."""
    cfg = ModelConfig(**{**MOE_TINY.__dict__, "num_experts": e,
                         "embed_dim": d, "mlp_dim": f,
                         "num_experts_per_token": k, "dtype": dtype,
                         "param_dtype": dtype,
                         "expert_capacity_factor": e / k})
    ks = jax.random.split(jax.random.key(seed), 4)
    layers = {"router": jax.random.normal(ks[0], (LAYERS, d, e)) * 0.3,
              "w_gate": jax.random.normal(ks[1], (LAYERS, e, d, f)) * 0.2,
              "w_up": jax.random.normal(ks[2], (LAYERS, e, d, f)) * 0.2,
              "w_down": jax.random.normal(ks[3], (LAYERS, e, f, d)) * 0.2}
    return cfg, jax.tree.map(lambda w: w.astype(dtype), layers)


def _both_dispatches(monkeypatch, x, layers, cfg):
    """(out, aux) of `moe_mlp` for layer LAYER of the stack through the
    sorted dispatch and through the dense one, at any T: the threshold is
    moved, nothing else."""
    lp = jax.tree.map(lambda w: w[LAYER], layers)
    stack = (layers, LAYER)
    t = x.shape[0] * x.shape[1]
    monkeypatch.setattr(moe, "GROUPED_MIN_TOKENS", 1)
    assert moe._dispatch_grouped(cfg, t, stack)
    grouped = moe.moe_mlp(x, lp, cfg, stack)
    monkeypatch.setattr(moe, "GROUPED_MIN_TOKENS", 10 ** 9)
    assert not moe._dispatch_grouped(cfg, t, stack)
    return grouped, moe.moe_mlp(x, lp, cfg, stack)


# T up to 2,048 at Mixtral's (8, 2); at OLMoE's (64, 8) the dense
# dispatch's (T, k, E, C) one-hot is 8 GB there, so 256
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("router", ["random", "one_expert", "empty_expert"])
@pytest.mark.parametrize("e,k,t", [
    (8, 2, 64), (8, 2, 512), (8, 2, 2048), (64, 8, 64), (64, 8, 256)])
def test_grouped_dispatch_matches_dense(monkeypatch, e, k, t, router, dtype):
    cfg, layers = _stack(e, k, dtype)
    x = jax.random.normal(jax.random.key(t), (2, t // 2, cfg.embed_dim))
    bias = jnp.zeros((e,))
    if router == "one_expert":
        # every token's first choice is expert 1 (and its others follow
        # the token): one group holds T rows, the capacity's worst case
        bias = bias.at[1].set(1e3)
    elif router == "empty_expert":
        bias = bias.at[e - 1].set(-1e3)
    # the bias rides a constant input feature, so the router stays (D, E)
    x = x.at[..., 0].set(1.0).astype(dtype)
    layers["router"] = layers["router"].astype(jnp.float32).at[
        LAYER, 0].set(bias).astype(dtype)

    (out_g, aux_g), (out_d, aux_d) = _both_dispatches(
        monkeypatch, x, layers, cfg)
    assert out_g.dtype == out_d.dtype == jnp.dtype(dtype)
    assert float(aux_d["dropped_frac"]) == float(aux_g["dropped_frac"]) == 0.0
    for name in ("load_balance", "router_z"):
        assert float(aux_g[name]) == float(aux_d[name]), name
    got = np.asarray(out_g, np.float32)
    want = np.asarray(out_d, np.float32)
    if dtype == "float32":
        np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)
    else:
        # the einsums' own tolerance: both round to bfloat16 at the same
        # places (gate, up, act, down, out) and sum in float32 in another
        # order, so they differ by an ulp (2**-8) of a few of those
        np.testing.assert_allclose(
            got, want, rtol=2.0 ** -6, atol=2.0 ** -6 * np.abs(want).max())
    if router == "one_expert":
        top1 = np.asarray(jnp.argmax(
            x.reshape(t, -1).astype(jnp.float32)
            @ layers["router"][LAYER].astype(jnp.float32), axis=-1))
        assert (top1 == 1).all()


def _counts(kind, e, n, rng):
    """Assignments an expert, `n` in all, of the shapes a router gives."""
    tile = moe._GMM_ROWS
    if kind == "uniform":
        return rng.multinomial(n, np.full(e, 1.0 / e))
    if kind == "one_expert":  # every token's first choice
        return np.bincount([1], minlength=e) * n
    if kind == "empty_experts":  # every other expert has no row
        return np.repeat(rng.multinomial(n, np.full(e // 2, 2.0 / e)),
                         2) * (np.arange(e) % 2)
    if kind == "one_tile":  # a group of exactly one tile
        rest = rng.multinomial(n - tile, np.full(e - 1, 1.0 / (e - 1)))
        return np.insert(rest, 2, tile)
    assert kind == "tile_and_one"  # the bound's worst case
    return np.full(e, tile * ((n // e) // tile) + 1)


def _visits(group_sizes, m):
    """Row-tile visits of the grouped matmul, by megablox's own metadata,
    for group sizes handed over as `_moe_grouped` hands them: the stack
    seen as L * E groups, every other layer's empty."""
    from jax.experimental.pallas.ops.tpu.megablox.gmm import (
        make_group_metadata)
    e = len(group_sizes)
    sizes = np.zeros((LAYERS * e,), np.int32)
    sizes[LAYER * e:(LAYER + 1) * e] = group_sizes
    # jitted: one program a shape, where its eager operations left some
    # thirty each behind in a process that never unmaps compiled code
    return int(jax.jit(lambda g: make_group_metadata(
        group_sizes=g, m=m, tm=moe._GMM_ROWS, start_group=0,
        num_nonzero_groups=LAYERS * e, visit_empty_groups=False)[1])(
            jnp.asarray(sizes)))


def _widths(e, k, d, f):
    """A configuration of real widths: the buffer's rule reads them."""
    return ModelConfig(**{**MOE_TINY.__dict__, "num_experts": e,
                          "num_experts_per_token": k, "embed_dim": d,
                          "mlp_dim": f})


MIXTRAL = _widths(8, 2, 4096, 14336)
NARROW = _widths(64, 6, 2560, 768)   # the second cell's experts
THIRD = _widths(128, 8, 2048, 768)   # a configuration no cell has
# (widths, tokens, whether the shape's buffer has room to pad): both
# cells' calls on both sides of the rule, as the v5e placed it (PERF.md,
# PR 39), and a third configuration
SHAPES = [(MIXTRAL, 256, False), (MIXTRAL, 288, True), (MIXTRAL, 320, True),
          (MIXTRAL, 832, True), (MIXTRAL, 1088, False),
          (MIXTRAL, 1344, False), (MIXTRAL, 1600, True),
          (MIXTRAL, 2112, False), (NARROW, 576, False),
          (NARROW, 832, False), (NARROW, 1088, False), (NARROW, 1344, True),
          (NARROW, 2112, True), (THIRD, 1088, False), (THIRD, 2112, True)]
SHAPE_IDS = [f"{c.num_experts}x{c.num_experts_per_token}-{t}"
             for c, t, _ in SHAPES]


@pytest.mark.parametrize("cfg,t,room", SHAPES, ids=SHAPE_IDS)
def test_the_buffer_has_room_where_alignment_cuts_the_visits(cfg, t, room):
    """`_sorted_buffer_rows` reads the call's shape and the widths: whole
    row tiles, never fewer than the packed rows need, never more than the
    bound of any router's padded extents, and more than the packed rows'
    only where an even router's aligned visits and the longer buffer cost
    seven eighths of the packed visits or less."""
    tile, e = moe._GMM_ROWS, cfg.num_experts
    n = t * cfg.num_experts_per_token
    rows = moe._sorted_buffer_rows(n, cfg)
    packed = -(-n // tile)
    assert rows % tile == 0 and packed * tile <= rows
    assert rows <= (n // tile + e) * tile
    assert (rows > packed * tile) == room
    if room:
        assert rows == (e * -(-n // (e * tile)) + e // 8) * tile
    # tiny widths (every other CPU test's): a tile of rows costs more than
    # an expert's weights, and no shape makes room
    tiny = _widths(e, cfg.num_experts_per_token, 32, 64)
    assert moe._sorted_buffer_rows(n, tiny) == packed * tile


@pytest.mark.parametrize("kind", ["uniform", "one_expert", "empty_experts",
                                  "one_tile", "tile_and_one"])
@pytest.mark.parametrize("cfg,t,room", SHAPES, ids=SHAPE_IDS)
def test_every_experts_rows_start_on_a_row_tile(cfg, t, room, kind):
    """`_aligned_layout`: the extents are whole row tiles for as many of
    the first experts as the buffer has room to pad, they fit the buffer,
    a rank's row lies in its expert's extent in the sorted order, and
    megablox's own metadata visits each tile of a padded extent once, for
    one expert: sum(ceil(g / tile)) visits where every expert is padded,
    against a visit for every expert with a row in a tile."""
    tile, e, k = moe._GMM_ROWS, cfg.num_experts, cfg.num_experts_per_token
    counts = _counts(kind, e, t * k, np.random.default_rng(e + t))
    n = int(counts.sum())
    assert n <= t * k and counts.shape == (e,)
    buffer = moe._sorted_buffer_rows(n, cfg)
    sizes, shift = (np.asarray(a) for a in jax.jit(
        moe._aligned_layout, static_argnums=1)(
            jnp.asarray(counts, jnp.int32), buffer))
    padded = -(-counts // tile) * tile
    # a prefix of the experts is padded: as many as there is room for
    fits = np.cumsum(padded - counts) <= buffer - n
    n_fit = int(fits.sum())
    assert fits[:n_fit].all()
    assert (sizes == np.where(fits, padded, counts)).all()
    assert (sizes[counts == 0] == 0).all() and sizes.sum() <= buffer
    start = np.cumsum(sizes) - sizes
    assert (shift == start - (np.cumsum(counts) - counts)).all()
    # an expert behind padded ones starts on a row tile
    assert (start[:n_fit + 1] % tile == 0).all()
    expert_of_rank = np.repeat(np.arange(e), counts)
    row_of_rank = np.arange(n) + shift[expert_of_rank]
    assert (row_of_rank >= start[expert_of_rank]).all()
    assert (row_of_rank < (start + counts)[expert_of_rank]).all()
    assert (np.diff(row_of_rank) > 0).all()  # the sorted order, no row twice
    visits = _visits(sizes, buffer)
    packed_visits = _visits(counts, -(-n // tile) * tile)
    assert visits <= packed_visits
    if fits.all():
        assert visits == -(-counts // tile).sum() == sizes.sum() // tile
    if room and kind in ("uniform", "one_tile"):
        # an even router finds the room the shape's rule made
        assert fits.all() and 8 * visits <= 7 * packed_visits
    if kind == "tile_and_one" and counts[0] > 1:
        # the worst case a router can give: every pad a row short of a tile
        assert not fits.all()


@pytest.mark.parametrize("router", ["uniform", "one_expert", "empty_experts",
                                    "one_tile"])
@pytest.mark.parametrize("e,k", [(8, 2), (64, 6)])
def test_aligned_layout_matches_dense(monkeypatch, e, k, router):
    """The sorted dispatch with every expert on row tiles of its own gives
    the dense dispatch's output whatever the groups: uniform routing, one
    expert holding a row of every token, experts without a row, and a
    group that fills exactly one tile (no pad row behind it)."""
    t = moe._GMM_ROWS if router == "one_tile" else 320
    cfg, layers = _stack(e, k, "float32")
    # room for any router's padded extents, which the rule gives no shape
    # at these widths
    monkeypatch.setattr(
        moe, "_sorted_buffer_rows",
        lambda n, cfg: (n // moe._GMM_ROWS + cfg.num_experts) * moe._GMM_ROWS)
    x = jax.random.normal(jax.random.key(e), (1, t, cfg.embed_dim))
    bias = jnp.zeros((e,))
    if router in ("one_expert", "one_tile"):
        bias = bias.at[1].set(1e3)
    elif router == "empty_experts":
        bias = bias.at[::2].set(-1e3)
    x = x.at[..., 0].set(1.0)
    layers["router"] = layers["router"].at[LAYER, 0].set(bias)
    seen = []
    real = moe._grouped_experts
    monkeypatch.setattr(
        moe, "_grouped_experts",
        lambda rows, wg, wu, wd, sizes, real_rows, **kw: seen.append(
            (rows.shape[0], np.asarray(sizes), np.asarray(real_rows)))
        or real(rows, wg, wu, wd, sizes, real_rows, **kw))
    (out_g, _), (out_d, _) = _both_dispatches(monkeypatch, x, layers, cfg)
    np.testing.assert_allclose(np.asarray(out_g), np.asarray(out_d),
                               atol=1e-5, rtol=1e-5)
    (n_rows, sizes, real_rows), = seen
    assert n_rows == moe._sorted_buffer_rows(t * k, cfg)
    mine = sizes[LAYER * e:(LAYER + 1) * e]
    assert sizes.sum() == mine.sum() <= n_rows
    # beside the extents, the assignments that landed at the head of each
    assert real_rows.sum() == t * k and (real_rows <= sizes).all()
    assert (-(-real_rows // moe._GMM_ROWS) * moe._GMM_ROWS == sizes).all()
    assert (mine % moe._GMM_ROWS == 0).all()
    if router in ("one_expert", "one_tile"):
        assert mine[1] == -(-t // moe._GMM_ROWS) * moe._GMM_ROWS
    if router == "one_tile":
        assert mine[1] == t == moe._GMM_ROWS
    if router == "empty_experts":
        assert (mine[::2] == 0).all() and (mine[1::2] > 0).all()
    if (e, k) == (8, 2) and router == "uniform":
        # 320 tokens of 8 x 2: every expert on a tile of its own
        assert (mine == moe._GMM_ROWS).all()


@pytest.mark.parametrize("kind", ["every_expert_held", "a_share"])
def test_a_row_no_assignment_landed_on_reaches_no_token(monkeypatch, kind):
    """What the experts' way in leans on since a visit computes only the
    sub-tiles its expert has a row in (`ops/grouped_matmul.py`): with every
    buffer row that no assignment landed on overwritten by NaN between the
    experts and the combine (the rest of a padded extent, the rows behind
    the last expert's), the layer's output is finite and the dense
    dispatch's, through `_weighted_sum` and, for one chip's share of a
    wider router, through `_weighted_sum_held`."""
    e, k, t = 8, 2, 320
    cfg, layers = _stack(e, k, "float32")
    if kind == "a_share":  # 8 of 12 routed experts held, 4 identity ones
        cfg = ModelConfig(**{**cfg.__dict__, "num_routed_experts": 12,
                             "num_zero_experts": 4,
                             "routed_scaling_factor": 2.0})
        layers["router"] = jax.random.normal(
            jax.random.key(3), (LAYERS, cfg.embed_dim, 16)) * 0.3
        layers["router_bias"] = jnp.zeros((LAYERS, 16))
    # room to pad every expert's extent, which tiny widths are not given
    monkeypatch.setattr(
        moe, "_sorted_buffer_rows",
        lambda n, cfg: (n // moe._GMM_ROWS + cfg.num_experts) * moe._GMM_ROWS)
    holes = []
    real = moe._grouped_experts

    def holed(rows, wg, wu, wd, sizes, real_rows, **kw):
        ys = real(rows, wg, wu, wd, sizes, real_rows, **kw)
        starts = jnp.cumsum(sizes) - sizes
        at = jnp.arange(ys.shape[0])[:, None]
        landed = ((at >= starts) & (at < starts + real_rows)).any(1)
        holes.append(int((~landed).sum()))
        return jnp.where(landed[:, None], ys, jnp.nan)

    monkeypatch.setattr(moe, "_grouped_experts", holed)
    x = jax.random.normal(jax.random.key(e), (1, t, cfg.embed_dim))
    (out_g, _), (out_d, _) = _both_dispatches(monkeypatch, x, layers, cfg)
    assert holes and holes[0] >= e * moe._GMM_ROWS - t * k > 0
    assert np.isfinite(np.asarray(out_g)).all()
    np.testing.assert_allclose(np.asarray(out_g), np.asarray(out_d),
                               atol=1e-5, rtol=1e-5)


def _values(jaxpr):
    """Every value an equation of `jaxpr` writes, nested jaxprs' too."""
    for eqn in jaxpr.eqns:
        yield from (v.aval for v in eqn.outvars)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _values(sub)


@pytest.mark.parametrize("e,k,t", [(8, 2, 320), (64, 6, 2112)])
def test_the_combine_writes_no_tokens_by_k_by_hidden_array(monkeypatch, e, k,
                                                           t):
    """Both cells' widest calls (tiny widths): outside the experts'
    kernels the sorted dispatch writes no float32 value of T k D elements
    or more, and the combine itself none of that size in any dtype: a
    token's k rows are gathered and added one slot after the other."""
    cfg, layers = _stack(e, k, "bfloat16")
    d = cfg.embed_dim
    # the experts stand aside: off the chip `lax.ragged_dot` accumulates
    # its (M, N) output in float32, which the megablox kernel does not
    monkeypatch.setattr(moe, "_grouped_experts",
                        lambda rows, *a, **kw: rows)
    whole = jax.make_jaxpr(
        lambda x, logits: moe._moe_grouped(x, logits, layers, LAYER, cfg))(
            jax.ShapeDtypeStruct((t, d), jnp.bfloat16),
            jax.ShapeDtypeStruct((t, e), jnp.float32))
    wide = [v for v in _values(whole.jaxpr)
            if v.dtype == jnp.float32 and v.size >= t * k * d]
    assert not wide, wide
    rows = moe._sorted_buffer_rows(t * k, cfg)
    combine = jax.make_jaxpr(moe._weighted_sum)(
        jax.ShapeDtypeStruct((rows, d), jnp.bfloat16),
        jax.ShapeDtypeStruct((t, k), jnp.int32),
        jax.ShapeDtypeStruct((t, k), jnp.float32))
    sizes = [v.size for v in _values(combine.jaxpr)]
    assert max(sizes) == t * d < t * k * d, max(sizes)
    assert combine.out_avals[0].dtype == jnp.bfloat16


def _bf16_ulp(x):
    """The spacing of bfloat16 (8 significant bits) at |x|, float64."""
    return 2.0 ** (np.floor(np.log2(np.maximum(np.abs(x), 2.0 ** -126))) - 7)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("e,k,t", [(8, 1, 320), (8, 2, 320), (64, 6, 200)])
def test_the_combine_is_the_float64_sum_rounded_once(monkeypatch, e, k, t,
                                                     dtype):
    """out[t] = sum_j gate[t, j] * ys[row_of[t, j]] with the gates in
    float32 as the router gives them (what the v5e's program computed
    all along: PERF.md, PR 41), against numpy in float64: float32 to
    1e-6, bfloat16 within one ulp of the rounded float64 sum. Every other
    expert has no row. The experts' outputs are a table by buffer row, so
    a row taken from the wrong place shows; the rows' places are counted
    here from the router's choices (a stable sort by expert in the packed
    rows' whole tiles: tiny widths make no more room)."""
    cfg, layers = _stack(e, k, dtype)
    keys = jax.random.split(jax.random.key(e + k), 3)
    logits = jax.random.normal(keys[0], (t, e)).at[:, ::2].add(-1e3)
    x = jax.random.normal(keys[1], (t, cfg.embed_dim)).astype(dtype)
    n_rows = moe._sorted_buffer_rows(t * k, cfg)
    assert n_rows == -(-t * k // moe._GMM_ROWS) * moe._GMM_ROWS
    table = jax.random.normal(keys[2], (n_rows, cfg.embed_dim)).astype(dtype)
    monkeypatch.setattr(moe, "_grouped_experts", lambda rows, *a, **kw: table)
    out, _ = jax.jit(lambda x, logits: moe._moe_grouped(
        x, logits, layers, LAYER, cfg))(x, logits)
    assert out.dtype == jnp.dtype(dtype)

    _, gates, idx = jax.jit(moe._top_k_gates, static_argnums=1)(logits, k)
    assert (np.asarray(idx) % 2 == 1).all()  # the even experts are empty
    expert_of = np.asarray(idx).reshape(t * k)
    order = np.argsort(expert_of, kind="stable")
    counts = np.bincount(expert_of, minlength=e)
    # the last tile's spare rows pad as many of the first experts as fit
    pad = -counts % moe._GMM_ROWS
    pad[np.cumsum(pad) > n_rows - t * k] = 0
    row_of = np.empty(t * k, np.int64)
    row_of[order] = np.arange(t * k) + np.repeat(np.cumsum(pad) - pad, counts)
    assert gates.dtype == jnp.float32
    terms = (np.asarray(gates, np.float64)[:, :, None]
             * np.asarray(table, np.float64)[row_of.reshape(t, k)])
    want = terms.sum(1)
    got = np.asarray(out, np.float64)
    # the float32 products and additions: 2 k - 1 roundings of 2**-24
    adds = (2 * k - 1) * 2.0 ** -24 * np.abs(terms).sum(1)
    if dtype == "float32":
        np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
    else:
        rounded = want.astype(jnp.bfloat16).astype(np.float64)
        assert (np.abs(got - rounded)
                <= np.maximum(_bf16_ulp(rounded), adds)).all()


@pytest.mark.parametrize("t", [4, 64, 256, 288, 512, 1024, 2048, 4096, 16384])
def test_which_dispatch_runs(t, devices8):
    """The rule reads the capacity, the token count, where the weights
    lie and the mesh, and nothing else."""
    from cloud_server_tpu.models.quantization import quantize_params
    from cloud_server_tpu.parallel.mesh import set_current_mesh
    no_drop, layers = _stack(8, 2, "float32")          # factor E / k
    wide, wide_layers = _stack(64, 8, "float32")
    stack = (layers, LAYER)
    default = ModelConfig(**{**no_drop.__dict__,
                             "expert_capacity_factor": 1.25})
    almost = ModelConfig(**{**no_drop.__dict__,
                            "expert_capacity_factor": 3.9})
    # a capacity that can drop keeps the dense dispatch at every T
    assert not moe._dispatch_grouped(default, t, stack)
    if t > 4:  # under 4 rows `_capacity` rounds up to all of them
        assert moe._capacity(no_drop, t) >= t > moe._capacity(almost, t)
        assert not moe._dispatch_grouped(almost, t, stack)
    # one that cannot moves at the threshold and above, not below
    moved = t >= moe.GROUPED_MIN_TOKENS
    assert moe._dispatch_grouped(no_drop, t, stack) == moved
    # at (64, 8) the threshold is the experts' own (PR 35, placed again
    # by PR 39): 144 tokens
    assert moe.grouped_min_tokens(no_drop) == moe.GROUPED_MIN_TOKENS == 288
    assert moe._dispatch_grouped(wide, t, (wide_layers, 0)) == (
        t >= moe.grouped_min_tokens(wide) == 144)
    assert moe.grouped_min_tokens(NARROW) == 153
    # a caller that scans its layers has no stack to point into; weights
    # that need a cast or a dequantization first are not used in place
    assert not moe._dispatch_grouped(no_drop, t, None)
    assert not moe._dispatch_grouped(
        no_drop, t, (jax.tree.map(lambda w: w.astype("bfloat16"), layers),
                     LAYER))
    assert not moe._dispatch_grouped(
        no_drop, t, (quantize_params({"layers": layers})["layers"], LAYER))
    # under a mesh of more than one device the dense dispatch stays
    make_mesh(MeshConfig(fsdp=2, ep=4))
    assert not moe._dispatch_grouped(no_drop, t, stack)
    set_current_mesh(jax.sharding.Mesh(np.array(devices8[:1]), ("ep",)))
    assert moe._dispatch_grouped(no_drop, t, stack) == moved


def test_paged_prefill_takes_the_sorted_dispatch_and_agrees(monkeypatch):
    """The paged engine's window forward hands `moe_mlp` the stack: a
    prefill group over the threshold runs the sorted dispatch (seen
    through `_grouped_experts`) and gives the dense dispatch's logits."""
    from cloud_server_tpu.inference import paged_engine
    cfg = ModelConfig(**{**MOE_TINY.__dict__, "expert_capacity_factor": 2.0})
    params = moe.init_params(cfg, jax.random.key(0))
    cache = paged_engine.init_paged_cache(
        cfg, num_pages=16, page_size=8, batch=2, max_pages_per_slot=4)
    cache = cache._replace(
        tables=jnp.arange(8, dtype=jnp.int32).reshape(2, 4))
    tokens = jax.random.randint(jax.random.key(1), (2, 16), 0, 64)
    calls = []
    real = moe._grouped_experts
    monkeypatch.setattr(moe, "_grouped_experts",
                        lambda *a, **kw: calls.append(a[0].shape) or
                        real(*a, **kw))

    def logits(threshold):
        monkeypatch.setattr(moe, "GROUPED_MIN_TOKENS", threshold)
        out, _ = paged_engine.window_forward(
            params, tokens, cfg, cache, logits_at=None, all_logits=True)
        return np.asarray(out)

    grouped = logits(32)
    # the T * k sorted rows in the buffer their shape is given: here the
    # packed rows' one tile (4 experts on tiles of their own would be 4
    # visits for the packed layout's 4: no room is made)
    n_rows = 2 * 16 * cfg.num_experts_per_token
    assert n_rows % moe._GMM_ROWS
    assert moe._sorted_buffer_rows(n_rows, cfg) == moe._GMM_ROWS
    assert calls == [(moe._GMM_ROWS, cfg.embed_dim)] * cfg.num_layers
    dense = logits(33)
    assert len(calls) == cfg.num_layers
    np.testing.assert_allclose(grouped, dense, atol=2e-5, rtol=1e-5)


@pytest.mark.on_tpu
def test_compiled_on_tpu_grouped_dispatch(monkeypatch):
    """The megablox kernel as Mosaic compiles it, reading the layer's
    experts out of the stack, against the dense einsums on the chip: off
    the chip the sorted dispatch runs `lax.ragged_dot`."""
    assert jax.default_backend() == "tpu"
    t = moe.GROUPED_MIN_TOKENS
    cfg, layers = _stack(8, 2, "bfloat16", d=512, f=1024)
    x = jax.random.normal(jax.random.key(5), (2, t // 2, 512)).astype(
        jnp.bfloat16)
    lp = jax.tree.map(lambda w: w[LAYER], layers)
    stack = (layers, LAYER)
    assert moe._dispatch_grouped(cfg, t, stack)
    got, _ = jax.jit(lambda x, lp, layers: moe.moe_mlp(
        x, lp, cfg, (layers, LAYER)))(x, lp, layers)
    monkeypatch.setattr(moe, "GROUPED_MIN_TOKENS", 10 ** 9)
    want, _ = jax.jit(lambda x, lp: moe.moe_mlp(x, lp, cfg))(x, lp)
    want = np.asarray(want, np.float32)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), want, rtol=2.0 ** -6,
        atol=2.0 ** -6 * np.abs(want).max())


# -- a sigmoid router balanced by a bias ------------------------------------

SIGMOID = ModelConfig(**{**MOE_TINY.__dict__, "router_score": "sigmoid",
                         "route_scale": 2.5, "num_experts": 4,
                         "num_experts_per_token": 2})


@pytest.mark.parametrize("scale", [1.0, 2.5])
def test_sigmoid_gates_are_the_hand_computation(scale):
    """A score an expert; the choice is the top k of score + bias; the
    gates are the kept scores, renormalised and scaled, and never hold the
    bias."""
    cfg = ModelConfig(**{**SIGMOID.__dict__, "route_scale": scale})
    logits = jnp.asarray([[2.0, 1.0, 0.0, -1.0],
                          [0.5, 0.4, 0.3, 0.2]])
    bias = jnp.asarray([0.0, 0.0, 0.0, 0.9])
    scores, gates, chosen = moe._sigmoid_gates(logits, bias, cfg)
    s = 1 / (1 + np.exp(-np.asarray(logits, np.float64)))
    np.testing.assert_allclose(scores, s, rtol=1e-6)
    # token 0: expert 3's score 0.269 + 0.9 passes every other; token 1:
    # 0.550 + 0.9 too. Without the bias neither would choose it
    np.testing.assert_array_equal(np.asarray(chosen), [[3, 0], [3, 0]])
    assert not (np.argsort(-s, 1)[:, :2] == 3).any()
    kept = np.take_along_axis(s, np.asarray(chosen), 1)
    np.testing.assert_allclose(
        gates, scale * kept / kept.sum(1, keepdims=True), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(gates).sum(1), scale, rtol=1e-6)
    # a larger bias moves no gate while the choice stays
    _, again, same = moe._sigmoid_gates(logits, 3.0 * bias, cfg)
    np.testing.assert_array_equal(np.asarray(same), np.asarray(chosen))
    np.testing.assert_array_equal(np.asarray(again), np.asarray(gates))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_both_dispatches_carry_the_sigmoid_gates(monkeypatch, dtype):
    """The sorted and the one-hot dispatch of a sigmoid router: the same
    output, the same load an expert, and the softmax router's aux keys."""
    cfg = ModelConfig(**{**SIGMOID.__dict__, "dtype": dtype,
                         "param_dtype": dtype, "num_experts": 8,
                         "num_experts_per_token": 3, "num_layers": LAYERS,
                         "expert_capacity_factor": 8 / 3})
    layers = jax.tree.map(
        lambda p: p.astype(dtype),
        moe.init_params(cfg, jax.random.key(2))["layers"])
    layers["router_bias"] = (0.2 * jax.random.normal(
        jax.random.key(3), layers["router_bias"].shape)).astype(dtype)
    x = jax.random.normal(jax.random.key(4), (2, 96, cfg.embed_dim)).astype(
        dtype)
    (out_g, aux_g), (out_d, aux_d) = _both_dispatches(
        monkeypatch, x, layers, cfg)
    np.testing.assert_array_equal(np.asarray(aux_g["load"]),
                                  np.asarray(aux_d["load"]))
    assert int(aux_g["load"].sum()) == 192 * 3
    assert float(aux_d["dropped_frac"]) == float(aux_g["dropped_frac"]) == 0
    got, want = np.asarray(out_g, np.float32), np.asarray(out_d, np.float32)
    tol = 1e-5 if dtype == "float32" else 2.0 ** -6 * np.abs(want).max()
    np.testing.assert_allclose(got, want, atol=tol, rtol=tol)
