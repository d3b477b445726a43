"""Pipeline parallelism over the `pp` mesh axis.

SPMD GPipe: the layer stack is split into `pp` stages (the stacked layer
axis is sharded over the pp mesh axis, so each device holds L/pp layers).
Under `shard_map`, every device runs the same program: at step t it applies
its stage to the microbatch it holds, then `ppermute`s the activation to the
next stage. After M + pp - 1 steps all M microbatches have flowed through;
the last stage's collected outputs are broadcast with a masked psum.

This is differentiable end-to-end (ppermute has a transpose rule: the
reverse permutation), so the backward pass is the mirrored pipeline —
no hand-written schedule, XLA sees one fused program per device.

The bubble is the standard GPipe (pp - 1) / (M + pp - 1); raise
`num_microbatches` to amortise it.

Schedule design note: grad-of-SPMD-GPipe is deliberate on TPU. XLA derives
the backward pipeline (the transposed ring) from this one traced program,
so there is no hand-written 1F1B interleave — that would require manually
scheduling fwd/bwd microbatch ops against each other, which fights XLA's
whole-program compilation model. 1F1B's actual win, bounding live
activations to O(pp) instead of O(M) microbatches, is recovered
compositionally: wrap the pipelined loss in the train step's in-jit
gradient accumulation (`TrainConfig.microbatch_steps`) — each outer
accumulation step pipelines only M_inner microbatches, so peak liveness is
M_inner while the bubble amortises over M_inner * microbatch_steps
(tested in tests/test_pipeline.py::test_pipeline_composes_with_grad_accum).

Payloads are pytrees: the MoE stack pipelines with its router-stat
accumulators riding the ring next to the activations.
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from cloud_server_tpu.models import transformer
from cloud_server_tpu.parallel import collectives


def pipeline_spmd(stage_params, microbatches, stage_fn: Callable,
                  *, axis_name: str = "pp"):
    """Run microbatches through the pipeline. Call under shard_map.

    Args:
      stage_params: this device's slice of the stacked layer params
        (leading layer axis length L/pp locally).
      microbatches: pytree of (M, mb, ...) replicated input microbatches —
        any pytree payload rides the ring (e.g. MoE activations plus their
        accumulated router-stat scalars).
      stage_fn: (stage_params, payload) -> payload applying this stage's
        layers; must preserve the payload's pytree structure/shapes.
      axis_name: the pipeline mesh axis.

    Returns:
      pytree of (M, mb, ...) outputs, replicated (valid on every device).
    """
    pp = lax.axis_size(axis_name)
    stage = lax.axis_index(axis_name)
    m = jax.tree.leaves(microbatches)[0].shape[0]
    t_total = m + pp - 1

    # Stage results vary over the pp axis (each stage computes different
    # values) and possibly over more axes than their inputs (e.g. MoE
    # router stats enter replicated but accumulate batch-sharded values).
    # Zero-init carries and injected microbatches must declare the stage
    # OUTPUT's varying-axes set up front or check_vma=True rejects the
    # cond/scan — so derive each payload leaf's target vma by abstract
    # evaluation of stage_fn.
    def promote(z, aval):
        missing = tuple(set(aval.vma) - set(jax.typeof(z).vma))
        return collectives.pvary(z, missing) if missing else z

    x_probe = jax.tree.map(
        lambda mb: collectives.pvary(mb[0], (axis_name,)), microbatches)
    y_avals = jax.eval_shape(
        lambda x: stage_fn(stage_params, x), x_probe)

    def body(carry, t):
        recv, outputs = carry
        mb_idx = jnp.clip(t, 0, m - 1)
        x_in = jax.tree.map(
            lambda mb, r, av: jnp.where(
                stage == 0, promote(mb[mb_idx], av), r),
            microbatches, recv, y_avals)
        y = stage_fn(stage_params, x_in)
        out_idx = t - (pp - 1)
        is_valid_out = jnp.logical_and(stage == pp - 1, out_idx >= 0)
        outputs = lax.cond(
            is_valid_out,
            lambda o: jax.tree.map(
                lambda ol, yl: lax.dynamic_update_index_in_dim(
                    ol, yl, jnp.clip(out_idx, 0, m - 1), axis=0),
                o, y),
            lambda o: o,
            outputs)
        recv_next = collectives.ring_exchange(y, axis_name)
        return (recv_next, outputs), None

    recv0 = jax.tree.map(
        lambda mb, av: promote(jnp.zeros_like(mb[0]), av),
        microbatches, y_avals)
    outputs0 = jax.tree.map(
        lambda mb, av: promote(jnp.zeros_like(mb), av),
        microbatches, y_avals)
    (_, outputs), _ = lax.scan(body, (recv0, outputs0), jnp.arange(t_total))

    # Only the last stage holds real outputs; masked psum broadcasts them.
    return jax.tree.map(
        lambda o: collectives.psum(
            o * (stage == pp - 1).astype(o.dtype), axis_name),
        outputs)



def _is_moe_module(loss_fn_module) -> bool:
    """Capability check, not name sniffing: a module pipelines as MoE iff
    it exposes the (x, aux)-returning `_moe_block` stage primitive."""
    return hasattr(loss_fn_module, "_moe_block")


def _dense_stage_factory(model_cfg, cos, sin, attn_fn, packed=False):
    def stage_fn(stage_params, payload):
        if packed:
            # segment ids + per-document positions ride the ring with the
            # activations so every stage masks/ropes its microbatch right
            x, seg, pos = payload
            attn = transformer._packed_attention_fn(model_cfg, seg)
        else:
            x, seg, pos, attn = payload, None, None, attn_fn
        block = functools.partial(transformer._block, cfg=model_cfg,
                                  cos=cos, sin=sin, attn_fn=attn,
                                  positions=pos)
        block = transformer.apply_remat(block, model_cfg)

        def scan_body(h, lp):
            return block(h, lp), None

        out, _ = lax.scan(scan_body, x, stage_params)
        return (out, seg, pos) if packed else out
    return stage_fn


def _moe_stage_factory(model_cfg, cos, sin, attn_fn, packed=False):
    """MoE stage: payload is (x, aux3) — the three router stats
    (load_balance, router_z, dropped_frac) accumulate across layers and
    ride the ring with the activations."""
    from cloud_server_tpu.models import moe

    def stage_fn(stage_params, payload):
        if packed:
            x, aux3, seg, pos = payload
            attn = transformer._packed_attention_fn(model_cfg, seg)
        else:
            (x, aux3), seg, pos, attn = payload, None, None, attn_fn
        # aux3 enters replicated over the batch axes while x is sharded
        # over them; the scan carry must agree, so promote aux3 to x's vma.
        aux3 = collectives.pvary(aux3, tuple(
            set(jax.typeof(x).vma) - set(jax.typeof(aux3).vma)))
        block = functools.partial(moe._moe_block, cfg=model_cfg,
                                  cos=cos, sin=sin, attn_fn=attn,
                                  positions=pos)
        block = transformer.apply_remat(block, model_cfg)

        def scan_body(carry, lp):
            h, a = carry
            h, aux = block(h, lp)
            a = a + jnp.stack([aux["load_balance"], aux["router_z"],
                               aux["dropped_frac"]])
            return (h, a), None

        (x, aux3), _ = lax.scan(scan_body, (x, aux3), stage_params)
        return (x, aux3, seg, pos) if packed else (x, aux3)
    return stage_fn


def make_pipelined_hidden(model_cfg, mesh: Mesh, num_microbatches: int,
                          rules=None, loss_fn_module=transformer):
    """Return hidden(params, tokens) with the block stack run as a pipeline.

    Dense (`loss_fn_module=transformer`): hidden -> final-normed (B, S, D).
    MoE (`loss_fn_module=models.moe`): hidden -> (x, aux dict of averaged
    router stats), mirroring `moe.forward_hidden`.

    Embedding / final norm / head run replicated over pp (they are cheap
    relative to the stack); only the L-layer block scan is pipelined.
    """
    from cloud_server_tpu.ops import rms_norm, rope_table
    from cloud_server_tpu.parallel.sharding import DEFAULT_RULES

    rules = rules or DEFAULT_RULES
    pp = mesh.shape["pp"]
    transformer.one_stack(model_cfg, "the pipelined stack")
    if model_cfg.num_layers % pp:
        raise ValueError(f"num_layers={model_cfg.num_layers} not divisible "
                         f"by pp={pp}")
    is_moe = _is_moe_module(loss_fn_module)
    factory = _moe_stage_factory if is_moe else _dense_stage_factory

    layer_spec = P("pp")  # stacked layer axis sharded over pp
    batch_spec = P(rules["batch"])

    def hidden(params, tokens, segment_ids=None):
        cfg = model_cfg
        packed = segment_ids is not None
        if packed and cfg.attention_impl not in ("xla", "flash"):
            raise ValueError(
                "pipelined packed batches need attention_impl 'xla' or "
                "'flash' (ring/ulysses would nest shard_map inside the "
                f"pipeline shard_map); got {cfg.attention_impl!r}")
        cos, sin = rope_table(cfg, tokens.shape[1])
        x = params["embed"]["tokens"].astype(cfg.dtype)[tokens]  # (B, S, D)
        b = x.shape[0]
        mb = b // num_microbatches
        micro_x = x.reshape((num_microbatches, mb) + x.shape[1:])
        seg_pos_specs = ()
        seg_pos = ()
        if packed:
            from cloud_server_tpu.ops.segments import positions_from_segments
            pos = positions_from_segments(segment_ids)
            mshape = (num_microbatches, mb, tokens.shape[1])
            seg_pos = (segment_ids.reshape(mshape), pos.reshape(mshape))
            seg_pos_specs = (P(None, *batch_spec[:1], None),
                             P(None, *batch_spec[:1], None))
        if is_moe:
            micro = (micro_x, jnp.zeros((num_microbatches, 3), jnp.float32),
                     *seg_pos)
            payload_spec = (P(None, *batch_spec), P(None, None),
                            *seg_pos_specs)
            if not packed:
                micro = micro[:2]
        else:
            micro = (micro_x, *seg_pos) if packed else micro_x
            payload_spec = ((P(None, *batch_spec), *seg_pos_specs)
                            if packed else P(None, *batch_spec))

        attn_fn = None if packed else transformer._get_attention_fn(cfg)
        stage_fn = factory(cfg, cos, sin, attn_fn, packed=packed)

        def pipe_fn(layers, micro_in):
            out = pipeline_spmd(layers, micro_in, stage_fn=stage_fn)
            if is_moe:
                # payload may carry (x, aux3[, seg, pos]); router stats
                # are per-batch-shard, averaged so the replicated
                # out_spec is truthful
                return (out[0], lax.pmean(out[1], rules["batch"]),
                        *out[2:])
            return out

        pipe = jax.shard_map(
            pipe_fn,
            mesh=mesh,
            in_specs=(jax.tree.map(lambda _: layer_spec, params["layers"]),
                      payload_spec),
            out_specs=payload_spec,
            check_vma=True,
        )
        micro_out = pipe(params["layers"], micro)
        if packed:
            micro_out = (micro_out[:2] if is_moe else micro_out[0])
        if is_moe:
            micro_x_out, aux_out = micro_out
            xo = rms_norm(micro_x_out.reshape(x.shape),
                          params["final_norm"]["scale"], cfg.norm_eps)
            # per-microbatch layer sums -> batch mean, per-layer mean
            avg = aux_out.mean(axis=0) / cfg.num_layers
            return xo, {"load_balance": avg[0], "router_z": avg[1],
                        "dropped_frac": avg[2]}
        xo = micro_out.reshape(x.shape)
        return rms_norm(xo, params["final_norm"]["scale"], cfg.norm_eps)

    return hidden


def make_pipelined_forward(model_cfg, mesh: Mesh, num_microbatches: int,
                           rules=None, loss_fn_module=transformer):
    """Return forward(params, tokens) with the block stack pipelined:
    dense -> (B, S, V) f32 logits; MoE -> (logits, aux dict), mirroring
    the unpipelined module forwards."""
    hidden = make_pipelined_hidden(model_cfg, mesh, num_microbatches, rules,
                                   loss_fn_module)
    is_moe = _is_moe_module(loss_fn_module)

    def forward(params, tokens, segment_ids=None):
        if is_moe:
            x, aux = hidden(params, tokens, segment_ids)
            return transformer.unembed(x, params, model_cfg), aux
        return transformer.unembed(hidden(params, tokens, segment_ids),
                                   params, model_cfg)

    return forward


def make_pipelined_loss(model_cfg, mesh: Mesh, num_microbatches: int,
                        z_loss_coef: float = 0.0, loss_fn_module=transformer,
                        aux_loss_coef: float = 0.01,
                        router_z_coef: float = 0.0):
    """Pipelined replacement for <module>.next_token_loss; same signature
    (params, batch, cfg) so it drops into make_train_step(loss_fn=...).

    Honors cfg.vocab_chunk and cfg.ce_impl (transformer.
    hidden_state_loss is the single dispatch point): chunked or fused
    CE instead of materialising (B, S, V) logits. With
    loss_fn_module=models.moe the MoE stack pipelines and the router
    aux losses match moe.next_token_loss.
    """
    hidden = make_pipelined_hidden(model_cfg, mesh, num_microbatches,
                                   loss_fn_module=loss_fn_module)
    is_moe = _is_moe_module(loss_fn_module)

    def loss_fn(params, batch, cfg):
        # The stack is built from the closed-over model_cfg; ignore the
        # runtime cfg so the head/softcap/chunking can't silently diverge
        # from the pipelined body.
        del cfg
        seg = batch.get("segment_ids")
        batch = transformer.apply_segment_loss_mask(batch)
        out = hidden(params, batch["tokens"], seg)
        x, aux = out if is_moe else (out, None)
        # single CE dispatch point: honors ce_impl AND vocab_chunk
        loss, metrics = transformer.hidden_state_loss(
            x, params, batch, model_cfg, z_loss_coef)
        if is_moe:
            metrics.update(load_balance=aux["load_balance"],
                           router_z=aux["router_z"],
                           dropped_frac=aux["dropped_frac"])
            loss = loss + aux_loss_coef * aux["load_balance"]
            if router_z_coef > 0.0:
                loss = loss + router_z_coef * aux["router_z"]
        return loss, metrics

    return loss_fn
