"""HuggingFace LLaMA-family checkpoint interop.

Lets a user bring existing weights to this framework (and take ours back
out): `LlamaForCausalLM`-style state dicts convert losslessly to/from our
parameter tree. The RoPE convention matches (both use the half-split
"rotate_half" layout and the same theta schedule), so conversion is pure
reshaping/transposition — verified to logits parity against the
`transformers` reference implementation in tests/test_hf_convert.py.

Layout mapping (HF `nn.Linear.weight` is (out, in); ours are (in, out)-
style einsum operands):

  model.embed_tokens.weight (V, D)      -> embed.tokens (V, D)
  layers.i.self_attn.q_proj (H*Dh, D)   -> wq[i] (D, H, Dh)    (T + reshape)
  layers.i.self_attn.k_proj (KH*Dh, D)  -> wk[i] (D, KH, Dh)
  layers.i.self_attn.v_proj (KH*Dh, D)  -> wv[i] (D, KH, Dh)
  layers.i.self_attn.o_proj (D, H*Dh)   -> wo[i] (H, Dh, D)
  layers.i.mlp.gate_proj (F, D)         -> w_gate[i] (D, F)
  layers.i.mlp.up_proj (F, D)           -> w_up[i] (D, F)
  layers.i.mlp.down_proj (D, F)         -> w_down[i] (F, D)
  layers.i.input_layernorm (D,)         -> attn_norm[i]
  layers.i.post_attention_layernorm (D,)-> mlp_norm[i]
  model.norm.weight (D,)                -> final_norm.scale
  lm_head.weight (V, D)                 -> lm_head.kernel (D, V)
                                           (absent when tie_word_embeddings)

Reference parity note: view-sonic/Cloud-Server @ v0 is an empty tree
(SURVEY.md); checkpoint interop is part of the re-scoped build inventory.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Mapping

import jax.numpy as jnp
import numpy as np

from cloud_server_tpu.config import ModelConfig


# Overriding these changes the parameter-tree shapes / semantics and can
# only corrupt a conversion, so config_from_hf rejects them rather than
# forwarding them into a reshape error deep inside params_from_hf.
_STRUCTURAL_FIELDS = frozenset({
    "vocab_size", "embed_dim", "num_layers", "num_heads", "num_kv_heads",
    "head_dim", "mlp_dim", "tie_embeddings", "num_experts",
    "rope_theta", "rope_scaling", "rope_scaling_factor",
    "rope_low_freq_factor", "rope_high_freq_factor", "rope_original_max_len",
    "num_dense_layers", "shared_expert_dim", "router_score", "qk_norm",
    "attention_gate", "post_norms",
})


def _llama_leaves_only(cfg: ModelConfig) -> None:
    """Raise for a model with leaves the LLaMA-family state dict has no
    name for here: the converter would leave them out, or never fill them."""
    stated = [f for f in ("num_dense_layers", "shared_expert_dim", "qk_norm",
                          "attention_gate", "post_norms")
              if getattr(cfg, f)]
    if cfg.router_score != "softmax":
        stated.append("router_score")
    if stated:
        raise ValueError(
            "the converter maps the LLaMA family's leaves (one stack of "
            "layers: two norms, q, k, v, o and a gated MLP); this model "
            f"states more ({stated}), which it would drop or leave unfilled")


def _rope_fields_from_hf(hf_config: Any) -> dict:
    """Map transformers' rope_scaling dict onto ModelConfig rope fields.

    Supported: absent/default (no scaling), "linear", "llama3". Anything
    else (yarn, dynamic, longrope...) raises — silently dropping the
    schedule would serve wrong logits at every position."""
    rs = getattr(hf_config, "rope_scaling", None)
    if not rs:
        return {}
    kind = rs.get("rope_type", rs.get("type", "default"))
    if kind in (None, "default"):
        return {}
    if kind == "linear":
        return dict(rope_scaling="linear",
                    rope_scaling_factor=float(rs["factor"]))
    if kind == "llama3":
        return dict(
            rope_scaling="llama3",
            rope_scaling_factor=float(rs["factor"]),
            rope_low_freq_factor=float(rs.get("low_freq_factor", 1.0)),
            rope_high_freq_factor=float(rs.get("high_freq_factor", 4.0)),
            rope_original_max_len=int(
                rs.get("original_max_position_embeddings", 8192)))
    raise ValueError(
        f"unsupported rope_scaling type {kind!r} in HF config — supported: "
        "default/linear/llama3")


def config_from_hf(hf_config: Any, **overrides) -> ModelConfig:
    """Build a ModelConfig from a transformers LlamaConfig-like object.

    `overrides` may adjust behavioral fields (dtype, attention_impl,
    remat, max_seq_len, ...); structural fields that must match the
    checkpoint tensors are rejected when they contradict the HF config.
    Unsupported architecture variants (non-SiLU activation, attention/MLP
    biases, exotic rope scaling) raise instead of converting silently
    wrong."""
    act = getattr(hf_config, "hidden_act", "silu")
    if act not in ("silu", "swish"):
        raise ValueError(f"unsupported hidden_act {act!r} (SwiGLU/SiLU only)")
    for bias_field in ("attention_bias", "mlp_bias"):
        if getattr(hf_config, bias_field, False):
            raise ValueError(
                f"unsupported {bias_field}=True — this framework's "
                "LLaMA-family layers are bias-free")
    fields = dict(
        vocab_size=hf_config.vocab_size,
        embed_dim=hf_config.hidden_size,
        num_layers=hf_config.num_hidden_layers,
        num_heads=hf_config.num_attention_heads,
        num_kv_heads=getattr(hf_config, "num_key_value_heads",
                             hf_config.num_attention_heads),
        head_dim=getattr(hf_config, "head_dim", None)
        or hf_config.hidden_size // hf_config.num_attention_heads,
        mlp_dim=hf_config.intermediate_size,
        max_seq_len=hf_config.max_position_embeddings,
        rope_theta=float(getattr(hf_config, "rope_theta", 10000.0)),
        norm_eps=float(hf_config.rms_norm_eps),
        tie_embeddings=bool(getattr(hf_config, "tie_word_embeddings",
                                    False)),
        **_rope_fields_from_hf(hf_config),
    )
    # Structural fields the HF config doesn't mention still have a correct
    # value for this checkpoint: the ModelConfig default (dense model, no
    # rope scaling). Seed those so every structural override is compared
    # against SOMETHING — `fields.get(key, val)` would vacuously accept
    # e.g. num_experts=8 on a dense checkpoint.
    defaults = {f.name: f.default for f in dataclasses.fields(ModelConfig)}
    for key, val in overrides.items():
        if key in _STRUCTURAL_FIELDS and val != fields.get(key, defaults[key]):
            raise ValueError(
                f"config override {key}={val!r} contradicts the checkpoint "
                f"({fields.get(key, defaults[key])!r}) — structural fields "
                "come from the HF config; drop the override")
    fields.update(overrides)
    return ModelConfig(**fields)


def _np(t) -> np.ndarray:
    if hasattr(t, "detach"):  # torch tensor
        return t.detach().to("cpu").float().numpy()
    return np.asarray(t, np.float32)


# State-dict keys that are buffers/bookkeeping, not weights — safe to skip.
_IGNORABLE_KEY_PARTS = ("rotary_emb", "position_ids", "masked_bias",
                        "attn.bias")


def params_from_hf(state_dict: Mapping[str, Any], cfg: ModelConfig,
                   dtype: str | None = None) -> dict:
    """Convert an HF LlamaForCausalLM state dict to this framework's
    parameter tree (leaves in `dtype`, default cfg.param_dtype).

    Conversion runs one stacked tensor family at a time — each per-layer
    stack is built, transposed, converted to a jnp leaf and its f32 numpy
    intermediate freed before the next family starts — so peak host
    memory is the source checkpoint + the growing output tree + ONE
    f32 layer stack, not four attention stacks at once.

    Every state-dict key must either be consumed or match a known
    ignorable buffer pattern; leftovers (e.g. attention biases from a
    checkpoint with attention_bias=True) raise instead of being silently
    dropped."""
    _llama_leaves_only(cfg)
    L, D, H, KH, Dh = (cfg.num_layers, cfg.embed_dim, cfg.num_heads,
                       cfg.num_kv_heads, cfg.head_dim)
    out_dtype = jnp.dtype(dtype or cfg.param_dtype)
    consumed: set[str] = set()

    def get(key: str) -> np.ndarray:
        consumed.add(key)
        return _np(state_dict[key])

    def stack(fmt: str, transform=None) -> jnp.ndarray:
        arr = np.stack([get(fmt.format(i)) for i in range(L)])
        if transform is not None:
            arr = transform(arr)
        return jnp.asarray(arr, out_dtype)

    layers = {
        "attn_norm": stack("model.layers.{}.input_layernorm.weight"),
        "mlp_norm": stack("model.layers.{}.post_attention_layernorm.weight"),
        # HF projections are (out, in); transpose then split the head dims.
        "wq": stack("model.layers.{}.self_attn.q_proj.weight",
                    lambda a: a.transpose(0, 2, 1).reshape(L, D, H, Dh)),
        "wk": stack("model.layers.{}.self_attn.k_proj.weight",
                    lambda a: a.transpose(0, 2, 1).reshape(L, D, KH, Dh)),
        "wv": stack("model.layers.{}.self_attn.v_proj.weight",
                    lambda a: a.transpose(0, 2, 1).reshape(L, D, KH, Dh)),
        "wo": stack("model.layers.{}.self_attn.o_proj.weight",
                    lambda a: a.transpose(0, 2, 1).reshape(L, H, Dh, D)),
        "w_gate": stack("model.layers.{}.mlp.gate_proj.weight",
                        lambda a: a.transpose(0, 2, 1)),
        "w_up": stack("model.layers.{}.mlp.up_proj.weight",
                      lambda a: a.transpose(0, 2, 1)),
        "w_down": stack("model.layers.{}.mlp.down_proj.weight",
                        lambda a: a.transpose(0, 2, 1)),
    }
    params = {
        "embed": {"tokens": jnp.asarray(
            get("model.embed_tokens.weight"), out_dtype)},
        "layers": layers,
        "final_norm": {"scale": jnp.asarray(
            get("model.norm.weight"), out_dtype)},
    }
    if not cfg.tie_embeddings:
        if "lm_head.weight" not in state_dict:
            raise ValueError(
                "state dict has no lm_head.weight but cfg.tie_embeddings "
                "is False — pass a config with tie_embeddings=True")
        params["lm_head"] = {"kernel": jnp.asarray(
            get("lm_head.weight").T, out_dtype)}
    else:
        consumed.add("lm_head.weight")  # alias of the embedding when tied

    leftover = sorted(
        k for k in state_dict
        if k not in consumed
        and not any(part in k for part in _IGNORABLE_KEY_PARTS))
    if leftover:
        preview = ", ".join(leftover[:6])
        raise ValueError(
            f"{len(leftover)} unsupported weight(s) in checkpoint would be "
            f"silently dropped: {preview}"
            + (" ..." if len(leftover) > 6 else "")
            + " — this architecture variant (biases?) is not supported")
    return params


def params_to_hf(params: Mapping[str, Any], cfg: ModelConfig) -> dict:
    """Inverse of `params_from_hf`: our tree -> HF state-dict numpy arrays
    (torch-free; wrap with torch.from_numpy for transformers)."""
    _llama_leaves_only(cfg)
    L, D, H, KH, Dh = (cfg.num_layers, cfg.embed_dim, cfg.num_heads,
                       cfg.num_kv_heads, cfg.head_dim)
    lp = params["layers"]
    sd: dict[str, np.ndarray] = {
        "model.embed_tokens.weight": np.asarray(
            params["embed"]["tokens"], np.float32),
        "model.norm.weight": np.asarray(
            params["final_norm"]["scale"], np.float32),
    }
    for i in range(L):
        pre = f"model.layers.{i}"
        sd[f"{pre}.input_layernorm.weight"] = np.asarray(
            lp["attn_norm"][i], np.float32)
        sd[f"{pre}.post_attention_layernorm.weight"] = np.asarray(
            lp["mlp_norm"][i], np.float32)
        sd[f"{pre}.self_attn.q_proj.weight"] = np.asarray(
            lp["wq"][i], np.float32).reshape(D, H * Dh).T
        sd[f"{pre}.self_attn.k_proj.weight"] = np.asarray(
            lp["wk"][i], np.float32).reshape(D, KH * Dh).T
        sd[f"{pre}.self_attn.v_proj.weight"] = np.asarray(
            lp["wv"][i], np.float32).reshape(D, KH * Dh).T
        sd[f"{pre}.self_attn.o_proj.weight"] = np.asarray(
            lp["wo"][i], np.float32).reshape(H * Dh, D).T
        sd[f"{pre}.mlp.gate_proj.weight"] = np.asarray(
            lp["w_gate"][i], np.float32).T
        sd[f"{pre}.mlp.up_proj.weight"] = np.asarray(
            lp["w_up"][i], np.float32).T
        sd[f"{pre}.mlp.down_proj.weight"] = np.asarray(
            lp["w_down"][i], np.float32).T
    if not cfg.tie_embeddings:
        sd["lm_head.weight"] = np.asarray(
            params["lm_head"]["kernel"], np.float32).T
    return sd


def load_hf_checkpoint(path: str,
                       **config_overrides) -> tuple[ModelConfig, dict]:
    """Load a local HF LLaMA-family checkpoint directory: returns
    (ModelConfig, params). Requires `transformers` + `torch` (CPU).

    `config_overrides` go to ModelConfig (e.g. dtype="float32",
    attention_impl="flash"); parameter leaves follow the resulting
    cfg.param_dtype. The torch model loads in its checkpoint dtype
    (torch_dtype="auto"), not f32, to halve peak host memory."""
    import transformers

    model = transformers.AutoModelForCausalLM.from_pretrained(
        path, torch_dtype="auto")
    cfg = config_from_hf(model.config, **config_overrides)
    return cfg, params_from_hf(model.state_dict(), cfg)
