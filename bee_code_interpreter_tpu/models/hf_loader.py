"""Load HuggingFace Llama-family checkpoints into this framework.

The migration path for real weights: ``transformers`` ships the checkpoint
ecosystem, this framework ships the TPU-first runtime — the loader maps an
HF ``LlamaForCausalLM`` (or its state dict) onto our param pytree and
config, after which every path in the library (mesh-sharded forward,
KV-cached decode, paged serving, speculative, LoRA, checkpoints) serves
the real model.

The mapping is exact, not approximate — our transformer IS Llama
semantics:

- RoPE: the half-split rotate convention (``[x1·cos − x2·sin,
  x1·sin + x2·cos]`` with freqs paired (i, i+d/2)) matches HF's
  ``rotate_half`` application term for term.
- RMSNorm (x/rms·scale, f32 accumulation), SwiGLU (silu(gate)·up·down),
  pre-norm residual order, 1/sqrt(head_dim) score scaling, no biases.
- Weight layout: torch ``Linear.weight`` is [out, in]; our einsums take
  [in, out] — every projection transposes. Heads are laid out
  [head·head_dim + j] on the out axis in both, so no permutation is
  needed beyond the transpose.

Logits parity against ``transformers``' own forward is pinned to 1e-4 by
tests/test_hf_loader.py — the strongest correctness statement the
transformer family has, and the reason this module lives next to the
model code rather than in an example.

Scope honestly stated: attention biases and rope scaling configs other
than linear interpolation (and ``deepseek_yarn`` for ``sarvam_mla``) are
refused rather than silently mis-loaded. ``config_from_hf`` also maps
``granitemoehybrid``, ``sarvam_mla`` and ``exaone_moe`` configurations. ``rms_norm_eps`` is a field of
``TransformerConfig`` and is taken as published.
"""

from __future__ import annotations

from typing import Any

import jax.numpy as jnp
import numpy as np

from bee_code_interpreter_tpu.models.transformer import TransformerConfig

Params = dict[str, Any]


def config_from_hf(hf_config, dtype=jnp.bfloat16) -> TransformerConfig:
    """Our TransformerConfig for an HF ``LlamaConfig``, or for a
    ``GraniteMoeHybridConfig`` (``model_type`` ``granitemoehybrid``: Mamba-2
    layers beside attention layers, with or without routed experts under
    each). A plain dict of the published keys (a ``config.json``)
    is taken like the object. Refuses silently unloadable settings instead
    of approximating them."""
    if isinstance(hf_config, dict):
        import types

        hf_config = types.SimpleNamespace(**hf_config)
    eps = getattr(hf_config, "rms_norm_eps", 1e-5)
    if getattr(hf_config, "attention_bias", False):
        raise ValueError("attention_bias checkpoints are not supported")
    if getattr(hf_config, "mlp_bias", False):
        raise ValueError("mlp_bias checkpoints are not supported")
    act = getattr(hf_config, "hidden_act", "silu")
    if act not in ("silu", "swish"):
        raise ValueError(
            f"hidden_act {act!r} unsupported (our MLP is SwiGLU/silu); "
            "refusing a silently wrong load"
        )
    if getattr(hf_config, "model_type", None) == "granitemoehybrid":
        return _granite_hybrid_config(hf_config, dtype, eps)
    if getattr(hf_config, "model_type", None) == "sarvam_mla":
        return _sarvam_mla_config(hf_config, dtype, eps)
    if getattr(hf_config, "model_type", None) == "exaone_moe":
        return _exaone_moe_config(hf_config, dtype, eps)
    scaling = getattr(hf_config, "rope_scaling", None)
    rope_scaling = 1.0
    if scaling is not None:
        kind = scaling.get("rope_type", scaling.get("type"))
        if kind != "linear":
            raise ValueError(
                f"rope_scaling type {kind!r} unsupported (only linear "
                "position interpolation maps onto our rope scaling)"
            )
        rope_scaling = float(scaling["factor"])
    return TransformerConfig(
        vocab_size=hf_config.vocab_size,
        d_model=hf_config.hidden_size,
        n_layers=hf_config.num_hidden_layers,
        n_heads=hf_config.num_attention_heads,
        n_kv_heads=hf_config.num_key_value_heads,
        # a head of its own width (increasingly common in HF Llama-family
        # configs) is a field; absent, it is hidden_size // heads
        head_dim=getattr(hf_config, "head_dim", None),
        d_ff=hf_config.intermediate_size,
        max_seq_len=hf_config.max_position_embeddings,
        rope_theta=float(getattr(hf_config, "rope_theta", 10000.0)),
        rope_scaling=rope_scaling,
        dtype=dtype,
        rms_norm_eps=eps,
    )


def _sarvam_mla_config(hf_config, dtype, eps) -> TransformerConfig:
    """``sarvam_mla``: latent attention without a query latent, leading
    dense layers, then routed experts (every one of them held: a chip's
    share is the configuration's to cut) beside shared ones, under the
    DeepSeek-V3 lineage's router. What is not computed is refused by
    name."""
    if getattr(hf_config, "q_lora_rank", None):
        raise ValueError(
            f"q_lora_rank {hf_config.q_lora_rank} unsupported: the query is "
            "projected whole (no query latent)"
        )
    if getattr(hf_config, "n_group", 1) not in (None, 1):
        raise ValueError(
            f"n_group {hf_config.n_group} unsupported: the router has one "
            "routing group"
        )
    scaling = getattr(hf_config, "rope_scaling", None)
    if scaling is not None:
        kind = scaling.get("rope_type", scaling.get("type"))
        if kind != "deepseek_yarn":
            raise ValueError(
                f"rope_scaling type {kind!r} unsupported for sarvam_mla "
                "(deepseek_yarn, or none)"
            )
    return TransformerConfig(
        vocab_size=hf_config.vocab_size,
        d_model=hf_config.hidden_size,
        n_layers=hf_config.num_hidden_layers,
        n_heads=hf_config.num_attention_heads,
        d_ff=hf_config.intermediate_size,
        max_seq_len=hf_config.max_position_embeddings,
        rope_theta=getattr(hf_config, "rope_theta", 10000.0),
        dtype=dtype,
        rms_norm_eps=eps,
        kv_lora_rank=hf_config.kv_lora_rank,
        qk_nope_head_dim=hf_config.qk_nope_head_dim,
        qk_rope_head_dim=hf_config.qk_rope_head_dim,
        v_head_dim=hf_config.v_head_dim,
        qk_norm=bool(getattr(hf_config, "use_qk_norm", False)),
        rope_yarn=scaling,
        n_dense_layers=getattr(hf_config, "first_k_dense_replace", 0),
        n_experts=hf_config.num_experts,
        moe_top_k=hf_config.num_experts_per_tok,
        moe_scoring="sigmoid",
        moe_held_experts=hf_config.num_experts,
        moe_d_ff=hf_config.moe_intermediate_size,
        moe_shared_experts=getattr(hf_config, "num_shared_experts", 0),
        moe_routed_scaling=getattr(hf_config, "routed_scaling_factor", 1.0),
        moe_router_bias=bool(
            getattr(hf_config, "moe_router_enable_expert_bias", False)
        ),
    )


def _exaone_moe_config(hf_config, dtype, eps) -> TransformerConfig:
    """``exaone_moe`` (K-EXAONE): window layers and full layers under the
    published ``layer_types``, a head of its own width, an RMSNorm over each
    head's query and key, rotary embedding in the window layers alone,
    leading dense layers, then routed experts (every one of them held: a
    chip's share is the configuration's to cut) beside shared ones, under
    the DeepSeek-V3 lineage's router. It loads WITHOUT the multi-token
    prediction block (``num_nextn_predict_layers``, ``mtp_*``): the forward
    pass that gives the next token's logits does not contain it, the
    program has no field for it and a step yields one token a row, so
    nothing here can use it (self-speculative serving over rings is refused
    by name in ``ContinuousBatcher``). What is not computed is refused by
    name."""
    if getattr(hf_config, "n_group", 1) not in (None, 1) or getattr(
        hf_config, "topk_group", 1
    ) not in (None, 1):
        raise ValueError(
            f"n_group {getattr(hf_config, 'n_group', 1)} / topk_group "
            f"{getattr(hf_config, 'topk_group', 1)} unsupported: the router "
            "has one routing group"
        )
    scoring = getattr(hf_config, "scoring_func", "sigmoid")
    if scoring != "sigmoid":
        raise ValueError(
            f"scoring_func {scoring!r} unsupported for exaone_moe: the held "
            "experts' router scores with a sigmoid"
        )
    if not getattr(hf_config, "norm_topk_prob", True):
        raise ValueError(
            "norm_topk_prob false unsupported: the kept scores are weighted "
            "over their sum"
        )
    rope = getattr(hf_config, "rope_parameters", None) or {}
    kind = rope.get("rope_type", rope.get("type", "default"))
    if kind != "default":
        raise ValueError(
            f"rope_parameters type {kind!r} unsupported for exaone_moe "
            "(default rotary frequencies)"
        )
    kinds = tuple(hf_config.layer_types)
    mlps = getattr(hf_config, "mlp_layer_types", None)
    n_dense = getattr(hf_config, "first_k_dense_replace", 0)
    if mlps is not None and list(mlps) != (
        ["dense"] * n_dense + ["sparse"] * (len(kinds) - n_dense)
    ):
        raise ValueError(
            "mlp_layer_types unsupported: dense layers lead "
            "(first_k_dense_replace) and every other layer is sparse"
        )
    return TransformerConfig(
        vocab_size=hf_config.vocab_size,
        d_model=hf_config.hidden_size,
        n_layers=hf_config.num_hidden_layers,
        n_heads=hf_config.num_attention_heads,
        n_kv_heads=hf_config.num_key_value_heads,
        head_dim=getattr(hf_config, "head_dim", None),
        d_ff=hf_config.intermediate_size,
        max_seq_len=hf_config.max_position_embeddings,
        rope_theta=float(rope.get(
            "rope_theta", getattr(hf_config, "rope_theta", 10000.0)
        )),
        dtype=dtype,
        rms_norm_eps=eps,
        sliding_window=hf_config.sliding_window,
        layer_types=kinds,
        position_embedding="rope_window",
        qk_norm=True,
        n_dense_layers=n_dense,
        n_experts=hf_config.num_experts,
        moe_top_k=hf_config.num_experts_per_tok,
        moe_scoring="sigmoid",
        moe_held_experts=hf_config.num_experts,
        moe_d_ff=hf_config.moe_intermediate_size,
        moe_shared_experts=getattr(hf_config, "num_shared_experts", 0),
        moe_routed_scaling=getattr(hf_config, "routed_scaling_factor", 1.0),
        moe_router_bias=True,
    )


def _granite_hybrid_config(hf_config, dtype, eps=1e-5) -> TransformerConfig:
    """``granitemoehybrid``: every published key the program has a field
    for, and a refusal by name for what it computes otherwise. Without
    routed experts (``num_local_experts`` 0) every layer's MLP is the
    shared SwiGLU of ``shared_intermediate_size``; with them, the sorted
    expert layer under a softmax over the top-k logits (every expert held:
    a chip's share is the configuration's to cut), experts of
    ``intermediate_size`` beside the shared MLP, which is ONE SwiGLU of
    ``shared_intermediate_size``: that many experts' widths."""
    experts = getattr(hf_config, "num_local_experts", 0)
    top_k = getattr(hf_config, "num_experts_per_tok", 0)
    width = hf_config.intermediate_size
    shared = getattr(hf_config, "shared_intermediate_size", width)
    if experts and not 0 < top_k <= experts:
        raise ValueError(
            f"num_local_experts {experts} unsupported with "
            f"num_experts_per_tok {top_k}: a token keeps 1 to "
            f"{experts} experts"
        )
    if experts and shared % width:
        raise ValueError(
            f"shared_intermediate_size {shared} unsupported beside experts "
            f"of {width}: the shared MLP is a whole number of experts' widths"
        )
    moe = {} if not experts else {
        "n_experts": experts, "moe_top_k": top_k, "moe_held_experts": experts,
        "moe_shared_experts": shared // width,
    }
    refused = {
        "mamba_proj_bias": (False, "the mixer's projections have no bias"),
        "mamba_conv_bias": (True, "the mixer's conv has a bias"),
        "normalization_function": ("rmsnorm", "every norm is an RMSNorm"),
    }
    for key, (fixed, why) in refused.items():
        value = getattr(hf_config, key, fixed)
        if value != fixed:
            raise ValueError(f"{key} {value!r} unsupported: {why}")
    return TransformerConfig(
        vocab_size=hf_config.vocab_size,
        d_model=hf_config.hidden_size,
        n_layers=hf_config.num_hidden_layers,
        n_heads=hf_config.num_attention_heads,
        n_kv_heads=hf_config.num_key_value_heads,
        d_ff=width if experts else shared,
        max_seq_len=hf_config.max_position_embeddings,
        rope_theta=getattr(hf_config, "rope_theta", 10000.0),
        dtype=dtype,
        layer_types=tuple(hf_config.layer_types),
        mamba_n_heads=hf_config.mamba_n_heads,
        mamba_d_head=hf_config.mamba_d_head,
        mamba_d_state=hf_config.mamba_d_state,
        mamba_n_groups=hf_config.mamba_n_groups,
        mamba_d_conv=hf_config.mamba_d_conv,
        mamba_expand=hf_config.mamba_expand,
        mamba_chunk_size=hf_config.mamba_chunk_size,
        embedding_multiplier=hf_config.embedding_multiplier,
        residual_multiplier=hf_config.residual_multiplier,
        attention_multiplier=hf_config.attention_multiplier,
        logits_scaling=hf_config.logits_scaling,
        position_embedding=hf_config.position_embedding_type,
        tie_embeddings=bool(getattr(hf_config, "tie_word_embeddings", False)),
        rms_norm_eps=eps,
        **moe,
    )


def _to_numpy(tensor) -> np.ndarray:
    if hasattr(tensor, "detach"):  # torch tensor
        return tensor.detach().to("cpu").float().numpy()
    return np.asarray(tensor, dtype=np.float32)


def load_llama_params(
    model_or_state_dict, hf_config=None, dtype=jnp.bfloat16
) -> tuple[Params, TransformerConfig]:
    """(params, config) for an HF ``LlamaForCausalLM`` or its state dict.

    Params are f32 masters (matching ``init_params``' convention — compute
    casts to ``config.dtype`` at use). Tied word embeddings are honored:
    a missing ``lm_head.weight`` falls back to the embedding transposed.
    """
    if hf_config is None:
        hf_config = getattr(model_or_state_dict, "config", None)
        if hf_config is None:
            raise ValueError(
                "pass hf_config when loading from a bare state dict"
            )
    config = config_from_hf(hf_config, dtype=dtype)
    sd = (
        model_or_state_dict
        if isinstance(model_or_state_dict, dict)
        else model_or_state_dict.state_dict()
    )

    def get(name: str) -> np.ndarray:
        if name in sd:
            return _to_numpy(sd[name])
        raise KeyError(
            f"{name} missing from the state dict — not a Llama-family "
            f"checkpoint? (have e.g. {sorted(sd)[:4]})"
        )

    embed = get("model.embed_tokens.weight")  # [V, D]
    if "lm_head.weight" in sd:
        lm_head = _to_numpy(sd["lm_head.weight"]).T  # [D, V]
    else:  # tie_word_embeddings
        lm_head = embed.T.copy()

    layers: dict[str, list[np.ndarray]] = {
        k: [] for k in ("ln1", "wq", "wk", "wv", "wo", "ln2",
                        "w_gate", "w_up", "w_down")
    }
    for i in range(config.n_layers):
        p = f"model.layers.{i}"
        layers["ln1"].append(get(f"{p}.input_layernorm.weight"))
        layers["wq"].append(get(f"{p}.self_attn.q_proj.weight").T)
        layers["wk"].append(get(f"{p}.self_attn.k_proj.weight").T)
        layers["wv"].append(get(f"{p}.self_attn.v_proj.weight").T)
        layers["wo"].append(get(f"{p}.self_attn.o_proj.weight").T)
        layers["ln2"].append(get(f"{p}.post_attention_layernorm.weight"))
        layers["w_gate"].append(get(f"{p}.mlp.gate_proj.weight").T)
        layers["w_up"].append(get(f"{p}.mlp.up_proj.weight").T)
        layers["w_down"].append(get(f"{p}.mlp.down_proj.weight").T)

    params: Params = {
        "embed": jnp.asarray(embed, jnp.float32),
        "layers": {
            name: jnp.asarray(np.stack(mats), jnp.float32)
            for name, mats in layers.items()
        },
        "ln_f": jnp.asarray(get("model.norm.weight"), jnp.float32),
        "lm_head": jnp.asarray(lm_head, jnp.float32),
    }
    return params, config
