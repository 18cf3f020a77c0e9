"""HF Llama checkpoint loading (models/hf_loader.py), pinned by LOGITS
PARITY against transformers' own forward pass — the strongest correctness
statement the transformer family has: every component (RoPE convention,
RMSNorm, SwiGLU, GQA layout, scaling) must agree simultaneously for the
full-model logits to match to 1e-4 in f32."""

import dataclasses

import numpy as np
import pytest

import jax.numpy as jnp

torch = pytest.importorskip("torch")
transformers = pytest.importorskip("transformers")

from bee_code_interpreter_tpu.models.hf_loader import (  # noqa: E402
    config_from_hf,
    load_llama_params,
)
from bee_code_interpreter_tpu.models.serving import (  # noqa: E402
    ContinuousBatcher,
)
from bee_code_interpreter_tpu.models.transformer import (  # noqa: E402
    Transformer,
    forward,
)


def tiny_hf(tie=False, **kw):
    cfg = transformers.LlamaConfig(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=128, rms_norm_eps=1e-5, rope_theta=10000.0,
        attention_bias=False, attention_dropout=0.0,
        tie_word_embeddings=tie, **kw,
    )
    torch.manual_seed(0)
    return transformers.LlamaForCausalLM(cfg).eval()


TOKENS = np.array([[5, 3, 7, 2, 9, 4, 1, 8, 100, 200, 17, 42],
                   [1, 1, 2, 3, 5, 8, 13, 21, 34, 55, 89, 144]],
                  dtype=np.int32)


def hf_logits(model):
    with torch.no_grad():
        return model(torch.tensor(TOKENS, dtype=torch.long)).logits.numpy()


def test_logits_parity_with_transformers():
    model = tiny_hf()
    params, config = load_llama_params(model, dtype=jnp.float32)
    ours = np.asarray(forward(params, jnp.asarray(TOKENS), config))
    np.testing.assert_allclose(ours, hf_logits(model), atol=1e-4, rtol=1e-4)


def test_tied_embeddings_fall_back():
    model = tiny_hf(tie=True)
    params, config = load_llama_params(model, dtype=jnp.float32)
    ours = np.asarray(forward(params, jnp.asarray(TOKENS), config))
    np.testing.assert_allclose(ours, hf_logits(model), atol=1e-4, rtol=1e-4)


def test_loaded_model_decodes_and_serves():
    """The loaded weights run the decode family: cached greedy decode
    matches HF's own greedy generation, and the paged batcher serves it."""
    model = tiny_hf()
    params, config = load_llama_params(model, dtype=jnp.float32)
    prompt = TOKENS[0, :8]
    n = 6
    with torch.no_grad():
        hf_out = model.generate(
            torch.tensor(prompt[None, :], dtype=torch.long),
            max_new_tokens=n, do_sample=False, num_beams=1,
        )[0, len(prompt):].numpy().tolist()
    ours = Transformer(config).generate_cached(
        params, jnp.asarray(prompt[None, :]), max_new_tokens=n
    )
    assert np.asarray(ours[0, len(prompt):]).tolist() == hf_out

    b = ContinuousBatcher(params, config, max_batch=2, n_pages=16,
                          page_size=4, max_pages_per_seq=8)
    r = b.submit(prompt, n)
    b.run_to_completion()
    assert b.result(r) == hf_out


def test_config_mapping_and_refusals():
    model = tiny_hf()
    config = config_from_hf(model.config)
    assert (config.d_model, config.n_layers, config.n_heads,
            config.kv_heads, config.ff_dim) == (64, 2, 4, 2, 128)
    bad_eps = dataclasses.replace  # noqa: F841 (readability anchor)
    # every norm's epsilon is a field of the config since PR 33
    assert config_from_hf(
        transformers.LlamaConfig(rms_norm_eps=1e-6)
    ).rms_norm_eps == 1e-6
    cfg = transformers.LlamaConfig(rms_norm_eps=1e-5, attention_bias=True)
    with pytest.raises(ValueError, match="attention_bias"):
        config_from_hf(cfg)
    cfg = transformers.LlamaConfig(
        rms_norm_eps=1e-5,
        rope_scaling={"rope_type": "yarn", "factor": 4.0},
    )
    with pytest.raises(ValueError, match="rope_scaling"):
        config_from_hf(cfg)


def test_linear_rope_scaling_maps():
    model = tiny_hf(rope_scaling={"rope_type": "linear", "factor": 2.0})
    params, config = load_llama_params(model, dtype=jnp.float32)
    assert config.rope_scaling == 2.0
    ours = np.asarray(forward(params, jnp.asarray(TOKENS), config))
    np.testing.assert_allclose(ours, hf_logits(model), atol=1e-4, rtol=1e-4)


def test_state_dict_path_needs_config():
    model = tiny_hf()
    with pytest.raises(ValueError, match="hf_config"):
        load_llama_params(model.state_dict())
    params, config = load_llama_params(
        model.state_dict(), hf_config=model.config, dtype=jnp.float32
    )
    ours = np.asarray(forward(params, jnp.asarray(TOKENS), config))
    np.testing.assert_allclose(ours, hf_logits(model), atol=1e-4, rtol=1e-4)


def test_hidden_act_and_mlp_bias_refused():
    cfg = transformers.LlamaConfig(rms_norm_eps=1e-5, hidden_act="gelu")
    with pytest.raises(ValueError, match="hidden_act"):
        config_from_hf(cfg)
    cfg = transformers.LlamaConfig(rms_norm_eps=1e-5, mlp_bias=True)
    with pytest.raises(ValueError, match="mlp_bias"):
        config_from_hf(cfg)


def test_non_derived_head_dim_loads_and_matches_transformers():
    """A checkpoint with an explicit head_dim != hidden_size // n_heads
    (increasingly common in HF Llama-family configs; refused until
    ``TransformerConfig.head_dim`` became a field, PR 35) loads at that
    head size, and the logits match transformers' own forward."""
    model = tiny_hf(head_dim=32)
    params, config = load_llama_params(model, dtype=jnp.float32)
    assert config.head_dim == 32 != config.d_model // config.n_heads
    assert params["layers"]["wq"].shape == (2, 64, 4 * 32)
    assert params["layers"]["wk"].shape == (2, 64, 2 * 32)
    assert params["layers"]["wo"].shape == (2, 4 * 32, 64)
    ours = np.asarray(forward(params, jnp.asarray(TOKENS), config))
    np.testing.assert_allclose(ours, hf_logits(model), atol=1e-4, rtol=1e-4)
    # a derived (or absent) head_dim still loads
    cfg = transformers.LlamaConfig(
        rms_norm_eps=1e-5, hidden_size=64, num_attention_heads=4,
        head_dim=16,
    )
    assert config_from_hf(cfg).d_model == 64
    assert config_from_hf(cfg).head_dim == 16
