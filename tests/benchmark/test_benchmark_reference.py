"""The plain float32 references against the program's own forward pass, at
tiny widths on the CPU, and the comparison that decides ``correct``.

On the chip the harness compares the system, in bf16 at the published
widths, with the same reference files (PERF.md has what it measured). Here
the program runs in float32 too, so the two must agree to float32 rounding:
what is tested is that the reference computes the same mathematics as
``models/transformer.forward`` — rotary embedding, grouped heads, the
sliding window, the router's softmax, top-k and renormalisation — not how
well bf16 holds up.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.lib import check
from benchmarks.lib.params import seeded_params
from benchmarks.reference import mistral, mixtral
from bee_code_interpreter_tpu.models import transformer as T

DENSE = dict(vocab_size=128, d_model=64, n_layers=3, n_heads=4, n_kv_heads=2,
             d_ff=96, max_seq_len=64, rope_theta=1e6, dtype=jnp.float32)
PUBLISHED = dict(hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
                 num_hidden_layers=3, rope_theta=1e6, rms_norm_eps=1e-5,
                 sliding_window=None)
TOKENS = np.random.default_rng(0).integers(0, 128, (2, 40), dtype=np.int32)


def system_logits(config, params):
    with jax.default_matmul_precision("highest"):
        return np.asarray(T.forward(params, jnp.asarray(TOKENS), config))


@pytest.mark.parametrize("window", [None, 16])
def test_mistral_reference_is_the_programs_forward_pass(window):
    config = T.TransformerConfig(**DENSE, sliding_window=window)
    params = seeded_params(T.init_params, config, 3)
    want = system_logits(config, params)
    got = mistral.forward(params, list(TOKENS), {**PUBLISHED, "sliding_window": window})
    for (g, margins), w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), w, atol=2e-5, rtol=1e-4)
        assert margins is None  # a dense layer chooses nothing


@pytest.mark.parametrize("group_size", [1024, 1])
def test_mixtral_reference_is_the_programs_dropless_forward_pass(group_size):
    config = T.TransformerConfig(
        **DENSE, n_experts=4, moe_top_k=2, moe_dropless=True,
        moe_group_size=group_size,
    )
    params = seeded_params(T.init_params, config, 4)
    want = system_logits(config, params)
    cfg = {**PUBLISHED, "num_local_experts": 4, "num_experts_per_tok": 2}
    got = mixtral.forward(params, list(TOKENS), cfg)
    for (g, margins), w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), w, atol=2e-5, rtol=1e-4)
        assert margins.shape == (3, 40) and np.all(np.asarray(margins) >= 0)


def test_mixtral_reference_gives_its_routers_margin():
    """Beside the logits, per layer and position: the router's logit of the
    second expert less the third's, the margin of the one discrete choice
    a token makes there (checked at the first layer, whose input is the
    embedding alone)."""
    config = T.TransformerConfig(**DENSE, n_experts=4, moe_top_k=2, moe_dropless=True)
    params = seeded_params(T.init_params, config, 4)
    cfg = {**PUBLISHED, "num_local_experts": 4, "num_experts_per_tok": 2}
    (_, margins), = mixtral.forward(params, [TOKENS[0]], cfg)
    layer0 = jax.tree.map(lambda x: x[0], params["layers"])
    with jax.default_matmul_precision("highest"):
        h = mistral.attention(mistral.f32(params["embed"][TOKENS[0]]), layer0, cfg)
        y = mistral.rms_norm(h, layer0["ln2"], 1e-5)
        logits = np.sort(np.asarray(y @ mistral.f32(layer0["moe"]["router"])), axis=-1)
    np.testing.assert_allclose(
        np.asarray(margins[0]), logits[:, -2] - logits[:, -3], atol=1e-5
    )
    # as many experts as are kept: nothing can tie
    two = T.TransformerConfig(**DENSE, n_experts=2, moe_top_k=2, moe_dropless=True)
    (_, margins), = mixtral.forward(
        seeded_params(T.init_params, two, 4), [TOKENS[0]],
        {**cfg, "num_local_experts": 2},
    )
    assert np.all(np.isinf(np.asarray(margins)))


def test_capacity_routing_is_not_mixtral():
    """The program's default routing drops tokens over capacity: with a
    capacity factor that cannot hold the tokens it differs from the
    reference by far more than rounding, which is why the configuration
    states ``moe_dropless``."""
    config = T.TransformerConfig(
        **DENSE, n_experts=4, moe_top_k=2, moe_capacity_factor=0.5
    )
    params = seeded_params(T.init_params, config, 4)
    want = system_logits(config, params)
    cfg = {**PUBLISHED, "num_local_experts": 4, "num_experts_per_tok": 2}
    got = np.asarray(next(mixtral.forward(params, list(TOKENS), cfg))[0])
    assert np.abs(got - want[0]).max() > 1e-2


def test_seeded_params_are_the_programs_tree_at_unit_variance():
    config = T.TransformerConfig(**{**DENSE, "dtype": jnp.bfloat16}, n_experts=4)
    params = seeded_params(T.init_params, config, 9)
    shapes = jax.eval_shape(lambda k: T.init_params(config, k), jax.random.PRNGKey(0))
    assert jax.tree.structure(params) == jax.tree.structure(shapes)
    for leaf, shape in zip(jax.tree.leaves(params), jax.tree.leaves(shapes)):
        assert leaf.shape == shape.shape and leaf.dtype == jnp.bfloat16
    w = np.asarray(params["layers"]["moe"]["we_down"], np.float32)  # [3,4,96,64]
    assert w.std() == pytest.approx(96 ** -0.5, rel=0.02) and abs(w.mean()) < 1e-3
    assert not np.array_equal(w[0], w[1])  # layers differ
    assert np.all(np.asarray(params["layers"]["ln1"], np.float32) == 1.0)
    again = seeded_params(T.init_params, config, 9)
    other = seeded_params(T.init_params, config, 10)
    assert np.array_equal(np.asarray(again["embed"]), np.asarray(params["embed"]))
    assert not np.array_equal(np.asarray(other["embed"]), np.asarray(params["embed"]))


# ------------------------------------------------- the comparison itself

ROWS = np.array([[0.0, 2.0, 1.0, -1.0], [3.0, 0.0, 0.0, 0.0]], np.float32)
TOL = {"requests": 1, "logprob_median": 0.03, "logprob_abs": 0.05,
       "argmax_margin": 0.05, "tie_gap": None, "out_share_close": 0.0,
       "out_share_clear": 0.0}


def own(tokens, shift=0.0):
    """The reference's own log-probabilities for ``tokens``, shifted."""
    return [check.log_softmax_at(ROWS[j], t) + shift for j, t in enumerate(tokens)]


def test_compare_accepts_the_references_own_numbers():
    lps = own([1, 0])
    got = check.compare(ROWS, 1, [1, 0], lps, greedy=True)
    assert got == {"logprob_diff": [0.0, 0.0], "margin": [0.0, 0.0], "tie_gap": None}
    assert lps[0] == pytest.approx(2 - np.log(np.exp([0, 2, 1, -1]).sum()))
    out = check.verdict([got], TOL)
    assert out["problems"] == [] and out["positions"] == 2
    assert out["logprob_diff_max"] == 0 and out["argmax_margin_max"] == 0
    assert out["close"] == out["out_clear"] == out["out_close"] == 0


@pytest.mark.parametrize("tokens, shift, greedy, message", [
    ([1, 0], 0.2, True, "log-probability differs by up to 0.2000 (more than 0.05)"),
    ([1, 0], 0.04, True, "median difference of the log-probability from the reference is 0.0400"),
    ([2, 0], 0.0, True, "1 of 2 positions are out where the reference's choice was clear (at most 0% may be)"),
    ([2, 0], 0.0, True, "lies up to 1.0000 below the reference's best logit"),
    ([1, 0], float("nan"), False, "not finite"),
])
def test_verdict_names_what_is_wrong(tokens, shift, greedy, message):
    got = check.compare(ROWS, 1, tokens, own(tokens, shift), greedy=greedy)
    problems = check.verdict([got], TOL)["problems"]
    assert any(message in p for p in problems), problems


def test_the_median_tells_a_coarser_arithmetic_that_no_position_shows():
    """Every position inside its own bound, all of them off by more than
    the stated arithmetic is: only the aggregate can say so."""
    got = check.compare(ROWS, 1, [1, 0], own([1, 0], 0.04), greedy=True)
    problems = check.verdict([got], TOL)["problems"]
    assert len(problems) == 1 and "median" in problems[0]
    assert check.verdict([got], {**TOL, "logprob_median": 0.045})["problems"] == []


def test_a_sampled_token_far_from_the_argmax_is_no_problem():
    got = check.compare(ROWS, 1, [3, 2], own([3, 2]), greedy=False)
    assert got["margin"] is None
    out = check.verdict([got], TOL)
    assert out["problems"] == [] and out["argmax_margin_max"] == 0.0


ROUTED = {**TOL, "tie_gap": 0.1, "out_share_close": 0.25}


def routed(shifts, gaps):
    """Positions of a reference that chooses: its own numbers shifted, with
    its margin for the choice at each."""
    rows = np.tile(ROWS, (len(shifts) // 2, 1))
    tokens = [1, 0] * (len(shifts) // 2)
    lps = [
        check.log_softmax_at(rows[j], t) + shift
        for j, (t, shift) in enumerate(zip(tokens, shifts))
    ]
    return check.compare(rows, 1, tokens, lps, greedy=False, tie_gaps=gaps)


def test_a_position_may_be_out_where_the_references_choice_was_close():
    """An expert router flips where its second and third expert are nearly
    tied: a position that is out is excused there, and nowhere else."""
    close = routed([0.9, 0, 0, 0], gaps=[0.02, 0.5, 0.5, 0.5])
    assert close["tie_gap"] == [0.02, 0.5, 0.5, 0.5]
    out = check.verdict([close], ROUTED)
    assert out["problems"] == []
    assert (out["close"], out["out_close"], out["out_clear"]) == (1, 1, 0)
    far = routed([0.9, 0, 0, 0], gaps=[0.3, 0.02, 0.5, 0.5])
    out = check.verdict([far], ROUTED)
    assert (out["close"], out["out_close"], out["out_clear"]) == (1, 0, 1)
    assert "1 of 4 positions are out where the reference's choice was clear" in out["problems"][0]
    # ... unless the reference allows a share of those too, with its reason
    assert check.verdict([far], {**ROUTED, "out_share_clear": 0.25})["problems"] == []
    # a dense reference excuses nothing, whatever margins it is handed
    assert check.verdict([close], {**ROUTED, "tie_gap": None})["problems"] != []


def test_the_positions_that_are_out_are_capped_however_close_their_choice():
    two = routed([0.9, 0.9, 0, 0, 0, 0], gaps=[0.02, 0.03, 0.04, 0.5, 0.5, 0.5])
    out = check.verdict([two], ROUTED)
    assert (out["close"], out["out_close"], out["out_clear"]) == (3, 2, 0)
    assert "2 of 6 positions are out where the reference's choice was close" in out["problems"][0]
    assert check.verdict([two], {**ROUTED, "out_share_close": 0.5})["problems"] == []
    with pytest.raises(ValueError, match="1 log-probabilities for 2 tokens"):
        check.compare(ROWS, 1, [1, 0], [0.0], greedy=True)


@pytest.mark.parametrize("module", [mistral, mixtral])
def test_references_state_their_tolerance(module):
    assert set(module.TOLERANCE) == {
        "requests", "logprob_median", "logprob_abs", "argmax_margin",
        "tie_gap", "out_share_close", "out_share_clear",
    }
    # an aggregate needs positions: 9 to a request
    assert module.TOLERANCE["requests"] * check.NEW_TOKENS >= 72
    assert module.TOLERANCE["logprob_median"] < module.TOLERANCE["logprob_abs"] / 3
    assert (module.TOLERANCE["tie_gap"] is None) == (module is mistral)
    assert (module.TOLERANCE["out_share_close"] == 0.0) == (module is mistral)
    assert module.TOLERANCE["out_share_clear"] <= 0.01
    assert module.TOLERANCE["out_share_close"] <= 0.125


def test_reference_input_is_the_prompt_and_all_but_the_last_token():
    seq = check.reference_input([5, 6, 7], [8, 9, 10])
    assert seq.tolist() == [5, 6, 7, 8, 9] and seq.dtype == np.int32


def test_the_check_through_the_engine_passes_the_model_and_fails_another():
    """The whole of part (a) at a tiny size: a request through ``Engine``
    and the paged pool in bf16 meets the reference's tolerance, and the
    same request served with another rotary base than the configuration
    states misses it. (What the tolerance cannot tell from bf16's own
    rounding, an int8 K/V pool for one, is in PERF.md's open questions.)"""
    from bee_code_interpreter_tpu.models.engine import Engine
    from bee_code_interpreter_tpu.models.serving import (
        ContinuousBatcher,
        SamplingParams,
    )

    def served(**changed) -> dict:
        config = T.TransformerConfig(**{**DENSE, "dtype": jnp.bfloat16, **changed})
        params = seeded_params(T.init_params, config, 3)
        engine = Engine(ContinuousBatcher(
            params, config, max_batch=2, n_pages=16, page_size=16,
            max_pages_per_seq=4,
        ))
        out, (prompt, tokens) = check.against_reference(
            engine, mistral, params, PUBLISHED, SamplingParams, seed=5,
            prompts=list(TOKENS),
        )
        assert out["positions"] == 2 * check.NEW_TOKENS == 18
        assert np.array_equal(prompt, TOKENS[0]) and len(tokens) == check.NEW_TOKENS
        return out

    assert served()["problems"] == []
    assert served(rope_theta=1e4)["problems"] != []
