"""Stop sequences, finish reasons, and per-token logprobs in the
continuous batcher — the request-level serving contract on top of the
decode machinery (models/serving.py).

Semantics pinned here: a matched stop sequence retires the request and is
TRIMMED from the result (eos, the model's own stop, stays in); finish
reasons are 'eos' | 'stop' | 'length'; logprobs report the UNFILTERED
model distribution (log-softmax of the raw logits row), so the same token
reports the same value whatever top-k/top-p produced it, and they are
identical between the plain and speculative paths (same tokens, same
target distributions).
"""

import dataclasses
import math

import numpy as np
import pytest

import jax

from bee_code_interpreter_tpu.models.serving import (
    ContinuousBatcher,
    SamplingParams,
    log_normalizers,
    logprob_of,
)
from bee_code_interpreter_tpu.models.transformer import (
    TransformerConfig,
    init_params,
)

CFG = dataclasses.replace(TransformerConfig.tiny(), n_kv_heads=2)
PARAMS = init_params(CFG, jax.random.PRNGKey(0))
PROMPT = [5, 3, 7, 2, 9, 4, 1, 8]


def make_batcher(**kw):
    kw.setdefault("max_batch", 2)
    kw.setdefault("n_pages", 32)
    kw.setdefault("page_size", 4)
    kw.setdefault("max_pages_per_seq", 8)
    return ContinuousBatcher(PARAMS, CFG, **kw)


def run_one(b, prompt, n, **kw):
    r = b.submit(prompt, n, **kw)
    b.run_to_completion()
    return r


def greedy_tokens(n):
    b = make_batcher()
    return b.result(run_one(b, PROMPT, n))


def test_stop_sequence_trims_and_reports_stop():
    want = greedy_tokens(10)
    # stop on the 4th+5th greedy tokens: result must be the first three
    stop = (want[3], want[4])
    b = make_batcher()
    r = run_one(b, PROMPT, 10, sampling=SamplingParams(stop_sequences=(stop,)))
    assert b.result(r) == want[:3]
    assert b.finish_reason(r) == "stop"


def test_first_token_stop_can_empty_the_result():
    want = greedy_tokens(3)
    b = make_batcher()
    r = run_one(b, PROMPT, 3,
                sampling=SamplingParams(stop_sequences=((want[0],),)))
    assert b.result(r) == []
    assert b.finish_reason(r) == "stop"


def test_finish_reasons_length_and_eos():
    want = greedy_tokens(6)
    b = make_batcher()
    r = run_one(b, PROMPT, 6)
    assert b.finish_reason(r) == "length"
    # eos: pick the 3rd greedy token as eos; it stays in the output
    b2 = make_batcher(eos_id=want[2])
    r2 = run_one(b2, PROMPT, 6)
    assert b2.result(r2) == want[:3]
    assert b2.finish_reason(r2) == "eos"
    # finish reason survives release; still-decoding raises
    b2.release(r2)
    assert b2.finish_reason(r2) == "eos"
    with pytest.raises(KeyError):
        b.finish_reason(999)


def test_eos_wins_over_stop_sequence():
    want = greedy_tokens(6)
    b = make_batcher(eos_id=want[2])
    r = run_one(b, PROMPT, 6,
                sampling=SamplingParams(stop_sequences=((want[2],),)))
    assert b.result(r) == want[:3]  # eos kept, not trimmed
    assert b.finish_reason(r) == "eos"


def test_greedy_logprobs_match_manual_log_softmax():
    n = 5
    want = greedy_tokens(n)
    b = make_batcher()
    r = run_one(b, PROMPT, n, sampling=SamplingParams(logprobs=True))
    assert b.result(r) == want
    lps = b.result_logprobs(r)
    assert len(lps) == n
    # greedy tokens are each row's argmax -> every logprob is the max
    # log-softmax entry, finite and <= 0
    assert all(math.isfinite(x) and x <= 0.0 for x in lps)
    # spot-check the helper against numpy on a synthetic row
    row = np.array([0.1, 2.0, -1.0, 0.5], dtype=np.float32)
    want_lp = float(
        np.log(np.exp(row.astype(np.float64) - row.max())
               / np.exp(row.astype(np.float64) - row.max()).sum())[1]
    )
    log_z = float(log_normalizers(row))
    assert abs(logprob_of(row, 1, log_z) - want_lp) < 1e-6


def test_logprobs_are_unfiltered_under_sampling():
    """A top-k=1 sampled request emits the greedy tokens; its logprobs
    must equal the greedy request's (the filter never changes the
    report)."""
    n = 5
    b = make_batcher()
    r_greedy = run_one(b, PROMPT, n, sampling=SamplingParams(logprobs=True))
    greedy_lps = b.result_logprobs(r_greedy)
    b2 = make_batcher()
    r_k1 = run_one(
        b2, PROMPT, n,
        sampling=SamplingParams(temperature=0.7, top_k=1, logprobs=True,
                                seed=3),
    )
    assert b2.result(r_k1) == b.result(r_greedy)
    np.testing.assert_allclose(b2.result_logprobs(r_k1), greedy_lps,
                               rtol=1e-5)


def test_speculative_logprobs_and_stops_match_plain():
    draft_cfg = dataclasses.replace(CFG, n_layers=1)
    draft = init_params(draft_cfg, jax.random.PRNGKey(2))
    n = 8
    want = greedy_tokens(n)
    stop = (want[4], want[5])
    sp = SamplingParams(stop_sequences=(stop,), logprobs=True)

    plain = make_batcher()
    r_p = run_one(plain, PROMPT, n, sampling=sp)

    b = ContinuousBatcher(
        PARAMS, CFG, max_batch=2, n_pages=32, page_size=4,
        max_pages_per_seq=8, draft_params=draft, draft_config=draft_cfg,
        gamma=3,
    )
    r_s = run_one(b, PROMPT, n, sampling=sp)
    assert b.result(r_s) == plain.result(r_p) == want[:4]
    assert b.finish_reason(r_s) == plain.finish_reason(r_p) == "stop"
    # same tokens, same target distributions -> same logprobs (the verify
    # window and the single-step program differ only at the ULP level)
    np.testing.assert_allclose(
        b.result_logprobs(r_s), plain.result_logprobs(r_p), atol=1e-3
    )


def test_logprobs_released_and_unrecorded_requests_raise():
    b = make_batcher()
    r_plain = run_one(b, PROMPT, 3)
    with pytest.raises(KeyError, match="did not record"):
        b.result_logprobs(r_plain)
    r_lp = run_one(b, PROMPT, 3, sampling=SamplingParams(logprobs=True))
    assert len(b.result_logprobs(r_lp)) == 3
    b.release(r_lp)
    with pytest.raises(KeyError, match="released"):
        b.result_logprobs(r_lp)


def test_empty_stop_sequence_rejected():
    with pytest.raises(ValueError, match="non-empty"):
        SamplingParams(stop_sequences=((),))


def test_unknown_request_logprobs_says_unknown():
    with pytest.raises(KeyError, match="unknown request"):
        make_batcher().result_logprobs(999)


def test_moe_serving_is_deterministic_not_solo_pinned():
    """MoE through the plain batcher: usable and deterministic — two
    identical batcher runs produce identical outputs — but NOT pinned
    equal to solo decode (capacity routing couples batch-mates and the
    padded admission prompt; the module docstring documents the stance,
    tests/test_moe.py the underlying inherent property)."""
    cfg = dataclasses.replace(TransformerConfig.tiny_moe(),
                              moe_capacity_factor=8.0)
    params = init_params(cfg, jax.random.PRNGKey(0))

    def run():
        b = ContinuousBatcher(params, cfg, max_batch=2, n_pages=32,
                              page_size=4, max_pages_per_seq=8)
        r1 = b.submit(PROMPT, 5)
        r2 = b.submit([3, 1, 4, 1, 5], 5)
        b.run_to_completion()
        return b.result(r1), b.result(r2)

    first, second = run(), run()
    assert first == second
    assert all(len(out) == 5 for out in first)


# ------------------------- logit_bias / allowed_tokens (constrained decode)

def test_logit_bias_bans_and_forces():
    want = greedy_tokens(4)
    # ban the greedy first token: output must start differently
    b = make_batcher()
    r = run_one(b, PROMPT, 4,
                sampling=SamplingParams(logit_bias={want[0]: -1e9}))
    banned = b.result(r)
    # the bias applies at EVERY step, not just admission
    assert want[0] not in banned
    # force an arbitrary token everywhere with a huge positive bias
    b2 = make_batcher()
    r2 = run_one(b2, PROMPT, 4, sampling=SamplingParams(logit_bias={7: 1e9}))
    assert b2.result(r2) == [7, 7, 7, 7]


def test_allowed_tokens_masks_greedy_to_the_set():
    allowed_set = [2, 3, 5, 7, 11, 13]
    b = make_batcher()
    r = run_one(b, PROMPT, 6,
                sampling=SamplingParams(
                    allowed_tokens=lambda generated: allowed_set))
    assert all(t in allowed_set for t in b.result(r))


def test_allowed_tokens_sees_generated_prefixes():
    seen = []

    def constraint(generated):
        seen.append(list(generated))
        return None  # unconstrained: output must equal plain greedy

    b = make_batcher()
    r = run_one(b, PROMPT, 4,
                sampling=SamplingParams(allowed_tokens=constraint))
    out = b.result(r)
    assert out == greedy_tokens(4)
    assert seen == [out[:i] for i in range(4)]


def test_grammar_style_constraint_drives_a_sequence():
    """A stateful grammar: after token A only B is legal, after B only A —
    the closure-over-parser-state pattern a JSON engine would use."""
    A, B = 9, 17

    def alternate(generated):
        if not generated:
            return [A]
        return [B] if generated[-1] == A else [A]

    b = make_batcher()
    r = run_one(b, PROMPT, 6,
                sampling=SamplingParams(allowed_tokens=alternate))
    assert b.result(r) == [A, B, A, B, A, B]


def test_sampled_constrained_draws_stay_in_set_and_are_seeded():
    allowed_set = [1, 2, 3, 4]
    sp = SamplingParams(temperature=1.5, seed=11,
                        allowed_tokens=lambda g: allowed_set)
    b = make_batcher()
    out1 = b.result(run_one(b, PROMPT, 8, sampling=sp))
    b2 = make_batcher()
    out2 = b2.result(run_one(b2, PROMPT, 8, sampling=sp))
    assert out1 == out2  # same seed, same draws
    assert all(t in allowed_set for t in out1)
    assert len(set(out1)) > 1  # hot temperature actually explores the set


def test_logprobs_report_model_distribution_even_when_steered():
    b = make_batcher()
    r = run_one(b, PROMPT, 3,
                sampling=SamplingParams(logit_bias={7: 1e9}, logprobs=True))
    assert b.result(r) == [7, 7, 7]
    # 7 is (whp) not the model's argmax: its raw logprob is well below 0,
    # proving the report ignores the bias that forced it
    assert all(lp < -0.5 for lp in b.result_logprobs(r))


def test_speculative_refuses_steering():
    draft_cfg = dataclasses.replace(CFG, n_layers=1)
    draft = init_params(draft_cfg, jax.random.PRNGKey(2))
    b = ContinuousBatcher(
        PARAMS, CFG, max_batch=2, n_pages=32, page_size=4,
        max_pages_per_seq=8, draft_params=draft, draft_config=draft_cfg,
    )
    with pytest.raises(ValueError, match="unsteered argmax"):
        b.submit(PROMPT, 4, sampling=SamplingParams(logit_bias={1: 5.0}))
    with pytest.raises(ValueError, match="unsteered argmax"):
        b.submit(PROMPT, 4,
                 sampling=SamplingParams(allowed_tokens=lambda g: [1]))


def test_terminal_constraint_at_admission_completes_empty():
    """A grammar already in its terminal state at step 0 is a FINISHED
    request with an empty output — not an error, and no leaked pages."""
    b = make_batcher()
    free0 = len(b.free_pages)
    r = b.submit(PROMPT, 4,
                 sampling=SamplingParams(allowed_tokens=lambda g: [],
                                         logprobs=True))
    assert b.is_done(r)
    assert b.result(r) == []
    assert b.result_logprobs(r) == []
    assert b.finish_reason(r) == "constraint"
    assert len(b.free_pages) == free0  # nothing leaked


def test_terminal_constraint_mid_decode_retires_cleanly():
    """A grammar completing after 3 tokens retires the request with
    finish reason 'constraint'; its batch-mate keeps decoding."""
    A, B_tok = 9, 17

    def three_then_done(generated):
        if len(generated) >= 3:
            return []
        return [A] if len(generated) % 2 == 0 else [B_tok]

    b = make_batcher()
    r_grammar = b.submit(
        PROMPT, 10,
        sampling=SamplingParams(allowed_tokens=three_then_done),
    )
    r_plain = b.submit([3, 1, 4, 1, 5], 6)
    b.run_to_completion()
    assert b.result(r_grammar) == [A, B_tok, A]
    assert b.finish_reason(r_grammar) == "constraint"
    assert len(b.result(r_plain)) == 6  # batch-mate unaffected
    assert b.finish_reason(r_plain) == "length"
    assert (b.page_ref > 0).sum() == 0  # all pages back


def test_buggy_constraint_retires_with_error_not_wedge():
    """A user callable that raises mid-decode retires ITS row with finish
    reason 'error' (message recorded); the batch keeps serving."""

    def explode_after_two(generated):
        if len(generated) >= 2:
            raise KeyError("grammar state corrupted")
        return None

    b = make_batcher()
    r_bad = b.submit(
        PROMPT, 8, sampling=SamplingParams(allowed_tokens=explode_after_two)
    )
    r_ok = b.submit([3, 1, 4, 1, 5], 6)
    b.run_to_completion()
    assert b.finish_reason(r_bad) == "error"
    assert "grammar state corrupted" in b.request_error(r_bad)
    assert len(b.result(r_bad)) == 2  # tokens before the failure kept
    assert len(b.result(r_ok)) == 6
    assert b.request_error(r_ok) is None
    assert (b.page_ref > 0).sum() == 0


def test_out_of_vocab_constraint_is_an_error():
    b = make_batcher()
    r = b.submit(
        PROMPT, 4,
        sampling=SamplingParams(
            allowed_tokens=lambda g: [10**9] if g else None
        ),
    )
    b.run_to_completion()
    assert b.finish_reason(r) == "error"
    assert "out-of-vocab" in b.request_error(r)


def test_cancel_frees_the_row_and_keeps_partial_output():
    b = make_batcher()
    r_cancel = b.submit(PROMPT, 20)
    r_keep = b.submit([3, 1, 4, 1, 5], 6)
    b.step()
    b.step()
    free_before = len(b.free_pages)
    b.cancel(r_cancel)
    assert b.is_done(r_cancel)
    assert b.finish_reason(r_cancel) == "cancelled"
    assert len(b.result(r_cancel)) == 3  # first token + two steps
    assert len(b.free_pages) > free_before  # pages back immediately
    # the freed row is admittable again while the batch-mate finishes
    r_new = b.submit(PROMPT, 4)
    b.run_to_completion()
    assert len(b.result(r_keep)) == 6
    assert b.result(r_new) == greedy_tokens(4)
    # cancelling a finished request is a no-op, not an error
    b.cancel(r_keep)
    assert b.finish_reason(r_keep) == "length"


def test_cancel_unknown_id_raises():
    with pytest.raises(KeyError, match="unknown request"):
        make_batcher().cancel(999)
