"""Continuous batching: per-request outputs must be independent of what
else shares the batch, and pages must recycle."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bee_code_interpreter_tpu.models import transformer as T
from bee_code_interpreter_tpu.models.serving import (
    ContinuousBatcher,
    SamplingParams,
)


def cfg(**kw):
    return dataclasses.replace(
        T.TransformerConfig.tiny(), dtype=jnp.float32, n_kv_heads=2, **kw
    )


def reference_tokens(params, config, prompt, n):
    """The target each request must reproduce: the model's own greedy
    cached decode, run solo."""
    out = T.Transformer(config).generate_cached(
        params, jnp.asarray(prompt)[None, :], max_new_tokens=n
    )
    return np.asarray(out[0, len(prompt):]).tolist()


def test_staggered_requests_match_solo_decode():
    # Three prompts of different lengths admitted at different times; each
    # result must equal that prompt's solo greedy decode token-for-token.
    config = cfg()
    params = T.init_params(config, jax.random.PRNGKey(0))
    prompts = [
        np.asarray(jax.random.randint(jax.random.PRNGKey(i + 1), (L,), 0,
                                      config.vocab_size))
        for i, L in enumerate([3, 7, 5])
    ]
    want = [reference_tokens(params, config, p, 6) for p in prompts]

    b = ContinuousBatcher(
        params, config, max_batch=2, n_pages=16, page_size=4,
        max_pages_per_seq=4,
    )
    r0 = b.submit(prompts[0], 6)
    r1 = b.submit(prompts[1], 6)
    b.step(); b.step()
    # batch full: third request waits until a row frees
    with pytest.raises(RuntimeError, match="no free batch row"):
        b.submit(prompts[2], 6)
    b.run_to_completion()
    r2 = b.submit(prompts[2], 6)  # admitted into a recycled row + pages
    b.run_to_completion()

    assert b.result(r0) == want[0]
    assert b.result(r1) == want[1]
    assert b.result(r2) == want[2]


def test_pages_recycle():
    config = cfg()
    params = T.init_params(config, jax.random.PRNGKey(0))
    b = ContinuousBatcher(
        params, config, max_batch=1, n_pages=5, page_size=4,
        max_pages_per_seq=4,
    )
    free0 = len(b.free_pages)
    prompt = np.asarray([1, 2, 3, 4, 5])
    row = b.submit(prompt, 4)
    assert len(b.free_pages) < free0  # pages held while decoding
    b.run_to_completion()
    assert b.is_done(row)
    assert len(b.free_pages) == free0  # all pages back after retirement


def test_budget_and_pool_validation():
    config = cfg()
    params = T.init_params(config, jax.random.PRNGKey(0))
    b = ContinuousBatcher(
        params, config, max_batch=2, n_pages=3, page_size=4,
        max_pages_per_seq=2,
    )
    with pytest.raises(ValueError, match="exceeds the block table"):
        b.submit(np.arange(1, 8), 4)  # 7 + 4 > 2*4
    with pytest.raises(ValueError, match="max_new_tokens"):
        b.submit(np.arange(1, 4), 0)  # asking for zero tokens is a bug
    with pytest.raises(RuntimeError, match="page pool exhausted"):
        b.submit(np.arange(1, 6), 3)  # needs 2 pages, pool has (3-1)=2... ok
        b.submit(np.arange(1, 6), 3)  # second request: pool empty


def test_eos_retires_early():
    config = cfg()
    params = T.init_params(config, jax.random.PRNGKey(0))
    prompt = np.asarray([1, 2, 3])
    solo = reference_tokens(params, config, prompt, 8)
    # pick an eos value whose FIRST occurrence is past the first token, so
    # the stop is genuinely early and genuinely at that position
    stop_at = next(
        (i for i in range(1, len(solo)) if solo[i] not in solo[:i]), None
    )
    if stop_at is None:
        pytest.skip("greedy output has no late first-occurrence token")
    b = ContinuousBatcher(
        params, config, max_batch=1, n_pages=8, page_size=4,
        max_pages_per_seq=3, eos_id=solo[stop_at],
    )
    req = b.submit(prompt, 8)
    b.run_to_completion()
    assert b.result(req) == solo[: stop_at + 1]  # stopped at eos, prefix identical


def test_per_request_sampling_deterministic_and_isolated():
    # Heterogeneous sampling in one batch: a greedy request batched with
    # sampled ones must still equal its solo greedy decode (per-request
    # isolation), and a sampled request with a fixed seed must reproduce
    # exactly across separate batcher instances.
    from bee_code_interpreter_tpu.models.serving import SamplingParams

    config = cfg()
    params = T.init_params(config, jax.random.PRNGKey(0))
    p_greedy = np.asarray([3, 1, 4, 1, 5])
    p_sampled = np.asarray([9, 2, 6])
    want_greedy = reference_tokens(params, config, p_greedy, 6)
    hot = SamplingParams(temperature=1.0, top_k=8, seed=123)

    def run():
        b = ContinuousBatcher(
            params, config, max_batch=2, n_pages=16, page_size=4,
            max_pages_per_seq=4,
        )
        rg = b.submit(p_greedy, 6)
        rs = b.submit(p_sampled, 6, sampling=hot)
        b.run_to_completion()
        return b.result(rg), b.result(rs)

    g1, s1 = run()
    g2, s2 = run()
    assert g1 == want_greedy == g2  # greedy unaffected by sampled batchmate
    assert s1 == s2  # fixed seed: fully deterministic
    other = ContinuousBatcher(
        params, config, max_batch=1, n_pages=16, page_size=4,
        max_pages_per_seq=4,
    )
    r = other.submit(
        p_sampled, 6, sampling=SamplingParams(temperature=1.0, top_k=8, seed=7)
    )
    other.run_to_completion()
    assert other.result(r) != s1  # different seed: different draw (whp)


def test_sampling_filters_respected():
    # top_k=1 degenerates to greedy regardless of temperature; top_p tiny
    # keeps only the argmax mass — both must equal the greedy output.
    from bee_code_interpreter_tpu.models.serving import SamplingParams

    config = cfg()
    params = T.init_params(config, jax.random.PRNGKey(0))
    prompt = np.asarray([2, 7, 1, 8])
    want = reference_tokens(params, config, prompt, 5)
    for sp in (
        SamplingParams(temperature=1.0, top_k=1, seed=11),
        SamplingParams(temperature=0.7, top_p=1e-9, seed=12),
        # degenerate top_p=0 keeps at least the top token (sample_logits
        # parity) instead of masking the vocab into NaNs
        SamplingParams(temperature=0.7, top_p=0.0, seed=13),
    ):
        b = ContinuousBatcher(
            params, config, max_batch=1, n_pages=16, page_size=4,
            max_pages_per_seq=4,
        )
        r = b.submit(prompt, 5, sampling=sp)
        b.run_to_completion()
        assert b.result(r) == want, sp


def test_sampling_params_validated():
    from bee_code_interpreter_tpu.models.serving import SamplingParams

    with pytest.raises(ValueError, match="top_k must be >= 1"):
        SamplingParams(top_k=0)
    with pytest.raises(ValueError, match="temperature must be >= 0"):
        SamplingParams(temperature=-1.0)


def test_host_filter_parity_with_device():
    # The host sampler must draw from EXACTLY the distribution the device
    # filter defines — not just on degenerate cases: random logits (with
    # planted ties to exercise tie semantics) across a top-k/top-p grid,
    # comparing the full filtered probability vectors.
    from bee_code_interpreter_tpu.models.serving import (
        SamplingParams,
        filtered_probs_host,
    )
    from bee_code_interpreter_tpu.models.transformer import filter_logits

    rng = np.random.default_rng(0)
    V = 64
    for trial in range(4):
        logits = rng.normal(size=V).astype(np.float32)
        logits[5] = logits[9]  # planted tie
        for temperature in (0.5, 1.3):
            for top_k in (None, 1, 7, V):
                for top_p in (None, 0.0, 0.3, 0.95, 1.0):
                    params = SamplingParams(
                        temperature=temperature, top_k=top_k, top_p=top_p
                    )
                    host = filtered_probs_host(logits, params)
                    dev = np.asarray(
                        jax.nn.softmax(
                            filter_logits(
                                jnp.asarray(logits)[None, :] / temperature,
                                top_k, top_p,
                            ),
                            axis=-1,
                        )[0]
                    )
                    np.testing.assert_allclose(
                        host, dev, atol=1e-6, rtol=1e-5,
                        err_msg=f"t={temperature} k={top_k} p={top_p}",
                    )


def test_failed_submit_does_not_leak_pages():
    # An admission that fails AFTER pages were allocated (here: top_k
    # larger than the vocab blows up in the first-token draw) must return
    # its pages and leave the row free — otherwise repeated failures drain
    # the pool permanently.
    from bee_code_interpreter_tpu.models.serving import SamplingParams

    config = cfg()
    params = T.init_params(config, jax.random.PRNGKey(0))
    b = ContinuousBatcher(
        params, config, max_batch=1, n_pages=8, page_size=4,
        max_pages_per_seq=4,
    )
    free0 = len(b.free_pages)
    bad = SamplingParams(temperature=1.0, top_k=config.vocab_size + 1)
    for _ in range(3):
        with pytest.raises(Exception):
            b.submit(np.asarray([1, 2, 3]), 4, sampling=bad)
    assert len(b.free_pages) == free0
    assert not b.active.any()
    # the pool still admits a good request afterwards
    req = b.submit(np.asarray([1, 2, 3]), 4)
    b.run_to_completion()
    assert b.result(req) == reference_tokens(params, config, [1, 2, 3], 4)


def test_release_frees_results():
    config = cfg()
    params = T.init_params(config, jax.random.PRNGKey(0))
    b = ContinuousBatcher(
        params, config, max_batch=1, n_pages=8, page_size=4,
        max_pages_per_seq=4,
    )
    req = b.submit(np.asarray([5, 6]), 3)
    with pytest.raises(RuntimeError, match="still decoding"):
        b.release(req)
    b.run_to_completion()
    b.result(req)
    b.release(req)
    assert req not in b.results
    assert b.is_done(req)  # terminal state stays observable after release
    with pytest.raises(KeyError, match="released"):
        b.result(req)


def test_chunked_admission_matches_one_shot():
    # submit(prefill_chunk=...) — the bounded-memory long-prompt admission —
    # must produce the same tokens as the one-shot O(L^2) admission (f32
    # config: the chunked prefill is pinned exactly equal to the full
    # forward, so the whole request pipeline must agree).
    config = cfg()
    params = T.init_params(config, jax.random.PRNGKey(0))
    prompt = np.asarray(jax.random.randint(jax.random.PRNGKey(21), (11,), 0,
                                           config.vocab_size))

    def run(**kw):
        b = ContinuousBatcher(
            params, config, max_batch=1, n_pages=16, page_size=4,
            max_pages_per_seq=4,
        )
        r = b.submit(prompt, 5, **kw)
        b.run_to_completion()
        return b.result(r)

    assert run(prefill_chunk=4) == run()


def test_chunked_admission_int8_matches_generate_cached():
    # int8 + chunked admission: the pool is seeded by VERBATIM copy of the
    # chunked cache's int8 leaves (never re-quantized), so the batcher
    # equals generate_cached(prefill_chunk=...) on the same config.
    config = cfg(kv_cache_dtype="int8")
    params = T.init_params(config, jax.random.PRNGKey(0))
    prompt = np.asarray(jax.random.randint(jax.random.PRNGKey(22), (9,), 0,
                                           config.vocab_size))
    want = np.asarray(T.Transformer(config).generate_cached(
        params, jnp.asarray(prompt)[None, :], max_new_tokens=4,
        prefill_chunk=4,
    )[0, len(prompt):]).tolist()
    b = ContinuousBatcher(
        params, config, max_batch=1, n_pages=16, page_size=4,
        max_pages_per_seq=4,
    )
    r = b.submit(prompt, 4, prefill_chunk=4)
    b.run_to_completion()
    assert b.result(r) == want


def draft_cfg():
    return dataclasses.replace(
        T.TransformerConfig.tiny(), dtype=jnp.float32, n_layers=1,
        d_model=32, n_heads=2, d_ff=64,
    )


def test_speculative_serving_matches_solo_greedy():
    # Speculative continuous batching: staggered heterogeneous requests,
    # an unrelated random draft, per-row accept lengths — every request
    # must equal its solo greedy decode token-for-token.
    config = cfg()
    params = T.init_params(config, jax.random.PRNGKey(0))
    dparams = T.init_params(draft_cfg(), jax.random.PRNGKey(42))
    prompts = [
        np.asarray(jax.random.randint(jax.random.PRNGKey(60 + i), (L,), 0,
                                      config.vocab_size))
        for i, L in enumerate([3, 7, 5])
    ]
    want = [reference_tokens(params, config, p, 6) for p in prompts]

    b = ContinuousBatcher(
        params, config, max_batch=2, n_pages=24, page_size=4,
        max_pages_per_seq=6, draft_params=dparams, draft_config=draft_cfg(),
        gamma=3,
    )
    r0 = b.submit(prompts[0], 6)
    r1 = b.submit(prompts[1], 6)
    b.step()
    with pytest.raises(RuntimeError, match="no free batch row"):
        b.submit(prompts[2], 6)
    b.run_to_completion()
    r2 = b.submit(prompts[2], 6)
    b.run_to_completion()
    assert [b.result(r) for r in (r0, r1, r2)] == want


def test_speculative_serving_perfect_draft_fewer_rounds():
    # draft == target: every proposal accepted, so a request finishes in
    # ~max_new/(gamma+1) rounds instead of max_new — and stays exact.
    #
    # The prompt is chosen TIE-FREE: the draft proposes via the one-token
    # decode_step_paged and the target verifies via the windowed
    # decode_window_paged — different XLA programs whose reduction order
    # can differ by ~1e-6 (and flip between standalone and in-suite runs,
    # which is how the old [5, 3, 8, 2] fixture went env-sensitive). Along
    # this prompt's greedy path every top-2 logit gap is >= 0.02 (paged
    # paths >= 0.037 measured), so the argmax is deterministic in any run
    # order. The canary below fails loudly — instead of flaking — if a
    # config/seed change ever erodes that margin.
    config = cfg()
    params = T.init_params(config, jax.random.PRNGKey(0))
    prompt = np.asarray([8, 2, 5, 9])
    want = reference_tokens(params, config, prompt, 8)
    toks = prompt.tolist()
    for tok in want:
        last = T.forward(params, jnp.asarray(toks)[None, :], config)[0, -1, :]
        top2 = np.sort(np.asarray(last, dtype=np.float64))[-2:]
        assert top2[1] - top2[0] > 0.01, (
            "fixture no longer tie-free: re-pick a prompt with a clear "
            f"argmax margin (got {top2[1] - top2[0]:.2e} at {len(toks)})"
        )
        toks.append(tok)
    b = ContinuousBatcher(
        params, config, max_batch=1, n_pages=16, page_size=4,
        max_pages_per_seq=4, draft_params=params, draft_config=config,
        gamma=3,
    )
    r = b.submit(prompt, 8)
    rounds = 0
    while not b.is_done(r):
        b.step()
        rounds += 1
    assert b.result(r) == want
    assert rounds <= 3  # ceil((8-1)/(gamma+1)) = 2 plus slack


def test_speculative_serving_int8_target():
    # The full stack composed: speculative + paged + int8 target pool must
    # equal the solo int8 greedy decode (the draft stays bf16 — drafts
    # only propose).
    config = cfg(kv_cache_dtype="int8")
    params = T.init_params(config, jax.random.PRNGKey(0))
    dparams = T.init_params(draft_cfg(), jax.random.PRNGKey(42))
    prompt = np.asarray([7, 1, 6, 3, 9])
    want = reference_tokens(params, config, prompt, 6)
    b = ContinuousBatcher(
        params, config, max_batch=1, n_pages=16, page_size=4,
        max_pages_per_seq=4, draft_params=dparams, draft_config=draft_cfg(),
        gamma=3,
    )
    r = b.submit(prompt, 6)
    b.run_to_completion()
    assert b.result(r) == want


def test_speculative_rounds_pool_history_independent():
    # Pages are zeroed at admission, so a request's round count (draft
    # acceptance) must not depend on what a PREVIOUS request left in the
    # recycled pages — throughput isolation, not just output isolation.
    config = cfg()
    params = T.init_params(config, jax.random.PRNGKey(0))
    dparams = T.init_params(draft_cfg(), jax.random.PRNGKey(42))
    prompt = np.asarray([6, 2, 9, 1])

    def make():
        return ContinuousBatcher(
            params, config, max_batch=1, n_pages=16, page_size=4,
            max_pages_per_seq=4, draft_params=dparams,
            draft_config=draft_cfg(), gamma=3,
        )

    def run(b):
        r = b.submit(prompt, 6)
        n = 0
        while not b.is_done(r):
            b.step()
            n += 1
        return b.result(r), n

    out_fresh, n_fresh = run(make())
    dirty = make()
    r0 = dirty.submit(np.asarray([8, 8, 8, 8, 8, 8, 8]), 6)  # dirty the pool
    dirty.run_to_completion()
    out_reused, n_reused = run(dirty)
    assert out_fresh == out_reused
    assert n_fresh == n_reused


def test_speculative_serving_validations():
    config = cfg()
    params = T.init_params(config, jax.random.PRNGKey(0))
    dparams = T.init_params(draft_cfg(), jax.random.PRNGKey(1))
    with pytest.raises(ValueError, match="share a vocabulary"):
        ContinuousBatcher(
            params, config,
            draft_params=dparams,
            draft_config=dataclasses.replace(draft_cfg(), vocab_size=17),
        )
    with pytest.raises(ValueError, match="BOTH draft_params"):
        ContinuousBatcher(params, config, draft_params=dparams)
    with pytest.raises(ValueError, match="gamma"):
        ContinuousBatcher(
            params, config, draft_params=dparams, draft_config=draft_cfg(),
            gamma=0,
        )
    from bee_code_interpreter_tpu.models.serving import SamplingParams

    b = ContinuousBatcher(
        params, config, max_batch=1, n_pages=16, page_size=4,
        max_pages_per_seq=4, draft_params=dparams, draft_config=draft_cfg(),
    )
    # sampled speculative is supported since round 4 (rejection sampling,
    # tests/test_speculative_sampling.py); steering is still refused
    with pytest.raises(ValueError, match="unsteered argmax"):
        b.submit(np.asarray([1, 2]), 3,
                 sampling=SamplingParams(logit_bias={1: 5.0}))
    r = b.submit(np.asarray([1, 2]), 3,
                 sampling=SamplingParams(temperature=1.0))
    b.run_to_completion()
    assert len(b.result(r)) == 3


def test_speculative_serving_eos_stops_early():
    config = cfg()
    params = T.init_params(config, jax.random.PRNGKey(0))
    dparams = T.init_params(draft_cfg(), jax.random.PRNGKey(7))
    prompt = np.asarray([4, 9, 2])
    solo = reference_tokens(params, config, prompt, 8)
    stop_at = next(
        (i for i in range(1, len(solo)) if solo[i] not in solo[:i]), None
    )
    if stop_at is None:
        pytest.skip("greedy output has no late first-occurrence token")
    b = ContinuousBatcher(
        params, config, max_batch=1, n_pages=16, page_size=4,
        max_pages_per_seq=6, draft_params=dparams, draft_config=draft_cfg(),
        eos_id=solo[stop_at], gamma=3,
    )
    r = b.submit(prompt, 8)
    b.run_to_completion()
    assert b.result(r) == solo[: stop_at + 1]


def test_int8_pool_matches_solo_int8_decode():
    # The int8 paged pool (scale planes per page) must reproduce the solo
    # int8 contiguous decode — both quantize per (token, head) row, so the
    # cache evolutions are identical.
    config = cfg(kv_cache_dtype="int8")
    params = T.init_params(config, jax.random.PRNGKey(0))
    prompts = [
        np.asarray(jax.random.randint(jax.random.PRNGKey(i + 30), (L,), 0,
                                      config.vocab_size))
        for i, L in enumerate([4, 9])
    ]
    want = [reference_tokens(params, config, p, 5) for p in prompts]
    b = ContinuousBatcher(
        params, config, max_batch=2, n_pages=16, page_size=4,
        max_pages_per_seq=4,
    )
    reqs = [b.submit(p, 5) for p in prompts]
    b.run_to_completion()
    assert [b.result(r) for r in reqs] == want


def moe_dropless_cfg():
    return dataclasses.replace(
        T.TransformerConfig.tiny_moe(), moe_dropless=True,
        moe_group_size=1, dtype=jnp.float32
    )


def test_moe_dropless_serving_matches_solo_decode():
    """With dropless routing no token can be evicted, so routing is per-
    token independent and the batcher's solo-equality bar — previously
    dense-only — extends to MoE: each request's output equals its own solo
    greedy decode, whatever shares the batch."""
    config = moe_dropless_cfg()
    params = T.init_params(config, jax.random.PRNGKey(0))
    prompts = [
        np.asarray(jax.random.randint(jax.random.PRNGKey(i + 1), (L,), 0,
                                      config.vocab_size))
        for i, L in enumerate([3, 7, 5])
    ]
    want = [reference_tokens(params, config, p, 5) for p in prompts]
    b = ContinuousBatcher(
        params, config, max_batch=2, n_pages=32, page_size=4,
        max_pages_per_seq=8,
    )
    r0 = b.submit(prompts[0], 5)
    b.step()  # staggered admission: r1 joins mid-decode of r0
    r1 = b.submit(prompts[1], 5)
    b.run_to_completion()
    r2 = b.submit(prompts[2], 5)
    b.run_to_completion()
    assert b.result(r0) == want[0]
    assert b.result(r1) == want[1]
    assert b.result(r2) == want[2]


def test_moe_dropless_prefix_cache_accepted_and_exact():
    """The prefix-cache guard lifts for dropless configs: shared-prefix
    admissions reuse pages AND still reproduce solo decode exactly."""
    config = moe_dropless_cfg()
    params = T.init_params(config, jax.random.PRNGKey(0))
    shared = np.asarray(jax.random.randint(jax.random.PRNGKey(9), (8,), 0,
                                           config.vocab_size))
    p1 = np.concatenate([shared, [1, 2]])
    p2 = np.concatenate([shared, [3]])
    want1 = reference_tokens(params, config, p1, 4)
    want2 = reference_tokens(params, config, p2, 4)
    b = ContinuousBatcher(
        params, config, max_batch=2, n_pages=32, page_size=4,
        max_pages_per_seq=8, prefix_cache=True,
    )
    r1 = b.submit(p1, 4)
    b.run_to_completion()
    r2 = b.submit(p2, 4)  # shares the prefix pages of r1
    b.run_to_completion()
    assert b.prefix_stats["hits"] >= 1
    assert b.result(r1) == want1
    assert b.result(r2) == want2


def test_snapshot_resume_matches_uninterrupted_run():
    """Preemption recovery: snapshot mid-decode, restore into a FRESH
    batcher (fresh jits, fresh pools), finish there — tokens, logprobs,
    finish reasons, and page accounting must equal the uninterrupted run,
    including a request admitted only after the restore."""
    config = cfg()
    params = T.init_params(config, jax.random.PRNGKey(0))
    prompts = [[5, 3, 7, 2, 9, 4, 1, 8], [1, 2, 3], [4, 4, 2, 6]]

    def make():
        return ContinuousBatcher(
            params, config, max_batch=2, n_pages=24, page_size=4,
            max_pages_per_seq=6,
        )

    # uninterrupted reference
    ref = make()
    r0 = ref.submit(prompts[0], 6, sampling=SamplingParams(
        temperature=0.8, top_k=40, seed=7, logprobs=True))
    r1 = ref.submit(prompts[1], 6)
    for _ in range(3):
        ref.step()
    ref.run_to_completion()
    r2 = ref.submit(prompts[2], 5)
    ref.run_to_completion()

    # interrupted run: 3 steps, snapshot, resume elsewhere
    a = make()
    a0 = a.submit(prompts[0], 6, sampling=SamplingParams(
        temperature=0.8, top_k=40, seed=7, logprobs=True))
    a1 = a.submit(prompts[1], 6)
    for _ in range(3):
        a.step()
    snap = a.state_dict()
    del a  # the preempted host is gone

    b = make()
    b.load_state_dict(snap)
    b.run_to_completion()
    b2 = b.submit(prompts[2], 5)  # post-restore admission reuses pages
    b.run_to_completion()

    assert b.result(a0) == ref.result(r0)
    assert b.result_logprobs(a0) == ref.result_logprobs(r0)
    assert b.result(a1) == ref.result(r1)
    assert b.result(b2) == ref.result(r2)
    assert b.finish_reason(a0) == ref.finish_reason(r0)
    assert sorted(b.free_pages) == sorted(ref.free_pages)


def test_snapshot_survives_pickle_and_geometry_is_checked():
    import pickle

    config = cfg()
    params = T.init_params(config, jax.random.PRNGKey(0))
    b1 = ContinuousBatcher(
        params, config, max_batch=2, n_pages=24, page_size=4,
        max_pages_per_seq=6,
    )
    r = b1.submit([5, 3, 7, 2], 4, sampling=SamplingParams(seed=3))
    b1.step()
    blob = pickle.dumps(b1.state_dict())  # disk-persistable
    want = None
    b1.run_to_completion()
    want = b1.result(r)

    b2 = ContinuousBatcher(
        params, config, max_batch=2, n_pages=24, page_size=4,
        max_pages_per_seq=6,
    )
    b2.load_state_dict(pickle.loads(blob))
    b2.run_to_completion()
    assert b2.result(r) == want

    wrong = ContinuousBatcher(
        params, config, max_batch=2, n_pages=32, page_size=4,
        max_pages_per_seq=6,
    )
    with pytest.raises(ValueError, match="geometry mismatch"):
        wrong.load_state_dict(pickle.loads(blob))


def test_snapshot_while_serving_continues_is_stable():
    """Periodic-checkpoint pattern: the snapshot must own its memory — the
    decode jits donate the pool buffer, so further step()s after
    state_dict() must not corrupt an earlier snapshot."""
    config = cfg()
    params = T.init_params(config, jax.random.PRNGKey(0))
    kw = dict(max_batch=2, n_pages=24, page_size=4, max_pages_per_seq=6)
    a = ContinuousBatcher(params, config, **kw)
    r = a.submit([5, 3, 7, 2, 9], 6)
    for _ in range(2):
        a.step()
    snap = a.state_dict()
    frozen = {k: v.copy() for k, v in snap["device"]["cache"].items()}
    a.run_to_completion()  # keeps serving; donates the pool repeatedly
    want = a.result(r)
    for k in frozen:
        np.testing.assert_array_equal(frozen[k], snap["device"]["cache"][k])
    b = ContinuousBatcher(params, config, **kw)
    b.load_state_dict(snap)
    b.run_to_completion()
    assert b.result(r) == want


def test_snapshot_geometry_checks_behavioral_fields():
    config = cfg()
    params = T.init_params(config, jax.random.PRNGKey(0))
    kw = dict(max_batch=2, n_pages=24, page_size=4, max_pages_per_seq=6)
    snap = ContinuousBatcher(params, config, eos_id=2, **kw).state_dict()
    other = ContinuousBatcher(params, config, eos_id=None, **kw)
    with pytest.raises(ValueError, match="eos_id"):
        other.load_state_dict(snap)


# ----------------------------------- the device pick (``pick_tokens``)

PICK_V = 64
# (top_k, top_p) as a request states them
PICK_FILTERS = {
    "neither": (None, None),
    "top_k": (7, None),
    "top_p": (None, 0.8),
    "both": (12, 0.8),
}
PICK_TEMPERATURES = (0.5, 0.8, 1.0, 1.3)


def _scatter(values):
    """``values`` (descending) dealt onto token ids in a fixed shuffled
    order, so that equal values are told apart by id, not by position."""
    row = np.empty(PICK_V, dtype=np.float32)
    row[np.random.default_rng(5).permutation(PICK_V)] = values
    return row


def _pick_rows(kind):
    rng = np.random.default_rng(11)
    if kind == "seeded":
        return (rng.normal(size=(4, PICK_V)) * 2.0).astype(np.float32)
    if kind == "ties_at_kth":
        # ranks 5..9 and 10..14 hold one value each: the 7th and the 12th
        # largest both sit inside a run of equals, which top-k keeps whole
        rows = []
        for _ in range(4):
            values = np.sort(rng.normal(size=PICK_V) * 2.0)[::-1].copy()
            values[5:10] = values[5]
            values[10:15] = values[10]
            rows.append(_scatter(values))
        return np.stack(rows)
    # ties_at_nucleus: one token of 0.5, six of 0.07, the rest share 0.08.
    # Mass before the six: .50 .57 .64 .71 .78 .85, so top_p 0.8 cuts
    # INSIDE the run of equals (after top-k 12 too: .54 .61 .69 .76 .84),
    # and which of them stay is decided by token id alone
    probs = np.r_[0.5, [0.07] * 6, np.full(PICK_V - 7, 0.08 / (PICK_V - 7))]
    return np.stack([_scatter(np.log(probs))] * 4) * np.asarray(
        PICK_TEMPERATURES, dtype=np.float32
    )[:, None]  # the temperature gives the probabilities back


def _pick_arrays(samplings, draws):
    """``pick_tokens``' arguments after the logits, every row sampling."""
    from bee_code_interpreter_tpu.models.serving import pick_settings

    settings = pick_settings(samplings, range(len(samplings)), PICK_V)
    return *settings, np.asarray(draws, dtype=np.float32)


@pytest.mark.parametrize("rows", ["seeded", "ties_at_kth", "ties_at_nucleus"])
@pytest.mark.parametrize("filters", PICK_FILTERS)
def test_device_pick_keeps_the_hosts_tokens(filters, rows):
    # The device's filters keep EXACTLY the tokens the host's keep, ties at
    # the k-th value and inside the nucleus's edge included, with every
    # row's own settings in one program; and whatever the draw, the picked
    # token is a kept one, every kept one reachable.
    from bee_code_interpreter_tpu.models.serving import (
        filtered_probs_host,
        kept_tokens,
        pick_tokens,
    )

    top_k, top_p = PICK_FILTERS[filters]
    logits = _pick_rows(rows)
    samplings = [
        SamplingParams(temperature=t, top_k=top_k, top_p=top_p)
        for t in PICK_TEMPERATURES
    ]
    host = np.stack([
        filtered_probs_host(row, sp) for row, sp in zip(logits, samplings)
    ])
    temperature, k, p, _ = _pick_arrays(samplings, [1.0] * 4)
    keep, prob = jax.jit(kept_tokens)(logits / temperature[:, None], k, p)
    keep, prob = np.asarray(keep), np.asarray(prob)
    assert (keep == (host > 0)).all(), (keep.sum(1), (host > 0).sum(1))
    if rows != "seeded":
        # the planted ties really straddle an edge: some row keeps a part
        # of a run of equal values, or more than k because of one
        assert filters == "neither" or any(
            len(np.unique(row[kept])) < kept.sum()
            for row, kept in zip(logits, keep)
        )
    kept_prob = np.where(keep, prob, 0.0)
    np.testing.assert_allclose(
        kept_prob / kept_prob.sum(1, keepdims=True), host, atol=1e-6
    )
    pick = jax.jit(pick_tokens)
    seen = np.zeros_like(keep)
    for draw in np.linspace(1.0, 1e-6, 400):
        _, _, _, draws = _pick_arrays(samplings, [draw] * 4)
        tokens = np.asarray(pick(logits[:, None, :], temperature, k, p, draws))
        seen[np.arange(4), tokens] = True
    assert not (seen & ~keep).any()
    assert (seen | (host < 1.0 / 300)).all()


def test_device_pick_draws_from_the_hosts_distribution():
    # chi-square on a small vocabulary: tokens picked on the device from
    # uniform draws follow filtered_probs_host's probabilities
    from bee_code_interpreter_tpu.models.serving import (
        filtered_probs_host,
        pick_tokens,
    )

    rng = np.random.default_rng(3)
    sp = SamplingParams(temperature=1.1, top_k=24, top_p=0.9)
    row = rng.normal(size=PICK_V).astype(np.float32)
    want = filtered_probs_host(row, sp)
    n_rows, n_calls = 250, 80
    pick = jax.jit(pick_tokens)
    logits = np.repeat(row[None, None, :], n_rows, axis=0)
    counts = np.zeros(PICK_V)
    for _ in range(n_calls):
        draws = 1.0 - rng.random(n_rows)
        tokens = pick(logits, *_pick_arrays([sp] * n_rows, draws))
        counts += np.bincount(np.asarray(tokens), minlength=PICK_V)
    n = n_rows * n_calls
    support = want > 0
    assert counts[~support].sum() == 0
    assert 10 <= support.sum() <= 24
    expected = n * want[support]
    chi2 = ((counts[support] - expected) ** 2 / expected).sum()
    dof = support.sum() - 1
    # mean dof, standard deviation sqrt(2 dof): five of them is p < 1e-5
    assert chi2 < dof + 5.0 * np.sqrt(2.0 * dof), (chi2, dof)


def _mixed_batcher(params, config, **kw):
    return ContinuousBatcher(
        params, config, max_batch=4, n_pages=48, page_size=4,
        max_pages_per_seq=6, **kw,
    )


MATES = [
    SamplingParams(temperature=1.2, top_p=0.7, seed=1),
    SamplingParams(),
    SamplingParams(temperature=0.6, top_k=5, seed=2, logprobs=True),
]


@pytest.mark.parametrize("place", ["alone", "first_row", "last_row"])
def test_sampled_tokens_do_not_depend_on_row_or_batch_mates(place):
    # a request's draws come from its own generator, one a token: the same
    # tokens alone, in a full batch, and in another row of one
    config = cfg()
    params = T.init_params(config, jax.random.PRNGKey(0))
    prompt = np.asarray([9, 2, 6, 5])
    hot = SamplingParams(temperature=1.0, top_k=30, top_p=0.95, seed=123)

    def run(where):
        b = _mixed_batcher(params, config)
        mates = [] if where == "alone" else MATES
        order = [hot] + mates if where != "last_row" else mates + [hot]
        reqs = {
            id(sp): b.submit(
                prompt if sp is hot else [3, 1, 4, 1, 5], 8, sampling=sp
            )
            for sp in order
        }
        assert b.row_request[0 if where != "last_row" else 3] == reqs[id(hot)]
        b.run_to_completion()
        return b.result(reqs[id(hot)])

    want = run("alone")
    assert len(want) == 8 and len(set(want)) > 2  # it did sample
    assert run(place) == want


def test_steered_and_logprob_rows_beside_device_picked_rows():
    # in one batch with device-picked rows: a steered row still gets
    # choose_host's token (the host's stream: what it gets alone), and a
    # logprobs row logprob_of's report of the token the device picked
    from bee_code_interpreter_tpu.models import serving

    config = cfg()
    params = T.init_params(config, jax.random.PRNGKey(0))
    prompt = [3, 1, 4, 1, 5]
    steered = SamplingParams(
        temperature=0.9, seed=8, logit_bias={7: 4.0},
        allowed_tokens=lambda out: range(0, config.vocab_size, 2),
    )
    reported = SamplingParams(temperature=0.9, top_p=0.9, seed=9, logprobs=True)

    alone = _mixed_batcher(params, config)
    r = alone.submit(prompt, 6, sampling=steered)
    alone.run_to_completion()
    want_steered = alone.result(r)
    assert all(t % 2 == 0 for t in want_steered)

    chosen = []
    real = serving.choose_host

    def spy(logits, sp, rng, generated):
        chosen.append(sp)
        return real(logits, sp, rng, generated)

    b = _mixed_batcher(params, config)
    reqs = [
        b.submit(prompt, 6, sampling=sp)
        for sp in (MATES[0], steered, reported, MATES[1])
    ]
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(serving, "choose_host", spy)
        b.run_to_completion()
    # only the steered row chose on the host, once a step
    assert chosen == [steered] * 5
    assert b.result(reqs[1]) == want_steered
    # the model's own log-probability of each token the device picked: a
    # plain forward over prompt + output, float32 end to end
    out = b.result(reqs[2])
    logits = T.forward(params, jnp.asarray(prompt + out)[None, :], config)[0]
    logp = np.asarray(jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1))
    want = [logp[len(prompt) - 1 + j, t] for j, t in enumerate(out)]
    np.testing.assert_allclose(b.result_logprobs(reqs[2]), want, atol=2e-4)
    assert len(set(out)) > 1


def test_pick_program_compiles_once_over_a_mixed_run():
    # greedy, sampled, top-k, top-p, steered and logprobs requests coming
    # and going: every setting is an array, so TrackedJit sees ONE compile
    config = cfg()
    params = T.init_params(config, jax.random.PRNGKey(0))

    class Compiles:
        names: list = []

        def on_compile(self, name, **_):
            self.names.append(name)

        def record_step(self, *_, **__):
            pass

    b = _mixed_batcher(params, config)
    b.set_device_monitor(Compiles())
    kinds = MATES + [
        SamplingParams(temperature=0.7, seed=4),
        SamplingParams(temperature=1.5, top_k=3, top_p=0.5, seed=5),
        SamplingParams(temperature=0.9, seed=6, logit_bias={2: 1.0}),
    ]
    pending = [(kind, 2 + i % 4) for i, kind in enumerate(kinds * 2)]
    live = []
    while pending or live:
        while pending and b.has_free_row():
            kind, n = pending.pop()
            live.append(b.submit([5, 3, 7, 2], n, sampling=kind))
        b.step()
        live = [r for r in live if not b.is_done(r)]
    assert Compiles.names.count("pick_tokens") == 1
    assert Compiles.names.count("decode_step_paged") == 1
    assert b._pick._cache_size() == 1
