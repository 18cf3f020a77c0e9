"""Tensor-parallel serving: the continuous batcher over a tp mesh.

``ContinuousBatcher(mesh=...)`` shards params under the Megatron specs and
the K/V page pool's head axis over ``tp``; GSPMD compiles the same decode/
prefill/window programs with the tp collectives inserted. The host
scheduling loop is untouched, so every serving feature rides along — these
tests pin the ones with distinct device-side layouts (bf16/f32 pool, int8
pool + scale planes, speculative draft+verify, prefix-cache suffix
admission) against the UNSHARDED solo decode, token-for-token, on the
virtual device mesh (tests/conftest.py)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from bee_code_interpreter_tpu.models import transformer as T
from bee_code_interpreter_tpu.models.serving import ContinuousBatcher

PROMPT = [5, 3, 7, 2, 9, 4, 1, 8]


def cfg(**kw):
    return dataclasses.replace(
        T.TransformerConfig.tiny(), dtype=jnp.float32, n_kv_heads=2, **kw
    )


def tp_mesh(n=2):
    return Mesh(np.array(jax.devices()[:n]), ("tp",))


def solo(params, config, prompt, n):
    out = T.Transformer(config).generate_cached(
        params, jnp.asarray(prompt)[None, :], max_new_tokens=n
    )
    return np.asarray(out[0, len(prompt):]).tolist()


def test_tp_batcher_matches_unsharded_solo_decode():
    config = cfg()
    params = T.init_params(config, jax.random.PRNGKey(0))
    want1 = solo(params, config, PROMPT, 6)
    want2 = solo(params, config, [1, 2, 3], 6)
    b = ContinuousBatcher(
        params, config, max_batch=2, n_pages=16, page_size=4,
        max_pages_per_seq=4, mesh=tp_mesh(),
    )
    r1 = b.submit(PROMPT, 6)
    r2 = b.submit([1, 2, 3], 6)
    b.run_to_completion()
    assert b.result(r1) == want1
    assert b.result(r2) == want2
    # params and pool really are distributed (not replicated onto one chip)
    wq = b.params["layers"]["wq"]
    assert len(wq.sharding.device_set) == 2
    assert len(b.cache["k"].sharding.device_set) == 2


def test_tp_sampled_rows_match_the_unsharded_batcher():
    # the pick program takes the decode step's logits as the mesh leaves
    # them (sharded over the vocabulary) and draws the tokens the unsharded
    # batcher draws: same seed, same settings, same stream
    from bee_code_interpreter_tpu.models.serving import SamplingParams

    config = cfg()
    params = T.init_params(config, jax.random.PRNGKey(0))
    kinds = [
        SamplingParams(temperature=0.9, top_p=0.9, seed=3),
        SamplingParams(temperature=1.2, top_k=20, seed=4),
    ]

    def run(**kw):
        b = ContinuousBatcher(
            params, config, max_batch=2, n_pages=16, page_size=4,
            max_pages_per_seq=4, **kw,
        )
        reqs = [b.submit(PROMPT, 6, sampling=sp) for sp in kinds]
        b.run_to_completion()
        assert b._device_picked == 2 * 5  # the first token is the admission's
        return [b.result(r) for r in reqs]

    want = run()
    assert all(len(set(out)) > 2 for out in want)  # they did sample
    assert run(mesh=tp_mesh()) == want


@pytest.mark.parametrize("speculative", [False, True])
def test_tp_logprobs_match_the_unsharded_batcher(speculative):
    # a row that records log-probabilities takes its normaliser from the
    # device (log_normalizers) at admission, in the plain step and in a
    # speculative round: under a mesh the logits reach that program sharded
    # over the vocabulary, and it reports what a float64 log-softmax of the
    # unsharded model's logits gives
    from bee_code_interpreter_tpu.models.serving import SamplingParams

    config = cfg()
    params = T.init_params(config, jax.random.PRNGKey(0))
    extra = {}
    if speculative:
        draft_config = cfg(n_layers=1)
        extra = dict(
            draft_params=T.init_params(draft_config, jax.random.PRNGKey(1)),
            draft_config=draft_config, gamma=3,
        )
    kinds = [
        SamplingParams(logprobs=True),
        SamplingParams(temperature=0.9, seed=3, logprobs=True),
    ]

    def run(**kw):
        b = ContinuousBatcher(
            params, config, max_batch=2, n_pages=32, page_size=4,
            max_pages_per_seq=6, **extra, **kw,
        )
        reqs = [b.submit(PROMPT, 6, sampling=sp) for sp in kinds]
        b.run_to_completion()
        return [(b.result(r), b.result_logprobs(r)) for r in reqs]

    want, got = run(), run(mesh=tp_mesh())
    for (tokens, logprobs), (m_tokens, m_logprobs), prompt in zip(
        want, got, [PROMPT, PROMPT]
    ):
        assert m_tokens == tokens
        np.testing.assert_allclose(m_logprobs, logprobs, atol=2e-5)
        logits = np.asarray(T.forward(
            params, jnp.asarray(prompt + tokens[:-1])[None, :], config=config
        )[0, len(prompt) - 1:], np.float64)
        exact = logits - np.log(np.exp(logits).sum(-1, keepdims=True))
        np.testing.assert_allclose(
            logprobs, exact[np.arange(len(tokens)), tokens], atol=2e-5
        )


def test_tp_int8_pool_matches_unsharded_solo():
    config = cfg(kv_cache_dtype="int8")
    params = T.init_params(config, jax.random.PRNGKey(0))
    want = solo(params, config, PROMPT, 5)
    b = ContinuousBatcher(
        params, config, max_batch=2, n_pages=16, page_size=4,
        max_pages_per_seq=4, mesh=tp_mesh(),
    )
    r = b.submit(PROMPT, 5)
    b.run_to_completion()
    assert b.result(r) == want
    assert len(b.cache["k_s"].sharding.device_set) == 2  # scale planes too


def test_tp_speculative_matches_unsharded_solo():
    config = cfg()
    params = T.init_params(config, jax.random.PRNGKey(0))
    draft_config = cfg(n_layers=1)
    draft_params = T.init_params(draft_config, jax.random.PRNGKey(1))
    want = solo(params, config, PROMPT, 6)
    b = ContinuousBatcher(
        params, config, max_batch=2, n_pages=32, page_size=4,
        max_pages_per_seq=6, mesh=tp_mesh(),
        draft_params=draft_params, draft_config=draft_config, gamma=3,
    )
    r = b.submit(PROMPT, 6)
    b.run_to_completion()
    assert b.result(r) == want


def test_tp_prefix_cache_matches_unsharded_solo():
    config = cfg()
    params = T.init_params(config, jax.random.PRNGKey(0))
    p1 = PROMPT + [1, 2]
    p2 = PROMPT + [3]
    want1 = solo(params, config, p1, 4)
    want2 = solo(params, config, p2, 4)
    b = ContinuousBatcher(
        params, config, max_batch=2, n_pages=32, page_size=4,
        max_pages_per_seq=8, mesh=tp_mesh(), prefix_cache=True,
    )
    r1 = b.submit(p1, 4)
    b.run_to_completion()
    r2 = b.submit(p2, 4)  # admits through the suffix window on shared pages
    b.run_to_completion()
    assert b.prefix_stats["hits"] >= 1
    assert b.result(r1) == want1
    assert b.result(r2) == want2


def test_tp_requires_divisible_kv_heads():
    config = dataclasses.replace(
        T.TransformerConfig.tiny(), dtype=jnp.float32, n_kv_heads=1
    )  # 1 % 2 != 0
    params = T.init_params(config, jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="kv_heads"):
        ContinuousBatcher(
            params, config, max_batch=2, n_pages=16, page_size=4,
            max_pages_per_seq=4, mesh=tp_mesh(),
        )


def test_snapshot_restores_across_topologies():
    """Preemption recovery composes with resharding: a snapshot taken on a
    single-device batcher resumes on a tp=2 batcher (the pool is resharded
    on load) — the serving analogue of utils/checkpoint.py's
    cross-topology restore."""
    config = cfg()
    params = T.init_params(config, jax.random.PRNGKey(0))
    want = solo(params, config, PROMPT, 6)

    a = ContinuousBatcher(
        params, config, max_batch=2, n_pages=16, page_size=4,
        max_pages_per_seq=4,
    )
    r = a.submit(PROMPT, 6)
    for _ in range(2):
        a.step()
    snap = a.state_dict()

    b = ContinuousBatcher(
        params, config, max_batch=2, n_pages=16, page_size=4,
        max_pages_per_seq=4, mesh=tp_mesh(),
    )
    b.load_state_dict(snap)
    b.run_to_completion()
    assert b.result(r) == want
    assert len(b.cache["k"].sharding.device_set) == 2  # resharded on load


def test_sp_ring_admission_matches_unsharded_solo():
    """Long-context admission: with an sp axis in the mesh, the one-shot
    prefill rings the attention across devices (forward's sequence
    parallelism) and the K/V reshards into the page pool — outputs must
    still equal unsharded solo decode."""
    config = cfg()
    params = T.init_params(config, jax.random.PRNGKey(0))
    long_prompt = [int(x) for x in
                   np.random.default_rng(0).integers(0, 200, 21)]
    want = solo(params, config, long_prompt, 5)
    mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("sp", "tp"))
    b = ContinuousBatcher(
        params, config, max_batch=2, n_pages=32, page_size=4,
        max_pages_per_seq=8, mesh=mesh,
    )
    r = b.submit(long_prompt, 5)
    b.run_to_completion()
    assert b.result(r) == want


def test_sp_requires_divisible_page_size():
    config = cfg()
    params = T.init_params(config, jax.random.PRNGKey(0))
    mesh = Mesh(np.array(jax.devices()[:2]).reshape(2, 1), ("sp", "tp"))
    with pytest.raises(ValueError, match="page_size"):
        ContinuousBatcher(
            params, config, max_batch=2, n_pages=16, page_size=3,
            max_pages_per_seq=4, mesh=mesh,
        )


def test_ulysses_sp_admission_validated_and_matches_solo():
    """sp admission under Ulysses: head divisibility refuses at
    construction (not at the first submit's trace), and a valid config
    still matches unsharded solo decode."""
    bad = cfg(sp_attention="ulysses")  # kv_heads=2, sp=4 below: refuses
    params_bad = T.init_params(bad, jax.random.PRNGKey(0))
    mesh4 = Mesh(np.array(jax.devices()[:4]).reshape(4, 1), ("sp", "tp"))
    with pytest.raises(ValueError, match="ulysses"):
        ContinuousBatcher(
            params_bad, bad, max_batch=2, n_pages=32, page_size=4,
            max_pages_per_seq=8, mesh=mesh4,
        )
    good = cfg(sp_attention="ulysses")  # sp=2 divides both head counts
    params = T.init_params(good, jax.random.PRNGKey(0))
    long_prompt = [int(x) for x in
                   np.random.default_rng(3).integers(0, 200, 13)]
    want = solo(params, good, long_prompt, 4)
    mesh2 = Mesh(np.array(jax.devices()[:2]).reshape(2, 1), ("sp", "tp"))
    b = ContinuousBatcher(
        params, good, max_batch=2, n_pages=32, page_size=4,
        max_pages_per_seq=8, mesh=mesh2,
    )
    r = b.submit(long_prompt, 4)
    b.run_to_completion()
    assert b.result(r) == want
