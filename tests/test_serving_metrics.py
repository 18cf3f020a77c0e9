"""Serving-engine instrumentation (ISSUE 2 acceptance): after a batched
decode, TTFT / inter-token / tokens-per-second / occupancy metrics appear in
the Prometheus exposition, and the engine's queue metrics track intake."""

import jax
import numpy as np

from bee_code_interpreter_tpu.models import transformer as T
from bee_code_interpreter_tpu.models.engine import Engine
from bee_code_interpreter_tpu.models.serving import ContinuousBatcher
from bee_code_interpreter_tpu.utils.metrics import Registry


def make_batcher(registry, **kw):
    config = T.TransformerConfig.tiny()
    params = T.init_params(config, jax.random.PRNGKey(0))
    defaults = dict(
        max_batch=2, n_pages=16, page_size=4, max_pages_per_seq=4,
        metrics=registry,
    )
    defaults.update(kw)
    return ContinuousBatcher(params, config, **defaults)


def test_batched_decode_exports_ttft_and_throughput():
    registry = Registry()
    b = make_batcher(registry)
    prompts = [
        np.asarray(
            jax.random.randint(jax.random.PRNGKey(i + 1), (L,), 0,
                               b.config.vocab_size)
        )
        for i, L in enumerate([3, 5])
    ]
    r0 = b.submit(prompts[0], 6)
    r1 = b.submit(prompts[1], 6)
    b.run_to_completion()
    assert b.is_done(r0) and b.is_done(r1)

    text = registry.expose()
    # one TTFT observation per request
    assert "bci_serving_ttft_seconds_count 2" in text
    # 2 requests x 6 tokens
    assert "bci_serving_tokens_total 12" in text
    # steps ran and were timed; inter-token latency observed
    assert "bci_serving_step_seconds_count" in text
    assert "bci_serving_inter_token_seconds_count" in text
    # throughput gauge reads a real rate after a batched decode
    tps = float(
        next(
            line.split()[-1]
            for line in text.splitlines()
            if line.startswith("bci_serving_tokens_per_second ")
        )
    )
    assert tps > 0.0
    # batch drained: occupancy gauges read empty again
    assert "bci_serving_active_rows 0" in text
    assert "bci_serving_batch_occupancy 0" in text


def test_metrics_free_batcher_pays_nothing():
    # metrics=None keeps the hot loop untouched (no attributes, no observes)
    b = make_batcher(None)
    r = b.submit(np.asarray([1, 2, 3]), 4)
    b.run_to_completion()
    assert b.is_done(r)
    assert b._metrics is None


def test_engine_queue_metrics_track_intake_and_wait():
    registry = Registry()
    b = make_batcher(registry, max_batch=1, n_pages=8)
    engine = Engine(b, max_queue=2, metrics=registry)
    t0 = engine.submit(np.asarray([1, 2, 3]), 4)
    t1 = engine.submit(np.asarray([4, 5, 6]), 4)  # waits for the single row
    assert engine.pending == 2  # admission happens inside step()
    text = registry.expose()
    assert "bci_serving_queue_depth 2" in text
    engine.run_to_completion()
    assert engine.is_done(t0) and engine.is_done(t1)
    text = registry.expose()
    # both tickets eventually admitted; their queue wait was observed
    assert "bci_serving_queue_wait_seconds_count 2" in text
    assert "bci_serving_queue_depth 0" in text
    # the requeue/rejection counters exist for scrapers even when zero here
    assert "# TYPE bci_serving_requeues_total counter" in text
    assert "# TYPE bci_serving_queue_rejected_total counter" in text


def test_snapshot_restore_does_not_replay_metrics():
    # Counters are per-process: adopting a snapshot must not pour the
    # snapshot's lifetime token total into the fresh registry, and restored
    # in-flight state must not observe TTFT against a foreign clock.
    reg1 = Registry()
    b1 = make_batcher(reg1)
    b1.submit(np.asarray([1, 2, 3]), 6)
    b1.step()
    b1.step()
    # ...and one admission caught mid-prefill, whose TTFT anchor is a
    # reading of the snapshotting process's clock
    b1.submit(np.arange(1, 10), 2, interleave_admission=4)
    snap = b1.state_dict()

    reg2 = Registry()
    b2 = make_batcher(reg2)
    b2.load_state_dict(snap)
    assert b2.prefill_state
    assert not any("t_submit" in rec for rec in b2.prefill_state.values())
    import re

    assert not re.search(
        r"^bci_serving_tokens_total \d", reg2.expose(), re.M
    ), "restored lifetime total replayed into the fresh registry"
    b2.run_to_completion()
    generated_before = snap["host"]["n_tokens_generated"]
    expected = b2.n_tokens_generated - generated_before
    assert f"bci_serving_tokens_total {expected}" in reg2.expose()
    assert not re.search(r"^bci_serving_ttft_seconds_count [1-9]", reg2.expose(), re.M)


def test_tokens_per_second_decays_to_zero_when_idle():
    registry = Registry()
    b = make_batcher(registry)
    b.submit(np.asarray([1, 2, 3]), 6)
    b.run_to_completion()
    assert b._tokens_per_second() > 0.0
    # age the window out: an idle server must not report its last burst
    b._rate_samples = type(b._rate_samples)(
        ((t - 1000.0, n) for t, n in b._rate_samples),
        maxlen=b._rate_samples.maxlen,
    )
    assert b._tokens_per_second() == 0.0


def test_engine_counts_queue_rejections():
    registry = Registry()
    b = make_batcher(registry, max_batch=1, n_pages=8)
    engine = Engine(b, max_queue=0, metrics=registry)
    import pytest

    with pytest.raises(RuntimeError, match="queue full"):
        engine.submit(np.asarray([1, 2, 3]), 4)
    assert "bci_serving_queue_rejected_total 1" in registry.expose()
