"""Seeded scheduling fuzz over the serving engine.

The unit suites pin each feature in isolation; this drives a RANDOM
interleaving of submits (mixed lengths, budgets, priorities, sampling),
steps, cancels and releases against one engine, then checks the global
contract: every request that ran to completion equals its solo decode,
cancelled tickets report 'cancelled' and hold a prefix of theirs (or, where
the cancel raced the step in flight, all of it), and the page pool balances
to empty.
Seeded, so a failure is a repro, not a flake."""

import dataclasses

import numpy as np

import jax
import jax.numpy as jnp

from bee_code_interpreter_tpu.models import transformer as T
from bee_code_interpreter_tpu.models.engine import Engine
from bee_code_interpreter_tpu.models.serving import (
    ContinuousBatcher,
    SamplingParams,
)

CFG = dataclasses.replace(
    T.TransformerConfig.tiny(), dtype=jnp.float32, n_kv_heads=2
)
PARAMS = T.init_params(CFG, jax.random.PRNGKey(0))


def solo(prompt, n, sampling=None):
    b = ContinuousBatcher(
        PARAMS, CFG, max_batch=1, n_pages=16, page_size=4,
        max_pages_per_seq=4,
    )
    r = b.submit(prompt, n, sampling=sampling)
    b.run_to_completion()
    return b.result(r)


def test_random_schedule_matches_solo_oracle():
    rng = np.random.default_rng(20260731)
    eng = Engine(
        ContinuousBatcher(
            PARAMS, CFG, max_batch=2, n_pages=16, page_size=4,
            max_pages_per_seq=4,
        ),
        max_queue=6,
    )
    live: dict[int, tuple[list[int], int, SamplingParams | None]] = {}
    cancelled: dict[int, tuple[list[int], int, SamplingParams | None]] = {}
    finished: dict[int, tuple[list[int], int, SamplingParams | None]] = {}

    for op_i in range(120):
        op = rng.choice(["submit", "step", "cancel", "step", "step"])
        if op == "submit":
            prompt = [int(x) for x in rng.integers(0, 200, rng.integers(2, 8))]
            n = int(rng.integers(1, 6))
            sampling = None
            if rng.random() < 0.4:
                sampling = SamplingParams(
                    temperature=0.8, top_k=20, seed=int(rng.integers(1e6))
                )
            try:
                t = eng.submit(
                    prompt, n, sampling=sampling,
                    priority=int(rng.integers(0, 3)),
                )
            except RuntimeError:
                continue  # queue full: legal backpressure
            live[t] = (prompt, n, sampling)
        elif op == "cancel" and live and rng.random() < 0.5:
            t = int(rng.choice(list(live)))
            eng.cancel(t)
            cancelled[t] = live.pop(t)
        else:
            eng.step()
        for t in list(live):
            if eng.is_done(t):
                finished[t] = live.pop(t)
    eng.run_to_completion()
    finished.update(live)

    # every completed request equals its solo decode (sampling included:
    # per-row seeded generators are batch-independent)
    assert len(finished) >= 10, "fuzz schedule degenerated"
    for t, (prompt, n, sampling) in finished.items():
        assert eng.result(t) == solo(prompt, n, sampling), (t, prompt)
        assert eng.finish_reason(t) == "length"
    raced = 0
    for t, (prompt, n, sampling) in cancelled.items():
        # a cancel lands the step in flight first, and that step may have
        # been the request's last: the cancel then raced completion and is
        # a no-op, the request whole (ContinuousBatcher.cancel)
        if eng.finish_reason(t) == "length":
            raced += 1
            assert eng.result(t) == solo(prompt, n, sampling), (t, prompt)
        else:
            assert eng.finish_reason(t) == "cancelled"
            assert eng.result(t) == solo(prompt, n, sampling)[:len(eng.result(t))]
    assert raced < len(cancelled)
    # pool drains back to empty: no leaked pages, no stuck rows
    st = eng.stats
    assert st["active_rows"] == 0 and st["queued"] == 0
    assert st["held_pages"] == 0
