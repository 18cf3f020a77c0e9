"""Serving-engine deep observability (ISSUE 9): ServingMonitor lifecycle
units, the engine/batcher hook integration on a real tiny CPU model, the
`GET /v1/serving` + `/v1/serving/requests` HTTP endpoints and their gRPC
mirrors, the saturation-accounting twin of chaos scenario 12, and the
acceptance e2e — one serving request's wide event, its `/v1/traces` trace,
and its `bci_serving_ttft_seconds` exemplar all share one trace_id."""

import dataclasses
import functools
import gc
import json
import re
import time

import grpc.aio
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from aiohttp.test_utils import TestClient, TestServer

from bee_code_interpreter_tpu.api.grpc_server import (
    GrpcServer,
    observability_stubs,
)
from bee_code_interpreter_tpu.api.http_server import create_http_server
from bee_code_interpreter_tpu.models import transformer as T
from bee_code_interpreter_tpu.models.engine import Engine
from bee_code_interpreter_tpu.models.serving import (
    CapacityError,
    ContinuousBatcher,
    SamplingParams,
)
from bee_code_interpreter_tpu.observability import (
    FlightRecorder,
    ServingMonitor,
    ServingProfiler,
    TraceStore,
    Tracer,
)
from bee_code_interpreter_tpu.services.custom_tool_executor import (
    CustomToolExecutor,
)
from bee_code_interpreter_tpu.utils.metrics import Registry

CFG = dataclasses.replace(
    T.TransformerConfig.tiny(), dtype=jnp.float32, n_kv_heads=2
)
PARAMS = T.init_params(CFG, jax.random.PRNGKey(0))
LONG = [int(x) for x in np.random.default_rng(7).integers(0, 200, 21)]
SHORT = [5, 3, 7, 2]


def monitored_stack(
    *,
    max_batch=2,
    n_pages=32,
    max_queue=None,
    max_steps=512,
    max_requests=256,
    **batcher_kw,
):
    """Registry + tracer-shared store + recorder + monitor over a tiny
    engine/batcher — the production wiring in miniature (the geometry
    matches test_interleaved_admission so jit programs are shared)."""
    metrics = Registry()
    store = TraceStore()
    recorder = FlightRecorder(metrics=metrics)
    monitor = ServingMonitor(
        metrics=metrics,
        store=store,
        recorder=recorder,
        max_steps=max_steps,
        max_requests=max_requests,
    )
    batcher_kw.setdefault("page_size", 4)
    batcher_kw.setdefault("max_pages_per_seq", 8)
    batcher = ContinuousBatcher(
        PARAMS, CFG, max_batch=max_batch, n_pages=n_pages,
        metrics=metrics, **batcher_kw,
    )
    engine = Engine(batcher, max_queue=max_queue, metrics=metrics)
    monitor.attach(engine)
    return engine, monitor, metrics, store, recorder


def counter_value(metrics: Registry, needle: str) -> float:
    """One sample's value out of the classic exposition text."""
    for line in metrics.expose().splitlines():
        if line.startswith(needle + " ") or (
            line.startswith(needle + "{")
        ):
            return float(line.rsplit(" ", 1)[1])
    return 0.0


# --------------------------------------------------------------- unit level


def test_monitor_lifecycle_trace_event_and_metrics():
    """Hand-driven hook sequence: the trace lands in the shared store with
    the span tree (queued → prefill → decode), the wide event is
    kind="serving" with the SAME trace_id, and the counters/gauges see the
    request."""
    metrics = Registry()
    store = TraceStore()
    recorder = FlightRecorder(metrics=metrics)
    mon = ServingMonitor(metrics=metrics, store=store, recorder=recorder)

    mon.on_ticket_queued(1)
    time.sleep(0.02)  # a real queue wait TTFT must not hide
    mon.on_ticket_admitting(1)
    mon.on_submit(
        7, prompt_tokens=5, max_new_tokens=4, pages=2, prefix_pages=1,
        adapter=None, speculative=True, interleaved=False,
    )
    mon.on_first_token(7)
    mon.on_commit(7, accepted=2, rejected=1)
    mon.on_done(7, "length", tokens=4)

    traces = store.traces()
    assert len(traces) == 1
    trace = traces[0]
    spans = {s.name: s for s in trace.spans}
    assert {"serving.request", "queued", "prefill", "decode"} <= set(spans)
    assert all(s.duration_s is not None for s in trace.spans)
    # the queued span precedes the prefill it waited for, and the root's
    # clock starts at engine intake
    queued_span, prefill_span = spans["queued"], spans["prefill"]
    assert (
        queued_span.start_mono + queued_span.duration_s
        <= prefill_span.start_mono + 1e-9
    )
    assert trace.root.start_mono == pytest.approx(queued_span.start_mono)

    events = recorder.events(kind="serving")
    assert len(events) == 1
    event = events[0]
    assert event["trace_id"] == trace.trace_id
    assert event["outcome"] == "ok"
    assert event["serving"]["finish"] == "length"
    assert event["serving"]["output_tokens"] == 4
    assert event["serving"]["prefix_hit_pages"] == 1
    assert event["serving"]["spec_accepted"] == 2
    assert event["timings_ms"].keys() >= {"queued", "prefill", "decode"}

    rows = mon.requests()
    assert len(rows) == 1 and rows[0]["active"] is False
    # TTFT is user-perceived: it INCLUDES the queue wait (the blocking
    # admission path fixes TTFT inside submit, so this pins the backdate)
    assert rows[0]["queued_ms"] >= 20.0
    assert rows[0]["ttft_ms"] >= rows[0]["queued_ms"]
    assert rows[0]["trace_id"] == trace.trace_id
    assert mon.spec_accept_ratio() == pytest.approx(2 / 3)
    assert counter_value(metrics, 'bci_serving_requests_total{outcome="length"}') == 1
    snap = mon.snapshot()
    assert snap["totals"]["finished"] == 1
    assert snap["totals"]["spec_accepted"] == 2
    assert snap["attached"] is False  # no engine bound in this unit test


def test_monitor_reject_requeue_preempt_accounting():
    metrics = Registry()
    recorder = FlightRecorder(metrics=metrics)
    mon = ServingMonitor(metrics=metrics, recorder=recorder)

    mon.on_ticket_rejected("queue_full")
    mon.on_ticket_rejected("queue_full")
    mon.on_ticket_queued(3)
    mon.on_ticket_requeued(3)
    mon.on_submit(
        9, prompt_tokens=3, max_new_tokens=2, pages=1, prefix_pages=0,
        adapter=None, speculative=False, interleaved=True,
    )
    mon.on_preempt(9)

    snap = mon.snapshot()
    assert snap["totals"]["rejected"] == 2
    assert snap["totals"]["requeued"] == 1
    assert snap["totals"]["preempted"] == 1
    kinds = [
        (e["name"], e["outcome"]) for e in recorder.events(kind="serving")
    ]
    assert kinds.count(("serving.reject", "rejected")) == 2
    assert kinds.count(("serving.requeue", "requeued")) == 1
    assert ("serving.request", "preempted") in kinds
    assert counter_value(metrics, "bci_serving_preemptions_total") == 1
    # the preempted request is a finished record with its own outcome
    assert mon.requests(outcome="preempted")[0]["finish"] == "preempted"


def test_step_ring_bounded_and_seq_monotonic():
    mon = ServingMonitor(max_steps=4)
    for i in range(10):
        mon.on_step({"duration_ms": float(i)})
    snap = mon.snapshot()
    assert snap["steps"]["recorded"] == 10
    assert snap["steps"]["retained"] == 4
    seqs = [s["seq"] for s in snap["steps"]["last"]]
    assert seqs == [7, 8, 9, 10]
    assert all("ts" in s for s in snap["steps"]["last"])
    # the query bound trims from the retained tail
    assert len(mon.snapshot(steps=2)["steps"]["last"]) == 2
    assert mon.snapshot(steps=0)["steps"]["last"] == []


def test_request_record_ring_bounded_and_filters():
    mon = ServingMonitor(max_requests=3)
    for req in range(5):
        mon.on_submit(
            req, prompt_tokens=2, max_new_tokens=1, pages=1, prefix_pages=0,
            adapter=req % 2, speculative=False, interleaved=False,
        )
        mon.on_first_token(req)
        mon.on_done(req, "length" if req % 2 else "stop", tokens=1)
    rows = mon.requests()
    assert len(rows) == 3  # ring keeps the newest finished records
    assert [r["request_id"] for r in rows] == [4, 3, 2]
    assert [r["request_id"] for r in mon.requests(limit=1)] == [4]
    assert mon.requests(limit=0) == []  # FlightRecorder.events semantics
    assert all(r["adapter"] == 1 for r in mon.requests(adapter=1))
    assert all(r["finish"] == "length" for r in mon.requests(finish="length"))
    assert mon.requests(active=True) == []


# ------------------------------------------------- engine/batcher integration


def test_engine_run_records_requests_steps_and_kv_telemetry():
    engine, mon, metrics, store, recorder = monitored_stack()
    tickets = [engine.submit(SHORT, 4), engine.submit(LONG, 4)]
    engine.run_to_completion()
    for t in tickets:
        assert len(engine.result(t)) == 4
        engine.release(t)

    rows = mon.requests()
    assert len(rows) == 2
    for row in rows:
        assert row["active"] is False
        assert row["outcome"] == "ok" and row["finish"] == "length"
        assert row["output_tokens"] == 4
        assert row["ttft_ms"] is not None and row["ttft_ms"] > 0
        assert row["queued_ms"] is not None
        assert row["duration_ms"] >= row["ttft_ms"]
        assert store.get(row["trace_id"]) is not None

    # the wide events carry the same ids, and the store's span trees agree
    events = recorder.events(kind="serving")
    assert {e["trace_id"] for e in events} == {r["trace_id"] for r in rows}
    for event in events:
        trace = store.get(event["trace_id"])
        assert sum(event["timings_ms"].values()) == pytest.approx(
            sum(trace.stage_ms().values())
        )

    snap = mon.snapshot()
    assert snap["attached"] is True
    assert snap["totals"]["finished"] == 2
    assert snap["queue_depth"] == 0
    assert snap["batcher"]["active_rows"] == 0
    assert snap["steps"]["recorded"] > 0
    steps = snap["steps"]["last"]
    assert sum(s["decode_tokens"] for s in steps) > 0
    assert all(s["max_batch"] == 2 for s in steps)
    assert all(s["duration_ms"] > 0 for s in steps)

    kv = snap["kv_cache"]
    assert kv["pages_total"] == 31  # n_pages minus the scratch page
    # every page is free, parked (prefix-cache), or held — and with all
    # requests retired and released, none is held
    assert kv["pages_free"] + kv["pages_parked"] + kv["pages_held"] == 31
    assert kv["pages_held"] == 0
    assert 0.0 <= kv["fragmentation"] <= 1.0
    assert kv["pages_allocated_total"] >= kv["pages_released_total"]
    assert kv["prefix"]["lookups"] == kv["prefix"]["hits"] + kv["prefix"]["misses"]
    assert 0.0 <= kv["prefix"]["hit_ratio"] <= 1.0
    # integer-math churn agrees with the pool scan: allocated - released
    # is the held count
    assert (
        kv["pages_allocated_total"] - kv["pages_released_total"]
        == kv["pages_held"]
    )


def test_page_churn_counters_survive_prefix_reuse():
    """Regression: reviving a parked prefix page (ref 0 → 1) must count as
    an allocation, or every reuse cycle drifts the alloc/release counters
    negative against the pool scan (held_pages went to -2 after one
    cycle)."""
    engine, mon, *_ = monitored_stack(prefix_cache=True)
    batcher = engine.batcher
    for _ in range(2):  # second pass revives the first pass's parked pages
        ticket = engine.submit(LONG, 3)
        engine.run_to_completion()
        assert len(engine.result(ticket)) == 3
        engine.release(ticket)
    kv = batcher.kv_telemetry()
    assert kv["prefix"]["hits"] >= 1, "second pass must hit the prefix cache"
    assert kv["pages_held"] == 0
    assert (
        kv["pages_allocated_total"] - kv["pages_released_total"]
        == kv["pages_held"]
    )
    assert kv["pages_free"] + kv["pages_parked"] + kv["pages_held"] == (
        kv["pages_total"]
    )


def test_saturation_rejections_and_requeues_account_exactly():
    """Tier-1 twin of chaos scenario 12: drive the engine past queue
    capacity and through an admission capacity race; every bounce is
    accounted once in the monitor totals, the wide-event journal, and the
    bci_serving_* counters — no double counting, no losses."""
    engine, mon, metrics, store, recorder = monitored_stack(max_queue=2)

    # capacity race: queue-level admission believes pages are available
    # (over-reported prefix credit) but the batcher's own arithmetic says
    # no — the CapacityError requeues the ticket instead of failing it
    queued = [engine.submit(LONG, 3)]
    real_credit = engine.batcher.prefix_credit
    free_backup = engine.batcher.free_pages
    engine.batcher.prefix_credit = lambda prompt, adapter: 10_000
    engine.batcher.free_pages = []
    engine._admit_ready()
    engine.batcher.prefix_credit = real_credit
    engine.batcher.free_pages = free_backup

    queued.append(engine.submit(SHORT, 3))
    rejected = 0
    for _ in range(3):  # queue is full (2): every further submit bounces
        with pytest.raises(RuntimeError, match="queue full"):
            engine.submit(SHORT, 3)
        rejected += 1

    engine.run_to_completion()
    for t in queued:
        assert len(engine.result(t)) == 3

    snap = mon.snapshot()
    assert snap["totals"]["rejected"] == rejected == 3
    assert snap["totals"]["requeued"] == 1
    assert snap["totals"]["finished"] == 2
    events = recorder.events(kind="serving", limit=100)
    assert (
        len([e for e in events if e["name"] == "serving.reject"]) == rejected
    )
    assert len([e for e in events if e["name"] == "serving.requeue"]) == 1
    assert (
        len([e for e in events if e["name"] == "serving.request"]) == 2
    )
    assert counter_value(metrics, "bci_serving_queue_rejected_total") == 3
    assert counter_value(metrics, "bci_serving_requeues_total") == 1
    # a requeued ticket's record carries its bounce count
    requeued_rows = [r for r in mon.requests() if r["requeues"]]
    assert len(requeued_rows) == 1 and requeued_rows[0]["requeues"] == 1


def test_preempt_interleaved_prefill_requeues_and_stays_exact():
    # reference: the same prompt decoded with nothing else going on
    engine0, *_ = monitored_stack(max_batch=1)
    t0 = engine0.submit(LONG, 4)
    engine0.run_to_completion()
    want = engine0.result(t0)

    engine, mon, metrics, store, recorder = monitored_stack()
    decoding = engine.submit(SHORT, 8)
    ticket = engine.submit(LONG, 4, interleave_admission=4)
    engine.step()  # admits both; LONG starts its windowed prefill
    assert engine.partial_result(ticket) == []

    # a decoding ticket is NOT preemptable (cancel is the tool for those);
    # an unknown ticket is the caller's bug, same contract as cancel()
    assert engine.preempt(decoding) is False
    with pytest.raises(KeyError, match="unknown ticket"):
        engine.preempt(10_000)
    assert engine.preempt(ticket) is True
    assert engine.preempt(ticket) is False  # back in the queue now

    engine.run_to_completion()
    assert engine.result(ticket) == want  # recompute preemption is exact
    assert len(engine.result(decoding)) == 8

    assert counter_value(metrics, "bci_serving_preemptions_total") == 1
    preempted = mon.requests(outcome="preempted")
    assert len(preempted) == 1 and preempted[0]["output_tokens"] == 0
    # the re-admitted run finished ok as a NEW serving request record
    finished = mon.requests(outcome="ok")
    assert len(finished) == 2
    events = [
        e for e in recorder.events(kind="serving")
        if e["name"] == "serving.request"
    ]
    assert {e["outcome"] for e in events} == {"ok", "preempted"}


def test_speculative_commit_accounting():
    engine, mon, metrics, *_ = monitored_stack(
        draft_params=PARAMS, draft_config=CFG, gamma=2,
    )
    ticket = engine.submit(SHORT, 6)
    engine.run_to_completion()
    assert len(engine.result(ticket)) == 6

    row = mon.requests()[0]
    proposed = row["spec_accepted"] + row["spec_rejected"]
    assert proposed > 0
    assert row["speculative"] is True
    # a perfect draft (draft == target) accepts nearly everything
    assert mon.spec_accept_ratio() == pytest.approx(
        row["spec_accepted"] / proposed
    )
    accepted = counter_value(
        metrics, 'bci_serving_spec_tokens_total{result="accepted"}'
    )
    assert accepted == row["spec_accepted"]
    snap = mon.snapshot()
    assert snap["totals"]["spec_accepted"] == row["spec_accepted"]
    steps = snap["steps"]["last"]
    assert sum(s["spec_accepted"] for s in steps) == row["spec_accepted"]


# ------------------------------------------------ phases, spans, program names

@pytest.fixture
def no_full_collections():
    """A full collection of Python's garbage inside a monitored record adds
    the key ``gc`` to its ``phase_ms``: the tests that hold a record to an
    exact list of keys run with the collector off."""
    gc.collect()
    gc.disable()
    yield
    gc.enable()


GREEDY = SamplingParams()
SAMPLED = SamplingParams(temperature=0.8, top_p=0.95, seed=3, logprobs=True)
MIXES = {
    "greedy": [GREEDY, GREEDY],
    "greedy_logprobs": [SamplingParams(logprobs=True), GREEDY],
    "sampled": [SAMPLED, dataclasses.replace(SAMPLED, seed=4)],
    "mixed": [GREEDY, SAMPLED],
    # a steered row picks on the host from its pulled logits row
    "steered": [
        GREEDY, SamplingParams(temperature=0.8, seed=5, logit_bias={3: 2.0}),
    ],
}
TOP_PHASES = ("upload", "dispatch", "wait", "pull", "sample")


@functools.lru_cache(maxsize=None)
def decoded(mix: str, monitored: bool):
    """Two requests of the mix through a batcher, with or without a
    lifecycle monitor: (tokens by request, step records, batcher)."""
    if monitored:
        engine, mon, *_ = monitored_stack()
        batcher = engine.batcher
    else:
        mon = None
        batcher = ContinuousBatcher(
            PARAMS, CFG, max_batch=2, n_pages=32, page_size=4,
            max_pages_per_seq=8,
        )
    reqs = [
        batcher.submit(prompt, 5, sampling=sampling)
        for prompt, sampling in zip((SHORT, LONG), MIXES[mix])
    ]
    batcher.run_to_completion()
    tokens = [batcher.result(r) for r in reqs]
    steps = mon.snapshot(steps=512)["steps"]["last"] if monitored else None
    return tokens, steps, batcher


@pytest.mark.parametrize("mix", MIXES)
@pytest.mark.usefixtures("no_full_collections")
def test_phase_ms_splits_every_decode_step(mix):
    _, steps, batcher = decoded(mix, True)
    plain = [s for s in steps if s["phase_ms"]]
    decode = [s for s in plain if s["decode_tokens"]]
    assert decode and batcher._phase_ms is None
    picks_on_host = sum(sp.steered for sp in MIXES[mix])
    picks_on_device = sum(
        sp.temperature > 0 and not sp.steered for sp in MIXES[mix]
    )
    # two requests of 5 tokens, the first from the admission: four decode
    # steps. A steered row's step is landed in the call that dispatched it
    # (the host chooses its token); every other mix runs one step ahead:
    # the first call dispatches and lands nothing, the next three dispatch
    # a step and land the one before, the last lands the fourth
    if picks_on_host:
        assert [s["ahead"] for s in plain] == [False] * 4
        assert [s["decode_tokens"] for s in plain] == [2] * 4
    else:
        assert [s["ahead"] for s in plain] == [False, True, True, True, False]
        assert [s["decode_tokens"] for s in plain] == [0, 2, 2, 2, 2]
    assert all(s["discarded_tokens"] == 0 for s in steps)
    stats = batcher.stats
    assert stats["steps_ahead"] == sum(s["ahead"] for s in plain)
    assert stats["steps_ahead"] + stats["steps_synchronous"] == 4
    for record in plain:
        phases = record["phase_ms"]
        dispatched = {"upload", "dispatch"} if "dispatch" in phases else set()
        landed = (
            {"wait", "pull", "sample", "sample_choose", "sample_logprob"}
            if record["decode_tokens"] else set()
        )
        # in the order they began: a step goes out before one comes in
        assert list(phases) == [
            k for k in (*TOP_PHASES, "sample_choose", "sample_logprob")
            if k in dispatched | landed
        ]
        assert record["ahead"] == (
            bool(dispatched and landed) and not picks_on_host
        )
        assert all(ms >= 0.0 for ms in phases.values())
        # the phases lie inside the step, one after another
        assert sum(
            phases.get(k, 0.0) for k in TOP_PHASES
        ) <= record["duration_ms"]
        # where the tokens of the step that WENT OUT are picked
        assert record["host_picked_rows"] == picks_on_host * bool(dispatched)
        assert record["device_picked_rows"] == (
            picks_on_device * bool(dispatched)
        )
        if not landed:
            continue
        inside = phases["sample_choose"] + phases["sample_logprob"]
        assert inside <= phases["sample"]
        # a greedy row's token and an unsteered sampling row's come off the
        # device: nothing is chosen here
        assert (phases["sample_choose"] > 0.0) == (picks_on_host > 0)
        assert (phases["sample_logprob"] > 0.0) == any(
            sp.logprobs for sp in MIXES[mix]
        )


@pytest.mark.parametrize("mix", MIXES)
def test_unmonitored_batcher_keeps_no_phase_state_and_the_same_tokens(mix):
    tokens, _, batcher = decoded(mix, False)
    assert batcher._phase_ms is None and batcher._monitor is None
    assert all(len(t) == 5 for t in tokens)
    assert tokens == decoded(mix, True)[0]


# ------------------------------------------------------ the admission's record

# a hybrid (state by row) and window layers' rings beside pages, in small:
# tests/test_hybrid_serving.py's and tests/test_window_full_serving.py's
HYBRID = T.TransformerConfig(
    vocab_size=256, d_model=64, n_layers=3, n_heads=4, n_kv_heads=2, d_ff=128,
    max_seq_len=64, layer_types=("mamba", "attention", "mamba"),
    mamba_n_heads=8, mamba_d_head=16, mamba_d_state=16, mamba_chunk_size=32,
    position_embedding="nope", tie_embeddings=True, dtype=jnp.float32,
)
RINGS = T.TransformerConfig(
    vocab_size=256, d_model=48, n_layers=3, n_heads=4, n_kv_heads=2,
    head_dim=8, d_ff=96, max_seq_len=64, dtype=jnp.float32, sliding_window=8,
    layer_types=("sliding_attention", "full_attention", "sliding_attention"),
    position_embedding="rope_window",
)
ADMIT_TOP = (
    "prefill", "seed_window", "seed_pool", "seed_state", "land", "pull",
    "activate",
)
DRAIN = (
    "land_wait", "land_pull", "land_sample", "land_sample_choose",
    "land_sample_logprob",
)


def admissions_of(mon) -> list[dict]:
    return [
        a for s in mon.snapshot(steps=512)["steps"]["last"]
        for a in s.get("admissions", ())
    ]


def unspanned_ms(record: dict) -> float:
    return record["duration_ms"] - sum(
        record["phase_ms"].get(k, 0.0) for k in ADMIT_TOP
    )


@pytest.mark.parametrize("kind, config, seeds", [
    ("dense", CFG, ()),
    ("hybrid", HYBRID, ("seed_state",)),
    ("rings", RINGS, ("seed_window",)),
])
@pytest.mark.usefixtures("no_full_collections")
def test_an_admission_is_one_record_with_the_phases_that_ran(
    kind, config, seeds
):
    params = PARAMS if kind == "dense" else T.init_params(
        config, jax.random.PRNGKey(1)
    )
    mon = ServingMonitor()
    batcher = ContinuousBatcher(
        params, config, max_batch=2, n_pages=32, page_size=4,
        max_pages_per_seq=8,
    )
    mon.attach(batcher)
    first = batcher.submit(SHORT, 4)
    batcher.step()  # leaves a step in flight: the next admission lands it
    second = batcher.submit(LONG, 4, sampling=SAMPLED)
    assert batcher._phase_ms is None
    batcher.run_to_completion()
    a, b = admissions_of(mon)
    assert [a["req"], b["req"]] == [first, second]
    assert (a["row"], a["prompt_tokens"], a["padded_tokens"], a["pages"]) == (
        0, 4, 4, 2
    )
    assert (b["row"], b["prompt_tokens"], b["padded_tokens"], b["pages"]) == (
        1, 21, 24, 7
    )
    assert (a["decoding_rows"], a["landed_step"]) == (0, False)
    assert (b["decoding_rows"], b["landed_step"]) == (1, True)
    assert a["windows"] == b["windows"] == 0
    # a bare batcher has no queue: nothing staged a wait
    assert "queued_ms" not in a and "queued_ms" not in b
    # the spans that ran, in the order they began; the drain's phases under
    # their own names, so that its pull is not the admission's
    one_shot = [
        k for k in ADMIT_TOP
        if k in ("prefill", "seed_pool", "pull", "activate", *seeds)
    ]
    assert list(a["phase_ms"]) == one_shot
    at = one_shot.index("pull")
    assert list(b["phase_ms"]) == (
        one_shot[:at] + ["land", *DRAIN] + one_shot[at:]
    )
    assert b["phase_ms"]["pull"] > 0.0 and b["phase_ms"]["land_pull"] > 0.0
    for record in (a, b):
        phases = record["phase_ms"]
        assert all(ms >= 0.0 for ms in phases.values())
        # the top-level phases lie inside the admission, one after another
        assert 0.0 <= unspanned_ms(record) < 0.5 * record["duration_ms"]
    drain = b["phase_ms"]
    assert (
        drain["land_wait"] + drain["land_pull"] + drain["land_sample"]
        <= drain["land"]
    )
    assert drain["land_sample_logprob"] == 0.0  # the row it landed is greedy
    # the records ride on the step record after them, and on no other
    steps = mon.snapshot(steps=512)["steps"]["last"]
    assert [len(s.get("admissions", ())) for s in steps[:2]] == [1, 1]
    assert not any("admissions" in s for s in steps[2:])


@pytest.mark.usefixtures("no_full_collections")
def test_two_admissions_in_one_engine_step_and_what_the_request_keeps():
    engine, mon, _, store, recorder = monitored_stack()
    tickets = [engine.submit(SHORT, 3), engine.submit(LONG, 3)]
    engine.step()
    (step,) = mon.snapshot(steps=8)["steps"]["last"]
    a, b = step["admissions"]
    assert (a["decoding_rows"], b["decoding_rows"]) == (0, 1)
    assert not a["landed_step"] and not b["landed_step"]
    # the second waited out the first's admission in the queue
    assert 0.0 <= a["queued_ms"] < b["queued_ms"]
    assert b["queued_ms"] >= a["duration_ms"]
    engine.run_to_completion()
    for ticket in tickets:
        engine.release(ticket)
    rows = {r["request_id"]: r for r in mon.requests()}
    events = {
        int(e["request_id"].rpartition("-")[2]): e
        for e in recorder.events(kind="serving")
    }
    for record in (a, b):
        row = rows[record["req"]]
        assert row["admit_ms"] == record["duration_ms"]
        assert row["admit_phase_ms"] == record["phase_ms"]
        assert row["queued_ms"] == record["queued_ms"]
        assert row["ttft_ms"] >= row["queued_ms"] + row["admit_ms"] * 0.9
        serving = events[record["req"]]["serving"]
        assert serving["admit_ms"] == record["duration_ms"]
        assert serving["admit_phase_ms"] == record["phase_ms"]
        # the request's prefill span is split by the top-level phases
        trace = store.get(row["trace_id"])
        prefill = next(s for s in trace.spans if s.name == "prefill")
        parts = [s for s in trace.spans if s.parent_id == prefill.span_id]
        assert [s.name for s in parts] == [
            f"admit.{k}" for k in record["phase_ms"]
        ]
        assert [s.duration_ms for s in parts] == pytest.approx(
            list(record["phase_ms"].values())
        )
        assert parts[0].start_mono == prefill.start_mono
        for before, after in zip(parts, parts[1:]):
            assert after.start_mono == pytest.approx(
                before.start_mono + before.duration_s
            )


@pytest.mark.usefixtures("no_full_collections")
def test_an_admission_through_windows_has_them_in_its_unspanned_part():
    engine, mon, *_ = monitored_stack()
    ticket = engine.submit(LONG, 3, prefill_chunk=4)
    engine.run_to_completion()
    assert len(engine.result(ticket)) == 3
    (record,) = admissions_of(mon)
    # 21 tokens through windows of one page: six, the last one padded
    assert (record["windows"], record["padded_tokens"]) == (6, 24)
    assert list(record["phase_ms"]) == ["activate"]
    assert unspanned_ms(record) > record["phase_ms"]["activate"]
    assert unspanned_ms(record) > 0.5 * record["duration_ms"]


@pytest.mark.usefixtures("no_full_collections")
def test_a_failed_admission_leaves_no_half_record():
    engine, mon, *_ = monitored_stack()
    batcher = engine.batcher
    bad = SamplingParams(temperature=1.0, top_k=CFG.vocab_size + 1)
    with pytest.raises(Exception):
        batcher.submit(SHORT, 4, sampling=bad)  # the first-token draw fails
    assert batcher._phase_ms is None and not batcher.active.any()
    good = batcher.submit(SHORT, 2)
    batcher.run_to_completion()
    (record,) = admissions_of(mon)
    assert record["req"] == good
    assert list(record["phase_ms"]) == [
        "prefill", "seed_pool", "pull", "activate",
    ]
    failed = [r for r in mon.requests() if r["outcome"] == "error"]
    assert len(failed) == 1 and failed[0]["admit_ms"] is None


def test_a_full_collection_inside_a_record_is_named_in_it(monkeypatch):
    from bee_code_interpreter_tpu.models import serving

    hooked, choose_host = [], serving.choose_host

    def collecting(*args):
        hooked.append(engine.batcher._gc_pause in gc.callbacks)
        gc.collect()  # a full one, as the stalls on the chip are suspected
        return choose_host(*args)

    monkeypatch.setattr(serving, "choose_host", collecting)
    engine, mon, *_ = monitored_stack()
    steered = SamplingParams(temperature=0.8, seed=5, logit_bias={3: 2.0})
    engine.batcher.submit(SHORT, 3, sampling=steered)
    engine.batcher.run_to_completion()
    (admission,) = admissions_of(mon)
    phases = admission["phase_ms"]
    # beside the phases, inside the one it hit, never one of them
    assert 0.0 < phases["gc"] <= phases["activate"]
    assert unspanned_ms(admission) >= 0.0
    steps = [
        s for s in mon.snapshot(steps=64)["steps"]["last"]
        if s["decode_tokens"]
    ]
    assert steps and all(
        0.0 < s["phase_ms"]["gc"] <= s["phase_ms"]["sample_choose"]
        for s in steps
    )
    # registered while a record is being made, and at no other time
    assert hooked and all(hooked)
    assert engine.batcher._gc_pause not in gc.callbacks
    # the request's prefill span is split by phases, and this is none
    (row,) = mon.requests()
    assert "gc" in row["admit_phase_ms"]


def test_without_a_monitor_an_admission_reads_no_clock_and_keeps_no_state(
    monkeypatch,
):
    import types

    from bee_code_interpreter_tpu.models import serving

    reads = []
    monkeypatch.setattr(serving, "time", types.SimpleNamespace(
        monotonic=time.monotonic,  # submit's TTFT anchor, as before
        perf_counter=lambda: reads.append(1) or time.perf_counter(),
    ))
    batcher = ContinuousBatcher(
        PARAMS, CFG, max_batch=2, n_pages=32, page_size=4,
        max_pages_per_seq=8,
    )
    seen = []
    activate = batcher._activate_row
    monkeypatch.setattr(
        batcher, "_activate_row",
        lambda row, rec: seen.append(
            (batcher._phase_ms, batcher._gc_pause in gc.callbacks)
        ) or activate(row, rec),
    )
    reqs = [
        batcher.submit(SHORT, 5),
        batcher.submit(LONG, 5, sampling=SAMPLED, prefill_chunk=4),
    ]
    batcher.run_to_completion()
    assert seen == [(None, False)] * 2 and batcher._phase_ms is None
    assert not reads
    # ... and with one, it reads it: the stand-in does count
    engine, mon, *_ = monitored_stack()
    monitored = [
        engine.batcher.submit(SHORT, 5),
        engine.batcher.submit(LONG, 5, sampling=SAMPLED, prefill_chunk=4),
    ]
    engine.batcher.run_to_completion()
    assert reads and len(admissions_of(mon)) == 2
    assert [batcher.result(r) for r in reqs] == [
        engine.batcher.result(r) for r in monitored
    ]


class SpanSpy:
    """Stands in for ``jax.profiler.TraceAnnotation``: every span with its
    stats and the spans that were open when it was entered."""

    spans: list = []
    open_: list = []

    def __init__(self, name, **stats):
        self.name, self.stats = name, stats

    def __enter__(self):
        SpanSpy.spans.append((self.name, self.stats, tuple(SpanSpy.open_)))
        SpanSpy.open_.append(self.name)

    def __exit__(self, *exc):
        assert SpanSpy.open_.pop() == self.name


@pytest.mark.parametrize("mix", ["greedy", "mixed", "steered"])
def test_serve_spans_nest_and_carry_their_stats(monkeypatch, mix):
    monkeypatch.setattr(SpanSpy, "spans", [])
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", SpanSpy)
    batcher = ContinuousBatcher(
        PARAMS, CFG, max_batch=2, n_pages=32, page_size=4, max_pages_per_seq=8,
    )
    reqs = [
        batcher.submit(prompt, 3, sampling=sampling)
        for prompt, sampling in zip((SHORT, LONG), MIXES[mix])
    ]
    batcher.run_to_completion()
    spans = SpanSpy.spans
    assert not SpanSpy.open_
    # every span lies inside the one its name extends, and in no other
    for name, _, parents in spans:
        want = name.rpartition(".")[0]
        assert parents == ((want,) if want != "serve" else ()), (name, parents)

    admits = [stats for name, stats, _ in spans if name == "serve.admit"]
    assert admits == [
        {"req": reqs[0], "prompt_tokens": len(SHORT), "pages": 2},
        {"req": reqs[1], "prompt_tokens": len(LONG), "pages": 6},
    ]
    # the one-shot program's width: the prompt padded to whole pages
    prefills = [s for name, s, _ in spans if name == "serve.admit.prefill"]
    assert prefills == [{"padded_tokens": 4}, {"padded_tokens": 24}]
    names = [name for name, _, _ in spans]
    assert names[:5] == [
        "serve.admit", "serve.admit.prefill", "serve.admit.seed_pool",
        "serve.admit.pull", "serve.admit.activate",
    ]
    steps = [stats for name, stats, _ in spans if name == "serve.step"]
    assert [s["n"] for s in steps] == list(range(1, len(steps) + 1))
    assert steps[0]["rows"] == 2
    went_out = ["serve.step.upload", "serve.step.dispatch"]
    came_in = [f"serve.step.{phase}" for phase in ("wait", "pull", "sample")]
    if mix == "steered":
        # the host chooses a steered row's token: each step is landed in
        # the call that dispatched it
        assert [s["ahead"] for s in steps] == [0, 0]
        assert names[10:] == (["serve.step"] + went_out + came_in) * 2
    else:
        # one step ahead: the first call dispatches, the second dispatches
        # the second step and then lands the first, the third lands that
        assert [s["ahead"] for s in steps] == [0, 1, 0]
        assert names[10:] == (
            ["serve.step"] + went_out
            + ["serve.step"] + went_out + came_in
            + ["serve.step"] + came_in
        )
    # one [3, B] int32 array a step, whatever the rows (row_answers: the
    # token, its logit and the row's normaliser); steered: the rows the
    # host picks from beside it
    answers, rows = 3 * 4 * 2, 4 * 2 * CFG.vocab_size
    pulls = {s["bytes"] for name, s, _ in spans if name == "serve.step.pull"}
    assert pulls == {answers + (rows if mix == "steered" else 0)}
    # where the rows' tokens were picked is the step record's to say
    # (device_picked_rows, host_picked_rows): the span carries nothing
    assert {
        tuple(s) for name, s, _ in spans if name == "serve.step.sample"
    } == {()}


@pytest.mark.parametrize("rows", ["greedy", "sampled", "both"])
def test_rows_the_device_chose_for_cross_as_one_small_array(rows):
    # row_answers: each row's token (the pick's where it samples, else the
    # argmax), its logit there, the same float32 the host would have read
    # from the row, and its normaliser, in ONE program and one [3, B] array
    # whichever rows the batch holds
    from bee_code_interpreter_tpu.models.serving import (
        log_normalizers,
        row_answers,
    )

    logits = jax.random.normal(jax.random.PRNGKey(3), (4, 1, 97), jnp.float32)
    picked = jnp.asarray([5, 96, 0, 41], jnp.int32)
    sampled = np.array([False, True, True, False])
    # ...and beside it what the next step is given, left on the device: a
    # stepping row's token as its current, its position one slot on (never
    # past the table's last); every other row's as they were
    current = jnp.asarray([[7], [8], [9], [10]], jnp.int32)
    pos = jnp.asarray([3, 11, 5, 6], jnp.int32)
    stepping = np.array([True, True, False, True])
    answers, fed_current, fed_pos = row_answers(
        logits, picked, sampled, current, pos, stepping, last_slot=11
    )
    token, logit, log_z = np.asarray(answers)
    assert np.asarray(fed_current)[:, 0].tolist() == [
        token[0], token[1], 9, token[3]
    ]
    assert np.asarray(fed_pos).tolist() == [4, 11, 5, 7]
    last = np.asarray(logits)[:, 0]
    assert token.tolist() == np.where(sampled, picked, last.argmax(-1)).tolist()
    assert logit.view(np.float32).tolist() == last[np.arange(4), token].tolist()
    assert log_z.view(np.float32).tolist() == np.asarray(
        log_normalizers(logits[:, 0])
    ).tolist()

    kinds = {
        "greedy": [SamplingParams(logprobs=True)] * 2,
        "sampled": [SAMPLED, dataclasses.replace(SAMPLED, seed=4)],
        "both": [SamplingParams(logprobs=True), SAMPLED],
    }[rows]
    batcher = ContinuousBatcher(
        PARAMS, CFG, max_batch=2, n_pages=32, page_size=4, max_pages_per_seq=8,
    )
    # the function's jit cache is one for every batcher of the process
    before = batcher._row_answers._cache_size()
    reqs = [batcher.submit(SHORT, 5, sampling=sp) for sp in kinds]
    batcher.run_to_completion()
    assert batcher._row_answers._cache_size() - before <= 1
    for r in reqs:
        out = batcher.result(r)
        full = T.forward(PARAMS, jnp.asarray(SHORT + out)[None, :], CFG)[0]
        logp = np.asarray(jax.nn.log_softmax(full.astype(jnp.float32), axis=-1))
        want = [logp[len(SHORT) - 1 + j, t] for j, t in enumerate(out)]
        np.testing.assert_allclose(batcher.result_logprobs(r), want, atol=2e-4)


TRACKED = {
    "_decode": "decode_step_paged", "_prefill": "prefill_forward",
    "_window": "decode_window_paged", "_seed_pool": "seed_pool",
    "_draft_decode": "draft_decode_step_paged",
    "_draft_prefill": "draft_prefill_forward",
    "_draft_window": "draft_decode_window_paged",
    "_pick": "pick_tokens",
    "_row_answers": "row_answers",
}


@pytest.mark.parametrize("attr", TRACKED)
def test_jitted_programs_carry_their_tracked_names(attr):
    batcher = ContinuousBatcher(
        PARAMS, CFG, max_batch=2, n_pages=32, page_size=4, max_pages_per_seq=8,
        draft_params=PARAMS, draft_config=CFG, gamma=2,
    )
    tracked = getattr(batcher, attr)
    assert tracked.name == TRACKED[attr] == tracked.fn.__name__
    if attr == "_decode":
        # ...and so does the program the profiler's XLA Modules line names
        lowered = tracked.lower(
            batcher.params, jnp.asarray(batcher.current),
            jnp.asarray(batcher.pos), batcher.cache,
            jnp.asarray(batcher.block_table),
        )
        assert lowered.as_text().startswith("module @jit_decode_step_paged ")


@pytest.mark.parametrize("with_phases", [True, False])
@pytest.mark.usefixtures("no_full_collections")
def test_serving_top_prints_the_median_phase_times(with_phases):
    import importlib.util
    from pathlib import Path

    path = Path(__file__).resolve().parents[1] / "scripts" / "serving-top.py"
    spec = importlib.util.spec_from_file_location("serving_top", path)
    top = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(top)
    steps = [dict(s) for s in decoded("mixed", True)[1]]
    if not with_phases:  # an older replica's records
        for s in steps:
            del s["phase_ms"]
            s.pop("admissions", None)
    text = top.render_steps(
        {"steps": {"recorded": len(steps), "retained": len(steps), "last": steps}}
    )
    # two lines of medians: the steps' phases, then the two admissions'
    assert len(text.splitlines()) == 2 + len(steps) + 2 * with_phases
    if with_phases:
        line, admit = text.splitlines()[-2:]
        assert line.startswith("  phase p50: upload ")
        assert [w for w in line.split() if w.isalpha() or "_" in w] == [
            "phase", *TOP_PHASES, "sample_choose", "sample_logprob",
        ]
        assert admit.startswith("  admit p50 (2): admit ")
        assert [w for w in admit.split() if w.isalpha() or "_" in w] == [
            "admit", "admit", "prefill", "seed_pool", "pull", "activate",
        ]


# ----------------------------------------------------------- bench trajectory


def test_serving_bench_phase_fields_and_overhead_bound():
    """The bench serving phase's arithmetic (models/serving_bench.py), on
    parameters tiny enough for the tier-1 CPU lane: every BENCH-artifact
    field is present, the latency numbers come from real lifecycle records,
    and the A/B overhead bound is COMPUTED (overhead_ok mirrors
    overhead_pct vs the budget) rather than asserted true — tiny-model CPU
    steps are a far harsher overhead denominator than any real serving
    config, so tier-1 must not flake on a noisy box."""
    import time

    from bee_code_interpreter_tpu.models.serving_bench import (
        run_serving_bench,
    )

    t0 = time.monotonic()
    out = run_serving_bench(
        n_requests=3, max_new_tokens=6, repeats=2, max_batch=2, inner=1
    )
    wall = time.monotonic() - t0
    assert wall < 120.0, f"tiny serving bench took {wall:.0f}s"

    for field in (
        "tokens_per_s", "uninstrumented_tokens_per_s", "overhead_pct",
        "overhead_budget_pct", "overhead_ok", "ttft_p50_ms", "ttft_p95_ms",
        "inter_token_p50_ms", "requests", "max_new_tokens", "repeats",
        "config",
    ):
        assert field in out, field
    assert out["tokens_per_s"] > 0
    assert out["uninstrumented_tokens_per_s"] > 0
    assert out["overhead_pct"] >= 0.0
    assert out["overhead_ok"] == (
        out["overhead_pct"] < out["overhead_budget_pct"]
    )
    # three requests finished ok through the instrumented arm, so the
    # latency percentiles exist and are ordered
    assert out["ttft_p50_ms"] is not None
    assert out["ttft_p95_ms"] >= out["ttft_p50_ms"]
    assert out["inter_token_p50_ms"] is not None and (
        out["inter_token_p50_ms"] > 0
    )
    assert out["requests"] == 3


# ------------------------------------------------------------- HTTP transport


def make_serving_app(local_executor, *, attach_engine=True):
    metrics = Registry()
    store = TraceStore()
    tracer = Tracer(store=store, metrics=metrics)
    recorder = FlightRecorder(metrics=metrics)
    tracer.add_sink(recorder.record_trace)
    monitor = ServingMonitor(
        metrics=metrics, store=store, recorder=recorder
    )
    if attach_engine:
        batcher = ContinuousBatcher(
            PARAMS, CFG, max_batch=2, n_pages=32, page_size=4,
            max_pages_per_seq=8, metrics=metrics,
        )
        monitor.attach(Engine(batcher, metrics=metrics))
    app = create_http_server(
        code_executor=local_executor,
        custom_tool_executor=CustomToolExecutor(code_executor=local_executor),
        metrics=metrics,
        tracer=tracer,
        recorder=recorder,
        serving=monitor,
        profiler=ServingProfiler(monitor),
    )
    return app, monitor, metrics, store, recorder


async def with_client(app, fn):
    client = TestClient(TestServer(app))
    await client.start_server()
    try:
        await fn(client)
    finally:
        await client.close()


async def test_http_serving_endpoints_and_trace_id_agreement(local_executor):
    """The acceptance e2e: one serving request's wide event (/v1/events),
    its /v1/traces/{id} trace, and the bci_serving_ttft_seconds exemplar on
    the OpenMetrics exposition all share one trace_id."""
    app, monitor, metrics, store, recorder = make_serving_app(local_executor)
    engine = monitor._engine
    ticket = engine.submit(SHORT, 4)
    engine.run_to_completion()
    assert len(engine.result(ticket)) == 4
    trace_id = monitor.requests()[0]["trace_id"]

    async def go(client):
        snap = await (await client.get("/v1/serving")).json()
        assert snap["attached"] is True
        assert snap["totals"]["finished"] == 1
        assert snap["batcher"]["max_batch"] == 2
        assert snap["kv_cache"]["pages_total"] == 31
        assert snap["steps"]["last"], "no step records served"
        assert (
            await (await client.get("/v1/serving", params={"steps": "0"}))
            .json()
        )["steps"]["last"] == []

        rows = (
            await (
                await client.get(
                    "/v1/serving/requests", params={"outcome": "ok"}
                )
            ).json()
        )["requests"]
        assert len(rows) == 1 and rows[0]["trace_id"] == trace_id
        assert (
            await (
                await client.get(
                    "/v1/serving/requests", params={"outcome": "error"}
                )
            ).json()
        )["requests"] == []
        assert (
            await (
                await client.get("/v1/serving/requests", params={"limit": "0"})
            ).json()
        )["requests"] == []
        for bad_params in (
            {"steps": "nope"}, {"steps": "-1"},
        ):
            assert (
                await client.get("/v1/serving", params=bad_params)
            ).status == 400
        for bad_params in (
            {"limit": "nope"}, {"limit": "-1"}, {"min_duration_ms": "x"},
        ):
            assert (
                await client.get("/v1/serving/requests", params=bad_params)
            ).status == 400

        # wide event ↔ trace ↔ exemplar: one trace_id
        events = (
            await (
                await client.get("/v1/events", params={"kind": "serving"})
            ).json()
        )["events"]
        assert len(events) == 1 and events[0]["trace_id"] == trace_id
        detail = await (await client.get(f"/v1/traces/{trace_id}")).json()
        assert detail["trace_id"] == trace_id
        assert {"queued", "prefill", "decode"} <= set(detail["stage_ms"])

        exposition = await (
            await client.get(
                "/metrics",
                headers={"Accept": "application/openmetrics-text"},
            )
        ).text()
        pattern = re.compile(
            r'^bci_serving_ttft_seconds_bucket{[^}]*} \d+ '
            r'# {trace_id="([0-9a-f]{32})"',
            re.M,
        )
        exemplar_ids = set(pattern.findall(exposition))
        assert exemplar_ids == {trace_id}

        # the one-call incident bundle carries the serving section
        bundle = await (await client.get("/v1/debug/bundle")).json()
        assert bundle["serving"]["attached"] is True
        assert bundle["serving"]["totals"]["finished"] == 1

    await with_client(app, go)


async def test_http_profile_target_serving_captures_real_steps(
    local_executor, tmp_path
):
    """POST /v1/profile target=serving steps real batcher steps through the
    attached engine (501 only when nothing is attached — the other test)."""
    app, monitor, metrics, store, recorder = make_serving_app(local_executor)
    engine = monitor._engine
    # queue work so the profiled steps actually run the model
    tickets = [engine.submit(SHORT, 6), engine.submit(LONG, 6)]

    async def go(client):
        resp = await client.post(
            "/v1/profile", json={"target": "serving", "steps": 3}
        )
        body = await resp.json()
        assert resp.status == 200, body
        assert body["steps"] == 3 and body["duration_ms"] > 0
        assert body["files"], "no profiler artifacts captured"

    await with_client(app, go)
    engine.run_to_completion()
    for t in tickets:
        assert len(engine.result(t)) == 6


async def test_http_serving_unwired_and_unattached(local_executor):
    # no monitor at all: both endpoints answer 501
    bare = create_http_server(
        code_executor=local_executor,
        custom_tool_executor=CustomToolExecutor(code_executor=local_executor),
        metrics=Registry(),
    )

    async def bare_go(client):
        assert (await client.get("/v1/serving")).status == 501
        assert (await client.get("/v1/serving/requests")).status == 501

    await with_client(bare, bare_go)

    # monitor wired but no engine attached: the snapshot answers honestly
    # and target=serving profiling is 501 (nothing can step)
    app, monitor, *_ = make_serving_app(local_executor, attach_engine=False)

    async def go(client):
        snap = await (await client.get("/v1/serving")).json()
        assert snap["attached"] is False
        assert "batcher" not in snap
        resp = await client.post(
            "/v1/profile", json={"target": "serving", "steps": 2}
        )
        assert resp.status == 501

    await with_client(app, go)


# ------------------------------------------------------------- gRPC transport


async def test_grpc_serving_snapshot_and_requests(local_executor):
    metrics = Registry()
    store = TraceStore()
    tracer = Tracer(store=store, metrics=metrics)
    recorder = FlightRecorder(metrics=metrics)
    tracer.add_sink(recorder.record_trace)
    monitor = ServingMonitor(metrics=metrics, store=store, recorder=recorder)
    batcher = ContinuousBatcher(
        PARAMS, CFG, max_batch=2, n_pages=32, page_size=4,
        max_pages_per_seq=8, metrics=metrics,
    )
    monitor.attach(Engine(batcher, metrics=metrics))
    engine = monitor._engine
    ticket = engine.submit(SHORT, 3)
    engine.run_to_completion()
    assert len(engine.result(ticket)) == 3

    server = GrpcServer(
        code_executor=local_executor,
        custom_tool_executor=CustomToolExecutor(code_executor=local_executor),
        metrics=metrics,
        tracer=tracer,
        recorder=recorder,
        serving=monitor,
    )
    port = await server.start("127.0.0.1:0")
    try:
        async with grpc.aio.insecure_channel(f"127.0.0.1:{port}") as channel:
            obs = observability_stubs(channel)
            snap = json.loads(await obs["GetServing"](b""))
            assert snap["attached"] is True
            assert snap["totals"]["finished"] == 1
            assert snap["kv_cache"]["pages_total"] == 31
            trimmed = json.loads(await obs["GetServing"](b'{"steps": 0}'))
            assert trimmed["steps"]["last"] == []

            rows = json.loads(
                await obs["GetServingRequests"](b'{"outcome": "ok"}')
            )["requests"]
            assert len(rows) == 1 and rows[0]["output_tokens"] == 3
            none = json.loads(
                await obs["GetServingRequests"](b'{"finish": "stop"}')
            )["requests"]
            assert none == []
            # the HTTP edge's ?active=1/0 string forms mean the same thing
            # here (bool("0") would invert them): "0" selects FINISHED rows
            done_rows = json.loads(
                await obs["GetServingRequests"](b'{"active": "0"}')
            )["requests"]
            assert len(done_rows) == 1 and done_rows[0]["active"] is False
            assert json.loads(
                await obs["GetServingRequests"](b'{"active": true}')
            )["requests"] == []

            for method, payload in (
                ("GetServing", b"not json"),
                ("GetServing", b'{"steps": -1}'),
                ("GetServingRequests", b'{"limit": "x"}'),
                ("GetServingRequests", b'{"limit": -5}'),
            ):
                with pytest.raises(grpc.aio.AioRpcError) as excinfo:
                    await obs[method](payload)
                assert (
                    excinfo.value.code() == grpc.StatusCode.INVALID_ARGUMENT
                )
    finally:
        await server.stop(None)


async def test_grpc_serving_unimplemented_without_monitor(local_executor):
    server = GrpcServer(
        code_executor=local_executor,
        custom_tool_executor=CustomToolExecutor(code_executor=local_executor),
        metrics=Registry(),
    )
    port = await server.start("127.0.0.1:0")
    try:
        async with grpc.aio.insecure_channel(f"127.0.0.1:{port}") as channel:
            obs = observability_stubs(channel)
            for method in ("GetServing", "GetServingRequests"):
                with pytest.raises(grpc.aio.AioRpcError) as excinfo:
                    await obs[method](b"")
                assert (
                    excinfo.value.code() == grpc.StatusCode.UNIMPLEMENTED
                )
    finally:
        await server.stop(None)
