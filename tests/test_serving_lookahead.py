"""The plain decode step runs one step ahead of the host.

``ContinuousBatcher.step`` dispatches step k from what step k - 1 left on
the device, then lands step k - 1, and returns with step k in flight
(models/serving.py). Pinned here: the results are the synchronous step's,
request by request; a row that ends by eos or a stop sequence has run one
step too many by the time the host sees it, and that token is thrown away;
whatever changes rows or reads the pool lands the step in flight first;
``busy`` holds until the last step has landed; the step records and
``stats`` say which steps went ahead; and a steady step uploads nothing.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

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
A = [17, 35, 47, 36, 160, 173, 116, 7, 18, 66]
B = [27, 62, 8, 178, 132]
C = [48, 94, 38, 154, 94, 6, 50, 141]
D = [22, 90, 78]
SAMPLED = SamplingParams(temperature=0.9, top_p=0.9, seed=11, logprobs=True)
# steered (the host picks its token from the row's logits) with no effect
# on the choice: beside it, every step is landed in the call that made it
STEERED_MATE = SamplingParams(logit_bias={1: 0.0})


def spy_on_spans(monkeypatch) -> list:
    """Every ``serve.*`` span from here on, as (name, stats), in order."""
    spans = []

    class Spy:
        def __init__(self, name, **stats):
            spans.append((name, stats))

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Spy)
    return spans


def make(**kw):
    kw.setdefault("max_batch", 4)
    kw.setdefault("n_pages", 48)
    kw.setdefault("page_size", 4)
    kw.setdefault("max_pages_per_seq", 8)
    return ContinuousBatcher(PARAMS, CFG, **kw)


@functools.lru_cache(maxsize=None)
def solo(prompt: tuple, n: int, sampling=None, eos_id=None):
    """(tokens, finish reason, log-probabilities or None) of one request
    decoded alone."""
    b = make(max_batch=1, n_pages=16, eos_id=eos_id)
    r = b.submit(list(prompt), n, sampling=sampling)
    b.run_to_completion()
    logprobs = sampling is not None and sampling.logprobs
    return (
        b.result(r), b.finish_reason(r),
        b.result_logprobs(r) if logprobs else None,
    )


def eos_of_a() -> int:
    """A token A's greedy decode reaches at its 4th position and not
    before: as ``eos_id`` it ends A three tokens early."""
    tokens = solo(tuple(A), 12)[0]
    assert tokens[3] not in tokens[:3]
    return tokens[3]


# ------------------------------------------------- (a) the same results


def mixed_batch(eos_id):
    """(prompt, budget, sampling) of a batch that ends every way there is:
    by budget, by ``eos_id`` and by a stop sequence; greedy and sampled,
    with and without log-probabilities."""
    c_tokens = solo(tuple(C), 10, eos_id=eos_id)[0]
    stop = SamplingParams(
        stop_sequences=[tuple(c_tokens[4:6])], logprobs=True
    )
    return [
        (A, 12, SamplingParams(logprobs=True)),  # eos at its 4th token
        (B, 6, None),
        (C, 10, stop),
        (D, 9, SAMPLED),
        (B, 3, dataclasses.replace(SAMPLED, seed=5, logprobs=False)),
        (A, 2, dataclasses.replace(SAMPLED, seed=6, top_p=None, top_k=9)),
        (D, 1, None),  # done at admission: never in a step
    ]


def run_mixed(reference: str | None):
    """The mixed batch through an engine, admissions staggered over the
    steps. ``reference`` makes every step synchronous: ``"steered_mate"``
    by what the batcher observes (a steered row decodes beside the batch
    throughout), ``"landed_each_step"`` by landing each step in the call
    that dispatched it. Returns each request's (tokens, log-probabilities
    or None, finish reason) and the batcher."""
    eos_id = eos_of_a()
    b = make(max_batch=4, eos_id=eos_id)
    eng = Engine(b)
    requests = mixed_batch(eos_id)
    if reference == "steered_mate":
        mate = eng.submit(C, 24, sampling=STEERED_MATE)
    tickets = []
    for i, (prompt, n, sampling) in enumerate(requests):
        tickets.append(eng.submit(prompt, n, sampling=sampling))
        if i % 2:  # two arrive, then two steps pass
            for _ in range(2):
                eng.step()
                if reference == "landed_each_step":
                    b._land()
    while eng.pending or b.busy:
        eng.step()
        if reference == "landed_each_step":
            b._land()
    if reference == "steered_mate":
        assert eng.is_done(mate)
    out = []
    for t, (_, _, sampling) in zip(tickets, requests):
        logprobs = (
            eng.result_logprobs(t)
            if sampling is not None and sampling.logprobs else None
        )
        out.append((eng.result(t), logprobs, eng.finish_reason(t)))
    return out, b


@functools.lru_cache(maxsize=None)
def mixed(reference):
    return run_mixed(reference)


@pytest.mark.parametrize("reference", ["steered_mate", "landed_each_step"])
def test_ahead_gives_the_synchronous_steps_results(reference):
    ahead, b_ahead = mixed(None)
    synchronous, b_sync = mixed(reference)
    # the two runs did differ in how they stepped
    assert b_ahead.stats["steps_ahead"] > b_ahead.stats["steps_synchronous"]
    assert b_sync.stats["steps_ahead"] == 0
    for got, want in zip(ahead, synchronous):
        assert got[0] == want[0]  # tokens
        assert got[2] == want[2]  # finish reason
        if want[1] is None:
            assert got[1] is None
        else:  # the same float32 logit less the same normaliser
            assert got[1] == want[1]
    assert {reason for _, _, reason in ahead} == {"length", "eos", "stop"}
    # pages and rows all came back
    for b in (b_ahead, b_sync):
        assert b.stats["held_pages"] == 0 and not b.busy


@pytest.mark.parametrize("i", range(7))
def test_ahead_gives_each_requests_solo_result(i):
    eos_id = eos_of_a()
    prompt, n, sampling = mixed_batch(eos_id)[i]
    tokens, _, reason = mixed(None)[0][i]
    assert (tokens, reason) == solo(tuple(prompt), n, sampling, eos_id)[:2]


# ----------------------------------- (b) the token of a step too many


def test_a_row_that_ends_by_eos_is_not_given_its_extra_token():
    eos_id = eos_of_a()
    want, reason, _ = solo(tuple(A), 12, eos_id=eos_id)
    assert reason == "eos" and len(want) == 4 and want[-1] == eos_id
    b = make(max_batch=2, eos_id=eos_id)
    r = b.submit(A, 12, sampling=SamplingParams(logprobs=True))
    mate = b.submit(B, 12)
    while not b.is_done(r):
        b.step()
    # the step that found the eos had already dispatched the next one,
    # with the row in it: one token too many is on its way
    assert b._in_flight is not None
    row_steps = b.stats["steps_ahead"] + b.stats["steps_synchronous"]
    assert row_steps == len(want)  # 3 landed + the one too many
    assert b._discarded_tokens == 0
    b.step()
    assert b._discarded_tokens == 1
    assert b.result(r) == want and len(b.result_logprobs(r)) == len(want)
    b.run_to_completion()
    assert b.result(mate) == solo(tuple(B), 12, None, eos_id)[0]
    # every token counted is in a result: the discarded one is in neither
    assert b.n_tokens_generated == len(want) + len(b.result(mate))
    assert b._discarded_tokens == 1


@pytest.mark.parametrize("when", ["step_in_flight", "after_it_landed"])
def test_a_request_admitted_into_the_ended_rows_pages_decodes_as_alone(when):
    # one row, and a pool so small that the next request must take the
    # pages the first gave back: the extra step's write into them lands
    # before the admission's seeding does (it was queued first)
    eos_id = eos_of_a()
    b = make(max_batch=1, n_pages=7, eos_id=eos_id)
    r = b.submit(A, 12)  # 22 slots: all 6 usable pages
    pages = set(b.block_table[0].tolist()) - {0}
    assert len(pages) == 6
    while not b.is_done(r):
        b.step()
    assert b._in_flight is not None and b.finish_reason(r) == "eos"
    if when == "after_it_landed":
        b.step()
        assert b._in_flight is None and b._discarded_tokens == 1
    nxt = b.submit(C, 10, sampling=SAMPLED)
    assert b._discarded_tokens == 1  # the admission landed it first
    assert set(b.block_table[0].tolist()) - {0} <= pages
    b.run_to_completion()
    assert b.result(nxt) == solo(tuple(C), 10, SAMPLED, eos_id)[0]
    assert b.result(r) == solo(tuple(A), 12, None, eos_id)[0]


def test_a_stop_sequence_ends_a_row_one_step_late_and_trims_as_before():
    tokens = solo(tuple(C), 10)[0]
    stop = SamplingParams(stop_sequences=[tuple(tokens[3:5])], logprobs=True)
    b = make(max_batch=2)
    r = b.submit(C, 10, sampling=stop)
    mate = b.submit(D, 10)
    b.run_to_completion()
    assert b.finish_reason(r) == "stop" and b.result(r) == tokens[:3]
    assert len(b.result_logprobs(r)) == 3
    assert b._discarded_tokens == 1
    assert b.result(mate) == solo(tuple(D), 10)[0]


def test_a_row_that_ends_by_its_budget_is_left_out_of_the_next_step():
    # the host counts tokens, it need not see them: no step too many
    b = make(max_batch=2)
    short, long_ = b.submit(B, 3), b.submit(D, 6)
    b.step()  # tokens 2 in flight
    b.step()  # tokens 3 in flight: the short row's last
    assert b._in_flight["rows"].tolist() == [0, 1]
    assert b._in_flight["outlives"].tolist() == [False, True]
    b.step()  # lands them; the step it dispatched ran the long row alone
    assert b.is_done(short) and b.finish_reason(short) == "length"
    assert b._in_flight["rows"].tolist() == [1]
    b.run_to_completion()
    assert b._discarded_tokens == 0
    assert b.result(short) == solo(tuple(B), 3)[0]
    assert b.result(long_) == solo(tuple(D), 6)[0]


# ------------------------------ (c) what lands the step in flight first


def two_steps_in(budget=8, **kw):
    """A batcher with one request of ``budget`` tokens, two steps called:
    the first token from the admission, the second landed, the third in
    flight."""
    b = make(max_batch=2, **kw)
    r = b.submit(A, budget)
    b.step()
    b.step()
    assert b._in_flight is not None and len(b.results[r]) == 2
    return b, r


def test_cancel_lands_the_step_in_flight_and_keeps_its_token():
    b, r = two_steps_in()
    b.cancel(r)
    assert b._in_flight is None and not b.busy
    assert b.finish_reason(r) == "cancelled"
    assert b.result(r) == solo(tuple(A), 8)[0][:3]
    assert b.stats["held_pages"] == 0


def test_cancel_that_races_the_last_step_is_a_no_op():
    b, r = two_steps_in(budget=3)
    b.cancel(r)  # the step in flight was the request's last
    assert b.finish_reason(r) == "length"
    assert b.result(r) == solo(tuple(A), 3)[0]


def test_preempt_with_a_step_in_flight():
    b, r = two_steps_in()
    long_prompt = (A + C) * 2
    p = b.submit(long_prompt[:24], 4, interleave_admission=4)
    b.step()  # one window; the decoding row's step is left in flight
    assert b._in_flight is not None and b.prefill_state
    assert b.preempt(p)
    assert b._in_flight is None and not b.prefill_state
    assert not b.preempt(r)  # decoding: cancel's business
    b.run_to_completion()
    assert b.result(r) == solo(tuple(A), 8)[0]
    assert b.stats["held_pages"] == 0


@pytest.mark.parametrize("resume_on", ["a_fresh_batcher", "the_same_batcher"])
def test_snapshot_with_a_step_in_flight_resumes_exactly(resume_on):
    b, r = two_steps_in(budget=9)
    r2 = b.submit(D, 7, sampling=SAMPLED)
    b.step()
    assert b._in_flight is not None
    snap = b.state_dict()
    # the snapshot is of one point of the sequence: everything landed
    assert b._in_flight is None
    landed = {k: list(v) for k, v in snap["host"]["results"].items()}
    assert landed == {r: solo(tuple(A), 9)[0][:4],
                      r2: solo(tuple(D), 7, SAMPLED)[0][:2]}
    target = make(max_batch=2) if resume_on == "a_fresh_batcher" else b
    if target is b:
        b.step()  # the snapshot is adopted over a step in flight
        assert b._in_flight is not None
    target.load_state_dict(snap)
    assert target._in_flight is None
    target.run_to_completion()
    assert target.result(r) == solo(tuple(A), 9)[0]
    assert target.result(r2) == solo(tuple(D), 7, SAMPLED)[0]
    assert target.result_logprobs(r2) == pytest.approx(
        solo(tuple(D), 7, SAMPLED)[2], abs=1e-5
    )


def test_release_of_a_live_request_lands_the_step_in_flight_first():
    b, r = two_steps_in(budget=3)
    assert not b.is_done(r)  # its last token is in flight
    b.release(r)  # ...and landing it finishes the request: released
    assert b._in_flight is None and b.is_done(r)
    with pytest.raises(KeyError, match="released"):
        b.result(r)
    b, r = two_steps_in(budget=8)
    with pytest.raises(RuntimeError, match="still decoding"):
        b.release(r)
    assert b._in_flight is None and len(b.results[r]) == 3
    b.run_to_completion()
    assert b.result(r) == solo(tuple(A), 8)[0]


def test_submit_lands_the_step_in_flight_and_sees_the_row_it_frees():
    b = make(max_batch=1)
    r = b.submit(B, 3)
    b.step()
    b.step()  # the last token in flight: the one row still looks taken
    assert not b.has_free_row() and not b.is_done(r)
    nxt = b.submit(D, 4)  # lands it, which frees the row
    assert b.is_done(r) and b.result(r) == solo(tuple(B), 3)[0]
    b.run_to_completion()
    assert b.result(nxt) == solo(tuple(D), 4)[0]


@pytest.mark.parametrize("how", ["prefill_chunk", "prefix_hit", "adapter"])
def test_a_windowed_admission_lands_the_step_in_flight_first(how):
    # the window programs read and write the pool through the row's own
    # table: the step in flight has landed before the first of them runs
    kw, submit_kw = {}, {}
    if how == "prefix_hit":
        kw["prefix_cache"] = True
    elif how == "adapter":
        from bee_code_interpreter_tpu.models.lora import init_lora

        kw["adapters"] = [init_lora(CFG, jax.random.PRNGKey(3), rank=2)]
        submit_kw["adapter"] = 0
    else:
        submit_kw["prefill_chunk"] = 4
    b = make(max_batch=2, **kw)
    first = b.submit(C, 6)
    b.step()
    b.step()
    assert b._in_flight is not None
    second = b.submit(C, 6, **submit_kw)
    assert b._in_flight is None and len(b.results[first]) == 3
    if how == "prefix_hit":
        assert b.prefix_stats["hits"] == 1
    b.run_to_completion()
    want = solo(tuple(C), 6)[0]
    assert b.result(first) == want
    # (a fresh adapter's B is zero: the base model's tokens)
    assert b.result(second) == want


# ------------------------------------------- (d) busy, run_to_completion


def test_busy_holds_until_the_last_step_has_landed():
    b = make(max_batch=1)
    r = b.submit(A, 2)
    assert b.busy and len(b.results[r]) == 1
    b.step()  # dispatched; its token is not readable yet
    assert b.busy and b._in_flight is not None
    assert len(b.results[r]) == 1 and not b.is_done(r)
    b.step()  # nothing outlives it: this call only lands
    assert not b.busy and b._in_flight is None
    assert b.result(r) == solo(tuple(A), 2)[0]
    steps = b._n_steps
    b.run_to_completion()  # nothing left to do
    assert b._n_steps == steps


@pytest.mark.parametrize("layer", ["batcher", "engine"])
def test_run_to_completion_lands_the_last_step(layer):
    b = make(max_batch=2)
    top = Engine(b) if layer == "engine" else b
    # the engine queues the third until a row frees
    requests = ((A, 5), (B, 7), (D, 3))[:3 if layer == "engine" else 2]
    tickets = [top.submit(p, n) for p, n in requests]
    top.run_to_completion()
    assert b._in_flight is None and not b.busy
    for t, (p, n) in zip(tickets, requests):
        assert top.result(t) == solo(tuple(p), n)[0]


def test_a_stream_reads_a_steps_tokens_after_the_next_step():
    eng = Engine(make(max_batch=1))
    t = eng.submit(A, 4)
    seen = []
    for _ in range(6):
        eng.step()
        seen.append(eng.new_tokens(t))
    want = solo(tuple(A), 4)[0]
    # admitted and one step dispatched; then one token a call; then done
    assert seen == [want[:1], want[1:2], want[2:3], want[3:4], [], []]
    assert eng.is_done(t)


# --------------------------------------------- (e) records and counters


def monitored(**kw):
    from bee_code_interpreter_tpu.observability import ServingMonitor

    b = make(**kw)
    monitor = ServingMonitor(max_steps=256, max_requests=64)
    monitor.attach(b)
    return b, monitor


def records(monitor):
    return monitor.snapshot(steps=256)["steps"]["last"]


def test_step_records_say_which_steps_went_ahead():
    b, monitor = monitored(max_batch=2)
    r = b.submit(A, 5)
    b.step()
    b.step()
    late = b.submit(B, 3)  # lands the step in flight: the next is not ahead
    b.run_to_completion()
    steps = records(monitor)
    assert [s["ahead"] for s in steps] == [
        False, True,  # after the first admission; then one step ahead
        False, True,  # after the second admission; then ahead again, with
        # both rows' last tokens: nothing outlives that step
        False,  # so the last call only lands
    ]
    assert [list(s["phase_ms"])[:2] for s in steps[:4]] == [
        ["upload", "dispatch"]
    ] * 4
    assert "dispatch" not in steps[4]["phase_ms"]
    assert sum(s["decode_tokens"] for s in steps) + 1 == (
        # all but the first tokens, less the one the admission landed
        len(b.result(r)) - 1 + len(b.result(late)) - 1
    )
    assert all(s["discarded_tokens"] == 0 for s in steps)
    stats = b.stats
    assert stats["steps_ahead"] == sum(s["ahead"] for s in steps)
    plain = [s for s in steps if "dispatch" in s["phase_ms"]]
    assert stats["steps_synchronous"] == len(plain) - stats["steps_ahead"]


def test_step_records_count_the_discarded_token():
    b, monitor = monitored(max_batch=2, eos_id=eos_of_a())
    r = b.submit(A, 12)
    mate = b.submit(B, 8)
    b.run_to_completion()
    steps = records(monitor)
    assert b.finish_reason(r) == "eos" and b.finish_reason(mate) == "length"
    assert [s["discarded_tokens"] for s in steps] == [0, 0, 0, 0, 1, 0, 0, 0]
    # the row that ended is counted where its extra token was landed, a
    # step after the one that found the eos
    found = next(i for i, s in enumerate(steps) if s["active_rows_after"] == 1)
    assert steps[found + 1]["discarded_tokens"] == 1


def test_a_steered_row_keeps_every_step_synchronous():
    b, monitor = monitored(max_batch=2)
    plain = b.submit(A, 6)
    steered = b.submit(B, 3, sampling=SamplingParams(logit_bias={7: 1.5}))
    b.run_to_completion()
    steps = records(monitor)
    # beside the steered row nothing is left in flight; once it has ended
    # the batcher goes ahead again, with no switch thrown
    assert [s["ahead"] for s in steps] == [False, False, False, True, True, False]
    assert [s["host_picked_rows"] for s in steps] == [1, 1, 0, 0, 0, 0]
    assert b.result(plain) == solo(tuple(A), 6)[0]
    want = solo(tuple(B), 3, SamplingParams(logit_bias={7: 1.5}))[0]
    assert b.result(steered) == want


def test_the_serve_step_span_carries_ahead(monkeypatch):
    spans = spy_on_spans(monkeypatch)
    b = make(max_batch=1)
    b.submit(A, 4)
    b.step()
    b.step()
    b.cancel(0)  # a drain between steps: its own span, not a step's
    steps = [stats["ahead"] for name, stats in spans if name == "serve.step"]
    assert steps == [0, 1]
    names = [name for name, _ in spans]
    assert names[-4:] == [
        "serve.land", "serve.land.wait", "serve.land.pull", "serve.land.sample",
    ]
    assert names.count("serve.step.wait") == 1


@pytest.mark.parametrize("room", ["a_free_row", "none_until_it_lands"])
def test_an_admission_queues_its_prefill_before_it_lands_the_step(
    monkeypatch, room
):
    b = make(max_batch=2 if room == "a_free_row" else 1)
    r = b.submit(B, 3)
    b.step()
    b.step()  # the last token of `r` in flight
    assert b._in_flight is not None
    spans = spy_on_spans(monkeypatch)
    nxt = b.submit(D, 4)
    monkeypatch.undo()
    names = [name for name, _ in spans]
    assert b._in_flight is None and b.is_done(r)
    land = ["serve.land", "serve.land.wait", "serve.land.pull",
            "serve.land.sample"]
    admit = ["serve.admit", "serve.admit.prefill", "serve.admit.seed_pool"]
    end = ["serve.admit.pull", "serve.admit.activate"]
    if room == "a_free_row":
        # the row and the pages are free by what has landed: the prefill
        # and the seeding go to the device behind the step in flight, and
        # the host lands that step while they run
        assert names == admit + land + end
    else:
        # the one row is the step in flight's to free: landed first
        assert names == land + admit + end
    b.run_to_completion()
    assert b.result(r) == solo(tuple(B), 3)[0]
    assert b.result(nxt) == solo(tuple(D), 4)[0]


# ------------------------------------------ (f) nothing goes up a step


def spy_on_uploads(b):
    """Every array the plain step uploads, by shape, in order."""
    uploads, real = [], b._upload

    def upload(host):
        uploads.append(tuple(host.shape))
        return real(host)

    b._upload = upload
    return uploads


@pytest.mark.parametrize("tp", [4, None])
def test_a_steady_step_uploads_nothing(tp):
    config = dataclasses.replace(CFG, n_kv_heads=4)
    params = T.init_params(config, jax.random.PRNGKey(0))
    mesh = None if tp is None else Mesh(np.array(jax.devices()[:tp]), ("tp",))
    b = ContinuousBatcher(
        params, config, max_batch=3, n_pages=32, page_size=4,
        max_pages_per_seq=6, mesh=mesh,
    )
    uploads = spy_on_uploads(b)
    greedy = b.submit(A, 9)
    sampled = b.submit(B, 5, sampling=SAMPLED)
    b.step()
    rows, table = (3,), (3, 6)
    # after a drain everything goes up: current, pos, the table, the mask
    # of the stepping rows, the three pick settings, the mask of the picked
    assert uploads == [(3, 1), rows, table, rows, rows, rows, rows, rows]
    current, pos = b._in_flight["next"]
    if tp is not None:
        for x in (current, pos, *(dev for _, dev in b._resident.values())):
            assert len(x.sharding.device_set) == tp
            assert x.sharding.is_fully_replicated
    compiled = b._decode._cache_size()
    del uploads[:]
    for _ in range(3):
        b.step()
    assert uploads == []  # current and pos never left the device
    assert b._in_flight["next"][0] is not current
    b.step()  # the sampled row's last step is in flight: it is left out
    # the table (its row to the scratch page), the mask of the stepping
    # rows, the mask of the picked; not current, not pos
    assert uploads == [table, rows, rows]
    del uploads[:]
    b.step()
    b.step()
    assert uploads == []
    # the arrays the device left and the ones the host uploaded are the
    # same kind of argument: no other decode program was compiled for them
    # (nor, under a mesh, for the pool a step hands back: the allocator's
    # pool carries the sharding spec a program's output does,
    # ``_pool_sharding``)
    late = b.submit(D, 3)
    b.run_to_completion()
    assert b._decode._cache_size() == compiled
    unsharded = ContinuousBatcher(
        params, config, max_batch=3, n_pages=32, page_size=4,
        max_pages_per_seq=6,
    )
    want = [
        unsharded.submit(A, 9), unsharded.submit(B, 5, sampling=SAMPLED),
    ]
    unsharded.run_to_completion()
    assert b.result(greedy) == unsharded.result(want[0])
    assert b.result(sampled) == unsharded.result(want[1])
    want_late = unsharded.submit(D, 3)
    unsharded.run_to_completion()
    assert b.result(late) == unsharded.result(want_late)


def test_a_failed_dispatch_leaves_the_step_in_flight_to_be_landed():
    b, r = two_steps_in()
    in_flight, real = b._in_flight, b._decode

    def boom(*a, **kw):
        raise RuntimeError("INTERNAL: device lost")

    b._decode = boom
    with pytest.raises(RuntimeError, match="device lost"):
        b.step()
    assert b._in_flight is in_flight and len(b.results[r]) == 2
    b._decode = real
    b.run_to_completion()
    assert b.result(r) == solo(tuple(A), 8)[0]
