"""The traffic generator and the closed-loop driver's arithmetic
(benchmarks/lib/traffic.py, driver.py): what is drawn from the seed, and
how deliveries become the end-to-end numbers."""

from __future__ import annotations

import itertools
from collections import Counter
from pathlib import Path

import numpy as np
import pytest

from benchmarks.lib import driver, traffic

MIXES = Path(__file__).resolve().parents[2] / "benchmarks" / "traffic"


@pytest.mark.parametrize("name", ["chat", "docqa"])
def test_a_deck_holds_the_mix_in_proportion(name):
    mix = traffic.load_mix(MIXES / f"{name}.json")
    for key in ("prompt_tokens", "output_tokens"):
        deck = traffic.deck_of(**mix[key], size=mix["deck"])
        assert len(deck) == mix["deck"]
        for value, weight in zip(mix[key]["values"], mix[key]["weights"]):
            assert deck.count(value) == round(weight * mix["deck"])


def test_deck_rounding_goes_to_the_largest_remainders():
    assert Counter(traffic.deck_of([1, 2, 3], [0.5, 0.25, 0.25], 5)) == {1: 3, 2: 1, 3: 1}
    assert traffic.deck_of([7], [1.0], 3) == [7, 7, 7]


def deal(mix, seed, order):
    """The requests callers get when they draw in ``order``."""
    streams = traffic.client_streams(mix, seed, 32000)
    return [next(streams[c]) for c in order]


@pytest.mark.parametrize("name", ["chat", "docqa"])
def test_streams_are_drawn_from_the_seed(name):
    mix = traffic.load_mix(MIXES / f"{name}.json")
    order = [c % mix["clients"] for c in range(60)]
    a, b = deal(mix, 7, order), deal(mix, 7, order)
    assert [r.max_new_tokens for r in a] == [r.max_new_tokens for r in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    assert [r.seed for r in a] == [r.seed for r in b]
    other = deal(mix, 8, order)
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in other]
    assert not np.array_equal(a[0].prompt[:64], a[1].prompt[:64])  # callers differ
    assert all(0 <= r.prompt.min() and r.prompt.max() < 32000 for r in a)
    assert [r.client for r in a[:3]] == [0, 1, 2]
    assert a[mix["clients"]].index == 1 and a[0].index == 0


@pytest.mark.parametrize("name", ["chat", "docqa"])
def test_all_callers_deal_from_one_deck(name):
    """Whoever draws, any 20 consecutive draws after a deck's start carry
    the stated mix: a window's few dozen requests are not independent."""
    mix = traffic.load_mix(MIXES / f"{name}.json")
    rng = np.random.default_rng(5)
    order = [int(c) for c in rng.integers(0, mix["clients"], 60)]
    dealt = deal(mix, 3, order)
    for start in (0, 20, 40):
        lengths = Counter(len(r.prompt) for r in dealt[start:start + 20])
        for value, weight in zip(
            mix["prompt_tokens"]["values"], mix["prompt_tokens"]["weights"]
        ):
            assert lengths[value] == round(weight * 20)
    # output budgets too, on every request that is not a caller's first
    later = [r.max_new_tokens for r in dealt[20:40] if r.index > 0]
    assert set(later) <= set(mix["output_tokens"]["values"])


def test_first_answer_is_cut_so_that_callers_start_out_of_step():
    mix = traffic.load_mix(MIXES / "chat.json")
    firsts = [r.max_new_tokens for r in deal(mix, 1, range(mix["clients"]))]
    assert len(set(firsts)) > mix["clients"] // 2
    assert all(2 <= f <= max(mix["output_tokens"]["values"]) for f in firsts)


def test_which_callers_sample_is_fixed_by_their_index():
    assert [traffic.is_sampled(c, 0.5) for c in range(6)] == [False, True] * 3
    assert sum(traffic.is_sampled(c, 0.25) for c in range(32)) == 8
    assert not any(traffic.is_sampled(c, 0.0) for c in range(8))
    assert all(traffic.is_sampled(c, 1.0) for c in range(8))


def test_mix_helpers():
    mix = traffic.load_mix(MIXES / "docqa.json")
    assert traffic.prompt_lengths(mix) == [512, 1024, 2048, 4096]
    assert traffic.longest_request(mix) == 4096 + 128


def test_a_malformed_mix_is_refused(tmp_path):
    import json

    mix = json.loads((MIXES / "chat.json").read_text())
    mix["prompt_tokens"]["weights"] = [0.5, 0.5, 0.5]
    path = tmp_path / "bad.json"
    path.write_text(json.dumps(mix))
    with pytest.raises(traffic.TrafficError, match="weights sum"):
        traffic.load_mix(path)


# ----------------------------------------------------- the driver's numbers


def flight(t_submit, deliveries, prompt=8):
    request = traffic.Request(0, 0, np.zeros(prompt, np.int32), 99, False, 0)
    f = driver.Flight(request, 0, False, t_submit)
    f.deliveries = list(deliveries)
    f.t_first = deliveries[0][0] if deliveries else None
    f.n_tokens = sum(k for _, k in deliveries)
    return f


def test_percentile_is_a_measured_value_weighted_by_count():
    assert driver.percentile([10, 20, 30, 40], [1, 1, 1, 1], 50) == 20
    assert driver.percentile([10, 20, 30, 40], [1, 1, 1, 1], 99) == 40
    assert driver.percentile([40, 10], [1, 9], 90) == 10
    assert driver.percentile([40, 10], [1, 9], 91) == 40
    assert driver.percentile([5], [3], 50) == 5


MIX = {"prompt_tokens": {"values": [8, 16, 32], "weights": [0.5, 0.25, 0.25]}}


def test_end_to_end_numbers_by_hand():
    flights = [
        # submitted before the window: no TTFT sample; its gaps count once
        # both ends are inside: (12-11)/1 and (14-12)/2 twice
        flight(5.0, [(9.0, 2), (11.0, 1), (12.0, 1), (14.0, 2)]),
        # submitted inside: TTFT 1.5 s; one gap of 2 s
        flight(10.5, [(12.0, 2), (14.0, 1)]),
        # first token after the window closed: no TTFT sample, no tokens
        flight(19.0, [(21.0, 2)]),
    ]
    out = driver.end_to_end(flights, t_open=10.0, t_close=20.0, mix=MIX)
    assert out["tokens"] == 1 + 1 + 2 + 2 + 1
    assert out["out_tok_s"] == 0.7
    assert out["n_ttft_by_length"] == {8: 1}
    assert out["ttft_ms_p50_mix"] == 1500.0  # one length seen: its median
    # gaps in ms: 1000 (x1), 1000 (x2, a delivery of two), 2000 (x1); the
    # gap from 9.0 to 11.0 starts before the window and is left out
    assert out["n_gaps"] == 4
    assert out["itl_ms_p50"] == 1000.0 and out["itl_ms_p95"] == 2000.0
    gaps = driver.token_gaps(flights, 10.0, 20.0)
    assert sorted(gaps) == [(11.0, 12.0, 1), (12.0, 14.0, 1), (12.0, 14.0, 2)]
    assert driver.itl_ms(gaps, 99) == 2000.0 and driver.itl_ms(gaps, 75) == 1000.0


def test_the_recorded_tail_leaves_out_the_gaps_the_profiler_stalled():
    """``itl_ms_p99_rec`` is printed by traced runs: the gaps that touch
    the profiler's span (its start and stop stall the loop) are not the
    caller's, and are left out."""
    import importlib.util
    from pathlib import Path

    from benchmarks.lib import harness

    path = Path(driver.__file__).parents[1] / "layer_metrics" / "itl_ms_p99_rec.py"
    spec = importlib.util.spec_from_file_location("_reader_itl", path)
    reader = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(reader)
    # a token every 0.1 s, but 0.3 s once; the profiler starts at 5.05 (a
    # stall of 1.5 s), traces to 8.0, and its stop stalls the step after
    stamps = [0.1 * i for i in range(1, 31)] + [3.3] + [3.3 + 0.1 * i for i in range(1, 18)]
    stamps += [6.5 + 0.1 * i for i in range(16)] + [9.0] + [9.0 + 0.1 * i for i in range(1, 20)]
    run = harness.RunData(
        cfg={}, chips=1, peaks=None, memory_peak_bytes=0, e2e={},
        window=(0.0, 11.0), flights=[flight(0.0, [(t, 1) for t in stamps])],
        loop_steps=[], steps=[], compiles_in_window=0,
    )
    assert reader.read(run) == pytest.approx(1500.0)  # untraced: every gap
    run.profiler_span = (5.05, 9.0)
    assert reader.read(run) == pytest.approx(300.0)
    run.flights = []
    assert reader.read(run) is None


def test_time_to_first_token_is_a_median_per_length_weighted_by_the_mix():
    flights = (
        [flight(10.0, [(10.0 + ms / 1000, 1)], prompt=8) for ms in (100, 110, 400)]
        + [flight(11.0, [(11.0 + ms / 1000, 1)], prompt=16) for ms in (200, 220)]
        + [flight(12.0, [(12.9, 1)], prompt=32)]
    )
    out = driver.end_to_end(flights, t_open=9.0, t_close=20.0, mix=MIX)
    assert out["n_ttft_by_length"] == {8: 3, 16: 2, 32: 1}
    # 0.5 * 110 + 0.25 * 200 + 0.25 * 900 (the plain median would be 200)
    assert out["ttft_ms_p50_mix"] == pytest.approx(330.0)
    # a length the window did not see gives its weight to the others
    out = driver.end_to_end(flights[:5], t_open=9.0, t_close=20.0, mix=MIX)
    assert out["ttft_ms_p50_mix"] == pytest.approx((0.5 * 110 + 0.25 * 200) / 0.75)


def test_live_tokens_are_prompts_plus_what_was_delivered_before():
    a = flight(0.0, [(1.0, 2), (2.0, 1), (3.0, 1)], prompt=100)
    b = flight(0.0, [(2.5, 2)], prompt=50)
    b.t_done = 2.5
    assert driver.live_tokens([a, b], 0.5) == 0
    assert driver.live_tokens([a, b], 2.2) == 100 + 3
    assert driver.live_tokens([a, b], 2.6) == 100 + 3  # b was done at 2.5
    assert driver.live_tokens([a, b], 3.5) == 100 + 4


class FakeEngine:
    """An engine that admits at once and emits one token a step: the
    driver's bookkeeping without a model."""

    def __init__(self):
        self.tickets = {}
        self.steps = 0

    def submit(self, prompt, max_new_tokens, sampling=None):
        if len(prompt) > 1000:
            raise ValueError("too long")
        ticket = len(self.tickets)
        self.tickets[ticket] = {"budget": max_new_tokens, "out": [], "read": 0,
                                "logprobs": sampling.logprobs}
        return ticket

    def step(self):
        self.steps += 1
        for t in self.tickets.values():
            if len(t["out"]) < t["budget"] and not t.get("cancelled"):
                # the first step delivers the prefill's token and one more
                t["out"] += [1] * min(2 if not t["out"] else 1, t["budget"] - len(t["out"]))

    def new_tokens(self, ticket):
        t = self.tickets[ticket]
        new = t["out"][t["read"]:]
        t["read"] = len(t["out"])
        return new

    def is_done(self, ticket):
        t = self.tickets[ticket]
        return len(t["out"]) >= t["budget"] or t.get("cancelled", False)

    def finish_reason(self, ticket):
        return "cancelled" if self.tickets[ticket].get("cancelled") else "length"

    def result(self, ticket):
        return list(self.tickets[ticket]["out"])

    def result_logprobs(self, ticket):
        return [-1.0] * len(self.tickets[ticket]["out"])

    def ticket_error(self, ticket):
        return None

    def release(self, ticket):
        self.tickets[ticket]["released"] = True

    def cancel(self, ticket):
        self.tickets[ticket]["cancelled"] = True

    def run_to_completion(self):
        pass


class Sampling:
    def __init__(self, temperature=0.0, top_p=None, seed=0, logprobs=False):
        self.temperature, self.logprobs = temperature, logprobs


def small_mix(prompts=(8, 16)):
    return {
        "name": "t", "loop": "closed", "clients": 3, "deck": 4,
        "prompt_tokens": {"values": list(prompts), "weights": [0.5, 0.5]},
        "output_tokens": {"values": [4, 6], "weights": [0.5, 0.5]},
        "sampled_share": 0.34, "sampling": {"temperature": 1.0, "top_p": 0.9},
        "first_budget_fraction": [1.0, 1.0],
    }


def closed_loop(mix, engine):
    ticks = itertools.count()
    streams = traffic.client_streams(mix, 1, 50)
    return driver.ClosedLoop(
        engine, streams, mix, Sampling, 50, clock=lambda: float(next(ticks))
    )


def test_closed_loop_keeps_one_request_in_flight_per_caller():
    engine = FakeEngine()
    loop = closed_loop(small_mix(), engine)
    for _ in range(12):
        loop.step()
        assert len(loop.live) <= 3
    assert engine.steps == 12 and len(loop.steps) == 12
    # every finished request delivered its whole budget, first two at once
    assert loop.failed == 0 and loop.problems == []
    assert len(loop.finished) >= 6
    for f in loop.finished:
        assert f.n_tokens == f.request.max_new_tokens and f.finish == "length"
        assert f.deliveries[0][1] == 2 and f.first_step is not None
    assert loop.attempted == len(loop.finished) + len(loop.live)
    # a caller's next request is submitted on the step after its last ended
    first = loop.steps[0]
    assert sorted(first.admitted_prompt_tokens) == sorted(
        len(f.request.prompt)
        for f in loop.finished + list(loop.live.values()) if f.first_step == 0
    )
    assert first.live == 3 and first.delivered == 6
    # only greedy requests that recorded no log-probabilities keep tokens
    assert all(
        (f.tokens is not None) == (not f.request.sampled and not f.logprobs)
        for f in loop.finished
    )
    loop.drain()
    assert loop.live == {} and loop.failed == 0  # cancelled is not failed


def test_a_refused_or_short_request_counts_as_failed():
    engine = FakeEngine()
    loop = closed_loop(small_mix(prompts=(8, 2000)), engine)
    for _ in range(4):
        loop.step()
    assert loop.failed > 0 and any("refused" in p for p in loop.problems)
    assert loop.attempted > loop.failed

    class Short(FakeEngine):
        def finish_reason(self, ticket):
            return "error"

    loop = closed_loop(small_mix(), Short())
    for _ in range(8):
        loop.step()
    assert loop.failed == len(loop.finished) > 0
    assert all("finished 'error'" in p for p in loop.problems)
