"""The closed-loop driver: N callers, each with one request in flight.

Everything is timed at the ``Engine`` boundary, on the host clock, by the
harness itself: a caller's request is stamped when ``engine.submit`` is
called, and after every ``engine.step()`` the clock is stamped once and
``engine.new_tokens(ticket)`` is read for every live ticket. What a ticket
returns there is a delivery: the tokens a streaming caller would have
received at that stamp. The program's own counters are not read here.

One thread, one loop: submit for idle callers, step, poll. The three parts
run under ``jax.profiler.TraceAnnotation`` spans (``bench.submit``,
``bench.engine_step``, ``bench.poll``), which cost nothing while no
profiler is on and put the host's side on the device trace's clock when
one is.
"""

from __future__ import annotations

import dataclasses
import math
import time
from contextlib import nullcontext

import numpy as np

SPAN_SUBMIT = "bench.submit"
SPAN_STEP = "bench.engine_step"
SPAN_POLL = "bench.poll"


@dataclasses.dataclass
class Flight:
    """One request's life as the caller saw it."""

    request: object  # traffic.Request
    ticket: int
    logprobs: bool
    t_submit: float
    t_first: float | None = None
    first_step: int | None = None
    deliveries: list = dataclasses.field(default_factory=list)  # (t, k)
    n_tokens: int = 0
    t_done: float | None = None
    finish: str | None = None
    error: str | None = None
    tokens: list | None = None  # kept for finished greedy requests only


@dataclasses.dataclass
class Step:
    index: int
    t_start: float
    t_end: float
    admitted_prompt_tokens: list  # prompt lengths first delivered here
    delivered: int  # tokens delivered at this stamp
    live: int  # requests in flight during the step


def sampling_for(request, mix: dict, sampling_cls):
    """The program's SamplingParams for one generated request: sampled
    callers draw at the mix's temperature and top-p and record log
    probabilities; greedy callers record them on every other request, so
    that both the device-argmax and the host-logits paths stay in use."""
    if request.sampled:
        return sampling_cls(
            temperature=mix["sampling"]["temperature"],
            top_p=mix["sampling"].get("top_p"),
            seed=request.seed,
            logprobs=True,
        )
    return sampling_cls(logprobs=request.index % 2 == 0)


class ClosedLoop:
    """``clients`` callers over one engine. ``annotate(name, **kw)`` returns
    a context manager (``jax.profiler.TraceAnnotation`` in a run; the
    default does nothing, so the driver itself never imports jax)."""

    def __init__(self, engine, streams, mix: dict, sampling_cls,
                 vocab_size: int, clock=time.perf_counter, annotate=None):
        self.engine = engine
        self.streams = streams
        self.mix = mix
        self.sampling_cls = sampling_cls
        self.vocab_size = vocab_size
        self.clock = clock
        self.annotate = annotate or (lambda name, **kw: nullcontext())
        self.live: dict[int, Flight] = {}  # client -> flight
        self.started: set[int] = set()  # callers that have had a first token
        self.finished: list[Flight] = []
        self.steps: list[Step] = []
        self.attempted = 0
        self.failed = 0
        self.problems: list[str] = []

    # ---------------------------------------------------------------- loop

    def submit_idle(self) -> None:
        """Every caller without a request in flight sends its next one."""
        with self.annotate(SPAN_SUBMIT):
            for client, stream in enumerate(self.streams):
                if client in self.live:
                    continue
                request = next(stream)
                sampling = sampling_for(request, self.mix, self.sampling_cls)
                self.attempted += 1
                t_submit = self.clock()
                try:
                    ticket = self.engine.submit(
                        request.prompt, request.max_new_tokens,
                        sampling=sampling,
                    )
                except Exception as e:  # refused at intake: counted, not fatal
                    self.failed += 1
                    self.problems.append(f"client {client} refused: {e!r}")
                    continue
                self.live[client] = Flight(
                    request, ticket, sampling.logprobs, t_submit
                )

    def step(self) -> Step:
        """Submit for idle callers, advance the engine once, read what
        every live ticket delivered."""
        self.submit_idle()
        index = len(self.steps)
        n_live = len(self.live)
        t_start = self.clock()
        with self.annotate(SPAN_STEP, i=index):
            self.engine.step()
        t_end = self.clock()
        admitted: list[int] = []
        delivered = 0
        with self.annotate(SPAN_POLL):
            for client, flight in list(self.live.items()):
                new = self.engine.new_tokens(flight.ticket)
                if new:
                    if flight.t_first is None:
                        flight.t_first = t_end
                        flight.first_step = index
                        self.started.add(client)
                        admitted.append(int(flight.request.prompt.shape[0]))
                    flight.deliveries.append((t_end, len(new)))
                    flight.n_tokens += len(new)
                    delivered += len(new)
                if self.engine.is_done(flight.ticket):
                    self._finish(client, flight, t_end)
        record = Step(index, t_start, t_end, admitted, delivered, n_live)
        self.steps.append(record)
        return record

    def _finish(self, client: int, flight: Flight, t_end: float) -> None:
        """Check (b) of ``correct`` on a finished request, then free it."""
        engine = self.engine
        flight.t_done = t_end
        flight.finish = engine.finish_reason(flight.ticket)
        tokens = engine.result(flight.ticket)
        budget = flight.request.max_new_tokens
        if flight.finish != "length":
            flight.error = (
                f"finished {flight.finish!r}: "
                f"{engine.ticket_error(flight.ticket)}"
            )
        elif len(tokens) != budget or flight.n_tokens != budget:
            flight.error = (
                f"{len(tokens)} tokens in the result and {flight.n_tokens} "
                f"delivered for a budget of {budget}"
            )
        elif not all(0 <= t < self.vocab_size for t in tokens):
            flight.error = "token ids out of range"
        elif flight.logprobs:
            logprobs = engine.result_logprobs(flight.ticket)
            if len(logprobs) != budget or not all(
                math.isfinite(x) and x <= 1e-6 for x in logprobs
            ):
                flight.error = "log probabilities missing or not finite"
        if flight.error is not None:
            self.failed += 1
            self.problems.append(
                f"client {client} request {flight.request.index}: "
                f"{flight.error}"
            )
        elif not flight.request.sampled and not flight.logprobs:
            flight.tokens = list(tokens)  # a candidate for the solo re-run
        engine.release(flight.ticket)
        del self.live[client]
        self.finished.append(flight)

    def drain(self) -> None:
        """The window has closed: cancel what is in flight (not a failure)
        and step until the engine is idle."""
        for flight in self.live.values():
            self.engine.cancel(flight.ticket)
        self.engine.run_to_completion()
        for flight in self.live.values():
            self.engine.release(flight.ticket)
        self.live.clear()


# ------------------------------------------------------------ the arithmetic


def percentile(values, weights, q: float) -> float:
    """The ``q``-th percentile (0-100) of values repeated ``weights``
    times, the lower of the two neighbours (no interpolation: a gap that
    was measured, not a blend of two)."""
    values = np.asarray(values, dtype=np.float64)
    weights = np.asarray(weights, dtype=np.int64)
    order = np.argsort(values, kind="stable")
    cumulative = np.cumsum(weights[order])
    rank = max(1, math.ceil(q / 100.0 * cumulative[-1]))
    return float(values[order][np.searchsorted(cumulative, rank)])


def live_tokens(flights: list[Flight], t: float) -> int:
    """Tokens whose K/V the pool holds for requests decoding at time ``t``:
    each one's prompt and what it had been delivered before ``t``."""
    total = 0
    for f in flights:
        if f.t_first is None or f.t_first >= t:
            continue
        if f.t_done is not None and f.t_done < t:
            continue
        total += int(f.request.prompt.shape[0]) + sum(
            k for at, k in f.deliveries if at < t
        )
    return total


def token_gaps(flights: list[Flight], t_open: float,
               t_close: float) -> list[tuple[float, float, int]]:
    """(previous stamp, stamp, tokens) of every delivery inside the window
    whose request's previous delivery was inside it too: a delivery of k
    tokens is k gaps of (stamp - previous) / k."""
    out = []
    for flight in flights:
        previous = None
        for t, k in flight.deliveries:
            if previous is not None and previous >= t_open and t <= t_close:
                out.append((previous, t, k))
            previous = t
    return out


def itl_ms(gaps: list[tuple[float, float, int]], q: float) -> float:
    """The ``q``-th percentile of the gap between tokens, in ms."""
    return percentile(
        [(t - previous) * 1000.0 / k for previous, t, k in gaps],
        [k for _, _, k in gaps], q,
    )


def end_to_end(flights: list[Flight], t_open: float, t_close: float,
               mix: dict) -> dict:
    """The end-to-end numbers of one window, with their sample counts.

    - ``out_tok_s``: tokens delivered at stamps inside the window over its
      length;
    - ``ttft_ms_p50_mix``: submit to first delivery, over requests
      submitted inside the window whose first token arrived inside it: the
      median of each prompt length of the mix, averaged with the mix's
      weights (over the lengths the window saw). A window holds a few dozen
      requests of three or four lengths whose times lie far apart, so the
      plain median jumps from one length to the next with the order of the
      draw; this does not;
    - ``itl_ms_p50``, ``itl_ms_p95``: over ``token_gaps``.
    """
    tokens = 0
    ttft: dict[int, list[float]] = {}
    for flight in flights:
        if (
            flight.t_first is not None
            and flight.t_submit >= t_open
            and flight.t_first <= t_close
        ):
            ttft.setdefault(int(flight.request.prompt.shape[0]), []).append(
                (flight.t_first - flight.t_submit) * 1000.0
            )
        tokens += sum(k for t, k in flight.deliveries if t_open < t <= t_close)
    gaps = token_gaps(flights, t_open, t_close)
    out = {
        "window_s": t_close - t_open,
        "tokens": tokens,
        "out_tok_s": tokens / (t_close - t_open),
        "n_ttft_by_length": {k: len(v) for k, v in sorted(ttft.items())},
        "n_gaps": sum(k for _, _, k in gaps),
    }
    if ttft:
        weights = dict(zip(
            mix["prompt_tokens"]["values"], mix["prompt_tokens"]["weights"]
        ))
        seen = sum(weights[length] for length in ttft)
        out["ttft_ms_p50_mix"] = sum(
            weights[length] / seen * percentile(xs, [1] * len(xs), 50)
            for length, xs in ttft.items()
        )
    if gaps:
        out["itl_ms_p50"] = itl_ms(gaps, 50)
        out["itl_ms_p95"] = itl_ms(gaps, 95)
    return out
