"""Serving engine: a request queue in front of the continuous batcher.

``ContinuousBatcher`` (models/serving.py) is deliberately mechanism-only:
``submit`` raises when no row or not enough pages are free, and every
example had to hand-roll the same admit-when-capacity-frees loop around
it. This module is that loop as library code:

- ``submit`` ALWAYS accepts (up to an optional queue bound) and returns a
  ticket; admission into the batcher happens inside ``step`` the moment a
  row AND enough pages are free — page-pool exhaustion is backpressure,
  not an error.
- Admission order is (priority desc, arrival order) — a plain FCFS queue
  unless priorities are used. Head-of-line blocking is intentional: a
  large request at the head is not starved by small ones behind it
  (admitting out of order would let it wait forever under load).
- ``new_tokens`` is the STREAMING read: tokens appended since the last
  call for that ticket — poll it between steps to stream a response out.
- ``cancel`` works on queued tickets (dropped before ever touching the
  device, finish reason 'cancelled') and on admitted ones (proxied to the
  batcher, pages freed mid-decode).

The engine is host-side orchestration only — everything the device
executes is still the batcher's fixed-shape programs. The reference has
no serving stack at all (SURVEY §2).
"""

from __future__ import annotations

import heapq
import time
from dataclasses import dataclass

import numpy as np

from bee_code_interpreter_tpu.models.serving import (
    CapacityError,
    ContinuousBatcher,
    SamplingParams,
)


@dataclass
class _Queued:
    prompt: object
    max_new_tokens: int
    sampling: SamplingParams | None
    prefill_chunk: int | None
    adapter: int | None
    pages_needed: int
    interleave_admission: int | None = None
    priority: int = 0  # kept so a preempted ticket requeues in class


class Engine:
    """Queue + admission loop over a ``ContinuousBatcher``.

    ``max_queue`` bounds accepted-but-not-admitted requests (None =
    unbounded); ``submit`` raises RuntimeError at the bound — the one
    overload signal the caller must handle.
    """

    def __init__(self, batcher: ContinuousBatcher,
                 max_queue: int | None = None, metrics=None,
                 monitor=None) -> None:
        self.batcher = batcher
        self.max_queue = max_queue
        # Lifecycle monitor (observability.ServingMonitor): the engine owns
        # the queued/requeued/rejected part of a request's story, the
        # batcher the rest — one monitor sees both. Inherits the batcher's
        # when not given so a single attach() wires the whole stack.
        self._monitor = monitor if monitor is not None else getattr(
            batcher, "_monitor", None
        )
        # ticket -> original request for tickets admitted with interleaved
        # prefill — the only preemptable kind (see preempt); dropped on
        # preempt-resubmit consumption or release().
        self._preemptable: dict[int, _Queued] = {}
        # preempted tickets requeue at the HEAD of their priority class:
        # strictly decreasing negative seqs sort before every arrival seq
        self._front_seq = 0
        # Queue-level instrumentation (docs/observability.md): the batcher
        # covers decode cadence; the engine covers what happens BEFORE a
        # request reaches a batch row — depth, wait, capacity bounce-backs.
        self._metrics = metrics
        self._ticket_submit_t: dict[int, float] = {}
        if metrics is not None:
            self._queue_wait_seconds = metrics.histogram(
                "bci_serving_queue_wait_seconds",
                "Ticket wait from engine submit to batcher admission",
            )
            self._requeues_total = metrics.counter(
                "bci_serving_requeues_total",
                "Admissions bounced back to the queue by a capacity race",
            )
            self._rejected_total = metrics.counter(
                "bci_serving_queue_rejected_total",
                "Submissions rejected at the queue bound",
            )
            metrics.gauge(
                "bci_serving_queue_depth",
                "Accepted-but-not-admitted tickets",
                lambda: len(self._queued),
            )
        # heap entries: (-priority, arrival seq, ticket, request);
        # cancellation of a queued ticket is LAZY — the ticket leaves
        # self._queued and its entry is skipped when it surfaces
        self._heap: list[tuple[int, int, int, _Queued]] = []
        self._next_seq = 0
        self._next_ticket = 0
        # ticket -> batcher request id (admitted), 'queued',
        # 'cancelled', or ('error', msg) for an admission-time failure
        self._state: dict[int, object] = {}
        self._queued: set[int] = set()
        self._stream_cursor: dict[int, int] = {}
        self._holdback: dict[int, int] = {}

    # ----------------------------------------------------- snapshot/resume

    def state_dict(self) -> dict:
        """The engine's full serving state: the batcher snapshot (device
        pool + in-flight rows, serving.ContinuousBatcher.state_dict) plus
        the queue — tickets not yet admitted resume queued, in their
        original (priority, arrival) order. Same persistence caveat as the
        batcher's: pickles unless a request carries callable constraints."""
        import copy

        return {
            "batcher": self.batcher.state_dict(),
            "heap": copy.deepcopy(self._heap),
            "state": copy.deepcopy(self._state),
            "queued": set(self._queued),
            "stream_cursor": dict(self._stream_cursor),
            "holdback": dict(self._holdback),
            "next_seq": self._next_seq,
            "next_ticket": self._next_ticket,
            "preemptable": copy.deepcopy(self._preemptable),
            "front_seq": self._front_seq,
        }

    def load_state_dict(self, state: dict) -> None:
        import copy

        self.batcher.load_state_dict(state["batcher"])
        self._heap = copy.deepcopy(state["heap"])
        heapq.heapify(self._heap)
        self._state = copy.deepcopy(state["state"])
        self._queued = set(state["queued"])
        self._stream_cursor = dict(state["stream_cursor"])
        self._holdback = dict(state["holdback"])
        self._next_seq = state["next_seq"]
        self._next_ticket = state["next_ticket"]
        # .get(): snapshots from before the preemption API lack these keys
        self._preemptable = copy.deepcopy(state.get("preemptable", {}))
        self._front_seq = state.get("front_seq", 0)
        # max_queue is POLICY, not serving state: the receiving engine's
        # configured bound stays (a snapshot must not smuggle in an old
        # overload policy)

    # ------------------------------------------------------------- intake
    def submit(
        self,
        prompt,
        max_new_tokens: int,
        sampling: SamplingParams | None = None,
        prefill_chunk: int | None = None,
        adapter: int | None = None,
        priority: int = 0,
        interleave_admission: int | None = None,
    ) -> int:
        """Accept a request and return a ticket. Everything
        capacity-independent (empty prompt, budget > block table, pages >
        the whole pool, speculative sampling constraints, adapter range)
        fails HERE via the batcher's own ``validate_request`` — a queued
        request must not explode minutes later on an error the caller
        could have seen at submit."""
        prompt = np.asarray(prompt, dtype=np.int32).reshape(-1)
        pages_needed = self.batcher.validate_request(
            prompt, max_new_tokens, sampling=sampling, adapter=adapter,
            interleave_admission=interleave_admission,
            prefill_chunk=prefill_chunk,
        )
        if self.max_queue is not None and len(self._queued) >= self.max_queue:
            if self._metrics is not None:
                self._rejected_total.inc()
            if self._monitor is not None:
                self._monitor.on_ticket_rejected("queue_full")
            raise RuntimeError(f"queue full ({self.max_queue})")
        req = _Queued(
            prompt, max_new_tokens, sampling, prefill_chunk, adapter,
            pages_needed=pages_needed,
            interleave_admission=interleave_admission,
            priority=priority,
        )
        ticket = self._next_ticket
        self._next_ticket += 1
        seq = self._next_seq
        self._next_seq += 1
        heapq.heappush(self._heap, (-priority, seq, ticket, req))
        self._state[ticket] = "queued"
        self._queued.add(ticket)
        self._stream_cursor[ticket] = 0
        # streaming holdback: while the request is live, the last
        # (max stop length - 1) tokens stay unstreamed — a stop sequence
        # completing later would TRIM tokens the stream had already
        # emitted otherwise. At retirement the remainder flushes post-trim.
        stops = sampling.stop_sequences if sampling is not None else ()
        self._holdback[ticket] = max((len(s) for s in stops), default=1) - 1
        if self._metrics is not None:
            self._ticket_submit_t[ticket] = time.monotonic()
        if self._monitor is not None:
            self._monitor.on_ticket_queued(ticket)
        return ticket

    def set_monitor(self, monitor) -> None:
        """Attach a lifecycle monitor (ServingMonitor.attach calls this)."""
        self._monitor = monitor

    # -------------------------------------------------------------- admit
    def _admit_ready(self) -> None:
        while self._heap:
            neg_prio, seq, ticket, req = self._heap[0]
            if ticket not in self._queued:  # cancelled while queued
                heapq.heappop(self._heap)
                continue
            if not self.batcher.has_free_row():
                return
            # page backpressure: strictly FCFS-within-priority — the head
            # waits for ITS pages; smaller requests behind it do not jump.
            # Prefix-cache credit counts: pages the submission would REUSE
            # (held by sharing rows or parked) need no fresh allocation,
            # so ignoring them would stall admissions the batcher accepts.
            available = (
                len(self.batcher.free_pages) + len(self.batcher.evictable)
            )
            fresh_needed = req.pages_needed - self.batcher.prefix_credit(
                req.prompt, req.adapter
            )
            if fresh_needed > available:
                return
            heapq.heappop(self._heap)
            self._queued.discard(ticket)
            if self._monitor is not None:
                # BEFORE the submit: the monitor stages this ticket's queue
                # wait so the lifecycle record born inside the call starts
                # its clock at engine intake (blocking admission fixes TTFT
                # before submit returns)
                self._monitor.on_ticket_admitting(ticket)
            try:
                rid = self.batcher.submit(
                    req.prompt, req.max_new_tokens, sampling=req.sampling,
                    prefill_chunk=req.prefill_chunk, adapter=req.adapter,
                    interleave_admission=req.interleave_admission,
                )
            except CapacityError:
                # capacity race (e.g. prefix-matched pages changed the
                # arithmetic): put it back and stop admitting this step.
                # Only the batcher's own backpressure signal requeues —
                # a bare RuntimeError here could be jaxlib's
                # XlaRuntimeError (device OOM/failure during admission
                # prefill), which must become an error ticket below, not
                # an infinite requeue loop against a failing device.
                heapq.heappush(self._heap, (neg_prio, seq, ticket, req))
                self._queued.add(ticket)
                if self._metrics is not None:
                    self._requeues_total.inc()
                if self._monitor is not None:
                    self._monitor.on_ticket_requeued(ticket)
                return
            except Exception as e:
                # validate_request ran at intake, so this "cannot happen";
                # if it does anyway (validation drift), fail the ticket
                # loudly-but-locally instead of wedging it in 'queued'
                # forever and taking the whole step loop down
                self._state[ticket] = ("error", repr(e))
                self._ticket_submit_t.pop(ticket, None)
                if self._monitor is not None:
                    self._monitor.on_ticket_failed(ticket, repr(e))
                continue
            self._state[ticket] = rid
            if req.interleave_admission is not None:
                # only interleaved admissions are preemptable mid-prefill;
                # keep the request so preempt() can requeue it verbatim
                self._preemptable[ticket] = req
            if self._metrics is not None:
                t0 = self._ticket_submit_t.pop(ticket, None)
                if t0 is not None:
                    self._queue_wait_seconds.observe(time.monotonic() - t0)

    # --------------------------------------------------------------- step
    def step(self) -> None:
        """Admit whatever fits, then advance the batch one round.

        The batcher's plain step runs one step ahead of the host
        (``ContinuousBatcher.step``): this call returns with the step it
        dispatched still in flight on the device, having landed the one
        before, so the tokens that step picks are read (``new_tokens``,
        ``partial_result``, ``is_done``) after the NEXT ``step``. An
        admission lands the step in flight before it takes its row, so a
        request's first token is readable when the ``step`` that admitted
        it returns, as before. Rows and pages a step frees are seen by the
        admission after this call's, or by the next call's."""
        self._admit_ready()
        self.batcher.step()
        self._admit_ready()  # rows/pages freed by retirements this step

    def run_to_completion(self, max_steps: int = 100_000) -> None:
        """``step`` until nothing is queued and the batcher is idle, which
        it is not while a step is in flight: every token is landed at the
        end."""
        for _ in range(max_steps):
            if not self._queued and not self.batcher.busy:
                return
            self.step()
        raise RuntimeError("run_to_completion exceeded max_steps")

    @property
    def pending(self) -> int:
        """Accepted-but-not-admitted request count (queue depth)."""
        return len(self._queued)

    @property
    def stats(self) -> dict:
        """The batcher's operator counters plus queue depth, with the
        request counts at TICKET level (a queued ticket exists before the
        batcher ever sees it)."""
        st = {**self.batcher.stats, "queued": len(self._queued)}
        st["requests_submitted"] = len(self._state)
        st["requests_finished"] = sum(
            1 for t in self._state if self.is_done(t)
        )
        return st

    # ------------------------------------------------------------ results
    def _rid(self, ticket: int):
        if ticket not in self._state:
            raise KeyError(f"unknown ticket {ticket}")
        return self._state[ticket]

    def is_done(self, ticket: int) -> bool:
        rid = self._rid(ticket)
        if rid == "queued":
            return False
        if rid == "cancelled" or isinstance(rid, tuple):
            return True
        return self.batcher.is_done(rid)

    def result(self, ticket: int) -> list[int]:
        rid = self._rid(ticket)
        if rid == "queued":
            raise RuntimeError(f"ticket {ticket} still queued")
        if rid == "cancelled" or isinstance(rid, tuple):
            return []
        return self.batcher.result(rid)

    def result_logprobs(self, ticket: int) -> list[float]:
        rid = self._rid(ticket)
        if rid == "queued":
            raise RuntimeError(f"ticket {ticket} still queued")
        if rid == "cancelled" or isinstance(rid, tuple):
            return []
        return self.batcher.result_logprobs(rid)

    def finish_reason(self, ticket: int) -> str:
        rid = self._rid(ticket)
        if rid == "queued":
            raise RuntimeError(f"ticket {ticket} still queued")
        if rid == "cancelled":
            return "cancelled"
        if isinstance(rid, tuple):
            return "error"
        return self.batcher.finish_reason(rid)

    def ticket_error(self, ticket: int) -> str | None:
        """repr of an admission-time failure (finish reason 'error' from
        the engine itself) or the batcher's recorded callable error."""
        rid = self._rid(ticket)
        if isinstance(rid, tuple):
            return rid[1]
        if rid in ("queued", "cancelled"):
            return None
        return self.batcher.request_error(rid)

    def partial_result(self, ticket: int) -> list[int]:
        """Tokens generated so far — safe at ANY time (empty while queued,
        after cancellation of queued work, on an admission failure, or
        after release). The streaming and text layers build on this
        instead of poking at internal state."""
        rid = self._rid(ticket)
        if rid in ("queued", "cancelled") or isinstance(rid, tuple):
            return []
        return list(self.batcher.results.get(rid, ()))

    def new_tokens(self, ticket: int) -> list[int]:
        """STREAMING read: tokens appended for this ticket since the last
        ``new_tokens`` call (empty while queued). Poll between steps to
        stream a response: a poll reads what has LANDED, which after a
        ``step`` is everything but the token of the step that call left in
        flight (one token a poll in the steady state, each a step after the
        device picked it); the final chunk lands with the ``step`` that
        finishes the request (``is_done`` turns true in the same call), or
        with whatever lands the step in flight sooner (``cancel``,
        ``state_dict``, an admission). While the request is live, the last
        (max stop length - 1) tokens are held back so a stop sequence
        completing later can never trim a token the stream already
        emitted — the stream's concatenation always equals ``result``."""
        tokens = self.partial_result(ticket)
        if not tokens:
            return []
        limit = (
            len(tokens) if self.is_done(ticket)
            else max(0, len(tokens) - self._holdback[ticket])
        )
        cursor = self._stream_cursor[ticket]
        if limit <= cursor:
            return []
        self._stream_cursor[ticket] = limit
        return list(tokens[cursor:limit])

    def preempt(self, ticket: int) -> bool:
        """Evict an admitted ticket whose INTERLEAVED prefill hasn't
        produced a token yet, back to the HEAD of its priority class (it
        already earned its pages once; making it re-race arrivals would
        starve long prompts under load). The batcher frees its pages and
        forgets the old request id; re-admission recomputes the prefill —
        exact, because nothing was emitted. Returns False for queued,
        finished, decoding (use :meth:`cancel` to stop those and keep their
        partial output) or blocking-admitted tickets; an unknown ticket
        raises KeyError — the same contract as :meth:`result`."""
        rid = self._rid(ticket)
        if not isinstance(rid, int):
            return False
        req = self._preemptable.pop(ticket, None)
        if req is None or not self.batcher.preempt(rid):
            return False
        self._front_seq -= 1
        heapq.heappush(
            self._heap, (-req.priority, self._front_seq, ticket, req)
        )
        self._queued.add(ticket)
        self._state[ticket] = "queued"
        self._stream_cursor[ticket] = 0
        if self._metrics is not None:
            # queue wait re-measures from the preemption, matching the
            # monitor's fresh queued clock below
            self._ticket_submit_t[ticket] = time.monotonic()
        if self._monitor is not None:
            self._monitor.on_ticket_queued(ticket)
        return True

    def cancel(self, ticket: int) -> None:
        """Cancel queued (never touches the device) or admitted (pages
        freed mid-decode) work; racing completion is a no-op, and the step
        in flight, landed first, may be what completes it."""
        rid = self._rid(ticket)
        if rid == "queued":
            self._queued.discard(ticket)  # heap entry skipped lazily
            self._state[ticket] = "cancelled"
            self._stream_cursor.pop(ticket, None)
            self._holdback.pop(ticket, None)
            self._ticket_submit_t.pop(ticket, None)
            if self._monitor is not None:
                self._monitor.on_ticket_cancelled(ticket)
            return
        if rid != "cancelled" and not isinstance(rid, tuple):
            self.batcher.cancel(rid)

    def release(self, ticket: int) -> None:
        rid = self._rid(ticket)
        if rid == "queued":
            raise RuntimeError(f"ticket {ticket} still queued")
        if rid != "cancelled" and not isinstance(rid, tuple):
            self.batcher.release(rid)
        self._stream_cursor.pop(ticket, None)
        self._holdback.pop(ticket, None)
        self._preemptable.pop(ticket, None)
