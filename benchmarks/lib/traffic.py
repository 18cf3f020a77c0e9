"""The one traffic generator: a mix is a file of parameters, never code.

A mix (``benchmarks/traffic/<mix>.json``) gives ``loop`` (``closed``: each
of ``clients`` callers holds one request in flight and sends the next when
it returns), the discrete sets of prompt and output lengths with their
weights, the share of callers that sample, and the sampling parameters.
Lengths come from small sets because every distinct padded prompt width is
its own prefill program today (ROADMAP S5): the harness warms one request
of each length.

Everything is drawn from the seed. Lengths are not drawn one by one: the
callers of a run deal them from one shuffled deck of ``deck`` cards that
holds each length in proportion to its weight (and is shuffled anew when it
runs out), so that any stretch of a run carries the stated mix and two
seeds differ in order, not in the amount of work. One deck for all callers,
because a caller finishes only a request or two in a window: with a deck
each, the requests of a window would again be independent draws, and a
median over some thirty of them flips between two lengths. Who draws next
is decided by which request finishes, and that is fixed by the lengths
already drawn, not by the clock, so a seed gives one schedule. Which
callers sample is fixed by their index, so the number of sampled rows in
the batch (host work per step) does not wander either.
"""

from __future__ import annotations

import dataclasses
import json
from pathlib import Path

import numpy as np

LOOPS_IMPLEMENTED = ("closed",)


class TrafficError(ValueError):
    """A mix file asks for something the generator cannot do."""


@dataclasses.dataclass(frozen=True)
class Request:
    client: int
    index: int  # this caller's n-th request
    prompt: np.ndarray  # [L] int32
    max_new_tokens: int
    sampled: bool
    seed: int


def load_mix(path: str | Path) -> dict:
    mix = json.loads(Path(path).read_text())
    loop = mix.get("loop")
    if loop not in LOOPS_IMPLEMENTED:
        raise TrafficError(
            f"traffic mix {mix.get('name', path)!r} asks for loop {loop!r}; "
            f"the generator implements {list(LOOPS_IMPLEMENTED)} (an open "
            "loop needs a benchmark PR that adds it)"
        )
    for key in ("prompt_tokens", "output_tokens"):
        values, weights = mix[key]["values"], mix[key]["weights"]
        if len(values) != len(weights) or not values:
            raise TrafficError(f"mix {mix['name']!r}: {key} values/weights")
        if abs(sum(weights) - 1.0) > 1e-6:
            raise TrafficError(f"mix {mix['name']!r}: {key} weights sum")
    if mix["clients"] < 1 or mix["deck"] < 1:
        raise TrafficError(f"mix {mix['name']!r}: clients and deck must be >= 1")
    return mix


def deck_of(values: list[int], weights: list[float], size: int) -> list[int]:
    """``size`` cards holding each value in proportion to its weight
    (largest remainders make up the rounding)."""
    exact = [w * size for w in weights]
    counts = [int(x) for x in exact]
    by_remainder = sorted(
        range(len(values)), key=lambda i: (exact[i] - counts[i], -i),
        reverse=True,
    )
    for i in by_remainder[: size - sum(counts)]:
        counts[i] += 1
    return [v for v, n in zip(values, counts) for _ in range(n)]


def is_sampled(client: int, share: float) -> bool:
    """Caller ``client`` samples iff the running count of sampled callers
    steps at its index: share 0.5 gives every other caller."""
    return int((client + 1) * share) > int(client * share)


class Deck:
    """Cards dealt without replacement; reshuffled from the seed when out."""

    def __init__(self, values, weights, size: int, rng) -> None:
        self.cards = deck_of(values, weights, size)
        self.rng = rng
        self.hand: list[int] = []

    def draw(self) -> int:
        if not self.hand:
            self.hand = list(self.cards)
            self.rng.shuffle(self.hand)
        return self.hand.pop()


def client_streams(mix: dict, seed: int, vocab_size: int) -> list:
    """One endless stream of requests per caller, all dealing prompt and
    output lengths from the run's two decks."""
    prompts = Deck(
        **mix["prompt_tokens"], size=mix["deck"],
        rng=np.random.default_rng([seed, 1]),
    )
    outputs = Deck(
        **mix["output_tokens"], size=mix["deck"],
        rng=np.random.default_rng([seed, 2]),
    )
    lo, hi = mix.get("first_budget_fraction", [1.0, 1.0])

    def stream(client: int):
        rng = np.random.default_rng([seed, 3, client])
        sampled = is_sampled(client, mix["sampled_share"])
        index = 0
        while True:
            budget = outputs.draw()
            if index == 0:
                # callers start out of step: the first answer is cut short
                budget = max(2, int(budget * rng.uniform(lo, hi)))
            yield Request(
                client=client,
                index=index,
                prompt=rng.integers(
                    0, vocab_size, prompts.draw(), dtype=np.int32
                ),
                max_new_tokens=int(budget),
                sampled=sampled,
                seed=int(rng.integers(0, 2**31 - 1)),
            )
            index += 1

    return [stream(client) for client in range(mix["clients"])]


def prompt_lengths(mix: dict) -> list[int]:
    return sorted(set(mix["prompt_tokens"]["values"]))


def longest_request(mix: dict) -> int:
    return max(mix["prompt_tokens"]["values"]) + max(
        mix["output_tokens"]["values"]
    )
