"""The comparison that decides part (a) of ``correct``: the system against
the plain reference, on the chip, outside the timed window.

``Engine`` hands out tokens and the log-probability of each emitted token
under the raw float32 logits row, not the rows themselves. So the check
runs seeded requests through the normal path (admission, the paged pool,
the decode program at the cell's batch shape), then gives each request's
whole sequence to the reference and compares, position by position, the
log-probability the system reported with the one the reference's logits
give the same token. A greedy request's tokens must also be the
reference's argmax up to a margin: with random weights the largest logit
changes on rounding, so tokens are never compared for equality. A sampled
request at a high temperature spreads its tokens over the vocabulary, so
the probes are not all at the top of the row.

Three things are held, to the numbers of the reference's ``TOLERANCE``
(each with its reason and the chip readings behind it, there): the median
difference over all positions, which is what tells a coarser arithmetic
from the stated one; how many single positions are out; and, where the
architecture makes a discrete choice that rounding can flip (an expert
router), where they are. The reference gives its own margin for that choice
(``tie_gap``: its router's logit for the last kept expert less the first
dropped one's, the least over the layers). A flip needs a small margin, so
positions that are out are counted apart where the margin was small and
where it was not, and the second count is held near zero: a fault that
strikes positions without regard to the router shows there.
"""

from __future__ import annotations

import numpy as np

NEW_TOKENS = 9  # the prefill's token and 8 decode steps
SAMPLED_TEMPERATURE = 1.5


def log_softmax_at(row: np.ndarray, token: int) -> float:
    row = row.astype(np.float64)
    top = row.max()
    return float(row[token] - top - np.log(np.exp(row - top).sum()))


def reference_input(prompt, generated) -> np.ndarray:
    """The sequence whose logits predict every generated token: the prompt
    and all generated tokens but the last."""
    return np.concatenate(
        [np.asarray(prompt, np.int32), np.asarray(generated[:-1], np.int32)]
    )


def compare(reference_logits: np.ndarray, prompt_len: int, generated,
            system_logprobs, greedy: bool, tie_gaps=None) -> dict:
    """One request against the reference, position by position.
    ``reference_logits`` [L + n - 1, vocab] for ``reference_input``;
    position ``prompt_len - 1 + j`` predicts ``generated[j]``. Gives the
    absolute difference of the log-probabilities, for a greedy request how
    far below the reference's best logit each chosen token lies, and the
    reference's ``tie_gaps`` [L + n - 1] at the same positions (None for a
    reference that makes no discrete choice)."""
    if len(system_logprobs) != len(generated):
        raise ValueError(
            f"{len(system_logprobs)} log-probabilities for "
            f"{len(generated)} tokens"
        )
    logprob_diff, margin = [], []
    for j, (token, system) in enumerate(zip(generated, system_logprobs)):
        row = np.asarray(reference_logits[prompt_len - 1 + j], np.float32)
        logprob_diff.append(abs(system - log_softmax_at(row, token)))
        margin.append(float(row.max() - row[token]))
    return {
        "logprob_diff": logprob_diff,
        "margin": margin if greedy else None,
        "tie_gap": None if tie_gaps is None else [
            float(tie_gaps[prompt_len - 1 + j]) for j in range(len(generated))
        ],
    }


def verdict(requests: list[dict], tolerance: dict) -> dict:
    """Whether the compared requests agree with the reference.

    - The median over all positions of the log-probability's difference
      may not pass ``logprob_median``.
    - A position is out where its log-probability differs by more than
      ``logprob_abs`` or (greedy requests) its token lies more than
      ``argmax_margin`` below the reference's best.
    - The reference's choice at a position was close where its own margin
      for a discrete choice is under ``tie_gap``: there rounding may have
      chosen otherwise, and the position then differs by what the choice
      is worth. A dense reference gives no margins and ``tie_gap`` None:
      no choice is close.
    - Of all positions at most ``out_share_close`` may be out where the
      choice was close, and at most ``out_share_clear`` where it was not
      (both 0 for a dense model, whose every position must agree)."""
    diffs, margins, close = [], [], []
    for r in requests:
        n = len(r["logprob_diff"])
        diffs += r["logprob_diff"]
        margins += r["margin"] if r["margin"] is not None else [0.0] * n
        close += [
            tolerance["tie_gap"] is not None and r["tie_gap"] is not None
            and r["tie_gap"][j] < tolerance["tie_gap"] for j in range(n)
        ]
    problems = []
    if not np.all(np.isfinite(diffs)):
        problems.append("log-probabilities not finite")
        diffs = [d if np.isfinite(d) else np.inf for d in diffs]
    diffs, margins = np.asarray(diffs), np.asarray(margins)
    close = np.asarray(close, bool)
    out = (diffs > tolerance["logprob_abs"]) | (margins > tolerance["argmax_margin"])
    median = float(np.median(diffs))
    if median > tolerance["logprob_median"]:
        problems.append(
            f"the median difference of the log-probability from the "
            f"reference is {median:.4f} over {len(diffs)} positions "
            f"(more than {tolerance['logprob_median']})"
        )
    for where, mask, share in (
        ("clear", ~close, tolerance["out_share_clear"]),
        ("close", close, tolerance["out_share_close"]),
    ):
        found = out & mask
        if found.sum() > share * len(diffs):
            problems.append(
                f"{int(found.sum())} of {len(diffs)} positions are out where "
                f"the reference's choice was {where} (at most {share:.0%} "
                f"may be): the log-probability differs by up to "
                f"{diffs[found].max():.4f} (more than "
                f"{tolerance['logprob_abs']}) or a greedy token lies up to "
                f"{margins[found].max():.4f} below the reference's best "
                f"logit (more than {tolerance['argmax_margin']})"
            )
    return {
        "positions": len(diffs),
        "logprob_diff_median": median,
        "logprob_diff_max": float(diffs.max()),
        "argmax_margin_max": float(margins.max()),
        "close": int(close.sum()),
        "out_close": int((out & close).sum()),
        "out_clear": int((out & ~close).sum()),
        "problems": problems,
    }


def against_reference(engine, reference, params, cfg: dict, sampling_cls,
                      seed: int, prompts: list) -> tuple[dict, tuple]:
    """Part (a) of ``correct``: one request per prompt through ``engine``
    (greedy and sampled in turn, each recording log-probabilities), the
    same sequences through ``reference.forward``, and the verdict under
    ``reference.TOLERANCE`` with every position's difference. Also gives
    the first greedy request's (prompt, tokens), which a run may decode
    again alone."""
    samplings = [
        sampling_cls(logprobs=True) if i % 2 == 0 else sampling_cls(
            temperature=SAMPLED_TEMPERATURE, seed=seed + i, logprobs=True
        )
        for i in range(len(prompts))
    ]
    tickets = [
        engine.submit(prompt, NEW_TOKENS, sampling=sampling)
        for prompt, sampling in zip(prompts, samplings)
    ]
    engine.run_to_completion()
    generated = [engine.result(t) for t in tickets]
    logprobs = [engine.result_logprobs(t) for t in tickets]
    for t in tickets:
        engine.release(t)
    sequences = [
        reference_input(prompt, tokens)
        for prompt, tokens in zip(prompts, generated)
    ]
    compared = [
        compare(
            # only the rows from the prompt's last position on cross to the
            # host: compare() then indexes from a prompt of length 1
            np.asarray(rows[len(prompt) - 1:], dtype=np.float32), 1, tokens,
            lps, greedy=sampling.temperature == 0.0,
            tie_gaps=None if gaps is None else np.asarray(
                gaps[:, len(prompt) - 1:], np.float32
            ).min(axis=0),
        )
        for (rows, gaps), prompt, tokens, lps, sampling in zip(
            reference.forward(params, sequences, cfg), prompts, generated,
            logprobs, samplings,
        )
    ]
    out = verdict(compared, reference.TOLERANCE)
    for key in ("logprob_diff", "tie_gap"):  # every position, for the log
        out[key] = [
            c[key] and [round(x, 3) for x in c[key]] for c in compared
        ]
    return out, (prompts[0], generated[0])
