"""Continuous batching over the paged KV cache.

The serving loop the paged cache exists for: requests of heterogeneous
lengths share one decode batch and one physical page pool. A request is
admitted into a free batch row the moment one exists (no waiting for the
whole batch to drain — "continuous" as opposed to static batching), its
prompt is prefilled into freshly allocated pages, and every ``step()``
advances ALL active rows by one token through a single compiled
``decode_step_paged`` program. Finished rows (EOS or budget) free their
pages immediately for the next admission.

TPU-first split of responsibilities:

- **Device**: one jitted fixed-shape program per step — [max_batch]-wide
  regardless of how many rows are live (idle rows compute into a reserved
  scratch page and are ignored). Shapes never depend on occupancy, so the
  program compiles once.
- **Host**: integer bookkeeping only — the free-page stack, block tables,
  row admission/retirement. Mutating a block table or recycling pages is
  numpy work between steps, never a re-trace.
- **One step apart**: the plain step's feedback edge (the picked token is
  the next step's input, the position one on) stays on the device, so the
  host dispatches step k before it has pulled step k - 1's answers and
  does its bookkeeping for one step while the device runs the next
  (``ContinuousBatcher.step``). The block table, the pick settings and the
  row masks are the host's and go up only when it changed them.

Greedy decoding matches ``Transformer.generate_cached`` token-for-token
per request (pinned by tests/test_serving.py) — batching other requests
alongside cannot change a request's output, which is the correctness bar
for continuous batching.

That bar applies to every ``config.moe_exact`` config — dense, or MoE
with ``moe_dropless`` + ``moe_group_size=1``.
Capacity-based MoE routing pools couple whatever tokens share a forward
pass (an inherent property of the GShard scheme — tests/test_moe.py
documents that even solo decode-vs-forward only matches drop-free), so
capacity-routed MoE requests here route against their batch-mates and the
padded admission prompt: outputs are deterministic per pool state but not
pinned equal to solo decode. Speculative mode and the prefix cache refuse
capacity-routed MoE because their guarantees are exactness claims; plain
serving keeps it usable under the same documented caveat as the rest of
the decode family (pinned deterministic by tests/test_serving_stops.py).
With ``moe_dropless`` (worst-case expert capacity: no token can ever be
evicted) plus per-token routing groups (``moe_group_size=1``, making pool
size a mere batch dim of the expert einsums) routing is bitwise per-token
independent, the solo-equality pin holds (tests/test_serving.py), and
every serving feature accepts the config. The price is every token paying
all E experts' MLPs — an inference-exactness configuration, not a
training one.

Sampling is PER REQUEST (temperature / top-k / top-p / seed — the
heterogeneity serving actually needs). The decode program stays one
fixed-shape greedy-agnostic forward; a second small program
(``pick_tokens``), queued behind it, picks the token of every row that
samples, with the rows' settings as arrays and one uniform draw a row from
the request's own seeded ``numpy`` Generator — fully deterministic per
request and independent of what shares the batch. Rows that are steered
(``logit_bias``, ``allowed_tokens``: Python per row) pick on the host from
the pulled logits row (``choose_host``), as do a request's first token and
the speculative steps.

The reference has no model serving at all (SURVEY §2); within this rebuild
the batcher is the library-level analogue of the service's warm sandbox
pool: admit, run isolated, recycle.
"""

from __future__ import annotations

import functools
import gc
import hashlib
import time
from collections import OrderedDict, deque
from contextlib import contextmanager, nullcontext
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from bee_code_interpreter_tpu.models.moe import expert_matmul
from bee_code_interpreter_tpu.models.transformer import (
    TransformerConfig,
    decode_step_paged,
    decode_window_paged,
    forward,
)
from bee_code_interpreter_tpu.ops.paged_attention import reads_pages_in_place
from bee_code_interpreter_tpu.ops.paged_kv_cache import (
    BY_ROW_LEAVES,
    alloc_paged_cache,
    pages_leaf,
    seed_pool,
    seed_rings,
    seed_state,
)
from bee_code_interpreter_tpu.parallel.mesh import mesh_shape_key
from bee_code_interpreter_tpu.utils.jitwatch import TrackedJit

# physical page 0 is the scratch page: idle rows' block tables point at it,
# so their (masked, ignored) reads and writes never touch a live request's
# pages; the allocator never hands it out.
_SCRATCH_PAGE = 0


@dataclass(frozen=True)
class SamplingParams:
    """Per-request decoding knobs — the same semantics as
    ``transformer.sample_logits`` (greedy at temperature 0; otherwise
    categorical over temperature-scaled logits with top-k, then
    smallest-set-above-top-p filtering, always keeping at least the top
    token), drawn from a per-request seeded generator so a request's
    output never depends on its batch-mates.

    Where a selection runs is read off the request alone. In the plain
    decode step a greedy row's token is the device's argmax; a row that
    samples (``temperature > 0``) and is not ``steered`` is picked on the
    device by ``pick_tokens``, from one uniform draw of the request's
    generator a token; a ``steered`` row (``logit_bias``,
    ``allowed_tokens``) is picked on the host by ``choose_host`` from its
    pulled logits row, greedy or not. A request's first token (admission)
    and every token of a speculative batcher are picked on the host too.
    ``logprobs=True`` pulls the row for ``logprob_of`` wherever the token
    was picked.

    ``stop_sequences`` are token-id sequences: generation retires the
    moment the output ends with any of them, and the matched sequence is
    TRIMMED from the result (the common serving-API contract; ``eos_id``
    stays in the output by comparison). ``logprobs=True`` records the
    model's log-probability of each emitted token — under the UNFILTERED
    distribution (log-softmax of the raw logits row), so a sampled
    token's report doesn't change with top-k/top-p settings — and that
    stays true under bias/constraints: the report is always the MODEL's
    probability of the emitted token, however the sampler was steered.

    ``logit_bias`` maps token id -> additive bias on the raw logits
    before selection (the OpenAI-style knob: strongly negative bans a
    token, strongly positive forces it). ``allowed_tokens`` is the
    grammar hook: a callable receiving the tokens GENERATED SO FAR for
    this request (prompt excluded) and returning the iterable of token
    ids currently permitted, or None for "unconstrained this step" —
    everything else is masked to -inf. A grammar/JSON engine plugs in by
    closing over its own parser state. Both run host-side per row; the
    device programs stay constraint-agnostic and fixed-shape."""

    temperature: float = 0.0
    top_k: int | None = None
    top_p: float | None = None
    seed: int = 0
    stop_sequences: tuple[tuple[int, ...], ...] = ()
    logprobs: bool = False
    logit_bias: tuple[tuple[int, float], ...] = ()
    allowed_tokens: object = None  # Callable[[list[int]], Iterable[int] | None]

    def __post_init__(self) -> None:
        # same fail-fast rule as sample_logits: validated regardless of
        # temperature, so a greedy-tested config can't blow up later
        if self.top_k is not None and self.top_k < 1:
            raise ValueError(f"top_k must be >= 1, got {self.top_k}")
        if self.temperature < 0.0:
            raise ValueError(
                f"temperature must be >= 0, got {self.temperature}"
            )
        # normalize so callers can pass lists/dicts; frozen dataclass needs
        # object.__setattr__ for the canonicalized copies
        object.__setattr__(
            self, "stop_sequences",
            tuple(tuple(int(t) for t in s) for s in self.stop_sequences),
        )
        if any(len(s) == 0 for s in self.stop_sequences):
            raise ValueError("stop sequences must be non-empty")
        bias = self.logit_bias
        if isinstance(bias, dict):
            bias = tuple(sorted(bias.items()))
        object.__setattr__(
            self, "logit_bias",
            tuple((int(t), float(b)) for t, b in bias),
        )
        if self.allowed_tokens is not None and not callable(
            self.allowed_tokens
        ):
            raise ValueError("allowed_tokens must be callable or None")

    @property
    def steered(self) -> bool:
        """True when selection needs the full logits row on host (bias or
        constraint active) even for a greedy request."""
        return bool(self.logit_bias) or self.allowed_tokens is not None


def logprob_of(logits: np.ndarray, token: int, log_z: float) -> float:
    """log P(token) under the raw (unfiltered) logits row: the token's
    logit less the row's normaliser ``log_z``, which ``log_normalizers``
    computed on the device. The one rule of the admission's first token,
    the plain step and the speculative rounds, so that reported logprobs
    cannot drift between paths."""
    return float(logits[token]) - float(log_z)


def log_normalizers(logits: jax.Array) -> jax.Array:
    """log sum exp over the vocabulary of logits rows [..., V], float32
    [...]: what ``logprob_of`` subtracts, computed where the logits are and
    queued behind the program that made them, wherever a row records
    log-probabilities. (A float64 log-softmax on the host cost 0.6 ms a row
    at a vocabulary of 100,352, so a step's host share followed the number
    of such rows: the largest source of run-to-run spread; PERF.md, PR
    29.)"""
    return jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)


def row_answers(
    logits: jax.Array,  # [B, 1, V] — the decode step's, as it leaves them
    picked: jax.Array,  # [B] int32: ``pick_tokens``' ids
    sampled: jax.Array,  # [B] bool: the rows whose token is ``picked``'s
    current: jax.Array,  # [B, 1] int32: the tokens the step was given
    pos: jax.Array,  # [B] int32: the slots it wrote them at
    stepping: jax.Array,  # [B] bool: the rows the step ran for
    last_slot: int,  # the block table's last slot (static)
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """All that the host reads of a plain step's logits when the device
    chooses the tokens, as ONE array [3, B] int32: each row's token
    (``picked``'s where it samples, else the argmax, first of the
    largest), its logit at that token and its normaliser
    (``log_normalizers``), the two float32s carried bit for bit. One
    copy crosses to the host a step, not one per answer (each costs half
    a millisecond on a v5e whatever its size) and not the ``[B, V]`` rows
    for one logit each (16.8 MB and 3.7 ms a step at 64 rows of 65,536
    logits): the part of a step that followed the host's load from run
    to run (PERF.md, PR 33). The logit is a masked sum over the
    vocabulary, which has one term and is exact; it splits over a
    vocabulary sharded under a mesh as a gather by index would not.

    Beside it, what the NEXT step is given, left on the device: each
    ``stepping`` row's token as its ``current`` and its ``pos`` one slot
    on (never past the table's last), every other row's as they were. The
    loop's feedback edge does not cross to the host, so the next step can
    be called before this one's answers are pulled (PERF.md, PR 34)."""
    last = logits[:, -1, :].astype(jnp.float32)
    token = jnp.where(sampled, picked, jnp.argmax(last, axis=-1))
    token = token.astype(jnp.int32)
    ids = lax.broadcasted_iota(jnp.int32, last.shape, 1)
    logit = jnp.sum(jnp.where(ids == token[:, None], last, 0.0), axis=-1)
    answers = jnp.stack([
        token,
        lax.bitcast_convert_type(logit, jnp.int32),
        lax.bitcast_convert_type(log_normalizers(last), jnp.int32),
    ])
    return (
        answers,
        jnp.where(stepping[:, None], token[:, None], current),
        jnp.where(stepping, jnp.minimum(pos + 1, last_slot), pos),
    )


def filtered_probs_host(
    logits: np.ndarray, params: SamplingParams
) -> np.ndarray:
    """The numpy mirror of ``transformer.filter_logits`` + softmax for one
    row — pure host math so the decode loop never dispatches per-row jax
    ops (each a dispatch and a sync of its own). Tie semantics match the
    device filter exactly (top-k keeps >= kth; nucleus order is a stable
    descending argsort; top token always kept) — pinned by
    tests/test_serving.py::test_host_filter_parity_with_device."""
    lg = logits.astype(np.float64) / params.temperature
    if params.top_k is not None:
        kth = np.partition(lg, -params.top_k)[-params.top_k]
        lg = np.where(lg < kth, -np.inf, lg)
    if params.top_p is not None:
        kept = _nucleus(lg, params.top_p)
        masked = np.full(lg.shape, -np.inf)
        masked[kept] = lg[kept]
        lg = masked
    probs = np.exp(lg - lg.max())
    return probs / probs.sum()


def _nucleus(lg: np.ndarray, top_p: float) -> np.ndarray:
    """The ids the nucleus keeps of one row of scaled logits: the smallest
    prefix of the stable descending order (logit descending, id ascending)
    whose mass BEFORE each member is under ``top_p``, the top token always.
    No sort of the row: the logits are binned by value (equal logits share
    a bin), the bins' masses summed from the top find the one bin the
    prefix ends in, every bin above it is kept whole, and only that bin's
    few members are ordered. A stable argsort of the whole row cost 9 ms at
    a vocabulary of 65,536, for the first token of every sampling request
    (PERF.md, PR 33)."""
    top = lg.max()
    weights = np.exp(lg - top)
    total = weights.sum()
    live = weights > 0  # top-k's -inf, and what underflows, weigh nothing
    low = lg[live].min()
    n_bins = 4096
    # bin 0 holds the largest logits
    scale = (n_bins - 1) / (top - low) if top > low else 0.0
    bins = np.where(live, (top - np.where(live, lg, top)) * scale, n_bins).astype(np.int64)
    mass = np.bincount(bins, weights=weights, minlength=n_bins + 1)[:n_bins] / total
    ends = np.cumsum(mass)
    reached = np.flatnonzero(ends >= top_p)
    last = int(reached[0]) if reached.size else int(np.flatnonzero(mass > 0)[-1])
    members = np.flatnonzero(bins == last)
    members = members[np.lexsort((members, -lg[members]))]
    probs = weights[members] / total
    before = ends[last] - mass[last] + np.cumsum(probs) - probs
    keep = before < top_p
    if last == 0:
        keep[0] = True  # at least the top token (device-filter parity:
        # top_p <= 0 would otherwise mask the whole vocab into NaNs)
    return np.concatenate([np.flatnonzero(bins < last), members[keep]])


def sample_host(
    logits: np.ndarray,  # [V] f32
    params: SamplingParams,
    rng: np.random.Generator,
) -> int:
    """One host-side draw mirroring ``sample_logits`` for a single row."""
    if params.temperature <= 0.0:
        return int(np.argmax(logits))
    probs = filtered_probs_host(logits, params)
    return int(rng.choice(logits.shape[0], p=probs))


def _largest(holds, rows: int, bits: int) -> jax.Array:
    """Per row the largest ``bits``-bit unsigned ``t`` for which ``holds(t)``
    is true, for a ``holds`` that is true up to some ``t`` and false beyond
    (0 where it never is): one pass over the row per bit, highest first.
    ``holds`` takes ``t`` as [rows, 1] and answers [rows]."""

    def set_bit(i, t):
        higher = t | (jnp.uint32(1) << (bits - 1 - i).astype(jnp.uint32))
        return jnp.where(holds(higher[:, None]), higher, t)

    return lax.fori_loop(0, bits, set_bit, jnp.zeros(rows, jnp.uint32))


def kept_tokens(
    logits: jax.Array,  # [B, V] f32 — temperature-scaled
    top_k: jax.Array,  # [B] int32; V keeps every token
    top_p: jax.Array,  # [B] f32; +inf keeps every token
) -> tuple[jax.Array, jax.Array]:
    """``transformer.filter_logits`` with PER-ROW settings as arrays, so one
    compiled program serves every mix of rows: the mask of the tokens the
    filters keep, [B, V] bool, and every token's unnormalised probability
    ``exp(logit - max)``, [B, V] f32. Same tie rules as ``filter_logits``
    and ``filtered_probs_host``: top-k keeps every value ``>=`` the k-th
    largest; the nucleus keeps a token while the mass BEFORE it in the
    stable descending order (equal values by token id) is ``< top_p``, and
    the top token always.

    No sort (on a TPU one over a vocabulary costs a quarter of a minute to
    compile): the k-th largest value and the value at the nucleus's edge
    are each found bit by bit over the floats' order-preserving integer
    keys, 32 masked sums a row; the last kept of the edge's equal values,
    over the token ids. Arithmetic in float32, where the host's is float64:
    the two can differ on a token whose mass-before is within float32
    rounding of ``top_p`` (tests/test_serving.py pins the rest)."""
    rows, vocab = logits.shape
    x = jnp.where(logits == 0.0, 0.0, logits)  # -0.0 is 0.0's equal
    bits = lax.bitcast_convert_type(x, jnp.uint32)
    # unsigned order of the keys = order of the floats
    keys = jnp.where(bits >> 31 == 1, ~bits, bits | jnp.uint32(1 << 31))
    ids = lax.broadcasted_iota(jnp.uint32, x.shape, 1)
    id_bits = max(1, (vocab - 1).bit_length())

    k = jnp.clip(top_k, 1, vocab)
    kth = _largest(lambda t: jnp.sum(keys >= t, axis=1) >= k, rows, 32)
    in_k = keys >= kth[:, None]
    prob = jnp.where(
        in_k, jnp.exp(x - jnp.max(x, axis=1, keepdims=True)), 0.0
    )

    def mass(mask):
        return jnp.sum(jnp.where(mask, prob, 0.0), axis=1)

    goal = top_p * jnp.sum(prob, axis=1)
    # the smallest value whose own and larger values together reach top_p:
    # larger values lie inside the nucleus whole, smaller ones outside
    edge = _largest(lambda t: mass(keys >= t) >= goal, rows, 32)
    above = keys > edge[:, None]
    at_edge = in_k & (keys == edge[:, None])
    before = mass(above)
    last = _largest(
        lambda i: before + mass(at_edge & (ids < i)) < goal, rows, id_bits
    )
    top = jnp.argmax(x, axis=1).astype(jnp.uint32)  # first of the largest
    keep = in_k & (above | (at_edge & (ids <= last[:, None])))
    return keep | (ids == top[:, None]), prob


def pick_tokens(
    logits: jax.Array,  # [B, 1, V] f32 — the decode step's, as it leaves them
    temperature: jax.Array,  # [B] f32 > 0
    top_k: jax.Array,  # [B] int32; V for none
    top_p: jax.Array,  # [B] f32; +inf for none
    draw: jax.Array,  # [B] f32 in (0, 1]: one uniform a row
    replicated=None,
) -> jax.Array:
    """The sampled rows' next tokens in one device program: temperature,
    then ``kept_tokens``' filters, then the token at which the kept
    probabilities, summed from the last token id down, first reach ``draw``
    of their total (an inverse-CDF pick: token ``i`` with probability
    ``p_i``, whatever the order of summation). Every argument is an array:
    no setting and no number of sampling rows compiles a second program,
    and a row that does not sample passes ``temperature`` 1 and ignores its
    answer. The token is always one ``kept_tokens`` kept. Under a mesh the
    logits arrive sharded over the vocabulary: ``replicated`` (a sharding)
    gathers them once, so that the sums are local. Returns [B] int32."""
    if replicated is not None:
        logits = lax.with_sharding_constraint(logits, replicated)
    x = logits[:, -1, :] / temperature[:, None]
    rows, vocab = x.shape
    keep, prob = kept_tokens(x, top_k, top_p)
    ids = lax.broadcasted_iota(jnp.uint32, x.shape, 1)

    def tail(i):
        return jnp.sum(jnp.where(keep & (ids >= i), prob, 0.0), axis=1)

    goal = draw * tail(jnp.zeros((rows, 1), jnp.uint32))
    token = _largest(
        lambda i: tail(i) >= goal, rows, max(1, (vocab - 1).bit_length())
    )
    return token.astype(jnp.int32)


def pick_settings(samplings, rows, vocab_size: int) -> tuple:
    """``pick_tokens``' per-row setting arrays, on the host (the call
    uploads them), for a batch whose ``samplings[row]`` sample in ``rows``:
    (temperature, top_k, top_p), "none" as the value that keeps every
    token. The other rows get settings that filter nothing; their answers
    are not read."""
    temperature = np.ones(len(samplings), dtype=np.float32)
    top_k = np.full(len(samplings), vocab_size, dtype=np.int32)
    top_p = np.full(len(samplings), np.inf, dtype=np.float32)
    for row in rows:
        sp = samplings[row]
        temperature[row] = sp.temperature
        if sp.top_k is not None:
            top_k[row] = min(sp.top_k, vocab_size)
        # from 1 up the nucleus is everything (the host's float64 sums
        # agree; float32's would cut a tail of 1e-7 of the mass)
        if sp.top_p is not None and sp.top_p < 1.0:
            top_p[row] = sp.top_p
    return temperature, top_k, top_p


class ConstraintExhausted(Exception):
    """The ``allowed_tokens`` constraint permits no continuation — a
    grammar reaching its terminal state. NORMAL control flow, not an
    error: the batcher retires the request with finish reason
    'constraint' (empty output if it happens at admission)."""


class CapacityError(RuntimeError):
    """``submit`` found no free row / not enough free pages RIGHT NOW —
    transient backpressure, retryable after a ``step`` frees capacity.
    Subclasses RuntimeError for callers that catch broadly, but exists so
    the serving engine can requeue on capacity alone: jaxlib's
    XlaRuntimeError also subclasses RuntimeError, and a device failure
    during admission prefill must reach the error-ticket path, not spin
    in the queue forever."""


def choose_host(
    logits: np.ndarray,  # [V] f32 — RAW model logits for this row
    params: SamplingParams,
    rng: np.random.Generator,
    generated: list[int],
) -> int:
    """Full per-row selection: apply ``logit_bias`` and the
    ``allowed_tokens`` constraint to a copy of the raw row, then greedy
    argmax or the ``sample_host`` draw. ``generated`` is this request's
    output so far (prompt excluded) — the constraint callable's input.
    Raises ConstraintExhausted when the constraint returns an empty set
    (grammar complete), ValueError on out-of-vocab ids."""
    if params.steered:
        logits = logits.astype(np.float64, copy=True)
        for token, bias in params.logit_bias:
            logits[token] += bias
        if params.allowed_tokens is not None:
            allowed = params.allowed_tokens(list(generated))
            if allowed is not None:
                idx = np.fromiter(
                    (int(t) for t in allowed), dtype=np.int64
                )
                if idx.size == 0:
                    raise ConstraintExhausted(
                        "allowed_tokens permits no continuation"
                    )
                if (idx < 0).any() or (idx >= logits.shape[0]).any():
                    raise ValueError(
                        "allowed_tokens returned out-of-vocab token ids"
                    )
                mask = np.full(logits.shape, -np.inf)
                mask[idx] = 0.0
                logits = logits + mask
    return sample_host(logits, params, rng)


def rejection_sample_commit(
    proposals,  # gamma draft proposals, x_g ~ q_dists[g]
    q_dists,  # gamma FILTERED draft distributions [V]
    p_fn,  # g -> FILTERED target distribution [V], g in [0, gamma]
    rng: np.random.Generator,
) -> tuple[list[int], int]:
    """Leviathan et al. rejection sampling for one verify window: accept
    proposal x with probability min(1, p(x)/q(x)); the first rejection
    resamples from normalize(max(p - q, 0)); a fully-accepted window
    draws its bonus token from the last target distribution. Returns
    (committed tokens, accepted proposal count). Target distributions
    come through ``p_fn`` LAZILY — a rejection at position k never pays
    for the filters beyond k+1. Acceptance uses strict ``<`` so a token
    outside the target's filtered support (p(x) == 0) can never commit,
    whatever ``rng.random()`` returns.

    The guarantee — each committed token is distributed EXACTLY per its
    target distribution, whatever the draft proposed — is pinned
    distributionally by tests/test_speculative_sampling.py against this
    function directly (end-to-end token marginals mix too many
    conditionals for statistical power)."""
    commit: list[int] = []
    n = 0
    for g, x in enumerate(proposals):
        p_dist, q_dist = p_fn(g), q_dists[g]
        x = int(x)
        if q_dist[x] > 0 and rng.random() < min(
            1.0, float(p_dist[x] / q_dist[x])
        ):
            commit.append(x)
            n += 1
            continue
        resid = np.maximum(p_dist - q_dist, 0.0)
        total = float(resid.sum())
        if total <= 0.0:  # p == q pointwise: resample from p directly
            resid, total = p_dist, float(p_dist.sum())
        commit.append(int(rng.choice(resid.shape[0], p=resid / total)))
        return commit, n
    p_last = p_fn(len(proposals))
    commit.append(int(rng.choice(p_last.shape[0], p=p_last)))
    return commit, n


def _phase_key(name: str) -> str:
    """A ``serve.*`` span's key in the ``phase_ms`` of the record it runs
    in. Under the record's own span the rest of its name
    (``serve.step.wait``: ``wait``; ``serve.admit.pull``: ``pull``;
    ``serve.admit`` itself ``admit``, which the admission's record turns
    into its ``duration_ms``); under any other span its own name, so that
    the drain inside an admission (``serve.land``, ``serve.land.pull``)
    lands in ``land`` and ``land_pull`` and not in the admission's
    ``pull``."""
    key = name.removeprefix("serve.")
    if key.startswith(("step.", "admit.")):
        key = key.partition(".")[2]
    return key.replace(".", "_")


class ContinuousBatcher:
    """Admit → step → collect loop over ``decode_step_paged``.

    ``max_batch`` bounds concurrent requests; ``n_pages``/``page_size``
    size the shared pool; ``max_pages_per_seq`` is the block-table width
    (the static gather width per step, so it bounds prompt+generation
    length at ``max_pages_per_seq * page_size``).

    A prompt's K/V reach its row's pages by one of TWO PROGRAMS, chosen at
    one site in ``submit``: the one-shot forward over the padded prompt
    with the donating ``seed_pool`` program behind it (a base row with no
    prefix hit and no window width asked for: what every benchmark cell
    runs), or windows of
    ``decode_window_paged`` over the row's own block table (prefix hits,
    adapter rows, ``prefill_chunk``, ``interleave_admission``). One
    admission record (``prefill_state`` holds those in flight) says which,
    and ``_admit_next`` runs its next piece. TWO DRIVES call it:
    ``_admit_blocking`` until the record is done, before ``submit``
    returns, or ``_advance_prefills`` once a ``step`` for every prefilling
    row (``interleave_admission``). Both end in ``_activate_row`` and share
    one failure handler (``_admission``).
    """

    def __init__(
        self,
        params,
        config: TransformerConfig,
        *,
        max_batch: int = 8,
        n_pages: int = 64,
        page_size: int = 16,
        max_pages_per_seq: int = 8,
        eos_id: int | None = None,
        draft_params=None,
        draft_config: TransformerConfig | None = None,
        gamma: int = 4,
        prefix_cache: bool = False,
        adapters: list | None = None,
        lora_scale: float = 1.0,
        mesh=None,
        metrics=None,
        monitor=None,
    ) -> None:
        """``draft_params``/``draft_config`` switch the batcher into
        SPECULATIVE mode: every step, the draft proposes ``gamma`` greedy
        tokens per active row (its own paged pool, same pages), the target
        scores each row's window in ONE ``decode_window_paged`` pass, and
        each row commits its own accept length — per-row cursors mean no
        lockstep minimum across the batch (the continuous-batching
        advantage over ``speculative_generate``'s static batch). Greedy
        rows carry the exact draft-verify guarantee (pinned by
        tests/test_serving.py); sampled rows decode via REJECTION
        SAMPLING (see ``_step_speculative_sampled``) — distributed
        exactly as plain sampled decoding from the target. Bias and
        allowed_tokens constraints remain unsupported in speculative
        mode.

        ``prefix_cache=True`` turns on vLLM-style prompt prefix caching:
        full prompt pages are content-addressed by chain hash and shared
        across requests (refcounted, LRU-evicted under pool pressure, kept
        alive past retirement for repeat prompts), and a hit admits its
        suffix alone, through windows — per-request outputs are unchanged,
        pinned by tests/test_prefix_cache.py.

        ``adapters`` turns on MULTI-LoRA serving (S-LoRA style): a list of
        LoRA pytrees (``models/lora.py``, attention-projection targets)
        stacked into one device bank; ``submit(adapter=i)`` serves request
        rows under adapter i — heterogeneous adapters decode together in
        one compiled program, the shared base weights streaming from HBM
        once for the whole batch. Adapter admissions prefill through
        windows; decode applies the delta unmerged per row; both use
        ``lora_scale`` (alpha/rank). The
        prefix cache keys pages by (adapter, tokens), so requests under
        different adapters never share K/V. Pinned equal to solo decode
        on the merged params by tests/test_multilora_serving.py.

        ``mesh`` turns on TENSOR-PARALLEL serving: params shard under the
        Megatron specs (``transformer.shard_params``) and the K/V page
        pool shards its head axis over the mesh's ``tp`` axis; the decode
        /prefill/window programs compile under GSPMD, which inserts the
        tp collectives (row-parallel psum, vocab-sharded logits gather)
        — the host-side scheduling loop is unchanged. Requires
        ``kv_heads % tp == 0`` (and the draft's, in speculative mode);
        block tables and token streams stay replicated. The solo-equality
        bar holds WITHIN a mesh (row independence is sharding-invariant);
        cross-mesh token equality additionally holds in the pinned test
        configs but reduction-order ulps make it environment-pinned, not
        guaranteed (tests/test_serving_mesh.py).

        A config with MAMBA layers (``config.layer_types``) keeps each
        row's recurrent state in the same pool as the K/V pages
        (``ops.paged_kv_cache.alloc_paged_cache``): admission seeds the
        row's state, whole, at the prompt's true length, and the decode
        program advances every row's by a token. What cannot hold with
        state kept by row is refused here by name (``_refuse_over_state``)
        or at ``validate_request`` (chunked and interleaved admission).

        A config whose ``layer_types`` tell WINDOW layers from full ones
        keeps the window layers' K/V in rings by row in that pool too
        (``sliding_window`` slots a row a layer): pages, ``n_pages`` and
        the admission's page arithmetic count the full layers alone;
        admission seeds the row's rings from the prompt's last positions;
        what cannot be served over rings is refused the same two ways."""
        self._refuse_over_state(
            config, prefix_cache=prefix_cache, draft_params=draft_params,
            adapters=adapters, mesh=mesh,
        )
        self.params = params
        self.mesh = mesh
        self.max_batch = max_batch
        # duck-typed observability.DeviceMonitor (compile/retrace tracking
        # + per-mesh-shape step telemetry); injected via
        # DeviceMonitor.attach -> set_device_monitor. None keeps every
        # tracked-jit call a single falsy check. The shape key tags step
        # records so multi-shape fleets aggregate per mesh.
        self._device_monitor = None
        self._mesh_key = mesh_shape_key(mesh)
        if mesh is not None:
            from bee_code_interpreter_tpu.models.transformer import (
                shard_params,
            )

            tp = mesh.shape.get("tp", 1)
            if config.kv_heads % tp:
                raise ValueError(
                    f"kv_heads {config.kv_heads} not divisible by tp={tp}"
                )
            sp = mesh.shape.get("sp", 1)
            if sp > 1 and page_size % sp:
                # padded admission widths are page multiples; the sp
                # attention chunks the sequence axis sp ways, so every
                # admission width must divide
                raise ValueError(
                    f"page_size {page_size} not divisible by sp={sp} "
                    "(sp admission chunks the padded prompt)"
                )
            if sp > 1 and config.sp_attention == "ulysses":
                # Ulysses all-to-alls the HEAD axis: validate its
                # divisibility at construction, not at the first submit's
                # jit trace (a server must refuse a config it can never
                # admit under)
                for name, heads in (
                    ("n_heads", config.n_heads),
                    ("kv_heads", config.kv_heads),
                ):
                    if heads % sp:
                        raise ValueError(
                            f"{name} {heads} not divisible by sp={sp} "
                            "(ulysses sp admission shards heads)"
                        )
            if draft_config is not None and draft_config.kv_heads % tp:
                raise ValueError(
                    f"draft kv_heads {draft_config.kv_heads} not divisible "
                    f"by tp={tp}"
                )
            self.params = shard_params(params, config, mesh)
        self.config = config
        self.page_size = page_size
        self.eos_id = eos_id
        self.max_len = max_pages_per_seq * page_size
        self.draft_params = draft_params
        self.draft_config = draft_config
        self.gamma = gamma
        if prefix_cache and not config.moe_exact:
            # capacity-based MoE routing pools couple tokens that share a
            # forward pass: the suffix-only prefill routes W tokens where
            # the full prefill routes L, so shared-prefix K/V would stop
            # being the K/V an unshared admission computes — the same
            # routing-pool hazard beam/speculative refuse
            # (tests/test_beam.py::test_moe_routing_pool_coupling_demonstrated).
            # moe_exact (dropless + per-token groups) removes the coupling
            # bitwise, so those configs pass.
            raise NotImplementedError(
                "prefix_cache requires a moe_exact config — dense, or MoE "
                "with moe_dropless + moe_group_size=1 (capacity routing "
                "pools differ between suffix-only and full prefill)"
            )
        self.prefix_cache_enabled = prefix_cache
        self.lora_scale = float(lora_scale)
        # only the stacked bank is kept: holding the original adapter
        # pytrees too would double adapter memory for the server's life
        self.n_adapters = len(adapters) if adapters else 0
        if adapters:
            from bee_code_interpreter_tpu.models.lora import stack_lora_bank

            self.lora_bank = stack_lora_bank(list(adapters))
            unknown = set(self.lora_bank) - {"wq", "wk", "wv", "wo"}
            if unknown:
                raise ValueError(
                    f"serving adapters target {sorted(unknown)}; the decode "
                    "path supports attention projections (wq/wk/wv/wo) only"
                )
        else:
            self.lora_bank = None
        self.row_adapter = np.zeros(max_batch, dtype=np.int32)
        if (draft_params is None) != (draft_config is None):
            raise ValueError(
                "speculative mode needs BOTH draft_params and draft_config"
            )
        if draft_config is not None:
            if draft_config.vocab_size != config.vocab_size:
                raise ValueError("target and draft must share a vocabulary")
            if not config.moe_exact:
                # same routing-pool hazard speculative_generate refuses:
                # tests/test_beam.py::test_moe_routing_pool_coupling_demonstrated
                # (moe_exact targets route per-token independently, so the
                # verify window and plain decode agree bitwise)
                raise NotImplementedError(
                    "speculative serving requires a moe_exact target — "
                    "dense, or MoE with moe_dropless + moe_group_size=1"
                )
            if gamma < 1:
                raise ValueError(f"gamma must be >= 1, got {gamma}")
        self.cache = self._alloc_pool(config, n_pages)
        self.block_table = np.full(
            (max_batch, max_pages_per_seq), _SCRATCH_PAGE, dtype=np.int32
        )
        self.pos = np.zeros(max_batch, dtype=np.int32)
        self.active = np.zeros(max_batch, dtype=bool)
        self.current = np.zeros((max_batch, 1), dtype=np.int32)
        self.budget = np.zeros(max_batch, dtype=np.int32)
        # rows are recycled; request ids are forever — results are keyed by
        # the id submit() returned, not by the row that happened to host it
        self.row_request = np.full(max_batch, -1, dtype=np.int64)
        self.results: dict[int, list[int]] = {}
        self.results_logprobs: dict[int, list[float]] = {}
        self.done: dict[int, bool] = {}
        # request -> eos | stop | length | constraint | error | cancelled
        self.finish: dict[int, str] = {}
        self.errors: dict[int, str] = {}  # request -> repr of callable error
        self.row_sampling: list[SamplingParams | None] = [None] * max_batch
        self.row_rng: list[np.random.Generator | None] = [None] * max_batch
        self._next_request_id = 0
        self.n_tokens_generated = 0
        self.free_pages = list(range(n_pages - 1, _SCRATCH_PAGE, -1))
        # Prefix cache (vLLM-style, host-side bookkeeping only): pages
        # holding a FULL page of prompt K/V are content-addressed by the
        # chain hash of their tokens-so-far and shared across requests via
        # refcounts; refcount-0 cached pages park in an LRU instead of the
        # free list and are evicted only under pool pressure, so a repeat
        # prompt arriving after the first finished still hits. Only pages
        # fully inside [0, L) are ever shared — the decode cursor starts at
        # L, so shared pages are write-free by construction.
        self.page_ref = np.zeros(n_pages, dtype=np.int32)
        self.prefix_index: dict[bytes, int] = {}
        self.page_hash: dict[int, bytes] = {}
        self.evictable: OrderedDict[int, None] = OrderedDict()
        self.prefix_stats = {
            "lookups": 0, "hits": 0, "pages_reused": 0, "evictions": 0,
        }
        # row -> the record of an interleaved admission in flight (built in
        # submit): the row is occupied but not yet active
        self.prefill_state: dict[int, dict] = {}
        # donate the pool: without aliasing, every decoded token would pay
        # a full page-pool HBM copy (precedent: make_train_step's donation)
        self._decode = self._track(
            functools.partial(
                decode_step_paged, config=config, lora_scale=self.lora_scale,
                mesh=mesh,
            ),
            "decode_step_paged",
            donate_argnums=(3,),
        )
        # which way that program reads and writes the pool (kv_telemetry):
        # the predicate it is traced under, over the same pool, window of
        # one token and mesh
        self._decode_in_place = reads_pages_in_place(
            self.cache, 1, config.paged_window, mesh
        )
        # which grouped matmul the held experts take (kv_telemetry), by the
        # rule the programs are traced under: the decode program's buffer
        # (max_batch tokens), a prefill of the longest prompt a row holds,
        # and the widest prompt whose prefill takes the kernel (0: none)
        self._expert_matmul = None
        if config.sorted_experts:
            widths = range(page_size, self.max_len + 1, page_size)
            self._expert_matmul = {
                "decode": expert_matmul(max_batch, config),
                "prefill": expert_matmul(self.max_len, config),
                "prefill_gmm_tokens": max(
                    (w for w in widths if expert_matmul(w, config) == "gmm"),
                    default=0,
                ),
            }
        # what the mamba layers keep for a row, replaced whole at admission
        self._seed_state = None
        self._state_bytes_per_row = 0
        if config.n_mamba_layers:
            from bee_code_interpreter_tpu.models.mamba import state_bytes_per_row

            self._seed_state = self._track(
                seed_state, "seed_state", donate_argnums=(0,)
            )
            self._state_bytes_per_row = state_bytes_per_row(config)
        # what the window layers keep for a row: its rings, replaced whole
        # at admission from the prompt's last ``sliding_window`` positions
        self._seed_rings = None
        self._ring_bytes_per_row = 0
        if config.window_layers:
            self._seed_rings = self._track(
                functools.partial(
                    seed_rings, window_layers=config.window_layers
                ),
                "seed_rings", donate_argnums=(0,),
            )
            self._ring_bytes_per_row = sum(
                self.cache[name].nbytes for name in ("wk", "wv")
            ) // max_batch
            self._state_bytes_per_row += self._ring_bytes_per_row
        # a prompt's K/V into its pages, behind the prefill: one program a
        # padded prompt width, the pool donated and, under a mesh, kept in
        # its sharding, so that the write is each chip's own (the draft pool
        # of the speculative drive goes through it too)
        self._seed_pool = self._track(
            functools.partial(
                seed_pool,
                paged_layers=config.paged_layers if config.window_layers else None,
            ),
            "seed_pool", donate_argnums=(0,),
            **({} if mesh is None else {
                "in_shardings": (self._pool_sharding(), None, None, None),
                "out_shardings": self._pool_sharding(),
            }),
        )
        # The one-shot admission program. With a mesh the full forward runs
        # under it — in particular an ``sp`` axis shards the attention over
        # the sequence axis (ring or Ulysses per ``config.sp_attention``,
        # via transformer.forward), which is the LONG-CONTEXT admission
        # path: prefill activation memory and attention FLOPs spread across
        # sp, then the K/V reshards into the (tp-sharded) page pool. Decode
        # itself stays single-token and ignores sp. ``prefill_chunk``
        # remains the single-chip activation-memory tool; sp admission is
        # the multi-chip one.
        self._prefill = self._track(
            functools.partial(
                forward, config=config, return_kv=True, mesh=mesh
            ),
            "prefill_forward",
        )
        # the admission window program; compiles once per page-aligned
        # window width, bounded by max_pages_per_seq
        self._window = self._track(
            functools.partial(
                decode_window_paged, config=config, lora_scale=self.lora_scale,
                mesh=mesh,
            ),
            "decode_window_paged",
            donate_argnums=(3,),
        )
        # The token of every row that samples and is not steered, queued
        # behind the decode program in the plain step. Every setting is an
        # array and the logits go in whole, so this is one program at one
        # shape per batcher. Under a mesh the decode program leaves the
        # logits sharded over the vocabulary (lm_head is column-parallel):
        # they are taken as they are and gathered once inside.
        replicated = None
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec

            replicated = NamedSharding(mesh, PartitionSpec())
        self._pick = self._track(
            functools.partial(pick_tokens, replicated=replicated),
            "pick_tokens",
        )
        self._log_normalizers = self._track(log_normalizers, "log_normalizers")
        # Under a mesh the plain step's small operands live whole on every
        # device, under a sharding named once (``_upload``), and
        # ``row_answers`` leaves the next step's ``current`` and ``pos``
        # under the same.
        self._replicated = replicated
        self._row_answers = self._track(
            functools.partial(row_answers, last_slot=self.max_len - 1),
            "row_answers",
            **({} if mesh is None else {"out_shardings": (replicated,) * 3}),
        )
        self._no_picks = jnp.zeros(max_batch, jnp.int32)
        # The plain step that has been dispatched and not landed (its
        # answers not pulled): ``_dispatch``'s record, or None. At most one,
        # and only the plain step leaves one (``step``).
        self._in_flight: dict | None = None
        # name -> (the host's copy, the device's) of the plain step's
        # host-owned operands as last uploaded (``_operand``)
        self._resident: dict[str, tuple[np.ndarray, jax.Array]] = {}
        if draft_config is not None:
            # the draft's own paged pool, addressed by the SAME block
            # tables/pages (one allocation covers both models' K/V)
            self.draft_cache = self._alloc_pool(draft_config, n_pages)
            if mesh is not None:
                self.draft_params = shard_params(
                    draft_params, draft_config, mesh
                )
            self._draft_decode = self._track(
                functools.partial(
                    decode_step_paged, config=draft_config, mesh=mesh
                ),
                "draft_decode_step_paged",
                donate_argnums=(3,),
            )
            self._draft_prefill = self._track(
                functools.partial(
                    forward, config=draft_config, return_kv=True, mesh=mesh
                ),
                "draft_prefill_forward",
            )
            # the verify pass IS a window over the target pool — one jit
            # wrapper (self._window) so an admission width that happens to
            # equal gamma+1 reuses the compiled program
            self._verify = self._window
            self._draft_window = self._track(
                functools.partial(
                    decode_window_paged, config=draft_config, mesh=mesh
                ),
                "draft_decode_window_paged",
                donate_argnums=(3,),
            )

        # Serving-engine instrumentation (docs/observability.md): ``metrics``
        # is a utils.metrics Registry; None keeps the batcher metrics-free
        # (zero overhead on the hot loop). TTFT and inter-token latency are
        # the serving-quality numbers (Orca-style per-stage visibility);
        # occupancy/pages/tokens-per-second are the capacity ones.
        self._metrics = metrics
        # ``monitor`` is a duck-typed observability.ServingMonitor (per-
        # request lifecycle traces + step records + wide events); usually
        # injected via monitor.attach(engine) -> set_monitor. None keeps
        # every hook site a single falsy check.
        self._monitor = monitor
        # Lifetime telemetry counters the monitor's step records difference.
        # Deliberately NOT serving state (excluded from _HOST_STATE, like
        # the metrics cursors): a restored snapshot starts its telemetry
        # from this process's zero.
        self._pages_allocated = 0
        self._pages_released = 0
        self._prefill_tokens = 0
        self._spec_accepted = 0
        self._spec_rejected = 0
        self._device_picked = 0
        self._host_picked = 0
        self._n_steps = 0
        # plain steps dispatched before / after the step before them was
        # landed, and the tokens of rows that ran one step past their end
        self._steps_ahead = 0
        self._steps_synchronous = 0
        self._discarded_tokens = 0
        # the phase -> ms of the step or the blocking admission being
        # recorded (see _recording); a dict only inside one with a
        # lifecycle monitor attached, None otherwise
        self._phase_ms: dict[str, float] | None = None
        # of the record being made: when the full collection under way
        # began, and the milliseconds of those that have ended
        self._gc_t0 = self._gc_ms = 0.0
        if metrics is not None:
            from bee_code_interpreter_tpu.utils.metrics import (
                TOKEN_LATENCY_BUCKETS,
            )

            self._ttft_seconds = metrics.histogram(
                "bci_serving_ttft_seconds",
                "Time from submit to a request's first generated token",
                buckets=TOKEN_LATENCY_BUCKETS,
            )
            self._inter_token_seconds = metrics.histogram(
                "bci_serving_inter_token_seconds",
                "Per-row latency between consecutive generated tokens",
                buckets=TOKEN_LATENCY_BUCKETS,
            )
            self._step_seconds = metrics.histogram(
                "bci_serving_step_seconds",
                "Wall time of one batcher step",
                buckets=TOKEN_LATENCY_BUCKETS,
            )
            self._tokens_total = metrics.counter(
                "bci_serving_tokens_total",
                "Tokens generated across all requests",
            )
            metrics.gauge(
                "bci_serving_active_rows",
                "Batch rows currently decoding",
                lambda: int(self.active.sum()),
            )
            metrics.gauge(
                "bci_serving_batch_occupancy",
                "Fraction of batch rows decoding (0-1)",
                lambda: float(self.active.sum()) / float(self.active.shape[0]),
            )
            metrics.gauge(
                "bci_serving_free_pages",
                "KV-cache pages on the free list",
                lambda: len(self.free_pages),
            )
            metrics.gauge(
                "bci_serving_tokens_per_second",
                "Decode throughput over the recent step window",
                self._tokens_per_second,
            )
            self._tokens_counted = 0
            # (monotonic time, cumulative tokens) samples; the rate gauge
            # reads the spread so a scrape never pays more than a subtraction
            self._rate_samples: deque[tuple[float, int]] = deque(maxlen=512)

    @staticmethod
    def _refuse_over_state(config, *, prefix_cache, draft_params, adapters, mesh):
        """What a config with a latent cache, with mamba layers or with
        window layers' rings cannot be served with, each refused by name.
        Over mamba layers the features below assume that everything a row
        keeps is K/V by position, which a page can share, a window can
        overwrite and a mesh can split by head; recurrent state is one
        value a row, advanced in place. A ring by row holds the last
        ``sliding_window`` positions alone, each slot overwritten a window
        later. A latent cache is one KV head a layer, kept whole on one
        chip, and the window paths were not carried over to it (PERF.md
        7)."""
        speculative = draft_params is not None
        refusals = []
        if config.kv_lora_rank:
            refusals.append(("a latent cache", (
                (prefix_cache, "prefix_cache",
                 "a hit admits its suffix through windows, which the latent "
                 "pool does not run yet"),
                (speculative, "draft_params (speculative mode)",
                 "the verify window is not run over a latent pool yet"),
                (bool(adapters), "adapters",
                 "they target wq/wk/wv/wo, and latent attention has no wk "
                 "and wv"),
                (mesh is not None, "mesh (tp > 1)",
                 "a latent cache has one KV head, which does not split by "
                 "head"),
            )))
        if config.n_mamba_layers:
            refusals.append(("mamba layers", (
                (prefix_cache, "prefix_cache",
                 "a shared page carries K/V of its tokens but not the "
                 "recurrent state after them"),
                (speculative, "draft_params (speculative mode)",
                 "a rejected draft would have to roll the recurrent state "
                 "back"),
                (bool(adapters), "adapters",
                 "adapter admissions prefill through windows, which do not "
                 "carry the recurrent state"),
                (mesh is not None, "mesh (tp > 1)",
                 "the recurrent state and the mixer are kept whole on one "
                 "chip"),
            )))
        if config.window_layers:
            refusals.append(("window layers' rings", (
                (prefix_cache, "prefix_cache",
                 "a shared page carries K/V of its tokens but no ring: a hit "
                 "would admit its suffix with the window layers' slots of "
                 "the prefix missing"),
                (speculative, "draft_params (speculative mode)",
                 "a rejected draft has overwritten the slot of the position "
                 "a window before it, which the row needs back"),
                (bool(adapters), "adapters",
                 "adapter admissions prefill through windows of several "
                 "tokens, which a ring of one slot a position does not take"),
                (mesh is not None, "mesh (tp > 1)",
                 "the rings are kept whole on one chip"),
            )))
        for over, table in refusals:
            for asked, name, why in table:
                if asked:
                    raise NotImplementedError(
                        f"{name} is not supported over {over}: {why}"
                    )

    # throughput gauge window: samples older than this are dropped at read
    # time, and a gauge whose newest sample is older reads 0 — an idle
    # server must not report its last burst's rate forever
    _RATE_WINDOW_S = 30.0

    def _tokens_per_second(self) -> float:
        s = self._rate_samples
        if len(s) < 2:
            return 0.0
        now = time.monotonic()
        if now - s[-1][0] > self._RATE_WINDOW_S:
            return 0.0
        while len(s) > 2 and now - s[0][0] > self._RATE_WINDOW_S:
            s.popleft()
        (t0, n0), (t1, n1) = s[0], s[-1]
        return (n1 - n0) / (t1 - t0) if t1 > t0 else 0.0

    def _sync_token_counter(self) -> None:
        """Advance the Prometheus counter to the lifetime token total —
        exact whichever path (step, submit-time activation, interleaved
        finalization) produced the tokens."""
        delta = self.n_tokens_generated - self._tokens_counted
        if delta > 0:
            self._tokens_total.inc(delta)
            self._tokens_counted = self.n_tokens_generated

    def set_monitor(self, monitor) -> None:
        """Attach (or detach, with None) a lifecycle monitor
        (observability.ServingMonitor.attach calls this). Requests already
        in flight are not traced retroactively."""
        self._monitor = monitor

    def _track(self, fn, name: str, **jit_kwargs) -> TrackedJit:
        """Jit ``fn`` as the program ``name`` and wrap it so an attached
        device monitor sees its compilations. ``fn`` is a
        ``functools.partial``, which jax would call ``_unknown``: given the
        name, the profiler's ``XLA Modules`` line reads ``jit_<name>(...)``
        and agrees with the compile records. The monitor resolves per call,
        so attach/detach works after construction and the unmonitored path
        pays one None check."""
        fn.__name__ = name
        return TrackedJit(
            jax.jit(fn, **jit_kwargs), name, lambda: self._device_monitor
        )

    @contextmanager
    def _phase(self, name: str, **stats):
        """One ``serve.*`` span (docs/observability.md "Serving
        observability"): always a ``jax.profiler.TraceAnnotation``, which
        is a flag test while no profiler session is open and an event on
        the device trace's clock while one is; and, while a monitored
        ``step`` or blocking admission is recording, the span's
        milliseconds under its key (``_phase_key``) in that record's
        ``phase_ms``."""
        phases = self._phase_ms
        with jax.profiler.TraceAnnotation(name, **stats):
            if phases is None:
                yield
                return
            key = _phase_key(name)
            phases.setdefault(key, 0.0)  # keys in the order the phases began
            t0 = time.perf_counter()
            try:
                yield
            finally:
                phases[key] += (time.perf_counter() - t0) * 1000.0

    def _clocked(self, fn, under: str, part: str):
        """``fn`` itself, or, while a monitored ``step`` or admission is
        recording, ``fn`` with its calls' milliseconds summed in
        ``phase_ms`` under the key of ``part`` of the span ``under`` (two
        clock reads a call; no span: a row's pick is too small a thing to
        put on the trace)."""
        phases = self._phase_ms
        if phases is None:
            return fn
        key = _phase_key(f"{under}.{part}")
        phases[key] = 0.0

        def clocked(*args):
            t0 = time.perf_counter()
            try:
                return fn(*args)
            finally:
                phases[key] += (time.perf_counter() - t0) * 1000.0

        return clocked

    @contextmanager
    def _recording(self):
        """The ``phase_ms`` of ONE record, a step's or a blocking
        admission's, while a lifecycle monitor has it made: ``_phase`` and
        ``_clocked`` fill it, and a full collection of Python's garbage
        that falls inside it is named in it (``_gc_pause``). Nothing of
        this exists outside a monitored record."""
        phases = self._phase_ms = {}
        self._gc_ms = 0.0
        gc.callbacks.append(self._gc_pause)
        try:
            yield phases
        finally:
            self._phase_ms = None
            gc.callbacks.remove(self._gc_pause)
            if self._gc_ms:  # the last key, whenever it came
                phases["gc"] = self._gc_ms

    def _gc_pause(self, phase: str, info: dict) -> None:
        """``gc.callbacks`` hook (``_recording``): the milliseconds of the
        FULL collections (generation 2: the ones that walk every object
        the process keeps and stop it for a tenth of a second; the young
        ones take microseconds) under ``gc`` in the record being made.
        The pause lies inside whatever phase was allocating when it came,
        a wait in the runtime's code as likely as the host's own loop: it
        is beside the phases like ``sample_choose``, not one of them."""
        if info["generation"] < 2:
            return
        if phase == "start":
            self._gc_t0 = time.perf_counter()
        else:
            self._gc_ms += (time.perf_counter() - self._gc_t0) * 1000.0

    def set_device_monitor(self, monitor) -> None:
        """Attach (or detach, with None) a compile/step telemetry monitor
        (observability.DeviceMonitor.attach calls this). Programs compiled
        before attachment are not reported retroactively."""
        self._device_monitor = monitor

    def device_memory(self) -> list[dict]:
        """Memory rows (``parallel.mesh.device_memory_rows``) for the
        devices this batcher's page pool lives on. The device monitor
        samples through this: only a process that runs an engine touches
        the accelerator, so the control plane never imports jax itself."""
        from bee_code_interpreter_tpu.parallel.mesh import device_memory_rows

        return device_memory_rows(
            sorted(pages_leaf(self.cache).devices(), key=lambda d: d.id)
        )

    def profiler_trace(self, trace_dir: str):
        """Context manager: a ``jax.profiler`` trace of this process into
        ``trace_dir``. ``POST /v1/profile`` captures through this for the
        same reason ``device_memory`` exists — only the process that holds
        the chip can trace it."""
        return jax.profiler.trace(trace_dir)

    def kv_telemetry(self) -> dict:
        """KV-cache pool telemetry (docs/observability.md "Serving
        observability"): page accounting + slot-level internal
        fragmentation from ``ops.paged_kv_cache.pool_telemetry``, plus the
        prefix-chain reuse counters. Pure host bookkeeping — safe on every
        scrape."""
        from bee_code_interpreter_tpu.ops.paged_kv_cache import pool_telemetry

        out = pool_telemetry(
            block_table=self.block_table,
            pos=self.pos,
            active=self.active,
            page_ref=self.page_ref,
            page_size=self.page_size,
            free_pages=len(self.free_pages),
            parked_pages=len(self.evictable),
            scratch_page=_SCRATCH_PAGE,
        )
        lookups = self.prefix_stats["lookups"]
        hits = self.prefix_stats["hits"]
        out["prefix"] = {
            **self.prefix_stats,
            "misses": lookups - hits,
            "hit_ratio": hits / lookups if lookups else 0.0,
            "indexed_pages": len(self.prefix_index),
            "enabled": self.prefix_cache_enabled,
        }
        out["pages_allocated_total"] = self._pages_allocated
        out["pages_released_total"] = self._pages_released
        # what mamba layers and window layers keep by row beside the pages
        # (0 without them)
        per_row = self._state_bytes_per_row
        out["state_bytes_per_row"] = per_row
        out["state_rows_live"] = int(self.active.sum()) if per_row else 0
        out["state_bytes"] = per_row * self.max_batch
        # how the plain decode step addresses the pool
        # (ops.paged_attention.reads_pages_in_place): the Pallas kernel
        # reads a row's live pages and writes the new token's page where
        # they lie in the stacked leaf, or a layer's slice is cut, scattered
        # into and the table's width gathered out of it
        in_place = self._decode_in_place
        # what a token keeps in the pages: K and V per head, or one latent
        # for all heads (its bytes as the pool lays them, pad included)
        c = self.config
        out["cache_kind"] = "latent" if c.kv_lora_rank else "kv_heads"
        out["cache_bytes_per_token"] = sum(
            x.dtype.itemsize * x.shape[0] * int(np.prod(x.shape[2:]))
            // self.page_size
            for name, x in self.cache.items() if name not in BY_ROW_LEAVES
        )
        out["decode_attention"] = "pages_in_place" if in_place else "gathered"
        out["decode_append"] = "in_place" if in_place else "scattered"
        # which attention layers keep pages and which a ring of
        # ``window_slots`` slots a row (the ring's bytes are inside
        # ``state_bytes_per_row``), and how each kind decodes
        out["paged_layers"] = len(c.paged_layers)
        out["window_layers"] = len(c.window_layers)
        out["window_slots"] = c.sliding_window if c.window_layers else 0
        out["ring_bytes_per_row"] = self._ring_bytes_per_row
        by_kind = {
            kind: out["decode_attention"] for kind in c.layer_kinds
            if kind not in ("mamba", "sliding_attention")
        }
        if c.window_layers:
            by_kind["sliding_attention"] = "ring_by_row"
        out["decode_attention_by_kind"] = by_kind
        # the expert layer, where there is one: the layers that have it, the
        # experts held here of the router's width, the experts a token
        # keeps and how the router scores ("softmax" / "sigmoid"; None
        # without experts)
        out["expert_layers"] = c.n_experts and c.n_layers - c.n_dense_layers
        out["held_experts"] = c.n_experts and c.held_experts
        out["router_width"] = c.n_experts
        out["experts_per_token"] = c.n_experts and c.moe_top_k
        out["router"] = c.moe_scoring if c.n_experts else None
        # the sorted expert layer's grouped matmul, program by program:
        # "gmm" (ops.grouped_matmul's kernel, over the groups that have
        # rows) or "ragged_dot"; None without that layer
        out["expert_matmul"] = self._expert_matmul and dict(self._expert_matmul)
        return out

    # ----------------------------------------------------- snapshot/resume

    _HOST_STATE = (
        "block_table", "pos", "active", "current", "budget", "row_request",
        "row_adapter", "page_ref", "results", "results_logprobs", "done",
        "finish", "errors", "row_sampling", "row_rng", "_next_request_id",
        "n_tokens_generated", "free_pages", "prefix_index", "page_hash",
        "prefix_stats", "prefill_state",
    )

    def _geometry(self) -> dict:
        """The ONE compatibility contract between a snapshot and the
        batcher restoring it: everything that changes what in-flight rows
        mean. eos_id/gamma/lora_scale/prefix-cache mode are behavioral, not
        just shapes — e.g. a different gamma changes how far past budget
        speculative rows may write, and a different eos_id changes when
        restored rows retire."""
        return {
            "config": self.config,
            "draft_config": self.draft_config,
            "n_pages": int(self.page_ref.shape[0]),
            "page_size": self.page_size,
            "max_batch": int(self.active.shape[0]),
            "max_pages_per_seq": int(self.block_table.shape[1]),
            "n_adapters": self.n_adapters,
            "eos_id": self.eos_id,
            "gamma": self.gamma,
            "lora_scale": self.lora_scale,
            "prefix_cache": self.prefix_cache_enabled,
        }

    def state_dict(self) -> dict:
        """Everything needed to resume serving mid-decode on a fresh
        batcher — the preemption-recovery primitive for serving the way
        ``utils/checkpoint.py`` is for training (preemptible TPU slices
        make this a first-class need). Device pools come back as host
        numpy; host bookkeeping is copied (numpy arrays, request maps,
        per-row rng states). The receiving batcher must be constructed
        with the same config and pool geometry — ``load_state_dict``
        verifies. NOTE for disk persistence: the dict pickles cleanly
        unless a live request carries a callable ``allowed_tokens``
        constraint (functions don't serialize; seed/bias/stop-based
        sampling all do).
        """
        import copy

        self._land()  # the pool and the lists at one point of the sequence
        # copy=True: the decode jits DONATE the pool buffer, so a zero-copy
        # view (np.asarray can return one on CPU) would alias memory the
        # very next step() invalidates — the periodic-checkpoint pattern
        # must leave the snapshot owning its bytes
        snap_leaf = lambda x: np.array(x, copy=True)  # noqa: E731
        device = {"cache": jax.tree.map(snap_leaf, self.cache)}
        if self.draft_config is not None:
            device["draft_cache"] = jax.tree.map(snap_leaf, self.draft_cache)
        host = {
            name: copy.deepcopy(getattr(self, name))
            for name in self._HOST_STATE
        }
        host["evictable"] = list(self.evictable)  # LRU order, oldest first
        return {"device": device, "host": host, "meta": self._geometry()}

    def load_state_dict(self, state: dict) -> None:
        """Adopt a snapshot taken by ``state_dict``. Decode then continues
        exactly where the snapshot stopped (pinned by
        tests/test_serving.py::test_snapshot_resume_*): same tokens, same
        logprobs, same page accounting."""
        import copy

        self._land()  # what was in flight is this batcher's, not the snapshot's
        meta = state["meta"]
        mine = self._geometry()
        if set(meta) != set(mine):
            raise ValueError(
                "snapshot geometry keys differ from this build's "
                f"({sorted(set(meta) ^ set(mine))}) — version skew"
            )
        for key, want in meta.items():
            if mine[key] != want:
                raise ValueError(
                    f"snapshot geometry mismatch on {key!r}: snapshot has "
                    f"{want}, this batcher has {mine[key]}"
                )
        cache = {
            k: jnp.asarray(v) for k, v in state["device"]["cache"].items()
        }
        self.cache = self._shard_pool(cache) if self.mesh is not None else cache
        if self.draft_config is not None:
            draft = {
                k: jnp.asarray(v)
                for k, v in state["device"]["draft_cache"].items()
            }
            self.draft_cache = (
                self._shard_pool(draft) if self.mesh is not None else draft
            )
        for name in self._HOST_STATE:
            setattr(self, name, copy.deepcopy(state["host"][name]))
        self.evictable = OrderedDict(
            (page, None) for page in state["host"]["evictable"]
        )
        # Metrics are per-process, not serving state: realign the counter
        # cursor so the restored lifetime total doesn't replay into
        # Prometheus, clear the throughput window, and drop TTFT anchors —
        # they are time.monotonic() values from the SNAPSHOTTING process's
        # clock, meaningless (possibly negative) against ours.
        for rec in self.prefill_state.values():
            rec.pop("t_submit", None)
        if self._metrics is not None:
            self._tokens_counted = self.n_tokens_generated
            self._rate_samples.clear()
        # Telemetry counters are per-process too, but the adopted page_ref
        # table changes what "held" means here: realign so the step
        # records' held_pages (allocated - released) keeps equaling the
        # pool scan's ref>0 count from this point on.
        self._pages_allocated = self._pages_released + int(
            (self.page_ref > 0).sum()
        )

    def _pool_sharding(self):
        """The page pool's sharding under the mesh: the kv-head axis over tp
        (axis 2 of [n_layers, n_pages, kvh, ps, dh]; the int8 scale planes
        share the leading dims, so the one spec covers every leaf). A mesh
        without a tp axis replicates the pool — matching param_specs'
        whichever-axes-exist stance. The spec names no axis behind the last
        sharded one: that is how a compiled program's output carries it, and
        a spec that differs from it by trailing ``None`` alone is another
        key to every program's cache (a pool fresh from the allocator or
        from ``seed_pool`` and one from a decode step compiled each program
        that takes the pool twice)."""
        from jax.sharding import NamedSharding, PartitionSpec

        if "tp" not in self.mesh.axis_names:
            return NamedSharding(self.mesh, PartitionSpec())
        return NamedSharding(self.mesh, PartitionSpec(None, None, "tp"))

    def _alloc_pool(self, config: TransformerConfig, n_pages: int) -> dict:
        """A zeroed page pool, placed where it will live: under a mesh each
        device receives only its own shard (ops.paged_kv_cache
        .alloc_paged_cache has the why)."""
        return alloc_paged_cache(
            config, n_pages, self.page_size,
            sharding=None if self.mesh is None else self._pool_sharding(),
            max_batch=self.max_batch,
        )

    def _shard_pool(self, pool: dict) -> dict:
        """Place a restored snapshot's pool under the mesh."""
        spec = self._pool_sharding()
        return {k: jax.device_put(v, spec) for k, v in pool.items()}

    # ------------------------------------------------------------- admission
    def has_free_row(self) -> bool:
        free = ~self.active
        for row in self.prefill_state:
            free[row] = False
        return bool(free.any())

    @property
    def busy(self) -> bool:
        """Rows decoding, admissions still interleaving OR a step in
        flight whose tokens are not landed yet — the loop-until condition
        for ``run_to_completion`` at every layer."""
        return (
            bool(self.active.any()) or bool(self.prefill_state)
            or self._in_flight is not None
        )

    def validate_request(
        self,
        prompt,
        max_new_tokens: int,
        sampling: SamplingParams | None = None,
        adapter: int | None = None,
        interleave_admission: int | None = None,
        prefill_chunk: int | None = None,
    ) -> int:
        """Capacity-independent request validation; returns the page count
        the request will need. The ONE copy of the admission arithmetic:
        ``submit`` calls it first, and the serving engine
        (models/engine.py) calls it at intake so a queued request can
        never explode minutes later on an error the caller could have
        seen at submit. Anything that passes here can fail admission only
        TRANSIENTLY (rows/pages busy — CapacityError), never permanently.
        """
        prompt = np.asarray(prompt, dtype=np.int32).reshape(-1)
        L = int(prompt.shape[0])
        if L < 1:
            raise ValueError("prompt must be non-empty")
        windowless = None  # what the admission window cannot run over, and why
        if self.config.n_mamba_layers:
            windowless = ("mamba layers", (
                "the chunks and windows of such an admission do not "
                "carry the recurrent state from one to the next"
            ))
        elif self.config.kv_lora_rank:
            windowless = ("a latent cache", (
                "the admission window is not run over a latent pool yet"
            ))
        elif self.config.window_layers:
            windowless = ("window layers' rings", (
                "a chunk or window of several tokens overwrites slots its "
                "own earlier tokens still attend over"
            ))
        if windowless is not None:
            over, why = windowless
            for asked, name in (
                (prefill_chunk, "prefill_chunk"),
                (interleave_admission, "interleave_admission"),
            ):
                if asked is not None:
                    raise NotImplementedError(
                        f"{name} is not supported over {over}: {why}"
                    )
        if interleave_admission is not None and (
            interleave_admission < self.page_size
            or interleave_admission % self.page_size
        ):
            raise ValueError(
                f"interleave_admission must be a positive multiple of "
                f"page_size ({self.page_size}), got {interleave_admission}"
            )
        if prefill_chunk is not None:
            if prefill_chunk < 1:
                raise ValueError(f"chunk must be >= 1, got {prefill_chunk}")
            if interleave_admission is not None:
                raise ValueError(
                    "prefill_chunk and interleave_admission both name the "
                    "admission window's width: pass one of them"
                )
        if max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        if adapter is not None:
            if self.lora_bank is None:
                raise ValueError(
                    "no adapters configured (pass adapters= at construction)"
                )
            if not 0 <= adapter < self.n_adapters:
                raise ValueError(
                    f"adapter {adapter} out of range "
                    f"(have {self.n_adapters})"
                )
        speculative = self.draft_params is not None
        if speculative and sampling is not None and sampling.steered:
            raise ValueError(
                "speculative serving cannot apply logit_bias/allowed_tokens "
                "(draft-verify commits the target's unsteered argmax tokens)"
            )
        # speculative rounds write draft/verify K/V past the budget before
        # truncation — those slots must be OWNED pages (a scratch-page read
        # inside the still-visible window would corrupt the verify). An
        # active row's cursor is at most L + budget - 2 (rows at budget
        # retire), so the deepest window write is cursor + gamma:
        # overshoot = gamma - 1 slots beyond L + budget.
        overshoot = self.gamma - 1 if speculative else 0
        total = L + max_new_tokens + overshoot
        if total > self.max_len:
            raise ValueError(
                f"prompt+generation ({total}, incl. speculative overshoot "
                f"{overshoot}) exceeds the block table's budget "
                f"({self.max_len})"
            )
        n_need = -(-total // self.page_size)  # ceil
        usable = self.page_ref.shape[0] - 1  # minus the scratch page
        if n_need > usable:
            raise ValueError(
                f"request needs {n_need} pages but the pool only has "
                f"{usable} (a permanent misfit, not backpressure)"
            )
        return n_need

    def submit(
        self,
        prompt,
        max_new_tokens: int,
        sampling: SamplingParams | None = None,
        prefill_chunk: int | None = None,
        adapter: int | None = None,
        interleave_admission: int | None = None,
    ) -> int:
        """Prefill ``prompt`` into freshly allocated pages and return a
        REQUEST id (stable across row recycling). ``sampling`` defaults to
        greedy; a fixed seed makes the request fully deterministic. Raises
        if no free row or not enough free pages (callers queue and retry
        after a step frees capacity).

        Without ``interleave_admission`` the call BLOCKS until the prompt
        is in its pages and the first token is picked: through the one-shot
        program, or (prefix hit, ``adapter``, ``prefill_chunk``) through
        windows as wide as the block table. ``prefill_chunk`` bounds the
        window to that many tokens, rounded down to whole pages and at
        least one — activation memory bounded by the window, the
        long-prompt admission — and is the same loop at another width, so
        the result is that of the unbounded admission.

        ``interleave_admission`` (a page-multiple window width) admits the
        prompt INCREMENTALLY: submit allocates the row and pages but runs
        no model; each subsequent ``step`` runs one window BEFORE
        decoding, so other rows keep producing tokens while a long prompt
        admits (Sarathi-style chunked-prefill interleaving — a blocking
        admission stalls the whole batch for its prefill). Until the
        prefill completes the request has no tokens, and a failure lands
        on the request (finish reason 'error') where the blocking drive
        raises. Both widths name the one window, so giving both is refused.

        On either drive the row's block-table entry stays on the scratch
        page until activation (decode steps cannot touch the half-written
        pages). A plain step in flight (``step``) is landed before the row
        is activated: its tokens are readable when ``submit`` returns, and
        what it frees counts toward this request's row and pages.

        ``adapter`` serves this request under the i-th LoRA adapter the
        batcher was constructed with (None = the base model)."""
        t_submit = time.monotonic()  # TTFT anchor (metrics only)
        prompt = np.asarray(prompt, dtype=np.int32).reshape(-1)
        n_need = self.validate_request(
            prompt, max_new_tokens, sampling=sampling, adapter=adapter,
            interleave_admission=interleave_admission,
            prefill_chunk=prefill_chunk,
        )
        # An admission changes rows, so the step in flight lands before
        # this one is activated: but AFTER its prefill has been queued
        # behind that step (``_one_shot``), where the row and the pages are
        # free already by what has landed. A step in flight can only free
        # more, so it is landed here only where they are not. What it frees
        # may go to this admission at once: the admission's programs run
        # after it on the device, so the write of a row that ran one step
        # past its end lands before they seed the pages it gave back.
        if not (
            self.has_free_row()
            and n_need <= len(self.free_pages) + len(self.evictable)
        ):
            self._land()
        L = int(prompt.shape[0])
        # internal index: 0 is the all-zeros base adapter in the bank
        adapter_internal = 0 if adapter is None else adapter + 1
        speculative = self.draft_params is not None
        occupied = self.active.copy()
        for r in self.prefill_state:
            occupied[r] = True
        free_rows = np.flatnonzero(~occupied)
        if free_rows.size == 0:
            raise CapacityError(
                "no free batch row (step() until one frees)"
            )
        # Prefix match BEFORE allocating: matched pages come from the index
        # (a ref, not an allocation). The match is capped at (L-1)//ps full
        # pages so at least one suffix token remains — the admission must
        # still produce last-prompt-token logits to sample from.
        matched = 0
        hashes: list[bytes] = []
        shared: list[int] = []
        if self.prefix_cache_enabled:
            self.prefix_stats["lookups"] += 1
            hashes, shared = self._prefix_match(prompt, adapter_internal)
            matched = len(shared)
        # acquire refs on shared pages BEFORE measuring availability: a
        # matched page parked in the evictable LRU must neither count
        # toward the fresh-page budget nor be pickable by the allocator's
        # eviction. Refs are released if the capacity check then fails.
        for page in shared:
            if self.page_ref[page] == 0:
                # reviving a parked page re-enters "held": count it as an
                # allocation so the churn counters stay symmetric with
                # _release_page's 1 -> 0 accounting (held == alloc - rel)
                self._pages_allocated += 1
            self.page_ref[page] += 1
            self.evictable.pop(page, None)
        available = len(self.free_pages) + len(self.evictable)
        if n_need - matched > available:
            for page in reversed(shared):
                self._release_page(page)
            raise CapacityError(
                f"page pool exhausted ({n_need - matched} needed, "
                f"{available} free)"
            )
        if matched:
            self.prefix_stats["hits"] += 1
            self.prefix_stats["pages_reused"] += matched
        row = int(free_rows[0])
        pages = shared + [self._alloc_page() for _ in range(n_need - matched)]
        # The request id is born HERE, once admission is committed (row and
        # pages secured): the lifecycle monitor needs it before the prefill
        # runs, and both the blocking and interleaved paths share it.
        req = self._next_request_id
        self._next_request_id += 1
        if self._monitor is not None:
            self._monitor.on_submit(
                req,
                prompt_tokens=L,
                max_new_tokens=max_new_tokens,
                pages=n_need,
                prefix_pages=matched,
                adapter=adapter,
                speculative=speculative,
                interleaved=interleave_admission is not None,
            )

        # The one site that chooses how the prompt's K/V reach the pages.
        # A base row with no prefix hit takes the ONE-SHOT program (width
        # None): the program family of generate_cached's prefill, which the
        # solo-equality pins rely on bitwise at bf16, and bulk seeding.
        # Everything else takes WINDOWS of decode_window_paged, which attend
        # to a shared prefix through the block table and are lora- and
        # quantization-aware (adapters serve on a weight-only-int8 base
        # too; with no hit the whole prompt is the suffix). A window is as
        # wide as the table unless the caller bounded it; whole pages keep
        # the compile count bounded by max_pages_per_seq.
        ps = self.page_size
        if interleave_admission is not None:
            width = interleave_admission
        elif prefill_chunk is not None:
            width = max(1, prefill_chunk // ps) * ps
        elif matched or adapter_internal:
            width = self.max_len
        else:
            width = None
        rec = {
            "req": req, "prompt": prompt, "pages": pages, "hashes": hashes,
            "start": matched * ps, "pos": matched * ps, "width": width,
            "sampling": sampling, "max_new_tokens": max_new_tokens,
            "adapter_internal": adapter_internal, "last_row": None,
            "t_submit": t_submit,
        }
        if interleave_admission is not None:
            self.results[req] = []
            self.done[req] = False
            self.prefill_state[row] = rec  # step drives it (_advance_prefills)
        elif self._monitor is None:
            self._admit_blocking(row, rec)
        else:
            self._admit_observed(row, rec)
        return req

    def _admit_blocking(self, row: int, rec: dict) -> None:
        """The blocking drive: every piece of the admission, then the
        activation, before ``submit`` returns. It runs under the request's
        serving trace (when a monitor is attached): a compile forced by a
        new prefill shape lands as an ``xla.compile`` span inside THIS
        request's span tree, so the TTFT it inflated is explained where
        the operator looks for it (observability/device.py)."""
        req = rec["req"]
        with self._request_context(req), self._phase(
            "serve.admit", req=req, prompt_tokens=int(rec["prompt"].shape[0]),
            pages=len(rec["pages"]),
            **self._held_pairs_stat(int(rec["prompt"].shape[0])),
        ), self._admission(row, rec, propagate=True):
            if rec["width"] is not None:
                self._land()  # the one-shot program lands it further on
            while not self._admit_next(row, rec):
                pass
            with self._phase("serve.admit.activate"):
                self._activate_row(row, rec)

    def _admit_observed(self, row: int, rec: dict) -> None:
        """``_admit_blocking`` under the attached lifecycle monitor: the
        admission as ONE record, the step record's sibling, handed to
        ``on_admitted`` (docs/observability.md "Serving observability").
        ``duration_ms`` is the ``serve.admit`` span and ``phase_ms`` the
        spans that ran inside it, in the order they began (``_phase``);
        what the top-level ones leave of ``duration_ms`` is unspanned: next
        to nothing of a one-shot admission, and of one through ``windows``
        (``_admit_next``'s loop, which draws no spans) the windows
        themselves. A failed admission (``_admission``'s handler) raises
        through here and leaves no record. Only the monitored path enters
        this frame: the unmonitored one calls ``_admit_blocking`` from
        ``submit`` as before (PERF.md, PR 31: the depth of the Python stack
        at a first compile is part of ``setup_s``)."""
        decoding_rows = int(np.count_nonzero(self.active))
        pages = len(rec["pages"])  # the activation hands them to the row
        with self._recording() as phases:
            self._admit_blocking(row, rec)
        padded = rec["pos"] - rec["start"]
        self._monitor.on_admitted(rec["req"], {
            "req": rec["req"],
            "row": row,
            "prompt_tokens": int(rec["prompt"].shape[0]),
            # what ran through the model, padding included (a prefix hit's
            # matched pages did not)
            "padded_tokens": padded,
            "pages": pages,
            # 0: the one-shot program
            "windows": (
                0 if rec["width"] is None else -(-padded // rec["width"])
            ),
            # the rows it stalls, and whether it landed the step in flight
            "decoding_rows": decoding_rows,
            "landed_step": "land" in phases,
            "duration_ms": phases.pop("admit"),
            "phase_ms": phases,
        })

    def _held_pairs_stat(self, tokens: int) -> dict:
        """For a configuration whose expert layers hold a share of the
        experts (the sorted dispatch): the (token, expert) pairs ``tokens``
        route to the held experts over all expert layers, as
        ``held_expert_pairs``. The EXPECTATION under even routing (tokens x
        top-k x held / routed, a layer): the programs hand back logits and
        the pool alone, and a count of their own would be one more pull a
        step. Empty for every other configuration."""
        c = self.config
        if not c.sorted_experts:
            return {}
        per_token = (
            (c.n_layers - c.n_dense_layers) * c.moe_top_k
            * c.held_experts / c.n_experts
        )
        return {"held_expert_pairs": int(round(tokens * per_token))}

    def _request_context(self, req: int):
        """The request's serving trace as the current context, while a
        monitor is attached."""
        if self._monitor is None:
            return nullcontext()
        return self._monitor.exemplar_context(req)

    def _advance_prefills(self) -> None:
        """The interleaved drive: one window of every prefilling row, at
        the top of every ``step``."""
        for row, rec in sorted(self.prefill_state.items()):
            t_win, before = time.monotonic(), rec["pos"]
            with self._request_context(rec["req"]), self._admission(
                row, rec, propagate=False
            ):
                complete = self._admit_next(row, rec)
                if self._monitor is not None:
                    self._monitor.on_prefill_window(
                        rec["req"],
                        tokens=rec["pos"] - before,
                        duration_s=time.monotonic() - t_win,
                    )
                if complete:
                    del self.prefill_state[row]
                    self._activate_row(row, rec)

    @contextmanager
    def _admission(self, row: int, rec: dict, *, propagate: bool):
        """The one failure handler around an admission record's life, on
        both drives. A failed admission (prefill OOM, a device error in a
        window, a bad seed or a user callable at the first token) must not
        leak its pages: the row never activated, so nothing else will ever
        return them to the pool. The blocking drive then PROPAGATES (submit
        is synchronous and the caller never receives the request id); the
        interleaved drive, whose submit returned long ago, records the
        error on the request and lets the step loop live (an interrupt or
        an exit goes on up all the same)."""
        try:
            yield
        except BaseException as e:
            if "pages" not in rec:
                raise  # the row is active and owns them: not an admission's
            self._finish_unadmitted(
                row, rec, "error", error=repr(e), record=not propagate
            )
            if propagate or not isinstance(e, Exception):
                raise

    def _abandon(self, row: int, rec: dict) -> None:
        """Give back what an admission record holds: the row, and its
        pages (shared ones drop the acquired ref, back to the LRU if nobody
        else holds them; fresh ones go straight back to the free list)."""
        self.prefill_state.pop(row, None)
        self.block_table[row, :] = _SCRATCH_PAGE
        for page in reversed(rec.pop("pages")):
            self._release_page(page)

    def _finish_unadmitted(
        self, row: int, rec: dict, reason: str, error=None, record=True
    ) -> None:
        """End a request whose row never activated, with no tokens:
        ``_abandon`` its record and, unless the caller never received the
        id (``record`` False), leave the empty result readable."""
        req = rec["req"]
        self._abandon(row, rec)
        if record:
            self.results[req] = []
            self.done[req] = True
            self.finish[req] = reason
            if error is not None:
                self.errors[req] = error
            if rec["sampling"] is not None and rec["sampling"].logprobs:
                self.results_logprobs[req] = []
        if self._monitor is not None:
            self._monitor.on_done(req, reason, tokens=0, error=error)

    def _pull_last_row(self, logits_row, logprobs: bool):
        """The last prompt token's logits row [V] off the device, with its
        normaliser where the request records log-probabilities (else
        None): what ``_activate_row`` picks and reports the first token
        from."""
        log_z = self._log_normalizers(logits_row) if logprobs else None
        row = np.asarray(logits_row, dtype=np.float32)
        return row, None if log_z is None else float(log_z)

    def _activate_row(self, row: int, rec: dict) -> None:
        """Admission epilogue of both drives: pick the first token (from
        ``rec["last_row"]``, ``_pull_last_row``'s pair), register prefix
        pages, hand the record's pages to the row and activate it. A
        first-token failure (a bad seed, a user callable) is the
        ``_admission`` handler's."""
        sampling = rec["sampling"] or SamplingParams()
        req, prompt, hashes = rec["req"], rec["prompt"], rec["hashes"]
        L = int(prompt.shape[0])
        last_row, log_z = rec["last_row"]
        rng = np.random.default_rng(sampling.seed)
        try:
            first = choose_host(last_row, sampling, rng, [])
        except ConstraintExhausted:
            # the constraint permits no FIRST token: the request is
            # complete with an empty output (grammar terminal at step 0) —
            # a finished request, not an error; pages go straight back
            self._finish_unadmitted(row, rec, "constraint")
            return
        # the row owns the pages from here: _retire gives them back
        pages = rec.pop("pages")
        if self.prefix_cache_enabled:
            # index every page fully inside [0, L): those pages are
            # write-free for the rest of this request's life (the decode
            # cursor starts at L), so their K/V is shareable from now on.
            # Matched pages re-register as a no-op; last-writer-wins when
            # two in-flight admissions computed the same chunk.
            for j in range(L // self.page_size):
                page = int(pages[j])
                prev = self.prefix_index.get(hashes[j])
                if prev == page:
                    continue
                if prev is not None:
                    # displaced duplicate (two in-flight admissions computed
                    # the same chunk): drop its cache identity so the
                    # index/page_hash bijection holds; if it was parked
                    # awaiting reuse, nothing can hit it anymore — free it
                    self.page_hash.pop(prev, None)
                    if prev in self.evictable:
                        del self.evictable[prev]
                        self.free_pages.append(prev)
                self.prefix_index[hashes[j]] = page
                self.page_hash[page] = hashes[j]
        self.block_table[row, : len(pages)] = pages  # the rest is scratch
        self.pos[row] = L
        self.current[row, 0] = first
        self.budget[row] = rec["max_new_tokens"]
        self.row_adapter[row] = rec["adapter_internal"]
        self.row_request[row] = req
        self.row_sampling[row] = sampling
        self.row_rng[row] = rng
        self.results[req] = [first]
        self.n_tokens_generated += 1
        if self._monitor is not None:
            # first token exists: the prefill span closes, TTFT is fixed,
            # and the decode span opens — BEFORE the metric observation so
            # the exemplar context below finds the live record.
            self._monitor.on_first_token(req)
        if self._metrics is not None:
            # absent on a record restored from a snapshot: its anchor was
            # another process's clock (load_state_dict)
            if "t_submit" in rec:
                # Observed under the request's serving trace (when a
                # monitor is attached) so the OpenMetrics exemplar on
                # bci_serving_ttft_seconds names the same trace_id the wide
                # event and /v1/traces carry.
                with self._request_context(req):
                    self._ttft_seconds.observe(
                        time.monotonic() - rec["t_submit"]
                    )
            self._sync_token_counter()
        if sampling.logprobs:
            self.results_logprobs[req] = [logprob_of(last_row, first, log_z)]
        self.done[req] = False
        self.active[row] = True
        self._retire_if_done(row)

    def _admit_next(self, row: int, rec: dict) -> bool:
        """Run the next piece of ``rec``'s admission and move its cursor:
        the whole prompt through the one-shot program (``width`` None), or
        one window. True once the prompt's K/V are all in the row's pages;
        ``rec["last_row"]`` then holds the last prompt token's logits row
        as ``_pull_last_row`` gives it.

        Windows are page-aligned. Pad tokens in the final window write
        garbage K/V at positions >= L, which is safe for the same reason
        the speculative window's rejected drafts are: those slots sit
        beyond the cursor, are causally invisible until the cursor reaches
        them, and every decode write lands before the read that could see
        it. In speculative mode the draft pool replays the same windows so
        both caches stay in lockstep. The windows carry their own table:
        the global one keeps the row on the scratch page until activation."""
        prompt, pos, pages = rec["prompt"], rec["pos"], rec["pages"]
        ps = self.page_size
        L = int(prompt.shape[0])
        Lp = -(-L // ps) * ps  # the prompt padded to a whole number of pages
        speculative = self.draft_params is not None
        logprobs = rec["sampling"] is not None and rec["sampling"].logprobs
        if speculative and pos == rec["start"]:
            # zero the DRAFT pool's fresh pages: recycled pages hold a
            # previous request's K/V, and only speculative drafting can
            # read a not-yet-written slot inside its visible window (the
            # full-accept gap, _step_speculative) — zeros make that read
            # deterministic and pool-history-independent, matching the
            # contiguous speculative_generate's zero-initialized cache.
            # Only the FRESH pages: matched prefix pages hold valid draft
            # K/V that other rows may be sharing right now. The target
            # pool needs no zeroing: plain decode and the verify only read
            # slots already written (seeded, or appended by the very
            # window doing the reading; the rest are masked), so zeroing
            # it would just copy the whole pool per admission.
            fresh = jnp.asarray(pages[pos // ps:], dtype=jnp.int32)
            self.draft_cache = {
                name: x.at[:, fresh].set(0)
                for name, x in self.draft_cache.items()
            }
        end = Lp if rec["width"] is None else min(pos + rec["width"], Lp)
        # one copy of the padding: a divergent pad between the target's
        # and the draft's tokens would desync their caches
        tokens = np.zeros((1, end - pos), dtype=np.int32)
        tokens[0, : L - pos] = prompt[pos:end]
        if rec["width"] is None:
            rec["last_row"] = self._one_shot(
                row, tokens, L, pages, speculative, logprobs
            )
        else:
            table = np.full(
                (1, self.block_table.shape[1]), _SCRATCH_PAGE, dtype=np.int32
            )
            table[0, : len(pages)] = pages
            tokens, table = jnp.asarray(tokens), jnp.asarray(table)
            at = jnp.asarray([pos], dtype=jnp.int32)
            logits, self.cache = self._window(
                self.params, tokens, at, self.cache, table,
                **self._lora_kwargs(np.array([rec["adapter_internal"]])),
            )
            if speculative:
                _, self.draft_cache = self._draft_window(
                    self.draft_params, tokens, at, self.draft_cache, table
                )
            if end >= L:  # the window that holds the last REAL token
                rec["last_row"] = self._pull_last_row(
                    logits[0, L - 1 - pos], logprobs
                )
        rec["pos"] = end
        self._prefill_tokens += min(end, L) - pos
        return end >= L

    def _one_shot(self, row, padded, L, pages, speculative, logprobs):
        """The one-shot admission program over the ``padded`` prompt
        [1, Lp]: the exact O(L^2) forward, then the page seeding
        (``seed_pool``, a donating program over ``seed_prefill`` — the
        equality tests call the same function, so the tested path IS this
        path) and, over mamba layers, the row's state; over window layers,
        the row's rings first and the pages from the full layers alone. The
        padded prompt bounds the compile count: pad tokens are
        causal-masked for every row < L, so logits[L-1] and K/V[:L] are
        exact, and distinct prompt lengths share a program per page count
        instead of one per length."""
        with self._phase(
            "serve.admit.prefill", padded_tokens=padded.shape[1]
        ):
            # K and V [layers, 1, kvh, Lp, dh], or a latent [layers, 1, Lp,
            # width] alone
            if self._seed_state is None:
                logits, kv = self._prefill(self.params, padded)
            else:  # the state it hands back is that of the L real tokens
                logits, (*kv, ssm, conv) = self._prefill(
                    self.params, padded, length=np.int32(L)
                )
        if self._seed_rings is not None:
            # the window layers' K/V of the last ``sliding_window`` real
            # positions into the row's rings; the pages take the rest
            with self._phase("serve.admit.seed_window"):
                self.cache = self._seed_rings(
                    self.cache, np.int32(row), *kv, np.int32(L)
                )
        with self._phase("serve.admit.seed_pool"):
            # the prompt's pages (those past them hold its answer) and its
            # true length: all that is uploaded
            pages = np.asarray(
                pages[: padded.shape[1] // self.page_size], dtype=np.int32
            )
            self.cache = self._seed_pool(
                self.cache, pages, np.int32(L), tuple(kv)
            )
        if self._seed_state is not None:
            with self._phase("serve.admit.seed_state"):
                self.cache = self._seed_state(
                    self.cache, np.int32(row), ssm, conv
                )
        # The step in flight lands HERE, with the prefill and the seeding
        # queued behind it: the device goes from that step into the prefill
        # while the host is woken, pulls the step's answers and retires its
        # rows, where it would idle through all of that and the prefill's
        # dispatch (4-6 ms an admission on a v5e; PERF.md, PR 34).
        self._land()
        # waits for the device (prefill and seeding), then copies
        with self._phase("serve.admit.pull"):
            last = self._pull_last_row(logits[0, L - 1, :], logprobs)
        if speculative:
            # the draft's prefill into ITS pool at the same pages
            _, draft_kv = self._draft_prefill(self.draft_params, padded)
            self.draft_cache = self._seed_pool(
                self.draft_cache, pages, np.int32(L), tuple(draft_kv)
            )
        return last

    # ------------------------------------------------------------ multi-LoRA
    def _lora_kwargs(self, adapter_rows: np.ndarray, resident=False) -> dict:
        """Extra kwargs for the paged decode/window programs when a lora
        bank is configured; empty (the untouched base path) otherwise.
        ``resident``: the rows are the batch's, kept on the device between
        the plain steps (``_operand``)."""
        if self.lora_bank is None:
            return {}
        rows = np.asarray(adapter_rows, dtype=np.int32)
        return {
            "lora_bank": self.lora_bank,
            "adapter_idx": (
                self._operand("adapter_idx", rows) if resident
                else jnp.asarray(rows)
            ),
        }

    # -------------------------------------------------- prefix-cache pages
    def _prefix_match(
        self, prompt: np.ndarray, adapter_internal: int
    ) -> tuple[list[bytes], list[int]]:
        """(chain hashes, currently-matched prefix pages) for a would-be
        submission — the ONE copy of the match walk, shared by ``submit``
        and ``prefix_credit``. The match is capped at (L-1)//ps full pages
        so at least one suffix token remains."""
        hashes = self._chain_hashes(prompt, adapter_internal)
        shared: list[int] = []
        limit = min(len(hashes), (int(prompt.shape[0]) - 1) // self.page_size)
        for i in range(limit):
            page = self.prefix_index.get(hashes[i])
            if page is None:
                break
            shared.append(page)
        return hashes, shared

    def prefix_credit(self, prompt, adapter: int | None = None) -> int:
        """Full prompt pages a submission would reuse from the prefix
        index RIGHT NOW (0 with the cache off) — capacity planners
        (models/engine.py) subtract this from a request's page need so
        backpressure doesn't stall admissions the batcher would accept."""
        if not self.prefix_cache_enabled:
            return 0
        prompt = np.asarray(prompt, dtype=np.int32).reshape(-1)
        adapter_internal = 0 if adapter is None else adapter + 1
        return len(self._prefix_match(prompt, adapter_internal)[1])

    def _chain_hashes(self, prompt: np.ndarray,
                      adapter_internal: int = 0) -> list[bytes]:
        """Chain hash after each FULL page of the prompt: ``hashes[i]``
        commits to tokens [0, (i+1)*page_size) — a page is reusable only
        when its entire history matches, which is what makes shared K/V
        position-exact (prefixes always align at position 0). The adapter
        index salts the chain: K/V under different LoRA adapters are
        different values, so they must never share pages."""
        h = hashlib.blake2b(digest_size=16)
        h.update(int(adapter_internal).to_bytes(8, "little"))
        out: list[bytes] = []
        ps = self.page_size
        for i in range(len(prompt) // ps):
            h.update(prompt[i * ps:(i + 1) * ps].astype(np.int32).tobytes())
            out.append(h.digest())
        return out

    def _alloc_page(self) -> int:
        """One fresh page: free list first, then LRU eviction of a
        refcount-0 cached prefix page (its index entry dies with it).
        Callers check capacity up front, so exhaustion here is a bug."""
        if self.free_pages:
            page = self.free_pages.pop()
        else:
            page, _ = self.evictable.popitem(last=False)  # LRU victim
            h = self.page_hash.pop(page, None)
            if h is not None and self.prefix_index.get(h) == page:
                del self.prefix_index[h]
            self.prefix_stats["evictions"] += 1
        self.page_ref[page] = 1
        self._pages_allocated += 1
        return page

    def _release_page(self, page: int) -> None:
        """Drop one reference. At refcount 0 an indexed prefix page parks
        in the LRU (K/V kept for future hits); anything else is freed."""
        self.page_ref[page] -= 1
        if self.page_ref[page] > 0:
            return
        self._pages_released += 1  # leaves "held" (parks or frees below)
        h = self.page_hash.get(page)
        if h is not None and self.prefix_index.get(h) == page:
            self.evictable[page] = None  # MRU end
        else:
            self.page_hash.pop(page, None)
            self.free_pages.append(page)

    # ----------------------------------------------------------------- step
    def step(self) -> None:
        """Advance every active row — by one token (plain mode, one
        compiled program), or by its own accept length (speculative
        mode). Interleaved admissions advance one window first, so their
        prefill and the batch's decode share the step cadence.

        The plain step runs ONE STEP AHEAD of the host. A call dispatches
        step *k* from what step *k - 1* left on the device (its tokens as
        the next ``current``, its positions one on: ``row_answers``), THEN
        lands step *k - 1* (waits for its answers, pulls them, appends
        tokens and log-probabilities, retires rows), and returns with step
        *k* in flight: the device works on it while the host lands, returns
        to its caller and is called again. So the tokens a call's own step
        picked become readable after the NEXT call, or after anything that
        drains (``_land``: ``submit``, ``cancel``, ``preempt``, ``release``
        of a live request, ``state_dict``, ``load_state_dict``); ``busy``
        stays true while a step is in flight, so ``while busy: step()``
        ends with every token landed. A row that ends by its budget is left
        out of the step after its last (the host counts tokens, it need not
        see them); one that ends by ``eos_id`` or a stop sequence is seen
        at landing, when the next step has run it once more: that token is
        thrown away (``discarded_tokens``), and its write went to the row's
        own page or the scratch page. Results are those of the synchronous
        step, request by request.

        It engages by what the batcher holds, not by a switch: a step
        finds a step in flight only if nothing drained since, and a row
        whose token is chosen on the host (``SamplingParams.steered``) is
        landed in the call that dispatched it, because the next step needs
        its choice; speculative steps and interleaved windows land first
        and leave nothing in flight.

        With a metrics registry configured, each step also observes its
        wall time, the per-row inter-token latency (step time scaled by how
        many tokens each row committed — one in plain mode, the accept
        length in speculative mode), and the throughput window the
        tokens-per-second gauge reads. With a lifecycle monitor attached,
        each step additionally lands one step record (occupancy, token
        counts, speculative accepts, page churn, ``ahead`` and
        ``discarded_tokens``, and ``phase_ms``, the step's host time by
        phase — see docs/observability.md "Serving observability").

        The step is one ``serve.step`` span with the ``serve.step.*``
        phases inside it (``_phase``): nothing while no profiler session
        is open, the host's side of the device trace while one is."""
        self._n_steps += 1
        rows = int(np.count_nonzero(self.active))
        ahead = self._ahead()
        with jax.profiler.TraceAnnotation(
            "serve.step", n=self._n_steps, rows=rows, ahead=int(ahead)
        ):
            if (
                self._metrics is None
                and self._monitor is None
                and self._device_monitor is None
            ):
                self._step_inner(ahead)
            else:
                self._step_observed(rows, ahead)

    def _step_observed(self, rows_before: int, ahead: bool) -> None:
        """``_step_inner`` under the attached metrics and monitors."""
        prefilling_before = len(self.prefill_state)
        tokens_before = self.n_tokens_generated
        prefill_before = self._prefill_tokens
        spec_acc_before = self._spec_accepted
        spec_rej_before = self._spec_rejected
        device_picked_before = self._device_picked
        host_picked_before = self._host_picked
        alloc_before = self._pages_allocated
        released_before = self._pages_released
        discarded_before = self._discarded_tokens
        with (
            nullcontext() if self._monitor is None else self._recording()
        ) as phase_ms:
            t0 = time.monotonic()
            self._step_inner(ahead)
            t1 = time.monotonic()
        produced = self.n_tokens_generated - tokens_before
        if self._metrics is not None:
            self._step_seconds.observe(t1 - t0)
            if produced:
                if rows_before:
                    self._inter_token_seconds.observe(
                        (t1 - t0) * rows_before / produced
                    )
                self._rate_samples.append((t1, self.n_tokens_generated))
            self._sync_token_counter()
        if self._device_monitor is not None:
            # per-mesh-shape step timing (observability/device.py): the
            # aggregate behind the tokens/sec-vs-mesh-shape curve
            self._device_monitor.record_step(
                (t1 - t0) * 1000.0, shape=self._mesh_key
            )
        if self._monitor is not None:
            # occupancy is deliberately NOT a field: it is active_rows /
            # max_batch, and the step path builds this record thousands of
            # times a second — derivable values are the reader's job
            self._monitor.on_step(
                {
                    "duration_ms": (t1 - t0) * 1000.0,
                    # the phases that ran (_phase; an absent key did not);
                    # what they leave of duration_ms is unspanned
                    "phase_ms": phase_ms,
                    # dispatched before the step before it was landed: its
                    # decode_tokens are that step's, landed here
                    "ahead": ahead,
                    "active_rows": rows_before,
                    "active_rows_after": int(np.count_nonzero(self.active)),
                    "prefilling_rows": prefilling_before,
                    "max_batch": int(self.active.shape[0]),
                    "decode_tokens": produced,
                    # rows that ran one step past an eos or a stop
                    # sequence: landed here, their token thrown away
                    "discarded_tokens": (
                        self._discarded_tokens - discarded_before
                    ),
                    "prefill_tokens": self._prefill_tokens - prefill_before,
                    **self._held_pairs_stat(
                        produced + self._prefill_tokens - prefill_before
                    ),
                    "spec_accepted": self._spec_accepted - spec_acc_before,
                    "spec_rejected": self._spec_rejected - spec_rej_before,
                    # rows of the plain step whose token pick_tokens drew /
                    # choose_host chose (steered); the rest are greedy
                    "device_picked_rows": (
                        self._device_picked - device_picked_before
                    ),
                    "host_picked_rows": self._host_picked - host_picked_before,
                    "pages_allocated": self._pages_allocated - alloc_before,
                    "pages_released": self._pages_released - released_before,
                    "free_pages": len(self.free_pages),
                    "parked_pages": len(self.evictable),
                    # allocated-minus-released IS the held count (a release
                    # is counted exactly when a page's refcount hits 0):
                    # integer math instead of a page_ref scan per step
                    "held_pages": self._pages_allocated - self._pages_released,
                }
            )

    def _ahead(self) -> bool:
        """Whether the coming step will be dispatched BEFORE the step in
        flight is landed: there is one (nothing drained since its dispatch,
        so no active row is steered: such a row's step is landed in its own
        call), some row goes on after it, and no interleaved admission has
        a window to run first (a window may activate a row)."""
        step = self._in_flight
        return (
            step is not None
            and not self.prefill_state
            and bool((self.active & step["outlives"]).any())
        )

    def _step_inner(self, ahead: bool) -> None:
        landing, self._in_flight = self._in_flight, None
        if landing is not None and not ahead:
            self._land_step(landing)
            landing = None
        if self.prefill_state:
            with self._phase("serve.step.prefills"):
                self._advance_prefills()
        if not self.active.any():
            return
        if self.draft_params is not None:
            self._step_speculative()
            return
        try:
            step = self._dispatch(landing)
        except BaseException:
            self._in_flight = landing  # not landed yet: the next drain's
            raise
        if landing is not None:
            self._land_step(landing)
        if step["host_rows"]:
            # a steered row's token is chosen on the host from its logits,
            # and the next step is given it: seen before anything else runs
            self._land_step(step)
        else:
            self._in_flight = step

    def _upload(self, host: np.ndarray) -> jax.Array:
        """One small operand of the plain step to the device: the one
        place the step uploads through. It arrives as ``row_answers``
        leaves the arrays it feeds to the next step, so that the decode
        program is ONE compiled program whichever it is given: replicated
        over the mesh; without one, committed to the pool's device if the
        pool is (a caller who placed the params: everything the programs
        return is then), else wherever jax puts an array."""
        sharding = self._replicated
        if sharding is None:
            pages = pages_leaf(self.cache)
            sharding = pages.sharding if pages.committed else None
        return jax.device_put(host, sharding)

    def _operand(self, name: str, host: np.ndarray) -> jax.Array:
        """The device's copy of a HOST-OWNED operand of the plain step (the
        block table, the pick settings, the row masks, the adapter rows):
        uploaded when its value is not the one last uploaded under
        ``name``, which is when an admission or a retirement changed it,
        and otherwise the array that is there. The comparison is of a few
        hundred numbers; an upload is a call into the runtime (and under a
        mesh one ``DevicePutWithSharding`` a device) that the device used
        to wait out before every step (PERF.md, PR 34)."""
        held = self._resident.get(name)
        if held is None or not np.array_equal(held[0], host):
            kept = host.copy()  # the host goes on writing into its own
            held = self._resident[name] = (kept, self._upload(kept))
        return held[1]

    def _dispatch(self, before: dict | None) -> dict:
        """Queue one plain decode step, and what is picked and read of its
        logits, on the device; nothing waits. ``before`` is the step that
        is in flight ahead of it (not landed), or None. Returns the step's
        record, which ``_land_step`` lands.

        With a step ``before``, ``current`` and ``pos`` are the arrays that
        step left on the device and the rows are those that outlive it;
        with none, the host's mirrors are exact and go up whole. Either
        way a row not stepping is pointed at the scratch page and keeps
        its ``current`` and ``pos``."""
        with self._phase("serve.step.upload"):
            if before is None:
                stepping = self.active.copy()
                # copies: the host goes on writing into its mirrors
                current = self._upload(self.current.copy())
                pos = self._upload(self.pos.copy())
                self._steps_synchronous += 1
            else:
                # rows that ended at the last landing (eos, a stop
                # sequence) are inactive by now; rows that ``before`` takes
                # to their budget end when it lands
                stepping = self.active & before["outlives"]
                current, pos = before["next"]
                self._steps_ahead += 1
            rows = np.flatnonzero(stepping)
            # a row ends by its budget at a token count the host knows
            # without seeing the tokens: this step's is one more than the
            # landed ones and the one in flight
            outlives = stepping.copy()
            for row in rows:
                landed = len(self.results[int(self.row_request[row])])
                outlives[row] = (
                    landed + (before is not None) + 1 < self.budget[row]
                )
            block_table = self._operand("block_table", np.where(
                stepping[:, None], self.block_table, _SCRATCH_PAGE
            ))
            stepping_dev = self._operand("stepping", stepping)
            lora = self._lora_kwargs(self.row_adapter, resident=True)
        with self._phase("serve.step.dispatch"):
            logits, self.cache = self._decode(
                self.params, current, pos, self.cache, block_table, **lora
            )
            # where each row's token is picked, read off its request:
            # steered rows on the host from the row's logits (bias, or a
            # constraint that is Python), other sampling rows by the pick
            # program, the rest by the device's argmax
            host_rows = [
                row for row in rows if self.row_sampling[row].steered
            ]
            device_rows = [
                row for row in rows
                if self.row_sampling[row].temperature > 0.0
                and not self.row_sampling[row].steered
            ]
            self._host_picked += len(host_rows)
            self._device_picked += len(device_rows)
            # what the device answers is cut down to what the host reads
            # by small programs queued behind the decode step before
            # anything waits: the device runs them back to back and their
            # dispatch hides under it
            picked = self._no_picks
            if device_rows:
                # one uniform a row from the request's own generator: its
                # tokens depend on its seed and on how many it has drawn,
                # never on its row or its batch-mates. Drawn a step ahead
                # of the logits it picks from: it depends on no token
                draw = np.ones(self.active.shape[0], dtype=np.float32)
                for row in device_rows:
                    draw[row] = 1.0 - self.row_rng[row].random()  # (0, 1]
                settings = pick_settings(
                    self.row_sampling, device_rows, self.config.vocab_size
                )
                picked = self._pick(
                    logits,
                    *(
                        self._operand(name, setting) for name, setting
                        in zip(("temperature", "top_k", "top_p"), settings)
                    ),
                    draw,
                )
            is_picked = np.zeros(self.active.shape[0], dtype=bool)
            is_picked[device_rows] = True
            answers, *fed = self._row_answers(
                logits, picked, self._operand("is_picked", is_picked),
                current, pos, stepping_dev,
            )
            # the full [max_batch, V] logits cross to the host only when
            # some active row is steered: its token is picked there
            lg = logits[:, -1, :] if host_rows else None
        return {
            "rows": rows, "requests": self.row_request[rows],
            "outlives": outlives, "answers": answers, "logits": lg,
            "next": tuple(fed), "host_rows": len(host_rows),
        }

    def _land(self) -> None:
        """Land the step in flight, if there is one: the one drain, which
        whatever changes rows or reads the pool calls before it does. After
        it every token is in its list, every finished row retired, and the
        host's ``current`` / ``pos`` mirrors are exact, so the next step
        uploads them whole."""
        step, self._in_flight = self._in_flight, None
        if step is not None:
            with self._phase("serve.land"):
                self._land_step(step, under="serve.land")

    def _land_step(self, step: dict, under: str = "serve.step") -> None:
        """Wait for a dispatched step's answers, pull them, and do the
        host's part for every row it ran: token, log-probability,
        retirement. The three phases are spans ``under`` the one they run
        in: a step's, or a drain's between steps."""
        answers, lg = step["answers"], step["logits"]
        with self._phase(f"{under}.wait"):
            jax.block_until_ready((answers, lg))
        with self._phase(
            f"{under}.pull",
            bytes=answers.nbytes + (0 if lg is None else lg.nbytes),
        ):
            token, logit, log_z = np.asarray(answers, dtype=np.int32)
            logit = logit.view(np.float32)
            log_z = log_z.view(np.float32).astype(np.float64)
            if lg is not None:
                lg = np.asarray(lg, dtype=np.float32)
        with self._phase(f"{under}.sample"):
            choose = self._clocked(choose_host, under, "sample.choose")
            logprob = self._clocked(logprob_of, under, "sample.logprob")
            for row, req_row in zip(step["rows"], step["requests"]):
                if self.row_request[row] != req_row:
                    # the row ended at the landing before this one, by eos
                    # or a stop sequence, after this step had been
                    # dispatched with it: its extra token is no one's
                    self._discarded_tokens += 1
                    continue
                req_row = int(req_row)
                sp = self.row_sampling[row]
                if sp.steered:
                    try:
                        nxt = choose(
                            lg[row], sp, self.row_rng[row],
                            self.results[req_row],
                        )
                    except ConstraintExhausted:
                        # grammar terminal state: the request is complete
                        # as-is
                        self._retire(int(row), "constraint")
                        continue
                    except Exception as e:
                        # a buggy user callable must not wedge the whole
                        # batch (request isolation is continuous batching's
                        # promise): the row retires with the error
                        # recorded, batch-mates keep decoding
                        self.errors[req_row] = repr(e)
                        self._retire(int(row), "error")
                        continue
                else:
                    nxt = int(token[row])
                self.pos[row] += 1
                self.current[row, 0] = nxt
                self.results[req_row].append(nxt)
                self.n_tokens_generated += 1
                if sp.logprobs:
                    # the row's logits and the token, or the one logit the
                    # device looked up for it
                    at = (
                        (lg[row], nxt) if sp.steered
                        else (logit[row:row + 1], 0)
                    )
                    self.results_logprobs[req_row].append(
                        logprob(*at, log_z[row])
                    )
                self._retire_if_done(int(row))

    def _step_speculative(self) -> None:
        """One draft-propose / target-verify / per-row-commit round.

        The draft runs γ paged decode steps (each one compiled program over
        the whole batch); the target scores every row's (current + drafts)
        window in ONE ``decode_window_paged``; each row then commits its
        own accepted prefix plus a correction token — rows never wait for
        each other (no lockstep minimum). Rejected draft positions stay in
        both pools as stale K/V, invisible behind each row's cursor until
        overwritten — the same no-rewind masking argument as
        ``speculative_generate``, applied per row.

        An all-greedy batch runs the exact argmax draft-verify with the
        draft loop fully on device; the moment any active row samples, the
        round routes through ``_step_speculative_sampled`` (rejection
        sampling, host-in-the-loop proposals) for the whole batch — greedy
        rows keep argmax semantics there, token for token.

        Known draft-quality (not correctness) gap, shared with the
        contiguous ``speculative_generate``: on a fully-accepted round the
        DRAFT pool never receives K/V for the last accepted draft token
        (the loop feeds it forward without appending), so later draft
        steps see zeros at that slot (pages are zeroed at admission —
        deterministic, pool-history-independent). The target verify is
        unaffected; only draft acceptance on those rows can dip."""
        active_rows = np.flatnonzero(self.active)
        if any(
            self.row_sampling[row].temperature > 0.0 for row in active_rows
        ):
            self._step_speculative_sampled(active_rows)
            return
        bt = jnp.asarray(self.block_table)
        pos_dev = jnp.asarray(self.pos)
        cur = jnp.asarray(self.current)

        drafts = []
        tok, p = cur, pos_dev
        for _ in range(self.gamma):
            lg, self.draft_cache = self._draft_decode(
                self.draft_params, tok, p, self.draft_cache, bt
            )
            tok = jnp.argmax(lg[:, -1:, :], axis=-1).astype(jnp.int32)
            drafts.append(tok)
            p = p + 1
        drafts_dev = jnp.concatenate(drafts, axis=1)  # [B, gamma]

        window = jnp.concatenate([cur, drafts_dev], axis=1)  # [B, gamma+1]
        t_logits, self.cache = self._verify(
            self.params, window, pos_dev, self.cache, bt,
            **self._lora_kwargs(self.row_adapter),
        )
        t_pred = np.asarray(
            jnp.argmax(t_logits, axis=-1), dtype=np.int32
        )  # [B, gamma+1]
        drafts_np = np.asarray(drafts_dev, dtype=np.int32)
        # full verify logits cross to host only when some row records
        # logprobs (commit[j]'s distribution is t_logits[row, j] — the
        # target's prediction for the token following window position j)
        t_np = t_log_z = None
        if any(self.row_sampling[row].logprobs for row in active_rows):
            t_log_z = np.asarray(self._log_normalizers(t_logits))
            t_np = np.asarray(t_logits, dtype=np.float32)

        for row in active_rows:
            match = drafts_np[row] == t_pred[row, : self.gamma]
            n = int(np.argmin(match)) if not match.all() else self.gamma
            commit = [*drafts_np[row, :n].tolist(), int(t_pred[row, n])]
            self._commit_row(row, commit, n, t_np, t_log_z)

    def _commit_row(self, row, commit, n, t_np, t_log_z) -> None:
        """Land one speculative round's committed tokens for a row —
        per-token stop checks, logprobs off the verify logits, cursor
        advance by accepted+1, retirement. The ONE copy shared by the
        greedy and sampled rounds so their semantics cannot drift."""
        sp = self.row_sampling[row]
        req = int(self.row_request[row])
        self._spec_accepted += n
        self._spec_rejected += self.gamma - n
        if self._monitor is not None:
            self._monitor.on_commit(
                req, accepted=n, rejected=self.gamma - n
            )
        out = self.results[req]
        lp = self.results_logprobs.get(req) if sp.logprobs else None
        for j, tok_committed in enumerate(commit):
            out.append(int(tok_committed))
            self.n_tokens_generated += 1
            if lp is not None:
                lp.append(logprob_of(
                    t_np[row, j], int(tok_committed), t_log_z[row, j]
                ))
            if self._done_reason(row, out) is not None:
                break  # later commits would exceed the stop — drop them
        self.pos[row] += n + 1
        self.current[row, 0] = int(commit[-1])
        self._retire_if_done(row)

    def _step_speculative_sampled(self, active_rows) -> None:
        """Speculative round with SAMPLED rows: rejection sampling
        (Leviathan et al., "Fast Inference from Transformers via
        Speculative Decoding"). Per position, with p and q the row's
        FILTERED target/draft distributions (temperature + top-k/top-p
        applied to both via the one ``filtered_probs_host``):

        - the proposal x ~ q is accepted with probability min(1, p(x)/q(x));
        - the first rejection resamples from normalize(max(p - q, 0));
        - a fully-accepted window draws its bonus token from the target's
          last distribution.

        The committed stream is distributed exactly as plain sampled
        decoding from the target — the distributional pin lives in
        tests/test_speculative_sampling.py; same-seed determinism and
        batch-mate isolation are pinned there too. Greedy rows in the
        same batch keep the exact argmax draft-verify semantics.

        Proposals are sampled host-side from each draft step's logits
        with the row's own seeded generator, so the draft loop pays one
        device->host [B, V] transfer per gamma — the target still scores
        the whole window in ONE pass, which is the speedup that matters."""
        bt = jnp.asarray(self.block_table)
        pos_dev = jnp.asarray(self.pos)
        cur = jnp.asarray(self.current)
        B = self.current.shape[0]
        gamma = self.gamma

        drafts_np = np.zeros((B, gamma), dtype=np.int32)
        q_dists: dict[int, list] = {int(r): [] for r in active_rows}
        tok, p = cur, pos_dev
        for g in range(gamma):
            lg, self.draft_cache = self._draft_decode(
                self.draft_params, tok, p, self.draft_cache, bt
            )
            lg_np = np.asarray(lg[:, -1, :], dtype=np.float32)
            # one transfer per step: greedy + idle rows propose host argmax
            drafts_np[:, g] = lg_np.argmax(-1).astype(np.int32)
            for row in active_rows:
                sp = self.row_sampling[row]
                if sp.temperature > 0.0:
                    q = filtered_probs_host(lg_np[row], sp)
                    drafts_np[row, g] = int(
                        self.row_rng[row].choice(q.shape[0], p=q)
                    )
                    q_dists[int(row)].append(q)
                else:
                    q_dists[int(row)].append(None)
            tok = jnp.asarray(drafts_np[:, g: g + 1])
            p = p + 1

        window = jnp.concatenate([cur, jnp.asarray(drafts_np)], axis=1)
        t_logits, self.cache = self._verify(
            self.params, window, pos_dev, self.cache, bt,
            **self._lora_kwargs(self.row_adapter),
        )
        t_log_z = (
            np.asarray(self._log_normalizers(t_logits))
            if any(self.row_sampling[row].logprobs for row in active_rows)
            else None
        )
        t_np = np.asarray(t_logits, dtype=np.float32)  # [B, gamma+1, V]

        for row in active_rows:
            sp = self.row_sampling[row]
            rng = self.row_rng[row]
            if sp.temperature <= 0.0:
                preds = t_np[row].argmax(-1).astype(np.int32)
                match = drafts_np[row] == preds[:gamma]
                n = int(np.argmin(match)) if not match.all() else gamma
                commit = [*drafts_np[row, :n].tolist(), int(preds[n])]
            else:
                commit, n = rejection_sample_commit(
                    drafts_np[row].tolist(),
                    q_dists[int(row)],
                    lambda g, row=row, sp=sp: filtered_probs_host(
                        t_np[row, g], sp
                    ),
                    rng,
                )
            self._commit_row(row, commit, n, t_np, t_log_z)

    def _done_reason(self, row: int, out: list[int]) -> tuple[str, int] | None:
        """(finish_reason, tokens_to_trim) once a row's output is complete,
        else None — the ONE copy of the stop logic, shared by the plain
        retire path and the speculative commit loop so the two cannot
        drift. Precedence: eos (the model's own stop, kept in the output),
        then a stop sequence (trimmed from the output), then the length
        budget."""
        if self.eos_id is not None and out and out[-1] == self.eos_id:
            return "eos", 0
        sp = self.row_sampling[row]
        if sp is not None:
            for s in sp.stop_sequences:
                if len(out) >= len(s) and tuple(out[-len(s):]) == s:
                    return "stop", len(s)
        if len(out) >= self.budget[row]:
            return "length", 0
        return None

    def _retire_if_done(self, row: int) -> None:
        verdict = self._done_reason(row, self.results[int(self.row_request[row])])
        if verdict is not None:
            self._retire(row, *verdict)

    def _retire(self, row: int, reason: str, trim: int = 0) -> None:
        """Retire a row unconditionally: trim, record the finish reason,
        free the row and its pages. The _retire_if_done path and the
        constraint-terminal/callable-error paths all land here."""
        req = int(self.row_request[row])
        out = self.results[req]
        if trim:
            del out[len(out) - trim:]
            lp = self.results_logprobs.get(req)
            if lp is not None:
                del lp[len(lp) - trim:]
        self.finish[req] = reason
        self.active[row] = False
        self.done[req] = True
        self.row_request[row] = -1
        self.row_sampling[row] = None
        self.row_rng[row] = None
        self.row_adapter[row] = 0
        used = set(self.block_table[row].tolist()) - {_SCRATCH_PAGE}
        for page in sorted(used, reverse=True):
            self._release_page(page)
        self.block_table[row, :] = _SCRATCH_PAGE
        # pos stays for inspection; scratch-page writes are masked
        if self._monitor is not None:
            self._monitor.on_done(
                req, reason, tokens=len(out), error=self.errors.get(req)
            )

    # -------------------------------------------------------------- results
    @property
    def stats(self) -> dict:
        """Operator counters — occupancy, page accounting, lifetime
        totals, prefix-cache stats. Cheap to read every scrape; a serving
        loop exports these however it likes (the service's Prometheus
        registry, logs, ...)."""
        return {
            "active_rows": int(self.active.sum()),
            "prefilling_rows": len(self.prefill_state),
            "max_batch": int(self.active.shape[0]),
            "free_pages": len(self.free_pages),
            "parked_pages": len(self.evictable),
            "held_pages": int((self.page_ref > 0).sum()),
            "requests_submitted": self._next_request_id,
            "requests_finished": sum(1 for v in self.done.values() if v),
            "tokens_generated": self.n_tokens_generated,
            # plain steps dispatched before the step before them was
            # landed / with nothing in flight (after a drain, or beside a
            # steered row)
            "steps_ahead": self._steps_ahead,
            "steps_synchronous": self._steps_synchronous,
            "prefix_cache": dict(self.prefix_stats),
        }

    def is_done(self, request_id: int) -> bool:
        return self.done.get(request_id, False)

    def result(self, request_id: int) -> list[int]:
        """Generated tokens for a request (first token included). Results
        are held until ``release`` — a long-running server should release
        each consumed result or host memory grows with request count."""
        if request_id not in self.results:
            if self.done.get(request_id):
                raise KeyError(f"request {request_id} was released")
            raise KeyError(f"unknown request {request_id}")
        if not self.done[request_id]:
            raise RuntimeError(f"request {request_id} still decoding")
        return list(self.results[request_id])

    def result_logprobs(self, request_id: int) -> list[float]:
        """Per-token log-probabilities for a finished request that was
        submitted with ``SamplingParams(logprobs=True)`` — same length and
        order as ``result`` (trimmed stop sequences drop their logprobs
        too). Unfiltered-distribution semantics: see SamplingParams."""
        if request_id not in self.done:
            raise KeyError(f"unknown request {request_id}")
        if request_id not in self.results_logprobs:
            if self.done[request_id] and request_id not in self.results:
                raise KeyError(f"request {request_id} was released")
            raise KeyError(
                f"request {request_id} did not record logprobs "
                "(submit with SamplingParams(logprobs=True))"
            )
        if not self.done[request_id]:
            raise RuntimeError(f"request {request_id} still decoding")
        return list(self.results_logprobs[request_id])

    def request_error(self, request_id: int) -> str | None:
        """repr of the user-callable exception that retired a request with
        finish reason 'error', else None. Survives ``release``."""
        return self.errors.get(request_id)

    def finish_reason(self, request_id: int) -> str:
        """'eos' | 'stop' | 'length' | 'constraint' | 'error' |
        'cancelled' for a
        finished request; survives ``release`` (a string per request,
        like the done-flag)."""
        if request_id not in self.finish:
            if self.done.get(request_id) is False:
                raise RuntimeError(f"request {request_id} still decoding")
            raise KeyError(f"unknown request {request_id}")
        return self.finish[request_id]

    def cancel(self, request_id: int) -> None:
        """Abort a still-decoding request: its row and pages free
        immediately (the next admission can use them), the tokens
        generated so far stay readable via ``result``, and
        ``finish_reason`` reports 'cancelled'. Cancelling a finished or
        released request is a no-op (the cancel raced completion — the
        caller shouldn't have to care who won); an id the batcher never
        issued raises KeyError like every other request API. The step in
        flight lands first: its token is one of those generated so far."""
        self._land()
        for row in np.flatnonzero(self.active):
            if int(self.row_request[row]) == request_id:
                self._retire(int(row), "cancelled")
                return
        for row, rec in list(self.prefill_state.items()):
            if rec["req"] == request_id:
                # admission still interleaving
                self._finish_unadmitted(row, rec, "cancelled")
                return
        if request_id not in self.done:
            raise KeyError(f"unknown request {request_id}")

    def preempt(self, request_id: int) -> bool:
        """Evict a request whose INTERLEAVED admission is still prefilling:
        its pages free immediately and the request is erased as if never
        submitted (the id is dead; the caller re-submits the same prompt
        later and the prefill recomputes — vLLM-style recompute preemption,
        restricted to the pre-first-token window where recomputation is
        trivially exact because there is nothing else to reproduce).
        Returns False once the request has produced a token (decoding),
        finished, or is unknown — callers that need to stop a decoding
        request want :meth:`cancel`, which keeps its partial output."""
        self._land()
        for row, rec in list(self.prefill_state.items()):
            if rec["req"] == request_id:
                self._abandon(row, rec)
                self.results.pop(request_id, None)
                self.done.pop(request_id, None)
                if self._monitor is not None:
                    self._monitor.on_preempt(request_id)
                return True
        return False

    def release(self, request_id: int) -> None:
        """Drop a finished request's stored result (pages were already
        recycled at retirement; this frees the host-side token list). The
        done-flag and finish reason are kept — small per-request scalars —
        so ``is_done``/``finish_reason`` stay observable and a poller
        can't spin forever on a released id; ``result`` then reports
        'released', not 'unknown'. A request still decoding is refused,
        once the step in flight, which may be its last, has landed."""
        if self.done.get(request_id) is False:
            self._land()
            if not self.done[request_id]:
                raise RuntimeError(f"request {request_id} still decoding")
        self.results.pop(request_id, None)
        self.results_logprobs.pop(request_id, None)

    def run_to_completion(self, max_steps: int = 10_000) -> None:
        for _ in range(max_steps):
            if not self.busy:
                return
            self.step()
        raise RuntimeError("run_to_completion exceeded max_steps")
