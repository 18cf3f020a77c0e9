"""Sarathi-style interleaved chunked admission: a long prompt's prefill
advances one window per step while other rows keep decoding — the result
must be IDENTICAL to the blocking admission (same window program family),
and the scheduler bookkeeping (occupancy, pages, cancel, snapshot) must
treat a prefilling row as occupied-but-not-active."""

import dataclasses
import pickle

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from bee_code_interpreter_tpu.models import transformer as T
from bee_code_interpreter_tpu.models.engine import Engine
from bee_code_interpreter_tpu.models.lora import init_lora, merge_lora
from bee_code_interpreter_tpu.models.serving import (
    ContinuousBatcher,
    SamplingParams,
)

CFG = dataclasses.replace(
    T.TransformerConfig.tiny(), dtype=jnp.float32, n_kv_heads=2
)
PARAMS = T.init_params(CFG, jax.random.PRNGKey(0))
LONG = [int(x) for x in np.random.default_rng(7).integers(0, 200, 21)]
SHORT = [5, 3, 7, 2]


def make(**kw):
    kw.setdefault("max_batch", 2)
    kw.setdefault("n_pages", 32)
    kw.setdefault("page_size", 4)
    kw.setdefault("max_pages_per_seq", 8)
    return ContinuousBatcher(PARAMS, CFG, **kw)


def solo(prompt, n, sampling=None):
    b = make(max_batch=1)
    r = b.submit(prompt, n, sampling=sampling)
    b.run_to_completion()
    return b.result(r)


def test_interleaved_matches_blocking_and_solo():
    want = solo(LONG, 5)
    b = make()
    r = b.submit(LONG, 5, interleave_admission=8)
    assert b.results[r] == []  # nothing yet: no model ran at submit
    assert b.stats["prefilling_rows"] == 1
    b.run_to_completion()
    assert b.result(r) == want
    assert b.finish_reason(r) == "length"
    assert b.stats["prefilling_rows"] == 0


def test_interleaved_sampled_with_logprobs_matches_blocking():
    sp = SamplingParams(temperature=0.7, top_k=30, seed=11, logprobs=True)
    blocking = make()
    rb = blocking.submit(LONG, 5, sampling=sp)
    blocking.run_to_completion()
    b = make()
    r = b.submit(LONG, 5, sampling=sp, interleave_admission=4)
    b.run_to_completion()
    assert b.result(r) == blocking.result(rb)
    # logprobs agree to reduction-order ulps: the window family and the
    # one-shot prefill are numerically distinct programs (tokens are
    # pinned exact; the drift lives below sampling resolution)
    assert b.result_logprobs(r) == pytest.approx(
        blocking.result_logprobs(rb), rel=1e-4
    )


def test_other_rows_keep_decoding_during_admission():
    """The point of interleaving: a short request decodes a token on every
    step while the long prompt's prefill is still windowing in."""
    b = make()
    r_short = b.submit(SHORT, 8)
    r_long = b.submit(LONG, 4, interleave_admission=4)  # 6 windows of 4
    produced = []
    while b.prefill_state:
        before = len(b.results[r_short])
        b.step()
        produced.append(len(b.results[r_short]) - before)
    # every interleave step also advanced the short row (until it retired):
    # it dispatched the row's step behind the window, and landed the token
    # of the step before, so the first call's token is read after the second
    assert produced[0] == 0 and b.busy
    assert all(d == 1 for d in produced[1: min(len(produced), 7)])
    assert len(produced) >= 6
    b.run_to_completion()
    assert b.result(r_short) == solo(SHORT, 8)
    assert b.result(r_long) == solo(LONG, 4)


def test_interleaved_registers_prefix_pages():
    b = make(prefix_cache=True)
    r1 = b.submit(LONG, 4, interleave_admission=4)
    b.run_to_completion()
    r2 = b.submit(LONG, 4)  # repeat: must hit the pages the windows wrote
    b.run_to_completion()
    assert b.prefix_stats["hits"] >= 1
    assert b.result(r1) == b.result(r2) == solo(LONG, 4)


def test_cancel_mid_prefill_releases_everything():
    b = make()
    r = b.submit(LONG, 4, interleave_admission=4)
    b.step()  # one window in
    assert b.prefill_state
    b.cancel(r)
    assert not b.prefill_state
    assert b.finish_reason(r) == "cancelled"
    assert b.result(r) == []
    b.run_to_completion()
    assert int(b.stats["held_pages"]) == 0
    # the freed row and pages admit a fresh request
    r2 = b.submit(LONG, 4)
    b.run_to_completion()
    assert b.result(r2) == solo(LONG, 4)


def test_snapshot_mid_prefill_resumes_exactly():
    want = solo(LONG, 5)
    a = make()
    r = a.submit(LONG, 5, interleave_admission=4)
    a.step(); a.step()  # part-way through the windows
    snap = pickle.dumps(a.state_dict())
    del a
    b = make()
    b.load_state_dict(pickle.loads(snap))
    assert b.prefill_state  # resumed mid-admission
    b.run_to_completion()
    assert b.result(r) == want


def test_speculative_interleaved_matches_solo():
    draft_cfg = dataclasses.replace(CFG, n_layers=1)
    draft_params = T.init_params(draft_cfg, jax.random.PRNGKey(1))
    want_b = make(
        draft_params=draft_params, draft_config=draft_cfg, gamma=3,
    )
    rb = want_b.submit(LONG, 5)
    want_b.run_to_completion()
    b = make(draft_params=draft_params, draft_config=draft_cfg, gamma=3)
    r = b.submit(LONG, 5, interleave_admission=4)
    b.run_to_completion()
    assert b.result(r) == want_b.result(rb) == solo(LONG, 5)


def test_width_validated_and_row_occupancy():
    b = make()
    with pytest.raises(ValueError, match="interleave_admission"):
        b.submit(LONG, 4, interleave_admission=3)  # not a page multiple
    r1 = b.submit(LONG, 4, interleave_admission=4)
    r2 = b.submit(SHORT, 4)  # second row
    with pytest.raises(RuntimeError, match="no free batch row"):
        b.submit(SHORT, 4)  # prefilling row counts as occupied
    b.run_to_completion()
    assert b.result(r1) == solo(LONG, 4)
    assert b.result(r2) == solo(SHORT, 4)


def test_engine_passthrough():
    want = solo(LONG, 4)
    eng = Engine(make())
    t = eng.submit(LONG, 4, interleave_admission=4)
    eng.run_to_completion()
    assert eng.result(t) == want


def test_engine_validates_width_eagerly():
    eng = Engine(make())
    with pytest.raises(ValueError, match="interleave_admission"):
        eng.submit(LONG, 4, interleave_admission=3)  # fails AT submit


def test_interleaved_speculative_preserves_shared_draft_prefix():
    """Zeroing discipline under speculative + prefix cache: an interleaved
    admission hitting a shared prefix must zero only its FRESH draft
    pages — wiping the matched pages would corrupt the draft K/V a
    decoding batch-mate is reading right now."""
    draft_cfg = dataclasses.replace(CFG, n_layers=1)
    draft_params = T.init_params(draft_cfg, jax.random.PRNGKey(1))

    def spec(**kw):
        return make(draft_params=draft_params, draft_config=draft_cfg,
                    gamma=3, prefix_cache=True, **kw)

    solo_b = spec(max_batch=1)
    rs = solo_b.submit(LONG, 6)
    solo_b.run_to_completion()
    want = solo_b.result(rs)

    b = spec()
    r1 = b.submit(LONG, 6)  # registers the prefix pages
    b.step()  # r1 mid-decode, sharing its prefix
    r2 = b.submit(LONG, 6, interleave_admission=4)  # hits the prefix
    b.run_to_completion()
    assert b.result(r1) == want  # batch-mate untouched by the admission
    assert b.result(r2) == want


def test_bad_seed_releases_pages_even_at_activation():
    """A first-token failure AFTER the pages were allocated (e.g. a bad
    rng seed surfacing at activation) must release them — on the blocking
    path by propagating post-release, on the interleaved path by failing
    the ticket without crashing the step loop."""
    b = make()
    with pytest.raises(ValueError):
        b.submit(SHORT, 4, sampling=SamplingParams(seed=-1))
    assert int(b.stats["held_pages"]) == 0  # blocking path released
    r = b.submit(SHORT, 4, sampling=SamplingParams(seed=-1),
                 interleave_admission=4)
    b.run_to_completion()  # the failure lands on the ticket, loop survives
    assert b.finish_reason(r) == "error"
    assert "ValueError" in b.request_error(r)
    assert int(b.stats["held_pages"]) == 0


# ------------------------------------------------- one admission, two drives
SCALE = 2.0
_lora = init_lora(CFG, jax.random.PRNGKey(1), rank=4, targets=("wq", "wv"))
# init_lora zeroes B (an adapter equal to the base): give it a delta that
# visibly changes the logits
ADAPTER = {
    t: {
        "A": ab["A"],
        "B": jax.random.normal(
            jax.random.PRNGKey(101), ab["B"].shape, jnp.float32
        ) * 0.25,
    }
    for t, ab in _lora.items()
}
DRIVES = {
    "one_shot": {},
    "prefill_chunk": {"prefill_chunk": 4},
    "interleave_admission": {"interleave_admission": 8},
}


@pytest.mark.parametrize("pool", ["bf16", "int8"])
@pytest.mark.parametrize("row", ["base", "adapter", "prefix_hit"])
@pytest.mark.parametrize("drive", DRIVES)
def test_every_drive_admits_what_the_one_shot_program_admits(drive, row, pool):
    """Whatever the drive (blocking at the default width, blocking at
    ``prefill_chunk``, a window a step) and whatever sends a row through
    windows (an adapter, a prefix hit, the width itself), the request
    decodes what the one-shot program admits for the same weights."""
    cfg = dataclasses.replace(CFG, kv_cache_dtype=pool)
    geometry = dict(max_batch=2, n_pages=32, page_size=4, max_pages_per_seq=8)
    merged = merge_lora(PARAMS, ADAPTER, SCALE) if row == "adapter" else PARAMS
    one_shot = ContinuousBatcher(merged, cfg, **geometry)
    want_id = one_shot.submit(LONG, 5)
    one_shot.run_to_completion()

    b = ContinuousBatcher(
        PARAMS, cfg, **geometry,
        adapters=[ADAPTER] if row == "adapter" else None, lora_scale=SCALE,
        prefix_cache=row == "prefix_hit",
    )
    if row == "prefix_hit":  # a first tenant leaves LONG's pages indexed
        b.submit(LONG, 2)
        b.run_to_completion()
    r = b.submit(
        LONG, 5, adapter=0 if row == "adapter" else None, **DRIVES[drive]
    )
    b.run_to_completion()
    assert b.result(r) == one_shot.result(want_id)
    if row == "prefix_hit":
        assert b.prefix_stats["hits"] == 1
    assert int(b.stats["held_pages"]) == 0


def books(b):
    """What an admission borrows from the batcher, as comparable values."""
    kv = b.kv_telemetry()
    return {
        "free_pages": sorted(b.free_pages),
        "page_ref": b.page_ref.tolist(),
        "evictable": sorted(b.evictable),
        "rows": (b.active.tolist(), sorted(b.prefill_state), b.has_free_row()),
        "block_table": b.block_table.tolist(),
        "held": kv["pages_allocated_total"] - kv["pages_released_total"],
    }


def drive_to_failure(b, drive, **kw):
    """Submit LONG on ``drive`` into a batcher rigged to fail: the blocking
    drive raises out of ``submit``; the interleaved one returns an id whose
    ticket reads the error once the windows have run, and the step loop
    lives."""
    if drive == "blocking":
        with pytest.raises(RuntimeError, match="device lost"):
            b.submit(LONG, 4, **kw)
        return
    r = b.submit(LONG, 4, interleave_admission=4, **kw)
    while not b.is_done(r):
        b.step()
    assert b.finish_reason(r) == "error"
    assert "device lost" in b.request_error(r)
    assert b.result(r) == []


@pytest.mark.parametrize("prefix_hit", [False, True])
@pytest.mark.parametrize("windows_before", [0, 2])
@pytest.mark.parametrize("drive", ["blocking", "interleaved"])
def test_a_failing_window_gives_everything_back(
    drive, windows_before, prefix_hit
):
    """A device error out of the window program, at the first window or a
    later one: fresh pages return to the free list, shared ones drop the
    ref the admission took, the row frees, and a row decoding beside it
    never notices."""
    b = make(prefix_cache=prefix_hit)
    if prefix_hit:  # a first tenant leaves LONG's first two pages indexed
        first = b.submit(LONG[:9], 2)
        b.run_to_completion()
        b.release(first)
    r_short = b.submit(SHORT, 8)  # decodes beside the admission
    before = books(b)
    real, calls = b._window, []

    def window(*args, **kwargs):
        calls.append(1)
        if len(calls) > windows_before:
            raise RuntimeError("device lost")
        return real(*args, **kwargs)

    b._window = window
    # a width sends the blocking drive through windows too
    drive_to_failure(
        b, drive, **({"prefill_chunk": 4} if drive == "blocking" else {})
    )
    assert len(calls) == windows_before + 1
    if prefix_hit:
        assert b.prefix_stats["pages_reused"] == 2
    b._window = real
    assert books(b) == before
    # and the pool admits the same prompt again
    r = b.submit(LONG, 4)
    b.run_to_completion()
    assert b.result(r) == solo(LONG, 4)
    assert b.result(r_short) == solo(SHORT, 8)


@pytest.mark.parametrize("drive", ["blocking", "interleaved"])
def test_a_failure_while_draft_pages_are_zeroed_gives_everything_back(drive):
    draft_cfg = dataclasses.replace(CFG, n_layers=1)
    draft_params = T.init_params(draft_cfg, jax.random.PRNGKey(1))
    b = make(draft_params=draft_params, draft_config=draft_cfg, gamma=3)
    before = books(b)

    class Lost(dict):
        def items(self):
            raise RuntimeError("device lost")

    pool = b.draft_cache
    b.draft_cache = Lost(pool)
    drive_to_failure(b, drive)
    b.draft_cache = pool
    assert books(b) == before
    r = b.submit(LONG, 4)
    b.run_to_completion()
    assert b.result(r) == solo(LONG, 4)


@pytest.mark.parametrize("surface", ["validate_request", "submit", "engine"])
@pytest.mark.parametrize(
    "kwargs, message",
    [
        ({"prefill_chunk": 4, "interleave_admission": 4}, "pass one of them"),
        ({"prefill_chunk": 0}, "chunk must be >= 1"),
    ],
    ids=["both_widths", "chunk_0"],
)
def test_window_width_is_validated_once_for_every_surface(
    surface, kwargs, message
):
    """Both parameters name the window's width, so the pair is refused
    (one used to be dropped without a word), and a width of 0 is refused by
    ``validate_request`` itself: at the engine's intake, not minutes later."""
    b = make()
    call = {
        "validate_request": b.validate_request,
        "submit": b.submit,
        "engine": Engine(b).submit,
    }[surface]
    with pytest.raises(ValueError, match=message):
        call(LONG, 4, **kwargs)
    assert books(b) == books(make())
