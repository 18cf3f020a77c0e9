"""Pallas paged-attention decode kernel (ops/paged_attention.py) — pinned
against the grouped-einsum oracle (the exact math the gather path
computes), and wired end-to-end through the batcher wherever the predicate
``paged_attention.reads_pages_in_place`` holds (patched on here: the CPU
is not a TPU). The slow lane's copy: ``tests/test_paged_decode_kernel.py``
holds the tier-1 cases.

CPU runs the kernel in Pallas interpreter mode; ``chip_smoke.py`` lowers it
with Mosaic and ``scripts/bench-decode.py`` times it."""

import dataclasses
import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from bee_code_interpreter_tpu.models.serving import ContinuousBatcher
from bee_code_interpreter_tpu.models.transformer import (
    TransformerConfig,
    init_params,
)
from bee_code_interpreter_tpu.ops import paged_attention
from bee_code_interpreter_tpu.ops.paged_attention import (
    paged_decode_attention,
)


def head_of_128(**kw):
    """A tiny model whose head fills the lane tile (the predicate's size)."""
    return dataclasses.replace(
        TransformerConfig.tiny(), d_model=256, n_heads=2, n_kv_heads=1, **kw
    )


def oracle(q, k_pages, v_pages, bt, lengths):
    """The gather path's math: contiguous view + grouped einsums + causal
    length mask, f32 statistics."""
    B, nh, dh = q.shape
    kvh, ps = k_pages.shape[1], k_pages.shape[2]
    P = bt.shape[1]
    rep = nh // kvh

    def view(pages):
        g = pages[bt]  # [B, P, kvh, ps, dh]
        return g.transpose(0, 2, 1, 3, 4).reshape(B, kvh, P * ps, dh)

    kf = view(k_pages).astype(jnp.float32)
    vf = view(v_pages).astype(jnp.float32)
    qg = q.reshape(B, kvh, rep, dh).astype(jnp.float32)
    s = jnp.einsum("bgrd,bgsd->bgrs", qg, kf) / math.sqrt(dh)
    visible = jnp.arange(P * ps)[None, :] < lengths[:, None]  # [B, S]
    s = jnp.where(visible[:, None, None, :], s, -jnp.inf)
    w = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bgrs,bgsd->bgrd", w, vf)
    return out.reshape(B, nh, dh)


def make_case(key, B, nh, kvh, ps, P, n_pages, dtype=jnp.float32):
    ks = jax.random.split(key, 5)
    q = jax.random.normal(ks[0], (B, nh, 128), dtype)
    k_pages = jax.random.normal(ks[1], (n_pages, kvh, ps, 128), dtype)
    v_pages = jax.random.normal(ks[2], (n_pages, kvh, ps, 128), dtype)
    # permuted, non-trivial page placement per row
    bt = jax.random.permutation(ks[3], n_pages)[: B * P].reshape(B, P)
    lengths = jax.random.randint(ks[4], (B,), 1, P * ps + 1)
    return q, k_pages, v_pages, bt.astype(jnp.int32), lengths


@pytest.mark.parametrize("nh,kvh", [(8, 2), (4, 4), (16, 2), (24, 2)])
def test_matches_oracle_gqa_shapes(nh, kvh):
    q, kp, vp, bt, lengths = make_case(
        jax.random.PRNGKey(0), B=3, nh=nh, kvh=kvh, ps=16, P=4, n_pages=20
    )
    got = paged_decode_attention(q, kp, vp, bt, lengths)
    want = oracle(q, kp, vp, bt, lengths)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


def test_bf16_pool_close_to_f32_oracle():
    q, kp, vp, bt, lengths = make_case(
        jax.random.PRNGKey(1), B=2, nh=8, kvh=2, ps=8, P=3, n_pages=12,
        dtype=jnp.bfloat16,
    )
    got = paged_decode_attention(q, kp, vp, bt, lengths)
    want = oracle(q, kp, vp, bt, lengths)
    np.testing.assert_allclose(
        np.asarray(got, dtype=np.float32), np.asarray(want),
        atol=3e-2, rtol=3e-2,
    )


def test_masked_slots_cannot_influence_output():
    q, kp, vp, bt, lengths = make_case(
        jax.random.PRNGKey(2), B=2, nh=4, kvh=2, ps=8, P=4, n_pages=16
    )
    lengths = jnp.asarray([5, 19], dtype=jnp.int32)
    base = paged_decode_attention(q, kp, vp, bt, lengths)
    # poison every slot at/after each row's length (per its own pages)
    kp2, vp2 = np.asarray(kp).copy(), np.asarray(vp).copy()
    bt_np = np.asarray(bt)
    for b in range(2):
        for logical in range(int(lengths[b]), 4 * 8):
            page, slot = bt_np[b, logical // 8], logical % 8
            kp2[page, :, slot] = 1e4
            vp2[page, :, slot] = -1e4
    poisoned = paged_decode_attention(
        q, jnp.asarray(kp2), jnp.asarray(vp2), bt, lengths
    )
    np.testing.assert_allclose(np.asarray(base), np.asarray(poisoned),
                               atol=1e-5, rtol=1e-5)


def test_batcher_kernel_path_matches_einsum_path(monkeypatch):
    """End to end: the batcher through the kernel produces the exact token
    streams of the einsum path (f32 config — the kernel sums a row's slots
    block by block where the einsums sum them at once, so bf16 near-ties
    could differ; determinism at bf16 is pinned separately below)."""
    cfg = head_of_128(dtype=jnp.float32)
    params = init_params(cfg, jax.random.PRNGKey(0))
    prompts = [[5, 3, 7, 2, 9, 4, 1, 8], [3, 1, 4, 1, 5]]

    def run(engaged):
        monkeypatch.setattr(paged_attention, "on_tpu", lambda: engaged)
        b = ContinuousBatcher(params, cfg, max_batch=2,
                              n_pages=24, page_size=4, max_pages_per_seq=8)
        assert b.kv_telemetry()["decode_attention"] == (
            "pages_in_place" if engaged else "gathered"
        )
        assert b.kv_telemetry()["decode_append"] == (
            "in_place" if engaged else "scattered"
        )
        reqs = [b.submit(p, 6) for p in prompts]
        b.run_to_completion()
        return [b.result(r) for r in reqs]

    assert run(True) == run(False)


def test_bf16_batcher_kernel_is_deterministic(monkeypatch):
    monkeypatch.setattr(paged_attention, "on_tpu", lambda: True)
    cfg = head_of_128()
    params = init_params(cfg, jax.random.PRNGKey(0))

    def run():
        b = ContinuousBatcher(params, cfg, max_batch=2, n_pages=24,
                              page_size=4, max_pages_per_seq=8)
        r = b.submit([5, 3, 7, 2, 9, 4, 1, 8], 6)
        b.run_to_completion()
        return b.result(r)

    assert run() == run()
    assert len(run()) == 6


def test_int8_pool_and_windows_keep_the_einsum_path(monkeypatch):
    """The predicate: int8 pools and sliding windows keep the gather where
    everything else about the program would take the kernel."""
    for extra in ({"kv_cache_dtype": "int8"}, {"sliding_window": 6}):
        cfg = head_of_128(**extra)
        params = init_params(cfg, jax.random.PRNGKey(0))

        def run(engaged):
            monkeypatch.setattr(paged_attention, "on_tpu", lambda: engaged)
            b = ContinuousBatcher(params, cfg, max_batch=1, n_pages=16,
                                  page_size=4, max_pages_per_seq=8)
            assert b.kv_telemetry()["decode_attention"] == "gathered"
            assert b.kv_telemetry()["decode_append"] == "scattered"
            r = b.submit([5, 3, 7, 2], 4)
            b.run_to_completion()
            return b.result(r)

        assert run(True) == run(False)


def test_validation():
    with pytest.raises(ValueError, match="multiple"):
        paged_decode_attention(
            jnp.zeros((1, 3, 128)), jnp.zeros((4, 2, 8, 128)),
            jnp.zeros((4, 2, 8, 128)), jnp.zeros((1, 2), jnp.int32),
            jnp.ones((1,), jnp.int32),
        )


def test_sentinel_block_table_entries_are_harmless():
    """-1 is a common block-table convention for 'no page'. The kernel's
    loop ends at the live page count, so an entry at or beyond a row's
    visible length is never read (identical output, no OOB in the Mosaic
    path)."""
    q, kp, vp, bt, lengths = make_case(
        jax.random.PRNGKey(7), B=2, nh=4, kvh=2, ps=8, P=4, n_pages=16
    )
    lengths = jnp.asarray([5, 9], dtype=jnp.int32)  # rows use 1 / 2 pages
    base = paged_decode_attention(q, kp, vp, bt, lengths)
    bt_sent = np.asarray(bt).copy()
    bt_sent[0, 1:] = -1  # pages past the visible length
    bt_sent[1, 2:] = -1
    out = paged_decode_attention(
        q, kp, vp, jnp.asarray(bt_sent, dtype=jnp.int32), lengths
    )
    np.testing.assert_allclose(np.asarray(base), np.asarray(out),
                               atol=1e-6, rtol=1e-6)
