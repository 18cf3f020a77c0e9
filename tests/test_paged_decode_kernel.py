"""The paged decode kernel (ops/paged_attention.py) against the path it
takes the place of: ``paged_read``'s gather and the grouped einsums of
``transformer._attend_paged``, which stay for every case the predicate
``reads_pages_in_place`` leaves them and are the oracle here.

CPU: the kernel runs in the Pallas interpreter, at a block of 32 slots so
that small tables still take several blocks (the copies' double buffer,
the online softmax across blocks, a boundary block with dead pages).
Mosaic lowers it at the benchmark's shapes in ``chip_smoke.py``."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from bee_code_interpreter_tpu.models import transformer as T
from bee_code_interpreter_tpu.models.serving import ContinuousBatcher
from bee_code_interpreter_tpu.ops import paged_attention
from bee_code_interpreter_tpu.ops.paged_kv_cache import alloc_paged_cache

PS, P, N_PAGES = 16, 6, 40
# a dead row, one token, a partial boundary page, exactly a page, a page
# and a slot, the full table
LENGTHS = (0, 1, 37, 16, 17, P * PS)
PROMPTS = [[5, 3, 7, 2, 9, 4, 1, 8], [3, 1, 4, 1, 5]]


@pytest.fixture(autouse=True)
def small_blocks(monkeypatch):
    monkeypatch.setattr(paged_attention, "PAGE_BLOCK_TOKENS", 2 * PS)


def engage(monkeypatch, on: bool = True):
    monkeypatch.setattr(paged_attention, "on_tpu", lambda: on)


def config_for(rep, dh, dtype, kvh=2, **kw):
    return dataclasses.replace(
        T.TransformerConfig.tiny(), d_model=kvh * rep * dh, n_heads=kvh * rep,
        n_kv_heads=kvh, dtype=dtype, **kw,
    )


def make_case(config, seed=0, lengths=LENGTHS):
    """q, one layer's pool slice and a table in which every row's pages lie
    scattered, row 3 shares row 2's first page (a shared prefix) and every
    entry past a row's live count names a page full of NaN."""
    c = config
    B = len(lengths)
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (B, c.n_heads, 1, c.head_dim), c.dtype)
    shape = (N_PAGES, c.kv_heads, PS, c.head_dim)
    k = np.array(jax.random.normal(ks[1], shape, jnp.float32))
    v = np.array(jax.random.normal(ks[2], shape, jnp.float32))
    k[N_PAGES - 1] = v[N_PAGES - 1] = np.nan
    table = (
        np.random.default_rng(seed).permutation(N_PAGES - 1)[: B * P]
        .reshape(B, P).astype(np.int32)
    )
    table[3, 0] = table[2, 0]
    for b, length in enumerate(lengths):
        table[b, -(-length // PS):] = N_PAGES - 1
    c_layer = {"k": jnp.asarray(k, c.dtype), "v": jnp.asarray(v, c.dtype)}
    positions = jnp.asarray(lengths, jnp.int32)[:, None] - 1
    return q, c_layer, jnp.asarray(table), positions


def gathered(q, c_layer, table, positions, config):
    """The einsum path, over a table whose dead entries name a finite page
    (it gathers them all, and 0 x NaN is NaN)."""
    table = jnp.where(table == N_PAGES - 1, 0, table)
    return np.asarray(
        T._attend_paged(q, c_layer, table, positions, config), np.float32
    )


def kernel(q, c_layer, table, positions, config):
    B, nh, _, dh = q.shape
    out = paged_attention.paged_decode_attention(
        q[:, :, 0], c_layer["k"], c_layer["v"], table, positions[:, 0] + 1,
        sm_scale=T._score_scale(config),
    )
    return np.asarray(out.reshape(B, 1, nh * dh), np.float32)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("dh", [64, 128])
@pytest.mark.parametrize("rep", [1, 4, 8])
def test_kernel_gives_what_the_gather_and_einsums_give(rep, dh, dtype):
    config = config_for(rep, dh, dtype)
    case = make_case(config)
    got, want = kernel(*case, config), gathered(*case, config)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    # row 0 is dead: the einsums give NaN there (a softmax over nothing),
    # the kernel zeros; no caller reads either
    np.testing.assert_allclose(got[1:], want[1:], atol=tol, rtol=tol)
    assert not got[0].any()


@pytest.mark.parametrize("multiplier", [None, 0.0078125, 0.25])
def test_kernel_scales_scores_as_the_configuration_says(multiplier):
    config = config_for(4, 128, jnp.float32, attention_multiplier=multiplier)
    case = make_case(config, seed=1)
    np.testing.assert_allclose(
        kernel(*case, config)[1:], gathered(*case, config)[1:],
        atol=1e-5, rtol=1e-5,
    )


@pytest.mark.parametrize("what", ["dead_slots", "dead_table_entries"])
def test_poison_beyond_what_is_live_changes_nothing(what):
    config = config_for(4, 128, jnp.float32)
    q, c_layer, table, positions = make_case(config, seed=2)
    base = kernel(q, c_layer, table, positions, config)
    assert np.isfinite(base).all()  # the NaN page is never read
    if what == "dead_slots":
        k, v = np.array(c_layer["k"]), np.array(c_layer["v"])
        for b, length in enumerate(LENGTHS):
            if length % PS == 0:  # no slot of a live page is dead
                continue
            page = int(table[b, length // PS])
            k[page, :, length % PS:] = 1e4
            v[page, :, length % PS:] = -1e4
        c_layer = {"k": jnp.asarray(k), "v": jnp.asarray(v)}
    else:
        # a sentinel and an index outside the pool, where NaN pages were
        dead = np.asarray(table) == N_PAGES - 1
        table = jnp.asarray(
            np.where(dead, np.where(np.arange(P) % 2, -1, N_PAGES + 7), table),
            jnp.int32,
        )
    np.testing.assert_array_equal(
        kernel(q, c_layer, table, positions, config), base
    )


@pytest.mark.parametrize("row", range(1, len(LENGTHS)))
def test_a_row_alone_gives_the_bits_it_gives_in_the_batch(row):
    config = config_for(4, 128, jnp.bfloat16)
    q, c_layer, table, positions = make_case(config, seed=3)
    batch = kernel(q, c_layer, table, positions, config)
    alone = kernel(
        q[row:row + 1], c_layer, table[row:row + 1], positions[row:row + 1],
        config,
    )
    np.testing.assert_array_equal(alone[0], batch[row])


def test_heads_that_do_not_share_evenly_are_refused():
    with pytest.raises(ValueError, match="multiple"):
        paged_attention.paged_decode_attention(
            jnp.zeros((1, 3, 128)), jnp.zeros((4, 2, 8, 128)),
            jnp.zeros((4, 2, 8, 128)), jnp.zeros((1, 2), jnp.int32),
            jnp.ones((1,), jnp.int32),
        )


# ------------------------------------------------------------ the predicate


@pytest.mark.parametrize("case,expected", [
    ("plain", True), ("cpu", False), ("window_of_two", False),
    ("int8_pool", False), ("sliding_window", False), ("head_of_64", False),
    ("tp2", True), ("tp_does_not_divide", False),
])
def test_the_predicate_reads_backend_window_pool_head_and_mesh(
    monkeypatch, case, expected
):
    engage(monkeypatch, case != "cpu")
    config = config_for(
        2, 64 if case == "head_of_64" else 128, jnp.bfloat16,
        kv_cache_dtype="int8" if case == "int8_pool" else "bf16",
        sliding_window=6 if case == "sliding_window" else None,
    )
    mesh = {
        "tp2": Mesh(np.array(jax.devices()[:2]), ("tp",)),
        "tp_does_not_divide": Mesh(np.array(jax.devices()[:4]), ("tp",)),
    }.get(case)
    assert paged_attention.reads_pages_in_place(
        alloc_paged_cache(config, 8, 4), 2 if case == "window_of_two" else 1,
        config.sliding_window, mesh,
    ) is expected


# -------------------------------------------------------------- the batcher


def served(params, config, n=6, **kw):
    b = ContinuousBatcher(
        params, config, max_batch=2, n_pages=24, page_size=4,
        max_pages_per_seq=8, **kw,
    )
    reqs = [b.submit(p, n) for p in PROMPTS]
    b.run_to_completion()
    return [b.result(r) for r in reqs], b.kv_telemetry()["decode_attention"]


@pytest.fixture(scope="module")
def f32_model():
    config = config_for(2, 128, jnp.float32, kvh=2)
    return config, T.init_params(config, jax.random.PRNGKey(0))


def test_batcher_through_the_kernel_gives_the_einsum_paths_tokens(
    monkeypatch, f32_model
):
    config, params = f32_model
    engage(monkeypatch, False)
    want, path = served(params, config)
    assert path == "gathered"
    engage(monkeypatch)
    got, path = served(params, config)
    assert path == "pages_in_place"
    assert got == want


def test_batcher_through_the_kernel_is_deterministic_in_bf16(monkeypatch):
    config = config_for(2, 128, jnp.bfloat16, kvh=1)
    params = T.init_params(config, jax.random.PRNGKey(0))
    engage(monkeypatch)
    first, path = served(params, config)
    assert path == "pages_in_place"
    assert served(params, config)[0] == first
    assert [len(tokens) for tokens in first] == [6, 6]


@pytest.mark.parametrize("extra", [
    {"kv_cache_dtype": "int8"}, {"sliding_window": 6},
], ids=["int8_pool", "sliding_window"])
def test_batcher_keeps_the_gather_where_the_kernel_does_not_reach(
    monkeypatch, extra
):
    config = config_for(2, 128, jnp.float32, kvh=1, **extra)
    params = T.init_params(config, jax.random.PRNGKey(0))
    engage(monkeypatch, False)
    want, _ = served(params, config, n=4)
    engage(monkeypatch)
    got, path = served(params, config, n=4)
    assert path == "gathered"
    assert got == want


def test_a_window_of_several_tokens_keeps_the_gather(monkeypatch, f32_model):
    config, params = f32_model
    pool = alloc_paged_cache(config, 8, 4)
    table = jnp.asarray([[1, 2, 3, 0]], jnp.int32)
    args = (params, jnp.asarray([[5, 3]], jnp.int32), jnp.asarray([2], jnp.int32))
    engage(monkeypatch, False)
    want, _ = T.decode_window_paged(*args, pool, table, config)
    engage(monkeypatch)
    monkeypatch.setattr(
        paged_attention, "paged_decode_attention",
        lambda *a, **k: pytest.fail("the kernel ran under a window of two"),
    )
    got, _ = T.decode_window_paged(*args, pool, table, config)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_under_a_tp_mesh_the_kernel_runs_by_kv_head_and_agrees(
    monkeypatch, f32_model
):
    config, params = f32_model
    engage(monkeypatch)
    want, _ = served(params, config)
    mesh = Mesh(np.array(jax.devices()[:2]), ("tp",))
    got, path = served(params, config, mesh=mesh)
    assert path == "pages_in_place"
    assert got == want


def test_sharded_kernel_equals_the_unsharded_one():
    config = config_for(4, 128, jnp.float32)
    q, c_layer, table, positions = make_case(config, seed=4)
    mesh = Mesh(np.array(jax.devices()[:2]), ("tp",))
    args = (q[:, :, 0], c_layer["k"], c_layer["v"], table, positions[:, 0] + 1)
    np.testing.assert_array_equal(
        np.asarray(paged_attention.paged_decode_attention(*args, mesh=mesh)),
        np.asarray(paged_attention.paged_decode_attention(*args)),
    )
