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


# ------------------------------------------- the stacked leaf and the write

LAYERS = 3


def stacked_case(config, seed=0, lengths=LENGTHS):
    """q, a leaf stacked over LAYERS layers (each layer its own values), one
    table in which every row's pages lie scattered and apart (no row's
    boundary page is another row's: a shared prefix is never written, the
    cursor starts past it), positions, and every row's new token. Entries
    past a row's live count name a page no row holds, which the einsum
    oracle gathers."""
    c = config
    B = len(lengths)
    ks = jax.random.split(jax.random.PRNGKey(seed + 100), 5)
    q = jax.random.normal(ks[0], (B, c.n_heads, 1, c.head_dim), c.dtype)
    shape = (LAYERS, N_PAGES, c.kv_heads, PS, c.head_dim)
    pool = {
        "k": jax.random.normal(ks[1], shape, jnp.float32).astype(c.dtype),
        "v": jax.random.normal(ks[2], shape, jnp.float32).astype(c.dtype),
    }
    new = tuple(
        jax.random.normal(k, (B, c.kv_heads, c.head_dim), jnp.float32)
        .astype(c.dtype)
        for k in ks[3:]
    )
    pages = np.random.default_rng(seed).permutation(N_PAGES - 2) + 1  # 1..38
    table = pages[: B * P].reshape(B, P).astype(np.int32)
    for b, length in enumerate(lengths):
        table[b, -(-length // PS):] = pages[-1]
    positions = jnp.asarray(lengths, jnp.int32)[:, None] - 1
    return q, pool, jnp.asarray(table), positions, new


def appended(pool, layer, table, lengths, new):
    """The pool after ``paged_append`` put every row's new token at slot
    ``length - 1`` of ``layer``'s slice: rows of length 0 write nothing."""
    from bee_code_interpreter_tpu.ops.paged_kv_cache import paged_append

    lengths = np.asarray(lengths)
    rows = np.flatnonzero(lengths > 0)
    at = jnp.asarray(lengths[rows] - 1)
    c_layer = paged_append(
        {n: x[layer] for n, x in pool.items()},
        new[0][rows][:, None], new[1][rows][:, None],
        jnp.take_along_axis(table[rows], (at // PS)[:, None], axis=1),
        (at % PS)[:, None],
    )
    return {n: x.at[layer].set(c_layer[n]) for n, x in pool.items()}


def written(q, pool, table, lengths, layer, new, **kw):
    """(attention, pool) of the kernel's form with the write."""
    out, k, v = paged_attention.paged_decode_attention(
        q[:, :, 0], pool["k"], pool["v"], table, jnp.asarray(lengths, jnp.int32),
        layer=layer, k_new=new[0], v_new=new[1], **kw,
    )
    return out, {"k": k, "v": v}


def bits(x):
    return np.asarray(x.astype(jnp.float32))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("layer", range(LAYERS))
def test_kernel_at_a_layer_index_reads_and_writes_that_layer_alone(layer, dtype):
    config = config_for(4, 128, dtype)
    q, pool, table, positions, new = stacked_case(config, seed=5)
    got, after = written(q, pool, table, LENGTHS, jnp.int32(layer), new)
    want_pool = appended(pool, layer, table, LENGTHS, new)
    # the einsum oracle on that layer's slice, the token appended first
    want = gathered(
        q, {n: x[layer] for n, x in want_pool.items()}, table, positions, config
    )
    B = len(LENGTHS)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        bits(got).reshape(B, 1, -1)[1:], want[1:], atol=tol, rtol=tol
    )
    for n in pool:
        np.testing.assert_array_equal(bits(after[n]), bits(want_pool[n]))
        for other in set(range(LAYERS)) - {layer}:  # not a byte elsewhere
            np.testing.assert_array_equal(
                bits(after[n][other]), bits(pool[n][other])
            )
    # and it reads what it wrote: the read-only form over the written slice
    np.testing.assert_array_equal(
        bits(got),
        bits(paged_attention.paged_decode_attention(
            q[:, :, 0], after["k"][layer], after["v"][layer], table,
            jnp.asarray(LENGTHS, jnp.int32),
        )),
    )


# a slot at a page's first row, at its last row, and a boundary crossed
# between two steps (the last row of one page, then the first of the next)
WRITE_STEPS = {
    "first_row_of_a_page": [(1, PS + 1, 2 * PS + 1)],
    "last_row_of_a_page": [(PS, 2 * PS, P * PS)],
    "boundary_crossed_between_two_steps": [(PS, 3 * PS, 21), (PS + 1, 3 * PS + 1, 22)],
    "a_row_of_length_0": [(0, 5, 0)],
}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("steps", WRITE_STEPS.values(), ids=WRITE_STEPS.keys())
def test_the_write_in_place_puts_exactly_what_paged_append_puts(steps, dtype):
    config = config_for(2, 128, dtype)
    q, pool, table, _, _ = stacked_case(config, seed=6, lengths=steps[0])
    want = pool
    for step, lengths in enumerate(steps):
        _, _, _, _, new = stacked_case(config, seed=7 + step, lengths=lengths)
        _, pool = written(q, pool, table, lengths, 1, new)
        want = appended(want, 1, table, lengths, new)
    for n in pool:
        np.testing.assert_array_equal(bits(pool[n]), bits(want[n]))
    assert np.isfinite(bits(pool["k"])).all()


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
def test_rows_that_share_the_scratch_page_write_it_one_after_another(dtype):
    """Dead rows: every table entry names the scratch page, the cursors are
    wherever their last tenants left them. Distinct slots all land (a
    later row fetches the page with the earlier rows' writes in it); the
    same slot keeps the last row's token."""
    config = config_for(2, 128, dtype)
    lengths = (3, 7, PS + 2, 7, 20)  # rows 1 and 3 write slot 6 of page 0
    q, pool, table, _, new = stacked_case(config, seed=8, lengths=lengths)
    live = table[4]
    table = jnp.zeros_like(table).at[4].set(live)  # row 4 lives, the rest are dead
    _, after = written(q, pool, table, lengths, 0, new)
    # what the scatter gives with the later of two equal slots applied last
    want = appended(pool, 0, table[:3], lengths[:3], tuple(x[:3] for x in new))
    want = appended(want, 0, table[3:], lengths[3:], tuple(x[3:] for x in new))
    for n, token in zip(("k", "v"), new):
        np.testing.assert_array_equal(bits(after[n]), bits(want[n]))
        np.testing.assert_array_equal(bits(after[n][0, 0, :, 6]), bits(token[3]))
        np.testing.assert_array_equal(bits(after[n][0, 0, :, 2]), bits(token[0]))


# ------------------------------------ the decode program on the new path


def slice_path(real):
    """``paged_decode_attention`` as the decode program ran it before the
    write was fused: the layer's slice cut out of the stacked leaf,
    ``paged_append``'s scatter, the kernel on the slice, the slice put
    back. Same arithmetic, so the same bits."""
    from bee_code_interpreter_tpu.ops.paged_kv_cache import paged_append

    def call(q, k_pages, v_pages, table, lengths, sm_scale=None, mesh=None,
             layer=0, k_new=None, v_new=None):
        at = lengths - 1
        ps = k_pages.shape[3]
        c_layer = paged_append(
            {"k": k_pages[layer], "v": v_pages[layer]},
            k_new[:, None], v_new[:, None],
            jnp.take_along_axis(table, (at // ps)[:, None], axis=1),
            (at % ps)[:, None],
        )
        out = real(q, c_layer["k"], c_layer["v"], table, lengths,
                   sm_scale=sm_scale, mesh=mesh)
        return (
            out, k_pages.at[layer].set(c_layer["k"]),
            v_pages.at[layer].set(c_layer["v"]),
        )

    return call


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("tp", [None, 2], ids=["alone", "tp2"])
def test_decode_step_returns_the_slice_paths_pool_bit_for_bit(
    monkeypatch, tp, dtype
):
    config = config_for(2, 128, dtype, kvh=2)
    params = T.init_params(config, jax.random.PRNGKey(1))
    mesh = None if tp is None else Mesh(np.array(jax.devices()[:tp]), ("tp",))
    B, ps, n_pages, width = 3, 4, 23, 4
    # every row's pages scattered over the pool, page 0 the scratch page
    table = jnp.asarray(
        np.random.default_rng(3).permutation(n_pages - 1)[: B * width]
        .reshape(B, width) + 1, jnp.int32,
    )
    engage(monkeypatch)

    def run(steps=4):
        pool = alloc_paged_cache(config, n_pages, ps)
        pos = jnp.asarray([0, 3, 6], jnp.int32)  # row 1 crosses a page at once
        token = jnp.asarray([[5], [3], [7]], jnp.int32)
        step = jax.jit(T.decode_step_paged, static_argnames=("config", "mesh"))
        out = []
        for _ in range(steps):
            logits, pool = step(
                params, token, pos, pool, table, config=config, mesh=mesh
            )
            out.append(logits)
            token = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
            pos = pos + 1
        return out, pool

    got_logits, got = run()
    monkeypatch.setattr(
        paged_attention, "paged_decode_attention",
        slice_path(paged_attention.paged_decode_attention),
    )
    want_logits, want = run()
    assert bits(got["k"]).any()
    for n in want:
        np.testing.assert_array_equal(bits(got[n]), bits(want[n]))
    for g, w in zip(got_logits, want_logits):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def pool_ops(jaxpr, n_pages, found=None, scans=None):
    """Every equation of ``jaxpr`` and of what it calls (not the Pallas
    kernel's own body) with an operand or a result that has an axis of
    ``n_pages``: (primitive names, scan equations met on the way)."""
    found = [] if found is None else found
    scans = [] if scans is None else scans
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            scans.append(eqn)
        if any(
            n_pages in getattr(v.aval, "shape", ())
            for v in (*eqn.invars, *eqn.outvars)
        ):
            found.append(eqn.primitive.name)
        if eqn.primitive.name == "pallas_call":
            continue
        for value in eqn.params.values():
            inner = getattr(value, "jaxpr", value)
            if hasattr(inner, "eqns"):
                pool_ops(inner, n_pages, found, scans)
    return found, scans


MOVES_THE_POOL = {"scatter", "gather", "dynamic_slice", "dynamic_update_slice"}


@pytest.mark.parametrize("case,moves", [
    ("kernel", False), ("kernel_tp2", False), ("int8_pool", True),
    ("window_of_two", True),
])
def test_the_traced_program_moves_no_part_of_the_pool_on_the_kernels_path(
    monkeypatch, case, moves
):
    """The jaxpr of the decode step: on the kernel's path nothing cuts,
    scatters into, gathers from or puts back anything with the pool's
    ``n_pages`` axis, and the pool is the layer scan's carry, never its
    ``xs`` or ``ys``. The slice path (an int8 pool, a window of two) may,
    inside the same scan. The ledger keeps ten instructions a cell: a copy
    of the pool brought back would not show there."""
    engage(monkeypatch)
    config = config_for(
        2, 128, jnp.float32, kvh=2,
        kv_cache_dtype="int8" if case == "int8_pool" else "bf16",
    )
    params = T.init_params(config, jax.random.PRNGKey(0))
    n_pages, W = 23, 2 if case == "window_of_two" else 1  # 23: no other axis
    pool = alloc_paged_cache(config, n_pages, 4)
    mesh = Mesh(np.array(jax.devices()[:2]), ("tp",)) if case == "kernel_tp2" else None
    jaxpr = jax.make_jaxpr(
        lambda params, pool: T.decode_window_paged(
            params, jnp.ones((3, W), jnp.int32), jnp.asarray([0, 3, 6], jnp.int32),
            pool, jnp.ones((3, 4), jnp.int32), config, mesh=mesh,
        )
    )(params, pool)
    found, scans = pool_ops(jaxpr.jaxpr, n_pages)
    assert bool(MOVES_THE_POOL & set(found)) is moves, found
    assert ("pallas_call" in found) is not moves
    (scan,) = scans  # one scan form
    n_consts, n_carry = scan.params["num_consts"], scan.params["num_carry"]
    has_pool = [n_pages in v.aval.shape for v in scan.invars]
    assert sum(has_pool[n_consts:n_consts + n_carry]) == len(pool)  # carried
    assert not any(has_pool[:n_consts]) and not any(has_pool[n_consts + n_carry:])
    assert not any(n_pages in v.aval.shape for v in scan.outvars[n_carry:])


@pytest.mark.parametrize("case,reads,writes", [
    ("plain", "pages_in_place", "in_place"),
    ("cpu", "gathered", "scattered"),
    ("int8_pool", "gathered", "scattered"),
    ("head_of_64", "gathered", "scattered"),
])
def test_kv_telemetry_says_how_the_program_writes_as_it_says_how_it_reads(
    monkeypatch, case, reads, writes
):
    engage(monkeypatch, case != "cpu")
    config = config_for(
        2, 64 if case == "head_of_64" else 128, jnp.float32, kvh=1,
        kv_cache_dtype="int8" if case == "int8_pool" else "bf16",
    )
    b = ContinuousBatcher(
        T.init_params(config, jax.random.PRNGKey(0)), config, max_batch=2,
        n_pages=8, page_size=4, max_pages_per_seq=4,
    )
    telemetry = b.kv_telemetry()
    assert telemetry["decode_attention"] == reads
    assert telemetry["decode_append"] == writes
