"""A prompt's K/V go into their pages through one donating program.

``ContinuousBatcher._seed_pool`` (``jit_seed_pool``) is
``ops.paged_kv_cache.seed_pool`` jitted with the pool donated: it takes the
prefill's K/V at their padded width and the prompt's true length as a traced
scalar, where the admission used to cut, pad, turn, cast and scatter eagerly
and the device copied every leaf of the pool whole to take one prompt's pages
(PERF.md, PR 38). Here, on the CPU: the program of a real batcher against the
eager ``seed_prefill`` on K/V cut to their true length, bit for bit in every
leaf of every kind of pool, and what an admission compiles and records. That
the compiled program writes in place on the chip is
``tests/test_projection_in_place.py``'s second part.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from bee_code_interpreter_tpu.models import transformer as T
from bee_code_interpreter_tpu.models.serving import ContinuousBatcher
from bee_code_interpreter_tpu.observability import DeviceMonitor, ServingMonitor
from bee_code_interpreter_tpu.ops.paged_kv_cache import BY_ROW_LEAVES, seed_prefill

PAGE = 4
PAGES = [7, 2, 11]  # the prompt's, neither in order nor side by side
WIDTH = PAGE * len(PAGES)
LENGTHS = (9, 11)  # two true lengths of the one padded width, inside the last page

PLAIN = T.TransformerConfig(
    vocab_size=128, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2, d_ff=128,
    max_seq_len=64, dtype=jnp.bfloat16,
)
CASES = {
    "bf16": PLAIN,
    "int8": dataclasses.replace(PLAIN, kv_cache_dtype="int8"),
    # one leaf, a token's latent beside its rotary key
    "latent": T.TransformerConfig(
        vocab_size=128, d_model=64, n_layers=2, n_heads=4, d_ff=128,
        max_seq_len=64, dtype=jnp.bfloat16, kv_lora_rank=32,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    ),
    # pages for layers 1 and 3 alone, rings beside them
    "window": T.TransformerConfig(
        vocab_size=128, d_model=48, n_layers=4, n_heads=4, n_kv_heads=2,
        head_dim=8, d_ff=96, max_seq_len=64, dtype=jnp.float32,
        sliding_window=4, position_embedding="rope_window",
        layer_types=("sliding_attention", "full_attention") * 2,
    ),
    # state by row beside the pages of the one attention layer in three
    "hybrid": T.TransformerConfig(
        vocab_size=128, d_model=64, n_layers=3, n_heads=4, n_kv_heads=2,
        d_ff=128, max_seq_len=64, dtype=jnp.float32,
        layer_types=("mamba", "attention", "mamba"), mamba_n_heads=8,
        mamba_d_head=16, mamba_d_state=16, mamba_chunk_size=32,
        position_embedding="nope",
    ),
    "tp": dataclasses.replace(PLAIN, dtype=jnp.float32),
}


def batcher_of(case: str, **kw) -> ContinuousBatcher:
    config = CASES[case]
    if case == "tp":
        kw["mesh"] = Mesh(np.array(jax.devices()[:2]), ("tp",))
    return ContinuousBatcher(
        T.init_params(config, jax.random.PRNGKey(0)), config, max_batch=2,
        n_pages=16, page_size=PAGE, max_pages_per_seq=8, **kw,
    )


def _filled(batcher) -> dict:
    """A pool of the batcher's shapes with something in every slot, on the
    host: what a recycled page holds must survive beside the written ones."""
    rng = np.random.default_rng(3)

    def noise(x):
        if x.dtype == jnp.int8:
            return rng.integers(-127, 128, x.shape, dtype=np.int8)
        return rng.standard_normal(x.shape).astype(x.dtype)

    return {name: noise(x) for name, x in batcher.cache.items()}


def _placed(batcher, pool: dict) -> dict:
    """A fresh copy on the device, where the batcher keeps its own (the
    program donates what it is given)."""
    return {
        name: jax.device_put(x, batcher.cache[name].sharding)
        for name, x in pool.items()
    }


def _prefill_kv(batcher, case: str) -> tuple:
    """K and V as ``forward(return_kv=True)`` hands them back: [attention
    layers, 1, kvh, WIDTH, dh], or the latent [layers, 1, WIDTH, width]
    alone, with real numbers past every true length too."""
    c = CASES[case]
    if c.kv_lora_rank:
        shapes = [(c.n_attention_layers, 1, WIDTH, c.latent_width)]
    else:
        shapes = [(c.n_attention_layers, 1, c.kv_heads, WIDTH, c.head_dim)] * 2
    kv = tuple(
        jax.random.normal(jax.random.PRNGKey(10 + i), shape, jnp.float32)
        .astype(c.dtype) for i, shape in enumerate(shapes)
    )
    if batcher.mesh is not None:  # as the prefill under the mesh leaves them
        kv = tuple(jax.device_put(x, batcher._pool_sharding()) for x in kv)
    return kv


def _the_compilers_division(eager: dict, compiled: dict) -> dict:
    """An int8 pool's scale is ``absmax / 127``: compiled, XLA multiplies by
    the constant's reciprocal (in this program as in the decode step's and
    the admission window's appends, which quantize under ``jit`` too), and
    the eager division may differ from it in the last bit of a scale and so
    by one in a value. The program is held, bit for bit, to the same
    function compiled on the cut K/V; and that to the eager one so far."""
    for name in ("k_s", "v_s"):
        np.testing.assert_array_almost_equal_nulp(
            np.asarray(compiled[name]), np.asarray(eager[name]), nulp=1
        )
    for name in ("k", "v"):
        apart = np.abs(
            np.asarray(compiled[name], np.int32) - np.asarray(eager[name], np.int32)
        )
        assert apart.max() <= 1 and (apart != 0).mean() < 0.01
    return compiled


@pytest.mark.parametrize("case", CASES)
def test_the_program_writes_what_eager_seed_prefill_writes(case):
    batcher = batcher_of(case)
    config = CASES[case]
    before = _filled(batcher)
    kv = _prefill_kv(batcher, case)
    pages = np.asarray(PAGES, np.int32)
    full = np.asarray(config.paged_layers, np.int32)
    for length in LENGTHS:
        # the parent's admission: the full layers' K/V cut to the true length
        parents = (
            {name: jnp.asarray(x) for name, x in before.items()},
            jnp.asarray(pages), *[x[full][:, 0, ..., :length, :] for x in kv],
        )
        want = seed_prefill(*parents)
        if case == "int8":
            want = _the_compilers_division(want, jax.jit(seed_prefill)(*parents))
        pool = _placed(batcher, before)
        got = batcher._seed_pool(pool, pages, np.int32(length), kv)
        assert set(got) == set(before)
        for name, leaf in got.items():
            assert leaf.sharding == batcher.cache[name].sharding
            leaf = np.asarray(leaf)
            np.testing.assert_array_equal(leaf, np.asarray(want[name]))
            if name in BY_ROW_LEAVES:  # rings and state are other programs'
                np.testing.assert_array_equal(leaf, before[name])
                continue
            untouched = np.setdiff1d(np.arange(leaf.shape[1]), pages)
            np.testing.assert_array_equal(
                leaf[:, untouched], before[name][:, untouched]
            )
            assert (leaf[:, pages] != before[name][:, pages]).any()
            # past the true length the last page holds zeros (an int8 pool:
            # zeros under a scale of zero), whatever the padded K/V held
            assert not leaf[:, PAGES[-1]][..., length % PAGE:, :].any()
        # the pool it was given went into the result
        assert all(x.is_deleted() for x in pool.values())
    # two lengths of one padded width: ONE program
    assert batcher._seed_pool._cache_size() == 1
    if case == "tp":
        assert len(got["k"].sharding.device_set) == 2


def test_a_length_past_the_pages_is_refused():
    batcher = batcher_of("bf16")
    kv = _prefill_kv(batcher, "bf16")
    with pytest.raises(ValueError, match="exceeds 2 pages of 4"):
        batcher._seed_pool(
            batcher.cache, np.asarray(PAGES[:2], np.int32), np.int32(9), kv
        )


@pytest.mark.parametrize("kind", ["plain", "draft", "tp"])
def test_an_admission_after_warm_up_compiles_nothing(kind):
    """``seed_pool`` compiles once a padded prompt width (twice with a draft
    pool beside the target's, whose leaves are another shape), beside the
    prefill's once; a second prompt of a width compiles nothing, and the
    admission's record keeps the key ``seed_pool``. Under a mesh too, where
    the pool reaches the program from the allocator, from itself and from
    the decode step: one sharding to the program's cache
    (``_pool_sharding``)."""
    extra, speculative = {}, kind == "draft"
    if speculative:
        draft = dataclasses.replace(PLAIN, n_layers=1)
        extra = dict(
            draft_params=T.init_params(draft, jax.random.PRNGKey(1)),
            draft_config=draft, gamma=2,
        )
    batcher = batcher_of("tp" if kind == "tp" else "bf16", **extra)
    device, serving = DeviceMonitor(), ServingMonitor()
    device.attach(batcher)
    serving.attach(batcher)

    def compiled() -> dict:
        functions = device.snapshot()["compile"]["functions"]
        return {name: fn["compiles"] for name, fn in functions.items()}

    for prompt in ([5, 3, 7], [1, 2, 3, 4, 5, 6]):  # one page, then two
        batcher.submit(prompt, 3)
        batcher.run_to_completion()
    warm = compiled()
    pools = 2 if speculative else 1
    assert warm["seed_pool"] == 2 * pools and warm["prefill_forward"] == 2
    for prompt in ([9, 8], [4, 4, 4, 4, 4]):  # the same widths, other lengths
        batcher.submit(prompt, 3)
        batcher.run_to_completion()
    assert compiled() == warm
    signatures = device.snapshot()["compile"]["functions"]["seed_pool"]["signatures"]
    assert len(signatures) == 2 * pools
    assert sum("int32[1]" in s for s in signatures) == pools  # the pages' array
    records = [
        a for s in serving.snapshot(steps=64)["steps"]["last"]
        for a in s.get("admissions", ())
    ]
    assert len(records) == 4
    assert all("seed_pool" in a["phase_ms"] for a in records)
