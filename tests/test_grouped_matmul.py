"""The held experts' grouped matmul kernel (``ops/grouped_matmul.py``) in the
Pallas interpreter: against ``lax.ragged_dot`` and a float32 product written
out, alone over a merged stack of expert layers, and through the sorted
expert layer (``models/moe.held_experts_mlp``) under both routers, in the
common buffer and in the buffer of every pair the ``lax.cond`` falls back
to; the shape rule that picks it, and the batcher's word for it.

The kernel path is taken where ``grouped_matmul.on_tpu`` says so: these
tests patch it, and the kernel itself still sees the CPU and interprets.
The interpreter fills what a kernel leaves unwritten with NaN, so a row
past the pairs that leaked into a sum would show.
"""

from __future__ import annotations

import dataclasses
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from benchmarks.lib import harness
from bee_code_interpreter_tpu.models import moe
from bee_code_interpreter_tpu.models import transformer as T
from bee_code_interpreter_tpu.models.serving import ContinuousBatcher
from bee_code_interpreter_tpu.ops import grouped_matmul

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture()
def kernel_path(monkeypatch):
    monkeypatch.setattr(grouped_matmul, "on_tpu", lambda: True)


def product(x, stack, groups) -> np.ndarray:
    """Each group's rows times its matrix, in float64; rows past the groups
    zero."""
    x, stack = np.asarray(x, np.float64), np.asarray(stack, np.float64)
    out = np.zeros((x.shape[0], stack.shape[2]))
    start = 0
    for g, size in enumerate(np.asarray(groups)):
        out[start:start + size] = x[start:start + size] @ stack[g]
        start += size
    return out


# (layers of the merged stack, the layer with rows, its groups' sizes, d,
# width, rows of the buffer, dtype)
STACKS = {
    "a_later_layer_with_empty_groups": (3, 2, [5, 0, 9, 3], 128, 256, 32, jnp.float32),
    "a_narrow_expert": (2, 1, [7, 4, 0, 11], 256, 128, 32, jnp.float32),
    "a_wide_expert": (2, 0, [3, 12, 6, 1], 128, 768, 32, jnp.float32),
    "rows_beyond_the_pairs": (2, 1, [2, 0, 3, 1], 128, 256, 48, jnp.float32),
    "bf16_down_projection": (3, 1, [9, 8, 0, 8], 768, 256, 32, jnp.bfloat16),
}


@pytest.mark.parametrize("case", list(STACKS))
def test_the_kernel_is_ragged_dot_and_the_written_out_product(case):
    layers, layer, sizes, d, f, rows, dtype = STACKS[case]
    held = len(sizes)
    keys = jax.random.split(jax.random.PRNGKey(len(case)), 2)
    x = jax.random.normal(keys[0], (rows, d)).astype(dtype)
    stack = (jax.random.normal(keys[1], (layers * held, d, f)) / d**0.5).astype(dtype)
    groups = np.zeros(layers * held, np.int32)
    groups[layer * held:(layer + 1) * held] = sizes
    groups = jnp.asarray(groups)
    got = jax.jit(grouped_matmul.gmm)(x, stack, groups)
    covered = sum(sizes)
    assert got.shape == (rows, f) and got.dtype == dtype
    want = lax.ragged_dot(x, stack, groups)
    exact = dtype == jnp.float32
    np.testing.assert_allclose(
        np.asarray(got[:covered], np.float32), np.asarray(want[:covered], np.float32),
        atol=1e-5 if exact else 2e-2, rtol=0 if exact else 2e-2,
    )
    np.testing.assert_allclose(
        np.asarray(got[:covered], np.float64), product(x, stack, groups)[:covered],
        atol=1e-4 if exact else 5e-2, rtol=0 if exact else 2e-2,
    )
    if covered < rows:  # never written: the interpreter's NaN
        assert not np.isfinite(np.asarray(got[covered:], np.float32)).any()


def test_the_tiling_fits_the_shapes():
    """The largest row tile that divides the buffer, the whole contraction
    width, the widest divisor of the columns in ``BLOCK_BYTES`` whose
    buffers fit VMEM; the buffer rounded up to ``ROW_TILE``."""
    t = grouped_matmul.tiling_for
    assert t(432, 4096, 768) == (48, 4096, 384)  # granite-4.0-h-small
    assert t(432, 768, 4096) == (48, 768, 2048)
    assert t(640, 768, 4096) == (128, 768, 2048)
    assert t(192, 4096, 2048) == (96, 4096, 512)  # sarvam-105b
    assert t(96, 6144, 2048) == (96, 6144, 256)  # k-exaone-236b-a23b
    assert t(384, 6144, 2048) == (128, 6144, 256)
    assert t(32, 64, 16) == (32, 64, 16)
    assert [grouped_matmul.padded_rows(r) for r in (16, 17, 432, 12)] == [
        16, 32, 432, 16,
    ]


# ------------------------------------------------- the sorted expert layer

# 16 experts, top-4, 8 held: softmax (experts 8-15) and sigmoid (0-7, a
# bias and a scaling); float32, so that the paths differ by the order of
# their sums
SOFTMAX = T.TransformerConfig(
    vocab_size=128, d_model=64, n_layers=3, n_heads=4, d_ff=32, max_seq_len=128,
    dtype=jnp.float32, n_experts=16, moe_top_k=4, moe_held_experts=8,
    moe_held_from=8, moe_shared_experts=2,
)
SIGMOID = dataclasses.replace(
    SOFTMAX, moe_scoring="sigmoid", moe_held_from=0, moe_shared_experts=1,
    moe_routed_scaling=2.5, moe_router_bias=True,
)
ROUTERS = {"softmax": SOFTMAX, "sigmoid": SIGMOID}


def dense_reference(layer, y, config) -> np.ndarray:
    """Every held expert on every token in float64, weighed by the router's
    choice of held experts, beside the shared MLP."""
    c = config
    x = np.asarray(y.reshape(-1, c.d_model), np.float64)
    chosen, weights = moe.ROUTERS[c.moe_scoring](jnp.asarray(x, jnp.float32), layer, c)
    w = {n: np.asarray(v, np.float64) for n, v in layer.items() if n != "layer"}
    at = int(layer["layer"])
    silu = lambda v: v / (1 + np.exp(-v))  # noqa: E731
    gate = np.einsum("td,hdf->thf", x, w["we_gate"][at])
    up = np.einsum("td,hdf->thf", x, w["we_up"][at])
    experts = np.einsum("thf,hfd->thd", silu(gate) * up, w["we_down"][at])
    out = np.zeros_like(x)
    for t, (ids, ws) in enumerate(zip(np.asarray(chosen), np.asarray(weights))):
        for e, weight in zip(ids - c.moe_held_from, ws):
            if 0 <= e < c.held_experts:
                out[t] += weight * experts[t, e]
    if c.moe_shared_experts:
        h = silu(x @ w["ws_gate"]) * (x @ w["ws_up"])
        out += h @ w["ws_down"]
    return out


def held_layer(config, toward_held: bool):
    """Layer 1 of a seeded stack, as the layer scan hands it over; with
    ``toward_held`` every token's top-k lands on held experts (the sigmoid
    router's bias), so the pairs outgrow the common buffer."""
    params = T.init_params(config, jax.random.PRNGKey(7))
    layer = moe.take_held_layer(params["layers"]["moe"], 1)
    if toward_held:
        held = np.zeros(config.n_experts, np.float32)
        held[config.moe_held_from:config.moe_held_from + config.held_experts] = 10.0
        layer["router"] = layer["router"].at[-1].set(jnp.asarray(held))
    return layer


# (router, tokens, every pair on a held expert); 3 tokens: 12 pairs, a
# buffer rounded up past them to the row tile
BATCHES = {
    "softmax_three_tokens": ("softmax", 3, False),
    "softmax_decode": ("softmax", 8, False),
    "sigmoid_decode": ("sigmoid", 8, False),
    "sigmoid_every_pair": ("sigmoid", 32, True),
    "softmax_prefill_sized": ("softmax", 96, False),
}


@pytest.mark.parametrize("batch", list(BATCHES))
def test_held_experts_mlp_through_the_kernel(batch, monkeypatch):
    """``held_experts_mlp`` jitted, the kernel's path against ``ragged_dot``'s
    and the float32 reference: finite where the buffer has rows past the
    pairs, the every-pair branch of the ``lax.cond`` where the pairs
    outgrow the common buffer, and ``ragged_dot`` kept where the buffer has
    more rows a held expert than a decode step's."""
    router, tokens, toward_held = BATCHES[batch]
    c = ROUTERS[router]
    layer = held_layer(c, toward_held)
    y = jax.random.normal(jax.random.PRNGKey(tokens), (1, tokens, c.d_model))
    mlp = jax.jit(lambda layer, y: moe.held_experts_mlp(layer, y, c))
    plain = mlp(layer, y)
    monkeypatch.setattr(grouped_matmul, "on_tpu", lambda: True)
    program = str(jax.make_jaxpr(lambda l, y: moe.held_experts_mlp(l, y, c))(layer, y))
    got = jax.jit(lambda layer, y: moe.held_experts_mlp(layer, y, c))(layer, y)
    assert bool(jnp.all(jnp.isfinite(got)))
    np.testing.assert_allclose(got, plain, atol=2e-5)
    np.testing.assert_allclose(
        np.asarray(got, np.float64).reshape(tokens, -1),
        dense_reference(layer, y, c), atol=1e-4,
    )
    rows, every = moe.sorted_rows(tokens, c), tokens * c.moe_top_k
    chosen, _ = moe.ROUTERS[c.moe_scoring](y[0], layer, c)
    _, sizes = moe.sort_pairs(chosen, c)
    if toward_held:  # the pairs outgrow the common buffer: the other branch
        assert rows < int(sizes.sum()) == every
    if moe.expert_matmul(tokens, c) == "gmm":
        assert "pallas_call" in program and "ragged_dot" not in program
    else:
        assert batch == "softmax_prefill_sized"
        assert "ragged_dot" in program and "pallas_call" not in program


def _bench_config(name: str):
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = next(c for c in bench["configs"] if c["name"] == name)
    cfg = json.loads((ROOT / entry["file"]).read_text())
    return harness.transformer_config(T, cfg), cfg["pool"]


@pytest.mark.parametrize(
    "name, prompt", [
        ("granite-4.0-h-small", 512), ("sarvam-105b", 512),
        ("k-exaone-236b-a23b", 1024),
    ],
)
def test_the_benchmarks_decode_steps_take_the_kernel_and_prefills_do_not(
    name, prompt, kernel_path
):
    """On a TPU: the decode step's buffer (the common one and every pair's)
    through the kernel, the prefill of the shortest prompt a cell sends
    through ``ragged_dot``; by shapes alone."""
    config, pool = _bench_config(name)
    batch, held = pool["max_batch"], config.held_experts
    assert moe.expert_matmul(batch, config) == "gmm"
    assert grouped_matmul.takes_kernel(batch * config.moe_top_k, held)
    assert moe.expert_matmul(prompt, config) == "ragged_dot"


def test_off_a_tpu_every_buffer_keeps_ragged_dot():
    config, pool = _bench_config("granite-4.0-h-small")
    assert moe.expert_matmul(pool["max_batch"], config) == "ragged_dot"


POOL = {"max_batch": 4, "page_size": 16, "max_pages_per_seq": 12, "n_pages": 64}


@pytest.mark.parametrize("on_tpu", [True, False], ids=["tpu", "cpu"])
def test_the_batcher_names_each_programs_grouped_matmul(on_tpu, monkeypatch):
    """``kv_telemetry()["expert_matmul"]``: the decode program's (4 rows, 16
    pairs over 8 held experts), a prefill of the longest prompt a row holds
    (192 tokens: 64 rows an expert), and the widest prompt whose prefill
    takes the kernel (80 tokens: 232 rows of 256); None without the sorted
    layer."""
    monkeypatch.setattr(grouped_matmul, "on_tpu", lambda: on_tpu)
    params = T.init_params(SOFTMAX, jax.random.PRNGKey(0))
    described = ContinuousBatcher(params, SOFTMAX, **POOL).kv_telemetry()
    if on_tpu:
        want = {"decode": "gmm", "prefill": "ragged_dot", "prefill_gmm_tokens": 80}
    else:
        want = {"decode": "ragged_dot", "prefill": "ragged_dot", "prefill_gmm_tokens": 0}
    assert described["expert_matmul"] == want
    for config in (T.TransformerConfig.tiny(), T.TransformerConfig.tiny_moe()):
        plain = ContinuousBatcher(
            T.init_params(config, jax.random.PRNGKey(0)), config, **POOL
        ).kv_telemetry()
        assert plain["expert_matmul"] is None
