"""The q / k / v projections read their layer of the weight stack in place.

``transformer._project_heads`` keeps the flat result of a projection whole
before it is split into heads. Left to itself the TPU compiler folds the
split into the dot and then wants the weight head-major: each layer of each
step it cut ``wq`` / ``wk`` / ``wv`` out of the stack into a buffer and
copied the buffer transposed before the dot read it (PERF.md, PR 36).

Two halves. The first reads the COMPILED program of each decoder
configuration of ``BENCHMARK.json`` on a described v5e (shapes alone, no
chip, no time: ``on-chip-measurement`` guide, 2.3) and asserts that no
instruction moves an array of the size of one layer of ``wq``, ``wk`` or
``wv``. The second runs on the CPU and holds the helper to the plain
formula, bit for bit, through every path that shares it.

The same reader holds the admission's ``seed_pool`` program to the same
words about the POOL (PERF.md, PR 38; it stands here because tests that
compile for the described chip stay in as few files as can be): every leaf
goes in and comes out in one buffer, and nothing but the scatter itself
yields an array of a leaf's size. ``tests/test_seed_pool_program.py`` has
what the program writes.

The topology is described inside a module-scoped fixture (the one
``tests/benchmark/test_benchmark_v5e_compile.py`` has, with its shapes):
only one process may load the TPU's library, and every worker imports
every test file. The module is held to two cores, as the benchmark's own
tests are and for their reason (``tests/benchmark/conftest.py``
``few_cores``, autouse here too): a compile for the TPU takes every core it
finds, and tier-1 runs tests that time a sandbox beside it.
"""

from __future__ import annotations

import functools
import re
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bee_code_interpreter_tpu.models import transformer as T
from bee_code_interpreter_tpu.models.transformer import TransformerConfig
from tests.benchmark.conftest import few_cores  # noqa: F401
from tests.benchmark.test_benchmark_v5e_compile import (  # noqa: F401
    CONFIGS,
    _shapes,
    flash_on,
    no_compile_cache,
    topo,
)

# ------------------------------------------------- the compiled program

PROJECTIONS = ("wq", "wk", "wv")
# what hands an array on without touching its bytes; ``copy-done`` ends a
# ``copy-start``, which is looked at where it stands
FREE = {
    "parameter", "get-tuple-element", "tuple", "bitcast", "while",
    "conditional", "call", "opt-barrier", "copy-done",
}
_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.-]+) \(.*\) -> .* \{$")
_INSTRUCTION = re.compile(
    r"^\s*(?:ROOT )?%?([\w.-]+) = (\w+)\[([\d,]*)\]\S* ([\w-]+)\("
)
_CALLS = re.compile(r"calls=%?([\w.-]+)")
# (to, from, context): the two layouts' minor-to-major orders
_PREFETCH = re.compile(
    r"^\s*%?([\w.-]+) = \((\w+)\[([\d,]*)\]\{([\d,]*)[^}]*\}, "
    r"\w+\[[\d,]*\]\{([\d,]*)[^}]*\}, .* copy-start\("
)


def _size(shape) -> tuple[int, ...]:
    """A shape as what it holds, whichever way it is laid out or turned."""
    return tuple(sorted(d for d in shape if d != 1))


def weight_moves(hlo: str, sizes: dict, uses=("convolution", "dot")) -> list[str]:
    """The instructions of a compiled program that MOVE an array of one of
    ``sizes`` ({sorted dims: name}): outside every fusion, a ``copy``, a
    slice, or a fusion that holds no dot (none of ``uses``: what may yield
    an array that large because it is the work itself), whose bf16 result
    is that large. A
    slice fused into the dot that reads it stands inside the dot's fusion
    and is no instruction of its own. A ``copy-start`` that keeps the layout
    is the weight's one read, fetched into fast memory under other work (the
    stack of ONE leading dense layer goes that way whole); one that turns
    the array is a move."""
    computations: dict[str, list[tuple]] = {}
    fused: set[str] = set()
    for line in hlo.splitlines():
        opened = _COMPUTATION.match(line)
        if opened:
            body = computations.setdefault(opened.group(1), [])
            continue
        called = _CALLS.search(line) if " fusion(" in line else None
        if called:  # whatever it gives back: a fusion of two results too
            fused.add(called.group(1))
        fetch = _PREFETCH.match(line)
        if fetch:
            name, dtype, dims, to, source = fetch.groups()
            if to == source:  # the layout kept: a fetch ahead, no move
                continue
            opcode = "copy-start"
        elif m := _INSTRUCTION.match(line):
            name, dtype, dims, opcode = m.groups()
        else:
            continue
        shape = tuple(int(d) for d in dims.split(",") if d)
        body.append((name, dtype, shape, opcode, called and called.group(1)))

    @functools.cache
    def holds_dot(computation: str) -> bool:
        return any(
            opcode in uses or (called and holds_dot(called))
            for *_, opcode, called in computations.get(computation, ())
        )

    moves = []
    for computation, body in computations.items():
        if computation in fused:
            continue
        for name, dtype, shape, opcode, called in body:
            if dtype != "bf16" or opcode in FREE or _size(shape) not in sizes:
                continue
            if opcode in uses or (called and holds_dot(called)):
                continue
            moves.append(
                f"{name} = {dtype}{list(shape)} {opcode} "
                f"(the size of {sizes[_size(shape)]})"
            )
    return moves


def _layer_sizes(params) -> dict:
    """{size of ONE layer of a q / k / v projection on one chip: its
    name}, from the params tree as the program is handed it."""
    sizes: dict = {}
    for path, leaf in jax.tree_util.tree_leaves_with_path(params):
        name = path[-1].key
        if name in PROJECTIONS:
            shard = leaf.sharding.shard_shape(leaf.shape)
            sizes.setdefault(_size(shard[1:]), name)
    return sizes


def test_the_reader_tells_a_moved_weight_from_one_read_in_place():
    """The parent's three instructions and the change's one, as the v5e's
    compiler wrote them (cut to what the reader reads)."""
    sizes = {(4096, 4096): "wq"}
    in_place = """
%fused_computation.23 (param_0.479: bf16[16,4096,4096], param_1.501: s32[]) -> bf16[4096,4096] {
  %dynamic_slice.120 = bf16[1,4096,4096]{2,1,0:T(8,128)(2,1)} dynamic-slice(%param_0.479, %param_1.501)
  ROOT %bitcast.208 = bf16[4096,4096]{1,0:T(8,128)(2,1)} bitcast(%dynamic_slice.120)
}
%fused_computation.80 (param_0.1: bf16[16,4096,4096], param_1.1: s32[], param_2.1: bf16[32,4096]) -> bf16[32,1,4096] {
  %fusion.139 = bf16[4096,4096]{1,0:T(8,128)(2,1)} fusion(%param_0.1, %param_1.1), kind=kLoop, calls=%fused_computation.23
  %convolution.34 = bf16[32,4096]{1,0:T(8,128)(2,1)} convolution(%param_2.1, %fusion.139), dim_labels=bf_io->bf
  ROOT %bitcast.209 = bf16[32,1,4096]{2,0,1:T(8,128)(2,1)S(1)} bitcast(%convolution.34)
}
%closed_call.1 (arg: (bf16[16,4096,4096], s32[], bf16[32,4096])) -> bf16[32,1,4096] {
  %stack = bf16[16,4096,4096]{2,1,0:T(8,128)(2,1)} get-tuple-element(%arg), index=0
  ROOT %fusion.140 = bf16[32,1,4096]{2,0,1:T(8,128)(2,1)S(1)} fusion(%stack), kind=kOutput, calls=%fused_computation.80
}
"""
    assert weight_moves(in_place, sizes) == []
    moved = """
%fused_computation.80.clone (param_0.451: bf16[16,4096,4096], param_1.473: s32[]) -> bf16[1,4096,4096] {
  ROOT %dynamic_slice.105 = bf16[1,4096,4096]{2,1,0:T(8,128)(2,1)S(1)} dynamic-slice(%param_0.451, %param_1.473)
}
%closed_call.1 (arg: (bf16[16,4096,4096], s32[])) -> bf16[1,4096,4096] {
  %stack = bf16[16,4096,4096]{2,1,0:T(8,128)(2,1)} get-tuple-element(%arg), index=0
  %constant_dynamic-slice_fusion.14 = bf16[1,4096,4096]{2,1,0:T(8,128)(2,1)S(1)} fusion(%stack), kind=kLoop, calls=%fused_computation.80.clone
  ROOT %copy.78 = bf16[1,4096,4096]{1,2,0:T(8,128)(2,1)S(1)} copy(%constant_dynamic-slice_fusion.14)
}
"""
    assert [m.split(" = ")[0] for m in weight_moves(moved, sizes)] == [
        "constant_dynamic-slice_fusion.14", "copy.78",
    ]


DECODERS = [
    "mistral-7b-v02", "mistral-7b-v02-tp4", "k-exaone-236b-a23b", "sarvam-105b",
]


@pytest.mark.parametrize("name", DECODERS)
def test_no_decode_step_moves_a_layer_of_a_projection(name, topo, no_compile_cache):
    cfg, tconfig, mesh, params, cache, ints = _shapes(name, topo)
    batch, width = cfg["pool"]["max_batch"], cfg["pool"]["max_pages_per_seq"]
    decode = jax.jit(
        functools.partial(
            T.decode_step_paged, config=tconfig, lora_scale=1.0, mesh=mesh
        ),
        donate_argnums=(3,),
    )
    compiled = decode.lower(
        params, ints(batch, 1), ints(batch), cache, ints(batch, width)
    ).compile()
    sizes = _layer_sizes(params)
    assert sizes, "no q / k / v projection in the params tree"
    assert weight_moves(compiled.as_text(), sizes) == []


def test_no_prefill_moves_a_layer_of_a_projection(topo, no_compile_cache, flash_on):
    cfg, tconfig, mesh, params, cache, ints = _shapes("mistral-7b-v02", topo)
    prefill = jax.jit(
        functools.partial(T.forward, config=tconfig, return_kv=True, mesh=mesh)
    )
    compiled = prefill.lower(params, ints(1, 512)).compile()
    assert weight_moves(compiled.as_text(), _layer_sizes(params)) == []


# ------------------------------------- the pool, written where it lies

def test_the_reader_tells_a_copied_leaf_from_one_written_in_place():
    """The scatter over a leaf that lies pages minor-most (a head of 64) and
    over one that lies page-major, as the v5e's compiler wrote them (cut to
    what the reader reads)."""
    read = functools.partial(weight_moves, uses=("scatter",))
    copied = """
%fused_computation (param_0: bf16[4,2112,8,16,64], param_1.2: s32[13], param_2.4: bf16[13,4,8,16,64]) -> bf16[4,2112,8,16,64] {
  %param_0 = bf16[4,2112,8,16,64]{4,3,2,1,0:T(8,128)(2,1)} parameter(0)
  ROOT %scatter.1 = bf16[4,2112,8,16,64]{4,3,2,1,0:T(8,128)(2,1)} scatter(%param_0, %param_1.2, %param_2.4), update_window_dims={0,2,3,4}
}
ENTRY %main.5 (cache__k__.1: bf16[4,2112,8,16,64], pages.1: s32[13]) -> bf16[4,2112,8,16,64] {
  %cache__k__.1 = bf16[4,2112,8,16,64]{1,4,3,2,0:T(8,128)(2,1)} parameter(1), metadata={op_name="cache['k']"}
  %copy.1 = bf16[4,2112,8,16,64]{4,3,2,1,0:T(8,128)(2,1)} copy(%cache__k__.1), metadata={op_name="cache['k']"}
  %fusion = bf16[4,2112,8,16,64]{4,3,2,1,0:T(8,128)(2,1)} fusion(%copy.1, %compare_select_fusion, %copy.2), kind=kCustom, calls=%fused_computation
  ROOT %copy.6 = bf16[4,2112,8,16,64]{1,4,3,2,0:T(8,128)(2,1)} copy(%fusion)
}
"""
    moves = read(copied, sizes={(4, 8, 16, 64, 2112): "k"})
    assert [m.split(" = ")[0] for m in moves] == ["copy.1", "copy.6"]
    in_place = """
%fused_computation (param_0: bf16[16,2560,8,16,128], param_1.2: s32[16], param_2.4: bf16[16,16,8,16,128]) -> bf16[16,2560,8,16,128] {
  %param_0 = bf16[16,2560,8,16,128]{4,3,2,1,0:T(8,128)(2,1)} parameter(0)
  ROOT %scatter.1 = bf16[16,2560,8,16,128]{4,3,2,1,0:T(8,128)(2,1)} scatter(%param_0, %transpose.6, %transpose.7), update_window_dims={0,2,3,4}
}
ENTRY %main.5 (cache__k__.1: bf16[16,2560,8,16,128], pages.1: s32[16]) -> bf16[16,2560,8,16,128] {
  %cache__k__.1 = bf16[16,2560,8,16,128]{4,3,2,1,0:T(8,128)(2,1)} parameter(0), metadata={op_name="cache['k']"}
  ROOT %fusion = bf16[16,2560,8,16,128]{4,3,2,1,0:T(8,128)(2,1)} fusion(%cache__k__.1, %compare_select_fusion, %bitcast.4), kind=kCustom, calls=%fused_computation
}
"""
    assert read(in_place, sizes={(8, 16, 16, 128, 2560): "k"}) == []


@pytest.mark.parametrize("name", CONFIGS)
def test_seed_pool_writes_every_leaf_of_the_pool_in_place(
    name, topo, no_compile_cache
):
    """The admission's program as the batcher jits it, at the longest prompt
    the configuration's cells send: the pool's bytes are all aliased input
    to output, the program's temporaries are the prompt's K/V and not a
    leaf, and no instruction but the scatter yields a leaf. A head narrower
    than the lane tile is the exception, and is held to what it does: the
    device lays that leaf out pages minor-most, and the scatter has it
    copied page-major and back (``seed_prefill``'s docstring has the
    price, and why a loop of in-place writes is not the cure)."""
    from jax.sharding import PartitionSpec

    from bee_code_interpreter_tpu.models.serving import ContinuousBatcher
    from bee_code_interpreter_tpu.ops.paged_kv_cache import (
        BY_ROW_LEAVES,
        seed_pool,
    )
    from tests.benchmark.test_benchmark_v5e_compile import (
        _bytes_per_chip,
        _longest_prompt,
    )

    cfg, tconfig, mesh, params, cache, ints = _shapes(name, topo)
    page = cfg["pool"]["page_size"]
    width = -(-_longest_prompt(name) // page) * page
    replicated = ints().sharding
    # K and V as the prefill hands them back: over every attention layer,
    # under the mesh with the KV heads where the pool has them
    if tconfig.kv_lora_rank:
        shapes = [(tconfig.n_attention_layers, 1, width, tconfig.latent_width)]
    else:
        shapes = 2 * [(
            tconfig.n_attention_layers, 1, tconfig.kv_heads, width,
            tconfig.head_dim,
        )]
    pool_sharding = mesh and ContinuousBatcher._pool_sharding(
        types.SimpleNamespace(mesh=mesh)
    )
    kv = tuple(
        jax.ShapeDtypeStruct(
            shape, tconfig.dtype, sharding=pool_sharding or replicated
        )
        for shape in shapes
    )
    fn = functools.partial(
        seed_pool,
        paged_layers=tconfig.paged_layers if tconfig.window_layers else None,
    )
    fn.__name__ = "seed_pool"
    program = jax.jit(fn, donate_argnums=(0,), **({} if mesh is None else {
        "in_shardings": (pool_sharding, None, None, None),
        "out_shardings": pool_sharding,
    }))
    compiled = program.lower(cache, ints(width // page), ints(), kv).compile()
    hlo = compiled.as_text()
    assert hlo.startswith("HloModule jit_seed_pool")
    written = {
        leaf: x for leaf, x in cache.items() if leaf not in BY_ROW_LEAVES
    }
    m = compiled.memory_analysis()
    assert m.alias_size_in_bytes >= _bytes_per_chip(cache)  # (a tile pads some)
    sizes = {
        _size(x.sharding.shard_shape(x.shape)): leaf for leaf, x in written.items()
    }
    moves = weight_moves(hlo, sizes, uses=("scatter",))
    if tconfig.kv_lora_rank or tconfig.head_dim % 128 == 0:
        assert moves == []
        smallest = min(_bytes_per_chip(x) for x in written.values())
        assert m.temp_size_in_bytes <= 2 * _bytes_per_chip(kv) < smallest
    else:  # each leaf there and back
        assert len(moves) == 2 * len(written)
        assert all(" copy " in move for move in moves)
    if mesh is not None:  # each chip writes its own KV heads: nothing crosses
        assert pool_sharding.spec == PartitionSpec(None, None, "tp")
        assert not re.search(r"all-(reduce|gather|to-all)|collective-permute", hlo)


# ------------------------------------------- nothing else changed (CPU)

GQA = TransformerConfig(
    vocab_size=128, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2, d_ff=128,
    max_seq_len=64, dtype=jnp.float32,
)
# a declared pattern with window layers that keep a ring by row, a head size
# of its own, per-head norms and rotary in the window layers alone
PATTERN = TransformerConfig(
    vocab_size=128, d_model=48, n_layers=4, n_heads=4, n_kv_heads=2, head_dim=8,
    d_ff=96, max_seq_len=64, dtype=jnp.float32, sliding_window=4,
    layer_types=("sliding_attention", "full_attention") * 2,
    position_embedding="rope_window", qk_norm=True,
)
# a latent K/V cache: one projection into heads (wq) beside the latent's own
LATENT = TransformerConfig(
    vocab_size=128, d_model=64, n_layers=2, n_heads=4, d_ff=128, max_seq_len=64,
    dtype=jnp.float32, kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
    v_head_dim=16, qk_norm=True,
)
SCALE = 2.0
CASES = {
    "gqa": GQA, "pattern": PATTERN, "latent": LATENT, "lora": GQA, "int8": GQA,
}
ROWS = 3


def plain_projection(x, w, heads, config, delta=None):
    """The parent's five lines."""
    out = T.qeinsum("bld,dk->blk", x, w, config.dtype)
    if delta is not None:
        out = out + delta
    B, L = x.shape[:2]
    return out.reshape(B, L, heads, -1).transpose(0, 2, 1, 3)


def _adapter(config, seed):
    from bee_code_interpreter_tpu.models.lora import init_lora

    lora = init_lora(
        config, jax.random.PRNGKey(seed), rank=4, targets=("wq", "wk", "wv", "wo")
    )
    return {  # init_lora zeroes B: give the delta a size that shows
        t: {"A": ab["A"], "B": 0.25 * jax.random.normal(
            jax.random.PRNGKey(seed + 100), ab["B"].shape, jnp.float32
        )}
        for t, ab in lora.items()
    }


@pytest.fixture(scope="module", params=list(CASES))
def case(request):
    """(name, config, params, the LoRA bank or None)."""
    from bee_code_interpreter_tpu.models.lora import stack_lora_bank
    from bee_code_interpreter_tpu.ops.weight_quant import quantize_weights

    name, config = request.param, CASES[request.param]
    params = T.init_params(config, jax.random.PRNGKey(0))
    for scale in ("ln_q", "ln_k"):  # not all ones: a scale left out shows
        if scale in params["layers"]:
            params["layers"][scale] = 1.0 + 0.3 * jax.random.normal(
                jax.random.PRNGKey(3), params["layers"][scale].shape
            )
    if name == "int8":
        params = quantize_weights(params)
    bank = None
    if name == "lora":
        bank = stack_lora_bank([_adapter(config, 1), _adapter(config, 2)])
    return name, config, params, bank


def _equal(got, want):
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want), strict=True):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("jitted", [False, True], ids=["eager", "jit"])
def test_the_helper_is_the_plain_formula_bit_for_bit(case, jitted):
    name, config, params, bank = case
    x = jax.random.normal(jax.random.PRNGKey(5), (ROWS, 6, config.d_model))
    w = T._take_layer(params["layers"]["wq"], 1)
    delta = None
    if bank is not None:
        delta = 0.1 * jax.random.normal(
            jax.random.PRNGKey(6), (ROWS, 6, config.n_heads * config.head_dim)
        )
    ours, plain = (
        functools.partial(f, heads=config.n_heads, config=config)
        for f in (T._project_heads, plain_projection)
    )
    if jitted:
        ours, plain = jax.jit(ours), jax.jit(plain)
    got = ours(x, w, delta=delta)
    assert got.shape[:3] == (ROWS, config.n_heads, 6)
    _equal(got, plain(x, w, delta=delta))


def _three_steps(config, params, bank):
    """A prefill of 5 tokens and three decode steps of ``ROWS`` rows at
    cursors of their own: every program's logits, and the pool."""
    from bee_code_interpreter_tpu.ops.paged_kv_cache import alloc_paged_cache

    tokens = jax.random.randint(jax.random.PRNGKey(7), (ROWS, 5), 0, 128)
    prefilled = jax.jit(
        functools.partial(T.forward, config=config, return_kv=True)
    )(params, tokens)
    pool = alloc_paged_cache(config, n_pages=16, page_size=4, max_batch=ROWS)
    table = jnp.arange(1, 1 + ROWS * 4, dtype=jnp.int32).reshape(ROWS, 4)
    lora = {} if bank is None else {
        "lora_bank": bank, "adapter_idx": jnp.asarray([1, 0, 2], jnp.int32),
        "lora_scale": SCALE,
    }
    step = jax.jit(functools.partial(T.decode_step_paged, config=config, **lora))
    pos = jnp.asarray([0, 2, 5], jnp.int32)
    logits = []
    for i in range(3):
        out, pool = step(params, tokens[:, i:i + 1], pos + i, pool, table)
        logits.append(out)
    return prefilled, logits, pool


def test_prefill_decode_step_and_pool_are_the_parents(case, monkeypatch):
    """``forward`` and three steps of ``decode_step_paged`` (pages, rings, a
    latent pool, a bank of adapters, int8 leaves) with the helper and with
    the parent's formula in its place: the same bits in every logit and
    every leaf of the pool."""
    name, config, params, bank = case
    got = _three_steps(config, params, bank)
    monkeypatch.setattr(T, "_project_heads", plain_projection)
    _equal(got, _three_steps(config, params, bank))


def test_the_gradient_passes_the_barrier_unchanged(case, monkeypatch):
    """``jax.grad`` of ``loss_fn`` (for the bank: of an adapter's loss
    through ``merge_lora``) with the helper equals the gradient with the
    parent's formula, leaf for leaf; an int8 leaf has none either way."""
    from bee_code_interpreter_tpu.models.lora import merge_lora

    name, config, params, bank = case
    tokens = jax.random.randint(jax.random.PRNGKey(8), (2, 9), 0, 128)
    batch = {"tokens": tokens[:, :-1], "targets": tokens[:, 1:]}
    if bank is None:
        at, loss = params, lambda p: T.loss_fn(p, batch, config)
    else:
        at = _adapter(config, 1)
        loss = lambda a: T.loss_fn(  # noqa: E731
            merge_lora(params, a, SCALE), batch, config
        )

    def gradient():
        grads = jax.jit(jax.grad(loss, allow_int=True))(at)
        return [g for g in jax.tree.leaves(grads) if g.dtype != jax.dtypes.float0]

    got = gradient()
    assert got and bool(jnp.any(got[0] != 0))
    monkeypatch.setattr(T, "_project_heads", plain_projection)
    _equal(got, gradient())
