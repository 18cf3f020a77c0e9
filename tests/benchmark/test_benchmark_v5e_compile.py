"""A rehearsal of the benchmark's programs on a described v5e.

The TPU's compiler is installed where there is no TPU: it compiles for a
chip that is described, not attached (``on-chip-measurement`` guide, 2.3).
For each configuration of ``BENCHMARK.json`` (one chip, or tensor-parallel
over the described 2x2) this compiles the two programs that decide whether
its cells fit the chip — the decode
step at the configuration's batch and block-table width, and the prefill of
the longest prompt any mix sends — as the batcher jits them, and reads
``memory_analysis()``: weights, pool and the program's temporaries must fit
the 15.75 GiB a v5e chip gives a process, and so must the admission's
transient (``seed_prefill`` runs eagerly and holds the old and the new pool
leaves at once; PERF.md section 5). What the compiler refuses here costs no
chip time. A compile that passes is not a chip run and gives no time.

The topology is described inside a fixture and all of these live in this
one file: only one process may load the TPU's library, and every test
worker imports every test file.
"""

from __future__ import annotations

import functools
import json
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CONFIGS = [c["name"] for c in BENCH["configs"]]
# bytes_limit of one v5e chip as memory_stats() reports it (PERF.md, PR 21)
HBM_LIMIT = int(15.75 * 2**30)


@pytest.fixture(scope="module")
def topo():
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler here: nothing to rehearse
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: keep these out of it."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


@pytest.fixture()
def flash_on(monkeypatch):
    """The program asks ``jax.devices()`` whether to run the Pallas kernel
    and sees the CPU: steer it to what it does on the chip (Mosaic, not the
    interpreter), here in the test."""
    import importlib

    # (the package re-exports the function under the module's name)
    flash_attention = importlib.import_module(
        "bee_code_interpreter_tpu.ops.flash_attention"
    )
    monkeypatch.setattr(flash_attention, "uses_flash", lambda: True)
    monkeypatch.setattr(
        flash_attention, "_resolve",
        lambda q, sm_scale, interpret: (
            q.shape[-1] ** -0.5 if sm_scale is None else sm_scale, False
        ),
    )


def _longest_prompt(name: str) -> int:
    """The longest prompt any cell of this configuration is sent."""
    mixes = {w["traffic"] for w in BENCH["workloads"] if w["config"] == name}
    return max(
        max(json.loads(
            (ROOT / "benchmarks" / "traffic" / f"{m}.json").read_text()
        )["prompt_tokens"]["values"])
        for m in mixes
    )


def _shapes(name: str, topo):
    """The configuration's params and pool as shapes on the described
    chips: on the first of them, or under the configuration's mesh with the
    program's own shardings."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec, SingleDeviceSharding

    from benchmarks.lib import harness
    from bee_code_interpreter_tpu.models import transformer as T
    from bee_code_interpreter_tpu.parallel import make_mesh

    entry = next(c for c in BENCH["configs"] if c["name"] == name)
    cfg = json.loads((ROOT / entry["file"]).read_text())
    tconfig = harness.transformer_config(T, cfg)
    shapes = jax.eval_shape(
        lambda k: T.init_params(tconfig, k), jax.random.PRNGKey(0)
    )
    if cfg["mesh"]:
        mesh = make_mesh(dict(cfg["mesh"]), devices=topo.devices)
        named = lambda *spec: NamedSharding(mesh, PartitionSpec(*spec))  # noqa: E731
        param_shardings = jax.tree.map(
            lambda spec: NamedSharding(mesh, spec), T.param_specs(tconfig, mesh),
            is_leaf=lambda x: isinstance(x, PartitionSpec),
        )
        pool_sharding, replicated = named(None, None, "tp", None, None), named()
    else:
        mesh = None
        replicated = pool_sharding = SingleDeviceSharding(topo.devices[0])
        param_shardings = jax.tree.map(lambda _: replicated, shapes)
    params = jax.tree.map(
        lambda x, sharding: jax.ShapeDtypeStruct(
            x.shape, tconfig.dtype, sharding=sharding
        ),
        shapes, param_shardings,
    )
    pool = cfg["pool"]
    page = (
        tconfig.n_layers, pool["n_pages"], tconfig.kv_heads,
        pool["page_size"], tconfig.head_dim,
    )
    cache = {
        leaf: jax.ShapeDtypeStruct(page, tconfig.dtype, sharding=pool_sharding)
        for leaf in ("k", "v")
    }
    ints = lambda *shape: jax.ShapeDtypeStruct(  # noqa: E731
        shape, jnp.int32, sharding=replicated
    )
    return cfg, tconfig, mesh, params, cache, ints


def _footprint(compiled) -> int:
    m = compiled.memory_analysis()
    return (
        m.argument_size_in_bytes + m.output_size_in_bytes
        + m.temp_size_in_bytes - m.alias_size_in_bytes
    )


def _bytes_per_chip(tree) -> int:
    import jax
    import numpy as np

    return sum(
        int(np.prod(x.sharding.shard_shape(x.shape))) * x.dtype.itemsize
        for x in jax.tree.leaves(tree)
    )


@pytest.mark.parametrize("name", CONFIGS)
def test_decode_step_fits_the_chip(name, topo, no_compile_cache):
    import jax

    from bee_code_interpreter_tpu.models import transformer as T

    cfg, tconfig, mesh, params, cache, ints = _shapes(name, topo)
    pool = cfg["pool"]
    batch, width = pool["max_batch"], pool["max_pages_per_seq"]
    decode = jax.jit(
        functools.partial(T.decode_step_paged, config=tconfig, lora_scale=1.0),
        donate_argnums=(3,),
    )
    compiled = decode.lower(
        params, ints(batch, 1), ints(batch), cache, ints(batch, width)
    ).compile()
    footprint = _footprint(compiled)  # of one chip
    assert footprint <= HBM_LIMIT, (
        f"{name}: the decode step needs {footprint / 2**30:.2f} GiB of "
        f"{HBM_LIMIT / 2**30:.2f}"
    )
    if mesh is not None:  # GSPMD put the tp collectives in
        assert "all-reduce" in compiled.as_text()


@pytest.mark.parametrize("name", CONFIGS)
def test_longest_prefill_and_its_admission_fit_the_chip(
    name, topo, no_compile_cache, flash_on
):
    import jax

    from bee_code_interpreter_tpu.models import transformer as T

    cfg, tconfig, mesh, params, cache, ints = _shapes(name, topo)
    longest = _longest_prompt(name)
    prefill = jax.jit(
        functools.partial(T.forward, config=tconfig, return_kv=True, mesh=mesh)
    )
    compiled = prefill.lower(params, ints(1, longest)).compile()
    assert "tpu_custom_call" in compiled.as_text(), (
        "the prefill compiled without the Pallas flash kernel"
    )
    pool_bytes = _bytes_per_chip(cache)
    # the pool is resident beside the prefill program, not one of its inputs
    during_prefill = _footprint(compiled) + pool_bytes
    assert during_prefill <= HBM_LIMIT, (
        f"{name}: a {longest}-token prefill needs "
        f"{during_prefill / 2**30:.2f} GiB beside the pool"
    )
    # seed_prefill: params, the old pool and the new one, and the
    # prefill's outputs (logits, K/V) still alive
    m = compiled.memory_analysis()
    during_seed = (
        _bytes_per_chip(params) + 2 * pool_bytes + m.output_size_in_bytes
    )
    assert during_seed <= HBM_LIMIT, (
        f"{name}: seeding the pool after a {longest}-token prefill "
        f"needs {during_seed / 2**30:.2f} GiB"
    )


@pytest.mark.parametrize("name", CONFIGS)
def test_seeded_fill_fits_beside_nothing_else(name, topo, no_compile_cache):
    """The weights are made on the device in one program: its outputs and
    temporaries are all there is at that point of a run."""
    import jax
    import jax.numpy as jnp

    from benchmarks.lib.params import fill_program
    from bee_code_interpreter_tpu.models import transformer as T

    cfg, tconfig, mesh, params, cache, ints = _shapes(name, topo)
    shardings = jax.tree.map(lambda x: x.sharding, params)
    seed = jax.ShapeDtypeStruct((), jnp.uint32, sharding=ints().sharding)
    compiled = fill_program(T.init_params, tconfig, shardings).lower(seed).compile()
    m = compiled.memory_analysis()
    assert m.output_size_in_bytes == pytest.approx(_bytes_per_chip(params), rel=0.01)
    # a layer at a time: never a second copy of the stack
    assert m.temp_size_in_bytes <= 0.15 * m.output_size_in_bytes
    assert _footprint(compiled) <= HBM_LIMIT
