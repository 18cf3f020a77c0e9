#!/usr/bin/env python
"""The held experts' grouped matmul alone, at the decode step's shapes.

For each configuration of ``BENCHMARK.json`` with the sorted expert layer
(``TransformerConfig.sorted_experts``), at the buffer its decode program
runs (``moe.sorted_rows`` of ``max_batch`` tokens, and the buffer of every
pair the ``lax.cond`` falls back to), the gate projection ([rows, d] x
[groups, d, width]) and the down projection ([rows, width] x [groups,
width, d]) are timed three ways:

- ``ragged_merged``: ``lax.ragged_dot`` over the stack of every expert
  layer merged into layers x held groups, all but one layer's empty (what
  the program ran before the kernel);
- ``ragged_layer``: ``lax.ragged_dot`` over one layer's experts handed over
  as an array of their own (a probe of what the empty groups cost: in the
  program such a slice is a copy);
- ``gmm_<tm>_<tk>_<tn>``: ``ops.grouped_matmul.gmm`` (megablox's kernel)
  over the merged stack at a tiling, and ``gmm_auto`` at the tiling
  ``ops.grouped_matmul.tiling_for`` picks.

The group sizes are one draw of even routing (seeded), in the middle expert
layer. Timing: the chained clock (``utils/benchclock.chain_diff``), the
matmul applied N times inside one jit, each application's input touched by
the last one's output. Bytes: the touched experts' matrices at the HBM peak.

Usage:  python scripts/bench-grouped-matmul.py [configuration ...]
        (needs a TPU; exits 2 if none; every such configuration by default)
Prints one JSON line per configuration, buffer and projection, each naming
the device. Not a measurement of any cell.
"""

from __future__ import annotations

import json
import math
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

import os  # noqa: E402

from bee_code_interpreter_tpu.utils.jaxcache import (  # noqa: E402
    ENV_VAR as _CACHE_ENV,
    jax_cache_dir,
)

os.environ[_CACHE_ENV] = jax_cache_dir()

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax import lax  # noqa: E402

N = 24  # applications in the long chain
HBM_BYTES_PER_S = 819e9  # v5e (benchmarks/lib/peaks.py)
# row tiles tried beside the automatic tiling; tk is always the whole width
ROW_TILES = (16, 32, 48, 64, 96, 128)
VMEM_BYTES = 16 << 20  # what a kernel may hold by default


def sorted_configs():
    """(name, TransformerConfig, max_batch) of every configuration of the
    benchmark with the sorted expert layer."""
    from benchmarks.lib import harness
    from bee_code_interpreter_tpu.models import transformer as T

    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    for entry in bench["configs"]:
        cfg = json.loads((REPO / entry["file"]).read_text())
        config = harness.transformer_config(T, cfg)
        if config.sorted_experts:
            yield entry["name"], config, cfg["pool"]["max_batch"]


def group_sizes(config, tokens: int, seed: int) -> np.ndarray:
    """Pairs of each held expert when ``tokens`` tokens each choose
    ``moe_top_k`` distinct experts of the router's width at random."""
    c = config
    rng = np.random.default_rng(seed)
    chosen = np.stack([
        rng.choice(c.n_experts, c.moe_top_k, replace=False) for _ in range(tokens)
    ]).ravel() - c.moe_held_from
    chosen = chosen[(chosen >= 0) & (chosen < c.held_experts)]
    return np.bincount(chosen, minlength=c.held_experts).astype(np.int32)


def chained(matmul, n):
    """``matmul(x, w, groups)`` applied ``n`` times in one program, each
    input touched by an element of the last output (at a row that moves, so
    that no slice of the result is all that needs computing)."""

    def f(x, w, groups):
        def body(i, x):
            out = matmul(x, w, groups)
            row = lax.dynamic_slice(out, (i % 8, 0), (1, 1))
            return lax.dynamic_update_slice(x, row.astype(x.dtype), (0, 0))

        return lax.fori_loop(0, n, body, x)[0, 0].astype(jnp.float32)

    return jax.jit(f)


def best_of(f, *args, reps=3) -> float:
    best = float("inf")
    for i in range(reps + 1):  # the first compiles and warms
        t0 = time.perf_counter()
        float(f(*args))
        if i:
            best = min(best, time.perf_counter() - t0)
    return best


def per_call(matmul, *args) -> float:
    from bee_code_interpreter_tpu.utils.benchclock import chain_diff

    return chain_diff(
        best_of(chained(matmul, N), *args), best_of(chained(matmul, 1), *args), N
    )


def tilings(k: int, n: int) -> list[tuple[int, int, int]]:
    """(tm, k, tn): every row tile, by every column block that divides
    ``n``, holds at most 6 MiB of weights and fits VMEM with its buffers."""
    out = []
    for tm in ROW_TILES:
        for tn in (256, 384, 512, 768, 1024, 1536, 2048, 4096):
            vmem = 2 * (k * tn + tm * k + tm * tn) * 2 + 4 * tm * tn
            if n % tn == 0 and k * tn * 2 <= 6 << 20 and vmem <= VMEM_BYTES:
                out.append((tm, k, tn))
    return out


def measure(emit, name, config, max_batch) -> None:
    from bee_code_interpreter_tpu.models import moe
    from bee_code_interpreter_tpu.ops import grouped_matmul

    c = config
    H, layers = c.held_experts, c.n_layers - c.n_dense_layers
    layer = layers // 2
    d, f = c.d_model, c.expert_ff_dim
    common = moe.sorted_rows(max_batch, c)
    buffers = {"common": common, "every_pair": max_batch * c.moe_top_k}
    if buffers["every_pair"] == common:
        del buffers["every_pair"]
    sizes = group_sizes(c, max_batch, seed=40)
    groups = np.zeros(layers * H, np.int32)
    groups[layer * H:(layer + 1) * H] = sizes
    touched = int(np.count_nonzero(sizes))
    key = jax.random.PRNGKey(0)
    for proj, (k, n) in (("gate", (d, f)), ("down", (f, d))):
        w = (jax.random.normal(key, (layers * H, k, n), jnp.bfloat16) / math.sqrt(k))
        w_layer = jnp.array(w[layer * H:(layer + 1) * H])
        least_s = touched * k * n * 2 / HBM_BYTES_PER_S
        for buffer, rows in buffers.items():
            g = jnp.asarray(groups)
            covered = int(sizes.sum())
            times = {}
            errors = {}

            def lhs(m):
                return jax.random.normal(jax.random.PRNGKey(1), (m, k), jnp.bfloat16)

            x = lhs(rows)
            times["ragged_merged"] = per_call(lax.ragged_dot, x, w, g)
            times["ragged_layer"] = per_call(
                lax.ragged_dot, x, w_layer, jnp.asarray(sizes)
            )
            auto = grouped_matmul.tiling_for(rows, k, n)
            tried = [auto] + [
                t for t in tilings(k, n)
                if t != auto and (buffer == "common" or t[2] == auto[2])
            ]
            for tiling in tried:
                label = "gmm_auto" if tiling == auto else "gmm_%d_%d_%d" % tiling
                # the buffer rounded up to the row tile, as the program would
                x = lhs(-(-rows // tiling[0]) * tiling[0])

                def call(x, w, g, tiling=tiling):
                    return grouped_matmul.gmm(x, w, g, tiling=tiling)

                try:
                    times[label] = per_call(call, x, w, g)
                    got = call(x, w, g)[:covered].astype(jnp.float32)
                    want = lax.ragged_dot(x, w, g)[:covered].astype(jnp.float32)
                    errors[label + "_max_abs"] = float(jnp.max(jnp.abs(got - want)))
                except Exception as e:  # a tiling the compiler refuses
                    times[label] = None
                    errors[label] = f"{type(e).__name__}: {str(e)[:200]}"
            emit("grouped_matmul", {
                "config": name, "buffer": buffer, "projection": proj,
                "shape": f"[{rows}, {k}] x [{layers * H}, {k}, {n}]",
                "groups_with_pairs": touched, "pairs": covered,
                "auto_tiling": list(auto),
                "least_us": round(least_s * 1e6, 1),
                "us": {
                    label: None if t is None else round(t * 1e6, 1)
                    for label, t in times.items()
                },
                "hbm_share": {
                    label: None if t is None else round(least_s / t, 3)
                    for label, t in times.items()
                },
                "checks": errors,
            })
        del w, w_layer


def main() -> None:
    from bee_code_interpreter_tpu.parallel.mesh import require_tpu

    emit = require_tpu("scripts/bench-grouped-matmul.py")
    configs = {name: rest for name, *rest in sorted_configs()}
    for name in sys.argv[1:] or list(configs):
        measure(emit, name, *configs[name])


if __name__ == "__main__":
    main()
