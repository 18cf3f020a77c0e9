#!/usr/bin/env python3
"""The quickest proof that the system still starts on the chip.

    python3 chip_smoke.py             one chip: phases service, serving, kernels
    python3 chip_smoke.py --chips 4   four chips: phases tp4, replicas

Drives the system's main paths once, through the entry points a user would
call, at the full width of Llama-3-8B (depth cut to fit one 16 GB chip and
stated as ``reduced``; seeded random bf16 weights), and checks what comes out
by the repo's own means. It claims no rate: every time it prints is labelled
"smoke, not a measurement".

- **service** — boots ``python -m bee_code_interpreter_tpu`` the README way
  (local backend, native executor pool built here from the committed
  sources) and sends, one after another: the bf16 jit matmul chain bench.py
  carries; ``examples/benchmark-numpy.py`` through the numpy->XLA reroute
  (result must be a ``TpuArray`` on the expected platform, the reroute's
  backend probe must have succeeded); ``examples/continuous-batching.py``
  (the bundled library: flash prefill and paged decode inside a sandbox);
  one gRPC ``Execute`` plus ``health_check``. Then: the service process
  never mapped libtpu or jaxlib, ``GET /v1/accelerator`` answers without
  touching a device, no ``executor-server`` survives shutdown — and one
  executor-server in the executor IMAGE's warm configuration
  (``APP_WARMUP=1`` + ``bci_tpu_warm`` preload) serves a three-execute lease
  with one accelerator process at a time.
- **serving** — ``Engine(ContinuousBatcher(...))`` over the paged pool with a
  ``DeviceMonitor`` attached: 12 seeded requests (prompts 48/200/512/1000,
  half greedy, half sampled), all must finish with finite logprobs; one
  greedy request re-run alone yields the same tokens (row independence);
  four decode steps are traced and the ``.xplane.pb`` must load with a device
  plane.
- **kernels** — the Pallas flash (forward and backward) and paged-decode
  kernels lowered by Mosaic (``interpret=False`` passed explicitly) against
  their references; the paged-decode kernel at the benchmark's table width,
  with all KV heads and with a tp=4 shard's.

This parent never imports jax: a chip belongs to one process at a time, so
each phase runs in a child of its own, strictly one after another. Children
get ``JAX_PLATFORMS=tpu`` unless the environment already names a platform —
a missing chip is an error, never a CPU run — and every phase asserts the
platform it ran on. Any phase failing makes the run exit non-zero, naming
the phase, and print no result. On success the last two lines of stdout are
``SUMMARY {..., "claim": null}`` (per-phase seconds, compile counts, peak HBM,
compile-cache entries) and then the result, one JSON object with exactly
these keys: ``{"ok": true, "device": {"platform": "tpu", "kind": "...",
"count": 1}}`` — the device as jax reported it to every phase.

The compile cache is where ``JAX_COMPILATION_CACHE_DIR`` says, else the
checkout's fixed ``.jax_cache`` (bee_code_interpreter_tpu/utils/jaxcache.py).
The phases are importable functions ``phase(config, platform, **sizes)``:
the command line always passes ``tpu``; tests/test_chip_smoke.py calls them
with ``TransformerConfig.tiny()`` and ``cpu``.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import shutil
import signal
import socket
import subprocess
import sys
import tempfile
import time
import urllib.error
import urllib.request
from pathlib import Path

REPO = Path(__file__).resolve().parent
SHIM_DIR = REPO / "bee_code_interpreter_tpu" / "runtime" / "shim"
OUT_DIR = REPO / "chiprun_out" / "chip_smoke"

SEED = 20260926
# the whole default invocation must end inside the chip check's 1200 s
GLOBAL_BUDGET_S = 1150.0
PHASE_TIMEOUT_S = {
    "service": 700.0, "serving": 600.0, "kernels": 400.0,
    "tp4": 900.0, "replicas": 900.0,
}
ONE_CHIP_PHASES = ("service", "serving", "kernels")
FOUR_CHIP_PHASES = ("tp4", "replicas")
SMOKE_NOTE = "smoke, not a measurement"


class SmokeFailure(AssertionError):
    """A check of the smoke did not hold."""


def check(condition, message: str) -> None:
    """``assert`` that survives ``python -O`` and names what failed."""
    if not condition:
        raise SmokeFailure(message)


# ------------------------------------------------------------------ devices


def backend_up(platform: str) -> tuple[dict, float]:
    """Import jax and bring its backend up: (the device as jax reports it,
    the seconds that took). Fails unless the backend is ``platform``."""
    t0 = time.monotonic()
    import jax

    devices = jax.devices()
    identity = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    check(
        identity["platform"] == platform,
        f"expected a {platform} backend, jax found {identity}",
    )
    return identity, time.monotonic() - t0


def seeded_params(config, seed: int, shardings=None):
    """``init_params``' tree, filled leaf by leaf in the compute dtype from a
    seed: the same 1/sqrt(fan_in) normal init for matrices and ones for the
    norm scales — without ever holding the f32 master stack ``init_params``
    builds under ``vmap`` (18 GB at Llama-3-8B widths x 16 layers, on a
    16 GB chip). ``shardings`` (a matching tree of NamedShardings) places
    each leaf as it is made."""
    import math

    import jax
    import jax.numpy as jnp

    from bee_code_interpreter_tpu.models import transformer as T

    shapes = jax.eval_shape(
        lambda key: T.init_params(config, key), jax.random.PRNGKey(0)
    )
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    placements = (
        treedef.flatten_up_to(shardings) if shardings is not None
        else [None] * len(leaves)
    )
    # the hardware bit generator: threefry's integer rounds took ~110 s for
    # these 4.5e9 elements on a v5e (my chip run, PR 21); "rbg" takes seconds
    key = jax.random.key(seed, impl="rbg")
    out = []
    for i, ((path, leaf), placement) in enumerate(zip(leaves, placements)):
        name = path[-1].key
        if name.startswith("ln"):
            make = lambda _key, shape=leaf.shape: jnp.ones(  # noqa: E731
                shape, config.dtype
            )
        else:
            fan_in = leaf.shape[-1] if name == "embed" else leaf.shape[-2]
            make = lambda key, shape=leaf.shape, fan_in=fan_in: (  # noqa: E731
                jax.random.normal(key, shape, jnp.float32)
                / math.sqrt(fan_in)
            ).astype(config.dtype)
        out.append(
            jax.jit(make, out_shardings=placement)(jax.random.fold_in(key, i))
        )
    return jax.tree_util.tree_unflatten(treedef, out)


def local_memory_rows() -> list[dict]:
    """``parallel.mesh.device_memory_rows`` for this process's devices
    (``memory_stats`` on a TPU; estimated rows on the CPU backend)."""
    import jax

    from bee_code_interpreter_tpu.parallel.mesh import device_memory_rows

    return device_memory_rows(jax.local_devices())


def requests_for(config, prompt_lens, n_requests: int, new_tokens: int):
    """``n_requests`` seeded (prompt, sampling) pairs cycling through
    ``prompt_lens``: even ones greedy (every other of those recording
    logprobs, so both the device-argmax and the host-logits paths run), odd
    ones sampled at temperature 0.8 / top-p 0.95 with logprobs."""
    import numpy as np

    from bee_code_interpreter_tpu.models.serving import SamplingParams

    out = []
    for i in range(n_requests):
        length = prompt_lens[i % len(prompt_lens)]
        prompt = np.random.default_rng(SEED + i).integers(
            0, config.vocab_size, length, dtype=np.int32
        )
        if i % 2:
            sampling = SamplingParams(
                temperature=0.8, top_p=0.95, seed=SEED + i, logprobs=True
            )
        else:
            sampling = SamplingParams(logprobs=i % 4 == 0)
        out.append((prompt, sampling))
    return out


def check_finished(engine, tickets, requests, config, new_tokens: int) -> None:
    import math

    for ticket, (_prompt, sampling) in zip(tickets, requests):
        check(engine.is_done(ticket), f"ticket {ticket} did not finish")
        reason = engine.finish_reason(ticket)
        check(
            reason == "length",
            f"ticket {ticket} finished {reason!r} "
            f"({engine.ticket_error(ticket)}), expected 'length'",
        )
        tokens = engine.result(ticket)
        check(
            len(tokens) == new_tokens
            and all(0 <= t < config.vocab_size for t in tokens),
            f"ticket {ticket}: {len(tokens)} tokens, ids out of range?",
        )
        if sampling.logprobs:
            logprobs = engine.result_logprobs(ticket)
            check(
                len(logprobs) == new_tokens
                and all(math.isfinite(x) and x <= 1e-6 for x in logprobs),
                f"ticket {ticket}: non-finite logprobs (logits not finite)",
            )


# ------------------------------------------------------------ phase: serving


def phase_serving(
    config,
    platform: str,
    *,
    n_layers: int = 16,
    prompt_lens: tuple[int, ...] = (48, 200, 512, 1000),
    n_requests: int = 12,
    new_tokens: int = 64,
    max_batch: int = 8,
    page_size: int = 16,
    max_pages_per_seq: int = 128,
    n_pages: int = 2048,
    trace_steps: int = 4,
    trace_dir: str | Path | None = None,
) -> dict:
    """The serving stack at the config's full width, in this one process."""
    import dataclasses
    import statistics

    t0 = time.monotonic()
    device, init_s = backend_up(platform)
    import jax

    from bee_code_interpreter_tpu.models.engine import Engine
    from bee_code_interpreter_tpu.models.serving import ContinuousBatcher
    from bee_code_interpreter_tpu.observability import (
        DeviceMonitor,
        ServingMonitor,
    )

    cfg = dataclasses.replace(config, n_layers=n_layers)
    t_params = time.monotonic()
    params = seeded_params(cfg, SEED)
    jax.block_until_ready(params)
    params_s = time.monotonic() - t_params
    n_params = sum(x.size for x in jax.tree.leaves(params))

    batcher = ContinuousBatcher(
        params, cfg, max_batch=max_batch, n_pages=n_pages,
        page_size=page_size, max_pages_per_seq=max_pages_per_seq,
    )
    engine = Engine(batcher)
    serving = ServingMonitor(max_steps=8192)
    monitor = DeviceMonitor()
    serving.attach(engine)
    monitor.attach(engine)

    requests = requests_for(cfg, prompt_lens, n_requests, new_tokens)
    t_run = time.monotonic()
    tickets = [
        engine.submit(prompt, new_tokens, sampling=sampling)
        for prompt, sampling in requests
    ]
    engine.run_to_completion()
    batch_s = time.monotonic() - t_run
    check_finished(engine, tickets, requests, cfg, new_tokens)
    compiles = monitor.snapshot(recent=256)["compile"]

    # Row independence, on this device: one greedy request re-run ALONE
    # through the same batcher yields the same tokens — and compiles nothing.
    solo_of = next(
        i for i, (_p, s) in enumerate(requests)
        if s.temperature == 0.0 and not s.logprobs
    )
    prompt, sampling = requests[solo_of]
    solo = engine.submit(prompt, new_tokens, sampling=sampling)
    engine.run_to_completion()
    check(
        engine.result(solo) == engine.result(tickets[solo_of]),
        f"request {solo_of} decoded alone differs from its in-batch tokens: "
        f"{engine.result(solo)[:8]}... vs "
        f"{engine.result(tickets[solo_of])[:8]}...",
    )

    # A device trace from the process that holds the chip: decode steps under
    # the batcher's own profiler trace must leave an .xplane.pb that loads
    # with a device plane — the benchmark's breakdowns depend on that.
    trace_dir = Path(trace_dir) if trace_dir else OUT_DIR / "trace"
    shutil.rmtree(trace_dir, ignore_errors=True)
    trace_dir.mkdir(parents=True)
    traced = [
        engine.submit(p, trace_steps + 4, sampling=s)
        for p, s in requests[:2]
    ]
    engine.step()  # admission + the first decode step, outside the trace
    with batcher.profiler_trace(str(trace_dir)):
        for _ in range(trace_steps):
            engine.step()
    engine.run_to_completion()
    check(
        all(engine.is_done(t) for t in traced), "traced requests unfinished"
    )
    xplanes = sorted(trace_dir.rglob("*.xplane.pb"))
    check(bool(xplanes), f"profiler wrote no .xplane.pb under {trace_dir}")
    planes = [
        plane.name
        for plane in jax.profiler.ProfileData.from_file(str(xplanes[0])).planes
    ]
    wanted = "/device:TPU" if platform == "tpu" else "/host:CPU"
    check(
        any(name.startswith(wanted) for name in planes),
        f"trace has no {wanted} plane: {planes}",
    )

    after = monitor.snapshot(recent=256)["compile"]
    check(
        after["total"] == compiles["total"],
        "the solo re-run / traced requests compiled new programs: "
        f"{after['by_trigger']} vs {compiles['by_trigger']}",
    )
    memory = monitor.sample_memory()
    check(
        memory and all(
            row["platform"] == platform
            and row["estimated"] == (platform != "tpu")
            for row in memory
        ),
        f"DeviceMonitor rows are not {platform}/measured: {memory}",
    )
    steps = serving.snapshot(steps=8192)["steps"]["last"]
    decode_ms = [
        s["duration_ms"] for s in steps
        if s["decode_tokens"] and not s["prefill_tokens"]
    ]
    compile_s = sum(c["duration_ms"] for c in after["recent"]) / 1000.0
    total_s = time.monotonic() - t0
    return {
        "device": device,
        "model": {
            "family": "llama3_8b widths" if cfg.d_model == 4096 else "test",
            "d_model": cfg.d_model, "n_heads": cfg.n_heads,
            "kv_heads": cfg.kv_heads, "ff_dim": cfg.ff_dim,
            "vocab_size": cfg.vocab_size, "n_layers": cfg.n_layers,
            "params": int(n_params), "dtype": str(cfg.dtype.__name__),
            "reduced": (
                {"n_layers": {"published": config.n_layers, "run": n_layers}}
                if n_layers != config.n_layers else None
            ),
        },
        "pool": {
            "max_batch": max_batch, "page_size": page_size,
            "max_pages_per_seq": max_pages_per_seq, "n_pages": n_pages,
        },
        "requests": {
            "n": n_requests, "prompt_lens": list(prompt_lens),
            "new_tokens": new_tokens, "solo_rerun_equal": True,
        },
        "compiles": {
            "total": after["total"], "by_trigger": after["by_trigger"],
            "functions": {
                name: fn["compiles"]
                for name, fn in after["functions"].items()
            },
        },
        "peak_hbm_bytes": max(
            (r["peak_bytes"] for r in memory if not r["estimated"]),
            default=None,
        ),
        "decode_step_ms_median": (
            round(statistics.median(decode_ms), 2) if decode_ms else None
        ),
        "decode_steps": len(decode_ms),
        "timing_note": SMOKE_NOTE,
        "trace": {
            "xplane": str(xplanes[0].relative_to(trace_dir)),
            "bytes": xplanes[0].stat().st_size,
            "planes": planes,
        },
        "seconds": {
            "init": round(init_s, 1),
            "params": round(params_s, 1),
            "compile": round(compile_s, 1),
            "run": round(total_s - init_s - params_s - compile_s, 1),
            "batch_wall": round(batch_s, 1),
            "total": round(total_s, 1),
        },
    }


# ------------------------------------------------------------ phase: kernels


def _timed_compile(fn, *args):
    """(compiled executable, lower+compile seconds)."""
    import jax

    t0 = time.monotonic()
    compiled = jax.jit(fn).lower(*args).compile()
    return compiled, time.monotonic() - t0


def phase_kernels(
    config,
    platform: str,
    *,
    batch: int = 2,
    seq_len: int = 2048,
    page_size: int = 16,
    pages_per_seq: int = 288,
    tol: float = 2e-2,
    grad_tol: float = 5e-2,
) -> dict:
    """The Pallas kernels lowered for ``platform`` — Mosaic on a TPU
    (``interpret=False`` passed explicitly, never inferred), the Pallas
    interpreter elsewhere — against their references, bf16, at the config's
    head geometry. Tolerances are bf16's: outputs are O(1), one bf16 ulp
    there is 2^-8 ~ 4e-3, and P is rounded to bf16 before the PV matmul."""
    import math
    from unittest import mock

    t0 = time.monotonic()
    device, init_s = backend_up(platform)
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bee_code_interpreter_tpu.ops import paged_attention
    from bee_code_interpreter_tpu.ops.flash_attention import flash_attention
    from bee_code_interpreter_tpu.ops.paged_attention import (
        paged_decode_attention,
    )
    from bee_code_interpreter_tpu.ops.paged_kv_cache import (
        paged_append,
        paged_read,
    )
    from bee_code_interpreter_tpu.parallel.ring_attention import (
        reference_attention,
    )

    interpret = platform != "tpu"
    nh, kvh, dh = config.n_heads, config.kv_heads, config.head_dim
    dtype = jnp.bfloat16
    compile_s = run_s = 0.0

    def err(a, b) -> float:
        a = np.asarray(a, dtype=np.float32)
        b = np.asarray(b, dtype=np.float32)
        return float(np.max(np.abs(a - b)) / max(1.0, np.max(np.abs(b))))

    # ---- flash attention, forward and backward, causal, GQA-native
    keys = jax.random.split(jax.random.PRNGKey(SEED), 8)
    q = jax.random.normal(keys[0], (batch, nh, seq_len, dh), dtype)
    k = jax.random.normal(keys[1], (batch, kvh, seq_len, dh), dtype)
    v = jax.random.normal(keys[2], (batch, kvh, seq_len, dh), dtype)
    w = jax.random.normal(keys[3], (batch, nh, seq_len, dh), dtype)

    def flash(q, k, v):
        return flash_attention(q, k, v, True, None, 1024, 1024, interpret)

    def reference(q, k, v):
        return reference_attention(q, k, v, causal=True).astype(dtype)

    def loss_of(attn):
        def loss(q, k, v):
            return jnp.sum(
                attn(q, k, v).astype(jnp.float32) * w.astype(jnp.float32)
            )

        return jax.grad(loss, argnums=(0, 1, 2))

    results: dict = {}
    for name, kernel, oracle in (
        ("flash_fwd", flash, reference),
        ("flash_bwd", loss_of(flash), loss_of(reference)),
    ):
        compiled, seconds = _timed_compile(kernel, q, k, v)
        compile_s += seconds
        t_run = time.monotonic()
        got = jax.block_until_ready(compiled(q, k, v))
        run_s += time.monotonic() - t_run
        want = jax.jit(oracle)(q, k, v)
        errors = [
            err(g, x) for g, x in zip(
                jax.tree.leaves(got), jax.tree.leaves(want)
            )
        ]
        limit = tol if name == "flash_fwd" else grad_tol
        check(
            all(math.isfinite(e) and e <= limit for e in errors),
            f"{name} disagrees with reference_attention: rel err {errors} "
            f"> {limit}",
        )
        results[name] = {
            "shape": [batch, nh, kvh, seq_len, dh],
            "rel_err": [round(e, 5) for e in errors],
            "tolerance": limit,
            "compile_s": round(seconds, 1),
        }

    # ---- paged decode attention as the decode program calls it: the
    # STACKED pool leaf and a layer index, the new token written into its
    # page in place (pool results aliased to the donated pool operands) and
    # the row's live pages read where they lie; at the benchmark's table
    # width (288 pages of 16 slots) with all KV heads and with the two a
    # tp=4 shard holds; ragged lengths: a single token, a partial boundary
    # page, an exact page multiple, nearly the whole table. Against
    # paged_append, paged_read and the einsums on the layer's slice.
    rep = nh // kvh
    rows, layers, layer = 4, 2, 1
    n_pool = rows * pages_per_seq + 8
    table = jnp.asarray(
        np.random.default_rng(SEED).permutation(n_pool - 1)[
            : rows * pages_per_seq
        ].reshape(rows, pages_per_seq) + 1,
        dtype=jnp.int32,
    )
    span = pages_per_seq * page_size
    lengths = jnp.asarray(
        [1, 2 * page_size + 5, 4 * page_size, span - 6], dtype=jnp.int32
    )

    def paged_kernel(qd, k_pool, v_pool, table, lengths, layer, k_new, v_new):
        return paged_decode_attention(
            qd, k_pool, v_pool, table, lengths, None, interpret,
            layer=layer, k_new=k_new, v_new=v_new,
        )

    def paged_einsum(qd, k_pool, v_pool, table, lengths, layer, k_new, v_new):
        heads = k_pool.shape[2]
        at = lengths - 1
        c_layer = paged_append(
            {"k": k_pool[layer], "v": v_pool[layer]},
            k_new[:, None], v_new[:, None],
            jnp.take_along_axis(table, (at // page_size)[:, None], axis=1),
            (at % page_size)[:, None],
        )
        kf, vf = paged_read(c_layer, table, dtype)
        qg = qd.reshape(rows, heads, rep, dh).astype(jnp.float32)
        scores = jnp.einsum("bgrd,bgsd->bgrs", qg, kf) / math.sqrt(dh)
        visible = jnp.arange(span)[None, :] < lengths[:, None]
        scores = jnp.where(visible[:, None, None, :], scores, -jnp.inf)
        weights = jax.nn.softmax(scores, axis=-1).astype(dtype)
        out = jnp.einsum("bgrs,bgsd->bgrd", weights, vf)
        return (
            out.reshape(rows, heads * rep, dh),
            k_pool.at[layer].set(c_layer["k"]),
            v_pool.at[layer].set(c_layer["v"]),
        )

    for name, heads in (
        ("paged_decode", kvh), ("paged_decode_tp4_shard", max(1, kvh // 4)),
    ):
        pool_shape = (layers, n_pool, heads, page_size, dh)
        args = (
            jax.random.normal(keys[6], (rows, heads * rep, dh), dtype),
            jax.random.normal(keys[4], pool_shape, dtype),
            jax.random.normal(keys[5], pool_shape, dtype),
            table, lengths, jnp.int32(layer),
            jax.random.normal(keys[7], (rows, heads, dh), dtype),
            jax.random.normal(keys[3], (rows, heads, dh), dtype),
        )
        want = jax.block_until_ready(jax.jit(paged_einsum)(*args))
        t_compile = time.monotonic()
        compiled = jax.jit(paged_kernel, donate_argnums=(1, 2)).lower(
            *args
        ).compile()
        seconds = time.monotonic() - t_compile
        compile_s += seconds
        aliased = compiled.memory_analysis().alias_size_in_bytes
        t_run = time.monotonic()
        got = jax.block_until_ready(compiled(*args))
        run_s += time.monotonic() - t_run
        paged_err = err(got[0], want[0])
        check(
            math.isfinite(paged_err) and paged_err <= tol,
            f"{name}: paged_decode_attention disagrees with the paged_read "
            f"einsum path: rel err {paged_err} > {tol}",
        )
        check(
            all(bool(jnp.array_equal(g, w)) for g, w in zip(got[1:], want[1:])),
            f"{name}: the pool the kernel wrote in place is not the pool "
            "paged_append writes",
        )
        check(
            interpret
            or aliased >= 2 * math.prod(pool_shape) * jnp.dtype(dtype).itemsize,
            f"{name}: the pool results do not alias the donated pool "
            f"operands ({aliased} bytes aliased)",
        )
        results[name] = {
            "shape": {"rows": rows, "nh": heads * rep, "kvh": heads, "rep": rep,
                      "dh": dh, "page_size": page_size,
                      "pages_per_seq": pages_per_seq, "layers": layers,
                      "layer": layer},
            "lengths": [int(x) for x in lengths],
            "rel_err": round(paged_err, 5),
            "pool_bitwise_equal": True,
            "aliased_bytes": int(aliased),
            "tolerance": tol,
            "compile_s": round(seconds, 1),
        }
    # a head of 64 (granite-4.0-h-micro) does not fill the lane tile: Mosaic
    # refuses the copy of such a page, and the predicate keeps the gather
    narrow = {"k": jax.ShapeDtypeStruct((n_pool, kvh, page_size, 64), dtype)}
    with mock.patch.object(paged_attention, "on_tpu", lambda: True):
        check(
            not paged_attention.reads_pages_in_place(narrow, 1, None),
            "a head of 64 would take the paged decode kernel",
        )
    total_s = time.monotonic() - t0
    return {
        "device": device,
        "lowering": "pallas interpreter" if interpret else "mosaic",
        "kernels": results,
        "timing_note": SMOKE_NOTE,
        "seconds": {
            "init": round(init_s, 1),
            "compile": round(compile_s, 1),
            "run": round(run_s, 2),
            "total": round(total_s, 1),
        },
    }


# --------------------------------------------------------- four-chip phases


def _balanced(rows: list[dict], what: str, slack: float = 0.2) -> None:
    if any(r["estimated"] for r in rows):  # CPU backend: no memory_stats
        return
    live = [r["live_bytes"] for r in rows]
    check(
        (max(live) - min(live)) <= slack * max(live),
        f"{what}: per-device bytes in use not balanced: {rows}",
    )


def phase_tp4(
    config,
    platform: str,
    *,
    n_layers: int = 16,
    prompt_lens: tuple[int, ...] = (48, 200),
    n_requests: int = 4,
    new_tokens: int = 16,
    max_batch: int = 4,
    page_size: int = 16,
    max_pages_per_seq: int = 32,
    n_pages: int = 2048,
) -> dict:
    """The serving phase's model tensor-parallel over four chips."""
    import dataclasses

    t0 = time.monotonic()
    device, init_s = backend_up(platform)
    check(device["count"] >= 4, f"tp4 needs four devices, found {device}")
    import jax
    from jax.sharding import NamedSharding

    from bee_code_interpreter_tpu.models import transformer as T
    from bee_code_interpreter_tpu.models.engine import Engine
    from bee_code_interpreter_tpu.models.serving import ContinuousBatcher
    from bee_code_interpreter_tpu.observability import DeviceMonitor
    from bee_code_interpreter_tpu.parallel import make_mesh

    cfg = dataclasses.replace(config, n_layers=n_layers)
    mesh = make_mesh({"tp": 4}, devices=jax.devices()[:4])
    shardings = jax.tree.map(
        lambda spec: NamedSharding(mesh, spec),
        T.param_specs(cfg, mesh),
        is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec),
    )
    params = seeded_params(cfg, SEED, shardings)
    jax.block_until_ready(params)
    batcher = ContinuousBatcher(
        params, cfg, mesh=mesh, max_batch=max_batch, n_pages=n_pages,
        page_size=page_size, max_pages_per_seq=max_pages_per_seq,
    )
    engine = Engine(batcher)
    monitor = DeviceMonitor()
    monitor.attach(engine)
    requests = requests_for(cfg, prompt_lens, n_requests, new_tokens)
    tickets = [
        engine.submit(p, new_tokens, sampling=s) for p, s in requests
    ]
    engine.run_to_completion()
    check_finished(engine, tickets, requests, cfg, new_tokens)

    for name, tree in (("params", batcher.params), ("pool", batcher.cache)):
        spread = {len(leaf.devices()) for leaf in jax.tree.leaves(tree)}
        check(spread == {4}, f"tp4 {name} not on all four devices: {spread}")
    rows = local_memory_rows()[:4]
    _balanced(rows, "tp4")
    compiles = monitor.snapshot(recent=256)["compile"]
    compile_s = sum(c["duration_ms"] for c in compiles["recent"]) / 1000.0
    total_s = time.monotonic() - t0
    return {
        "device": device,
        "mesh": monitor.snapshot(recent=0)["mesh"],
        "n_layers": n_layers,
        "requests": {"n": n_requests, "prompt_lens": list(prompt_lens),
                     "new_tokens": new_tokens},
        "hbm": rows,
        "compiles": {"total": compiles["total"],
                     "by_trigger": compiles["by_trigger"]},
        "timing_note": SMOKE_NOTE,
        "seconds": {
            "init": round(init_s, 1), "compile": round(compile_s, 1),
            "run": round(total_s - init_s - compile_s, 1),
            "total": round(total_s, 1),
        },
    }


def phase_replicas(
    config,
    platform: str,
    *,
    n_layers: int = 16,
    prompt_len: int = 48,
    n_requests: int = 8,
    new_tokens: int = 16,
    max_batch: int = 4,
    page_size: int = 16,
    max_pages_per_seq: int = 8,
    n_pages: int = 2048,
) -> dict:
    """Four one-chip engines in one process, each on its own device."""
    import dataclasses

    t0 = time.monotonic()
    device, init_s = backend_up(platform)
    check(device["count"] >= 4, f"replicas need four devices, found {device}")
    import jax

    from bee_code_interpreter_tpu.models.replicated import ReplicatedEngine

    cfg = dataclasses.replace(config, n_layers=n_layers)
    # one HOST copy of the params, as build() asks: each replica places its
    # own on its device, and no device carries the source beside its replica
    params = jax.device_get(seeded_params(cfg, SEED))
    fleet = ReplicatedEngine.build(
        params, cfg, n_replicas=4, max_batch=max_batch, n_pages=n_pages,
        page_size=page_size, max_pages_per_seq=max_pages_per_seq,
    )
    del params
    devices = jax.devices()[:4]
    for i, engine in enumerate(fleet.engines):
        for name, tree in (
            ("params", engine.batcher.params), ("pool", engine.batcher.cache)
        ):
            where = set().union(
                *(leaf.devices() for leaf in jax.tree.leaves(tree))
            )
            check(
                where == {devices[i]},
                f"replica {i} {name} live on {where}, not {devices[i]}",
            )
    requests = requests_for(cfg, (prompt_len,), n_requests, new_tokens)
    t_run = time.monotonic()
    tickets = [
        fleet.submit(p, new_tokens, sampling=s) for p, s in requests
    ]
    fleet.run_to_completion()
    run_s = time.monotonic() - t_run
    check_finished(fleet, tickets, requests, cfg, new_tokens)
    used = sorted({fleet.replica_of(t) for t in tickets})
    check(used == [0, 1, 2, 3], f"routing left replicas idle: used {used}")
    rows = local_memory_rows()[:4]
    _balanced(rows, "replicas")
    total_s = time.monotonic() - t0
    return {
        "device": device,
        "n_layers": n_layers,
        "replicas_used": used,
        "requests": {"n": n_requests, "prompt_len": prompt_len,
                     "new_tokens": new_tokens},
        "hbm": rows,
        "timing_note": SMOKE_NOTE,
        "seconds": {
            "init": round(init_s, 1),
            "serve_incl_compile": round(run_s, 1),
            "total": round(total_s, 1),
        },
    }


# ------------------------------------------------------------ phase: service


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _http_json(method: str, url: str, body: dict | None = None,
               timeout: float = 30.0) -> tuple[int, dict]:
    data = json.dumps(body).encode() if body is not None else None
    request = urllib.request.Request(
        url, data=data, method=method,
        headers={"Content-Type": "application/json"},
    )
    try:
        with urllib.request.urlopen(request, timeout=timeout) as response:
            return response.status, json.loads(response.read() or b"{}")
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read() or b"{}")


def _wait_http(url: str, timeout_s: float, proc=None, ready=None) -> dict:
    """Poll ``url`` until it answers 200 (and ``ready(body)`` holds)."""
    deadline = time.monotonic() + timeout_s
    last: object = None
    while time.monotonic() < deadline:
        if proc is not None and proc.poll() is not None:
            raise SmokeFailure(f"process behind {url} exited {proc.returncode}")
        try:
            status, body = _http_json("GET", url, timeout=5.0)
            if status == 200 and (ready is None or ready(body)):
                return body
            last = (status, body)
        except (OSError, ValueError) as e:
            last = e
        time.sleep(0.1)
    raise SmokeFailure(f"{url} not ready after {timeout_s:.0f}s: {last}")


def build_executor() -> Path:
    """The native executor, rebuilt from the committed sources: ``make -B``
    — a stale ignored ``executor/build/executor-server`` that happens to be
    on disk is never trusted."""
    build = subprocess.run(
        ["make", "-B", "-C", str(REPO / "executor")],
        capture_output=True, text=True, timeout=300,
    )
    binary = REPO / "executor" / "build" / "executor-server"
    check(
        build.returncode == 0 and binary.is_file(),
        f"make -C executor failed:\n{build.stdout}\n{build.stderr}",
    )
    return binary


def child_env(platform: str) -> dict[str, str]:
    """Environment for every process the smoke starts: the platform pinned
    (an ambient JAX_PLATFORMS wins — and then must BE ``platform``, or the
    phase's own check fails), the compile cache in its one place, the
    checkout importable (the executor image installs the package)."""
    from bee_code_interpreter_tpu.utils.jaxcache import ENV_VAR, jax_cache_dir

    env = dict(os.environ)
    env.setdefault("JAX_PLATFORMS", platform)
    env[ENV_VAR] = jax_cache_dir()
    parts = [str(REPO)] + [
        p for p in env.get("PYTHONPATH", "").split(os.pathsep) if p
    ]
    env["PYTHONPATH"] = os.pathsep.join(dict.fromkeys(parts))
    return env


def _maps_of(pid: int) -> str:
    return Path(f"/proc/{pid}/maps").read_text()


def _servers_of(service_pid: int) -> list[int]:
    """executor-server processes spawned by this service (they carry
    ``APP_PARENT_PID``) that are still alive."""
    out = subprocess.run(
        ["pgrep", "-x", "executor-server"], capture_output=True, text=True
    ).stdout
    alive = []
    for pid in map(int, out.split()):
        try:
            environ = Path(f"/proc/{pid}/environ").read_bytes()
        except OSError:
            continue
        if f"APP_PARENT_PID={service_pid}".encode() in environ.split(b"\0"):
            alive.append(pid)
    return alive


DEVICE_PROBE = """
import json, time
t0 = time.time()
import jax
d = jax.devices()[0]
print("DEVICE", json.dumps({"platform": d.platform, "kind": d.device_kind,
    "count": len(jax.devices()), "init_s": round(time.time() - t0, 1)}))
"""


def _marker(stdout: str, marker: str) -> dict:
    for line in stdout.splitlines():
        if line.startswith(marker + " "):
            return json.loads(line[len(marker):])
    raise SmokeFailure(f"no {marker} line in payload stdout: {stdout!r}")


def reroute_payload(platform: str) -> str:
    """examples/benchmark-numpy.py, then: its result must be a TpuArray on
    ``platform`` and the reroute's backend probe must have succeeded — the
    host fallback exists for user code, and must not pass for the device."""
    return (REPO / "examples" / "benchmark-numpy.py").read_text() + f"""
import json
from bee_code_interpreter_tpu.runtime import xla_reroute
status = xla_reroute.backend_status()
assert status["ok"] and status["platform"] == {platform!r}, status
assert type(y).__name__ == "TpuArray", type(y)
(where,) = y.jax_array.devices()
assert where.platform == {platform!r}, where
print("REROUTE", json.dumps({{"platform": where.platform,
    "kind": where.device_kind, "probe": status}}))
"""


def warm_worker_cycle(binary: Path, platform: str, tmp: Path) -> dict:
    """One executor-server in the executor image's warm configuration
    (``APP_WARMUP=1`` and the ``bci_tpu_warm`` preload holding the backend in
    the pre-started worker) serving a three-execute lease: warm, cold,
    re-warmed. One accelerator process at a time or this fails — the warm-up
    interpreter, the worker, the cold request and the replacement worker all
    want the chip."""
    port = _free_port()
    env = child_env(platform)
    env.update(
        APP_LISTEN_ADDR=f"127.0.0.1:{port}",
        APP_WORKSPACE=str(tmp / "image-ws"),
        APP_DISABLE_DEP_INSTALL="1",
        APP_PYPI_MAP=str(REPO / "executor" / "pypi_map.tsv"),
        APP_PYTHON=sys.executable,
        APP_SHIM_DIR=str(SHIM_DIR),
        APP_WARMUP="1",
        APP_PRESTART_IMPORTS="numpy,bci_tpu_warm",
        APP_PRESTART_PRELOAD_TIMEOUT_S="180",
        APP_EXECUTION_TIMEOUT_S="300",
        APP_DIE_WITH_PARENT="1",
        APP_PARENT_PID=str(os.getpid()),
    )
    log = open(tmp / "image-server.log", "wb")
    t0 = time.monotonic()
    server = subprocess.Popen(
        [str(binary)], env=env, stdout=log, stderr=subprocess.STDOUT
    )
    try:
        base = f"http://127.0.0.1:{port}"
        health = _wait_http(
            base + "/healthz", 300.0, proc=server,
            ready=lambda body: body.get("warm"),
        )
        warm_s = time.monotonic() - t0
        check(
            "warm_error" not in health,
            f"image configuration warm-up failed: {health.get('warm_error')}",
        )
        source = (
            "import sys\nwarm = 'bci_tpu_warm' in sys.modules\n"
            + DEVICE_PROBE + "print('WARM', warm)\n"
        )
        turns = []
        for turn, want_warm in enumerate((True, False, True), start=1):
            status, body = _http_json(
                "POST", base + "/execute", {"source_code": source},
                timeout=330.0,
            )
            check(
                status == 200 and body.get("exit_code") == 0,
                f"lease turn {turn} failed: {status} {body}",
            )
            seen = _marker(body["stdout"], "DEVICE")
            check(
                seen["platform"] == platform,
                f"lease turn {turn} ran on {seen}, expected {platform}",
            )
            check(
                f"WARM {want_warm}" in body["stdout"],
                f"lease turn {turn}: expected warm={want_warm}: "
                f"{body['stdout']!r}",
            )
            turns.append({"warm": want_warm, "device": seen})
        health = _http_json("GET", base + "/healthz")[1]
        check(
            "warm_error" not in health,
            f"a replacement worker failed to warm: {health.get('warm_error')}",
        )
        return {"warm_s": round(warm_s, 1), "turns": turns}
    except BaseException:
        log.flush()
        sys.stderr.write(
            (tmp / "image-server.log").read_text(errors="replace")[-4000:]
        )
        raise
    finally:
        server.kill()
        server.wait(timeout=30)
        log.close()


def phase_service(config, platform: str) -> dict:
    """The service on the chip, through its normal entry points. ``config``
    is accepted for the uniform phase signature: the sandbox payloads size
    themselves from the device they find (and must say which)."""
    import bench

    t0 = time.monotonic()
    binary = build_executor()
    build_s = time.monotonic() - t0
    tmp = Path(tempfile.mkdtemp(prefix="chip-smoke-"))
    http_port, grpc_port = _free_port(), _free_port()
    env = child_env(platform)
    env.update(
        APP_EXECUTOR_BACKEND="local",
        APP_LOCAL_EXECUTOR_BINARY=str(binary),
        APP_HTTP_LISTEN_ADDR=f"127.0.0.1:{http_port}",
        APP_GRPC_LISTEN_ADDR=f"127.0.0.1:{grpc_port}",
        APP_FILE_STORAGE_PATH=str(tmp / "files"),
        APP_LOCAL_WORKSPACE_ROOT=str(tmp / "ws"),
        APP_DISABLE_DEP_INSTALL="1",
        # cold compiles: init + compile of the bundled models takes minutes
        APP_EXECUTION_TIMEOUT_S="900",
        APP_EXECUTOR_HTTP_TIMEOUT_S="900",
        APP_REQUEST_DEADLINE_S="900",
    )
    log = open(tmp / "service.log", "wb")
    service = subprocess.Popen(
        [sys.executable, "-m", "bee_code_interpreter_tpu"],
        env=env, cwd=REPO, stdout=log, stderr=subprocess.STDOUT,
    )
    base = f"http://127.0.0.1:{http_port}"
    requests: dict = {}

    def execute(name: str, source: str) -> str:
        t_req = time.monotonic()
        status, body = _http_json(
            "POST", base + "/v1/execute", {"source_code": source},
            timeout=930.0,
        )
        check(
            status == 200 and body.get("exit_code") == 0,
            f"/v1/execute [{name}] failed: HTTP {status} "
            f"exit {body.get('exit_code')}\n{body.get('stderr', body)}",
        )
        requests[name] = {"seconds": round(time.monotonic() - t_req, 1)}
        return body["stdout"]

    try:
        _wait_http(base + "/healthz", 120.0, proc=service)

        matmul = _marker(
            execute("matmul", bench.matmul_chain_payload(platform)),
            "RESULT_MATMUL",
        )
        check(matmul["platform"] == platform, f"matmul ran on {matmul}")
        requests["matmul"].update(matmul)

        reroute = _marker(
            execute("reroute", reroute_payload(platform)), "REROUTE"
        )
        requests["reroute"].update(reroute)

        stdout = execute(
            "continuous_batching",
            (REPO / "examples" / "continuous-batching.py").read_text(),
        )
        check(
            f"platform={platform} " in stdout,
            f"continuous-batching.py did not run on {platform}: {stdout!r}",
        )
        for line in ("continuous batching OK", "speculative serving OK",
                     "prefix caching OK"):
            check(line in stdout, f"no {line!r} in: {stdout!r}")
        requests["continuous_batching"]["stdout"] = stdout.splitlines()

        import asyncio

        import grpc.aio

        from bee_code_interpreter_tpu.api.grpc_server import service_stubs
        from bee_code_interpreter_tpu.proto import code_interpreter_pb2 as pb

        async def grpc_execute():
            address = f"127.0.0.1:{grpc_port}"
            async with grpc.aio.insecure_channel(address) as channel:
                return await service_stubs(channel)["Execute"](
                    pb.ExecuteRequest(source_code=DEVICE_PROBE), timeout=900
                )

        t_req = time.monotonic()
        response = asyncio.run(grpc_execute())
        check(
            response.exit_code == 0,
            f"gRPC Execute failed: {response.stderr}",
        )
        seen = _marker(response.stdout, "DEVICE")
        check(seen["platform"] == platform, f"gRPC Execute ran on {seen}")
        requests["grpc_execute"] = {
            "seconds": round(time.monotonic() - t_req, 1), **seen
        }
        probe = subprocess.run(
            [sys.executable, "-m", "bee_code_interpreter_tpu.health_check",
             f"127.0.0.1:{grpc_port}"],
            env=env, cwd=REPO, capture_output=True, text=True, timeout=300,
        )
        check(
            probe.returncode == 0 and "healthy" in probe.stdout,
            f"health_check failed: {probe.stdout} {probe.stderr}",
        )

        # The control plane stays off the device: after serving all of the
        # above from sandbox children, the service process itself has mapped
        # neither libtpu nor jaxlib, and its accelerator routes answer
        # without touching one.
        status, accelerator = _http_json("GET", base + "/v1/accelerator")
        check(
            status == 200 and accelerator["attached"] is False
            and accelerator["memory"]["devices"] == []
            and "no in-process engine" in accelerator["memory"]["reason"],
            f"/v1/accelerator: {status} {accelerator}",
        )
        status, body = _http_json(
            "POST", base + "/v1/profile", {"target": "device", "steps": 1}
        )
        check(status == 501, f"/v1/profile target=device: {status} {body}")
        for library in ("libtpu", "jaxlib"):
            check(
                library not in _maps_of(service.pid),
                f"the service process mapped {library}: it holds, or could "
                "take, the chip its sandboxes need",
            )
    except BaseException:
        log.flush()
        sys.stderr.write(
            (tmp / "service.log").read_text(errors="replace")[-6000:]
        )
        raise
    finally:
        service.send_signal(signal.SIGTERM)
        try:
            service.wait(timeout=60)
        except subprocess.TimeoutExpired:
            service.kill()
            service.wait(timeout=30)
        log.close()
    deadline = time.monotonic() + 15.0
    while _servers_of(service.pid) and time.monotonic() < deadline:
        time.sleep(0.2)
    survivors = _servers_of(service.pid)
    check(not survivors, f"executor-server survived shutdown: {survivors}")

    warm = warm_worker_cycle(binary, platform, tmp)
    shutil.rmtree(tmp, ignore_errors=True)
    device = {
        "platform": matmul["platform"], "kind": matmul["device_kind"],
        "count": matmul["device_count"],
    }
    total_s = time.monotonic() - t0
    return {
        "device": device,
        "requests": requests,
        "image_warm_configuration": warm,
        "service_process": "no libtpu / jaxlib mapping",
        "timing_note": SMOKE_NOTE,
        "seconds": {
            "build": round(build_s, 1),
            # of the matmul sandbox, the one request that splits its time
            "init": matmul["init_s"],
            "compile": matmul["first_call_s"],
            "run": round(3 * matmul["best_s"], 2),
            "total": round(total_s, 1),
        },
    }


# ---------------------------------------------------------------- the parent

PHASES = {
    "service": phase_service,
    "serving": phase_serving,
    "kernels": phase_kernels,
    "tp4": phase_tp4,
    "replicas": phase_replicas,
}


def run_phase_child(name: str) -> None:
    """``--phase NAME``: one phase at full size on a TPU, in this process."""
    sys.path.insert(0, str(REPO))
    if name == "service":
        config = None  # unused by the service phase; keeps this process off jax
    else:
        from bee_code_interpreter_tpu.models.transformer import (
            TransformerConfig,
        )

        config = TransformerConfig.llama3_8b()
    result = PHASES[name](config, "tpu")
    print("PHASE_RESULT", json.dumps(result), flush=True)


def result_line(device: dict) -> str:
    """The last line of stdout: one JSON object with exactly the keys ``ok``
    and ``device``, the device exactly ``platform``, ``kind`` and ``count``
    as jax reported them (``backend_up``). Everything else the run learned
    goes on the SUMMARY line before it."""
    return json.dumps({
        "ok": True,
        "device": {
            "platform": str(device["platform"]),
            "kind": str(device["kind"]),
            "count": int(device["count"]),
        },
    })


def _cache_entries(path: str) -> int:
    try:
        return sum(1 for _ in os.scandir(path))
    except OSError:
        return 0


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1)
    parser.add_argument("--phase", choices=sorted(PHASES), help=argparse.SUPPRESS)
    args = parser.parse_args(argv)
    if args.phase:
        run_phase_child(args.phase)
        return 0

    sys.path.insert(0, str(REPO))
    try:
        from bee_code_interpreter_tpu.utils.jaxcache import jax_cache_dir
    except ImportError as e:
        print(f"chip_smoke: not inside a checkout of the repo ({e})",
              file=sys.stderr)
        return 2
    env = child_env("tpu")
    cache_dir = jax_cache_dir()
    cache_before = _cache_entries(cache_dir)
    names = ONE_CHIP_PHASES if args.chips == 1 else FOUR_CHIP_PHASES
    started = time.monotonic()
    phases: dict = {}
    for name in names:
        budget = PHASE_TIMEOUT_S[name]
        if args.chips == 1:
            budget = min(
                budget, GLOBAL_BUDGET_S - (time.monotonic() - started)
            )
        print(f"[chip_smoke] phase {name} ...", flush=True)
        t0 = time.monotonic()
        child = subprocess.Popen(
            [sys.executable, str(REPO / "chip_smoke.py"), "--phase", name],
            env=env, cwd=REPO, stdout=subprocess.PIPE, text=True,
            start_new_session=True,
        )
        try:
            stdout, _ = child.communicate(timeout=max(1.0, budget))
        except subprocess.TimeoutExpired:
            with contextlib.suppress(ProcessLookupError):
                os.killpg(child.pid, signal.SIGKILL)
            child.wait()
            print(f"chip_smoke: phase {name} FAILED: timed out after "
                  f"{budget:.0f}s", file=sys.stderr)
            return 1
        finally:
            # a phase that died may leave its service behind: nothing this
            # script started outlives it
            with contextlib.suppress(ProcessLookupError):
                os.killpg(child.pid, signal.SIGKILL)
        result = None
        for line in stdout.splitlines():
            if line.startswith("PHASE_RESULT "):
                result = json.loads(line[len("PHASE_RESULT "):])
        if child.returncode != 0 or result is None:
            print(f"chip_smoke: phase {name} FAILED (exit {child.returncode})"
                  f"\n{stdout[-2000:]}", file=sys.stderr)
            return 1
        if result["device"]["platform"] != "tpu":
            print(f"chip_smoke: phase {name} FAILED: ran on "
                  f"{result['device']}, not a TPU", file=sys.stderr)
            return 1
        phases[name] = result
        print(f"[chip_smoke] phase {name} ok in "
              f"{time.monotonic() - t0:.1f}s: {json.dumps(result)}", flush=True)

    devices = [p["device"] for p in phases.values()]
    if any(d != devices[0] for d in devices):
        print(f"chip_smoke: phases disagree on the device: {devices}",
              file=sys.stderr)
        return 1
    print("SUMMARY", json.dumps({
        "phases": {
            name: {"seconds": p["seconds"]} for name, p in phases.items()
        },
        "compiles": phases.get("serving", {}).get("compiles"),
        "peak_hbm_bytes": phases.get("serving", {}).get("peak_hbm_bytes"),
        "compile_cache": {
            "dir": cache_dir, "entries_before": cache_before,
            "entries_after": _cache_entries(cache_dir),
        },
        "seconds_total": round(time.monotonic() - started, 1),
        "timing_note": SMOKE_NOTE,
        "claim": None,
    }), flush=True)
    print(result_line(devices[0]), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
