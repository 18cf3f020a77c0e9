#!/usr/bin/env python
"""KV-cached decode throughput on the real TPU chip.

Three measurements:

1. ``decode``: tokens/sec of the full incremental decode loop
   (models/transformer.decode_step — one lax.scan-compiled program updating
   the cache in place) on a ~1B-param llama-shaped config sized for one
   v5e chip's HBM.
2. ``decode_attention``: the attention inner loop in isolation — the
   grouped-query einsum (reads the compact [B, KVH, S, D] cache once)
   against the jnp.repeat broadcast variant it replaced. Decode is
   KV-cache-bandwidth-bound, so the repeat variant's H/KVH× extra HBM
   traffic is the whole story.

Timing: the decode loop is naturally self-chaining (each step consumes the
previous cache/token), so one jit + one scalar readback measures N real
steps — the same chained clock as scripts/bench-flash-attention.py
(utils/benchclock.chain_diff).

3. ``paged_decode_kernel_alone``: the paged decode kernel at the
   benchmark's shapes, in the form the decode program runs (the stacked
   pool leaf at a layer index, the new token written in place), without
   the write, and the way it ran before (a slice cut, scattered into and
   put back). ``python scripts/bench-decode.py kernel`` runs this alone.
   Not a measurement of any cell.

One process, the one that holds the chip: no out-of-process probe.
Usage:  python scripts/bench-decode.py   (needs a TPU; exits 2 if none)
Prints one JSON line per case, each naming the device.
"""

from __future__ import annotations

import dataclasses
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

# before the first jax import: the compile cache lives where the environment
# says, else at the checkout's fixed path (utils/jaxcache.py)
os.environ[_CACHE_ENV] = jax_cache_dir()

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax import lax  # noqa: E402


def run_measurements(emit) -> None:
    """All decode measurements, in this one process."""
    from bee_code_interpreter_tpu.models.transformer import (
        TransformerConfig,
        decode_step,
        forward,
        init_decode_cache,
        init_params,
    )

    # ~1.1B params (f32 masters ~4.4 GB + bf16 cache) — fits one v5e chip
    config = TransformerConfig(
        vocab_size=32000, d_model=2048, n_layers=16, n_heads=16,
        n_kv_heads=4, d_ff=5632, max_seq_len=2048,
    )
    B, L_prompt, ctx = 8, 128, 2048
    params = init_params(config, jax.random.PRNGKey(0))
    n_params = sum(x.size for x in jax.tree.leaves(params))
    prompt = jax.random.randint(jax.random.PRNGKey(1), (B, L_prompt), 0, 32000)

    # prefill once to seed the cache
    logits, (k_pre, v_pre) = forward(params, prompt, config, None, return_kv=True)
    c = config
    first = jnp.argmax(logits[:, -1:, :], axis=-1).astype(jnp.int32)

    def decode_chain(step_fn, n_steps):
        """The ONE chained-decode loop both the contiguous and paged
        measurements compile — structurally identical by construction, so
        their comparison prices only the cache indexing.
        ``step_fn(tok, pos, cache) -> (logits, cache)``."""

        @jax.jit
        def f(tok, cache):
            def body(carry, pos):
                tok, cache = carry
                lg, cache = step_fn(tok, pos, cache)
                nxt = jnp.argmax(lg[:, -1:, :], axis=-1).astype(jnp.int32)
                return (nxt, cache), None

            (tok, _), _ = lax.scan(
                body, (tok, cache),
                jnp.arange(L_prompt, L_prompt + n_steps, dtype=jnp.int32),
            )
            return tok.astype(jnp.float32).sum()

        return f

    def decode_n(cfg, n_steps):
        return decode_chain(
            lambda tok, pos, cache: decode_step(params, tok, pos, cache, cfg),
            n_steps,
        )

    def best_of(f, *args, reps=3):
        float(f(*args))  # compile + warm
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            float(f(*args))
            best = min(best, time.perf_counter() - t0)
        return best

    from bee_code_interpreter_tpu.utils.benchclock import chain_diff

    N = 64
    per_step = {}
    for name in ("bf16", "int8"):
        cfg = dataclasses.replace(config, kv_cache_dtype=name)
        cache0 = init_decode_cache(cfg, B, ctx, k_pre, v_pre)
        t_n = best_of(decode_n(cfg, N), first, cache0)
        t_1 = best_of(decode_n(cfg, 1), first, cache0)
        per_step[name] = chain_diff(t_n, t_1, N)
    # decode is HBM-bound: each step streams params (bf16 at compute) + cache
    cache_bytes = {
        "bf16": 2 * c.n_layers * B * c.kv_heads * ctx * c.head_dim * 2,
        "int8": 2 * c.n_layers * B * c.kv_heads * ctx * (c.head_dim + 4),
    }
    emit("decode", {
        "config": {"d_model": c.d_model, "n_layers": c.n_layers,
                   "heads": f"{c.n_heads}/{c.kv_heads}", "batch": B,
                   "ctx": ctx, "params": n_params},
        "per_step_ms": round(per_step["bf16"] * 1e3, 3),
        "tokens_per_sec": round(B / per_step["bf16"], 1),
        "int8_cache_per_step_ms": round(per_step["int8"] * 1e3, 3),
        "int8_cache_tokens_per_sec": round(B / per_step["int8"], 1),
        "int8_speedup": round(per_step["bf16"] / per_step["int8"], 2),
        "approx_hbm_gbps": round(
            (2 * n_params + cache_bytes["bf16"]) / per_step["bf16"] / 1e9, 1
        ),
        "int8_approx_hbm_gbps": round(
            (2 * n_params + cache_bytes["int8"]) / per_step["int8"] / 1e9, 1
        ),
    })

    # --- paged cache: the serving layout's cost vs the contiguous cache ----
    # Same config, same step count; the delta prices the block-table
    # indirection (the capacity win — densely shared pages across
    # heterogeneous requests — is free only if this tax is small). The
    # step attends the way the predicate picks (below).
    from bee_code_interpreter_tpu.models.transformer import decode_step_paged
    from bee_code_interpreter_tpu.ops.paged_kv_cache import (
        alloc_paged_cache,
        seed_prefill,
    )

    import math as _math

    ps = 64
    P = ctx // ps
    paged0 = alloc_paged_cache(config, n_pages=1 + B * P, page_size=ps)
    bt = (1 + jnp.arange(B * P, dtype=jnp.int32)).reshape(B, P)
    n_prompt_pages = _math.ceil(L_prompt / ps)
    seed = jax.jit(seed_prefill, donate_argnums=(0,))  # the pool in place
    for b in range(B):
        # seed only the pages the prompt occupies (the rest are already
        # zero; scattering them again is pure setup traffic)
        paged0 = seed(
            paged0, bt[b, :n_prompt_pages], k_pre[:, b], v_pre[:, b]
        )

    def decode_paged_n(n_steps):
        return decode_chain(
            lambda tok, pos, cache: decode_step_paged(
                params, tok, jnp.full((B,), pos), cache, bt, config
            ),
            n_steps,
        )

    t_pn = best_of(decode_paged_n(N), first, paged0)
    t_p1 = best_of(decode_paged_n(1), first, paged0)
    per_step_paged = chain_diff(t_pn, t_p1, N)
    emit("paged_decode", {
        "page_size": ps, "pages_per_seq": P,
        "per_step_ms": round(per_step_paged * 1e3, 3),
        "tokens_per_sec": round(B / per_step_paged, 1),
        "overhead_vs_contiguous": round(
            per_step_paged / per_step["bf16"] - 1.0, 3
        ),
    })

    # --- the gather against the path the predicate picked ----------------
    # (ops/paged_attention.reads_pages_in_place: on a TPU, at this head of
    # 128, ``paged_decode`` above ran the Pallas kernel over each row's live
    # pages; with the predicate's backend part patched off, the same step
    # gathers the table's width through paged_read)
    from unittest import mock

    from bee_code_interpreter_tpu.ops import paged_attention

    picked = (
        "pages_in_place"
        if paged_attention.reads_pages_in_place(paged0, 1, None)
        else "gathered"
    )
    with mock.patch.object(paged_attention, "on_tpu", lambda: False):
        t_gn = best_of(decode_paged_n(N), first, paged0)
        t_g1 = best_of(decode_paged_n(1), first, paged0)
    per_step_gather = chain_diff(t_gn, t_g1, N)
    emit("paged_decode_attention_path", {
        "picked": picked,
        "picked_per_step_ms": round(per_step_paged * 1e3, 3),
        "gathered_per_step_ms": round(per_step_gather * 1e3, 3),
        "speedup_vs_gather_path": round(per_step_gather / per_step_paged, 2),
    })

    # --- weight-only int8: decode streams half the parameter bytes ------
    # (x @ q)*s epilogue form — ops/weight_quant.py; the win is pure HBM
    # bandwidth, so the speedup is the honest measure of how much of the
    # decode step the parameter stream actually is.
    from bee_code_interpreter_tpu.ops.weight_quant import quantize_weights

    qparams = quantize_weights(params)
    results_q = {}
    for name in ("bf16", "int8"):
        cfg = dataclasses.replace(config, kv_cache_dtype=name)
        cache0 = init_decode_cache(cfg, B, ctx, k_pre, v_pre)

        def decode_q_n(n_steps, cfg=cfg):
            return decode_chain(
                lambda tok, pos, cache: decode_step(
                    qparams, tok, pos, cache, cfg
                ),
                n_steps,
            )

        t_qn = best_of(decode_q_n(N), first, cache0)
        t_q1 = best_of(decode_q_n(1), first, cache0)
        results_q[name] = chain_diff(t_qn, t_q1, N)
    emit("w8a16_decode", {
        "per_step_ms": round(results_q["bf16"] * 1e3, 3),
        "tokens_per_sec": round(B / results_q["bf16"], 1),
        "speedup_vs_fp_weights": round(
            per_step["bf16"] / results_q["bf16"], 2
        ),
        "with_int8_kv_per_step_ms": round(results_q["int8"] * 1e3, 3),
        "with_int8_kv_tokens_per_sec": round(B / results_q["int8"], 1),
        "with_int8_kv_speedup_vs_fp_bf16": round(
            per_step["bf16"] / results_q["int8"], 2
        ),
    })

    # --- multi-LoRA serving: heterogeneous adapters riding the same paged
    # program (models/serving.py). The delta is unmerged per row, so the
    # overhead prices two rank-r einsums per target per layer — the
    # S-LoRA-style claim that N adapters share one base-weight HBM stream
    # is only real if this tax is small.
    from bee_code_interpreter_tpu.models.lora import (
        init_lora,
        stack_lora_bank,
    )

    n_adapters, rank = 8, 16
    adapters = [
        {
            t: {
                "A": ab["A"],
                "B": jax.random.normal(
                    jax.random.PRNGKey(200 + i), ab["B"].shape, jnp.float32
                ) * 0.02,
            }
            for t, ab in init_lora(
                config, jax.random.PRNGKey(100 + i), rank=rank
            ).items()
        }
        for i in range(n_adapters)
    ]
    bank = stack_lora_bank(adapters)
    # all-adapter mix: every row under a DIFFERENT adapter (1..8; per-step
    # cost is index-independent, but the labeled claim is 8 adapters/batch
    # so all 8 must actually be in the batch)
    ad_idx = 1 + jnp.arange(B, dtype=jnp.int32) % n_adapters

    def decode_lora_n(n_steps):
        return decode_chain(
            lambda tok, pos, cache: decode_step_paged(
                params, tok, jnp.full((B,), pos), cache, bt, config,
                lora_bank=bank, adapter_idx=ad_idx,
            ),
            n_steps,
        )

    t_ln = best_of(decode_lora_n(N), first, paged0)
    t_l1 = best_of(decode_lora_n(1), first, paged0)
    per_step_lora = chain_diff(t_ln, t_l1, N)
    emit("multilora_decode", {
        "n_adapters": n_adapters, "rank": rank,
        "targets": sorted(bank),
        "per_step_ms": round(per_step_lora * 1e3, 3),
        "tokens_per_sec": round(B / per_step_lora, 1),
        "overhead_vs_paged": round(
            per_step_lora / per_step_paged - 1.0, 3
        ),
    })

    # --- speculative decoding: tokens/sec with a small draft ---------------
    from bee_code_interpreter_tpu.models.speculative import speculative_generate

    draft_config = dataclasses.replace(
        config, n_layers=2, d_ff=2048, kv_cache_dtype="bf16"
    )
    draft_params = init_params(draft_config, jax.random.PRNGKey(9))
    spec_cfg = dataclasses.replace(config, kv_cache_dtype="bf16")
    n_spec, n_spec_small = 48, 8

    def run_spec_n(n):
        @jax.jit
        def f(prompt):
            return speculative_generate(
                params, spec_cfg, draft_params, draft_config, prompt,
                max_new_tokens=n, gamma=4,
            ).astype(jnp.float32).sum()

        return f

    # chain-diff between two lengths cancels the prefills + dispatch that
    # run_spec re-executes per call — the plain baseline below is the
    # prefill-free marginal per_step, so the comparison must be marginal too
    t_big = best_of(run_spec_n(n_spec), prompt)
    t_small = best_of(run_spec_n(n_spec_small), prompt)
    per_token_spec = chain_diff(t_big, t_small, n_spec - n_spec_small + 1)
    spec_toks_sec = B / per_token_spec
    emit("speculative_decode", {
        "draft": {"n_layers": draft_config.n_layers, "d_ff": draft_config.d_ff},
        "gamma": 4,
        "tokens_per_sec": round(spec_toks_sec, 1),
        "plain_tokens_per_sec": round(B / per_step["bf16"], 1),
        "speedup_vs_plain": round(
            spec_toks_sec / (B / per_step["bf16"]), 2
        ),
        "note": "random weights: draft-acceptance is adversarially low; a "
                "distilled draft on a trained target accepts far more",
    })

    # --- attention-only: grouped einsum vs repeat broadcast ---------------
    kvh, nh, dh, S = 8, 32, 128, 8192
    rep = nh // kvh
    kc = jax.random.normal(jax.random.PRNGKey(2), (B, kvh, S, dh), jnp.bfloat16)
    vc = jax.random.normal(jax.random.PRNGKey(3), (B, kvh, S, dh), jnp.bfloat16)
    q0 = jax.random.normal(jax.random.PRNGKey(4), (B, nh, dh), jnp.bfloat16)

    def grouped(q, k, v):
        qg = q.reshape(B, kvh, rep, dh).astype(jnp.float32)
        s = jnp.einsum("bgrd,bgsd->bgrs", qg, k.astype(jnp.float32)) / math.sqrt(dh)
        w = jax.nn.softmax(s, axis=-1).astype(k.dtype)
        return jnp.einsum("bgrs,bgsd->bgrd", w, v).reshape(B, nh, dh)

    def repeated(q, k, v):
        kf = jnp.repeat(k, rep, axis=1)
        vf = jnp.repeat(v, rep, axis=1)
        s = jnp.einsum(
            "bhd,bhsd->bhs", q.astype(jnp.float32), kf.astype(jnp.float32)
        ) / math.sqrt(dh)
        w = jax.nn.softmax(s, axis=-1).astype(k.dtype)
        return jnp.einsum("bhs,bhsd->bhd", w, vf)

    def chain(attn, n):
        @jax.jit
        def f(q, k, v):
            def body(c, _):
                return attn(c, k, v).astype(q.dtype), None

            c, _ = lax.scan(body, q, None, length=n)
            return c.astype(jnp.float32).sum()

        return f

    M = 32
    results = {}
    for name, fn in (("grouped", grouped), ("repeat", repeated)):
        t_m = best_of(chain(fn, M), q0, kc, vc)
        t_1 = best_of(chain(fn, 1), q0, kc, vc)
        results[name] = chain_diff(t_m, t_1, M)
    cache_bytes = 2 * kvh * S * dh * B * 2  # k+v, bf16
    emit("decode_attention", {
        "shape": {"batch": B, "heads": f"{nh}/{kvh}", "cache_len": S, "head_dim": dh},
        "grouped_us": round(results["grouped"] * 1e6, 1),
        "repeat_us": round(results["repeat"] * 1e6, 1),
        "speedup": round(results["repeat"] / results["grouped"], 2),
        "grouped_cache_gbps": round(cache_bytes / results["grouped"] / 1e9, 1),
    })


def kernel_alone(
    emit, layers=16, n_pages=2560, kvh=8, ps=16, dh=128, B=32, P=288, nh=32,
    N=64,
) -> None:
    """``paged_decode_attention`` alone, at ``mistral7b_chat``'s shapes: the
    stacked pool leaf of 16 layers x 2,560 pages (2.68 GB, K and V), 32
    rows of 128-960 live tokens under a table of 288 pages. One layer's
    call, chained with the pool as the loop's carry (donated), three ways:
    the form the decode program runs (the leaf at a layer index, the new
    token written in place), the same without the write, and what the
    program did before (the layer's slice cut out of the carry,
    ``paged_append``'s scatter, the kernel on the slice, the slice put
    back). Not a measurement of any cell: nothing else of the step runs."""
    import numpy as np

    from bee_code_interpreter_tpu.ops.paged_attention import (
        paged_decode_attention,
    )
    from bee_code_interpreter_tpu.ops.paged_kv_cache import paged_append
    from bee_code_interpreter_tpu.utils.benchclock import chain_diff

    dtype = jnp.bfloat16
    rng = np.random.default_rng(0)
    lengths = jnp.asarray(rng.integers(P * ps // 36, P * ps // 5 + 1, B), jnp.int32)
    table = np.zeros((B, P), np.int32)
    pages = iter(rng.permutation(n_pages - 1) + 1)
    for b in range(B):
        for j in range(-(-int(lengths[b]) // ps)):
            table[b, j] = next(pages)
    table = jnp.asarray(table)
    at = lengths - 1
    page_idx = jnp.take_along_axis(table, (at // ps)[:, None], axis=1)
    slot_idx = (at % ps)[:, None]
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    q0 = jax.random.normal(keys[0], (B, nh, dh), dtype)
    new = jax.random.normal(keys[1], (2, B, kvh, dh), dtype)

    def in_place(q, k, v, layer):
        return paged_decode_attention(
            q, k, v, table, lengths, layer=layer, k_new=new[0], v_new=new[1]
        )

    def read_only(q, k, v, layer):
        return paged_decode_attention(q, k, v, table, lengths, layer=layer), k, v

    def slice_cut_and_put_back(q, k, v, layer):
        c_layer = paged_append(
            {"k": lax.dynamic_index_in_dim(k, layer, 0, keepdims=False),
             "v": lax.dynamic_index_in_dim(v, layer, 0, keepdims=False)},
            new[0][:, None], new[1][:, None], page_idx, slot_idx,
        )
        out = paged_decode_attention(q, c_layer["k"], c_layer["v"], table, lengths)
        return (
            out,
            lax.dynamic_update_index_in_dim(k, c_layer["k"], layer, 0),
            lax.dynamic_update_index_in_dim(v, c_layer["v"], layer, 0),
        )

    def chain(call, n):
        def f(q, k, v):
            def body(i, carry):
                out, k, v = call(*carry, i % layers)
                return out.astype(dtype), k, v

            q, k, v = lax.fori_loop(0, n, body, (q, k, v))
            return q.astype(jnp.float32).sum(), k, v

        return jax.jit(f, donate_argnums=(1, 2))

    def best_of(f, reps=3):
        pool = [
            jax.random.normal(keys[2], (layers, n_pages, kvh, ps, dh), dtype)
            for _ in range(2)
        ]
        best = float("inf")
        for i in range(reps + 1):  # the first compiles and warms
            t0 = time.perf_counter()
            total, *pool = f(q0, *pool)
            float(total)
            if i:
                best = min(best, time.perf_counter() - t0)
        return best

    per_call = {
        name: chain_diff(best_of(chain(call, N)), best_of(chain(call, 1)), N)
        for name, call in (
            ("in_place", in_place), ("read_only", read_only),
            ("slice_cut_and_put_back", slice_cut_and_put_back),
        )
    }
    live_bytes = 2 * int(lengths.sum()) * kvh * dh * 2  # K and V, bf16
    emit("paged_decode_kernel_alone", {
        "shape": {"layers": layers, "n_pages": n_pages, "rows": B,
                  "heads": f"{nh}/{kvh}", "head_dim": dh, "page_size": ps,
                  "pages_per_seq": P, "live_tokens": int(lengths.sum())},
        **{f"{name}_us": round(t * 1e6, 1) for name, t in per_call.items()},
        "write_us": round((per_call["in_place"] - per_call["read_only"]) * 1e6, 1),
        "in_place_live_gbps": round(live_bytes / per_call["in_place"] / 1e9, 1),
        "note": "one layer's call alone, chained: not a measurement of a cell",
    })


def main() -> None:
    """``python scripts/bench-decode.py [kernel]``: everything, or the paged
    decode kernel alone."""
    from bee_code_interpreter_tpu.parallel.mesh import require_tpu

    emit = require_tpu("scripts/bench-decode.py")
    if sys.argv[1:] == ["kernel"]:
        kernel_alone(emit)
        return
    run_measurements(emit)
    kernel_alone(emit)


if __name__ == "__main__":
    main()
