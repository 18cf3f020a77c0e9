#!/usr/bin/env python
"""Run + time the Pallas flash-attention kernels on the real TPU chip.

CI exercises the kernels in Pallas interpreter mode only; this script is the
hardware proof: Mosaic-lowers the forward AND backward kernels on the
attached chip, checks numerics against the jax reference, and reports
achieved TFLOPS against two baselines — the XLA-compiled reference
attention (naive einsum+softmax) and ``jax.nn.dot_product_attention``
(the library's own fused entry point) — plus the grouped-query (GQA)
cases where the kernels read the compact KV heads directly.

Timing method: N data-dependent kernel applications chained inside ONE jit
(the output feeds the next call's query), a single scalar readback at the
end; the N-chain minus 1-chain difference (utils/benchclock.chain_diff)
cancels the fixed per-call cost, so ~ms kernels are not read as slow.

One process, the one that holds the chip: no out-of-process probe.
Usage:  python scripts/bench-flash-attention.py  [--sweep]
Prints one JSON line per case, each naming the device; exits 2 if no TPU.
"""

from __future__ import annotations

import json
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

from bee_code_interpreter_tpu.ops.flash_attention import flash_attention
from bee_code_interpreter_tpu.parallel.ring_attention import reference_attention


def attention_flops(B: int, H: int, L: int, D: int, causal: bool) -> float:
    # QK^T and PV: 2 matmuls of 2*B*H*L*L*D flops each; causal halves
    flops = 2 * 2 * B * H * L * L * D
    return flops / 2 if causal else flops


def _best_of(f, q, k, v, reps: int = 3) -> float:
    float(f(q, k, v))  # compile + warm
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        float(f(q, k, v))
        best = min(best, time.perf_counter() - t0)
    return best


def _timed_chain(make_f, q, k, v, n_chain: int) -> float:
    """Per-call seconds from the difference of an n_chain-long and a 1-long
    chain: (t_N - t_1) / (N - 1) cancels the per-measurement fixed cost
    (dispatch plus the readback). Difference + sanity guard live in
    utils/benchclock.chain_diff (shared with bench-decode and bench.py's
    flash payload)."""
    from bee_code_interpreter_tpu.utils.benchclock import chain_diff

    t_n = _best_of(make_f(n_chain), q, k, v)
    t_1 = _best_of(make_f(1), q, k, v)
    return chain_diff(t_n, t_1, n_chain)


def timed_fwd(attn, q, k, v, n_chain: int = 8) -> float:
    """Per-call seconds for ``attn(q, k, v) -> [B, H, L, D]``: the output is
    the next call's query, so the chain cannot be reordered or elided."""

    def make_f(length):
        @jax.jit
        def f(q, k, v):
            def body(c, _):
                return attn(c, k, v), None

            c, _ = lax.scan(body, q, None, length=length)
            return c.astype(jnp.float32).sum()

        return f

    return _timed_chain(make_f, q, k, v, n_chain)


def timed_fwd_bwd(loss, q, k, v, n_chain: int = 8) -> float:
    """Per-call seconds for one value_and_grad of ``loss`` wrt (q, k, v):
    chained as gradient-descent steps on all three operands, so dq, dk AND
    dv are all live (grad wrt q alone would let XLA prune the dk/dv work —
    a skewed comparison against the opaque custom_vjp kernel, which always
    computes all three)."""

    def make_f(length):
        @jax.jit
        def f(q, k, v):
            def body(carry, _):
                q, k, v = carry
                dq, dk, dv = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
                s = 1e-3
                return (
                    (q - s * dq).astype(q.dtype),
                    (k - s * dk.astype(jnp.float32)).astype(k.dtype),
                    (v - s * dv.astype(jnp.float32)).astype(v.dtype),
                ), None

            (q, _, _), _ = lax.scan(body, (q, k, v), None, length=length)
            return q.astype(jnp.float32).sum()

        return f

    return _timed_chain(make_f, q, k, v, n_chain)


def run_measurements(emit, sweep: bool = False) -> None:
    """Every hardware measurement, in this one process."""
    causal = True

    # --- correctness on hardware (fwd + bwd Mosaic lowering) -------------
    small = tuple(
        jax.random.normal(jax.random.PRNGKey(i), (1, 2, 512, 64), dtype=jnp.bfloat16)
        for i in range(3)
    )
    out_hw = flash_attention(*small, causal, None, 256, 256, False)
    out_ref = reference_attention(*small, causal=True)
    fwd_err = float(jnp.max(jnp.abs(out_hw.astype(jnp.float32) - out_ref.astype(jnp.float32))))

    def loss_flash(q, k, v):
        return (flash_attention(q, k, v, causal, None, 512, 512, False) ** 2).sum()

    def loss_ref(q, k, v):
        return (reference_attention(q, k, v, causal=causal) ** 2).sum()

    g_hw = jax.grad(loss_flash, argnums=(0, 1, 2))(*small)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(*small)
    bwd_err = max(
        float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32))))
        for a, b in zip(g_hw, g_ref)
    )
    # bf16 tolerance: values are O(sqrt(D)) after softmax-weighted sums
    assert fwd_err < 0.1, f"forward kernel diverges on hardware: {fwd_err}"
    assert bwd_err < 1.0, f"backward kernel diverges on hardware: {bwd_err}"

    # GQA on silicon: compact KV vs the broadcast reference
    qg = jax.random.normal(jax.random.PRNGKey(7), (1, 8, 512, 64), jnp.bfloat16)
    kg, vg = (
        jax.random.normal(jax.random.PRNGKey(8 + i), (1, 2, 512, 64), jnp.bfloat16)
        for i in range(2)
    )
    out_gqa = flash_attention(qg, kg, vg, causal, None, 256, 256, False)
    ref_gqa = reference_attention(
        qg, jnp.repeat(kg, 4, 1), jnp.repeat(vg, 4, 1), causal=True
    )
    gqa_err = float(
        jnp.max(jnp.abs(out_gqa.astype(jnp.float32) - ref_gqa.astype(jnp.float32)))
    )
    assert gqa_err < 0.1, f"GQA forward diverges on hardware: {gqa_err}"
    emit("hardware_numerics", {"fwd_max_err": round(fwd_err, 4),
                               "bwd_max_err": round(bwd_err, 4),
                               "gqa_fwd_max_err": round(gqa_err, 4)})

    # --- forward throughput (MHA) ----------------------------------------
    B, H, L, D = 4, 16, 4096, 128
    q, k, v = (
        jax.random.normal(jax.random.PRNGKey(i), (B, H, L, D), dtype=jnp.bfloat16)
        for i in range(3)
    )
    flops = attention_flops(B, H, L, D, causal)
    if sweep:
        for bq, bk in [(256, 256), (512, 512), (512, 1024), (1024, 512),
                       (1024, 1024), (1024, 2048)]:
            t = timed_fwd(
                lambda x, k, v, bq=bq, bk=bk: flash_attention(
                    x, k, v, causal, None, bq, bk, False
                ),
                q, k, v,
            )
            print(json.dumps({
                "case": "forward_sweep", "block_q": bq, "block_k": bk,
                "tflops": round(flops / t / 1e12, 1),
            }))
    t_flash = timed_fwd(
        lambda x, k, v: flash_attention(x, k, v, causal, None, 1024, 1024, False),
        q, k, v,
    )
    t_xla = timed_fwd(
        lambda x, k, v: reference_attention(x, k, v, causal=causal).astype(x.dtype),
        q, k, v,
    )
    # Honest fused baseline (ADVICE r3 #3): jax.nn.dot_product_attention is
    # the library's own attention entry point — whatever fused lowering XLA
    # ships is what a user gets without our kernel. It wants BTNH layout, so
    # it is timed natively in that layout (no transpose tax in its chain);
    # the flop count is identical.
    qT, kT, vT = (x.transpose(0, 2, 1, 3) for x in (q, k, v))
    t_dpa = timed_fwd(
        lambda x, k, v: jax.nn.dot_product_attention(x, k, v, is_causal=True),
        qT, kT, vT,
    )
    emit("forward", {
        "shape": [B, H, L, D],
        "flash_tflops": round(flops / t_flash / 1e12, 1),
        "xla_ref_tflops": round(flops / t_xla / 1e12, 1),
        "jax_dpa_tflops": round(flops / t_dpa / 1e12, 1),
        "speedup_vs_xla_ref": round(t_xla / t_flash, 2),
        "speedup_vs_jax_dpa": round(t_dpa / t_flash, 2),
    })

    # --- forward throughput (GQA, llama3-8b head geometry) ----------------
    KVH = 8
    Bg, Hg = 4, 32
    qG = jax.random.normal(jax.random.PRNGKey(10), (Bg, Hg, L, D), jnp.bfloat16)
    kG, vG = (
        jax.random.normal(jax.random.PRNGKey(11 + i), (Bg, KVH, L, D), jnp.bfloat16)
        for i in range(2)
    )
    flops_g = attention_flops(Bg, Hg, L, D, causal)
    t_gqa = timed_fwd(lambda x, k, v: flash_attention(x, k, v, causal), qG, kG, vG)
    t_rep = timed_fwd(
        lambda x, k, v: flash_attention(
            x, jnp.repeat(k, Hg // KVH, 1), jnp.repeat(v, Hg // KVH, 1), causal
        ),
        qG, kG, vG,
    )
    emit("forward_gqa", {
        "shape": [Bg, Hg, L, D], "kv_heads": KVH,
        "gqa_native_tflops": round(flops_g / t_gqa / 1e12, 1),
        "repeat_kv_tflops": round(flops_g / t_rep / 1e12, 1),
        "speedup_vs_repeat": round(t_rep / t_gqa, 2),
    })

    # --- train-step (fwd+bwd) throughput (~3x fwd flops) ------------------
    t_gflash = timed_fwd_bwd(loss_flash, q, k, v)
    t_gref = timed_fwd_bwd(loss_ref, q, k, v)
    emit("forward+backward", {
        "shape": [B, H, L, D],
        "flash_tflops": round(3 * flops / t_gflash / 1e12, 1),
        "xla_ref_tflops": round(3 * flops / t_gref / 1e12, 1),
        "speedup_vs_xla_ref": round(t_gref / t_gflash, 2),
    })

    def loss_gqa(q, k, v):
        return (flash_attention(q, k, v, causal).astype(jnp.float32) ** 2).sum()

    t_ggqa = timed_fwd_bwd(loss_gqa, qG, kG, vG, n_chain=4)
    emit("forward+backward_gqa", {
        "shape": [Bg, Hg, L, D], "kv_heads": KVH,
        "gqa_native_tflops": round(3 * flops_g / t_ggqa / 1e12, 1),
    })


def main() -> None:
    from bee_code_interpreter_tpu.parallel.mesh import require_tpu

    run_measurements(
        require_tpu("scripts/bench-flash-attention.py"),
        sweep="--sweep" in sys.argv,
    )


if __name__ == "__main__":
    main()
