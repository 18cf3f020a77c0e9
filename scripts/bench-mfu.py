#!/usr/bin/env python
"""Flagship-model MFU and decode tokens/sec THROUGH THE SERVICE PATH.

bench.py's headline is a raw matmul chain; this measures the transformer
library itself (VERDICT r3 next-round #3): a ~0.8B llama-shaped config
(fits one v5e chip's 16 GB HBM with f32 masters + AdamW moments) driven
via the same sandbox-executor path as /v1/execute —

1. ``mfu_train``: one full train step (forward + backward + AdamW update),
   timed as an N-step lax.scan chain inside one jit (params carry the data
   dependency; a single scalar readback — the chained clock every bench in
   this repo uses). MFU = achieved flops / the chip's bf16 peak (one table
   keyed by the ``device_kind`` the payload reports; an unknown kind is an
   error, not a default), with
   flops/step = (6·P + 12·n_layers·L·d_model)·B·L — the standard
   PaLM-appendix accounting (6N for the dense params fwd+bwd, the second
   term for attention score/value matmuls, causal already folded).
2. ``service_decode``: KV-cached greedy decode tokens/sec on the same
   config through the same path (bench-decode.py measures decode
   in-process; this is the service-path row).

One jax process — the sandbox child; this parent never touches the chip and
runs no probe. The payload fails without a TPU and every result names the
device it ran on.

The reference publishes no model-perf numbers at all (SURVEY §6) — this
script exists because the rebuild's own bar is a *measured* table.
"""

from __future__ import annotations

import importlib.util
import json
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

# Published single-chip bf16 peaks, keyed by the ``device_kind`` jax reports
# (Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16). A device that
# is not in the table is an error, not a default.
PEAK_BF16_FLOPS = {"TPU v5 lite": 197e12}


def peak_bf16_flops(device_kind: str) -> float:
    try:
        return PEAK_BF16_FLOPS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published bf16 peak for device_kind {device_kind!r}; add "
            "it (with its source) to PEAK_BF16_FLOPS"
        ) from None

# ~0.8B params: embed+head 2·(32000·2048)=131M·2, 12 layers of
# (attn 10.5M + swiglu 34.6M); f32 masters + AdamW m,v ≈ 9.7 GB.
CONFIG = dict(vocab_size=32000, d_model=2048, n_layers=12, n_heads=16,
              n_kv_heads=4, d_ff=5632, max_seq_len=2048)
B, L = 4, 1024
N_TRAIN = 8  # train-step chain length (each step ~0.1 s at 50% MFU)
B_DEC, L_PROMPT, N_DEC = 8, 128, 64

def build_payload(CONFIG=CONFIG, B=B, L=L, N_TRAIN=N_TRAIN, B_DEC=B_DEC,
                  L_PROMPT=L_PROMPT, N_DEC=N_DEC) -> str:
    """The in-sandbox source, parameterized so tests can run a tiny-config
    variant through the identical mechanics on CPU."""
    return f"""
import json, time
import jax, jax.numpy as jnp, optax
from jax import lax
from bee_code_interpreter_tpu.models.transformer import (
    TransformerConfig, Transformer, forward, decode_step,
    init_decode_cache, init_params, loss_fn,
)
from bee_code_interpreter_tpu.utils.benchclock import chain_diff

print("RESULT_DEVICE", json.dumps({{
    "platform": jax.devices()[0].platform,
    "kind": jax.devices()[0].device_kind,
    "count": len(jax.devices()),
}}))
config = TransformerConfig(**{CONFIG!r})
B, L = {B}, {L}
params = init_params(config, jax.random.PRNGKey(0))
n_params = sum(x.size for x in jax.tree.leaves(params))
optimizer = optax.adamw(3e-4)
opt_state = optimizer.init(params)
seq = jax.random.randint(jax.random.PRNGKey(1), (B, L + 1), 0, config.vocab_size)
batch = {{"tokens": seq[:, :-1], "targets": seq[:, 1:]}}

def train_chain(n_steps):
    @jax.jit
    def f(params, opt_state, batch):
        def step(carry, _):
            params, opt_state = carry
            grads = jax.grad(loss_fn)(params, batch, config)
            updates, opt_state = optimizer.update(grads, opt_state, params)
            return (optax.apply_updates(params, updates), opt_state), None
        (params, _), _ = lax.scan(step, (params, opt_state), None, length=n_steps)
        return params["ln_f"].astype(jnp.float32).sum()
    return f

def best_of(f, *args, reps=2):
    float(f(*args))  # compile + warm
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        float(f(*args))
        best = min(best, time.perf_counter() - t0)
    return best

t_n = best_of(train_chain({N_TRAIN}), params, opt_state, batch)
t_1 = best_of(train_chain(1), params, opt_state, batch)
per_step = chain_diff(t_n, t_1, {N_TRAIN}, "train")
# 6N counts only MATMUL params: the embedding table is a gather (no
# matmul flops), so it is excluded; the untied lm_head IS a matmul and
# stays. Counting the embed would inflate MFU ~10% at this config.
n_matmul_params = n_params - config.vocab_size * config.d_model
flops_per_step = (
    6 * n_matmul_params + 12 * config.n_layers * L * config.d_model
) * B * L
print(f"RESULT_TRAIN {{per_step * 1e3:.2f}} {{flops_per_step / per_step / 1e12:.4f}} {{n_params}}")

# --- decode tokens/sec on the same config -------------------------------
Bd, Lp = {B_DEC}, {L_PROMPT}
prompt = jax.random.randint(jax.random.PRNGKey(2), (Bd, Lp), 0, config.vocab_size)
logits, (k_pre, v_pre) = forward(params, prompt, config, None, return_kv=True)
cache0 = init_decode_cache(config, Bd, Lp + {N_DEC} + 1, k_pre, v_pre)
first = jnp.argmax(logits[:, -1:, :], axis=-1).astype(jnp.int32)

def decode_chain(n_steps):
    @jax.jit
    def f(tok, cache):
        def body(carry, pos):
            tok, cache = carry
            lg, cache = decode_step(params, tok, pos, cache, config)
            nxt = jnp.argmax(lg[:, -1:, :], axis=-1).astype(jnp.int32)
            return (nxt, cache), None
        (tok, _), _ = lax.scan(
            body, (tok, cache),
            jnp.arange(Lp, Lp + n_steps, dtype=jnp.int32),
        )
        return tok.astype(jnp.float32).sum()
    return f

t_dn = best_of(decode_chain({N_DEC}), first, cache0)
t_d1 = best_of(decode_chain(1), first, cache0)
per_tok = chain_diff(t_dn, t_d1, {N_DEC}, "decode")
print(f"RESULT_DECODE {{per_tok * 1e3:.3f}} {{Bd / per_tok:.1f}}")
"""


def _parse_results(stdout: str) -> tuple[dict, dict[str, list[float]]]:
    """(device the payload ran on, {marker: floats})."""
    device: dict | None = None
    out: dict[str, list[float]] = {}
    for line in stdout.splitlines():
        if line.startswith("RESULT_DEVICE"):
            device = json.loads(line[len("RESULT_DEVICE"):])
        for marker in ("RESULT_TRAIN", "RESULT_DECODE"):
            if line.startswith(marker):
                out[marker] = [float(tok) for tok in line.split()[1:]]
    missing = [m for m in ("RESULT_TRAIN", "RESULT_DECODE") if m not in out]
    if missing or device is None:
        raise RuntimeError(
            f"no {missing or 'RESULT_DEVICE'} in payload stdout: {stdout!r}"
        )
    return device, out


def results_rows(device: dict, results: dict[str, list[float]]) -> list[dict]:
    """The two result rows, each naming the device it ran on. Raises if the
    device is not a TPU (a host run is not an MFU) or its kind has no
    published peak."""
    if device["platform"] != "tpu":
        raise RuntimeError(
            f"payload ran on {device['platform']!r}, not a TPU: no MFU"
        )
    peak = peak_bf16_flops(device["kind"])
    per_step_ms, achieved_tflops, n_params = results["RESULT_TRAIN"][:3]
    per_tok_ms, toks_per_sec = results["RESULT_DECODE"][:2]
    via = "service execution path"
    return [
        {
            "case": "mfu_train", "device": device,
            "config": {**CONFIG, "batch": B, "seq_len": L,
                       "params": int(n_params)},
            "per_step_ms": round(per_step_ms, 1),
            "achieved_tflops": round(achieved_tflops, 1),
            "mfu": round(achieved_tflops * 1e12 / peak, 3),
            "peak_flops": peak,
            "optimizer": "adamw",
            "via": via,
        },
        {
            "case": "service_decode", "device": device,
            "config": {**CONFIG, "batch": B_DEC, "prompt_len": L_PROMPT},
            "per_step_ms": round(per_tok_ms, 3),
            "tokens_per_sec": round(toks_per_sec, 1),
            "via": via,
        },
    ]


def main() -> None:
    import asyncio

    spec = importlib.util.spec_from_file_location("bench", REPO / "bench.py")
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)
    # JAX_PLATFORMS=tpu in the sandbox: a missing or busy chip is an error,
    # not a quiet CPU run
    result = asyncio.run(
        bench._run_payload_result(
            build_payload(), {"JAX_PLATFORMS": "tpu"}, 1200.0
        )
    )
    for row in results_rows(*_parse_results(result.stdout)):
        print(json.dumps(row), flush=True)


if __name__ == "__main__":
    main()
