"""Ulysses-style sequence parallelism: all-to-all head/sequence exchange.

The second canonical long-context strategy next to ring attention
(parallel/ring_attention.py). Where the ring keeps queries resident and
rotates K/V around the ``sp`` axis in sp-1 ppermute hops, Ulysses
(DeepSpeed-Ulysses / all-to-all context parallelism) re-shards once: an
``all_to_all`` turns the sequence-sharded [B, H, L/sp, D] activations into
head-sharded [B, H/sp, L, D] — each device then holds the FULL sequence for
a slice of heads, runs an ordinary (here: Pallas flash, GQA-native) local
attention, and a second all-to-all restores sequence sharding.

Trade-off, TPU terms: the ring moves (sp-1)/sp of K+V over neighbour ICI
links and needs the online-softmax accumulation; Ulysses moves q+k+v+out
once each through all-to-alls (cheap on a torus, but all-pairs) and runs the
unmodified single-device kernel — better when heads are plentiful and the
per-device sequence is short, and it composes with the flash kernel's causal
block-skipping, which the ring's per-hop blocks cannot exploit across
devices. sp must divide the head count (asserted); grouped-query K/V stays
compact when sp also divides kv_heads, otherwise it is broadcast up first.

``models/transformer.py`` selects between the two via
``TransformerConfig.sp_attention`` ("ring" | "ulysses").

The reference has no parallelism of any kind (SURVEY.md §2 "Parallelism
strategies"); this module is part of the framework's first-class
long-context story (SURVEY.md §5).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P



def ulysses_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    axis_name: str = "sp",
    causal: bool = True,
    local_attention=None,
    window: int | None = None,
    use_flash: bool | None = None,
) -> jax.Array:
    """All-to-all sequence-parallel attention. Must run inside shard_map.

    Per-device shapes: q [B, H, L/sp, D]; k, v [B, KVH, L/sp, D] with KVH ≤ H
    (grouped-query). Returns [B, H, L/sp, D]. ``local_attention(q, k, v)``
    runs on the gathered [B, heads/sp, L, D] blocks and defaults to the
    GQA-native Pallas flash kernel on TPU (reference attention elsewhere).

    ``window`` (sliding-window attention, requires ``causal``) falls out
    structurally: after the all-to-all each device holds the FULL sequence
    for its head slice, so global positions equal local positions and the
    ordinary local window mask is exact — no per-hop geometry like the ring.
    """
    if local_attention is None:
        # the shared ops-level dispatch: Pallas flash on TPU in either
        # causal mode (the gathered full sequence is exactly where O(L²)
        # reference memory would blow up), reference einsum off-TPU.
        # ``use_flash`` FORCES a path (mirroring ring_attention's knob —
        # True must actually run the kernel, not just flip check_vma):
        if use_flash is None:
            from bee_code_interpreter_tpu.ops.flash_attention import (
                local_attention as _dispatch,
            )

            local_attention = functools.partial(
                _dispatch, causal=causal, window=window
            )
        elif use_flash:
            from bee_code_interpreter_tpu.ops.flash_attention import (
                flash_attention,
            )

            local_attention = lambda q, k, v: flash_attention(  # noqa: E731
                q, k, v, causal, window=window
            )
        else:
            from bee_code_interpreter_tpu.parallel.ring_attention import (
                reference_attention,
            )

            local_attention = functools.partial(
                reference_attention, causal=causal, window=window
            )
    elif window is not None or use_flash is not None:
        raise ValueError(
            "window/use_flash with a custom local_attention: fold them into "
            "the callable instead (the default dispatch handles them)"
        )
    sp = lax.axis_size(axis_name)
    B, H, Lloc, D = q.shape
    KVH = k.shape[1]
    if H % sp != 0:
        raise ValueError(f"sp={sp} must divide n_heads {H} for ulysses")
    if KVH % sp != 0:
        # KV heads don't scatter over sp: broadcast up — only to
        # lcm(KVH, sp), the minimal multiple that shards evenly (both divide
        # H, so the lcm does too and group-major q→kv pairing is preserved —
        # same argument as the tp-lcm broadcast in models/transformer.py).
        # The ring path keeps KV fully compact; prefer ring when KVH < sp.
        rep = math.lcm(KVH, sp) // KVH
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)

    # head-scatter / sequence-gather: [B, h, L/sp, D] -> [B, h/sp, L, D].
    # Sequence blocks concatenate in sp-rank order — the same contiguous
    # layout the sequence sharding put them in.
    a2a = functools.partial(
        lax.all_to_all, axis_name=axis_name, split_axis=1, concat_axis=2,
        tiled=True,
    )
    out = local_attention(a2a(q), a2a(k), a2a(v))  # [B, H/sp, L, D]
    # inverse exchange: sequence-scatter / head-gather
    return lax.all_to_all(
        out, axis_name=axis_name, split_axis=2, concat_axis=1, tiled=True
    )


def ulysses_attention_sharded(
    mesh: Mesh,
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    axis_name: str = "sp",
    causal: bool = True,
    use_flash: bool | None = None,
    window: int | None = None,
) -> jax.Array:
    """Standalone entry: shards [B, H, L, D] inputs over ``axis_name`` on L
    and runs the exchange. For use outside an existing shard_map context.

    ``use_flash`` mirrors ring_attention_sharded: when the local attention
    will dispatch to the Pallas flash kernel (the TPU default), the vma
    checker must be disabled — pallas_call cannot lower under it (ADVICE r3:
    without this the standalone entry failed on real TPU while CPU tests
    passed, because uses_flash() is false off-TPU).
    """
    from bee_code_interpreter_tpu.ops.flash_attention import uses_flash

    flash = use_flash if use_flash is not None else uses_flash()
    spec = P(None, None, axis_name, None)
    fn = jax.shard_map(
        functools.partial(
            ulysses_attention, axis_name=axis_name, causal=causal,
            window=window, use_flash=use_flash,
        ),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=not flash,
    )
    return fn(q, k, v)
