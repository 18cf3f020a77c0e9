"""GPipe-style pipeline parallelism over a ``pp`` mesh axis (shard_map).

TPU-first design: the stacked ``[n_layers, ...]`` parameter pytree (the same
layout ``models/transformer.py`` scans over) is sharded on its leading axis
over ``pp``, so each rank holds a contiguous block of layers. Microbatches
flow stage-to-stage with ``lax.ppermute`` over ICI in a static
``M + S - 1``-tick schedule (GPipe fill/drain bubbles) — one compiled
program, no data-dependent control flow.

Everything is differentiable (ppermute/psum transpose cleanly), so the same
primitive serves training: grads flow back through the pipeline in the
transposed schedule XLA derives automatically.

Stages may carry a scalar auxiliary loss (``with_aux`` — MoE load
balancing): per-tick contributions are masked to the ticks that process a
real microbatch (fill/drain bubbles run the layer body on garbage and must
not pollute the sum), summed across the pp ring, averaged over microbatches
and any data-parallel batch axes.

The reference has no parallelism at all (SURVEY.md §2); this module completes
the dp/fsdp/sp/tp/ep/pp axis set the framework's scheduler can provision.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P


def spmd_pipeline(
    stage_fn: Callable,
    layer_params,
    x: jax.Array,
    *,
    mesh: Mesh,
    axis: str = "pp",
    n_microbatches: int,
    batch_axes: tuple[str, ...] = (),
    with_aux: bool = False,
):
    """Apply ``n_layers`` stacked layers to ``x`` pipelined over ``axis``.

    ``stage_fn(h, layer) -> h`` applies ONE layer (the per-step body the
    sequential implementation would ``lax.scan``); with ``with_aux`` it
    returns ``(h, aux)`` where ``aux`` is a scalar f32 per-layer loss term.
    ``layer_params`` is a pytree whose leaves have a leading ``[n_layers]``
    axis with ``n_layers % mesh.shape[axis] == 0``. ``x`` is ``[B, ...]``
    with ``B % n_microbatches == 0``; ``batch_axes`` optionally shards B over
    data-parallel mesh axes (composing dp x pp).

    Returns ``[B, ...]`` — identical to the sequential scan, modulo dtype
    rounding — or ``(out, aux)`` with ``with_aux``, where ``aux`` is the
    layer-summed loss term averaged over microbatches and ``batch_axes``
    (matching a sequential per-microbatch forward).
    """
    S = mesh.shape[axis]
    n_layers = jax.tree.leaves(layer_params)[0].shape[0]
    if n_layers % S != 0:
        raise ValueError(f"{n_layers} layers not divisible by {axis}={S}")
    B = x.shape[0]
    if B % n_microbatches != 0:
        raise ValueError(f"batch {B} not divisible by {n_microbatches} microbatches")
    M = n_microbatches
    mb = B // M
    xm = x.reshape(M, mb, *x.shape[1:])

    def scalar_zero(ref):
        # Scalar f32 zero for the aux accumulators: data-derived, because
        # under vma typing a plain constant is unvarying and scan/fori
        # reject the carry.
        return (ref.reshape(-1)[0] * 0.0).astype(jnp.float32)

    def per_rank(local_params, xm):
        # local_params: [n_layers/S, ...] (this rank's layer block)
        # xm: [M, mb_local, ...] (microbatches; batch possibly dp-sharded)
        idx = lax.axis_index(axis)

        def apply_stage(h):
            def body(carry, layer):
                h, aux = carry
                if with_aux:
                    h, a = stage_fn(h, layer)
                    aux = aux + a.astype(jnp.float32)
                else:
                    h = stage_fn(h, layer)
                return (h, aux), None

            (h, aux), _ = lax.scan(body, (h, scalar_zero(h)), local_params)
            return h, aux

        def tick(t, carry):
            state, outputs, aux_acc = carry
            # stage 0 ingests microbatch t; later stages consume the
            # activation ppermute'd from their predecessor last tick
            feed = lax.dynamic_index_in_dim(
                xm, jnp.clip(t, 0, M - 1), 0, keepdims=False
            )
            h = jnp.where(idx == 0, feed, state)
            y, aux_t = apply_stage(h)
            # this rank processes microbatch t - idx at tick t; outside
            # [0, M) it's a fill/drain bubble chewing on garbage — its aux
            # contribution must be masked out
            m_idx = t - idx
            valid = jnp.logical_and(m_idx >= 0, m_idx < M)
            aux_acc = aux_acc + jnp.where(valid, aux_t, 0.0)
            # the last stage completes microbatch t-(S-1) at tick t
            out_idx = jnp.clip(t - (S - 1), 0, M - 1)
            updated = lax.dynamic_update_index_in_dim(outputs, y, out_idx, 0)
            take = jnp.logical_and(idx == S - 1, t >= S - 1)
            outputs = jnp.where(take, updated, outputs)
            state = lax.ppermute(
                y, axis, [(i, (i + 1) % S) for i in range(S)]
            )
            return state, outputs, aux_acc

        # the loop body produces pp-varying values (axis_index branches), so
        # the initial carry must be marked varying too or scan rejects it
        state0 = lax.pcast(jnp.zeros_like(xm[0]), (axis,), to="varying")
        outputs0 = lax.pcast(jnp.zeros_like(xm), (axis,), to="varying")
        aux0 = lax.pcast(scalar_zero(xm), (axis,), to="varying")
        _, outputs, aux_acc = lax.fori_loop(
            0, M + S - 1, tick, (state0, outputs0, aux0)
        )
        # sum each rank's layer contributions across the ring, then average
        # over microbatches and data-parallel shards → replicated scalar
        aux = lax.psum(aux_acc, axis) / M
        if batch_axes:
            aux = lax.pmean(aux, batch_axes)
        # replicate the last stage's collected outputs across the pp ring
        out = lax.psum(
            jnp.where(idx == S - 1, outputs, jnp.zeros_like(outputs)), axis
        )
        return out, aux

    batch = batch_axes or None
    out, aux = jax.shard_map(
        per_rank,
        mesh=mesh,
        in_specs=(P(axis), P(None, batch)),
        out_specs=(P(None, batch), P()),
    )(layer_params, xm)
    out = out.reshape(B, *x.shape[1:])
    if with_aux:
        return out, aux
    return out
