"""Ring attention: exact attention over sequences sharded across devices.

Long-context is first-class in this framework (SURVEY.md §5 "Long-context"):
a sequence of length L is sharded L/sp per device over the ``sp`` mesh axis,
and K/V blocks rotate around the ring via ``lax.ppermute`` (ICI
neighbour-to-neighbour — the cheapest collective on TPU) while each device
accumulates its queries' attention with a numerically-stable online softmax
(flash-attention style running max/normalizer). Peak memory per device is
O(L/sp · d); communication is sp-1 ppermute steps of the local K/V block,
fully overlappable with compute by XLA since each step's matmuls depend only
on the block already received.

Causality is handled per block pair: a device's query block q_idx attends to
rotating K/V blocks k_idx with full attention (k_idx < q_idx), triangular
masking (k_idx == q_idx), or is skipped entirely via lax.cond (k_idx > q_idx).

``ring_attention`` is the collective core, to be called *inside* shard_map
(models/transformer.py does this when the mesh has sp > 1);
``ring_attention_sharded`` wraps it for standalone use on a mesh.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P



def _block_attend(q, k, v, m, l, o, sm_scale, mask):
    """One online-softmax accumulation step against a K/V block.

    q, o: [B, G, R, Lq, D]; k, v: [B, G, Lk, D]; m, l: [B, G, R, Lq, 1]
    (all float32 accumulators) — G = KV heads, R = query heads per KV head
    (R == 1 when not grouped-query; the einsums broadcast K/V over R, so the
    compact KV block is what rotates the ring). mask: [Lq, Lk] additive
    (-inf) or None.
    """
    scores = jnp.einsum(
        "bgrqd,bgkd->bgrqk", q, k, preferred_element_type=jnp.float32
    ) * sm_scale
    if mask is not None:
        scores = scores + mask
    block_max = jnp.max(scores, axis=-1, keepdims=True)  # [B,G,R,Lq,1]
    new_m = jnp.maximum(m, block_max)
    # rescale previous accumulator to the new max
    correction = jnp.exp(m - new_m)
    p = jnp.exp(scores - new_m)  # [B,G,R,Lq,Lk]
    new_l = l * correction + jnp.sum(p, axis=-1, keepdims=True)
    pv = jnp.einsum("bgrqk,bgkd->bgrqd", p, v.astype(jnp.float32))
    new_o = o * correction + pv
    return new_m, new_l, new_o


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    axis_name: str = "sp",
    causal: bool = True,
    sm_scale: float | None = None,
    use_flash: bool | None = None,
    window: int | None = None,
) -> jax.Array:
    """Exact attention with K/V rotating around the ``axis_name`` ring.

    Shapes (per device): q: [B, H, L_local, D]; k, v: [B, KVH, L_local, D]
    with ``H % KVH == 0`` — grouped-query KV stays compact, so the ring
    rotates (and each hop's ppermute moves) KVH heads of K/V, not H. Returns
    [B, H, L_local, D] in q's dtype. Must run inside shard_map with
    ``axis_name`` bound.

    ``use_flash`` (default: on TPU) runs each hop through the Pallas flash
    kernel (ops/flash_attention.flash_attention_with_lse) and merges hops on
    their log-sum-exp — the MXU-tiled kernel replaces the jax-level einsum
    accumulation, and the same-block hop gets the kernel's causal
    block-skipping. Differentiable either way (the lse outputs carry real
    gradients; the kernel's VJP folds them into its delta shift).

    ``window`` (requires ``causal``) is sliding-window attention in GLOBAL
    positions: query at global position p sees keys in (p - window, p].
    Block structure per hop, with delta = (my_idx - k_idx) · L_local the
    query-block/key-block global offset: hops entirely below the window
    (delta ≥ window + L_local - 1) are skipped like future blocks — a
    window spanning w/L_local blocks turns the ring's O(sp) attended hops
    into O(w/L_local) while still paying sp-1 ppermutes; the own block uses
    the local causal+window mask; straddling hops mask rows to
    row - col < window - delta.
    """
    if window is not None:
        if not causal:
            raise ValueError("window requires causal=True (sliding window)")
        if window < 1:
            # the einsum path would otherwise mask every row of the own
            # block and emit silent NaNs where the flash kernel raises
            raise ValueError(f"window must be >= 1, got {window}")
    if use_flash is None:
        from bee_code_interpreter_tpu.ops.flash_attention import uses_flash

        use_flash = uses_flash()
    if use_flash:
        return _ring_attention_flash(
            q, k, v, axis_name=axis_name, causal=causal, sm_scale=sm_scale,
            window=window,
        )
    orig_dtype = q.dtype
    B, H, Lq, D = q.shape
    KVH = k.shape[1]
    if H % KVH != 0:
        raise ValueError(f"n_heads {H} not a multiple of kv_heads {KVH}")
    Lk = k.shape[2]
    sm_scale = sm_scale if sm_scale is not None else D ** -0.5

    n = lax.axis_size(axis_name)
    my_idx = lax.axis_index(axis_name)

    qf = q.astype(jnp.float32).reshape(B, KVH, H // KVH, Lq, D)
    # derive accumulators from qf so they carry the same varying-axes type as
    # the data (shard_map vma typing: plain constants are "unvarying" and make
    # lax.cond branches disagree, whatever the surrounding mesh axes are)
    m0 = jnp.zeros_like(qf[..., :1]) - jnp.inf
    l0 = jnp.zeros_like(qf[..., :1])
    o0 = jnp.zeros_like(qf)

    causal_mask = None
    row = lax.broadcasted_iota(jnp.int32, (Lq, Lk), 0)
    col = lax.broadcasted_iota(jnp.int32, (Lq, Lk), 1)
    if causal:
        visible = row >= col
        if window is not None:  # own block: local offsets == global offsets
            visible &= row - col < window
        causal_mask = jnp.where(visible, 0.0, -jnp.inf).astype(jnp.float32)

    # send to next ring member; after `step` hops we hold block (my_idx - step)
    perm = [(i, (i + 1) % n) for i in range(n)]

    def body(step, carry):
        m, l, o, k_blk, v_blk = carry
        k_idx = (my_idx - step) % n

        def attend(args):
            m, l, o = args
            if causal:
                # same block: triangular (+window) mask; earlier block: no
                # mask, or the window-straddle mask in global offsets
                def same_block(_):
                    return _block_attend(qf, k_blk, v_blk, m, l, o, sm_scale, causal_mask)

                def earlier_block(_):
                    mask = None
                    if window is not None:
                        delta = (my_idx - k_idx) * Lq  # global row - col shift
                        mask = jnp.where(
                            row - col < window - delta, 0.0, -jnp.inf
                        ).astype(jnp.float32)
                    return _block_attend(qf, k_blk, v_blk, m, l, o, sm_scale, mask)

                return lax.cond(k_idx == my_idx, same_block, earlier_block, None)
            return _block_attend(qf, k_blk, v_blk, m, l, o, sm_scale, None)

        def skip(args):
            return args

        if causal:
            skip_pred = k_idx > my_idx  # future block
            if window is not None:
                # entirely below the window: min global offset over the
                # block, (my_idx - k_idx)·L - (L-1), already >= window
                skip_pred |= (my_idx - k_idx) * Lq - (Lq - 1) >= window
            m, l, o = lax.cond(skip_pred, skip, attend, (m, l, o))
        else:
            m, l, o = attend((m, l, o))

        k_next = lax.ppermute(k_blk, axis_name, perm)
        v_next = lax.ppermute(v_blk, axis_name, perm)
        return m, l, o, k_next, v_next

    m, l, o, _, _ = lax.fori_loop(0, n, body, (m0, l0, o0, k, v))
    # guard fully-masked rows (shouldn't occur: every query sees its own block)
    out = o / jnp.maximum(l, 1e-30)
    return out.reshape(B, H, Lq, D).astype(orig_dtype)


def _ring_attention_flash(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    axis_name: str,
    causal: bool,
    sm_scale: float | None,
    window: int | None = None,
) -> jax.Array:
    """Ring attention with the Pallas flash kernel per hop.

    Each hop computes a *normalized* attention block plus its log-sum-exp;
    hops merge in the standard lse algebra — running
    (m = max lse, s = Σ e^{lse−m}, o = Σ out·e^{lse−m}), final o/s. The
    causal structure is per block pair exactly as the einsum ring: earlier
    blocks attend fully (kernel causal=False), the own block triangularly
    (causal=True), later blocks are skipped. lax.cond keeps both kernel
    variants compiled once; the skip branch costs nothing but the carry.

    ``window`` rides the same structure: the own block uses the kernel's
    causal+window masking (static width — same offsets as local attention);
    hops fully inside the window run the plain non-causal kernel; hops the
    window boundary straddles (at most ceil(window/L_local) of them) run a
    jax-level masked softmax block — its mask width (window − delta) is
    device-dependent, which a static kernel parameter cannot express — and
    merge on lse exactly like kernel hops; hops entirely below the window
    are skipped like future blocks.
    """
    from bee_code_interpreter_tpu.ops.flash_attention import (
        flash_attention_with_lse,
    )

    orig_dtype = q.dtype
    B, H, Lq, D = q.shape
    KVH = k.shape[1]
    Lk = k.shape[2]
    n = lax.axis_size(axis_name)
    my_idx = lax.axis_index(axis_name)
    scale = sm_scale if sm_scale is not None else D ** -0.5

    NEG = jnp.float32(-1e30)  # not -inf: (-inf) - (-inf) would NaN the scale
    m0 = jnp.full((B, H, Lq, 1), NEG) + jnp.zeros_like(
        q[..., :1], dtype=jnp.float32
    )  # derive vma from q (shard_map typing), value NEG
    s0 = jnp.zeros_like(m0)
    o0 = jnp.zeros_like(q, dtype=jnp.float32)

    perm = [(i, (i + 1) % n) for i in range(n)]

    def boundary_block(k_idx, k_blk, v_blk):
        """One jax-level online-softmax block with the window-straddle mask
        (row − col < window − delta in global offsets), returned as
        (normalized out, lse) so it merges like a kernel hop. Fully-masked
        rows surface as lse ≈ −1e30 and merge to weight 0."""
        delta = (my_idx - k_idx) * Lq
        qf = q.astype(jnp.float32).reshape(B, KVH, H // KVH, Lq, D)
        scores = jnp.einsum(
            "bgrqd,bgkd->bgrqk", qf, k_blk.astype(jnp.float32),
            preferred_element_type=jnp.float32,
        ) * scale
        row = lax.broadcasted_iota(jnp.int32, (Lq, Lk), 0)
        col = lax.broadcasted_iota(jnp.int32, (Lq, Lk), 1)
        scores = jnp.where(row - col < window - delta, scores, NEG)
        m_b = jnp.max(scores, axis=-1, keepdims=True)
        p = jnp.exp(scores - m_b)
        l_b = jnp.sum(p, axis=-1, keepdims=True)  # >= 1: some e^0 survives
        out = jnp.einsum(
            "bgrqk,bgkd->bgrqd", p, v_blk.astype(jnp.float32)
        ) / l_b
        lse = (m_b + jnp.log(l_b))[..., 0]  # [B, KVH, rep, Lq]
        return (
            out.reshape(B, H, Lq, D).astype(orig_dtype),
            lse.reshape(B, H, Lq),
        )

    def body(step, carry):
        m, s, o, k_blk, v_blk = carry
        k_idx = (my_idx - step) % n

        def attend(args):
            m, s, o = args

            def own_block(_):
                return flash_attention_with_lse(
                    q, k_blk, v_blk, True, sm_scale, window=window
                )

            def earlier_block(_):
                if window is None:
                    return flash_attention_with_lse(q, k_blk, v_blk, False, sm_scale)

                def full_block(_):
                    return flash_attention_with_lse(q, k_blk, v_blk, False, sm_scale)

                # fully visible iff even the largest offset, delta + (L-1),
                # is inside the window
                delta = (my_idx - k_idx) * Lq
                return lax.cond(
                    delta + Lq - 1 < window,
                    full_block,
                    lambda _: boundary_block(k_idx, k_blk, v_blk),
                    None,
                )

            if causal:
                out_blk, lse_blk = lax.cond(
                    k_idx == my_idx, own_block, earlier_block, None
                )
            else:
                out_blk, lse_blk = earlier_block(None)
            lse_blk = lse_blk[..., None]  # [B, H, Lq, 1]
            m_new = jnp.maximum(m, lse_blk)
            scale_old = jnp.exp(m - m_new)
            scale_blk = jnp.exp(lse_blk - m_new)
            o = o * scale_old + out_blk.astype(jnp.float32) * scale_blk
            s = s * scale_old + scale_blk
            return m_new, s, o

        def skip(args):
            return args

        if causal:
            skip_pred = k_idx > my_idx
            if window is not None:
                skip_pred |= (my_idx - k_idx) * Lq - (Lq - 1) >= window
            m, s, o = lax.cond(skip_pred, skip, attend, (m, s, o))
        else:
            m, s, o = attend((m, s, o))

        k_next = lax.ppermute(k_blk, axis_name, perm)
        v_next = lax.ppermute(v_blk, axis_name, perm)
        return m, s, o, k_next, v_next

    m, s, o, _, _ = lax.fori_loop(0, n, body, (m0, s0, o0, k, v))
    out = o / jnp.maximum(s, 1e-30)
    return out.astype(orig_dtype)


def ring_attention_sharded(
    mesh: Mesh,
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    axis_name: str = "sp",
    causal: bool = True,
    sm_scale: float | None = None,
    use_flash: bool | None = None,
    window: int | None = None,
) -> jax.Array:
    """Standalone entry: shards [B, H, L, D] inputs over ``axis_name`` on L
    and runs the ring. For use outside an existing shard_map context.
    ``sm_scale``/``use_flash``/``window`` forward to ``ring_attention`` (so
    the einsum fallback or the flash-hop path can be forced from here too)."""
    spec = P(None, None, axis_name, None)
    # the flash-hop path runs pallas_call under shard_map, which vma
    # checking cannot lower yet — disable the check exactly when that path
    # is taken (see models/transformer._attention)
    from bee_code_interpreter_tpu.ops.flash_attention import uses_flash

    flash = use_flash if use_flash is not None else uses_flash()
    fn = jax.shard_map(
        functools.partial(
            ring_attention, axis_name=axis_name, causal=causal,
            sm_scale=sm_scale, use_flash=use_flash, window=window,
        ),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=not flash,
    )
    return fn(q, k, v)


def reference_attention(q, k, v, *, causal=True, window=None, sm_scale=None):
    """O(L²)-memory reference for tests. Accepts grouped-query K/V
    ([B, KVH, L, D] with KVH dividing q's head count) by broadcasting;
    ``window`` masks keys more than window-1 positions behind the query
    (sliding-window attention; requires causal); ``sm_scale`` scales the
    scores (None = 1/sqrt(D))."""
    if k.shape[1] != q.shape[1]:
        rep = q.shape[1] // k.shape[1]
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
    scores = jnp.einsum(
        "bhqd,bhkd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32),
        preferred_element_type=jnp.float32,
    ) * (q.shape[-1] ** -0.5 if sm_scale is None else sm_scale)
    if window is not None and not causal:
        # mirror the flash kernel's validation: local_attention must behave
        # identically across platforms
        raise ValueError("window requires causal=True (sliding window)")
    if window is not None and window < 1:
        raise ValueError(f"window must be >= 1, got {window}")
    if causal:
        Lq, Lk = scores.shape[-2:]
        row = lax.broadcasted_iota(jnp.int32, (Lq, Lk), 0)
        col = lax.broadcasted_iota(jnp.int32, (Lq, Lk), 1)
        mask = row >= col
        if window is not None:
            mask = mask & (row - col < window)
        scores = jnp.where(mask, scores, -jnp.inf)
    weights = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", weights, v.astype(jnp.float32)).astype(q.dtype)
