"""Mixture-of-Experts MLP with expert parallelism over an ``ep`` mesh axis.

TPU-first design (GShard / Mesh-TensorFlow capacity-based dense dispatch —
NOT a ragged/sort-based CUDA-style implementation):

- Routing produces static-shaped **dispatch** and **combine** tensors
  ``[G, E, C]`` (tokens × experts × capacity slots); token movement is plain
  einsums. No dynamic shapes, no sorting — everything lowers to MXU matmuls
  and XLA keeps the program fully static.
- Expert weights carry a leading ``[n_experts]`` axis sharded over the mesh's
  ``ep`` axis (PartitionSpec ``P('ep', ...)``); the dispatch/combine einsums
  contract the token dimension (sharded over dp/fsdp) against the expert
  dimension (sharded over ep), so **GSPMD inserts the all-to-alls over ICI**
  — the same collective pattern a hand-written MoE would issue, without any
  hand-written communication.
- Tokens over capacity are *dropped* (contribute zero; the residual
  connection carries them), the standard trade for static shapes on TPU.
- An auxiliary load-balancing loss (Shazeer-style: E · Σ_e fraction_e ·
  mean-prob_e) keeps routing from collapsing; the transformer adds it to the
  training loss scaled by ``moe_aux_weight``.

Beside it, the SORTED dispatch (``held_experts_mlp``; the DeepSeek-V3
lineage's router): an expert layer that is told which experts it holds,
routes over all of them, sorts the (token, expert) pairs routed to its own
experts by expert and runs them through one grouped matmul
(``jax.lax.ragged_dot``, on a TPU a Mosaic kernel over the rows the groups
cover). Nothing is dropped, a pair's row is computed from its token alone,
and what experts held elsewhere would add is left out: the chip's share of
an expert-parallel layer, without the exchange.

The reference (a code-execution service) has no MoE; this module exists for
the framework's model-family/parallelism completeness: the full dp × ep × tp
training step is exercised on virtual devices by tests/test_moe.py and the
driver's ``dryrun_multichip``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

Params = dict


def init_moe_params(
    key: jax.Array,
    d_model: int,
    ff_dim: int,
    n_experts: int,
) -> Params:
    """Router + per-expert SwiGLU weights (f32 masters, [E, ...] stacked)."""
    k_router, k_gate, k_up, k_down = jax.random.split(key, 4)

    def dense(key, fan_in, *shape):
        return jax.random.normal(key, shape, dtype=jnp.float32) / math.sqrt(fan_in)

    return {
        "router": dense(k_router, d_model, d_model, n_experts),
        "we_gate": dense(k_gate, d_model, n_experts, d_model, ff_dim),
        "we_up": dense(k_up, d_model, n_experts, d_model, ff_dim),
        "we_down": dense(k_down, ff_dim, n_experts, ff_dim, d_model),
    }


def expert_capacity(
    n_tokens: int,
    n_experts: int,
    top_k: int,
    capacity_factor: float,
    dropless: bool = False,
) -> int:
    """Per-expert capacity slots, rounded up to 8 (sublane-friendly tiles).

    ``dropless`` sizes capacity to the worst case — every token in the group
    choosing this expert — so no token can ever be evicted. That makes
    routing per-token independent: a token's expert assignment and combine
    weights depend only on its own router logits, never on batch-mates
    competing for slots. Cost: dispatch/combine grow to [g, E, g] per group
    (quadratic in group size) — affordable for decode-sized groups, which is
    what serving-exactness needs it for."""
    if dropless:
        return max(8, -(-n_tokens // 8) * 8)
    raw = capacity_factor * n_tokens * top_k / n_experts
    return max(8, int(math.ceil(raw / 8)) * 8)


def _route_group(
    xf: jax.Array,  # [g, D] one routing group
    router: jax.Array,  # [D, E]
    *,
    n_experts: int,
    top_k: int,
    capacity: int,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Per-group dispatch/combine tensors [g, E, C] + per-group aux loss.

    GShard position-in-expert assignment: earlier tokens (and earlier top-k
    choices) win capacity slots; losers are dropped (combine weight zero —
    the residual stream carries them unchanged). Routing math stays in f32
    (softmax over expert logits is precision-sensitive).
    """
    logits = jnp.einsum(
        "gd,de->ge", xf.astype(jnp.float32), router.astype(jnp.float32)
    )
    probs = jax.nn.softmax(logits, axis=-1)

    gate_vals, gate_idx = lax.top_k(probs, top_k)  # [g, k]
    # renormalize the kept gates so the combine weights sum to 1 per token
    gate_vals = gate_vals / jnp.maximum(
        gate_vals.sum(axis=-1, keepdims=True), 1e-9
    )

    C = capacity
    dispatch = jnp.zeros((xf.shape[0], n_experts, C), dtype=jnp.float32)
    combine = jnp.zeros_like(dispatch)
    filled = jnp.zeros((n_experts,), dtype=jnp.int32)
    for j in range(top_k):
        onehot = jax.nn.one_hot(gate_idx[:, j], n_experts, dtype=jnp.int32)
        position = jnp.cumsum(onehot, axis=0) - onehot + filled[None, :]
        filled = filled + onehot.sum(axis=0)
        slot = (position * onehot).sum(axis=-1)  # position in chosen expert
        keep = (slot < C).astype(jnp.float32)
        slot_oh = jax.nn.one_hot(slot, C, dtype=jnp.float32)
        pair = onehot.astype(jnp.float32)[:, :, None] * slot_oh[:, None, :]
        dispatch = dispatch + pair * keep[:, None, None]
        combine = combine + pair * (gate_vals[:, j] * keep)[:, None, None]

    # Load balancing (Shazeer): E · Σ_e (fraction of tokens routed to e) ·
    # (mean router prob of e). Uses the top-1 assignment for the fraction.
    top1 = jax.nn.one_hot(gate_idx[:, 0], n_experts, dtype=jnp.float32)
    aux = n_experts * jnp.sum(top1.mean(axis=0) * probs.mean(axis=0))
    return dispatch, combine, aux


def moe_mlp(
    params: Params,
    x: jax.Array,  # [B, L, D]
    *,
    n_experts: int,
    top_k: int = 2,
    capacity_factor: float = 1.25,
    dtype=jnp.bfloat16,
    group_size: int = 1024,
    dropless: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Returns (output [B, L, D], aux load-balancing loss scalar f32).

    Tokens are routed in fixed-size **groups** (GShard's group dimension):
    dispatch/combine memory is ``G · E · C_group`` with ``C_group`` set by
    the group size, i.e. linear in the global token count — without the
    group axis it is quadratic (capacity itself grows with G). Groups also
    bound the all-to-all message sizes. When the token count doesn't divide
    into groups, routing falls back to one global group.
    """
    B, L, D = x.shape
    G = B * L
    xf = x.reshape(G, D)

    n_groups = max(1, G // group_size)
    if G % n_groups != 0:
        n_groups = 1
    g = G // n_groups
    C = expert_capacity(g, n_experts, top_k, capacity_factor, dropless)

    xg = xf.reshape(n_groups, g, D)
    dispatch, combine, aux = jax.vmap(
        lambda xs: _route_group(
            xs, params["router"], n_experts=n_experts, top_k=top_k, capacity=C
        )
    )(xg)  # [n, g, E, C] ×2, [n]

    # token → expert movement: contraction over the (dp-sharded) token dim
    # against the (ep-sharded) expert dim — GSPMD's all-to-all lives here.
    # The group axis rides along as a batch dim into the expert matmuls
    # ([E, n·C, D] worth of rows per expert).
    expert_in = jnp.einsum(
        "ngec,ngd->necd", dispatch.astype(dtype), xg.astype(dtype)
    )  # [n, E, C, D]
    gate = jnp.einsum("necd,edf->necf", expert_in, params["we_gate"].astype(dtype))
    up = jnp.einsum("necd,edf->necf", expert_in, params["we_up"].astype(dtype))
    expert_out = jnp.einsum(
        "necf,efd->necd", jax.nn.silu(gate) * up, params["we_down"].astype(dtype)
    )  # [n, E, C, D]
    out = jnp.einsum(
        "ngec,necd->ngd", combine.astype(dtype), expert_out
    )  # [n, g, D]

    return out.reshape(B, L, D), aux.mean()


# ------------------------------------------------- the sorted (held) dispatch


def init_held_params(key: jax.Array, config) -> Params:
    """An expert layer that holds ``config.held_experts`` of the
    ``config.n_experts`` its router scores: the router [d, E], or with a
    per-expert selection bias [d + 1, E], the bias its LAST ROW (zeros
    until trained; one leaf, so that a fill by fan-in gives the bias the
    magnitude of a router weight and not that of a score), the held
    experts' SwiGLU weights [H, ...] and the shared experts' as one SwiGLU
    of their summed width (``ws_*``)."""
    c = config
    d, f = c.d_model, c.expert_ff_dim
    k_router, k_gate, k_up, k_down, k_sg, k_su, k_sd = jax.random.split(key, 7)

    def dense(key, fan_in, *shape):
        return jax.random.normal(key, shape, dtype=jnp.float32) / math.sqrt(fan_in)

    held = c.held_experts
    router = dense(k_router, d, d, c.n_experts)
    if c.moe_router_bias:
        router = jnp.concatenate([router, jnp.zeros((1, c.n_experts))])
    out = {
        "router": router,
        "we_gate": dense(k_gate, d, held, d, f),
        "we_up": dense(k_up, d, held, d, f),
        "we_down": dense(k_down, f, held, f, d),
    }
    if c.moe_shared_experts:
        fs = c.moe_shared_experts * f
        out.update({
            "ws_gate": dense(k_sg, d, d, fs), "ws_up": dense(k_su, d, d, fs),
            "ws_down": dense(k_sd, fs, fs, d),
        })
    return out


EXPERT_STACKS = ("we_gate", "we_up", "we_down")


def take_held_layer(stacked: Params, index) -> Params:
    """Layer ``index`` (traced) of an expert layer's leaves stacked over
    layers: the small leaves cut out, the experts' stacks WHOLE beside the
    index (``layer``). A slice of a stack handed to a kernel is a copy of
    the slice, 1.6 GB a layer at 32 experts of 4096 x 2048; the grouped
    matmul takes layers x experts as its groups and reads in place."""
    out = {
        name: lax.dynamic_index_in_dim(x, index, 0, keepdims=False)
        for name, x in stacked.items() if name not in EXPERT_STACKS
    }
    out.update({name: stacked[name] for name in EXPERT_STACKS})
    out["layer"] = index
    return out


def route_sigmoid(x: jax.Array, moe: Params, config):
    """The router's choice for tokens ``x`` [T, D], in float32: sigmoid
    scores over all experts, the ``moe_top_k`` largest of score + bias
    chosen, weighted by their scores (no bias) over the sum of the kept
    scores times ``moe_routed_scaling``. Gives (expert ids [T, k] int32,
    weights [T, k] float32)."""
    c = config
    router = moe["router"].astype(jnp.float32)
    scores = jax.nn.sigmoid(jnp.einsum(
        "td,de->te", x.astype(jnp.float32), router[:x.shape[1]],
        precision=lax.Precision.HIGHEST,
    ))
    biased = scores + router[-1] if c.moe_router_bias else scores
    _, chosen = lax.top_k(biased, c.moe_top_k)
    kept = jnp.take_along_axis(scores, chosen, axis=1)
    weights = kept / kept.sum(axis=-1, keepdims=True) * c.moe_routed_scaling
    return chosen.astype(jnp.int32), weights


def sorted_rows(n_tokens: int, config) -> int:
    """Rows of the sorted buffer the common case runs at: the pairs
    ``n_tokens`` route to the held experts in expectation, and a quarter
    more, and 32 (a decode batch's count spreads by a tenth of its mean),
    rounded up to 8. More pairs than that take the buffer of every pair."""
    c = config
    mean = n_tokens * c.moe_top_k * c.held_experts / c.n_experts
    rows = -(-int(math.ceil(1.25 * mean) + 32) // 8) * 8
    return min(rows, n_tokens * c.moe_top_k)


def sort_pairs(chosen: jax.Array, config):
    """The (token, expert) pairs ``chosen`` [T, k] in the order the grouped
    matmul wants them: pairs of held experts first, by expert, in token
    order within one (a stable sort); every other pair after. Gives
    (``order`` [T*k]: the flat pair at each sorted position, ``sizes`` [H]:
    pairs of each held expert)."""
    c = config
    held = c.held_experts
    local = chosen.reshape(-1) - c.moe_held_from
    key = jnp.where((local >= 0) & (local < held), local, held)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    sizes = jnp.zeros((held + 1,), jnp.int32).at[key].add(1)[:held]
    return order, sizes


def grouped_experts(x, order, sizes, weights, moe: Params, config, rows: int):
    """The held experts' weighted sum for every token of ``x`` [T, D], in
    float32, over a sorted buffer of ``rows`` rows (which must hold every
    pair ``sizes`` counts): the rows' tokens gathered, three grouped
    matmuls whose groups are layers x held experts (all but this layer's
    empty), and each token's pairs gathered back and summed in the order of
    its top-k slots."""
    c = config
    T, k, held = x.shape[0], c.moe_top_k, c.held_experts
    stacks = [moe[name] for name in EXPERT_STACKS]
    if stacks[0].ndim == 3:  # one layer's experts: a stack of one layer
        stacks, layer = [w[None] for w in stacks], 0
    else:
        layer = moe["layer"]
    n_layers = stacks[0].shape[0]
    groups = lax.dynamic_update_slice_in_dim(
        jnp.zeros((n_layers * held,), jnp.int32), sizes, layer * held, 0
    )
    gate_w, up_w, down_w = (
        w.astype(c.dtype).reshape(n_layers * held, *w.shape[2:]) for w in stacks
    )
    xs = x.astype(c.dtype)[order[:rows] // k]  # [rows, D]
    with jax.named_scope("moe.experts"):
        gate = lax.ragged_dot(xs, gate_w, groups)
        up = lax.ragged_dot(xs, up_w, groups)
        out = lax.ragged_dot(jax.nn.silu(gate) * up, down_w, groups)
    # where each pair sits in the buffer (beyond the pairs counted: nowhere)
    at = jnp.zeros((T * k,), jnp.int32).at[order].set(
        jnp.arange(T * k, dtype=jnp.int32)
    ).reshape(T, k)
    mine = at < sizes.sum()
    picked = out[jnp.minimum(at, rows - 1)].astype(jnp.float32)  # [T, k, D]
    return jnp.einsum(
        "tk,tkd->td", jnp.where(mine, weights, 0.0),
        jnp.where(mine[..., None], picked, 0.0),
    )


def held_experts_mlp(moe: Params, y: jax.Array, config) -> jax.Array:
    """The expert layer's output for ``y`` [B, L, D]: what the held experts
    give the tokens routed to them, beside the shared experts that take
    every token. ``moe`` is ``take_held_layer``'s (or one layer's leaves)."""
    c = config
    B, L, D = y.shape
    T = B * L
    x = y.reshape(T, D)
    with jax.named_scope("moe.route"):
        chosen, weights = route_sigmoid(x, moe, c)
    with jax.named_scope("moe.sort"):
        order, sizes = sort_pairs(chosen, c)
    small, every = sorted_rows(T, c), T * c.moe_top_k

    def at(rows):
        return lambda: grouped_experts(x, order, sizes, weights, moe, c, rows)

    # dropless: the rare batch with more pairs than the common buffer
    # holds runs at the buffer that holds every pair
    if small < every:
        routed = lax.cond(sizes.sum() <= small, at(small), at(every))
    else:
        routed = at(every)()
    if c.moe_shared_experts:
        with jax.named_scope("moe.shared"):
            dt = c.dtype
            gate = jnp.einsum("td,df->tf", x, moe["ws_gate"].astype(dt))
            up = jnp.einsum("td,df->tf", x, moe["ws_up"].astype(dt))
            routed = routed + jnp.einsum(
                "tf,fd->td", jax.nn.silu(gate) * up, moe["ws_down"].astype(dt)
            ).astype(jnp.float32)
    return routed.astype(y.dtype).reshape(B, L, D)
