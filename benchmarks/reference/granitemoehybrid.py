"""The plain forward pass of Granite-4.0-H with routed experts
(granite-4.0-h-small; transformers' ``GraniteMoeHybridForCausalLM`` with
``num_local_experts`` 72), in float32, for ONE CHIP'S SHARE of a layer: the
experts this chip holds and its slice of the vocabulary.

The mixer and attention are ``granitehybrid.py``'s, imported from there. What
this file adds is each layer's MLP half: a router over every expert, the
held experts one after another (``lax.fori_loop``), each over every token
and weighted by its routing weight or by zero, so that only one expert's
weights are ever held in float32 beside the system's bf16 weights, and the
shared MLP. Matrix multiplications run at ``precision="highest"``.

It reads the system's parameter tree and the published keys of the
configuration file. Under ``params["layers"]``: ``ln1 ln2`` stacked over all
layers, ``wq wk wv wo`` over the attention layers, the mixer's leaves over
the mamba layers, and ``moe`` over all layers; matrices are [in, out]:

    moe.router                  [d, E]      E: every routed expert (72)
    moe.we_gate we_up we_down   [H, ...]    H: the experts held here (36),
                                            experts ``experts_held_from`` on
    moe.ws_gate ws_up ws_down   the shared MLP's SwiGLU of
                                ``shared_intermediate_size`` (1536)

    per layer of layer_types[:num_hidden_layers]:
      h = h + residual_multiplier * mixer(rmsnorm(h, ln1))      (granitehybrid.py)
      y = rmsnorm(h, ln2)
      l = y router                                     float32, [E]
      top = the num_experts_per_tok largest of l       (10)
      g = softmax(l[top])                              over the kept alone
      h = h + residual_multiplier * (sum over e in top HELD HERE of
              g_e SwiGLU_e(y) + SwiGLU_shared(y))
    logits = rmsnorm(h, ln_f) embed^T / logits_scaling  over the rows held here

with SwiGLU(y) = (silu(y w_gate) * (y w_up)) w_down: the published fused
``input_linear`` is [gate | up], the gate its first half (the configuration
file's ``assumed``).

Departures from the published model:

- THE SHARE. The experts a token keeps but that are held elsewhere add
  nothing, here as in the program, and that partial sum goes on to the next
  layer (model-configs guide, section 4). The shared MLP, which every chip
  computes alike, is counted once. The vocabulary is the slice held.
- the weights are seeded, not trained.

Beside the logits it gives, per layer and position, the router's margin for
its one discrete choice: the 10th largest logit less the 11th.
"""

from __future__ import annotations

import functools

from benchmarks.reference.granitehybrid import (  # noqa: F401
    MIXERS,
    OWN_LEAVES,
    f32,
    rms_norm,
)

# What part (a) of ``correct`` holds the system to (``benchmarks/lib/check.py``
# has the comparison): ``requests`` seeded requests of 9 tokens after a
# 512-token prompt, greedy and sampled in turn, 144 positions. The system
# computes in bf16 and keeps the recurrent state in bf16 between steps; this
# file is float32.
#
# The numbers are small because the logits are: rmsnorm(h) embed^T has unit
# variance under seeded weights and logits_scaling divides it by 16, so a
# logit lies some 0.06 from the mean. The router's choice is close almost
# everywhere: the 10th and 11th of 72 seeded logits lie 0.044 apart at the
# median, and a position's margin is the least over 10 layers (0.004 to
# 0.005 at the median, under 0.02 at 135 to 142 of 144 positions). So a
# flip is everywhere and moves a token by less than rounding does: ``tie_gap``
# parts nothing here, and the limits hold every position alike.
#
# Read on the v5e at the published widths and 10 layers (my chip runs, PR 39;
# PERF.md section 6), the served path, 12 seeds of 16 requests: median of
# |log p_system - log p_reference| 0.00103 to 0.00174 a run; largest single
# position 0.0058 to 0.0088: none of 1,728 beyond 0.01; every greedy token
# this file's argmax. Against them, in scratch copies of the program:
#
# - the MLP half (router, held experts, shared MLP) through float8_e4m3's
#   rounding, the nearest precision below bf16 (``lax.reduce_precision`` to
#   4 exponent and 3 mantissa bits of its input and of its output), on 4
#   seeds: median 0.00364 to 0.00423, 6 to 13 positions beyond 0.012 a run,
#   the largest 0.029;
# - the shared MLP dropped (one seed): median 0.0766, largest 0.243, 131
#   positions beyond 0.012.
#
# - ``logprob_median`` 0.0025: 1.4 times the sound runs' largest (0.00174),
#   two thirds of the float8 controls' least (0.00364);
# - ``logprob_abs`` and ``argmax_margin`` 0.012: 1.36 times the largest sound
#   position (0.0088), under the float8 control's tail (0.013 to 0.029);
# - ``out_share_close`` 0.01 (one position of 144) and ``out_share_clear``
#   0: no sound run had a position out, every float8 control 6 or more.
#   The float8 control fails by the median and by the positions out, each
#   alone; the dropped shared MLP by every limit.
TOLERANCE = {
    "requests": 16, "logprob_median": 0.0025, "logprob_abs": 0.012,
    "argmax_margin": 0.012, "tie_gap": 0.02, "out_share_close": 0.01,
    "out_share_clear": 0.0,
}

EXPERT_STACKS = ("we_gate", "we_up", "we_down")
SHARED = ("ws_gate", "ws_up", "ws_down")


def swiglu(y, gate, up, down):
    import jax

    return (jax.nn.silu(y @ f32(gate)) * (y @ f32(up))) @ f32(down)


def expert_mlp(y, moe, experts, cfg):
    """The MLP half of a layer for this chip's share, of y = rmsnorm(h, ln2)
    [L, d]: (the routed sum over the held experts and the shared MLP, the
    router's margin [L]). ``moe`` holds the router and the shared MLP;
    ``experts(name, e)`` gives held expert e's matrix ``name`` (one at a
    time)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    top_k = cfg["num_experts_per_tok"]
    held, first = cfg["num_local_experts"], cfg["experts_held_from"]
    logits = y @ f32(moe["router"])  # [L, E]
    ranked, chosen = lax.top_k(logits, top_k + 1)
    gates = jax.nn.softmax(ranked[:, :top_k], axis=-1)
    # [L, E]: the weight of each kept expert, zero elsewhere
    weights = jnp.einsum(
        "lk,lke->le", gates,
        jax.nn.one_hot(chosen[:, :top_k], logits.shape[1], dtype=y.dtype),
    )

    def add_expert(e, total):
        out = swiglu(y, *(experts(name, e) for name in EXPERT_STACKS))
        return total + lax.dynamic_index_in_dim(
            weights, first + e, 1, keepdims=True
        ) * out

    routed = lax.fori_loop(0, held, add_expert, jnp.zeros_like(y))
    if SHARED[0] in moe:
        routed = routed + swiglu(y, *(moe[name] for name in SHARED))
    return routed, ranked[:, top_k - 1] - ranked[:, top_k]


def forward(params, sequences, cfg):
    """Yields (logits [L, vocab held] in float32, router margins [layers,
    L]) for each of ``sequences`` (token ids [L]), one sequence and one layer
    at a time. Layer l's leaves are taken out of the stacks inside one
    jitted program for its kind: the norms and the expert layer's small
    leaves at l, its mixer's or attention's at its index among the layers of
    its kind, the held experts' one at a time."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    residual = cfg["residual_multiplier"]
    eps = cfg["rms_norm_eps"]
    kinds = cfg["layer_types"][:cfg["num_hidden_layers"]]

    @functools.partial(jax.jit, static_argnums=(0,), donate_argnums=(1,))
    def one_layer(kind, h, layers, index, index_of_kind):
        take = lambda x, i: lax.dynamic_index_in_dim(  # noqa: E731
            x, i, 0, keepdims=False
        )
        layer = {name: take(layers[name], index) for name in ("ln1", "ln2")}
        layer.update({
            name: take(layers[name], index_of_kind) for name in OWN_LEAVES[kind]
        })
        moe = {
            name: take(x, index) for name, x in layers["moe"].items()
            if name not in EXPERT_STACKS
        }

        def experts(name, e):  # [layers, held, ...] -> one expert's matrix
            return take(take(layers["moe"][name], index), e)

        h = h + residual * MIXERS[kind](rms_norm(h, layer["ln1"], eps), layer, cfg)
        mlp, margin = expert_mlp(rms_norm(h, layer["ln2"], eps), moe, experts, cfg)
        return h + residual * mlp, margin

    @jax.jit
    def embed(table, tokens):
        return cfg["embedding_multiplier"] * f32(table[tokens])

    @jax.jit
    def head(h, ln_f, table):
        return rms_norm(h, ln_f, eps) @ f32(table).T / cfg["logits_scaling"]

    for tokens in sequences:
        with jax.default_matmul_precision("highest"):
            h = embed(params["embed"], jnp.asarray(tokens, jnp.int32))
            seen = {"attention": 0, "mamba": 0}
            margins = []
            for index, kind in enumerate(kinds):
                h, margin = one_layer(
                    kind, h, params["layers"], jnp.int32(index),
                    jnp.int32(seen[kind]),
                )
                seen[kind] += 1
                margins.append(margin)
            logits = head(h, params["ln_f"], params["embed"])
        # one at a time: a sequence's logits are [L, vocab] in float32, and
        # the caller is done with them before the next are made
        yield logits, jnp.stack(margins)
