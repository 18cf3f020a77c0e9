"""The plain forward pass of the Mixtral architecture (Mixtral-8x7B-v0.1;
transformers' ``MixtralForCausalLM``), in float32.

Mistral's layer (``benchmarks/reference/mistral.py``: same attention, same
norms, same rotary embedding) with the dense MLP replaced by
``num_local_experts`` SwiGLU experts, of which every token uses
``num_experts_per_tok``:

    y = rmsnorm(h)
    p = softmax(y router)                     over all experts, float32
    keep the num_experts_per_tok largest p, divide them by their sum
    h += sum over the kept experts e of  p_e * (silu(y we_gate[e]) *
                                               (y we_up[e])) we_down[e]

No token is ever dropped and no capacity exists: every expert is run over
every token and weighted by p_e or by zero, which is the same sum. The
experts run one after another (``lax.fori_loop``), so only one expert's
weights are held in float32 at a time. It reads the system's parameter
tree, whose expert layers hold ``moe: {router [d, E], we_gate [E, d, f],
we_up [E, d, f], we_down [E, f, d]}``.
"""

from __future__ import annotations

from benchmarks.reference import mistral

# As mistral.py's, with what the architecture forces. A router makes a
# discrete choice: where a token's last kept expert and its first dropped
# one are nearly tied, the system's router (float32, but fed bf16
# activations) and this one can choose differently, and that position then
# differs by what an expert's output is worth, not by rounding. So this file
# also gives, per layer and position, its own margin for that choice (the
# difference of the two router logits), and the comparison counts the
# positions that are out apart where the least margin over the layers is
# under ``tie_gap`` and where it is not.
#
# Measured on the v5e at 4 layers (my chip runs, PR 22; PERF.md section 6):
# 12 seeds of 16 requests, 1,728 positions, prompts of 512 tokens. 71
# positions (4.1 %) differed by more than 0.15, by up to 1.7, and every one
# had a margin under 0.07: a third of the positions with a margin under
# 0.005 were out, a tenth at 0.01 to 0.02, one in a hundred at 0.05 to 0.07,
# none of 909 beyond. The 680 positions with a margin of 0.1 or more
# differed by 0.015 at the median, 0.067 at the 99th percentile and 0.085 at
# most. Per seed the median over all 144 positions was 0.0145 to 0.0185, and
# 3 to 12 positions were out (13 runs, mean 6.4).
#
# - ``requests`` 16: 144 positions, one batch of the configuration;
# - ``logprob_median`` about twice what bf16 does: as in mistral.py, the
#   bound that tells a coarser arithmetic from the stated one, and a median
#   does not see the flipped twentieth;
# - ``logprob_abs``, ``argmax_margin``: a position is out beyond about
#   twice the largest difference seen where no choice was close;
# - ``tie_gap`` one and a half times the widest margin a flip was seen at.
#   Six positions in ten lie under it: with seeded weights a router's logits
#   are as close as that, which is why the count is split and not waived;
# - ``out_share_close`` 1 in 8: twice the mean and half again the most seen.
#   The count varies like a Poisson variable, and the driver's check makes
#   some sixty runs of a cell: a cap of 10 % would fail one such check in
#   seven on bf16 alone (15 or more of 144 at a mean of 6.4), this one in
#   four hundred;
# - ``out_share_clear`` 1 %, one position: none was seen in 680, and a
#   check of sixty runs holds five times as many. A fault that strikes
#   positions without regard to the router (a dropped token, a wrong page)
#   puts four in ten of its positions here.
TOLERANCE = {
    "requests": 16, "logprob_median": 0.032, "logprob_abs": 0.15,
    "argmax_margin": 0.15, "tie_gap": 0.1, "out_share_close": 0.125,
    "out_share_clear": 0.01,
}


def moe_mlp(h, layer, cfg):
    import jax
    import jax.numpy as jnp
    from jax import lax

    moe = layer["moe"]
    n_experts = cfg["num_local_experts"]
    top_k = cfg["num_experts_per_tok"]
    y = mistral.rms_norm(h, layer["ln2"], cfg["rms_norm_eps"])
    logits = y @ mistral.f32(moe["router"])  # [L, E]
    probs = jax.nn.softmax(logits, axis=-1)
    kept, chosen = lax.top_k(probs, top_k)
    kept = kept / kept.sum(axis=-1, keepdims=True)
    # [L, E]: the renormalised weight of each kept expert, zero elsewhere
    weights = jnp.einsum(
        "lk,lke->le", kept, jax.nn.one_hot(chosen, n_experts, dtype=y.dtype)
    )

    def add_expert(e, total):
        take = lambda x: mistral.f32(  # noqa: E731
            lax.dynamic_index_in_dim(x, e, 0, keepdims=False)
        )
        out = (
            jax.nn.silu(y @ take(moe["we_gate"])) * (y @ take(moe["we_up"]))
        ) @ take(moe["we_down"])
        return total + lax.dynamic_index_in_dim(
            weights, e, 1, keepdims=True
        ) * out

    out = h + lax.fori_loop(0, n_experts, add_expert, jnp.zeros_like(h))
    if n_experts == top_k:  # every expert is kept: nothing to tie
        return out, jnp.full(h.shape[:1], jnp.inf)
    # how far the last kept expert's router logit lies above the first
    # dropped one's: the margin of this token's one discrete choice
    ranked = lax.top_k(logits, top_k + 1)[0]
    return out, ranked[:, top_k - 1] - ranked[:, top_k]


def layer_forward(h, layer, cfg):
    return moe_mlp(mistral.attention(h, layer, cfg), layer, cfg)


def forward(params, sequences, cfg):
    """Yields (logits [L, vocab], router margins [n_layers, L]) for each
    sequence."""
    return mistral.run(layer_forward, params, sequences, cfg)
