"""The plain forward pass of sarvam-105b (``model_type`` ``sarvam_mla``:
latent attention as in DeepSeek-V2, arXiv:2405.04434, without a query latent;
a dense first layer; then layers of routed experts beside a shared expert),
in float32, for ONE CHIP'S SHARE of a layer: the experts this chip holds and
its slice of the vocabulary.

Straightforward ``jax.numpy``: the PUBLISHED (non-absorbed) attention over
the whole sequence, no cache, no kernel, no sort, no batching. One sequence
goes through one layer at a time, matrix multiplications run at
``precision="highest"``, and the held experts run one after another
(``lax.fori_loop``), each over every token and weighted by its routing
weight or by zero, so only one expert's weights are ever held in float32.
YaRN is written out here and shares nothing with the program's ``rope``.

It reads the system's parameter tree and the published keys of the
configuration file. ``params["dense_layers"]`` holds the ``first_k_dense_
replace`` leading layers and ``params["layers"]`` the expert layers after
them, both stacked over their layers; matrices are [in, out]:

    ln1 ln2                     [d]
    wq                          [d, heads * (qk_nope + qk_rope)]
    ln_q                        [qk_nope + qk_rope]
    w_kva                       [d, kv_lora_rank + qk_rope]
    ln_kv                       [kv_lora_rank]
    w_kvb                       [kv_lora_rank, heads * (qk_nope + v)]
    wo                          [heads * v, d]
    w_gate w_up w_down          the dense layers' SwiGLU
    moe.router                  [d + 1, E]  E: every routed expert (128);
                                            the last row is the router bias
    moe.we_gate we_up we_down   [H, ...]    H: the experts held here (32),
                                            experts ``experts_held_from`` on
    moe.ws_gate ws_up ws_down   the shared expert's SwiGLU

    h = embed[tokens]
    per layer:
      x = rmsnorm(h, ln1)
      q = rmsnorm_192(x wq per head, ln_q) = [q_nope 128 | q_rope 64]
      [c_raw 512 | k_rope_raw 64] = x w_kva ;  c = rmsnorm_512(c_raw, ln_kv)
      q_rope, k_rope = yarn_rope(q_rope), yarn_rope(k_rope_raw)   (k_rope: one for all heads)
      [k_nope_h 128 | v_h 128] = c w_kvb per head
      s_h = (q_nope_h . k_nope_h + q_rope_h . k_rope) * scale, causal
      h += concat_h(softmax(s_h) v_h) wo
      y = rmsnorm(h, ln2)
      layer 0:   h += (silu(y w_gate) * (y w_up)) w_down
      others:    score = sigmoid(y router)                       float32
                 keep the 8 largest of score + router_bias
                 w_e = score_e / (sum of the 8 kept scores) * routed_scaling_factor
                 h += shared(y) + sum over kept e HELD HERE of w_e * expert_e(y)
    logits = rmsnorm(h, ln_f) lm_head          over the vocabulary rows held here

    scale = (qk_nope + qk_rope)^-0.5 * (0.1 * mscale_all_dim * ln(factor) + 1)^2

Departures from the published model, and the readings taken where the
catalog's copy of ``config.json`` does not say (the modelling code is not in
the catalog; each is the convention of the lineage whose key names the config
carries, DeepSeek-V3 / Bailing-V2; the configuration file lists them under
``assumed``):

- THE SHARE. The experts a token keeps but that are held elsewhere add
  nothing, here as in the program, and that partial sum goes on to the next
  layer (model-configs guide, section 4). The vocabulary is the slice held.
- the router scores with a sigmoid, selects by score + bias, weights by the
  selected scores alone over their sum, times ``routed_scaling_factor``;
  one routing group (the config has no ``n_group``);
- ``use_qk_norm``: the latent's RMSNorm (which every MLA has) and an RMSNorm
  with a scale of its own over each head's whole 192-wide query, before the
  rotary split. The other reading (the key is left over from the parent
  config class and only the latent is normed) is ``use_qk_norm: false`` here;
- rotary pairs dimension i with i + 32 of the 64 (rotate-half), as the
  program's ``rope`` does; the published model interleaves, which under
  seeded weights is a relabelling of ``wq``'s and ``w_kva``'s columns;
- the weights are seeded, not trained; the router bias is seeded too (a
  trained one is what balances the load).

Beside the logits it gives, per layer and position, the router's margin for
its one discrete choice: the 8th kept ``score + bias`` less the 9th
(``inf`` in the dense layer).
"""

from __future__ import annotations

import functools
import math

# What part (a) of ``correct`` holds the system to (``benchmarks/lib/check.py``
# has the comparison): ``requests`` seeded requests of 9 tokens after a
# 512-token prompt, greedy and sampled in turn, 144 positions. The system
# computes in bf16 and keeps the latent in bf16; this file is float32.
#
# A router makes a discrete choice, as Mixtral's does (``mixtral.py`` has
# the argument), and here it is close EVERYWHERE: 128 sigmoid scores of a
# seeded router lie some 0.01 apart around the 8th, so the least margin over
# the five expert layers read 0.001 at the median and 0.013 at most, over
# 1,700 positions. The system's router (float32, fed bf16 activations)
# chooses otherwise than this file's at some layer of most tokens, of the
# prompt's too, whose latents every later position attends over. So the
# margin at a position tells nothing here (positions with a margin of 0.004
# to 0.008 are out as often as those under 0.0005), every position counts as
# close, and what a flip is worth (one of 8 kept experts, held here a
# quarter of the time, at a weight of 0.3) is the noise floor of the whole
# comparison: three to five times what bf16 gives a dense model.
#
# Read on the v5e at the published widths and 6 layers (my chip runs, PR 33;
# PERF.md section 6), the served path, 12 seeds of 16 requests: median of
# |log p_system - log p_reference| 0.0544 to 0.0698 a run (mean 0.060,
# standard deviation 0.004); positions beyond 0.2: 13 to 27 of 144; beyond
# 0.5: 1 to 4; largest 0.46 to 1.55; greedy tokens more than 0.5 below this
# file's best logit: 1 to 4 of 72 (largest 0.53 to 1.85).
# Against it, in scratch copies of the program:
#   - the latent kept through float8_e4m3's rounding, the nearest precision
#     below bf16 (three seeds, four runs): median 0.180, 0.190, 0.206, 0.212;
#     18 to 28 positions beyond 0.5 and 8 to 10 greedy tokens more than 0.5
#     below: 28 positions out under the limits below;
#   - the held experts' weights and the rows into each grouped matmul through
#     the same rounding (``lax.reduce_precision`` to 4 exponent and 3
#     mantissa bits: a pair of converts XLA drops; two seeds): median 0.313,
#     0.390; 44 and 47 positions beyond 0.5; 48 and 54 out;
#   - no router bias: median 0.197, 20 positions beyond 0.5; no
#     routed_scaling_factor (1 for 2.5): 0.316, 38 beyond; the latent left
#     un-normed: 0.461, 66 beyond; no shared expert: 1.477, 123 beyond; YaRN's
#     scale left out of the scores (0.0722 for 0.1352): 1.680, 124 beyond.
#
# - ``logprob_median`` 0.11: eleven standard deviations above the sound
#   runs' mean, half again their largest, and six tenths of the nearest
#   control (0.180): the bound that tells a coarser arithmetic, or a part
#   left out, from bf16. Every control above fails by it;
# - ``logprob_abs`` and ``argmax_margin`` 0.5: a position is out where a flip
#   alone rarely puts it (at most 10 of 144 in 30 sound runs, 4.5 in the mean),
#   and ``out_share_close`` 1 in 10 allows 14: a Poisson count of mean 4.5
#   passes that once in twenty thousand runs. Every control puts 28 or more
#   out, so each fails by this limit too;
# - ``tie_gap`` 0.05, above every margin seen (0.013 at most): every
#   position is close, in words: no position's choice was clear of rounding.
#   ``out_share_clear`` 1 % then holds nothing at these widths and is kept
#   for a seed whose margins are wider.
TOLERANCE = {
    "requests": 16, "logprob_median": 0.11, "logprob_abs": 0.5,
    "argmax_margin": 0.5, "tie_gap": 0.05, "out_share_close": 0.1,
    "out_share_clear": 0.01,
}


def f32(x):
    import jax.numpy as jnp

    return x.astype(jnp.float32)


def rms_norm(x, scale, eps):
    import jax.numpy as jnp
    from jax import lax

    variance = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * lax.rsqrt(variance + eps) * f32(scale)


def yarn_mscale(factor: float, mscale: float) -> float:
    if factor <= 1:
        return 1.0
    return 0.1 * mscale * math.log(factor) + 1.0


def yarn_inverse_frequencies(dim: int, theta: float, scaling: dict):
    """``deepseek_yarn``: the rotary dimension pairs that turn more than
    ``beta_fast`` times over the original context keep theta^(-2i/dim);
    those that turn fewer than ``beta_slow`` times have it divided by
    ``factor``; a linear ramp blends the pairs between."""
    import jax.numpy as jnp

    original = scaling["original_max_position_embeddings"]

    def correction_dim(rotations: float) -> float:
        return dim * math.log(original / (rotations * 2 * math.pi)) / (
            2 * math.log(theta)
        )

    low = max(math.floor(correction_dim(scaling["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(scaling["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    pairs = jnp.arange(dim // 2, dtype=jnp.float32)
    ramp = jnp.clip((pairs - low) / (high - low), 0.0, 1.0)
    extrapolated = theta ** (-2.0 * pairs / dim)
    interpolated = extrapolated / scaling["factor"]
    return interpolated * ramp + extrapolated * (1.0 - ramp)


def yarn_rotary(x, cfg):
    """Rotate-half rotary embedding with YaRN frequencies over x [L, heads,
    dim]; cos and sin carry mscale(factor, mscale) / mscale(factor,
    mscale_all_dim), which is 1 for this model."""
    import jax.numpy as jnp

    scaling = cfg["rope_scaling"]
    length, _, dim = x.shape
    inv_freq = yarn_inverse_frequencies(dim, cfg["rope_theta"], scaling)
    amplitude = yarn_mscale(scaling["factor"], scaling["mscale"]) / yarn_mscale(
        scaling["factor"], scaling["mscale_all_dim"]
    )
    angles = jnp.arange(length, dtype=jnp.float32)[:, None] * inv_freq[None]
    cos = (jnp.cos(angles) * amplitude)[:, None, :]
    sin = (jnp.sin(angles) * amplitude)[:, None, :]
    x1, x2 = x[..., : dim // 2], x[..., dim // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def softmax_scale(cfg) -> float:
    scaling = cfg["rope_scaling"]
    mscale = yarn_mscale(scaling["factor"], scaling["mscale_all_dim"])
    return cfg["q_head_dim"] ** -0.5 * mscale * mscale


def attention(h, layer, cfg):
    """The attention half of a layer, published form: h [L, d] -> h +
    attention."""
    import jax
    import jax.numpy as jnp

    heads = cfg["num_attention_heads"]
    nope, rot = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    v_dim, rank = cfg["v_head_dim"], cfg["kv_lora_rank"]
    eps = cfg["rms_norm_eps"]
    length = h.shape[0]
    x = rms_norm(h, layer["ln1"], eps)
    q = (x @ f32(layer["wq"])).reshape(length, heads, nope + rot)
    if cfg["use_qk_norm"]:
        q = rms_norm(q, layer["ln_q"], eps)
    q_nope, q_rope = q[..., :nope], yarn_rotary(q[..., nope:], cfg)
    kva = x @ f32(layer["w_kva"])
    latent = rms_norm(kva[:, :rank], layer["ln_kv"], eps)
    k_rope = yarn_rotary(kva[:, None, rank:], cfg)[:, 0]  # [L, rot], all heads'
    kv = (latent @ f32(layer["w_kvb"])).reshape(length, heads, nope + v_dim)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    scores = (
        jnp.einsum("qhd,khd->hqk", q_nope, k_nope)
        + jnp.einsum("qhd,kd->hqk", q_rope, k_rope)
    ) * softmax_scale(cfg)
    position = jnp.arange(length)
    visible = position[None, :] <= position[:, None]
    scores = jnp.where(visible[None], scores, -jnp.inf)
    weights = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("hqk,khd->qhd", weights, v).reshape(length, heads * v_dim)
    return h + out @ f32(layer["wo"])


def swiglu(y, gate, up, down):
    import jax

    return (jax.nn.silu(y @ f32(gate)) * (y @ f32(up))) @ f32(down)


def dense_mlp(h, layer, cfg):
    import jax.numpy as jnp

    y = rms_norm(h, layer["ln2"], cfg["rms_norm_eps"])
    out = h + swiglu(y, layer["w_gate"], layer["w_up"], layer["w_down"])
    return out, jnp.full(h.shape[:1], jnp.inf)


def expert_mlp(h, layer, experts, cfg):
    """The expert half of a layer for this chip's share. ``layer["moe"]``
    holds the router and the shared expert; ``experts(name, e)`` gives held
    expert e's matrix ``name`` (one at a time)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    moe = layer["moe"]
    top_k = cfg["num_experts_per_tok"]
    held, first = cfg["num_experts"], cfg["experts_held_from"]
    y = rms_norm(h, layer["ln2"], cfg["rms_norm_eps"])
    router, biased_by = f32(moe["router"]), 0.0
    if cfg["moe_router_enable_expert_bias"]:
        router, biased_by = router[:-1], router[-1]
    scores = jax.nn.sigmoid(y @ router)  # [L, E]
    biased = scores + biased_by
    ranked, chosen = lax.top_k(biased, top_k + 1)
    kept = jnp.take_along_axis(scores, chosen[:, :top_k], axis=1)
    kept = kept / kept.sum(axis=-1, keepdims=True) * cfg["routed_scaling_factor"]
    # [L, E]: the weight of each kept expert, zero elsewhere
    weights = jnp.einsum(
        "lk,lke->le", kept,
        jax.nn.one_hot(chosen[:, :top_k], scores.shape[1], dtype=y.dtype),
    )

    def add_expert(e, total):
        out = swiglu(
            y, experts("we_gate", e), experts("we_up", e), experts("we_down", e)
        )
        return total + lax.dynamic_index_in_dim(
            weights, first + e, 1, keepdims=True
        ) * out

    routed = lax.fori_loop(0, held, add_expert, jnp.zeros_like(h))
    shared = 0.0
    if cfg["num_shared_experts"]:
        shared = swiglu(y, moe["ws_gate"], moe["ws_up"], moe["ws_down"])
    # the margin of this token's one discrete choice
    return h + shared + routed, ranked[:, top_k - 1] - ranked[:, top_k]


def forward(params, sequences, cfg):
    """Yields (logits [L, vocab held] in float32, router margins [n_layers,
    L]) for each of ``sequences`` (token ids [L]), one sequence and one
    layer at a time."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    n_dense = cfg["first_k_dense_replace"]
    eps = cfg["rms_norm_eps"]

    def take(tree, i):
        return jax.tree.map(
            lambda x: lax.dynamic_index_in_dim(x, i, 0, keepdims=False), tree
        )

    @functools.partial(jax.jit, donate_argnums=(0,))
    def dense_layer(h, layers, i):
        layer = take(layers, i)
        return dense_mlp(attention(h, layer, cfg), layer, cfg)

    @functools.partial(jax.jit, donate_argnums=(0,))
    def expert_layer(h, layers, i):
        stacks = {n: layers["moe"][n] for n in ("we_gate", "we_up", "we_down")}
        small = {n: x for n, x in layers.items() if n != "moe"}
        small["moe"] = {
            n: x for n, x in layers["moe"].items() if n not in stacks
        }
        layer = take(small, i)

        def experts(name, e):  # [layers, held, ...] -> one expert's matrix
            at = lax.dynamic_index_in_dim(stacks[name], i, 0, keepdims=False)
            return lax.dynamic_index_in_dim(at, e, 0, keepdims=False)

        return expert_mlp(attention(h, layer, cfg), layer, experts, cfg)

    @jax.jit
    def embed(table, tokens):
        return f32(table[tokens])

    @jax.jit
    def head(h, ln_f, lm_head):
        return rms_norm(h, ln_f, eps) @ f32(lm_head)

    for tokens in sequences:
        with jax.default_matmul_precision("highest"):
            h = embed(params["embed"], jnp.asarray(tokens, jnp.int32))
            margins = []
            for i in range(cfg["num_hidden_layers"]):
                if i < n_dense:
                    h, margin = dense_layer(h, params["dense_layers"], jnp.int32(i))
                else:
                    h, margin = expert_layer(
                        h, params["layers"], jnp.int32(i - n_dense)
                    )
                margins.append(margin)
            logits = head(h, params["ln_f"], params["lm_head"])
        # one at a time: a sequence's logits are [L, vocab] in float32, and
        # the caller is done with them before the next are made
        yield logits, jnp.stack(margins)
