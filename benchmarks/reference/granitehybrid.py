"""The plain forward pass of Granite-4.0-H (granite-4.0-h-micro;
transformers' ``GraniteMoeHybridForCausalLM`` with ``num_local_experts`` 0,
whose mixer is Bamba's Mamba-2), in float32.

Straightforward ``jax.numpy``: no cache, no kernel, no batching, no chunks.
One sequence goes through one layer at a time, a layer's weights are cast up
to float32 only while that layer runs, and matrix multiplications run at
``precision="highest"``. The recurrence is a plain ``lax.scan`` over time,
one position a step, so it shares nothing with the program's chunked scan
or with its decode step.

It reads the system's parameter tree and the published keys of the
configuration file. Under ``params["layers"]`` the norms and the MLP
(``ln1 ln2 w_gate w_up w_down``) are stacked over all layers, ``wq wk wv wo``
over the attention layers and the mixer's leaves (``in_proj conv_w conv_b
A_log dt_bias D ln_gate out_proj``) over the mamba layers, in the order of
``layer_types``; matrices are [in, out], ``conv_w`` is [d_conv, channels]
and the head is ``embed`` transposed. Departures from the published model:
none in the mathematics; the weights are seeded, not trained.

    h = embedding_multiplier * embed[tokens]
    per layer l of layer_types:
      x = rmsnorm(h, ln1)
      mamba:     [z | xBC | dt] = x in_proj
                 xBC = silu(causal depthwise conv1d(xBC, kernel 4) + conv_b)
                 [x_ | B | C] = xBC
                 dt = softplus(dt + dt_bias) ;  A = -exp(A_log)    (per head)
                 S_t = exp(dt_t A) S_(t-1) + dt_t x_t (outer) B_t
                 y_t = S_t C_t + D x_t
                 mix = rmsnorm(y * silu(z), ln_gate) out_proj
      attention: q, k, v = x wq, x wk, x wv      (no position embedding)
                 mix = softmax(attention_multiplier q k^T, causal) v wo
      h = h + residual_multiplier * mix
      h = h + residual_multiplier * (silu(y w_gate) * (y w_up)) w_down,
          y = rmsnorm(h, ln2)
    logits = rmsnorm(h, ln_f) embed^T / logits_scaling
"""

from __future__ import annotations

import functools

# What part (a) of ``correct`` holds the system to (``benchmarks/lib/check.py``
# has the comparison): ``requests`` seeded requests of 9 tokens after a
# 100-token prompt, greedy and sampled in turn, 144 positions. The system
# computes in bf16 and keeps the recurrent state in bf16 between steps, this
# file is float32 throughout.
#
# The numbers are small beside mistral.py's because the logits are: with
# seeded weights rmsnorm(h) embed^T has unit variance and logits_scaling
# divides it by 8, so a logit lies some 0.125 from the mean. Read on the v5e
# at the published widths and all 40 layers (my chip runs, PR 29; PERF.md
# section 6), 69 seeds of the served path (the state kept in float32 on 45 of
# them, in bf16 on 24: the same readings): median of |log p_system - log
# p_reference| 0.0035 to 0.0069 a run (mean 0.0050, standard deviation
# 0.0006; one run above 0.0062), largest single position 0.016 to 0.035, a
# greedy token below this file's best logit in four runs, by 0.0076 at most.
# Against them, in scratch copies:
#
# - the state kept in float8_e4m3fn, the nearest precision below (six
#   seeds): median 0.0073 to 0.0092, largest position 0.046 to 0.095;
# - every matrix rounded through float8_e4m3fn where it is used (one seed):
#   median 0.033, 41 positions of 144 beyond 0.06, up to 0.16;
# - the state seeded at the padded width, 112 for a prompt of 100 (three
#   seeds): median 0.088 to 0.100, 92 to 94 positions out, up to 0.62;
# - a row's state zeroed at admission (three seeds): median 0.074 to 0.094,
#   83 to 96 positions out, up to 0.44.
#
# - ``logprob_median`` 0.009: the bound that tells a coarser arithmetic from
#   bf16, seven standard deviations above what bf16 gives and a quarter of
#   what 8-bit matrices give. Of the six runs with an 8-bit STATE it refuses
#   one, and ``logprob_abs`` two more: half a run's chance, so no such
#   program passes a dozen seeds. (0.007 refused all six, but a sound run
#   then read 0.0069; 0.011 refused two.)
# - ``logprob_abs`` and ``argmax_margin`` 0.06: every single position, about
#   twice the largest seen and a seventh of what a wrong state gives (an 8-bit
#   state's largest lie at 0.046 to 0.095, too near the sound runs' for a
#   limit between them);
# - a dense model makes no discrete choice (``tie_gap`` None), so no
#   position may be out.
#
# What these limits do NOT tell apart: a state kept in float32 from one kept
# in bf16 (four paired seeds: median 0.0046 to 0.0057 in float32, 0.0042 to
# 0.0057 in bf16). Nothing decoded for 9 tokens can: a rounding to nearest is
# unbiased and 2^-9 of an element, below what bf16 activations give the same
# sum (models/mamba.py has the CPU reading over 800 tokens).
TOLERANCE = {
    "requests": 16, "logprob_median": 0.009, "logprob_abs": 0.06,
    "argmax_margin": 0.06, "tie_gap": None, "out_share_close": 0.0,
    "out_share_clear": 0.0,
}


def f32(x):
    import jax.numpy as jnp

    return x.astype(jnp.float32)


def rms_norm(x, scale, eps):
    import jax.numpy as jnp
    from jax import lax

    variance = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * lax.rsqrt(variance + eps) * f32(scale)


def attention(x, layer, cfg):
    """x [L, d] (normed) -> the attention mix [L, d]."""
    import jax
    import jax.numpy as jnp

    n_heads = cfg["num_attention_heads"]
    n_kv = cfg["num_key_value_heads"]
    dim = cfg["hidden_size"] // n_heads
    length = x.shape[0]
    q = (x @ f32(layer["wq"])).reshape(length, n_heads, dim)
    k = (x @ f32(layer["wk"])).reshape(length, n_kv, dim)
    v = (x @ f32(layer["wv"])).reshape(length, n_kv, dim)
    k = jnp.repeat(k, n_heads // n_kv, axis=1)
    v = jnp.repeat(v, n_heads // n_kv, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) * cfg["attention_multiplier"]
    position = jnp.arange(length)
    visible = position[None, :] <= position[:, None]
    scores = jnp.where(visible[None], scores, -jnp.inf)
    weights = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("hqk,khd->qhd", weights, v).reshape(length, n_heads * dim)
    return out @ f32(layer["wo"])


def mamba(x, layer, cfg):
    """x [L, d] (normed) -> the mixer's output [L, d], a position at a
    time."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    heads, dim = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    groups, state = cfg["mamba_n_groups"], cfg["mamba_d_state"]
    taps = cfg["mamba_d_conv"]
    inner = heads * dim
    channels = inner + 2 * groups * state
    length = x.shape[0]

    zxbcdt = x @ f32(layer["in_proj"])
    z = zxbcdt[:, :inner]
    xbc = zxbcdt[:, inner:inner + channels]
    dt = zxbcdt[:, inner + channels:]

    # tap k multiplies the input taps - 1 - k positions back
    padded = jnp.pad(xbc, ((taps - 1, 0), (0, 0)))
    w = f32(layer["conv_w"])
    xbc = f32(layer["conv_b"]) + sum(
        padded[k:k + length] * w[k] for k in range(taps)
    )
    xbc = jax.nn.silu(xbc)

    x_ = xbc[:, :inner].reshape(length, heads, dim)
    per_group = heads // groups
    b = jnp.repeat(
        xbc[:, inner:inner + groups * state].reshape(length, groups, state),
        per_group, axis=1,
    )  # [L, heads, state]
    c = jnp.repeat(
        xbc[:, inner + groups * state:].reshape(length, groups, state),
        per_group, axis=1,
    )
    dt = jax.nn.softplus(dt + f32(layer["dt_bias"]).reshape(heads))
    a = -jnp.exp(f32(layer["A_log"]).reshape(heads))

    def step(s, inputs):
        x_t, b_t, c_t, dt_t = inputs  # [H,P] [H,N] [H,N] [H]
        s = (
            jnp.exp(dt_t * a)[:, None, None] * s
            + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        )
        return s, jnp.einsum("hpn,hn->hp", s, c_t)

    _, y = lax.scan(
        step, jnp.zeros((heads, dim, state), jnp.float32), (x_, b, c, dt)
    )
    y = y + f32(layer["D"]).reshape(heads)[None, :, None] * x_
    y = y.reshape(length, inner) * jax.nn.silu(z)
    return rms_norm(y, layer["ln_gate"], cfg["rms_norm_eps"]) @ f32(layer["out_proj"])


def mlp(h, layer, cfg):
    import jax

    y = rms_norm(h, layer["ln2"], cfg["rms_norm_eps"])
    gate = jax.nn.silu(y @ f32(layer["w_gate"]))
    return (gate * (y @ f32(layer["w_up"]))) @ f32(layer["w_down"])


MIXERS = {"attention": attention, "mamba": mamba}
OWN_LEAVES = {
    "attention": ("wq", "wk", "wv", "wo"),
    "mamba": ("in_proj", "conv_w", "conv_b", "A_log", "dt_bias", "D",
              "ln_gate", "out_proj"),
}
SHARED_LEAVES = ("ln1", "ln2", "w_gate", "w_up", "w_down")


def forward(params, sequences, cfg):
    """Yields (logits [L, vocab] in float32, None) for each of ``sequences``
    (token ids [L]), one sequence and one layer at a time. Layer l's leaves
    are taken out of the stacks inside one jitted program for its kind:
    the shared ones at l, its own at its index among the layers of its
    kind."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    residual = cfg["residual_multiplier"]
    eps = cfg["rms_norm_eps"]

    @functools.partial(jax.jit, static_argnums=(0,), donate_argnums=(1,))
    def one_layer(kind, h, layers, index, index_of_kind):
        take = lambda name, i: lax.dynamic_index_in_dim(  # noqa: E731
            layers[name], i, 0, keepdims=False
        )
        layer = {name: take(name, index) for name in SHARED_LEAVES}
        layer.update({name: take(name, index_of_kind) for name in OWN_LEAVES[kind]})
        x = rms_norm(h, layer["ln1"], eps)
        h = h + residual * MIXERS[kind](x, layer, cfg)
        return h + residual * mlp(h, layer, cfg)

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
            for index, kind in enumerate(cfg["layer_types"]):
                h = one_layer(
                    kind, h, params["layers"], jnp.int32(index),
                    jnp.int32(seen[kind]),
                )
                seen[kind] += 1
            logits = head(h, params["ln_f"], params["embed"])
        # one at a time: a sequence's logits are [L, vocab] in float32, and
        # the caller is done with them before the next are made
        yield logits, None
