"""The plain forward pass of the Mistral architecture (Mistral-7B-v0.1 /
-Instruct-v0.2; transformers' ``MistralForCausalLM``), in float32.

Straightforward ``jax.numpy``: no cache, no kernel, no batching, no scan.
One sequence goes through one layer at a time, and a layer's weights are
cast up to float32 only while that layer runs, so the reference fits beside
the model it checks. Matrix multiplications run at ``precision="highest"``:
on a TPU a float32 matmul is otherwise done in bf16 passes.

It reads the system's parameter tree (``embed``, ``layers`` stacked on a
leading axis with ``ln1 wq wk wv wo ln2 w_gate w_up w_down``, ``ln_f``,
``lm_head``; matrices are [in, out]) and the published keys of the
configuration file. Departures from the published model: none in the
mathematics; the weights are seeded, not trained.

    h = embed[tokens]
    per layer:  x = rmsnorm(h) ;  q, k, v = x wq, x wk, x wv  (8 KV heads
                shared by 4 query heads each) ; rotary embedding on q and k
                (rotate-half, theta = rope_theta) ; causal softmax attention
                (inside ``sliding_window`` where the config has one) ;
                h += attn wo ;  y = rmsnorm(h) ;
                h += (silu(y w_gate) * (y w_up)) w_down
    logits = rmsnorm(h) lm_head
"""

from __future__ import annotations

import functools
import math

# What part (a) of ``correct`` holds the system to (``benchmarks/lib/check.py``
# has the comparison). ``requests`` seeded requests of 9 tokens are checked,
# greedy and sampled in turn: 72 positions. The system computes in bf16 (8
# bits of mantissa) and this file in float32; at a position the two differ
# in log p(token) mostly by the rounding of the logits themselves (a bf16
# logit near 3 is 0.016 from its neighbour), so the difference does not grow
# with depth. Measured on the v5e at the published widths (my chip runs, PR
# 22; PERF.md section 6), over some 1,000 positions of 16 layers on one chip
# and 32 layers at tp=4: median 0.010 (0.0075 to 0.012 from run to run),
# 99th percentile 0.029, largest 0.043; largest distance of a greedy token
# below this file's best logit 0.041.
#
# - ``logprob_median`` bounds the median of |log p_system - log p_reference|
#   over all positions at about twice what bf16 does. An aggregate over 72
#   positions moves by a tenth of itself from run to run, so this is the
#   bound that tells a coarser arithmetic (a quantised pool or weight, bf16
#   where float32 is stated) from bf16, which a bound on single positions
#   set at several times their noise cannot;
# - ``logprob_abs`` bounds every single position, and ``argmax_margin`` how
#   far below this file's best logit a greedily chosen token may lie, at
#   about twice the largest seen: a wrong mathematics at few positions (a
#   page read from the wrong slot, a dropped token, a window) moves those
#   by far more;
# - a dense model makes no discrete choice (``tie_gap`` None), so no
#   position may be out.
TOLERANCE = {
    "requests": 8, "logprob_median": 0.022, "logprob_abs": 0.08,
    "argmax_margin": 0.08, "tie_gap": None, "out_share_close": 0.0,
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


def rotary(x, theta: float):
    """Rotate-half rotary embedding over x [L, heads, head_dim]."""
    import jax.numpy as jnp

    length, _, dim = x.shape
    inv_freq = theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    angles = jnp.arange(length, dtype=jnp.float32)[:, None] * inv_freq[None]
    cos = jnp.cos(angles)[:, None, :]
    sin = jnp.sin(angles)[:, None, :]
    x1, x2 = x[..., : dim // 2], x[..., dim // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def attention(h, layer, cfg):
    """The attention half of a layer: h [L, d] -> h + attention. Weights
    arrive in their stored dtype and are cast up where they are used."""
    import jax
    import jax.numpy as jnp

    n_heads = cfg["num_attention_heads"]
    n_kv = cfg["num_key_value_heads"]
    dim = cfg["hidden_size"] // n_heads
    length = h.shape[0]
    theta = cfg["rope_theta"]
    x = rms_norm(h, layer["ln1"], cfg["rms_norm_eps"])
    q = rotary((x @ f32(layer["wq"])).reshape(length, n_heads, dim), theta)
    k = rotary((x @ f32(layer["wk"])).reshape(length, n_kv, dim), theta)
    v = (x @ f32(layer["wv"])).reshape(length, n_kv, dim)
    k = jnp.repeat(k, n_heads // n_kv, axis=1)
    v = jnp.repeat(v, n_heads // n_kv, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(dim)
    position = jnp.arange(length)
    visible = position[None, :] <= position[:, None]
    window = cfg.get("sliding_window")
    if window is not None:
        visible &= position[None, :] > position[:, None] - window
    scores = jnp.where(visible[None], scores, -jnp.inf)
    weights = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("hqk,khd->qhd", weights, v).reshape(length, n_heads * dim)
    return h + out @ f32(layer["wo"])


def mlp(h, layer, cfg):
    import jax

    y = rms_norm(h, layer["ln2"], cfg["rms_norm_eps"])
    gate = jax.nn.silu(y @ f32(layer["w_gate"]))
    return h + (gate * (y @ f32(layer["w_up"]))) @ f32(layer["w_down"])


def layer_forward(h, layer, cfg):
    """One layer: the new h [L, d], and None where ``mixtral.py`` gives its
    router's margin (a dense layer chooses nothing)."""
    return mlp(attention(h, layer, cfg), layer, cfg), None


def run(layer_fn, params, sequences, cfg):
    """Yields for each of ``sequences`` (token ids [L], all of one length),
    one sequence at a time: the logits [L, vocab] in float32 and what
    ``layer_fn(h, layer, cfg) -> (h, margin)`` gave beside h, stacked over
    the layers ([n_layers, L], or None where the layer gives None). Layer
    i's weights are taken out of the stack inside one jitted program, a
    layer at a time."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    @functools.partial(jax.jit, donate_argnums=(0,))
    def one_layer(h, layers, i):
        layer = jax.tree.map(
            lambda x: lax.dynamic_index_in_dim(x, i, 0, keepdims=False),
            layers,
        )
        return layer_fn(h, layer, cfg)

    @jax.jit
    def embed(table, tokens):
        return f32(table[tokens])

    @jax.jit
    def head(h, ln_f, lm_head):
        return rms_norm(h, ln_f, cfg["rms_norm_eps"]) @ f32(lm_head)

    for tokens in sequences:
        with jax.default_matmul_precision("highest"):
            h = embed(params["embed"], jnp.asarray(tokens, jnp.int32))
            margins = []
            for i in range(cfg["num_hidden_layers"]):
                h, margin = one_layer(h, params["layers"], jnp.int32(i))
                margins.append(margin)
            logits = head(h, params["ln_f"], params["lm_head"])
        # one at a time: a sequence's logits are [L, vocab] in float32, and
        # the caller is done with them before the next are made
        yield logits, None if margins[0] is None else jnp.stack(margins)


def forward(params, sequences, cfg):
    """Yields (logits [L, vocab], None) for each sequence."""
    return run(layer_forward, params, sequences, cfg)
