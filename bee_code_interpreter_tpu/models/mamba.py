"""The Mamba-2 mixer (Dao & Gu, "Transformers are SSMs"; as Bamba and
Granite-4.0-H use it), beside attention in ``models/transformer.py``.

    [z | xBC | dt] = x in_proj
    xBC = silu(causal depthwise conv1d(xBC) + conv_b)     (kernel d_conv)
    [x_ | B | C] = xBC            (heads x head size | groups x state, twice)
    dt = softplus(dt + dt_bias) ;  A = -exp(A_log)                (per head)
    S_t = exp(dt_t A) S_(t-1) + dt_t x_t (outer) B_t   (per head, float32)
    y_t = S_t C_t + D x_t
    out = rmsnorm(y * silu(z), ln_gate) out_proj

Two programs compute it. The prefill scans a whole prompt in chunks
(``ssd_chunked``: inside a chunk the recurrence is two matmuls against a
masked decay matrix, between chunks a short scan carries the state) and
hands back what decoding needs: the state and the last ``d_conv - 1`` conv
inputs at the prompt's TRUE length, whatever width it was padded to. The
decode step (``mixer_step``) advances every row's state by one token.

Both programs compute decays and the recurrence in float32. What a row
KEEPS between steps, the state and the conv tail, is in the model's dtype:
a rounding to nearest a step is unbiased, and it cannot swamp an increment
until a state element is some 2^8 times its increments, which a head
reaches only after hundreds of like-signed ones. Read on the CPU (PERF.md section 6, PR 29: 8 layers, decay rates
as ``init_mixer_params`` draws them, dt A 0.003 to 0.8 a token, 800 decoded
tokens against ``benchmarks/reference/granitehybrid.py``): in a float32
model a bf16 state moves a token's log-probability by a median of 0.0001
(0.0008 at most) with no growth over the 800; a bf16 model reads 0.0007
(0.005 at most) with a float32 and with a bf16 state alike. A float32
state would double the decode step's largest stream for nothing the
comparison can see. ``tests/test_hybrid_serving.py`` holds the state to
this precision over 200 tokens: one kept in an 8-bit float is told apart.

Leaves (stacked over the mamba layers under ``params["layers"]``; matrices
are [in, out]): ``in_proj``, ``conv_w`` [d_conv, channels] (tap k multiplies
the input ``d_conv - 1 - k`` positions back, torch's ``Conv1d`` weight
transposed), ``conv_b`` [channels], ``A_log`` ``dt_bias`` ``D`` [1, heads],
``ln_gate`` [inner], ``out_proj``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from bee_code_interpreter_tpu.models.transformer import qeinsum, rms_norm

MIXER_LEAVES = (
    "in_proj", "conv_w", "conv_b", "A_log", "dt_bias", "D", "ln_gate",
    "out_proj",
)


def init_mixer_params(config, key: jax.Array) -> dict:
    """One layer's mixer leaves (f32 masters), initialised as the published
    model's module does: A in [1, 16], dt in [1e-3, 1e-1], D ones."""
    c = config
    k_in, k_conv, k_out, k_a, k_dt = jax.random.split(key, 5)
    inner, heads = c.mamba_d_inner, c.mamba_n_heads
    channels = c.mamba_conv_channels
    dt = jnp.exp(jax.random.uniform(
        k_dt, (1, heads), minval=math.log(1e-3), maxval=math.log(1e-1)
    ))
    return {
        "in_proj": jax.random.normal(
            k_in, (c.d_model, inner + channels + heads)
        ) / math.sqrt(c.d_model),
        "conv_w": jax.random.normal(
            k_conv, (c.mamba_d_conv, channels)
        ) / math.sqrt(c.mamba_d_conv),
        "conv_b": jnp.zeros((channels,), jnp.float32),
        "A_log": jnp.log(jax.random.uniform(k_a, (1, heads), minval=1.0, maxval=16.0)),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),  # softplus^-1(dt)
        "D": jnp.ones((1, heads), jnp.float32),
        "ln_gate": jnp.ones((inner,), jnp.float32),
        "out_proj": jax.random.normal(k_out, (inner, c.d_model)) / math.sqrt(inner),
    }


def alloc_state(config, max_batch: int, zeros) -> dict:
    """What the mamba layers keep for each row of the batch, zeroed, in
    the model's dtype: ``ssm`` [mamba layers, rows, heads, head size,
    state] and ``conv`` [mamba layers, rows, d_conv - 1, channels].
    ``zeros(shape, dtype)`` makes a leaf where the pool lives."""
    c = config
    n = c.n_mamba_layers
    return {
        "ssm": zeros(
            (n, max_batch, c.mamba_n_heads, c.mamba_d_head, c.mamba_d_state),
            c.dtype,
        ),
        "conv": zeros(
            (n, max_batch, c.mamba_d_conv - 1, c.mamba_conv_channels), c.dtype
        ),
    }


def state_bytes_per_row(config) -> int:
    """Bytes of recurrent state and conv tail one row keeps over all the
    mamba layers."""
    c = config
    return c.n_mamba_layers * jnp.dtype(c.dtype).itemsize * (
        c.mamba_n_heads * c.mamba_d_head * c.mamba_d_state
        + (c.mamba_d_conv - 1) * c.mamba_conv_channels
    )


def _split(zxbcdt, config):
    c = config
    inner, channels = c.mamba_d_inner, c.mamba_conv_channels
    return (
        zxbcdt[..., :inner],
        zxbcdt[..., inner:inner + channels],
        zxbcdt[..., inner + channels:],
    )


def _split_xbc(xbc, config):
    """[..., channels] -> x_ [..., groups, heads a group, head size], B and
    C [..., groups, state]: a group's heads share its B and C."""
    c = config
    inner, gn = c.mamba_d_inner, c.mamba_n_groups * c.mamba_d_state
    lead, groups = xbc.shape[:-1], c.mamba_n_groups
    return (
        xbc[..., :inner].reshape(
            *lead, groups, c.mamba_n_heads // groups, c.mamba_d_head
        ),
        xbc[..., inner:inner + gn].reshape(*lead, groups, c.mamba_d_state),
        xbc[..., inner + gn:].reshape(*lead, groups, c.mamba_d_state),
    )


def _per_head(leaf, config):
    """A per-head leaf [1, heads] as float32 [groups, heads a group]."""
    c = config
    return leaf.astype(jnp.float32).reshape(
        c.mamba_n_groups, c.mamba_n_heads // c.mamba_n_groups
    )


def _dt_and_decay_rate(dt_raw, layer, config):
    """softplus(dt + dt_bias) [..., groups, heads a group] and A =
    -exp(A_log), both float32."""
    groups = config.mamba_n_groups
    dt = dt_raw.astype(jnp.float32).reshape(*dt_raw.shape[:-1], groups, -1)
    return (
        jax.nn.softplus(dt + _per_head(layer["dt_bias"], config)),
        -jnp.exp(_per_head(layer["A_log"], config)),
    )


def _finish(y, x_, z, layer, config):
    """y + D x, gated by silu(z), normed as one group over the inner width,
    and projected out: [B, L, d]."""
    c = config
    y = y + _per_head(layer["D"], c)[..., None] * x_.astype(jnp.float32)
    y = y.reshape(*z.shape) * jax.nn.silu(z.astype(jnp.float32))
    return qeinsum(
        "blk,kd->bld", rms_norm(y.astype(c.dtype), layer["ln_gate"], c.rms_norm_eps),
        layer["out_proj"], c.dtype,
    )


def ssd_chunked(x, dt, a, b, c, chunk: int, dtype=jnp.bfloat16):
    """The recurrence over a whole sequence, a chunk at a time.

    ``x`` [B, L, G, R, P] (G groups of R heads of size P), ``dt`` [B, L, G,
    R] (float32; 0 where a position is to leave the state untouched), ``a``
    [G, R] (float32, negative), ``b`` and ``c`` [B, L, G, N]. Returns y
    [B, L, G, R, P] (float32, without the D x term) and the state after the
    last position [B, G, R, P, N] (float32). The matmuls take ``dtype``
    operands and accumulate in float32; decays and the carried state stay
    float32. L is padded to a multiple of ``chunk`` with dt = 0, which
    changes neither."""
    B, L, G, R, P = x.shape
    N = b.shape[-1]
    Q = min(chunk, L)
    pad = -L % Q
    if pad:
        widths = lambda t: ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2)  # noqa: E731
        x, dt, b, c = (jnp.pad(t, widths(t)) for t in (x, dt, b, c))
    nc = (L + pad) // Q
    chunks = lambda t: t.reshape(B, nc, Q, *t.shape[2:])  # noqa: E731
    x, dt, b, c = chunks(x), chunks(dt), chunks(b), chunks(c)
    f32 = jnp.float32
    b, c = b.astype(dtype), c.astype(dtype)
    xdt = x.astype(f32) * dt[..., None]  # [B,nc,Q,G,R,P]
    a_cum = jnp.cumsum(dt * a, axis=2)  # [B,nc,Q,G,R], falling from 0

    # inside a chunk: y_i = sum_{j<=i} exp(a_cum_i - a_cum_j) (C_i . B_j) xdt_j
    diff = a_cum[:, :, :, None] - a_cum[:, :, None, :]  # [B,nc,i,j,G,R]
    causal = jnp.tril(jnp.ones((Q, Q), bool))[None, None, :, :, None, None]
    # masked before the exponential: above the diagonal the difference is
    # positive and may overflow
    decay = jnp.exp(jnp.where(causal, diff, -jnp.inf))
    scores = jnp.einsum("bcign,bcjgn->bcijg", c, b, preferred_element_type=f32)
    y = jnp.einsum(
        "bcijgr,bcjgrp->bcigrp", (scores[..., None] * decay).astype(dtype),
        xdt.astype(dtype), preferred_element_type=f32,
    )

    # what each chunk adds to the state by its end, and its whole decay
    to_end = jnp.exp(a_cum[:, :, -1:] - a_cum)  # [B,nc,Q,G,R]
    added = jnp.einsum(
        "bcjgn,bcjgrp->bcgrpn", b, (xdt * to_end[..., None]).astype(dtype),
        preferred_element_type=f32,
    )
    chunk_decay = jnp.exp(a_cum[:, :, -1])  # [B,nc,G,R]

    def carry(state, inputs):
        decay_c, added_c = inputs
        return state * decay_c[..., None, None] + added_c, state

    last, before = lax.scan(
        carry, jnp.zeros((B, G, R, P, N), f32),
        (jnp.moveaxis(chunk_decay, 1, 0), jnp.moveaxis(added, 1, 0)),
    )
    # what the state a chunk began with still gives at each position in it
    y = y + jnp.einsum(
        "bcign,cbgrpn->bcigrp", c, before.astype(dtype),
        preferred_element_type=f32,
    ) * jnp.exp(a_cum)[..., None]
    return y.reshape(B, nc * Q, G, R, P)[:, :L], last


def ssm_step(state, x, dt, a, b, c):
    """One position of the recurrence for every row: ``state`` [B, G, R, P,
    N] (as the pool keeps it), ``x`` [B, G, R, P], ``dt`` [B, G, R], ``a``
    [G, R], ``b`` and ``c`` [B, G, N]. Returns y [B, G, R, P] (without D x)
    and the state, both float32: the caller rounds the state it stores."""
    f32 = jnp.float32
    state = (
        state.astype(f32) * jnp.exp(dt * a)[..., None, None]
        + (x.astype(f32) * dt[..., None])[..., None]
        * b.astype(f32)[:, :, None, None, :]
    )
    return jnp.einsum("bgrpn,bgn->bgrp", state, c.astype(f32)), state


def mixer_prefill(x, layer, config, length=None):
    """The mixer over whole sequences ``x`` [B, L, d] (normed input).
    ``length`` (a traced int32 scalar, or None for L) is the sequences' true
    length: positions at or beyond it leave the state untouched. Returns
    the mixer's output [B, L, d], the state at ``length`` [B, heads, head
    size, state] and the last ``d_conv - 1`` conv inputs before ``length``
    [B, d_conv - 1, channels] (zeros where the sequence is shorter)."""
    c = config
    B, L, _ = x.shape
    taps = c.mamba_d_conv
    z, xbc, dt_raw = _split(qeinsum("bld,dk->blk", x, layer["in_proj"], c.dtype), c)
    with jax.named_scope("ssm.conv"):
        padded = jnp.pad(xbc, ((0, 0), (taps - 1, 0), (0, 0)))
        w = layer["conv_w"].astype(jnp.float32)
        mixed = layer["conv_b"].astype(jnp.float32) + sum(
            padded[:, k:k + L].astype(jnp.float32) * w[k] for k in range(taps)
        )
        mixed = jax.nn.silu(mixed).astype(c.dtype)
        # padded[i] is the input at position i - (taps - 1)
        tail = lax.dynamic_slice_in_dim(
            padded, L if length is None else length, taps - 1, axis=1
        )
    x_, b, cc = _split_xbc(mixed, c)
    dt, a = _dt_and_decay_rate(dt_raw, layer, c)
    if length is not None:
        dt = jnp.where(jnp.arange(L)[None, :, None, None] < length, dt, 0.0)
    with jax.named_scope("ssm.scan"):
        y, state = ssd_chunked(x_, dt, a, b, cc, c.mamba_chunk_size, c.dtype)
    state = state.reshape(B, c.mamba_n_heads, c.mamba_d_head, c.mamba_d_state)
    return _finish(y, x_, z, layer, c), state, tail


def mixer_step(x, layer, config, ssm, conv):
    """The mixer for one new token a row: ``x`` [B, 1, d] (normed input),
    ``ssm`` [B, heads, head size, state] and ``conv`` [B, d_conv - 1,
    channels] as the pool keeps them. Returns the output [B, 1, d] and
    both, advanced by the token."""
    c = config
    z, xbc, dt_raw = _split(qeinsum("bld,dk->blk", x, layer["in_proj"], c.dtype), c)
    with jax.named_scope("ssm.conv"):
        window = jnp.concatenate([conv, xbc.astype(conv.dtype)], axis=1)
        mixed = layer["conv_b"].astype(jnp.float32) + jnp.einsum(
            "bkc,kc->bc", window.astype(jnp.float32),
            layer["conv_w"].astype(jnp.float32),
        )
        mixed = jax.nn.silu(mixed).astype(c.dtype)
    x_, b, cc = _split_xbc(mixed, c)
    dt, a = _dt_and_decay_rate(dt_raw[:, 0], layer, c)
    with jax.named_scope("ssm.step"):
        y, state = ssm_step(ssm.reshape(*x_.shape, c.mamba_d_state), x_, dt, a, b, cc)
    return (
        _finish(y[:, None], x_[:, None], z, layer, c),
        state.reshape(ssm.shape), window[:, 1:],
    )
