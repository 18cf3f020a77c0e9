"""Llama-style decoder transformer, TPU-first.

Design (idiomatic JAX/XLA, not a port of anything):

- **Pure functional**: params are a pytree of jnp arrays; init/apply/loss/
  train_step are free functions bundled in a thin ``Transformer`` class.
- **Scan over layers**: per-layer params are stacked on a leading [n_layers]
  axis and the decoder body is a single ``lax.scan`` — one layer gets traced
  and compiled once regardless of depth (compile time and HLO size stay flat).
- **bfloat16 compute, float32 master params**: matmuls ride the MXU in bf16
  via a cast at apply time; the optimizer state and params stay f32.
- **GSPMD sharding**: ``param_specs`` gives Megatron-style PartitionSpecs
  (column-parallel wq/wk/wv/w_gate/w_up, row-parallel wo/w_down, replicated
  norms) over the mesh axes that exist; activations are constrained to
  P('dp', 'sp') on (batch, sequence). XLA inserts the all-reduces over ICI.
- **Sequence parallelism** when the mesh has sp > 1: the ppermute ring
  (parallel/ring_attention.py — flash kernel per hop on TPU) or Ulysses
  all-to-all (parallel/ulysses.py), per ``sp_attention`` — long-context is
  a first-class path, not a fallback. On sp == 1 meshes the GQA-native
  Pallas flash kernel (ops/flash_attention.py) runs directly on TPU.

Components: RMSNorm, RoPE, grouped multi-head attention (K/V never
broadcast — compact through kernels, ring, decode), SwiGLU or MoE MLP
(one ``_mlp_block``), next-token cross-entropy with z-loss, AdamW train
step, pipelined forward, KV-cached decode (bf16 or int8 cache),
temperature/top-k/top-p sampling, and the decode_window verify primitive
behind models/speculative.py.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any

import jax
import jax.numpy as jnp
import optax
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from bee_code_interpreter_tpu.parallel.ring_attention import ring_attention

Params = dict[str, Any]
# an attention layer's own leaves (the norms and the MLP are every layer's):
# K and V per head, or a latent for all heads (``kv_lora_rank``)
# (``ln_q`` / ``ln_k``: the per-head norms of ``qk_norm``)
ATTENTION_LEAVES = ("wq", "wk", "wv", "wo", "ln_q", "ln_k")
LATENT_LEAVES = ("wq", "ln_q", "w_kva", "ln_kv", "w_kvb", "wo")
# what ``layer_types`` may name beside "mamba". "attention": a layer of a
# model whose layers are not told apart (every one masked by
# ``sliding_window`` where that is set, its K/V kept by page whole). The
# published pair: "full_attention" (no window, K/V by page) and
# "sliding_attention" (``sliding_window``, and only the window kept, in a
# ring by row: ``ops/paged_kv_cache.py``).
ATTENTION_KINDS = ("attention", "full_attention", "sliding_attention")


class _Derived(int):
    """A size worked out from other fields because none was given. It reads
    as the number; set back (``dataclasses.replace`` hands every field to
    the new object) it is "none given" again, so a replaced ``d_model``
    still decides it."""


class _HeadDim:
    """``TransformerConfig.head_dim`` as a field that reads as a number:
    None (the default) is ``d_model // n_heads``."""

    def __get__(self, obj, owner=None):
        if obj is None:
            return None  # the field's default
        given = obj.__dict__.get("_head_dim")
        if given is None:
            return _Derived(obj.d_model // obj.n_heads)
        return given

    def __set__(self, obj, value):
        obj.__dict__["_head_dim"] = None if isinstance(value, _Derived) else value


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32000
    d_model: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int | None = None  # grouped-query attention; None = MHA
    # a head's width (the published ``head_dim``); None = d_model // n_heads
    head_dim: int | None = _HeadDim()
    d_ff: int | None = None  # None = SwiGLU default 8/3 * d_model rounded
    max_seq_len: int = 8192
    rope_theta: float = 500000.0
    dtype: Any = jnp.bfloat16
    z_loss: float = 1e-4
    # Mixture-of-Experts: n_experts > 0 replaces every layer's dense SwiGLU
    # MLP with an expert-parallel MoE MLP (models/moe.py — GShard-style
    # dense dispatch; expert weights shard over the mesh's "ep" axis).
    n_experts: int = 0
    moe_top_k: int = 2
    moe_capacity_factor: float = 1.25
    moe_aux_weight: float = 1e-2
    moe_group_size: int = 1024  # GShard routing-group size (memory bound)
    # Dropless routing: capacity sized to the worst case so no token is ever
    # evicted — routing becomes per-token independent. With
    # moe_group_size=1 on top (each token routes in its own group, so the
    # expert einsums see pool size only as a batch dim) the forward is
    # BITWISE batch-independent, which restores the batch-isolation /
    # solo-equality bar for SERVING MoE configs — see `moe_exact` below;
    # the guards in serving/beam/speculative key on it. Cost: every token
    # pays all E experts' MLPs (E/top_k × the routed FLOPs) — the price of
    # exactness, not the training configuration.
    moe_dropless: bool = False
    # RoPE linear position interpolation (context extension): effective
    # position = position / rope_scaling. 1.0 = off; e.g. 4.0 runs a model
    # trained at max_seq_len L with positions compressed from 4L into the
    # trained range.
    rope_scaling: float = 1.0
    # Sequence-parallel attention strategy when the mesh has sp > 1:
    # "ring" rotates compact K/V over ppermute (parallel/ring_attention.py);
    # "ulysses" re-shards heads<->sequence with all-to-alls and runs the
    # local flash kernel on the full sequence (parallel/ulysses.py).
    sp_attention: str = "ring"
    # Decode KV-cache storage: "bf16" (compute dtype) or "int8" (symmetric
    # per-token/head absmax quantization, ops/kv_cache.py — halves the bytes
    # the bandwidth-bound decode loop streams per step).
    kv_cache_dtype: str = "bf16"
    # Sliding-window attention: a query attends only the last
    # ``sliding_window`` positions. ONE number: the window of the layers
    # ``layer_types`` calls "sliding_attention" (which keep no more than
    # it, in a ring by row) and, where the layers are not told apart
    # (Mistral-style: no pattern, or the kind "attention"), of every layer,
    # as a mask over K/V that are all kept. None = full causal attention.
    # The flash kernels skip fully-out-of-window blocks.
    sliding_window: int | None = None
    # A declared layer pattern: "mamba" or one of ``ATTENTION_KINDS`` per
    # layer (the published ``layer_types`` list; frozen to a tuple, since
    # the config is a static jit argument). None = attention in every
    # layer, the one ``lax.scan`` below. With a pattern the stack is scanned
    # a PERIOD at a time (``layer_period``) and each kind's leaves are
    # stacked over the layers of that kind alone (the attention leaves over
    # the attention layers of every kind). The pattern covers ALL layers:
    # leading dense layers (``n_dense_layers``) are its first entries and
    # the period is that of what follows them.
    layer_types: tuple[str, ...] | None = None
    # Mamba-2 mixer sizes (published ``mamba_*`` keys): heads x head size is
    # the inner width (``mamba_expand`` x d_model), B and C are shared by
    # the heads of a group, the depthwise conv spans ``mamba_d_conv``
    # positions, and the prefill scans in chunks of ``mamba_chunk_size``.
    mamba_n_heads: int = 0
    mamba_d_head: int = 0
    mamba_d_state: int = 0
    mamba_n_groups: int = 1
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_chunk_size: int = 256
    # Granite's scalars: h = embedding_multiplier * embed[tokens]; every
    # residual branch is scaled by residual_multiplier; attention scores by
    # attention_multiplier (None = 1/sqrt(head_dim)); logits are divided by
    # logits_scaling.
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    attention_multiplier: float | None = None
    logits_scaling: float = 1.0
    # "rope"; the published "nope": no position embedding at all; or
    # "rope_window": rotary in the "sliding_attention" layers and none in
    # the others (K-EXAONE's family: a full layer sees the whole context
    # without positions, a window layer orders its window)
    position_embedding: str = "rope"
    # the output head is the embedding transposed: no ``lm_head`` leaf
    tie_embeddings: bool = False
    # every RMSNorm's epsilon (the published ``rms_norm_eps``)
    rms_norm_eps: float = 1e-5
    # Latent attention (MLA; DeepSeek-V2, arXiv:2405.04434, without a query
    # latent): ``kv_lora_rank`` > 0 replaces K and V per head by ONE latent
    # of ``kv_lora_rank`` values a token, normed, beside one rotary key of
    # ``qk_rope_head_dim`` shared by all heads; that pair is what a token
    # keeps in the cache (``latent_width``). A head's query is ``qk_nope_
    # head_dim`` values scored against the latent's up-projection beside
    # ``qk_rope_head_dim`` rotary ones; its value is ``v_head_dim`` wide.
    # The prefill makes K and V per head from the latent (the published
    # form, through the flash kernel); a decode step scores the query's
    # up-projected form against the cached latent itself (the absorbed
    # form: one KV head whose values are the first ``kv_lora_rank`` of its
    # keys). ``qk_norm`` (published ``use_qk_norm``) norms each head's
    # whole query, with a scale of its own, before the rotary split. With K
    # and V per head (no latent) ``qk_norm`` is an RMSNorm of each head's
    # query and of each head's key, each with a scale of its own (``ln_q``,
    # ``ln_k`` [head_dim]), before the position embedding.
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    qk_norm: bool = False
    # The published ``rope_scaling`` group of type ``deepseek_yarn`` (YaRN,
    # arXiv:2309.00071: ``factor``, ``original_max_position_embeddings``,
    # ``beta_fast``, ``beta_slow``, ``mscale``, ``mscale_all_dim``), frozen
    # to a sorted tuple of its items. None = plain rotary frequencies.
    rope_yarn: Any = None
    # The first ``n_dense_layers`` layers (published ``first_k_dense_
    # replace``) keep the dense SwiGLU of ``d_ff`` in an expert model; their
    # leaves are stacked under ``params["dense_layers"]`` and they run
    # before the scan over the expert layers.
    n_dense_layers: int = 0
    # "softmax": Mixtral's router (softmax over all experts, top-k,
    # renormalised) through the GShard dispatch of ``moe.moe_mlp``.
    # "sigmoid": the DeepSeek-V3 lineage's (sigmoid scores in float32, the
    # top-k of score + a per-expert bias, weighted by the kept scores over
    # their sum times ``moe_routed_scaling``) through the token-sorted
    # dropless dispatch of ``moe.held_experts_mlp``, which is told which
    # experts it holds: ``moe_held_experts`` of them from ``moe_held_from``
    # on (None = all ``n_experts``, the router's width). What the experts
    # held elsewhere would add is left out and the partial sum goes on.
    # ``moe_d_ff`` is an expert's own width (None = ``d_ff``),
    # ``moe_shared_experts`` experts of that width take every token, and
    # with ``moe_router_bias`` the router leaf's last row is the bias.
    moe_scoring: str = "softmax"
    moe_held_experts: int | None = None
    moe_held_from: int = 0
    moe_d_ff: int | None = None
    moe_shared_experts: int = 0
    moe_routed_scaling: float = 1.0
    moe_router_bias: bool = False

    def __post_init__(self) -> None:
        if self.position_embedding not in ("rope", "nope", "rope_window"):
            raise ValueError(
                f"position_embedding must be 'rope', 'nope' or 'rope_window', "
                f"got {self.position_embedding!r}"
            )
        if self.rope_yarn is not None:
            yarn = dict(self.rope_yarn)
            kind = yarn.get("type", yarn.get("rope_type"))
            if kind != "deepseek_yarn":
                raise ValueError(
                    f"rope_yarn is a rope_scaling group of type "
                    f"'deepseek_yarn', got {kind!r}"
                )
            object.__setattr__(self, "rope_yarn", tuple(sorted(yarn.items())))
        if self.moe_scoring not in ("softmax", "sigmoid"):
            raise ValueError(
                f"moe_scoring must be 'softmax' or 'sigmoid', got "
                f"{self.moe_scoring!r}"
            )
        if self.kv_lora_rank and not (
            self.qk_nope_head_dim and self.qk_rope_head_dim and self.v_head_dim
        ):
            raise ValueError(
                "a latent cache (kv_lora_rank) needs qk_nope_head_dim, "
                "qk_rope_head_dim and v_head_dim"
            )
        if self.kv_lora_rank and self.kv_cache_dtype != "bf16":
            raise NotImplementedError(
                "an int8 latent cache is not supported: the latent is kept "
                "in the compute dtype"
            )
        sorted_only = {
            "moe_held_experts": self.moe_held_experts is not None,
            "moe_shared_experts": bool(self.moe_shared_experts),
            "moe_router_bias": self.moe_router_bias,
            "moe_routed_scaling": self.moe_routed_scaling != 1.0,
            "n_dense_layers": bool(self.n_dense_layers),
        }
        if self.moe_scoring != "sigmoid" and any(sorted_only.values()):
            raise NotImplementedError(
                f"{[k for k, v in sorted_only.items() if v]} belong to the "
                "sorted expert layer (moe_scoring='sigmoid'); the GShard "
                "dispatch holds every expert and has none of them"
            )
        if self.moe_scoring == "sigmoid" and not (
            0 <= self.moe_held_from
            and self.moe_held_from + self.held_experts <= self.n_experts
            and self.moe_top_k <= self.n_experts
        ):
            raise ValueError(
                f"experts {self.moe_held_from}..{self.moe_held_from + self.held_experts}"
                f" held of a router of {self.n_experts}, top {self.moe_top_k}"
            )
        if self.n_dense_layers and self.n_dense_layers >= self.n_layers:
            raise ValueError(
                f"{self.n_dense_layers} leading dense layers of "
                f"{self.n_layers}: they precede a scan of expert layers"
            )
        if self.layer_types is None:
            if self.position_embedding == "rope_window":
                raise ValueError(
                    "position_embedding 'rope_window' needs layer_types to "
                    "say which layers are 'sliding_attention'"
                )
            return
        types_ = tuple(self.layer_types)
        object.__setattr__(self, "layer_types", types_)
        unknown = set(types_) - {"mamba", *ATTENTION_KINDS}
        if unknown or len(types_) != self.n_layers:
            raise ValueError(
                f"layer_types must name 'mamba' or one of {ATTENTION_KINDS} "
                f"for each of {self.n_layers} layers, got {len(types_)} entries"
                + (f" with {sorted(unknown)}" if unknown else "")
            )
        if "attention" in types_ and len(set(types_) & set(ATTENTION_KINDS)) > 1:
            raise ValueError(
                "layer_types tells its attention layers apart "
                "('full_attention' / 'sliding_attention') or does not "
                "('attention'), not both"
            )
        if "sliding_attention" in types_ and (
            self.sliding_window is None or self.sliding_window < 1
            or self.kv_lora_rank or self.kv_cache_dtype != "bf16"
        ):
            raise ValueError(
                "a 'sliding_attention' layer keeps sliding_window slots a "
                "row, K and V per head, in the compute dtype: it needs "
                f"sliding_window (got {self.sliding_window}), no latent "
                "cache and no int8 cache"
            )
        if self.n_experts and self.moe_scoring != "sigmoid":
            raise ValueError(
                "a layer pattern with expert MLPs takes the sorted expert "
                "layer (moe_scoring='sigmoid'); the GShard dispatch scans "
                "one layer kind"
            )
        if "mamba" in types_[:self.n_dense_layers]:
            raise ValueError("a leading dense layer is an attention layer")
        if "mamba" in types_ and (
            self.mamba_n_heads * self.mamba_d_head
            != self.mamba_expand * self.d_model
            or self.mamba_d_state < 1
            or self.mamba_n_heads % self.mamba_n_groups
        ):
            raise ValueError(
                f"mamba sizes do not agree: {self.mamba_n_heads} heads of "
                f"{self.mamba_d_head} against mamba_expand "
                f"{self.mamba_expand} x d_model {self.d_model}, state "
                f"{self.mamba_d_state}, groups {self.mamba_n_groups}"
            )

    @property
    def n_attention_layers(self) -> int:
        """Attention layers of every kind: what ``wq wk wv wo`` are stacked
        over, and the K/V ``forward(return_kv=True)`` hands back."""
        return self.n_layers - self.n_mamba_layers

    @property
    def n_mamba_layers(self) -> int:
        return 0 if self.layer_types is None else self.layer_types.count("mamba")

    @property
    def layer_kinds(self) -> tuple[str, ...]:
        """Every layer's kind: ``layer_types``, or "attention" throughout."""
        return self.layer_types or ("attention",) * self.n_layers

    @property
    def window_layers(self) -> tuple[int, ...]:
        """Which of the attention layers, counted among themselves, keep
        only their window, in a ring by row ("sliding_attention")."""
        kinds = [k for k in self.layer_kinds if k != "mamba"]
        return tuple(i for i, k in enumerate(kinds) if k == "sliding_attention")

    @property
    def paged_layers(self) -> tuple[int, ...]:
        """Which of the attention layers keep every token's K/V, by page."""
        ring = set(self.window_layers)
        return tuple(i for i in range(self.n_attention_layers) if i not in ring)

    @property
    def paged_window(self) -> int | None:
        """The window masked over K/V that are kept by page:
        ``sliding_window`` where the attention layers are not told apart,
        none where they are (the window layers then keep rings, and the
        full layers see everything)."""
        told_apart = {"full_attention", "sliding_attention"} & set(self.layer_kinds)
        return None if told_apart else self.sliding_window

    @property
    def layer_period(self) -> int:
        """The shortest period of ``layer_types`` after the leading dense
        layers (1 where it has none: a pattern of one attention layer): the
        layers one iteration of the scan unrolls. The layers need not be a
        whole number of periods: what is left over, the start of one more
        period, runs unrolled after the scan (a dense layer 0 before
        ``[sliding x 3, full] x 12`` leaves 11 periods of 4 and 3 layers).
        Of the lengths the pattern repeats at, the one that unrolls fewest
        layers in all, and of two such the one that leaves none over."""
        if self.layer_types is None:
            return 1
        types_ = self.layer_types[self.n_dense_layers:]
        n = len(types_)
        repeats = [
            p for p in range(1, n + 1)
            if all(types_[i] == types_[i % p] for i in range(n))
        ]
        return min(repeats, key=lambda p: (p + n % p, n % p))

    @property
    def mamba_d_inner(self) -> int:
        return self.mamba_n_heads * self.mamba_d_head

    @property
    def mamba_conv_channels(self) -> int:
        """x, B and C go through the conv together."""
        return self.mamba_d_inner + 2 * self.mamba_n_groups * self.mamba_d_state

    @property
    def kv_heads(self) -> int:
        return self.n_kv_heads or self.n_heads

    @property
    def held_experts(self) -> int:
        """Routed experts whose weights this program holds."""
        if self.moe_held_experts is None:
            return self.n_experts
        return self.moe_held_experts

    @property
    def expert_ff_dim(self) -> int:
        return self.ff_dim if self.moe_d_ff is None else self.moe_d_ff

    @property
    def qk_head_dim(self) -> int:
        """A latent-attention head's query: the part scored against the
        latent's up-projection and the rotary part."""
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def latent_width(self) -> int:
        """What a token keeps a layer in a latent cache: the normed latent
        and the shared rotary key, padded with zeros to whole lane tiles.
        The decode kernel copies whole pages, and Mosaic slices no page
        whose rows are not whole tiles out of the leaf ("Slice shape along
        dimension 4 must be aligned to tiling (128), but is 576", compiled
        for a described v5e; PERF.md, PR 33); in the (8, 128) tiles the
        kernel takes its operand in, a 576-wide row occupies 640 anyway."""
        return -(-(self.kv_lora_rank + self.qk_rope_head_dim) // 128) * 128

    @property
    def yarn(self) -> dict | None:
        return None if self.rope_yarn is None else dict(self.rope_yarn)

    @property
    def ff_dim(self) -> int:
        if self.d_ff is not None:
            return self.d_ff
        # SwiGLU sizing, rounded to 256 for MXU-friendly tiles
        raw = int(8 * self.d_model / 3)
        return (raw + 255) // 256 * 256

    @property
    def moe_exact(self) -> bool:
        """True when per-request outputs are bitwise independent of batch
        composition — dense configs always; MoE configs under dropless
        per-token routing (moe_dropless + moe_group_size=1: no capacity
        eviction, and the expert einsums see the pool only as a batch
        dim), and always under the sorted dispatch (``moe_scoring``
        "sigmoid"), which drops nothing and computes a (token, expert)
        pair's row from that token alone. The exactness-claiming features (serving solo-equality,
        prefix cache, speculative verify, beam rescoring) key on this;
        dropless with larger groups is deterministic and ulp-stable but
        reduction tiling varies with pool shape, so near-exact logit ties
        could flip a token."""
        return self.n_experts == 0 or self.moe_scoring == "sigmoid" or (
            self.moe_dropless and self.moe_group_size == 1
        )

    @classmethod
    def tiny(cls) -> "TransformerConfig":
        """Test/dry-run size."""
        return cls(vocab_size=256, d_model=64, n_layers=2, n_heads=4,
                   max_seq_len=128, d_ff=128)

    @classmethod
    def tiny_moe(cls) -> "TransformerConfig":
        """Test/dry-run MoE size (4 experts, top-2 routing)."""
        return cls(vocab_size=256, d_model=64, n_layers=2, n_heads=4,
                   max_seq_len=128, d_ff=128, n_experts=4)

    @classmethod
    def llama3_8b(cls) -> "TransformerConfig":
        """The BASELINE.json flagship config (Llama-3-8B shapes)."""
        return cls(vocab_size=128256, d_model=4096, n_layers=32, n_heads=32,
                   n_kv_heads=8, d_ff=14336, max_seq_len=8192)

    @classmethod
    def mixtral_8x7b(cls) -> "TransformerConfig":
        """Flagship MoE config (Mixtral-8x7B shapes: 8 experts, top-2)."""
        return cls(vocab_size=32000, d_model=4096, n_layers=32, n_heads=32,
                   n_kv_heads=8, d_ff=14336, max_seq_len=8192,
                   n_experts=8, moe_top_k=2)


# ---------------------------------------------------------------- components


def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-5) -> jax.Array:
    xf = x.astype(jnp.float32)
    norm = xf * lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (norm * scale.astype(jnp.float32)).astype(x.dtype)


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention temperature: 0.1 mscale ln(factor) + 1."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_frequencies(d: int, theta: float, yarn: dict) -> jax.Array:
    """The [d/2] rotary frequencies of a ``deepseek_yarn`` group (YaRN,
    "NTK-by-parts"): a dimension that turns more than ``beta_fast`` times
    within the original context keeps its frequency, one that turns fewer
    than ``beta_slow`` times has it divided by ``factor``, and a linear ramp
    over the dimensions between blends the two."""
    plain = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    original = yarn["original_max_position_embeddings"]

    def turns_at(rotations):  # the dimension that turns so often
        return d * math.log(original / (rotations * 2 * math.pi)) / (
            2 * math.log(theta)
        )

    low = max(math.floor(turns_at(yarn["beta_fast"])), 0)
    high = min(math.ceil(turns_at(yarn["beta_slow"])), d - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip(
        (jnp.arange(d // 2, dtype=jnp.float32) - low) / (high - low), 0.0, 1.0
    )
    return plain / yarn["factor"] * ramp + plain * (1.0 - ramp)


def rope(
    x: jax.Array, positions: jax.Array, theta: float, scaling: float = 1.0,
    yarn: dict | None = None,
) -> jax.Array:
    """Rotary embeddings over [B, H, L, D_head] with positions [B, L].

    ``scaling`` > 1 is linear position interpolation (Chen et al. — effective
    position = position / scaling), the simple context-extension recipe: a
    model trained at L runs at scaling·L with positions compressed back into
    the trained range. ``yarn`` (a ``deepseek_yarn`` group) takes
    ``yarn_frequencies`` in place of the plain ones and scales cos and sin
    by mscale(factor, mscale) / mscale(factor, mscale_all_dim)."""
    if scaling <= 0:
        raise ValueError(f"rope scaling must be > 0, got {scaling}")
    d = x.shape[-1]
    amplitude = 1.0
    if yarn is None:
        freqs = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)  # [d/2]
    else:
        freqs = yarn_frequencies(d, theta, yarn)
        amplitude = yarn_mscale(yarn["factor"], yarn.get("mscale", 1)) / (
            yarn_mscale(yarn["factor"], yarn.get("mscale_all_dim", 0))
        )
    scaled = positions.astype(jnp.float32) / scaling
    angles = scaled[:, None, :, None] * freqs  # [B,1,L,d/2]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    if amplitude != 1.0:
        cos, sin = cos * amplitude, sin * amplitude
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    rotated = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return rotated.astype(x.dtype)


def qeinsum(spec: str, x: jax.Array, leaf, dtype) -> jax.Array:
    """Einsum against a weight LEAF that is either a plain array or a
    weight-only-int8 dict ({"q", "s"} — ops/weight_quant.py). Quantized
    leaves compute ``(x @ q) * s``: the per-out-channel scale applied as
    the matmul epilogue (exact algebra), so the int8→compute-dtype convert
    fuses into the dot and no dequantized copy materializes. The ONE
    dispatch point every dense projection in forward/decode shares, which
    is why the quantized pytree is a drop-in everywhere at once."""
    from bee_code_interpreter_tpu.ops.weight_quant import is_quantized

    if is_quantized(leaf):
        y = jnp.einsum(spec, x, leaf["q"].astype(dtype))
        return (y * leaf["s"]).astype(dtype)
    return jnp.einsum(spec, x, leaf.astype(dtype))


# ------------------------------------------------------------------- weights


def init_params(config: TransformerConfig, key: jax.Array) -> Params:
    """f32 master params; stacked [n_layers, ...] leading axis for lax.scan.

    Leading dense layers of an expert model (``config.n_dense_layers``) are
    stacked under ``dense_layers`` and ``layers`` holds the expert layers
    after them. With latent attention (``config.kv_lora_rank``) an attention
    layer's leaves are ``LATENT_LEAVES``.
    With a layer pattern (``config.layer_types``) every layer-stacked leaf
    still sits under ``layers``, each stacked over the layers that have it:
    the norms and the MLP over all of them, ``wq wk wv wo`` over the
    attention layers, the mixer's leaves over the mamba layers. A tied head
    (``tie_embeddings``) has no ``lm_head`` leaf."""
    c = config
    k_embed, k_layers, k_out = jax.random.split(key, 3)

    def dense(key, fan_in, *shape):
        return jax.random.normal(key, shape, dtype=jnp.float32) / math.sqrt(fan_in)

    def attention(ks):
        if c.kv_lora_rank:
            # wq a head's [nope | rope] query; w_kva the latent beside the
            # shared rotary key; w_kvb a head's [k_nope | v] from the latent
            rank, per_head = c.kv_lora_rank, c.qk_nope_head_dim + c.v_head_dim
            out = {
                "wq": dense(ks[0], c.d_model, c.d_model, c.n_heads * c.qk_head_dim),
                "w_kva": dense(
                    ks[1], c.d_model, c.d_model, rank + c.qk_rope_head_dim
                ),
                "ln_kv": jnp.ones((rank,), jnp.float32),
                "w_kvb": dense(ks[2], rank, rank, c.n_heads * per_head),
                "wo": dense(
                    ks[3], c.n_heads * c.v_head_dim,
                    c.n_heads * c.v_head_dim, c.d_model,
                ),
            }
            if c.qk_norm:
                out["ln_q"] = jnp.ones((c.qk_head_dim,), jnp.float32)
            return out
        dh, kvh = c.head_dim, c.kv_heads
        out = {
            "wq": dense(ks[0], c.d_model, c.d_model, c.n_heads * dh),
            "wk": dense(ks[1], c.d_model, c.d_model, kvh * dh),
            "wv": dense(ks[2], c.d_model, c.d_model, kvh * dh),
            "wo": dense(ks[3], c.n_heads * dh, c.n_heads * dh, c.d_model),
        }
        if c.qk_norm:
            out["ln_q"] = jnp.ones((dh,), jnp.float32)
            out["ln_k"] = jnp.ones((dh,), jnp.float32)
        return out

    def mlp(ks, experts=bool(c.n_experts)):
        out = {
            "ln1": jnp.ones((c.d_model,), jnp.float32),
            "ln2": jnp.ones((c.d_model,), jnp.float32),
        }
        if experts and c.moe_scoring == "sigmoid":
            from bee_code_interpreter_tpu.models.moe import init_held_params

            out["moe"] = init_held_params(ks[0], c)
        elif experts:
            from bee_code_interpreter_tpu.models.moe import init_moe_params

            out["moe"] = init_moe_params(ks[0], c.d_model, c.ff_dim, c.n_experts)
        else:
            out["w_gate"] = dense(ks[0], c.d_model, c.d_model, c.ff_dim)
            out["w_up"] = dense(ks[1], c.d_model, c.d_model, c.ff_dim)
            out["w_down"] = dense(ks[2], c.ff_dim, c.ff_dim, c.d_model)
        return out

    def per_layer(part, n_keys, key, n):
        return jax.vmap(lambda k: part(jax.random.split(k, n_keys)))(
            jax.random.split(key, n)
        )

    dense_layers = None
    if c.layer_types is None:
        def layer(key, **kind):
            ks = jax.random.split(key, 7)
            return {**attention(ks[:4]), **mlp(ks[4:], **kind)}

        keys = jax.random.split(k_layers, c.n_layers)
        stacked = jax.vmap(layer)(keys[c.n_dense_layers:])
        if c.n_dense_layers:
            dense_layers = jax.vmap(functools.partial(layer, experts=False))(
                keys[:c.n_dense_layers]
            )
    else:
        # the leading dense layers, whole, apart; each stack below over the
        # layers after them that have the leaf
        k_attn, k_mamba, k_mlp = jax.random.split(k_layers, 3)
        n_dense = c.n_dense_layers
        stacked = {
            **per_layer(mlp, 3, k_mlp, c.n_layers - n_dense),
            **per_layer(attention, 4, k_attn, c.n_attention_layers - n_dense),
        }
        if n_dense:
            dense_layers = per_layer(
                lambda ks: {**attention(ks[:4]), **mlp(ks[4:], experts=False)},
                7, jax.random.fold_in(k_layers, 1), n_dense,
            )
        if c.n_mamba_layers:
            from bee_code_interpreter_tpu.models.mamba import init_mixer_params

            stacked.update(jax.vmap(
                functools.partial(init_mixer_params, c)
            )(jax.random.split(k_mamba, c.n_mamba_layers)))
    params = {
        "embed": dense(k_embed, c.d_model, c.vocab_size, c.d_model),
        "layers": stacked,
        "ln_f": jnp.ones((c.d_model,), jnp.float32),
    }
    if dense_layers is not None:
        params["dense_layers"] = dense_layers
    if not c.tie_embeddings:
        params["lm_head"] = dense(k_out, c.d_model, c.d_model, c.vocab_size)
    return params


def param_specs(config: TransformerConfig, mesh: Mesh) -> Params:
    """Megatron-style PartitionSpecs over whichever of (fsdp, tp, ep) exist."""
    tp = "tp" if "tp" in mesh.axis_names else None
    fsdp = "fsdp" if "fsdp" in mesh.axis_names else None
    ep = "ep" if "ep" in mesh.axis_names else None

    col = P(fsdp, tp)      # [d_in, d_out/tp] column-parallel
    row = P(tp, fsdp)      # [d_in/tp, d_out] row-parallel
    rep = P()
    layer = {
        "ln1": _stack(rep), "ln2": _stack(rep),
        "wq": _stack(col), "wk": _stack(col), "wv": _stack(col),
        "wo": _stack(row),
    }
    if config.kv_lora_rank:
        # heads over tp; the latent projection and its norm are every chip's
        for name in ("wk", "wv"):
            del layer[name]
        layer.update({
            "w_kva": _stack(rep), "ln_kv": _stack(rep), "w_kvb": _stack(col),
        })
        if config.qk_norm:
            layer["ln_q"] = _stack(rep)
    elif config.qk_norm:
        layer["ln_q"] = layer["ln_k"] = _stack(rep)
    dense_layer = dict(layer)
    if config.n_experts and config.moe_scoring == "sigmoid":
        layer["moe"] = {
            "router": _stack(rep), "we_gate": _stack(P(ep, fsdp, tp)),
            "we_up": _stack(P(ep, fsdp, tp)), "we_down": _stack(P(ep, tp, fsdp)),
        }
        if config.moe_shared_experts:
            layer["moe"].update({
                "ws_gate": _stack(col), "ws_up": _stack(col),
                "ws_down": _stack(row),
            })
    elif config.n_experts:
        # expert axis over ep, expert-internal matmuls Megatron-style
        layer["moe"] = {
            "router": _stack(P(None, None)),  # small; replicated
            "we_gate": _stack(P(ep, fsdp, tp)),
            "we_up": _stack(P(ep, fsdp, tp)),
            "we_down": _stack(P(ep, tp, fsdp)),
        }
    else:
        layer["w_gate"] = _stack(col)
        layer["w_up"] = _stack(col)
        layer["w_down"] = _stack(row)
    if config.n_mamba_layers:
        # the mixer is replicated: its heads share B and C, and the state it
        # keeps by row is whole on every chip (serving refuses tp over it)
        from bee_code_interpreter_tpu.models.mamba import MIXER_LEAVES

        layer.update({name: P() for name in MIXER_LEAVES})
    specs = {
        "embed": P(tp, None),     # vocab-sharded embedding
        "layers": layer,
        "ln_f": rep,
    }
    if config.n_dense_layers:
        specs["dense_layers"] = {
            **dense_layer, "w_gate": _stack(col), "w_up": _stack(col),
            "w_down": _stack(row),
        }
    if not config.tie_embeddings:
        specs["lm_head"] = P(None, tp)   # column-parallel output projection
    return specs


def _stack(spec: P) -> P:
    return P(None, *spec)  # leading n_layers axis is replicated


def shard_params(params: Params, config: TransformerConfig, mesh: Mesh) -> Params:
    """Place params per ``param_specs``. Weight-only-quantized leaves
    ({'q','s'} — ops/weight_quant.py) shard too: q takes the fp weight's
    spec verbatim, and s (per-out-channel, shape = weight shape minus the
    contracted axis) takes the spec with the d_in axis dropped — so a
    tp-column-sharded weight keeps its scales on the same shards and
    qeinsum's epilogue multiply stays local (no collective)."""
    from bee_code_interpreter_tpu.ops.weight_quant import is_quantized

    specs = param_specs(config, mesh)

    def place(x, spec):
        if is_quantized(x):
            s_spec = P(*spec[:-2], spec[-1])
            return {
                "q": jax.device_put(x["q"], NamedSharding(mesh, spec)),
                "s": jax.device_put(x["s"], NamedSharding(mesh, s_spec)),
            }
        return jax.device_put(x, NamedSharding(mesh, spec))

    return jax.tree.map(
        place, params, specs,
        is_leaf=lambda x: is_quantized(x)
        or isinstance(x, jnp.ndarray) or hasattr(x, "shape"),
    )


# ------------------------------------------------------------------- forward


def _local_attention(
    q, k, v, causal: bool = True, window: int | None = None,
    sm_scale: float | None = None,
):
    """Single-shard attention — the shared ops-level platform dispatch
    (Pallas flash on TPU, reference elsewhere; GQA-native)."""
    from bee_code_interpreter_tpu.ops.flash_attention import local_attention

    return local_attention(
        q, k, v, causal=causal, window=window, sm_scale=sm_scale
    )


def _attention(
    q, k, v, mesh: Mesh | None, sp_attention: str = "ring",
    causal: bool = True, window: int | None = None,
    sm_scale: float | None = None,
):
    """Attention (causal by default; ``causal=False`` for encoders — the
    ViT path); q [B, H, L, D], k/v [B, KVH, L, D] (KVH ≤ H). ``sm_scale``
    scales the scores (None = 1/sqrt(D)).

    K/V stay compact through the whole path (flash kernel index-maps KV
    heads, the ring rotates KVH-sized blocks) — GQA never materializes the
    head broadcast, saving H/KVH × KV HBM/ICI traffic.

    With a mesh, runs inside shard_map — batch over dp, heads over tp,
    sequence over sp. Manual SPMD is required here anyway: GSPMD cannot
    partition a pallas_call, and the sp > 1 path needs explicit collectives
    (the ppermute ring, or Ulysses' all-to-alls per ``sp_attention``).
    """
    if sp_attention not in ("ring", "ulysses"):
        raise ValueError(
            f"sp_attention must be 'ring' or 'ulysses', got {sp_attention!r}"
        )
    if mesh is None:
        return _local_attention(q, k, v, causal, window, sm_scale)
    axes = mesh.axis_names
    tp = "tp" if "tp" in axes else None
    has_sp = "sp" in axes and mesh.shape["sp"] > 1
    sp = "sp" if has_sp else None
    if tp is not None and k.shape[1] % mesh.shape["tp"] != 0:
        # KV heads don't split over tp: broadcast up — but only to
        # lcm(KVH, tp), the minimal multiple that shards evenly (both divide
        # n_heads, so the lcm does too and group-major q→kv pairing is
        # preserved); repeating all the way to n_heads would multiply KV
        # HBM/ICI traffic in exactly the KV-bandwidth-bound regime the
        # compact-GQA path exists for
        rep = math.lcm(k.shape[1], mesh.shape["tp"]) // k.shape[1]
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
    spec = P(_batch_axes(mesh), tp, sp, None)

    if has_sp:
        if sm_scale is not None:
            raise NotImplementedError(
                "a caller-given attention scale is not threaded through the "
                "sp ring / Ulysses paths"
            )
        # sliding_window rides both sp strategies: the ring masks per hop in
        # global offsets (parallel/ring_attention.py), Ulysses applies the
        # ordinary local mask after its sequence gather (parallel/ulysses.py)
        if sp_attention == "ulysses":
            from bee_code_interpreter_tpu.parallel.ulysses import (
                ulysses_attention,
            )

            local = functools.partial(
                ulysses_attention, axis_name="sp", causal=causal,
                window=window,
            )
        else:
            local = functools.partial(
                ring_attention, axis_name="sp", causal=causal, window=window
            )
    else:
        local = functools.partial(
            _local_attention, causal=causal, window=window, sm_scale=sm_scale
        )
    # pallas_call under shard_map's vma checking hits a jax-internal lowering
    # limitation (see tests/test_parallel.py flash-ring cases); every
    # uses_flash() branch here runs the kernel (local, flash-hop ring, or
    # inside ulysses), so disable the check exactly there and keep it for
    # the kernel-free CPU paths.
    from bee_code_interpreter_tpu.ops.flash_attention import uses_flash

    uses_pallas = uses_flash()
    fn = jax.shard_map(
        local, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=not uses_pallas,
    )
    return fn(q, k, v)


def _positioned(x, positions, config: TransformerConfig, kind: str = "attention"):
    """q or k of an attention layer of ``kind`` with the configuration's
    position embedding applied: rotary, nothing at all (the published
    "nope"), or rotary in the window layers alone ("rope_window")."""
    if config.position_embedding == "nope" or (
        config.position_embedding == "rope_window" and kind != "sliding_attention"
    ):
        return x
    return rope(
        x, positions, config.rope_theta, config.rope_scaling, config.yarn
    )


def _kind_window(config: TransformerConfig, kind: str) -> int | None:
    """The window an attention layer of ``kind`` attends within:
    ``sliding_window`` in a "sliding_attention" layer and in the layers of a
    model that does not tell them apart ("attention"), none in a
    "full_attention" layer."""
    if kind == "sliding_attention":
        return config.sliding_window
    return config.paged_window


def _head_qk(x, layer, scale: str, positions, config, kind: str = "attention"):
    """A layer's projected queries (``scale`` "ln_q") or keys ("ln_k") [B,
    heads, L, dh] as attention takes them: under ``qk_norm`` each head
    RMS-normed over its own values, with a scale of its own, then the
    position embedding of a layer of ``kind``."""
    if config.qk_norm:
        x = rms_norm(x, layer[scale], config.rms_norm_eps)
    return _positioned(x, positions, config, kind)


def _project_heads(x, w, heads: int, config: TransformerConfig, delta=None):
    """The normed ``x`` [B, L, D] through a projection into ``heads`` heads:
    ONE flat matmul against ``w`` [D, heads * dh] (a plain or weight-only-int8
    leaf; ``delta``, a LoRA's [B, L, heads * dh], added to its result), then
    split into heads, [B, heads, L, dh].

    The split sits behind ``lax.optimization_barrier`` so that the flat
    result is materialised first. Left to itself the TPU compiler folds the
    split into the dot, which then wants its weight head-major with D minor,
    where the stacks hold [layers, D, heads * dh]: each layer of each step it
    cut the layer out of the stack into a buffer, copied the buffer
    transposed and ran the dot from the copy (1.1 ms of a 13.6 ms decode
    step at Mistral-7B's widths, 3.2 of 16.2 at K-EXAONE's; PERF.md, PR 36).
    Kept flat, the dot reads its layer of the stack where it lies, the slice
    fused into it as for ``wo`` and the MLP. The barrier is the identity, to
    a gradient too."""
    B, L = x.shape[:2]
    out = lax.optimization_barrier(qeinsum("bld,dk->blk", x, w, config.dtype))
    if delta is not None:
        out = out + delta
    return out.reshape(B, L, heads, -1).transpose(0, 2, 1, 3)


def _residual(h, branch, config: TransformerConfig):
    """h + residual_multiplier * branch (the multiplier is 1 for every
    architecture but Granite's, and then nothing is multiplied)."""
    if config.residual_multiplier == 1.0:
        return h + branch
    return h + branch * jnp.asarray(config.residual_multiplier, branch.dtype)


def _layer_apply(
    h: jax.Array,  # [B, L, D]
    layer: Params,
    config: TransformerConfig,
    positions: jax.Array,  # [B, L]
    *,
    mesh: Mesh | None = None,
    constrain=lambda x: x,
    return_kv: bool = False,
    kind: str = "attention",
) -> tuple[jax.Array, tuple | None, jax.Array]:
    """One decoder layer — THE single source of the layer math, shared by
    ``forward`` (mesh attention + sharding constraints via the hooks) and
    ``forward_pipelined`` (single-shard defaults). ``kind`` (static, one of
    ``ATTENTION_KINDS``) decides the layer's window and whether its q and k
    are rotated. Returns (h, kv_out | None, aux-loss scalar)."""
    c = config
    B, L = h.shape[0], h.shape[1]
    x = rms_norm(h, layer["ln1"], c.rms_norm_eps)
    if c.kv_lora_rank:
        q_nope, q_rope, latent = _latent_projections(x, layer, c, positions)
        # what a token keeps: the latent beside the shared rotary key
        kv_out = (_pad_latent(latent, c),) if return_kv else None
        attn = _latent_attention_published(q_nope, q_rope, latent, layer, c, mesh)
    else:
        dh, nh, kvh = c.head_dim, c.n_heads, c.kv_heads
        q = _head_qk(
            _project_heads(x, layer["wq"], nh, c), layer, "ln_q", positions, c, kind
        )
        k = _head_qk(
            _project_heads(x, layer["wk"], kvh, c), layer, "ln_k", positions, c, kind
        )
        v = _project_heads(x, layer["wv"], kvh, c)
        kv_out = (k, v) if return_kv else None
        # GQA-native: compact k/v go in as-is
        attn = _attention(
            q, k, v, mesh, c.sp_attention, window=_kind_window(c, kind),
            sm_scale=c.attention_multiplier,
        )
        attn = attn.transpose(0, 2, 1, 3).reshape(B, L, nh * dh)
    h = _residual(
        h, constrain(qeinsum("blk,kd->bld", attn, layer["wo"], c.dtype)), c
    )
    h, aux = _mlp_residual(h, layer, c, constrain)
    return h, kv_out, aux


def _latent_projections(x, layer, config: TransformerConfig, positions):
    """Latent attention's projections of the normed ``x`` [B, L, D]: a head's
    query as ``q_nope`` [B, nh, L, qk_nope] and the rotated ``q_rope`` [B,
    nh, L, qk_rope] (the whole query normed first under ``qk_norm``), and
    what a token keeps, [B, L, kv_lora_rank + qk_rope]: the normed latent
    beside the one rotated key all heads share."""
    c = config
    rank, eps = c.kv_lora_rank, c.rms_norm_eps
    q = _project_heads(x, layer["wq"], c.n_heads, c)  # [B, nh, L, qk_head_dim]
    if c.qk_norm:
        q = rms_norm(q, layer["ln_q"], eps)
    q_nope, q_rope = q[..., :c.qk_nope_head_dim], q[..., c.qk_nope_head_dim:]
    kva = qeinsum("bld,dk->blk", x, layer["w_kva"], c.dtype)
    latent = rms_norm(kva[..., :rank], layer["ln_kv"], eps)
    k_rope = _positioned(kva[:, None, :, rank:], positions, c)[:, 0]
    return (
        q_nope, _positioned(q_rope, positions, c),
        jnp.concatenate([latent, k_rope], axis=-1),
    )


def _pad_latent(latent, config: TransformerConfig):
    """A token's latent and rotary key at the width the pool keeps them."""
    pad = config.latent_width - latent.shape[-1]
    return jnp.pad(latent, ((0, 0),) * (latent.ndim - 1) + ((0, pad),))


def _kvb_by_head(layer, config: TransformerConfig):
    """``w_kvb`` as [kv_lora_rank, heads, qk_nope + v]: a head's key and
    value up-projections side by side."""
    c = config
    return layer["w_kvb"].astype(c.dtype).reshape(
        c.kv_lora_rank, c.n_heads, c.qk_nope_head_dim + c.v_head_dim
    )


def _latent_attention_published(q_nope, q_rope, latent, layer, config, mesh):
    """The published form, over whole sequences: K and V made per head from
    the latent, the shared rotary key beside every head's, then ordinary
    causal attention at a query of qk_nope + qk_rope and a value of v_head_dim
    (the flash kernel on a TPU): [B, L, heads * v_head_dim]."""
    c = config
    B, nh, L, dn = q_nope.shape
    rank = c.kv_lora_rank
    kv = jnp.einsum(
        "blc,chd->bhld", latent[..., :rank], _kvb_by_head(layer, c)
    )
    k_rope = jnp.broadcast_to(
        latent[:, None, :, rank:], (B, nh, L, c.qk_rope_head_dim)
    )
    attn = _attention(
        jnp.concatenate([q_nope, q_rope], axis=-1),
        jnp.concatenate([kv[..., :dn], k_rope], axis=-1), kv[..., dn:],
        mesh, c.sp_attention, sm_scale=_score_scale(c),
    )
    return attn.transpose(0, 2, 1, 3).reshape(B, L, nh * c.v_head_dim)


def _absorbed_query(q_nope, q_rope, layer, config: TransformerConfig):
    """The absorbed form's query, [B, heads, W, latent_width]: a head's
    q_nope through its key up-projection (so that it scores against the
    cached latent itself), its rotary part, and zeros over the pool's pad."""
    c = config
    w_uk = _kvb_by_head(layer, c)[..., :c.qk_nope_head_dim]
    q_latent = jnp.einsum("bhwd,chd->bhwc", q_nope, w_uk)
    return _pad_latent(jnp.concatenate([q_latent, q_rope], axis=-1), c)


def _absorbed_output(o_latent, layer, config: TransformerConfig):
    """The attention-weighted latents [B, heads, W, kv_lora_rank] through
    each head's value up-projection: [B, W, heads * v_head_dim]."""
    c = config
    B, nh, W, _ = o_latent.shape
    w_uv = _kvb_by_head(layer, c)[..., c.qk_nope_head_dim:]
    out = jnp.einsum("bhwc,chd->bwhd", o_latent.astype(c.dtype), w_uv)
    return out.reshape(B, W, nh * c.v_head_dim)


def _mlp_residual(h, layer, config, constrain=lambda x: x):
    """The MLP half of every layer kind: (h + mlp(rmsnorm(h)), aux)."""
    y = rms_norm(h, layer["ln2"], config.rms_norm_eps)
    mlp, aux = _mlp_block(y, layer, config)
    return _residual(h, constrain(mlp), config), aux


def _mamba_layer_apply(h, layer, config, length, constrain=lambda x: x):
    """One mamba layer over whole sequences: (h, (state at ``length``, conv
    tail before it)). See models/mamba.py."""
    from bee_code_interpreter_tpu.models.mamba import mixer_prefill

    mix, state, tail = mixer_prefill(
        rms_norm(h, layer["ln1"], config.rms_norm_eps), layer, config, length
    )
    h, _ = _mlp_residual(_residual(h, constrain(mix), config), layer, config, constrain)
    return h, (state, tail)


def _mlp_block(
    y: jax.Array, layer: Params, config: TransformerConfig
) -> tuple[jax.Array, jax.Array]:
    """The post-attention MLP (dense SwiGLU or MoE) — ONE copy shared by
    _layer_apply, decode_window (and through it decode_step), and
    decode_step_paged. Returns (mlp_out, aux) with aux = 0.0 for dense
    configs (decode paths drop it)."""
    c = config
    if "moe" in layer and c.moe_scoring == "sigmoid":
        from bee_code_interpreter_tpu.models.moe import held_experts_mlp

        return held_experts_mlp(layer["moe"], y, c), jnp.float32(0.0)
    if "moe" in layer:
        from bee_code_interpreter_tpu.models.moe import moe_mlp

        return moe_mlp(
            layer["moe"], y,
            n_experts=c.n_experts, top_k=c.moe_top_k,
            capacity_factor=c.moe_capacity_factor, dtype=c.dtype,
            group_size=c.moe_group_size, dropless=c.moe_dropless,
        )
    gate = qeinsum("bld,df->blf", y, layer["w_gate"], c.dtype)
    up = qeinsum("bld,df->blf", y, layer["w_up"], c.dtype)
    mlp = qeinsum("blf,fd->bld", jax.nn.silu(gate) * up, layer["w_down"], c.dtype)
    return mlp, jnp.float32(0.0)


def _batch_axes(mesh: Mesh | None):
    """Activation batch dim shards over every data-parallel-ish axis present
    (shared policy: parallel.mesh.batch_axes)."""
    from bee_code_interpreter_tpu.parallel.mesh import batch_axes

    return batch_axes(mesh)


def _embed(params: Params, tokens, config: TransformerConfig):
    h = params["embed"].astype(config.dtype)[tokens]
    if config.embedding_multiplier != 1.0:
        h = h * jnp.asarray(config.embedding_multiplier, h.dtype)
    return h


def _head(params: Params, h, config: TransformerConfig):
    """Final norm and output head: logits [B, L, vocab] in float32. A tied
    head multiplies by the embedding transposed; ``logits_scaling`` divides
    the result."""
    c = config
    h = rms_norm(h, params["ln_f"], c.rms_norm_eps)
    if c.tie_embeddings:
        logits = qeinsum("bld,vd->blv", h, params["embed"], c.dtype)
    else:
        logits = qeinsum("bld,dv->blv", h, params["lm_head"], c.dtype)
    logits = logits.astype(jnp.float32)
    if c.logits_scaling != 1.0:
        logits = logits / c.logits_scaling
    return logits


def _pool_part(kind: str) -> tuple[str, ...]:
    """The layer kinds that keep what they keep in the same part of the pool
    as ``kind``: recurrent state by row, a ring by row, or pages."""
    if kind in ("mamba", "sliding_attention"):
        return (kind,)
    return ("attention", "full_attention")


def _pattern_layer(layers: Params, config: TransformerConfig, period_index, j):
    """Layer ``j`` of period ``period_index`` (traced) of the layer pattern
    after the leading dense layers (without ``layer_types``: a period of one
    attention layer): its kind, its index among the layers that share its
    part of the pool (``_pool_part``; the leading dense layers counted), and
    its leaves taken out of the stacks (the norms and MLP are stacked over
    all layers, the mixer's leaves over the mamba layers, the attention
    leaves over the attention layers of every kind)."""
    from bee_code_interpreter_tpu.models.mamba import MIXER_LEAVES

    c = config
    first = c.layer_kinds[:c.n_dense_layers]
    period = c.layer_kinds[c.n_dense_layers:c.n_dense_layers + c.layer_period]
    kind = period[j]

    def among(kinds, upto=None):
        """Where this layer stands among the layers of ``kinds`` (from
        layer ``upto`` on: the stacks hold what follows the dense ones)."""
        count = lambda layers: sum(k in kinds for k in layers)  # noqa: E731
        return (
            count(upto or ()) + period_index * count(period) + count(period[:j])
        )

    if kind == "mamba":
        own, in_stack = MIXER_LEAVES, among(("mamba",))
    else:
        own = LATENT_LEAVES if c.kv_lora_rank else ATTENTION_LEAVES
        in_stack = among(ATTENTION_KINDS)
    every = period_index * len(period) + j

    layer = {
        name: _take_layer(layers[name], in_stack) for name in own if name in layers
    }
    for name in layers:
        if name in MIXER_LEAVES + ATTENTION_LEAVES + LATENT_LEAVES:
            continue
        if name == "moe" and c.moe_scoring == "sigmoid":
            from bee_code_interpreter_tpu.models.moe import take_held_layer

            layer[name] = take_held_layer(layers[name], every)
        else:
            layer[name] = _take_layer(layers[name], every)
    return kind, among(_pool_part(kind), first), layer


def _take_layer(stacked, index):
    """One layer's leaves out of a tree stacked over layers."""
    return jax.tree.map(
        lambda x: lax.dynamic_index_in_dim(x, index, 0, keepdims=False), stacked
    )


def _n_periods(config: TransformerConfig) -> tuple[int, int]:
    """(iterations of the layer scan: the whole periods of a declared
    pattern, or the layers after the leading dense ones; the layers left
    over after them, the start of one more period)."""
    return divmod(config.n_layers - config.n_dense_layers, config.layer_period)


def _scans_by_index(config: TransformerConfig) -> bool:
    """Whether the layer scan takes each layer's leaves out of the stacks at
    its index (a declared pattern, whose kinds are stacked apart; leading
    dense layers, which run before it; held experts, whose stacks go to
    the grouped matmul whole) where a plain decoder scans the stacks as
    ``xs``."""
    c = config
    return (
        c.layer_types is not None or bool(c.n_dense_layers)
        or (bool(c.n_experts) and c.moe_scoring == "sigmoid")
    )


def _one_layer_kind(config: TransformerConfig, what: str) -> None:
    if _scans_by_index(config) or config.kv_lora_rank:
        raise NotImplementedError(
            f"{what} runs one layer kind under its scan, K and V per head: "
            "a declared layer pattern, leading dense layers, held experts "
            "and a latent cache run through forward and decode_step_paged"
        )


def _scaled_scores(scores, config: TransformerConfig):
    """Decode-path attention scores, scaled: by ``attention_multiplier``
    where the configuration gives one, else divided by sqrt(head_dim)."""
    if config.attention_multiplier is not None:
        return scores * config.attention_multiplier
    return scores / math.sqrt(config.head_dim)


def _score_scale(config: TransformerConfig) -> float:
    """``_scaled_scores`` as the one factor a kernel multiplies by. Latent
    attention scales by the whole query's width, and under YaRN by
    mscale(factor, mscale_all_dim) squared."""
    if config.attention_multiplier is not None:
        return config.attention_multiplier
    if config.kv_lora_rank:
        scale = 1.0 / math.sqrt(config.qk_head_dim)
        yarn = config.yarn
        if yarn is not None:
            scale *= yarn_mscale(yarn["factor"], yarn.get("mscale_all_dim", 0)) ** 2
        return scale
    return 1.0 / math.sqrt(config.head_dim)


def forward(
    params: Params,
    tokens: jax.Array,  # [B, L] int32
    config: TransformerConfig,
    mesh: Mesh | None = None,
    return_kv: bool = False,
    return_aux: bool = False,
    length: jax.Array | None = None,
) -> jax.Array | tuple:
    """Returns logits [B, L, vocab] (f32).

    With ``return_kv`` (the prefill half of cached decoding), also returns the
    per-layer post-RoPE K/V stacked [n_layers, B, kv_heads, L, head_dim] —
    pre-GQA-broadcast, so the cache stores kv_heads not n_heads. Under a
    layer pattern with mamba layers K and V are stacked over the attention
    layers alone and the tuple goes on with what the mamba layers keep:
    (k, v, ssm [mamba layers, B, heads, head size, state] f32, conv [mamba
    layers, B, d_conv - 1, channels]), both at the sequences' true
    ``length`` (a traced int32 scalar; None = L): positions at or beyond it
    leave the state untouched, so a prompt padded to a page multiple seeds
    the state of its real tokens.
    With ``return_aux`` (MoE training), also returns the summed per-layer
    load-balancing auxiliary loss (0.0 for dense configs).
    """
    c = config
    use_ring = mesh is not None and "sp" in mesh.axis_names and (
        mesh.shape["sp"] > 1
    )

    def act_spec(*spec):  # noqa: D401
        if mesh is None:
            return None
        return NamedSharding(mesh, P(*spec))

    def constrain(x, *spec):
        if mesh is None:
            return x
        return lax.with_sharding_constraint(x, act_spec(*spec))

    B, L = tokens.shape
    sp = "sp" if use_ring else None
    batch_ax = _batch_axes(mesh)
    positions = jnp.broadcast_to(jnp.arange(L, dtype=jnp.int32), (B, L))

    h = _embed(params, tokens, c)  # [B, L, D]
    h = constrain(h, batch_ax, sp, None)
    constrain_h = lambda x: constrain(x, batch_ax, sp, None)  # noqa: E731

    def layer_step(h, layer, kind="attention"):
        h, kv_out, aux = _layer_apply(
            h, layer, c, positions,
            mesh=mesh,
            constrain=constrain_h,
            return_kv=return_kv,
            kind=kind,
        )
        return h, (kv_out, aux)

    def period_step(h, period_index, n_layers=c.layer_period):
        """One period of a declared pattern (or one layer taken out of the
        stacks at its index), its layers (the first ``n_layers``) unrolled."""
        kv, state = [], []
        for j in range(n_layers):
            kind, _, layer = _pattern_layer(params["layers"], c, period_index, j)
            if kind == "mamba":
                h, kept = _mamba_layer_apply(h, layer, c, length, constrain_h)
                state.append(kept)
            else:
                h, kept, _ = _layer_apply(
                    h, layer, c, positions, mesh=mesh, constrain=constrain_h,
                    return_kv=return_kv, kind=kind,
                )
                kv.append(kept)
        stack = lambda xs: tuple(jnp.stack(x) for x in zip(*xs))  # noqa: E731
        return h, (stack(kv), stack(state)) if return_kv else None

    if not _scans_by_index(c):
        h, (kv, aux_layers) = lax.scan(layer_step, h, params["layers"])
    else:
        dense_kv = []
        for i in range(c.n_dense_layers):
            h, (kept, _) = layer_step(
                h, _take_layer(params["dense_layers"], i), c.layer_kinds[i]
            )
            dense_kv.append(kept)
        periods, left_over = _n_periods(c)
        h, kept = lax.scan(period_step, h, jnp.arange(periods))
        if left_over:
            h, last = period_step(h, periods, left_over)
        aux_layers = jnp.zeros((), jnp.float32)
        if return_kv:  # [periods, a period's layers of a kind, ...] -> [layers, ...]
            kept = [
                tuple(x.reshape(-1, *x.shape[2:]) for x in part) for part in kept
            ]
            if left_over:  # (a kind the last layers lack adds nothing)
                kept = [
                    tuple(jnp.concatenate(xs) for xs in zip(part, more))
                    if more else part for part, more in zip(kept, last)
                ]
            kv = tuple(x for part in kept for x in part)
            if dense_kv:  # the leading layers' first
                kv = tuple(
                    jnp.concatenate([jnp.stack(first), rest])
                    for first, rest in zip(zip(*dense_kv), kv)
                )
    logits = _head(params, h, c)
    extras = []
    if return_kv:
        extras.append(kv)
    if return_aux:
        extras.append(aux_layers.sum())
    if extras:
        return (logits, *extras)
    return logits


# -------------------------------------------------------------- pipelined fwd


def forward_pipelined(
    params: Params,
    tokens: jax.Array,  # [B, L] int32
    config: TransformerConfig,
    mesh: Mesh,
    n_microbatches: int,
    return_aux: bool = False,
) -> jax.Array | tuple:
    """Pipeline-parallel forward: the layer stack sharded over the mesh's
    ``pp`` axis, microbatches (batch-dim splits) streamed through the GPipe
    schedule (parallel/pipeline.py); batch additionally shards over dp/fsdp
    axes when present. Embedding / final norm / lm head run outside the
    pipeline. Differentiable — ``jax.grad`` through this is pipeline-parallel
    training. tp/sp inside stages would need nested shard_map; use the
    non-pipelined ``forward`` for those axes instead.

    MoE configs ride the pipeline's aux carry: each stage returns its
    layers' load-balancing loss, masked to real (non-bubble) ticks and
    averaged over microbatches (``with_aux`` in spmd_pipeline) — equal to a
    sequential per-microbatch forward. Note routing pools are per
    microbatch: under capacity pressure tokens compete within their
    microbatch, not the full batch, so logits match the non-pipelined
    ``forward`` only drop-free (ample capacity) — the same caveat as cached
    decode (see ``generate_cached``)."""
    from bee_code_interpreter_tpu.parallel.pipeline import spmd_pipeline

    c = config
    if c.n_experts and not return_aux:
        # training MoE without the load-balancing term drives experts toward
        # collapse; fail loudly rather than silently discard it (inference
        # callers pass return_aux=True and drop the scalar)
        raise ValueError(
            "MoE configs require return_aux=True on forward_pipelined: the "
            "load-balancing aux loss must reach the objective"
        )
    _one_layer_kind(c, "forward_pipelined")
    B, L = tokens.shape
    if B % n_microbatches != 0:
        raise ValueError(
            f"batch {B} not divisible into {n_microbatches} microbatches"
        )

    h = _embed(params, tokens, c)  # [B, L, D]

    batch_axes = _batch_axes(mesh) or ()

    def stage(h, layer):
        # batch-dim microbatching: absolute positions are simply 0..L-1 for
        # every row, whatever shard of the batch this stage holds
        pos = jnp.broadcast_to(
            jnp.arange(h.shape[1], dtype=jnp.int32), h.shape[:2]
        )
        h, _, aux = _layer_apply(h, layer, c, pos)
        return h, aux

    h, aux = spmd_pipeline(
        stage, params["layers"], h,
        mesh=mesh, n_microbatches=n_microbatches, batch_axes=batch_axes,
        with_aux=True,
    )
    logits = _head(params, h, c)
    if return_aux:
        return logits, aux
    return logits


# ------------------------------------------------------------- cached decode


def alloc_decode_cache(
    config: TransformerConfig, B: int, total_len: int
) -> dict:
    """Zeroed decode cache in the configured layout. bf16 stores values
    directly; int8 adds per-(token, head) scale leaves — the presence of
    scales is what selects the quantized strategy in ops/kv_cache.py."""
    c = config
    shape = (c.n_layers, B, c.kv_heads, total_len, c.head_dim)
    if c.kv_cache_dtype == "int8":
        return {
            "k": jnp.zeros(shape, jnp.int8),
            "v": jnp.zeros(shape, jnp.int8),
            "k_s": jnp.zeros(shape[:-1] + (1,), jnp.float32),
            "v_s": jnp.zeros(shape[:-1] + (1,), jnp.float32),
        }
    return {"k": jnp.zeros(shape, c.dtype), "v": jnp.zeros(shape, c.dtype)}


def init_decode_cache(
    config: TransformerConfig,
    B: int,
    total_len: int,
    k_pre: jax.Array,  # [n_layers, B, kvh, L_prompt, Dh] (prefill K)
    v_pre: jax.Array,
) -> dict:
    """Allocate the full-length decode cache and seed it with the prefill
    K/V through the same append strategy the decode bodies use."""
    from bee_code_interpreter_tpu.ops.kv_cache import cache_append

    return cache_append(
        alloc_decode_cache(config, B, total_len), k_pre, v_pre, 0
    )


def decode_step(
    params: Params,
    token: jax.Array,  # [B, 1] int32 — the token just produced/fed
    pos: jax.Array,  # scalar int32: its position in the sequence
    cache: dict,  # init_decode_cache layout; leaves [n_layers, B, kvh, max, ·]
    config: TransformerConfig,
) -> tuple[jax.Array, dict]:
    """One incremental decode step: O(L) attention against the cache instead
    of the O(L^2) full re-encode (the round-1 generate). Static shapes: the
    cache is allocated at its final length and masked by position, so the
    whole decode loop is one compiled program.

    Runs with plain einsum attention (no pallas/shard_map): a 1-token query
    is MXU-trivial and GSPMD can shard these einsums over tp on its own.
    With ``kv_cache_dtype="int8"`` the cache stays int8 in HBM (half the
    bytes the bandwidth-bound loop streams); dequantization rides the
    attention einsums' operand pipeline.

    This IS ``decode_window`` with W=1 for both cache layouts — ONE layer
    body (cache strategy selected by ops/kv_cache.cache_append/cache_read),
    so the int8 and bf16 decode math cannot drift apart.
    """
    return decode_window(params, token, pos, cache, config)


def decode_window(
    params: Params,
    tokens: jax.Array,  # [B, W] int32 — W consecutive tokens
    pos0: jax.Array,  # scalar int32: position of tokens[:, 0]
    cache: dict,  # init_decode_cache layout
    config: TransformerConfig,
    mesh: Mesh | None = None,
) -> tuple[jax.Array, dict]:
    """Multi-token cached decode: like ``decode_step`` but for a window of
    ``W`` consecutive tokens at positions ``pos0..pos0+W-1`` — one forward
    over the window with causal masking against the (updated) cache. This
    is speculative decoding's verify step: the target model scores a
    drafted window in ONE pass instead of W sequential steps.

    Static shapes throughout (W is static; ``pos0`` is dynamic). Both cache
    layouts: the int8 strategy quantizes the window per (token, head) row —
    each row's scale is independent, so a window append is bit-identical to
    W single-step appends and the speculative verify stays exact over the
    quantized cache.

    ``mesh``: decode attention is plain einsums, so GSPMD shards them from
    the param shardings on its own; the constraint here just pins the
    activation batch to the data axes (same annotation level as ``forward``)
    so a chunked prefill on a sharded model lays out like the decode loop.
    """
    c = config
    _one_layer_kind(c, "decode_window")
    B, W = tokens.shape
    max_len = cache["k"].shape[3]
    positions = pos0 + jnp.arange(W, dtype=jnp.int32)[None, :]  # [1, W]
    positions = jnp.broadcast_to(positions, (B, W))

    def constrain(x):
        if mesh is None:
            return x
        return lax.with_sharding_constraint(
            x, NamedSharding(mesh, P(_batch_axes(mesh), None, None))
        )

    h = constrain(_embed(params, tokens, c))  # [B, W, D]

    def layer_step(h, scanned):
        layer, c_layer = scanned
        x = rms_norm(h, layer["ln1"], c.rms_norm_eps)
        dh, nh, kvh = c.head_dim, c.n_heads, c.kv_heads
        q = _head_qk(
            _project_heads(x, layer["wq"], nh, c), layer, "ln_q", positions, c
        )
        k_new = _head_qk(
            _project_heads(x, layer["wk"], kvh, c), layer, "ln_k", positions, c
        )
        v_new = _project_heads(x, layer["wv"], kvh, c)
        from bee_code_interpreter_tpu.ops.kv_cache import (
            cache_append,
            cache_read,
        )

        c_layer = cache_append(c_layer, k_new, v_new, pos0)
        kf, vf = cache_read(c_layer, c.dtype)  # kf f32, vf c.dtype

        rep = nh // kvh
        qg = q.reshape(B, kvh, rep, W, dh).astype(jnp.float32)
        scores = _scaled_scores(jnp.einsum("bgrwd,bgsd->bgrws", qg, kf), c)
        # row w (position pos0+w) sees cache positions s <= pos0+w (and
        # within the sliding window when configured)
        row_pos = (pos0 + jnp.arange(W))[:, None]  # [W, 1]
        visible = jnp.arange(max_len)[None, :] <= row_pos  # [W, max]
        if c.sliding_window is not None:
            visible &= (
                jnp.arange(max_len)[None, :] > row_pos - c.sliding_window
            )
        scores = jnp.where(
            visible[None, None, None, :, :], scores, -jnp.inf
        )
        weights = jax.nn.softmax(scores, axis=-1).astype(c.dtype)
        attn = jnp.einsum("bgrws,bgsd->bgrwd", weights, vf)
        attn = attn.transpose(0, 3, 1, 2, 4).reshape(B, W, nh * dh)
        h = _residual(
            h, constrain(qeinsum("blk,kd->bld", attn, layer["wo"], c.dtype)), c
        )
        h, _ = _mlp_residual(h, layer, c, constrain)
        return h, c_layer

    h, cache = lax.scan(layer_step, h, (params["layers"], cache))
    return _head(params, h, c), cache


def decode_step_paged(
    params: Params,
    token: jax.Array,  # [B, 1] int32 — each row's current token
    pos: jax.Array,  # [B] int32 — PER-ROW positions (heterogeneous lengths)
    cache: dict,  # ops/paged_kv_cache.alloc_paged_cache pool
    block_table: jax.Array,  # [B, P] int32 logical block -> physical page
    config: TransformerConfig,
    lora_bank: dict | None = None,
    adapter_idx: jax.Array | None = None,
    lora_scale: float = 1.0,
    mesh: Mesh | None = None,
) -> tuple[jax.Array, dict]:
    """One incremental decode step over the PAGED cache — the serving-side
    sibling of ``decode_step``. This IS ``decode_window_paged`` with W=1
    (one body, mirroring the contiguous decode_step/decode_window
    unification)."""
    return decode_window_paged(
        params, token, pos, cache, block_table, config,
        lora_bank, adapter_idx, lora_scale, mesh=mesh,
    )


def decode_window_paged(
    params: Params,
    tokens: jax.Array,  # [B, W] int32 — W consecutive tokens per row
    pos0: jax.Array,  # [B] int32 — PER-ROW position of tokens[:, 0]
    cache: dict,  # ops/paged_kv_cache.alloc_paged_cache pool
    block_table: jax.Array,  # [B, P] int32 logical block -> physical page
    config: TransformerConfig,
    lora_bank: dict | None = None,  # {target: {A: [n_layers, n_adapters, d, r], B: ...}}
    adapter_idx: jax.Array | None = None,  # [B] int32 per-row adapter
    lora_scale: float = 1.0,
    mesh: Mesh | None = None,  # the mesh the params and the pool lie under
) -> tuple[jax.Array, dict]:
    """Multi-token cached decode over the PAGED pool with PER-ROW window
    positions — the verify primitive for speculative decoding INSIDE
    continuous batching: each row scores its own drafted window at its own
    cursor in one pass, rows at heterogeneous lengths together
    (models/serving.py). The serving-side sibling of ``decode_window``.

    The layer math is decode_window's grouped-query einsums verbatim; only
    the cache indexing differs (a row's W tokens may straddle a page
    boundary — one scatter either way), so paged-vs-contiguous equality is
    an indexing property (pinned by tests/test_paged_kv_cache.py,
    including permuted page tables). Both pool layouts — int8 pools carry
    per-row scale planes per page and append/read quantize exactly like
    the contiguous strategy. Rows whose slots would exceed the table's
    page budget are a scheduler bug (the scatter clamps).

    A window of ONE token goes through the Pallas kernel that addresses the
    pool where it lies, wherever ``ops.paged_attention.reads_pages_in_place``
    says it can, asked for each kind of layer that keeps pages (``mesh`` is
    read for that alone: the kernel runs in ``shard_map`` over the KV
    heads): the stacked leaf and the layer's index
    go to the kernel, which puts the new token into its row's boundary page
    and reads the row's live pages; no slice of the pool is cut. Everything
    else cuts the layer's slice, scatters into it and gathers the table's
    width (``paged_append``, ``_attend_paged``). A "sliding_attention" layer
    keeps no pages: it writes the token into its row's ring and attends over
    the ring (``_ring_layer``; a window of one token only). Either way the
    pool is the CARRY of the one layer scan (``_decode_layers``), so the
    donated input is updated in place and never copied through the scan.

    The weights are read where they lie too. A layer's projection is a dot
    against its slice of the stack with the slice fused into the dot; for
    the projections into heads that holds because the split into heads
    happens on the dot's materialised flat result (``_project_heads``):
    folded into the dot, as the TPU compiler folds it when it may, the split
    asks for the weight head-major, and the layer was cut out of the stack
    into a buffer and copied transposed every step (PERF.md, PR 36).

    ``lora_bank`` enables MULTI-LoRA serving (S-LoRA style): a stacked
    bank of adapters for the attention projections, with ``adapter_idx``
    selecting each row's adapter — heterogeneous adapters decode together
    in ONE compiled program. The delta is applied unmerged
    (``x@A[idx]@B[idx]·scale`` — two rank-r einsums per target, tiny next
    to the base matmul), so the shared base weights stream from HBM once
    for the whole batch regardless of how many adapters ride on it.
    ``lora_bank is None`` is a static (trace-time) branch: the base path
    is untouched. Pinned by tests/test_multilora_serving.py.
    """
    from bee_code_interpreter_tpu.ops import paged_attention
    from bee_code_interpreter_tpu.ops.paged_kv_cache import (
        BY_ROW_LEAVES,
        paged_append,
        paged_page_size,
    )

    c = config
    B, W = tokens.shape
    if lora_bank is not None:
        if adapter_idx is None:
            raise ValueError("lora_bank needs adapter_idx")
        unknown = set(lora_bank) - {"wq", "wk", "wv", "wo"}
        if unknown:
            raise ValueError(
                f"lora_bank targets {sorted(unknown)} unsupported in the "
                "decode path (attention projections only)"
            )
    if c.layer_types is not None and (
        lora_bank is not None or (W != 1 and c.n_mamba_layers)
    ):
        raise NotImplementedError(
            "a layer pattern with mamba layers decodes one token a row "
            "and takes no adapters: its state advances a token at a time"
        )
    if c.kv_lora_rank and lora_bank is not None:
        raise NotImplementedError(
            "adapters target wq/wk/wv/wo: latent attention has no wk and wv"
        )
    if c.window_layers and (W != 1 or lora_bank is not None):
        raise NotImplementedError(
            "window layers that keep a ring by row decode one token a row "
            "and take no adapters: a window of several tokens would "
            "overwrite slots its own earlier tokens still attend over"
        )
    page_size = paged_page_size(cache)
    positions = pos0[:, None] + jnp.arange(W, dtype=jnp.int32)[None, :]  # [B, W]
    page_idx = jnp.take_along_axis(
        block_table, positions // page_size, axis=1
    )  # [B, W]
    slot_idx = positions % page_size
    kv_names = [name for name in cache if name not in BY_ROW_LEAVES]

    def in_place(kind):  # of a layer kind that keeps pages
        return paged_attention.reads_pages_in_place(
            cache, W, _kind_window(c, kind), mesh
        )

    h = _embed(params, tokens, c)  # [B, W, D]

    def latent_layer(h, layer, cache, index, kind):
        """Latent-attention layer ``index`` (traced) of the pool ``cache``,
        whose one leaf ``ckv`` is [layers, n_pages, ps, latent_width]: the
        absorbed form, one KV head whose values are the first
        ``kv_lora_rank`` of its keys."""
        x = rms_norm(h, layer["ln1"], c.rms_norm_eps)
        q_nope, q_rope, latent = _latent_projections(x, layer, c, positions)
        latent = _pad_latent(latent, c)  # [B, W, latent_width]
        with jax.named_scope("mla.absorb"):
            q = _absorbed_query(q_nope, q_rope, layer, c)
        with jax.named_scope("mla.attend"):
            if in_place(kind):
                o_latent, ckv = paged_attention.paged_decode_attention(
                    q[:, :, 0], cache["ckv"], None, block_table,
                    positions[:, 0] + 1, sm_scale=_score_scale(c), mesh=mesh,
                    layer=index, k_new=latent, v_width=c.kv_lora_rank,
                )
                o_latent = o_latent[:, :, None]  # [B, nh, 1, rank]
                cache = {**cache, "ckv": ckv}
            else:
                c_layer = paged_append(
                    _take_layer({"ckv": cache["ckv"]}, index), latent, None,
                    page_idx, slot_idx,
                )
                o_latent = _attend_paged(
                    q, c_layer, block_table, positions, c
                ).reshape(B, W, c.n_heads, c.kv_lora_rank).transpose(0, 2, 1, 3)
                cache = _put_layer(cache, c_layer, index)
        with jax.named_scope("mla.absorb"):
            attn = _absorbed_output(o_latent, layer, c)
        o = qeinsum("blk,kd->bld", attn, layer["wo"], c.dtype)
        h, _ = _mlp_residual(_residual(h, o, c), layer, c)
        return h, cache

    def attention_layer(h, layer, cache, index, kind):
        """The attention layer of ``kind`` that keeps what it keeps at
        ``index`` (traced) of its part of the pool ``cache``: of the K/V
        leaves [paged layers, n_pages, kvh, ps, dh], or of the rings
        [window layers, B, window, kvh, dh]."""
        x = rms_norm(h, layer["ln1"], c.rms_norm_eps)
        dh, nh, kvh = c.head_dim, c.n_heads, c.kv_heads
        lora_layer = {} if lora_bank is None else _take_layer(lora_bank, index)

        def lora_delta(x_in, name):
            if name not in lora_layer:
                return None
            Ab = lora_layer[name]["A"][adapter_idx].astype(c.dtype)  # [B,d,r]
            Bb = lora_layer[name]["B"][adapter_idx].astype(c.dtype)  # [B,r,o]
            return jnp.einsum(
                "blr,bro->blo", jnp.einsum("bld,bdr->blr", x_in, Ab), Bb
            ) * jnp.asarray(lora_scale, c.dtype)

        def proj(w, heads, name):
            return _project_heads(x, w, heads, c, lora_delta(x, name))

        q = _head_qk(proj(layer["wq"], nh, "wq"), layer, "ln_q", positions, c, kind)
        k_new = _head_qk(
            proj(layer["wk"], kvh, "wk"), layer, "ln_k", positions, c, kind
        )
        v_new = proj(layer["wv"], kvh, "wv")
        if kind == "sliding_attention":
            with jax.named_scope("attn.window"):
                attn, cache = _ring_layer(
                    q, k_new, v_new, cache, index, positions[:, 0], c
                )
        elif in_place(kind):
            # the new token into its page and the row's live pages read,
            # both where they lie in the stacked leaf: nothing cut, nothing
            # scattered, nothing gathered
            with jax.named_scope("attn.full"):
                attn, k, v = paged_attention.paged_decode_attention(
                    q[:, :, 0], cache["k"], cache["v"], block_table,
                    positions[:, 0] + 1, sm_scale=_score_scale(c), mesh=mesh,
                    layer=index, k_new=k_new[:, :, 0], v_new=v_new[:, :, 0],
                )
            attn = attn.reshape(B, 1, nh * dh).astype(c.dtype)
            cache = {**cache, "k": k, "v": v}
        else:
            with jax.named_scope("attn.full"):
                c_layer = paged_append(
                    _take_layer({n: cache[n] for n in kv_names}, index),
                    k_new.transpose(0, 2, 1, 3),  # [B, W, kvh, dh]
                    v_new.transpose(0, 2, 1, 3),
                    page_idx, slot_idx,
                )
                attn = _attend_paged(
                    q, c_layer, block_table, positions, c, _kind_window(c, kind)
                )
                cache = _put_layer(cache, c_layer, index)
        o = qeinsum("blk,kd->bld", attn, layer["wo"], c.dtype)
        delta_o = lora_delta(attn, "wo")
        if delta_o is not None:
            o = o + delta_o
        h, _ = _mlp_residual(_residual(h, o, c), layer, c)
        return h, cache

    h, cache = _decode_layers(
        params, h, cache, c, latent_layer if c.kv_lora_rank else attention_layer
    )
    return _head(params, h, c), cache


def _attend_paged(
    q, c_layer, block_table, positions, config: TransformerConfig,
    window: int | None = None,
):
    """Attention of ``q`` [B, nh, W, dh] (at ``positions`` [B, W]) over one
    layer's pages (within ``window`` positions where the layer has one),
    the width of each row's block table gathered
    (``paged_read``) for the grouped einsums: [B, kvh, rep, W, dv] (a latent
    layer: one KV head, ``dv`` the latent's rank, the absorbed query's heads
    all on it). What a plain decode step on a TPU does in its place is
    ``paged_decode_attention``, whose oracle this is."""
    from bee_code_interpreter_tpu.ops.paged_kv_cache import paged_read

    c = config
    B, nh, W, dh = q.shape
    kf, vf = paged_read(c_layer, block_table, c.dtype, c.kv_lora_rank)
    kvh, S = kf.shape[1:3]  # [B,kvh,S,dh]

    rep = nh // kvh
    qg = q.reshape(B, kvh, rep, W, dh).astype(jnp.float32)
    scores = jnp.einsum("bgrwd,bgsd->bgrws", qg, kf)
    if c.kv_lora_rank:
        scores = scores * _score_scale(c)
    else:
        scores = _scaled_scores(scores, c)
    # row (b, w) sees cache positions s <= pos0_b + w (and within
    # the sliding window when configured)
    visible = (
        jnp.arange(S)[None, None, :] <= positions[:, :, None]
    )  # [B, W, S]
    if window is not None:
        visible &= jnp.arange(S)[None, None, :] > positions[:, :, None] - window
    scores = jnp.where(visible[:, None, None, :, :], scores, -jnp.inf)
    weights = jax.nn.softmax(scores, axis=-1).astype(c.dtype)
    attn = jnp.einsum("bgrws,bgsd->bgrwd", weights, vf)
    return attn.transpose(0, 3, 1, 2, 4).reshape(B, W, nh * vf.shape[-1])


def _ring_layer(q, k_new, v_new, cache, index, pos, config: TransformerConfig):
    """A window layer's decode step over the rows' rings, ``wk`` / ``wv``
    [window layers, B, window, kvh, dh] of the pool (``index``, traced: the
    layer's among them): the new token's K/V (q, k_new, v_new [B, heads, 1,
    dh], at positions ``pos`` [B]) go to slot ``pos mod window`` of their
    row, B slots written in place on the scan's carry, and the row attends
    over its ``min(pos + 1, window)`` live slots. A softmax does not mind the
    order of its keys and the keys carry their rotary position, so the ring
    is never unrolled; a slot beyond a short row's cursor holds whatever the
    row's last tenant left and is masked. Gives (attention [B, 1, nh * dh],
    the pool).

    A slot with all its KV heads is one contiguous block: the layout the
    TPU compiler scatters B such blocks into in place. (Head-major, [.., kvh,
    window, dh], it copied the whole leaf slot-major before the scatter and
    back after it, 0.6 ms of a 16.8 ms step; written head-major as B x kvh
    rows of dh, each scatter took 0.18 ms, 1.4 ms a step; PERF.md, PR 35.
    The einsums read a layer's ring head-major, so each layer's is cut out
    and turned once: a layer's worth, never the leaf.)"""
    c = config
    B, nh, _, dh = q.shape
    wk, wv = cache["wk"], cache["wv"]
    window, kvh = wk.shape[2:4]
    rows, slot = jnp.arange(B), pos % window
    wk = wk.at[index, rows, slot].set(k_new[:, :, 0].astype(wk.dtype))
    wv = wv.at[index, rows, slot].set(v_new[:, :, 0].astype(wv.dtype))
    k = lax.dynamic_index_in_dim(wk, index, 0, keepdims=False)  # [B,window,kvh,dh]
    v = lax.dynamic_index_in_dim(wv, index, 0, keepdims=False)
    # operands in the ring's dtype, sums in float32: as the paged kernel
    qg = q[:, :, 0].reshape(B, kvh, nh // kvh, dh).astype(k.dtype)
    scores = _scaled_scores(jnp.einsum(
        "bgrd,bsgd->bgrs", qg, k, preferred_element_type=jnp.float32
    ), c)
    live = jnp.arange(window)[None, :] <= pos[:, None]  # [B, window]
    scores = jnp.where(live[:, None, None, :], scores, -jnp.inf)
    weights = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    attn = jnp.einsum("bgrs,bsgd->bgrd", weights, v)
    return (
        attn.reshape(B, 1, nh * dh).astype(c.dtype), {**cache, "wk": wk, "wv": wv}
    )


def _put_layer(stacked: dict, new: dict, index) -> dict:
    """``stacked`` with the leaves of ``new`` written at ``index`` of their
    leading axis, in place where ``stacked`` is a loop's carry."""
    return {**stacked, **{
        name: lax.dynamic_update_index_in_dim(
            stacked[name], x.astype(stacked[name].dtype), index, 0
        )
        for name, x in new.items()
    }}


def _decode_layers(params, h, cache, config: TransformerConfig, attention_layer):
    """THE layer scan of a decode step: a period of the layer pattern an
    iteration (one attention layer where none is declared), the whole pool
    the carry beside ``h``. Each layer takes its weights out of the stacks
    at its index (a slice the compiler fuses into the dot that reads it, so
    a projection reads its layer of the stack where it lies and nothing is
    cut into a buffer: ``_project_heads`` says what that takes of a
    projection into heads) and updates its own part of the pool in place
    (``attention_layer(h, layer, cache, index)``: the K/V pages of its
    attention layer; here, the rows' state of its mamba layer), so the pool
    is never a scan input or output and the donated buffer is the one the
    loop works on. Leading dense layers run before the scan, on the first
    layers of the pool."""
    from bee_code_interpreter_tpu.models.mamba import mixer_step

    c = config

    def period_step(carry, period_index, n_layers=c.layer_period):
        h, cache = carry
        for j in range(n_layers):
            kind, index, layer = _pattern_layer(
                params["layers"], c, period_index, j
            )
            if kind == "mamba":
                mix, ssm, conv = mixer_step(
                    rms_norm(h, layer["ln1"], c.rms_norm_eps), layer, c,
                    _take_layer(cache["ssm"], index),
                    _take_layer(cache["conv"], index),
                )
                h, _ = _mlp_residual(_residual(h, mix, c), layer, c)
                cache = _put_layer(cache, {"ssm": ssm, "conv": conv}, index)
            else:
                h, cache = attention_layer(h, layer, cache, index, kind)
        return (h, cache), None

    first = c.layer_kinds[:c.n_dense_layers]
    for i, kind in enumerate(first):
        part = _pool_part(kind)
        h, cache = attention_layer(
            h, _take_layer(params["dense_layers"], i), cache,
            sum(k in part for k in first[:i]), kind,
        )
    periods, left_over = _n_periods(c)
    carry, _ = lax.scan(period_step, (h, cache), jnp.arange(periods))
    if left_over:  # the start of one more period
        carry, _ = period_step(carry, periods, left_over)
    return carry


def prefill_chunked(
    params: Params,
    prompt: jax.Array,  # [B, L] int32
    config: TransformerConfig,
    total_len: int,
    chunk: int = 512,
    mesh: Mesh | None = None,
) -> tuple[jax.Array, dict]:
    """Build the decode cache by streaming the prompt through
    ``decode_window`` in fixed-size chunks instead of one O(L²) forward —
    activation memory is bounded by the chunk (attention scores are
    [B, H, chunk, L] instead of [B, H, L, L]), the standard long-prompt
    prefill. Returns (last-position logits [B, vocab], cache) — exactly
    what starting decode needs; per-chunk causality is decode_window's
    position masking, so the result is pinned equal to the full forward
    (tests/test_chunked_prefill.py).

    Full chunks run under one ``lax.scan`` (one compile); a static
    remainder chunk (L % chunk) adds at most one more.
    """
    c = config
    B, L = prompt.shape
    if L == 0:
        # an empty prompt yields no last_logits to start decode from; fail
        # here, not later in sample_logits with an opaque None error
        raise ValueError("prompt must be non-empty (L >= 1)")
    if total_len < L:
        # an undersized cache would be silently corrupted: clamped
        # dynamic_update_slice writes shift later chunks onto earlier rows
        raise ValueError(
            f"total_len ({total_len}) must cover the prompt length ({L})"
        )
    if chunk < 1:
        raise ValueError(f"chunk must be >= 1, got {chunk}")
    cache = alloc_decode_cache(c, B, total_len)

    n_full, rem = divmod(L, chunk)
    last_logits = None
    if n_full:
        chunks = prompt[:, : n_full * chunk].reshape(B, n_full, chunk)

        def body(cache, x):
            toks, pos0 = x
            logits, cache = decode_window(params, toks, pos0, cache, c, mesh)
            return cache, logits[:, -1, :]

        cache, last_per_chunk = lax.scan(
            body,
            cache,
            (
                chunks.transpose(1, 0, 2),  # [n_full, B, chunk]
                jnp.arange(n_full, dtype=jnp.int32) * chunk,
            ),
        )
        last_logits = last_per_chunk[-1]
    if rem:
        logits, cache = decode_window(
            params, prompt[:, n_full * chunk :], jnp.int32(n_full * chunk),
            cache, c, mesh,
        )
        last_logits = logits[:, -1, :]
    return last_logits, cache


# ----------------------------------------------------------------- sampling


def sample_logits(
    logits: jax.Array,  # [B, V] f32
    key: jax.Array,
    temperature: float = 0.0,
    top_k: int | None = None,
    top_p: float | None = None,
) -> jax.Array:
    """Next-token selection: greedy at ``temperature == 0`` (exact argmax),
    otherwise categorical over temperature-scaled logits with optional
    top-k then top-p (nucleus) filtering. All filters are static-shape
    (mask-to--inf, no dynamic vocab slicing) so the decode loop stays one
    compiled program. Returns [B, 1] int32."""
    if top_k is not None and top_k < 1:
        # validated regardless of temperature: a config tested greedy-first
        # must fail fast, not only when sampling is later enabled
        raise ValueError(f"top_k must be >= 1, got {top_k}")
    if temperature == 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None]
    x = filter_logits(logits / temperature, top_k, top_p)
    return jax.random.categorical(key, x, axis=-1).astype(jnp.int32)[:, None]


def filter_logits(
    x: jax.Array,  # [B, V] temperature-scaled logits
    top_k: int | None = None,
    top_p: float | None = None,
) -> jax.Array:
    """THE definition of the sampling filters (mask-to--inf): top-k, then
    nucleus — keep the smallest prefix of the descending-prob order whose
    mass reaches ``top_p``, always at least the top token. ``sample_logits``
    draws from this on device; serving's host-side sampler mirrors it in
    numpy with parity pinned against this function
    (tests/test_serving.py::test_host_filter_parity_with_device)."""
    if top_k is not None:
        kth = lax.top_k(x, top_k)[0][:, -1:]  # [B, 1] k-th largest
        x = jnp.where(x >= kth, x, -jnp.inf)
    if top_p is not None:
        sort_idx = jnp.argsort(-x, axis=-1)
        sorted_x = jnp.take_along_axis(x, sort_idx, axis=-1)
        probs = jax.nn.softmax(sorted_x, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        keep_sorted = (cum - probs) < top_p  # mass BEFORE this token < p
        # position 0 of the descending order is the top token: always
        # eligible, so degenerate top_p (<= 0) cannot mask the whole vocab
        keep_sorted = keep_sorted.at[:, 0].set(True)
        keep = jnp.zeros_like(keep_sorted).at[
            jnp.arange(x.shape[0])[:, None], sort_idx
        ].set(keep_sorted)
        x = jnp.where(keep, x, -jnp.inf)
    return x


# ---------------------------------------------------------------- loss/train


def loss_fn(
    params: Params,
    batch: dict[str, jax.Array],  # tokens [B, L], targets [B, L]
    config: TransformerConfig,
    mesh: Mesh | None = None,
) -> jax.Array:
    logits, aux = forward(
        params, batch["tokens"], config, mesh, return_aux=True
    )
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    target_logit = jnp.take_along_axis(
        logits, batch["targets"][..., None], axis=-1
    )[..., 0]
    nll = logz - target_logit
    # z-loss keeps logits from drifting (stability at bf16)
    loss = nll + config.z_loss * logz**2
    # MoE load-balancing term (0.0 for dense configs)
    return loss.mean() + config.moe_aux_weight * aux


class Transformer:
    """Config + mesh bundle with jitted apply/train_step factories."""

    def __init__(self, config: TransformerConfig, mesh: Mesh | None = None) -> None:
        self.config = config
        self.mesh = mesh

    def init(self, key: jax.Array) -> Params:
        params = init_params(self.config, key)
        if self.mesh is not None:
            params = shard_params(params, self.config, self.mesh)
        return params

    def apply(self, params: Params, tokens: jax.Array) -> jax.Array:
        return forward(params, tokens, self.config, self.mesh)

    def make_optimizer(self, learning_rate: float = 3e-4):
        return optax.adamw(learning_rate, b1=0.9, b2=0.95, weight_decay=0.1)

    def make_train_step(self, optimizer=None):
        optimizer = optimizer or self.make_optimizer()

        def train_step(params, opt_state, batch):
            loss, grads = jax.value_and_grad(loss_fn)(
                params, batch, self.config, self.mesh
            )
            updates, opt_state = optimizer.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
            return params, opt_state, loss

        return jax.jit(train_step, donate_argnums=(0, 1))

    def batch_sharding(self) -> NamedSharding | None:
        if self.mesh is None:
            return None
        sp = "sp" if "sp" in self.mesh.axis_names else None
        return NamedSharding(self.mesh, P(_batch_axes(self.mesh), sp))

    # ------------------------------------------------------------- generate

    def generate(
        self, params: Params, prompt: jax.Array, max_new_tokens: int = 32
    ) -> jax.Array:
        """Greedy decode (no KV cache; full-sequence re-encode per step —
        the simple correctness path; cached decode is the listed follow-up)."""
        B, L = prompt.shape
        total = L + max_new_tokens
        tokens = jnp.zeros((B, total), dtype=jnp.int32).at[:, :L].set(prompt)

        def step(carry, idx):
            tokens = carry
            logits = forward(params, tokens, self.config, self.mesh)
            # logits at position idx-1 predict token idx
            prev = lax.dynamic_slice_in_dim(logits, idx - 1, 1, axis=1)  # [B,1,V]
            next_tok = jnp.argmax(prev, axis=-1).astype(jnp.int32)  # [B,1]
            tokens = lax.dynamic_update_slice(tokens, next_tok, (0, idx))
            return tokens, None

        tokens, _ = lax.scan(
            step, tokens, jnp.arange(L, total), length=max_new_tokens
        )
        return tokens

    def generate_cached(
        self,
        params: Params,
        prompt: jax.Array,
        max_new_tokens: int = 32,
        *,
        temperature: float = 0.0,
        top_k: int | None = None,
        top_p: float | None = None,
        key: jax.Array | None = None,
        eos_id: int | None = None,
        prefill_chunk: int | None = None,
    ) -> jax.Array:
        """KV-cached decode: one O(L^2) prefill, then ``max_new_tokens - 1``
        O(L) incremental steps (decode_step). Default is greedy
        (``temperature=0``) and pinned equal to ``generate`` by
        tests/test_models.py; ``temperature``/``top_k``/``top_p`` select
        sampled decoding (``sample_logits``; ``key`` defaults to PRNGKey(0)
        and is split per step, so a fixed key is fully deterministic).
        ``eos_id`` freezes a row once it emits that token — every later
        position repeats ``eos_id`` (static shapes: the loop always runs
        ``max_new_tokens`` steps; finished rows just stop changing).
        ``prefill_chunk`` streams the prompt through ``prefill_chunked``
        instead of one O(L²) forward (long prompts in bounded memory;
        either cache layout — note the int8 cache's prefill attention reads
        progressively quantized K/V, the same semantics incremental decode
        has, where the full prefill attends in exact bf16 before
        quantizing). For
        MoE configs greedy equality holds only drop-free (ample capacity):
        under capacity pressure the full forward routes tokens in
        competition while decode routes each token alone — inherent to
        capacity-based MoE (tests/test_moe.py)."""
        c = self.config
        B, L = prompt.shape
        total = L + max_new_tokens
        if key is None:
            key = jax.random.PRNGKey(0)

        if prefill_chunk is not None:
            last_logits, cache = prefill_chunked(
                params, prompt, c, total, chunk=prefill_chunk, mesh=self.mesh
            )
        else:
            logits, (k_pre, v_pre) = forward(
                params, prompt, c, self.mesh, return_kv=True
            )
            cache = init_decode_cache(c, B, total, k_pre, v_pre)
            last_logits = logits[:, L - 1, :]

        key, sub = jax.random.split(key)
        first = sample_logits(last_logits, sub, temperature, top_k, top_p)
        tokens = (
            jnp.zeros((B, total), dtype=jnp.int32)
            .at[:, :L].set(prompt)
            .at[:, L : L + 1].set(first)
        )

        done0 = (
            (first == eos_id) if eos_id is not None
            else jnp.zeros_like(first, dtype=bool)
        )

        def step(carry, pos):
            tokens, current, cache, key, done = carry
            step_logits, cache = decode_step(params, current, pos, cache, c)
            key, sub = jax.random.split(key)
            next_tok = sample_logits(
                step_logits[:, -1, :], sub, temperature, top_k, top_p
            )
            if eos_id is not None:
                next_tok = jnp.where(done, jnp.int32(eos_id), next_tok)
                done = done | (next_tok == eos_id)
            tokens = lax.dynamic_update_slice(tokens, next_tok, (0, pos + 1))
            return (tokens, next_tok, cache, key, done), None

        (tokens, _, _, _, _), _ = lax.scan(
            step,
            (tokens, first, cache, key, done0),
            jnp.arange(L, total - 1, dtype=jnp.int32),
        )
        return tokens
