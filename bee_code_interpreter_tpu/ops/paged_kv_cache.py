"""Paged KV cache: block-table indirection over a shared page pool.

The modern serving primitive (vLLM's PagedAttention, here TPU-first):
instead of one contiguous [B, kvh, max_len, dh] cache per batch — which
reserves worst-case length for every row — K/V live in fixed-size PAGES
drawn from one pool, and each sequence maps logical block → physical page
through a small int32 block table. Heterogeneous-length requests then share
the pool densely: a 100-token and a 4000-token request cost pages
proportional to their actual lengths, and a finished request's pages are
recycled immediately (models/serving.py does the recycling — continuous
batching).

TPU-first constraints shape the layout:

- **Static shapes everywhere.** The pool, the block table and every
  program over them are fixed-size; "allocation" is host-side integer
  bookkeeping between steps, never a traced shape change.
- **The served decode step moves nothing of the pool's size.** The pool
  is the carry of the decode program's one layer scan, and a window of one
  token goes through ``ops/paged_attention.py``, a Pallas kernel that takes
  the STACKED leaf and a layer index, writes the new token's page in place
  and copies each row's LIVE pages from where they lie, wherever
  ``paged_attention.reads_pages_in_place`` holds for the layer (a TPU, no
  scale planes, no window masked over its pages, a head that fills the
  lane tile): nothing is cut out
  of the pool, scattered into it or gathered from it. ``paged_append`` and
  ``paged_read`` below are the other path, on one layer's slice inside the
  same scan: speculative windows, int8 pools, a ``sliding_window`` over
  layers that are not told apart (a mask over pages that keep every
  token), a head of
  64 and the CPU of the tests scatter the new tokens (an XLA scatter,
  which copies the slice and picks its layout) and gather the whole
  block-table width into the [B, kvh, S, dh] view the contiguous attention
  einsums consume (one advanced-indexing gather), so paged-vs-contiguous
  equality is a pure indexing property, pinned by
  tests/test_paged_kv_cache.py, and this path is the kernel's oracle, for
  what it reads and for what it writes (tests/test_paged_decode_kernel.py).
- **A page with all its KV heads is one contiguous block.** Pages are
  [kvh, page_size, dh] slabs of the leaf [n_pages, kvh, page_size, dh]:
  the kernel copies whole pages in and out, ``seed_prefill`` scatters whole
  pages, the mesh shards the kvh axis. dh is contiguous and page_size defaults to
  a multiple of 8 so slabs keep the (8, 128) tiling XLA wants.
- **A pool of two parts where the model tells window layers from full
  ones.** A "sliding_attention" layer (``TransformerConfig.layer_types``)
  attends within ``sliding_window`` positions, so a row never needs more of
  it than that: those layers keep a RING by row, ``wk`` / ``wv`` [window
  layers, max_batch, sliding_window, kvh, dh], a token at slot ``position
  mod sliding_window`` (at 128 slots, 8 whole pages' worth; a slot with all
  its KV heads is one contiguous block, at 8 heads of 128 one (8, 128)
  tile, which the step's scatter writes in place), beside the pages of the
  full layers, ``k`` / ``v`` stacked
  over THOSE alone. The block table, ``n_pages`` and the admission's page
  arithmetic count the full layers only: a row of L tokens holds
  ``ceil(L / page_size)`` pages whatever the number of window layers. The
  ring is seeded at admission from the prompt's last positions
  (``seed_rings``), written a slot a row a step and read whole by the
  decode program (``transformer._ring_layer``), as part of the one tree it
  donates, like the recurrent state of mamba layers.

The reference has no serving stack at all (SURVEY §2); this module is part
of the rebuild's decode family next to the int8 cache (ops/kv_cache.py).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from bee_code_interpreter_tpu.ops.kv_cache import quantize


def pool_telemetry(
    *,
    block_table: np.ndarray,  # [B, P] int32, scratch-page entries for holes
    pos: np.ndarray,  # [B] int32 decode cursors (tokens written per row)
    active: np.ndarray,  # [B] bool
    page_ref: np.ndarray,  # [n_pages] int32 refcounts
    page_size: int,
    free_pages: int,
    parked_pages: int,
    scratch_page: int = 0,
) -> dict:
    """Host-side page-pool telemetry (docs/observability.md "Serving
    observability") — pure integer bookkeeping over the scheduler's own
    state, zero device traffic, cheap enough for every ``/metrics`` scrape.

    ``fragmentation`` is slot-level INTERNAL fragmentation of the pages
    active rows hold: ``1 - used_slots / allocated_slots``. A page holds
    ``page_size`` K/V slots but a row's cursor covers only ``pos`` of the
    slots its pages reserve — the tail of the last page (and budget-sized
    over-allocation) is capacity the pool cannot hand to anyone else.
    Prefix-shared pages are counted once per HOLDER (each sharer's table
    maps them), which is deliberate: the metric describes how efficiently
    *reserved* capacity is used, and a shared page is reserved by every
    sharer's admission arithmetic. ``pages_shared`` (refcount > 1) reports
    the sharing itself.
    """
    n_pages = int(page_ref.shape[0])
    held = int((page_ref > 0).sum())
    shared = int((page_ref > 1).sum())
    slots_allocated = 0
    slots_used = 0
    for row in np.flatnonzero(active):
        row_pages = int((block_table[row] != scratch_page).sum())
        slots_allocated += row_pages * page_size
        slots_used += int(pos[row])
    fragmentation = (
        1.0 - slots_used / slots_allocated if slots_allocated else 0.0
    )
    return {
        "pages_total": n_pages - 1,  # the scratch page is never allocatable
        "pages_free": free_pages,
        "pages_parked": parked_pages,
        "pages_held": held,
        "pages_shared": shared,
        "page_size": page_size,
        "slots_allocated": slots_allocated,
        "slots_used": slots_used,
        "fragmentation": fragmentation,
    }


# what a row of the batch keeps apart from its pages: the mamba layers'
# state and conv tail, the window layers' rings
BY_ROW_LEAVES = ("ssm", "conv", "wk", "wv")


def paged_page_size(cache: dict) -> int:
    """Slots a page of the pool holds."""
    if "ckv" in cache:
        return cache["ckv"].shape[2]
    return cache["k"].shape[3]


def pages_leaf(cache: dict):
    """A leaf of the pool that holds pages (for where the pool lives)."""
    return cache["ckv"] if "ckv" in cache else cache["k"]


def alloc_paged_cache(
    config, n_pages: int, page_size: int, sharding=None,
    max_batch: int | None = None,
) -> dict:
    """Zeroed page pool: k/v [paged layers, n_pages, kvh, page_size, dh]
    over the attention layers that keep every token (all of them, unless
    ``config.layer_types`` names "sliding_attention" layers: those keep
    ``wk`` / ``wv`` [window layers, max_batch, sliding_window, kvh, dh], a
    ring by ROW; module docstring),
    or for latent attention (``config.kv_lora_rank``) ONE leaf ``ckv``
    [layers, n_pages, page_size, latent_width], a token's normed latent and
    shared rotary key side by side (keys and values both: the values are
    the first ``kv_lora_rank`` of a slot), and, for a configuration with
    mamba layers, what those keep by ROW of
    the batch beside it (``models/mamba.alloc_state``: ``ssm`` and ``conv``
    over [mamba layers, max_batch, ...]). One tree holds everything a
    request keeps on the device between steps, and the decode program
    donates it whole. ``max_batch`` is read only where something is kept by
    row.

    ``sharding`` (one ``jax.sharding.Sharding`` for every leaf — they share
    the leading dims) places the pool where it will live, from host zeros,
    so each device receives only its own shard. (A pool made on the default
    device and moved — which is also what ``jnp.zeros(device=...)`` does for
    a one-device sharding — put every replica's pool on device 0 on its way
    through: 15.5 of 15.75 GiB at the peak of a four-replica run on v5e;
    PERF.md, PR 21.)

    One pool serves every layer by giving each layer its own leading-axis
    slice of every page — a sequence's page i holds layer ℓ's tokens at
    ``pages[ℓ, page]``, so the block table is shared across layers (one
    table per sequence, not per layer — same trick as the stacked
    contiguous cache).

    ``kv_cache_dtype="int8"`` stores int8 values plus per-(token, head)
    scale planes per page — the same self-describing layout convention as
    the contiguous cache (ops/kv_cache.py): scale leaves present selects
    the quantized strategy in append/read, and the decode bandwidth halves
    on top of paging's density win.
    """
    c = config
    if page_size < 1:
        raise ValueError(f"page_size must be >= 1, got {page_size}")
    shape = (len(c.paged_layers), n_pages, c.kv_heads, page_size, c.head_dim)

    def zeros(shape, dtype):
        if sharding is None:
            return jnp.zeros(shape, dtype)
        return jax.device_put(np.zeros(shape, dtype), sharding)

    if c.kv_lora_rank:
        if sharding is not None:
            raise NotImplementedError(
                "a latent cache has one KV head and is not sharded: no mesh "
                "over latent attention"
            )
        return {"ckv": zeros(
            (c.n_attention_layers, n_pages, page_size, c.latent_width), c.dtype
        )}
    if c.n_mamba_layers or c.window_layers:
        if max_batch is None:
            raise ValueError(
                "a configuration with mamba layers or window layers keeps "
                "state by row: alloc_paged_cache needs max_batch"
            )
        if sharding is not None:
            raise NotImplementedError(
                "state kept by row is not sharded: no mesh over mamba "
                "layers or over window layers' rings"
            )
    if c.kv_cache_dtype == "int8":
        pool = {
            "k": zeros(shape, jnp.int8),
            "v": zeros(shape, jnp.int8),
            "k_s": zeros(shape[:-1] + (1,), jnp.float32),
            "v_s": zeros(shape[:-1] + (1,), jnp.float32),
        }
    else:
        pool = {"k": zeros(shape, c.dtype), "v": zeros(shape, c.dtype)}
    if c.n_mamba_layers:
        from bee_code_interpreter_tpu.models.mamba import alloc_state

        pool.update(alloc_state(c, max_batch, zeros))
    if c.window_layers:
        ring = (
            len(c.window_layers), max_batch, c.sliding_window, c.kv_heads,
            c.head_dim,
        )
        pool.update({"wk": zeros(ring, c.dtype), "wv": zeros(ring, c.dtype)})
    return pool


def paged_append(
    c_layer: dict,  # one layer's pool slice: [n_pages, kvh, ps, dh]
    k_new: jax.Array,  # [B, W, kvh, dh] — W consecutive tokens per row
    v_new: jax.Array,
    page_idx: jax.Array,  # [B, W] int32 physical page per (row, token)
    slot_idx: jax.Array,  # [B, W] int32 slot within the page
) -> dict:
    """Scatter W new tokens' K/V per batch row into their (page, slot)s.

    Rows of a batch may land in arbitrary distinct pages, and a row's W
    tokens may straddle a page boundary — the scatter is one XLA scatter
    op either way. Two (row, token)s writing the same (page, slot) is a
    scheduler bug (pages are owned by one sequence); last-writer-wins as
    with any scatter. The int8 layout quantizes per (token, head) row —
    identical semantics to the contiguous cache_append, so paged int8
    decode equals contiguous int8 decode (and a window append is
    bit-identical to W single appends, which keeps paged speculative
    verify exact).

    A latent layer's slice (``ckv`` [n_pages, ps, width]) takes ``k_new``
    [B, W, width], the latent beside the rotary key, and no ``v_new``.
    """
    if "ckv" in c_layer:
        leaf = c_layer["ckv"]
        return {"ckv": leaf.at[page_idx, slot_idx, :].set(k_new.astype(leaf.dtype))}
    if "k_s" in c_layer:
        kq, ks = quantize(k_new)  # [B, W, kvh, dh] -> values + [B, W, kvh, 1]
        vq, vs = quantize(v_new)
        return {
            "k": c_layer["k"].at[page_idx, :, slot_idx, :].set(kq),
            "v": c_layer["v"].at[page_idx, :, slot_idx, :].set(vq),
            "k_s": c_layer["k_s"].at[page_idx, :, slot_idx, :].set(ks),
            "v_s": c_layer["v_s"].at[page_idx, :, slot_idx, :].set(vs),
        }
    dtype = c_layer["k"].dtype
    return {
        "k": c_layer["k"].at[page_idx, :, slot_idx, :].set(
            k_new.astype(dtype)
        ),
        "v": c_layer["v"].at[page_idx, :, slot_idx, :].set(
            v_new.astype(dtype)
        ),
    }


def paged_read(
    c_layer: dict,  # [n_pages, kvh, ps, dh]
    block_table: jax.Array,  # [B, P] int32 logical block -> physical page
    dtype,  # V compute dtype — required, matching cache_read's contract
    v_width: int = 0,  # a latent layer: the values are so many of a slot's first
) -> tuple[jax.Array, jax.Array]:
    """Gather each row's pages into the contiguous [B, kvh, P·ps, dh] view
    the attention einsums consume. K comes back f32 (scores operand), V in
    ``dtype`` — the same contract as ops/kv_cache.cache_read; int8 pools
    dequantize after the gather (scales gathered alongside). A latent
    layer's slice comes back as one KV head: keys the whole slot, values
    its first ``v_width``."""
    B, P = block_table.shape
    if "ckv" in c_layer:
        ps, width = c_layer["ckv"].shape[1:]
        g = c_layer["ckv"][block_table].reshape(B, 1, P * ps, width)
        return g.astype(jnp.float32), g[..., :v_width].astype(dtype)
    n_pages, kvh, ps, dh = c_layer["k"].shape

    def view(x, out_dtype):
        g = x[block_table]  # [B, P, kvh, ps, last]
        last = x.shape[-1]
        return (
            g.transpose(0, 2, 1, 3, 4).reshape(B, kvh, P * ps, last)
            .astype(out_dtype)
        )

    if "k_s" in c_layer:
        from bee_code_interpreter_tpu.ops.kv_cache import dequantize

        return (
            dequantize(view(c_layer["k"], jnp.int8), view(c_layer["k_s"], jnp.float32)),
            dequantize(view(c_layer["v"], jnp.int8), view(c_layer["v_s"], jnp.float32), dtype),
        )
    return view(c_layer["k"], jnp.float32), view(c_layer["v"], dtype)


def seed_prefill(
    cache: dict,  # full pool: leaves [n_layers, n_pages, ...]
    pages: jax.Array,  # [P] int32 physical pages covering ceil(L/ps)
    k_pre: jax.Array,  # [n_layers, kvh, L, dh] — one sequence's prefill K
    v_pre: jax.Array | None = None,  # (a latent pool: [n_layers, L, width] alone)
    length: jax.Array | None = None,  # the TRUE length where L is a padded width
) -> dict:
    """Write one sequence's prefill K/V into its pages — ONE batched
    scatter per pool leaf; the single copy of the prefill-seeding logic
    (the batcher's ``seed_pool`` program and the equality tests both call
    this, so the tested path IS the served path). int8 pools quantize per
    (token, head) row, identical to cache_append's semantics; the pad tail
    quantizes to scale-0 exact zeros and stays masked by ``s <= pos``.

    ``length`` (a traced int32 scalar) is given where the K/V come at a
    padded width: the positions at and beyond it are written as zeros, which
    is what cutting the K/V to their true length beforehand writes, so one
    compiled program serves every length of a width.

    Jitted with ``cache`` donated, the scatter writes a leaf where it lies
    as long as the leaf lies page-major on the device; a head narrower than
    the lane tile does not (Granite's 64: the TPU lays that leaf out pages
    minor-most, and the compiler copies it page-major and back around the
    scatter, 3.1 ms an admission on a v5e; a loop of
    ``dynamic_update_slice`` writes it in place at 0.22 ms a PAGE, which is
    more for a prompt of 27; PERF.md, PR 38)."""
    ps = paged_page_size(cache)
    n_pages_used = int(pages.shape[0])
    L = k_pre.shape[-2]
    if L > n_pages_used * ps:
        raise ValueError(
            f"prefill length {L} exceeds {n_pages_used} pages of {ps}"
        )

    def whole_pages(x):  # [..., L, last] -> [..., P * ps, last], zeros past the end
        if length is not None:
            real = jnp.arange(L, dtype=jnp.int32) < length
            x = jnp.where(real[:, None], x, jnp.zeros((), x.dtype))
        tail = [(0, 0)] * (x.ndim - 2) + [(0, n_pages_used * ps - L), (0, 0)]
        return jnp.pad(x, tail)

    def page_view(x):  # [n_layers, kvh, L, dh] -> [n_layers, P, kvh, ps, dh]
        x = whole_pages(x)
        nl, kvh, _, dh = x.shape
        return x.reshape(nl, kvh, n_pages_used, ps, dh).transpose(0, 2, 1, 3, 4)

    if "ckv" in cache:
        leaf = cache["ckv"]
        vals = whole_pages(k_pre).reshape(
            leaf.shape[0], n_pages_used, ps, leaf.shape[-1]
        )
        return {**cache, "ckv": leaf.at[:, pages].set(vals.astype(leaf.dtype))}

    def put(cache, name, sname, pre):
        vals = page_view(pre)
        if sname in cache:
            q, s = quantize(vals)
            return {
                **cache,
                name: cache[name].at[:, pages].set(q),
                sname: cache[sname].at[:, pages].set(s),
            }
        return {
            **cache,
            name: cache[name].at[:, pages].set(
                vals.astype(cache[name].dtype)
            ),
        }

    cache = put(cache, "k", "k_s", k_pre)
    return put(cache, "v", "v_s", v_pre)


def seed_pool(
    cache: dict, pages: jax.Array, length: jax.Array, kv: tuple,
    paged_layers: tuple[int, ...] | None = None,
) -> dict:
    """``seed_prefill`` over a one-sequence prefill's K/V as
    ``forward(return_kv=True)`` hands them back: ``kv`` is K and V [attention
    layers, 1, kvh, Lp, dh], or a latent [layers, 1, Lp, width] alone, at
    the padded width; ``length`` (traced) is the prompt's TRUE length.
    ``paged_layers`` (static) names the layers that keep pages where the
    others keep rings (``seed_rings`` takes those). To be jitted with
    ``cache`` donated, as ``seed_state``: an eager ``.at[].set`` copies each
    leaf of the pool whole to write one prompt's pages into it."""
    if paged_layers is not None:
        kv = [x[np.asarray(paged_layers, np.int32)] for x in kv]
    return seed_prefill(cache, pages, *[x[:, 0] for x in kv], length=length)


def seed_state(cache: dict, row: jax.Array, ssm: jax.Array, conv: jax.Array) -> dict:
    """Replace, whole and in place, what the mamba layers keep for one
    ``row`` (a traced int32 scalar) with a one-sequence prefill's ``ssm``
    [mamba layers, 1, heads, head size, state] and ``conv`` [mamba layers,
    1, d_conv - 1, channels]: whatever the row's last tenant left is gone.
    To be jitted with ``cache`` donated (the batcher's ``seed_state``
    program): an eager ``.at[].set`` would hold the state leaf twice."""
    def put(leaf, new):
        return lax.dynamic_update_slice_in_dim(leaf, new.astype(leaf.dtype), row, 1)

    return {**cache, "ssm": put(cache["ssm"], ssm), "conv": put(cache["conv"], conv)}


def seed_rings(
    cache: dict, row: jax.Array, k_pre: jax.Array, v_pre: jax.Array,
    length: jax.Array, window_layers: tuple[int, ...],
) -> dict:
    """Replace, whole and in place, the rings of one ``row`` (a traced int32
    scalar) with the last ``window`` positions of a one-sequence prefill's
    K/V: ``k_pre`` / ``v_pre`` [attention layers, 1, kvh, Lp, dh] as
    ``forward(return_kv=True)`` hands them back, of which ``window_layers``
    (static) keep a ring; ``length`` (traced) is the prompt's TRUE length,
    not the padded width. Slot s takes the last position p < length with
    p mod window == s; a slot no position of a short prompt falls on takes
    position 0's and stays masked until the cursor reaches it
    (``transformer._ring_layer``). Whatever the row's last tenant left is
    gone. To be jitted with ``cache`` donated, as ``seed_state``."""
    window = cache["wk"].shape[2]
    slots = jnp.arange(window, dtype=jnp.int32)
    position = jnp.maximum(length - 1 - (length - 1 - slots) % window, 0)
    layers = jnp.asarray(window_layers, jnp.int32)

    def put(leaf, pre):
        new = jnp.take(pre[layers, 0], position, axis=2)  # [layers, kvh, window, dh]
        return lax.dynamic_update_slice_in_dim(
            leaf, new.transpose(0, 2, 1, 3)[:, None].astype(leaf.dtype), row, 1
        )

    return {**cache, "wk": put(cache["wk"], k_pre), "wv": put(cache["wv"], v_pre)}
