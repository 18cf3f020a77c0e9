"""Pallas paged-attention decode kernel: the pool addressed where it lies.

The plain decode step (``models/transformer.decode_window_paged``, a window
of one token) writes the new token's K/V and attends through this kernel
wherever ``reads_pages_in_place`` says it can; everything else keeps
``ops/paged_kv_cache``'s ``paged_append`` and ``paged_read`` on a layer's
slice and the grouped einsums, which are also this kernel's oracle in the
tests. The gather costs what the block TABLE is wide (every slot of every
row, live or not, copied and transposed in HBM each step); a scatter over a
layer's slice, a slice handed to a kernel, a pool scanned as ``xs``/``ys``
cost what the POOL is large (each copies it, 34 ms of a 50 ms step; PERF.md,
PR 30); the kernel costs what is LIVE.

Structure — the flash kernel's online softmax, for one query token a row:

- grid ``(B,)``, one row a step. The STACKED pool leaf ``[layers, n_pages,
  kvh, ps, dh]`` (stacked over the layers that keep pages: every attention
  layer, or the full layers alone beside window layers' rings) stays in HBM (``memory_space`` any) in the layout
  everything else reads, and the layer is a scalar-prefetched index beside
  the lengths and the block table: no operand is a slice. A page with all
  its KV heads is one contiguous block there, and the kernel copies
  ``PAGE_BLOCK_TOKENS // ps`` such pages a block into VMEM with its own
  async copies, double-buffered: block i+1 is in flight while block i is
  computed;
- the loop runs to ``ceil(length / ps)`` pages, from the scalar-prefetched
  lengths and block table: a table entry past the live count is never
  read (so a sentinel there is harmless), slots past the length in the
  boundary page are masked, and a row of length 0 copies nothing and
  gives zeros;
- THE WRITE (``k_new`` / ``v_new``): the row's newest token, which the
  length already counts, belongs in slot ``length - 1``, in the boundary
  page the last block has just brought into VMEM. The kernel sets that
  slot there before the block is computed and copies the ONE page (all KV
  heads: whole tiles, 32 KB at the benchmark's shapes) back to where it
  lies, through pool results that alias the pool operands
  (``input_output_aliases``): bytes touched in proportion to the rows,
  never to the pool. The copy back has landed before the row's grid step
  ends, so a later row that names the same page (dead rows all name the
  scratch page; rows run one after another, last writer wins) fetches what
  was written, and the buffer it leaves from is free. Prefix-shared pages
  are never a boundary page (the cursor starts past them); a row of length
  0 writes nothing. Reads and writes both go through the RESULT's
  reference, which on the chip is the operand's buffer and in the
  interpreter starts as its copy;
- per KV head, the ``rep = nh / kvh`` query heads that share it are the
  matmul's rows (padded to the sublane tile). Operands enter the MXU in
  the pool's dtype and accumulate in float32; scores, running max,
  normaliser and accumulator are float32; the probabilities are rounded
  to the pool's dtype for the PV product, as the einsum path rounds them;
- a row's result depends on its own pages and length alone, block by
  block in logical order at a fixed block size: the same bits alone and
  in any batch, and the same bits whether the token was written by the
  kernel or by ``paged_append`` before it.

bf16/f32 pools only: the int8 pool's scale planes stay on the einsum path.
CPU tests run the kernel in Pallas interpreter mode
(tests/test_paged_decode_kernel.py); ``chip_smoke.py`` lowers it with
Mosaic at the benchmark's shapes and ``scripts/bench-decode.py`` times it.

The reference has no kernels at all (SURVEY §2); within this rebuild the
kernel is the serving-side sibling of ops/flash_attention.py.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

NEG_INF = -1e30
# K/V slots a block holds: the grain of the copies and of the online
# softmax. Fixed, so that a row's sums are taken in one order everywhere.
PAGE_BLOCK_TOKENS = 512
LANES = 128  # the lane tile: Mosaic copies no page whose head is narrower


def on_tpu() -> bool:
    """The backend's part of ``reads_pages_in_place``. Tests patch this, as
    they patch ``flash_attention.uses_flash``; the kernel itself still asks
    the real backend whether to lower or to interpret."""
    return jax.devices()[0].platform == "tpu"


def reads_pages_in_place(
    c_layer: dict, window: int, sliding_window: int | None, mesh=None
) -> bool:
    """THE predicate of how the decode step addresses the pool (``c_layer``:
    the pool, or one layer's slice of it), over what the traced program can
    see: the kernel, writing and reading in place, where the backend is a
    TPU, the window is one token, the pool has no scale planes, no
    ``sliding_window`` is masked over the layer's pages (asked per layer
    kind: a "full_attention" layer has none, and a "sliding_attention"
    layer keeps a ring by row and no pages, so it never asks), the head
    fills the lane tile and, under a mesh, the KV heads divide over tp;
    ``paged_append``, ``paged_read`` and the einsums on the layer's slice
    otherwise."""
    if "ckv" in c_layer:  # a latent: one KV head of the slot's width
        kvh, dh = 1, c_layer["ckv"].shape[-1]
    else:
        kvh, _, dh = c_layer["k"].shape[-3:]
    tp = 1 if mesh is None else dict(mesh.shape).get("tp", 1)
    return (
        on_tpu()
        and window == 1
        and "k_s" not in c_layer
        and sliding_window is None
        and dh % LANES == 0
        and kvh % tp == 0
    )


def _kernel(
    bt_ref,        # scalar prefetch: [B, P] block table (int32)
    len_ref,       # scalar prefetch: [B] visible lengths (int32)
    layer_ref,     # scalar prefetch: [1] the layer of the stacked leaf
    q_ref,         # VMEM [1, kvh, rep_p, dh]
    *refs,         # see ``paged_decode_attention``: the form with a write
                   # has the new token's K/V before the pool and the pool
                   # again, aliased, among the outputs
    sm_scale: float, writes: bool, v_width: int,
):
    # ``leaves`` K/V leaves: two, or ONE whose slots are keys whole and
    # values in their first ``v_width`` (a latent)
    leaves = 1 if v_width else 2
    if writes:
        new_refs = refs[:leaves]  # VMEM [1, kvh, 1, dh]: the row's new token
        # (the pool as an input follows: donated to the outputs)
        o_ref = refs[2 * leaves]
        hbm = refs[2 * leaves + 1:3 * leaves + 1]  # as an output: read AND written
        bufs = refs[3 * leaves + 1:4 * leaves + 1]
        sems, write_sems = refs[4 * leaves + 1:]
    else:
        hbm, o_ref = refs[:leaves], refs[leaves]
        bufs, (sems,) = refs[leaves + 1:2 * leaves + 1], refs[2 * leaves + 1:]
    # hbm: HBM [layers, n_pages, kvh, ps, dh], each leaf as it lies
    # o_ref: VMEM [1, kvh, rep_p, dv]
    # bufs: VMEM [2, ppb, kvh, ps, dh] each, two blocks of pages
    # sems: DMA semaphores [2 (k, v), 2 (buffer)]; write_sems [2 (k, v)]
    b = pl.program_id(0)
    layer = layer_ref[0]
    _, ppb, kvh, ps, dh = bufs[0].shape
    rep_p, dv = q_ref.shape[2], o_ref.shape[3]
    block_tokens = ppb * ps
    length = len_ref[b]
    n_live = (length + ps - 1) // ps           # pages with a visible slot
    n_blocks = (n_live + ppb - 1) // ppb

    def page_copies(page, buf, j):
        return tuple(
            pltpu.make_async_copy(
                x_hbm.at[layer, page], x_buf.at[buf, j], sems.at[i, buf]
            )
            for i, (x_hbm, x_buf) in enumerate(zip(hbm, bufs))
        )

    def write_backs(page, buf, j):
        return tuple(
            pltpu.make_async_copy(
                x_buf.at[buf, j], x_hbm.at[layer, page], write_sems.at[i]
            )
            for i, (x_hbm, x_buf) in enumerate(zip(hbm, bufs))
        )

    def live_in(block):  # pages of this block that hold a visible slot
        return jnp.minimum(ppb, n_live - block * ppb)

    def fetch(block, buf):
        def start(j, carry):
            for copy in page_copies(bt_ref[b, block * ppb + j], buf, j):
                copy.start()
            return carry

        # a dead page of the boundary block is not copied: its scores are
        # masked, but 0 * whatever VMEM held must still be 0
        def clear(j, carry):
            v_buf = bufs[-1]
            v_buf[buf, j] = jnp.zeros(v_buf.shape[2:], v_buf.dtype)
            return carry

        live = live_in(block)
        lax.fori_loop(0, live, start, 0)
        lax.fori_loop(live, ppb, clear, 0)

    def wait(block, buf):
        def one(j, carry):
            for copy in page_copies(0, buf, j):  # the page is not read
                copy.wait()
            return carry

        lax.fori_loop(0, live_in(block), one, 0)

    def append(buf):
        """The new token into its slot of the row's boundary page, which
        the last block has just brought into VMEM, and that ONE page on its
        way back to where it lies: the block is then computed over what the
        pool will hold."""
        at = length - 1
        j = at // ps % ppb
        here = lax.broadcasted_iota(jnp.int32, (kvh, ps, dh), 1) == at % ps
        for new_ref, x_buf in zip(new_refs, bufs):
            x_buf[buf, j] = jnp.where(here, new_ref[0], x_buf[buf, j])
        for copy in write_backs(bt_ref[b, at // ps], buf, j):
            copy.start()

    @pl.when(n_blocks > 0)
    def _first():
        fetch(0, 0)

    def block_step(i, carry):
        buf = i % 2

        @pl.when(i + 1 < n_blocks)
        def _next():
            fetch(i + 1, 1 - buf)

        wait(i, buf)
        if writes:
            pl.when(i + 1 == n_blocks)(functools.partial(append, buf))
        slot = i * block_tokens + lax.broadcasted_iota(
            jnp.int32, (rep_p, block_tokens), 1
        )
        visible = slot < length
        out = []
        # the heads unrolled, their running sums carried as values: the
        # scheduler overlaps one head's matmuls with another's softmax
        # (rolled, or with the sums in VMEM, a layer took 219-254 us where
        # this takes 188 in mistral7b_chat; my chip runs, PR 30)
        for g, (m_prev, l_prev, acc) in enumerate(carry):
            k = bufs[0][buf, :, g].reshape(block_tokens, dh)
            v = k[:, :dv] if v_width else bufs[1][buf, :, g].reshape(
                block_tokens, dh
            )
            s = lax.dot_general(
                q_ref[0, g], k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * sm_scale                           # [rep_p, block_tokens]
            s = jnp.where(visible, s, NEG_INF)
            m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)
            out.append((
                m_new,
                l_prev * alpha + p.sum(axis=-1, keepdims=True),
                acc * alpha + lax.dot_general(
                    p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                ),
            ))
        return tuple(out)

    start = tuple(
        (
            jnp.full((rep_p, 1), NEG_INF, jnp.float32),
            jnp.zeros((rep_p, 1), jnp.float32),
            jnp.zeros((rep_p, dv), jnp.float32),
        )
        for _ in range(kvh)
    )
    for g, (_, l, acc) in enumerate(lax.fori_loop(0, n_blocks, block_step, start)):
        o_ref[0, g] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)

    if writes:
        # the page has landed before the next row may fetch it (dead rows
        # all write the scratch page) or reuse the buffer it leaves from
        @pl.when(n_blocks > 0)
        def _landed():
            for copy in write_backs(0, 0, 0):  # the page is not read
                copy.wait()


def paged_decode_attention(
    q: jax.Array,            # [B, nh, dh] — ONE query token per row
    k_pages: jax.Array,      # [layers, n_pages, kvh, ps, dh] — the stacked
    v_pages: jax.Array | None,  # pool leaf ([n_pages, kvh, ps, dh]: of one layer)
    block_table: jax.Array,  # [B, P] int32 logical block -> physical page
    lengths: jax.Array,      # [B] int32 visible length per row (pos + 1)
    sm_scale: float | None = None,
    interpret: bool | None = None,
    mesh=None,
    layer=0,                 # int32 scalar, traced or not: the leaf's layer
    k_new: jax.Array | None = None,  # [B, kvh, dh] — the token at slot
    v_new: jax.Array | None = None,  # ``lengths - 1``, to be written first
    v_width: int = 0,        # a latent pool: the values' width (see below)
):
    """Single-token paged attention over each row's live pages of ``layer``
    (module docstring): [B, nh, dh]. GQA-native: ``nh % kvh == 0``; bf16/f32
    pools.

    With ``k_new`` / ``v_new`` the row's newest token, the one the lengths
    already count, is put into the pool in place on the way, and the result
    is ``(attention, k_pages, v_pages)``: the leaves the call was given,
    which it must be allowed to overwrite (donated, or a loop's carry), with
    B slots changed. A row of length 0 writes nothing.

    A LATENT pool (``v_pages`` None, ``v_width`` > 0; latent attention's
    absorbed form) is ONE stacked leaf ``k_pages`` [layers, n_pages, ps, dh]
    with one KV head: a slot is a key whole and a value in its first
    ``v_width``, so a page is copied once and serves both products. The
    result is [B, nh, v_width], with ``k_new`` [B, 1, dh]: ``(attention,
    k_pages)``.

    Under ``mesh`` each device runs the kernel over its own KV heads in
    ``shard_map`` (GSPMD cannot partition a ``pallas_call``): the kvh axis
    of the pool leaf over tp, as ``ContinuousBatcher._pool_sharding`` lays
    it, and the heads of q and of the new token the same way, q's being
    group-major. Heads are independent, so there is no collective; every
    other axis sees replicas."""
    writes = k_new is not None
    if v_pages is None:
        if not v_width or mesh is not None:
            raise ValueError(
                "one leaf for keys and values is a latent pool: it needs "
                "v_width and takes no mesh (one KV head)"
            )
        out = _paged_decode(
            q, (k_pages[:, :, None],), block_table, lengths, sm_scale,
            interpret, layer, (k_new,) if writes else (), v_width,
        )
        return (out[0], out[1][:, :, 0]) if writes else out
    if k_pages.ndim == 4:  # one layer's slice is a stack of one layer
        out = paged_decode_attention(
            q, k_pages[None], v_pages[None], block_table, lengths, sm_scale,
            interpret, mesh, 0, k_new, v_new,
        )
        return (out[0], out[1][0], out[2][0]) if writes else out
    if mesh is not None:
        tp = "tp" if "tp" in mesh.axis_names else None
        heads, pool = P(None, tp, None), P(None, None, tp, None, None)

        def per_device(q, k_pages, v_pages, block_table, lengths, layer, *new):
            return paged_decode_attention(
                q, k_pages, v_pages, block_table, lengths, sm_scale,
                interpret, None, layer, *new,
            )

        return jax.shard_map(
            per_device,
            mesh=mesh,
            in_specs=(heads, pool, pool, P(), P(), P()) + (heads,) * 2 * writes,
            out_specs=(heads, pool, pool) if writes else heads,
            check_vma=False,  # vma checking cannot lower a pallas_call yet
        )(
            q, k_pages, v_pages, block_table, lengths,
            jnp.asarray(layer, jnp.int32), *((k_new, v_new) if writes else ()),
        )
    return _paged_decode(
        q, (k_pages, v_pages), block_table, lengths, sm_scale, interpret,
        layer, (k_new, v_new) if writes else (), 0,
    )


def _paged_decode(
    q, pages, block_table, lengths, sm_scale, interpret, layer, new, v_width
):
    """The ``pallas_call`` on one device: ``pages`` the K and V leaves
    [layers, n_pages, kvh, ps, dh], or the one leaf of a latent pool
    (``v_width``); ``new`` the new token for each leaf, or nothing. Gives
    the attention, then the leaves where there was a write."""
    writes = bool(new)
    B, nh, dh = q.shape
    _, _, kvh, ps, _ = pages[0].shape
    dv = v_width or dh
    dtype = pages[0].dtype
    if nh % kvh:
        raise ValueError(f"n_heads {nh} not a multiple of kv_heads {kvh}")
    rep = nh // kvh
    # query rows padded to the sublane tile of the pool's dtype
    sublanes = 32 // dtype.itemsize
    rep_p = -(-rep // sublanes) * sublanes
    if sm_scale is None:
        sm_scale = dh ** -0.5
    if interpret is None:
        interpret = jax.devices()[0].platform != "tpu"
    ppb = max(1, min(PAGE_BLOCK_TOKENS // ps, block_table.shape[1]))

    # group-major view [B, kvh, rep, dh], zero-padded to rep_p rows
    qg = q.reshape(B, kvh, rep, dh).astype(dtype)
    if rep_p != rep:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, rep_p - rep), (0, 0)))

    def by_row(*block):
        return pl.BlockSpec((1,) + block, lambda b, *_: (b,) + (0,) * len(block))

    q_spec, new_spec = by_row(kvh, rep_p, dh), by_row(kvh, 1, dh)
    o_spec = by_row(kvh, rep_p, dv)
    in_place = pl.BlockSpec(memory_space=pl.ANY)
    o_shape = jax.ShapeDtypeStruct((B, kvh, rep_p, dv), q.dtype)
    new = tuple(x.astype(dtype).reshape(B, kvh, 1, dh) for x in new)
    n_prefetch = 3  # block table, lengths, layer
    n = len(pages)
    out = pl.pallas_call(
        functools.partial(
            _kernel, sm_scale=float(sm_scale), writes=writes, v_width=v_width
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=n_prefetch,
            grid=(B,),
            in_specs=[q_spec] + [new_spec] * len(new) + [in_place] * n,
            out_specs=(o_spec,) + (in_place,) * n if writes else o_spec,
            scratch_shapes=[
                pltpu.VMEM((2, ppb, kvh, ps, dh), dtype) for _ in pages
            ] + [pltpu.SemaphoreType.DMA((2, 2))]
            + [pltpu.SemaphoreType.DMA((2,))] * writes,
        ),
        out_shape=(o_shape,) + tuple(
            jax.ShapeDtypeStruct(x.shape, x.dtype) for x in pages
        ) if writes else o_shape,
        # the pool operands ARE the pool results (operands count from the
        # scalar-prefetched three, then q and the new token's leaves)
        input_output_aliases={
            n_prefetch + 1 + n + i: 1 + i for i in range(n)
        } if writes else {},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ) if not interpret else None,
        interpret=interpret,
        name="paged_decode_attention",  # the trace's ``XLA Ops`` line shows it
    )(
        block_table.astype(jnp.int32), lengths.astype(jnp.int32),
        jnp.asarray(layer, jnp.int32).reshape(1), qg, *new, *pages,
    )
    if not writes:
        return out[:, :, :rep].reshape(B, nh, dv)
    return (out[0][:, :, :rep].reshape(B, nh, dv),) + tuple(out[1:])
