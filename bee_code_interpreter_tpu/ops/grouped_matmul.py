"""The grouped matmul of the held experts at a decode step's sizes.

``models/moe.grouped_experts`` hands its three matmuls the held experts'
weights of EVERY expert layer, merged into layers x held groups, with the
groups of every layer but the current one empty: a stack is read where it
lies and never cut. At a decode step, where a held expert sees a handful of
rows, ``lax.ragged_dot``'s TPU lowering walks that stack's groups, empty or
not, and the buffer's rows in tiles of its own, and reads the touched
experts' bytes at 44-79 % of the HBM peak (PERF.md section 6).

This kernel is megablox's (``jax.experimental.pallas.ops.tpu.megablox``),
which ships with JAX:

- its metadata squeezes the empty groups out (``visit_empty_groups``
  False): the grid visits the m-tiles of the groups that have rows, one
  step a tile and a column block, in group order, and a tile two groups
  share is visited once by each;
- the weights' index map reads block (group, 0, column block) of the whole
  stack in place. The block is the whole contraction width (``tk = k``), so
  the m-tiles of one group read the same block one after another and the
  pipeline fetches it once; a block is about ``BLOCK_BYTES``, which the
  pipeline double-buffers in VMEM;
- rows that no group covers are never written: what they hold is garbage,
  and the caller keeps them out of every sum with a select. Operands enter
  the MXU in their dtype, accumulate in float32 and are stored in the
  operand's dtype, as ``ragged_dot`` stores them.

CPU tests run it in the Pallas interpreter (tests/test_grouped_matmul.py);
``scripts/bench-grouped-matmul.py`` times it against ``ragged_dot`` at the
benchmark's decode shapes on the chip.
"""

from __future__ import annotations

import importlib

import jax

# the package re-exports its differentiable wrapper under the module's name
_megablox = importlib.import_module("jax.experimental.pallas.ops.tpu.megablox.gmm")

# rows of an m-tile: a multiple of bf16's packed sublane tile, at most
# MAX_ROW_TILE (sarvam-105b's shapes: 128 rows a tile read the HBM peak's
# 84-90 %, 16 rows 79-83 %; PERF.md section 6)
ROW_TILE = 16
MAX_ROW_TILE = 128
# a block of weights, whole contraction width by a multiple of 128 columns:
# at most BLOCK_BYTES, and the pipeline's buffers (two of each block, the
# f32 accumulator) inside VMEM_BUDGET of the 16 MiB a kernel may hold
BLOCK_BYTES = 4 << 20
VMEM_BUDGET = 14 << 20
# buffers with at most this many rows a held expert (a decode step's) take
# the kernel; larger ones (a prefill's) keep ``ragged_dot``
MAX_ROWS_PER_GROUP = 32


def on_tpu() -> bool:
    """The backend's part of ``takes_kernel``. Tests patch this; the kernel
    itself still asks the real backend whether to lower or to interpret."""
    return jax.devices()[0].platform == "tpu"


def takes_kernel(rows: int, groups: int) -> bool:
    """THE rule by which a sorted buffer of ``rows`` rows over ``groups``
    held experts (of one layer) runs through this kernel rather than
    ``lax.ragged_dot``: on a TPU, at most ``MAX_ROWS_PER_GROUP`` rows a
    held expert. Shapes alone, never the configuration's name."""
    return on_tpu() and rows <= MAX_ROWS_PER_GROUP * groups


def padded_rows(rows: int) -> int:
    """The rows the kernel runs a buffer of ``rows`` at: a whole number of
    ``ROW_TILE``."""
    return -(-rows // ROW_TILE) * ROW_TILE


def tiling_for(m: int, k: int, n: int, itemsize: int = 2) -> tuple[int, int, int]:
    """(tm, tk, tn) for ``[m, k] x [groups, k, n]`` (``m`` a multiple of
    ``ROW_TILE``): the largest multiple of ``ROW_TILE`` up to
    ``MAX_ROW_TILE`` that divides ``m``; the whole contraction width; the
    widest multiple of 128 columns that divides ``n`` in a block of at most
    ``BLOCK_BYTES`` whose buffers fit ``VMEM_BUDGET`` (128 at the least, or
    all of a narrower ``n``)."""
    tm = max(
        t for t in range(ROW_TILE, MAX_ROW_TILE + 1, ROW_TILE) if m % t == 0
    )

    def vmem(tn):  # weights and rows double-buffered, out too, accumulator
        return 2 * (k * tn + tm * k + tm * tn) * itemsize + 4 * tm * tn

    tn = min(n, 128)
    for cols in range(128, n + 1, 128):
        if (
            n % cols == 0 and k * cols * itemsize <= BLOCK_BYTES
            and vmem(cols) <= VMEM_BUDGET
        ):
            tn = cols
    return tm, k, tn


def gmm(lhs, rhs, group_sizes, *, tiling=None, interpret=None):
    """``lhs`` [m, k] (m a multiple of the row tile) times ``rhs`` [groups,
    k, n] by the groups ``group_sizes`` [groups] (int32) lays over its
    rows, in ``lhs``'s dtype; rows past the groups are left unwritten."""
    m, k = lhs.shape
    n = rhs.shape[2]
    if interpret is None:
        interpret = jax.devices()[0].platform != "tpu"
    return _megablox.gmm(
        lhs, rhs, group_sizes,
        preferred_element_type=lhs.dtype,
        tiling=tiling or tiling_for(m, k, n, rhs.dtype.itemsize),
        interpret=interpret,
    )
