"""The rows every Pallas kernel wrapper here launches on, and how a vmap of
a wrapper reaches one launch.

Rows pad up to the kernel's row tile, client slots up to the 128-lane
width.  The row tile follows the lanes: a grid step holds ``planes``
(tile, lanes) float32 planes in VMEM (the kernel's double-buffered input
and output blocks and the temporaries of a bisection trip), which must fit
``VMEM_BUDGET``.  Each folded kernel's module states its planes once and
gives its launch by them (``row_blocks``); its wrapper pads to that
launch, and the fleet engine counts the rows, lanes and VMEM bytes of the
same launch (``policy.launch_shape``).  ``fold_rows`` gives a wrapper's
launch a batching rule that folds the vmapped batch into the row axis, so
a vmapped call is one launch over every row of the batch and each grid
step bisects a whole row block.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu

LANES = 128
SUBLANES = 8
# Most rows a grid step of bisect_alloc, dual_demand and mbdf_demand takes:
# the fastest of 64, 128, 256 and 512 on a TPU v5e for the selfish sweep's
# 640-row launches at 128 lanes (PERF.md).
ROW_CAP = 512
# Scoped VMEM a TPU v5e gives a kernel unless it asks for more, and what a
# launch whose blocks pass three quarters of that asks for (of the chip's
# 128 MiB).  Narrower launches ask for nothing, so they compile as before.
DEFAULT_VMEM_LIMIT = 16 * 2 ** 20
VMEM_LIMIT = 32 * 2 ** 20
# VMEM bytes a grid step's (tile, lanes) planes may take: three quarters of
# the limit, leaving the rest to the (tile, 1) and (tile, M) blocks, which
# take a whole 128-lane group each, and to the compiler's own scratch.
VMEM_BUDGET = VMEM_LIMIT * 3 // 4


def block_bytes(tile_n: int, k: int, planes: int) -> int:
    """VMEM bytes of ``planes`` float32 planes of ``tile_n`` rows of ``k``
    client slots padded to the lane width: one grid step's account."""
    return 4 * planes * tile_n * (-(-k // LANES) * LANES)


def row_tile(rows: int, k: int, planes: int) -> int:
    """Row block of a launch over ``rows`` rows of ``k`` client slots, for a
    kernel that keeps ``planes`` (block, lanes) float32 planes in VMEM: the
    fewest grid steps whose block is at most ``ROW_CAP`` rows and fits
    ``VMEM_BUDGET``, with the rows split evenly among them and the block
    rounded up to the 8 sublanes, so each step pads under 8 rows."""
    fit = VMEM_BUDGET // block_bytes(SUBLANES, k, planes) * SUBLANES
    if fit < SUBLANES:
        raise ValueError(f"{k} client slots: a block of {SUBLANES} rows "
                         f"takes {block_bytes(SUBLANES, k, planes)} bytes, "
                         f"over the VMEM budget of {VMEM_BUDGET}")
    cap = min(ROW_CAP, fit)
    steps = -(-rows // cap)
    per_step = -(-rows // steps)
    return -(-per_step // SUBLANES) * SUBLANES


def padded_shape(n: int, k: int, tile_n: int) -> tuple[int, int]:
    """(rows, lanes) that a launch in row blocks of ``tile_n`` covers for an
    (n, k) service tensor."""
    return -(-n // tile_n) * tile_n, -(-k // LANES) * LANES


class Launch(NamedTuple):
    """One launch over a row axis: the padded rows and lanes it covers, the
    rows a grid step takes, and the VMEM bytes of one grid step."""
    rows: int
    lanes: int
    tile: int
    block_bytes: int

    @property
    def compiler_params(self):
        """The kernel's compiler parameters: ``VMEM_LIMIT`` where a grid
        step's planes pass three quarters of the default scoped VMEM, else
        none."""
        if self.block_bytes <= DEFAULT_VMEM_LIMIT * 3 // 4:
            return None
        return pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT)


def row_blocks(rows: int, k: int, planes: int) -> Launch:
    """The launch of a kernel that keeps ``planes`` (block, lanes) float32
    planes in VMEM over ``rows`` rows of ``k`` client slots."""
    tile = row_tile(rows, k, planes)
    return Launch(*padded_shape(rows, k, tile), tile,
                  block_bytes(tile, k, planes))


def fold_rows(launch):
    """``launch(*operands)``, every operand and output leading with the row
    axis, with a vmap rule that folds the batch into that axis: the batch
    moves to the front (an unbatched operand is broadcast to it), (E, n, ...)
    folds to (E * n, ...), ``launch`` runs once on the folded rows and its
    outputs unfold to (E, n, ...).  Rows are independent in every kernel
    here, so each row's result is what an unbatched call gives it.  The
    rule calls the folded function again, so nested vmaps fold level by
    level into one launch."""

    @jax.custom_batching.custom_vmap
    def folded(*operands):
        return launch(*operands)

    @folded.def_vmap
    def _fold(axis_size, in_batched, *operands):
        def to_rows(x, batched):
            if not batched:
                x = jnp.broadcast_to(x, (axis_size,) + x.shape)
            return x.reshape((-1,) + x.shape[2:])

        out = folded(*map(to_rows, operands, in_batched))
        unfolded = jax.tree.map(
            lambda y: y.reshape((axis_size, -1) + y.shape[1:]), out)
        return unfolded, jax.tree.map(lambda _: True, out)

    return folded
