"""The rows every Pallas kernel wrapper here launches on, and how a vmap of
a wrapper reaches one launch.

Rows pad up to the kernel's row tile, client slots up to the 128-lane
width.  The wrappers pad with these shapes, and the fleet engine counts
the rows and lanes a launch covers with them.  ``fold_rows`` gives a
wrapper's launch a batching rule that folds the vmapped batch into the row
axis, so a vmapped call is one launch over every row of the batch and
each grid step bisects a whole row block.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

LANES = 128
SUBLANES = 8
# Most rows a grid step of bisect_alloc, dual_demand and mbdf_demand takes:
# the fastest of 64, 128, 256 and 512 on a TPU v5e for the selfish sweep's
# 640-row launches, inside the default scoped VMEM (PERF.md).
ROW_CAP = 512


def row_tile(rows: int) -> int:
    """Row block of a launch over ``rows`` rows: the fewest grid steps of at
    most ``ROW_CAP`` rows, with the rows split evenly among them and the
    block rounded up to the 8 sublanes, so each step pads under 8 rows."""
    steps = -(-rows // ROW_CAP)
    per_step = -(-rows // steps)
    return -(-per_step // SUBLANES) * SUBLANES


def padded_shape(n: int, k: int, tile_n: int | None = None
                 ) -> tuple[int, int]:
    """(rows, lanes) that a launch covers for an (n, k) service tensor, at
    ``row_tile(n)`` unless a fixed row tile is given."""
    tile_n = tile_n or row_tile(n)
    return -(-n // tile_n) * tile_n, -(-k // LANES) * LANES


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
