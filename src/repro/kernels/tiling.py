"""The padding every Pallas kernel wrapper here applies before it launches:
rows up to the kernel's row tile, client slots up to the 128-lane width.
The wrappers pad with these shapes, and the fleet engine counts the rows
and lanes a launch covers with them."""
from __future__ import annotations

LANES = 128
TILE_N = 8     # row tile of bisect_alloc, dual_demand and mbdf_demand


def padded_shape(n: int, k: int, tile_n: int = TILE_N) -> tuple[int, int]:
    """(rows, lanes) that a launch covers for an (n, k) service tensor."""
    return -(-n // tile_n) * tile_n, -(-k // LANES) * LANES
