"""int8 gradient compression for cross-pod reduction (DESIGN.md §6).

Cross-pod ICI/DCN links are the scarcest bandwidth at multi-pod scale. The
hierarchical scheme: GSPMD reduces gradients *within* a pod at full precision
(implicit in the sharded train step); the *cross-pod* reduction runs through
`compressed_psum` inside a shard_map over the 'pod' axis — int8 codes + one
f32 scale per tensor, a 4x byte reduction on the slowest links.

Quantization is symmetric per-tensor: q = round(g / s), s = max|g| / 127,
summed in int32 (pod counts are tiny: no overflow below 2^23 / 127 pods).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def quantize_int8(g):
    scale = jnp.max(jnp.abs(g.astype(jnp.float32))) / 127.0
    scale = jnp.maximum(scale, 1e-30)
    q = jnp.clip(jnp.round(g.astype(jnp.float32) / scale), -127, 127)
    return q.astype(jnp.int8), scale


def dequantize_int8(q, scale):
    return q.astype(jnp.float32) * scale


def compressed_psum(grads, axis: str):
    """psum a gradient pytree across `axis` with int8 payload.

    Each participant quantizes with its own scale; scales are maxed across
    the axis first so codes are commensurable (one extra scalar all-reduce).
    """
    def one(g):
        scale = jnp.max(jnp.abs(g.astype(jnp.float32))) / 127.0
        scale = jax.lax.pmax(jnp.maximum(scale, 1e-30), axis)
        q = jnp.clip(jnp.round(g.astype(jnp.float32) / scale), -127, 127)
        total = jax.lax.psum(q.astype(jnp.int32), axis)
        n = jax.lax.psum(1, axis)
        return (total.astype(jnp.float32) * scale / n).astype(g.dtype)

    return jax.tree.map(one, grads)


def make_crosspod_mean(mesh, axis: str = "pod"):
    """Returns fn(grads)->grads averaging across pods with int8 payload.

    grads are assumed replicated across `axis` shards *within* each pod
    already (the in-pod reduction is full precision, done by GSPMD)."""
    other = tuple(n for n in mesh.axis_names if n != axis)

    def spec_for(g):
        return P()  # replicated entering the wrapper; shard_map splits axis

    @partial(jax.shard_map, mesh=mesh, in_specs=(P(),), out_specs=P(),
             check_vma=False)
    def _mean(g):
        return compressed_psum(g, axis)

    return _mean
