"""Compute/communication overlap: ring collective matmul (shard_map).

Sequence-parallel layers gather the sequence dim before their first
matmul: y = all_gather(x) @ W.  The naive plan serializes the gather
before any MXU work.  The ring form computes the output **row block** for
the x-chunk currently resident while the next chunk travels the ring —
hiding (P−1)/P of the communication behind compute.  XLA performs this
rewrite itself in favourable cases ("collective matmul"); expressing it
explicitly via shard_map + ppermute makes the overlap deterministic and
available as a §Perf lever.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from jax import shard_map


def ring_allgather_matmul(mesh, axis: str = "model"):
    """fn(x (S, D) seq-sharded over `axis`, w (D, F) replicated) → (S, F).

    Per device: world steps; step t multiplies the chunk from device
    (me − t) mod world and writes its output row block, then forwards the
    chunk along the ring.  Output replicated (all devices hold all rows).
    """
    world = mesh.shape[axis]

    def local(x, w):  # x (S/P, D); w (D, F)
        me = jax.lax.axis_index(axis)
        s_loc = x.shape[0]
        perm = [(i, (i + 1) % world) for i in range(world)]

        def step(carry, t):
            y, xs = carry
            src = (me - t) % world
            blk = jnp.dot(xs, w, preferred_element_type=jnp.float32)
            y = jax.lax.dynamic_update_slice_in_dim(
                y, blk.astype(y.dtype)[None], src, axis=0
            )
            xs = jax.lax.ppermute(xs, axis, perm)
            return (y, xs), None

        y0 = jnp.zeros((world, s_loc, w.shape[-1]), x.dtype)
        if hasattr(jax.lax, "pcast"):  # mark the carry device-varying (VMA)
            y0 = jax.lax.pcast(y0, (axis,), to="varying")
        (y, _), _ = jax.lax.scan(step, (y0, x), jnp.arange(world))
        return y.reshape(world * s_loc, w.shape[-1])

    # output is replicated by construction, but VMA can't prove it
    return shard_map(
        local, mesh=mesh,
        in_specs=(P(axis, None), P(None, None)),
        out_specs=P(None, None),
    )


def reference_allgather_matmul(mesh, axis: str = "model"):
    """Unoverlapped baseline: all_gather(x) then one big matmul."""

    def local(x, w):
        xg = jax.lax.all_gather(x, axis, axis=0, tiled=True)
        return jnp.dot(xg, w, preferred_element_type=jnp.float32).astype(x.dtype)

    return shard_map(
        local, mesh=mesh,
        in_specs=(P(axis, None), P(None, None)),
        out_specs=P(None, None),
    )
