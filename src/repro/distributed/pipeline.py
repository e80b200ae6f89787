"""Pipeline parallelism (GPipe) over the inter-pod axis.

At 2+ pods the `pod` axis can act as pipeline stages instead of data
parallelism: inter-pod links are the slowest in the fleet, and PP crosses
them once per microbatch boundary instead of once per gradient
all-reduce.  Implementation: shard_map over `pod`; layers are split into
`stages` contiguous groups; microbatches stream through with
`ppermute`-rotated activations (1F1B-simplified: forward streaming,
backward handled by autodiff through the loop — checkpointed per stage).

The schedule executes stages*microbatches steps; at step t, stage s works
on microbatch (t − s), giving the classic (stages−1) bubble out of
(microbatches + stages − 1) slots — bubble fraction reported by
``bubble_fraction``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from jax import shard_map


def bubble_fraction(stages: int, microbatches: int) -> float:
    return (stages - 1) / (microbatches + stages - 1)


def make_pipeline(mesh, apply_layer, n_layers: int, axis: str = "pod",
                  *, microbatches: int):
    """apply_layer(params_l, x) → x; params stacked (L, …).

    Returns fn(params, x (B, …)) → y computed as `stages` pipeline stages
    over `axis`, microbatching the leading batch dim.
    """
    stages = mesh.shape[axis]
    assert n_layers % stages == 0
    per_stage = n_layers // stages

    def local(params_stage, x_all):
        """params_stage: this stage's (L/stages, …) slice; x_all: full
        batch (every stage holds the input; only stage 0 uses it)."""
        me = jax.lax.axis_index(axis)
        b = x_all.shape[0]
        assert b % microbatches == 0
        mb = b // microbatches
        xmb = x_all.reshape((microbatches, mb) + x_all.shape[1:])
        perm = [(i, (i + 1) % stages) for i in range(stages)]
        n_steps = microbatches + stages - 1

        def stage_apply(x):
            def body(h, p_l):
                return apply_layer(p_l, h), None
            h, _ = jax.lax.scan(jax.checkpoint(body), x, params_stage)
            return h

        def step(carry, t):
            inflight, outputs = carry
            # stage 0 injects microbatch t; others take the rotated buffer
            mb_idx = jnp.clip(t, 0, microbatches - 1)
            inject = jax.lax.dynamic_index_in_dim(xmb, mb_idx, 0,
                                                  keepdims=False)
            h_in = jnp.where(me == 0, inject, inflight)
            h_out = stage_apply(h_in)
            # last stage writes its finished microbatch (t - stages + 1)
            out_idx = jnp.clip(t - stages + 1, 0, microbatches - 1)
            write = (me == stages - 1) & (t >= stages - 1)
            outputs = jax.lax.cond(
                write,
                lambda o: jax.lax.dynamic_update_index_in_dim(
                    o, h_out, out_idx, 0),
                lambda o: o,
                outputs,
            )
            inflight = jax.lax.ppermute(h_out, axis, perm)
            return (inflight, outputs), None

        inflight0 = jnp.zeros_like(xmb[0])
        outputs0 = jnp.zeros_like(xmb)
        if hasattr(jax.lax, "pcast"):
            inflight0 = jax.lax.pcast(inflight0, (axis,), to="varying")
            outputs0 = jax.lax.pcast(outputs0, (axis,), to="varying")
        (_, outputs), _ = jax.lax.scan(step, (inflight0, outputs0),
                                       jnp.arange(n_steps))
        # only the last stage holds real outputs; broadcast via psum of
        # the masked buffer (ppermute needs unique destinations)
        outputs = jnp.where(me == stages - 1, outputs, 0)
        outputs = jax.lax.psum(outputs, axis)
        return outputs.reshape((b,) + x_all.shape[1:])

    def run(params, x):
        kw = dict(
            mesh=mesh,
            in_specs=(P(axis), P()),   # params layer-split across stages
            out_specs=P(),
        )
        fn = shard_map(local, **kw)
        return fn(params, x)

    return run
