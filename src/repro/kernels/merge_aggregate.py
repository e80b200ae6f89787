"""Fused merge-and-absorb of two sorted tiles — the wide-merge inner loop.

One wide-merge step (§4, Fig 9) takes the resident sorted index tile and
one incoming run page, and must produce the merged, duplicate-combined
index.  Unfused, that is: concat → full sort → segmented reduce.  Fused,
we exploit that **both inputs are already sorted**: concatenating A with
reverse(B) yields a bitonic sequence, so a *single* bitonic-merge sweep
(log₂(2N) compare-exchange stages instead of the full sort's
log²-stage network) orders the union; the segmented-scan absorb then runs
in the same kernel while everything is VMEM-resident — one HBM round trip
per page instead of three.

Payload columns (count/sum/min/max) ride along through both phases.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.types import EMPTY
from repro.kernels.segmented_reduce import _segmented_scan, pallas_call


def _bitonic_merge(keys, cols):
    """keys (1,2N) forming a bitonic sequence; cols: list of (C,2N) arrays.
    One descending-stride sweep yields ascending order."""
    n2 = keys.shape[-1]
    idx = jax.lax.broadcasted_iota(jnp.int32, keys.shape, 1)
    j = n2 // 2
    while j >= 1:
        upper = (idx & j) != 0
        part_hi = jnp.roll(keys, j, axis=-1)
        part_lo = jnp.roll(keys, -j, axis=-1)
        partner = jnp.where(upper, part_hi, part_lo)
        take_self = jnp.where(~upper, keys <= partner, keys >= partner)
        new_cols = []
        for c in cols:
            c_hi = jnp.roll(c, j, axis=-1)
            c_lo = jnp.roll(c, -j, axis=-1)
            c_part = jnp.where(upper, c_hi, c_lo)
            new_cols.append(jnp.where(take_self, c, c_part))
        keys = jnp.where(take_self, keys, partner)
        cols = new_cols
        j //= 2
    return keys, cols


def _kernel(ka_ref, ca_ref, sa_ref, mna_ref, mxa_ref,
            kb_ref, cb_ref, sb_ref, mnb_ref, mxb_ref,
            ok_ref, oc_ref, os_ref, omn_ref, omx_ref, ot_ref):
    # phase 1: bitonic merge of (A, reverse(B))
    keys = jnp.concatenate([ka_ref[...], kb_ref[...][:, ::-1]], axis=-1)
    cols = [
        jnp.concatenate([ca_ref[...], cb_ref[...][:, ::-1]], axis=-1),
        jnp.concatenate([sa_ref[0], sb_ref[0][:, ::-1]], axis=-1),
        jnp.concatenate([mna_ref[0], mnb_ref[0][:, ::-1]], axis=-1),
        jnp.concatenate([mxa_ref[0], mxb_ref[0][:, ::-1]], axis=-1),
    ]
    keys, cols = _bitonic_merge(keys, cols)
    cnt, ssum, smin, smax = cols
    # phase 2: absorb duplicates (segmented scan) while still in VMEM
    cnt, ssum, smin, smax, tails = _segmented_scan(keys, cnt, ssum, smin, smax)
    ok_ref[...] = keys
    oc_ref[...] = cnt
    os_ref[...] = ssum[None]
    omn_ref[...] = smin[None]
    omx_ref[...] = smax[None]
    ot_ref[...] = tails


@functools.partial(jax.jit, static_argnames=("interpret",))
def merge_absorb_tiles(ka, ca, sa, mna, mxa, kb, cb, sb, mnb, mxb, *,
                       interpret: bool = True):
    """Merge two sorted (T,N)/(T,V,N) tile sets → (T,2N) merged + scanned
    aggregates + tail mask (compaction done by the caller, see ops.py)."""
    t, n = ka.shape
    v = sa.shape[1]
    s1 = pl.BlockSpec((1, n), lambda i: (i, 0))
    sv = pl.BlockSpec((1, v, n), lambda i: (i, 0, 0))
    o1 = pl.BlockSpec((1, 2 * n), lambda i: (i, 0))
    ov = pl.BlockSpec((1, v, 2 * n), lambda i: (i, 0, 0))
    *out, tails = pallas_call(
        _kernel,
        out_shape=(
            jax.ShapeDtypeStruct((t, 2 * n), ka.dtype),
            jax.ShapeDtypeStruct((t, 2 * n), ca.dtype),
            jax.ShapeDtypeStruct((t, v, 2 * n), sa.dtype),
            jax.ShapeDtypeStruct((t, v, 2 * n), mna.dtype),
            jax.ShapeDtypeStruct((t, v, 2 * n), mxa.dtype),
            jax.ShapeDtypeStruct((t, 2 * n), jnp.int32),
        ),
        grid=(t,),
        in_specs=[s1, s1, sv, sv, sv, s1, s1, sv, sv, sv],
        out_specs=(o1, o1, ov, ov, ov, o1),
        interpret=interpret,
    )(ka, ca, sa, mna, mxa, kb, cb, sb, mnb, mxb)
    return (*out, tails != 0)
