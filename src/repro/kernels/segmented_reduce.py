"""Segmented reduce-by-key over sorted tiles — the "absorb" hot spot.

Given key-sorted rows, equal keys form segments; the paper's b-tree absorb
(aggregate a row into its group) becomes a **flag-based segmented scan**:

    for d in 1, 2, 4, … N/2:
        v[i] ← v[i] ⊕ v[i−d]   unless a segment boundary lies in (i−d, i]
        f[i] ← f[i] ∨ f[i−d]

log₂N data-parallel steps, each a lane roll + masked combine — exactly the
structure the bitonic kernel uses, so both map onto the same VPU idiom.
Segment *tails* then hold complete group aggregates (count/sum/min/max);
compaction of tails to the front is a cheap memory-bound scatter done by
the XLA caller (see ops.py) — the O(N log N) compute lives here.

The kernel carries all aggregate columns in one fused pass: count and sum
scan with ⊕ = add, min/max columns with ⊕ = min/max, sharing the boundary
flags and the rolls' mask logic.  The value planes may have different
widths (an AggSpec that skips e.g. min/max passes a 1-wide dummy plane).

Keys arrive as one or two uint32 **lanes**: 32-bit keys are a single
lane; 64-bit keys are a (hi, lo) pair compared/equality-tested per lane,
so the kernel never needs native 64-bit integer ops on the VPU.  A key is
EMPTY iff *every* lane is the uint32 EMPTY (the 64-bit sentinel's halves
are both 0xFFFF_FFFF).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.types import EMPTY, match_vma


def pallas_call(kernel, *, out_shape, **kw):
    """``pl.pallas_call`` for the 32-bit kernels, callable from a uint64
    engine and per shard.  Under the scoped x64 of 64-bit keys the
    kernel (its grid index maps included) would trace with 64-bit
    integers, which Mosaic cannot lower, so it traces with x64 off; its
    operands are 32-bit lanes either way.  Inside a ``shard_map`` whose
    varying-manual-axes check is on, the operands are cast to vary over
    the same mesh axes and the outputs are declared to vary over them."""

    def call(*args):
        args = match_vma(args, args)
        vma = frozenset().union(*(jax.typeof(a).vma for a in args))
        shapes = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, vma=vma),
            out_shape)
        with jax.enable_x64(False):
            return pl.pallas_call(kernel, out_shape=shapes, **kw)(*args)

    return call


def _lex_leq(a_lanes, b_lanes):
    """a <= b on multi-lane keys (hi lane first)."""
    leq = a_lanes[-1] <= b_lanes[-1]
    for a, b in zip(reversed(a_lanes[:-1]), reversed(b_lanes[:-1])):
        leq = (a < b) | ((a == b) & leq)
    return leq


def _lanes_eq(a_lanes, b_lanes):
    """Elementwise equality of two multi-lane key vectors."""
    eq = a_lanes[0] == b_lanes[0]
    for a, b in zip(a_lanes[1:], b_lanes[1:]):
        eq = eq & (a == b)
    return eq


def _lanes_empty(lanes):
    """True where the (possibly multi-lane) key is the EMPTY sentinel."""
    e = lanes[0] == EMPTY
    for k in lanes[1:]:
        e = e & (k == EMPTY)
    return e


def _segmented_scan(keys_lanes, cnt, ssum, smin, smax):
    """keys_lanes: tuple of (1,N) uint32 lanes (hi→lo); cnt (1,N);
    ssum/smin/smax (V?,N).  Returns scanned values and the int32 tail
    flags (1 on the last row of each segment).  Segment flags travel as
    int32: Mosaic can neither roll nor store boolean vectors."""
    if not isinstance(keys_lanes, (tuple, list)):
        keys_lanes = (keys_lanes,)
    k0 = keys_lanes[0]
    n = k0.shape[-1]
    idx = jax.lax.broadcasted_iota(jnp.int32, k0.shape, 1)
    valid = ~_lanes_empty(keys_lanes)
    prev = [jnp.roll(k, 1, axis=-1) for k in keys_lanes]
    heads = ~_lanes_eq(keys_lanes, prev) | (idx == 0)
    f = (heads | ~valid).astype(jnp.int32)
    d = 1
    while d < n:
        fd = jnp.roll(f, d, axis=-1)
        edge = idx < d
        can_add = (f == 0) & ~edge
        cd = jnp.roll(cnt, d, axis=-1)
        cnt = jnp.where(can_add, cnt + cd, cnt)
        # value columns broadcast the (1,N) mask over their V rows
        sd = jnp.roll(ssum, d, axis=-1)
        ssum = jnp.where(can_add, ssum + sd, ssum)
        mnd = jnp.roll(smin, d, axis=-1)
        smin = jnp.where(can_add, jnp.minimum(smin, mnd), smin)
        mxd = jnp.roll(smax, d, axis=-1)
        smax = jnp.where(can_add, jnp.maximum(smax, mxd), smax)
        f = jnp.where(edge, 1, f | fd)
        d *= 2
    nxt = [jnp.roll(k, -1, axis=-1) for k in keys_lanes]
    tails = (~_lanes_eq(keys_lanes, nxt) | (idx == n - 1)) & valid
    return cnt, ssum, smin, smax, tails.astype(jnp.int32)


def _make_kernel(nlanes: int):
    def _kernel(*refs):
        k_refs = refs[:nlanes]
        c_ref, s_ref, mn_ref, mx_ref = refs[nlanes : nlanes + 4]
        oc_ref, os_ref, omn_ref, omx_ref, ot_ref = refs[nlanes + 4 :]
        cnt, ssum, smin, smax, tails = _segmented_scan(
            tuple(k[...] for k in k_refs),
            c_ref[...], s_ref[...], mn_ref[...], mx_ref[...],
        )
        oc_ref[...] = cnt
        os_ref[...] = ssum
        omn_ref[...] = smin
        omx_ref[...] = smax
        ot_ref[...] = tails

    return _kernel


@functools.partial(jax.jit, static_argnames=("interpret",))
def segmented_scan_tiles(keys, cnt, ssum, smin, smax, *, interpret: bool = True):
    """(T,N) key lane(s) / cnt and (T,V?,N) value tiles → scanned values +
    boolean tail mask.  ``keys`` is a (T,N) array (one lane) or a tuple of (T,N)
    uint32 lanes, hi lane first, for 64-bit keys."""
    keys_lanes = keys if isinstance(keys, (tuple, list)) else (keys,)
    keys_lanes = tuple(keys_lanes)
    t, n = keys_lanes[0].shape
    spec1 = pl.BlockSpec((1, n), lambda i: (i, 0))

    def specv(x):
        v = x.shape[1]
        return pl.BlockSpec((1, v, n), lambda i: (i, 0, 0))

    *out, tails = pallas_call(
        _make_kernel(len(keys_lanes)),
        out_shape=(
            jax.ShapeDtypeStruct((t, n), cnt.dtype),
            jax.ShapeDtypeStruct(ssum.shape, ssum.dtype),
            jax.ShapeDtypeStruct(smin.shape, smin.dtype),
            jax.ShapeDtypeStruct(smax.shape, smax.dtype),
            jax.ShapeDtypeStruct((t, n), jnp.int32),
        ),
        grid=(t,),
        in_specs=[spec1] * len(keys_lanes)
        + [spec1, specv(ssum), specv(smin), specv(smax)],
        out_specs=(spec1, specv(ssum), specv(smin), specv(smax), spec1),
        interpret=interpret,
    )(*keys_lanes, cnt, ssum, smin, smax)
    return (*out, tails != 0)
