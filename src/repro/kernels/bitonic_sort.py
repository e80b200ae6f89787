"""Bitonic key(+payload) sort of VMEM tiles — the run-generation hot spot.

The paper replaces quicksort/priority queues with an ordered in-memory
index; on TPU the index's "insert a sorted batch" operation needs the
batch sorted first (§3.4).  This kernel sorts one power-of-two tile of
uint32 keys (with optional uint32 payload lanes moved alongside, e.g. the
original row position for argsort) entirely in VMEM.

TPU adaptation: the classic compare-exchange `partner = i XOR j` is
expressed with **lane/sublane rolls + masked min/max**, never gathers:
for stride j,  partner values = where(bit_j(i), roll(x, +j), roll(x, -j)).
All rolls are power-of-two strides of the trailing (lane) axis of a
(1, N) tile, which Mosaic supports natively; masks come from broadcasted
iota.  Work/depth: N·log²N compares, fully VPU-vectorized, zero control
flow (the stage loops unroll at trace time).

Keys may span multiple uint32 **lanes** compared lexicographically (hi
lane first): 64-bit composite keys sort as a (hi, lo) pair without any
native 64-bit ops — each compare-exchange stage rolls every lane and
selects with one shared lexicographic predicate.

Grid: one program per tile; ``ops.py`` shards larger inputs into tiles
and merges with :mod:`repro.kernels.merge_path`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.segmented_reduce import _lex_leq, pallas_call


def _cex(key_lanes, move_lanes, j: int, dir_shift: int | None):
    """One compare-exchange stage at stride j.

    key_lanes / move_lanes: tuples of (1, N) arrays.  A row's block sorts
    descending iff bit ``dir_shift`` of its index is set (``None``: the
    whole tile ascends).  Keys compare lexicographically across lanes;
    move lanes travel with their row.  Masks come from int32 index bits
    and are combined with and/or/not only: Mosaic cannot select between
    boolean vectors.
    """
    idx = jax.lax.broadcasted_iota(jnp.int32, key_lanes[0].shape, 1)
    upper = (idx & j) != 0  # bit_j set → partner is i - j

    def partner(x):
        # roll(+j) brings x[i-j] to lane i; roll(-j) brings x[i+j]
        return jnp.where(upper, jnp.roll(x, j, axis=-1), jnp.roll(x, -j, axis=-1))

    part_keys = tuple(partner(k) for k in key_lanes)
    # ascending block: the row with bit_j clear keeps the min; a
    # descending block flips that, so keep the min iff bit_j == bit_dir
    flip = idx >> (j.bit_length() - 1)
    if dir_shift is not None:
        flip = flip ^ (idx >> dir_shift)
    keep_min = (flip & 1) == 0
    take_self = (keep_min & _lex_leq(key_lanes, part_keys)) | (
        ~keep_min & _lex_leq(part_keys, key_lanes))
    new_keys = tuple(jnp.where(take_self, k, p) for k, p in zip(key_lanes, part_keys))
    new_move = tuple(jnp.where(take_self, m, partner(m)) for m in move_lanes)
    return new_keys, new_move


def _bitonic_body(key_lanes, move_lanes):
    n = key_lanes[0].shape[-1]
    assert n & (n - 1) == 0, "tile length must be a power of two"
    k = 2
    while k <= n:
        # block of size k sorts ascending iff bit_k(i) clear (global ascending)
        dir_shift = k.bit_length() - 1 if k < n else None
        j = k // 2
        while j >= 1:
            key_lanes, move_lanes = _cex(key_lanes, move_lanes, j, dir_shift)
            j //= 2
        k *= 2
    return key_lanes, move_lanes


def _make_kernel(nk: int, nm: int):
    def _kernel(*refs):
        keys = tuple(r[...] for r in refs[:nk])
        move = tuple(r[...] for r in refs[nk : nk + nm])
        keys, move = _bitonic_body(keys, move)
        for r, v in zip(refs[nk + nm : 2 * nk + nm], keys):
            r[...] = v
        for r, v in zip(refs[2 * nk + nm :], move):
            r[...] = v

    return _kernel


@functools.partial(jax.jit, static_argnames=("interpret",))
def bitonic_sort_multi(key_lanes, move_lanes=(), *, interpret: bool = True):
    """Sort (T, N) tile batches along the last axis (N a power of 2).

    ``key_lanes``: tuple of (T, N) arrays compared lexicographically (hi
    lane first).  ``move_lanes``: tuple of (T, N) arrays carried alongside.
    Returns (sorted_key_lanes, moved_lanes) as tuples.
    """
    key_lanes = tuple(key_lanes)
    move_lanes = tuple(move_lanes)
    t, n = key_lanes[0].shape
    spec = pl.BlockSpec((1, n), lambda i: (i, 0))
    all_in = key_lanes + move_lanes
    out = pallas_call(
        _make_kernel(len(key_lanes), len(move_lanes)),
        out_shape=tuple(jax.ShapeDtypeStruct((t, n), x.dtype) for x in all_in),
        grid=(t,),
        in_specs=[spec] * len(all_in),
        out_specs=tuple([spec] * len(all_in)),
        interpret=interpret,
    )(*all_in)
    return out[: len(key_lanes)], out[len(key_lanes) :]


@functools.partial(jax.jit, static_argnames=("interpret",))
def bitonic_sort(keys: jax.Array, *, interpret: bool = True) -> jax.Array:
    """Sort a (T, N) batch of tiles along the last axis (N a power of 2)."""
    (sorted_keys,), _ = bitonic_sort_multi((keys,), (), interpret=interpret)
    return sorted_keys


@functools.partial(jax.jit, static_argnames=("interpret",))
def bitonic_sort_kv(keys: jax.Array, vals: jax.Array, *, interpret: bool = True):
    """Key-sort with a payload column moved alongside (stable w.r.t. the
    payload when the payload encodes the original position in low bits)."""
    (sorted_keys,), (moved,) = bitonic_sort_multi((keys,), (vals,), interpret=interpret)
    return sorted_keys, moved
