"""Merge kernel: fused linear merge + absorb of two sorted tiles.

This is the Pallas twin of :mod:`repro.core.ordered_index`'s rank-scatter
merge, and the replacement for the bitonic-merge kernel in
:mod:`repro.kernels.merge_aggregate` on the engine's hot path.

Co-ranking (the idea behind Merge Path, Green, McColl & Bader): in the
stable merge of sorted A and B, row ``i`` of A lands at output position
``i + #{j : B[j] < A[i]}`` and row ``j`` of B at ``j + #{i : A[i] <= B[j]}``
(A wins ties), so the positions form a permutation of the output.  The
kernel counts both ranks with one broadcast comparison of the two tiles,
then places every row with a one-hot select — the TPU's vector unit has
no gather across vector registers, so no data-dependent indexing is used
at all.  That costs O(|A|·|B|) compares per tile, which bounds the tile
(``ops.MAX_TILE_ROWS``).  The duplicate absorb (flag-based segmented
scan, shared with :mod:`repro.kernels.segmented_reduce`) runs fused in
the same VMEM residency, so one page absorb costs one HBM round trip.

Inputs need only be **sorted** — duplicates within either input are fine
(they stay adjacent through the merge and the scan combines them).
Keys arrive as one or two uint32 **lanes**: 32-bit keys are one lane,
64-bit keys a (hi, lo) pair compared lexicographically per lane — the
TPU path needs no native 64-bit ops.  EMPTY (= all lanes 0xFFFF_FFFF)
padding ranks to the tail like any other key.  Each tile arrives both as
a row (1, N) and as a column (N, 1): comparing a column with a row
broadcasts to the (N, M) rank matrix, and summing it along its lanes or
sublanes gives a rank per row in either layout.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.segmented_reduce import (
    _lanes_empty,
    _lanes_eq,
    _lex_leq,
    _segmented_scan,
    pallas_call,
)


def _lt(a_lanes, b_lanes):
    """a < b on multi-lane keys (hi lane first)."""
    return ~_lex_leq(b_lanes, a_lanes)


def _count(mask, axis: int):
    return jnp.sum(mask.astype(jnp.int32), axis=axis, keepdims=True,
                   dtype=jnp.int32)


def _place(onehot_a, onehot_b, xa, xb):
    """Output row (1, K) whose lane k holds the A row (column ``xa``,
    (N, 1)) or B row (``xb``, (M, 1)) the one-hot masks put there."""
    as_int = xa.dtype == jnp.uint32
    if as_int:  # sum in int32: exactly one term per lane is non-zero
        xa = jax.lax.bitcast_convert_type(xa, jnp.int32)
        xb = jax.lax.bitcast_convert_type(xb, jnp.int32)
    zero = jnp.zeros((), xa.dtype)

    def pick(onehot, x):  # dtype pinned: x64 would widen an int32 sum
        return jnp.sum(jnp.where(onehot, x, zero), axis=0, keepdims=True,
                       dtype=x.dtype)

    row = pick(onehot_a, xa) + pick(onehot_b, xb)
    return jax.lax.bitcast_convert_type(row, jnp.uint32) if as_int else row


def _make_kernel(nlanes: int):
    def _kernel(*refs):
        ka_row = tuple(r[...] for r in refs[:nlanes])
        kb_row = tuple(r[...] for r in refs[nlanes:2 * nlanes])
        ka_col = tuple(r[...] for r in refs[2 * nlanes:3 * nlanes])
        kb_col = tuple(r[...] for r in refs[3 * nlanes:4 * nlanes])
        ca_ref, sa_ref, mna_ref, mxa_ref = refs[4 * nlanes:4 * nlanes + 4]
        cb_ref, sb_ref, mnb_ref, mxb_ref = refs[4 * nlanes + 4:4 * nlanes + 8]
        outs = refs[4 * nlanes + 8:]
        ok_refs = outs[:nlanes]
        oc_ref, os_ref, omn_ref, omx_ref, ot_ref = outs[nlanes:]

        n, m = ka_col[0].shape[0], kb_col[0].shape[0]
        k_out = jax.lax.broadcasted_iota(jnp.int32, (1, n + m), 1)
        pos_a = (jax.lax.broadcasted_iota(jnp.int32, (n, 1), 0)
                 + _count(_lt(kb_row, ka_col), 1))
        pos_b = (jax.lax.broadcasted_iota(jnp.int32, (m, 1), 0)
                 + _count(_lex_leq(ka_row, kb_col), 1))
        onehot_a, onehot_b = pos_a == k_out, pos_b == k_out

        def place(xa, xb):
            return _place(onehot_a, onehot_b, xa, xb)

        def planes(xa, xb):  # (N, V) / (M, V) columns → (V, N+M) rows
            return jnp.concatenate(
                [place(xa[:, v:v + 1], xb[:, v:v + 1])
                 for v in range(xa.shape[1])], axis=0)

        keys = tuple(place(a, b) for a, b in zip(ka_col, kb_col))
        cnt = place(ca_ref[...], cb_ref[...])
        ssum = planes(sa_ref[0], sb_ref[0])
        smin = planes(mna_ref[0], mnb_ref[0])
        smax = planes(mxa_ref[0], mxb_ref[0])
        # absorb duplicates (segmented scan) while everything is VMEM-resident
        cnt, ssum, smin, smax, tails = _segmented_scan(keys, cnt, ssum, smin, smax)
        for o, kk in zip(ok_refs, keys):
            o[...] = kk
        oc_ref[...] = cnt
        os_ref[...] = ssum[None]
        omn_ref[...] = smin[None]
        omx_ref[...] = smax[None]
        ot_ref[...] = tails

    return _kernel


def _lanes(k):
    return tuple(k) if isinstance(k, (tuple, list)) else (k,)


def _col(x):
    """(T, N) tile set → (T·N, 1): each tile as a column."""
    return x.reshape(-1, 1)


def _cols(x):
    """(T, V, N) value tiles → (T, N, V): each plane as a column."""
    return jnp.swapaxes(x, 1, 2)


@functools.partial(jax.jit, static_argnames=("interpret",))
def merge_path_tiles(ka, ca, sa, mna, mxa, kb, cb, sb, mnb, mxb, *,
                     interpret: bool = True):
    """Merge two sorted tile sets — (T,N)+(T,M) key lane(s), (T,V?,·)
    payloads — into (T,N+M) merged + scanned aggregates + boolean tail
    mask.  ``ka``/``kb`` are (T,N) arrays (one lane) or tuples of (T,N)
    uint32 lanes (hi first) for 64-bit keys.  N and M need not match
    (compaction by the caller, see ops.py), and the sum / min / max
    planes may have different widths."""
    ka_lanes, kb_lanes = _lanes(ka), _lanes(kb)
    assert len(ka_lanes) == len(kb_lanes)
    nlanes = len(ka_lanes)
    t, n = ka_lanes[0].shape
    m = kb_lanes[0].shape[-1]
    k_out = n + m

    def row(w):
        return pl.BlockSpec((1, w), lambda i: (i, 0))

    def col(w):
        return pl.BlockSpec((w, 1), lambda i: (i, 0))

    def vcol(x):
        return pl.BlockSpec((1,) + x.shape[1:], lambda i: (i, 0, 0))

    def ov(v):
        return pl.BlockSpec((1, v, k_out), lambda i: (i, 0, 0))

    a_vals = (_col(ca), _cols(sa), _cols(mna), _cols(mxa))
    b_vals = (_col(cb), _cols(sb), _cols(mnb), _cols(mxb))
    *out, tails = pallas_call(
        _make_kernel(nlanes),
        out_shape=tuple(
            jax.ShapeDtypeStruct((t, k_out), k.dtype) for k in ka_lanes
        ) + (
            jax.ShapeDtypeStruct((t, k_out), ca.dtype),
            jax.ShapeDtypeStruct((t, sa.shape[1], k_out), sa.dtype),
            jax.ShapeDtypeStruct((t, mna.shape[1], k_out), mna.dtype),
            jax.ShapeDtypeStruct((t, mxa.shape[1], k_out), mxa.dtype),
            jax.ShapeDtypeStruct((t, k_out), jnp.int32),
        ),
        grid=(t,),
        in_specs=[row(n)] * nlanes + [row(m)] * nlanes
        + [col(n)] * nlanes + [col(m)] * nlanes
        + [col(n)] + [vcol(x) for x in a_vals[1:]]
        + [col(m)] + [vcol(x) for x in b_vals[1:]],
        out_specs=tuple([row(k_out)] * nlanes) + (
            row(k_out), ov(sa.shape[1]), ov(mna.shape[1]), ov(mxa.shape[1]),
            row(k_out),
        ),
        interpret=interpret,
    )(*ka_lanes, *kb_lanes, *map(_col, ka_lanes), *map(_col, kb_lanes),
      *a_vals, *b_vals)
    return (*out, tails != 0)


def _make_probe_kernel(nlanes: int):
    def _kernel(*refs):
        ka_row = tuple(r[...] for r in refs[:nlanes])
        kb_col = tuple(r[...] for r in refs[nlanes:2 * nlanes])
        pos_ref, hit_ref = refs[2 * nlanes:]
        m = kb_col[0].shape[0]
        # lower_bound of A[i] in sorted B is #{j : B[j] < A[i]}; A[i] is
        # in B iff some B[j] equals it
        pos = jnp.minimum(_count(_lt(kb_col, ka_row), 0), m - 1)
        hit = (_count(_lanes_eq(kb_col, ka_row), 0) > 0) & ~_lanes_empty(ka_row)
        pos_ref[...] = pos
        hit_ref[...] = hit.astype(jnp.int32)

    return _kernel


@functools.partial(jax.jit, static_argnames=("interpret",))
def merge_path_probe_tiles(ka, kb, *, interpret: bool = True):
    """Two-sided merge-join probe: rank-align each key of sorted tile set
    ``ka`` — (T,N) uint32 array or tuple of lanes (hi first) — against
    sorted tile set ``kb`` (T,M).  Returns ``(pos, hit)`` of shape (T,N):
    ``kb[pos[i]] == ka[i]`` where ``hit`` (EMPTY keys never hit).  The
    rank is the co-rank count of the merge kernel; no sort, no scatter,
    no gather, one VMEM residency.
    """
    ka_lanes, kb_lanes = _lanes(ka), _lanes(kb)
    assert len(ka_lanes) == len(kb_lanes)
    nlanes = len(ka_lanes)
    t, n = ka_lanes[0].shape
    m = kb_lanes[0].shape[-1]
    a_spec = pl.BlockSpec((1, n), lambda i: (i, 0))
    b_spec = pl.BlockSpec((m, 1), lambda i: (i, 0))
    pos, hit = pallas_call(
        _make_probe_kernel(nlanes),
        out_shape=(
            jax.ShapeDtypeStruct((t, n), jnp.int32),
            jax.ShapeDtypeStruct((t, n), jnp.int32),
        ),
        grid=(t,),
        in_specs=[a_spec] * nlanes + [b_spec] * nlanes,
        out_specs=(a_spec, a_spec),
        interpret=interpret,
    )(*ka_lanes, *map(_col, kb_lanes))
    return pos, hit != 0
