"""jit'd wrappers exposing the Pallas kernels with framework-level shapes.

These handle the impedance between user shapes and kernel tiles: padding
to powers of two / MXU multiples, EMPTY-key padding, AggState struct ↔
(T,N)/(T,V,N) tile layout, and the XLA-side compaction scatter that
follows the in-kernel segmented scans.  Interpret mode is decided at
trace time by :func:`repro.core.dispatch.should_interpret`: everywhere
off-TPU (Mosaic is TPU-only), never on a TPU.

Each kernel holds its whole input in one VMEM tile.  On a TPU an input
larger than :data:`MAX_TILE_ROWS` raises :class:`TileTooLarge`; it never
falls back to the interpreter or to another backend.

Key-width handling: kernels only ever see uint32 lanes.  A uint64 key
vector is split here into a (hi, lo) pair of uint32 lanes — compared
lexicographically inside the kernels — and recombined on the way out, so
the TPU path needs no native 64-bit integer ops.  Callers must hold
:func:`repro.core.types.key_dtype_context` for uint64 inputs (the
engine's sorted_ops entry points do).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import dispatch as _dispatch
from repro.core.types import (
    EMPTY,
    AggState,
    concat_states,
    empty_key,
    empty_like,
)
from repro.kernels import bitonic_sort as _bs
from repro.kernels import grouped_matmul as _gm
from repro.kernels import merge_aggregate as _ma
from repro.kernels import merge_path as _mp
from repro.kernels import segmented_reduce as _sr

_LO32 = 0xFFFFFFFF

# Largest padded row count one kernel call holds in a single VMEM tile on
# a TPU, per kernel family (compile-checked for v5e at these sizes).
MAX_TILE_ROWS = {"sort": 4096, "scan": 16384, "merge": 1024, "probe": 2048}


class TileTooLarge(ValueError):
    """A Pallas kernel's input exceeds its single-tile VMEM limit."""


def _interpret(kind: str, rows: int) -> bool:
    """Interpret mode for one kernel call of ``rows`` padded rows; on a
    TPU, refuse a tile over the kernel's VMEM limit."""
    if _dispatch.should_interpret():
        return True
    if rows > MAX_TILE_ROWS[kind]:
        raise TileTooLarge(
            f"pallas {kind} kernel: {rows} rows exceed the single-tile limit "
            f"of {MAX_TILE_ROWS[kind]} rows on TPU (the kernels are not "
            "tiled across a grid yet); use backend='xla'"
        )
    return False


def _next_pow2(n: int) -> int:
    return 1 << max(1, (n - 1).bit_length())


def _key_lanes(keys: jax.Array) -> tuple[jax.Array, ...]:
    """Split a 1-D key vector into uint32 lanes (hi lane first)."""
    if keys.dtype == jnp.uint64:
        hi = (keys >> np.uint64(32)).astype(jnp.uint32)
        lo = (keys & np.uint64(_LO32)).astype(jnp.uint32)
        return (hi, lo)
    return (keys.astype(jnp.uint32),)


def _lanes_to_keys(lanes: tuple[jax.Array, ...], dtype) -> jax.Array:
    """Recombine uint32 lanes into a key vector of ``dtype``."""
    if len(lanes) == 1:
        return lanes[0].astype(dtype)
    hi, lo = lanes
    return (hi.astype(jnp.uint64) << np.uint64(32)) | lo.astype(jnp.uint64)


def sort_keys(keys: jax.Array) -> jax.Array:
    """Sort a 1-D uint32/uint64 key vector (EMPTY-padded to a power of 2)."""
    n = keys.shape[0]
    m = _next_pow2(n)
    lanes = tuple(
        jnp.full((1, m), EMPTY, jnp.uint32).at[0, :n].set(lane)
        for lane in _key_lanes(keys)
    )
    sorted_lanes, _ = _bs.bitonic_sort_multi(
        lanes, (), interpret=_interpret("sort", m))
    return _lanes_to_keys(tuple(l[0, :n] for l in sorted_lanes), keys.dtype)


def argsort_keys(keys: jax.Array) -> jax.Array:
    """Key-argsort via the multi-lane kernel, with the row index as an
    extra LEAST-significant key lane.

    The index lane makes the bitonic network stable: all EMPTY keys tie,
    and without it the (unstable) network could emit a pow2-pad slot
    (index ≥ n) ahead of one of the state's own EMPTY rows — the first n
    outputs would then reference a pad row and any clamp would duplicate
    a real row into the tail.  With the index tie-break, in-state rows
    (indices < n) always precede pad rows, so the first n outputs are
    exactly a permutation of range(n)."""
    n = keys.shape[0]
    m = _next_pow2(n)
    lanes = tuple(
        jnp.full((1, m), EMPTY, jnp.uint32).at[0, :n].set(lane)
        for lane in _key_lanes(keys)
    )
    idx_lane = jnp.arange(m, dtype=jnp.uint32)[None, :]
    sorted_lanes, _ = _bs.bitonic_sort_multi(
        lanes + (idx_lane,), (), interpret=_interpret("sort", m)
    )
    perm = sorted_lanes[-1][0]
    return perm[:n].astype(jnp.int32)


# Back-compat aliases (the registry and older callers use the u32 names).
sort_u32 = sort_keys
argsort_u32 = argsort_keys


def _plane_to_tile(plane: jax.Array, n: int, fill: float) -> jax.Array:
    """(N, V) value plane → (1, V, N) kernel tile; width-0 planes become a
    1-wide neutral dummy the kernel scans and the caller drops."""
    if plane.shape[1] == 0:
        return jnp.full((1, 1, n), fill, jnp.float32)
    return jnp.moveaxis(plane, 0, -1)[None]


def _state_to_tiles(state: AggState, n: int):
    """AggState (N rows) → key lanes (1,N), cnt (1,N), value tiles
    (1,V?,N) with per-plane widths (dummy 1-wide plane when absent)."""
    key_lanes = tuple(lane[None] for lane in _key_lanes(state.keys))
    cnt = state.count[None]
    ssum = _plane_to_tile(state.sum, n, 0.0)
    smin = _plane_to_tile(state.min, n, jnp.inf)
    smax = _plane_to_tile(state.max, n, -jnp.inf)
    return key_lanes, cnt, ssum, smin, smax


def _compact(keys, cnt, ssum, smin, smax, tails, widths) -> AggState:
    """Scatter segment tails to the front (XLA side; memory-bound).

    ``keys`` is the merged/sorted key *vector* (n,) in its native dtype;
    the value tiles are (1,V?,n); ``widths`` the output per-plane widths.
    """
    n = keys.shape[-1]
    cnt, tails = cnt[0], tails[0]
    ssum, smin, smax = ssum[0], smin[0], smax[0]
    pos = jnp.cumsum(tails.astype(jnp.int32)) - 1
    idx = jnp.where(tails, pos, n)  # out-of-range → dropped
    kd = keys.dtype
    out_keys = jnp.full((n,), empty_key(kd), kd).at[idx].set(keys, mode="drop")
    out_cnt = jnp.zeros((n,), cnt.dtype).at[idx].set(cnt, mode="drop")

    def sc(col, fill):
        return jnp.full((n,), fill, col.dtype).at[idx].set(col, mode="drop")

    def plane(tile, width, fill):
        if width == 0:
            return jnp.zeros((n, 0), jnp.float32)
        return jnp.stack([sc(tile[v], fill) for v in range(width)], axis=-1)

    ws, wm, wx = widths
    return AggState(
        out_keys,
        out_cnt,
        plane(ssum, ws, 0.0),
        plane(smin, wm, jnp.inf),
        plane(smax, wx, -jnp.inf),
    )


def segmented_combine(state: AggState) -> AggState:
    """Pallas-backed equivalent of sorted_ops.segmented_combine (input must
    be key-sorted; output compacted to the front, EMPTY-padded)."""
    n0 = state.capacity
    n = _next_pow2(n0)
    state = _pad_state(state, n)
    key_lanes, cnt, ssum, smin, smax = _state_to_tiles(state, n)
    c2, s2, mn2, mx2, tails = _sr.segmented_scan_tiles(
        key_lanes, cnt, ssum, smin, smax, interpret=_interpret("scan", n)
    )
    out = _compact(state.keys, c2, s2, mn2, mx2, tails, state.widths)
    return jax.tree.map(lambda x: x[:n0], out)


def merge_absorb_sorted(a: AggState, b: AggState, *, assume_unique: bool = False) -> AggState:
    """Fused merge-absorb of two key-sorted states via the merge-path
    kernel: linear merge (per-lane diagonal binary search, no sort/
    compare-exchange network), absorb fused in-kernel.  Returns the
    combined state of capacity exactly |a|+|b| (sorted, duplicate-
    combined, EMPTY-padded) so jitted callers see the same shapes as the
    XLA engine.  ``assume_unique`` is accepted for interface parity; the
    in-VMEM segmented scan handles both cases in the same pass."""
    del assume_unique
    cap_out = a.capacity + b.capacity
    na = _next_pow2(a.capacity)
    nb = _next_pow2(b.capacity)
    a = _pad_state(a, na)
    b = _pad_state(b, nb)
    ka, ca, sa, mna, mxa = _state_to_tiles(a, na)
    kb, cb, sb, mnb, mxb = _state_to_tiles(b, nb)
    out_tiles = _mp.merge_path_tiles(
        ka, ca, sa, mna, mxa, kb, cb, sb, mnb, mxb,
        interpret=_interpret("merge", na + nb),
    )
    nlanes = len(ka)
    merged_lanes = tuple(t[0] for t in out_tiles[:nlanes])
    c2, s2, mn2, mx2, tails = out_tiles[nlanes:]
    merged_keys = _lanes_to_keys(merged_lanes, a.keys.dtype)
    out = _compact(merged_keys, c2, s2, mn2, mx2, tails, a.widths)
    # compacted rows ≤ |a|+|b| ≤ na+nb: trimming the EMPTY tail is lossless
    return jax.tree.map(lambda x: x[:cap_out], out)


def merge_absorb_sorted_bitonic(a: AggState, b: AggState) -> AggState:
    """Previous-generation fused step (bitonic merge network); kept for
    benchmarking against the merge-path kernel.  uint32 keys only."""
    assert a.keys.dtype == jnp.uint32, "bitonic merge benchmark path is u32-only"
    cap_out = a.capacity + b.capacity
    n = _next_pow2(max(a.capacity, b.capacity))
    a = _pad_state(a, n)
    b = _pad_state(b, n)
    (ka,), ca, sa, mna, mxa = _state_to_tiles(a, n)
    (kb,), cb, sb, mnb, mxb = _state_to_tiles(b, n)
    k2, c2, s2, mn2, mx2, tails = _ma.merge_absorb_tiles(
        ka, ca, sa, mna, mxa, kb, cb, sb, mnb, mxb,
        interpret=_dispatch.should_interpret(),
    )
    out = _compact(k2[0], c2, s2, mn2, mx2, tails, a.widths)
    return jax.tree.map(lambda x: x[: min(cap_out, 2 * n)], out)


def join_probe(a_keys: jax.Array, b_keys: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Merge-join probe via the merge-path kernel's lane-parallel binary
    search: rank-align each (sorted) a-key against the (sorted) b-keys.
    Returns ``(pos, hit)`` shaped like ``a_keys`` with ``pos`` clipped
    into b's row range (see :func:`repro.core.merge_join.join_probe`).
    EMPTY pow2 padding on either side is benign: EMPTY ranks to the tail
    and never equals a valid key, so padded rows cannot hit."""
    n0, m0 = a_keys.shape[0], b_keys.shape[0]
    n, m = _next_pow2(n0), _next_pow2(m0)
    ka = tuple(
        jnp.full((1, n), EMPTY, jnp.uint32).at[0, :n0].set(lane)
        for lane in _key_lanes(a_keys)
    )
    kb = tuple(
        jnp.full((1, m), EMPTY, jnp.uint32).at[0, :m0].set(lane)
        for lane in _key_lanes(b_keys)
    )
    pos, hit = _mp.merge_path_probe_tiles(
        ka, kb, interpret=_interpret("probe", max(n, m)))
    return jnp.clip(pos[0, :n0], 0, max(m0 - 1, 0)), hit[0, :n0]


def _pad_state(state: AggState, n: int) -> AggState:
    if state.capacity == n:
        return state
    return concat_states(state, empty_like(state, n - state.capacity))


def moe_grouped_matmul(x, w, *, capacity, block_m=128, block_n=128, block_k=128):
    return _gm.grouped_matmul(
        x, w, capacity=capacity, block_m=block_m, block_n=block_n,
        block_k=block_k, interpret=_dispatch.should_interpret(),
    )
