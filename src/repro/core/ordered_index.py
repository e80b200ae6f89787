"""The ordered in-memory index engine (paper §3.4), with *merge*-based
absorption instead of sort-the-world.

The paper's central data structure is an ordered in-memory index whose
batched insert "turns the per-row search into a merge".  The previous
implementation absorbed a batch by concatenating it with the table and
re-sorting the union — O((M+B)·log(M+B)) comparisons per batch.  This
module implements the batched insert as an actual **linear two-pointer
merge**, vectorized for XLA:

* :func:`merge_ranks` — the output position of every row of two sorted
  key vectors in their merged order, via two ``searchsorted`` rank
  computations (each row binary-searches the *other* side once; no sort
  of the union ever happens).
* :func:`interleave_sorted` — gather both states through those ranks:
  the ranks are a permutation of ``range(|a|+|b|)``, inverted by one more
  binary search, so one gather per column produces the merged,
  still-sorted union.
* :func:`merge_absorb_xla` — interleave + segmented combine: equal keys
  are adjacent after the merge, so the b-tree "absorb" is the same
  segmented combine used everywhere else.  The combine itself is a
  segmented associative scan + compaction gather, so the whole XLA
  merge-absorb path emits **no sort and no scatter**.

The :class:`OrderedIndex` wrapper carries the engine invariant **in the
type**:

    keys ascending · valid keys duplicate-free · EMPTY-padded suffix

Every constructor either establishes the invariant (``from_unsorted`` —
the only remaining full-argsort path) or preserves it (``merge_absorb``,
``trim``, ``empty``), so a function receiving an ``OrderedIndex`` never
needs to re-sort defensively.  The Pallas twin of this engine is the
merge-path kernel in :mod:`repro.kernels.merge_path`; backend selection
goes through :mod:`repro.core.dispatch`.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.core import dispatch
from repro.core.types import (
    AggState,
    concat_states,
    empty_key,
    empty_state,
    take,
)

_INF = jnp.float32(jnp.inf)


# ---------------------------------------------------------------------------
# linear merge of two sorted key vectors (rank computation)
# ---------------------------------------------------------------------------


def merge_ranks(a_keys: jax.Array, b_keys: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Output positions of two *sorted* key vectors in merged order.

    ``pos_a[i] = i + |{j : b[j] <  a[i]}|`` and
    ``pos_b[j] = j + |{i : a[i] <= b[j]}|`` — together a permutation of
    ``range(|a|+|b|)`` (stable: ``a`` precedes ``b`` on ties).  EMPTY is
    the key dtype's maximum, so padding naturally ranks to the tail.  No
    sort primitive is used (see the jaxpr test in tests/test_ordered_index.py).
    """
    na, nb = a_keys.shape[0], b_keys.shape[0]
    pos_a = jnp.arange(na, dtype=jnp.int32) + jnp.searchsorted(
        b_keys, a_keys, side="left", method="scan_unrolled"
    ).astype(jnp.int32)
    pos_b = jnp.arange(nb, dtype=jnp.int32) + jnp.searchsorted(
        a_keys, b_keys, side="right", method="scan_unrolled"
    ).astype(jnp.int32)
    return pos_a, pos_b


def merge_gather_indices(a_keys: jax.Array, b_keys: jax.Array) -> jax.Array:
    """Gather indices realizing the linear merge: ``src[k]`` is the row of
    ``concat(a, b)`` that lands at merged position ``k``.

    Built from :func:`merge_ranks` by *inverting* the (sorted) ``pos_a``
    rank vector with one more binary search instead of scattering through
    it — scatters are the expensive primitive on every backend, gathers
    are nearly free.
    """
    na, nb = a_keys.shape[0], b_keys.shape[0]
    pos_a, _ = merge_ranks(a_keys, b_keys)
    k = jnp.arange(na + nb, dtype=jnp.int32)
    # ca[k] = #rows of `a` among the first k merged rows; where position k
    # holds an `a` row, pos_a[ca[k]] == k.
    ca = jnp.searchsorted(pos_a, k, side="left", method="scan_unrolled").astype(
        jnp.int32
    )
    ca_c = jnp.minimum(ca, max(na - 1, 0))
    take_a = jnp.take(pos_a, ca_c, mode="clip") == k
    ib = jnp.minimum(k - ca, max(nb - 1, 0))
    return jnp.where(take_a, ca_c, na + ib)


def interleave_sorted(a: AggState, b: AggState) -> AggState:
    """Merge two key-sorted states into one sorted state of capacity
    ``|a|+|b|`` (duplicates kept adjacent, not yet combined)."""
    src = merge_gather_indices(a.keys, b.keys)

    def pick(xa, xb):
        return jnp.take(jnp.concatenate([xa, xb], axis=0), src, axis=0, mode="clip")

    return jax.tree.map(pick, a, b)


# ---------------------------------------------------------------------------
# segmented combine (the b-tree absorb) — XLA reference implementation
# ---------------------------------------------------------------------------


def _segmented_scan_xla(state: AggState) -> tuple[AggState, jax.Array]:
    """Inclusive segmented scan over a key-sorted state: row i holds the
    aggregate of its segment's prefix, so segment *tails* hold complete
    group aggregates.  Returns (scanned state, tail mask).

    This is the XLA rendering of the flag-based segmented scan the Pallas
    kernel uses (:mod:`repro.kernels.segmented_reduce`): log2(n) steps of
    (restart-flag, count, sum, min, max) combines with the row ``d``
    places up, ``d`` doubling each step — shifts and elementwise
    combines, **no scatter**.  The steps are one ``fori_loop`` body with
    a dynamic shift, so the program does not grow with n: compiled for
    a TPU v5e at 2^20 rows, ``lax.associative_scan``'s strided slices
    took 136 s and this loop 2.7 s.
    """
    k = state.keys
    n = k.shape[0]
    valid = k != empty_key(k.dtype)
    same_prev = jnp.concatenate([jnp.zeros((1,), bool), k[1:] == k[:-1]]) & valid
    starts = ~same_prev  # EMPTY rows restart too: they never join a group

    def shifted(x, d, fill):
        """Row i of the result is x[i - d]; rows above d hold ``fill``."""
        pad = jnp.full(x.shape, fill, x.dtype)
        return jax.lax.dynamic_slice_in_dim(
            jnp.concatenate([pad, x]), n - d, n)

    def step(i, carry):
        f, cnt, ssum, serr, smin, smax = carry
        d = jnp.left_shift(1, i)
        keep = f  # row starts its segment ⇒ ignore the rows above it
        kcol = keep[:, None]
        # the sums add with their rounding error carried alongside
        # (TwoSum), so a group's sum does not depend on how the doubling
        # steps happen to split it
        up = shifted(ssum, d, 0.0)
        total = up + ssum
        back = total - up
        err = (up - (total - back)) + (ssum - back)
        return (
            f | shifted(f, d, False),
            jnp.where(keep, cnt, shifted(cnt, d, 0) + cnt),
            jnp.where(kcol, ssum, total),
            jnp.where(kcol, serr, shifted(serr, d, 0.0) + serr + err),
            jnp.where(kcol, smin, jnp.minimum(shifted(smin, d, _INF), smin)),
            jnp.where(kcol, smax, jnp.maximum(shifted(smax, d, -_INF), smax)),
        )

    _, cnt, ssum, serr, smin, smax = jax.lax.fori_loop(
        0, max(1, (n - 1).bit_length()), step,
        (starts, state.count, state.sum, jnp.zeros_like(state.sum),
         state.min, state.max))
    ssum = ssum + serr
    tails = jnp.concatenate([k[1:] != k[:-1], jnp.ones((1,), bool)]) & valid
    return AggState(k, cnt, ssum, smin, smax), tails


def segmented_combine_xla(state: AggState) -> AggState:
    """Combine adjacent equal-key rows of a key-sorted state.

    Output keeps the input capacity: unique groups are compacted to the
    front (still sorted), the tail is EMPTY.  Implemented scatter-free: a
    segmented associative scan leaves each group's aggregate at its tail
    row, and the tails are compacted to the front with the same
    cumsum-invert *gather* used everywhere else (:func:`_compact_rows`) —
    scatters are the expensive primitive on every backend.
    """
    if state.capacity == 0:
        return state
    scanned, tails = _segmented_scan_xla(state)
    return _compact_rows(scanned, tails)


def compact_indices(keep: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Gather indices compacting the ``keep``-flagged rows to the front
    without a scatter: ``src[j]`` is the row index of the j-th kept row
    (found by a binary search over the running count of kept rows) and
    ``live[j]`` flags whether output row j holds a kept row at all.
    Shared by the segmented-combine compaction and the merge join's
    match compaction."""
    n = keep.shape[0]
    csum = jnp.cumsum(keep.astype(jnp.int32))
    n_keep = csum[-1]
    j = jnp.arange(n, dtype=jnp.int32)
    src = jnp.searchsorted(csum, j + 1, side="left", method="scan_unrolled").astype(
        jnp.int32
    )
    return jnp.minimum(src, n - 1), j < n_keep


def _compact_rows(state: AggState, keep: jax.Array) -> AggState:
    """Gather the ``keep``-flagged rows to the front (EMPTY/neutral tail)
    via :func:`compact_indices` — no scatter."""
    pos, live = compact_indices(keep)

    def take_live(col, fill):
        v = jnp.take(col, pos, axis=0, mode="clip")
        mask = live.reshape((-1,) + (1,) * (v.ndim - 1))
        return jnp.where(mask, v, fill)

    return AggState(
        keys=take_live(state.keys, empty_key(state.keys.dtype)),
        count=take_live(state.count, 0),
        sum=take_live(state.sum, 0.0),
        min=take_live(state.min, _INF),
        max=take_live(state.max, -_INF),
    )


def pair_combine_xla(merged: AggState) -> AggState:
    """Absorb duplicates in a sorted state where every key appears at most
    twice — the case after merging two *duplicate-free* sorted states
    (the OrderedIndex invariant).  One shifted compare + one compaction
    gather; no segmented scan, no scatter.
    """
    k = merged.keys
    n = merged.capacity
    if n == 0:
        return merged
    valid = k != empty_key(k.dtype)
    same_next = jnp.concatenate([k[1:] == k[:-1], jnp.zeros((1,), bool)]) & valid
    same_prev = jnp.concatenate([jnp.zeros((1,), bool), k[1:] == k[:-1]]) & valid
    heads = valid & ~same_prev

    def shift_up(x, fill):
        return jnp.concatenate(
            [x[1:], jnp.full((1,) + x.shape[1:], fill, x.dtype)], axis=0
        )

    m = same_next
    mcol = m[:, None]
    cnt = merged.count + jnp.where(m, shift_up(merged.count, 0), 0)
    ssum = merged.sum + jnp.where(mcol, shift_up(merged.sum, 0.0), 0.0)
    smin = jnp.where(mcol, jnp.minimum(merged.min, shift_up(merged.min, _INF)), merged.min)
    smax = jnp.where(mcol, jnp.maximum(merged.max, shift_up(merged.max, -_INF)), merged.max)
    return _compact_rows(AggState(k, cnt, ssum, smin, smax), heads)


def merge_absorb_xla(
    a: AggState, b: AggState, *, assume_unique: bool = False
) -> AggState:
    """Linear merge-absorb of two key-sorted states: interleave by rank,
    then combine the now-adjacent equal keys.  Capacity ``|a|+|b|``.

    ``assume_unique=True`` asserts each input is duplicate-free (the
    OrderedIndex invariant): merged groups then hold at most two rows and
    the combine collapses to :func:`pair_combine_xla`.
    """
    if a.capacity == 0 or b.capacity == 0:  # degenerate: nothing to merge
        merged = concat_states(a, b)
        return merged if assume_unique else segmented_combine_xla(merged)
    merged = interleave_sorted(a, b)
    if assume_unique:
        return pair_combine_xla(merged)
    return segmented_combine_xla(merged)


# ---------------------------------------------------------------------------
# the typed engine layer
# ---------------------------------------------------------------------------


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class OrderedIndex:
    """A fixed-capacity AggState carrying the engine invariant in the type:
    keys ascending, valid keys duplicate-free, EMPTY-padded suffix.

    Constructors either establish the invariant (``from_unsorted`` — the
    only full-argsort path) or preserve it (``empty``, ``merge_absorb``,
    ``trim``).  ``wrap`` asserts nothing and exists for callers that
    maintain the invariant themselves (e.g. shift/mask steps that keep
    prefixes of sorted states).
    """

    state: AggState

    # -- plain accessors -------------------------------------------------
    @property
    def capacity(self) -> int:
        return self.state.capacity

    @property
    def width(self) -> int:
        return self.state.width

    @property
    def keys(self) -> jax.Array:
        return self.state.keys

    def occupancy(self) -> jax.Array:
        return self.state.occupancy()

    # -- constructors ----------------------------------------------------
    @classmethod
    def empty(
        cls, capacity: int, width: int, *, key_dtype=jnp.uint32
    ) -> "OrderedIndex":
        return cls(empty_state(capacity, width, key_dtype=key_dtype))

    @classmethod
    def wrap(cls, state: AggState) -> "OrderedIndex":
        """Trust the caller that ``state`` already satisfies the invariant."""
        return cls(state)

    @classmethod
    def from_unsorted(cls, state: AggState, *, backend: str = "xla") -> "OrderedIndex":
        """Canonicalize arbitrary rows: full argsort + combine.  This is
        the only entry point that sorts; everything else merges."""
        be = dispatch.get_backend(backend)
        return cls(be.segmented_combine(take(state, be.argsort(state.keys))))

    # -- invariant-preserving ops ---------------------------------------
    def merge_absorb(self, other: "OrderedIndex", *, backend: str = "xla") -> "OrderedIndex":
        """Batched insert (§3.4): linear merge, never a full sort.
        Result capacity is ``self.capacity + other.capacity``.  Both
        sides carry the duplicate-free invariant, so the absorb is a
        single pair-combine."""
        be = dispatch.get_backend(backend)
        return OrderedIndex(be.merge_sorted(self.state, other.state, assume_unique=True))

    def trim(self, capacity: int) -> "OrderedIndex":
        """Keep the first ``capacity`` rows (the smallest keys).  Safe
        whenever occupancy ≤ capacity; callers check occupancy first."""
        return OrderedIndex(jax.tree.map(lambda x: x[:capacity], self.state))
